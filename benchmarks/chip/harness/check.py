"""The comparison that decides `correct`.

Every number compared has its limit (configs/<config>.json "check"
block for the logit gaps; 0 for counts):

  failed_requests     requests due in the window that the program failed
                      (an error finish, or no first token at all)
  prompt_mismatch     finished requests whose prompt ids, as the program
                      tokenized them, differ from the generator's
  grammar_violations  (constrained mixes) finished outputs with a special
                      token, outside the JSON prefix language, or ended
                      by EOS outside the language
  max_logit_gap       over a seeded sample of finished greedy requests
                      (the longest always in it): at every served
                      position, how far the served token's float32
                      reference logit lies below the reference's best
                      among the tokens the grammar allows there (all
                      tokens when unconstrained); the widest such gap
  mean_logit_gap      the same gaps' mean over every compared position
                      (where the configuration states its limit): a
                      rounding step lower flips the pick more often and
                      by more, and a mean reads every flip where the
                      widest gap reads one
  compared_tokens     served tokens the gap was read at; at least the
                      mix's `check_tokens`, or every greedy token the
                      window finished where it finished fewer

The reference (harness/reference.py) runs once over each sampled prompt
with its served tokens. The same positions give the control's reading
(`position_gaps(..., control=...)`): the gap of the token the fp8
reference puts first, read as the widest (`control_gap`) and the mean
(`control_mean_gap`).
"""
from __future__ import annotations

import numpy as np

from . import jsongrammar, reference
from .vocab import BOS_ID, EOS_ID, PAD_ID


def _model(config: dict) -> dict:
    s = config["serve"]
    return {"num_heads": s["num_heads"], "num_kv_heads": s["num_kv_heads"],
            "head_dim": s.get("head_dim") or s["d_model"] // s["num_heads"],
            "norm_eps": s["norm_eps"], "rope_theta": s["rope_theta"]}


def served_tokens(r) -> list:
    return list(r.state.token_ids[r.state.prompt_len:])


def position_gaps(ref: np.ndarray, served: list, grammar, vocab,
                  control: np.ndarray = None):
    """-> (gaps [n], invalid count, control gaps [n] or None).

    ref / control: [n, V] logits predicting each served token."""
    allowed = jsongrammar.Allowed(vocab.token_bytes, EOS_ID) \
        if grammar else None
    state = jsongrammar.START
    gaps, ctl, invalid = [], [], 0
    for k, t in enumerate(served):
        r = ref[k]
        if allowed is None:
            best = int(np.argmax(r))
            gaps.append(float(r[best] - r[t]))
            if control is not None:
                ctl.append(float(r[best] - r[int(np.argmax(control[k]))]))
            continue
        first = allowed.first_ok(state)
        if not allowed.allows(state, t):
            invalid += 1
        b = allowed.best(state, r, first, above=r[t])
        gaps.append(float(r[b] - r[t]) if b is not None else 0.0)
        if control is not None:
            top = allowed.best(state, control[k], first)
            best = allowed.best(state, r, first)
            ctl.append(float(r[best] - r[top]))
        if t != EOS_ID:
            state = jsongrammar.feed(state, vocab.token_bytes[t]) or state
    return gaps, invalid, (ctl if control is not None else None)


def sample(finished: list, seed: int, budget: int) -> list:
    """Seeded sample of finished greedy requests, the longest first,
    until `budget` served tokens are covered."""
    greedy = [r for r in finished if r.greedy]
    if not greedy:
        return []
    longest = max(greedy, key=lambda r: len(served_tokens(r)))
    rest = [r for r in greedy if r is not longest]
    order = np.random.default_rng([seed, 3]).permutation(len(rest))
    out, n = [longest], len(served_tokens(longest))
    for j in order:
        if n >= budget:
            break
        out.append(rest[j])
        n += len(served_tokens(rest[j]))
    return out


def reference_logits(weights, config, mix, r, control=False) -> np.ndarray:
    served = served_tokens(r)
    tokens = list(r.prompt_ids) + served
    want = np.arange(len(r.prompt_ids) - 1, len(tokens) - 1)
    return reference.logits_at(weights, _model(config), tokens, want,
                               mix["max_len"], mix["new_tokens"]["max"],
                               control=control)


def compare(finished: list, window: list, weights, config: dict, mix: dict,
            vocab, seed: int, control: bool = False) -> dict:
    grammar = mix["grammar"]
    out = {}

    def put(name, value, limit, ok):
        out[name] = {"value": value, "limit": limit, "ok": bool(ok)}

    failed = sum(1 for r in window if r.failed)
    put("failed_requests", failed, 0, failed == 0)
    mism = sum(1 for r in finished
               if list(r.state.token_ids[:len(r.prompt_ids)])
               != list(r.prompt_ids)
               or r.state.prompt_len != len(r.prompt_ids))
    put("prompt_mismatch", mism, 0, mism == 0)
    if grammar:
        if grammar != "json":
            raise ValueError(f"no reference grammar for {grammar!r}")
        bad = 0
        for r in finished:
            served = served_tokens(r)
            text = vocab.decode(t for t in served if t != EOS_ID)
            if any(t in (PAD_ID, BOS_ID) for t in served) or \
                    not jsongrammar.prefix_ok(text) or \
                    (r.state.finish_reason == "eos"
                     and not jsongrammar.complete(text)):
                bad += 1
        put("grammar_violations", bad, 0, bad == 0)
    picked = sample(finished, seed, int(mix["check_tokens"]))
    gaps, ctl, invalid = [], [], 0
    for r in picked:
        ref = reference_logits(weights, config, mix, r)
        c = reference_logits(weights, config, mix, r, True) if control \
            else None
        g, inv, cg = position_gaps(ref, served_tokens(r), grammar, vocab, c)
        gaps += g
        invalid += inv
        ctl += cg or []
    limits = config["check"]
    worst = max(gaps) if gaps else float("nan")
    limit = limits["max_logit_gap"]
    put("max_logit_gap", worst, limit, bool(gaps) and worst <= limit)
    if "mean_logit_gap" in limits:
        mean = float(np.mean(gaps)) if gaps else float("nan")
        put("mean_logit_gap", mean, limits["mean_logit_gap"],
            bool(gaps) and mean <= limits["mean_logit_gap"])
    if grammar:
        put("served_not_allowed", invalid, 0, invalid == 0)
    total = sum(len(served_tokens(r)) for r in finished if r.greedy)
    need = min(int(mix["check_tokens"]), total)
    put("compared_tokens", len(gaps), need, len(gaps) >= max(need, 1))
    if control:
        out["control_gap"] = {"value": max(ctl) if ctl else float("nan"),
                              "limit": limit,
                              "ok": bool(ctl) and max(ctl) <= limit}
        if "mean_logit_gap" in limits:
            m = float(np.mean(ctl)) if ctl else float("nan")
            out["control_mean_gap"] = {
                "value": m, "limit": limits["mean_logit_gap"],
                "ok": bool(ctl) and m <= limits["mean_logit_gap"]}
    return out
