"""The step loop's own accounting (serving/loop.py, docs/observability.md).

  * **step spans** — while tracing, one `step` span per step of the loop;
    every phase span recorded inside it lies inside its interval and
    carries its number, `admit` spans carry the request's rid, and ring
    entries keep the 6-tuple layout the chip benchmark reads;
  * **discarded forwards** — a speculative forward that an admission or
    an unclean step throws away is counted where it is dropped, so
    `dispatched - hit - discarded` is 0 or 1 (one in flight) after every
    step and the decode-program calls equal `decode_steps +
    overlap_discarded` (plus the one in flight);
  * **the exact oracle** — checks, rejections, resample dispatches;
  * **first commit** — `RequestState.first_commit_t` on the clock of
    `admit_t`, never before it.
"""
import jax
import pytest

from repro.core.decoding import DecodeConfig
from repro.obs import Telemetry
from repro.serving.engine import Engine, Request
from repro.serving.loop import ListSource, StepLoop, make_mode

MAX_LEN = 96


@pytest.fixture(scope="module")
def make_engine(tokenizer, grammar_bundle):
    from dataclasses import replace

    from repro.configs import get_config
    from repro.models.model import build_model
    g, tab, store, _ = grammar_bundle("json")
    cfg = replace(get_config("syncode-demo"),
                  vocab_size=tokenizer.vocab_size, num_layers=2,
                  d_model=64, d_ff=128, num_heads=4, num_kv_heads=2,
                  head_dim=16)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    def make(**kw):
        kw.setdefault("slots", 2)
        return Engine(model, params, tokenizer, {"json": (g, tab, store)},
                      max_len=MAX_LEN, **kw)
    return make


def _reqs(grammar="json", method="sample", max_new=(4, 9, 6)):
    return [Request(rid=i, prompt=b"Q: generate. A:", grammar=grammar,
                    max_new_tokens=n,
                    decode=DecodeConfig(method=method, temperature=1.0),
                    seed=i) for i, n in enumerate(max_new)]


def _loop(eng, reqs, tele=None):
    return StepLoop(eng, make_mode(eng), ListSource(reqs), telemetry=tele)


def _in_flight(loop):
    return (loop.c_overlap_disp.value - loop.c_overlap_hit.value
            - sum(c.value for c in loop.c_overlap_discard.values()))


# ============================ discarded forwards ========================

@pytest.mark.parametrize("grammar,method", [(None, "greedy"),
                                            ("json", "sample")],
                         ids=["free-greedy", "json-sample"])
def test_discarded_forwards_balance_decode_calls(make_engine, grammar,
                                                 method):
    eng = make_engine()
    calls = []
    real_decode = eng._decode

    def decode(*a):
        calls.append(1)
        return real_decode(*a)
    eng._decode = decode
    loop = _loop(eng, _reqs(grammar, method))
    real_step = loop.mode.step
    after = []

    def step(lp, active):
        real_step(lp, active)
        after.append(_in_flight(lp))
    loop.mode.step = step
    states, stats = loop.run()
    assert after and set(after) <= {0, 1}
    flight = _in_flight(loop)
    assert len(calls) == stats.decode_steps + stats.overlap_discarded \
        + flight
    assert stats.overlap_dispatched - stats.overlap_hits \
        - stats.overlap_discarded == flight
    if grammar is None:
        # unconstrained greedy never changes a step on the host: the
        # one forward thrown away is the one pending when the third
        # request took the first free slot
        assert loop.c_overlap_discard["admit"].value == 1
        assert loop.c_overlap_discard["unclean"].value == 0
    assert all(s.finish_reason in ("eos", "length") for s in states)


# ============================== the oracle ==============================

def test_forced_oracle_rejection_counts(make_engine):
    eng = make_engine()
    real_make = eng._make_constraint
    rejected = []

    def make_constraint(req):
        gc = real_make(req)
        real_check = gc.is_valid_extension

        def check(text, t):
            if not rejected:            # the first pick checked fails
                rejected.append(t)
                return False
            return real_check(text, t)
        gc.is_valid_extension = check
        return gc
    eng._make_constraint = make_constraint
    loop = _loop(eng, _reqs(method="greedy", max_new=(5,)))
    _, stats = loop.run()
    assert rejected
    assert stats.oracle_rejected >= 1 and stats.resample_calls >= 1
    assert stats.oracle_accepted >= 1
    reg = loop.tele.registry
    assert reg.counter("repro_oracle_checks_total", "",
                       {"outcome": "rejected"}).value == \
        stats.oracle_rejected
    assert reg.counter("repro_resample_calls_total").value == \
        stats.resample_calls
    assert stats.exact_fallbacks == \
        reg.counter("repro_exact_fallbacks_total").value


# =============================== step spans =============================

def test_step_spans_nest_the_phase_spans(make_engine):
    tele = Telemetry(enabled=True)
    tele.tracer.start()
    states, stats = _loop(make_engine(), _reqs(), tele).run()
    ring = list(tele.tracer._ring)
    assert all(len(e) == 6 for e in ring)
    steps = [e for e in ring if e[1] == "step"]
    assert [e[5]["step"] for e in steps] == list(range(len(steps)))
    assert len(steps) == stats.steps_timed > 0
    assert sum(e[5]["admitted"] for e in steps) == len(states) == 3
    assert sum(e[5]["admitted"] > 0 for e in steps) == stats.admit_steps
    assert stats.step_s == pytest.approx(sum(e[4] for e in steps))
    by_n = {e[5]["step"]: e for e in steps}
    phases = [e for e in ring if e[0] == "X" and e[1] != "step"
              and not e[1].startswith(("slot ", "device:"))]
    assert {e[1] for e in phases} >= {"admit", "forward", "ci_lookup",
                                      "select_resolve", "host_oracle"}
    for ph, track, name, t0, dur, args in phases:
        _, _, _, s0, sdur, _ = by_n[args["step"]]
        assert s0 <= t0 and t0 + dur <= s0 + sdur, (name, args)
    assert sorted(e[5]["rid"] for e in phases if e[1] == "admit") == \
        [0, 1, 2]
    waits = [e for e in ring if e[2] == "admit_to_first_token"]
    assert sorted(e[5]["rid"] for e in waits) == [0, 1, 2]
    assert all(e[1].startswith("slot ") for e in waits)


def test_step_counts_stay_live_with_telemetry_off(make_engine):
    _, on = make_engine().generate(_reqs())
    _, off = make_engine(telemetry=False).generate(_reqs())
    assert off.steps_timed == on.steps_timed > 0
    assert off.admit_steps == on.admit_steps > 0
    assert off.step_s == 0.0 and off.admit_step_s == 0.0
    assert on.step_s >= on.admit_step_s > 0.0


def test_first_commit_follows_admission(make_engine):
    states, _ = make_engine().generate(_reqs())
    assert states
    for st in states:
        assert st.steps > 0
        assert st.first_commit_t is not None and st.admit_t is not None
        assert st.first_commit_t >= st.admit_t
