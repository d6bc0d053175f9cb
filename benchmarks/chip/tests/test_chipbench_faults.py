"""The comparison that decides `correct`, on the tiny cell on the CPU.

Each test skips the harness's look for a chip and drives the rest of a
run (`cell.Run.go`: set-up, warm-up, window, drain, comparison). A sound
run comes out correct; the faults a served cell can have come out not
correct: a served token altered where it is produced, and a decode step
that returns its state (the KV cache) unchanged. The fp8 control, put
in the program's place at the same served positions, fails the limits
(widest and mean gap) that the sound run meets."""
import time

import jax
import pytest

import tiny
from harness import cell, check

jax.config.update("jax_enable_compilation_cache", False)
SEED = 2**31 + 77


def _run(hook=None, grammar="json"):
    r = cell.Run(tiny.spec(grammar=grammar), SEED, 3.0, False,
                 time.perf_counter())
    out = r.go(engine_hook=hook)
    return r, out


@pytest.fixture(scope="module")
def sound():
    return _run()


def test_sound_run_is_correct(sound):
    run, out = sound
    assert out["correct"], out["checks"]
    assert out["checks"]["compared_tokens"]["value"] >= 64
    assert out["attempted"] > 5 and out["failed"] == 0
    assert list(out)[-1] == "checks"


def test_fp8_control_fails_the_limit(sound):
    run, out = sound
    got = check.compare(run.finished, run.window_reqs, run.make_weights(),
                        run.config, run.mix, run.vocab, run.seed,
                        control=True)
    assert got["max_logit_gap"]["ok"] and got["mean_logit_gap"]["ok"]
    assert not got["control_gap"]["ok"], got["control_gap"]
    assert not got["control_mean_gap"]["ok"], got["control_mean_gap"]


def _alter_tokens(engine):
    real = engine._select_resolve

    def resolve(ctx, slot_state, *a, **k):
        committed, ctr = real(ctx, slot_state, *a, **k)
        for b, t in committed.items():
            st = slot_state[b]
            if st is not None and st.steps % 5 == 3 and t > 2:
                committed[b] = t + 1 if t + 1 < engine.tok.vocab_size \
                    else t - 1
        return committed, ctr
    engine._select_resolve = resolve


def _stale_state(engine):
    real = engine._decode

    def decode(params, caches, tok, pos):
        logits, _ = real(params, caches, tok, pos)
        return logits, caches
    engine._decode = decode


@pytest.mark.parametrize("fault,grammar", [
    (_alter_tokens, "json"), (_alter_tokens, None), (_stale_state, "json")],
    ids=["token_altered-json", "token_altered-free", "state_unchanged"])
def test_faults_come_out_not_correct(fault, grammar):
    run, out = _run(fault, grammar)
    assert not out["correct"], out["checks"]
    if grammar is None:     # any token is valid: the logit gap catches it
        assert not out["checks"]["max_logit_gap"]["value"] <= \
            out["checks"]["max_logit_gap"]["limit"]
