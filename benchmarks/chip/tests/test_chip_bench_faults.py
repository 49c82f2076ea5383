"""The correctness check fails a broken timed path.

Each case drives the rest of a run (rehearsal size, on the CPU, past the
harness's look for a chip) with the decode program broken underneath:
emitted tokens altered where the program produces them, and a step that
leaves its KV state as it was.  The unbroken run passes the same check.
A cell on one chip has no exchange between chips to leave out.
"""
import time

import jax
import jax.numpy as jnp
import pytest

from benchmarks.chip import harness

CELL = "qwen2_5_3b.reason_decode"


def alter_tokens(eng):
    """Every token of each decode tick's first step, changed on device."""
    decode, vocab = eng._decode_fn, eng.cfg.vocab_size

    def f(*args):
        out = decode(*args)
        emits = out[-1]                         # (steps, slots)
        bad = jnp.where(emits[0] >= 0, (emits[0] + 1) % vocab, emits[0])
        return out[:-1] + (emits.at[0].set(bad),)

    eng._decode_fn = f


def keep_state(eng):
    """The decode step writes K/V but leaves every position track as it
    was, so its writes are never attended to."""
    decode = eng._decode_fn
    key = jax.tree_util.keystr

    def f(params, cache, *rest):
        saved = {key(p): jnp.copy(x) for p, x in
                 jax.tree_util.tree_flatten_with_path(cache)[0]
                 if key(p[-1:]) == "['pos']"}
        out = decode(params, cache, *rest)
        kept = jax.tree_util.tree_map_with_path(
            lambda p, x: saved.get(key(p), x), out[0])
        return (kept,) + out[1:]

    eng._decode_fn = f


def run(tamper):
    return harness.execute(CELL, 2**31 + 99, 2.0, False,
                           t_process=time.perf_counter(), rehearse=True,
                           tamper=tamper)


def test_unbroken_run_is_correct():
    res = run(None)
    assert res["correct"], res["checks"]


@pytest.mark.parametrize("tamper", [alter_tokens, keep_state])
def test_broken_run_is_not_correct(tamper):
    res = run(tamper)
    assert not res["correct"], res["checks"]
    gap = res["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]
