"""An untied readout in the benchmark's weights and its reference."""
import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import harness, reference, weights

SEED = 2**31 + 31


def _cfgs():
    cfg = harness.rehearsal_cfg(harness.load_cell("qwen2_7b.tp4_chat")["cfg"])
    assert cfg["tie_word_embeddings"] is False
    return {**cfg, "tie_word_embeddings": True}, cfg


def test_untied_weights_add_a_readout_and_change_nothing_else():
    tied_cfg, untied_cfg = _cfgs()
    tied = weights.make(tied_cfg, SEED, jnp.float32)
    untied = weights.make(untied_cfg, SEED, jnp.float32)
    assert "lm_head" not in tied
    d, v = untied_cfg["hidden_size"], untied_cfg["vocab_size"]
    assert untied["lm_head"]["w"].shape == (d, v)
    rest = {k: x for k, x in untied.items() if k != "lm_head"}
    for a, b in zip(jax.tree.leaves(tied), jax.tree.leaves(rest)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    std = float(np.std(np.asarray(untied["lm_head"]["w"])))
    assert abs(std - d ** -0.5) < 0.1 * d ** -0.5


def test_untied_reference_equals_tied_when_the_readout_is_the_table():
    tied_cfg, untied_cfg = _cfgs()
    w = weights.make(tied_cfg, SEED, jnp.float32)
    u = {**w, "lm_head": {"w": jnp.asarray(w["embed"]["table"]).T}}
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, tied_cfg["vocab_size"], 40)
    served = rng.integers(0, tied_cfg["vocab_size"], 20)
    length = reference.Q_BLOCK
    for lowp in (False, True):
        a = reference.served_gaps(w, tied_cfg, length, prompt, served, lowp)
        b = reference.served_gaps(u, untied_cfg, length, prompt, served,
                                  lowp)
        np.testing.assert_array_equal(a, b)
    # and a readout of its own reads other logits
    u2 = weights.make(untied_cfg, SEED, jnp.float32)
    c = reference.served_gaps(u2, untied_cfg, length, prompt, served)
    assert not np.array_equal(c, reference.served_gaps(
        w, tied_cfg, length, prompt, served))
