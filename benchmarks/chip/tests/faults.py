"""Faults planted in the timed path, for the tests of the correctness
check: each breaks the engine's decode program or its weights underneath
a run that is otherwise whole.

* ``alter_tokens``: every token of each decode tick's first step changed
  where the program produces it;
* ``keep_state``: a decode step that writes K/V but leaves every position
  track as it was, so its writes are never attended to;
* ``drop_exchange`` (a mesh only): the exchange between chips left out.
  Under the program's rules the attention heads and the FFN columns are
  split over ``model`` and gathered whole before the replicated ``wo`` and
  ``down``; here each gather brings only the first chip's share, the
  other shares reading zero, which is what zeroing the rows of ``wo`` and
  ``down`` that the other chips' shares meet does.
"""
import jax
import jax.numpy as jnp


def alter_tokens(eng):
    decode, vocab = eng._decode_fn, eng.cfg.vocab_size

    def f(*args):
        out = decode(*args)
        emits = out[-1]                         # (steps, slots)
        bad = jnp.where(emits[0] >= 0, (emits[0] + 1) % vocab, emits[0])
        return out[:-1] + (emits.at[0].set(bad),)

    eng._decode_fn = f


def keep_state(eng):
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


def drop_exchange(eng):
    share = eng.mesh.shape["model"]
    blk = eng.params["groups"]["b0"]
    # wo (layers, heads, head_dim, d), down (layers, d_ff, d)
    wo, down = blk["attn"]["wo"], blk["ffn"]["down"]
    mine_h = jnp.arange(wo.shape[1]) < wo.shape[1] // share
    mine_f = jnp.arange(down.shape[1]) < down.shape[1] // share
    wo = jax.device_put(wo * mine_h[None, :, None, None], wo.sharding)
    down = jax.device_put(down * mine_f[None, :, None], down.sharding)
    eng.params = {**eng.params, "groups": {**eng.params["groups"], "b0": {
        **blk, "attn": {**blk["attn"], "wo": wo},
        "ffn": {**blk["ffn"], "down": down}}}}


FAULTS = (alter_tokens, keep_state, drop_exchange)
