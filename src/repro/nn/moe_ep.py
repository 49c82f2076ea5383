"""Expert-parallel MoE via shard_map + all-to-all (§Perf cell-A iteration).

The pjit-safe MoE in ``moe.py`` shards expert weights on the per-expert FFN
dim ("expert-TP"), so under FSDP training the expert weights are still
all-gathered per layer (2.4 GB/layer f32 for qwen3-moe — the residual
bottleneck identified in EXPERIMENTS.md §Perf cell A).  This module keeps
the experts *resident*: the expert dim is sharded over the ``model`` axis
and only token buffers move, via two all-to-alls:

  1. each device routes its local tokens, buckets assignments by the
     owner column of the chosen expert (capacity-padded), and
     ``all_to_all`` sends the buckets over ``model``;
  2. the owner computes its local experts' FFN for the received tokens;
  3. the reverse ``all_to_all`` returns results, which are gate-combined.

Wire per device per layer = 2 x (T_loc * topk * cf * d) activations
— ~14x less than gathering qwen3's expert weights.  Deterministic static
shapes throughout (capacity-padded buckets; overflow drops, like the
capacity path of moe.py).

Usage: ``moe_apply_ep(p, x, spec, mesh, data_axes=("data",),
model_axis="model")`` — requires a mesh; single-device tests use a (1, n)
mesh.  Correctness vs the dense reference is checked in
tests/test_moe_ep.py on 8 host devices.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


from .moe import MoESpec


def moe_apply_ep(p, x: jax.Array, s: MoESpec, mesh, *,
                 data_axes: tuple = ("data",), model_axis: str = "model",
                 act: str = "silu") -> jax.Array:
    """x: (B, S, d) batch-sharded over data_axes; experts over model_axis."""
    ep = mesh.shape[model_axis]
    assert s.n_experts % ep == 0, (s.n_experts, ep)
    e_local = s.n_experts // ep

    def body(p_local, x_local):
        b, seq, d = x_local.shape
        t = b * seq
        xt = x_local.reshape(t, d)
        router = p_local["router"]                        # replicated
        logits = xt.astype(jnp.float32) @ router.astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gates, eidx = jax.lax.top_k(probs, s.top_k)       # (t, k)
        if s.router_norm_topk:
            gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)

        a = t * s.top_k
        fe = eidx.reshape(a)                              # global expert ids
        owner = fe // e_local                             # destination column
        tok = jnp.arange(a) // s.top_k

        # bucket assignments by owner with per-destination capacity
        cap = max(8, math.ceil(a / ep * max(s.capacity_factor, 1.0))) \
            if s.capacity_factor > 0 else a
        order = jnp.argsort(owner, stable=True)
        owner_s = owner[order]
        counts = jnp.bincount(owner_s, length=ep)
        starts = jnp.cumsum(counts) - counts
        rank = jnp.arange(a) - starts[owner_s]            # rank within bucket
        keep_s = rank < cap
        # bucket slot (dest, cap) <- sorted position starts[dest] + slot
        pos = starts[:, None] + jnp.arange(cap)[None, :]  # (ep, cap)
        valid = jnp.arange(cap)[None, :] < jnp.minimum(counts, cap)[:, None]
        src_assign = jnp.take(order, jnp.clip(pos, 0, a - 1).reshape(-1))
        send_tok = jnp.take(tok, src_assign)              # (ep*cap,)
        send_x = jnp.take(xt, send_tok, axis=0).reshape(ep, cap, d)
        send_x = send_x * valid[..., None].astype(send_x.dtype)
        send_e = (jnp.take(fe, src_assign).reshape(ep, cap) % e_local)
        send_e = jnp.where(valid, send_e, e_local)        # sentinel expert

        # a2a #1: tokens travel to their experts' owner column
        recv_x = jax.lax.all_to_all(send_x, model_axis, split_axis=0,
                                    concat_axis=0, tiled=False)
        recv_e = jax.lax.all_to_all(send_e, model_axis, split_axis=0,
                                    concat_axis=0, tiled=False)
        # recv_*: (ep, cap, ...) — rows now indexed by SOURCE column
        rx = recv_x.reshape(ep * cap, d)
        re = recv_e.reshape(ep * cap)

        # compute local experts: one-hot dispatch into (e_local, ...) via
        # masked accumulation (cap*ep rows, e_local small)
        y = jnp.zeros((ep * cap, d), jnp.float32)
        for le in range(e_local):                         # static, small
            m = (re == le)[:, None].astype(jnp.float32)
            h = jax.nn.silu(rx @ p_local["gate"][le]) * (rx @ p_local["up"][le])
            y = y + (h @ p_local["down"][le]) * m

        # a2a #2: results return to the source column
        back = jax.lax.all_to_all(y.reshape(ep, cap, d), model_axis,
                                  split_axis=0, concat_axis=0, tiled=False)
        back = back.reshape(ep * cap, d)

        # combine: invert the bucketing (gather each kept assignment's slot)
        inv = jnp.argsort(order, stable=True)             # assignment -> sorted pos
        slot = owner * cap + jnp.clip(jnp.take(rank, inv), 0, cap - 1)
        kept = jnp.take(keep_s, inv)
        vals = jnp.take(back, slot, axis=0) * kept[:, None]
        vals = vals.reshape(t, s.top_k, d) * gates[..., None]
        return jnp.sum(vals, axis=1).reshape(b, seq, d).astype(x_local.dtype)

    in_p = jax.tree.map(lambda _: P(), {k: v for k, v in p.items()})
    # expert-dim sharding for the three weight stacks; router replicated
    in_p = {"router": P(), "gate": P(model_axis), "up": P(model_axis),
            "down": P(model_axis)}
    x_spec = P(data_axes if len(data_axes) > 1 else data_axes[0], None, None)
    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(in_p, x_spec), out_specs=x_spec,
                       check_vma=False)
    return fn(p, x)
