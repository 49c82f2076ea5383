"""Pipeline parallelism over the pod axis (GPipe-style, shard_map+ppermute).

The multi-pod mesh's "pod" axis is DP by default; this module re-purposes it
as a pipeline axis: layer-stage parameters are sharded over "pod", and
microbatches stream through stages with ``jax.lax.ppermute`` moving
activations pod-to-pod (the DCI hop).  Autodiff through ppermute gives the
reverse-direction backward pipeline for free, so ``jax.grad`` of a pipelined
loss is a correct (GPipe-scheduled) pipeline-parallel training step.

Schedule: T = M + K - 1 ticks for M microbatches over K stages; bubble
fraction (K-1)/T — reported by ``bubble_fraction`` so the §Perf loop can
trade microbatch count vs memory.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P



def bubble_fraction(n_micro: int, n_stages: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)


def pipeline_forward(stage_params, x_micro, body_fn, mesh, axis: str = "pod"):
    """Run microbatches through pod-sharded stages.

    stage_params: pytree with leading dim = n_stages (sharded over ``axis``).
    x_micro: (M, mb, ...) microbatched input (replicated across ``axis``).
    body_fn(params_slice, x) -> y, applied by each stage.
    Returns (M, mb, ...) outputs (valid on the last stage, broadcast back).
    """
    k = mesh.shape[axis]
    m = x_micro.shape[0]
    t_total = m + k - 1

    def per_stage(params_local, xs_local):
        params_local = jax.tree.map(lambda a: a[0], params_local)
        stage = jax.lax.axis_index(axis)
        mb_shape = xs_local.shape[1:]
        state = jnp.zeros(mb_shape, xs_local.dtype)
        outs = jnp.zeros((m,) + mb_shape, xs_local.dtype)

        def tick(carry, t):
            state, outs = carry
            # stage 0 ingests microbatch t (when in range); others take the
            # activation handed over from stage-1 on the previous tick.
            x_in = jnp.where(
                stage == 0,
                xs_local[jnp.clip(t, 0, m - 1)],
                state)
            y = body_fn(params_local, x_in)
            # pass forward: stage s -> s+1 (last stage keeps its output)
            passed = jax.lax.ppermute(
                y, axis, [(i, i + 1) for i in range(k - 1)])
            out_idx = jnp.clip(t - (k - 1), 0, m - 1)
            is_valid = (t >= k - 1)
            outs = jax.lax.cond(
                is_valid & (stage == k - 1),
                lambda o: o.at[out_idx].set(y),
                lambda o: o,
                outs)
            return (passed, outs), None

        (state, outs), _ = jax.lax.scan(tick, (state, outs),
                                        jnp.arange(t_total))
        return outs

    specs_p = jax.tree.map(lambda _: P(axis), stage_params)
    fn = jax.shard_map(per_stage, mesh=mesh,
                       in_specs=(specs_p, P()), out_specs=P(axis),
                       check_vma=False)
    outs = fn(stage_params, x_micro)
    # out_specs=P(axis) stacks per-stage outputs; only the last stage's slice
    # is meaningful — slice it out (static index, no collective needed
    # beyond the implicit reshard).
    return outs.reshape((k, m) + x_micro.shape[1:])[-1] if outs.shape[0] == k * m \
        else outs


def pipeline_loss(stage_params, x_micro, y_micro, body_fn, loss_fn, mesh,
                  axis: str = "pod"):
    """Differentiable pipelined loss (backward pipeline via autodiff)."""
    outs = pipeline_forward(stage_params, x_micro, body_fn, mesh, axis)
    return loss_fn(outs, y_micro)
