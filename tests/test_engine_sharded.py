"""Sharded differential matrix: mesh serving == single-device serving.

ISSUE 5 acceptance: on a forced 8-device host-platform CPU mesh, the
mesh-sharded ``ServeEngine``/``PagedServeEngine`` must be **token-for-token
identical** to the single-device engines — dp-only, tp-only, and dp x tp
meshes, greedy and sampled, spec_k in {0, 2}, OFF and NL-DPE-fused
numerics — with identical host-side scheduling stats and no page leaks.
Chained with the single-device differential suite
(tests/test_engine_differential.py: lockstep run-alone == slotted == paged
== spec), this makes the whole battery a dp x tp conformance oracle.

Each test shells out to ``tests/sharded_driver.py`` with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` — the flag must be
set before jax initializes, so the main pytest process (whatever its
device count) is never touched.  Why these mesh shapes, given 2 engine
slots and the reduced model's 4 query / 2 KV heads:

* (2, 1) — dp-only: both slots shard over "data";
* (1, 2) — tp-only: heads 4 and kv-heads 2 both shard over "model";
* (2, 2) — dp x tp, every axis divides (slow: the widest compile);
* (2, 4) — dp x tp where kv-heads 2 do NOT divide model=4: the resolver's
  divisibility fallback must replicate the KV cache (and the shard_map
  kernel wrapper must replicate heads) rather than crash or diverge.

The numerics contract that makes exact equality (not a tolerance) the
right assertion is DESIGN.md §9.
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_driver(spec: dict, extra_env: dict | None = None,
               timeout: int = 900) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(REPO, "src"), os.path.join(REPO, "tests")]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    env.update(extra_env or {})
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tests", "sharded_driver.py"),
         json.dumps(spec)],
        capture_output=True, text=True, env=env, timeout=timeout)
    assert out.returncode == 0, (
        f"sharded driver failed for {spec}\n--- stdout:\n"
        f"{out.stdout[-3000:]}\n--- stderr:\n{out.stderr[-6000:]}")
    assert "SHARDED-OK" in out.stdout
    return out.stdout


def test_dp_and_tp_greedy_cow_spec():
    """dp-only and tp-only: greedy Poisson traces + the shared-prefix /
    COW / zero-headroom-eviction trace, spec_k in {0, 2}."""
    run_driver({"meshes": [[2, 1], [1, 2]], "engines": ["paged"],
                "spec_ks": [0, 2], "traces": ["greedy", "cow"],
                "seeds": [0]})


def test_slotted_and_mixed_sampling_tp():
    """The slotted engine shards too, and sampled (temperature/top-k)
    requests stay draw-for-draw identical under tp sharding."""
    run_driver({"meshes": [[1, 2]], "engines": ["slotted", "paged"],
                "spec_ks": [0], "traces": ["mixed"], "seeds": [10]})


@pytest.mark.slow
def test_dpxtp_full_matrix():
    """dp x tp cells, including the kv-heads-don't-divide (2, 4) mesh
    (divisibility fallback replicates the KV pool): greedy + mixed + COW,
    spec_k in {0, 2}."""
    run_driver({"meshes": [[2, 2], [2, 4]], "engines": ["paged"],
                "spec_ks": [0, 2], "traces": ["greedy", "cow", "mixed"],
                "seeds": [3]})


@pytest.mark.slow
def test_fused_numerics_sharded():
    """NL-DPE fused dual-compute numerics (Pallas kernels inside the tick
    jits) under tp and dp x tp meshes, spec_k in {0, 2}."""
    run_driver({"meshes": [[1, 2], [2, 2]], "engines": ["paged"],
                "spec_ks": [0, 2], "traces": ["greedy"], "seeds": [5],
                "numerics": "fused"})


@pytest.mark.slow
def test_sharded_through_paged_kernel():
    """NLDPE_PAGED_KERNEL=1 under a mesh routes decode and the q_len>1
    verify chunk through the Pallas kernel per-shard via shard_map
    (block table replicated across the model axis).  Float-tolerance
    internally, but greedy tokens must still match the single-device
    engine — which uses the same kernel, so the comparison is exact."""
    run_driver({"meshes": [[2, 4]], "engines": ["paged"], "spec_ks": [2],
                "traces": ["greedy"], "seeds": [7]},
               extra_env={"NLDPE_PAGED_KERNEL": "1"})
