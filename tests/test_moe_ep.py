"""Expert-parallel MoE (shard_map + all-to-all) vs the dense reference,
on 8 forced host devices (subprocess)."""
import os
import subprocess
import sys
import textwrap
import pytest

pytestmark = pytest.mark.slow  # distributed/model e2e; excluded from the CI fast subset

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_moe_ep_matches_dense_reference():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    script = textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.nn import moe as M
        from repro.nn.moe_ep import moe_apply_ep

        mesh = jax.make_mesh((2, 4), ("data", "model"))
        spec = M.MoESpec(n_experts=8, top_k=2, d_expert_ff=16,
                         capacity_factor=0.0)
        d = 32
        p = M.moe_init(jax.random.key(0), d, spec)
        x = jax.random.normal(jax.random.key(1), (4, 8, d))

        got = moe_apply_ep(p, x, spec, mesh)
        ref = M.moe_apply(p, x, spec)            # dropless pjit reference
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-3, atol=2e-3)
        print("EP-OK")

        # capacity mode also runs (drops allowed, shapes static)
        spec_c = M.MoESpec(n_experts=8, top_k=2, d_expert_ff=16,
                           capacity_factor=1.25)
        out = moe_apply_ep(p, x, spec_c, mesh)
        assert bool(jnp.all(jnp.isfinite(out)))
        print("CAP-OK")
    """)
    out = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "EP-OK" in out.stdout and "CAP-OK" in out.stdout
