"""Context-parallel flash-decode vs the dense decode reference (8 devices)."""
import os
import subprocess
import sys
import textwrap
import pytest

pytestmark = pytest.mark.slow  # distributed/model e2e; excluded from the CI fast subset

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_cp_decode_matches_reference():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    script = textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.parallel.cp_decode import cp_decode_attention
        from repro.nn.attention import AttnSpec, decode_attention

        mesh = jax.make_mesh((8,), ("model",))
        B, HQ, HKV, L, D = 2, 8, 2, 64, 16
        key = jax.random.key(0)
        q = jax.random.normal(key, (B, HQ, 1, D))
        k = jax.random.normal(jax.random.fold_in(key, 1), (B, HKV, L, D))
        v = jax.random.normal(jax.random.fold_in(key, 2), (B, HKV, L, D))
        kv_pos = jnp.where(jnp.arange(L) < 40, jnp.arange(L), -1)  # 40 valid

        for pos, window in [(39, None), (39, 16), (20, None)]:
            out = cp_decode_attention(q, k, v, kv_pos, jnp.int32(pos), mesh,
                                      window=window)
            s = AttnSpec(d_model=HQ*D, n_heads=HQ, n_kv_heads=HKV,
                         head_dim=D, window=window)
            ref = decode_attention(q, {"k": k, "v": v, "pos": kv_pos},
                                   jnp.int32(pos), s)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       rtol=2e-4, atol=2e-4)
        print("CP-OK")
    """)
    out = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "CP-OK" in out.stdout
