"""Multi-device tests (subprocess with forced host device count):
small-mesh dry-run lowering, pipeline parallelism, elastic reshard.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.slow  # distributed/model e2e; excluded from the CI fast subset

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script: str, devices: int = 8, timeout: int = 600):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                         capture_output=True, text=True, env=env,
                         timeout=timeout)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


def test_small_mesh_dryrun_lowers_with_collectives():
    """Reduced qwen2 on a (2,4) mesh: compile + parse collectives."""
    out = _run("""
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config
        from repro.launch.mesh import make_mesh
        from repro.models import lm
        from repro.nn.module import param_dtype, spec_mode
        from repro.optim import adamw
        from repro.parallel.context import sharding_ctx
        from repro.parallel.sharding import rules_for, resolve
        from repro.launch.train import build_train_step
        from repro.utils.hlo import collective_summary

        mesh = make_mesh((2, 4), ("data", "model"))
        rules = rules_for("train", False)
        cfg = get_config("qwen2_7b", reduced=True)
        key = jax.random.key(0)
        with param_dtype(jnp.float32):
            shapes = jax.eval_shape(lambda: lm.init_params(key, cfg))
            with spec_mode(mesh, rules):
                pspecs = lm.init_params(key, cfg)
        sh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                          is_leaf=lambda x: isinstance(x, P))
        opt_shapes = jax.eval_shape(adamw.init, shapes)
        opt_sh = {"m": sh, "v": sh, "step": NamedSharding(mesh, P())}
        bspec = {"tokens": NamedSharding(mesh, P("data", None)),
                 "labels": NamedSharding(mesh, P("data", None))}
        batch = {"tokens": jax.ShapeDtypeStruct((8, 32), jnp.int32),
                 "labels": jax.ShapeDtypeStruct((8, 32), jnp.int32)}
        step = jax.jit(build_train_step(cfg, adamw.AdamWConfig()),
                       in_shardings=(sh, opt_sh, bspec))
        with sharding_ctx(mesh, rules):
            lowered = step.lower(shapes, opt_shapes, batch)
        compiled = lowered.compile()
        cost = compiled.cost_analysis()
        coll = collective_summary(compiled.as_text(), 8)
        print("FLOPS", cost.get("flops", 0.0))
        print("COLL", coll["total_wire_bytes_per_device"])
        assert cost.get("flops", 0) > 0
        assert coll["n_ops"] > 0
        print("OK")
    """)
    assert "OK" in out


def test_small_mesh_execute_train_step():
    """Actually EXECUTE a sharded train step on 8 host devices."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config
        from repro.launch.mesh import make_mesh
        from repro.models import lm
        from repro.nn.module import param_dtype
        from repro.optim import adamw
        from repro.parallel.context import sharding_ctx
        from repro.parallel.sharding import rules_for
        from repro.launch.train import build_train_step

        mesh = make_mesh((2, 4), ("data", "model"))
        rules = rules_for("train", False)
        cfg = get_config("qwen2_5_3b", reduced=True)
        with param_dtype(jnp.float32):
            params = lm.init_params(jax.random.key(0), cfg)
        opt = adamw.init(params)
        key = jax.random.key(1)
        batch = {"tokens": jax.random.randint(key, (8, 32), 0, cfg.vocab_size),
                 "labels": jax.random.randint(key, (8, 32), 0, cfg.vocab_size)}
        step = jax.jit(build_train_step(cfg, adamw.AdamWConfig(lr=1e-3)))
        with sharding_ctx(mesh, rules):
            losses = []
            for i in range(5):
                params, opt, m = step(params, opt, batch)
                losses.append(float(m["loss"]))
        print("LOSSES", losses)
        assert all(np.isfinite(losses))
        assert losses[-1] < losses[0]            # overfits one batch
        print("OK")
    """, devices=8, timeout=900)
    assert "OK" in out


def test_pipeline_forward_matches_sequential():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.parallel.pipeline import pipeline_forward, bubble_fraction

        mesh = make_mesh((4,), ("pod",))
        k, m, d = 4, 6, 16
        keys = jax.random.split(jax.random.key(0), k)
        stage_w = jax.vmap(lambda kk: jax.random.normal(kk, (d, d)) * 0.3)(keys)
        x = jax.random.normal(jax.random.key(1), (m, 2, d))

        def body(w, h):
            return jnp.tanh(h @ w)

        out = pipeline_forward({"w": stage_w}, x, lambda p, h: body(p["w"], h),
                               mesh, axis="pod")
        ref = x
        for s in range(k):
            ref = body(stage_w[s], ref)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)
        assert 0 < bubble_fraction(m, k) < 1
        print("OK")
    """, devices=4)
    assert "OK" in out


def test_elastic_reshard_roundtrip(tmp_path):
    out = _run(f"""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint.manager import CheckpointManager
        from repro.checkpoint.reshard import reshard_tree
        from repro.launch.mesh import make_mesh

        mesh_a = make_mesh((2, 4), ("data", "model"))
        mesh_b = make_mesh((4, 2), ("data", "model"))
        tree = {{"w": jax.device_put(
            jnp.arange(64.0).reshape(8, 8),
            NamedSharding(mesh_a, P("data", "model")))}}
        m = CheckpointManager({json.dumps(str(tmp_path))})
        m.save(tree, 1)
        restored, step = m.restore_latest(
            {{"w": jax.ShapeDtypeStruct((8, 8), jnp.float32)}})
        specs = {{"w": P("data", "model")}}
        placed = reshard_tree(restored, specs, mesh_b)
        np.testing.assert_array_equal(np.asarray(placed["w"]),
                                      np.arange(64.0).reshape(8, 8))
        assert placed["w"].sharding.mesh.shape["data"] == 4
        print("OK")
    """, devices=8)
    assert "OK" in out
