import os
os.environ["XLA_FLAGS"] = (os.environ.get("DRYRUN_EXTRA_XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

This process (and ONLY this process) forces 512 host devices so
``make_production_mesh`` can build the 16x16 single-pod and 2x16x16
multi-pod meshes.  For each cell we:

  1. build the step function (train / prefill / decode) for the FULL config,
  2. derive fully-sharded in_shardings from the spec-mode param init +
     cache/batch spec resolvers (no array is ever materialized),
  3. jit(...).lower(**ShapeDtypeStructs).compile(),
  4. record memory_analysis / cost_analysis / parsed collective bytes and
     the three §Roofline terms into experiments/dryrun/<cell>.json.

Any sharding mismatch, compile OOM, or unsupported collective here is a
framework bug.  Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2_7b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--skip-existing]

(no ``from __future__ import annotations`` here: the XLA_FLAGS lines above
must be the first statements in the file, before any jax-importing module.)
"""
import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs import SHAPES, ArchConfig, cells, get_config, input_specs
from ..models import lm
from ..nn.module import param_dtype as param_dtype_ctx, spec_mode
from ..optim import adamw
from ..parallel.context import sharding_ctx
from ..parallel.sharding import resolve, rules_for
from ..perfmodel.roofline import Roofline, analytic_step_flops
from ..utils.hlo import collective_summary
from .mesh import make_production_mesh

_DTYPE_BYTES = {jnp.dtype(k): v for k, v in {
    "float32": 4, "bfloat16": 2, "int32": 4, "float16": 2, "int8": 1,
    "uint8": 1, "int64": 8, "float64": 8, "bool": 1}.items()}


def _bytes_per_device(shapes, specs, mesh) -> float:
    """Analytic per-device bytes of a (shape, spec) tree pair."""
    total = 0.0
    flat_s = jax.tree.leaves(shapes)
    flat_p = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    for s, spec in zip(flat_s, flat_p):
        n = float(np.prod(s.shape)) if s.shape else 1.0
        shard = 1
        for ax in (spec or ()):
            if ax is None:
                continue
            for a in (ax if isinstance(ax, tuple) else (ax,)):
                shard *= mesh.shape[a]
        total += n / shard * _DTYPE_BYTES.get(jnp.dtype(s.dtype), 4)
    return total


def _shardings(specs, mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def _batch_groups(mesh, rules, global_batch: int) -> int:
    ax = rules.lookup("expert_group")
    if ax is None or mesh is None:
        return 1
    size = 1
    for a in (ax if isinstance(ax, (tuple, list)) else (ax,)):
        if a in mesh.shape:
            size *= mesh.shape[a]
    return size if global_batch % size == 0 else 1


def lower_cell(arch: str, shape_name: str, multi_pod: bool = False,
               rules_name: str | None = None, donate: bool = True,
               verbose: bool = True, param_dtype: str | None = None,
               kv_int8: bool = False, nldpe: bool = False) -> dict:
    cfg = get_config(arch)
    if kv_int8:
        cfg = dataclasses.replace(cfg, kv_cache_dtype="int8")
    from ..core.engine import NLDPEConfig
    nldpe_cfg = NLDPEConfig(enabled=nldpe)
    shape = SHAPES[shape_name]
    mode = {"train": "train", "prefill": "serve", "decode": "serve",
            "long_decode": "long"}[shape.kind]
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = rules_for(rules_name or mode, multi_pod)
    chips = mesh.devices.size
    key = jax.random.key(0)
    is_train = shape.kind == "train"
    pdtype = jnp.float32 if is_train else jnp.bfloat16
    if param_dtype is not None:
        pdtype = {"f32": jnp.float32, "bf16": jnp.bfloat16}[param_dtype]

    with param_dtype_ctx(pdtype):
        param_shapes = jax.eval_shape(lambda: lm.init_params(key, cfg))
        with spec_mode(mesh, rules):
            pspecs = lm.init_params(key, cfg)
    groups = _batch_groups(mesh, rules, shape.global_batch * shape.seq_len)

    specs_in = input_specs(cfg, shape)
    batch_specs = {}
    for name, s in specs_in.items():
        axes = {"tokens": ("batch", None), "labels": ("batch", None),
                "token": ("batch",), "pos": (),
                "patch_embeds": ("batch", None, None)}[name]
        batch_specs[name] = resolve(rules, axes, s.shape, mesh)

    report = {"arch": arch, "shape": shape_name,
              "mesh": "2x16x16" if multi_pod else "16x16",
              "chips": chips, "rules": rules.name, "kind": shape.kind}

    if is_train:
        opt_shapes = jax.eval_shape(adamw.init, param_shapes)
        opt_specs = {"m": pspecs, "v": pspecs, "step": P()}
        from .train import build_train_step
        step = jax.jit(
            build_train_step(cfg, adamw.AdamWConfig(), batch_groups=groups,
                             nldpe=nldpe_cfg),
            in_shardings=(_shardings(pspecs, mesh), _shardings(opt_specs, mesh),
                          _shardings(batch_specs, mesh)),
            donate_argnums=(0, 1) if donate else ())
        args = (param_shapes, opt_shapes, specs_in)
        state_bytes = (_bytes_per_device(param_shapes, pspecs, mesh)
                       + _bytes_per_device(opt_shapes, opt_specs, mesh))
    else:
        from .serve import build_decode_step, build_prefill_step
        cache_shapes = jax.eval_shape(
            lambda: lm.init_model_cache(cfg, shape.global_batch, shape.seq_len))
        cache_specs_tree = lm.cache_pspecs(cfg, shape.global_batch,
                                           shape.seq_len, mesh, rules)
        if shape.kind == "prefill":
            fn = build_prefill_step(cfg, batch_groups=groups, nldpe=nldpe_cfg)
            extra = ({"patch_embeds": specs_in["patch_embeds"]}
                     if "patch_embeds" in specs_in else {})
            step = jax.jit(
                fn,
                in_shardings=(_shardings(pspecs, mesh),
                              _shardings(cache_specs_tree, mesh),
                              NamedSharding(mesh, batch_specs["tokens"]),
                              *([NamedSharding(mesh, batch_specs["patch_embeds"])]
                                if extra else [])),
                donate_argnums=(1,) if donate else ())
            args = (param_shapes, cache_shapes, specs_in["tokens"],
                    *(extra.values()))
        else:
            fn = build_decode_step(cfg, batch_groups=groups, nldpe=nldpe_cfg)
            step = jax.jit(
                fn,
                in_shardings=(_shardings(pspecs, mesh),
                              _shardings(cache_specs_tree, mesh),
                              NamedSharding(mesh, batch_specs["token"]),
                              NamedSharding(mesh, batch_specs["pos"])),
                donate_argnums=(1,) if donate else ())
            args = (param_shapes, cache_shapes, specs_in["token"],
                    specs_in["pos"])
        state_bytes = (_bytes_per_device(param_shapes, pspecs, mesh)
                       + _bytes_per_device(cache_shapes, cache_specs_tree, mesh))

    t0 = time.time()
    with sharding_ctx(mesh, rules):
        lowered = step.lower(*args)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    cost = compiled.cost_analysis() or {}
    try:
        mem = compiled.memory_analysis()
        mem_report = {k: getattr(mem, k) for k in
                      ("argument_size_in_bytes", "output_size_in_bytes",
                       "temp_size_in_bytes", "generated_code_size_in_bytes")
                      if hasattr(mem, k)}
    except Exception as e:  # CPU backend may not implement it
        mem_report = {"error": str(e)}

    hlo = compiled.as_text()
    n_groups = max(cfg.n_layers // len(cfg.layer_pattern), 1)
    coll = collective_summary(hlo, chips, loop_trip_hint=n_groups)
    model_flops, analytic_flops = analytic_step_flops(cfg, shape)
    hlo_flops = float(cost.get("flops", 0.0))
    hlo_bytes = float(cost.get("bytes accessed", 0.0))

    rf = Roofline(
        arch=arch, shape=shape_name, mesh=report["mesh"], chips=chips,
        hlo_flops_per_device=hlo_flops, hlo_bytes_per_device=hlo_bytes,
        collective_bytes_per_device=coll["total_wire_bytes_per_device"],
        model_flops_global=model_flops, analytic_flops_global=analytic_flops)

    report.update({
        "ok": True,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "cost_analysis": {k: float(v) for k, v in cost.items()
                          if isinstance(v, (int, float))},
        "memory_analysis": mem_report,
        "state_bytes_per_device": state_bytes,
        "collectives": coll,
        "roofline": rf.row(),
        "hlo_lines": hlo.count("\n"),
    })
    if verbose:
        r = rf.row()
        print(f"[dryrun] {arch:24s} {shape_name:12s} {report['mesh']:8s} "
              f"ok lower={t_lower:.1f}s compile={t_compile:.1f}s "
              f"state/dev={state_bytes / 2**30:.2f}GiB "
              f"compute={r['compute_s']:.2e}s mem={r['memory_s']:.2e}s "
              f"coll={r['collective_s']:.2e}s dom={r['dominant']}")
    return report


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--arch")
    p.add_argument("--shape")
    p.add_argument("--all", action="store_true")
    p.add_argument("--multi-pod", action="store_true")
    p.add_argument("--both-meshes", action="store_true")
    p.add_argument("--rules", default=None)
    p.add_argument("--param-dtype", default=None, choices=[None, "f32", "bf16"])
    p.add_argument("--kv-int8", action="store_true")
    p.add_argument("--nldpe", action="store_true",
                   help="lower the analog-numerics mode (log-domain DMMul, "
                        "ACAM activations/softmax) instead of bf16")
    p.add_argument("--out", default="experiments/dryrun")
    p.add_argument("--skip-existing", action="store_true")
    p.add_argument("--tag", default="")
    args = p.parse_args(argv)

    os.makedirs(args.out, exist_ok=True)
    todo = []
    if args.all:
        meshes = [False, True] if args.both_meshes else [args.multi_pod]
        for arch, shape, skipped in cells():
            for mp in meshes:
                todo.append((arch, shape, mp))
    else:
        todo.append((args.arch, args.shape, args.multi_pod))

    failures = 0
    for arch, shape, mp in todo:
        mesh_tag = "2x16x16" if mp else "16x16"
        name = f"{arch}__{shape}__{mesh_tag}{args.tag}.json"
        path = os.path.join(args.out, name)
        if args.skip_existing and os.path.exists(path):
            print(f"[dryrun] skip {name} (exists)")
            continue
        try:
            report = lower_cell(arch, shape, multi_pod=mp,
                                rules_name=args.rules,
                                param_dtype=args.param_dtype,
                                kv_int8=args.kv_int8, nldpe=args.nldpe)
        except Exception as e:
            failures += 1
            report = {"arch": arch, "shape": shape, "mesh": mesh_tag,
                      "ok": False, "error": f"{type(e).__name__}: {e}",
                      "traceback": traceback.format_exc()[-4000:]}
            print(f"[dryrun] {arch} {shape} {mesh_tag} FAILED: {e}")
        with open(path, "w") as f:
            json.dump(report, f, indent=1, default=str)
    print(f"[dryrun] wrote {len(todo)} reports, {failures} failures")
    return failures


if __name__ == "__main__":
    raise SystemExit(main())
