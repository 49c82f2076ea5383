"""Lockstep serving steps (prefill / decode / scanned generate) + CPU demo.

``build_prefill_step``/``build_decode_step`` are the functions the dry-run
lowers for the inference shapes.  ``build_generate_fn`` is the fixed-batch
decode loop: the whole greedy generation is one ``jax.lax.scan`` inside one
jit, with the KV cache donated so decode buffers update in place — no
per-token Python dispatch, no per-token cache copy (DESIGN.md §5).  The old
per-token Python loop survives as ``python_loop_decode``, the baseline that
``benchmarks/serve_bench.py`` measures the scan against.

Everything here is *lockstep*: one fixed-shape batch that prefills,
decodes, and finishes together.  Irregular traffic (staggered arrivals,
mixed lengths, per-request sampling) goes through the continuous-batching
engine in ``launch/engine.py`` instead — ``--continuous`` below demos it,
``--paged`` demos the paged KV-cache engine with radix prefix sharing
on a shared-system-prompt trace (DESIGN.md §7), and ``--paged --spec K``
adds analog-draft speculative decoding (DESIGN.md §8).

Mesh-sharded serving (``--mesh DP,TP``, DESIGN.md §9): both engine demos
accept a mesh shape and serve tensor/data-parallel — heads and KV pools
shard over the "model" axis, slots over "data", host-side scheduling
stays global.  ``DP * TP`` must not exceed the process's device count; on
a CPU-only host, fake the devices first::

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python -m repro.launch.serve --paged --spec 2 --mesh 2,4

Outputs are bit-identical to the unsharded engine under the default
``serve_exact`` rules (pass ``--mesh-rules serve`` / ``serve_dshard`` for
the production psum-based tables, which trade that exactness back for
lower collective volume).

The CLI driver below runs a reduced config end-to-end (prefill a batch of
prompts, then decode), optionally through the NL-DPE numerics mode.
``--published-width`` serves the architecture at its published config
with bf16 weights and KV cache instead — the size to run on a TPU.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from ..configs import get_config
from ..core.engine import NLDPEConfig, OFF
from ..models import lm


def build_prefill_step(cfg, *, nldpe: NLDPEConfig = OFF, batch_groups: int = 1,
                       with_cache: bool = True, max_len: int | None = None):
    def prefill(params, cache, tokens, patch_embeds=None):
        logits, new_cache = lm.forward(
            params, tokens, cfg, mode="prefill", cache=cache,
            patch_embeds=patch_embeds, nldpe=nldpe, batch_groups=batch_groups)
        return logits[:, -1], new_cache

    def prefill_nocache(params, tokens, patch_embeds=None):
        logits, _ = lm.forward(params, tokens, cfg, mode="prefill", cache=None,
                               patch_embeds=patch_embeds, nldpe=nldpe,
                               batch_groups=batch_groups)
        return logits[:, -1]

    return prefill if with_cache else prefill_nocache


def build_decode_step(cfg, *, nldpe: NLDPEConfig = OFF, batch_groups: int = 1):
    def decode(params, cache, token, pos):
        return lm.decode_step(params, cfg, token, pos, cache, nldpe=nldpe,
                              batch_groups=batch_groups)
    return decode


def _cache_capacity(cache) -> int:
    """Largest attention ring length in the cache (== max_len whenever the
    model has at least one non-windowed attention layer)."""
    import jax.tree_util as jtu
    lengths = [leaf.shape[-1]
               for path, leaf in jtu.tree_flatten_with_path(cache)[0]
               if any(isinstance(k, jtu.DictKey) and k.key == "pos"
                      for k in path)]
    return max(lengths) if lengths else 0


def build_generate_fn(cfg, gen_len: int, *, nldpe: NLDPEConfig = OFF,
                      batch_groups: int = 1, donate_cache: bool = True,
                      donate_params: bool = False, max_len: int | None = None):
    """Jit'd greedy decode of ``gen_len`` tokens as a single lax.scan.

    generate(params, cache, tok0, start_pos) -> (tokens (B, gen_len), cache).

    The cache is donated by default: XLA aliases the input KV buffers to the
    output, so each scan step's dynamic_update_slice happens in place instead
    of copying the whole cache per token.  ``donate_params`` additionally
    donates the parameter buffers — only safe for one-shot calls (the caller
    loses them), so it is opt-in.

    Overflow guard: generating past the cache capacity silently wraps the
    ring buffer of every non-windowed layer — old positions get overwritten
    while the validity mask still admits the new ones, i.e. garbage.  When
    the model has any non-windowed attention layer the call validates
    ``start_pos + gen_len - 1 <= max_len`` (``max_len`` explicit, or
    inferred from the cache) and raises instead.  Purely windowed stacks
    wrap rings by design and are exempt.
    """
    def generate(params, cache, tok0, start_pos):
        def step(carry, i):
            tok, cache = carry
            logits, cache = lm.decode_step(params, cfg, tok, start_pos + i,
                                           cache, nldpe=nldpe,
                                           batch_groups=batch_groups)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (nxt, cache), nxt

        steps = jnp.arange(gen_len - 1, dtype=jnp.int32)
        (_, cache), toks = jax.lax.scan(step, (tok0, cache), steps)
        return jnp.concatenate([tok0[:, None], toks.T], axis=1), cache

    donate = tuple(argnum for argnum, on in ((1, donate_cache),
                                             (0, donate_params)) if on)
    jitted = jax.jit(generate, donate_argnums=donate)
    wraps_garbage = any(t in ("attn", "global", "moe")
                        for t in cfg.layer_pattern)

    def checked(params, cache, tok0, start_pos):
        limit = max_len if max_len is not None else (
            _cache_capacity(cache) if wraps_garbage else None)
        try:
            sp = int(start_pos)
        except Exception:           # traced start_pos: cannot validate here
            sp = None
        if wraps_garbage and limit and sp is not None \
                and sp + gen_len - 1 > limit:
            raise ValueError(
                f"generate overflows the KV cache: start_pos={sp} + "
                f"gen_len={gen_len} needs {sp + gen_len - 1} positions but "
                f"the cache holds {limit}; non-windowed layers would wrap "
                f"their ring buffers and silently produce garbage. "
                f"Grow max_len or shrink gen_len.")
        return jitted(params, cache, tok0, start_pos)

    return checked


def python_loop_decode(decode_fn, params, cache, tok0, start_pos: int,
                       gen_len: int):
    """The seed per-token Python loop (kept as the serve_bench baseline):
    one jit dispatch and one full cache copy per generated token."""
    tok = tok0
    out = [tok]
    for i in range(gen_len - 1):
        logits, cache = decode_fn(params, cache, tok,
                                  jnp.int32(start_pos + i))
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        out.append(tok)
    return jnp.stack(out, axis=1), cache


def _wrap_async(eng, args):
    """--async-serve: run the demo through the dispatch/drain pipeline
    (the engine was built with prefill_buckets=True, so admission waves go
    through the AOT bucket executables)."""
    if not args.async_serve:
        return eng
    from .async_engine import AsyncServeEngine
    return AsyncServeEngine(eng)


def _report_async(runner, eng, args) -> None:
    if not args.async_serve:
        return
    a = eng.metrics.snapshot()["async"]
    print(f"  async: {a['dispatched_ticks']} dispatched ticks, max "
          f"inflight {a['max_inflight']}/{a['drain_depth']}, "
          f"{a['pipeline_flushes']} pipeline flushes; prefill buckets "
          f"{eng._bucket_sizes} (x{eng.prefill_chunk} tok), "
          f"{eng.prefill_pad_chunks} pad chunks, "
          f"AOT={'yes' if eng.aot_prefill else 'no (mesh)'}")
    runner.close()


def _report_obs(eng, args) -> None:
    """Print the telemetry story after an engine demo: latency percentile
    summaries, phase wall shares, optional JSONL trace / profiler output,
    optional Prometheus exposition of the unified registry."""
    tel = eng.telemetry
    if tel is not None:
        tel.close()                      # stops a still-open profiler trace
        s = tel.summary()

        def ms(x):
            return "-" if x is None else f"{x * 1e3:.1f}ms"

        tt, tp, qw = s["ttft_s"], s["tpot_s"], s["queue_wait_s"]
        print(f"  telemetry: {s['requests_finished']} finished / "
              f"{s['ticks']} ticks; TTFT p50/p99 {ms(tt['p50'])}/"
              f"{ms(tt['p99'])}, TPOT p50/p99 {ms(tp['p50'])}/"
              f"{ms(tp['p99'])}, queue-wait p99 {ms(qw['p99'])}")
        for phase, d in s["phases"].items():
            print(f"    phase {phase:>16}: {d['seconds'] * 1e3:8.1f} ms "
                  f"over {d['calls']} calls")
        if args.trace_out:
            n = tel.flush_jsonl(args.trace_out)
            print(f"    trace: {n} events ({tel.trace.dropped} dropped) "
                  f"-> {args.trace_out}")
        if args.profile_ticks:
            print(f"    profiler: first {args.profile_ticks} ticks -> "
                  f"{tel.profiler.logdir} (load in perfetto)")
    if args.metrics:
        print(eng.metrics.prometheus_text(), end="")


def _validate_args(p, args) -> None:
    """Fail fast on incoherent flag combinations instead of silently
    ignoring them (ISSUE 10): every engine-only or paged-only flag that
    moved off its default must actually reach a code path that reads it."""
    engine = args.continuous or args.paged

    def moved(name):
        return getattr(args, name) != p.get_default(name)

    if args.continuous and args.paged:
        p.error("--continuous and --paged are mutually exclusive "
                "(pick one engine demo)")
    paged_only = ("page_size", "host_cache_pages", "priority", "num_pages",
                  "system_prompt_len", "spec", "spec_full_analog", "drift",
                  "fault_rate", "drift_dt", "kv_quant")
    bad = [n for n in paged_only if moved(n)]
    if bad and not args.paged:
        p.error(f"--{bad[0].replace('_', '-')} requires --paged "
                f"(the lockstep/--continuous paths ignore it)")
    engine_only = ("slots", "requests", "mesh", "mesh_rules", "telemetry",
                   "trace_out", "profile_ticks", "metrics", "async_serve")
    bad = [n for n in engine_only if moved(n)]
    if bad and not engine:
        p.error(f"--{bad[0].replace('_', '-')} requires an engine demo "
                f"(--continuous or --paged); the lockstep path ignores it")
    if moved("mesh_rules") and not args.mesh:
        p.error("--mesh-rules requires --mesh DP,TP")
    if moved("profile_dir") and not args.profile_ticks:
        p.error("--profile-dir requires --profile-ticks N")
    if args.python_loop and engine:
        p.error("--python-loop is a lockstep-path baseline; the engine "
                "demos always use the scanned decode")
    if moved("batch") and engine:
        p.error("--batch sizes the lockstep path; engine demos size by "
                "--slots/--requests")
    if (args.drift is not None or args.fault_rate) and not args.spec:
        p.error("--drift/--fault-rate need --spec K (they age the "
                "analog draft path)")


def _parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="qwen2_5_3b")
    p.add_argument("--published-width", action="store_true",
                   help="the architecture's published config with bf16 "
                        "weights and KV cache (default: the reduced CPU "
                        "demo config in f32)")
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--prompt-len", type=int, default=32)
    p.add_argument("--gen-len", type=int, default=32)
    p.add_argument("--nldpe", action="store_true")
    p.add_argument("--fused", action="store_true",
                   help="NL-DPE fused dual-compute pipeline")
    p.add_argument("--python-loop", action="store_true",
                   help="seed-style per-token Python decode loop "
                        "(baseline; default is the scanned generate fn)")
    p.add_argument("--continuous", action="store_true",
                   help="continuous-batching engine over a mixed trace "
                        "(slot-based KV cache, staggered arrivals)")
    p.add_argument("--paged", action="store_true",
                   help="paged KV-cache engine with radix prefix sharing "
                        "over a shared-system-prompt trace")
    p.add_argument("--page-size", type=int, default=16,
                   help="tokens per KV page for --paged")
    p.add_argument("--host-cache-pages", type=int, default=0,
                   help="host-RAM spill tier capacity for --paged "
                        "(DESIGN.md §13): LRU-evicted radix pages demote "
                        "to host instead of being destroyed, and radix "
                        "hits restore them; 0 disables")
    p.add_argument("--priority", type=int, default=0, metavar="K",
                   help="for --paged: give every Kth demo request "
                        "priority 1 (0 disables) — higher-priority "
                        "arrivals admit first and may preempt a running "
                        "lower-priority slot to host RAM, which resumes "
                        "bit-identically later")
    p.add_argument("--num-pages", type=int, default=None,
                   help="physical pages in the pool for --paged "
                        "(default: slots * ceil(max_len / page_size))")
    p.add_argument("--system-prompt-len", type=int, default=24,
                   help="shared prefix length of the --paged demo trace")
    p.add_argument("--spec", type=int, default=0, metavar="K",
                   help="speculative decode for --paged: K analog drafts "
                        "(NL-DPE log-quant numerics) per exact batched "
                        "verify pass (0 = off)")
    p.add_argument("--spec-full-analog", action="store_true",
                   help="draft with the full analog numerics (log-domain "
                        "DMMul + ACAM softmax) instead of the "
                        "conductance-programmed weights only; much slower "
                        "to *simulate* on CPU, identical outputs")
    p.add_argument("--drift", type=float, default=None, metavar="NU",
                   help="with --paged --spec: age the analog drafter live "
                        "— power-law conductance drift exponent nu on a "
                        "virtual clock, with the acceptance-driven "
                        "backoff/reprogram/disable ladder closed around it "
                        "(DESIGN.md §10).  Exact output is unaffected; "
                        "only throughput moves")
    p.add_argument("--fault-rate", type=float, default=0.0,
                   metavar="RATE",
                   help="per-cell stuck-at-fault Poisson arrival rate "
                        "(1/virtual-second) for --drift; faults survive "
                        "reprogramming")
    p.add_argument("--drift-dt", type=float, default=60.0, metavar="S",
                   help="virtual seconds per decode position for --drift "
                        "(accelerated aging clock; default 60)")
    p.add_argument("--kv-quant", choices=("log8", "int8"), default=None,
                   help="store KV pages as 8-bit codes + per-(page, head, "
                        "position) scales for --paged: 'log8' = the "
                        "drafter's sign-magnitude log grid (DESIGN.md "
                        "§11), 'int8' = uniform absmax grid.  ~3.5x pool "
                        "capacity at the same HBM; on a TPU the Pallas "
                        "paged-attention kernel dequantizes each block "
                        "of pages in VMEM")
    p.add_argument("--slots", type=int, default=4,
                   help="KV-cache slots for --continuous/--paged")
    p.add_argument("--requests", type=int, default=12,
                   help="trace length for --continuous/--paged")
    p.add_argument("--mesh", default=None, metavar="DP,TP",
                   help="serve --continuous/--paged on a (data, model) "
                        "mesh, e.g. 2,4 (needs DP*TP devices; see the "
                        "module docstring for the CPU fake-device flag)")
    p.add_argument("--mesh-rules", default=None,
                   help="sharding rule table for --mesh (default "
                        "serve_exact: bit-identical to unsharded; "
                        "also: serve, serve_dshard, long)")
    p.add_argument("--telemetry", action="store_true",
                   help="per-request latency tracing + phase timers for "
                        "--continuous/--paged (DESIGN.md §12): TTFT/TPOT/"
                        "queue-wait percentiles and a structured event "
                        "trace.  Host-side observation only — emitted "
                        "tokens are bit-identical with it off")
    p.add_argument("--trace-out", default=None, metavar="PATH",
                   help="flush the telemetry event trace as JSONL to PATH "
                        "(implies --telemetry; first line is a meta record "
                        "with the schema version and ring-drop count)")
    p.add_argument("--profile-ticks", type=int, default=0, metavar="N",
                   help="capture the first N engine ticks with "
                        "jax.profiler (implies --telemetry; perfetto-"
                        "loadable trace)")
    p.add_argument("--profile-dir", default=None,
                   help="output directory for --profile-ticks "
                        "(default /tmp/nldpe_profile)")
    p.add_argument("--metrics", action="store_true",
                   help="print the engine's unified metrics registry as "
                        "Prometheus text exposition after the run")
    p.add_argument("--async-serve", action="store_true",
                   help="drive the engine demo through the async "
                        "dispatch/drain pipeline with AOT-compiled prefill "
                        "length buckets (DESIGN.md §14); tokens are "
                        "bit-identical to the tick loop")
    p.add_argument("--seed", type=int, default=0)
    return p


def parse_args(argv=None) -> argparse.Namespace:
    p = _parser()
    args = p.parse_args(argv)
    _validate_args(p, args)
    return args


def load_model(args):
    """(cfg, params, nldpe) for the parsed CLI: the reduced config in f32,
    or with ``--published-width`` the published config in bf16.  The
    weights are random, drawn from ``--seed``."""
    from ..nn.module import param_dtype
    cfg = get_config(args.arch, reduced=not args.published_width)
    nldpe = NLDPEConfig(enabled=args.nldpe or args.fused,
                        fused_dual_compute=args.fused)
    dtype = jnp.bfloat16 if args.published_width else jnp.float32
    with param_dtype(dtype):
        params = jax.jit(lambda k: lm.init_params(k, cfg))(
            jax.random.key(args.seed))
    return cfg, params, nldpe


def build_paged_engine(cfg, params, nldpe, args, *, max_len: int,
                       mesh=None, telemetry=None):
    """The ``--paged`` engine for the parsed CLI (the demo and
    ``chip_smoke.py`` both build it here)."""
    from .engine import PagedServeEngine
    spec_draft = NLDPEConfig(enabled=args.spec_full_analog)
    drift = fidelity = None
    if args.drift is not None or args.fault_rate:
        from ..core.drift import DriftModel
        from .fidelity import DriftInjection, FidelityPolicy
        drift = DriftInjection(
            model=DriftModel(nu=args.drift or 0.0, t0=args.drift_dt,
                             fault_rate=args.fault_rate),
            seed=args.seed, dt_step=args.drift_dt,
            reprogram_s=10 * args.drift_dt)
        # short demo traces: decide every 4 spec ticks so the ladder
        # is visible within a few dozen requests
        fidelity = FidelityPolicy(window=4)
    return PagedServeEngine(cfg, params, max_slots=args.slots,
                            max_len=max_len, nldpe=nldpe,
                            page_size=args.page_size,
                            num_pages=args.num_pages,
                            host_cache_pages=args.host_cache_pages,
                            spec_k=args.spec, spec_draft=spec_draft,
                            drift=drift, fidelity=fidelity,
                            kv_quant=args.kv_quant,
                            mesh=mesh, rules=args.mesh_rules,
                            telemetry=telemetry,
                            prefill_buckets=args.async_serve or None)


def run(argv=None):
    from ..runtime.compile_cache import enable_compile_cache
    args = parse_args(argv)
    enable_compile_cache()

    mesh = None
    if args.mesh:
        from .mesh import serve_mesh
        dp, tp = (int(x) for x in args.mesh.split(","))
        mesh = serve_mesh(dp, tp)

    tel = None
    if args.telemetry or args.trace_out or args.profile_ticks:
        from ..obs import Telemetry
        tel = Telemetry(profile_ticks=args.profile_ticks,
                        profile_dir=args.profile_dir)

    cfg, params, nldpe = load_model(args)
    key = jax.random.key(args.seed)

    if args.paged:
        import numpy as np

        from .engine import Request
        rng = np.random.default_rng(args.seed)
        sys_len = min(args.system_prompt_len, args.prompt_len)
        max_len = args.prompt_len + args.gen_len
        system = tuple(int(t) for t in rng.integers(0, cfg.vocab_size,
                                                    sys_len))
        reqs = [Request(rid=i,
                        tokens=system + tuple(int(t) for t in rng.integers(
                            0, cfg.vocab_size,
                            int(rng.integers(1, max(
                                2, args.prompt_len - sys_len + 1))))),
                        max_new_tokens=int(rng.integers(2, args.gen_len + 1)),
                        arrival=int(rng.poisson(2) * i),
                        priority=(1 if args.priority
                                  and i % args.priority == args.priority - 1
                                  else 0))
                for i in range(args.requests)]
        eng = build_paged_engine(cfg, params, nldpe, args, max_len=max_len,
                                 mesh=mesh, telemetry=tel)
        drift = eng.drift
        runner = _wrap_async(eng, args)
        t0 = time.time()
        comps = runner.run(reqs)
        dt = time.time() - t0
        n_tok = sum(len(c.tokens) for c in comps)
        st = eng.stats
        mode = f", spec_k={args.spec}" if args.spec else ""
        if args.kv_quant:
            mode += f", kv_quant={args.kv_quant}"
        if mesh is not None:
            mode += f", mesh {args.mesh} [{eng.rules.name}]"
        print(f"[serve] paged: {len(comps)} requests, {n_tok} tokens in "
              f"{dt * 1e3:.0f} ms ({n_tok / max(dt, 1e-9):.1f} tok/s, "
              f"{args.slots} slots, {eng.pool.num_pages} pages x "
              f"{args.page_size} tok{mode})")
        print(f"  prefix hits {st['hits']}/{st['lookups']}, "
              f"prefill tokens saved {st['prefill_tokens_saved']}, "
              f"cow forks {st['cow_forks']}, evicted {st['evicted']}")
        if args.host_cache_pages or args.priority:
            print(f"  tiers: spilled {st['spilled']}, restored "
                  f"{st['restored']}, host {eng.pool.host_used}/"
                  f"{eng.pool.host_pages} pages; preempts {eng.preempts}, "
                  f"resumes {eng.resumes}")
        if args.spec:
            sp = eng.spec_stats
            print(f"  speculative: {sp['spec_steps']} steps, accepted "
                  f"{sp['accepted']}/{sp['drafted']} drafts "
                  f"({sp['acceptance_rate']:.1%} — the analog-fidelity "
                  f"signal), {n_tok / max(sp['spec_steps'], 1):.2f} "
                  f"tokens/verify pass")
        if drift is not None:
            fs = eng.fidelity_stats
            ev = "".join(f"\n    {e['event']:>9} @ t={e['t']:.0f}s "
                         f"(spec_k -> {e['spec_k']}, ewma={e['ewma']})"
                         for e in fs["events"]) or " (none)"
            print(f"  fidelity loop: vclock {fs['vclock_s']:.0f}s, "
                  f"{fs['reprograms']} reprograms "
                  f"({fs['downtime_s']:.0f}s downtime), "
                  f"{fs['fault_fraction']:.2%} cells stuck, live spec_k "
                  f"{fs['spec_k_live']}; events:{ev}")
        _report_async(runner, eng, args)
        _report_obs(eng, args)
        for c in comps[:4]:
            print(f"  rid={c.rid} admitted@{c.admitted_tick} "
                  f"finished@{c.finished_tick} [{c.finish_reason}] "
                  f"tokens={c.tokens[:8]}")
        return comps
    if args.continuous:
        import numpy as np

        from .engine import Request, ServeEngine
        rng = np.random.default_rng(args.seed)
        max_len = args.prompt_len + args.gen_len
        reqs = [Request(rid=i,
                        tokens=tuple(int(t) for t in rng.integers(
                            0, cfg.vocab_size,
                            int(rng.integers(2, args.prompt_len + 1)))),
                        max_new_tokens=int(rng.integers(2, args.gen_len + 1)),
                        arrival=int(rng.poisson(2) * i))
                for i in range(args.requests)]
        eng = ServeEngine(cfg, params, max_slots=args.slots, max_len=max_len,
                          nldpe=nldpe, mesh=mesh, rules=args.mesh_rules,
                          telemetry=tel,
                          prefill_buckets=args.async_serve or None)
        runner = _wrap_async(eng, args)
        t0 = time.time()
        comps = runner.run(reqs)
        dt = time.time() - t0
        n_tok = sum(len(c.tokens) for c in comps)
        print(f"[serve] continuous: {len(comps)} requests, {n_tok} tokens "
              f"in {dt * 1e3:.0f} ms ({n_tok / max(dt, 1e-9):.1f} tok/s, "
              f"{args.slots} slots, {eng.tick} ticks)")
        _report_async(runner, eng, args)
        _report_obs(eng, args)
        for c in comps[:4]:
            print(f"  rid={c.rid} admitted@{c.admitted_tick} "
                  f"finished@{c.finished_tick} [{c.finish_reason}] "
                  f"tokens={c.tokens[:8]}")
        return comps
    max_len = args.prompt_len + args.gen_len
    cache = lm.init_model_cache(cfg, args.batch, max_len,
                                dtype=params["embed"]["table"].dtype)
    prompts = jax.random.randint(key, (args.batch, args.prompt_len), 0,
                                 cfg.vocab_size)

    prefill = jax.jit(build_prefill_step(cfg, nldpe=nldpe))

    t0 = time.time()
    last_logits, cache = prefill(params, cache, prompts)
    jax.block_until_ready(last_logits)
    print(f"[serve] prefill {args.batch}x{args.prompt_len} in "
          f"{(time.time() - t0) * 1e3:.0f} ms")
    tok = jnp.argmax(last_logits, axis=-1).astype(jnp.int32)
    t0 = time.time()
    if args.python_loop:
        decode = jax.jit(build_decode_step(cfg, nldpe=nldpe))
        gen, cache = python_loop_decode(decode, params, cache, tok,
                                        args.prompt_len, args.gen_len)
    else:
        generate = build_generate_fn(cfg, args.gen_len, nldpe=nldpe)
        gen, cache = generate(params, cache, tok,
                              jnp.int32(args.prompt_len))
    gen = jax.block_until_ready(gen)
    dt = time.time() - t0
    print(f"[serve] decoded {args.gen_len - 1} steps in {dt * 1e3:.0f} ms "
          f"({dt / max(args.gen_len - 1, 1) * 1e3:.1f} ms/tok, "
          f"{'python loop' if args.python_loop else 'scan'}); "
          f"sample row: {gen[0, :12].tolist()}")
    return gen


if __name__ == "__main__":
    run()
