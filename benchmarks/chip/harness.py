"""The benchmark harness: one cell, one seed, one run.

A cell is ``workloads/<cell>.json``: it names a configuration
(``configs/<config>.json``), a traffic mix (``traffic/<traffic>.json``),
the chips it needs and the limits of its correctness check.  The metrics
a cell reports are the entries of ``BENCHMARK.json`` that list it, each
read by ``metrics/<metric>.py``.  Nothing here names a cell, a mix or a
metric, so a later change adds one by adding files.

A run:

1. set-up (``setup_s`` counts all of it, from process start): the
   benchmark's weights from the seed (``weights.py``), the program's paged
   engine as ``launch/serve.build_paged_engine`` builds it for
   ``--paged --published-width --telemetry`` (on a ``("data", "model")``
   mesh of the cell's chips where the configuration's ``engine`` block
   gives ``"mesh": [data, model]``, under the program's default rules,
   with every weight made straight into the placement the engine gives
   it), wrapped in
   ``AsyncServeEngine``, and a warm-up that runs every program the window
   drives (chunk prefill, a copy-on-write fork, first-token sampling,
   decode) so that nothing compiles in the window;
2. the window: requests go in through ``submit`` at their due time on the
   host clock (open loop), or are fed so that the engine always has more
   waiting than it has slots (backlog); with ``--trace 1`` the profiler
   records a few steady seconds in its middle;
3. after the window: the peak device memory is read, the engine is
   stopped and freed, and a sample of the finished requests is compared
   with the plain reference (``reference.py``).
"""
from __future__ import annotations

import gc
import importlib.util
import json
import pathlib
import shutil
import sys
import tempfile
import threading
import time

import numpy as np

from . import model_math, reference, weights
from .gen import traffic

HERE = pathlib.Path(__file__).resolve().parent
REPO = HERE.parents[1]
WARM_RID = 10**9                 # warm-up request ids; traffic ids are small
TAIL_S = 60.0                    # wait this long past the window at most
PROFILE_S = 10.0                 # the traced stretch inside the window
BACKLOG_AHEAD = 2                # keep slots x this many requests submitted


class NoChip(RuntimeError):
    """JAX sees no TPU, or fewer chips than the cell asks for."""


class _Abort(Exception):
    pass


# ---------------------------------------------------------------------------
# finding things by name
# ---------------------------------------------------------------------------

def _find(kind: str, name: str, roots) -> pathlib.Path:
    for root in roots:
        for p in (pathlib.Path(root) / kind / f"{name}.json",
                  pathlib.Path(root) / kind / f"{name}.py"):
            if p.is_file():
                return p
    raise FileNotFoundError(f"no {kind[:-1]} named {name!r} under "
                            f"{[str(r) for r in roots]}")


def load_cell(name: str, roots=(HERE,)) -> dict:
    cell = json.loads(_find("workloads", name, roots).read_text())
    cfg = json.loads(_find("configs", cell["config"], roots).read_text())
    mix = json.loads(_find("traffic", cell["traffic"], roots).read_text())
    if "arrival" in cell:
        mix = {**mix, "arrival": {**mix["arrival"], **cell["arrival"]}}
    return {"name": name, "cell": cell, "cfg": cfg, "mix": mix,
            "roots": list(roots)}


def cell_metrics(bench: dict, cell: str, trace: bool) -> list[dict]:
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def read_metric(name: str, run, roots):
    """Read metric ``name`` with ``metrics/<name>.py``; a split name
    (``decode_step_ms.chat``) without a reader of its own falls back to
    the reader of its stem (``metrics/decode_step_ms.py``)."""
    try:
        path = _find("metrics", name, roots)
    except FileNotFoundError:
        if "." not in name:
            raise
        path = _find("metrics", name.rsplit(".", 1)[0], roots)
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


def rehearsal_cfg(cfg: dict) -> dict:
    """The configuration at its CPU rehearsal size."""
    small = dict(cfg)
    small.update({k: v for k, v in cfg["rehearsal"].items()
                  if k not in ("engine",)})
    small["engine"] = cfg["rehearsal"]["engine"]
    small["torch_dtype"] = "float32"
    return small


# ---------------------------------------------------------------------------
# the run's record, handed to every metric reader
# ---------------------------------------------------------------------------

class Run:
    """What one run observed, on the host's ``perf_counter`` clock.

    ``records``: rid -> the program's RequestRecord (admit, first token,
    finish stamps, prompt length and prefix reuse); ``due``/``submitted``:
    rid -> host time; ``window``: (open, close); ``harvests``: one entry
    per decode tick as its tokens reach the host, ``(t, emitted,
    attended, steps)``; ``events``: the program's telemetry events;
    ``profile``: the traced stretch (start, end) and ``reduced`` its
    ``xplane.Reduced``; ``peaks``: the chip's row of ``peaks.json``;
    ``chips``: the devices the cell runs on.
    """

    def __init__(self, **kw):
        self.__dict__.update(kw)

    # shared arithmetic for readers --------------------------------------
    def in_window(self, t) -> bool:
        return t is not None and self.window[0] <= t <= self.window[1]

    def in_profile(self, t) -> bool:
        return (self.profile is not None and t is not None
                and self.profile[0] <= t <= self.profile[1])

    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def peak(self, name: str) -> float:
        """A peak of ``peaks.json`` over all the chips the cell runs on."""
        return self.chips * self.peaks[name]

    def due_in_window(self) -> list[int]:
        return [rid for rid, t in self.due.items() if self.in_window(t)]

    def admitted_in_window(self) -> list:
        return [r for r in self.records.values() if self.in_window(r.admit_s)]

    def step_seconds(self, module: str = "jit_decode") -> float | None:
        """Device seconds per scanned decode step, from the trace."""
        if self.reduced is None or module not in self.reduced.modules:
            return None
        secs, count = self.reduced.modules[module]
        return secs / count / self.decode_block if count else None

    def profile_decode(self):
        """(steps, emitted, attended) of the decode ticks whose tokens
        reached the host inside the traced stretch."""
        hs = [h for h in self.harvests if self.in_profile(h[0])]
        return (sum(h[3] for h in hs), sum(h[1] for h in hs),
                sum(h[2] for h in hs))


def p90(values) -> float:
    """The 90th percentile, a missing value counting as infinitely late."""
    return float(np.percentile(np.asarray(values, np.float64), 90))


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def _device_check(rehearse: bool, chips: int):
    import jax
    devs = jax.devices()
    if not rehearse:
        if devs[0].platform != "tpu":
            raise NoChip(f"no TPU: JAX platform {devs[0].platform!r}")
        if len(devs) < chips:
            raise NoChip(f"the cell needs {chips} chips, JAX sees "
                         f"{len(devs)}")
    return devs[:chips]


def mesh_shape(cell: dict, cfg: dict) -> tuple[int, int] | None:
    """The configuration's ``(data, model)`` mesh, or None for one device;
    a cell whose chips differ from the mesh's devices is refused."""
    mesh = cfg["engine"].get("mesh")
    devices = 1 if mesh is None else mesh[0] * mesh[1]
    if cell.get("chips", 1) != devices:
        raise ValueError(f"the cell asks for {cell.get('chips', 1)} chips "
                         f"and its engine's mesh {mesh} for {devices}")
    return None if mesh is None else (int(mesh[0]), int(mesh[1]))


def _mesh(devs, shape):
    from jax.sharding import AxisType, Mesh
    return Mesh(np.asarray(devs).reshape(shape), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)


def _param_shardings(pcfg, mesh, rules):
    """Where the engine places each weight: the program's parameter specs
    under its rules on the mesh."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.models import lm
    from repro.nn.module import spec_mode
    with spec_mode(mesh, rules):
        specs = lm.init_params(jax.random.key(0), pcfg)
    return jax.tree.map(lambda p: NamedSharding(mesh, p), specs,
                        is_leaf=lambda x: isinstance(x, PartitionSpec))


def _check_layout(params, pcfg) -> None:
    """The benchmark's weight tree must be the one the program reads."""
    import jax

    from repro.models import lm
    want = jax.eval_shape(lambda k: lm.init_params(k, pcfg),
                          jax.random.key(0))
    got_s = jax.tree.map(lambda x: tuple(x.shape), params)
    want_s = jax.tree.map(lambda x: tuple(x.shape), want)
    if got_s != want_s:
        raise ValueError(f"benchmark weights {got_s} do not match the "
                         f"program's parameter tree {want_s}")


def _check_config(cfg: dict, pcfg) -> None:
    pairs = {"num_hidden_layers": pcfg.n_layers, "hidden_size": pcfg.d_model,
             "num_attention_heads": pcfg.n_heads,
             "num_key_value_heads": pcfg.n_kv_heads,
             "head_dim": pcfg.resolved_head_dim,
             "intermediate_size": pcfg.d_ff, "vocab_size": pcfg.vocab_size,
             "rope_theta": pcfg.rope_theta,
             "attention_bias": pcfg.qkv_bias,
             "tie_word_embeddings": pcfg.tie_embeddings}
    bad = {k: (cfg[k], v) for k, v in pairs.items() if cfg[k] != v}
    if bad:
        raise ValueError(f"configuration file and program disagree: {bad}")


def _install_probes(eng, harvests: list, waves: list, state: dict) -> None:
    """Observe the two host boundaries of the engine's tick loop: where a
    decode tick's tokens reach the host, and an admission wave."""
    harvest, admit = eng._harvest, eng._admit_wave

    def probe_harvest(emits, active):
        emitted = attended = steps = 0
        for s, req in enumerate(eng._slot_owner):
            if req is None:
                continue
            n = int((emits[:, s] >= 0).sum())
            if n:
                base = len(req.tokens) + len(eng._out[req.rid])
                attended += n * base + n * (n - 1) // 2
                emitted += n
                steps = max(steps, n)
        harvests.append((time.perf_counter(), emitted, attended, steps))
        return harvest(emits, active)

    def probe_admit(reqs):
        t0 = time.perf_counter()
        out = admit(reqs)
        t1 = time.perf_counter()
        waves.append((t0, t1, len(reqs)))
        if not eng._free and state.get("full_at") is None:
            state["full_at"] = t1
        return out

    eng._harvest = probe_harvest
    eng._admit_wave = probe_admit


def _abort(runner) -> None:
    """Stop the scheduler at its next engine call and join both threads;
    requests still in flight are dropped."""
    eng = runner.engine

    def stop(*a, **k):
        raise _Abort()

    eng._dispatch_tick = stop
    eng._admit_wave = stop
    try:
        runner.close()
    except RuntimeError as e:
        if not isinstance(e.__cause__, _Abort):
            raise


def execute(cell_name: str, seed: int, seconds: float, trace: bool, *,
            t_process: float, rehearse: bool = False, roots=(HERE,),
            bench: dict | None = None, tamper=None,
            control: bool = False, check: bool = True,
            arrival: dict | None = None, wait_for: str = "finish",
            log=None) -> dict:
    """One run of one cell; returns the result line as a dict.

    ``tamper(engine)`` may break the timed path before the run (tests of
    the check), ``control`` puts the float8 control in the program's place
    in the check (``correct`` is then the control's), ``check=False`` skips
    the comparison (the knee sweep), ``arrival`` overrides the mix's
    arrival parameters (the sweep's rates), and ``wait_for="admit"`` ends
    an open-loop run once every request due in the window is admitted
    rather than finished (at most ``TAIL_S`` past the close either way)."""
    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    c = load_cell(cell_name, roots)
    cell, mix = c["cell"], c["mix"]
    if arrival:
        mix = {**mix, "arrival": {**mix["arrival"], **arrival}}
    cfg = rehearsal_cfg(c["cfg"]) if rehearse else c["cfg"]
    E = cfg["engine"]
    scale = cfg.get("length_scale", 1.0)
    if bench is None:
        bench = json.loads((REPO / "BENCHMARK.json").read_text())
    shape = mesh_shape(cell, cfg)
    devs = _device_check(rehearse, cell.get("chips", 1))
    if str(REPO / "src") not in sys.path:    # the program under test
        sys.path.append(str(REPO / "src"))

    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.core.engine import NLDPEConfig
    from repro.launch import serve
    from repro.launch.async_engine import AsyncServeEngine
    from repro.launch.engine import Completion, Request
    from repro.obs import Telemetry
    from repro.parallel.sharding import serve_exact_rules
    from repro.runtime.compile_cache import enable_compile_cache

    # every trace and compile the process makes, with the host time it
    # ended: none may fall inside the window
    compiles: list = []

    def on_compile(event, duration, **kw):
        if event.startswith("/jax/core/compile/"):
            compiles.append((time.perf_counter(), event))

    jax.monitoring.register_event_duration_secs_listener(on_compile)
    if not rehearse:
        log(f"[bench] compile cache {enable_compile_cache()}")
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    peaks = None if rehearse else model_math.peaks(devs[0].device_kind)

    # -- set-up ---------------------------------------------------------
    pcfg = get_config(cfg["arch"], reduced=rehearse)
    _check_config(cfg, pcfg)
    mesh = placed = None
    if shape is not None:
        mesh = _mesh(devs, shape)
        placed = _param_shardings(pcfg, mesh, serve_exact_rules())
    dtype = jnp.float32 if rehearse else jnp.bfloat16
    params = weights.make(cfg, seed, dtype, placed)
    jax.block_until_ready(params)
    log(f"[bench] weights at {time.perf_counter() - t_process:.1f} s")
    argv = ["--paged", "--telemetry", "--arch", cfg["arch"],
            "--slots", str(E["slots"]), "--page-size", str(E["page_size"]),
            "--num-pages", str(E["num_pages"]), "--seed", str(seed % 2**31)]
    if not rehearse:
        argv.append("--published-width")
    args = serve.parse_args(argv)
    _check_layout(params, pcfg)
    tel = Telemetry(capacity=1 << 18, record_capacity=1 << 17,
                    percentile_window=1 << 17)
    eng = serve.build_paged_engine(pcfg, params, NLDPEConfig(enabled=False),
                                   args, max_len=E["max_len"], mesh=mesh,
                                   telemetry=tel)
    if mesh is not None and any(
            a is not b for a, b in zip(jax.tree.leaves(params),
                                       jax.tree.leaves(eng.params))):
        raise ValueError("the engine moved the benchmark's weights: their "
                         "placement is not the one the engine gives them")
    if (eng.prefill_chunk, eng.decode_block) != (E["prefill_chunk"],
                                                 E["decode_block"]):
        raise ValueError("engine chunk/block sizes differ from the "
                         "configuration file")
    harvests, waves, state, pool = [], [], {}, {}
    _install_probes(eng, harvests, waves, state)
    if tamper is not None:
        tamper(eng)
    runner = AsyncServeEngine(eng)
    log(f"[bench] engine at {time.perf_counter() - t_process:.1f} s")

    # warm-up: a prompt of whole pages, then the same prompt again, which
    # the prefix cache covers entirely (a copy-on-write fork)
    wrng = np.random.default_rng(12345)
    warm = tuple(int(t) for t in wrng.integers(0, cfg["vocab_size"],
                                               2 * E["page_size"]))
    for i in range(2):
        runner.run([Request(rid=WARM_RID + i, tokens=warm,
                            max_new_tokens=2 * E["decode_block"] + 1)])
    if mix.get("warm_system_prompts"):
        # a deployment's prefix cache holds its system prompts: prefill
        # them once, each as a request that ends at its first token
        prompts = traffic.system_prompts(mix, seed=seed,
                                         vocab=cfg["vocab_size"], scale=scale)
        runner.run([Request(rid=WARM_RID + 2 + i, tokens=tuple(p),
                            max_new_tokens=1)
                    for i, p in enumerate(prompts)])
    jax.block_until_ready(eng.cache)
    log(f"[bench] warm-up done at {time.perf_counter() - t_process:.1f} s")

    backlog = mix["arrival"]["process"] == "backlog"
    if backlog:
        reqs = traffic.stream(mix, seed=seed, vocab=cfg["vocab_size"],
                              max_len=E["max_len"], scale=scale)
    else:
        preroll = mix.get("preroll_s", 0.0) * (0.1 if rehearse else 1.0)
        reqs = traffic.build(mix, seed=seed, vocab=cfg["vocab_size"],
                             max_len=E["max_len"],
                             horizon_s=preroll + seconds + TAIL_S,
                             scale=scale)
    by_rid = {}

    # -- the window -----------------------------------------------------
    completions: dict = {}
    finished: set = set()
    stop = threading.Event()
    errors: list = []

    def consume():
        while not stop.is_set():
            try:
                for comp in runner.results():
                    completions[comp.rid] = comp
                    finished.add(comp.rid)
            except RuntimeError as e:
                if not isinstance(e.__cause__, _Abort):
                    errors.append(e)
                return
            time.sleep(0.002)

    consumer = threading.Thread(target=consume, name="bench-results",
                                daemon=True)
    due, submitted, rejected = {}, {}, []

    def at_close():
        """The page pool when the window closes: pages taken (by slots or
        kept by the prefix cache), pages with a live reference, and the
        tokens the slots hold."""
        if pool:
            return
        owners = [r for r in eng._slot_owner if r is not None]
        pool.update(
            pages=eng.pool.num_pages,
            taken=eng.pool.num_pages - eng.pool.free_pages,
            referenced=int((eng.pool._ref > 0).sum()),
            slot_tokens=sum(len(r.tokens) + len(eng._out.get(r.rid, ()))
                            for r in owners),
            slots_busy=len(owners))

    def admitted(rid):
        rec = tel.live.get(rid)
        return rid in finished or (rec is not None
                                   and rec.admit_s is not None)

    def send(r):
        by_rid[r.rid] = r
        try:
            runner.submit(Request(rid=r.rid, tokens=r.tokens,
                                  max_new_tokens=r.max_new_tokens,
                                  arrival=0))
            submitted[r.rid] = time.perf_counter()
        except ValueError as e:
            rejected.append((r.rid, str(e)))

    profile = {}
    prof_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None

    def profiler(t_at):
        time.sleep(max(0.0, t_at - time.perf_counter()))
        jax.profiler.start_trace(prof_dir)
        from .xplane import WINDOW_SPAN
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            profile["start"] = time.perf_counter()
            time.sleep(min(PROFILE_S, seconds / 3))
            profile["end"] = time.perf_counter()
        jax.profiler.stop_trace()

    prof_t = None
    t_start = time.perf_counter()
    setup_s = t_start - t_process
    log(f"[bench] {cell_name} seed {seed}: set-up {setup_s:.3f} s")
    consumer.start()
    if backlog:
        it = reqs
        pending_cap = BACKLOG_AHEAD * E["slots"]
        for r in it:
            due[r.rid] = t_start
            send(r)
            if len(submitted) >= pending_cap:
                break
        t_open = None
        while True:
            now = time.perf_counter()
            if t_open is None and state.get("full_at") is not None:
                t_open = state["full_at"]
                t_close = t_open + seconds
                if trace:
                    prof_t = threading.Thread(
                        target=profiler, name="bench-profiler", daemon=True,
                        args=(t_open + max(0.0, (seconds - PROFILE_S) / 2),))
                    prof_t.start()
            if t_open is not None and now >= t_close:
                at_close()
                break
            if errors:
                break
            while len(submitted) - len(finished) < pending_cap:
                r = next(it)
                due[r.rid] = t_start
                send(r)
            time.sleep(0.005)
    else:
        t_open = t_start + preroll
        t_close = t_open + seconds
        for r in reqs:
            due[r.rid] = t_start + r.due_s
        in_window = [rid for rid, t in due.items()
                     if t_open <= t <= t_close]
        if trace:
            prof_t = threading.Thread(
                target=profiler, name="bench-profiler", daemon=True,
                args=(t_open + max(0.0, (seconds - PROFILE_S) / 2),))
            prof_t.start()

        def may_stop(now):
            if now <= t_close:
                return bool(errors)
            at_close()
            done = (all(admitted(rid) for rid in in_window)
                    if wait_for == "admit" else finished.issuperset(in_window))
            return bool(errors) or done or now > t_close + TAIL_S

        for r in reqs:
            now = time.perf_counter()
            while now < due[r.rid] and not may_stop(now):
                time.sleep(min(0.002, due[r.rid] - now))
                now = time.perf_counter()
            if may_stop(now):
                break
            send(r)
        while not may_stop(time.perf_counter()):
            time.sleep(0.005)
    if prof_t is not None:
        prof_t.join()
    _abort(runner)
    t_end = time.perf_counter()
    # requests still in a slot when the engine stopped: the tokens they
    # had served by then (the scheduler thread has ended)
    partial = [Completion(rid=r.rid, prompt=tuple(r.tokens),
                          tokens=list(eng._out[r.rid]),
                          finish_reason="stopped", admitted_tick=0,
                          finished_tick=0)
               for r in eng._slot_owner
               if r is not None and r.rid < WARM_RID and eng._out.get(r.rid)]
    stop.set()
    consumer.join()
    if errors:
        raise errors[0]

    jax.monitoring.unregister_event_duration_listener(on_compile)
    in_win = [e for t, e in compiles if t_open <= t <= t_close]
    log(f"[bench] compile events in the window: {len(in_win)} "
        f"({sorted(set(in_win))})")
    log(f"[bench] window {t_close - t_open:.1f} s closed; "
        f"{len(submitted)} submitted, {len(finished)} finished, "
        f"{len(harvests)} decode ticks, {len(waves)} waves; stopped "
        f"{t_end - t_close:.1f} s after the close")
    log(f"[bench] pool at the close: {pool}")
    mem = 0
    for d in devs:
        st = d.memory_stats() or {}
        mem = max(mem, int(st.get("peak_bytes_in_use", 0)))

    records = {r.rid: r for r in list(tel.records) + list(tel.live.values())
               if r.rid < WARM_RID}
    events = [e for e in tel.trace]
    reduced = None
    if trace and not rehearse:
        from .xplane import find_xplane, reduce
        reduced = reduce(find_xplane(prof_dir))
        for name, (secs, count) in sorted(reduced.modules.items(),
                                          key=lambda kv: -kv[1][0]):
            log(f"[trace] {name}: {secs:.4f} s in {count} executions")
        log(f"[trace] busy {reduced.busy_s:.4f} s of "
            f"{reduced.window_s:.4f} s; collectives "
            f"{reduced.collective_s:.4f} s, of which exposed "
            f"{reduced.collective_exposed_s:.4f} s (per chip)")
    if prof_dir:
        shutil.rmtree(prof_dir, ignore_errors=True)
    run = Run(cfg=cfg, window=(t_open, t_close), t_end=t_end,
              setup_s=setup_s, due=due,
              submitted=submitted, records=records, harvests=harvests,
              events=events, reduced=reduced, peaks=peaks,
              profile=((profile["start"], profile["end"]) if profile
                       else None),
              chips=len(devs),
              slots=E["slots"], prefill_chunk=E["prefill_chunk"],
              decode_block=E["decode_block"], requests=by_rid)

    metrics = {}
    for m in cell_metrics(bench, cell_name, trace):
        v = read_metric(m["name"], run, roots)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        else:
            log(f"[bench] {m['name']}: nothing to read in this run")

    if backlog:
        attempted = [r.rid for r in records.values()
                     if r.admit_s is not None and r.admit_s <= t_close]
        failed = len(rejected)
    else:
        attempted = run.due_in_window()
        failed = sum(1 for rid in attempted if rid not in finished) \
            + sum(1 for rid, _ in rejected if rid in attempted)
    # -- free the program, then the correctness check ----------------------
    done = [completions[rid] for rid in sorted(completions)
            if rid < WARM_RID] + partial
    del runner, eng, tel
    gc.collect()
    if check:
        checks, detail = _check(cfg, cell, params, done, records, by_rid,
                                seed, control=control, log=log)
    else:
        checks, detail = {}, {}
    detail["run"] = run
    correct = all(v["value"] <= v["limit"] for v in checks.values())
    result = {"correct": bool(correct), "attempted": len(attempted),
              "failed": int(failed), "metrics": metrics,
              "device": {"platform": devs[0].platform,
                         "kind": devs[0].device_kind, "count": len(devs),
                         "memory_peak_bytes": mem}}
    if reduced is not None:
        result["device"]["busy_s"] = reduced.busy_s
        result["device"]["window_s"] = reduced.window_s
        result["breakdown"] = {"device_ops": [list(x) for x in
                                              reduced.top_ops],
                               "idle_gaps": [list(x) for x in
                                             reduced.idle_gaps]}
    result["kv_pool"] = pool
    result["checks"] = checks
    result["_detail"] = detail
    return result


def _sample(done, records, seed: int, ref_tokens: int, ref_max: int):
    """The requests the reference reads, among those finished and those
    still in a slot when the engine stopped (with the tokens served by
    then): the longest, one that forked a cached boundary page and one
    with a prefix hit where there are such, then others drawn from the
    seed, until ``ref_tokens`` served tokens or ``ref_max`` requests."""
    if not done:
        return []
    rng = np.random.default_rng(np.random.SeedSequence((seed + 1)
                                                       & (2**64 - 1)))
    order = list(rng.permutation(len(done)))
    pick = [max(range(len(done)),
                key=lambda i: len(done[i].prompt) + len(done[i].tokens))]

    def rec(i):
        return records.get(done[i].rid)

    for want in (lambda r: r.reuse == r.prompt_len - 1,
                 lambda r: r.reuse > 0):
        for i in order:
            r = rec(i)
            if i not in pick and r is not None and want(r):
                pick.append(i)
                break
    for i in order:
        if (sum(len(done[j].tokens) for j in pick) >= ref_tokens
                or len(pick) >= ref_max):
            break
        if i not in pick:
            pick.append(i)
    return [done[i] for i in pick]


def _check(cfg, cell, params, done, records, by_rid, seed, *, control,
           log):
    """Compare served tokens with the plain reference.  Returns the checks
    (each number beside its limit) and detail for the control tooling.

    With ``control`` the float8 control takes the program's place: at each
    served position the token that the float8 pass puts first is judged
    instead of the served one, so the checks are the control's; the
    program's widest gap stays in ``detail["program_max_gap"]``."""
    chk = cell["check"]
    mismatched = sum(1 for c in done if c.finish_reason != "stopped"
                     and len(c.tokens) != by_rid[c.rid].max_new_tokens)
    sample = _sample(done, records, seed, chk["ref_tokens"],
                     chk["ref_max_requests"])
    length = -(-cfg["engine"]["max_len"] // reference.Q_BLOCK) \
        * reference.Q_BLOCK
    worst, worst_ctl, n_tok = 0.0, 0.0, 0
    detail = {"sample": [], "gaps": [], "control_gaps": []}
    t0 = time.perf_counter()
    for comp in sample:
        g = reference.served_gaps(params, cfg, length, comp.prompt,
                                  comp.tokens)
        worst = max(worst, float(g.max()))
        n_tok += len(g)
        r = records.get(comp.rid)
        detail["sample"].append({
            "rid": comp.rid, "prompt": len(comp.prompt),
            "served": len(comp.tokens),
            "reuse": None if r is None else r.reuse,
            "max_gap": float(g.max())})
        detail["gaps"].append(float(g.max()))
        if control:
            gc_ = reference.served_gaps(params, cfg, length, comp.prompt,
                                        comp.tokens, lowp=True)
            worst_ctl = max(worst_ctl, float(gc_.max()))
            detail["control_gaps"].append(float(gc_.max()))
    detail["reference_s"] = time.perf_counter() - t0
    detail["compared_tokens"] = n_tok
    detail["compared_requests"] = len(sample)
    detail["program_max_gap"] = worst
    log(f"[check] {len(sample)} requests, {n_tok} served tokens against "
        f"the reference in {detail['reference_s']:.1f} s"
        + (f"; the float8 control in the program's place (program "
           f"{worst!r})" if control else ""))
    judged = worst_ctl if control else worst
    checks = {
        "max_logit_gap": {"value": judged if sample else 1e9,
                          "limit": chk["max_logit_gap"]},
        "token_count_mismatches": {"value": mismatched, "limit": 0},
    }
    return checks, detail
