"""Smoke run of the serving path on a TPU at published width.

    python chip_smoke.py               # one chip: main phase, kernel phase
    python chip_smoke.py --four-chips  # the tp=4 engine against mesh=None
    python chip_smoke.py --reduced     # the same phases at the reduced CPU
                                       # config on any backend (rehearsal)

Main phase: Qwen2.5-3B at its published config (36 layers, d_model 2048,
16/2 heads, d_ff 11008, vocab 151,936) with random bf16 weights drawn from
``--seed`` serves a few requests through ``PagedServeEngine.run``, built
the way the serve CLI builds it, then serves them again with ``spec_k=2``
(the analog-numerics drafter).  Its checks:

* every request finishes with exactly its token count;
* the engine's prefill logits for one request agree with the lockstep
  ``build_prefill_step`` on the same chip within ``PREFILL_ATOL``;
* a request gives the same tokens in the batch as alone, and speculative
  decoding the same tokens as plain greedy decoding.  bf16 on the chip may
  flip near-ties, so a divergence fails only where the lockstep
  reference's top-2 logit margin at that position exceeds ``NEAR_TIE``;
  the others are counted and printed.

Kernel phase: the Pallas kernels of the serving path compile and run at
the model's widths (paged attention for decode, the q_len = spec_k + 1
verify grid and log8 pages; the fused crossbar->ACAM linear; log-domain
flash attention) and match their reference within the conformance suite's
bf16 tolerance.  On a TPU each compiled program must hold the Mosaic
kernel (``tpu_custom_call``), so a kernel that quietly interprets fails.

``--four-chips`` runs only the published-width engine on a (1, 4) serve
mesh under the bit-exact ``serve_exact`` rules against the same requests
on ``mesh=None``, with the same near-tie rule.

The script is one process and starts no other.  It exits non-zero when
JAX finds no TPU (unless ``--reduced``) or any phase fails; only then is
the last line of stdout ``{"ok": true, "device": {...}}``.  Every time it
prints is a smoke reading, not a benchmark.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# Engine prefill logits against the lockstep prefill, both bf16 on one
# chip: chunked attention over the paged cache and whole-prompt blockwise
# attention sum in different orders.  Absolute, on logits of std ~1.
PREFILL_ATOL = 0.25
# Two paths that each stay within PREFILL_ATOL of the reference can swap
# two candidates whose reference logits differ by up to twice that.
NEAR_TIE = 2 * PREFILL_ATOL
# tests/test_kernel_conformance.py's tolerance for bf16 inputs
KERNEL_TOL = dict(rtol=0.05, atol=0.05)
SPEC_K = 2


@dataclasses.dataclass(frozen=True)
class Sizes:
    max_len: int                # engine max_len (prompt + generation)
    system: int                 # shared system-prompt tokens
    prompt: tuple[int, int]     # own prompt tokens after the system prompt
    gen: tuple[int, int]        # max_new_tokens range
    requests: int = 8
    slots: int = 8
    page: int = 16
    flash_len: int = 256        # log-domain flash sequence length


PUBLISHED = Sizes(max_len=1024, system=128, prompt=(64, 512), gen=(32, 64))
REDUCED = Sizes(max_len=96, system=16, prompt=(8, 48), gen=(4, 12),
                flash_len=32)


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# requests and the lockstep reference
# ---------------------------------------------------------------------------

def make_requests(cfg, sizes: Sizes, seed: int):
    from repro.launch.engine import Request
    rng = np.random.default_rng(seed)
    system = rng.integers(0, cfg.vocab_size, sizes.system).tolist()
    reqs = []
    for i in range(sizes.requests):
        own = rng.integers(0, cfg.vocab_size,
                           int(rng.integers(*sizes.prompt, endpoint=True)))
        reqs.append(Request(
            rid=i, tokens=tuple(system + own.tolist()),
            max_new_tokens=int(rng.integers(*sizes.gen, endpoint=True)),
            arrival=int(rng.integers(0, 8))))
    return reqs


class Reference:
    """The lockstep model on the same device: ``build_prefill_step`` for
    one prompt's last-position logits, and a whole-sequence forward padded
    to ``max_len`` (causal, so padding never reaches earlier positions)
    for the top-2 margin at every position of a generated sequence."""

    def __init__(self, cfg, params, length: int):
        import jax

        from repro.launch.serve import build_prefill_step
        from repro.models import lm
        self.params, self.length = params, length
        self.prefill = jax.jit(build_prefill_step(cfg, with_cache=False))

        def margins(p, tokens):
            logits = lm.forward(p, tokens, cfg, mode="prefill")[0][0]
            top2 = jax.lax.top_k(logits.astype(np.float32), 2)[0]
            return top2[:, 0] - top2[:, 1]

        self._margins = jax.jit(margins)

    def last_logits(self, prompt) -> np.ndarray:
        import jax.numpy as jnp
        toks = jnp.asarray(np.asarray(prompt, np.int32)[None])
        return np.asarray(self.prefill(self.params, toks)[0], np.float32)

    def margins(self, seq) -> np.ndarray:
        import jax.numpy as jnp
        toks = np.zeros((1, self.length), np.int32)
        toks[0, :len(seq)] = seq
        return np.asarray(self._margins(self.params, jnp.asarray(toks)))


def compare_tokens(what: str, reqs, got: dict, want: dict, ref: Reference):
    """Count divergences of ``got`` from ``want`` per request; raise on one
    whose reference top-2 margin exceeds NEAR_TIE.  Returns the count."""
    near_ties = 0
    for r in reqs:
        a, b = got[r.rid], want[r.rid]
        j = next((i for i, (x, y) in enumerate(zip(a, b)) if x != y), None)
        if j is None:
            continue
        # the margin at the divergence depends only on the shared prefix
        margin = float(ref.margins(list(r.tokens) + b)[len(r.tokens) - 1 + j])
        log(f"  {what}: rid={r.rid} diverges at token {j} "
            f"({a[j]} vs {b[j]}), reference top-2 margin {margin:.4f}")
        if margin > NEAR_TIE:
            raise AssertionError(
                f"{what}: rid={r.rid} token {j} differs with reference "
                f"margin {margin:.4f} > near-tie tolerance {NEAR_TIE}")
        near_ties += 1
    log(f"  {what}: {len(reqs) - near_ties}/{len(reqs)} requests identical, "
        f"{near_ties} near-tie divergence(s)")
    return near_ties


def shifted(reqs, tick: int):
    """The same requests arriving relative to an engine's current tick."""
    return [dataclasses.replace(r, arrival=r.arrival + tick) for r in reqs]


def check_counts(comps, reqs) -> dict:
    want = {r.rid: r.max_new_tokens for r in reqs}
    got = {c.rid: len(c.tokens) for c in comps}
    if got != want:
        raise AssertionError(f"token counts {got} != requested {want}")
    return {c.rid: list(c.tokens) for c in comps}


def run_alone_with_logits(eng, req):
    """Serve ``req`` alone on a fresh engine; return its tokens and the
    prefill logits its admission wave hands to the first-token sampler."""
    seen = []
    sample = eng._sample_fn

    def recording_sample(last, *rest):
        seen.append(np.asarray(last, np.float32))
        return sample(last, *rest)

    eng._sample_fn = recording_sample
    try:
        tokens = check_counts(eng.run(shifted([req], eng.tick)), [req])
    finally:
        eng._sample_fn = sample
    rows = np.flatnonzero(np.any(seen[0] != 0, axis=1))
    assert len(rows) == 1, f"one admitted slot expected, got rows {rows}"
    return tokens, seen[0][rows[0]]


def check_logits(what: str, got: np.ndarray, want: np.ndarray) -> None:
    err = float(np.max(np.abs(got - want)))
    log(f"  prefill logits, {what}: max |diff| {err:.4f} (tolerance "
        f"{PREFILL_ATOL}), argmax {int(got.argmax())} vs "
        f"{int(want.argmax())}")
    if not err <= PREFILL_ATOL:
        raise AssertionError(f"prefill logits, {what}: differ by {err:.4f}")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def serve_args(sizes: Sizes, published: bool, seed: int, *extra):
    from repro.launch import serve
    argv = ["--paged", "--slots", str(sizes.slots), "--page-size",
            str(sizes.page), "--seed", str(seed), *extra]
    if published:
        argv.append("--published-width")
    return serve.parse_args(argv)


def main_phase(sizes: Sizes, published: bool, seed: int) -> None:
    from repro.launch import serve

    args = serve_args(sizes, published, seed)
    cfg, params, nldpe = serve.load_model(args)
    reqs = make_requests(cfg, sizes, seed)
    ref = Reference(cfg, params, sizes.max_len)
    log(f"[main] {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.n_heads}/{cfg.n_kv_heads} heads, d_ff {cfg.d_ff}, vocab "
        f"{cfg.vocab_size}, weights {params['embed']['table'].dtype}; "
        f"{len(reqs)} requests, prompts "
        f"{min(len(r.tokens) for r in reqs)}-"
        f"{max(len(r.tokens) for r in reqs)} tokens")

    eng = serve.build_paged_engine(cfg, params, nldpe, args,
                                   max_len=sizes.max_len)
    alone, logits = run_alone_with_logits(eng, reqs[0])
    check_logits("engine vs lockstep build_prefill_step", logits,
                 ref.last_logits(reqs[0].tokens))

    t0 = time.perf_counter()
    batch = check_counts(eng.run(shifted(reqs, eng.tick)), reqs)
    wall = time.perf_counter() - t0
    n_tok = sum(r.max_new_tokens for r in reqs)
    log(f"  batch: {n_tok} tokens in {wall:.3f} s, {n_tok / wall:.1f} tok/s "
        f"(smoke reading, compiles included; not a benchmark); prefix hits "
        f"{eng.stats['hits']}/{eng.stats['lookups']}")
    for r in reqs[1:]:
        alone.update(check_counts(eng.run(shifted([r], eng.tick)), [r]))
    # an engine's jits and metrics close over it: collect the cycle so its
    # pool (and later the drafter's weights) leave the device
    del eng
    gc.collect()

    spec_args = serve_args(sizes, published, seed, "--spec", str(SPEC_K))
    eng = serve.build_paged_engine(cfg, params, nldpe, spec_args,
                                   max_len=sizes.max_len)
    t0 = time.perf_counter()
    spec = check_counts(eng.run(shifted(reqs, eng.tick)), reqs)
    wall = time.perf_counter() - t0
    sp = eng.spec_stats
    log(f"  spec_k={SPEC_K}: {n_tok} tokens in {wall:.3f} s "
        f"({n_tok / wall:.1f} tok/s, smoke reading), acceptance "
        f"{sp['accepted']}/{sp['drafted']} = {sp['acceptance_rate']:.4f}")
    del eng
    gc.collect()

    compare_tokens("batch vs alone", reqs, batch, alone, ref)
    compare_tokens("spec vs greedy", reqs, spec, batch, ref)


def four_chip_phase(sizes: Sizes, published: bool, seed: int) -> None:
    from repro.launch import serve
    from repro.launch.mesh import serve_mesh

    mesh = serve_mesh(1, 4)                # raises below four devices
    args = serve_args(sizes, published, seed, "--mesh", "1,4")
    cfg, params, nldpe = serve.load_model(args)
    reqs = make_requests(cfg, sizes, seed)
    out, logits = {}, {}
    for label, m in (("mesh 1x4", mesh), ("mesh=None", None)):
        eng = serve.build_paged_engine(cfg, params, nldpe, args,
                                       max_len=sizes.max_len, mesh=m)
        _, logits[label] = run_alone_with_logits(eng, reqs[0])
        t0 = time.perf_counter()
        out[label] = check_counts(eng.run(shifted(reqs, eng.tick)), reqs)
        wall = time.perf_counter() - t0
        log(f"[four-chips] {label} [{eng.rules.name}]: "
            f"{sum(r.max_new_tokens for r in reqs)} tokens in {wall:.3f} s "
            f"(smoke reading, compiles included)")
        del eng
        gc.collect()
    check_logits("mesh 1x4 vs mesh=None", logits["mesh 1x4"],
                 logits["mesh=None"])
    ref = Reference(cfg, params, sizes.max_len)
    n = compare_tokens("mesh 1x4 vs mesh=None", reqs, out["mesh 1x4"],
                       out["mesh=None"], ref)
    log(f"  serve_exact bit-exactness on this chip: "
        f"{'held' if n == 0 else f'broken by {n} near-tie divergence(s)'}")


def kernel_phase(sizes: Sizes, published: bool, on_tpu: bool,
                 seed: int) -> None:
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.core.acam import get_table
    from repro.core.logdomain import DEFAULT_CFG
    from repro.kernels import (fused_linear_acam, logdomain_flash_attention,
                               paged_attention)

    cfg = get_config("qwen2_5_3b", reduced=not published)
    hq, hkv, d = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    b, ps = sizes.slots, sizes.page
    nb = sizes.max_len // ps
    pages = b * nb
    rng = np.random.default_rng(seed)

    def normal(shape, dtype=jnp.bfloat16, scale=1.0):
        return jnp.asarray(rng.normal(size=shape) * scale, dtype)

    def paged_case(q_len, quant):
        q = normal((b, hq, d) if q_len == 1 else (b, hq, q_len, d))
        bt = jnp.asarray(rng.integers(0, pages, (b, nb)), jnp.int32)
        lengths = jnp.asarray(rng.integers(1, nb * ps - q_len + 2, (b,)),
                              jnp.int32)
        if not quant:
            kp, vp = normal((pages, hkv, ps, d)), normal((pages, hkv, ps, d))
            return ((q, kp, vp, bt, lengths), paged_attention,
                    lambda *a: paged_attention(*a, use_ref=True), 0.0)
        # log8 pages: int8 sign-magnitude codes + per-position scales
        codes = [jnp.asarray(rng.integers(-127, 128, (pages, hkv, ps, d)),
                             jnp.int8) for _ in range(2)]
        scales = [jnp.asarray(rng.uniform(0.5, 1.5, (pages, hkv, ps)),
                              jnp.float32) for _ in range(2)]

        def run(q, kp, vp, bt, lengths, ks, vs, use_ref=False):
            return paged_attention(q, kp, vp, bt, lengths, k_scale=ks,
                                   v_scale=vs, kv_quant="log8",
                                   use_ref=use_ref)
        return ((q, *codes, bt, lengths, *scales), run,
                lambda *a: run(*a, use_ref=True), 0.0)

    acam_step = float(get_table("silu", 8, "gray", None).out_spec.step)
    x = normal((16, cfg.d_model))
    w = normal((cfg.d_model, cfg.d_ff), scale=cfg.d_model ** -0.5)
    lq = sizes.flash_len
    qkv = (normal((1, hq, lq, d)), normal((1, hkv, lq, d)),
           normal((1, hkv, lq, d)))
    cases = {
        "paged_attention decode": paged_case(1, False),
        f"paged_attention verify q_len={SPEC_K + 1}": paged_case(SPEC_K + 1,
                                                                 False),
        "paged_attention log8 decode": paged_case(1, True),
        f"fused_linear_acam {cfg.d_model}x{cfg.d_ff}": (
            (x, w), lambda x, w: fused_linear_acam(x, w, "silu"),
            lambda x, w: fused_linear_acam(x, w, "silu", use_ref=True),
            acam_step),
        f"logdomain_flash_attention {hq}/{hkv} heads D={d} L={lq}": (
            qkv, logdomain_flash_attention,
            lambda *a: logdomain_flash_attention(
                *(t.astype(jnp.float32) for t in a), use_ref=True),
            1.0 / ((1 << DEFAULT_CFG.bits) - 1)),
    }
    for name, (operands, kernel, oracle, grid_step) in cases.items():
        t0 = time.perf_counter()
        compiled = jax.jit(kernel).lower(*operands).compile()
        if on_tpu and "tpu_custom_call" not in compiled.as_text():
            raise AssertionError(f"{name}: no Mosaic kernel in the program")
        out = np.asarray(compiled(*operands), np.float32)
        with jax.default_matmul_precision("highest"):
            want = np.asarray(jax.jit(oracle)(*operands), np.float32)
        err = float(np.max(np.abs(out - want)))
        np.testing.assert_allclose(out, want, rtol=KERNEL_TOL["rtol"],
                                   atol=KERNEL_TOL["atol"] + grid_step,
                                   err_msg=name)
        log(f"[kernel] {name}: ok, max |diff| {err:.2e}, compile+run "
            f"{time.perf_counter() - t0:.2f} s")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--four-chips", action="store_true",
                   help="run only the tp=4 engine against mesh=None")
    p.add_argument("--reduced", action="store_true",
                   help="rehearse at the reduced config on any backend; "
                        "prints no result line")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)

    import jax

    from repro.runtime.compile_cache import enable_compile_cache

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    if not on_tpu and not args.reduced:
        print(f"chip_smoke: no TPU (JAX platform {dev.platform!r})",
              file=sys.stderr)
        return 2
    log(f"[device] {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
        f"compile cache {enable_compile_cache()}")
    published = not args.reduced
    sizes = PUBLISHED if published else REDUCED
    t0 = time.perf_counter()
    if args.four_chips:
        four_chip_phase(sizes, published, args.seed)
    else:
        main_phase(sizes, published, args.seed)
        kernel_phase(sizes, published, on_tpu, args.seed)
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    log(f"[done] {time.perf_counter() - t0:.1f} s wall (smoke reading); "
        f"peak device memory "
        f"{'not reported' if peak is None else f'{peak / 2**30:.3f} GiB'}")
    if args.reduced:
        log("rehearsal passed (no result line at the reduced config)")
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
