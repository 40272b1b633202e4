#!/usr/bin/env python3
"""Chip smoke test: the coded served path end to end on a TPU.

    python chip_smoke.py            # one chip: gemma-2b serving + VGG16@224
    python chip_smoke.py --chips 4  # four chips: mesh backend vs threads
    python chip_smoke.py --tiny     # CPU rehearsal at toy sizes

One chip (the default) runs two phases in this one process:

* ``serve`` — gemma-2b at its published widths (18 layers, d_model 2048,
  MQA, d_ff 16384, vocab 256000, bf16 weights from ``--seed``) served
  through ``Engine`` + ``ServingScheduler`` with mds(4, 3) coded FFN GEMMs
  on a real-clock ``CodedExecutor`` (all four pieces on the one chip),
  under three fault patterns: none, one straggling worker, one dead
  worker.  The reference is the same engine with coding off.  Packed
  prefill logits must agree within ``SERVE_TOL`` of the reference's
  largest logit; where the reference's top-2 margin exceeds twice the
  measured error the first token must agree; overall token agreement is
  reported.
* ``vgg16`` — the paper's CNN at 224x224 (1000 classes, He-init weights
  from ``--seed``), two images through the compiled ``forward_plan`` on a
  ``CodedExecutor`` under the same three patterns, against the uncoded
  forward.  Both arms run with f32 matmul precision so the comparison
  isolates the coding; logits must agree within ``VGG_TOL`` of the
  reference's largest logit and give the same classes.

``--chips 4`` runs only the mesh phase: gemma-2b through
``Engine(executor=MeshExecutor)``, one mds(4, 3) piece per chip, healthy
and with one dead slice, compared with the threaded backend on the same
inputs (within ``SERVE_TOL``); it checks that the decoded output spans
four devices and that every chip held a piece's weights.

Each phase prints its wall time, compile time (with persistent-cache hits
and misses), errors against its reference, executor run/dispatch counts
and the device's ``peak_bytes_in_use``.  The last line is one JSON object
naming the device as JAX reports it.  Without a TPU the script exits
non-zero before doing anything, except under ``--tiny``, which forces the
CPU backend and toy sizes and never reports a TPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from pathlib import Path

SERVE_TOL = 5e-2   # max |coded - ref| prefill logit, over max |ref logit|
VGG_TOL = 1e-3     # same bound for the VGG16 class logits (f32 arms)
PATTERNS = ("none", "straggler", "dead")
STRAGGLER, DEAD = 1, 2          # worker ids the patterns slow / kill
SLOWDOWN = 10.0                 # straggler's service-time multiplier
N, K = 4, 3                     # mds(n, k)


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or missing result."""


def require(ok, message) -> None:
    """A check that survives ``python -O`` (unlike ``assert``)."""
    if not ok:
        raise SmokeFailure(message)


def _args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip mesh phase")
    ap.add_argument("--tiny", action="store_true",
                    help="CPU rehearsal at toy sizes (never reports a TPU)")
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args(argv)


class CompileMeter:
    """Sums backend compile time and persistent-cache hits/misses from
    JAX's monitoring events (every thread's compiles count)."""

    def __init__(self):
        import jax

        self.secs, self.compiles, self.hits, self.misses = 0.0, 0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += secs
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return self.secs, self.compiles, self.hits, self.misses


def _peak_bytes(dev):
    stats = dev.memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def run_phase(name, fn, meter, dev):
    """Run one phase; print its wall and compile time and peak memory."""
    c0, t0 = meter.snapshot(), time.perf_counter()
    out = fn()
    wall = time.perf_counter() - t0
    c1 = meter.snapshot()
    print(f"[{name}] done: wall_s={wall:.3f} compile_s={c1[0] - c0[0]:.3f} "
          f"compiles={c1[1] - c0[1]} cache_hits={c1[2] - c0[2]} "
          f"cache_misses={c1[3] - c0[3]} "
          f"peak_bytes_in_use={_peak_bytes(dev)}", flush=True)
    return out


def _fault_plan(pattern):
    from repro.dist import FaultPlan

    if pattern == "straggler":
        return FaultPlan(straggler={STRAGGLER: SLOWDOWN})
    if pattern == "dead":
        return FaultPlan(dead=frozenset({DEAD}))
    return FaultPlan()


def _check_executor(tag, pattern, reports, runs, dispatches):
    """The coded path really dispatched, and the pattern shaped it."""
    print(f"[{tag}] executor: runs={runs} dispatches={dispatches}")
    require(runs > 0 and dispatches > 0, (
        f"{tag}: the executor never ran (runs={runs}, "
        f"dispatches={dispatches}) — the coded GEMMs were bypassed"))
    if pattern == "dead":
        bad = [r.subset for r in reports if DEAD in r.subset]
        require(not bad, f"{tag}: dead worker's piece was decoded: {bad[:3]}")
        # its failure is booked only when it reaches the master before the
        # k-th arrival (a real-clock race); that it never arrives is not
        require(all(a.worker != DEAD for r in reports for a in r.arrivals),
                f"{tag}: the dead worker delivered a piece")
    if pattern == "straggler":
        skipped = sum(STRAGGLER not in r.subset for r in reports)
        print(f"[{tag}] runs decoded without the straggler: "
              f"{skipped}/{len(reports)}")
        require(skipped > len(reports) // 2, (
            f"{tag}: the straggler's piece was awaited in "
            f"{len(reports) - skipped}/{len(reports)} runs"))


# ---------------------------------------------------------------------------
# serving (gemma-2b)
# ---------------------------------------------------------------------------

def _serving_setup(args):
    import jax
    import numpy as np

    from repro.configs import get_config, smoke_config
    from repro.models import init_params

    if args.tiny:
        import jax.numpy as jnp

        cfg = dataclasses.replace(smoke_config("gemma-2b"), dtype=jnp.bfloat16)
        lens, max_new = (5, 9, 12, 16), 4
    else:
        cfg = get_config("gemma-2b")
        lens, max_new = (32, 57, 96, 128), 8
    rng = np.random.default_rng(args.seed)
    prompts = [rng.integers(0, cfg.vocab, n, dtype=np.int32) for n in lens]
    params = jax.jit(init_params, static_argnums=0)(
        cfg, jax.random.PRNGKey(args.seed))
    return cfg, params, prompts, max_new


def _requests(prompts, max_new):
    from repro.serving import Request

    return [Request(rid=i, prompt=p, max_new=max_new)
            for i, p in enumerate(prompts)]


def _packed(prompts):
    import jax.numpy as jnp
    import numpy as np

    lens = np.asarray([len(p) for p in prompts], np.int32)
    toks = np.zeros((len(prompts), int(lens.max())), np.int32)
    for j, p in enumerate(prompts):
        toks[j, :len(p)] = p
    return jnp.asarray(toks), jnp.asarray(lens)


def _serve(engine, prompts, max_new):
    """Packed prefill logits (the scheduler's admission call) and the
    scheduler's generated tokens for every request.  With coding off this
    is the reference."""
    import numpy as np

    from repro.models import prefill
    from repro.serving import ServingScheduler

    toks, lens = _packed(prompts)
    max_seq = int(lens.max()) + max_new
    with engine.executor_ctx():
        logits, _ = prefill(engine.cfg, engine.params, toks,
                            max_seq=max_seq, lens=lens)
    logits = np.asarray(logits[:, 0, :engine.cfg.vocab], np.float32)
    res = ServingScheduler(engine, max_seq=max_seq,
                           max_batch=len(prompts)).serve(
        _requests(prompts, max_new))
    tokens = {c.rid: np.asarray(c.tokens) for c in res.completions}
    return logits, tokens


def _unstacked(cfg, params):
    """Per-layer params for the executor path, built once and shared by
    every engine (the stacked copy is dropped by the caller)."""
    import jax

    layers = [jax.tree_util.tree_map(lambda a, i=i: a[i], params["layers"])
              for i in range(cfg.n_layers)]
    return {**params, "layers": layers}


def _compare(tag, logits, tokens, ref_logits, ref_tokens, tol):
    """Logit bound, margin-aware first-token check, token agreement."""
    import numpy as np

    err = float(np.max(np.abs(logits - ref_logits)))
    scale = float(np.max(np.abs(ref_logits)))
    top2 = np.sort(ref_logits, axis=-1)[:, -2:]
    margin = top2[:, 1] - top2[:, 0]
    sure = margin > 2 * err
    first_ok = np.argmax(logits, -1) == np.argmax(ref_logits, -1)
    agree = sum(int(np.sum(tokens[r] == ref_tokens[r])) for r in ref_tokens)
    total = sum(len(t) for t in ref_tokens.values())
    print(f"[{tag}] prefill logits: max_abs_err={err:.6g} "
          f"ref_max_abs={scale:.6g} rel={err / scale:.3e} (tol {tol:g}); "
          f"first tokens equal {int(first_ok.sum())}/{len(first_ok)} "
          f"(margin-certain lanes {int(sure.sum())}); "
          f"token agreement {agree}/{total}")
    require(np.all(np.isfinite(logits)), f"{tag}: non-finite logits")
    require(err <= tol * scale, (
        f"{tag}: prefill logits off by {err:.6g} > {tol:g} x {scale:.6g}"))
    require(np.all(first_ok[sure]), (
        f"{tag}: a first token differs where the reference margin exceeds "
        "twice the logit error"))


def _require_pallas_compiled(cfg, n_tokens):
    """The coded piece GEMM lowers to a compiled Mosaic kernel
    (``tpu_custom_call``), not the Pallas interpreter."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.mds_encode import skinny_gemm_pallas
    from repro.core.splitting import plan_token_split

    t_p = plan_token_split(n_tokens, K).w_out_p
    text = jax.jit(skinny_gemm_pallas).lower(
        jax.ShapeDtypeStruct((t_p, cfg.d_model), jnp.float32),
        jax.ShapeDtypeStruct((cfg.d_model, cfg.d_ff), jnp.float32)).as_text()
    require("tpu_custom_call" in text, "piece GEMM is not a compiled kernel")
    print(f"[serve] piece GEMM ({t_p}, {cfg.d_model}) @ ({cfg.d_model}, "
          f"{cfg.d_ff}) lowers to tpu_custom_call")


def serving_phase(args, meter, dev):
    import jax

    from repro.dist import CodedExecutor, RealClock
    from repro.serving import Engine

    cfg, params, prompts, max_new = _serving_setup(args)
    print(f"[serve] {cfg.name}: layers={cfg.n_layers} d_model={cfg.d_model} "
          f"d_ff={cfg.d_ff} vocab={cfg.vocab} kv_heads={cfg.n_kv_heads} "
          f"dtype={jax.numpy.dtype(cfg.dtype).name}; prompts "
          f"{[len(p) for p in prompts]} tokens, {max_new} new each; "
          f"mds({N},{K})")
    ref_logits, ref_tokens = run_phase(
        "serve/reference",
        lambda: _serve(Engine(cfg, params, max_batch=len(prompts)), prompts,
                       max_new), meter, dev)
    params = _unstacked(cfg, params)
    if not args.tiny:
        _require_pallas_compiled(cfg, len(prompts) * max(map(len, prompts)))
    ex = CodedExecutor(N, clock=RealClock())
    try:
        eng = Engine(cfg, params, coded=(N, K), scheme="mds", executor=ex,
                     max_batch=len(prompts))
        for pattern in PATTERNS:
            tag = f"serve/{pattern}"
            ex.pool.fault_plan = _fault_plan(pattern)
            reports = []
            ex.on_report = reports.append
            r0, d0 = ex.run_count, ex.pool.dispatch_count
            logits, tokens = run_phase(
                tag, lambda: _serve(eng, prompts, max_new), meter, dev)
            ex.on_report = None
            _check_executor(tag, pattern, reports, ex.run_count - r0,
                            ex.pool.dispatch_count - d0)
            _compare(tag, logits, tokens, ref_logits, ref_tokens, SERVE_TOL)
    finally:
        ex.close()


# ---------------------------------------------------------------------------
# VGG16 (the paper's CNN)
# ---------------------------------------------------------------------------

def vgg_phase(args, meter, dev):
    import jax
    import numpy as np

    from repro.dist import CodedExecutor, RealClock
    from repro.models.cnn import init_vgg16, vgg16_forward

    image, classes, batch = (32, 10, 1) if args.tiny else (224, 1000, 2)
    kp, kx = jax.random.split(jax.random.PRNGKey(args.seed + 1))
    params = init_vgg16(kp, n_classes=classes, image=image)
    x = jax.random.normal(kx, (batch, 3, image, image), jax.numpy.float32)
    print(f"[vgg16] image={image} classes={classes} batch={batch} "
          f"mds({N},{K}) through forward_plan; f32 matmul precision")
    prev = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", "highest")
    ex = CodedExecutor(N, clock=RealClock())
    try:
        ref = np.asarray(run_phase(
            "vgg16/reference",
            lambda: jax.block_until_ready(jax.jit(vgg16_forward)(params, x)),
            meter, dev))
        scale = float(np.max(np.abs(ref)))
        for pattern in PATTERNS:
            tag = f"vgg16/{pattern}"
            ex.pool.fault_plan = _fault_plan(pattern)
            reports = []
            ex.on_report = reports.append
            r0, d0 = ex.run_count, ex.pool.dispatch_count
            out = np.asarray(run_phase(
                tag, lambda: jax.block_until_ready(vgg16_forward(
                    params, x, scheme="mds", n=N, executor=ex)), meter, dev))
            ex.on_report = None
            _check_executor(tag, pattern, reports, ex.run_count - r0,
                            ex.pool.dispatch_count - d0)
            err = float(np.max(np.abs(out - ref)))
            same = np.argmax(out, -1) == np.argmax(ref, -1)
            print(f"[{tag}] logits: max_abs_err={err:.6g} ref_max_abs="
                  f"{scale:.6g} rel={err / scale:.3e} (tol {VGG_TOL:g}); "
                  f"classes equal {int(same.sum())}/{len(same)}")
            require(out.shape == ref.shape and np.all(np.isfinite(out)),
                    f"{tag}: logits of shape {out.shape}, or not finite")
            require(err <= VGG_TOL * scale, f"{tag}: logits off by {err:.6g}")
            require(np.all(same), f"{tag}: predicted classes differ")
    finally:
        ex.close()
        jax.config.update("jax_default_matmul_precision", prev)


# ---------------------------------------------------------------------------
# four chips: the mesh backend against the threaded one
# ---------------------------------------------------------------------------

def mesh_phase(args, meter, dev):
    import jax
    import jax.numpy as jnp

    from repro.core.coded_linear import coded_matmul
    from repro.dist import CodedExecutor, MeshExecutor, RealClock
    from repro.core.schemes import get_scheme
    from repro.serving import Engine

    devices = jax.devices()
    cfg, params, prompts, max_new = _serving_setup(args)
    params = _unstacked(cfg, params)
    print(f"[mesh] {cfg.name} at d_model={cfg.d_model} d_ff={cfg.d_ff}; "
          f"mds({N},{K}), one piece per device of {len(devices)}")
    ex_t = CodedExecutor(N, clock=RealClock())
    try:
        threads = Engine(cfg, params, coded=(N, K), scheme="mds",
                         executor=ex_t, max_batch=len(prompts))
        ref_logits, ref_tokens = run_phase(
            "mesh/threads", lambda: _serve(threads, prompts, max_new),
            meter, dev)
    finally:
        ex_t.close()
    for pattern, dead in (("none", ()), ("dead", (DEAD,))):
        tag = f"mesh/{pattern}"
        ex = MeshExecutor(dead=dead)
        eng = Engine(cfg, params, coded=(N, K), scheme="mds", executor=ex,
                     max_batch=len(prompts))
        logits, tokens = run_phase(
            tag, lambda: _serve(eng, prompts, max_new), meter, dev)
        print(f"[{tag}] mesh programs compiled: {ex.compile_count}")
        _check_executor(tag, pattern, [ex.last_report], ex.run_count,
                        ex.pool.dispatch_count)
        _compare(tag, logits, tokens, ref_logits, ref_tokens, SERVE_TOL)
    # placement: one coded FFN GEMM at a prefill's width, on the mesh
    w = params["layers"][0]["ffn"]["w_in"].astype(jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(args.seed),
                          (len(prompts) * 32, cfg.d_model), jnp.float32)
    out = coded_matmul(x, w, get_scheme("mds").make(N, K),
                       executor=MeshExecutor())
    spread = {d.id for d in out.sharding.device_set}
    peaks = [_peak_bytes(d) for d in devices]
    print(f"[mesh] decoded output spans devices {sorted(spread)}; "
          f"per-device peak_bytes_in_use={peaks}")
    require(len(spread) == len(devices) == N, (
        f"pieces did not land on {N} devices: {sorted(spread)}"))
    if peaks[0] is not None:
        require(all(p >= w.nbytes for p in peaks), (
            "a device never held a piece's weight"))


def main(argv=None) -> int:
    args = _args(argv)
    if args.tiny:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if args.chips > 1:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={args.chips}"
            ).strip()
    import jax

    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    if not args.tiny and dev.platform != "tpu":
        print(f"no TPU found (JAX reports {dev.platform}); run with --tiny "
              "for the CPU rehearsal", file=sys.stderr)
        return 2
    if len(devices) != args.chips:
        print(f"--chips {args.chips} needs {args.chips} devices, JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.launch.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    meter = CompileMeter()
    t0 = time.perf_counter()
    if args.chips == 4:
        run_phase("mesh", lambda: mesh_phase(args, meter, dev), meter, dev)
    else:
        run_phase("serve", lambda: serving_phase(args, meter, dev), meter, dev)
        run_phase("vgg16", lambda: vgg_phase(args, meter, dev), meter, dev)
    print(f"total wall_s={time.perf_counter() - t0:.3f}")
    result = {"ok": True,
              "device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(devices)}}
    if args.tiny:
        result["tiny"] = True
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
