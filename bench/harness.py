"""Run one cell of the benchmark once and print its result line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is found by name from ``BENCHMARK.json``:
the configuration file it names, ``bench/traffic/<mix>.json`` (read by the
one generator, ``bench/traffic.py``), the plain reference
``bench/references/<reference>.py`` and the system adapter
``bench/systems/<system>.py`` that the configuration names, and one reader
``bench/metrics/<metric>.py`` per metric.  Adding a configuration, a mix or
a metric adds files and entries; no file here changes.

One run: make the weights and the inputs on the device from the seed, warm
up every program the cell's traffic uses (that and start-up are
``setup_s``), measure a closed loop of requests for ``--seconds``, read the
device's peak memory, free the system under test, then check every answer
of the window against the plain float32 reference.  ``--trace 1`` records a
profiler trace of the window and reports the per-layer metrics instead of
the end-to-end ones.  Without a TPU the run ends before it measures.
"""
from __future__ import annotations

import argparse
import contextlib
import functools
import gc
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
BENCH = "bench"
CATEGORIES = {"conv": "conv_general_dilated", "coding": "skinny_gemm"}


class NoChip(RuntimeError):
    """JAX finds no accelerator, or fewer chips than the cell asks for."""


# ---------------------------------------------------------------------------
# finding a cell's parts by name
# ---------------------------------------------------------------------------

def load_json(path: Path):
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """Import one file of the benchmark by path (its name may hold dots)."""
    name = "bench_part_" + "".join(c if c.isalnum() else "_"
                                   for c in str(path.relative_to(path.anchor)))
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(root: Path, workload: str) -> SimpleNamespace:
    """The workload entry of ``BENCHMARK.json`` and every file it names."""
    spec = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; known: "
                       f"{sorted(cells)}")
    wl = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    cfg = load_json(root / configs[wl["config"]]["file"])
    bench = root / BENCH
    mix = load_json(bench / "traffic" / f"{wl['traffic']}.json")

    def applies(m):
        return workload in m.get("workloads", [workload])

    return SimpleNamespace(
        spec=spec, workload=wl, cfg=cfg, mix=mix, root=root,
        reference=load_module(bench / "references" / f"{cfg['reference']}.py"),
        system=load_module(bench / "systems" / f"{cfg['system']}.py"),
        end_to_end=[m for m in spec["end_to_end"] if applies(m)],
        per_layer=[m for m in spec["per_layer"] if applies(m)])


def metric_reader(root: Path, name: str):
    return load_module(root / BENCH / "metrics" / f"{name}.py").read


def peaks_for(root: Path, kind: str) -> dict:
    table = load_json(root / BENCH / "peaks.json")
    if kind not in table:
        raise KeyError(f"device kind {kind!r} has no peaks in "
                       f"{BENCH}/peaks.json; known: {sorted(table)}")
    return table[kind]


# ---------------------------------------------------------------------------
# device, compile cache, seeds
# ---------------------------------------------------------------------------

def require_chips(chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX reports platform {devices[0].platform!r}")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX sees "
                     f"{len(devices)}")
    return devices


class CompileMeter:
    """Backend compiles and persistent-cache hits, from JAX's monitoring
    events (every thread's compiles count)."""

    def __init__(self):
        import jax

        self.secs, self.compiles, self.hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += secs
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self):
        return self.secs, self.compiles, self.hits


@functools.lru_cache(maxsize=1)
def compile_meter() -> CompileMeter:
    return CompileMeter()


def key_for(seed: int, stream: int):
    """A PRNG key from every bit of a non-negative seed (beyond 32 bits)."""
    import jax

    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(key, stream)


def jitted(cell, name: str, fn):
    """``jax.jit(fn)``, made once per cell and kept on it."""
    import jax

    cache = cell.__dict__.setdefault("jitted", {})
    if name not in cache:
        cache[name] = jax.jit(fn)
    return cache[name]


def make_params(cell, seed: int):
    """The configuration's weights, in one jitted call on the device."""
    import jax

    init = jitted(cell, "init", functools.partial(cell.reference.init,
                                                  cell.cfg))
    return jax.block_until_ready(init(key_for(seed, 1)))


def make_inputs(cell, seed: int):
    """The mix's distinct input batches, in one jitted call on the device."""
    import jax
    import jax.numpy as jnp

    cfg, mix = cell.cfg, cell.mix
    shape = (int(mix["batch"]), cfg["in_channels"], cfg["image"], cfg["image"])
    n = int(mix["distinct_inputs"])

    def make(key):
        return tuple(jax.random.normal(k, shape, jnp.float32)
                     for k in jax.random.split(key, n))
    make = jitted(cell, "inputs", make)
    return list(jax.block_until_ready(make(key_for(seed, 2))))


# ---------------------------------------------------------------------------
# the control: the plain reference in the program's place, one precision down
# ---------------------------------------------------------------------------

class ReferenceSystem:
    """The reference served as if it were the system under test."""

    n = 4

    def __init__(self, cell, params, mode: str):
        self.reports: list = []
        self._fwd = jitted(cell, f"reference.{mode}", functools.partial(
            cell.reference.forward, cell.cfg, mode=mode))
        self._params = params

    def set_fault(self, straggler, slowdown):
        pass

    def annotate(self, on):
        pass

    def forward(self, x):
        return self._fwd(self._params, x)

    def warm(self, x):
        self.forward(x).block_until_ready()
        return {}

    def close(self):
        pass


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def _work(seen: dict) -> dict:
    """Operations and bytes of one forward's recorded convs and GEMMs."""
    from bench import counts

    coding = seen["encode"] + seen["decode"]
    return {
        "conv_flops": sum(counts.conv_flops(*c) for c in seen["conv"]),
        "conv_bytes": sum(counts.conv_bytes(*c) for c in seen["conv"]),
        "coding_flops": sum(counts.gemm_flops(*g) for g in coding),
        "coding_bytes": sum(counts.gemm_bytes(*g) for g in coding),
        "convs": len(seen["conv"]), "encodes": len(seen["encode"]),
        "decodes": len(seen["decode"])}


def warm_up(cell, sut, xs, log) -> dict:
    """Compile every program the window will run, then count one forward's
    work.  Each fault kind the mix uses gets a pooled forward of its own."""
    from bench import traffic

    info = sut.warm(xs[0])
    slow = traffic.slowdown(cell.mix)
    seen = None
    if hasattr(sut, "count_work"):
        sut.set_fault(None, 1.0)
        with sut.count_work() as seen:
            sut.forward(xs[0]).block_until_ready()
    if cell.mix["fault"]["kind"] == "straggler":
        sut.set_fault(1 % sut.n, slow)
    sut.forward(xs[-1]).block_until_ready()
    del sut.reports[:]
    log(f"warm-up: {info}")
    return _work(seen) if seen is not None else {}


def measure(cell, sut, xs, seed: int, seconds: float, trace: bool):
    """The closed loop: requests back to back until ``seconds`` have passed.
    Returns (window start, last answer's time, requests, outputs)."""
    import jax

    from bench import traffic

    ann = jax.profiler.TraceAnnotation if trace else (
        lambda _name: contextlib.nullcontext())
    sut.annotate(trace)
    slow = traffic.slowdown(cell.mix)
    stream = traffic.requests(cell.mix, seed, sut.n)
    reqs, outs = [], []
    clock = time.perf_counter
    t_start = clock()
    deadline = t_start + seconds
    with ann("bench.window"):
        while True:
            req = next(stream)
            with ann("bench.request"):
                t0 = clock()
                sut.set_fault(req.straggler, slow)
                r0 = len(sut.reports)
                with ann("bench.forward"):
                    tf = clock()
                    y = sut.forward(xs[req.input])
                    y.block_until_ready()
                    t1 = clock()
            runs = sut.reports[r0:]
            reqs.append(SimpleNamespace(
                input=req.input, straggler=req.straggler,
                latency_s=t1 - t0, forward_s=t1 - tf,
                run_wall_s=sum(wall for _n, _k, wall in runs),
                runs=len(runs)))
            outs.append(y)
            if t1 >= deadline:
                return t_start, t1, reqs, outs


def check(cell, params, xs, reqs, outs, limits) -> tuple[dict, int]:
    """Every answer of the window against the plain float32 reference:
    the largest |logit - reference| over the reference's largest |logit|,
    per request.  Returns (checks, wrong answers)."""
    fwd = jitted(cell, "reference.highest", functools.partial(
        cell.reference.forward, cell.cfg, mode="highest"))
    refs = {i: np.asarray(fwd(params, xs[i]))
            for i in sorted({r.input for r in reqs})}
    worst, wrong = 0.0, 0
    for r, y in zip(reqs, outs):
        y, ref = np.asarray(y, np.float64), refs[r.input]
        err = (float(np.max(np.abs(y - ref)) / np.max(np.abs(ref)))
               if y.shape == ref.shape and np.all(np.isfinite(y))
               else float("inf"))
        worst = max(worst, err)
        wrong += not err <= limits["logit_rel_err"]
    return ({"logit_rel_err": {"value": worst,
                               "limit": limits["logit_rel_err"]},
             "wrong_answers": {"value": wrong, "limit": 0}}, wrong)


def run_cell(cell, seed: int, seconds: float, trace: bool, *, t0: float,
             devices, control: str | None = None, trace_out=None,
             log=print) -> dict:
    """One run of one cell; returns the result line's object.

    ``control`` (set only by ``bench/control.py`` and the tests) puts
    something else in the system's place: ``"reference:<mode>"`` serves the
    plain reference in that arithmetic (``bench/references/common.py``),
    ``"program:<precision>"`` the program under another default matmul
    precision than the configuration's."""
    import jax

    meter = compile_meter()
    cfg, mix = cell.cfg, cell.mix
    dev = devices[0]
    peaks = peaks_for(cell.root, dev.device_kind) if dev.platform == "tpu" \
        else None
    kind, _, mode = (control or "program:").partition(":")
    prev = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision",
                      mode if kind == "program" and mode
                      else cfg["matmul_precision"])
    try:
        params = make_params(cell, seed)
        xs = make_inputs(cell, seed)
        sut = (ReferenceSystem(cell, params, mode) if kind == "reference"
               else cell.system.build(cfg, params))
        try:
            work = warm_up(cell, sut, xs, log)
            setup_s = time.perf_counter() - t0
            c_before, gc_before = meter.snapshot(), gc.get_stats()[2]
            tdir = None
            if trace:
                tdir = tempfile.mkdtemp(prefix="bench-trace-")
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(tdir, profiler_options=opts)
            try:
                t_start, t_end, reqs, outs = measure(cell, sut, xs, seed,
                                                     seconds, trace)
            finally:
                if trace:
                    jax.profiler.stop_trace()
            c_after, gc_after = meter.snapshot(), gc.get_stats()[2]
            runs = list(sut.reports)
        finally:
            sut.close()
        stats = dev.memory_stats() or {}
        memory_peak = stats.get("peak_bytes_in_use")
        del sut
        reduced = None
        if trace:
            from bench import tracing

            path = tracing.find_trace(tdir)
            if trace_out and path is not None:
                Path(trace_out).mkdir(parents=True, exist_ok=True)
                shutil.copy(path, Path(trace_out) / path.name)
            reduced = (tracing.reduce(tracing.load(path), CATEGORIES)
                       if path is not None else None)
            shutil.rmtree(tdir, ignore_errors=True)
        t_check = time.perf_counter()
        checks, wrong = check(cell, params, xs, reqs, outs, cfg["limits"])
        check_s = time.perf_counter() - t_check
    finally:
        jax.config.update("jax_default_matmul_precision", prev)

    from bench import counts

    batch = int(mix["batch"])
    layers = cell.reference.conv_layers(cfg)
    rec = SimpleNamespace(
        cfg=cfg, mix=mix, setup_s=setup_s, window_s=t_end - t_start,
        requests=reqs, images=batch * len(reqs), runs=runs, work=work,
        model_flops_per_image=counts.model_flops(
            layers, 1, cell.reference.head_features(cfg), cfg["classes"]),
        trace=reduced, peaks=peaks)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = metric_reader(cell.root, m["name"])(rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    lat = [r.latency_s for r in reqs]
    log(f"window: {len(reqs)} requests, {rec.images} images in "
        f"{rec.window_s:.6f} s; {len(runs)} executor runs; compiles in "
        f"window {c_after[1] - c_before[1]} ({c_after[0] - c_before[0]:.3f} "
        f"s); latency p50/p90/p95 ms "
        + "/".join(f"{np.percentile(lat, q) * 1e3:.3f}" for q in (50, 90, 95))
        + f"; slowest ms {sorted((round(x * 1e3, 1) for x in lat))[-5:]}; "
        f"full GC passes in window "
        f"{gc_after['collections'] - gc_before['collections']}; reference "
        f"check {check_s:.3f} s; setup_s {setup_s:.3f}")
    if work:
        log(f"work per forward: {work}")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    out = {"correct": wrong == 0 and len(reqs) > 0, "attempted": len(reqs),
           "failed": wrong, "metrics": metrics, "device": device,
           "window_compiles": c_after[1] - c_before[1]}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
    out["checks"] = checks
    return out


def print_result(out: dict) -> None:
    """Each compared number beside its limit as the last lines of standard
    error; the result object as the last line of standard output."""
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-out", default=None,
                    help="also keep the raw profiler trace in this directory")
    return ap.parse_args(argv)


def main(argv=None, *, t0: float | None = None, root: Path = ROOT) -> int:
    t0 = time.perf_counter() if t0 is None else t0
    args = parse_args(argv)
    if args.seed < 0:
        print("--seed must be non-negative", file=sys.stderr)
        return 2
    cell = find_cell(root, args.workload)
    try:
        devices = require_chips(int(cell.workload["chips"]))
    except NoChip as e:
        print(f"bench: {e}; nothing measured", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache

    log = functools.partial(print, file=sys.stderr, flush=True)
    log(f"compile cache: {enable_compile_cache()}")
    log(f"cell {args.workload}: seed {args.seed}, {args.seconds} s, trace "
        f"{args.trace}; device {devices[0].device_kind} x{len(devices)}")
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), t0=t0,
                   devices=devices, trace_out=args.trace_out, log=log)
    print_result(out)
    return 0
