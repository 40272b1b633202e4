#!/usr/bin/env python3
"""Readings that set a cell's correctness limit, on the chip; not part of a
benchmark run.

    python3 bench/control.py --workload <name> --seeds 1,2,3 --seconds 5 \
        --modes program,reference:high,reference:bf16_3x,program:high

Each mode runs the cell's own window, inputs and check with something in
the system's place (``harness.run_cell``'s ``control``): ``program`` is the
system under test as configured, and the largest ``logit_rel_err`` it reads
over a dozen seeds or more is a limit's lower reading.  The others compute
one precision below the configuration's float32 at "highest": the plain
reference at XLA's ``high`` (three bfloat16 passes) or in its explicit
emulation ``bf16_3x``, or the program itself under ``high``; the smallest
they read is the upper reading.  One process serves every seed, so programs
compile once.  Prints one JSON line per seed and mode.
"""
import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--modes", default="program,reference:high")
    args = ap.parse_args(argv)
    cell = harness.find_cell(ROOT, args.workload)
    try:
        devices = harness.require_chips(int(cell.workload["chips"]))
    except harness.NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    modes = args.modes.split(",")
    for seed in (int(s) for s in args.seeds.split(",")):
        for mode in modes:
            out = harness.run_cell(
                cell, seed, args.seconds, False, t0=time.perf_counter(),
                devices=devices, control=None if mode == "program" else mode,
                log=lambda *a: print(*a, file=sys.stderr, flush=True))
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "mode": mode,
                              "attempted": out["attempted"],
                              "checks": out["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
