"""Serving entry point: ``python -m repro.launch.serve --arch <id> [--coded n k]``.

Serves batched synthetic requests through the Engine.  The default is the
reduced smoke config (CPU-runnable); ``--published`` serves the
architecture at its published widths (an accelerator's worth of memory).
``--coded N K`` routes every FFN GEMM through the (n, k)-MDS pipeline.
Weights and prompts come from ``--seed``.
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from ..configs import ARCHS, get_config, smoke_config
from ..serving import Engine, Request
from .compile_cache import enable_compile_cache

__all__ = ["main"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=list(ARCHS), default="gemma-2b")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--coded", nargs=2, type=int, default=None,
                    metavar=("N", "K"))
    ap.add_argument("--published", action="store_true",
                    help="serve the published widths, not the smoke config")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cache_dir = enable_compile_cache()
    import jax

    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}"
          f"  compile cache: {cache_dir}")
    cfg = get_config(args.arch) if args.published else smoke_config(args.arch)
    rng = np.random.default_rng(args.seed)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab, args.prompt_len,
                                        dtype=np.int32),
                    max_new=args.max_new)
            for i in range(args.requests)]
    eng = Engine(cfg, coded=tuple(args.coded) if args.coded else None,
                 seed=args.seed)
    t0 = time.time()
    completions = eng.generate(reqs)
    dt = time.time() - t0
    total_tokens = sum(len(c.tokens) for c in completions)
    print(f"{cfg.name}: served {len(completions)} requests, "
          f"{total_tokens} tokens in {dt:.2f}s "
          f"({total_tokens / dt:.1f} tok/s, compile included)"
          + (f"  [coded (n={args.coded[0]}, k={args.coded[1]})]"
             if args.coded else ""))
    for c in completions[:3]:
        print(f"  req {c.rid}: {c.tokens[:8]}...")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
