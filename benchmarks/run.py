# One function per paper table/figure. Prints ``name,us_per_call,derived`` CSV.
from __future__ import annotations

import os
import sys
import time

# the mesh-dispatch bench needs multiple XLA devices; the split must be
# requested before anything initializes the jax backend (benchmarks.run is
# the entry point, so this is the one place early enough for every bench)
if "--xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8"
                               ).strip()

from .common import Csv


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from . import (
        adaptive_replan,
        elastic_churn,
        explain_forensics,
        ext_hetero,
        fig4_overhead,
        fig5_scenario1,
        fig6_scenario23,
        fig7_layer_breakdown,
        fig9_approx_gap,
        fig10_param_impact,
        kernels_micro,
        mesh_dispatch,
        pipeline_depth,
        roofline,
        serving_load,
        sim_speedup,
        table1_k_approx,
    )

    only = sys.argv[1] if len(sys.argv) > 1 else None
    csv = Csv()
    benches = [
        ("fig7", fig7_layer_breakdown.run),
        ("fig4", fig4_overhead.run),
        ("table1", table1_k_approx.run),
        ("fig5", fig5_scenario1.run),
        ("fig6", fig6_scenario23.run),
        ("fig9", fig9_approx_gap.run),
        ("fig10", fig10_param_impact.run),
        ("ext_hetero", ext_hetero.run),
        ("adaptive", adaptive_replan.run),
        ("pipeline", pipeline_depth.run),
        ("serving", serving_load.run),
        ("prefill", serving_load.run_prefill),
        ("elastic", elastic_churn.run),
        ("explain", explain_forensics.run),
        ("mesh", mesh_dispatch.run),
        ("kernels", kernels_micro.run),
        ("roofline", roofline.run),
        ("sim_speedup", sim_speedup.run),
    ]
    for name, fn in benches:
        if only and only != name:
            continue
        t0 = time.time()
        fn(csv)
        print(f"# [{name}] done in {time.time() - t0:.1f}s", file=sys.stderr)


if __name__ == "__main__":
    main()
