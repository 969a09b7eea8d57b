"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (see benchmarks/common.py).
Set REPRO_BENCH_SCALE=big for larger datasets; REPRO_BENCH_ONLY=<substr>
to run a subset (e.g. REPRO_BENCH_ONLY=fig7).
"""
import os
import sys
import time
import traceback

from benchmarks import (
    bench_main_search,
    bench_realworld,
    bench_relations,
    bench_distributions,
    bench_index_cost,
    bench_scalability,
    bench_patch_ablation,
    bench_kp_sweep,
    bench_kernels,
    bench_batched,
    bench_planner,
    bench_scale,
    bench_serving,
    bench_streaming,
    bench_telemetry,
    bench_recovery,
)

ALL = [
    ("fig2+3_main_search", bench_main_search.main),
    ("fig4a_realworld", bench_realworld.main),
    ("fig4b_relations", bench_relations.main),
    ("fig5_distributions", bench_distributions.main),
    ("table4_index_cost", bench_index_cost.main),
    ("fig6_scalability", bench_scalability.main),
    ("fig7_patch_ablation", bench_patch_ablation.main),
    ("fig8_kp_sweep", bench_kp_sweep.main),
    ("kernels", bench_kernels.main),
    ("batched_search", bench_batched.main),
    ("query_planner", bench_planner.main),
    ("scale_segmented", bench_scale.main),
    ("distributed_serving", bench_serving.main),
    ("streaming_index", bench_streaming.main),
    ("telemetry", bench_telemetry.main),
    ("crash_recovery", bench_recovery.main),
]


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    only = os.environ.get("REPRO_BENCH_ONLY", "")
    print("name,us_per_call,derived")
    failures = 0
    for name, fn in ALL:
        if only and only not in name:
            continue
        t0 = time.perf_counter()
        print(f"# --- {name} ---", flush=True)
        try:
            fn()
        except Exception:
            failures += 1
            traceback.print_exc()
        print(f"# {name} done in {time.perf_counter()-t0:.1f}s", flush=True)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
