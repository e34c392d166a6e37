"""Benchmark harness entry: one function per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run [--full]``
Prints ``name,us_per_call,derived`` CSV rows per the repo contract.
"""
from __future__ import annotations

import argparse
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="paper-scale sizes")
    ap.add_argument("--only", default=None, help="comma list of figure keys")
    args, _ = ap.parse_known_args()
    fast = not args.full

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()

    from . import (
        bench_ablation,
        bench_cost,
        bench_exec_time,
        bench_forecast,
        bench_heterogeneity,
        bench_kernels,
        bench_migration,
        bench_obs_overhead,
        bench_offline,
        bench_online,
        bench_optimality,
        bench_placement,
        bench_precache,
        bench_scheduler,
        bench_serving,
        bench_sharded,
        bench_streaming,
    )

    suites = {
        "fig7_online": bench_online.run,
        "fig8_cost": bench_cost.run,
        "fig9_optimality": bench_optimality.run,
        "fig10_exec_time": bench_exec_time.run,
        "fig11_heterogeneity": bench_heterogeneity.run,
        "fig12_precache": bench_precache.run,
        "fig13_15_offline": bench_offline.run,
        "fig16_ablation": bench_ablation.run,
        "kernels": bench_kernels.run,
        "streaming": bench_streaming.run,
        "serving": bench_serving.run,
        "sharded": bench_sharded.run,
        "placement": bench_placement.run,
        "migration": bench_migration.run,
        "scheduler": bench_scheduler.run,
        "forecast": bench_forecast.run,
        "obs": bench_obs_overhead.run,
    }
    only = set(args.only.split(",")) if args.only else None
    print("name,us_per_call,derived")
    t_all = time.perf_counter()
    for name, fn in suites.items():
        if only and name not in only:
            continue
        t0 = time.perf_counter()
        print(f"# --- {name} ---", flush=True)
        try:
            fn(fast=fast)
        except Exception as e:  # noqa: BLE001
            print(f"{name},0.0,FAILED:{type(e).__name__}:{e}")
        print(f"# {name} done in {time.perf_counter()-t0:.1f}s", flush=True)
    print(f"# all benchmarks done in {time.perf_counter()-t_all:.1f}s")


if __name__ == "__main__":
    main()
