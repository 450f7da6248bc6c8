"""Benchmark harness — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full] [--only fig3,table1]

Prints ``name,us_per_call,derived`` CSV and saves per-figure artifacts
under benchmarks/artifacts/.  ``--full`` uses the paper-scale token
counts (slow on CPU); default is the fast profile.
"""
from __future__ import annotations

import argparse
import sys
import time

import jax

from repro.launch.compile_cache import enable_compile_cache

from . import (decode_wallclock, fig3_recall, fig6_periods_recall,
               fig7_prefill, fig8_ablation, fig9_periods_speed,
               fleet_degradation, kv_occupancy, roofline,
               serving_throughput, table1_predictors, table2_speed,
               transport_precision)

MODULES = {
    "fig3": fig3_recall,
    "fig6": fig6_periods_recall,
    "fig7": fig7_prefill,
    "fig8": fig8_ablation,
    "fig9": fig9_periods_speed,
    "table1": table1_predictors,
    "table2": table2_speed,
    "roofline": roofline,
    "serving": serving_throughput,
    "fleet": fleet_degradation,
    "transport": transport_precision,
    "kv_occupancy": kv_occupancy,
    "decode_wallclock": decode_wallclock,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of: " + ",".join(MODULES))
    args = ap.parse_args()
    enable_compile_cache()
    names = (args.only.split(",") if args.only else list(MODULES))
    print("name,us_per_call,derived")
    failures = []
    for name in names:
        mod = MODULES[name]
        t0 = time.time()
        try:
            rows = mod.run(fast=not args.full)
        except Exception as e:  # report and continue
            failures.append((name, repr(e)))
            print(f"{name}/ERROR,0,{e!r}", flush=True)
            continue
        for r in rows:
            print(f"{r['name']},{r['us_per_call']},{r['derived']}",
                  flush=True)
        print(f"# {name} done in {time.time()-t0:.1f}s", file=sys.stderr)
        # engine benchmarks JIT thousands of small executables; release
        # them or LLVM eventually fails to allocate JIT code pages
        jax.clear_caches()
    if failures:
        raise SystemExit(f"benchmark failures: {failures}")


if __name__ == "__main__":
    main()
