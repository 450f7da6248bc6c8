"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 benchmarks/chip/readings.py --workload <cell> \
        --seeds 1,2,...,12 --control-seeds 1,2,3 --seconds 20 \
        [--out chiprun_out/readings.jsonl]

Runs the cell once per seed in this one process (a short window at the
cell's own load), and prints one JSON line per seed: the program's
numbers (the lower readings) and, for the control seeds, the float8
control's numbers on the same served requests (the upper readings).
The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(HERE.parents[1] / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    import numpy as np
    from chipbench import driver, yardstick
    seeds = [int(s) for s in args.seeds.split(",") if s]
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    sink = open(args.out, "a") if args.out else None
    try:
        for seed in seeds:
            out = driver.run(args.workload, seed, args.seconds, False,
                             lambda m: print(m, file=sys.stderr, flush=True),
                             t_start=time.perf_counter(), keep_served=True)
            ref = (out["plugin"], out["arch"], out["params"])
            parts = [yardstick.program_readings(*ref, s)
                     for s in out["served"]]
            row = {"workload": args.workload, "seed": seed,
                   "program": yardstick.summarize(parts),
                   "judged_steps": out["result"]["checks"]["judged_steps"][
                       "value"],
                   "logit_err_steps": np.percentile(
                       [v for p in parts for v in p["logit_err"]],
                       [50, 90, 99, 100]).tolist(),
                   "metrics": {k: v["value"] for k, v in
                               out["result"]["metrics"].items()}}
            if seed in controls:
                row["control"] = yardstick.summarize([
                    yardstick.control_readings(*ref, s)
                    for s in out["served"]])
            line = json.dumps(row)
            print(line, flush=True)
            if sink:
                sink.write(line + "\n")
                sink.flush()
            del out
    finally:
        if sink:
            sink.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
