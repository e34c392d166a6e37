"""Readings for the correctness limits: the program's and the control's.

    python3 bench/control.py --workload snb.read --seeds 1,2,3 --seconds 10

For each seed, in one process: builds, warms and runs the cell's window as
``bench/run.py`` does, then prints one JSON line with the numbers the check
compares for the program (the lower readings) and for the control: the
plain reference put in the program's place one precision below the
configuration's float32, that is, Eq. 1 byte sums in bfloat16 (the upper
readings).  Runs on a TPU only.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

REPO = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(REPO / "src"))

import ml_dtypes  # noqa: E402

from bench import harness  # noqa: E402
from bench.run import device_check  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    cell, cfg, traffic = harness.load_cell(args.workload)
    device = device_check(cell["chips"])
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    harness.cache_everything()
    for seed in (int(s) for s in args.seeds.split(",")):
        keep = {}
        t0 = time.perf_counter()
        res = harness.run_cell(cell, cfg, traffic, seed, args.seconds, False, t0, keep=keep)
        ctrl = harness.check(keep["st"], keep["win"], route_dtype=ml_dtypes.bfloat16)
        print(json.dumps({
            "seed": seed, "device": device, "correct": res["correct"],
            "program": {k: v["value"] for k, v in res["checks"].items()},
            "control": {k: v["value"] for k, v in ctrl.items()},
            "control_correct": harness.passed(ctrl), "e2e": res["e2e"],
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
