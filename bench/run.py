"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload snb.read --seed 7 --seconds 30 --trace 0

The cell (configuration, traffic mix, chips) is looked up by name in
``BENCHMARK.json``; everything else is found by name, so a deployment, a
traffic mix or a metric joins the benchmark as new files and edits none:

* the configuration's file (``configs[].file``), whose ``graph.generator``
  names the graph generator ``bench/graphs/<generator>.py``;
* the traffic file ``bench/traffic/<mix>.json``, whose optional
  ``events.source`` names an event source ``bench/events/<source>.py``
  that acts on the store inside the window and brings its own check and
  limits (``bench/harness.py`` says what it provides);
* the per-layer readers ``bench/metrics/<metric>.py``.

``bench/files.py`` loads the three kinds of file.  The run refuses to
measure anywhere but on a TPU with the chips the cell asks for.  It builds
the store from the seed, warms every shape the window uses, measures for
``--seconds``, checks what the timed path produced against the plain
reference, and prints one JSON line: end-to-end metrics with ``--trace 0``,
per-layer metrics (from a profiler trace of part of the window, the store's
spans and counters) with ``--trace 1``.  The numbers the check compares are
printed last on standard error and last in the JSON line.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

REPO = pathlib.Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))
sys.path.insert(0, str(REPO / "src"))

from bench import files  # noqa: E402


def device_check(chips: int) -> dict:
    """The devices JAX sees; exits non-zero unless they are ``chips`` TPUs."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX's default device is {devs[0].platform}")
    if len(devs) < chips:
        raise SystemExit(f"{chips} TPU chips requested, {len(devs)} found")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}


def load_reader(name: str):
    return files.load("metrics", name).read


def result_line(cell: dict, spec: dict, res: dict, device: dict, trace: bool) -> dict:
    """The contract's last line from a run's result."""
    metrics = {}
    units = {m["name"]: m["unit"] for m in spec["end_to_end"] + spec["per_layer"]}
    if not trace:
        for m in spec["end_to_end"]:
            if "workloads" in m and cell["name"] not in m["workloads"]:
                continue
            v = res["setup_s"] if m["name"] == "setup_s" else res["e2e"].get(m["name"])
            if v is not None and math.isfinite(v):
                metrics[m["name"]] = {"value": v, "unit": units[m["name"]]}
    else:
        ctx = dict(res["ctx"], device_kind=device["kind"])
        for m in spec["per_layer"]:
            if "workloads" in m and cell["name"] not in m["workloads"]:
                continue
            v = load_reader(m["name"])(ctx)
            if v is not None and math.isfinite(v):
                metrics[m["name"]] = {"value": v, "unit": units[m["name"]]}
    dev = dict(device, memory_peak_bytes=res["memory_peak_bytes"])
    line = {"correct": res["correct"], "attempted": res["attempted"], "failed": res["failed"],
            "metrics": metrics, "device": dev}
    if trace:
        red = res["ctx"]["trace"]
        if red:
            from bench import tracefile

            dev["busy_s"] = red["busy_s"]
            dev["window_s"] = red["window_s"]
            line["breakdown"] = {"device_ops": tracefile.top(red["op_time"]),
                                 "idle_gaps": tracefile.top(red["idle_by_span"])}
    line["checks"] = res["checks"]
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness
    from repro.compile_cache import enable_compile_cache

    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    cell, cfg, traffic = harness.load_cell(args.workload)
    device = device_check(cell["chips"])
    harness.log(f"# device: {device}; compile cache: {enable_compile_cache()}")
    harness.cache_everything()
    res = harness.run_cell(cell, cfg, traffic, args.seed, args.seconds, bool(args.trace),
                           T_PROCESS)
    line = result_line(cell, spec, res, device, bool(args.trace))
    for k, c in res["checks"].items():
        harness.log(f"check {k} = {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
