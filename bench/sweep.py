"""Find a cell's knee: one store, windows at a ladder of offered read rates.

    python3 bench/sweep.py --workload snb.read --seed 11 --seconds 8 --rates 2000,4000,6000

``--backlog`` replaces the traffic's opening backlog, to see how the store
behaves without it.

Builds and warms the cell's store once, then for each rate runs the cell's
measured window with a fresh Poisson stream at that rate and prints one JSON
line: offered and completed reads per second, p50 and p99 latency, mean
batch size, and the reads still unanswered when the window closed.  The
knee is the highest rate whose completions keep up with the offered load
with no backlog at the close.  Runs on a TPU only.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

T_PROCESS = time.perf_counter()
REPO = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(REPO / "src"))

import numpy as np  # noqa: E402

from bench import gen, harness  # noqa: E402
from bench.run import device_check  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--backlog", type=int, default=None)
    args = ap.parse_args(argv)
    cell, cfg, traffic = harness.load_cell(args.workload)
    device = device_check(cell["chips"])
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    harness.cache_everything()
    st = harness.Setup(cfg, traffic, args.seed, args.seconds)
    st.build_store()
    harness.warm_up(st)
    harness.settle_heap()
    backlog = traffic["opening_backlog"] if args.backlog is None else args.backlog
    print(json.dumps({"device": device, "setup_s": time.perf_counter() - T_PROCESS}), flush=True)
    for k, rate in enumerate(float(r) for r in args.rates.split(",")):
        st.requests = gen.request_stream(st.pool, rate, args.seconds, traffic["home_share"],
                                         st.reg.n_dcs, args.seed + 100 + k,
                                         backlog)
        win = harness.run_window(st)
        e2e = harness.end_to_end(st, win)
        sizes = [c[2] for c in win.serve_calls]
        late = int(((np.isnan(win.done)) | (win.done > args.seconds)).sum())
        print(json.dumps({
            "rate": rate, "scheduled": len(st.requests.t), **e2e,
            "mean_batch": float(np.mean(sizes)) if sizes else 0.0,
            "batches_ge_64": float(np.mean(np.asarray(sizes) >= 64)) if sizes else 0.0,
            "unanswered_at_close": late, "compiles": win.compiles, "backlog": backlog,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
