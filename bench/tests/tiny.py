"""Tiny sizes for rehearsing the benchmark on the CPU (set here, not
through an option of the command)."""
from bench import harness


def tiny_cell(name: str):
    cell, cfg, traffic = harness.load_cell(name)
    cfg["graph"].update(n_nodes=600, p_in=0.05, p_out=0.002)
    cfg["pool"]["n_patterns"] = 64
    traffic.update(rate_per_s=300.0, opening_backlog=min(traffic["opening_backlog"], 96))
    return cell, cfg, traffic


def run_tiny(name: str, seed: int = 2**31 + 17, trace: bool = False, keep=None,
             seconds: float = 2.0, tmp=None):
    import time

    cell, cfg, traffic = tiny_cell(name)
    return cell, harness.run_cell(cell, cfg, traffic, seed, seconds, trace,
                                  time.perf_counter(), trace_root=tmp, keep=keep)
