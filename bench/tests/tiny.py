"""Tiny sizes for rehearsing the benchmark on the CPU (set here, not
through an option of the command).  The graph generator's and the event
source's own ``tiny`` give their sizes."""
from bench import files, harness


def tiny_cell(name: str, graph=None, events=None):
    """The cell ``name`` at its tiny size; ``graph`` replaces the
    configuration's graph entry and ``events`` sets the traffic's event
    source, to rehearse a generator or a source that no cell names yet."""
    cell, cfg, traffic = harness.load_cell(name)
    if graph is not None:
        cfg["graph"] = graph
    if events is not None:
        traffic["events"] = events
    cfg["graph"] = files.load("graphs", cfg["graph"]["generator"]).tiny(cfg["graph"])
    if "events" in traffic:
        traffic["events"] = files.load("events", traffic["events"]["source"]).tiny(
            traffic["events"])
    cfg["pool"]["n_patterns"] = 64
    traffic.update(rate_per_s=300.0, opening_backlog=min(traffic["opening_backlog"], 96))
    return cell, cfg, traffic


def run_tiny(name: str, seed: int = 2**31 + 17, trace: bool = False, keep=None,
             seconds: float = 2.0, tmp=None, graph=None, events=None):
    import time

    cell, cfg, traffic = tiny_cell(name, graph, events)
    return cell, harness.run_cell(cell, cfg, traffic, seed, seconds, trace,
                                  time.perf_counter(), trace_root=tmp, keep=keep)
