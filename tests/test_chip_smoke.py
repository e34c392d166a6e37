"""Rehearsal of ``chip_smoke.py`` on the CPU at a tiny size.

The script's phases run in-process with the dispatch steered onto the
Pallas kernels (``ops.on_tpu`` patched) and every kernel forced into
interpret mode, so the script's paths, arguments and identity checks are
exercised without a chip.  The four-chip phase runs in a subprocess over
four virtual CPU devices.  The script's own CLI still refuses a non-TPU
backend.
"""
import functools
import os
import pathlib
import subprocess
import sys
import textwrap

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402

TINY = dict(n_vertices=600, n_patterns=40, hops=3, branch=2)


def _interpreted(fn, *args, **kw):
    kw["interpret"] = True
    return fn(*args, **kw)


@pytest.fixture
def kernels_interpreted(monkeypatch):
    from repro.core import routing
    from repro.kernels import autotune, ops

    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    for name in ("dhd_ell_step", "dhd_ell_step_batch", "dhd_sliced_step", "_route_expand_kernel"):
        monkeypatch.setattr(
            ops, name, functools.partial(_interpreted, getattr(ops, name))
        )
    monkeypatch.setattr(autotune, "_AUTOTUNER", autotune.Autotuner())
    routing.reset_routing_caches()
    yield
    routing.reset_routing_caches()


def test_single_chip_phases_rehearsed(kernels_interpreted, capsys):
    chip_smoke.single_chip(sizes=TINY)
    out = capsys.readouterr().out
    for phase in ("# build:", "# serve:", "# churn:", "# dispatch:", "# dhd kernel"):
        assert phase in out


def test_dispatch_check_rejects_reference_path():
    from repro.obs import MetricsRegistry

    reg = MetricsRegistry(enabled=True)
    for op in chip_smoke.CHECKED_OPS:
        reg.counter("kernels.dispatch", op=op, path="kernel").inc()
    assert chip_smoke.dispatch_counts(reg)["dhd_sliced"]["kernel"] == 1
    reg.counter("kernels.dispatch", op="dhd_sliced", path="ref").inc()
    with pytest.raises(AssertionError, match="dhd_sliced"):
        chip_smoke.dispatch_counts(reg)


def test_cli_refuses_without_tpu():
    proc = subprocess.run(
        [sys.executable, str(ROOT / "chip_smoke.py")],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


def test_sharded_phase_on_four_virtual_devices():
    code = textwrap.dedent(f"""
        import os, sys
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        os.environ["JAX_PLATFORMS"] = "cpu"
        sys.path.insert(0, {str(ROOT)!r})
        import chip_smoke
        out = chip_smoke.sharded_phase(
            n_vertices=1500, n_patterns=60, n_dcs=8, n_shards=4
        )
        assert len(out["devices"]) == 4 and out["cross_device_links"] > 0, out
        print("OK", out)
    """)
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=600, env={**os.environ, "PYTHONPATH": str(ROOT / "src")},
    )
    assert "OK" in proc.stdout, proc.stderr[-3000:]
