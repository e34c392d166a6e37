"""Ahead-of-time TPU compiles of the main-path Pallas kernels at real widths.

The TPU compiler ships with the installed JAX and compiles for a described
``v5e:2x2`` topology with no chip attached, so these tests catch what the
interpreter cannot: unsupported primitives in the Mosaic lowering, block
shapes that break the (8, 128) tiling rule, and tiles that overflow the
scoped VMEM.  Nothing runs; each test only asserts that the compiled
program holds the Pallas kernels (``tpu_custom_call``) under their stable
names, which trace readers match on.
"""
import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.routing import RouteFastConfig
from repro.kernels import ops
from repro.kernels.dhd_spmv import dhd_ell_step, dhd_ell_step_batch, dhd_sliced_step
from repro.kernels.route_expand import route_expand
from repro.streaming import delta_dhd


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # compiles for a described chip are written to the persistent cache but
    # cannot be read back without one: keep the cache out of these tests
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _kernels(txt):
    """Names of the Pallas kernel ops in compiled HLO text, without the
    ``.N`` suffix: ``%route_expand.1 = ... tpu_custom_call`` -> route_expand."""
    return sorted(re.findall(
        r"%([A-Za-z_]+)\.\d+ = [^\n]*custom_call_target=\"tpu_custom_call\"", txt))


def _route_shapes(R, K, D, L):
    i32, f32 = jnp.int32, jnp.float32
    return [((R, K), i32), ((R, K), f32), ((R,), i32), ((R,), i32),
            ((L + 1, D), i32), ((D, D), f32), ((D, D), f32)]


TPU_BLOCKS = [
    c["block_r"] for c in ops.route_expand_candidates("tpu") if c["impl"] == "kernel"
]


@pytest.mark.parametrize("R,K,D,L,block_r", [
    # widest request the fast path admits, at the default block
    (1024, RouteFastConfig().max_kmax, 5, 3, 128),
] + [(1024, 512, 5, 3, b) for b in TPU_BLOCKS])  # every autotuner block
def test_route_expand_compiles_for_v5e(one_chip, R, K, D, L, block_r):
    fn = functools.partial(route_expand, block_r=block_r, interpret=False)
    assert _kernels(_compile(fn, one_chip, *_route_shapes(R, K, D, L))) == ["route_expand"]


def test_route_expand_packed_compiles_for_v5e(one_chip):
    """The program ``ops.route_expand_batch`` launches on the chip, at the
    widest batch the serving cell forms: the kernel keeps its name, and the
    XLA module's name still holds ``route_expand`` (what the trace readers
    match modules on)."""
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in _route_shapes(256, 1024, 5, 3)]
    txt = ops._route_expand_packed.lower(
        *args, use_kernel=True, block_r=128, interpret=False
    ).compile().as_text()
    assert _kernels(txt) == ["route_expand"]
    module = re.match(r"HloModule (\S+?),", txt).group(1)
    assert "route_expand" in module


def test_dhd_ell_step_compiles_for_v5e(one_chip):
    n, kmax = 65536, 32
    f32 = jnp.float32
    fn = functools.partial(dhd_ell_step, interpret=False)
    txt = _compile(fn, one_chip, ((n,), f32), ((n, kmax), jnp.int32),
                   ((n, kmax), f32), ((n,), f32))
    assert _kernels(txt) == ["dhd_ell_count", "dhd_ell_flow"]


@pytest.mark.parametrize("B,n,kmax,batched_vals", [
    (32, 4096, 32, True),  # placement arena: per-candidate weights
    (5, 26_000, 64, False),  # per-DC heat caches over one topology
    (40, 1001, 1000, True),  # wide super-node rows: narrowed tiles
])
def test_dhd_ell_step_batch_compiles_for_v5e(one_chip, B, n, kmax, batched_vals):
    f32 = jnp.float32
    vals = (B, n, kmax) if batched_vals else (n, kmax)
    fn = functools.partial(dhd_ell_step_batch, interpret=False)
    txt = _compile(fn, one_chip, ((B, n), f32), ((n, kmax), jnp.int32),
                   (vals, f32), ((B, n), f32))
    assert _kernels(txt) == ["dhd_ell_count", "dhd_ell_flow"]


def test_dhd_sliced_step_compiles_for_v5e(one_chip):
    """The sliced kernels at the SF10 warm solve's size: 69,632 rows over
    18,432 slot rows."""
    n, slot_rows = 69_632, 18_432
    f32, i32 = jnp.float32, jnp.int32
    fn = functools.partial(dhd_sliced_step, interpret=False)
    txt = _compile(fn, one_chip, ((n,), f32), ((slot_rows, 256), i32),
                   ((slot_rows, 256), f32), ((slot_rows // 8,), i32), ((n,), f32))
    assert _kernels(txt) == ["dhd_sliced_count", "dhd_sliced_flow"]


def test_warm_sliced_relax_compiles_for_v5e(one_chip, monkeypatch):
    """The warm solve's global relaxation as ``StreamingHeat`` compiles it on
    a TPU: the module's name still holds ``heat_relax`` (what the trace
    readers match on) and it runs the sliced kernels."""
    monkeypatch.setattr(ops, "on_tpu", lambda: True)  # the kernels, not the interpreter
    n, kmax, slot_rows = 69_632, 112, 18_432
    f32, i32 = jnp.float32, jnp.int32
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in [
        ((n,), f32), ((n, kmax), i32), ((n, kmax), f32), ((slot_rows,), i32),
        ((slot_rows // 8,), i32), ((n,), f32), ((n,), jnp.bool_), ((n,), f32), ((3,), f32),
        ((), f32), ((), i32)]]
    txt = jax.jit(delta_dhd._heat_relax_sliced, static_argnames="use_kernel").lower(
        *args, use_kernel=True).compile().as_text()
    assert "heat_relax" in re.match(r"HloModule (\S+?),", txt).group(1)
    assert set(_kernels(txt)) == {"dhd_sliced_count", "dhd_sliced_flow"}
