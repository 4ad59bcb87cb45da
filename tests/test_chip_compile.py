"""Compile the mapspace kernel for a described TPU v5e (no chip needed).

The TPU compiler ships with libtpu, so the kernel of the main path is
lowered and compiled here for a chip that is described but not attached.
This catches what interpret mode cannot: primitives Mosaic does not lower,
layouts it refuses, and blocks that overflow VMEM.  Nothing runs.

The topology is described inside a module fixture (never at import), so
every pytest-xdist worker collects the same tests and only the worker that
runs this file loads libtpu.
"""
from __future__ import annotations

import functools
import os
import re
import time

import jax
import pytest

from repro.core import MapperConfig, analyze, make_fpga_arch, make_spatial_arch
from repro.core.mapspace_array import build_packed_mapspace
from repro.core.task_analyst import NETWORKS
from repro.kernels.mapspace_eval import ops
from repro.kernels.mapspace_eval.kernel import (_multi_call,
                                                mapspace_eval_multi_fwd)

BLOCK = 256


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:            # noqa: BLE001 — any failure = no TPU
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A described-chip compile cannot be read back without the chip, so
    keep it out of any persistent compilation cache."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _arch(kind: str):
    if kind == "spatial":
        return make_spatial_arch(num_pes=256, rf_words=256,
                                 gbuf_words=64 * 1024, zero_skip=True)
    return make_fpga_arch(name="fpga", num_pes=256, cache_kb=128.0)


@functools.lru_cache(maxsize=None)
def _packed(kind: str):
    """Kernel inputs of a real no-bypass mapspace: ResNet-18's first
    ImageNet conv on the given architecture."""
    wl = analyze(NETWORKS["resnet18-im"](batch_size=1)).intra[0]
    cfg = MapperConfig(max_mappings=64, seed=0, enable_bypass=False)
    pm = build_packed_mapspace(wl, _arch(kind), cfg)
    return pm.static, pm.factors, pm.rank


def _compile(fn, arrays, n_rows: int, sharding):
    shapes = [jax.ShapeDtypeStruct(a.shape[:-1] + (n_rows,), a.dtype,
                                   sharding=sharding) for a in arrays]
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*shapes).compile()
    return compiled, time.perf_counter() - t0


def _assert_one_kernel_op(hlo: str) -> None:
    """Exactly one op is named `%mapspace_eval*`, and no other op name
    holds `mapspace_eval`, which the device-trace metrics match."""
    names = re.findall(r"%([\w.-]*mapspace_eval[\w.-]*) = ", hlo)
    assert len(names) == 1, names
    assert re.fullmatch(r"mapspace_eval\w*(\.\d+)?", names[0]), names


@pytest.mark.parametrize("kind", ["spatial", "fpga"])
@pytest.mark.parametrize("n_rows", [BLOCK, 4 * BLOCK])
def test_multi_kernel_compiles_for_v5e(one_chip, kind, n_rows):
    st, factors, rank = _packed(kind)
    arrays, static, _, block = ops.pack_for_kernel_multi(
        [(st, factors, rank)], BLOCK)
    fn = functools.partial(mapspace_eval_multi_fwd, static=static,
                           block=block, interpret=False)
    compiled, secs = _compile(fn, arrays, n_rows, one_chip)
    assert "tpu_custom_call" in compiled.as_text()
    _assert_one_kernel_op(compiled.as_text())
    assert secs < 60, f"{kind} B={n_rows}: compile took {secs:.1f}s"


@pytest.mark.parametrize("kind", ["spatial", "fpga"])
def test_cached_multi_entry_compiles_for_v5e(one_chip, kind):
    """The module-level jitted entry the search calls compiles, prologue
    and kernel, and only its kernel op carries the `mapspace_eval` name
    the device-trace metrics (`kernel_ms`, `mapspace_eval_roofline`)
    match: no op of the prologue is counted as kernel time."""
    st, factors, rank = _packed(kind)
    arrays, static, _, block = ops.pack_for_kernel_multi(
        [(st, factors, rank)], BLOCK)
    shapes = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
              for a in arrays]
    hlo = _multi_call.lower(*shapes, **static, block=block,
                            interpret=False).compile().as_text()
    assert "tpu_custom_call" in hlo
    _assert_one_kernel_op(hlo)
