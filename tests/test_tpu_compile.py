"""Compile rehearsals for one TPU v5e chip, with no chip attached.

The device path is compiled at its real sizes for a described v5e
(``topologies.get_topology_desc``), so whatever the chip's compiler refuses
fails here instead of on the chip. Nothing runs: these tests say nothing
about results or times. The two Pallas engine kernels are refused today;
they are strict xfails that name the refusal, so a fix flips them.

The topology is described inside a module-scoped fixture only: describing
it loads the TPU library, which one process at a time may hold.
"""
import functools

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core import fused_window as FW
from repro.core import grid_eval as G
from repro.core import simulate as S
from repro.core.problem import INFER_BATCH_SIZES
from repro.launch.mesh import HBM_BYTES

ENGINE_LANES, ENGINE_EVENTS = 8192, 1024    # simulate._LANE_CHUNK x 1024
FLEET_K, FLEET_T = 512, 512                 # K=512 fleet, pow2 event bucket
GRID_N = 441 * len(INFER_BATCH_SIZES)       # every power mode x every bs
SOLVER_ROWS = 2048                          # one pow2 chunk of problems
SORT_REQS = 256                             # requests per lane to sort


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a described compile cannot be read back from the persistent cache
    # without a chip, so keep it out of the cache
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args, x64=True):
    with jax.enable_x64(x64):
        return fn.lower(*args).compile()


def _fits_one_chip(compiled):
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 0 < used <= HBM_BYTES, used
    return mem


def test_engine_scan_compiles_f64(one_chip):
    kernel = S._jax_engine().jitted
    f64 = functools.partial(_sds, one_chip, dtype=jnp.float64)
    lanes = (ENGINE_LANES,)
    with S._quiet_donation():
        compiled = _compile(kernel, f64((ENGINE_LANES, ENGINE_EVENTS)),
                            f64((ENGINE_LANES, ENGINE_EVENTS)),
                            f64(lanes), f64(lanes), f64(lanes))
    _fits_one_chip(compiled)


@pytest.mark.parametrize("trims", [False, True], ids=["no-admission",
                                                      "admission"])
def test_fused_fleet_window_compiles_k512(one_chip, trims):
    kernel = FW._fused_kernel(trims, max(INFER_BATCH_SIZES)).jitted
    sds = functools.partial(_sds, one_chip)
    grid = [sds((GRID_N,), jnp.float64)] * 3 + [sds((GRID_N,), jnp.int32)]
    k64 = sds((FLEET_K,), jnp.float64)
    k32 = sds((FLEET_K,), jnp.int32)
    args = (*grid, k64, k64, k64, k64, k64, k64, k64,     # ts .. hi
            sds((FLEET_K,), jnp.bool_), k32,              # live, prev_mode
            sds((FLEET_K, FLEET_T), jnp.float64), k32,    # times, n_times
            k32, k64, sds((), jnp.float64), k64)          # n_carry .. budget
    _fits_one_chip(_compile(kernel, *args))


def test_concurrent_solver_compiles(one_chip):
    kernel = G._jax_kernels()["concurrent"].jitted
    col = functools.partial(_sds, one_chip, (GRID_N,))
    row = functools.partial(_sds, one_chip, (SOLVER_ROWS,), jnp.float64)
    args = (col(jnp.float64), col(jnp.float64), col(jnp.float64),
            col(jnp.float64), col(jnp.bool_), row(), row(), row())
    _fits_one_chip(_compile(kernel, *args))


def _refused_as(compile_fn, refusal: str):
    """Compile; a refusal that does not name ``refusal`` is a failure of
    its own, not the expected one."""
    try:
        compile_fn()
    except Exception as e:
        if refusal not in str(e):
            pytest.fail(f"refused for another reason: {e}")
        raise


MAXPLUS_REFUSAL = "'tpu.weird' op Input type must be F32"


@pytest.mark.xfail(strict=True, raises=Exception,
                   reason=f"v5e refuses the f64 kernel: {MAXPLUS_REFUSAL}")
def test_maxplus_scan_kernel_compiles(one_chip):
    from repro.kernels.fulcrum.maxplus_scan import maxplus_scan
    f64 = functools.partial(_sds, one_chip, dtype=jnp.float64)
    fn = jax.jit(functools.partial(maxplus_scan, interpret=False))
    lanes = (ENGINE_LANES,)
    _refused_as(lambda: _compile(fn, f64((ENGINE_LANES, ENGINE_EVENTS)),
                                 f64((ENGINE_LANES, ENGINE_EVENTS)),
                                 f64(lanes), f64(lanes), f64(lanes)),
                MAXPLUS_REFUSAL)


# the report builder sorts float64 under x64; float32 with x64 off is
# refused too, for its layout
@pytest.mark.parametrize("dtype,refusal", [
    (jnp.float64, "64-bit types are not supported"),
    (jnp.float32, "unsupported shape cast")], ids=["f64", "f32"])
@pytest.mark.xfail(strict=True, raises=Exception,
                   reason="v5e refuses the bitonic lane-sort kernel")
def test_lane_sort_kernel_compiles(one_chip, dtype, refusal):
    from repro.kernels.fulcrum.lane_sort import lane_sort
    sds = functools.partial(_sds, one_chip, dtype=dtype)
    fn = jax.jit(functools.partial(lane_sort, interpret=False))
    _refused_as(lambda: _compile(fn, sds((ENGINE_LANES, SORT_REQS)),
                                 sds((ENGINE_LANES,)),
                                 x64=dtype == jnp.float64),
                refusal)
