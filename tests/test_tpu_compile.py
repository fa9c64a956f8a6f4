"""Compile the device programs for a described TPU v5e, no chip attached.

The TPU compiler is installed with JAX, so what it would refuse on the chip
it refuses here: the engine's batched chunk scan at a real batch, its
``shard_map`` version over a 2x2 host, and the Pallas kernels at the
``benchmarks/run.py --kernels`` shapes.  Nothing runs, so these tests say
nothing about results or times; ``chip_smoke.py`` runs the programs.

The topology is described inside a module fixture (never at import): only
one process may load the TPU library, and every xdist worker imports this
file.  The persistent compilation cache is off around these compiles (an
entry written without a chip cannot be read back).
"""
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import engine as eng
from repro.core import tracegen
from repro.kernels import ops

BATCH = 64
COLLECTIVES = ("all-reduce", "all-gather", "all-to-all", "collective-permute",
               "reduce-scatter")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)


def _chunk_args(sharding):
    """(carry, xs, params) shapes of one batched chunk dispatch."""
    cfg = eng.VectorEngineConfig()
    body = tracegen.body_for("blackscholes", 64, cfg)
    carry = tuple(_sds((BATCH,) + a.shape, a.dtype, sharding)
                  for a in eng._init_carry())
    xs = tuple(_sds((BATCH, eng.CHUNK), getattr(body, f).dtype, sharding)
               for f in eng._TRACE_FIELDS)
    params = tuple(_sds((BATCH,), np.asarray(p).dtype, sharding)
                   for p in eng._cfg_params_np(cfg))
    return carry, xs, params


def test_chunk_scan_compiles_on_one_chip(one_chip):
    compiled = eng._chunk_batch_jit.lower(*_chunk_args(one_chip)).compile()
    assert compiled.memory_analysis() is not None


def test_sharded_chunk_scan_compiles_on_four_chips(topo):
    devices = topo.devices[:4]
    assert len(devices) == 4
    mesh = Mesh(np.asarray(devices), ("cfg",))
    cfg_axis = NamedSharding(mesh, P("cfg"))
    compiled = eng._sharded_chunk_program(mesh).lower(
        *_chunk_args(cfg_axis)).compile()
    hlo = compiled.as_text()
    # the config axis is embarrassingly parallel: nothing crosses a shard
    assert not [c for c in COLLECTIVES if c in hlo]


def _f32(*shape):
    return (shape, jnp.float32)


def _i32(*shape):
    return (shape, jnp.int32)


# (kernel call, argument shapes) at the kernel microbenchmark's shapes
KERNELS = {
    "jacobi2d": (functools.partial(ops.jacobi2d_step, rows_per_block=64),
                 [_f32(258, 512)]),
    "streamcluster": (ops.streamcluster_dist,
                      [_f32(1024, 128), _f32(512, 128)]),
    "swaptions": (ops.cum_normal_inv, [_f32(16384)]),
    "flash_attention": (functools.partial(ops.flash_attention, bq=128,
                                          bk=128),
                        [_f32(1, 512, 4, 64)] * 3),
    "blackscholes": (ops.blackscholes, [_f32(16384)] * 5 + [_i32(16384)]),
    "pathfinder": (ops.pathfinder, [_f32(64, 512)]),
    "canneal": (ops.canneal_swap_cost,
                [_f32(1024, 2), _i32(512, 24), _f32(512, 2), _f32(512, 2)]),
    "particlefilter": (ops.particlefilter_findindex,
                       [_f32(8192), _f32(1024)]),
    "decode_attention": (ops.decode_attention,
                         [_f32(1, 4, 64), _f32(1, 4096, 4, 64),
                          _f32(1, 4096, 4, 64), _i32(1)]),
    "ssd_scan": (functools.partial(ops.ssd_scan, chunk=128),
                 [_f32(1, 512, 4, 16), _f32(1, 512, 4), _f32(4),
                  _f32(1, 512, 32), _f32(1, 512, 32)]),
}

# What the v5e compiler says about the kernels it refuses.  A repair flips
# its case to XPASS, which strict=True turns into a failure to look at.
REFUSED = {
    "blackscholes": "Unimplemented primitive in Pallas TPU lowering for "
                    "KernelType.TC: erf.",
    "pathfinder": "The Pallas TPU lowering currently requires that the last "
                  "two dimensions of your block shape are divisible by 8 and "
                  "128 respectively, or be equal to the respective "
                  "dimensions of the overall array.",
    "canneal": "Only 2D gather is supported",
    "particlefilter": "Mosaic failed to compile TPU kernel: Failed to verify "
                      "layout for Mosaic kernel operand 3: XLA layout "
                      "({0:T(1024)}) does not match Mosaic layout "
                      "({0:T(256)}) for an operand of shape f32[1024].",
    "decode_attention": "Unable to parse attribute: "
                        "\"#tpu.dot_dimension_numbers<[1],[2],[],[0],"
                        "[0, 0, 1, 0],[0],[1]>\":1:37: expected integer "
                        "value",
    "ssd_scan": "Unimplemented primitive in Pallas TPU lowering for "
                "KernelType.TC: cumsum.",
}


@pytest.mark.parametrize("name", [
    pytest.param(n, marks=pytest.mark.xfail(strict=True, reason=REFUSED[n]))
    if n in REFUSED else n for n in KERNELS])
def test_pallas_kernel_compiles_for_v5e(name, one_chip):
    fn, shapes = KERNELS[name]
    args = [_sds(s, d, one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
