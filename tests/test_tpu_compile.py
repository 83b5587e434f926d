"""Compile the trainer's Pallas kernels for a described TPU v5e.

Nothing runs: each test lowers and compiles one kernel (or one dispatched
op inside a train-step-shaped shard_map) for a v5e chip that the TPU
compiler describes without one attached, at the shapes of the
``paper-lm-100m`` train step, and checks that the compiled program holds
the Mosaic kernel.  The compiler refuses here what interpret mode accepts:
unaligned block heights, reductions over unsigned integers, lane reshapes
it cannot lay out, and Mosaic calls left in auto-partitioned regions.

The topology is described inside a module-scoped fixture, never while a
module is imported: one process at a time may load the TPU library.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

from repro.kernels import dispatch, ops
from repro.kernels.ef_topk import (ef_apply, ef_block_stats,
                                   ef_stats_telemetry, threshold_split)
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rmsnorm import rmsnorm
from repro.kernels.wire_pack import pack_words, stream_shape, unpack_words
from repro.launch.mesh import make_mesh

# paper-lm-100m train step at seq 1024, global batch 8 (chip_smoke.py):
# the bucketed EF launch covers every 1024-wide block row of the model
# (107520 rows); its 16-bit index stream packs into 537600 words.
BUCKET_ROWS = 107520
INDEX_WORDS = 537600
K_B = 10                                    # round(0.01 * 1024)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip compile can be written to the persistent cache but
    # never read back without the chip
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


F32 = jnp.float32
U32 = jnp.uint32


@pytest.mark.parametrize("rows", [300, BUCKET_ROWS])
@pytest.mark.parametrize("kernel", ["ef_block_stats", "ef_stats_telemetry",
                                    "ef_apply", "threshold_split"])
def test_ef_kernel_compiles(one_chip, kernel, rows):
    blk = ((rows, 1024), F32)
    eta = ((), F32)
    tau = ((rows, 1), F32)
    fn, shapes = {
        "ef_block_stats": (lambda m, g, e: ef_block_stats(
            m, g, e, K_B, interpret=False), (blk, blk, eta)),
        "ef_stats_telemetry": (lambda m, g, e: ef_stats_telemetry(
            m, g, e, K_B, interpret=False), (blk, blk, eta)),
        "ef_apply": (functools.partial(ef_apply, interpret=False),
                     (blk, blk, eta, tau)),
        "threshold_split": (functools.partial(threshold_split,
                                              interpret=False), (blk, tau)),
    }[kernel]
    text = _compile(fn, *shapes, sharding=one_chip)
    if kernel in ("ef_block_stats", "ef_stats_telemetry"):
        # pass 1's wire pairs come out transposed, block rows on lanes
        assert f"f32[{K_B},{rows}]" in text
        assert f"s32[{K_B},{rows}]" in text


@pytest.mark.parametrize("words,ragged", [(stream_shape(INDEX_WORDS), False),
                                          ((256, 512), False),
                                          ((256, 512), True)])
@pytest.mark.parametrize("direction", ["pack", "unpack"])
def test_wire_kernel_compiles(one_chip, direction, words, ragged):
    """16-bit fields; ``ragged``: per-row valid counts (adaptive gamma)."""
    R, W = words
    kernel, width = (pack_words, 2 * W) if direction == "pack" \
        else (unpack_words, W)
    if ragged:
        _compile(lambda x, c: kernel(x, 16, c, 10, interpret=False),
                 ((R, width), U32), ((R,), jnp.int32), sharding=one_chip)
    else:
        _compile(lambda x: kernel(x, 16, interpret=False),
                 ((R, width), U32), sharding=one_chip)


@pytest.mark.parametrize("shape,dtype", [((8, 12, 1023, 64), F32),
                                         ((1, 8, 1024, 128), jnp.bfloat16)])
def test_flash_attention_compiles(one_chip, shape, dtype):
    _compile(functools.partial(flash_attention, interpret=False),
             (shape, dtype), (shape, dtype), (shape, dtype),
             sharding=one_chip)


def test_rmsnorm_compiles(one_chip):
    _compile(lambda x, w: rmsnorm(x, w, interpret=False),
             ((8192, 768), F32), ((768,), F32), sharding=one_chip)


def test_dispatched_kernel_compiles_under_manual_dp(topo):
    """The train step's region: manual over 'data', auto over 'model'.
    A Mosaic call there only lowers through dispatch's shard_map manual
    over every axis — forward and backward (kernel forward, reference
    VJP)."""
    mesh = make_mesh((2, 2), ("data", "model"), devices=topo.devices)

    def worker(x, w):
        return ops.rms_norm(x, w) \
            + jax.grad(lambda x: ops.rms_norm(x, w).sum())(x)

    with jax.set_mesh(mesh), dispatch.using("pallas-tpu"):
        step = jax.jit(jax.shard_map(worker, in_specs=(P("data"), P()),
                                     out_specs=P("data"),
                                     axis_names={"data"}, check_vma=False))
        x = jax.ShapeDtypeStruct((8, 1023, 768), F32,
                                 sharding=NamedSharding(mesh, P("data")))
        w = jax.ShapeDtypeStruct((768,), F32,
                                 sharding=NamedSharding(mesh, P()))
        text = step.lower(x, w).compile().as_text()
    assert "tpu_custom_call" in text
