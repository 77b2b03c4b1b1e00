"""The six Sum-stage kernels compile for a TPU v5e (Mosaic, not interpret).

Nothing runs here: each test lowers one kernel wrapper with
``interpret=False`` against a *described* ``v5e:2x2`` topology and asks
the TPU compiler for the executable, at the widths the system runs on
the chip — GAT-E messages (4 heads x 32) for the edge softmax, D = 128
for sum and max — and at the ``alipay_like`` graph's edge and node
counts with the plan geometry its CSC plan has; the packed forward
kernels also at the bucket rungs the benchmark's cells run, with their
scalar-prefetched step tables at the bucket's chunk bound (the SMEM
they take). Mosaic refuses what the
interpreter accepts (blocks off the (8, 128) tiling, vector loads from
SMEM, VMEM over budget), so this file is the guard that keeps the
kernels compilable without a chip.

The topology is described inside a module fixture: the TPU compiler's
library may be loaded by one process at a time, so the description must
happen only in the test process that runs these tests.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

# alipay_like (powerlaw_graph defaults): nodes, directed edges, and its
# packed CSC plan geometry at the default blocks (128 x 256)
N, E = 20000, 119862
N_CHUNKS, E_PAD = 537, 120064
BLOCK_N, BLOCK_E = 128, 256
HEADS, HEAD_DIM, D = 4, 32, 128
# (n_pad, e_pad) bucket rungs: mini-batch training's, and serving's top
RUNGS = [(16384, 65536), (32768, 131072)]


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no TPU lib
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args, **static):
    compiled = fn.lower(*args, **static).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("op", ["sum", "max"])
@pytest.mark.parametrize("width", [HEADS, D])
def test_segment_reduce_fwd_compiles(one_chip, op, width):
    # width HEADS: the distributed softmax's (E, H) logit passes
    _compile(ops._segment_reduce_planned,
             _spec(one_chip, (E, width)),
             _spec(one_chip, (N_CHUNKS, BLOCK_E), jnp.int32),
             _spec(one_chip, (N_CHUNKS, BLOCK_E), jnp.int32),
             num_segments=N, block_n=BLOCK_N, block_e=BLOCK_E,
             interpret=False, op=op)


def test_segment_sum_bwd_compiles(one_chip):
    _compile(ops._segment_sum_bwd_planned,
             _spec(one_chip, (N, D)),
             _spec(one_chip, (E_PAD,), jnp.int32),
             num_edges=E, block_e=BLOCK_E, interpret=False)


def test_segment_max_bwd_compiles(one_chip):
    _compile(ops._segment_max_bwd_planned,
             _spec(one_chip, (N, D)), _spec(one_chip, (N, D)),
             _spec(one_chip, (E, D)),
             _spec(one_chip, (E_PAD,), jnp.int32),
             num_edges=E, block_e=BLOCK_E, interpret=False)


def test_edge_softmax_fwd_compiles(one_chip):
    _compile(ops._edge_softmax_planned,
             _spec(one_chip, (E, HEADS)),
             _spec(one_chip, (E, HEADS, HEAD_DIM)),
             _spec(one_chip, (N_CHUNKS, BLOCK_E), jnp.int32),
             _spec(one_chip, (N_CHUNKS, BLOCK_E), jnp.int32),
             num_segments=N, block_n=BLOCK_N, block_e=BLOCK_E,
             interpret=False)


@pytest.mark.parametrize("kernel", ["softmax", "sum", "max"])
@pytest.mark.parametrize("rung", RUNGS, ids=lambda r: f"{r[0]}x{r[1]}")
def test_packed_fwd_kernels_compile_at_bucket_rungs(one_chip, rung, kernel):
    n_pad, e_pad = rung
    chunks = _spec(one_chip, (ops.bucket_plan_chunks(n_pad, e_pad), BLOCK_E),
                   jnp.int32)
    geometry = dict(num_segments=n_pad, block_n=BLOCK_N, block_e=BLOCK_E,
                    interpret=False)
    if kernel == "softmax":
        _compile(ops._edge_softmax_planned,
                 _spec(one_chip, (e_pad, HEADS)),
                 _spec(one_chip, (e_pad, HEADS, HEAD_DIM)), chunks, chunks,
                 **geometry)
    else:
        _compile(ops._segment_reduce_planned, _spec(one_chip, (e_pad, D)),
                 chunks, chunks, op=kernel, **geometry)


def test_edge_softmax_bwd_compiles(one_chip):
    _compile(ops._edge_softmax_bwd_planned,
             _spec(one_chip, (N, HEADS, HEAD_DIM)),
             _spec(one_chip, (E, HEADS)),
             _spec(one_chip, (E, HEADS, HEAD_DIM)),
             _spec(one_chip, (N, HEADS)), _spec(one_chip, (N, HEADS)),
             _spec(one_chip, (N, HEADS)),
             _spec(one_chip, (E_PAD,), jnp.int32),
             num_edges=E, block_e=BLOCK_E, interpret=False)
