"""Pallas TPU kernel: CSC-blocked neighbor aggregation (the Sum stage).

The paper's stage breakdown (Fig. A3) shows graph convolution — dominated
by the per-edge gather + per-destination aggregation — at 76% of runtime.
On GPU this is a scatter-add; here edges are sorted by destination (the
CSC order GraphTheta already maintains, §4.1) and destinations are tiled
into blocks of ``BN`` rows. Within a chunk the kernel folds each gathered
message row into its destination row, lane by lane in plan order::

    out[dst[l]] = op(out[dst[l]], messages[gather_idx[l]])

with ``op`` = add (sum) or max. A destination's edges are folded in
edge-id order whatever the block or chunk boundaries, so a node's
aggregate is the same bits in any view that holds its in-edges — the
full graph, a mini-batch, or a one-request serving view. (The one-hot
matmul form, ``onehot[BN, BE] @ messages[BE, D]`` on the MXU, groups the
additions by chunk and loses that property; it is left to a later
measured change.)

Packed plan and step table
--------------------------
The plan (``ops.build_csc_plan``) lays each destination block's edge
slice, in CSC order, into whole ``BE``-lane chunks: ``max(1, ceil(len_b /
BE))`` chunks a block, packed back to back into ``(n_chunks, BE)`` index
arrays. An empty block still takes one (dead) chunk: its step is what
initialises the block's output rows. Lanes past a block's last edge are
dead (``local_ids < 0``). A bucket's plans pad ``n_chunks`` with trailing
dead chunks to ``ceil(e_pad / BE) + nb``, a bound every view meets, so
their shapes depend on the bucket alone. A plan never holds more lanes
than the unpacked layout of ``nb`` slices of the widest block's length:
``Σ_b max(1, ceil(len_b / BE)) · BE <= nb · L_pad``.

The forward grid walks chunks: sum and max run
``(d_tiles, n_chunks)``, one step a chunk; the softmax runs ``(2 ·
n_chunks,)``, a block's phase-0 steps before its phase-1 steps.
:func:`step_table` derives, from each chunk's first lane, the block of
every chunk and the first chunk of every block; both are scalar
prefetched into SMEM, and the output ``index_map`` reads a step's block
from them. A block's steps are contiguous, so its output tile stays
resident in VMEM from its first step (which initialises it) to its last
(the softmax writes its outputs there). Steps past the last live chunk
do nothing: no SMEM copy, no lane loop, and the last block's tile stays
put.

Fused gather
------------
The per-edge gather happens **inside** the kernel: the raw ``(E, D)`` edge
messages stay in HBM (``memory_space=pl.ANY``) and each grid step copies
its chunk's ``BE`` plan indices into SMEM, then issues one row DMA per
lane into a ``(BE, BD)`` VMEM scratch (:func:`gather_rows`). There is no
``(n_chunks, BE, D)`` pre-gathered tensor in HBM (that tensor duplicated
every message byte; see ``benchmarks/kernels_bench.py aggregate`` for the
bytes-moved comparison). Dead lanes are never gathered nor folded: each
step also copies the chunk's destination rows to SMEM and issues DMAs
only for live lanes.

Mosaic layout rules shape the operands (every block's last two dims are
(8, 128)-aligned or whole):

- HBM operands that are gathered row by row are viewed as ``(rows, 1,
  Dp)`` with ``Dp`` the feature width padded to a lane multiple (128): a
  one-row DMA must not cut an (8, 128) tile, and the unit middle axis
  gives each row a ``(1, 128)`` tiling. At ``D % 128 == 0`` the view is a
  bitcast of the ``(E, D)`` array.
- Plan index chunks are ``(n_chunks, 1, BE)`` int32: one chunk is a
  whole ``(1, BE)`` tile, copied to SMEM (indices drive DMA addresses and
  the fold's row, and only scalars load from SMEM). Rows of VMEM refs
  are addressed with dynamic sublane offsets.
- Kernels compute in float32; the wrappers cast narrower inputs up and
  the outputs back.

Block geometry & VMEM budget
----------------------------
Per grid step the forward kernels hold, in f32:

=====================  =======================  =========================
buffer                 shape                    bytes (defaults)
=====================  =======================  =========================
messages               (E, 1, Dp) in HBM        0 (never resident)
step table (SMEM)      (n_chunks + nb + 1,)     4·(n_chunks + nb + 1)
                       int32, whole launch      (training rung: 2 KiB)
plan chunk (SMEM)      (1, BE) int32            4·BE
local ids (SMEM)       (1, BE) int32            4·BE
gather scratch         (BE, BD)                 4·BE·BD (256·512 → 512 KiB)
output tile            (BN, BD)                 4·BN·BD (128·512 → 256 KiB)
=====================  =======================  =========================

``BD`` is ``_pick_block_d``: the whole padded width up to the cap (512
lanes), else the largest multiple of 128 within the cap that divides it;
the d-tile grid axis covers the rest. The edge softmax (edge_softmax.py)
holds two ``(BE, H·D)`` gather buffers and three ``(BN, H·D)``
accumulators. Only the SMEM step table grows with the graph, by one
word a chunk and a block; the VMEM footprint does not.

Backward geometry (kernels in backward.py)
------------------------------------------
The backward kernels run over the **edge axis** (grid ``(d_tiles,
E_pad/BE)``; softmax: ``(E_pad/BE,)`` with heads unrolled in the body)
and fill a ``(BE, BD)`` buffer with each edge's row of a node-indexed
table (cotangent ``g``, saved forward output, softmax stats) through the
plan's inverse map ``edge_dst``. Where the table fits, it is **resident**
in VMEM for the whole launch and each lane is a row copy
(:func:`copy_rows`); where it does not, each live lane's row is one HBM
row DMA (:func:`gather_rows`). Per launch, in f32:

=====================  =======================  =========================
buffer                 shape                    bytes (defaults)
=====================  =======================  =========================
node table, resident   (N, BD) per table, one   4·N·BD (gcn-reddit:
                       buffer, read once per    14,560·128 → 7.45 MB)
                       d-tile
node table, DMA path   (N, 1, Dp) in HBM        0 (never resident)
edge_dst chunk (SMEM)  (1, BE) int32            4·BE
edge_dst column        (BE, 1) int32 block      4·BE (lane-padded: 128 KiB)
gathered rows          (BE, BD) per table       4·BE·BD (max: 2 tables)
edge tiles             (BE, BD) in + out        2·4·BE·BD
=====================  =======================  =========================

The size rule depends on the shape alone: a launch is resident when its
tables' lane-padded bytes, plus the ``edge_dst`` column and the edge
tiles, buffers and largest temporary, fit the analyzer's 16 MiB budget
(``backward.VMEM_BUDGET``); resident launches raise Mosaic's scoped VMEM
to 32 MiB for the pipeline's second buffers. At the benchmark's widths
the GCN cell and the bucket rungs up to (16,384, 65,536) are resident;
the (32,768, 131,072) rung and larger worker shares take the DMA path.
On a v5e the DMA path compiles only for 128-lane rows (``BD`` above 128
is refused: a one-row DMA may not cut the buffer's (8, 128) tiles); the
resident path copies rows of any width.

The softmax backward's table is one packed row per node, ``[g (H·D) | m
| den | out·g]`` lane-padded, so a lane takes one row, not two (GAT-E's 4
heads of 8: 44 lanes in one 128-lane row, an 8 MiB table at its training
rung); the DMA path gathers it 128 lanes at a time, since a one-row DMA
may not cut a wider buffer's (8, 128) tiles. The kernel rebuilds the
edge probability in registers from the saved logits — it is never
written to HBM.

Host-side planning (``build_csc_plan`` in ops.py) computes the packed
chunk layout once per graph — the paper's "reused CSR/CSC indexing"
(§4.2): views/batches reuse the plan, only messages change.

The budget arithmetic above is not only documentation: the static
analyzer in :mod:`repro.analysis.vmem` recomputes per-``pallas_call``
VMEM blocks + scratch buffers + peak temporary bytes from a traced jaxpr
and flags any kernel whose footprint exceeds the budget (``vmem.budget``
rule; CLI ``python -m repro.analysis --strict``). Changing a block
geometry here without re-checking the tables trips that gate in CI.

These kernels are wired into the forward paths through the Sum-stage
backend registry in :mod:`repro.core.aggregate`: the ``"csc"``
:class:`~repro.core.aggregate.AggregationBackend` (the default on TPU)
routes the combine of both ``layer_forward_block`` and the distributed
engine through ``segment_sum_csc`` / ``segment_max_csc`` /
``edge_softmax_csc`` (the ``"reference"`` backend keeps the portable jnp
segment ops). Multi-head ``(E, H, D)`` messages fold into the lane axis.

``NEG`` below is *the* masking sentinel of the repo — kernels, reference
oracles, and attention masks all import it from here so empty-segment
thresholds (``> NEG / 2`` in aggregate.py) can never drift out of sync.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30
LANES = 128


BLOCK_D_CAP = 512           # lanes per feature tile (VMEM budget above)


def _pick_block_d(d: int) -> int:
    """Feature tile of the kernels (see module docstring): the whole
    width when it fits the cap or is not a lane multiple, else the largest
    multiple of 128 within the cap that divides it."""
    if d <= BLOCK_D_CAP or d % LANES:
        return d
    for bd in range(BLOCK_D_CAP, 0, -LANES):
        if d % bd == 0:
            return bd
    return LANES


def lane_pad(x: jax.Array) -> jax.Array:
    """(R, D) -> (R, Dp) float32 with Dp the next multiple of 128."""
    x = x.astype(jnp.float32)
    pad = -x.shape[1] % LANES
    return jnp.pad(x, ((0, 0), (0, pad))) if pad else x


def row_view(x: jax.Array) -> jax.Array:
    """(R, D) -> lane-padded (R, 1, Dp): the HBM layout that one-row DMAs
    can address (module docstring)."""
    x = lane_pad(x)
    return x.reshape(x.shape[0], 1, x.shape[1])


def chunk_view(idx: jax.Array, block_e: int) -> jax.Array:
    """Plan index array -> (n_chunks, 1, BE): one chunk per tile."""
    return idx.reshape(-1, 1, block_e)


def gather_rows(idx_smem, src_hbm, buf, sem, num_rows: int, d0, width: int,
                live):
    """``buf[i] = src_hbm[idx_smem[0, i], 0, d0:d0+width]`` for every lane
    ``i`` with ``live(i)``: one row DMA per live lane, all in flight on one
    semaphore, then drained. Rows of dead lanes (padding) keep whatever
    the buffer held, so consumers mask them (or zero the buffer once)."""
    def copy(i, row):
        return pltpu.make_async_copy(
            src_hbm.at[pl.ds(row, 1), 0, pl.ds(d0, width)],
            buf.at[pl.ds(i, 1)], sem)

    def start(i, carry):
        @pl.when(live(i))
        def _():
            copy(i, jnp.minimum(idx_smem[0, i], num_rows - 1)).start()
        return carry

    def wait(i, carry):
        @pl.when(live(i))
        def _():
            copy(i, 0).wait()
        return carry

    jax.lax.fori_loop(0, buf.shape[0], start, 0)
    jax.lax.fori_loop(0, buf.shape[0], wait, 0)


def copy_rows(idx_smem, table, buf, num_rows: int):
    """``buf[i] = table[min(idx_smem[0, i], num_rows - 1)]`` for every
    lane ``i``: the VMEM-resident counterpart of :func:`gather_rows`, one
    row copy at a dynamic sublane offset per lane, no DMA. Lanes without a
    row (``idx >= num_rows``) copy the last one, so consumers mask them."""
    def copy(i):
        row = jnp.minimum(idx_smem[0, i], num_rows - 1)
        buf[pl.ds(i, 1), :] = table[pl.ds(row, 1), :]

    def trip(j, carry):
        for k in range(8):                    # unrolled by hand, 8 a trip
            copy(j * 8 + k)
        return carry

    lanes = buf.shape[0]
    jax.lax.fori_loop(0, lanes // 8, trip, 0)
    for i in range(lanes - lanes % 8, lanes):     # a tail of under 8
        copy(i)


def _check_plan(gather_idx, local_ids, block_e: int) -> int:
    n_chunks, lanes = gather_idx.shape
    if local_ids.shape != gather_idx.shape or lanes != block_e:
        raise ValueError(
            f"plan chunks {gather_idx.shape} / {local_ids.shape} are not "
            f"(n_chunks, block_e={block_e})")
    return n_chunks


def step_table(local_ids, num_blocks: int, block_n: int):
    """The packed plan's step table, scalar-prefetched into SMEM by the
    forward kernels: ``chunk_block`` (n_chunks,), the block of each chunk
    (trailing dead chunks keep the last block, so its output tile stays
    resident), and ``block_start`` (num_blocks + 1,), the first chunk of
    each block, ``block_start[num_blocks]`` the live chunk count.

    A chunk's block is read from its first lane: live lanes fill a chunk
    from lane 0 and name their destination row, and the one chunk of an
    empty block (or a trailing dead chunk) holds ``-1 - block`` there."""
    head = local_ids[:, 0]
    block = jnp.where(head >= 0, head // block_n, -1 - head)
    start = jnp.searchsorted(block, jnp.arange(num_blocks + 1,
                                               dtype=block.dtype),
                             method="scan_unrolled")
    return (jnp.minimum(block, num_blocks - 1).astype(jnp.int32),
            start.astype(jnp.int32))


def fold_rows(ids_smem, n_lanes: int, row0, body):
    """``body(i, r)`` for every live lane ``i`` (its destination row
    ``row0 + r``), in lane order — i.e. each destination folds its edges
    in plan (edge-id) order, whatever the chunking."""
    def step(i, carry):
        v = ids_smem[0, i]

        @pl.when(v >= 0)
        def _():
            body(i, v - row0)
        return carry

    jax.lax.fori_loop(0, n_lanes, step, 0)


def live_lane(ids_smem):
    """The ``live`` predicate of :func:`gather_rows`: lane ``i`` holds an
    edge."""
    return lambda i: ids_smem[0, i] >= 0


def _segment_fold_kernel(block_ref, start_ref, idx_hbm, ids_hbm, msg_hbm,
                         out_ref, idx_smem, ids_smem, buf, sem, *, op,
                         identity: float, num_blocks: int, num_edges: int,
                         block_d: int):
    """One (d_tile, step) grid step: step ``s`` folds chunk ``s`` into its
    block's output tile, gather fused in.

    block_ref / start_ref: the step table (:func:`step_table`) in SMEM.
    idx_hbm: (n_chunks, 1, BE) int32 plan chunks in HBM — rows of ``msg``
             feeding each lane.
    ids_hbm: (n_chunks, 1, BE) int32 — destination row of each lane, < 0
             for a dead lane (never gathered, never folded).
    msg_hbm: (E, 1, Dp) f32 raw edge messages in HBM.
    out_ref: (BN, BD) f32 destination tile (resident over its block's
             chunks).
    """
    dt, s = pl.program_id(0), pl.program_id(1)
    b = block_ref[s]

    @pl.when(s < start_ref[num_blocks])     # trailing dead steps: nothing
    def _step():
        @pl.when(s == start_ref[b])
        def _init():
            out_ref[...] = jnp.full_like(out_ref, identity)

        pltpu.sync_copy(idx_hbm.at[s], idx_smem)
        pltpu.sync_copy(ids_hbm.at[s], ids_smem)
        gather_rows(idx_smem, msg_hbm, buf, sem, num_edges, dt * block_d,
                    block_d, live_lane(ids_smem))

        def fold(i, r):
            out_ref[pl.ds(r, 1), :] = op(out_ref[pl.ds(r, 1), :],
                                         buf[pl.ds(i, 1), :])

        fold_rows(ids_smem, buf.shape[0], b * out_ref.shape[0], fold)


def _segment_fold_csc(data, gather_idx, local_ids, num_blocks: int,
                      block_n: int, block_e: int, block_d: int,
                      interpret: bool, op, identity: float):
    e, d = data.shape
    nc = _check_plan(gather_idx, local_ids, block_e)
    if e == 0:
        return jnp.full((num_blocks * block_n, d), identity, jnp.float32)
    msg = row_view(data)
    dp = msg.shape[-1]
    bd = block_d or _pick_block_d(dp)
    if dp % bd != 0:
        raise ValueError(f"feature dim {dp} not divisible by block_d={bd}")
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(dp // bd, nc),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 3,
        out_specs=pl.BlockSpec((block_n, bd),
                               lambda dt, s, blk, start: (blk[s], dt)),
        scratch_shapes=[pltpu.SMEM((1, block_e), jnp.int32),
                        pltpu.SMEM((1, block_e), jnp.int32),
                        pltpu.VMEM((block_e, bd), jnp.float32),
                        pltpu.SemaphoreType.DMA(())],
    )
    out = pl.pallas_call(
        functools.partial(_segment_fold_kernel, op=op, identity=identity,
                          num_blocks=num_blocks, num_edges=e, block_d=bd),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((num_blocks * block_n, dp),
                                       jnp.float32),
        interpret=interpret,
    )(*step_table(local_ids, num_blocks, block_n),
      chunk_view(gather_idx, block_e), chunk_view(local_ids, block_e), msg)
    return out[:, :d]


def segment_sum_csc(data: jax.Array, gather_idx: jax.Array,
                    local_ids: jax.Array, num_blocks: int, block_n: int,
                    block_e: int = 256, interpret: bool = False):
    """Blocked segment-sum with the per-edge gather fused into the kernel.

    data:       (E, D) raw edge messages (no pre-gathered layout).
    gather_idx: (n_chunks, block_e) int32 packed plan chunks: indices
                into the edge axis (dead lanes hold E).
    local_ids:  (n_chunks, block_e) int32 — destination row of each lane,
                negative for dead lanes (``ops.CSCPlan``).
    returns     (num_blocks * block_n, D) float32.
    """
    return _segment_fold_csc(data, gather_idx, local_ids, num_blocks,
                             block_n, block_e, 0, interpret, jnp.add, 0.0)


def segment_max_csc(data: jax.Array, gather_idx: jax.Array,
                    local_ids: jax.Array, num_blocks: int, block_n: int,
                    block_e: int = 256, block_d: int = 0,
                    interpret: bool = False):
    """Blocked segment-max; same fused-gather contract as
    :func:`segment_sum_csc`. ``block_d`` forces the feature tile (0 =
    :func:`_pick_block_d`). Empty destination rows come back as ``NEG``
    (callers clamp)."""
    return _segment_fold_csc(data, gather_idx, local_ids, num_blocks,
                             block_n, block_e, block_d, interpret,
                             jnp.maximum, NEG)
