"""Pallas kernels for the orchestration hot path.

The serving engine's per-tick work is dominated by two memory-bound
scatter/gather patterns that XLA lowers into long chains of small ops:

``group_occupancy``
    The shared-edge coupling needs, for every cell i, the total edge
    occupancy of its co-location group: ``out[i] = Σ_j own[j] ·
    [groups[j] == groups[i]]``.  The lax reference is a ``segment_sum``
    followed by a gather; the kernel fuses both into one blocked
    membership reduction over a 2-D grid of (cell-row block, cell-column
    block) tiles — each tile compares a ``(bj, 1)`` column of group ids
    against a ``(1, bi)`` row and sums the matching ``own`` values down
    the sublanes into the ``(1, bi)`` output row, so the mask tile stays
    ``bj × bi`` whatever the fleet size.

``queue_admit``
    Admitting one tick's arrival burst into the per-cell FIFO ring
    queues is sequential by nature: each lane reads and bumps its cell's
    ``q_len``.  The kernel runs exactly that loop over the lanes, on the
    scalar core (lane cells in SMEM, per-cell lengths as ``(C/128, 128)``
    rows in VMEM, one dynamic-row read-modify-write per lane), and emits
    each lane's position in its cell's queue (or -1 when it is dropped or
    padding).  The ring-slot writes are then one conflict-free XLA
    scatter.  The result is the sequential loop's (test-enforced against
    the lax reference over randomized bursts).

Both kernels lower to Mosaic on a TPU backend and run under the Pallas
interpreter elsewhere (the CPU test backend): :func:`interpret_mode`
decides from ``jax.default_backend()``, and ``interpret=`` overrides it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_GO_ROW_BLK = 1024   # output cells per tile (lane axis)
_GO_COL_BLK = 512    # summed-over cells per tile (sublane axis)
_QA_LANE_BLK = 4096  # arrival lanes per SMEM block
_SMEM_1D_TILE = 1024  # 1-D int32 SMEM blocks are laid out in 1024s


def interpret_mode() -> bool:
    """True unless the default backend is a TPU: Mosaic lowers only
    there, every other backend runs the kernels interpreted."""
    return jax.default_backend() != "tpu"


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _group_occupancy_kernel(own_ref, g_col_ref, g_row_ref, out_ref):
    """Tile (i, j): out[0, i-block] += Σ_{j in block} own[j] ·
    [g_j == g_i], accumulated over the column grid axis."""
    @pl.when(pl.program_id(1) == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    eq = g_col_ref[...] == g_row_ref[...]          # (bj, 1) vs (1, bi)
    out_ref[...] += jnp.sum(jnp.where(eq, own_ref[...], 0.0), axis=0,
                            keepdims=True)


def group_occupancy_pallas(own, groups, *, blk: int = _GO_COL_BLK,
                           interpret: bool | None = None) -> jnp.ndarray:
    """Fused segment-sum + gather: (C,) own, (C,) int group ids in
    [0, C) → (C,) per-cell group totals.  ``blk`` is the column block
    (a multiple of 8); the row block is up to 1024 cells.  Exact for
    integer-valued occupancies (counts ≤ 2^24 are exact in f32)."""
    it = interpret_mode() if interpret is None else interpret
    c = own.shape[0]
    bi = min(_GO_ROW_BLK, _round_up(c, _LANES))
    bj = min(blk, _round_up(c, 8))
    ci, cj = _round_up(c, bi), _round_up(c, bj)
    groups = jnp.asarray(groups, jnp.int32)
    # pad ids so padded columns (-1) match nothing and padded rows (-2)
    # produce zeros that are sliced off below
    own_col = jnp.pad(own.astype(jnp.float32), (0, cj - c)).reshape(cj, 1)
    g_col = jnp.pad(groups, (0, cj - c), constant_values=-1).reshape(cj, 1)
    g_row = jnp.pad(groups, (0, ci - c), constant_values=-2).reshape(1, ci)
    out = pl.pallas_call(
        _group_occupancy_kernel,
        grid=(ci // bi, cj // bj),
        in_specs=[pl.BlockSpec((bj, 1), lambda i, j: (j, 0)),
                  pl.BlockSpec((bj, 1), lambda i, j: (j, 0)),
                  pl.BlockSpec((1, bi), lambda i, j: (0, i))],
        out_specs=pl.BlockSpec((1, bi), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, ci), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=it,
        name="group_occupancy",
    )(own_col, g_col, g_row)
    return out[0, :c].astype(own.dtype)


def _queue_admit_kernel(cell_ref, len_ref, len_out, seen_ref, *,
                        q: int, blk: int, a: int):
    """Lanes of block b in order: a lane whose cell (-1 = invalid) has
    room takes queue position ``len[cell]`` (written to ``seen``) and
    bumps it; any other lane writes -1.  ``len_out`` stays resident
    across the lane blocks."""
    b = pl.program_id(0)

    @pl.when(b == 0)
    def _():
        len_out[...] = len_ref[...]

    lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)

    def admit(i, carry):
        c = cell_ref[i]
        cs = jnp.maximum(c, 0)
        r = cs // _LANES
        row = len_out[pl.ds(r, 1), :]
        hit = lane == cs % _LANES
        n = jnp.sum(jnp.where(hit, row, 0))
        ok = (c >= 0) & (n < q)
        seen_ref[i] = jnp.where(ok, n, -1)
        len_out[pl.ds(r, 1), :] = jnp.where(hit & ok, row + 1, row)
        return carry

    jax.lax.fori_loop(0, jnp.minimum(blk, a - b * blk), admit, 0)


def queue_admit_pallas(q_ids, q_head, q_len, rid, cell, valid,
                       interpret: bool | None = None):
    """Admit one tick's arrival burst into the per-cell FIFO rings.

    q_ids: (C, Q) int32 ring slots; q_head/q_len: (C,) int32;
    rid/cell: (A,) int32 arrival lanes; valid: (A,) bool (invalid lanes
    are padding or, under sharding, another shard's arrivals).
    Returns (q_ids', q_len', admitted (A,) bool) — identical to
    processing the lanes sequentially in order."""
    it = interpret_mode() if interpret is None else interpret
    c, q = q_ids.shape
    a = rid.shape[0]
    cell = jnp.where(valid, jnp.asarray(cell, jnp.int32), -1)
    rows = _round_up(c, _LANES) // _LANES
    len2 = jnp.pad(q_len, (0, rows * _LANES - c)).reshape(rows, _LANES)
    blk = min(_QA_LANE_BLK, _round_up(a, _SMEM_1D_TILE))
    ap = _round_up(a, blk)
    smem_blk = pl.BlockSpec((blk,), lambda b: (b,),
                            memory_space=pltpu.SMEM)
    len_blk = pl.BlockSpec((rows, _LANES), lambda b: (0, 0))
    len_out, seen = pl.pallas_call(
        functools.partial(_queue_admit_kernel, q=q, blk=blk, a=a),
        grid=(ap // blk,),
        in_specs=[smem_blk, len_blk],
        out_specs=[len_blk, smem_blk],
        out_shape=[jax.ShapeDtypeStruct((rows, _LANES), jnp.int32),
                   jax.ShapeDtypeStruct((ap,), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=it,
        name="queue_admit",
    )(jnp.pad(cell, (0, ap - a), constant_values=-1), len2)
    seen = seen[:a]
    admitted = seen >= 0
    c_safe = jnp.maximum(cell, 0)
    slot = (q_head[c_safe] + seen) % q
    q_ids = q_ids.at[jnp.where(admitted, c_safe, c), slot].set(
        jnp.asarray(rid, jnp.int32), mode="drop")
    return q_ids, len_out.reshape(-1)[:c], admitted


# ----------------------------------------------------------- references
def group_occupancy_lax(own, groups, num_segments: int | None = None
                        ) -> jnp.ndarray:
    """The unfused lax reference: segment_sum + gather (the parity
    baseline, and the building block of the sharded psum path)."""
    groups = jnp.asarray(groups)
    n = groups.shape[0] if num_segments is None else num_segments
    totals = jax.ops.segment_sum(own, groups, num_segments=n)
    return totals[groups]


def queue_admit_lax(q_ids, q_head, q_len, rid, cell, valid):
    """Sequential lax reference of :func:`queue_admit_pallas` — the
    engine's original per-lane ``fori_loop`` semantics."""
    q = q_ids.shape[1]
    a = rid.shape[0]
    adm = jnp.zeros((a,), bool)

    def body(i, acc):
        q_ids, q_len, adm = acc
        c = jnp.maximum(cell[i], 0)
        ok = valid[i] & (q_len[c] < q)
        pos = (q_head[c] + q_len[c]) % q
        q_ids = q_ids.at[c, pos].set(jnp.where(ok, rid[i], q_ids[c, pos]))
        q_len = q_len.at[c].add(ok.astype(jnp.int32))
        return q_ids, q_len, adm.at[i].set(ok)

    return jax.lax.fori_loop(0, a, body, (q_ids, q_len, adm))
