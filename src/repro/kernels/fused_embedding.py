"""Pallas TPU kernels for the fused sparse hot path (paper §III profiling).

PICASSO attributes the embedding layer's cost to fragmentary, memory-bound
gather / segment-reduce / scatter ops; HugeCTR and Tensor Casting both ship
the gather-scatter pair as dedicated fused kernels. These are those kernels
for the repro's hot path — each one replaces a take/segment_sum/argsort/
scatter chain in ``repro.core.packed_embedding`` with a single pass that
never materializes the ``[n, D]`` per-id intermediate:

``gather_pool_pallas``
    Forward SegmentReduction ``bags[seg[i]] += w[i] * rows_u[inv[i]]``: the
    ``embedding_bag`` kernel generalized to take an *indirection vector*
    (``inv`` from the fixed-shape unique) instead of raw table ids. One grid
    step per position; the scalar-prefetched ``inv`` drives the row
    BlockSpec (HBM->VMEM DMA of exactly the needed unique row), ``seg``
    drives the output index_map, so each bag block stays in VMEM while its
    (sorted) segment lasts and is flushed exactly once.

``segment_grad_pallas``
    The transpose: ``g_rows[u] = sum_{i: inv[i]=u} w[i] * g_bags[seg[i]]``.
    ``inv`` is *not* sorted, so positions are stably pre-sorted by slot and
    ``n_rows`` zero-weight ghost positions (one per output slot) are merged
    in — every output block is visited at least once, so slots past
    ``n_uniq`` come out exactly zero instead of holding garbage. Backward of
    ``gather_pool`` under ``jax.custom_vjp`` (see ``kernels.ops``), and the
    engine's explicit transposed path.

``dedup_adagrad_pallas``
    Fused dedup + row-wise adagrad + in-place scatter: replaces the
    argsort -> segment_sum -> ``.at[].add`` -> ``.at[].set`` chain of
    ``_dedup_apply``. Grid over sorted positions; duplicate row grads
    accumulate in a VMEM scratch across the run, and the run's *last* step
    applies adagrad and read-modify-writes the touched row through explicit
    HBM DMAs (the table is input_output_aliased, so the update is in-place
    and untouched rows are never copied — they stay bitwise identical). The
    duplicate-accumulation order matches the reference ``segment_sum``
    (stable sort, run-sequential adds), so touched rows agree with
    ``_dedup_apply`` to XLA-fusion reassociation (~1 ULP on the final
    adagrad arithmetic).

``tier_probe_pallas``
    Fused cache-tier probe: sorted-key binary search (rank-by-count over the
    VMEM-resident key vector) + hit-masked row gather in one kernel, for the
    L1 hot tier and L2 host tier probes that ``mp_lookup`` otherwise
    assembles from searchsorted / take / where. Returns ``(hit, slot,
    rows)`` with miss rows exactly zero, so the caller's stitch is a single
    ``where``.

``gather_project_pallas`` / ``gather_project_grad_pallas``
    The narrow-row stitch of ``picasso_narrow``: gather a ``[d]`` narrow row
    from the routed-back buffer and project it up through the learned
    ``[d, D]`` map in one grid step (per-row DMA + a tiny MXU dot), so the
    ``[n, d]`` gather and the ``[n, D]`` projection never exist as separate
    memory-bound XLA ops. The backward folds the wide cotangent through
    ``proj^T`` and run-accumulates onto the routed-buffer slots (positions
    pre-sorted by slot, one zero ghost per slot so every output block is
    written) — again one pass, no per-id intermediate.

All kernels run in ``interpret=True`` on non-TPU backends (the dispatch in
``kernels.ops`` decides); the CI soak forces every call through the
interpreter against the pure-jnp references.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.embedding_bag import embedding_bag_pallas


# ---------------------------------------------------------------------------
# fused gather + pool (forward) and its transpose (backward)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("n_bags", "interpret"))
def gather_pool_pallas(
    rows_u: jnp.ndarray,    # [n, D] unique rows
    inv: jnp.ndarray,       # [n] indirection: position -> unique slot
    weights: jnp.ndarray,   # [n]
    seg: jnp.ndarray,       # [n] bag index, sorted ascending
    n_bags: int,
    interpret: bool = False,
) -> jnp.ndarray:
    """Fused ``bags[seg[i]] += w[i] * rows_u[inv[i]]`` without the ``[n, D]``
    per-id intermediate: the embedding-bag kernel with ``inv`` as the
    indirection vector (its ``ids`` argument was always an indirection — the
    unique step just makes that explicit). One zero-weight ghost position per
    bag is merged in, so a bag no position maps to comes out zero exactly
    like the reference ``segment_sum`` — never as an unwritten output block
    (the packed layout covers every bag, but this is a public helper and
    silent fused/reference divergence on uncovered bags is a trap)."""
    n = inv.shape[0]
    seg2 = jnp.concatenate([seg.astype(jnp.int32),
                            jnp.arange(n_bags, dtype=jnp.int32)])
    inv2 = jnp.concatenate([inv.astype(jnp.int32),
                            jnp.zeros((n_bags,), jnp.int32)])
    w2 = jnp.concatenate([weights.astype(rows_u.dtype),
                          jnp.zeros((n_bags,), rows_u.dtype)])
    order = jnp.argsort(seg2, stable=True)   # ghosts sort after real positions
    return embedding_bag_pallas(rows_u, jnp.take(inv2, order),
                                jnp.take(seg2, order), jnp.take(w2, order),
                                n_bags, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("n_rows", "interpret"))
def segment_grad_pallas(
    g_bags: jnp.ndarray,    # [n_bags, D] cotangent of the pooled output
    seg: jnp.ndarray,       # [n] bag index per position
    weights: jnp.ndarray,   # [n]
    inv: jnp.ndarray,       # [n] position -> unique slot (NOT sorted)
    n_rows: int,            # number of unique-row slots (== n, fixed shape)
    interpret: bool = False,
) -> jnp.ndarray:
    """Transpose of ``gather_pool``: ``g_rows[u] = sum_{inv[i]=u} w[i] *
    g_bags[seg[i]]`` as one bag-kernel pass over positions stably sorted by
    slot. ``n_rows`` zero-weight ghost positions (slot j, bag 0, weight 0)
    are merged in so every output slot is visited: slots that no real
    position maps to (``>= n_uniq``) come out exactly zero."""
    n = inv.shape[0]
    slots = jnp.concatenate([inv.astype(jnp.int32),
                             jnp.arange(n_rows, dtype=jnp.int32)])
    gat = jnp.concatenate([seg.astype(jnp.int32),
                           jnp.zeros((n_rows,), jnp.int32)])
    wts = jnp.concatenate([weights.astype(g_bags.dtype),
                           jnp.zeros((n_rows,), g_bags.dtype)])
    # stable: real positions keep their original (reference segment_sum)
    # accumulation order within a slot; ghosts sort after them and add 0
    order = jnp.argsort(slots, stable=True).astype(jnp.int32)
    return embedding_bag_pallas(g_bags, jnp.take(gat, order),
                                jnp.take(slots, order), jnp.take(wts, order),
                                n_rows, interpret=interpret)


# ---------------------------------------------------------------------------
# fused dedup + row-wise adagrad + in-place scatter
# ---------------------------------------------------------------------------


def _dedup_kernel(si_ref, g_blk, w_any, acc_any, w_out, acc_out,
                  gsum, row, accrow, sems, *, m, lr, eps, rows):
    i = pl.program_id(0)
    idx = si_ref[i]
    ok = idx < rows
    first = jnp.logical_or(i == 0, idx != si_ref[jnp.maximum(i - 1, 0)])
    last = jnp.logical_or(i == m - 1, idx != si_ref[jnp.minimum(i + 1, m - 1)])
    contrib = g_blk[...] * ok.astype(g_blk.dtype)

    @pl.when(first)
    def _init():
        gsum[...] = contrib

    @pl.when(jnp.logical_not(first))
    def _acc():
        gsum[...] += contrib

    # last step of a valid run: adagrad the accumulated grad into the row.
    # Explicit DMAs keep the update in-place and ordered (grid steps are
    # sequential, each step waits on its own copies) — the blocked-pipeline
    # idiom cannot express this safely because the sentinel run clamps onto
    # a possibly-live row. Reads come from the *input* refs (every row is
    # read at most once, before its own run writes it — runs are unique), as
    # interpret-mode reads of an aliased output ref are unreliable under
    # multi-device shard_map; writes go to the aliased outputs, so untouched
    # rows pass through in place.
    @pl.when(jnp.logical_and(last, ok))
    def _apply():
        rd_w = pltpu.make_async_copy(w_any.at[pl.ds(idx, 1)], row, sems.at[0])
        rd_w.start()
        rd_a = pltpu.make_async_copy(acc_any.at[pl.ds(idx, 1)], accrow,
                                     sems.at[1])
        rd_a.start()
        rd_w.wait()
        rd_a.wait()
        g = gsum[...]
        acc_new = accrow[...] + jnp.mean(jnp.square(g), axis=-1, keepdims=True)
        upd = lr * g / jnp.sqrt(acc_new + eps)
        row[...] = row[...] - upd.astype(row.dtype)
        accrow[...] = acc_new.astype(accrow.dtype)
        wr_w = pltpu.make_async_copy(row, w_out.at[pl.ds(idx, 1)], sems.at[0])
        wr_w.start()
        wr_a = pltpu.make_async_copy(accrow, acc_out.at[pl.ds(idx, 1)],
                                     sems.at[1])
        wr_a.start()
        wr_w.wait()
        wr_a.wait()


@functools.partial(jax.jit, static_argnames=("lr", "eps", "interpret"))
def dedup_adagrad_pallas(
    w: jnp.ndarray,       # [rows, D] table (shard or replicated tier)
    acc: jnp.ndarray,     # [rows, 1] adagrad accumulator
    idx: jnp.ndarray,     # [m] destination row per gradient
    g: jnp.ndarray,       # [m, D] row gradients (duplicates allowed)
    valid: jnp.ndarray,   # [m] mask; invalid grads are dropped
    lr: float,
    eps: float,
    interpret: bool = False,
):
    """One fused pass: run detection over pre-sorted indices, duplicate-grad
    accumulation in VMEM (reference order), row-wise adagrad, in-place
    scatter via ``input_output_aliases``. Untouched rows are bitwise
    untouched; touched rows match ``_dedup_apply`` to ~1 ULP."""
    rows, d = w.shape
    m = idx.shape[0]
    sidx = jnp.where(valid, idx, rows).astype(jnp.int32)
    order = jnp.argsort(sidx)                     # invalid sorts to the end
    si = jnp.take(sidx, order)
    # the sorted grads are materialized once up front ([m, D], same cost the
    # reference chain pays) and streamed through the block pipeline with the
    # identity index map, not gathered by a prefetch-driven map (o[i]) next
    # to the ANY/aliased operands: that combination once mis-gathered on
    # devices > 0 under multi-device shard_map in interpret mode
    sg = jnp.take(g, order, axis=0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,   # si
        grid=(m,),
        in_specs=[
            pl.BlockSpec((1, d), lambda i, si: (i, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, d), g.dtype),
            pltpu.VMEM((1, d), w.dtype),
            pltpu.VMEM((1, 1), acc.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    kern = functools.partial(_dedup_kernel, m=m, lr=lr, eps=eps, rows=rows)
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(w.shape, w.dtype),
                   jax.ShapeDtypeStruct(acc.shape, acc.dtype)],
        input_output_aliases={2: 0, 3: 1},   # w, acc updated in place
        interpret=interpret,
    )(si, sg, w, acc)


# ---------------------------------------------------------------------------
# fused cache-tier probe (binary search + hit-masked gather)
# ---------------------------------------------------------------------------


def _probe_kernel(uniq_ref, uvalid_ref, keys_blk, rows_any,
                  hit_out, slot_out, rows_out, rowbuf, sem, *, h):
    i = pl.program_id(0)
    u = uniq_ref[i]
    keys = keys_blk[0, :]
    # rank of u among the sorted keys == searchsorted(keys, u, side='left')
    slot = jnp.minimum(jnp.sum((keys < u).astype(jnp.int32)), h - 1)
    kv = jax.lax.dynamic_slice(keys, (slot,), (1,))[0]
    hit = jnp.logical_and(kv == u, uvalid_ref[i] != 0)
    hit_out[0, 0] = hit.astype(jnp.int32)
    slot_out[0, 0] = slot

    @pl.when(hit)
    def _gather():
        cp = pltpu.make_async_copy(rows_any.at[pl.ds(slot, 1)], rowbuf, sem)
        cp.start()
        cp.wait()
        rows_out[...] = rowbuf[...]

    @pl.when(jnp.logical_not(hit))
    def _zero():
        rows_out[...] = jnp.zeros_like(rows_out)


@functools.partial(jax.jit, static_argnames=("interpret",))
def tier_probe_pallas(
    uniq: jnp.ndarray,    # [n] query ids (the fixed-shape unique set)
    uvalid: jnp.ndarray,  # [n] probe mask (slot validity & not-served-above)
    keys: jnp.ndarray,    # [H] sorted tier keys
    rows: jnp.ndarray,    # [H, D] tier rows (may live off-device)
    interpret: bool = False,
):
    """Fused probe of one cache tier: per query, binary search the sorted
    key vector (VMEM-resident) and DMA the hit row; misses produce exact
    zeros. Returns ``(hit [n] bool, slot [n] int32, rows [n, D])`` with
    ``slot`` clamped like ``cache_probe`` (backward reuses it)."""
    n = uniq.shape[0]
    h, d = rows.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,   # uniq, uvalid
        grid=(n,),
        in_specs=[
            pl.BlockSpec((1, h), lambda i, u, v: (0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((1, 1), lambda i, u, v: (i, 0)),
            pl.BlockSpec((1, 1), lambda i, u, v: (i, 0)),
            pl.BlockSpec((1, d), lambda i, u, v: (i, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, d), rows.dtype),
            pltpu.SemaphoreType.DMA(()),
        ],
    )
    kern = functools.partial(_probe_kernel, h=h)
    hit, slot, out_rows = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((n, 1), jnp.int32),
                   jax.ShapeDtypeStruct((n, 1), jnp.int32),
                   jax.ShapeDtypeStruct((n, d), rows.dtype)],
        interpret=interpret,
    )(uniq.astype(jnp.int32), uvalid.astype(jnp.int32),
      keys.reshape(1, h).astype(jnp.int32), rows)
    return hit[:, 0].astype(bool), slot[:, 0], out_rows


# ---------------------------------------------------------------------------
# fused narrow-row gather + up-projection (picasso_narrow's stitch) and its
# transpose
# ---------------------------------------------------------------------------


def _gproject_kernel(idx_ref, kept_ref, proj_blk, back_any,
                     wide_out, narrow_out, rowbuf, sem, *, m):
    i = pl.program_id(0)
    j = jnp.minimum(idx_ref[i], m - 1)
    ok = jnp.logical_and(kept_ref[i] != 0, idx_ref[i] < m)

    @pl.when(ok)
    def _hit():
        cp = pltpu.make_async_copy(back_any.at[pl.ds(j, 1)], rowbuf, sem)
        cp.start()
        cp.wait()
        narrow_out[...] = rowbuf[...]
        wide_out[...] = jax.lax.dot_general(
            rowbuf[...], proj_blk[...], (((1,), (0,)), ((), ())),
            preferred_element_type=wide_out.dtype)

    @pl.when(jnp.logical_not(ok))
    def _miss():
        narrow_out[...] = jnp.zeros_like(narrow_out)
        wide_out[...] = jnp.zeros_like(wide_out)


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_project_pallas(
    back: jnp.ndarray,   # [m, d] routed-back narrow rows (may live off-device)
    idx: jnp.ndarray,    # [n] routed-buffer slot per position
    kept: jnp.ndarray,   # [n] mask: padded / served-above positions drop out
    proj: jnp.ndarray,   # [d, D] learned up-projection
    interpret: bool = False,
):
    """Fused narrow stitch: per position, DMA the ``[d]`` narrow row out of
    the routed buffer and push it through the VMEM-resident projection on
    the MXU — one grid step per position, no ``[n, d]``-then-``[n, D]``
    op chain. Returns ``(wide [n, D], narrow [n, d])``; not-kept positions
    are exact zeros in both outputs (the caller's where-merge contract, and
    what makes ``narrow`` directly usable as the projection-grad residual)."""
    m, nd = back.shape
    n = idx.shape[0]
    d = proj.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,   # idx, kept
        grid=(n,),
        in_specs=[
            pl.BlockSpec((nd, d), lambda i, ix, k: (0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((1, d), lambda i, ix, k: (i, 0)),
            pl.BlockSpec((1, nd), lambda i, ix, k: (i, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, nd), back.dtype),
            pltpu.SemaphoreType.DMA(()),
        ],
    )
    kern = functools.partial(_gproject_kernel, m=m)
    wide, narrow = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((n, d), back.dtype),
                   jax.ShapeDtypeStruct((n, nd), back.dtype)],
        interpret=interpret,
    )(idx.astype(jnp.int32), kept.astype(jnp.int32), proj, back)
    return wide, narrow


def _gproject_bwd_kernel(si_ref, gw_blk, gn_blk, proj_blk, out_blk):
    i = pl.program_id(0)
    idx = si_ref[i]
    first = jnp.logical_or(i == 0, idx != si_ref[jnp.maximum(i - 1, 0)])
    contrib = jax.lax.dot_general(
        gw_blk[...], proj_blk[...], (((1,), (1,)), ((), ())),
        preferred_element_type=gn_blk.dtype) + gn_blk[...]

    @pl.when(first)
    def _init():
        out_blk[...] = contrib

    @pl.when(jnp.logical_not(first))
    def _acc():
        out_blk[...] += contrib


@functools.partial(jax.jit, static_argnames=("m", "interpret"))
def gather_project_grad_pallas(
    g_wide: jnp.ndarray,    # [n, D] cotangent of the projected rows
    g_narrow: jnp.ndarray,  # [n, d] cotangent of the narrow residual
    idx: jnp.ndarray,       # [n] routed-buffer slot per position
    kept: jnp.ndarray,      # [n] mask
    proj: jnp.ndarray,      # [d, D]
    m: int,                 # routed-buffer rows
    interpret: bool = False,
) -> jnp.ndarray:
    """Transpose of ``gather_project`` w.r.t. the routed buffer:
    ``g_back[j] = sum_{idx[i]=j} kept[i] * (g_wide[i] @ proj^T +
    g_narrow[i])`` — the fold through ``proj^T`` happens per grid step on
    the MXU and duplicate slots run-accumulate in the (sorted-slot) output
    block, so no ``[n, d]`` folded intermediate is materialized. One zero
    ghost position per output slot guarantees every block is written (slots
    nothing routes to come out exactly zero)."""
    n = idx.shape[0]
    nd, d = proj.shape
    keptf = kept.astype(g_wide.dtype)
    # not-kept positions contribute zero; ghosts (one per slot) likewise
    slots = jnp.concatenate([
        jnp.where(kept.astype(bool), idx.astype(jnp.int32), m - 1),
        jnp.arange(m, dtype=jnp.int32)])
    gw = jnp.concatenate([g_wide * keptf[:, None],
                          jnp.zeros((m, g_wide.shape[1]), g_wide.dtype)])
    gn = jnp.concatenate([g_narrow * keptf[:, None],
                          jnp.zeros((m, nd), g_narrow.dtype)])
    order = jnp.argsort(slots, stable=True).astype(jnp.int32)
    si = jnp.take(slots, order)
    sgw = jnp.take(gw, order, axis=0)
    sgn = jnp.take(gn, order, axis=0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,   # si
        grid=(n + m,),
        in_specs=[
            pl.BlockSpec((1, d), lambda i, si: (i, 0)),
            pl.BlockSpec((1, nd), lambda i, si: (i, 0)),
            pl.BlockSpec((nd, d), lambda i, si: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, nd), lambda i, si: (si[i], 0)),
    )
    return pl.pallas_call(
        _gproject_bwd_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, nd), g_wide.dtype),
        interpret=interpret,
    )(si, sgw, sgn, proj)
