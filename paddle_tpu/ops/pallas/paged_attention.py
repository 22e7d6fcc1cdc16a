"""Pallas TPU paged decode attention (ragged, page-table indirected).

The serving-side half of the fused-attention story (reference analog:
fused_multi_transformer_op.cu's masked decode attention — that kernel reads
a dense [B, S_max] cache; this one reads the paged KV pool of
serving/kv_cache.py directly).

The queries of a lane that share ONE last key attend over that lane's
pages: one query token a lane (the one-token decode step, full and window
layers), or the C queries of a block-generating step's block, which all
see the lane's keys to the block's end (``paged_gqa_decode_attention`` with
q ``[slots, C, nh, hd]``: the C x g queries of a KV head are the rows of
one product, since PR 37).  A chunk with a last key a QUERY (speculative
verification: causal inside the chunk) or on a window layer is not asked
of this file: ``PagedKV`` gathers for it.  The pages are walked through the
lane's int32 page-table row — the pool is never gathered into a dense
``[slots, S_max]`` view, and never sliced to one layer either: the operand
is the WHOLE stacked pool ``[layers, num_pages, page_size, heads, hd]``,
left where it lies in HBM (``memory_space=pl.ANY``), and the copies the
kernel makes itself pick the plane.  (A ``k_pages[layer]`` outside the
call is a copy of the plane: the operand of a Mosaic custom call cannot be
a fused slice.)  The page table and the per-lane positions ride in as
scalar-prefetch operands (pltpu.PrefetchScalarGridSpec), so the scalar
core reads a lane's extent and its page ids before anything is fetched.
``layer`` is static to the caller and no operand of the kernel: the table
the kernel gets counts its pages through the planes (``layer * num_pages +
page``, one small fusion a step for all layers), so one kernel serves
every layer and a decode executable traces and lowers it once (``_call``
is jitted).

Grid is ``(slots,)``: one grid step a lane, and the page walk is a loop
inside it (``_walk``) over the lane's OWN extent only, from its first
column (0, or the first column that meets a window) to the column of its
position, a trip count read from ``pos_ref`` / ``rows_ref``; a released
lane (row -1) or one never armed walks nothing and writes zeros.  The walk
goes by blocks of several pages (``_block_pages``: about 128 keys, 256
under a block step's 32 rows a product, from the call's shapes alone): a
block's mapped pages are copied HBM->VMEM by
``pltpu.make_async_copy``, one DMA a page, all started together into one
of two buffers, and block b + 1's are started before block b is computed
on.  A page that is unmapped (table entry -1) or entirely past the lane's
position is never the source of a copy; its keys in the buffer, and the
tokens beyond ``pos`` in the last live page, are masked to -1e30 —
matching the dense reference's validity mask exactly, token by token.  The
flash running-softmax (m/l/acc in VMEM scratch, float32) runs across the
blocks.  Both calls of this file take that walk; their bodies differ
(``_attend``: multiply and reduce on the VPU over ``[keys, nh, hd]``;
``_gqa_attend``: one ``[rows, hd] x [hd, keys]`` product a KV head, its
rows the head's g query heads of every query of the lane).

A second walk stands beside it, chosen by the pool's shape alone
(``_page_is_tiles``): Mosaic lets a DMA cut only whole tiles out of an HBM
operand, and a page ``[page_size, 12, 64]`` (GPT-2 124M's) is none.  Such
pools keep the walk the file had before PR 35 (``_grid_kernel``): grid
``(slots, columns)``, one page a grid step, fetched by the pipeline
through a ``BlockSpec`` of a whole page, every column visited whatever is
mapped and the dead ones skipped by ``pl.when``; same bodies, same masks,
same table that counts through the planes.  Its time does not follow the
live lanes; the way off it is the pool's layout (ROADMAP Speed 13).
Interpreted on the CPU, every pool takes the loop.

Used by serving/kv_cache.py ``PagedKV.attend`` (one last key a lane: the
one-token step, and the block step's ``limits`` [slots]) through
ops/fused.py when FLAGS_use_pallas_kernels is on; the dense-gather path
there stays as the fallback, the parity reference and the path of the
other chunks.  The kernel only READS the pool: the current token's (or
block's) K/V rows are scattered by XLA before the call
(``k_pages.at[layer, page, off].set``), in place into the donated pool,
and the call then reads that same buffer — no plane and no pool is copied
around it (tests/test_mosaic_compile.py holds the compiled decode step
and block step to that, and the calls to their names, operands and
results, by which the benchmark finds them on a trace).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30

from . import DoesNotTile, interpret_default as _interpret_default


# what the walk's four VMEM buffers (K and V, two blocks each) may take
# together, counted as Mosaic lays a page out (heads padded to the dtype's
# sublane tile, the head's width to 128 lanes); the bodies' float32
# temporaries of a block live beside them under the 16 MiB scoped limit
_VMEM_BUDGET = 4 * 1024 * 1024
# a block is as many pages as hold this many keys, and no fewer than this
# many pages (their copies fly together, and a loop turn's waits and
# softmax update are shared among them), if the budget allows: on a v5e
# 8 pages of 16 keys beat 1, 2 and 16 at chat's geometry, and 4 pages of
# 64 beat 1, 2 and 8 at repochat's (PERF.md section 6, PR 35).  From
# _WIDE_ROWS rows a product on (a block step's 4 queries x 8 heads a KV
# head) a turn's update of the running softmax is four times the work, and
# a block of twice the keys shares it: 16 pages of 16 beat 4, 8 and 32 by a
# tenth at the blockgen cell's geometry (PERF.md section 6, PR 37)
_BLOCK_KEYS = 128
_BLOCK_PAGES = 4
_WIDE_ROWS = 32


def _page_is_tiles(k_pages) -> bool:
    """Whether a DMA can cut one page out of the pool: Mosaic slices an
    HBM operand by whole tiles of its layout only, the whole dimension
    included ("Slice shape along dimension 3 must be aligned to tiling
    (8), but is 12"), so a page's [heads, hd] has to be heads of a
    multiple of 128 lanes, and 2, 4 or a multiple of 8 of them."""
    heads, hd = k_pages.shape[3:]
    return hd % 128 == 0 and (heads % 8 == 0 or heads in (2, 4))


def _block_pages(k_pages, cols: int, interpret: bool, rows: int = 1) -> int:
    """Pages a block of the walk holds, from the pool's shape, the
    columns a lane can walk and the rows of a KV head's products: enough
    for _BLOCK_KEYS keys (twice that from _WIDE_ROWS rows on) and at least
    _BLOCK_PAGES, no more than the walk is long, and no more than fit
    _VMEM_BUDGET twice over for K and for V (8 pages of 16 x 16 x 128
    bf16; 4 pages of 64 x 4 x 128; 16 pages of 16 x 4 x 128 under a block
    step's 32 rows).
    0, the grid's page walk (``_grid_kernel``), where the chip is the
    target and no DMA can cut a page out of the pool (GPT-2's 12 heads of
    64).  DoesNotTile where two pages for K and for V are over the
    budget."""
    if not interpret and not _page_is_tiles(k_pages):
        return 0
    ps, heads, hd = k_pages.shape[2:]
    itemsize = k_pages.dtype.itemsize
    sublanes = 8 * max(1, 4 // itemsize)
    page_bytes = (ps * -(-heads // sublanes) * sublanes
                  * -(-hd // 128) * 128 * itemsize)
    fit = _VMEM_BUDGET // (4 * page_bytes)
    if fit < 1:
        raise DoesNotTile(
            f"paged decode attention: two pages of {k_pages.shape[2:]} "
            f"{k_pages.dtype} for K and for V are over the walk's "
            f"{_VMEM_BUDGET >> 20} MiB of VMEM")
    keys = _BLOCK_KEYS * (2 if rows >= _WIDE_ROWS else 1)
    return max(1, min(max(-(-keys // ps), _BLOCK_PAGES), fit, cols))


def _walk(rows_ref, pos_ref, k_hbm, v_hbm, k_buf, v_buf, sems, ends_ref,
          attend, *, page_size, block_pages, table_cols, window):
    """One lane's page walk: the loop, the copies and the masks' positions
    of both kernels.

    The lane's extent is the table columns from the first whose page
    meets the window (0 without one) to the column of ``pos`` (bounded by
    the table), walked in blocks of ``block_pages`` columns.  A block's
    mapped pages are copied from the pools, where they lie in HBM, into
    one of two VMEM buffers: one DMA a page, all of a block's started
    together, and block b + 1's before block b is computed on.  A page id
    of the table counts through the planes (``layer * num_pages + page``,
    made by ``_call``), so the kernel is the same for every layer.  A
    column past the extent or unmapped (-1) is never the source of a
    copy.

    ``attend(k_ref, v_ref, seen)`` gets the block's buffers ``[block_pages
    * page_size, heads, hd]`` and ``seen(shape, axis)``, the bool array of
    ``shape`` that says along ``axis`` which of the block's keys the lane
    sees: those of a copied page, at or before ``pos`` and inside the
    window (what was not copied holds stale keys).  A lane with ``pos <
    0`` or whose first column is unmapped (a released lane) walks
    nothing and reads zero, whatever its later columns map: there alone
    the walk departs from the dense reference (``PagedKV._attend`` masks
    by ``ids >= 0`` and would attend the later pages), and no live lane
    is such a one, because ``PagedKV.slide_window`` never lets go of the
    first column that meets the window (tests/test_mellum_serving.py
    holds it to that).  ``ends_ref`` (SMEM ``[2, 2, block_pages]``) keeps, a buffer
    and page, what was started: the position its keys are seen up to, and
    its page id.  Scalars are ``lax`` on int32: the package turns x64 on
    where it runs on the CPU.
    """
    i32 = np.int32
    ps, P, cols = i32(page_size), i32(block_pages), i32(table_cols)
    num_pages = i32(k_hbm.shape[1])
    lane = pl.program_id(0)
    pos = pos_ref[lane]
    first = _first_col(pos, window, page_size)
    last = lax.min(lax.div(lax.max(pos, i32(0)), ps), cols - 1)
    live = ((pos >= 0) & (first <= last)
            & (rows_ref[lane, lax.min(first, cols - 1)] >= 0))
    nblocks = lax.select(live, lax.div(last - first + P, P), i32(0))

    # rows of a buffer that no copy has reached yet must not hold what the
    # chip left there: a masked key weighs 0, and 0 x NaN is NaN
    @pl.when(lane == 0)
    def _clear():
        v_buf[...] = jnp.zeros_like(v_buf)

    def copies(slot, j, page):
        """The two copies of `page` into row j of buffer `slot`."""
        rows = pl.ds(pl.multiple_of(j * ps, page_size), page_size)
        plane, page = lax.div(page, num_pages), lax.rem(page, num_pages)
        return [pltpu.make_async_copy(hbm.at[plane, page],
                                      buf.at[slot, rows], sems.at[slot, i])
                for i, (hbm, buf) in enumerate(((k_hbm, k_buf),
                                                (v_hbm, v_buf)))]

    def block(b, carry):
        # start block b's copies, then compute on block b - 1 under them
        @pl.when(b < nblocks)
        def _start_copies():
            slot = lax.rem(b, i32(2))

            def start(j, carry):
                col = first + b * P + j
                page = rows_ref[lane, lax.min(col, cols - 1)]
                copied = (col <= last) & (page >= 0)
                # the last position the page's keys are seen up to (-1:
                # not at all), and the page that is copied (-1: none)
                ends_ref[slot, 0, j] = lax.select(copied, pos, i32(-1))
                ends_ref[slot, 1, j] = lax.select(copied, page, i32(-1))

                @pl.when(copied)
                def _start():
                    for dma in copies(slot, j, page):
                        dma.start()
                return carry

            lax.fori_loop(i32(0), P, start, 0)

        @pl.when(b > 0)
        def _attend():
            slot = lax.rem(b - 1, i32(2))

            def wait(j, carry):
                page = ends_ref[slot, 1, j]

                @pl.when(page >= 0)
                def _wait():
                    for dma in copies(slot, j, page):
                        dma.wait()
                return carry

            lax.fori_loop(i32(0), P, wait, 0)
            tok0 = (first + (b - 1) * P) * ps

            def seen(shape, axis):
                tok = tok0 + lax.broadcasted_iota(jnp.int32, shape, axis)
                page = list(shape)
                page[axis] = page_size
                end = lax.concatenate(
                    [lax.full(page, ends_ref[slot, 0, j], jnp.int32)
                     for j in range(block_pages)], axis)
                if window:
                    return (tok <= end) & (tok > pos - i32(window))
                return tok <= end

            attend(k_buf.at[slot], v_buf.at[slot], seen)

        return carry

    lax.fori_loop(i32(0), lax.select(live, nblocks + 1, i32(0)), block, 0)


def _first_col(pos, window: int, page_size: int):
    """The first table column whose page meets ``[pos - window + 1, pos]``
    (0 without a window, and for a ``pos`` below 0)."""
    if not window:
        return np.int32(0)
    return lax.div(lax.max(pos - np.int32(window - 1), np.int32(0)),
                   np.int32(page_size))


def _init(acc_ref, m_ref, l_ref):
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)


def _finish(o_ref, acc_ref, l_ref):
    l = l_ref[(slice(None),) * (len(l_ref.shape) - 1) + (slice(0, 1),)]
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o_ref[0, ...] = (acc_ref[...] / l_safe).astype(o_ref.dtype)


def _attend(q_ref, k_ref, v_ref, seen, acc_ref, m_ref, l_ref, sm_scale):
    """``paddle_paged_decode_fwd``'s body: the keys ``[keys, nh, hd]`` of
    a block (or a page) into the running softmax of a lane's ``[nh, hd]``
    query."""
    # One query token per head: the score and value products run on
    # the VPU as multiply + reduce over the pages' own [keys, nh, hd]
    # layout.  Mosaic's matmul wants the batch (head) dimension leading
    # in both operands, and a page has it second.
    q = q_ref[0].astype(jnp.float32) * sm_scale      # [nh, hd]
    k = k_ref[...].astype(jnp.float32)               # [keys, nh, hd]
    s = jnp.sum(q[None] * k, axis=-1, keepdims=True)  # [keys, nh, 1]
    s = jnp.where(seen(s.shape, 0), s, _NEG_INF)

    m_prev = m_ref[:, :1]                            # [nh, 1]
    l_prev = l_ref[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=0))
    p = jnp.exp(s - m_new[None])                     # [keys, nh, 1]
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_prev + jnp.sum(p, axis=0)
    v = v_ref[...].astype(jnp.float32)               # [keys, nh, hd]
    pv = jnp.sum(p * v, axis=0)                      # [nh, hd]
    acc_ref[...] = acc_ref[...] * alpha + pv
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)


def _gqa_attend(q_ref, k_ref, v_ref, seen, acc_ref, m_ref, l_ref, sm_scale):
    """``paddle_paged_gqa_decode_fwd``'s body: the keys ``[keys, nkv,
    hd]`` of a block (or a page) into the running softmax of a lane's
    ``[nkv, rows, hd]`` queries: ``rows`` is the g query heads of a KV
    head, of each of the lane's queries (one, or a block step's C)."""
    nkv, g = q_ref.shape[1], q_ref.shape[2]
    sees = seen((g, k_ref.shape[0]), 1)
    for h in range(nkv):
        # the g query heads of KV head h are the rows of one product
        # with the block's [keys, hd] keys, and of one with its values
        q = q_ref[0, h].astype(jnp.float32) * sm_scale       # [g, hd]
        k = k_ref[:, h, :].astype(jnp.float32)               # [keys, hd]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)              # [g, keys]
        s = jnp.where(sees, s, _NEG_INF)

        m_prev = m_ref[h][:, :1]                             # [g, 1]
        l_prev = l_ref[h][:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)                               # [g, keys]
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[:, h, :].astype(jnp.float32)               # [keys, hd]
        pv = jnp.dot(p, v, preferred_element_type=jnp.float32)
        acc_ref[h] = acc_ref[h] * alpha + pv
        m_ref[h] = jnp.broadcast_to(m_new, m_ref.shape[1:])
        l_ref[h] = jnp.broadcast_to(l_new, l_ref.shape[1:])


def _walk_kernel(rows_ref, pos_ref, q_ref, k_hbm, v_hbm, o_ref,
                 k_buf, v_buf, sems, ends_ref, acc_ref, m_ref, l_ref, *,
                 attend, sm_scale, **walk):
    """Grid ``(slots,)``: a lane's pages walked in ``_walk``'s loop."""
    _init(acc_ref, m_ref, l_ref)
    _walk(rows_ref, pos_ref, k_hbm, v_hbm, k_buf, v_buf, sems, ends_ref,
          lambda k_ref, v_ref, seen: attend(
              q_ref, k_ref, v_ref, seen, acc_ref, m_ref, l_ref, sm_scale),
          **walk)
    _finish(o_ref, acc_ref, l_ref)


def _grid_kernel(rows_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                 acc_ref, m_ref, l_ref, *, attend, sm_scale, page_size,
                 table_cols, window, walked):
    """Grid ``(slots, walked)``: one page a grid step, fetched by the
    pipeline through a ``BlockSpec`` of a whole ``[page_size, heads, hd]``
    page (that Mosaic takes at any heads), every column a lane can walk
    visited whatever is mapped, a dead one skipped by ``pl.when``.  What
    the file did before PR 35, kept for pools whose page no DMA can cut
    out (``_page_is_tiles``): there its time does not follow the live
    lanes.  Who contributes is the walk's rule, but for a lane whose
    first column is unmapped and a later one is not: here the later pages
    are attended, as the dense reference does (no live lane is such a
    one: ``PagedKV.slide_window`` keeps the first column mapped)."""
    lane, step = pl.program_id(0), pl.program_id(1)

    @pl.when(step == 0)
    def _():
        _init(acc_ref, m_ref, l_ref)

    pos = pos_ref[lane]
    # behind a window the walked columns may run past the table's end
    col = _first_col(pos, window, page_size) + step
    page = rows_ref[lane, jnp.minimum(col, table_cols - 1) if window else col]

    @pl.when((page >= 0) & (col < table_cols) & (col * page_size <= pos))
    def _():
        def seen(shape, axis):
            tok = col * page_size + lax.broadcasted_iota(
                jnp.int32, shape, axis)
            if window:
                return (tok <= pos) & (tok > pos - window)
            return tok <= pos

        attend(q_ref, k_ref, v_ref, seen, acc_ref, m_ref, l_ref, sm_scale)

    @pl.when(step == walked - 1)
    def _():
        _finish(o_ref, acc_ref, l_ref)


@functools.partial(jax.jit, static_argnames=(
    "attend", "name", "stats", "sm_scale", "interpret", "page_size",
    "block_pages", "table_cols", "window", "walked"))
def _call(q, k_pages, v_pages, rows, pos, layer, *, attend, name, stats,
          sm_scale, interpret, page_size, block_pages, table_cols, window,
          walked):
    """The ``pallas_call`` both kernels are: the page table and the
    positions prefetched to the scalar core, a lane's query and result as
    blocks, and the running softmax's ``acc`` (the query's shape), ``m``
    and ``l`` (``stats``) in VMEM.  With ``block_pages`` the grid is
    ``(slots,)``, the two pools are whole and left in HBM, and the walk's
    buffers and semaphores stand beside the softmax's; with 0 it is the
    grid's walk over ``walked`` columns a lane (``_grid_kernel``), the
    pools blocked by pages and their planes laid end to end.

    ``layer`` is an argument and not a constant of the kernel: the table
    handed to the kernel counts its pages through the planes, and under
    this ``jit`` a decode executable traces and lowers the kernel once,
    not once a layer (24 traces were 1.9 s of chat's start-up on the chip
    machine: PERF.md section 6, PR 35)."""
    lane = q.shape[1:]
    num_pages = k_pages.shape[1]
    rows, pos = jnp.asarray(rows, jnp.int32), jnp.asarray(pos, jnp.int32)
    # page ids counted through the planes; unmapped stays unmapped
    rows = jnp.where(rows >= 0, rows + layer * num_pages, -1)
    lane_spec = pl.BlockSpec(
        (1,) + lane, lambda l, *_: (l,) + (0,) * len(lane))
    softmax = [pltpu.VMEM(lane, jnp.float32),
               pltpu.VMEM(stats, jnp.float32),
               pltpu.VMEM(stats, jnp.float32)]
    where = dict(page_size=page_size, table_cols=table_cols, window=window)
    if block_pages:
        kernel = functools.partial(_walk_kernel, block_pages=block_pages,
                                   **where)
        grid = (q.shape[0],)
        pool_spec = pl.BlockSpec(memory_space=pl.ANY)
        buf = (2, block_pages * page_size) + k_pages.shape[3:]
        scratch = [pltpu.VMEM(buf, k_pages.dtype),
                   pltpu.VMEM(buf, v_pages.dtype),
                   pltpu.SemaphoreType.DMA((2, 2)),
                   pltpu.SMEM((2, 2, block_pages), jnp.int32)] + softmax
        # lanes in order on one core: lane 0 clears the buffers
        params = pltpu.CompilerParams(dimension_semantics=("arbitrary",))
    else:
        kernel = functools.partial(_grid_kernel, walked=walked, **where)
        grid = (q.shape[0], walked)
        # the planes end to end: a page id of the table is a row of this
        # view (the leading dimensions are not tiled: no copy is made)
        k_pages, v_pages = (p.reshape((-1,) + p.shape[2:])
                            for p in (k_pages, v_pages))

        def page_map(l, step, rows, pos):
            # dead (unmapped / past-pos) columns clamp to page 0: the
            # fetch target must be in-bounds even though pl.when skips
            # the math
            col = _first_col(pos[l], window, page_size) + step
            if window:
                col = jnp.minimum(col, table_cols - 1)
            return (jnp.maximum(rows[l, col], 0), 0, 0, 0)

        pool_spec = pl.BlockSpec((None,) + k_pages.shape[1:], page_map)
        scratch, params = softmax, None
    return pl.pallas_call(
        functools.partial(kernel, attend=attend, sm_scale=sm_scale),
        name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=grid,
            in_specs=[lane_spec, pool_spec, pool_spec],
            out_specs=lane_spec, scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=params,
        interpret=interpret,
    )(rows, pos, q, k_pages, v_pages)


def paged_decode_attention(q, k_pages, v_pages, rows, pos, seq_cap: int,
                           layer: int, sm_scale=None,
                           interpret: bool | None = None):
    """Ragged decode attention over plane ``layer`` of the paged KV pool.

    q: [slots, nh, hd] (one token per lane); k_pages/v_pages:
    [layers, num_pages, page_size, nh, hd] (the WHOLE pool, AFTER the
    current token's scatter); rows: [slots, pages_per_slot] int32 page
    table (-1 = unmapped); pos: [slots] int32 attention extent per lane
    (inclusive); seq_cap: STATIC max extent: no lane walks past table
    column ceil(seq_cap / page_size); layer: STATIC plane of the pool
    (a Python int: it is no operand of the kernel, whose page table counts
    pages through the planes).
    Returns [slots, nh, hd] in q's dtype.  Raises DoesNotTile for
    untileable geometry (caller falls back to the dense gather).
    """
    _, nh, hd = q.shape
    if k_pages.ndim != 5 or v_pages.shape != k_pages.shape:
        raise ValueError(
            "paged_decode_attention takes the whole pools [layers, "
            f"num_pages, page_size, nh, hd], got {k_pages.shape} and "
            f"{v_pages.shape}")
    layer = int(layer)
    if not 0 <= layer < k_pages.shape[0]:
        raise ValueError(
            f"paged_decode_attention: layer {layer} outside a pool of "
            f"{k_pages.shape[0]} layers")
    ps = k_pages.shape[2]
    if k_pages.shape[3] != nh or k_pages.shape[4] != hd:
        raise DoesNotTile(
            f"paged_decode_attention: pool heads {k_pages.shape[3:]} != "
            f"query heads ({nh}, {hd})")
    table_cols = -(-int(seq_cap) // ps)
    if table_cols > rows.shape[1]:
        raise DoesNotTile(
            f"paged_decode_attention: seq_cap {seq_cap} needs "
            f"{table_cols} pages > table width {rows.shape[1]}")
    if ps < 8:
        raise DoesNotTile(
            f"paged_decode_attention: page_size {ps} < 8 sublanes")
    if sm_scale is None:
        sm_scale = 1.0 / (hd ** 0.5)
    if interpret is None:
        interpret = _interpret_default()
    return _call(
        q, k_pages, v_pages, rows, pos, layer, attend=_attend,
        name="paddle_paged_decode_fwd", stats=(nh, 128),
        sm_scale=float(sm_scale), interpret=interpret, page_size=ps,
        table_cols=table_cols, window=0, walked=table_cols,
        block_pages=_block_pages(k_pages, table_cols, interpret))


def paged_gqa_decode_attention(q, k_pages, v_pages, rows, pos, seq_cap: int,
                               layer: int, window: int = 0, sm_scale=None,
                               interpret: bool | None = None):
    """``paged_decode_attention`` for a pool of KV heads, each read by
    ``g = nh // nkv`` query heads, a layer that sees a window, and a lane
    that brings several queries to the same keys.

    q: [slots, nh, hd], query head h reading KV head h // g; k_pages /
    v_pages: [layers, num_pages, page_size, nkv, hd] (the WHOLE pool, after
    the current token's scatter); rows, pos, seq_cap, layer as there.
    ``window`` (STATIC; 0 = none): a lane sees the keys at ``(pos - window,
    pos]``, and its walk starts at the first table column whose page meets
    them: at most ``(window - 2) // page_size + 2`` columns.  A block's
    keys are read once for the g query heads of their KV head: those are
    the rows of one [g, hd] x [hd, keys] product.  Returns [slots, nh, hd]
    in q's dtype.

    q: [slots, C, nh, hd] is C queries a lane that ALL see the lane's keys
    up to ``pos`` (a block-generating step: ``pos`` is the block's last
    position, whose K/V the caller has scattered too).  One mask a lane,
    so the C x g queries of a KV head are the rows of the same product:
    the query is laid [slots, nkv, C * g, hd] (row c * g + j of KV head h
    is query c, head h * g + j) and the result laid back [slots, C, nh,
    hd]; same walk, same body, another row count.  No window there (a
    window is measured from each query's own position: a mask a row).

    A call of its own beside ``paged_decode_attention`` (same file, same
    walk): that one's operands, name and VPU body are what the
    one-KV-head-a-query-head engines were measured with, and stay as they
    are.
    """
    slots, (nh, hd) = q.shape[0], q.shape[-2:]
    if k_pages.ndim != 5 or v_pages.shape != k_pages.shape:
        raise ValueError(
            "paged_gqa_decode_attention takes the whole pools [layers, "
            f"num_pages, page_size, nkv, hd], got {k_pages.shape} and "
            f"{v_pages.shape}")
    layer, window = int(layer), int(window)
    if not 0 <= layer < k_pages.shape[0]:
        raise ValueError(
            f"paged_gqa_decode_attention: layer {layer} outside a pool of "
            f"{k_pages.shape[0]} layers")
    if q.ndim == 4 and window:
        raise ValueError(
            "paged_gqa_decode_attention: several queries a lane share one "
            "mask, which a window (from each query's own position) is not")
    ps, nkv = k_pages.shape[2], k_pages.shape[3]
    if k_pages.shape[4] != hd or nh % nkv:
        raise DoesNotTile(
            f"paged_gqa_decode_attention: pool heads {k_pages.shape[3:]} "
            f"do not group query heads ({nh}, {hd})")
    g = nh // nkv
    table_cols = -(-int(seq_cap) // ps)
    if table_cols > rows.shape[1]:
        raise DoesNotTile(
            f"paged_gqa_decode_attention: seq_cap {seq_cap} needs "
            f"{table_cols} pages > table width {rows.shape[1]}")
    if ps < 8:
        raise DoesNotTile(
            f"paged_gqa_decode_attention: page_size {ps} < 8 sublanes")
    if sm_scale is None:
        sm_scale = 1.0 / (hd ** 0.5)
    if interpret is None:
        interpret = _interpret_default()
    # the rows of a KV head's products: its g query heads, of every query
    # of the lane (of its one query: the transposes move an axis of 1)
    lanes = q.reshape(slots, -1, nkv, g, hd).transpose(0, 2, 1, 3, 4) \
        .reshape(slots, nkv, -1, hd)
    # the columns a lane can walk: a window meets this many pages at most
    walked = min(table_cols, (window - 2) // ps + 2) if window else table_cols
    out = _call(
        lanes, k_pages, v_pages, rows, pos, layer,
        attend=_gqa_attend, name="paddle_paged_gqa_decode_fwd",
        stats=lanes.shape[1:3] + (128,), sm_scale=float(sm_scale),
        interpret=interpret, page_size=ps, table_cols=table_cols,
        window=window, walked=walked,
        block_pages=_block_pages(k_pages, walked, interpret,
                                 rows=lanes.shape[2]))
    return out.reshape(slots, nkv, -1, g, hd).transpose(0, 2, 1, 3, 4) \
        .reshape(q.shape)


def sharded_paged_decode_attention(q, k_pages, v_pages, rows, pos,
                                   seq_cap: int, layer: int, mesh,
                                   head_axis, sm_scale=None,
                                   interpret: bool | None = None):
    """paged_decode_attention under shard_map: the pool's head axis is
    sharded over ``head_axis`` (layout.kv_page_spec() / the models' "mp"
    pin), the page table and positions are replicated, and each shard
    runs the kernel on its LOCAL heads — decode attention has no
    cross-head reduction, so no collectives are needed."""
    from jax.sharding import PartitionSpec as P

    nh = q.shape[1]
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    tp = sizes.get(head_axis, 1)
    if tp <= 1:
        head_axis = None     # whole heads on every device of the mesh
    if nh % tp:
        raise DoesNotTile(
            f"sharded paged_decode_attention: heads {nh} % tp {tp} != 0")

    def body(ql, kl, vl, rl, pl_):
        return paged_decode_attention(ql, kl, vl, rl, pl_, seq_cap, layer,
                                      sm_scale=sm_scale,
                                      interpret=interpret)

    f = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(None, head_axis, None),
                  P(None, None, None, head_axis, None),
                  P(None, None, None, head_axis, None),
                  P(None, None), P(None)),
        out_specs=P(None, head_axis, None), check_vma=False)
    return f(q, k_pages, v_pages, rows, pos)
