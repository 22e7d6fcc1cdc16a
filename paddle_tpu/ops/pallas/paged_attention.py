"""Pallas TPU paged decode attention (ragged, page-table indirected).

The serving-side half of the fused-attention story (reference analog:
fused_multi_transformer_op.cu's masked decode attention — that kernel reads
a dense [B, S_max] cache; this one reads the paged KV pool of
serving/kv_cache.py directly).

One query token per lane attends over that lane's pages, walked through its
int32 page-table row — the pool is never gathered into a dense
``[slots, S_max]`` view, and never sliced to one layer either: the operand
is the WHOLE stacked pool ``[layers, num_pages, page_size, nh, hd]`` and a
static ``layer`` picks the plane inside the BlockSpec index maps.  (A
``k_pages[layer]`` outside the call is a copy of the plane: the operand of
a Mosaic custom call cannot be a fused slice.)  The page table and
per-lane positions ride in as scalar-prefetch operands
(pltpu.PrefetchScalarGridSpec), so the KV index maps pick each grid
step's page straight from the table and Mosaic can start the HBM->VMEM
fetch of page ``(layer, rows[lane, p])`` while the previous page is still
being processed.

Grid is (slots, pages_walked): for each lane the kernel runs the flash
running-softmax (m/l/acc in VMEM scratch) across its pages; pages that are
unmapped (table entry -1) or entirely past the lane's position are skipped
with pl.when (no FLOPs, and the index map clamps their page id to 0 so no
out-of-bounds fetch is issued).  Within the last live page, tokens beyond
``pos`` are masked to -1e30 — matching the dense reference's validity mask
exactly, token by token.

Used by serving/kv_cache.py ``PagedKV.attend`` (the one-token-a-lane case)
through ops/fused.py when FLAGS_use_pallas_kernels is on; the dense-gather
path there stays as the fallback and parity reference.  The kernel only
READS the pool: the current token's K/V rows are scattered by XLA before
the call (``k_pages.at[layer, page, off].set``), in place into the donated
pool, and the call then reads that same buffer — no plane and no pool is
copied around it (tests/test_mosaic_compile.py holds the compiled decode
step to that).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30

from . import DoesNotTile, interpret_default as _interpret_default


def _kernel(rows_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
            acc_ref, m_ref, l_ref, *, sm_scale, page_size, pages_walked):
    lane, p_idx = pl.program_id(0), pl.program_id(1)

    @pl.when(p_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    page = rows_ref[lane, p_idx]
    pos = pos_ref[lane]
    # a page contributes iff it is mapped and starts at or before pos
    live = (page >= 0) & (p_idx * page_size <= pos)

    @pl.when(live)
    def _body():
        # One query token per head: the score and value products run on
        # the VPU as multiply + reduce over the page's own [ps, nh, hd]
        # layout.  Mosaic's matmul wants the batch (head) dimension leading
        # in both operands, and the page has it second.
        q = q_ref[0].astype(jnp.float32) * sm_scale      # [nh, hd]
        k = k_ref[0].astype(jnp.float32)                 # [ps, nh, hd]
        s = jnp.sum(q[None] * k, axis=-1, keepdims=True)  # [ps, nh, 1]
        tok = p_idx * page_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 0)
        s = jnp.where(tok <= pos, s, _NEG_INF)

        m_prev = m_ref[:, :1]                            # [nh, 1]
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=0))
        p = jnp.exp(s - m_new[None])                     # [ps, nh, 1]
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=0)
        v = v_ref[0].astype(jnp.float32)                 # [ps, nh, hd]
        pv = jnp.sum(p * v, axis=0)                      # [nh, hd]
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(p_idx == pages_walked - 1)
    def _finish():
        l = l_ref[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, ...] = (acc_ref[...] / l_safe).astype(o_ref.dtype)


def paged_decode_attention(q, k_pages, v_pages, rows, pos, seq_cap: int,
                           layer: int, sm_scale=None,
                           interpret: bool | None = None):
    """Ragged decode attention over plane ``layer`` of the paged KV pool.

    q: [slots, nh, hd] (one token per lane); k_pages/v_pages:
    [layers, num_pages, page_size, nh, hd] (the WHOLE pool, AFTER the
    current token's scatter); rows: [slots, pages_per_slot] int32 page
    table (-1 = unmapped); pos: [slots] int32 attention extent per lane
    (inclusive); seq_cap: STATIC max extent — only ceil(seq_cap /
    page_size) table columns are walked; layer: STATIC plane of the pool
    (a Python int, closed over by the index maps: it is no operand).
    Returns [slots, nh, hd] in q's dtype.  Raises DoesNotTile for
    untileable geometry (caller falls back to the dense gather).
    """
    slots, nh, hd = q.shape
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
    pages_walked = -(-int(seq_cap) // ps)
    if pages_walked > rows.shape[1]:
        raise DoesNotTile(
            f"paged_decode_attention: seq_cap {seq_cap} needs "
            f"{pages_walked} pages > table width {rows.shape[1]}")
    if ps < 8:
        raise DoesNotTile(
            f"paged_decode_attention: page_size {ps} < 8 sublanes")
    if sm_scale is None:
        sm_scale = 1.0 / (hd ** 0.5)
    if interpret is None:
        interpret = _interpret_default()

    rows = jnp.asarray(rows, jnp.int32)
    pos = jnp.asarray(pos, jnp.int32)
    # the layer axis is squeezed out of the block, so the kernel's body
    # sees the [1, ps, nh, hd] page it always saw; dead (unmapped /
    # past-pos) pages clamp to page 0: the fetch target must be in-bounds
    # even though pl.when skips the math
    page_spec = pl.BlockSpec(
        (None, 1, ps, nh, hd),
        lambda l, p, rows, pos: (layer, jnp.maximum(rows[l, p], 0), 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(slots, pages_walked),
        in_specs=[
            pl.BlockSpec((1, nh, hd),
                         lambda l, p, rows, pos: (l, 0, 0)),
            page_spec,
            page_spec,
        ],
        out_specs=pl.BlockSpec((1, nh, hd),
                               lambda l, p, rows, pos: (l, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((nh, hd), jnp.float32),
            pltpu.VMEM((nh, 128), jnp.float32),
            pltpu.VMEM((nh, 128), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, sm_scale=float(sm_scale), page_size=ps,
                          pages_walked=pages_walked),
        name="paddle_paged_decode_fwd",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((slots, nh, hd), q.dtype),
        interpret=interpret,
    )(rows, pos, q, k_pages, v_pages)
    return out


def _gqa_kernel(rows_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                acc_ref, m_ref, l_ref, *, sm_scale, page_size, pages_walked,
                window, table_cols):
    lane, p_idx = pl.program_id(0), pl.program_id(1)

    @pl.when(p_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    pos = pos_ref[lane]
    col = _first_col(pos, window, page_size) + p_idx
    page = rows_ref[lane, jnp.minimum(col, table_cols - 1)]
    # a page contributes iff it is mapped and starts at or before pos (the
    # walk starts at the first page that meets the window, so none of the
    # walked pages lies wholly behind it)
    live = (page >= 0) & (col < table_cols) & (col * page_size <= pos)

    @pl.when(live)
    def _body():
        nkv, g = q_ref.shape[1], q_ref.shape[2]
        for h in range(nkv):
            # the g query heads of KV head h are the rows of one product
            # with the page's [ps, hd] keys, and of one with its values
            q = q_ref[0, h].astype(jnp.float32) * sm_scale       # [g, hd]
            k = k_ref[0, :, h, :].astype(jnp.float32)            # [ps, hd]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)              # [g, ps]
            tok = col * page_size + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            seen = tok <= pos
            if window:
                seen = seen & (tok > pos - window)
            s = jnp.where(seen, s, _NEG_INF)

            m_prev = m_ref[h][:, :1]                             # [g, 1]
            l_prev = l_ref[h][:, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)                               # [g, ps]
            alpha = jnp.exp(m_prev - m_new)
            l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
            v = v_ref[0, :, h, :].astype(jnp.float32)            # [ps, hd]
            pv = jnp.dot(p, v, preferred_element_type=jnp.float32)
            acc_ref[h] = acc_ref[h] * alpha + pv
            m_ref[h] = jnp.broadcast_to(m_new, m_ref.shape[1:])
            l_ref[h] = jnp.broadcast_to(l_new, l_ref.shape[1:])

    @pl.when(p_idx == pages_walked - 1)
    def _finish():
        l = l_ref[:, :, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, ...] = (acc_ref[...] / l_safe).astype(o_ref.dtype)


def _first_col(pos, window, page_size):
    """The first table column whose page meets ``[pos - window + 1, pos]``
    (0 without a window)."""
    if not window:
        return 0
    return jnp.maximum(pos - window + 1, 0) // page_size


def paged_gqa_decode_attention(q, k_pages, v_pages, rows, pos, seq_cap: int,
                               layer: int, window: int = 0, sm_scale=None,
                               interpret: bool | None = None):
    """``paged_decode_attention`` for a pool of KV heads, each read by
    ``g = nh // nkv`` query heads, and a layer that sees a window.

    q: [slots, nh, hd], query head h reading KV head h // g; k_pages /
    v_pages: [layers, num_pages, page_size, nkv, hd] (the WHOLE pool, after
    the current token's scatter); rows, pos, seq_cap, layer as there.
    ``window`` (STATIC; 0 = none): a lane sees the keys at ``(pos - window,
    pos]``, and the kernel walks only the table columns whose pages meet
    them: ``(window - 2) // page_size + 2`` grid steps a lane instead of
    ``ceil(seq_cap / page_size)``.  A page's keys are read once for the g
    query heads of their KV head: those are the rows of one [g, hd] x [hd,
    ps] product.  Returns [slots, nh, hd] in q's dtype.  A call of its own
    beside ``paged_decode_attention`` (same file, same walk): that one's
    operands, name and VPU body are what the one-KV-head-a-query-head
    engines were measured with, and stay as they are.
    """
    slots, nh, hd = q.shape
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
    ps, nkv = k_pages.shape[2], k_pages.shape[3]
    if k_pages.shape[4] != hd or nh % nkv:
        raise DoesNotTile(
            f"paged_gqa_decode_attention: pool heads {k_pages.shape[3:]} "
            f"do not group query heads ({nh}, {hd})")
    g = nh // nkv
    pages_walked = -(-int(seq_cap) // ps)
    if pages_walked > rows.shape[1]:
        raise DoesNotTile(
            f"paged_gqa_decode_attention: seq_cap {seq_cap} needs "
            f"{pages_walked} pages > table width {rows.shape[1]}")
    table_cols = pages_walked
    if window:
        pages_walked = min(pages_walked, (window - 2) // ps + 2)
    if ps < 8:
        raise DoesNotTile(
            f"paged_gqa_decode_attention: page_size {ps} < 8 sublanes")
    if sm_scale is None:
        sm_scale = 1.0 / (hd ** 0.5)
    if interpret is None:
        interpret = _interpret_default()

    rows = jnp.asarray(rows, jnp.int32)
    pos = jnp.asarray(pos, jnp.int32)

    def page_of(l, p, rows, pos):
        col = jnp.minimum(_first_col(pos[l], window, ps) + p, table_cols - 1)
        return (layer, jnp.maximum(rows[l, col], 0), 0, 0, 0)

    page_spec = pl.BlockSpec((None, 1, ps, nkv, hd), page_of)
    lane_spec = pl.BlockSpec((1, nkv, g, hd),
                             lambda l, p, rows, pos: (l, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(slots, pages_walked),
        in_specs=[lane_spec, page_spec, page_spec],
        out_specs=lane_spec,
        scratch_shapes=[
            pltpu.VMEM((nkv, g, hd), jnp.float32),
            pltpu.VMEM((nkv, g, 128), jnp.float32),
            pltpu.VMEM((nkv, g, 128), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_gqa_kernel, sm_scale=float(sm_scale),
                          page_size=ps, pages_walked=pages_walked,
                          window=window, table_cols=table_cols),
        name="paddle_paged_gqa_decode_fwd",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((slots, nkv, g, hd), q.dtype),
        interpret=interpret,
    )(rows, pos, q.reshape(slots, nkv, g, hd), k_pages, v_pages)
    return out.reshape(slots, nh, hd)


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
