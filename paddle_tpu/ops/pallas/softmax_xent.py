"""Pallas TPU fused softmax cross-entropy (log-softmax + label gather,
forward AND backward in-kernel).

Reference analog: softmax_with_cross_entropy_op.cu — the fused loss that
kept Fluid's LM heads from materializing log-probabilities.  The XLA
composite in ops/fused.py computes max / lse / gather as separate HBM
passes over the [N, V] logits, and under autodiff keeps a float32 [N, V]
between them.  Here a grid step owns whole rows of the logits: `pick_blocks`
takes the row block from the call's shapes (n, v, itemsize, forward or
backward), the block [block_r, V] is fetched once, and the vocabulary is
walked INSIDE the kernel, a `fori_loop` over column chunks of the resident
block plus a tail.  The grid is (n // block_r,): 512 steps forward and
1,024 backward at GPT-2's 16384 x 50304 bf16.
- forward: two walks over the resident rows, the row maximum, then the sum
  of exponentials and the picked logit, so each element sees one `exp`.
  The running maximum, sum and pick are [block_r, 128] float32, folded
  element-wise a lane group at a time; the cross-lane reductions happen
  once a row block, not once a chunk.
- backward: one walk that forms (softmax - onehot) * g chunk by chunk from
  the saved logsumexp, without a resident [N, V] softmax.
- a vocabulary so wide that the narrowest row block outgrows the VMEM
  budget (backward: past 91k columns) gets that block and a scoped limit
  raised to hold it; past `_VMEM_CEILING` (222k columns) the call raises
  DoesNotTile, ops/fused.py counts a fallback and takes the composite.
  No column axis on the grid: one path for every shape the kernel accepts.

Hard labels only (soft_label=False — the ops/fused.py gate routes soft
labels to XLA); `ignore_index` rows produce loss 0 and gradient 0.  The
label gather is a select against a lane iota (TPU has no in-kernel
gather).  The vocab axis is padded to a lane multiple (128) with -1e30 by
the wrapper — exp underflows to exactly 0, so padding never perturbs the
loss; rows are padded to the type's sublane multiple with ignore_index
rows.  All math in float32 regardless of input dtype.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import (DoesNotTile, im as _im,
               interpret_default as _interpret_default)

_NEG_INF = -1e30
_LANES = 128
# bytes a row of a lane-replicated float32 (or int32) row vector takes
_ROW_BYTES = _LANES * 4
# what one grid step may keep in VMEM by `_vmem_bytes`: Mosaic's scoped
# limit on a v5e is 16 MiB, and the estimate leaves the compiler a quarter
_VMEM_SCOPED = 16 * 1024 * 1024
_VMEM_BUDGET = 12 * 1024 * 1024
# the scoped limit is raised only for a vocabulary whose narrowest row
# block outgrows the budget, and never past this: a quarter of a v5e's
# 128 MiB of VMEM, and half of the smallest VMEM of a later chip
_VMEM_CEILING = 32 * 1024 * 1024
# float32 elements of the tile [block_r, chunk] one iteration of a walk
# computes on.  Measured on a v5e at 16384 x 50304 bf16 (PERF.md, PR 30):
# the forward at 32 rows takes 4.92 ms with chunks of 128 columns, 2.59 at
# 384, 2.38 at 512, 2.30 at 1024, 2.29 at 2048 and 4096; the backward at
# 16 rows 7.12 ms at 128 and 5.11-5.13 from 384 up.  From 16K elements on
# the width hardly matters: both passes then wait for HBM.
_TILE_ELEMS = 32 * 1024
# float32 temporaries of one tile counted live at a time
_TILE_TEMPS = 6


# ---------------------------------------------------------------------------
# blocks from shapes
# ---------------------------------------------------------------------------
def _sublanes(itemsize):
    """Rows of the type's native tile: 8 of float32, 16 of bf16."""
    return max(8, 32 // itemsize)


def _chunk(rows, v):
    """Columns a walk takes at a time from a resident [rows, v] block: the
    lane multiple that makes the tile about _TILE_ELEMS, at most v."""
    return min(v, max(_LANES, _TILE_ELEMS // rows // _LANES * _LANES))


def _vmem_bytes(rows, v, itemsize, backward):
    """An upper bound of the VMEM of one grid step that owns `rows` rows:
    the logits block (backward also the gradient block) and three
    lane-replicated row operands, all double-buffered; the walk's three
    lane-wide carries; the float32 temporaries of one tile."""
    blocks = (4 if backward else 2) * rows * v * itemsize
    row_vectors = (2 * 3 + 3) * rows * _ROW_BYTES
    return (blocks + row_vectors
            + _TILE_TEMPS * rows * _chunk(rows, v) * 4)


def _vmem_limit(rows, v, itemsize, backward):
    """The scoped VMEM limit of a call: Mosaic's own 16 MiB, or what a
    block over the budget needs with the same room left to the compiler."""
    return max(_VMEM_SCOPED, _vmem_bytes(rows, v, itemsize, backward)
               + _VMEM_SCOPED - _VMEM_BUDGET)


def pick_blocks(n, v, itemsize, backward):
    """(block_r, chunk) of a call on [n, v] logits (padded: `n` to the
    type's sublanes, `v` to lanes), from its shapes alone: the rows a grid
    step owns and the columns its walk takes at a time.  The largest
    multiple of the sublanes that divides `n`, keeps a tile of 128 columns
    within _TILE_ELEMS and fits _VMEM_BUDGET; the narrowest block if none
    fits and that one fits _VMEM_CEILING.  Raises DoesNotTile for a
    vocabulary wider than that."""
    sub = _sublanes(itemsize)
    if _vmem_limit(sub, v, itemsize, backward) > _VMEM_CEILING:
        raise DoesNotTile(
            f"softmax_xent: {sub} rows of {v} columns outgrow VMEM")
    for rows in range(min(n, _TILE_ELEMS // _LANES) // sub * sub, sub, -sub):
        if n % rows == 0 and _vmem_bytes(rows, v, itemsize,
                                         backward) <= _VMEM_BUDGET:
            return rows, _chunk(rows, v)
    return sub, _chunk(sub, v)


# ---------------------------------------------------------------------------
# the walk over the vocabulary
# ---------------------------------------------------------------------------
def _walk(z_ref, chunk, tile, carry):
    """Run `tile(z, c0, carry)` over the resident block in `z_ref`
    ([block_r, v]) `chunk` columns at a time, then over the tail: `z` is
    the float32 columns [c0, c0 + width)."""
    v = z_ref.shape[1]

    def body(c, carry):
        c0 = pl.multiple_of(c * chunk, chunk)
        return tile(z_ref[:, pl.ds(c0, chunk)].astype(jnp.float32), c0, carry)

    carry = jax.lax.fori_loop(0, v // chunk, body, carry)
    if v % chunk:
        c0 = v - v % chunk
        carry = tile(z_ref[:, c0:].astype(jnp.float32), c0, carry)
    return carry


def _groups(z):
    """The 128-lane groups of a tile [block_r, width], whole vregs each."""
    return [z[:, k:k + _LANES] for k in range(0, z.shape[1], _LANES)]


def _label_offset(lab):
    """`lab - lane` of a lane-replicated label row vector: group k of the
    tile that starts at column c0 holds a row's label in the lane where
    this equals c0 + 128 k, so a group costs one compare with a scalar
    and no iota of its own."""
    return lab - jax.lax.broadcasted_iota(jnp.int32, lab.shape, 1)


def _across_lanes(reduce, x):
    """The cross-lane reduction of a lane-wide statistic, lane-replicated."""
    return jnp.broadcast_to(reduce(x, axis=-1, keepdims=True), x.shape)


def _fwd_kernel(z_ref, lab_ref, loss_ref, lse_ref, *, chunk, ignore_index):
    lab = lab_ref[...]                        # [br, 128] int32, lanes equal
    off = _label_offset(lab)

    def fold_max(z, c0, m):
        for zk in _groups(z):
            m = jnp.maximum(m, zk)
        return m

    m = _across_lanes(jnp.max, _walk(
        z_ref, chunk, fold_max, jnp.full(lab.shape, _NEG_INF, jnp.float32)))

    def fold_sum(z, c0, carry):
        l, pick = carry
        first = off - c0
        for k, zk in enumerate(_groups(z)):
            l = l + jnp.exp(zk - m)
            # one lane of one group of one chunk holds the row's label
            pick = jnp.where(first == k * _LANES, zk, pick)
        return l, pick

    zeros = jnp.zeros(lab.shape, jnp.float32)
    l, pick = _walk(z_ref, chunk, fold_sum, (zeros, zeros))
    lse = m + jnp.log(_across_lanes(jnp.sum, l))
    lse_ref[...] = lse
    loss_ref[...] = jnp.where(lab == ignore_index, 0.0,
                              lse - _across_lanes(jnp.sum, pick))


def _bwd_kernel(z_ref, lab_ref, lse_ref, g_ref, dz_ref, *, chunk,
                ignore_index):
    lab = lab_ref[...]
    lse = lse_ref[...]
    g = jnp.where(lab == ignore_index, 0.0, g_ref[...])
    off = _label_offset(lab)

    def tile(z, c0, _):
        first = off - c0
        dz = []
        for k, zk in enumerate(_groups(z)):
            p = jnp.exp(zk - lse)
            dz.append(jnp.where(first == k * _LANES, p - 1.0, p) * g)
        dz_ref[:, pl.ds(c0, z.shape[1])] = jnp.concatenate(
            dz, axis=1).astype(dz_ref.dtype)

    _walk(z_ref, chunk, tile, None)


def _lanes(col):
    """A per-row vector as a lane-replicated [n, 128] operand: Mosaic has
    no layout for a 1-D block cast to a column inside the kernel."""
    return jnp.broadcast_to(col[:, None], (col.shape[0], _LANES))


def _layout(n, v, itemsize, backward):
    """(grid, chunk, spec(width), compiler parameters) of a call whose
    grid step owns `block_r` whole rows of every operand."""
    block_r, chunk = pick_blocks(n, v, itemsize, backward)

    def spec(width):
        return pl.BlockSpec((block_r, width), _im(lambda i: (i, 0)))

    return (n // block_r,), chunk, spec, pltpu.CompilerParams(
        dimension_semantics=("parallel",),
        vmem_limit_bytes=_vmem_limit(block_r, v, itemsize, backward))


def _fwd_call(z, lab, ignore_index, interpret):
    n, v = z.shape
    grid, chunk, spec, params = _layout(n, v, z.dtype.itemsize, False)
    loss, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk,
                          ignore_index=ignore_index),
        name="paddle_softmax_xent_fwd",
        grid=grid,
        in_specs=[spec(v), spec(_LANES)],
        out_specs=[spec(_LANES), spec(_LANES)],
        out_shape=[jax.ShapeDtypeStruct((n, _LANES), jnp.float32)] * 2,
        compiler_params=params,
        interpret=interpret,
    )(z, _lanes(lab))
    return loss[:, 0], lse[:, 0]


def _bwd_call(z, lab, lse, g, ignore_index, interpret):
    n, v = z.shape
    grid, chunk, spec, params = _layout(n, v, z.dtype.itemsize, True)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk,
                          ignore_index=ignore_index),
        name="paddle_softmax_xent_bwd",
        grid=grid,
        in_specs=[spec(v), spec(_LANES), spec(_LANES), spec(_LANES)],
        out_specs=spec(v),
        out_shape=jax.ShapeDtypeStruct((n, v), z.dtype),
        compiler_params=params,
        interpret=interpret,
    )(z, _lanes(lab), _lanes(lse), _lanes(g.astype(jnp.float32)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _sxent(z, lab, ignore_index, interpret):
    loss, _ = _fwd_call(z, lab, ignore_index, interpret)
    return loss


def _sxent_fwd(z, lab, ignore_index, interpret):
    loss, lse = _fwd_call(z, lab, ignore_index, interpret)
    return loss, (z, lab, lse)


def _sxent_bwd(ignore_index, interpret, res, g):
    z, lab, lse = res
    dz = _bwd_call(z, lab, lse, g, ignore_index, interpret)
    return dz, None


_sxent.defvjp(_sxent_fwd, _sxent_bwd)


def softmax_xent(logits, labels, ignore_index: int = -100,
                 interpret: bool | None = None):
    """Fused per-token softmax cross-entropy loss over the last axis.

    logits [..., V]; labels int [...] (a trailing size-1 axis is
    squeezed).  Returns per-token loss with logits' leading shape, in
    logits' dtype.  Raises DoesNotTile for labels that do not match and
    for a vocabulary too wide for VMEM (`pick_blocks`); ops/fused.py then
    takes the XLA composite.
    """
    v = logits.shape[-1]
    lead = logits.shape[:-1]
    if labels.ndim == logits.ndim:
        labels = jnp.squeeze(labels, -1)
    if labels.shape != lead:
        raise DoesNotTile(
            f"softmax_xent: labels {labels.shape} vs logits lead {lead}")
    if interpret is None:
        interpret = _interpret_default()
    z = logits.reshape(-1, v)
    lab = labels.reshape(-1).astype(jnp.int32)
    n = z.shape[0]
    if n == 0:
        return jnp.zeros(lead, logits.dtype)
    # pad the vocab to a lane multiple with -1e30 (exp underflows to 0)
    # and rows to a sublane multiple with ignore_index rows (loss 0)
    sub = _sublanes(z.dtype.itemsize)
    vp = -(-v // _LANES) * _LANES
    np_ = -(-n // sub) * sub
    if vp != v:
        z = jnp.pad(z, ((0, 0), (0, vp - v)), constant_values=_NEG_INF)
    if np_ != n:
        z = jnp.pad(z, ((0, np_ - n), (0, 0)))
        lab = jnp.pad(lab, (0, np_ - n), constant_values=ignore_index)
    # said here, from shapes, for the backward too: its call is traced
    # after ops/fused.py has stopped listening
    pick_blocks(np_, vp, z.dtype.itemsize, backward=True)
    loss = _sxent(z, lab, int(ignore_index), interpret)
    return loss[:n].reshape(lead).astype(logits.dtype)
