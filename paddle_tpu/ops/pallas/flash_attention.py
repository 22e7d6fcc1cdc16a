"""Pallas TPU flash attention (forward + backward, causal + additive mask).

The fused-attention op of the framework (reference analogs:
paddle/fluid/operators/fused/multihead_matmul_op.cu and
math/bert_encoder_functor.cu — those are inference-only CUDA fusions; this
kernel is the training-grade TPU replacement that the seed named as
intent, fused_attention: SURVEY.md section 2).

Design (flash attention v2 style):
- public entry takes paddle layout [B, S, H, D]; internally folds to
  [B*H, S, D].  The unit of work is a score tile (block_q x block_k),
  (block_q x D) @ (D x block_k) on the MXU; `pick_blocks` sizes it from
  the call's shapes (s_q, s_k, D, mask or none, itemsize), and
  `block_q=`/`block_k=` override it.
- a grid step owns one block of one sequence axis and walks the other
  axis INSIDE the kernel, a `fori_loop` over tile-sized sub-blocks of a
  long resident block (`_span`: as much of the walked axis as the VMEM
  budget holds, the whole head at GPT-2's 1024 x 64).  The grid is
  (BH, blocks owned, resident spans), the last axis 1 unless the walked
  sequence outgrows VMEM; accumulators live in VMEM scratch across it.
- forward: a step owns block_q queries and walks the keys; the running
  max `m` and normalizer `l` are [block_q, 128] scratch beside the output
  accumulator, read and written a part at a time (`m` the same in every
  lane, `l` as lane-by-lane partial sums that are added up once a step:
  a tile pays one cross-lane reduction, the max; as loop carries their
  128 vregs were spilled and filled around every trip); output +
  logsumexp written once a step.
- backward: two kernels recomputing p = exp(s - lse) per tile, FLOPs
  ~ 2.5x fwd.  dq owns block_q queries and walks the keys; dkv owns
  block_k keys and walks the queries, so the lane-broadcast lse/delta
  rows are fetched once a key block.
- causal: the loop bounds come from the step's place on the diagonal, so
  tiles above it are neither stepped over nor computed, and a resident
  span wholly above it is not fetched (its index map repeats the last
  span that has work).  Only sub-blocks the diagonal crosses build the
  iota mask; those wholly under it run the unmasked body.
- the tile on the diagonal: where the call is causal over equal sequences
  in square tiles (`_strip_rows`; the train step's 1024 x 1024 in tiles
  of 512 has two such tiles of three a head), the one masked tile of an
  owned block starts on the diagonal, and its body walks it in the same
  loop trip as static strips of 128 owned rows, each against the part of
  the walked tile it can see: a strip of queries the keys up to its own
  end, a strip of keys the queries from its own start on.  A tile of 512
  computes 10 of its 16 squares of 128 and masks the four on the
  diagonal, by one `row >= col` of the square.  A strip keeps its own
  rows of the statistics and accumulators, so there is no further pass,
  loop trip or grid step.  The body writes every strip's score products
  before any strip's exponentials: the compiler schedules in the
  source's order, and written a strip at a time the strips' chains
  (product, reduction, exponentials, product) do not overlap and the
  saved work buys nothing.  Where the whole walked axis is resident that
  one trip is no loop but straight-line code (`_walk`), so it runs under
  the end of the clear loop and the step's end.  Every other call
  computes the tile whole.
- the scale: a power of two (`_folds_scale`: 1/8 at a head of 64) is
  folded into the owned operand once a grid step and, in the backward,
  applied to the float32 accumulators once at the end, exactly; any other
  scale multiplies every score tile.
- mask: an additive bias broadcastable to [B, H, S_q, S_k] (bool masks are
  converted to 0 / -1e30 by the wrapper) streamed tile-by-tile into the
  score matmul of all three kernels — the padding / attention-mask path of
  MultiHeadAttention runs through the kernel instead of falling back.  The
  mask is DATA, not a parameter: its cotangent is defined as zero (a
  learned attention bias would need the [BH, S, S] ds write-back this
  kernel deliberately avoids).
- all accumulation in float32 regardless of input dtype (bf16 in, f32 acc).

Sharding: `sharded_flash_attention` wraps the kernel in shard_map over the
mesh's head (tp/mp) and batch (dp/fsdp) axes so GSPMD runs one kernel per
shard with the LOCAL head count — attention has no cross-head or
cross-batch reduction, so no collectives are needed inside the body.

Raises DoesNotTile when shapes don't tile (seq not divisible by block);
ops/fused.py then takes the XLA softmax path and counts it.
"""
from __future__ import annotations

import functools
import logging
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import (DoesNotTile, im as _im,
               interpret_default as _interpret_default)

_NEG_INF = -1e30
_log = logging.getLogger(__name__)

# sides of a score tile, largest first.  Measured on a v5e at 16 x 1024 x
# 12 x 64 causal (PERF.md, PR 28): the time follows the count of tiles
# more than the scores computed above the diagonal, so 512 x 512 (2.83 ms
# a layer) beats 256 x 256 (4.00) and 128 x 128 (7.95); 1024 x 1024 (2.93)
# computes the whole square and gains nothing.
_TILE_SIDES = (512, 256, 128)
# what one grid step may keep in VMEM by `_vmem_bytes`: Mosaic's scoped
# limit on a v5e is 16 MiB, and the estimate leaves the compiler a quarter
_VMEM_BUDGET = 12 * 1024 * 1024
# bytes a row of a lane-broadcast float32 row vector takes
_ROW_BYTES = 128 * 4
# owned rows of a strip of a diagonal tile (`_strip_rows`)
_STRIP = 128


# ---------------------------------------------------------------------------
# blocks from shapes
# ---------------------------------------------------------------------------
def _vmem_bytes(own, tile, span, d, has_bias, itemsize):
    """An upper bound, over the three kernels, of the VMEM of one grid step
    that owns `own` rows of one sequence axis and keeps `span` rows of the
    other resident, walking them `tile` rows at a time: per row two
    [*, d] operands and two row vectors (double-buffered, as are the
    outputs and the bias block), the float32 accumulators and statistics,
    and six live float32 score tiles.  The six are the whole-tile body's
    (scores, mask, p, dp, ds and a cast); a tile walked by strips
    (`_strip_rows`) holds every strip's two score products at once, five
    eighths of a tile each, and the rest a strip at a time, the widest a
    quarter of a tile, so the bound covers it."""
    per_row = 2 * d * itemsize + 2 * _ROW_BYTES
    pipelined = 2 * (2 * own + span) * per_row
    if has_bias:
        pipelined += 2 * own * span * 4
    scratch = own * (2 * d * 4 + 2 * _ROW_BYTES)
    return pipelined + scratch + 6 * own * tile * 4


def _sides(s):
    """The tile sides a sequence of s can take, largest first: those of
    _TILE_SIDES that divide it, else the whole of a sequence shorter than
    the smallest (one that none divides gets one that does not tile)."""
    return [c for c in _TILE_SIDES if s % c == 0] or [min(_TILE_SIDES[-1], s)]


def pick_blocks(s_q, s_k, d, has_bias, itemsize):
    """(block_q, block_k) of a call, from its shapes alone: the score tile
    all three kernels compute at a time.  The largest tile, and of two as
    large the one with more keys, for which a grid step that keeps just
    one tile's worth of the other axis resident fits the VMEM budget, as
    owner of its queries (forward, dQ) and of its keys (dK/dV)."""
    def fits(bq, bk):
        return max(_vmem_bytes(bq, bk, bk, d, has_bias, itemsize),
                   _vmem_bytes(bk, bq, bq, d, has_bias, itemsize)
                   ) <= _VMEM_BUDGET

    tiles = [(bq, bk) for bq in _sides(s_q) for bk in _sides(s_k)]
    return max([t for t in tiles if fits(*t)] or tiles[-1:],
               key=lambda t: (t[0] * t[1], t[1]))


def _strip_rows(causal, has_bias, s_own, s_walked, own, tile):
    """Rows of the strips by which the tile on the diagonal is walked, 0
    where it is computed whole.  A causal call of equal sequences in
    square tiles has one masked tile an owned block, the one that starts
    on the diagonal (q0 == k0), and what each strip of its owned rows can
    see of it is static: strip r of the queries sees the first (r + 1)
    strips of keys, strip r of the keys is seen by the queries from strip
    r on.  Any other call (unequal sequences or tile sides, a bias tile
    to slice, a tile that is no whole number of strips) keeps the
    whole-tile body."""
    if (causal and not has_bias and s_own == s_walked and own == tile
            and own % _STRIP == 0):
        return _STRIP
    return 0


def _folds_scale(sm_scale):
    """Whether `sm_scale` leaves the score tile: a power of two (1/8 at a
    head of 64) scales the owned [block, d] operand once a grid step and
    the backward's float32 accumulators once at the end, exactly; any
    other scale multiplies the scores as before."""
    return sm_scale > 0 and math.frexp(sm_scale)[0] == 0.5


def _span(s, own, tile, d, has_bias, itemsize):
    """Rows of the walked axis (length `s`, walked `tile` at a time) that a
    grid step owning `own` rows of the other axis keeps resident: the
    largest multiple of `tile` that divides `s` and fits the budget."""
    n = s // tile
    for g in range(n, 0, -1):
        if n % g == 0 and _vmem_bytes(own, tile, g * tile, d, has_bias,
                                      itemsize) <= _VMEM_BUDGET:
            return g * tile
    return tile


# ---------------------------------------------------------------------------
# what the three kernels share
# ---------------------------------------------------------------------------
def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               preferred_element_type=jnp.float32)


_NT = ((1,), (1,))      # a @ b.T
_NN = ((1,), (0,))      # a @ b


def _rows(ref, t, size, lo=0, hi=None):
    """Rows [lo, hi) of sub-block t, `size` rows, of the block in `ref`
    ([1, rows, n]); the whole sub-block by default."""
    hi = size if hi is None else hi
    if ref.shape[1] == size:
        return ref[0, lo:hi]
    start = pl.multiple_of(t * size + lo, math.gcd(size, lo))
    return ref[0, pl.ds(start, hi - lo), :]


def _lanes(x, n):
    """[rows, n] from a row vector held lane-broadcast as [rows, 128].
    Whole vregs are reused, so this moves nothing; a [rows, 1] column
    would cost a cross-lane broadcast every time it met a tile."""
    if n <= 128:
        return x[:, :n]
    if n % 128 == 0:
        return jnp.tile(x, (1, n // 128))
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _cols(ref, t, size):
    """Columns [t*size, (t+1)*size) of the block in `ref` ([1, rows, n])."""
    if ref.shape[2] == size:
        return ref[0]
    return ref[0, :, pl.ds(pl.multiple_of(t * size, size), size)]


def _times(x, c):
    """x * c, and x itself, no multiply traced, where c is 1."""
    return x if c == 1.0 else x * c


def _lane_sums(p):
    """[rows, 128] partial sums of p [rows, n], lane by lane, whose lanes
    add up to the rows' sums: vector adds alone, no cross-lane reduction.
    A p that is no whole vregs wide (a sequence shorter than 128) gives
    its row sums in lane 0."""
    n = p.shape[1]
    if n % 128:
        lane = jax.lax.broadcasted_iota(jnp.int32, (p.shape[0], 128), 1)
        return jnp.where(lane == 0, jnp.sum(p, axis=-1, keepdims=True), 0.0)
    return sum(p[:, c:c + 128] for c in range(0, n, 128))


def _scores(q, k, bias, q0, k0, masked, sm_scale, keys_first=False,
            strip=0):
    """The score tile [queries, keys], or transposed if `keys_first`:
    scale, additive mask ([queries, keys] either way), and on a tile the
    diagonal crosses the causal mask (q0, k0: the sequence positions of
    the tile's first query and key).  With `strip`, the tile is a strip
    of a tile that starts on the diagonal: its `strip` owned rows against
    what they see of the walked axis, of which only the `strip` columns
    on the diagonal (the last of the keys, the first of the queries) are
    masked, by their place in that square alone."""
    s = _times(_dot(k, q, _NT) if keys_first else _dot(q, k, _NT), sm_scale)
    if bias is not None:
        bias = bias.astype(jnp.float32)
        s = s + (bias.T if keys_first else bias)
    if masked and strip:
        at = 0 if keys_first else s.shape[1] - strip
        square = (strip, strip)
        seen = (jax.lax.broadcasted_iota(jnp.int32, square, int(keys_first))
                >= jax.lax.broadcasted_iota(jnp.int32, square,
                                            int(not keys_first)))
        parts = [s[:, :at], jnp.where(seen, s[:, at:at + strip], _NEG_INF),
                 s[:, at + strip:]]
        s = jnp.concatenate([x for x in parts if x.shape[1]], axis=1)
    elif masked:
        q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, s.shape,
                                              int(keys_first))
        k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, s.shape,
                                              int(not keys_first))
        s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
    return s


def _parts(strip, block, from_diagonal):
    """How the body of a score tile walks it: [(owned rows, (lo, hi) of
    the walked tile's rows)].  Whole by default; a tile that starts on
    the diagonal by strips of `strip` owned rows, each against the part
    of the walked tile it sees: the keys up to its own end for a strip of
    queries, the queries from its own start (`from_diagonal`) for a strip
    of keys."""
    if not strip:
        return [(slice(None), (0, block))]
    return [(slice(r, r + strip),
             (r, block) if from_diagonal else (0, r + strip))
            for r in range(0, block, strip)]


def _walk(tile, ranges, once=False):
    """Run `tile(t, masked)` over the sub-blocks t of a resident span:
    `ranges` lists (first, end, masked) in the order to walk them.
    `once`: the masked range is known to hold exactly one sub-block
    (`_strip_rows` with the whole walked axis resident), which then is no
    loop but straight-line code between its neighbours, so the scheduler
    runs it under the end of the loop before it and the step's end."""
    for lo, hi, masked in ranges:
        if masked and once:
            tile(lo, True)
        else:
            jax.lax.fori_loop(
                lo, hi, lambda t, _, masked=masked: tile(t, masked), None)


def _k_ranges(causal, q0, block_q, k0, span, block_k):
    """Sub-blocks of the keys [k0, k0+span) that the queries
    [q0, q0+block_q) walk.  Causal: the keys the first query sees are
    wholly under the diagonal, the keys only the last query sees end the
    sub-blocks the diagonal crosses, and the rest are not walked."""
    if not causal:
        return [(0, span // block_k, False)]
    seen_by_first = jnp.clip(q0 + 1 - k0, 0, span)
    seen_by_last = jnp.clip(q0 + block_q - k0, 0, span)
    clear = seen_by_first // block_k
    return [(0, clear, False),
            (clear, (seen_by_last + block_k - 1) // block_k, True)]


def _q_ranges(causal, k0, block_k, q0, span, block_q):
    """Sub-blocks of the queries [q0, q0+span) that the keys
    [k0, k0+block_k) walk.  Causal: queries before the first key see none
    of them and are not walked, queries before the last key see some (the
    diagonal crosses their sub-blocks), the rest see all."""
    if not causal:
        return [(0, span // block_q, False)]
    before_first = jnp.clip(k0 - q0, 0, span)
    before_last = jnp.clip(k0 + block_k - 1 - q0, 0, span)
    clear = (before_last + block_q - 1) // block_q
    return [(before_first // block_q, clear, True),
            (clear, span // block_q, False)]


def _split(refs, n_in, has_bias):
    """(inputs, bias ref or None, outputs and scratch)."""
    return (refs[:n_in], refs[n_in] if has_bias else None,
            refs[n_in + has_bias:])


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _fwd_kernel(*refs, sm_scale, causal, has_bias, block_q, block_k, strip,
                once, fold):
    (q_ref, k_ref, v_ref), bias_ref, \
        (o_ref, lse_ref, acc_ref, m_ref, l_ref) = _split(refs, 3, has_bias)
    span = k_ref.shape[1]
    q0, k0 = pl.program_id(1) * block_q, pl.program_id(2) * span
    own_scale, scale = (sm_scale, 1.0) if fold else (1.0, sm_scale)
    q = _times(q_ref[0], own_scale)

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # m_ref, l_ref [bq, 128] float32: every lane of m the same, l's lanes
    # partial sums that `_finish` adds up (alpha is the same in every lane)
    def tile(t, masked):
        bias = _cols(bias_ref, t, block_k) if has_bias else None
        parts = _parts(masked and strip, block_k, False)
        # every part's scores before any part's softmax: the compiler
        # schedules in this order, and the products then run under the
        # exponentials of the part before
        ss = [_scores(q[rows], _rows(k_ref, t, block_k, lo, hi), bias,
                      q0, k0 + t * block_k, masked, scale, strip=strip)
              for rows, (lo, hi) in parts]
        for (rows, (lo, hi)), s in zip(parts, ss):
            m_prev = m_ref[rows, :]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - _lanes(m_new, hi - lo))
            alpha = jnp.exp(m_prev - m_new)
            v = _rows(v_ref, t, block_k, lo, hi)
            acc_ref[rows, :] = (
                acc_ref[rows, :] * _lanes(alpha, acc_ref.shape[1])
                + _dot(p.astype(v.dtype), v, _NN))
            m_ref[rows, :] = m_new
            l_ref[rows, :] = alpha * l_ref[rows, :] + _lane_sums(p)

    _walk(tile, _k_ranges(causal, q0, block_q, k0, span, block_k), once)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _finish():
        total = jnp.broadcast_to(       # of `_lane_sums`' partial sums
            jnp.sum(l_ref[...], axis=-1, keepdims=True), l_ref.shape)
        l_safe = jnp.where(total == 0.0, 1.0, total)
        o_ref[0, ...] = (acc_ref[...] / _lanes(l_safe, acc_ref.shape[1])
                         ).astype(o_ref.dtype)
        # lse broadcast over a 128-lane minor dim (TPU tiling-friendly)
        lse_ref[0, ...] = m_ref[...] + jnp.log(l_safe)


def _bias_group(bh: int, bias) -> int:
    """How many grid-b values share one bias plane (bias folded to
    [B*Hm, S_q, S_k]; group == H when the mask is per-batch only)."""
    return bh // bias.shape[0]


def _layout(name, bh, s_own, own, s_walked, tile, d, has_bias, itemsize,
            causal, from_diagonal):
    """Grid and block specs of a kernel whose grid step (b, i, j) owns
    block i (`own` rows) of one sequence axis and walks resident span j of
    the other, `tile` rows at a time.  Returns (grid, span, the index map's
    span for (i, j), spec(width, walked), how the kernel walks the tile on
    the diagonal: `strip` by `_strip_rows`, `once` for `_walk`).  Causal
    steps wholly above the
    diagonal repeat the span of the nearest step that has work (the last
    one, or the first when the walk starts from the diagonal as the keys'
    does), so nothing is fetched for them."""
    span = _span(s_walked, own, tile, d, has_bias, itemsize)
    grid = (bh, s_own // own, s_walked // span)
    strip = _strip_rows(causal, has_bias, s_own, s_walked, own, tile)
    diagonal = dict(strip=strip, once=bool(strip) and grid[2] == 1)
    _log.debug("%s: owns %d of %d rows, walks %d by %d in spans of %d, "
               "grid %s, the tile on the diagonal %s", name, own, s_own,
               s_walked, tile, span, grid,
               f"by strips of {strip} owned rows" if strip else "whole")
    if not causal:
        def walked_j(i, j):
            return j
    elif from_diagonal:
        def walked_j(i, j):
            return jnp.minimum(jnp.maximum(j, i * own // span), grid[2] - 1)
    else:
        def walked_j(i, j):
            return jnp.minimum(j, (i * own + own - 1) // span)

    def spec(width, walked=False):
        if walked:
            return pl.BlockSpec(
                (1, span, width), _im(lambda b, i, j: (b, walked_j(i, j), 0)))
        return pl.BlockSpec((1, own, width), _im(lambda b, i, j: (b, i, 0)))

    return grid, span, walked_j, spec, diagonal


_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


# The two calls below are jitted so that a model of many layers traces and
# lowers each kernel once, not once a layer: the layers share one inner
# computation, which XLA inlines.  `Model.fit` traces its step twice (the
# step, and its cost analysis for the MFU gauge), so at 12 layers this is
# seconds of every start-up.
@functools.partial(jax.jit, static_argnames=(
    "causal", "sm_scale", "block_q", "block_k", "interpret"))
def _fwd_call(q, k, v, bias, causal, sm_scale, block_q, block_k, interpret):
    bh, s_q, d = q.shape
    has_bias = bias is not None
    grid, span, kj, spec, diagonal = _layout(
        "paddle_flash_fwd", bh, s_q, block_q, k.shape[1], block_k, d,
        has_bias, q.dtype.itemsize, causal, False)
    in_specs = [spec(d), spec(d, True), spec(d, True)]
    operands = [q, k, v]
    if has_bias:
        g = _bias_group(bh, bias)
        in_specs.append(pl.BlockSpec(
            (1, block_q, span), _im(lambda b, i, j: (b // g, i, kj(i, j)))))
        operands.append(bias)

    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, sm_scale=sm_scale, causal=causal,
                          has_bias=has_bias, block_q=block_q,
                          block_k=block_k, fold=_folds_scale(sm_scale),
                          **diagonal),
        name="paddle_flash_fwd",
        grid=grid,
        in_specs=in_specs,
        out_specs=[spec(d), spec(128)],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s_q, d), q.dtype),
            jax.ShapeDtypeStruct((bh, s_q, 128), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        compiler_params=_PARAMS,
        interpret=interpret,
    )(*operands)
    # keep only one lane as the residual (128x smaller in HBM; the lane
    # broadcast is a Mosaic tiling requirement, not information)
    return out, lse[..., 0]


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------
def _dq_kernel(*refs, sm_scale, causal, has_bias, block_q, block_k, strip,
               once, fold):
    (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref), bias_ref, \
        (dq_ref, acc_ref) = _split(refs, 6, has_bias)
    span = k_ref.shape[1]
    q0, k0 = pl.program_id(1) * block_q, pl.program_id(2) * span
    own_scale, scale = (sm_scale, 1.0) if fold else (1.0, sm_scale)
    q = _times(q_ref[0], own_scale)

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def tile(t, masked):
        bias = _cols(bias_ref, t, block_k) if has_bias else None
        parts = _parts(masked and strip, block_k, False)
        # both products of every part before any part's exponentials (the
        # order the compiler schedules in)
        dots = []
        for rows, (lo, hi) in parts:
            k = _rows(k_ref, t, block_k, lo, hi)
            dots.append((
                k, _scores(q[rows], k, bias, q0, k0 + t * block_k, masked,
                           scale, strip=strip),
                _dot(do_ref[0, rows, :], _rows(v_ref, t, block_k, lo, hi),
                     _NT)))
        for (rows, (lo, hi)), (k, s, dp) in zip(parts, dots):
            p = jnp.exp(s - _lanes(lse_ref[0, rows, :], hi - lo))
            ds = _times(p * (dp - _lanes(delta_ref[0, rows, :], hi - lo)),
                        scale)
            acc_ref[rows, :] += _dot(ds.astype(k.dtype), k, _NN)

    _walk(tile, _k_ranges(causal, q0, block_q, k0, span, block_k), once)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _finish():
        dq_ref[0, ...] = _times(acc_ref[...], own_scale).astype(dq_ref.dtype)


def _dkv_kernel(*refs, sm_scale, causal, has_bias, block_q, block_k, strip,
                once, fold):
    (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref), bias_ref, \
        (dk_ref, dv_ref, dk_acc, dv_acc) = _split(refs, 6, has_bias)
    span = q_ref.shape[1]
    k0, q0 = pl.program_id(1) * block_k, pl.program_id(2) * span
    own_scale, scale = (sm_scale, 1.0) if fold else (1.0, sm_scale)
    k = _times(k_ref[0], own_scale)

    @pl.when(pl.program_id(2) == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    # scores transposed, [keys, queries], so that no product contracts
    # over rows; the queries' row vectors become rows of lanes
    def tile(t, masked):
        bias = _rows(bias_ref, t, block_q) if has_bias else None
        lse = _rows(lse_ref, t, block_q).T[:1]
        delta = _rows(delta_ref, t, block_q).T[:1]
        parts = _parts(masked and strip, block_q, True)
        dots = []
        for rows, (lo, hi) in parts:
            q = _rows(q_ref, t, block_q, lo, hi)
            do = _rows(do_ref, t, block_q, lo, hi)
            dots.append((
                q, do, _scores(q, k[rows], bias, q0 + t * block_q, k0,
                               masked, scale, keys_first=True,
                               strip=strip),                  # [bk, bq]
                _dot(v_ref[0, rows, :], do, _NT)))
        for (rows, (lo, hi)), (q, do, s, dp) in zip(parts, dots):
            p = jnp.exp(s - lse[:, lo:hi])
            dv_acc[rows, :] += _dot(p.astype(do.dtype), do, _NN)
            ds = _times(p * (dp - delta[:, lo:hi]), scale)
            dk_acc[rows, :] += _dot(ds.astype(q.dtype), q, _NN)

    _walk(tile, _q_ranges(causal, k0, block_k, q0, span, block_q), once)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _finish():
        dk_ref[0, ...] = _times(dk_acc[...], own_scale).astype(dk_ref.dtype)
        dv_ref[0, ...] = dv_acc[...].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "sm_scale", "block_q", "block_k", "interpret"))
def _bwd_call(q, k, v, o, lse, do, bias, causal, sm_scale, block_q, block_k,
              interpret):
    bh, s_q, d = q.shape
    s_k = k.shape[1]
    has_bias = bias is not None
    itemsize = q.dtype.itemsize
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)                       # [bh, s_q]
    # Mosaic requires >=8 sublanes on row blocks, so row vectors enter the
    # kernels broadcast over a 128-lane minor dim (transient in bwd only;
    # the saved fwd residual is the compact [bh, s_q]).
    lse_r = jnp.broadcast_to(lse[..., None], (bh, s_q, 128))
    delta_r = jnp.broadcast_to(delta[..., None], (bh, s_q, 128))
    operands = [q, k, v, do, lse_r, delta_r] + ([bias] if has_bias else [])
    g = _bias_group(bh, bias) if has_bias else 1
    kwargs = dict(sm_scale=sm_scale, causal=causal, has_bias=has_bias,
                  block_q=block_q, block_k=block_k,
                  fold=_folds_scale(sm_scale))

    # dq: a step owns block_q queries and walks the keys
    grid, span, kj, spec, diagonal = _layout(
        "paddle_flash_dq", bh, s_q, block_q, s_k, block_k, d, has_bias,
        itemsize, causal, False)
    in_specs = [spec(d), spec(d, True), spec(d, True), spec(d), spec(128),
                spec(128)]
    if has_bias:
        in_specs.append(pl.BlockSpec(
            (1, block_q, span), _im(lambda b, i, j: (b // g, i, kj(i, j)))))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **diagonal, **kwargs),
        name="paddle_flash_dq",
        grid=grid,
        in_specs=in_specs,
        out_specs=spec(d),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=_PARAMS,
        interpret=interpret,
    )(*operands)

    # dk, dv: a step owns block_k keys and walks the queries, so the
    # lane-broadcast rows are fetched once a key block, not once a tile
    grid, span, qj, spec, diagonal = _layout(
        "paddle_flash_dkv", bh, s_k, block_k, s_q, block_q, d, has_bias,
        itemsize, causal, True)
    in_specs = [spec(d, True), spec(d), spec(d), spec(d, True),
                spec(128, True), spec(128, True)]
    if has_bias:
        in_specs.append(pl.BlockSpec(
            (1, span, block_k), _im(lambda b, i, j: (b // g, qj(i, j), i))))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, **diagonal, **kwargs),
        name="paddle_flash_dkv",
        grid=grid,
        in_specs=in_specs,
        out_specs=[spec(d), spec(d)],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        compiler_params=_PARAMS,
        interpret=interpret,
    )(*operands)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom_vjp entries over [BH, S, D] (+ folded bias [B*Hm, S_q, S_k])
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _mha(q, k, v, causal, sm_scale, block_q, block_k, interpret):
    out, _ = _fwd_call(q, k, v, None, causal, sm_scale, block_q, block_k,
                       interpret)
    return out


def _mha_fwd(q, k, v, causal, sm_scale, block_q, block_k, interpret):
    out, lse = _fwd_call(q, k, v, None, causal, sm_scale, block_q, block_k,
                         interpret)
    return out, (q, k, v, out, lse)


def _mha_bwd(causal, sm_scale, block_q, block_k, interpret, res, do):
    q, k, v, o, lse = res
    dq, dk, dv = _bwd_call(q, k, v, o, lse, do, None, causal, sm_scale,
                           block_q, block_k, interpret)
    return dq, dk, dv


_mha.defvjp(_mha_fwd, _mha_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _mha_masked(q, k, v, bias, causal, sm_scale, block_q, block_k,
                interpret):
    out, _ = _fwd_call(q, k, v, bias, causal, sm_scale, block_q, block_k,
                       interpret)
    return out


def _mha_masked_fwd(q, k, v, bias, causal, sm_scale, block_q, block_k,
                    interpret):
    out, lse = _fwd_call(q, k, v, bias, causal, sm_scale, block_q, block_k,
                         interpret)
    return out, (q, k, v, bias, out, lse)


def _mha_masked_bwd(causal, sm_scale, block_q, block_k, interpret, res, do):
    q, k, v, bias, o, lse = res
    dq, dk, dv = _bwd_call(q, k, v, o, lse, do, bias, causal, sm_scale,
                           block_q, block_k, interpret)
    # the mask is data (padding/visibility), not a parameter: its
    # cotangent is defined as zero (see module docstring)
    return dq, dk, dv, jnp.zeros_like(bias)


_mha_masked.defvjp(_mha_masked_fwd, _mha_masked_bwd)


def _fold_mask(mask, b, h, s_q, s_k):
    """Normalize a bool/additive mask broadcastable to [B, H, S_q, S_k]
    into the folded additive bias [B*Hm, S_q, S_k] (Hm in {1, H})."""
    m = mask
    if m.dtype == jnp.bool_:
        m = jnp.where(m, 0.0, _NEG_INF)
    m = m.astype(jnp.float32)
    while m.ndim < 4:
        m = m[None]
    if m.ndim != 4:
        raise DoesNotTile(
            f"flash_attention: mask rank {mask.ndim} unsupported")
    hm = h if m.shape[1] != 1 else 1
    try:
        m = jnp.broadcast_to(m, (b, hm, s_q, s_k))
    except ValueError:
        raise DoesNotTile(
            f"flash_attention: mask shape {mask.shape} does not broadcast "
            f"to ({b}, {h}, {s_q}, {s_k})")
    return m.reshape(b * hm, s_q, s_k)


def flash_attention(q, k, v, causal: bool = False, sm_scale=None,
                    block_q: int | None = None, block_k: int | None = None,
                    interpret: bool | None = None, mask=None):
    """Flash attention over paddle layout [B, S, H, D] -> [B, S, H, D].

    ``mask`` is a bool (True = attend) or additive mask broadcastable to
    [B, H, S_q, S_k], composable with ``causal``.  ``block_q`` x
    ``block_k`` is the score tile; None takes it from the shapes
    (`pick_blocks`).  Raises DoesNotTile for shapes the kernel doesn't
    tile (caller falls back to the XLA path).
    """
    b, s_q, h, d = q.shape
    s_k = k.shape[1]
    picked = pick_blocks(s_q, s_k, d, mask is not None, q.dtype.itemsize)
    block_q = min(block_q or picked[0], s_q)
    block_k = min(block_k or picked[1], s_k)
    if s_q % block_q or s_k % block_k:
        raise DoesNotTile(
            f"flash_attention: seq ({s_q},{s_k}) not divisible by blocks "
            f"({block_q},{block_k})")
    if min(block_q, block_k) < 8:
        raise DoesNotTile("flash_attention: sequence too short")
    if k.shape[2] != h or v.shape[2] != h:
        # no silent composite for grouped heads: the kernel reads one KV
        # head a query head, so a caller with fewer KV heads expands them
        # first (models/sdar.py's prompt pass does); anything else is a
        # caller's error, here and in the composite alike
        raise ValueError(
            f"flash_attention: {h} query heads over {k.shape[2]} key and "
            f"{v.shape[2]} value heads; expand grouped KV heads to the "
            "query heads before the call")
    if sm_scale is None:
        sm_scale = 1.0 / (d ** 0.5)
    if interpret is None:
        interpret = _interpret_default()

    def fold(x, s):
        return jnp.swapaxes(x, 1, 2).reshape(b * h, s, d)

    if mask is None:
        out = _mha(fold(q, s_q), fold(k, s_k), fold(v, s_k), causal,
                   float(sm_scale), block_q, block_k, interpret)
    else:
        bias = _fold_mask(mask, b, h, s_q, s_k)
        out = _mha_masked(fold(q, s_q), fold(k, s_k), fold(v, s_k), bias,
                          causal, float(sm_scale), block_q, block_k,
                          interpret)
    return jnp.swapaxes(out.reshape(b, h, s_q, d), 1, 2)


# ---------------------------------------------------------------------------
# GSPMD composition: one kernel per shard via shard_map
# ---------------------------------------------------------------------------
def sharded_flash_attention(q, k, v, mesh, head_axis=None, batch_axes=(),
                            causal: bool = False, sm_scale=None,
                            block_q: int | None = None,
                            block_k: int | None = None,
                            interpret: bool | None = None, mask=None):
    """flash_attention under shard_map over ``mesh``: heads split over
    ``head_axis`` (tp/mp), batch over ``batch_axes`` (dp/fsdp) — the
    head-dim blocking inside each shard sees the LOCAL (sharded) head
    count, so `mesh3d` runs the kernel rather than falling back to one
    replicated call.  Axes absent from the mesh or not dividing the
    operand raise DoesNotTile (caller falls back)."""
    from jax.sharding import PartitionSpec as P

    b, s_q, h, d = q.shape
    s_k = k.shape[1]
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    batch_axes = tuple(a for a in batch_axes
                       if sizes.get(a, 1) > 1)
    if head_axis is not None and sizes.get(head_axis, 1) <= 1:
        head_axis = None
    tp = sizes.get(head_axis, 1) if head_axis else 1
    nb = 1
    for a in batch_axes:
        nb *= sizes[a]
    if h % tp or b % nb:
        raise DoesNotTile(
            f"sharded flash_attention: heads {h} % tp {tp} or batch {b} % "
            f"dp {nb} != 0")
    bspec = tuple(batch_axes) if len(batch_axes) > 1 else \
        (batch_axes[0] if batch_axes else None)
    qkv_spec = P(bspec, None, head_axis, None)
    in_specs = [qkv_spec, qkv_spec, qkv_spec]
    operands = [q, k, v]
    if mask is not None:
        m = mask
        if m.dtype == jnp.bool_:
            m = jnp.where(m, 0.0, _NEG_INF)
        m = m.astype(jnp.float32)
        while m.ndim < 4:
            m = m[None]
        hm = h if m.shape[1] not in (1,) else 1
        m = jnp.broadcast_to(m, (b, hm, s_q, s_k))
        in_specs.append(P(bspec, head_axis if hm == h else None, None, None))
        operands.append(m)

    def body(ql, kl, vl, *rest):
        return flash_attention(ql, kl, vl, causal=causal, sm_scale=sm_scale,
                               block_q=block_q, block_k=block_k,
                               interpret=interpret,
                               mask=rest[0] if rest else None)

    f = jax.shard_map(body, mesh=mesh, in_specs=tuple(in_specs),
                      out_specs=qkv_spec, check_vma=False)
    return f(*operands)
