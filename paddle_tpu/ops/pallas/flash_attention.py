"""Pallas TPU flash attention (forward + backward, causal + additive mask).

The fused-attention op of the framework (reference analogs:
paddle/fluid/operators/fused/multihead_matmul_op.cu and
math/bert_encoder_functor.cu — those are inference-only CUDA fusions; this
kernel is the training-grade TPU replacement named as intent by
BASELINE.json's fused_attention).

Design (flash attention v2 style):
- public entry takes paddle layout [B, S, H, D]; internally folds to
  [B*H, S, D] and tiles the MXU with (block_q x D) @ (D x block_k) matmuls.
- forward: grid (BH, num_q, num_k) with the KV dimension innermost;
  running max `m`, normalizer `l`, and the output accumulator live in VMEM
  scratch across KV steps; output + logsumexp written on the last KV step.
- backward: two kernels — dq (grid over KV innermost) and dkv (grid over Q
  innermost) — recomputing p = exp(s - lse) per tile, FLOPs ~ 2.5x fwd.
- causal: fully-masked tiles are skipped with pl.when (no FLOPs), the
  diagonal tile is masked with a broadcasted iota comparison.
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

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
_NEG_INF = -1e30


from . import (DoesNotTile, im as _im,
               interpret_default as _interpret_default)


def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               preferred_element_type=jnp.float32)


def _causal_mask(q_idx, k_idx, block_q, block_k):
    q_pos = q_idx * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = k_idx * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    return q_pos >= k_pos


def _scores(q, k, bias_ref, q_idx, k_idx, *, sm_scale, causal,
            block_q, block_k):
    """The shared score tile: scale, additive mask, causal mask."""
    s = _dot(q, k, ((1,), (1,))) * sm_scale        # [bq, bk] f32
    if bias_ref is not None:
        s = s + bias_ref[0].astype(jnp.float32)
    if causal:
        s = jnp.where(_causal_mask(q_idx, k_idx, block_q, block_k),
                      s, _NEG_INF)
    return s


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _fwd_kernel(*refs, sm_scale, causal, has_bias, block_q, block_k, num_k):
    if has_bias:
        q_ref, k_ref, v_ref, bias_ref, o_ref, lse_ref, acc_ref, m_ref, \
            l_ref = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref = refs
        bias_ref = None
    q_idx, k_idx = pl.program_id(1), pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # causal: tiles entirely above the diagonal contribute nothing
    run = (q_idx + 1) * block_q > k_idx * block_k if causal else True

    @pl.when(run)
    def _body():
        q = q_ref[0]
        k = k_ref[0]
        s = _scores(q, k, bias_ref, q_idx, k_idx, sm_scale=sm_scale,
                    causal=causal, block_q=block_q, block_k=block_k)

        m_prev = m_ref[:, :1]                      # [bq, 1]
        l_prev = l_ref[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                     # [bq, bk]
        alpha = jnp.exp(m_prev - m_new)            # [bq, 1]
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)

        acc_ref[...] = acc_ref[...] * alpha + _dot(
            p.astype(v_ref.dtype), v_ref[0], ((1,), (0,)))
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(k_idx == num_k - 1)
    def _finish():
        l = l_ref[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, ...] = (acc_ref[...] / l_safe).astype(o_ref.dtype)
        # lse broadcast over a 128-lane minor dim (TPU tiling-friendly)
        lse_ref[0, ...] = m_ref[...] + jnp.log(l_safe)


def _bias_group(bh: int, bias) -> int:
    """How many grid-b values share one bias plane (bias folded to
    [B*Hm, S_q, S_k]; group == H when the mask is per-batch only)."""
    return bh // bias.shape[0]


def _fwd_call(q, k, v, bias, causal, sm_scale, block_q, block_k, interpret):
    bh, s_q, d = q.shape
    s_k = k.shape[1]
    num_q, num_k = s_q // block_q, s_k // block_k
    has_bias = bias is not None

    kernel = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, causal=causal, has_bias=has_bias,
        block_q=block_q, block_k=block_k, num_k=num_k)

    in_specs = [
        pl.BlockSpec((1, block_q, d), _im(lambda b, i, j: (b, i, 0))),
        pl.BlockSpec((1, block_k, d), _im(lambda b, i, j: (b, j, 0))),
        pl.BlockSpec((1, block_k, d), _im(lambda b, i, j: (b, j, 0))),
    ]
    operands = [q, k, v]
    if has_bias:
        g = _bias_group(bh, bias)
        in_specs.append(pl.BlockSpec(
            (1, block_q, block_k), _im(lambda b, i, j: (b // g, i, j))))
        operands.append(bias)

    out, lse = pl.pallas_call(
        kernel,
        name="paddle_flash_fwd",
        grid=(bh, num_q, num_k),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, d), _im(lambda b, i, j: (b, i, 0))),
            pl.BlockSpec((1, block_q, 128), _im(lambda b, i, j: (b, i, 0))),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s_q, d), q.dtype),
            jax.ShapeDtypeStruct((bh, s_q, 128), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*operands)
    # keep only one lane as the residual (128x smaller in HBM; the lane
    # broadcast is a Mosaic tiling requirement, not information)
    return out, lse[..., 0]


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------
def _dq_kernel(*refs, sm_scale, causal, has_bias, block_q, block_k, num_k):
    if has_bias:
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, bias_ref, \
            dq_ref, acc_ref = refs
    else:
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, \
            acc_ref = refs
        bias_ref = None
    q_idx, k_idx = pl.program_id(1), pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    run = (q_idx + 1) * block_q > k_idx * block_k if causal else True

    @pl.when(run)
    def _body():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0][:, :1]                    # [bq, 1]
        delta = delta_ref[0][:, :1]

        s = _scores(q, k, bias_ref, q_idx, k_idx, sm_scale=sm_scale,
                    causal=causal, block_q=block_q, block_k=block_k)
        p = jnp.exp(s - lse)                       # [bq, bk] f32
        dp = _dot(do, v, ((1,), (1,)))             # [bq, bk]
        ds = p * (dp - delta) * sm_scale
        acc_ref[...] += _dot(ds.astype(k.dtype), k, ((1,), (0,)))

    @pl.when(k_idx == num_k - 1)
    def _finish():
        dq_ref[0, ...] = acc_ref[...].astype(dq_ref.dtype)


def _dkv_kernel(*refs, sm_scale, causal, has_bias, block_q, block_k, num_q):
    if has_bias:
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, bias_ref, \
            dk_ref, dv_ref, dk_acc, dv_acc = refs
    else:
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref, \
            dk_acc, dv_acc = refs
        bias_ref = None
    k_idx, q_idx = pl.program_id(1), pl.program_id(2)

    @pl.when(q_idx == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    run = (q_idx + 1) * block_q > k_idx * block_k if causal else True

    @pl.when(run)
    def _body():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0][:, :1]
        delta = delta_ref[0][:, :1]

        s = _scores(q, k, bias_ref, q_idx, k_idx, sm_scale=sm_scale,
                    causal=causal, block_q=block_q, block_k=block_k)
        p = jnp.exp(s - lse)
        dv_acc[...] += _dot(p.astype(do.dtype), do, ((0,), (0,)))
        dp = _dot(do, v, ((1,), (1,)))
        ds = p * (dp - delta) * sm_scale           # [bq, bk]
        dk_acc[...] += _dot(ds.astype(q.dtype), q, ((0,), (0,)))

    @pl.when(q_idx == num_q - 1)
    def _finish():
        dk_ref[0, ...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, ...] = dv_acc[...].astype(dv_ref.dtype)


def _bwd_call(q, k, v, o, lse, do, bias, causal, sm_scale, block_q, block_k,
              interpret):
    bh, s_q, d = q.shape
    s_k = k.shape[1]
    num_q, num_k = s_q // block_q, s_k // block_k
    has_bias = bias is not None
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)                       # [bh, s_q]
    # Mosaic requires >=8 sublanes on row blocks, so row vectors enter the
    # kernels broadcast over a 128-lane minor dim (transient in bwd only;
    # the saved fwd residual is the compact [bh, s_q]).
    lse_r = jnp.broadcast_to(lse[..., None], (bh, s_q, 128))
    delta_r = jnp.broadcast_to(delta[..., None], (bh, s_q, 128))

    q_spec = pl.BlockSpec((1, block_q, d), _im(lambda b, i, j: (b, i, 0)))
    k_spec_j = pl.BlockSpec((1, block_k, d), _im(lambda b, i, j: (b, j, 0)))
    row_spec = pl.BlockSpec((1, block_q, 128), _im(lambda b, i, j: (b, i, 0)))

    dq_in_specs = [q_spec, k_spec_j, k_spec_j, q_spec, row_spec, row_spec]
    dq_operands = [q, k, v, do, lse_r, delta_r]
    if has_bias:
        g = _bias_group(bh, bias)
        dq_in_specs.append(pl.BlockSpec(
            (1, block_q, block_k), _im(lambda b, i, j: (b // g, i, j))))
        dq_operands.append(bias)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, sm_scale=sm_scale, causal=causal,
                          has_bias=has_bias, block_q=block_q,
                          block_k=block_k, num_k=num_k),
        name="paddle_flash_dq",
        grid=(bh, num_q, num_k),
        in_specs=dq_in_specs,
        out_specs=pl.BlockSpec((1, block_q, d), _im(lambda b, i, j: (b, i, 0))),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*dq_operands)

    # dkv: grid is (bh, num_k, num_q) — q innermost
    q_spec_j = pl.BlockSpec((1, block_q, d), _im(lambda b, i, j: (b, j, 0)))
    k_spec_i = pl.BlockSpec((1, block_k, d), _im(lambda b, i, j: (b, i, 0)))
    row_spec_j = pl.BlockSpec((1, block_q, 128), _im(lambda b, i, j: (b, j, 0)))
    dkv_in_specs = [q_spec_j, k_spec_i, k_spec_i, q_spec_j, row_spec_j,
                    row_spec_j]
    dkv_operands = [q, k, v, do, lse_r, delta_r]
    if has_bias:
        g = _bias_group(bh, bias)
        # grid here is (b, k_idx=i, q_idx=j): bias tile rows follow j
        dkv_in_specs.append(pl.BlockSpec(
            (1, block_q, block_k), _im(lambda b, i, j: (b // g, j, i))))
        dkv_operands.append(bias)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, sm_scale=sm_scale, causal=causal,
                          has_bias=has_bias, block_q=block_q,
                          block_k=block_k, num_q=num_q),
        name="paddle_flash_dkv",
        grid=(bh, num_k, num_q),
        in_specs=dkv_in_specs,
        out_specs=[
            pl.BlockSpec((1, block_k, d), _im(lambda b, i, j: (b, i, 0))),
            pl.BlockSpec((1, block_k, d), _im(lambda b, i, j: (b, i, 0))),
        ],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*dkv_operands)
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
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    interpret: bool | None = None, mask=None):
    """Flash attention over paddle layout [B, S, H, D] -> [B, S, H, D].

    ``mask`` is a bool (True = attend) or additive mask broadcastable to
    [B, H, S_q, S_k], composable with ``causal``.  Raises
    DoesNotTile for shapes the kernel doesn't tile (caller falls
    back to the XLA path).
    """
    b, s_q, h, d = q.shape
    s_k = k.shape[1]
    block_q = min(block_q, s_q)
    block_k = min(block_k, s_k)
    if s_q % block_q or s_k % block_k:
        raise DoesNotTile(
            f"flash_attention: seq ({s_q},{s_k}) not divisible by blocks "
            f"({block_q},{block_k})")
    if min(block_q, block_k) < 8:
        raise DoesNotTile("flash_attention: sequence too short")
    if k.shape[2] != h:
        raise DoesNotTile("flash_attention: GQA head mismatch")
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
                            block_q: int = DEFAULT_BLOCK_Q,
                            block_k: int = DEFAULT_BLOCK_K,
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
