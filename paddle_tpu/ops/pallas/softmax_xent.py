"""Pallas TPU fused softmax cross-entropy (log-softmax + label gather,
forward AND backward in-kernel).

Reference analog: softmax_with_cross_entropy_op.cu — the fused loss that
kept Fluid's LM heads from materializing log-probabilities.  The XLA
composite in ops/fused.py computes max / lse / gather as separate HBM
passes over the [N, V] logits; this kernel streams each row tile once per
pass with the running max / normalizer / picked-logit in VMEM scratch
(vocab innermost, flash-style online logsumexp), and the backward kernel
forms (softmax - onehot) * g tile-by-tile without a resident [N, V]
softmax.

Hard labels only (soft_label=False — the ops/fused.py gate routes soft
labels to XLA); `ignore_index` rows produce loss 0 and gradient 0.  The
label gather is a one-hot select against a broadcasted iota (TPU has no
in-kernel gather).  The vocab axis is padded to a lane multiple (128) with
-1e30 by the wrapper — exp underflows to exactly 0, so padding never
perturbs the loss; padded rows carry ignore_index.  All math in float32
regardless of input dtype.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30

from . import (DoesNotTile, im as _im,
               interpret_default as _interpret_default)


def _fwd_kernel(z_ref, lab_ref, loss_ref, lse_ref, m_ref, l_ref, pick_ref,
                *, block_c, num_c, ignore_index):
    c_idx = pl.program_id(1)

    @pl.when(c_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        pick_ref[...] = jnp.zeros_like(pick_ref)

    z = z_ref[...].astype(jnp.float32)                 # [br, bc]
    lab = lab_ref[:, :1]                               # [br, 1] int32
    col = c_idx * block_c + jax.lax.broadcasted_iota(
        jnp.int32, z.shape, 1)
    m_prev = m_ref[:, :1]
    l_prev = l_ref[:, :1]
    m_cur = jnp.max(z, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    l_new = jnp.exp(m_prev - m_new) * l_prev + \
        jnp.sum(jnp.exp(z - m_new), axis=-1, keepdims=True)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)
    picked = jnp.sum(jnp.where(col == lab, z, 0.0),
                     axis=-1, keepdims=True)
    pick_ref[...] += jnp.broadcast_to(picked, pick_ref.shape)

    @pl.when(c_idx == num_c - 1)
    def _finish():
        lse = m_ref[:, :1] + jnp.log(l_ref[:, :1])
        loss = lse - pick_ref[:, :1]
        loss = jnp.where(lab == ignore_index, 0.0, loss)
        loss_ref[...] = jnp.broadcast_to(loss, loss_ref.shape)
        lse_ref[...] = jnp.broadcast_to(lse, lse_ref.shape)


def _bwd_kernel(z_ref, lab_ref, lse_ref, g_ref, dz_ref, *, block_c,
                ignore_index):
    c_idx = pl.program_id(1)
    z = z_ref[...].astype(jnp.float32)
    lab = lab_ref[:, :1]
    lse = lse_ref[:, :1]
    g = g_ref[:, :1]
    col = c_idx * block_c + jax.lax.broadcasted_iota(
        jnp.int32, z.shape, 1)
    p = jnp.exp(z - lse)
    onehot = (col == lab).astype(jnp.float32)
    dz = (p - onehot) * g
    dz = jnp.where(lab == ignore_index, 0.0, dz)
    dz_ref[...] = dz.astype(dz_ref.dtype)


def _pick_block(n: int, cands) -> int:
    for c in cands:
        if n % c == 0:
            return c
    return 0


def _lanes(col):
    """A per-row vector as a lane-replicated [n, 128] operand: Mosaic has
    no layout for a 1-D block cast to a column inside the kernel."""
    return jnp.broadcast_to(col[:, None], (col.shape[0], 128))


def _fwd_call(z, lab, ignore_index, interpret):
    n, v = z.shape
    block_r = _pick_block(n, (128, 64, 32, 16, 8))
    block_c = _pick_block(v, (1024, 512, 256, 128))
    num_r, num_c = n // block_r, v // block_c
    loss, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, block_c=block_c, num_c=num_c,
                          ignore_index=ignore_index),
        name="paddle_softmax_xent_fwd",
        grid=(num_r, num_c),
        in_specs=[
            pl.BlockSpec((block_r, block_c), _im(lambda i, j: (i, j))),
            pl.BlockSpec((block_r, 128), _im(lambda i, j: (i, 0))),
        ],
        out_specs=[
            pl.BlockSpec((block_r, 128), _im(lambda i, j: (i, 0))),
            pl.BlockSpec((block_r, 128), _im(lambda i, j: (i, 0))),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, 128), jnp.float32),
            jax.ShapeDtypeStruct((n, 128), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_r, 128), jnp.float32),
            pltpu.VMEM((block_r, 128), jnp.float32),
            pltpu.VMEM((block_r, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(z, _lanes(lab))
    return loss[:, 0], lse[:, 0]


def _bwd_call(z, lab, lse, g, ignore_index, interpret):
    n, v = z.shape
    block_r = _pick_block(n, (128, 64, 32, 16, 8))
    block_c = _pick_block(v, (1024, 512, 256, 128))
    lse_r = _lanes(lse)
    g_r = _lanes(g.astype(jnp.float32))
    dz = pl.pallas_call(
        functools.partial(_bwd_kernel, block_c=block_c,
                          ignore_index=ignore_index),
        name="paddle_softmax_xent_bwd",
        grid=(n // block_r, v // block_c),
        in_specs=[
            pl.BlockSpec((block_r, block_c), _im(lambda i, j: (i, j))),
            pl.BlockSpec((block_r, 128), _im(lambda i, j: (i, 0))),
            pl.BlockSpec((block_r, 128), _im(lambda i, j: (i, 0))),
            pl.BlockSpec((block_r, 128), _im(lambda i, j: (i, 0))),
        ],
        out_specs=pl.BlockSpec((block_r, block_c), _im(lambda i, j: (i, j))),
        out_shape=jax.ShapeDtypeStruct((n, v), z.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(z, _lanes(lab), lse_r, g_r)
    return dz


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
    logits' dtype.  Raises DoesNotTile for geometry the kernel
    can't tile even after padding (caller falls back to XLA).
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
    vp = -(-v // 128) * 128
    np_ = -(-n // 8) * 8
    if vp != v:
        z = jnp.pad(z, ((0, 0), (0, vp - v)), constant_values=_NEG_INF)
    if np_ != n:
        z = jnp.pad(z, ((0, np_ - n), (0, 0)))
        lab = jnp.pad(lab, (0, np_ - n), constant_values=ignore_index)
    loss = _sxent(z, lab, int(ignore_index), interpret)
    return loss[:n].reshape(lead).astype(logits.dtype)
