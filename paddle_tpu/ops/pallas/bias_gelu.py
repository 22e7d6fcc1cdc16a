"""Pallas TPU fused bias + GeLU (forward + backward in-kernel).

Reference analog: the fused_feedforward / fused_gemm_epilogue CUDA
epilogues — bias add and activation applied in the matmul's epilogue
instead of as separate HBM round-trips.  Here the matmul stays with XLA
(the MXU path XLA already schedules well) and this kernel fuses what XLA
keeps as separate elementwise HLOs under x64: one read of the activation
input produces gelu(x + b), and the backward kernel recomputes u = x + b
to emit dy * gelu'(u) in a single pass (db is the row-sum of dx, left to
XLA's reduction).

GeLU is the exact erf form (matches nn.functional.gelu's default
approximate=False).  Mosaic has no lowering for `lax.erf`, so the kernel
evaluates erf with XLA's own float32 rational polynomial (max abs error
4.5e-7 against float64 erf).  All math in float32.  Dropout is NOT
in-kernel: the
wrapper in ops/fused.py threads the per-step rng and applies the keep-mask
as XLA elementwise ops, which fuse into the surrounding matmul anyway.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_INV_SQRT2 = 0.7071067811865476
_INV_SQRT_2PI = 0.3989422804014327

from . import (DoesNotTile, im as _im,
               interpret_default as _interpret_default, pick_block_rows)


# erf(x) ~= x * P(x^2) / Q(x^2) on [-4, 4] (|erf| is 1 to float32 beyond)
_ERF_P = (-2.72614225801306e-10, 2.77068142495902e-08, -2.10102402082508e-06,
          -5.69250639462346e-05, -7.34990630326855e-04,
          -2.95459980854025e-03, -1.60960333262415e-02)
_ERF_Q = (-1.45660718464996e-05, -2.13374055278905e-04,
          -1.68282697438203e-03, -7.37332916720468e-03,
          -1.42647390514189e-02)


def _horner(x, coeffs):
    acc = jnp.full_like(x, coeffs[0])
    for c in coeffs[1:]:
        acc = acc * x + c
    return acc


def _erf_f32(x):
    x = jnp.clip(x, -4.0, 4.0)
    x2 = x * x
    return x * _horner(x2, _ERF_P) / _horner(x2, _ERF_Q)


def _gelu_f32(u):
    return 0.5 * u * (1.0 + _erf_f32(u * _INV_SQRT2))


def _dgelu_f32(u):
    cdf = 0.5 * (1.0 + _erf_f32(u * _INV_SQRT2))
    pdf = jnp.exp(-0.5 * u * u) * _INV_SQRT_2PI
    return cdf + u * pdf


def _fwd_kernel(x_ref, b_ref, y_ref):
    u = x_ref[...].astype(jnp.float32) + b_ref[...].astype(jnp.float32)
    y_ref[...] = _gelu_f32(u).astype(y_ref.dtype)


def _bwd_kernel(x_ref, b_ref, dy_ref, dx_ref):
    u = x_ref[...].astype(jnp.float32) + b_ref[...].astype(jnp.float32)
    dx = dy_ref[...].astype(jnp.float32) * _dgelu_f32(u)
    dx_ref[...] = dx.astype(dx_ref.dtype)


# jitted for the same reason as layer_norm's call: one traced computation
# per shape, shared by every layer and executable of the process
@functools.partial(jax.jit, static_argnames=(
    "name", "kernel", "outs", "interpret"))
def _row_call(name, kernel, outs, x2d, b, extra, interpret):
    r, n = x2d.shape
    block_r = pick_block_rows(r, n)
    row_spec = pl.BlockSpec((block_r, n), _im(lambda i: (i, 0)))
    vec_spec = pl.BlockSpec((n,), _im(lambda i: (0,)))
    return pl.pallas_call(
        kernel,
        name=name,
        grid=(r // block_r,),
        in_specs=[row_spec, vec_spec] + [row_spec] * len(extra),
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((r, n), outs),
        interpret=interpret,
    )(x2d, b, *extra)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _bg(x2d, b, interpret):
    return _row_call("paddle_bias_gelu_fwd", _fwd_kernel, x2d.dtype, x2d, b,
                     (), interpret)


def _bg_fwd(x2d, b, interpret):
    return _bg(x2d, b, interpret), (x2d, b)


def _bg_bwd(interpret, res, dy):
    x2d, b = res
    dx = _row_call("paddle_bias_gelu_bwd", _bwd_kernel, x2d.dtype, x2d, b,
                   (dy,), interpret)
    # d/db == d/dx elementwise (y = gelu(x + b)), so db is dx's row-sum
    db = jnp.sum(dx.astype(jnp.float32), axis=0).astype(b.dtype)
    return dx, db


_bg.defvjp(_bg_fwd, _bg_bwd)


def bias_gelu(x, bias, interpret: bool | None = None):
    """gelu(x + bias) over the last dim; any leading shape.

    x [..., F], bias [F].  Raises DoesNotTile for rows not
    tileable to 8 sublanes (caller falls back to XLA).
    """
    n = x.shape[-1]
    if bias.shape != (n,):
        raise DoesNotTile(
            f"bias_gelu: bias {bias.shape} must be 1D of size {n}")
    lead = x.shape[:-1]
    x2d = x.reshape(-1, n)
    if pick_block_rows(x2d.shape[0], n) == 0:
        raise DoesNotTile(
            f"bias_gelu: rows {x2d.shape[0]} not divisible by 8")
    if interpret is None:
        interpret = _interpret_default()
    return _bg(x2d, bias, interpret).reshape(*lead, n)
