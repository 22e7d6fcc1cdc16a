"""Pallas fused layer-norm (reference analog: layer_norm_op.cu fused CUDA
kernels + skip_layernorm_op.cu; see SURVEY.md §2.4 fused ops).

Forward is a single row-tiled Pallas kernel (one HBM read of x per row —
mean/var/scale/shift fused); backward is closed-form XLA math on saved
mean/rstd, which XLA fuses into 2-3 kernels on its own.  custom_vjp keeps
the pallas forward differentiable inside jitted train steps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import (DoesNotTile, im as _im,
               interpret_default as _interpret_default, pick_block_rows)


def _ln_kernel(x_ref, w_ref, b_ref, y_ref, mean_ref, rstd_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = (x - mean) * rstd
    y = xhat * w_ref[...].astype(jnp.float32) + b_ref[...].astype(jnp.float32)
    y_ref[...] = y.astype(y_ref.dtype)
    mean_ref[...] = jnp.broadcast_to(mean, mean_ref.shape)
    rstd_ref[...] = jnp.broadcast_to(rstd, rstd_ref.shape)


# jitted so that the layers of a model share one traced computation per
# shape, which XLA inlines: a kernel body traced once a layer and once an
# executable was most of the host's time in `GenerationEngine.start()`
# (PERF.md, PR 29), as for the flash kernels (flash_attention.py, PR 28)
@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def _ln_fwd_call(x2d, w, b, eps, interpret):
    r, n = x2d.shape
    block_r = pick_block_rows(r, n)
    y, mean, rstd = pl.pallas_call(
        functools.partial(_ln_kernel, eps=eps),
        name="paddle_layer_norm_fwd",
        grid=(r // block_r,),
        in_specs=[
            pl.BlockSpec((block_r, n), _im(lambda i: (i, 0))),
            pl.BlockSpec((n,), _im(lambda i: (0,))),
            pl.BlockSpec((n,), _im(lambda i: (0,))),
        ],
        out_specs=[
            pl.BlockSpec((block_r, n), _im(lambda i: (i, 0))),
            pl.BlockSpec((block_r, 128), _im(lambda i: (i, 0))),
            pl.BlockSpec((block_r, 128), _im(lambda i: (i, 0))),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((r, n), x2d.dtype),
            jax.ShapeDtypeStruct((r, 128), jnp.float32),
            jax.ShapeDtypeStruct((r, 128), jnp.float32),
        ],
        interpret=interpret,
    )(x2d, w, b)
    return y, mean[:, :1], rstd[:, :1]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _ln(x2d, w, b, eps, interpret):
    y, _, _ = _ln_fwd_call(x2d, w, b, eps, interpret)
    return y


def _ln_fwd(x2d, w, b, eps, interpret):
    y, mean, rstd = _ln_fwd_call(x2d, w, b, eps, interpret)
    return y, (x2d, w, mean, rstd)


def _ln_bwd(eps, interpret, res, dy):
    x2d, w, mean, rstd = res
    xf = x2d.astype(jnp.float32)
    dyf = dy.astype(jnp.float32)
    wf = w.astype(jnp.float32)
    xhat = (xf - mean) * rstd
    wdy = dyf * wf
    c1 = jnp.mean(wdy, axis=-1, keepdims=True)
    c2 = jnp.mean(wdy * xhat, axis=-1, keepdims=True)
    dx = (wdy - c1 - xhat * c2) * rstd
    dw = jnp.sum(dyf * xhat, axis=0)
    db = jnp.sum(dyf, axis=0)
    return dx.astype(x2d.dtype), dw.astype(w.dtype), db.astype(w.dtype)


_ln.defvjp(_ln_fwd, _ln_bwd)


def layer_norm(x, weight, bias, epsilon=1e-5, interpret: bool | None = None):
    """LN over the last dim; any leading shape."""
    n = x.shape[-1]
    if weight.shape != (n,) or bias is None or bias.shape != (n,):
        raise DoesNotTile("pallas layer_norm needs 1D scale+shift")
    if interpret is None:
        interpret = _interpret_default()
    lead = x.shape[:-1]
    x2d = x.reshape(-1, n)
    if pick_block_rows(x2d.shape[0], n) == 0:
        raise DoesNotTile(
            f"layer_norm rows {x2d.shape[0]} not divisible by 8")
    y = _ln(x2d, weight, bias, float(epsilon), interpret)
    return y.reshape(*lead, n)
