"""Pallas TPU grouped expert FFN (`paddle_moe_gmm`): the routed experts of
a mixture-of-experts layer over rows already grouped by expert.

    y[r] = W_down[e(r)] (silu(W_gate[e(r)] x[r]) * W_up[e(r)] x[r])

The rows come sorted by expert with every group starting at a multiple of
the row tile ``tm`` (ops/fused.py ``moe_layout`` builds that order and the
tiles' experts), so a tile belongs to ONE expert and the grid is just the
row tiles: the scalar-prefetched ``tile_expert`` picks the expert's three
weight blocks in the BlockSpec index maps, Mosaic fetches expert e+1's
weights while expert e's tile computes, and consecutive tiles of one
expert fetch nothing (an unchanged block index is not copied again).
Gate and up run in one pass with the SiLU product, then down: the
``[tm, F]`` intermediate never leaves VMEM.

What bounds it at serving shapes is the weights' bytes (three ``H x F``
matrices an expert touched, each read once), not the rows: 512
assignments over 128 experts are 4 rows a group.  The row tile follows
the call's shapes (``pick_tile_rows``): 16 rows where groups are a few
rows, up to 128 where a prefill gives each expert dozens.  Tiles past
``n_used`` keep the last used expert's block index (no fetch) and skip
the arithmetic; their output rows are never read.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import DoesNotTile, im as _im, interpret_default as _interpret_default

# two buffers of an expert's three matrices must fit beside the tiles
_VMEM_BUDGET = 96 * 1024 * 1024


def pick_tile_rows(assignments: int, num_experts: int) -> int:
    """Rows of a tile for `assignments` rows over `num_experts` groups:
    the power of two from 16 to 128 nearest above the mean group (16 rows
    are one bf16 sublane tile; 128 fill the MXU)."""
    mean = -(-assignments // num_experts)
    return next((t for t in (16, 32, 64, 128) if t >= mean), 128)


def _kernel(te_ref, nu_ref, x_ref, wg_ref, wu_ref, wd_ref, o_ref):
    @pl.when(pl.program_id(0) < nu_ref[0])
    def _tile():
        x = x_ref[...]
        g = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
        u = jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)
        a = (g * jax.nn.sigmoid(g) * u).astype(x.dtype)
        o_ref[...] = jnp.dot(
            a, wd_ref[...],
            preferred_element_type=jnp.float32).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tm", "vmem", "interpret"))
def _call(tile_expert, n_used, x, wg, wu, wd, tm, vmem, interpret):
    mp, h = x.shape
    f = wg.shape[2]
    row = pl.BlockSpec((tm, h), _im(lambda i, te, nu: (i, 0)))
    expert = _im(lambda i, te, nu: (te[i], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(mp // tm,),
        in_specs=[
            row,
            pl.BlockSpec((None, h, f), expert),
            pl.BlockSpec((None, h, f), expert),
            pl.BlockSpec((None, f, h), expert),
        ],
        out_specs=row,
    )
    return pl.pallas_call(
        _kernel,
        name="paddle_moe_gmm",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((mp, h), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=vmem),
        interpret=interpret,
    )(tile_expert, n_used, x, wg, wu, wd)


def moe_gmm(x, w_gate, w_up, w_down, tile_expert, n_used, tm: int,
            interpret: bool | None = None):
    """The grouped expert FFN over rows grouped by expert.

    x [Mp, H] (Mp a multiple of ``tm``; every group starts at a multiple
    of ``tm``); w_gate / w_up [E, H, F]; w_down [E, F, H]; tile_expert
    [Mp // tm] int32, the expert of each row tile; n_used: int32 scalar,
    tiles that hold rows (the rest are skipped).  Returns [Mp, H] in x's
    dtype.  Raises DoesNotTile for shapes the kernel does not tile."""
    mp, h = x.shape
    e, h2, f = w_gate.shape
    if w_up.shape != (e, h, f) or w_down.shape != (e, f, h) or h2 != h:
        raise ValueError(
            f"moe_gmm: weights {w_gate.shape}, {w_up.shape}, "
            f"{w_down.shape} do not fit rows of {h}")
    if tm % 16 or mp % tm:
        raise DoesNotTile(f"moe_gmm: {mp} rows in tiles of {tm}")
    if h % 128 or f % 128:
        raise DoesNotTile(f"moe_gmm: widths ({h}, {f}) not multiples of 128")
    item = jnp.dtype(w_gate.dtype).itemsize
    vmem = 2 * 3 * h * f * item + 4 * tm * h * item \
        + 4 * tm * (2 * f + h) * 4 + (4 << 20)
    if vmem > _VMEM_BUDGET:
        raise DoesNotTile(
            f"moe_gmm: one expert's matrices twice over ({vmem} bytes) "
            "exceed the VMEM budget")
    if interpret is None:
        interpret = _interpret_default()
    return _call(jnp.asarray(tile_expert, jnp.int32),
                 jnp.asarray(n_used, jnp.int32).reshape(1), x, w_gate, w_up,
                 w_down, int(tm), int(vmem), bool(interpret))
