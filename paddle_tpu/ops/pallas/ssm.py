"""Pallas TPU kernels of the selective state-space scan (Mamba-2, one group
of B and C):

    H_t = exp(dt_t A) H_{t-1} + dt_t x_t B_t^T        a head's [P, N] state
    y_t = H_t C_t

`paddle_ssd_chunk_scan` (the prompt pass) runs one sequence in chunks of Q
tokens.  The grid is (head blocks, chunks), the chunks of a head block in
order with the block's states carried in VMEM from one to the next: an
initial state comes in, the state at every chunk's start and the one after
the last token go out.  Inside a chunk everything is a product on the MXU:
with cum the running sum of dt A inside the chunk, query q sees source s <=
q through (C_q . B_s) exp(cum_q - cum_s) dt_s x_s, a [Q, Q] x [Q, P] product
a head (the [Q, Q] of C B^T is shared by the heads, there being one group);
the state it started from adds exp(cum_q) C_q H^T; and the chunk hands on
exp(cum_Q) H + sum_s exp(cum_Q - cum_s) dt_s x_s B_s^T, a [P, Q] x [Q, N]
product (dt x comes in both ways round, [Q, P] and [P, Q], so that every
weight is a row along the lanes).  A step of 0 (padding) decays nothing and
adds nothing.  The exponents' differences come from one row a head: exp
needs cum_q down the sublanes and cum_s along the lanes, and the column is
the transpose of the row laid [Q, Q].

`paddle_ssm_decode_update` (one token a lane) is bound by the state's
bytes: it reads and writes every live lane's state once, in place.  The
operand is the WHOLE array of held states [layers, slots, H / r, N, r * P]
(`fused.ssm_pack_state`: r heads side by side on the lanes, N down the
sublanes), aliased to the result, and the grid is (slots, head blocks): the
scalar-prefetched list of lanes (the live ones first) and their count pick
each step's block, a step past the live lanes keeps the last live block's
index (nothing is fetched or written for it) and skips the arithmetic, so
the time follows the live lanes and a dead lane's state is never touched.
In that layout a head's decay and dt x are rows along the lanes and the
lane's B and C run down the sublanes: every operand of H' = H * decay + dtx
* B is a row or a column broadcast, and y = sum over the sublanes of H' * C
is a row again.  The columns of B and C are the transposes of their rows
laid [r * P, N].  The layer is an operand (scalar-prefetched), not a
constant: a decode executable traces and lowers the kernel once.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import DoesNotTile, im as _im, interpret_default as _interpret_default

# heads a grid step of the chunked scan runs (their states, [P, N] float32
# each, live in VMEM across the chunks), and head rows of the held layout a
# grid step of the update moves (1 MiB of float32 at N = r P = 128)
_SCAN_HEADS = 8
_UPDATE_ROWS = 16


# -- the prompt pass ---------------------------------------------------------

def _scan_kernel(xd_ref, xdt_ref, cum_ref, w_ref, el_ref, b_ref, c_ref,
                 s0_ref, y_ref, st_ref, fin_ref, s_scr, *, hb, nc):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _():
        s_scr[...] = s0_ref[...]

    st_ref[...] = s_scr[...]
    bc, cc = b_ref[...], c_ref[...]                      # [Q, N]
    q = bc.shape[0]
    cb = lax.dot_general(cc, bc, (((1,), (1,)), ((), ())),
                         preferred_element_type=jnp.float32)      # [Q, Q]
    seen = lax.broadcasted_iota(jnp.int32, (q, q), 0) \
        >= lax.broadcasted_iota(jnp.int32, (q, q), 1)
    for h in range(hb):
        row = jnp.broadcast_to(cum_ref[h:h + 1, :], (q, q))  # [., s] cum_s
        col = row.T                                          # [q, .] cum_q
        decay = jnp.where(seen, jnp.exp(jnp.minimum(col - row, 0.0)), 0.0)
        xd = xd_ref[h]                                       # [Q, P] dt x
        y = jnp.dot((cb * decay).astype(xd.dtype), xd,
                    preferred_element_type=jnp.float32)
        s = s_scr[h]                                         # [P, N]
        y = y + jnp.exp(col[:, 0:1]) * lax.dot_general(
            cc, s.astype(cc.dtype), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        y_ref[h] = y
        # the chunk's own part of the state at its end: (dt x)^T, each
        # source weighted by exp(cum_Q - cum_s) along the lanes, times B
        xw = (xdt_ref[h].astype(jnp.float32) * w_ref[h:h + 1, :]) \
            .astype(xd.dtype)                                # [P, Q]
        s_scr[h] = s * el_ref[h:h + 1, :] + jnp.dot(
            xw, bc, preferred_element_type=jnp.float32)

    @pl.when(c == nc - 1)
    def _():
        fin_ref[...] = s_scr[...]


@functools.partial(jax.jit, static_argnames=("chunk", "hb", "interpret"))
def _scan_call(x, dt, A, B, C, state0, chunk, hb, interpret):
    T, H, P = x.shape
    N = B.shape[-1]
    nc = -(-T // chunk)
    pad = nc * chunk - T
    f32 = jnp.float32
    dt = jnp.pad(dt.astype(f32), ((0, pad), (0, 0)))
    # dt x, head-major, and its transpose; a row a head a chunk of the
    # running sum of dt A inside the chunk (cum), of exp(cum_Q - cum), and
    # of exp(cum_Q) along N lanes
    xd = (jnp.pad(x, ((0, pad), (0, 0), (0, 0))).astype(f32)
          * dt[:, :, None]).astype(x.dtype).transpose(1, 0, 2)
    cum = jnp.cumsum((dt * A.astype(f32)).reshape(nc, chunk, H), axis=1) \
        .transpose(0, 2, 1)                                  # [nc, H, Q]
    w_end = jnp.exp(cum[:, :, -1:] - cum)
    e_last = jnp.broadcast_to(jnp.exp(cum[:, :, -1:]), (nc, H, N))
    B, C = (jnp.pad(v, ((0, pad), (0, 0))).astype(x.dtype) for v in (B, C))
    heads = pl.BlockSpec((hb, chunk, P), _im(lambda g, c: (g, c, 0)))
    rows = pl.BlockSpec((None, hb, chunk), _im(lambda g, c: (c, g, 0)))
    tokens = pl.BlockSpec((chunk, N), _im(lambda g, c: (c, 0)))
    state = pl.BlockSpec((hb, P, N), _im(lambda g, c: (g, 0, 0)))
    y, starts, final = pl.pallas_call(
        functools.partial(_scan_kernel, hb=hb, nc=nc),
        name="paddle_ssd_chunk_scan",
        grid=(H // hb, nc),
        in_specs=[heads,
                  pl.BlockSpec((hb, P, chunk), _im(lambda g, c: (g, 0, c))),
                  rows, rows,
                  pl.BlockSpec((None, hb, N), _im(lambda g, c: (c, g, 0))),
                  tokens, tokens, state],
        out_specs=[heads,
                   pl.BlockSpec((None, hb, P, N),
                                _im(lambda g, c: (c, g, 0, 0))),
                   state],
        out_shape=[jax.ShapeDtypeStruct((H, nc * chunk, P), f32),
                   jax.ShapeDtypeStruct((nc, H, P, N), f32),
                   jax.ShapeDtypeStruct((H, P, N), f32)],
        scratch_shapes=[pltpu.VMEM((hb, P, N), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
    )(xd, xd.transpose(0, 2, 1), cum, w_end, e_last, B, C,
      state0.astype(f32))
    return y.transpose(1, 0, 2)[:T], final, starts


def ssd_chunk_scan(x, dt, A, B, C, state0, chunk: int,
                   interpret: bool | None = None):
    """The chunked scan of one sequence (`fused.ssd_chunk_scan` says what
    goes in and comes out).  Raises DoesNotTile for shapes the kernel does
    not tile: the chunk and N have to be multiples of 128 (the exponents'
    columns are transposes of whole tiles), P of 8, the heads of the head
    block."""
    T, H, P = x.shape
    N = B.shape[-1]
    if chunk % 128 or N % 128 or P % 8:
        raise DoesNotTile(
            f"ssd_chunk_scan: chunk {chunk}, N {N}, P {P} are not whole "
            "tiles")
    hb = min(_SCAN_HEADS, H)
    if H % hb or (hb % 8 and hb != H):
        raise DoesNotTile(f"ssd_chunk_scan: {H} heads in blocks of {hb}")
    if interpret is None:
        interpret = _interpret_default()
    return _scan_call(x, dt, A, B, C, state0, int(chunk), int(hb),
                      bool(interpret))


# -- one token a lane --------------------------------------------------------

def _update_kernel(plane_ref, lanes_ref, n_ref, s_ref, da_ref, dx_ref, b_ref,
                   c_ref, o_ref, y_ref, *, hb):
    del plane_ref, lanes_ref
    i, j = pl.program_id(0), pl.program_id(1)
    n = n_ref[0]

    @pl.when(i < n)
    def _():
        rp = s_ref.shape[-1]
        # B and C down the sublanes: their rows laid [r P, N], transposed
        bcol = jnp.broadcast_to(b_ref[...], (rp, b_ref.shape[-1])).T
        ccol = jnp.broadcast_to(c_ref[...], (rp, c_ref.shape[-1])).T
        for h in range(hb):
            new = s_ref[h] * da_ref[h:h + 1, :] + dx_ref[h:h + 1, :] * bcol
            o_ref[h] = new
            y_ref[h:h + 1, :] = jnp.sum(new * ccol, axis=0, keepdims=True)

    @pl.when((n == 0) & (i == 0) & (j == 0))
    def _():
        # no lane is live: every step maps to one block, which goes back
        # as it came
        o_ref[...] = s_ref[...]
        y_ref[...] = jnp.zeros_like(y_ref)


@functools.partial(jax.jit, static_argnames=("hb", "interpret"))
def _update_call(plane, lanes, n_live, ssm, decay, dtx, B, C, hb, interpret):
    _, slots, hr, N, rp = ssm.shape
    nblk = hr // hb

    def where(i, j, plane, lanes, n):
        """The block of grid step (i, j): lane lanes[i]'s, or the last live
        block's once the live lanes are done."""
        live = i < n[0]
        lane = lanes[jnp.where(live, i, jnp.maximum(n[0] - 1, 0))]
        return lane, jnp.where(live, j, nblk - 1)

    def held(i, j, plane, lanes, n):
        lane, blk = where(i, j, plane, lanes, n)
        return plane[0], lane, blk, 0, 0

    def rows(i, j, plane, lanes, n):
        lane, blk = where(i, j, plane, lanes, n)
        return lane, blk, 0

    def lane_row(i, j, plane, lanes, n):
        return where(i, j, plane, lanes, n)[0], 0, 0

    state = pl.BlockSpec((None, None, hb, N, rp), _im(held))
    head_rows = pl.BlockSpec((None, hb, rp), _im(rows))
    token = pl.BlockSpec((None, 1, N), _im(lane_row))
    out, y = pl.pallas_call(
        functools.partial(_update_kernel, hb=hb),
        name="paddle_ssm_decode_update",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(slots, nblk),
            in_specs=[state, head_rows, head_rows, token, token],
            out_specs=[state, head_rows]),
        out_shape=[jax.ShapeDtypeStruct(ssm.shape, ssm.dtype),
                   jax.ShapeDtypeStruct((slots, hr, rp), jnp.float32)],
        # the held states are rewritten where they lie (operand 3, counting
        # the prefetched scalars, is result 0)
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(plane, lanes, n_live, ssm, decay, dtx, B[:, None, :], C[:, None, :])
    return y, out


def ssm_decode_update(ssm, plane, lanes, n_live, decay, dtx, B, C,
                      interpret: bool | None = None):
    """One token of every live lane through layer `plane` of the held
    states, in place.

    ssm [layers, slots, Hr, N, rP] float32 (the WHOLE array: it is aliased
    to the result); plane: the layer (a Python int or a traced scalar: an
    operand of the kernel); lanes [slots] int32, the live lanes first;
    n_live: how many of them are live; decay = exp(dt A) and dtx = dt x,
    both [slots, Hr, rP] float32 in the held layout's rows; B, C [slots, N]
    float32.  Returns (y [slots, Hr, rP] float32 (a dead lane's rows hold
    whatever), ssm').  Raises DoesNotTile where the held layout is not
    whole tiles."""
    if ssm.ndim != 5 or ssm.dtype != jnp.float32:
        raise ValueError(
            "ssm_decode_update takes the whole held states [layers, slots, "
            f"Hr, N, rP] in float32, got {ssm.shape} {ssm.dtype}")
    _, slots, hr, N, rp = ssm.shape
    if rp % 128 or N % 128:
        raise DoesNotTile(
            f"ssm_decode_update: a held row of ({N}, {rp}) is not whole "
            "tiles")
    hb = min(_UPDATE_ROWS, hr)
    if hr % hb or (hb % 8 and hb != hr):
        raise DoesNotTile(
            f"ssm_decode_update: {hr} head rows in blocks of {hb}")
    if interpret is None:
        interpret = _interpret_default()
    i32 = jnp.int32
    return _update_call(
        jnp.asarray(plane, i32).reshape(1), jnp.asarray(lanes, i32),
        jnp.asarray(n_live, i32).reshape(1), ssm, decay, dtx, B, C,
        hb=int(hb), interpret=bool(interpret))
