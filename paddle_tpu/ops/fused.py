"""Fused transformer-path ops.

Reference parity: paddle/fluid/operators/fused/ — multihead_matmul_op.cu
(BERT attention), skip_layernorm_op.cu (residual+LN), layer_norm_op.cu fused
kernels, softmax_with_cross_entropy_op.cu (fused loss), and
math/bert_encoder_functor.cu.  The seed additionally named
fused_attention / fused_feedforward / fused_multi_transformer as intent
(SURVEY.md section 2).

TPU-native: each fused op has an XLA composite implementation (XLA fuses the
elementwise pieces into the matmuls on its own) and, for the hot ones, a
Pallas TPU kernel (ops/pallas/) that takes over when FLAGS_use_pallas_kernels
is on AND the arrays live on a TPU backend.  Selection happens here.
"""
from __future__ import annotations

import functools
import warnings

import jax
import jax.numpy as jnp

from ..framework.flags import flag
from ..tensor import Tensor, apply, unwrap


def _use_pallas() -> bool:
    return bool(flag("FLAGS_use_pallas_kernels")) \
        and jax.default_backend() != "cpu"


# ---------------------------------------------------------------------------
# fallback telemetry: an accidentally-XLA hot path must be VISIBLE
# ---------------------------------------------------------------------------
_warned_sites: set = set()


def fallback_counter():
    """The shared-registry `paddle_pallas_fallbacks_total{kernel,reason}`
    counter (zero-initialized lazily; rendered by /metrics)."""
    from ..utils.metrics import default_registry

    return default_registry().counter(
        "paddle_pallas_fallbacks_total",
        "fused-op calls that fell back to XLA while "
        "FLAGS_use_pallas_kernels was on, by kernel and reason",
        label=("kernel", "reason"))


def _note_fallback(kernel: str, reason: str):
    """Record one Pallas->XLA fallback: bump the shared-registry counter
    and warn ONCE per (kernel, reason) site.  Dispatch happens at trace
    time, so one recorded fallback means every step of that compiled
    graph runs the XLA path."""
    fallback_counter().inc((kernel, reason))
    site = (kernel, reason)
    if site not in _warned_sites:
        _warned_sites.add(site)
        warnings.warn(
            f"FLAGS_use_pallas_kernels is on but '{kernel}' fell back to "
            f"the XLA composite ({reason}); the hot path is NOT running "
            f"the Pallas kernel (see paddle_pallas_fallbacks_total in "
            f"/metrics)", RuntimeWarning, stacklevel=3)


def _kernel_or_none(kernel: str, call):
    """The result of `call`, one Pallas kernel dispatch, or None (counted)
    when the kernel itself says from the shapes that it does not tile
    this call.  Nothing else chooses the composite: an error from
    tracing, lowering or compiling the kernel propagates."""
    from .pallas import DoesNotTile

    try:
        return call()
    except DoesNotTile as e:
        _note_fallback(kernel, "mask_shape" if "mask" in str(e) else "shape")
        return None


def _mesh_axes():
    """(mesh, batch_axes, tp_axis) for kernel shard_map composition:
    batch axes are the >1-sized data axes ('dp'/'fsdp'), tp is the
    >1-sized head/column axis under either naming scheme — the models'
    in-layer 'mp' pin or SpecLayout's 'tp'.  The mesh is None only when
    no ambient mesh spans more than one device: a Mosaic kernel cannot be
    partitioned by GSPMD, so under any wider mesh EVERY kernel call goes
    through shard_map, with the axes that do not divide it left out."""
    from ..distributed.mesh import get_mesh

    mesh = get_mesh()
    if mesh is None or mesh.size <= 1:
        return None, (), None
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    batch = tuple(a for a in ("dp", "fsdp") if sizes.get(a, 1) > 1)
    tp = next((a for a in ("mp", "tp") if sizes.get(a, 1) > 1), None)
    return mesh, batch, tp


def _axes_size(mesh, axes) -> int:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n = 1
    for a in axes:
        n *= sizes.get(a, 1)
    return n


def _dividing(dim: int, mesh, axes) -> tuple:
    """The leading axes of `axes` whose joint size divides `dim`."""
    while axes and dim % _axes_size(mesh, axes) != 0:
        axes = axes[:-1]
    return axes


def _axes_entry(axes):
    """A PartitionSpec entry for a tuple of mesh axes (None if empty)."""
    return (axes if len(axes) > 1 else axes[0]) if axes else None


def _rows_entry(x, mesh, batch):
    """The PartitionSpec entry for x's leading (row) dim: the batch axes
    that divide it; None for a vector, whose only dim is the feature dim."""
    return _axes_entry(_dividing(x.shape[0], mesh, batch)) \
        if x.ndim >= 2 else None


def _axis_if_divides(dim: int, mesh, axis):
    """`axis` when the mesh has it and its size divides `dim`, else None."""
    return axis if axis is not None \
        and dim % _axes_size(mesh, (axis,)) == 0 else None


def _rows_sharded(kernel, mesh, batch, x, *replicated):
    """`kernel(x, *replicated)` under shard_map: x's rows split over the
    batch axes that divide them, every other operand whole."""
    from jax.sharding import PartitionSpec as P

    xspec = P(_rows_entry(x, mesh, batch), *([None] * (x.ndim - 1)))
    return jax.shard_map(
        kernel, mesh=mesh,
        in_specs=(xspec,) + tuple(P() for _ in replicated),
        out_specs=xspec, check_vma=False)(x, *replicated)


# ---------------------------------------------------------------------------
# layer norm (fused scale+shift; Pallas row kernel on TPU)
# ---------------------------------------------------------------------------
def layer_norm(x, weight, bias, epsilon=1e-5):
    if _use_pallas():
        from .pallas import layer_norm as pln

        mesh, batch, _ = _mesh_axes()

        def kernel(v, w, b):
            return pln.layer_norm(v, w, b, epsilon)

        def pf(v, w, b):
            if mesh is not None:
                return _rows_sharded(kernel, mesh, batch, v, w, b)
            return kernel(v, w, b)

        out = _kernel_or_none("layer_norm",
                              lambda: apply(pf, x, weight, bias))
        if out is not None:
            return out

    def f(v, w, b):
        mean = jnp.mean(v, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(v - mean), axis=-1, keepdims=True)
        return (v - mean) * jax.lax.rsqrt(var + epsilon) * w + b

    return apply(f, x, weight, bias)


def skip_layer_norm(x, residual, weight, bias, epsilon=1e-5):
    """residual-add + LN in one op (skip_layernorm_op.cu analog)."""
    def f(v, r, w, b):
        h = v + r
        mean = jnp.mean(h, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(h - mean), axis=-1, keepdims=True)
        return (h - mean) * jax.lax.rsqrt(var + epsilon) * w + b
    return apply(f, x, residual, weight, bias)


# ---------------------------------------------------------------------------
# softmax cross entropy (fused, numerically stable)
# ---------------------------------------------------------------------------
def softmax_cross_entropy(logits, label, ignore_index=-100):
    if _use_pallas():
        from .pallas import softmax_xent as sx

        mesh, batch, _ = _mesh_axes()

        def pf(z, l):
            if mesh is not None:
                from jax.sharding import PartitionSpec as P

                lead = _rows_entry(z, mesh, batch)
                li = l if l.ndim == z.ndim - 1 else jnp.squeeze(l, -1)
                body = functools.partial(sx.softmax_xent,
                                         ignore_index=ignore_index)
                return jax.shard_map(
                    body, mesh=mesh,
                    in_specs=(P(lead, *([None] * (z.ndim - 1))),
                              P(lead, *([None] * (li.ndim - 1)))),
                    out_specs=P(lead, *([None] * (z.ndim - 2))),
                    check_vma=False)(z, li)
            return sx.softmax_xent(z, l, ignore_index=ignore_index)

        out = _kernel_or_none("softmax_xent",
                              lambda: apply(pf, logits, label))
        if out is not None:
            return out

    def f(z, l):
        li = l.astype(jnp.int32)
        if li.ndim == z.ndim:
            li = jnp.squeeze(li, -1)
        m = jnp.max(z, axis=-1, keepdims=True)
        shifted = z - jax.lax.stop_gradient(m)
        lse = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1))
        picked = jnp.take_along_axis(shifted, li[..., None], axis=-1)[..., 0]
        loss = lse - picked
        return jnp.where(li == ignore_index, 0.0, loss)
    return apply(f, logits, label)


# ---------------------------------------------------------------------------
# fused LM-head matmul + cross entropy, chunked over the vocab
# ---------------------------------------------------------------------------
def _flce_impl(h, w, labels, chunk):
    """Online-logsumexp over vocab chunks: never materializes the full
    [N, V] logits in fp32 (the [B*S, 30k+] fp32 buffer is the single
    largest allocation in a BERT/GPT loss)."""
    N, H = h.shape
    V = w.shape[1]
    n_chunks = -(-V // chunk)
    Vp = n_chunks * chunk
    wp = jnp.pad(w, ((0, 0), (0, Vp - V)))
    w_chunks = wp.reshape(H, n_chunks, chunk).transpose(1, 0, 2)
    hf = h.astype(jnp.float32)
    li = labels.astype(jnp.int32)

    def body(carry, wc_i):
        m, s, picked = carry
        wc, i = wc_i
        z = (hf @ wc.astype(jnp.float32))              # [N, chunk] fp32
        base = i * chunk
        # mask padded vocab tail
        valid = (base + jnp.arange(chunk)) < V
        z = jnp.where(valid[None, :], z, -jnp.inf)
        m_new = jnp.maximum(m, z.max(-1))
        s = s * jnp.exp(m - m_new) + jnp.exp(
            z - m_new[:, None]).sum(-1)
        in_chunk = (li >= base) & (li < base + chunk)
        local = jnp.clip(li - base, 0, chunk - 1)
        picked = picked + jnp.where(
            in_chunk, jnp.take_along_axis(z, local[:, None], 1)[:, 0], 0.0)
        return (m_new, s, picked), None

    init = (jnp.full((N,), -jnp.inf, jnp.float32),
            jnp.zeros((N,), jnp.float32), jnp.zeros((N,), jnp.float32))
    (m, s, picked), _ = jax.lax.scan(
        body, init, (w_chunks, jnp.arange(n_chunks)))
    return jnp.log(s) + m - picked, (m, s)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _flce(h, w, labels, chunk):
    loss, _ = _flce_impl(h, w, labels, chunk)
    return loss


def _flce_fwd(h, w, labels, chunk):
    loss, (m, s) = _flce_impl(h, w, labels, chunk)
    return loss, (h, w, labels, m, s)


def _flce_bwd(chunk, res, g):
    h, w, labels, m, s = res
    N, H = h.shape
    V = w.shape[1]
    n_chunks = -(-V // chunk)
    Vp = n_chunks * chunk
    wp = jnp.pad(w, ((0, 0), (0, Vp - V)))
    w_chunks = wp.reshape(H, n_chunks, chunk).transpose(1, 0, 2)
    hf = h.astype(jnp.float32)
    li = labels.astype(jnp.int32)
    lse = jnp.log(s) + m
    gf = g.astype(jnp.float32)

    def body(dh, wc_i):
        wc, i = wc_i
        wcf = wc.astype(jnp.float32)
        z = hf @ wcf
        base = i * chunk
        valid = (base + jnp.arange(chunk)) < V
        p = jnp.where(valid[None, :], jnp.exp(z - lse[:, None]), 0.0)
        onehot = ((li[:, None] - base) ==
                  jnp.arange(chunk)[None, :]).astype(jnp.float32)
        dz = (p - onehot) * gf[:, None]               # [N, chunk]
        dh = dh + dz @ wcf.T
        dwc = hf.T @ dz                               # [H, chunk]
        return dh, dwc

    dh, dwcs = jax.lax.scan(body, jnp.zeros((N, H), jnp.float32),
                            (w_chunks, jnp.arange(n_chunks)))
    dw = dwcs.transpose(1, 0, 2).reshape(H, Vp)[:, :V]
    return dh.astype(h.dtype), dw.astype(w.dtype), None


_flce.defvjp(_flce_fwd, _flce_bwd)


def fused_linear_cross_entropy(hidden, weight, labels, chunk_size=8192):
    """loss = cross_entropy(hidden @ weight, labels), streamed over vocab
    chunks (TPU-native extension; the reference's closest analog is the
    fused softmax_with_cross_entropy_op.cc — this additionally fuses the
    LM-head matmul so the fp32 [N, V] logits never hit HBM at once).

    hidden [..., H], weight [H, V], labels [...] int. Returns per-token
    loss with hidden's leading shape.
    """
    def f(h, w, l):
        lead = h.shape[:-1]
        hf = h.reshape(-1, h.shape[-1])
        lf = l.reshape(-1)
        loss = _flce(hf, w, lf, chunk_size)
        return loss.reshape(lead)

    return apply(f, hidden, weight, labels)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------
def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False, training=True):
    """[B, S, H, D] in, [B, S, H, D] out (paddle layout)."""
    if _use_pallas():
        if dropout_p > 0.0 and training:
            # attention dropout has no kernel path (rng-in-kernel is out of
            # scope); the one hot loop that sets it (BERT/ERNIE training)
            # should see this in the fallback counter, not run silently slow
            _note_fallback("flash_attention", "dropout")
        else:
            from .pallas import flash_attention as fa

            mesh, batch, tp = _mesh_axes()

            def pf(q, k, v, *mask):
                m = mask[0] if mask else None
                if mesh is None:
                    return fa.flash_attention(q, k, v, causal=is_causal,
                                              mask=m)
                # an ambient mesh whose axes don't divide this call's
                # geometry must not knock it off the kernel path: shed
                # non-dividing axes and keep the (replicated) kernel
                return fa.sharded_flash_attention(
                    q, k, v, mesh,
                    head_axis=_axis_if_divides(q.shape[2], mesh, tp),
                    batch_axes=_dividing(q.shape[0], mesh, batch),
                    causal=is_causal, mask=m)

            args = (query, key, value) + (
                (attn_mask,) if attn_mask is not None else ())
            out = _kernel_or_none("flash_attention",
                                  lambda: apply(pf, *args))
            if out is not None:
                return out

    from ..framework import random as _random

    key_rng = _random.split_key() if (dropout_p > 0.0 and training) else None

    def f(q, k, v, *mask):
        scale = 1.0 / jnp.sqrt(q.shape[-1]).astype(q.dtype)
        # [B,S,H,D] -> [B,H,S,D]
        qh = jnp.swapaxes(q, 1, 2)
        kh = jnp.swapaxes(k, 1, 2)
        vh = jnp.swapaxes(v, 1, 2)
        logits = jnp.einsum("bhsd,bhtd->bhst", qh, kh) * scale
        if is_causal:
            s, t = logits.shape[-2], logits.shape[-1]
            cm = jnp.tril(jnp.ones((s, t), bool))
            logits = jnp.where(cm, logits, jnp.asarray(-1e30, logits.dtype))
        if mask:
            m = mask[0]
            if m.dtype == jnp.bool_:
                logits = jnp.where(m, logits, jnp.asarray(-1e30, logits.dtype))
            else:
                logits = logits + m
        w = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
        if key_rng is not None:
            keep = jax.random.bernoulli(key_rng, 1.0 - dropout_p, w.shape)
            w = jnp.where(keep, w / (1.0 - dropout_p), 0.0)
        out = jnp.einsum("bhst,bhtd->bhsd", w, vh)
        return jnp.swapaxes(out, 1, 2)

    args = (query, key, value) + ((attn_mask,) if attn_mask is not None else ())
    return apply(f, *args)


def masked_attention(q, keys, values, valid):
    """Explicit softmax attention of new tokens over a cached key/value
    view — the one arithmetic every KV source (serving/kv_cache.py,
    models/gpt.py ``DenseKV``) reduces to, so an engine lane, a verified
    chunk and a solo ``generate`` run agree bitwise.

    q [B, Q, nh, hd]; keys/values [B, S, nh, hd]; valid bool [B|1, Q|1, S]
    (True = query row may see key column).  Raw jax arrays in and out;
    returns [B, Q, nh, hd].  Masked columns get ``finfo.min``, so
    their ``exp`` underflows to exactly 0.
    """
    scores = jnp.einsum("bqnd,bsnd->bnqs", q, keys) \
        * (1.0 / float(q.shape[-1]) ** 0.5)
    scores = jnp.where(valid[:, None], scores, jnp.finfo(scores.dtype).min)
    probs = jnp.exp(scores - jax.lax.stop_gradient(
        scores.max(axis=-1, keepdims=True)))
    probs = probs / probs.sum(axis=-1, keepdims=True)
    return jnp.einsum("bnqs,bsnd->bqnd", probs, values)


def banded_attention(q, keys, values, offset=0, window=0, floor=0,
                     block=512):
    """Attention of a run of queries over a run of keys under a band:
    query i sees key c iff ``floor <= c <= i + offset`` and, with a
    ``window``, ``c > i + offset - window``.  Causal attention among a
    prompt's tokens is ``offset=0``; a suffix over [prefix ++ suffix] is
    ``offset=len(prefix)`` with ``floor`` hiding the rows before the
    prefix's first token.

    q [B, Q, nh, hd]; keys/values [B, S, nkv, hd], nkv dividing nh (query
    head h reads KV head h // (nh / nkv); the keys are never expanded).
    ``offset``, ``window`` and ``block`` static, ``floor`` may be traced.
    Queries go ``block`` at a time, so the scores held at once are
    [nh, block, S] float32 and never [nh, Q, S]; under a window a block
    reads only the ``block + window`` keys that some query of it can see.
    Softmax in float32 (a prompt of thousands of keys sums thousands of
    terms).  Raw jax arrays in and out; returns [B, Q, nh, hd].
    """
    B, Q, nh, hd = q.shape
    S, nkv = keys.shape[1], keys.shape[2]
    g = nh // nkv
    scale = 1.0 / float(hd) ** 0.5

    def part(qb, i0):
        n = qb.shape[1]
        kb, vb, start = keys, values, 0
        if window and S > n + window:
            # the first key query i0 sees is i0 + offset - window + 1
            start = jnp.clip(i0 + offset - window + 1, 0, S - n - window)
            kb = jax.lax.dynamic_slice_in_dim(keys, start, n + window, 1)
            vb = jax.lax.dynamic_slice_in_dim(values, start, n + window, 1)
        c = start + jnp.arange(kb.shape[1])[None]
        i = i0 + offset + jnp.arange(n)[:, None]
        ok = (c <= i) & (c >= floor)
        if window:
            ok = ok & (c > i - window)
        scores = jnp.einsum("bqngd,bsnd->bngqs",
                            qb.reshape(B, n, nkv, g, hd), kb,
                            preferred_element_type=jnp.float32) * scale
        scores = jnp.where(ok, scores, jnp.finfo(jnp.float32).min)
        probs = jnp.exp(scores - scores.max(axis=-1, keepdims=True))
        probs = probs / probs.sum(axis=-1, keepdims=True)
        out = jnp.einsum("bngqs,bsnd->bqngd", probs.astype(vb.dtype), vb)
        return out.reshape(B, n, nh, hd)

    if Q <= block or Q % block:
        return part(q, 0)
    nb = Q // block
    out = jax.lax.map(
        lambda x: part(*x),
        (q.reshape(B, nb, block, nh, hd).swapaxes(0, 1),
         jnp.arange(nb) * block))
    return out.swapaxes(0, 1).reshape(B, Q, nh, hd)


# ---------------------------------------------------------------------------
# paged decode attention (fused_multi_transformer's masked decode analog):
# ragged Pallas kernel walking each lane's page-table row over the KV pool
# ---------------------------------------------------------------------------
def paged_decode_attention(q, k_pages, v_pages, rows, pos, seq_cap, layer,
                           tp_axis=None, window=0):
    """Pallas paged decode attention over plane `layer` of the whole KV
    pool, or None when the kernels are off or the kernel says it does not
    tile this geometry (the caller keeps its dense-gather reference path
    and the refusal shows up in the fallback counter).

    A pool of as many heads as the query has, and no window, is
    `paddle_paged_decode_fwd`'s; a pool of fewer (KV heads, each read by a
    group of query heads), a layer that sees a static `window` of keys, or
    a lane with more than one query is `paddle_paged_gqa_decode_fwd`'s (no
    mesh yet: under one it is refused, counted, and the caller gathers).

    q [slots, C, nh, hd] (the step's queries, post-scatter); k_pages/v_pages
    [layers, num_pages, page_size, nkv, hd] (the stacked pools, never a
    slice of them: a sliced operand is a copied plane); rows [slots,
    pages_per_slot] int32 (-1 = unmapped); pos [slots] int32 inclusive
    extent, ONE a lane: with C > 1 every query of a lane sees the keys up
    to it (a block step's `limits`), and there is no window; seq_cap and
    layer static.  Returns [slots, C, nh, hd].
    `tp_axis` names the mesh axis the pool's head dim is sharded over (the
    models' "mp" pin), if any.
    """
    if not _use_pallas():
        return None
    from .pallas import paged_attention as pa

    mesh, _, _ = _mesh_axes()

    one = q.shape[1] == 1
    grouped = bool(window) or k_pages.shape[3] != q.shape[2] or not one

    def pf(qv, kp, vp, rw, ps_):
        q1 = qv[:, 0] if one else qv
        if grouped:
            if mesh is not None:
                raise pa.DoesNotTile(
                    "paged_gqa_decode_attention is not composed with a "
                    "mesh yet")
            out = pa.paged_gqa_decode_attention(q1, kp, vp, rw, ps_,
                                                seq_cap, layer, window)
        elif mesh is not None:
            out = pa.sharded_paged_decode_attention(
                q1, kp, vp, rw, ps_, seq_cap, layer, mesh,
                tp_axis if tp_axis in mesh.axis_names else None)
        else:
            out = pa.paged_decode_attention(q1, kp, vp, rw, ps_, seq_cap,
                                            layer)
        return out[:, None] if one else out

    return _kernel_or_none(
        "paged_attention", lambda: apply(pf, q, k_pages, v_pages, rows, pos))


# ---------------------------------------------------------------------------
# dropless mixture of experts: assignments sorted by expert, one grouped
# product over the groups' rows (Pallas `paddle_moe_gmm`), combine
# ---------------------------------------------------------------------------
def moe_layout(expert_ids, num_experts: int, tm: int):
    """Where each assignment's row goes when rows are grouped by expert
    with every group starting at a multiple of ``tm`` (a counting sort:
    no token is dropped, whatever the imbalance).

    expert_ids [N, k] int32.  Returns (dest [N, k] row of each assignment
    in the grouped buffer, src [Mp] the token each grouped row holds
    (padding rows hold token 0), tile_expert [Mp // tm] the expert of each
    row tile, n_used the tiles that hold rows, group_rows [E] each group's
    rows with its padding); Mp is static."""
    n, k = expert_ids.shape
    a, e = n * k, num_experts
    mp = -(-min(a + e * (tm - 1), a * tm) // tm) * tm
    flat = expert_ids.reshape(a).astype(jnp.int32)
    seen = jnp.cumsum(
        (flat[:, None] == jnp.arange(e, dtype=jnp.int32)[None]).astype(
            jnp.int32), axis=0)                               # [A, E]
    rank = jnp.take_along_axis(seen, flat[:, None], 1)[:, 0] - 1
    group_rows = (seen[-1] + tm - 1) // tm * tm
    ends = jnp.cumsum(group_rows)
    dest = (ends - group_rows)[flat] + rank
    src = jnp.zeros((mp,), jnp.int32).at[dest].set(
        jnp.arange(a, dtype=jnp.int32) // k)
    n_used = ends[-1] // tm
    tile = jnp.arange(mp // tm, dtype=jnp.int32)
    te = jnp.minimum(jnp.searchsorted(ends, tile * tm, side="right"),
                     e - 1).astype(jnp.int32)
    # tiles past the rows keep the last used expert: nothing new to fetch
    te = jnp.where(tile < n_used, te, te[jnp.maximum(n_used - 1, 0)])
    return dest.reshape(n, k), src, te, n_used.astype(jnp.int32), group_rows


def grouped_expert_ffn(x, w_gate, w_up, w_down, tile_expert, n_used,
                       group_rows, tm: int, differentiable=False):
    """W_down[e] (silu(W_gate[e] x) * W_up[e] x) over rows grouped by
    expert (``moe_layout``): the Pallas kernel on a TPU, ``lax.ragged_dot``
    as the counted fallback and wherever a gradient is wanted (the kernel
    has no backward pass yet).  Raw jax arrays in and out, [Mp, H]."""
    if _use_pallas() and not differentiable:
        from .pallas import moe_gmm as mg

        out = _kernel_or_none("moe_gmm", lambda: mg.moe_gmm(
            x, w_gate, w_up, w_down, tile_expert, n_used, tm))
        if out is not None:
            return out
    g = jax.lax.ragged_dot(x, w_gate, group_rows,
                           preferred_element_type=jnp.float32)
    u = jax.lax.ragged_dot(x, w_up, group_rows,
                           preferred_element_type=jnp.float32)
    a = (g * jax.nn.sigmoid(g) * u).astype(x.dtype)
    return jax.lax.ragged_dot(
        a, w_down, group_rows,
        preferred_element_type=jnp.float32).astype(x.dtype)


def moe_dropless(x, expert_ids, expert_weights, w_gate, w_up, w_down,
                 differentiable=False):
    """sum_k weight[n, k] * expert_{ids[n, k]}(x[n]) for x [N, H], the
    gated-SiLU experts stacked as w_gate / w_up [E, H, F], w_down
    [E, F, H]; expert_ids / expert_weights [N, k].  Every assignment is
    computed (no capacity).  The weighted sum is taken in float32.  Raw
    jax arrays; returns [N, H] in x's dtype."""
    from .pallas.moe_gmm import pick_tile_rows

    n, k = expert_ids.shape
    tm = pick_tile_rows(n * k, w_gate.shape[0])
    dest, src, te, n_used, group_rows = moe_layout(
        expert_ids, w_gate.shape[0], tm)
    y = grouped_expert_ffn(x[src], w_gate, w_up, w_down, te, n_used,
                           group_rows, tm, differentiable)
    return jnp.einsum("nk,nkh->nh", expert_weights.astype(jnp.float32),
                      y[dest].astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# selective state-space scan (Mamba-2, one group of B and C): the chunked
# prompt pass (Pallas `paddle_ssd_chunk_scan`) and the one-token update of
# the live lanes' state (Pallas `paddle_ssm_decode_update`)
# ---------------------------------------------------------------------------
def ssm_pack(heads: int, head_dim: int) -> int:
    """Heads that share one row of a held state: the most that divide
    `heads` and fill no more than 128 lanes (2 heads of 64)."""
    r = max(1, min(heads, 128 // max(1, head_dim)))
    while heads % r:
        r -= 1
    return r


def ssm_pack_state(h, pack: int):
    """A state as the scan computes it, [..., H, P, N], in the layout it is
    HELD in, [..., H / pack, N, pack * P]: the state's N on the sublanes
    and `pack` heads side by side on the lanes, so that the one-token update
    multiplies every row of a head pair by rows it can broadcast (a head's
    x and decay along the lanes, the lane's B and C down the sublanes) and
    never needs a column."""
    *lead, H, P, N = h.shape
    n = len(lead)
    h = h.reshape(*lead, H // pack, pack, P, N)
    return jnp.transpose(h, (*range(n), n, n + 3, n + 1, n + 2)) \
        .reshape(*lead, H // pack, N, pack * P)


def ssm_unpack_state(s, pack: int):
    """`ssm_pack_state`'s inverse: [..., H / pack, N, pack * P] -> [..., H,
    P, N]."""
    *lead, Hr, N, rP = s.shape
    n = len(lead)
    s = s.reshape(*lead, Hr, N, pack, rP // pack)
    return jnp.transpose(s, (*range(n), n, n + 2, n + 3, n + 1)) \
        .reshape(*lead, Hr * pack, rP // pack, N)


def _ssd_chunks(x, dt, A, B, C, chunk):
    """The operands of a chunked scan, padded to whole chunks with steps of
    0 (which neither decay the state nor add to it) and laid [chunks, Q,
    ...]; `cum` is the running sum of dt * A inside each chunk."""
    T, H, P = x.shape
    nc = -(-T // chunk)
    pad = nc * chunk - T

    def lay(v):
        v = jnp.pad(v, ((0, pad),) + ((0, 0),) * (v.ndim - 1))
        return v.reshape((nc, chunk) + v.shape[1:])

    x, dt, B, C = lay(x), lay(dt.astype(jnp.float32)), lay(B), lay(C)
    cum = jnp.cumsum(dt * A.astype(jnp.float32), axis=1)      # [nc, Q, H]
    return x, dt, B, C, cum


def _ssd_composite(x, dt, A, B, C, state0, chunk):
    """The chunked scan in `jax.numpy` (the CPU's path, the counted
    fallback, and what the kernel is tested against)."""
    T, H, P = x.shape
    f32 = jnp.float32
    xc, dtc, Bc, Cc, cum = _ssd_chunks(x, dt, A, B, C, chunk)
    Q = xc.shape[1]
    # inside a chunk: y_q += sum_{s <= q} (C_q . B_s) exp(cum_q - cum_s)
    # dt_s x_s
    cb = jnp.einsum("cqn,csn->cqs", Cc, Bc, preferred_element_type=f32)
    seen = jnp.arange(Q)[:, None] >= jnp.arange(Q)[None, :]
    gap = jnp.where(seen[None, :, :, None],
                    cum[:, :, None, :] - cum[:, None, :, :], -jnp.inf)
    m = cb[..., None] * jnp.exp(gap) * dtc[:, None, :, :]     # [nc, Q, S, H]
    y = jnp.einsum("cqsh,cshp->cqhp", m, xc.astype(f32))
    # a chunk's own contribution to the state at its end
    w_end = jnp.exp(cum[:, -1:, :] - cum) * dtc               # [nc, Q, H]
    local = jnp.einsum("cqh,cqhp,cqn->chpn", w_end, xc.astype(f32),
                       Bc.astype(f32))
    decay = jnp.exp(cum[:, -1, :])                            # [nc, H]

    def carry(s, inp):
        loc, dec = inp
        return dec[:, None, None] * s + loc, s

    final, starts = jax.lax.scan(carry, state0.astype(f32), (local, decay))
    y = y + jnp.einsum("cqn,chpn->cqhp", Cc.astype(f32), starts) \
        * jnp.exp(cum)[..., None]
    return y.reshape(-1, H, P)[:T], final, starts


def ssd_chunk_scan(x, dt, A, B, C, state0, chunk: int):
    """The selective scan of one sequence in chunks of `chunk` tokens:

        H_t = exp(dt_t A) H_{t-1} + dt_t x_t B_t^T,   y_t = H_t C_t

    x [T, H, P]; dt [T, H] >= 0 (after the softplus; a step of 0 leaves the
    state as it is: padding); A [H] < 0; B, C [T, N] (one group); state0
    [H, P, N].  Returns (y [T, H, P] float32, the state after the last
    token [H, P, N] float32, the state at every chunk's start [chunks, H,
    P, N] float32).  Inside a chunk the products run on the MXU, from chunk
    to chunk the state is carried.  Raw jax arrays."""
    if _use_pallas():
        from .pallas import ssm

        out = _kernel_or_none("ssd_chunk_scan", lambda: ssm.ssd_chunk_scan(
            x, dt, A, B, C, state0, chunk))
        if out is not None:
            return out
    return _ssd_composite(x, dt, A, B, C, state0, chunk)


def ssd_state_at(x, dt, A, B, starts, at, chunk: int):
    """The state after token `at` - 1 of a chunked scan (traced `at` in [0,
    T]; `starts` as `ssd_chunk_scan` returns them): the start of the chunk
    `at` lies in, carried over that chunk's tokens before `at`."""
    f32 = jnp.float32
    nc = starts.shape[0]
    at = jnp.asarray(at, jnp.int32)
    c = jnp.clip(at // chunk, 0, nc - 1)

    def of_chunk(v):    # the chunk's rows of v, zeros past the sequence
        v = jnp.pad(v, ((0, nc * chunk - v.shape[0]),)
                    + ((0, 0),) * (v.ndim - 1))
        return jax.lax.dynamic_slice_in_dim(v, c * chunk, chunk, 0)

    d = jnp.where(jnp.arange(chunk)[:, None] < at - c * chunk,
                  of_chunk(dt.astype(f32)), 0.0)
    cum = jnp.cumsum(d * A.astype(f32), axis=0)               # [Q, H]
    w_end = jnp.exp(cum[-1:] - cum) * d
    return jnp.exp(cum[-1])[:, None, None] * starts[c] + jnp.einsum(
        "qh,qhp,qn->hpn", w_end, of_chunk(x).astype(f32),
        of_chunk(B).astype(f32))


def ssm_decode_update(ssm, plane, lanes, n_live, x, dt, A, B, C):
    """One token of every live lane through layer `plane` of the held
    states, in place: H' = exp(dt A) H + dt x B^T, y = H' C.

    ssm [layers, slots, H / r, N, r * P] float32 (`ssm_pack_state`'s layout;
    the WHOLE array, so that a donated one is rewritten where it lies);
    plane static; lanes [slots] int32 the live lanes first, n_live how many
    they are (a dead lane's state stays as it is: the kernel neither reads
    nor writes it, the composite writes it back as it read it); x [slots, H,
    P]; dt [slots, H]; A [H]; B, C [slots, N].
    Returns (y [slots, H, P] float32, whatever for a dead lane; ssm')."""
    f32 = jnp.float32
    slots, H, P = x.shape
    Hr, N, rP = ssm.shape[2:]
    dt = dt.astype(f32)

    def rows(v):        # [slots, H, P] -> the held layout's [slots, Hr, rP]
        return v.reshape(slots, Hr, rP)

    decay = rows(jnp.broadcast_to(
        jnp.exp(dt * A.astype(f32))[:, :, None], (slots, H, P)))
    dtx = rows(dt[:, :, None] * x.astype(f32))
    if _use_pallas():
        from .pallas import ssm as _ssm

        out = _kernel_or_none(
            "ssm_decode_update", lambda: _ssm.ssm_decode_update(
                ssm, plane, lanes, n_live, decay, dtx, B.astype(f32),
                C.astype(f32)))
        if out is not None:
            return out[0].reshape(slots, H, P), out[1]
    new = ssm[plane] * decay[:, :, None, :] \
        + dtx[:, :, None, :] * B.astype(f32)[:, None, :, None]
    y = (new * C.astype(f32)[:, None, :, None]).sum(2)
    live = jnp.zeros((slots,), bool).at[lanes].set(
        jnp.arange(slots) < n_live)
    return y.reshape(slots, H, P), ssm.at[plane].set(
        jnp.where(live[:, None, None, None], new, ssm[plane]))


# ---------------------------------------------------------------------------
# fused bias + GeLU (fused_gemm_epilogue intent): the bias-add and the
# exact-erf GeLU are plain jnp under a custom_vjp, so XLA places them in
# the fusion of the product before them (forward) and of the product that
# makes their cotangent (backward, with db's row sum): the [rows, F]
# activation crosses HBM once a direction, and the MXU and the vector unit
# work in the same fusion.  A Pallas pass between the two products cannot
# have that: on a v5e at bf16[16384, 3072] it cost a layer 1.99 ms forward
# and backward where this costs 0.57 (PERF.md section 6, PR 42), so there
# is one form, on every backend and under every mesh.
#
# All arithmetic in float32 with one cast at the end (in float64 for a
# float64 input, with `lax.erf`).  erf is XLA's own float32 rational
# polynomial written out (max abs error 4.5e-7 against float64 erf);
# `lax.erf` in its place measured the same within 1%.  The
# backward recomputes from the saved pre-activation: the residuals are
# (x, b) alone, no erf or cdf kept at the activation's width.
# ---------------------------------------------------------------------------
_INV_SQRT2 = 0.7071067811865476
_INV_SQRT_2PI = 0.3989422804014327
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


def _erf(x):
    # the polynomial is float32's; float64 (x64 is on on the CPU) keeps
    # its own precision
    return _erf_f32(x) if x.dtype == jnp.float32 else jax.lax.erf(x)


def _gelu(u):
    return 0.5 * u * (1.0 + _erf(u * _INV_SQRT2))


def _dgelu(u):
    cdf = 0.5 * (1.0 + _erf(u * _INV_SQRT2))
    pdf = jnp.exp(-0.5 * u * u) * _INV_SQRT_2PI
    return cdf + u * pdf


def _preact(x, b):
    ct = jnp.promote_types(x.dtype, jnp.float32)
    return x.astype(ct) + b.astype(ct)


# jitted so that a program traces and lowers each body once a shape, not
# once a layer (a GPT step calls them 12 times each): XLA inlines the
# call before it fuses
@jax.jit
def _bias_gelu_value(x, b):
    return _gelu(_preact(x, b)).astype(x.dtype)


@jax.jit
def _bias_gelu_grads(x, b, dy):
    u = _preact(x, b)
    dx = dy.astype(u.dtype) * _dgelu(u)
    # d/db == d/dx elementwise (y = gelu(x + b)), so db is dx's row sum
    db = dx.sum(tuple(range(x.ndim - 1))).reshape(b.shape)
    return dx.astype(x.dtype), db.astype(b.dtype)


@jax.custom_vjp
def _bias_gelu(x, b):
    """gelu(x + b) over the last dim: x [..., F], b [F]; result of x's
    dtype."""
    return _bias_gelu_value(x, b)


def _bias_gelu_fwd(x, b):
    return _bias_gelu_value(x, b), (x, b)


def _bias_gelu_bwd(res, dy):
    return _bias_gelu_grads(*res, dy)


_bias_gelu.defvjp(_bias_gelu_fwd, _bias_gelu_bwd)


def _dropout(y, dropout_p, training):
    """Wrapper-level dropout keyed by the framework's per-step rng (the
    keep-mask is XLA elementwise and fuses into the surrounding matmul)."""
    if dropout_p <= 0.0 or not training:
        return y
    from ..framework import random as _random

    key_rng = _random.split_key()
    return apply(
        lambda v: jnp.where(
            jax.random.bernoulli(key_rng, 1.0 - dropout_p, v.shape),
            v / (1.0 - dropout_p), 0.0), y)


def bias_gelu(x, bias, dropout_p=0.0, training=True):
    """gelu(x + bias) (exact erf form; float32 arithmetic, float64 for a
    float64 input), optionally followed by dropout threaded through the
    per-step rng."""
    return _dropout(apply(_bias_gelu, x, bias), dropout_p, training)


def linear_bias_gelu(x, weight, bias, dropout_p=0.0, training=True):
    """gelu(x @ weight + bias): the FFN expansion matmul with its epilogue
    fused.  `bias` may be None (plain gelu of the matmul).  The matmul
    goes through the same AMP white_cast as nn.functional.linear."""
    from ..amp import white_cast

    y = apply(lambda v, w: jnp.matmul(*white_cast(v, w)), x, weight)
    if bias is None:
        return _dropout(
            apply(lambda v: jax.nn.gelu(v, approximate=False), y),
            dropout_p, training)
    return bias_gelu(y, bias, dropout_p=dropout_p, training=training)


# ---------------------------------------------------------------------------
# fused feedforward (fused_feedforward intent): LN -> linear -> act -> linear
# ---------------------------------------------------------------------------
def fused_feedforward(x, w1, b1, w2, b2, ln_scale=None, ln_bias=None,
                      activation="gelu", dropout_p=0.0, training=True,
                      pre_layer_norm=True, epsilon=1e-5):
    act = {"gelu": jax.nn.gelu, "relu": jax.nn.relu}[activation]

    h = x
    if pre_layer_norm and ln_scale is not None:
        def pre(v, s, b):
            mean = jnp.mean(v, -1, keepdims=True)
            var = jnp.mean(jnp.square(v - mean), -1, keepdims=True)
            return (v - mean) * jax.lax.rsqrt(var + epsilon) * s + b
        h = apply(pre, x, ln_scale, ln_bias)
    if activation == "gelu":
        h = linear_bias_gelu(h, w1, b1, dropout_p=dropout_p,
                             training=training)
    else:
        h = _dropout(apply(lambda v, w1_, b1_: act(v @ w1_ + b1_),
                           h, w1, b1), dropout_p, training)
    h = apply(lambda v, w2_, b2_: v @ w2_ + b2_, h, w2, b2)
    out = apply(lambda v, r: v + r, x, h)
    if not pre_layer_norm and ln_scale is not None:
        def post(o, s, b):
            mean = jnp.mean(o, -1, keepdims=True)
            var = jnp.mean(jnp.square(o - mean), -1, keepdims=True)
            return (o - mean) * jax.lax.rsqrt(var + epsilon) * s + b
        out = apply(post, out, ln_scale, ln_bias)
    return out


def fused_embedding_layernorm(word_ids, pos_ids, type_ids, word_emb, pos_emb,
                              type_emb, ln_scale, ln_bias, epsilon=1e-5):
    """fused_embedding_eltwise_layernorm analog (BERT embedding fusion)."""
    def f(wi, pi, ti, we, pe, te, s, b):
        h = jnp.take(we, wi.astype(jnp.int32), 0) \
            + jnp.take(pe, pi.astype(jnp.int32), 0) \
            + jnp.take(te, ti.astype(jnp.int32), 0)
        mean = jnp.mean(h, -1, keepdims=True)
        var = jnp.mean(jnp.square(h - mean), -1, keepdims=True)
        return (h - mean) * jax.lax.rsqrt(var + epsilon) * s + b
    return apply(f, word_ids, pos_ids, type_ids, word_emb, pos_emb, type_emb,
                 ln_scale, ln_bias)
