"""Ring attention + Ulysses attention: sequence/context parallelism.

The reference has NO long-context support (SURVEY.md §5: no ring attention,
no sequence parallel anywhere in tree; sequence length is bounded by one
device's memory).  This module is the TPU-native capability that fills that
gap, required for the GPT-3-class workloads of SURVEY.md section 6:

* ring_attention — blockwise attention with the KV shards rotating around
  the `sp` mesh axis via `lax.ppermute` over ICI (Ring Attention, Liu et al.
  2023).  Softmax is computed online (running max/normalizer, flash-style),
  so no device ever materializes the full [S, S] score matrix and sequence
  length scales linearly with the number of devices.
* ulysses_attention — DeepSpeed-Ulysses style: `all_to_all` swaps the
  sequence shard for a head shard, runs full local attention on H/n heads,
  and swaps back.  Cheaper comms for moderate S, needs H % n == 0.

Both run inside shard_map; gradients come from jax.grad transposing the
scan/ppermute (the backward ring rotates the opposite way automatically).
The per-block compute is jnp einsums — XLA fuses them onto the MXU; the
Pallas flash kernel (ops/pallas/flash_attention.py) covers the single-shard
fast path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

__all__ = ["ring_attention", "ulysses_attention", "ring_attention_inner"]

_NEG_INF = -1e30


def ring_attention_inner(q, k, v, *, axis_name="sp", causal=False,
                         sm_scale=None):
    """Blockwise ring attention. MUST run inside shard_map over `axis_name`.

    q, k, v: [B, S_local, H, D] sequence shards (S_global = S_local * n).
    Returns [B, S_local, H, D] in q.dtype (accumulation in float32).
    """
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    if sm_scale is None:
        sm_scale = 1.0 / (D ** 0.5)
    qf = q.astype(jnp.float32) * sm_scale
    perm = [(i, (i + 1) % n) for i in range(n)]

    o0 = jnp.zeros((B, Sq, H, D), jnp.float32)
    m0 = jnp.full((B, H, Sq), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, Sq), jnp.float32)

    q_pos = idx * Sq + jax.lax.broadcasted_iota(jnp.int32, (Sq, Sk), 0)

    def body(carry, step):
        o, m, l, kc, vc = carry
        src = (idx - step) % n  # shard the current kv block originated from
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, kc.astype(jnp.float32))
        if causal:
            k_pos = src * Sk + jax.lax.broadcasted_iota(jnp.int32, (Sq, Sk), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(-1)
        pv = jnp.einsum("bhqk,bkhd->bqhd", p, vc.astype(jnp.float32))
        o = o * alpha.transpose(0, 2, 1)[..., None] + pv
        kc = jax.lax.ppermute(kc, axis_name, perm)
        vc = jax.lax.ppermute(vc, axis_name, perm)
        return (o, m_new, l, kc, vc), None

    (o, m, l, _, _), _ = jax.lax.scan(
        jax.checkpoint(body), (o0, m0, l0, k, v), jnp.arange(n))
    out = o / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def ring_attention(q, k, v, mesh=None, *, axis_name="sp", causal=False,
                   sm_scale=None):
    """shard_map wrapper: q/k/v [B, S, H, D] sharded P(None, sp, None, None)."""
    from ..tensor import Tensor, apply
    from ..distributed.mesh import ensure_mesh

    mesh = mesh if mesh is not None else ensure_mesh()
    spec = P(None, axis_name, None, None)
    inner = functools.partial(ring_attention_inner, axis_name=axis_name,
                              causal=causal, sm_scale=sm_scale)
    f = shard_map(inner, mesh=mesh, in_specs=(spec, spec, spec),
                  out_specs=spec, check_vma=False)
    if not any(isinstance(t, Tensor) for t in (q, k, v)):
        return f(q, k, v)
    return apply(f, q, k, v)


def _ulysses_inner(q, k, v, *, axis_name, causal, sm_scale):
    n = jax.lax.axis_size(axis_name)

    def seq_to_heads(x):  # [B, S/n, H, D] -> [B, S, H/n, D]
        B, Sl, H, D = x.shape
        x = x.reshape(B, Sl, n, H // n, D).transpose(2, 0, 1, 3, 4)
        x = jax.lax.all_to_all(x, axis_name, split_axis=0, concat_axis=0,
                               tiled=False)
        return x.transpose(1, 0, 2, 3, 4).reshape(B, n * Sl, H // n, D)

    def heads_to_seq(x):  # [B, S, H/n, D] -> [B, S/n, H, D]
        B, S, Hl, D = x.shape
        x = x.reshape(B, n, S // n, Hl, D).transpose(1, 0, 2, 3, 4)
        x = jax.lax.all_to_all(x, axis_name, split_axis=0, concat_axis=0,
                               tiled=False)
        return x.transpose(1, 2, 0, 3, 4).reshape(B, S // n, n * Hl, D)

    qh, kh, vh = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    S = qh.shape[1]
    if sm_scale is None:
        sm_scale = 1.0 / (qh.shape[-1] ** 0.5)
    s = jnp.einsum("bqhd,bkhd->bhqk", qh.astype(jnp.float32) * sm_scale,
                   kh.astype(jnp.float32))
    if causal:
        pos_q = jax.lax.broadcasted_iota(jnp.int32, (S, S), 0)
        pos_k = jax.lax.broadcasted_iota(jnp.int32, (S, S), 1)
        s = jnp.where(pos_q >= pos_k, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, vh.astype(jnp.float32))
    return heads_to_seq(out.astype(q.dtype))


def ulysses_attention(q, k, v, mesh=None, *, axis_name="sp", causal=False,
                      sm_scale=None):
    """All-to-all sequence parallelism (heads % axis size must be 0)."""
    from ..tensor import Tensor, apply, unwrap
    from ..distributed.mesh import ensure_mesh

    mesh = mesh if mesh is not None else ensure_mesh()
    n = mesh.shape[axis_name]
    H = unwrap(q).shape[2]
    if H % n:
        raise ValueError(f"ulysses needs heads ({H}) divisible by "
                         f"{axis_name} size ({n}); use ring_attention")
    spec = P(None, axis_name, None, None)
    inner = functools.partial(_ulysses_inner, axis_name=axis_name,
                              causal=causal, sm_scale=sm_scale)
    f = shard_map(inner, mesh=mesh, in_specs=(spec, spec, spec),
                  out_specs=spec, check_vma=False)
    if not any(isinstance(t, Tensor) for t in (q, k, v)):
        return f(q, k, v)
    return apply(f, q, k, v)
