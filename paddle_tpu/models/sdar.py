"""SDAR-MoE: a Qwen3-MoE-shaped decoder that generates by diffusion over
blocks (JetLM SDAR-30B-A3B-Chat, `model_type` `sdar_moe`).

One layer (u = RMSNorm(x), no product has a bias):
  attention  q = W_q u as [nq, hd], k = W_k u, v = W_v u as [nkv, hd]; q
             and k pass an RMSNorm over the head with a learned gain, then
             rotary positions over the whole head (rotate-half); query
             head h reads KV head h // (nq / nkv); h = x + W_o ctx
  experts    nn.DroplessMoE: the k largest of a float32 softmax router
             over E experts, no capacity, gated-SiLU experts
  head       untied; the logits at position i predict the token AT i

Attention is under the block mask M(i, j) = [j // B <= i // B]: a
position sees every earlier block and all of its own, both ways.  The
prompt pass computes it among the prompt's tokens (the flash kernel with
the mask, KV heads expanded to the query heads); every other pass attends
through a KV source of serving/kv_cache.py, which holds the KV heads and
shows each query its block's end.

The model speaks the serving protocol (`slot_prefill`, `slot_step`,
`cfg`) and declares block generation through `cfg` (`block_length`,
`denoising_steps`, `mask_token_id`, `remasking_strategy`,
`confidence_threshold`): GenerationEngine then builds `block_step` in
place of `decode_step`.  This module is imported only by who uses it
(`paddle_tpu.models.sdar`); `import paddle_tpu` does not.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax

from ..nn import initializer as I
from ..nn.layer.common import Embedding, Linear
from ..nn.layer.moe import DroplessMoE
from ..nn.layer_base import Layer, ParamAttr
from ..ops import fused
from ..tensor import Tensor, apply, unwrap

__all__ = ["SDARConfig", "SDARForCausalLM"]


@dataclass
class SDARConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_layers: int = 48
    num_heads: int = 32            # query heads
    num_kv_heads: int = 4
    head_dim: int = 128
    moe_intermediate_size: int = 768
    num_experts: int = 128
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    max_position_embeddings: int = 32768
    initializer_range: float = 0.02
    # generation by diffusion over blocks (the family's defaults; the
    # published config gives neither block length nor schedule)
    block_length: int = 4
    denoising_steps: int = 4
    mask_token_id: int = 151669
    remasking_strategy: str = "low_confidence_static"
    confidence_threshold: float = 0.85


def _init(cfg):
    return ParamAttr(initializer=I.Normal(0.0, cfg.initializer_range))


class RMSNorm(Layer):
    """x * rsqrt(mean(x^2) + eps) * gain over the last axis, in float32."""

    def __init__(self, size, epsilon):
        super().__init__()
        self._epsilon = epsilon
        self.weight = self.create_parameter(
            [size], default_initializer=I.Constant(1.0))

    def forward(self, x):
        eps = self._epsilon

        def f(v, g):
            vf = v.astype(jnp.float32)
            return (vf * lax.rsqrt(jnp.mean(vf * vf, -1, keepdims=True) + eps)
                    * g.astype(jnp.float32)).astype(v.dtype)

        return apply(f, x, self.weight)


def rope(x, positions, theta, inv_freq=None, scale=None):
    """Rotary positions over the whole head, rotate-half: x [B, S, n, hd]
    at `positions` [B|1, S].  The frequencies are theta's, or `inv_freq`
    [hd / 2] where a layer has a law of its own, whose cos and sin are
    then multiplied by `scale`."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)) \
        if inv_freq is None else jnp.asarray(inv_freq, jnp.float32)
    ang = positions.astype(jnp.float32)[..., None] * inv
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, :, None, :]
    if scale is not None:
        cos, sin = cos * scale, sin * scale
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :hd // 2], xf[..., hd // 2:]
    return (xf * cos + jnp.concatenate([-x2, x1], -1) * sin).astype(x.dtype)


class SDARAttention(Layer):
    def __init__(self, cfg: SDARConfig):
        super().__init__()
        self.cfg = cfg
        H, hd = cfg.hidden_size, cfg.head_dim
        nq, nkv = cfg.num_heads, cfg.num_kv_heads
        if nq % nkv:
            raise ValueError(f"{nq} query heads over {nkv} KV heads")

        def lin(i, o):
            return Linear(i, o, weight_attr=_init(cfg), bias_attr=False)

        self.q, self.k, self.v = lin(H, nq * hd), lin(H, nkv * hd), \
            lin(H, nkv * hd)
        self.out = lin(nq * hd, H)
        self.q_norm = RMSNorm(hd, cfg.rms_norm_eps)
        self.k_norm = RMSNorm(hd, cfg.rms_norm_eps)

    def forward(self, x, positions, kv=None, layer=None):
        """``kv=None``: attention among the tokens of ``x`` under the block
        mask (the prompt pass), returning (out, k, v) with k, v [B, S, nkv,
        hd] to seed a cache.  Otherwise ``kv`` is a KV source that takes
        the new k, v in as layer ``layer``: returns (out, kv')."""
        cfg = self.cfg
        B, S = x.shape[0], x.shape[1]
        nq, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        pos = unwrap(positions)
        q = self.q_norm(Tensor(unwrap(self.q(x)).reshape(B, S, nq, hd)))
        k = self.k_norm(Tensor(unwrap(self.k(x)).reshape(B, S, nkv, hd)))
        q = self.rotary(unwrap(q), pos)
        k = self.rotary(unwrap(k), pos)
        v = unwrap(self.v(x)).reshape(B, S, nkv, hd)
        if kv is not None:
            ctx, kv = kv.attend(layer, q, k, v)
        else:
            ctx = self.among(q, k, v)
        out = self.out(Tensor(unwrap(ctx).reshape(B, S, nq * hd)))
        return (out, kv) if kv is not None else (out, k, v)

    def rotary(self, x, positions):
        """The layer's rotary law on q or k [B, S, n, hd]."""
        return rope(x, positions, self.cfg.rope_theta)

    def among(self, q, k, v):
        """The prompt pass: attention among the tokens themselves, here
        under the block mask."""
        cfg = self.cfg
        blk = jnp.arange(q.shape[1]) // cfg.block_length
        mask = (blk[None, :] <= blk[:, None])[None, None]
        g = cfg.num_heads // cfg.num_kv_heads
        # the flash kernel wants one KV head a head
        return unwrap(fused.scaled_dot_product_attention(
            Tensor(q), Tensor(jnp.repeat(k, g, axis=2)),
            Tensor(jnp.repeat(v, g, axis=2)), attn_mask=Tensor(mask),
            training=False))


class SDARBlock(Layer):
    def __init__(self, cfg: SDARConfig, attn=None):
        super().__init__()
        self.ln_1 = RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        self.attn = attn if attn is not None else SDARAttention(cfg)
        self.ln_2 = RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        self.moe = DroplessMoE(cfg.hidden_size, cfg.moe_intermediate_size,
                               cfg.num_experts, cfg.num_experts_per_tok,
                               norm_topk_prob=cfg.norm_topk_prob,
                               weight_attr=_init(cfg))

    def forward(self, x, positions, kv=None, layer=None, count=None):
        """(x', rest, stats): rest is the attention's KV source or (k, v);
        stats the expert layer's (assignments [E], experts touched) over
        the rows ``count`` marks, or None."""
        a, *rest = self.attn(self.ln_1(x), positions, kv, layer)
        x = x + a
        m = self.moe(self.ln_2(x), count)
        m, stats = (m[0], m[1:]) if count is not None else (m, None)
        return x + m, rest, stats


class SDARModel(Layer):
    def __init__(self, cfg: SDARConfig):
        super().__init__()
        self.cfg = cfg
        self.embed = Embedding(cfg.vocab_size, cfg.hidden_size,
                               weight_attr=_init(cfg))
        self.h = [self.block(cfg, i) for i in range(cfg.num_layers)]
        for i, blk in enumerate(self.h):
            self.add_sublayer(f"h_{i}", blk)
        self.norm_f = RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)

    @staticmethod
    def block(cfg, i):
        """Layer i of the decoder (a model of this block with layers of
        more than one kind gives each its own attention)."""
        return SDARBlock(cfg)

    def forward(self, input_ids, positions=None, kv=None, count=None):
        """Hidden states [B, S, H].  With a KV source every layer attends
        over it: (hidden, kv', stats); without, the tokens attend among
        themselves under the block mask: (hidden, [(k, v) per layer],
        stats).  stats: (assignments [layers, E], touched [layers]) over
        the rows ``count`` [B, S] marks, or None.  Eval only."""
        if self.training:
            raise RuntimeError(
                "SDARModel runs in eval mode only (training under the "
                "block mask is not in the tree yet); call model.eval()")
        if positions is None:
            positions = jnp.arange(input_ids.shape[1])[None]
        x = self.embed(input_ids)
        kvs, stats = [], []
        for i, blk in enumerate(self.h):
            x, rest, st = blk(x, positions, kv, i, count)
            if kv is not None:
                kv = rest[0]
            else:
                kvs.append(tuple(rest))
            stats.append(st)
        if count is not None:
            stats = (jnp.stack([unwrap(s[0]) for s in stats]),
                     jnp.stack([unwrap(s[1]) for s in stats]))
        else:
            stats = None
        return self.norm_f(x), (kv if kv is not None else kvs), stats


def _row(hidden, i):
    return Tensor(lax.dynamic_slice_in_dim(
        unwrap(hidden), jnp.asarray(unwrap(i), jnp.int32), 1, axis=1))


class SDARForCausalLM(Layer):
    backbone = SDARModel

    def __init__(self, cfg: SDARConfig):
        super().__init__()
        self.cfg = cfg
        self.sdar = self.backbone(cfg)
        self.lm_head = Linear(cfg.hidden_size, cfg.vocab_size,
                              weight_attr=_init(cfg), bias_attr=False)

    def forward(self, input_ids):
        """Logits [B, S, V] under the block mask (position i's logits
        predict the token at i)."""
        hidden, _, _ = self.sdar(input_ids)
        return self.lm_head(hidden)

    # -- the serving protocol (paddle_tpu.serving.generation) --------------
    def slot_prefill(self, input_ids, length):
        """Prompt pass for ONE request under the block mask: input_ids
        [1, Sp] right-padded to the bucket, ``length`` the prompt's length
        (traced).  A position sees its own block and the earlier ones, so
        the K/V of the prompt's whole blocks do not depend on the tail
        behind them; the engine reads no further.  Returns (k [layers, Sp,
        nkv, hd], v, logits [V] at position length - 1; a block engine
        drops the logits and the compiler the head with them)."""
        hidden, kvs, _ = self.sdar(input_ids)
        logits = self.lm_head(_row(hidden, unwrap(length) - 1))
        return (jnp.stack([k[0] for k, _ in kvs]),
                jnp.stack([v[0] for _, v in kvs]),
                unwrap(logits)[0, 0])

    def slot_step(self, tokens, positions, kv, last=None, live=None):
        """One model step over a KV source (serving/kv_cache.py): tokens
        [B, C] at absolute ``positions`` [B, C].  Returns (logits, kv'):
        logits [B, C, V], or [B, 1, V] of row ``last`` alone.  With
        ``live`` [B] bool (the engine's active lanes) also the step's
        routing statistics over the live lanes' rows: (assignments
        [layers, E], experts touched [layers]); a dead lane's rows then
        fetch no expert of their own (nn.DroplessMoE ``count``)."""
        tokens = jnp.asarray(unwrap(tokens), jnp.int32)
        positions = jnp.clip(jnp.asarray(unwrap(positions), jnp.int32), 0,
                             self.cfg.max_position_embeddings - 1)
        count = None if live is None else jnp.broadcast_to(
            unwrap(live)[:, None], tokens.shape)
        hidden, kv, stats = self.sdar(Tensor(tokens), positions, kv, count)
        if last is not None:
            hidden = _row(hidden, last)
        logits = unwrap(self.lm_head(hidden))
        return (logits, kv) if live is None else (logits, kv, stats)
