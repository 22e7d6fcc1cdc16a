"""GPT decoder-only language model, tensor-parallel-ready.

Workload parity: BASELINE.md config 5 (GPT-3 1.3B with TP+PP).  The reference
tree has no GPT implementation (it lives in PaddleNLP); this is the TPU-native
flagship: GSPMD tensor parallelism via the meta_parallel layers (weights carry
PartitionSpecs; XLA inserts the Megatron collectives), optional
sequence-parallel ring attention for long context, fused attention via the
Pallas flash kernel on TPU (ops/fused.scaled_dot_product_attention).
"""
from __future__ import annotations

from dataclasses import dataclass

from .. import tensor_ops as T
from ..distributed.meta_parallel import (ColumnParallelLinear,
                                         RowParallelLinear,
                                         VocabParallelEmbedding,
                                         shard_constraint)
from ..nn import functional as F
from ..nn import initializer as I
from ..nn.layer_base import Layer, ParamAttr
from ..nn.layer.common import Dropout, Embedding, Linear
from ..nn.layer.norm import LayerNorm
from ..ops import fused
from ..tensor import Tensor


@dataclass
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    ffn_hidden_size: int | None = None  # default 4*hidden
    max_position_embeddings: int = 1024
    dropout: float = 0.1
    attn_dropout: float = 0.1
    layer_norm_epsilon: float = 1e-5
    initializer_range: float = 0.02
    tensor_parallel: bool = False   # annotate weights for an `mp` mesh axis
    sequence_parallel: bool = False  # ring attention over an `sp` mesh axis
    tie_word_embeddings: bool = True
    recompute: bool = False  # remat each block (fluid RecomputeOptimizer,
                             # optimizer.py:4533) — activations between
                             # blocks are the only saved residuals

    @property
    def ffn_size(self):
        return self.ffn_hidden_size or 4 * self.hidden_size


def _init(cfg):
    return ParamAttr(initializer=I.Normal(0.0, cfg.initializer_range))


class GPTAttention(Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        H = cfg.hidden_size
        if cfg.tensor_parallel:
            self.qkv = ColumnParallelLinear(H, 3 * H, weight_attr=_init(cfg),
                                            gather_output=False)
            self.out = RowParallelLinear(H, H, weight_attr=_init(cfg),
                                         input_is_parallel=True)
        else:
            self.qkv = Linear(H, 3 * H, weight_attr=_init(cfg))
            self.out = Linear(H, H, weight_attr=_init(cfg))
        self.dropout = Dropout(cfg.dropout)

    def forward(self, x, return_kv=False):
        cfg = self.cfg
        B, S = x.shape[0], x.shape[1]
        nh, hd = cfg.num_heads, cfg.hidden_size // cfg.num_heads
        qkv = self.qkv(x)
        qkv = T.reshape(qkv, [B, S, 3, nh, hd])
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        if cfg.tensor_parallel:
            # heads follow the qkv column shards
            q = shard_constraint(q, None, None, "mp", None)
            k = shard_constraint(k, None, None, "mp", None)
            v = shard_constraint(v, None, None, "mp", None)
        if cfg.sequence_parallel:
            from ..ops.ring_attention import ring_attention

            ctx = ring_attention(q, k, v, causal=True)
        else:
            ctx = fused.scaled_dot_product_attention(
                q, k, v, dropout_p=cfg.attn_dropout, is_causal=True,
                training=self.training)
        ctx = T.reshape(ctx, [B, S, cfg.hidden_size])
        out = self.dropout(self.out(ctx))
        if return_kv:
            return out, k, v  # [B, S, nh, hd] — prefill seeds the KV cache
        return out

    def decode_slots(self, x, k_cache, v_cache, pos, active):
        """Continuous-batching decode: one token per cache SLOT, each at
        its OWN position (the batched generalization of decode_step for
        paddle_tpu.serving.generation — lanes belong to different
        requests admitted at different times, so there is no shared
        scalar position).

        x: [slots, 1, H] hidden; caches: [slots, S_max, nh, hd];
        pos: [slots] int32 per-lane write index; active: [slots] bool —
        inactive lanes leave their cache rows untouched.  Returns
        (out, k', v').  Per-lane math is identical to decode_step at the
        same position, which is what makes an engine lane bitwise-equal
        to a solo ``generate`` run.
        """
        import jax.numpy as jnp
        from jax import lax

        from ..tensor import unwrap

        cfg = self.cfg
        B = x.shape[0]
        nh, hd = cfg.num_heads, cfg.hidden_size // cfg.num_heads
        qkv = T.reshape(self.qkv(x), [B, 1, 3, nh, hd])
        q = unwrap(qkv[:, :, 0])                     # [slots, 1, nh, hd]
        k = unwrap(qkv[:, :, 1])
        v = unwrap(qkv[:, :, 2])
        pos = jnp.asarray(unwrap(pos), jnp.int32)
        active = jnp.asarray(unwrap(active), bool)
        k_cache, v_cache = unwrap(k_cache), unwrap(v_cache)
        # per-lane scatter: lane b writes column pos[b] (dynamic_update
        # _slice cannot express per-row offsets; the one-hot where is the
        # jit-safe equivalent and XLA fuses it into the cache update)
        write = (jnp.arange(k_cache.shape[1])[None, :] == pos[:, None]) \
            & active[:, None]                         # [slots, S_max]
        k_cache = jnp.where(write[:, :, None, None], k, k_cache)
        v_cache = jnp.where(write[:, :, None, None], v, v_cache)
        if cfg.tensor_parallel:
            # head-axis pinning, as in forward()/decode_step: without it
            # GSPMD may gather the cache every decode iteration
            q = unwrap(shard_constraint(Tensor(q), None, None, "mp", None))
            k_cache = unwrap(shard_constraint(
                Tensor(k_cache), None, None, "mp", None))
            v_cache = unwrap(shard_constraint(
                Tensor(v_cache), None, None, "mp", None))
        scores = jnp.einsum("bqnd,bsnd->bnqs", q, k_cache) \
            * (1.0 / float(hd) ** 0.5)
        valid = jnp.arange(k_cache.shape[1])[None, :] <= pos[:, None]
        scores = jnp.where(valid[:, None, None, :], scores,
                           jnp.finfo(scores.dtype).min)
        probs = jnp.exp(scores - lax.stop_gradient(
            scores.max(axis=-1, keepdims=True)))
        probs = probs / probs.sum(axis=-1, keepdims=True)
        ctx = jnp.einsum("bnqs,bsnd->bqnd", probs, v_cache)
        out = self.out(Tensor(ctx.reshape(B, 1, cfg.hidden_size)))
        return out, Tensor(k_cache), Tensor(v_cache)

    def decode_pages(self, x, k_pages, v_pages, rows, pos, active,
                     seq_cap, layer):
        """Paged continuous-batching decode: like ``decode_slots`` but
        each lane's KV lives in fixed-size pool pages indirected through
        its page-table row (serving/kv_cache.py) instead of a dense
        ``[slots, S_max]`` stripe.

        x: [slots, 1, H]; k_pages/v_pages: [layers, num_pages,
        page_size, nh, hd] (the WHOLE pools; this layer writes and reads
        plane ``layer``, a static int, and returns the whole pools, so a
        donated pool is rewritten in place and no plane is ever sliced
        out or stacked back); rows: [slots, pages_per_slot]
        int32 page table (-1 = unmapped); pos: [slots] write index;
        active: [slots]; seq_cap: STATIC attention extent (the engine's
        S_max) — the gathered view is sliced to it so the softmax
        reduction shape matches the dense path exactly, which is what
        keeps an engine lane bitwise-equal to a solo ``generate`` run.
        Unmapped (-1) table entries gather an arbitrary resident page
        whose positions sit past the validity mask, so they contribute
        exactly 0 to the softmax (exp of finfo.min underflows).
        """
        import jax.numpy as jnp
        from jax import lax

        from ..tensor import unwrap

        cfg = self.cfg
        B = x.shape[0]
        nh, hd = cfg.num_heads, cfg.hidden_size // cfg.num_heads
        qkv = T.reshape(self.qkv(x), [B, 1, 3, nh, hd])
        q = unwrap(qkv[:, :, 0])                     # [slots, 1, nh, hd]
        k = unwrap(qkv[:, :, 1])[:, 0]               # [slots, nh, hd]
        v = unwrap(qkv[:, :, 2])[:, 0]
        pos = jnp.asarray(unwrap(pos), jnp.int32)
        active = jnp.asarray(unwrap(active), bool)
        k_pages, v_pages = unwrap(k_pages), unwrap(v_pages)
        rows = jnp.asarray(unwrap(rows), jnp.int32)
        num_pages, ps = k_pages.shape[1], k_pages.shape[2]
        lane = jnp.arange(B)
        # per-lane scatter: lane b writes its token's K/V at
        # (layer, rows[b, pos[b]//ps], pos[b]%ps); inactive lanes target
        # one-past-the-pool and are dropped
        page = rows[lane, jnp.clip(pos // ps, 0, rows.shape[1] - 1)]
        page = jnp.where(active, page, num_pages)
        off = pos % ps
        k_pages = k_pages.at[layer, page, off].set(
            k.astype(k_pages.dtype), mode="drop")
        v_pages = v_pages.at[layer, page, off].set(
            v.astype(v_pages.dtype), mode="drop")
        # hot path: the Pallas ragged kernel walks each lane's page-table
        # row and reads plane `layer` of the pool in place — no dense
        # [slots, seq_cap] gather and no copy of the plane is
        # materialized.  None => flag off / untileable geometry
        # (counted in paddle_pallas_fallbacks_total); the dense gather
        # below stays as the reference and fallback.
        ctx = fused.paged_decode_attention(
            q, k_pages, v_pages, rows, pos, seq_cap, layer,
            tp_axis="mp" if cfg.tensor_parallel else None)
        if ctx is None:
            # gather each lane's pages into a contiguous [seq_cap] view
            gidx = jnp.clip(rows, 0, num_pages - 1)
            kg = k_pages[layer, gidx].reshape(B, rows.shape[1] * ps, nh, hd)
            vg = v_pages[layer, gidx].reshape(B, rows.shape[1] * ps, nh, hd)
            kg, vg = kg[:, :seq_cap], vg[:, :seq_cap]
            scores = jnp.einsum("bqnd,bsnd->bnqs", q, kg) \
                * (1.0 / float(hd) ** 0.5)
            valid = jnp.arange(seq_cap)[None, :] <= pos[:, None]
            scores = jnp.where(valid[:, None, None, :], scores,
                               jnp.finfo(scores.dtype).min)
            probs = jnp.exp(scores - lax.stop_gradient(
                scores.max(axis=-1, keepdims=True)))
            probs = probs / probs.sum(axis=-1, keepdims=True)
            ctx = jnp.einsum("bnqs,bsnd->bqnd", probs, vg)
        else:
            ctx = unwrap(ctx)
        out = self.out(Tensor(ctx.reshape(B, 1, cfg.hidden_size)))
        return out, Tensor(k_pages), Tensor(v_pages)

    def verify_pages(self, x, k_pages, v_pages, rows, positions, active,
                     seq_cap, layer):
        """Speculative-decode verification attention: like
        ``decode_pages`` but each lane carries a CHUNK of C candidate
        tokens at consecutive positions instead of one — the target
        model scores every draft proposal in a single batched step.

        x: [slots, C, H]; k_pages/v_pages: [layers, num_pages,
        page_size, nh, hd] (the whole pools, plane ``layer`` written and
        read, as in ``decode_pages``); rows: [slots, pages_per_slot]
        int32 page table; positions: [slots, C] absolute write index
        per candidate (consecutive per lane, clamped by the engine so
        they never run past the slot's reserved extent); active:
        [slots]; seq_cap: STATIC attention extent.  Causality inside
        the chunk falls out of the position mask: candidate i's query
        admits exactly the keys at slots <= positions[b, i], which by
        construction are the committed history plus candidates 0..i —
        the same reduction extent the non-speculative decode step would
        have seen one token at a time, which is what keeps accepted
        tokens bitwise-equal to the sequential path.
        """
        import jax.numpy as jnp
        from jax import lax

        from ..tensor import unwrap

        cfg = self.cfg
        B, C = x.shape[0], x.shape[1]
        nh, hd = cfg.num_heads, cfg.hidden_size // cfg.num_heads
        qkv = T.reshape(self.qkv(x), [B, C, 3, nh, hd])
        q = unwrap(qkv[:, :, 0])                     # [slots, C, nh, hd]
        k = unwrap(qkv[:, :, 1])
        v = unwrap(qkv[:, :, 2])
        positions = jnp.asarray(unwrap(positions), jnp.int32)
        active = jnp.asarray(unwrap(active), bool)
        k_pages, v_pages = unwrap(k_pages), unwrap(v_pages)
        rows = jnp.asarray(unwrap(rows), jnp.int32)
        num_pages, ps = k_pages.shape[1], k_pages.shape[2]
        lane = jnp.arange(B)
        # per-element scatter: candidate (b, i) writes its K/V at (layer,
        # rows[b, positions[b,i]//ps], positions[b,i]%ps); inactive
        # lanes target one-past-the-pool and are dropped.  Clamped
        # duplicate positions (end-of-budget) may collide — whichever
        # write wins is garbage no emitted query's mask ever exposes.
        page = rows[lane[:, None],
                    jnp.clip(positions // ps, 0, rows.shape[1] - 1)]
        page = jnp.where(active[:, None], page, num_pages)
        off = positions % ps
        k_pages = k_pages.at[layer, page, off].set(
            k.astype(k_pages.dtype), mode="drop")
        v_pages = v_pages.at[layer, page, off].set(
            v.astype(v_pages.dtype), mode="drop")
        # dense per-lane gather (the decode_pages fallback math with a
        # C-wide query dim); no Pallas path — verification is one step
        # per K drafted tokens, off the per-token hot loop
        gidx = jnp.clip(rows, 0, num_pages - 1)
        kg = k_pages[layer, gidx].reshape(B, rows.shape[1] * ps, nh, hd)
        vg = v_pages[layer, gidx].reshape(B, rows.shape[1] * ps, nh, hd)
        kg, vg = kg[:, :seq_cap], vg[:, :seq_cap]
        scores = jnp.einsum("bqnd,bsnd->bnqs", q, kg) \
            * (1.0 / float(hd) ** 0.5)
        valid = jnp.arange(seq_cap)[None, None, :] <= positions[:, :, None]
        scores = jnp.where(valid[:, None], scores,
                           jnp.finfo(scores.dtype).min)
        probs = jnp.exp(scores - lax.stop_gradient(
            scores.max(axis=-1, keepdims=True)))
        probs = probs / probs.sum(axis=-1, keepdims=True)
        ctx = jnp.einsum("bnqs,bsnd->bqnd", probs, vg)
        out = self.out(Tensor(ctx.reshape(B, C, cfg.hidden_size)))
        return out, Tensor(k_pages), Tensor(v_pages)

    def prefill_prefix(self, x, prefix_k, prefix_v, prefix_len):
        """Suffix-only prefill attending over a cached prefix: queries
        are the suffix tokens (absolute positions ``prefix_len + i``),
        keys are [prefix ++ suffix] with the prefix entries valid below
        ``prefix_len`` and the suffix causal — the attention that lets a
        prefix-cache hit skip recomputing the shared pages entirely.

        x: [1, Ss, H] suffix hidden; prefix_k/prefix_v: [C, nh, hd]
        gathered prefix K/V (C static, entries >= prefix_len garbage);
        prefix_len: traced scalar.  Returns (out, k_suf, v_suf) with
        k_suf/v_suf [1, Ss, nh, hd] — the engine pages them in at the
        (page-aligned) prefix boundary.
        """
        import jax.numpy as jnp
        from jax import lax

        from ..tensor import unwrap

        cfg = self.cfg
        S = x.shape[1]
        nh, hd = cfg.num_heads, cfg.hidden_size // cfg.num_heads
        qkv = T.reshape(self.qkv(x), [1, S, 3, nh, hd])
        q = unwrap(qkv[:, :, 0])                     # [1, Ss, nh, hd]
        k = unwrap(qkv[:, :, 1])
        v = unwrap(qkv[:, :, 2])
        prefix_len = jnp.asarray(unwrap(prefix_len), jnp.int32)
        pk = jnp.asarray(unwrap(prefix_k))[None]     # [1, C, nh, hd]
        pv = jnp.asarray(unwrap(prefix_v))[None]
        C = pk.shape[1]
        kk = jnp.concatenate([pk.astype(k.dtype), k], axis=1)
        vv = jnp.concatenate([pv.astype(v.dtype), v], axis=1)
        scores = jnp.einsum("bqnd,bsnd->bnqs", q, kk) \
            * (1.0 / float(hd) ** 0.5)
        i = jnp.arange(S)[:, None]
        j = jnp.arange(C + S)[None, :]
        ok = (j < prefix_len) | ((j >= C) & (j - C <= i))
        scores = jnp.where(ok[None, None], scores,
                           jnp.finfo(scores.dtype).min)
        probs = jnp.exp(scores - lax.stop_gradient(
            scores.max(axis=-1, keepdims=True)))
        probs = probs / probs.sum(axis=-1, keepdims=True)
        ctx = jnp.einsum("bnqs,bsnd->bqnd", probs, vv)
        out = self.dropout(self.out(Tensor(
            ctx.reshape(1, S, cfg.hidden_size))))
        return out, Tensor(k), Tensor(v)

    def decode_step(self, x, k_cache, v_cache, pos):
        """One-token cached attention (the KV-cache serving path; the
        reference's analog is fused_multi_transformer's CacheKV decode,
        operators/fused/ — here it is lax-level dynamic_update_slice +
        masked attention over the static cache, jit/scan-safe).

        x: [B, 1, H] hidden; caches: [B, S_max, nh, hd]; pos: scalar int32
        index of the slot this token occupies.  Returns (out, k', v').
        """
        import jax.numpy as jnp
        from jax import lax

        from ..tensor import unwrap

        cfg = self.cfg
        B = x.shape[0]
        nh, hd = cfg.num_heads, cfg.hidden_size // cfg.num_heads
        qkv = T.reshape(self.qkv(x), [B, 1, 3, nh, hd])
        q = unwrap(qkv[:, :, 0])                     # [B, 1, nh, hd]
        k = unwrap(qkv[:, :, 1])
        v = unwrap(qkv[:, :, 2])
        pos = jnp.asarray(unwrap(pos), jnp.int32)
        zero = jnp.int32(0)
        k_cache = lax.dynamic_update_slice(
            unwrap(k_cache), k, (zero, pos, zero, zero))
        v_cache = lax.dynamic_update_slice(
            unwrap(v_cache), v, (zero, pos, zero, zero))
        if cfg.tensor_parallel:
            # same head-axis pinning as forward(): without it GSPMD may
            # pick a gathered layout for the per-step attention and pay
            # an all-gather every decode step
            q = unwrap(shard_constraint(Tensor(q), None, None, "mp", None))
            k_cache = unwrap(shard_constraint(
                Tensor(k_cache), None, None, "mp", None))
            v_cache = unwrap(shard_constraint(
                Tensor(v_cache), None, None, "mp", None))
        # masked attention over the whole static cache: slots past `pos`
        # are -inf so the softmax ignores unwritten entries
        scores = jnp.einsum("bqnd,bsnd->bnqs", q, k_cache) \
            * (1.0 / float(hd) ** 0.5)
        valid = jnp.arange(k_cache.shape[1]) <= pos   # [S_max]
        scores = jnp.where(valid[None, None, None, :], scores,
                           jnp.finfo(scores.dtype).min)
        probs = jnp.exp(scores - lax.stop_gradient(
            scores.max(axis=-1, keepdims=True)))
        probs = probs / probs.sum(axis=-1, keepdims=True)
        ctx = jnp.einsum("bnqs,bsnd->bqnd", probs, v_cache)
        out = self.out(Tensor(ctx.reshape(B, 1, cfg.hidden_size)))
        return out, Tensor(k_cache), Tensor(v_cache)


class GPTMLP(Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        H, FF = cfg.hidden_size, cfg.ffn_size
        if cfg.tensor_parallel:
            self.fc1 = ColumnParallelLinear(H, FF, weight_attr=_init(cfg),
                                            gather_output=False)
            self.fc2 = RowParallelLinear(FF, H, weight_attr=_init(cfg),
                                         input_is_parallel=True)
        else:
            self.fc1 = Linear(H, FF, weight_attr=_init(cfg))
            self.fc2 = Linear(FF, H, weight_attr=_init(cfg))
        self._tp = cfg.tensor_parallel
        self.dropout = Dropout(cfg.dropout)

    def forward(self, x):
        # expansion matmul with fused bias+GeLU epilogue (exact erf, same
        # as F.gelu's default) instead of fc1 -> separate gelu
        h = fused.linear_bias_gelu(x, self.fc1.weight, self.fc1.bias)
        if self._tp:
            # re-pin the column shards fc1.forward would have pinned
            h = shard_constraint(h, *([None] * (len(h.shape) - 1) + ["mp"]))
        return self.dropout(self.fc2(h))


class GPTBlock(Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.ln_1 = LayerNorm(cfg.hidden_size, epsilon=cfg.layer_norm_epsilon)
        self.attn = GPTAttention(cfg)
        self.ln_2 = LayerNorm(cfg.hidden_size, epsilon=cfg.layer_norm_epsilon)
        self.mlp = GPTMLP(cfg)

    def forward(self, x, return_kv=False):
        if return_kv:
            a, k, v = self.attn(self.ln_1(x), return_kv=True)
            x = x + a
            x = x + self.mlp(self.ln_2(x))
            return x, k, v
        x = x + self.attn(self.ln_1(x))
        x = x + self.mlp(self.ln_2(x))
        return x

    def decode_step(self, x, k_cache, v_cache, pos):
        a, k_cache, v_cache = self.attn.decode_step(
            self.ln_1(x), k_cache, v_cache, pos)
        x = x + a
        x = x + self.mlp(self.ln_2(x))
        return x, k_cache, v_cache

    def decode_slots(self, x, k_cache, v_cache, pos, active):
        a, k_cache, v_cache = self.attn.decode_slots(
            self.ln_1(x), k_cache, v_cache, pos, active)
        x = x + a
        x = x + self.mlp(self.ln_2(x))
        return x, k_cache, v_cache

    def decode_pages(self, x, k_pages, v_pages, rows, pos, active,
                     seq_cap, layer):
        a, k_pages, v_pages = self.attn.decode_pages(
            self.ln_1(x), k_pages, v_pages, rows, pos, active, seq_cap,
            layer)
        x = x + a
        x = x + self.mlp(self.ln_2(x))
        return x, k_pages, v_pages

    def verify_pages(self, x, k_pages, v_pages, rows, positions, active,
                     seq_cap, layer):
        a, k_pages, v_pages = self.attn.verify_pages(
            self.ln_1(x), k_pages, v_pages, rows, positions, active,
            seq_cap, layer)
        x = x + a
        x = x + self.mlp(self.ln_2(x))
        return x, k_pages, v_pages

    def prefill_prefix(self, x, prefix_k, prefix_v, prefix_len):
        a, k, v = self.attn.prefill_prefix(
            self.ln_1(x), prefix_k, prefix_v, prefix_len)
        x = x + a
        x = x + self.mlp(self.ln_2(x))
        return x, k, v


class GPTModel(Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        if cfg.tensor_parallel:
            self.wte = VocabParallelEmbedding(cfg.vocab_size, cfg.hidden_size,
                                              weight_attr=_init(cfg))
        else:
            self.wte = Embedding(cfg.vocab_size, cfg.hidden_size,
                                 weight_attr=_init(cfg))
        self.wpe = Embedding(cfg.max_position_embeddings, cfg.hidden_size,
                             weight_attr=_init(cfg))
        self.drop = Dropout(cfg.dropout)
        self.h = [GPTBlock(cfg) for _ in range(cfg.num_layers)]
        for i, blk in enumerate(self.h):
            self.add_sublayer(f"h_{i}", blk)
        self.ln_f = LayerNorm(cfg.hidden_size, epsilon=cfg.layer_norm_epsilon)

    def forward(self, input_ids):
        import paddle_tpu as paddle

        pos = paddle.arange(input_ids.shape[1])
        x = self.wte(input_ids) + self.wpe(pos)
        x = self.drop(x)
        if self.cfg.recompute:
            from ..distributed.recompute import recompute as _remat
            for blk in self.h:
                x = _remat(blk, x)
        else:
            for blk in self.h:
                x = blk(x)
        return self.ln_f(x)

    def prefill(self, input_ids, cache_len):
        """Batched prompt pass seeding per-layer KV caches of static
        length ``cache_len`` (>= prompt + new tokens).  Returns
        (hidden [B,S,H], caches: tuple of (k,v) [B,cache_len,nh,hd])."""
        import jax.numpy as jnp

        import paddle_tpu as paddle

        from ..tensor import unwrap

        cfg = self.cfg
        if self.training:
            raise RuntimeError(
                "prefill/decode_step are eval-only serving paths (the "
                "decode half applies no dropout, so a training-mode "
                "prefill would be statistically inconsistent with it); "
                "call model.eval() first")
        B, S = input_ids.shape[0], input_ids.shape[1]
        nh, hd = cfg.num_heads, cfg.hidden_size // cfg.num_heads
        pos = paddle.arange(S)
        x = self.drop(self.wte(input_ids) + self.wpe(pos))
        caches = []
        for blk in self.h:
            x, k, v = blk(x, return_kv=True)
            kc = jnp.zeros((B, cache_len, nh, hd),
                           unwrap(k).dtype).at[:, :S].set(unwrap(k))
            vc = jnp.zeros((B, cache_len, nh, hd),
                           unwrap(v).dtype).at[:, :S].set(unwrap(v))
            caches.append((kc, vc))
        return self.ln_f(x), tuple(caches)

    def decode_step(self, token_ids, pos, caches):
        """One decode step: token_ids [B,1] at absolute position ``pos``
        (scalar); caches as returned by prefill.  Returns (hidden [B,1,H],
        new caches)."""
        from ..tensor import unwrap

        x = self.wte(token_ids) + self.wpe(T.reshape(Tensor(pos), [1]))
        new_caches = []
        for blk, (kc, vc) in zip(self.h, caches):
            x, kc, vc = blk.decode_step(x, kc, vc, pos)
            new_caches.append((unwrap(kc), unwrap(vc)))
        return self.ln_f(x), tuple(new_caches)

    def decode_slots(self, token_ids, pos, caches, active):
        """Continuous-batching decode step: token_ids [slots,1], each
        lane at its own absolute position ``pos[slot]``; ``active``
        masks lanes whose slot currently holds no request.  Returns
        (hidden [slots,1,H], new caches)."""
        from ..tensor import unwrap

        x = self.wte(token_ids) \
            + self.wpe(T.reshape(Tensor(unwrap(pos)), [-1, 1]))
        new_caches = []
        for blk, (kc, vc) in zip(self.h, caches):
            x, kc, vc = blk.decode_slots(x, kc, vc, pos, active)
            new_caches.append((unwrap(kc), unwrap(vc)))
        return self.ln_f(x), tuple(new_caches)


class GPTForCausalLM(Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        self.gpt = GPTModel(cfg)
        if not cfg.tie_word_embeddings:
            self.lm_head = Linear(cfg.hidden_size, cfg.vocab_size,
                                  weight_attr=_init(cfg), bias_attr=False)

    def forward(self, input_ids, labels=None):
        hidden = self.gpt(input_ids)
        logits = self._head(hidden)
        if labels is None:
            return logits
        loss = fused.softmax_cross_entropy(
            logits[:, :-1], labels[:, 1:])
        return logits, T.mean(loss)

    def loss(self, input_ids):
        """Next-token LM loss on a batch of token ids, via the chunked
        fused LM-head matmul + cross entropy (ops/fused.py
        fused_linear_cross_entropy) — the fp32 [B*S, V] logits never
        materialize in HBM at once."""
        hidden = self.gpt(input_ids)
        if self.cfg.tie_word_embeddings:
            w = T.transpose(self.gpt.wte.weight, [1, 0])
        else:
            w = self.lm_head.weight
        loss = fused.fused_linear_cross_entropy(
            hidden[:, :-1], w, input_ids[:, 1:])
        return T.mean(loss)

    def _head(self, hidden):
        if self.cfg.tie_word_embeddings:
            return T.matmul(hidden,
                            T.transpose(self.gpt.wte.weight, [1, 0]))
        return self.lm_head(hidden)

    def slot_prefill(self, input_ids, length):
        """Serving prefill for ONE request (paddle_tpu.serving.generation):
        input_ids [1, Sp] right-padded to the prompt bucket ``Sp``,
        ``length`` the real prompt length L (traced int32).  Causal
        attention makes the padded tail invisible to positions < L, so
        the returned last-real-token logits are exact; the padded tail's
        K/V entries are garbage the engine's per-slot position mask never
        exposes (and overwrites as decoding advances).

        Returns (k [layers, Sp, nh, hd], v [layers, Sp, nh, hd],
        logits [V] at position L-1) as raw jax arrays — the engine
        scatters them into its device-resident slot cache.
        """
        import jax.numpy as jnp
        from jax import lax

        import paddle_tpu as paddle

        from ..tensor import unwrap

        if self.training:
            raise RuntimeError(
                "slot_prefill/slot_decode are eval-only serving paths; "
                "call model.eval() first")
        gpt = self.gpt
        S = input_ids.shape[1]
        pos = paddle.arange(S)
        x = gpt.drop(gpt.wte(input_ids) + gpt.wpe(pos))
        ks, vs = [], []
        for blk in gpt.h:
            x, k, v = blk(x, return_kv=True)
            ks.append(unwrap(k)[0])
            vs.append(unwrap(v)[0])
        hidden = gpt.ln_f(x)                         # [1, Sp, H]
        length = jnp.asarray(unwrap(length), jnp.int32)
        last = lax.dynamic_slice_in_dim(unwrap(hidden), length - 1, 1,
                                        axis=1)      # [1, 1, H]
        logits = self._head(Tensor(last))
        return jnp.stack(ks), jnp.stack(vs), unwrap(logits)[0, 0]

    def slot_decode(self, tokens, pos, active, k_cache, v_cache):
        """Serving decode iteration over the slot-batched KV cache:
        tokens [slots] int32 (each lane's pending token), pos [slots]
        int32 write positions, active [slots] bool, caches
        [layers, slots, S_max, nh, hd].  Returns (logits [slots, V],
        k_cache', v_cache') — ONE fixed-shape program regardless of
        which lanes are live (continuous batching's iteration step).
        """
        import jax.numpy as jnp

        from ..tensor import unwrap

        if self.training:
            raise RuntimeError(
                "slot_prefill/slot_decode are eval-only serving paths; "
                "call model.eval() first")
        tokens = jnp.asarray(unwrap(tokens), jnp.int32)
        k_cache, v_cache = unwrap(k_cache), unwrap(v_cache)
        caches = tuple((k_cache[i], v_cache[i])
                       for i in range(self.cfg.num_layers))
        hidden, new_caches = self.gpt.decode_slots(
            Tensor(tokens[:, None]), pos, caches, active)
        logits = self._head(hidden)                  # [slots, 1, V]
        k2 = jnp.stack([k for k, _ in new_caches])
        v2 = jnp.stack([v for _, v in new_caches])
        return unwrap(logits)[:, 0], k2, v2

    def slot_decode_paged(self, tokens, pos, active, k_pages, v_pages,
                          rows, seq_cap):
        """Serving decode iteration over the PAGED slot-batched KV cache
        (serving/kv_cache.py): tokens [slots] int32, pos [slots] write
        positions, active [slots] bool, pools [layers, num_pages,
        page_size, nh, hd], rows [slots, pages_per_slot] int32 page
        table, seq_cap the static attention extent (engine S_max).
        Returns (logits [slots, V], k_pages', v_pages') — ONE
        fixed-shape program regardless of which lanes are live or how
        pages are scattered through the pool.  The two pools are threaded
        whole through the blocks: each writes its token's rows into its
        own plane and reads that plane where it lies, so with the pools
        donated the step rewrites them in place and holds no copy of a
        plane (tests/test_mosaic_compile.py reads the compiled step).
        """
        import jax.numpy as jnp

        from ..tensor import unwrap

        if self.training:
            raise RuntimeError(
                "slot_prefill/slot_decode are eval-only serving paths; "
                "call model.eval() first")
        gpt = self.gpt
        tokens = jnp.asarray(unwrap(tokens), jnp.int32)
        k_pages, v_pages = unwrap(k_pages), unwrap(v_pages)
        x = gpt.wte(Tensor(tokens[:, None])) \
            + gpt.wpe(T.reshape(Tensor(unwrap(pos)), [-1, 1]))
        for i, blk in enumerate(gpt.h):
            x, k_pages, v_pages = blk.decode_pages(
                x, k_pages, v_pages, rows, pos, active, seq_cap, i)
        logits = self._head(gpt.ln_f(x))             # [slots, 1, V]
        return unwrap(logits)[:, 0], unwrap(k_pages), unwrap(v_pages)

    def slot_verify_paged(self, tokens, positions, active, k_pages,
                          v_pages, rows, seq_cap):
        """Speculative-decode target verification over the PAGED cache:
        score a chunk of C candidate tokens per lane in ONE model step.
        tokens [slots, C] int32 (committed token ++ draft proposals),
        positions [slots, C] int32 absolute write indices (consecutive
        per lane), active [slots] bool, pools [layers, num_pages,
        page_size, nh, hd], rows [slots, pages_per_slot] int32.
        Returns (logits [slots, C, V], k_pages', v_pages') — the engine
        compares argmax(logits[:, i]) against draft proposal i+1 to
        accept or cut the speculation run.
        """
        import jax.numpy as jnp

        from ..tensor import unwrap

        if self.training:
            raise RuntimeError(
                "slot_prefill/slot_decode are eval-only serving paths; "
                "call model.eval() first")
        gpt = self.gpt
        cfg = self.cfg
        tokens = jnp.asarray(unwrap(tokens), jnp.int32)
        positions = jnp.asarray(unwrap(positions), jnp.int32)
        k_pages, v_pages = unwrap(k_pages), unwrap(v_pages)
        # clamped tail positions may sit at the extent edge; clip into
        # the embedding table (garbage rows the emission mask never
        # turns into output tokens)
        pos_emb = jnp.clip(positions, 0, cfg.max_position_embeddings - 1)
        x = gpt.wte(Tensor(tokens)) + gpt.wpe(Tensor(pos_emb))
        for i, blk in enumerate(gpt.h):
            x, k_pages, v_pages = blk.verify_pages(
                x, k_pages, v_pages, rows, positions, active, seq_cap, i)
        logits = self._head(gpt.ln_f(x))             # [slots, C, V]
        return unwrap(logits), unwrap(k_pages), unwrap(v_pages)

    def slot_prefill_prefix(self, input_ids, prefix_k, prefix_v,
                            prefix_len, length):
        """Prefix-cache-hit prefill: run ONLY the prompt's suffix
        through the model, attending over the cached prefix K/V — the
        shared pages are never recomputed.

        input_ids [1, Ss]: suffix tokens (positions ``prefix_len ..``)
        right-padded to the suffix bucket; prefix_k/prefix_v
        [layers, C, nh, hd]: prefix K/V gathered from the page pool
        (entries >= prefix_len are garbage the mask hides);
        ``prefix_len`` (traced) the shared-prefix length, ``length`` the
        FULL prompt length.  Returns (k_suf [layers, Ss, nh, hd], v_suf,
        logits [V] at suffix index length - prefix_len - 1).  Token-
        (not bitwise-) equivalent to the full ``slot_prefill`` path:
        the math matches up to float reassociation of the explicit
        softmax vs the fused causal kernel.
        """
        import jax.numpy as jnp
        from jax import lax

        from ..tensor import unwrap

        if self.training:
            raise RuntimeError(
                "slot_prefill/slot_decode are eval-only serving paths; "
                "call model.eval() first")
        gpt = self.gpt
        cfg = self.cfg
        S = input_ids.shape[1]
        prefix_len = jnp.asarray(unwrap(prefix_len), jnp.int32)
        length = jnp.asarray(unwrap(length), jnp.int32)
        # absolute positions of the suffix tokens; the padded tail may
        # run past max_position_embeddings — clip it into the table
        # (garbage rows the causal mask and length slice never expose)
        pos = jnp.clip(prefix_len + jnp.arange(S, dtype=jnp.int32),
                       0, cfg.max_position_embeddings - 1)
        x = gpt.drop(gpt.wte(input_ids) + gpt.wpe(Tensor(pos)))
        ks, vs = [], []
        for i, blk in enumerate(gpt.h):
            x, k, v = blk.prefill_prefix(x, prefix_k[i], prefix_v[i],
                                         prefix_len)
            ks.append(unwrap(k)[0])
            vs.append(unwrap(v)[0])
        hidden = gpt.ln_f(x)                         # [1, Ss, H]
        last = lax.dynamic_slice_in_dim(
            unwrap(hidden), length - prefix_len - 1, 1, axis=1)
        logits = self._head(Tensor(last))
        return jnp.stack(ks), jnp.stack(vs), unwrap(logits)[0, 0]

    def _beam_traced(self, input_ids, max_new_tokens, num_beams,
                     eos_token_id):
        """jit-traced beam search over the KV cache: beams live as an
        expanded batch [B*W]; each step expands W*V candidates through
        text.beam_search_step (the beam_search_op.cc redesign), reorders
        the caches along the surviving parents, and the final sequences
        are backtracked with text.gather_tree (gather_tree_op.cc)."""
        import jax
        import jax.numpy as jnp

        from ..tensor import unwrap
        from ..text import beam_search_decode, beam_search_step

        B, S = input_ids.shape[0], input_ids.shape[1]
        W = int(num_beams)
        V = self.cfg.vocab_size
        cache_len = S + int(max_new_tokens)
        eos = V if eos_token_id is None else int(eos_token_id)  # V = never

        ids = unwrap(input_ids)
        # prefill ONCE per prompt; beams only diverge after the first
        # expansion, so the caches/last-hidden just repeat along batch
        hidden, caches = self.gpt.prefill(input_ids, cache_len)
        caches = tuple((jnp.repeat(k, W, axis=0), jnp.repeat(v, W, axis=0))
                       for k, v in caches)

        def log_probs(hidden):
            lg = unwrap(self._head(hidden))[:, -1]            # [B*W, V]
            return jax.nn.log_softmax(lg, axis=-1).reshape(B, W, V)

        lg0 = unwrap(self._head(hidden[:, -1:]))[:, -1]       # [B, V]
        lp0 = jnp.broadcast_to(
            jax.nn.log_softmax(lg0, axis=-1)[:, None, :], (B, W, V))
        scores0 = jnp.full((B, W), jnp.finfo(jnp.float32).min,
                           jnp.float32).at[:, 0].set(0.0)
        finished0 = jnp.zeros((B, W), bool)
        batch_base = (jnp.arange(B, dtype=jnp.int32)[:, None] * W)

        def step(carry, _):
            lp, scores, finished, caches, pos = carry
            tok, parents, scores = (
                unwrap(t) for t in beam_search_step(
                    Tensor(lp), Tensor(scores), W, end_token=eos,
                    finished=Tensor(finished)))
            tok = tok.astype(jnp.int32)
            parents = parents.astype(jnp.int32)
            sel = (batch_base + parents).reshape(-1)          # [B*W]
            finished = jnp.take_along_axis(finished, parents, axis=1) \
                | (tok == eos)
            caches = tuple((k[sel], v[sel]) for k, v in caches)
            hidden, caches = self.gpt.decode_step(
                Tensor(tok.reshape(B * W, 1)), pos, caches)
            return ((log_probs(hidden), scores, finished, caches, pos + 1),
                    (tok, parents))

        (_, scores, _, _, _), (toks, parents) = jax.lax.scan(
            step, (lp0, scores0, finished0, caches,
                   jnp.asarray(S, jnp.int32)),
            None, length=int(max_new_tokens))
        # backtrack surviving paths (beam_search_decode_op analog)
        seqs, scores = beam_search_decode(Tensor(toks), Tensor(parents),
                                          Tensor(scores))
        best = jnp.argmax(unwrap(scores), axis=1)             # [B]
        seq = jnp.take_along_axis(
            unwrap(seqs), best[:, None, None].astype(jnp.int32),
            axis=1)[:, 0]
        return jnp.concatenate([ids, seq.astype(jnp.int32)], axis=1)

    def _generate_traced(self, input_ids, rng, max_new_tokens, temperature,
                         top_k, do_sample, eos_token_id):
        """jit-traced generation body: batched prefill, then lax.scan
        single-token decode over static-size KV caches — the
        TPU-idiomatic serving loop (static shapes, no per-step dispatch;
        the reference's dynamic while_loop + beam_search_op decoders,
        operators/beam_search_op.cc, trade shape dynamism for host
        round-trips that ICI latency makes prohibitive here)."""
        import jax
        import jax.numpy as jnp

        from ..tensor import unwrap

        B, S = input_ids.shape[0], input_ids.shape[1]
        cache_len = S + int(max_new_tokens)
        V = self.cfg.vocab_size
        eos = V if eos_token_id is None else int(eos_token_id)  # V = never

        def sample(logits, key):
            logits = unwrap(logits)[:, -1]            # [B, V]
            if not do_sample:
                return jnp.argmax(logits, axis=-1).astype(jnp.int32)
            logits = logits / jnp.maximum(temperature, 1e-6)
            if top_k and top_k > 0:
                kth = jax.lax.top_k(logits, top_k)[0][:, -1:]
                logits = jnp.where(logits < kth,
                                   jnp.finfo(logits.dtype).min, logits)
            return jax.random.categorical(key, logits).astype(jnp.int32)

        hidden, caches = self.gpt.prefill(input_ids, cache_len)
        key, sub = jax.random.split(rng)
        tok = sample(self._head(hidden[:, -1:]), sub)  # first new token
        finished = tok == eos

        def step(carry, _):
            tok, finished, pos, caches, key = carry
            key, sub = jax.random.split(key)
            hidden, caches = self.gpt.decode_step(
                Tensor(tok[:, None]), pos, caches)
            nxt = sample(self._head(hidden), sub)
            nxt = jnp.where(finished, jnp.int32(eos), nxt)  # pad past eos
            finished = finished | (nxt == eos)
            return (nxt, finished, pos + 1, caches, key), tok

        (last, _, _, _, _), toks = jax.lax.scan(
            step, (tok, finished, jnp.asarray(S, jnp.int32), caches, key),
            None, length=int(max_new_tokens) - 1)
        toks = jnp.concatenate(
            [jnp.moveaxis(toks, 0, 1), last[:, None]], axis=1)  # [B, new]
        return jnp.concatenate([unwrap(input_ids), toks], axis=1)

    def generate(self, input_ids, max_new_tokens=32, temperature=1.0,
                 top_k=0, do_sample=False, seed=0, num_beams=1,
                 eos_token_id=None):
        """Autoregressive generation with a static KV cache.

        Greedy by default; ``do_sample=True`` enables temperature / top-k
        categorical sampling; ``num_beams > 1`` runs beam search (length
        penalty not applied; finished beams propose only
        ``eos_token_id``).  The whole loop (prefill + every decode step)
        compiles to ONE XLA program per (batch, prompt_len,
        max_new_tokens, mode) shape — cached across calls in a per-shape
        dict.  Returns [B, prompt_len + max_new_tokens] int32 token ids
        (prompt included), matching the HF/paddlenlp generate contract.
        """
        import jax
        import numpy as np

        from ..nn.layer_base import functional_call, state_pytrees

        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if num_beams > 1 and do_sample:
            raise ValueError("beam search and sampling are exclusive "
                             "(num_beams > 1 with do_sample=True)")
        ids = input_ids if isinstance(input_ids, Tensor) \
            else Tensor(np.asarray(input_ids, np.int32))
        if ids.shape[1] + int(max_new_tokens) \
                > self.cfg.max_position_embeddings:
            raise ValueError(
                f"prompt {ids.shape[1]} + max_new_tokens {max_new_tokens} "
                f"exceeds max_position_embeddings "
                f"{self.cfg.max_position_embeddings}")
        was_training = self.training
        self.eval()
        try:
            params, buffers = state_pytrees(self)
            # sampling knobs only shape the program when do_sample is on
            key_static = (ids.shape[0], ids.shape[1], int(max_new_tokens),
                          bool(do_sample), int(num_beams),
                          None if eos_token_id is None else int(eos_token_id),
                          (float(temperature), int(top_k))
                          if do_sample else None)
            cache = getattr(self, "_gen_cache", None)
            if cache is None:
                cache = self._gen_cache = {}
            if key_static not in cache:
                if num_beams > 1:
                    def run(params, ids_arr, rng):
                        out, _ = functional_call(
                            self, params,
                            (Tensor(ids_arr), max_new_tokens, num_beams,
                             eos_token_id),
                            buffers=buffers, mutable=False,
                            method="_beam_traced")
                        return out
                else:
                    def run(params, ids_arr, rng):
                        out, _ = functional_call(
                            self, params,
                            (Tensor(ids_arr), rng, max_new_tokens,
                             temperature, top_k, do_sample, eos_token_id),
                            buffers=buffers, mutable=False,
                            method="_generate_traced")
                        return out

                cache[key_static] = jax.jit(run)
            fn = cache[key_static]
            rng = jax.random.PRNGKey(seed)
            return Tensor(fn(params, ids.value.astype("int32"), rng))
        finally:
            if was_training:
                self.train()
