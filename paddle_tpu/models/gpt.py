"""GPT decoder-only language model, tensor-parallel-ready.

Workload parity: SURVEY.md section 6, GPT-3 1.3B with TP+PP.  The reference
tree has no GPT implementation (it lives in PaddleNLP); this is the TPU-native
flagship: GSPMD tensor parallelism via the meta_parallel layers (weights carry
PartitionSpecs; XLA inserts the Megatron collectives), optional
sequence-parallel ring attention for long context, fused attention via the
Pallas flash kernel on TPU (ops/fused.scaled_dot_product_attention).
"""
from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from .. import tensor_ops as T
from ..distributed.meta_parallel import (ColumnParallelLinear,
                                         RowParallelLinear,
                                         VocabParallelEmbedding,
                                         shard_constraint)
from ..distributed.recompute import recompute as _remat
from ..nn import initializer as I
from ..nn.layer_base import Layer, ParamAttr
from ..nn.layer.common import Dropout, Embedding, Linear
from ..nn.layer.norm import LayerNorm
from ..ops import fused
from ..tensor import Tensor, unwrap


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
        # the mesh axis the heads follow (the qkv column shards): pinned on
        # q/k/v here, and handed to a KV source for its cache and kernel
        self._head_axis = "mp" if cfg.tensor_parallel else None
        self.dropout = Dropout(cfg.dropout)

    def forward(self, x, kv=None, layer=None, return_kv=False):
        """Project the new tokens' q, k, v; attend; project out.

        ``kv=None`` is causal self-attention among the tokens of ``x``:
        training, and the prompt pass, which asks with ``return_kv`` for
        its k, v [B, S, nh, hd] back to seed a cache.  Otherwise ``kv``
        is a KV source (``DenseKV`` below; serving/kv_cache.py
        ``PagedKV``, ``PrefixKV``): it takes the new k, v in as layer
        ``layer`` (a static int) and gives q's context over all it then
        holds.  Returns (out, kv').
        """
        cfg = self.cfg
        B, S = x.shape[0], x.shape[1]
        nh, hd = cfg.num_heads, cfg.hidden_size // cfg.num_heads
        qkv = T.reshape(self.qkv(x), [B, S, 3, nh, hd])
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        if kv is not None:
            ctx, kv = kv.attend(layer, unwrap(q), unwrap(k), unwrap(v),
                                head_axis=self._head_axis)
            ctx = Tensor(ctx)
        else:
            if self._head_axis:
                q, k, v = (shard_constraint(t, None, None, self._head_axis,
                                            None) for t in (q, k, v))
            if cfg.sequence_parallel:
                from ..ops.ring_attention import ring_attention

                ctx = ring_attention(q, k, v, causal=True)
            else:
                ctx = fused.scaled_dot_product_attention(
                    q, k, v, dropout_p=cfg.attn_dropout, is_causal=True,
                    training=self.training)
        out = self.dropout(self.out(T.reshape(ctx, [B, S, cfg.hidden_size])))
        if kv is not None:
            return out, kv
        return (out, k, v) if return_kv else out


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

    def forward(self, x, kv=None, layer=None, return_kv=False):
        """x, followed by whatever the attention returned beside its
        output (the KV source, or k and v)."""
        a = self.attn(self.ln_1(x), kv, layer, return_kv)
        a, rest = (a[0], a[1:]) if isinstance(a, tuple) else (a, ())
        x = x + a
        x = x + self.mlp(self.ln_2(x))
        return (x, *rest) if rest else x


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

    def forward(self, input_ids, positions=None, kv=None, return_kv=False):
        """Hidden states [B, S, H] of ``input_ids`` [B, S] at ``positions``
        (broadcastable to [B, S]; default 0..S-1).

        With a KV source ``kv`` every layer attends over it: returns
        (hidden, kv').  With ``return_kv`` (the prompt pass of a cached
        generation) returns (hidden, [(k, v) [B, S, nh, hd] per layer]).
        Both are eval-only.
        """
        import paddle_tpu as paddle

        if self.training and (kv is not None or return_kv):
            raise RuntimeError(
                "cached generation is eval-only (dropout over a prompt "
                "pass or a decode step would make the cache disagree "
                "with the tokens served from it); call model.eval() first")
        if positions is None:
            positions = paddle.arange(input_ids.shape[1])
        x = self.drop(self.wte(input_ids) + self.wpe(positions))
        kvs = []
        for i, blk in enumerate(self.h):
            if kv is not None:
                x, kv = blk(x, kv, i)
            elif return_kv:
                x, k, v = blk(x, return_kv=True)
                kvs.append((k, v))
            elif self.cfg.recompute:
                x = _remat(blk, x)
            else:
                x = blk(x)
        x = self.ln_f(x)
        if kv is not None:
            return x, kv
        return (x, kvs) if return_kv else x


@jax.tree_util.register_dataclass
@dataclass
class DenseKV:
    """``generate()``'s KV source: a static cache of S_max rows per
    sequence, every sequence at the same scalar position (the
    reference's analog is fused_multi_transformer's CacheKV decode; here
    it is dynamic_update_slice + masked attention over the static cache,
    jit/scan-safe).

    caches: per layer (k, v) [B, S_max, nh, hd]; pos: scalar int32, the
    row the next token occupies (rows past it are masked out).
    """
    caches: tuple
    pos: Any

    @classmethod
    def from_prompt(cls, kvs, cache_len):
        """Seed from a prompt pass's per-layer (k, v) [B, S, nh, hd],
        zero-padded to ``cache_len`` >= prompt + new tokens."""
        def pad(t):
            t = unwrap(t)
            return jnp.zeros((t.shape[0], cache_len) + t.shape[2:],
                             t.dtype).at[:, :t.shape[1]].set(t)

        return cls(tuple((pad(k), pad(v)) for k, v in kvs),
                   jnp.asarray(kvs[0][0].shape[1], jnp.int32))

    def attend(self, layer, q, k, v, head_axis=None):
        kc, vc = self.caches[layer]
        zero = jnp.int32(0)
        kc = lax.dynamic_update_slice(kc, k, (zero, self.pos, zero, zero))
        vc = lax.dynamic_update_slice(vc, v, (zero, self.pos, zero, zero))
        if head_axis:
            # without the pin GSPMD may pick a gathered layout for the
            # per-step attention and pay an all-gather every decode step
            q, kc, vc = (unwrap(shard_constraint(
                Tensor(t), None, None, head_axis, None)) for t in (q, kc, vc))
        valid = (jnp.arange(kc.shape[1]) <= self.pos)[None, None, :]
        ctx = fused.masked_attention(q, kc, vc, valid)
        caches = self.caches[:layer] + ((kc, vc),) + self.caches[layer + 1:]
        return ctx, replace(self, caches=caches)


def _row(hidden, i):
    """hidden[:, i:i+1] for a traced index ``i``."""
    return Tensor(lax.dynamic_slice_in_dim(
        unwrap(hidden), jnp.asarray(unwrap(i), jnp.int32), 1, axis=1))


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

    # -- the serving protocol (paddle_tpu.serving.generation): these two
    # methods and ``cfg`` are all GenerationEngine asks of a model --------
    def slot_prefill(self, input_ids, length):
        """Serving prefill for ONE request: input_ids [1, Sp] right-padded
        to the prompt bucket ``Sp``, ``length`` the real prompt length L
        (traced int32).  Causal attention makes the padded tail invisible
        to positions < L, so the returned last-real-token logits are
        exact; the padded tail's K/V entries are garbage the engine's
        per-slot position mask never exposes (and overwrites as decoding
        advances).

        Returns (k [layers, Sp, nh, hd], v [layers, Sp, nh, hd],
        logits [V] at position L-1) as raw jax arrays — the engine
        scatters them into its device-resident page pool.
        """
        hidden, kvs = self.gpt(input_ids, return_kv=True)
        logits = self._head(_row(hidden, unwrap(length) - 1))
        return (jnp.stack([unwrap(k)[0] for k, _ in kvs]),
                jnp.stack([unwrap(v)[0] for _, v in kvs]),
                unwrap(logits)[0, 0])

    def slot_step(self, tokens, positions, kv, last=None):
        """One model step over a KV source: tokens [B, C] int32 at
        absolute ``positions`` [B, C], every layer attending through
        ``kv.attend`` (serving/kv_cache.py).  What the step is depends on
        the source alone: a ``PagedKV`` with one token a lane is the
        decode iteration, ONE fixed-shape program whichever lanes are
        live and however pages lie in the pool; with a chunk of C
        candidates a lane it is speculative verification; a ``PrefixKV``
        makes it the suffix-only prefill of a prefix-cache hit.

        Returns (logits, kv'): logits [B, C, V], or [B, 1, V] of row
        ``last`` (a traced index) alone when given, so a prefill pays the
        head for one row.  Positions are clipped into the embedding table:
        a padded tail or a lane past its budget embeds garbage that no
        mask exposes and no emitted token reads.
        """
        tokens = jnp.asarray(unwrap(tokens), jnp.int32)
        positions = jnp.clip(jnp.asarray(unwrap(positions), jnp.int32),
                             0, self.cfg.max_position_embeddings - 1)
        hidden, kv = self.gpt(Tensor(tokens), Tensor(positions), kv)
        if last is not None:
            hidden = _row(hidden, last)
        return unwrap(self._head(hidden)), kv

    def _beam_traced(self, input_ids, max_new_tokens, num_beams,
                     eos_token_id):
        """jit-traced beam search over the KV cache: beams live as an
        expanded batch [B*W]; each step expands W*V candidates through
        text.beam_search_step (the beam_search_op.cc redesign), reorders
        the caches along the surviving parents, and the final sequences
        are backtracked with text.gather_tree (gather_tree_op.cc)."""
        from ..text import beam_search_decode, beam_search_step

        B, S = input_ids.shape[0], input_ids.shape[1]
        W = int(num_beams)
        V = self.cfg.vocab_size
        eos = V if eos_token_id is None else int(eos_token_id)  # V = never

        ids = unwrap(input_ids)
        # prefill ONCE per prompt; beams only diverge after the first
        # expansion, so the caches/last-hidden just repeat along batch
        hidden, kvs = self.gpt(input_ids, return_kv=True)
        kv = DenseKV.from_prompt(kvs, S + int(max_new_tokens))
        kv = replace(kv, caches=tuple(
            (jnp.repeat(k, W, axis=0), jnp.repeat(v, W, axis=0))
            for k, v in kv.caches))

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
            lp, scores, finished, kv = carry
            tok, parents, scores = (
                unwrap(t) for t in beam_search_step(
                    Tensor(lp), Tensor(scores), W, end_token=eos,
                    finished=Tensor(finished)))
            tok = tok.astype(jnp.int32)
            parents = parents.astype(jnp.int32)
            sel = (batch_base + parents).reshape(-1)          # [B*W]
            finished = jnp.take_along_axis(finished, parents, axis=1) \
                | (tok == eos)
            kv = replace(kv, caches=tuple(
                (k[sel], v[sel]) for k, v in kv.caches))
            hidden, kv = self.gpt(Tensor(tok.reshape(B * W, 1)),
                                  Tensor(kv.pos.reshape(1)), kv)
            return ((log_probs(hidden), scores, finished,
                     replace(kv, pos=kv.pos + 1)), (tok, parents))

        (_, scores, _, _), (toks, parents) = jax.lax.scan(
            step, (lp0, scores0, finished0, kv), None,
            length=int(max_new_tokens))
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
        S = input_ids.shape[1]
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

        hidden, kvs = self.gpt(input_ids, return_kv=True)
        kv = DenseKV.from_prompt(kvs, S + int(max_new_tokens))
        key, sub = jax.random.split(rng)
        tok = sample(self._head(hidden[:, -1:]), sub)  # first new token
        finished = tok == eos

        def step(carry, _):
            tok, finished, kv, key = carry
            key, sub = jax.random.split(key)
            hidden, kv = self.gpt(Tensor(tok[:, None]),
                                  Tensor(kv.pos.reshape(1)), kv)
            nxt = sample(self._head(hidden), sub)
            nxt = jnp.where(finished, jnp.int32(eos), nxt)  # pad past eos
            finished = finished | (nxt == eos)
            return (nxt, finished, replace(kv, pos=kv.pos + 1), key), tok

        (last, _, _, _), toks = jax.lax.scan(
            step, (tok, finished, kv, key), None,
            length=int(max_new_tokens) - 1)
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
                    def run(params, buffers, ids_arr, rng):
                        out, _ = functional_call(
                            self, params,
                            (Tensor(ids_arr), max_new_tokens, num_beams,
                             eos_token_id),
                            buffers=buffers, mutable=False,
                            method="_beam_traced")
                        return out
                else:
                    def run(params, buffers, ids_arr, rng):
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
            return Tensor(fn(params, buffers, ids.value.astype("int32"),
                             rng))
        finally:
            if was_training:
                self.train()
