"""Granite 4.0-H: a decoder of Mamba-2 layers with, one layer in ten,
attention without positions, every layer followed by a dense gated FFN
(IBM granite-4.0-h-micro, `model_type` `granitemoehybrid`).

With eps `rms_norm_eps` and no bias anywhere but the convolution:
  x = embedding_multiplier * E[token]
  each layer: x = x + residual_multiplier * Mixer(RMSNorm(x))
              x = x + residual_multiplier * FFN(RMSNorm(x))
  logits = RMSNorm(x) @ E.T / logits_scaling             (tied head)
  FFN        [g, u] = split(W_in h); W_out (silu(g) * u)
  attention  nq query heads over nkv KV heads of hd, no rotary or other
             positions, scores attention_multiplier * q k^T, causal
  Mamba-2    [z, xBC, dt] = split(W_in h); xBC through a depthwise causal
             convolution of width d_conv and a SiLU; [x, B, C] = split(xBC);
             a head's state H_t = exp(D_t A) H_{t-1} + D_t x_t B_t^T with
             D_t = softplus(dt_t + dt_bias), A = -exp(A_log); y_t = H_t C_t
             + D x_t; y = RMSNorm(y * silu(z)) over all of d_inner;
             out = W_out y

The model speaks the serving protocol (`slot_prefill`, `slot_step`, `cfg`)
and declares through `cfg` what GenerationEngine has to hold for it beside
the pages of its attention layers: `state_layers` (the layers that carry a
recurrent state), `state_shape` and `conv_shape` (one lane's state and
convolution tail in one such layer).  Its layers meet their state only
through a source of serving/kv_cache.py, as attention meets its keys:
`attend(plane, q, k, v)` for the attention layers (numbered among
themselves: the pool holds their planes alone), and for the Mamba layers
`window(plane, xBC)`, which lays the tokens behind the convolution's tail,
and `scan(plane, x, dt, A, B, C)`, which runs the recurrence: a chunked
scan over a prompt (`fused.ssd_chunk_scan`), one token of every live lane
in place while decoding (`fused.ssm_decode_update`).

The page pool holds the attention layers' KV heads `kv_pack` side by side
(two heads of 64 as one of 128): a page of 8 heads of 64 is no whole tile
of the pool and the paged kernel would walk it by the grid, every table
column of every slot, over a copy of the pool in the kernel's layout
(PERF.md section 6, PR 41); a page of 4 heads of 128 is whole tiles, and
the kernel walks a lane's own pages where they lie.  So `cfg.num_kv_heads`
and `cfg.head_dim`, which GenerationEngine builds the pool from, are the
packed ones, and a query head meets a pair of KV heads as one key of 128
lanes with zeros in the half that is not its own head's: the scores are
its own head's, and of the context it keeps its own head's half.

Attention's kernels scale scores by 1 / sqrt(of the key's width); the
model's scale is `attention_multiplier`, so q is multiplied by
attention_multiplier * sqrt(that width) before it meets a key.

Imported only by who uses it (`paddle_tpu.models.granite_hybrid`).
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..nn import initializer as I
from ..nn.layer.common import Embedding, Linear
from ..nn.layer_base import Layer, ParamAttr
from ..ops import fused
from ..tensor import Tensor, unwrap
from .sdar import RMSNorm, _row

__all__ = ["GraniteHybridConfig", "GraniteHybridForCausalLM"]


@dataclass
class GraniteHybridConfig:
    vocab_size: int = 100352
    hidden_size: int = 2048
    num_layers: int = 40
    num_heads: int = 32            # query heads of the attention layers
    kv_heads: int = 8              # their KV heads (`num_key_value_heads`)
    intermediate_size: int = 8192
    # one entry a layer, "mamba" | "attention"; () = attention at 5, 15, ...
    layer_types: tuple = ()
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 256
    attention_multiplier: float = 0.015625
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    logits_scaling: float = 8.0
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 131072
    initializer_range: float = 0.02

    def __post_init__(self):
        if not self.layer_types:
            self.layer_types = tuple(
                "attention" if i % 10 == 5 else "mamba"
                for i in range(self.num_layers))
        self.layer_types = tuple(self.layer_types)
        bad = set(self.layer_types) - {"mamba", "attention"}
        if bad or len(self.layer_types) != self.num_layers:
            raise ValueError(
                f"layer_types: {self.num_layers} entries of mamba | "
                f"attention, got {self.layer_types}")
        if self.hidden_size % self.num_heads:
            raise ValueError("hidden_size must divide over num_heads")

    @property
    def attn_head_dim(self) -> int:
        """The width of one attention head."""
        return self.hidden_size // self.num_heads

    @property
    def kv_pack(self) -> int:
        """KV heads the page pool holds side by side as one: as many as
        fill 128 lanes, if they divide the KV heads."""
        r = max(1, min(self.kv_heads, 128 // self.attn_head_dim))
        while self.kv_heads % r:
            r -= 1
        return r

    # what GenerationEngine builds the page pool from: the packed heads
    @property
    def num_kv_heads(self) -> int:
        return self.kv_heads // self.kv_pack

    @property
    def head_dim(self) -> int:
        return self.attn_head_dim * self.kv_pack

    @property
    def d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.mamba_d_state

    # -- what GenerationEngine holds for the model beside its pages --------
    @property
    def state_layers(self) -> tuple:
        """The layers that carry a recurrent state (and hold no page)."""
        return tuple(i for i, t in enumerate(self.layer_types)
                     if t == "mamba")

    @property
    def state_chunk(self) -> int:
        """Tokens a chunk of the prompt pass's scan."""
        return self.mamba_chunk_size

    @property
    def state_pack(self) -> int:
        return fused.ssm_pack(self.mamba_n_heads, self.mamba_d_head)

    @property
    def state_shape(self) -> tuple:
        """One lane's state in one Mamba layer as it is held (float32):
        `fused.ssm_pack_state`'s layout of [heads, d_head, d_state]."""
        r = self.state_pack
        return (self.mamba_n_heads // r, self.mamba_d_state,
                r * self.mamba_d_head)

    @property
    def conv_shape(self) -> tuple:
        """One lane's convolution tail in one Mamba layer: the last d_conv
        - 1 inputs of the convolution."""
        return (self.mamba_d_conv - 1, self.conv_dim)


def _init(cfg):
    return ParamAttr(initializer=I.Normal(0.0, cfg.initializer_range))


def _lin(cfg, i, o):
    return Linear(i, o, weight_attr=_init(cfg), bias_attr=False)


class GraniteAttention(Layer):
    """Grouped-query attention without positions.  `plane` is the layer's
    number among the attention layers: its plane of the page pool."""

    def __init__(self, cfg: GraniteHybridConfig, plane: int):
        super().__init__()
        self.cfg, self.plane = cfg, plane
        H, hd = cfg.hidden_size, cfg.attn_head_dim
        nq, nkv = cfg.num_heads, cfg.kv_heads
        if nq % nkv:
            raise ValueError(f"{nq} query heads over {nkv} KV heads")
        self.q, self.k, self.v = _lin(cfg, H, nq * hd), \
            _lin(cfg, H, nkv * hd), _lin(cfg, H, nkv * hd)
        self.out = _lin(cfg, nq * hd, H)

    def forward(self, x, src=None):
        """``src=None``: causal attention among the tokens of ``x`` (the
        cold prompt pass), returning (out, (k, v)) with k, v [B, S, packed
        KV heads, packed width] to seed the pages.  Otherwise ``src`` is a
        source that takes the new k, v in as plane ``self.plane``: returns
        (out, src')."""
        cfg = self.cfg
        B, S = x.shape[0], x.shape[1]
        nq, nkv, hd, r = (cfg.num_heads, cfg.kv_heads, cfg.attn_head_dim,
                          cfg.kv_pack)
        q = unwrap(self.q(x)).reshape(B, S, nq, hd)
        k = unwrap(self.k(x)).reshape(B, S, nkv, hd)
        v = unwrap(self.v(x)).reshape(B, S, nkv, hd)
        packed = (B, S, nkv // r, r * hd)

        def scaled(width):  # the kernels' 1 / sqrt(width) times this
            return (q.astype(jnp.float32) * (
                cfg.attention_multiplier * math.sqrt(width))).astype(q.dtype)

        if src is None:
            ctx = fused.banded_attention(scaled(hd), k, v)
            rest = (k.reshape(packed), v.reshape(packed))
        elif r == 1:
            ctx, rest = src.attend(self.plane, scaled(hd), k, v)
        else:
            # query head h reads KV head h // g, which is half (h // g) % r
            # of packed head h // (g r): its query lies in that half of the
            # packed width, zeros in the rest
            half = (jnp.arange(nq) // (nq // nkv)) % r
            own = jax.nn.one_hot(half, r, dtype=q.dtype)        # [nq, r]
            wide = (scaled(r * hd)[:, :, :, None, :]
                    * own[None, None, :, :, None]).reshape(B, S, nq, r * hd)
            ctx, rest = src.attend(self.plane, wide, k.reshape(packed),
                                   v.reshape(packed))
            ctx = jnp.einsum("bsnrd,nr->bsnd",
                             unwrap(ctx).reshape(B, S, nq, r, hd), own)
        return self.out(Tensor(unwrap(ctx).reshape(B, S, nq * hd))), rest


class GraniteMamba(Layer):
    """The Mamba-2 mixer.  `plane` is the layer's number among the Mamba
    layers: its plane of the held states."""

    def __init__(self, cfg: GraniteHybridConfig, plane: int):
        super().__init__()
        self.cfg, self.plane = cfg, plane
        H, nh = cfg.hidden_size, cfg.mamba_n_heads
        self.in_proj = _lin(cfg, H, cfg.d_inner + cfg.conv_dim + nh)
        self.conv_weight = self.create_parameter(
            [cfg.conv_dim, cfg.mamba_d_conv],
            default_initializer=I.Uniform(-0.5, 0.5))
        self.conv_bias = self.create_parameter(
            [cfg.conv_dim], default_initializer=I.Constant(0.0))
        self.dt_bias = self.create_parameter(
            [nh], default_initializer=I.Constant(-4.6))   # softplus^-1(0.01)
        self.A_log = self.create_parameter(
            [nh], default_initializer=I.Assign(
                np.log(np.arange(1, nh + 1, dtype=np.float32))))
        self.D = self.create_parameter(
            [nh], default_initializer=I.Constant(1.0))
        self.norm = RMSNorm(cfg.d_inner, cfg.rms_norm_eps)
        self.out_proj = _lin(cfg, cfg.d_inner, H)

    def forward(self, x, src):
        """x [B, S, H] through the mixer over the state source ``src``
        (serving/kv_cache.py): returns (out, src')."""
        cfg = self.cfg
        f32 = jnp.float32
        B, S = x.shape[0], x.shape[1]
        nh, P, N, K = (cfg.mamba_n_heads, cfg.mamba_d_head,
                       cfg.mamba_d_state, cfg.mamba_d_conv)
        d_inner, conv_dim = cfg.d_inner, cfg.conv_dim
        zxbcdt = unwrap(self.in_proj(x))
        z = zxbcdt[..., :d_inner]
        xBC = zxbcdt[..., d_inner:d_inner + conv_dim]
        dt = jax.nn.softplus(zxbcdt[..., d_inner + conv_dim:].astype(f32)
                             + unwrap(self.dt_bias).astype(f32))
        # the tokens behind the d_conv - 1 that came before them
        past, src = src.window(self.plane, xBC)          # [B, S + K - 1, C]
        taps = unwrap(self.conv_weight).astype(f32)
        conv = unwrap(self.conv_bias).astype(f32) + sum(
            taps[:, j] * past[:, j:j + S].astype(f32) for j in range(K))
        xBC = jax.nn.silu(conv).astype(xBC.dtype)
        xs = xBC[..., :d_inner].reshape(B, S, nh, P)
        y, src = src.scan(
            self.plane, xs, dt, -jnp.exp(unwrap(self.A_log).astype(f32)),
            xBC[..., d_inner:d_inner + N], xBC[..., d_inner + N:])
        y = y + unwrap(self.D).astype(f32)[:, None] * xs.astype(f32)
        y = y.reshape(B, S, d_inner) * jax.nn.silu(z.astype(f32))
        y = self.norm(Tensor(y.astype(z.dtype)))
        return self.out_proj(y), src


class GraniteFFN(Layer):
    def __init__(self, cfg: GraniteHybridConfig):
        super().__init__()
        self._width = cfg.intermediate_size
        self.w_in = _lin(cfg, cfg.hidden_size, 2 * cfg.intermediate_size)
        self.w_out = _lin(cfg, cfg.intermediate_size, cfg.hidden_size)

    def forward(self, x):
        gu = unwrap(self.w_in(x))
        g, u = gu[..., :self._width], gu[..., self._width:]
        return self.w_out(Tensor(
            (jax.nn.silu(g.astype(jnp.float32)) * u.astype(jnp.float32))
            .astype(gu.dtype)))


class GraniteBlock(Layer):
    def __init__(self, cfg: GraniteHybridConfig, kind: str, plane: int):
        super().__init__()
        self._r = cfg.residual_multiplier
        self.ln_1 = RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        self.mixer = (GraniteAttention if kind == "attention"
                      else GraniteMamba)(cfg, plane)
        self.ln_2 = RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        self.ffn = GraniteFFN(cfg)

    def forward(self, x, src):
        a, rest = self.mixer(self.ln_1(x), src)
        x = x + a * self._r
        return x + self.ffn(self.ln_2(x)) * self._r, rest


class GraniteHybridModel(Layer):
    def __init__(self, cfg: GraniteHybridConfig):
        super().__init__()
        self.cfg = cfg
        self.embed = Embedding(cfg.vocab_size, cfg.hidden_size,
                               weight_attr=_init(cfg))
        planes = {"mamba": 0, "attention": 0}
        self.h = []
        for i, kind in enumerate(cfg.layer_types):
            blk = GraniteBlock(cfg, kind, planes[kind])
            planes[kind] += 1
            self.h.append(blk)
            self.add_sublayer(f"h_{i}", blk)
        self.norm_f = RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)

    def forward(self, input_ids, src, among=False):
        """Hidden states [B, S, H] over the source ``src``.  ``among``: the
        attention layers attend among the tokens themselves (the cold prompt
        pass) and hand back their (k, v); the Mamba layers go through
        ``src`` either way.  Returns (hidden, src', [(k, v) ...]).  Eval
        only."""
        if self.training:
            raise RuntimeError(
                "GraniteHybridModel runs in eval mode only (the scan has no "
                "backward pass yet); call model.eval()")
        x = self.embed(input_ids) * self.cfg.embedding_multiplier
        kvs = []
        for blk, kind in zip(self.h, self.cfg.layer_types):
            if kind == "attention" and among:
                x, kv = blk(x, None)
                kvs.append(kv)
            else:
                x, src = blk(x, src)
        return self.norm_f(x), src, kvs


class GraniteHybridForCausalLM(Layer):
    def __init__(self, cfg: GraniteHybridConfig):
        super().__init__()
        self.cfg = cfg
        self.granite = GraniteHybridModel(cfg)

    def head(self, hidden):
        """The tied head: hidden @ E.T / logits_scaling, in float32 (the
        logits of random weights lie close together: a product rounded to
        bfloat16 would move the served token more than the sums do)."""
        e = unwrap(self.granite.embed.weight)
        return jnp.einsum("bsh,vh->bsv", unwrap(hidden), e,
                          preferred_element_type=jnp.float32) \
            / self.cfg.logits_scaling

    def forward(self, input_ids):
        """Logits [B, S, V] of whole sequences from a zero state (position
        i's logits predict the token at i + 1)."""
        from ..serving.kv_cache import PromptStates

        ids = unwrap(input_ids)
        out = []
        for row in ids:     # the scan takes one sequence at a time
            hidden, _, _ = self.granite(
                Tensor(row[None]), PromptStates.zeros(self.cfg, ids.shape[1]),
                among=True)
            out.append(self.head(hidden))
        return Tensor(jnp.concatenate(out))

    # -- the serving protocol (paddle_tpu.serving.generation) --------------
    def slot_prefill(self, input_ids, length, snap_at=0):
        """The cold prompt pass for ONE request from a zero state: input_ids
        [1, Sp] right-padded to the bucket, ``length`` the prompt's length
        (traced); padded positions leave state and tail untouched.  Returns
        (k [attention layers, Sp, nkv, hd], v, logits [V] at position length
        - 1, and the states: (end [state layers, ...], its tail, the state
        after token ``snap_at`` - 1, its tail))."""
        from ..serving.kv_cache import PromptStates

        hidden, src, kvs = self.granite(
            input_ids, PromptStates.zeros(self.cfg, length, snap_at),
            among=True)
        logits = self.head(_row(hidden, unwrap(length) - 1))
        return (jnp.stack([k[0] for k, _ in kvs]),
                jnp.stack([v[0] for _, v in kvs]),
                logits[0, 0], src.ends())

    def slot_step(self, tokens, positions, kv, last=None, live=None):
        """One model step over a source of serving/kv_cache.py that holds
        both kinds of state (``HybridKV``): tokens [B, C].  No layer takes a
        position: ``positions`` is the protocol's.  Returns (logits, kv'):
        logits [B, C, V], or [B, 1, V] of row ``last`` alone."""
        del positions, live
        tokens = jnp.asarray(unwrap(tokens), jnp.int32)
        hidden, kv, _ = self.granite(Tensor(tokens), kv)
        if last is not None:
            hidden = _row(hidden, last)
        return self.head(hidden), kv
