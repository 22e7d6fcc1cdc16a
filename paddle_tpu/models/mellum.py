"""Mellum: a Qwen3-MoE-shaped decoder whose layers alternate between a
sliding window and full attention (JetBrains Mellum2-12B-A2.5B-Instruct,
`model_type` `mellum`).

The block is models/sdar.py's (RMSNorm, q/k/v/o without bias, an RMSNorm
over the head on q and k, rotate-half rotary positions over the whole head,
grouped KV heads, `nn.DroplessMoE`), shared and not copied.  What this
module adds:

  layer kinds   `layer_types[i]` is "sliding_attention" (query i sees key j
                iff i - sliding_window < j <= i) or "full_attention" (j <=
                i); `cfg.layer_windows` gives the engine one window a layer
                (0 = full), from which it builds a page pool of its own
                for the window layers (serving/kv_cache.py)
  rotary laws   by layer kind: the default law on the sliding layers, YaRN
                on the full ones (blended frequencies, cos and sin scaled
                by the attention factor), as `transformers` computes them
  prompt pass   causal (and windowed) attention among the prompt's tokens in
                blocks of queries (`fused.banded_attention`): no [S, S]
                mask, no expanded KV heads
  decoding      one token a lane (no `block_length`): the logits at position
                i predict the token at i + 1, and GenerationEngine builds
                `decode_step`

Imported only by who uses it (`paddle_tpu.models.mellum`).
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field

import numpy as np

from ..ops import fused
from .sdar import SDARAttention, SDARBlock, SDARForCausalLM, SDARModel, rope

__all__ = ["MellumConfig", "MellumForCausalLM", "yarn_inv_freq"]


@dataclass
class MellumConfig:
    vocab_size: int = 98304
    hidden_size: int = 2304
    num_layers: int = 28
    num_heads: int = 32            # query heads
    num_kv_heads: int = 4
    head_dim: int = 128
    moe_intermediate_size: int = 896
    num_experts: int = 64
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 131072
    initializer_range: float = 0.02
    sliding_window: int = 1024
    # one entry a layer; () = (sliding x 3, full) repeated
    layer_types: tuple = ()
    rope_theta: float = 500000.0
    # the full layers' YaRN parameters (`rope_parameters.full_attention`)
    yarn: dict = field(default_factory=lambda: dict(
        factor=16.0, original_max_position_embeddings=8192, beta_fast=32.0,
        beta_slow=1.0, attention_factor=1.2772588722239782))

    def __post_init__(self):
        if not self.layer_types:
            self.layer_types = tuple(
                "full_attention" if i % 4 == 3 else "sliding_attention"
                for i in range(self.num_layers))
        self.layer_types = tuple(self.layer_types)
        bad = set(self.layer_types) - {"sliding_attention", "full_attention"}
        if bad or len(self.layer_types) != self.num_layers:
            raise ValueError(
                f"layer_types: {self.num_layers} entries of "
                f"sliding_attention | full_attention, got {self.layer_types}")

    @property
    def layer_windows(self) -> tuple:
        """The window of keys each layer sees, in tokens (0 = all): what
        GenerationEngine builds its page pools from."""
        return tuple(self.sliding_window if t == "sliding_attention" else 0
                     for t in self.layer_types)


def yarn_inv_freq(theta, head_dim, factor, original_max_position_embeddings,
                  beta_fast, beta_slow, **_):
    """YaRN's blended rotary frequencies [head_dim / 2] (float64): the
    dimensions that turn more than `beta_fast` times over the original
    context keep their frequency, those that turn fewer than `beta_slow`
    times are slowed by `factor`, a linear ramp between."""
    d = np.arange(head_dim // 2, dtype=np.float64)
    extra = theta ** (-2.0 * d / head_dim)

    def dim_of(turns):
        return head_dim * math.log(original_max_position_embeddings
                                   / (2 * math.pi * turns)) \
            / (2 * math.log(theta))

    low = max(math.floor(dim_of(beta_fast)), 0)
    high = min(math.ceil(dim_of(beta_slow)), head_dim - 1)
    ramp = np.clip((d - low) / max(high - low, 1e-3), 0.0, 1.0)
    return extra / factor * ramp + extra * (1.0 - ramp)


class MellumAttention(SDARAttention):
    """The shared attention with a layer kind: its window (0 = full) and
    its rotary law."""

    def __init__(self, cfg: MellumConfig, kind: str):
        super().__init__(cfg)
        self.window = cfg.sliding_window if kind == "sliding_attention" \
            else 0
        self._inv_freq = self._scale = None
        if kind == "full_attention" and cfg.yarn:
            self._inv_freq = yarn_inv_freq(
                cfg.rope_theta, cfg.head_dim, **cfg.yarn).astype(np.float32)
            self._scale = np.float32(cfg.yarn["attention_factor"])

    def rotary(self, x, positions):
        return rope(x, positions, self.cfg.rope_theta, self._inv_freq,
                    self._scale)

    def among(self, q, k, v):
        return fused.banded_attention(q, k, v, window=self.window)


class MellumModel(SDARModel):
    @staticmethod
    def block(cfg, i):
        return SDARBlock(cfg, MellumAttention(cfg, cfg.layer_types[i]))


class MellumForCausalLM(SDARForCausalLM):
    """The decoder with its untied head, speaking the serving protocol
    (`slot_prefill`, `slot_step`, `cfg`) as SDARForCausalLM does, one
    token a lane: `cfg` has no `block_length`."""
    backbone = MellumModel
