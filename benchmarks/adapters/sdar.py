"""The program's side of an SDAR-MoE configuration:
`paddle_tpu.models.sdar`, served by `GenerationEngine` in block mode.

Everything here imports the system under test; the reference
(`benchmarks/reference/sdar.py`) imports none of it.  The weights are the
benchmark's (made from the seed by the reference's `init_weights`) and are
handed to the program leaf by leaf under the program's own names.  What
is not specific to the model (the server, spans, fallbacks, freeing) is
the GPT adapter's.
"""
from __future__ import annotations

from benchmarks.adapters.gpt import (  # noqa: F401  (the adapter protocol)
    _default_dtype, build_server, finished_spans, free_server,
    pallas_fallbacks, slot_occupancy)
from benchmarks.adapters import gpt as _gpt

_LEAF = {"ln_1.g": "ln_1.weight", "q.w": "attn.q.weight",
         "k.w": "attn.k.weight", "v.w": "attn.v.weight",
         "q_norm.g": "attn.q_norm.weight", "k_norm.g": "attn.k_norm.weight",
         "o.w": "attn.out.weight", "ln_2.g": "ln_2.weight",
         "router.w": "moe.router.weight", "gate.w": "moe.w_gate",
         "up.w": "moe.w_up", "down.w": "moe.w_down"}

# the model's config key -> the program's SDARConfig field
_FIELDS = {"vocab_size": "vocab_size", "hidden_size": "hidden_size",
           "num_hidden_layers": "num_layers",
           "num_attention_heads": "num_heads",
           "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim",
           "moe_intermediate_size": "moe_intermediate_size",
           "num_experts": "num_experts",
           "num_experts_per_tok": "num_experts_per_tok",
           "norm_topk_prob": "norm_topk_prob",
           "rms_norm_eps": "rms_norm_eps", "rope_theta": "rope_theta",
           "max_position_embeddings": "max_position_embeddings",
           "initializer_range": "initializer_range",
           "block_length": "block_length",
           "denoising_steps": "denoising_steps",
           "mask_token_id": "mask_token_id",
           "remasking_strategy": "remasking_strategy",
           "confidence_threshold": "confidence_threshold"}

BLOCK_COUNTERS = ("block_steps", "block_lane_steps_denoised",
                  "block_lane_steps_committed", "block_tokens_emitted")


def program_name(ref_name: str) -> str:
    """The reference's leaf name -> the program's parameter name."""
    top = {"embed": "sdar.embed.weight", "head": "lm_head.weight",
           "norm_f.g": "sdar.norm_f.weight"}
    if ref_name in top:
        return top[ref_name]
    layer, leaf = ref_name.split(".", 1)
    return f"sdar.h_{layer[1:]}.{_LEAF[leaf]}"


def build_network(cfg: dict, weights: dict, dtype: str):
    """An `SDARForCausalLM` of the configuration holding `weights` (under
    the reference's names, already of `dtype`, on the device).  The
    constructor's own initial values are never drawn: at the published
    widths they would be a second copy of 8.7 GB."""
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.models.sdar import SDARConfig, SDARForCausalLM
    from paddle_tpu.nn import initializer as I

    scfg = SDARConfig(**{_FIELDS[k]: v for k, v in cfg.items()
                         if k in _FIELDS})
    want = {program_name(k): v for k, v in weights.items()}
    # every matrix of the model is drawn by I.Normal: a scalar stands in
    # for each until the benchmark's leaf takes its place below
    draw = I.Normal.generate
    I.Normal.generate = lambda self, shape, dt: jnp.zeros((), dt)
    try:
        with _default_dtype(paddle, dtype):
            net = SDARForCausalLM(scfg)
    finally:
        I.Normal.generate = draw
    params = dict(net.named_parameters())
    if set(want) != set(params):
        raise RuntimeError(
            "the program's parameters and the reference's leaves differ: "
            f"{sorted(set(want) ^ set(params))[:8]}")
    shapes = _param_shapes(scfg)
    for name, p in params.items():
        v = want[name]
        leaf = name.split(".", 2)[-1] if name.startswith("sdar.h_") else name
        if tuple(v.shape) != shapes[leaf]:
            raise RuntimeError(f"{name}: program {shapes[leaf]}, "
                               f"reference {tuple(v.shape)}")
        p._value = v
    return net


def _param_shapes(c):
    """The program's parameter shapes from its own config (the scalars
    standing in for the matrices cannot say them)."""
    H, hd, F, E = c.hidden_size, c.head_dim, c.moe_intermediate_size, \
        c.num_experts
    q, kv = c.num_heads * hd, c.num_kv_heads * hd
    return {"sdar.embed.weight": (c.vocab_size, H),
            "lm_head.weight": (H, c.vocab_size),
            "sdar.norm_f.weight": (H,), "ln_1.weight": (H,),
            "ln_2.weight": (H,), "attn.q.weight": (H, q),
            "attn.k.weight": (H, kv), "attn.v.weight": (H, kv),
            "attn.q_norm.weight": (hd,), "attn.k_norm.weight": (hd,),
            "attn.out.weight": (q, H), "moe.router.weight": (H, E),
            "moe.w_gate": (E, H, F), "moe.w_up": (E, H, F),
            "moe.w_down": (E, F, H)}


def engine_counters(engine):
    """The GPT adapter's counters and the block engine's: block steps,
    lane-steps by kind, tokens emitted, and the routed assignments the
    device counted, read from the engine's last published copy (a buffer
    of its own: the decode loop's state is never touched from here)."""
    out = _gpt.engine_counters(engine)
    snap = engine.metrics.snapshot()
    out.update({k: snap[k] for k in BLOCK_COUNTERS})
    out["block_lane_steps"] = (out["block_lane_steps_denoised"]
                               + out["block_lane_steps_committed"])
    stats = engine.expert_counts()
    counts = stats["assignments"]               # [layers, experts]
    out["moe_assignments"] = int(counts.sum())
    out["moe_experts_touched"] = int(stats["touched"].sum())
    for layer, row in enumerate(counts):
        for e, n in enumerate(row):
            out[f"moe_assignments.{layer}.{e}"] = int(n)
    return out
