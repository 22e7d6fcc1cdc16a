"""The program's side of a Granite 4.0-H configuration:
`paddle_tpu.models.granite_hybrid`, served by `GenerationEngine` one token
a lane over the pages of its attention layers and the recurrent state of
its Mamba layers.

Everything here imports the system under test; the reference
(`benchmarks/reference/granite_hybrid.py`) imports none of it.  The weights
are the benchmark's (made from the seed by the reference's `init_weights`)
and are handed to the program leaf by leaf under the program's own names.
What is not specific to the model (the server, spans, fallbacks, freeing,
the counters every engine has) is the GPT adapter's; the state's registers
are added here.
"""
from __future__ import annotations

# a program that has no such model cannot run the configuration: it says so
# here, as the adapter is found, before any weight is drawn
from paddle_tpu.models import granite_hybrid as _program  # noqa: F401

from benchmarks.adapters.gpt import (  # noqa: F401  (the adapter protocol)
    _default_dtype, build_server, finished_spans, pallas_fallbacks,
    slot_occupancy)
from benchmarks.adapters import gpt as _gpt

# the model's config key -> the program's GraniteHybridConfig field
_FIELDS = {"vocab_size": "vocab_size", "hidden_size": "hidden_size",
           "num_hidden_layers": "num_layers",
           "num_attention_heads": "num_heads",
           "num_key_value_heads": "kv_heads",
           "intermediate_size": "intermediate_size",
           "layer_types": "layer_types", "mamba_n_heads": "mamba_n_heads",
           "mamba_d_head": "mamba_d_head", "mamba_d_state": "mamba_d_state",
           "mamba_d_conv": "mamba_d_conv",
           "mamba_chunk_size": "mamba_chunk_size",
           "attention_multiplier": "attention_multiplier",
           "embedding_multiplier": "embedding_multiplier",
           "residual_multiplier": "residual_multiplier",
           "logits_scaling": "logits_scaling", "rms_norm_eps": "rms_norm_eps",
           "max_position_embeddings": "max_position_embeddings",
           "initializer_range": "initializer_range"}

_LEAF = {"ln_1.g": "ln_1.weight", "ln_2.g": "ln_2.weight",
         "ffn_in.w": "ffn.w_in.weight", "ffn_out.w": "ffn.w_out.weight",
         "q.w": "mixer.q.weight", "k.w": "mixer.k.weight",
         "v.w": "mixer.v.weight", "o.w": "mixer.out.weight",
         "in.w": "mixer.in_proj.weight", "conv.w": "mixer.conv_weight",
         "conv.b": "mixer.conv_bias", "dt_bias": "mixer.dt_bias",
         "A_log": "mixer.A_log", "D": "mixer.D",
         "norm.g": "mixer.norm.weight", "out.w": "mixer.out_proj.weight"}


def program_name(ref_name: str) -> str:
    """The reference's leaf name -> the program's parameter name."""
    top = {"embed": "granite.embed.weight",
           "norm_f.g": "granite.norm_f.weight"}
    if ref_name in top:
        return top[ref_name]
    layer, leaf = ref_name.split(".", 1)
    return f"granite.h_{layer[1:]}.{_LEAF[leaf]}"


def program_config(cfg: dict):
    """The program's `GraniteHybridConfig` of a configuration file's
    `model`."""
    from paddle_tpu.models.granite_hybrid import GraniteHybridConfig

    if cfg.get("mamba_n_groups", 1) != 1 or cfg.get(
            "position_embedding_type", "nope") != "nope" \
            or not cfg.get("tie_word_embeddings", True):
        raise RuntimeError("the program serves one group of B and C, no "
                           f"positions and a tied head: {cfg}")
    return GraniteHybridConfig(**{_FIELDS[k]: v for k, v in cfg.items()
                                  if k in _FIELDS})


def build_network(cfg: dict, weights: dict, dtype: str):
    """A `GraniteHybridForCausalLM` of the configuration holding `weights`
    (under the reference's names, already of `dtype`, on the device).  The
    constructor's own matrices are never drawn: at the published widths
    they would be a second copy of 6.4 GB."""
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.models.granite_hybrid import GraniteHybridForCausalLM
    from paddle_tpu.nn import initializer as I

    gcfg = program_config(cfg)
    want = {program_name(k): v for k, v in weights.items()}
    # a scalar stands in for each drawn leaf until the benchmark's takes
    # its place below
    draws = [(c, c.generate) for c in (I.Normal, I.Uniform)]
    for c, _ in draws:
        c.generate = lambda self, shape, dt: jnp.zeros((), dt)
    try:
        with _default_dtype(paddle, dtype):
            net = GraniteHybridForCausalLM(gcfg)
    finally:
        for c, g in draws:
            c.generate = g
    params = dict(net.named_parameters())
    if set(want) != set(params):
        raise RuntimeError(
            "the program's parameters and the reference's leaves differ: "
            f"{sorted(set(want) ^ set(params))[:8]}")
    for name, p in params.items():
        v = want[name]
        if p.shape and tuple(p.shape) != tuple(v.shape):
            raise RuntimeError(f"{name}: program {p.shape}, "
                               f"reference {v.shape}")
        p._value = v
    return net


def engine_counters(engine):
    """The GPT adapter's counters and the state's registers: snapshots
    restored, taken and evicted, the live lanes summed over the decode
    steps (what the one-token update's cost counts), the true (unpadded)
    tokens the prompt passes scanned and the passes themselves."""
    out = _gpt.engine_counters(engine)
    snap = engine.metrics.snapshot()
    for k in ("state_restores", "state_snapshots",
              "state_snapshot_evictions", "state_lane_steps",
              "state_scan_tokens", "state_scans"):
        out[k] = snap[k]
    out["decode_steps"] = snap["steps"]
    return out


_HELD = []


def held_states(engine, most: int = 8):
    """What up to `most` of the drained engine's slots still hold, on the
    host: for each the position and the token of the lane that lived there
    last (after a lane's last step: its prompt and all but the last of its
    tokens lie in the state, the last token in `tok`), and its recurrent
    state in the reference's layout: H [state layers, heads, d_head,
    d_state] and the convolutions' tails [state layers, d_conv - 1,
    conv_dim], float32.  A release leaves a slot's state as it is."""
    import numpy as np

    from paddle_tpu.ops import fused

    st, geom = engine._state, engine.geometry
    pos, tok = np.asarray(st["pos"]), np.asarray(st["tok"])
    used = np.flatnonzero(pos > 0)
    if not len(used):
        return []
    pick = used[np.unique(np.linspace(0, len(used) - 1, min(most, len(used)))
                          .astype(int))]
    out = []
    for s in pick:
        h = fused.ssm_unpack_state(st["ssm"][:, s], geom.state_pack)
        tail = st["conv"][:, s].reshape((-1,) + tuple(geom.conv_shape))
        out.append({"slot": int(s), "pos": int(pos[s]), "tok": int(tok[s]),
                    "ssm": np.asarray(h, np.float32),
                    "conv": np.asarray(tail, np.float32)})
    return out


def free_server(server, engine, net):
    """The GPT adapter's, after a look at what the slots hold (kind
    `serve_state` compares it with the reference's scan)."""
    _HELD[:] = held_states(engine)
    _gpt.free_server(server, engine, net)


def take_held():
    """What `free_server` saw, handed over once."""
    out = list(_HELD)
    del _HELD[:]
    return out
