"""The program's side of a Mellum configuration:
`paddle_tpu.models.mellum`, served by `GenerationEngine` one token a lane
over two page pools (full layers, window layers).

Everything here imports the system under test; the reference
(`benchmarks/reference/mellum.py`) imports none of it.  The weights are the
benchmark's (made from the seed by the reference's `init_weights`) and are
handed to the program leaf by leaf under the program's own names.  What
is not specific to the model (the server, spans, fallbacks, freeing) is
the GPT adapter's; the counters are the GPT adapter's, the routed
assignments as the SDAR adapter gives them, and the two pools' pages.
"""
from __future__ import annotations

from benchmarks.adapters.gpt import (  # noqa: F401  (the adapter protocol)
    _default_dtype, build_server, finished_spans, free_server,
    pallas_fallbacks, slot_occupancy)
from benchmarks.adapters import gpt as _gpt
# the decoder is models/sdar.py's block under its attribute names: the
# leaves' names and shapes are the SDAR adapter's
from benchmarks.adapters.sdar import _param_shapes, program_name  # noqa: F401

# the model's config key -> the program's MellumConfig field
_FIELDS = {"vocab_size": "vocab_size", "hidden_size": "hidden_size",
           "num_hidden_layers": "num_layers",
           "num_attention_heads": "num_heads",
           "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim",
           "moe_intermediate_size": "moe_intermediate_size",
           "num_experts": "num_experts",
           "num_experts_per_tok": "num_experts_per_tok",
           "norm_topk_prob": "norm_topk_prob",
           "rms_norm_eps": "rms_norm_eps",
           "max_position_embeddings": "max_position_embeddings",
           "initializer_range": "initializer_range",
           "sliding_window": "sliding_window", "layer_types": "layer_types"}


def program_config(cfg: dict):
    """The program's `MellumConfig` of a configuration file's `model`."""
    from paddle_tpu.models.mellum import MellumConfig

    rp = cfg["rope_parameters"]
    sliding, full = rp["sliding_attention"], rp["full_attention"]
    if sliding.get("rope_type", "default") != "default" \
            or full.get("rope_type") not in ("yarn", "default") \
            or sliding["rope_theta"] != full["rope_theta"]:
        raise RuntimeError(f"rotary laws the program has not: {rp}")
    yarn = {k: v for k, v in full.items()
            if k not in ("rope_type", "rope_theta")} \
        if full["rope_type"] == "yarn" else {}
    return MellumConfig(rope_theta=float(sliding["rope_theta"]), yarn=yarn,
                        **{_FIELDS[k]: v for k, v in cfg.items()
                           if k in _FIELDS})


def build_network(cfg: dict, weights: dict, dtype: str):
    """A `MellumForCausalLM` of the configuration holding `weights` (under
    the reference's names, already of `dtype`, on the device).  The
    constructor's own initial values are never drawn: at the published
    widths they would be a second copy of 7.6 GB."""
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.models.mellum import MellumForCausalLM
    from paddle_tpu.nn import initializer as I

    mcfg = program_config(cfg)
    want = {program_name(k): v for k, v in weights.items()}
    # every matrix of the model is drawn by I.Normal: a scalar stands in
    # for each until the benchmark's leaf takes its place below
    draw = I.Normal.generate
    I.Normal.generate = lambda self, shape, dt: jnp.zeros((), dt)
    try:
        with _default_dtype(paddle, dtype):
            net = MellumForCausalLM(mcfg)
    finally:
        I.Normal.generate = draw
    params = dict(net.named_parameters())
    if set(want) != set(params):
        raise RuntimeError(
            "the program's parameters and the reference's leaves differ: "
            f"{sorted(set(want) ^ set(params))[:8]}")
    shapes = _param_shapes(mcfg)
    for name, p in params.items():
        v = want[name]
        leaf = name.split(".", 2)[-1] if name.startswith("sdar.h_") else name
        if tuple(v.shape) != shapes[leaf]:
            raise RuntimeError(f"{name}: program {shapes[leaf]}, "
                               f"reference {tuple(v.shape)}")
        p._value = v
    return net


def engine_counters(engine):
    """The GPT adapter's counters; the routed assignments the device
    counted for live lanes over the decode steps, read from the engine's
    last published copy (a buffer of its own: the decode loop's state is
    never touched from here); the two pools' registers as the steps
    reported them: window pages let go behind the window, and the page
    table entries the live lanes held, summed over the steps."""
    out = _gpt.engine_counters(engine)
    stats = engine.expert_counts()
    counts = stats["assignments"]               # [layers, experts]
    out["moe_assignments"] = int(counts.sum())
    out["moe_experts_touched"] = int(stats["touched"].sum())
    for layer, row in enumerate(counts):
        for e, n in enumerate(row):
            out[f"moe_assignments.{layer}.{e}"] = int(n)
    snap = engine.metrics.snapshot()
    out["kv_window_pages_released"] = snap["kv_window_pages_released"]
    for pool, n in snap["kv_mapped_page_steps"].items():
        out[f"kv_mapped_page_steps.{pool}"] = n
    out["decode_steps"] = snap["steps"]
    return out
