"""The program's side of a GPT configuration: `paddle_tpu.models.gpt`.

Everything here imports the system under test; the reference
(`benchmarks/reference/gpt.py`) imports none of it.  The weights are the
benchmark's (made from the seed by the reference's `init_weights`) and are
handed to the program leaf by leaf under the program's own names.
"""
from __future__ import annotations

import contextlib

CONFIG_KEYS = ("vocab_size", "hidden_size", "num_layers", "num_heads",
               "ffn_hidden_size", "max_position_embeddings",
               "layer_norm_epsilon", "tie_word_embeddings")

_LEAF = {"ln_1.g": "ln_1.weight", "ln_1.b": "ln_1.bias",
         "qkv.w": "attn.qkv.weight", "qkv.b": "attn.qkv.bias",
         "proj.w": "attn.out.weight", "proj.b": "attn.out.bias",
         "ln_2.g": "ln_2.weight", "ln_2.b": "ln_2.bias",
         "fc1.w": "mlp.fc1.weight", "fc1.b": "mlp.fc1.bias",
         "fc2.w": "mlp.fc2.weight", "fc2.b": "mlp.fc2.bias"}


def program_name(ref_name: str) -> str:
    """The reference's leaf name -> the program's parameter name."""
    top = {"wte": "gpt.wte.weight", "wpe": "gpt.wpe.weight",
           "ln_f.g": "gpt.ln_f.weight", "ln_f.b": "gpt.ln_f.bias"}
    if ref_name in top:
        return top[ref_name]
    layer, leaf = ref_name.split(".", 1)
    return f"gpt.h_{layer[1:]}.{_LEAF[leaf]}"


@contextlib.contextmanager
def _default_dtype(paddle, dtype):
    was = paddle.get_default_dtype()
    paddle.set_default_dtype(dtype)
    try:
        yield
    finally:
        paddle.set_default_dtype(was)


def build_network(cfg: dict, weights: dict, dtype: str):
    """A `GPTForCausalLM` of the configuration holding `weights` (a dict
    under the reference's names, already of `dtype`, on the device)."""
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    gcfg = GPTConfig(dropout=0.0, attn_dropout=0.0,
                     **{k: cfg[k] for k in CONFIG_KEYS})
    # the constructor draws its own initial values leaf by leaf; in the
    # served dtype they are half the bytes, and each is dropped as the
    # benchmark's leaf takes its place
    with _default_dtype(paddle, dtype):
        net = GPTForCausalLM(gcfg)
    params = dict(net.named_parameters())
    want = {program_name(k): v for k, v in weights.items()}
    if set(want) != set(params):
        raise RuntimeError(
            "the program's parameters and the reference's leaves differ: "
            f"{sorted(set(want) ^ set(params))[:8]}")
    for name, p in params.items():
        v = want[name]
        if tuple(p.shape) != tuple(v.shape):
            raise RuntimeError(f"{name}: program {p.shape}, "
                               f"reference {v.shape}")
        p._value = v
    return net


def build_trainer(net, opt: dict):
    """`paddle.Model` prepared with AdamW and cross-entropy over logits."""
    import paddle_tpu as paddle

    model = paddle.Model(net)
    model.prepare(
        paddle.optimizer.AdamW(
            learning_rate=opt["learning_rate"], beta1=opt["beta1"],
            beta2=opt["beta2"], epsilon=opt["epsilon"],
            weight_decay=opt["weight_decay"], parameters=net.parameters()),
        paddle.nn.CrossEntropyLoss())
    return model


def engine_state(model):
    """The live device state of `Model.fit`'s engine (trainable
    parameters and Adam's first moment, under the program's names).
    Internals of hapi/engine.py: the check reads them because `fit` has
    no public view of its state between steps."""
    st = model._engine.state
    return st["trainable"], st["opt"]


def pending_loss(model):
    """The newest loss `fit` has not fetched yet (a device scalar), or
    None right after a fetch."""
    pend = model._engine.ring._pending
    return pend[-1] if pend else None


def first_moment(opt_state):
    """Adam's first moment per parameter, under the program's names."""
    return {name: slots["moment1"] for name, slots in opt_state.items()}


def free_trainer(model):
    """Free the device memory `fit` left behind (engine state, the
    network's parameters, optimizer slots) before the reference runs."""
    _delete([getattr(model._engine, "state", None),
             getattr(model, "_opt_state", None),
             [p._value for p in model.network.parameters()]])


def _delete(trees):
    import jax

    for leaf in jax.tree_util.tree_leaves(trees):
        if hasattr(leaf, "delete") and not leaf.is_deleted():
            leaf.delete()


def build_server(net, geometry: dict, trace_spans: bool):
    """`ServingServer` over a `GenerationEngine` of the traffic file's
    geometry (not started).  With `trace_spans` the program's tracer
    keeps every request's spans (it samples 1% by default)."""
    import paddle_tpu as paddle
    from paddle_tpu.serving import GenerationEngine, ServingServer

    if trace_spans:
        paddle.set_flags({"FLAGS_trace_sample_rate": 1.0,
                          "FLAGS_trace_buffer_spans": 1 << 17})
    net.eval()
    engine = GenerationEngine(
        net, max_slots=geometry["max_slots"],
        max_seq_len=geometry["max_seq_len"],
        prompt_buckets=geometry["prompt_buckets"],
        page_size=geometry["page_size"],
        prefix_cache=geometry["prefix_cache"])
    server = ServingServer(None, gen_engine=engine, port=0,
                           install_signal_handlers=False)
    return server, engine


def engine_counters(engine):
    snap = engine.metrics.snapshot()
    keep = ("prefix_cache_hits", "prefix_cache_misses", "admitted",
            "retired", "errors", "preempted", "rejected_queue_full",
            "compile_count")
    out = {k: snap[k] for k in keep}
    out["tokens_total"] = engine.metrics._tokens.value
    return out


def slot_occupancy(engine):
    return engine.metrics.snapshot()["slot_occupancy"]


def finished_spans():
    from paddle_tpu.monitor import tracing

    return tracing.default_tracer().spans()


def pallas_fallbacks():
    from paddle_tpu.ops import fused

    return sum(fused.fallback_counter().values.values())


def free_server(server, engine, net):
    """Free the engine's device state and the weights (the engine's
    introspection hooks keep references, so the arrays are deleted)."""
    _delete([engine._state, engine._params, engine._buffers,
             [p._value for p in net.parameters()]])
