"""GPT with explicit 3D hybrid parallelism: dp x pp x mp in ONE SPMD program.

Workload parity: SURVEY.md section 6 (GPT-3 1.3B, TP+PP+DP — the reference
composes fleet meta-optimizers PipelineOptimizer + split() TP + DP rings,
SURVEY.md §2.10).  TPU-native equivalent: a single shard_map over a
(dp, pp, mp) mesh combining
  * dp  — microbatch dim sharded; gradient psum falls out of shard_map AD
  * pp  — GPipe schedule from distributed/pipeline.spmd_pipeline
          (ppermute activation hops ≙ send_v2/recv_v2)
  * mp  — Megatron tensor parallel, hand-written collectives: column-sharded
          qkv/fc1, row-sharded out/fc2 with psum ≙ c_allreduce_sum
          (collective.py:516), vocab-parallel embedding + cross entropy
          (shard_index masking ≙ collective.py:526 _parallel_embedding)

The loss is pmean'd over ALL mesh axes, which makes both the value and every
gradient correct without post-hoc rescaling (replicated uses are averaged,
psum-mixed uses chain through).  Everything here is functional (pytree
params), sized by GPTConfig; `make_init` + `make_loss_fn` are the public
surface, composed with any optimizer's apply_pytree.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from .gpt import GPTConfig
from ..distributed.pipeline import PipelineProgram, pipeline_loss_fn

__all__ = ["init_params", "param_specs", "make_loss_fn", "make_train_step",
           "pipeline_program", "GPTPipelineProgram"]


def _check(cfg: GPTConfig, pp: int, mp: int):
    if cfg.num_layers % pp:
        raise ValueError(f"num_layers {cfg.num_layers} % pp {pp} != 0")
    if cfg.num_heads % mp or cfg.ffn_size % mp or cfg.vocab_size % mp:
        raise ValueError("num_heads, ffn_size and vocab_size must divide mp")


def init_params(cfg: GPTConfig, pp: int, seed=0, dtype=jnp.float32):
    """Global (unsharded) parameter pytree; blocks stacked [pp, Lp, ...]."""
    rs = np.random.RandomState(seed)
    D, F, V = cfg.hidden_size, cfg.ffn_size, cfg.vocab_size
    Lp = cfg.num_layers // pp
    sd = cfg.initializer_range

    def n(*shape):
        return jnp.asarray(rs.randn(*shape) * sd, dtype)

    def z(*shape):
        return jnp.zeros(shape, dtype)

    def o(*shape):
        return jnp.ones(shape, dtype)

    return {
        "wte": n(V, D),
        "wpe": n(cfg.max_position_embeddings, D),
        "ln_f_w": o(D), "ln_f_b": z(D),
        "blocks": {
            "ln1_w": o(pp, Lp, D), "ln1_b": z(pp, Lp, D),
            "wqkv": n(pp, Lp, D, 3 * D), "bqkv": z(pp, Lp, 3 * D),
            "wo": n(pp, Lp, D, D), "bo": z(pp, Lp, D),
            "ln2_w": o(pp, Lp, D), "ln2_b": z(pp, Lp, D),
            "w1": n(pp, Lp, D, F), "b1": z(pp, Lp, F),
            "w2": n(pp, Lp, F, D), "b2": z(pp, Lp, D),
        },
    }


def param_specs(cfg: GPTConfig | None = None):
    """PartitionSpec pytree matching init_params' structure."""
    b = lambda *rest: P("pp", None, *rest)  # noqa: E731
    return {
        "wte": P("mp", None),
        "wpe": P(),
        "ln_f_w": P(), "ln_f_b": P(),
        "blocks": {
            "ln1_w": b(None), "ln1_b": b(None),
            "wqkv": b(None, "mp"), "bqkv": b("mp"),
            "wo": b("mp", None), "bo": b(None),
            "ln2_w": b(None), "ln2_b": b(None),
            "w1": b(None, "mp"), "b1": b("mp"),
            "w2": b("mp", None), "b2": b(None),
        },
    }


def _ln(x, w, b, eps):
    xf = x.astype(jnp.float32)
    mu = xf.mean(-1, keepdims=True)
    var = ((xf - mu) ** 2).mean(-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps) * w + b).astype(x.dtype)


def _causal_attn(q, k, v):
    # [mb, S, h, d] local heads, f32 accumulation
    d = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / (d ** 0.5)
    S = q.shape[1]
    iq = jax.lax.broadcasted_iota(jnp.int32, (S, S), 0)
    ik = jax.lax.broadcasted_iota(jnp.int32, (S, S), 1)
    s = jnp.where(iq >= ik, s, -1e30)
    p = jax.nn.softmax(s, -1)
    return jnp.einsum("bhqk,bkhd->bqhd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


def _make_block(cfg: GPTConfig, mp: int):
    eps = cfg.layer_norm_epsilon
    h_local = cfg.num_heads // mp

    def block(p, x):
        # attention (column qkv, row out + psum over mp).  wqkv columns are
        # HEAD-MAJOR ([D, H, 3, hd] flattened) so an mp shard holds whole
        # heads' q,k,v — the Megatron qkv layout; a naive [3D] split would
        # hand shard 0 all of q plus part of k.
        h = _ln(x, p["ln1_w"], p["ln1_b"], eps)
        qkv = h @ p["wqkv"] + p["bqkv"]              # [mb, S, 3D/mp]
        mb, S = qkv.shape[0], qkv.shape[1]
        hd = cfg.hidden_size // cfg.num_heads
        qkv = qkv.reshape(mb, S, h_local, 3, hd)
        ctx = _causal_attn(qkv[..., 0, :], qkv[..., 1, :], qkv[..., 2, :])
        ctx = ctx.reshape(mb, S, h_local * hd)
        attn = jax.lax.psum(ctx @ p["wo"], "mp") + p["bo"]
        x = x + attn
        # mlp (column fc1, row fc2 + psum)
        h2 = _ln(x, p["ln2_w"], p["ln2_b"], eps)
        u = jax.nn.gelu(h2 @ p["w1"] + p["b1"])
        x = x + jax.lax.psum(u @ p["w2"], "mp") + p["b2"]
        return x

    return block


def _vocab_parallel_embed(ids, wte_local, v_local):
    idx = jax.lax.axis_index("mp")
    local = ids - idx * v_local
    ok = (local >= 0) & (local < v_local)
    emb = jnp.take(wte_local, jnp.clip(local, 0, v_local - 1), axis=0)
    emb = jnp.where(ok[..., None], emb, 0.0)
    return jax.lax.psum(emb, "mp")


def _vocab_parallel_xent(h, wte_local, labels, v_local):
    """softmax cross entropy over mp-sharded logits (never materializes the
    full vocab on one device — the Megatron parallel_cross_entropy)."""
    z = jnp.einsum("bsd,vd->bsv", h.astype(jnp.float32),
                   wte_local.astype(jnp.float32))
    # stabilizer only — exact to stop-gradient (cancels between exp and log)
    m = jax.lax.pmax(jax.lax.stop_gradient(z.max(-1)), "mp")
    l = jax.lax.psum(jnp.exp(z - m[..., None]).sum(-1), "mp")
    log_z = m + jnp.log(l)
    idx = jax.lax.axis_index("mp")
    local = labels - idx * v_local
    ok = (local >= 0) & (local < v_local)
    picked = jnp.take_along_axis(
        z, jnp.clip(local, 0, v_local - 1)[..., None], -1)[..., 0]
    picked = jax.lax.psum(jnp.where(ok, picked, 0.0), "mp")
    return log_z - picked


class GPTPipelineProgram(PipelineProgram):
    """gpt_hybrid's stage structure as a fleet-consumable PipelineProgram
    (strategy.pipeline pp_degree routes it through spmd_pipeline — the
    Fleet-entrypoint equivalent of fluid.PipelineOptimizer optimizer.py:3702)."""

    stage_key = "blocks"

    def __init__(self, cfg: GPTConfig, mp: int):
        self.cfg = cfg
        self.mp = mp
        self._block = _make_block(cfg, mp)
        self._v_local = cfg.vocab_size // mp

    def embed(self, params, ids):
        S = ids.shape[-1]
        return (_vocab_parallel_embed(ids, params["wte"], self._v_local)
                + params["wpe"][:S])

    def stage(self, p_stage, a):
        out, _ = jax.lax.scan(lambda act, pl: (self._block(pl, act), None),
                              a, p_stage)
        return out

    def head(self, params, out, ids):
        cfg = self.cfg
        S = ids.shape[-1]
        h = _ln(out, params["ln_f_w"], params["ln_f_b"],
                cfg.layer_norm_epsilon)
        losses = _vocab_parallel_xent(
            h.reshape((-1,) + h.shape[2:])[:, :-1], params["wte"],
            ids.reshape(-1, S)[:, 1:], self._v_local)
        return losses.mean()

    def param_specs(self):
        return param_specs(self.cfg)


def pipeline_program(cfg: GPTConfig, mesh) -> GPTPipelineProgram:
    pp, mp = mesh.shape["pp"], mesh.shape["mp"]
    _check(cfg, pp, mp)
    return GPTPipelineProgram(cfg, mp)


def make_loss_fn(cfg: GPTConfig, mesh, n_microbatches: int, remat=True):
    """Jittable (params, ids[M*mb_global, S]) -> scalar LM loss over the
    (dp, pp, mp) mesh.  Implemented via the shared PipelineProgram path so
    the Fleet strategy.pipeline entrypoint is numerically identical."""
    return pipeline_loss_fn(pipeline_program(cfg, mesh), mesh,
                            n_microbatches, remat=remat)


def _flatten(tree):
    """Nested pytree -> flat {dotted.path: leaf} (optimizer-compatible)."""
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {".".join(str(getattr(k, "key", k)) for k in path): v
            for path, v in leaves}


def make_train_step(cfg: GPTConfig, mesh, optimizer, n_microbatches: int,
                    lr=1e-4, remat=True):
    """Full jitted train step: loss + grads + optimizer update, all sharded.

    Returns (step_fn, init_opt_state_fn, shardings) where
    step_fn(params, opt_state, ids) -> (new_params, new_opt_state, loss) and
    shardings = (param_shardings, opt_state_shardings, data_sharding) —
    optimizer moments inherit their parameter's (pp, mp) placement, the
    ZeRO-free hybrid baseline (compose with sharding.zero_shardings for
    dp-sharded optimizer state).
    """
    loss_fn = make_loss_fn(cfg, mesh, n_microbatches, remat=remat)
    specs = param_specs(cfg)
    p_shard = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                           is_leaf=lambda x: isinstance(x, P))
    treedef = jax.tree_util.tree_structure(specs,
                                           is_leaf=lambda x: isinstance(x, P))

    def init_opt_state(params):
        state = optimizer.init_pytree(_flatten(params))
        state["__step__"] = jnp.zeros((), jnp.int32)  # Adam bias-correction t
        return state

    def step(params, opt_state, ids):
        t = opt_state["__step__"] + 1
        slots = {k: v for k, v in opt_state.items() if k != "__step__"}
        loss, grads = jax.value_and_grad(loss_fn)(params, ids)
        flat_p, flat_g = _flatten(params), _flatten(grads)
        new_flat, new_state = optimizer.apply_pytree(flat_p, flat_g,
                                                     slots, lr=lr, step=t)
        new_state["__step__"] = t
        new_params = jax.tree_util.tree_unflatten(
            treedef, [new_flat[k] for k in sorted(new_flat)])
        return new_params, new_state, loss

    flat_shard = _flatten(p_shard)
    s_shard = {k: {n: flat_shard[k] for n in optimizer._slot_names()}
               for k in flat_shard}
    s_shard["__step__"] = NamedSharding(mesh, P())
    data_shard = NamedSharding(mesh, P("dp"))
    return jax.jit(step), init_opt_state, (p_shard, s_shard, data_shard)
