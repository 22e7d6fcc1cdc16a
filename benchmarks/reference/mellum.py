"""Plain reference for Mellum decoders (JetBrains
Mellum2-12B-A2.5B-Instruct, `model_type` `mellum`): a Qwen3-MoE-shaped
block whose layers alternate between a sliding window and full attention,
each kind with its own rotary law.

Written from the published description (the model's `config.json` keys
and the rotary laws as `transformers` computes them from
`rope_parameters`).  Straightforward `jax.numpy` in float32 under
`jax.default_matmul_precision("highest")`: no kernel, no cache, no
batching.  It imports nothing of the program under test and nothing of
the other references: every function here is this file's own.

One layer (u = RMSNorm(x), no product has a bias):
  attention  q = W_q u as [nq, hd], k = W_k u, v = W_v u as [nkv, hd]; q
             and k pass an RMSNorm over the head with a learned gain, then
             rotary positions over the whole head (rotate-half); query
             head h reads KV head h // (nq / nkv); scores q.k / sqrt(hd);
             in a `sliding_attention` layer query i sees key j iff
             i - sliding_window < j <= i, in a `full_attention` layer iff
             j <= i; h = x + W_o ctx
  rotary     sliding layers: inv_freq_d = theta^(-2d/hd), cos and sin
             unscaled.  Full layers (YaRN): e_d = theta^(-2d/hd), i_d =
             e_d / factor; c(r) = hd ln(original / (2 pi r)) / (2 ln
             theta); low = max(floor(c(beta_fast)), 0), high = min(ceil(
             c(beta_slow)), hd - 1); ramp_d = clip((d - low) / (high -
             low), 0, 1); inv_freq_d = i_d ramp_d + e_d (1 - ramp_d); cos
             and sin both multiplied by `attention_factor`
  experts    p = softmax(W_r u) over E in float32; the k largest; weights
             p_e / sum of the k; y = h + sum_e w_e W_down,e
             (silu(W_gate,e u) * W_up,e u): a loop over the experts, every
             routed token computed, none dropped, no shared expert
  head       logits = W_head RMSNorm(x_L); the logits at position i
             predict the token at position i + 1

Attention runs in blocks of queries under dense [block, T] masks, and the
layers one after another, each upcast as it is used, so that the float32
copy of the published widths never lies on the device whole.

DEPARTURES from the published model, each for a stated reason:
  * q and k pass an RMSNorm over the head: not a key of the config; the
    block of the Qwen3-MoE lineage whose keys this config carries
    (`norm_topk_prob`, `moe_intermediate_size`, `use_sliding_window`,
    `max_window_layers`).  ASSUMED.
  * the catalog describes an MTP head; the config has no key for it and
    the forward pass does not use it.  LEFT OUT.
  * the weights are random from the seed (matrices N(0, initializer_range),
    gains 1 + N(0, initializer_range)), not the published ones.

`precision` selects how matrix products are computed: "f32" (float32
operands, `Precision.HIGHEST`: the reference proper), "bf16" (operands
rounded to bfloat16, float32 accumulation), "fp8" (operands rounded to
float8 e4m3 under one absmax scale a tensor, float32 accumulation, the
product rounded to bfloat16: the control of a bfloat16 configuration).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

LAYER_LEAVES = ("ln_1.g", "q.w", "k.w", "v.w", "q_norm.g", "k_norm.g", "o.w",
                "ln_2.g", "router.w", "gate.w", "up.w", "down.w")
TOP_LEAVES = ("embed", "head", "norm_f.g")
HI = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 512


def key_from_seed(seed: int):
    """A PRNG key from any non-negative whole number (seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31)),
                              seed // (2 ** 31))


def leaf_shapes(cfg):
    """{name: (shape, kind)} of the top leaves and of ONE layer's leaves
    (under their bare names); kind is "matrix" or "gain"."""
    V, H = cfg["vocab_size"], cfg["hidden_size"]
    nq, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    E, F = cfg["num_experts"], cfg["moe_intermediate_size"]
    return {"embed": ((V, H), "matrix"), "head": ((H, V), "matrix"),
            "norm_f.g": ((H,), "gain"),
            "ln_1.g": ((H,), "gain"), "q.w": ((H, nq * hd), "matrix"),
            "k.w": ((H, nkv * hd), "matrix"),
            "v.w": ((H, nkv * hd), "matrix"),
            "q_norm.g": ((hd,), "gain"), "k_norm.g": ((hd,), "gain"),
            "o.w": ((nq * hd, H), "matrix"), "ln_2.g": ((H,), "gain"),
            "router.w": ((H, E), "matrix"), "gate.w": ((E, H, F), "matrix"),
            "up.w": ((E, H, F), "matrix"), "down.w": ((E, F, H), "matrix")}


def init_weights(key, cfg, dtype=jnp.float32):
    """Every leaf from one key: matrices N(0, initializer_range), gains
    1 + N(0, initializer_range), drawn in float32 and rounded to `dtype`.
    Layer i's leaves are `h{i}.<leaf>`."""
    shapes, std = leaf_shapes(cfg), cfg.get("initializer_range", 0.02)

    def draw(k, shape, kind):
        x = std * jax.random.normal(k, shape, jnp.float32)
        return ((1.0 + x) if kind == "gain" else x).astype(dtype)

    out = {n: draw(jax.random.fold_in(key, i), *shapes[n])
           for i, n in enumerate(TOP_LEAVES)}
    for i in range(cfg["num_hidden_layers"]):
        lk = jax.random.fold_in(key, 1000 + i)
        out.update({f"h{i}.{n}": draw(jax.random.fold_in(lk, j), *shapes[n])
                    for j, n in enumerate(LAYER_LEAVES)})
    return out


def _fp8(x):
    scale = jnp.max(jnp.abs(x)) / 448.0          # e4m3's largest finite
    scale = jnp.where(scale == 0, 1.0, scale)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def matmul(a, b, precision):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if precision == "f32":
        return jnp.matmul(a, b, precision=HI)
    if precision == "bf16":
        return jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    if precision == "fp8":
        return jnp.matmul(_fp8(a), _fp8(b), precision=HI) \
            .astype(jnp.bfloat16).astype(jnp.float32)
    raise ValueError(f"unknown precision {precision!r}")


def rms_norm(x, g, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(jnp.float32)


def rotary_law(params, hd):
    """(inv_freq [hd / 2], scale of cos and sin) of one kind of layer from
    its `rope_parameters` entry."""
    theta = float(params["rope_theta"])
    d = jnp.arange(hd // 2, dtype=jnp.float32)
    extra = theta ** (-2.0 * d / hd)
    if params.get("rope_type", "default") == "default":
        return extra, 1.0
    if params["rope_type"] != "yarn":
        raise ValueError(f"unknown rope_type {params['rope_type']!r}")
    inter = extra / params["factor"]
    orig = params["original_max_position_embeddings"]

    def dim_of(turns):
        return hd * math.log(orig / (2 * math.pi * turns)) \
            / (2 * math.log(theta))

    low = max(math.floor(dim_of(params["beta_fast"])), 0)
    high = min(math.ceil(dim_of(params["beta_slow"])), hd - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((d - low) / (high - low), 0.0, 1.0)
    scale = params.get("attention_factor")
    if scale is None:
        scale = 0.1 * math.log(params["factor"]) + 1.0
    return inter * ramp + extra * (1.0 - ramp), float(scale)


def rope(x, positions, law):
    """Rotary positions over the whole head, rotate-half: x [T, n, hd]."""
    inv, scale = law
    hd = x.shape[-1]
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos = scale * jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = scale * jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def attention(x, w, positions, kind, cfg, precision):
    T = x.shape[0]
    nq, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    law = rotary_law(cfg["rope_parameters"][kind], hd)
    window = cfg["sliding_window"] if kind == "sliding_attention" else None
    u = rms_norm(x, w["ln_1.g"], eps)
    q = matmul(u, w["q.w"], precision).reshape(T, nq, hd)
    k = matmul(u, w["k.w"], precision).reshape(T, nkv, hd)
    v = matmul(u, w["v.w"], precision).reshape(T, nkv, hd)
    q = rope(rms_norm(q, w["q_norm.g"], eps), positions, law)
    k = rope(rms_norm(k, w["k_norm.g"], eps), positions, law)
    group = nq // nkv                      # query head h reads KV head h // g
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    j = jnp.arange(T)[None, :]

    # queries in blocks (the whole sequence where it does not divide)
    n = QUERY_BLOCK if T % QUERY_BLOCK == 0 else T

    def rows(i0):
        """Queries [i0, i0 + n) under their dense [n, T] mask."""
        qb = jax.lax.dynamic_slice_in_dim(q, i0, n, 0)
        i = i0 + jnp.arange(n)[:, None]
        mask = j <= i
        if window is not None:
            mask = mask & (j > i - window)
        s = jnp.einsum("qnd,knd->nqk", qb, k, precision=HI) / math.sqrt(hd)
        s = jnp.where(mask[None], s, -jnp.inf)
        return jnp.einsum("nqk,knd->qnd", jax.nn.softmax(s, -1), v,
                          precision=HI)

    ctx = jax.lax.map(rows, jnp.arange(0, T, n)).reshape(T, nq, hd)
    return x + matmul(ctx.reshape(T, nq * hd), w["o.w"], precision)


def route(u, w, cfg, precision):
    """[T, E] float32: expert e's weight for each token, 0 where e is not
    among the token's k largest."""
    k = cfg["num_experts_per_tok"]
    p = jax.nn.softmax(matmul(u, w["router.w"], precision), -1)
    top, idx = jax.lax.top_k(p, k)
    if cfg.get("norm_topk_prob", True):
        top = top / top.sum(-1, keepdims=True)
    return jnp.zeros_like(p).at[jnp.arange(u.shape[0])[:, None], idx].set(top)


def experts(h, w, cfg, precision):
    """h + the routed experts' outputs: a loop over the experts, each
    computed for every token and weighted (0 for the tokens not routed to
    it), so no token is dropped and nothing is gathered."""
    u = rms_norm(h, w["ln_2.g"], cfg["rms_norm_eps"])
    weight = route(u, w, cfg, precision)

    def one(y, leaves):
        gate, up, down, we = leaves
        a = jax.nn.silu(matmul(u, gate, precision)) * matmul(u, up, precision)
        return y + we[:, None] * matmul(a, down, precision), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h),
                        (w["gate.w"], w["up.w"], w["down.w"], weight.T))
    return h + y


def layer(x, w, positions, kind, cfg, precision="f32"):
    """One decoder layer of `kind` on x [T, H] float32 at `positions` [T];
    `w` holds the layer's leaves under their bare names."""
    return experts(attention(x, w, positions, kind, cfg, precision), w, cfg,
                   precision)


def logits_at(w, ids, read, cfg, precision="f32"):
    """Logits [len(read), V] of one sequence ids [T] at the rows `read`
    (each the distribution over the NEXT token), positions 0..T-1."""
    with jax.default_matmul_precision("highest"):
        T = ids.shape[0]
        positions = jnp.arange(T)
        x = w["embed"].astype(jnp.float32)[ids]
        for i in range(cfg["num_hidden_layers"]):
            wl = {n: w[f"h{i}.{n}"] for n in LAYER_LEAVES}
            x = layer(x, wl, positions, cfg["layer_types"][i], cfg, precision)
        return matmul(rms_norm(x[read], w["norm_f.g"], cfg["rms_norm_eps"]),
                      w["head"], precision)
