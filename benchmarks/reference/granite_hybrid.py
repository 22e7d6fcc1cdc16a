"""Plain reference for Granite 4.0-H decoders (IBM granite-4.0-h-micro,
`model_type` `granitemoehybrid`): Mamba-2 layers and, one layer in ten,
attention without positions, every layer followed by a dense gated FFN.

Written from the published description (the model's `config.json` keys).
Straightforward `jax.numpy` in float32 under
`jax.default_matmul_precision("highest")`: the recurrence is a plain
`lax.scan` over tokens (no chunks), no kernel, no cache, no batching.  It
imports nothing of the program under test and nothing of the other
references: every function here is this file's own.

With h the hidden state, eps `rms_norm_eps`, no bias but the convolution's:
  x = embedding_multiplier * E[token]
  each layer: x = x + residual_multiplier * Mixer(RMSNorm(x))
              x = x + residual_multiplier * FFN(RMSNorm(x))
  logits = RMSNorm(x) @ E.T / logits_scaling            (tied head)
  FFN        [g, u] = split(W_in h) (H -> 2F); W_out (silu(g) * u)
  attention  (`layer_types[i] == "attention"`) nq query heads over nkv KV
             heads of hd, query head h reading KV head h // (nq / nkv); no
             rotary or other positions (`position_embedding_type` nope);
             scores attention_multiplier * q k^T, causal softmax
  Mamba-2    (`"mamba"`; d_inner = n_heads * d_head, one group, d_state N)
             [z, xBC, dt] = split(W_in h) of d_inner, d_inner + 2N, n_heads
             xBC_t = silu(b + sum_{j<K} w[:, j] * xBC_{t-K+1+j})   (depthwise
                     causal convolution of width K = d_conv, zeros before 0)
             [x, B, C] = split(xBC) of d_inner, N, N
             D_t = softplus(dt_t + dt_bias) a head (no clamp),
             A = -exp(A_log)
             a head's state H_t = exp(D_t A) H_{t-1} + D_t * x_t B_t^T
                                                              ([d_head, N])
             y_t = H_t C_t + D * x_t
             y = RMSNorm(y * silu(z)) over all d_inner, learned gain
             out = W_out y
The logits at position i predict the token at i + 1.

Attention runs in blocks of queries under dense [block, T] masks, and the
layers one after another, each leaf upcast as it is used, so that the
float32 copy of the published widths never lies on the device whole.

DEPARTURES from the published model: none in the equations.  ASSUMED (not
keys of the config; the configuration file lists them too): the weights
are random from the seed: matrices and the embedding N(0, 0.02), gains 1 +
N(0, 0.02), the convolution's taps uniform in (-0.5, 0.5) (a Conv1d's
default at fan-in 4) and its bias N(0, 0.02), A_log = log(1..n_heads),
D = 1, dt_bias the inverse softplus of a log-uniform draw in [0.001, 0.1]:
so that the state neither dies nor grows.

`precision` selects how matrix products are computed: "f32" (float32
operands, `Precision.HIGHEST`: the reference proper), "bf16" (operands
rounded to bfloat16, float32 accumulation), "fp8" (operands rounded to
float8 e4m3 under one absmax scale a tensor, float32 accumulation, the
product rounded to bfloat16: the control of a bfloat16 configuration).
The recurrence itself is float32 in every mode; `state_dtype` (or the
precision "state_bf16": float32 products, the state in bfloat16) rounds the
carried state H_t to another type after every token (the reading with the
state held in bfloat16, PERF.md section 6).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

TOP_LEAVES = ("embed", "norm_f.g")
COMMON_LEAVES = ("ln_1.g", "ln_2.g", "ffn_in.w", "ffn_out.w")
ATTENTION_LEAVES = ("q.w", "k.w", "v.w", "o.w")
MAMBA_LEAVES = ("in.w", "conv.w", "conv.b", "dt_bias", "A_log", "D",
                "norm.g", "out.w")
HI = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 512


def key_from_seed(seed: int):
    """A PRNG key from any non-negative whole number (seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31)),
                              seed // (2 ** 31))


def layer_leaves(kind: str):
    return COMMON_LEAVES + (ATTENTION_LEAVES if kind == "attention"
                            else MAMBA_LEAVES)


def sizes(cfg):
    """The derived sizes of a configuration."""
    H = cfg["hidden_size"]
    nq = cfg["num_attention_heads"]
    nh, P, N, G = (cfg["mamba_n_heads"], cfg["mamba_d_head"],
                   cfg["mamba_d_state"], cfg["mamba_n_groups"])
    if G != 1:
        raise ValueError("written for one group of B and C, as published")
    d_inner = nh * P
    if d_inner != cfg["mamba_expand"] * H:
        raise ValueError("mamba_n_heads * mamba_d_head != mamba_expand * H")
    return dict(H=H, V=cfg["vocab_size"], F=cfg["intermediate_size"], nq=nq,
                nkv=cfg["num_key_value_heads"], hd=H // nq, nh=nh, P=P, N=N,
                K=cfg["mamba_d_conv"], d_inner=d_inner,
                conv_dim=d_inner + 2 * N)


def leaf_shapes(cfg):
    """{name: (shape, kind)} of the top leaves and of one layer's leaves of
    either kind (under their bare names); kind says how a leaf is drawn."""
    s = sizes(cfg)
    H, F = s["H"], s["F"]
    return {"embed": ((s["V"], H), "matrix"), "norm_f.g": ((H,), "gain"),
            "ln_1.g": ((H,), "gain"), "ln_2.g": ((H,), "gain"),
            "ffn_in.w": ((H, 2 * F), "matrix"),
            "ffn_out.w": ((F, H), "matrix"),
            "q.w": ((H, s["nq"] * s["hd"]), "matrix"),
            "k.w": ((H, s["nkv"] * s["hd"]), "matrix"),
            "v.w": ((H, s["nkv"] * s["hd"]), "matrix"),
            "o.w": ((s["nq"] * s["hd"], H), "matrix"),
            "in.w": ((H, s["d_inner"] + s["conv_dim"] + s["nh"]), "matrix"),
            "conv.w": ((s["conv_dim"], s["K"]), "taps"),
            "conv.b": ((s["conv_dim"],), "bias"),
            "dt_bias": ((s["nh"],), "dt_bias"),
            "A_log": ((s["nh"],), "A_log"), "D": ((s["nh"],), "one"),
            "norm.g": ((s["d_inner"],), "gain"),
            "out.w": ((s["d_inner"], H), "matrix")}


def init_weights(key, cfg, dtype=jnp.float32):
    """Every leaf from one key, drawn in float32 and rounded to `dtype`
    (the module's text says how each kind is drawn).  Layer i's leaves are
    `h{i}.<leaf>`."""
    shapes, std = leaf_shapes(cfg), cfg.get("initializer_range", 0.02)

    def draw(k, shape, kind):
        # the device's own bit generator under the seed's key: 450 leaves
        # drawn by the default generator compile for two minutes
        k = jax.random.wrap_key_data(
            jnp.tile(jax.random.key_data(k), 2), impl="rbg")
        if kind == "A_log":
            x = jnp.log(jnp.arange(1, shape[0] + 1, dtype=jnp.float32))
        elif kind == "one":
            x = jnp.ones(shape, jnp.float32)
        elif kind == "dt_bias":
            dt = jnp.exp(jax.random.uniform(
                k, shape, jnp.float32, math.log(0.001), math.log(0.1)))
            x = dt + jnp.log(-jnp.expm1(-dt))       # inverse of softplus
        elif kind == "taps":
            x = jax.random.uniform(k, shape, jnp.float32, -0.5, 0.5)
        else:
            x = std * jax.random.normal(k, shape, jnp.float32)
            if kind == "gain":
                x = 1.0 + x
        return x.astype(dtype)

    out = {n: draw(jax.random.fold_in(key, i), *shapes[n])
           for i, n in enumerate(TOP_LEAVES)}
    for i, kind in enumerate(cfg["layer_types"]):
        lk = jax.random.fold_in(key, 1000 + i)
        out.update({f"h{i}.{n}": draw(jax.random.fold_in(lk, j), *shapes[n])
                    for j, n in enumerate(layer_leaves(kind))})
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


def attention(u, w, cfg, precision):
    """Causal attention without positions over u [T, H] (already normed)."""
    s = sizes(cfg)
    T, nq, nkv, hd = u.shape[0], s["nq"], s["nkv"], s["hd"]
    q = matmul(u, w["q.w"], precision).reshape(T, nq, hd)
    k = matmul(u, w["k.w"], precision).reshape(T, nkv, hd)
    v = matmul(u, w["v.w"], precision).reshape(T, nkv, hd)
    group = nq // nkv                      # query head h reads KV head h // g
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    j = jnp.arange(T)[None, :]
    # queries in blocks (the whole sequence where it does not divide)
    n = QUERY_BLOCK if T % QUERY_BLOCK == 0 else T

    def rows(i0):
        """Queries [i0, i0 + n) under their dense [n, T] mask."""
        qb = jax.lax.dynamic_slice_in_dim(q, i0, n, 0)
        mask = j <= i0 + jnp.arange(n)[:, None]
        sc = jnp.einsum("qnd,knd->nqk", qb, k, precision=HI) \
            * cfg["attention_multiplier"]
        sc = jnp.where(mask[None], sc, -jnp.inf)
        return jnp.einsum("nqk,knd->qnd", jax.nn.softmax(sc, -1), v,
                          precision=HI)

    ctx = jax.lax.map(rows, jnp.arange(0, T, n)).reshape(T, nq * hd)
    return matmul(ctx, w["o.w"], precision)


def mamba(u, w, cfg, precision, state_dtype=None, length=None):
    """The Mamba-2 mixer over u [T, H] (already normed), token by token.
    With `length` also what the layer holds after the first `length`
    tokens: (H [n_heads, d_head, d_state], the convolution's last d_conv - 1
    inputs [d_conv - 1, conv_dim])."""
    s = sizes(cfg)
    T, nh, P, N, K = u.shape[0], s["nh"], s["P"], s["N"], s["K"]
    d_inner, conv_dim = s["d_inner"], s["conv_dim"]
    zxbcdt = matmul(u, w["in.w"], precision)
    z = zxbcdt[:, :d_inner]
    xBC = zxbcdt[:, d_inner:d_inner + conv_dim]
    dt = zxbcdt[:, d_inner + conv_dim:]
    taps = w["conv.w"].astype(jnp.float32)
    past = jnp.concatenate([jnp.zeros((K - 1, conv_dim), jnp.float32), xBC])
    xBC = jax.nn.silu(w["conv.b"].astype(jnp.float32) + sum(
        taps[:, j] * past[j:j + T] for j in range(K)))
    x = xBC[:, :d_inner].reshape(T, nh, P)
    B = xBC[:, d_inner:d_inner + N]
    C = xBC[:, d_inner + N:]
    step = jax.nn.softplus(dt + w["dt_bias"].astype(jnp.float32))  # [T, nh]
    if length is not None:      # a step of zero leaves the state as it is
        step = jnp.where(jnp.arange(T)[:, None] < length, step, 0.0)
    A = -jnp.exp(w["A_log"].astype(jnp.float32))
    D = w["D"].astype(jnp.float32)

    def token(h, inp):
        x_t, b_t, c_t, d_t = inp
        h = jnp.exp(d_t * A)[:, None, None] * h \
            + (d_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
        if state_dtype is not None:
            h = h.astype(state_dtype).astype(jnp.float32)
        y = jnp.einsum("hpn,n->hp", h, c_t, precision=HI) + D[:, None] * x_t
        return h, y

    h_end, y = jax.lax.scan(token, jnp.zeros((nh, P, N), jnp.float32),
                            (x, B, C, step))
    y = rms_norm(y.reshape(T, d_inner) * jax.nn.silu(z), w["norm.g"],
                 cfg["rms_norm_eps"])
    out = matmul(y, w["out.w"], precision)
    if length is None:
        return out
    # inputs [length - K + 1, length): `past` has K - 1 zeros before token 0
    return out, (h_end, jax.lax.dynamic_slice_in_dim(past, length, K - 1, 0))


def ffn(u, w, cfg, precision):
    F = cfg["intermediate_size"]
    gu = matmul(u, w["ffn_in.w"], precision)
    return matmul(jax.nn.silu(gu[:, :F]) * gu[:, F:], w["ffn_out.w"],
                  precision)


def layer(x, w, kind, cfg, precision="f32", state_dtype=None, length=None):
    """One decoder layer of `kind` on x [T, H] float32; `w` holds the
    layer's leaves under their bare names.  With `length` (a Mamba layer):
    (x', what the layer holds after `length` tokens)."""
    eps, r = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    u = rms_norm(x, w["ln_1.g"], eps)
    held = None
    if kind == "attention":
        mixed = attention(u, w, cfg, precision)
    elif length is None:
        mixed = mamba(u, w, cfg, precision, state_dtype)
    else:
        mixed, held = mamba(u, w, cfg, precision, state_dtype, length)
    x = x + r * mixed
    x = x + r * ffn(rms_norm(x, w["ln_2.g"], eps), w, cfg, precision)
    return x if held is None else (x, held)


def logits_at(w, ids, read, cfg, precision="f32", state_dtype=None):
    """Logits [len(read), V] of one sequence ids [T] at the rows `read`
    (each the distribution over the NEXT token)."""
    if precision == "state_bf16":       # every product exact, the state not
        precision, state_dtype = "f32", jnp.bfloat16
    with jax.default_matmul_precision("highest"):
        x = cfg["embedding_multiplier"] * w["embed"][ids].astype(jnp.float32)
        for i, kind in enumerate(cfg["layer_types"]):
            wl = {n: w[f"h{i}.{n}"] for n in layer_leaves(kind)}
            x = layer(x, wl, kind, cfg, precision, state_dtype)
        out = rms_norm(x[read], w["norm_f.g"], cfg["rms_norm_eps"])
        return matmul(out, w["embed"].astype(jnp.float32).T, precision) \
            / cfg["logits_scaling"]


def states_after(w, ids, length, cfg):
    """What the Mamba layers hold after the first `length` tokens of one
    sequence ids [T] (what lies behind them changes nothing): their states
    H [layers, n_heads, d_head, d_state] and the convolutions' last
    d_conv - 1 inputs [layers, d_conv - 1, conv_dim], float32."""
    with jax.default_matmul_precision("highest"):
        x = cfg["embedding_multiplier"] * w["embed"][ids].astype(jnp.float32)
        held = []
        for i, kind in enumerate(cfg["layer_types"]):
            wl = {n: w[f"h{i}.{n}"] for n in layer_leaves(kind)}
            if kind == "attention":
                x = layer(x, wl, kind, cfg)
            else:
                x, h = layer(x, wl, kind, cfg, length=length)
                held.append(h)
        return (jnp.stack([h for h, _ in held]),
                jnp.stack([t for _, t in held]))
