"""Plain reference for SDAR-MoE decoders (JetLM SDAR-30B-A3B-Chat,
`model_type` `sdar_moe`): a Qwen3-MoE block trained to generate by
diffusion over blocks.

Written from the published description (the model's `config.json` keys,
the Qwen3-MoE block SDAR was trained from, and `block_diffusion_generate`
of the SDAR repository's `generate.py`).  Straightforward `jax.numpy` in
float32: no kernel, no cache, no batching.  It imports nothing of the
program under test; the matrix product in a chosen `precision` and the
seed's key are the GPT reference's.

One layer (u = RMSNorm(x), every product without bias):
  attention  q = W_q u as [nq, hd], k = W_k u, v = W_v u as [nkv, hd]; q
             and k pass an RMSNorm over the head with a learned gain, then
             rotary positions over the whole head (rotate-half); query
             head h reads KV head h // (nq / nkv); scores q.k / sqrt(hd)
             under a mask; h = x + W_o ctx
  experts    p = softmax(W_r u) over E in float32; the k largest; weights
             p_e / sum of the k; y = h + sum_e w_e W_down,e
             (silu(W_gate,e u) * W_up,e u): a loop over the experts, every
             routed token computed, none dropped
  head       logits = W_head RMSNorm(x_L); the logits at position i
             predict the token AT position i (no shift)

The block mask is M(i, j) = [j // B <= i // B]: a position sees every
earlier block and all of its own, both ways.

Departures from the published procedure, each marked DEPARTURE below:
the published generator keeps a KV cache of finished blocks (here every
pass runs the whole sequence again: the same arithmetic, no cache);
greedy only; generation is cut at `gen_length` and at the first eos
where the published code runs whole blocks to the end of the last one
and stops after a block that holds a stop token; a step never overwrites
a known token.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks.reference.gpt import key_from_seed, matmul  # noqa: F401

LAYER_LEAVES = ("ln_1.g", "q.w", "k.w", "v.w", "q_norm.g", "k_norm.g", "o.w",
                "ln_2.g", "router.w", "gate.w", "up.w", "down.w")
TOP_LEAVES = ("embed", "head", "norm_f.g")
HI = jax.lax.Precision.HIGHEST


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


def _draw(key, shape, kind, dtype, std):
    x = std * jax.random.normal(key, shape, jnp.float32)
    return ((1.0 + x) if kind == "gain" else x).astype(dtype)


def top_weights(key, cfg, dtype=jnp.float32):
    """Embedding, head and final norm from the key."""
    shapes, std = leaf_shapes(cfg), cfg.get("initializer_range", 0.02)
    return {n: _draw(jax.random.fold_in(key, i), *shapes[n], dtype, std)
            for i, n in enumerate(TOP_LEAVES)}


def layer_weights(key, cfg, layer, dtype=jnp.float32):
    """Layer `layer`'s leaves alone, under their bare names: any layer
    can be made without the others, so that the chip's check holds one
    float32 layer at a time."""
    shapes, std = leaf_shapes(cfg), cfg.get("initializer_range", 0.02)
    lk = jax.random.fold_in(key, 1000 + layer)
    return {n: _draw(jax.random.fold_in(lk, j), *shapes[n], dtype, std)
            for j, n in enumerate(LAYER_LEAVES)}


def init_weights(key, cfg, dtype=jnp.float32):
    """Every leaf from one key: matrices N(0, initializer_range), gains
    1 + N(0, initializer_range), drawn in float32 and rounded to `dtype`.
    Layer i's leaves are `h{i}.<leaf>`."""
    out = top_weights(key, cfg, dtype)
    for i in range(cfg["num_hidden_layers"]):
        out.update({f"h{i}.{n}": v for n, v in
                    layer_weights(key, cfg, i, dtype).items()})
    return out


def rms_norm(x, g, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(jnp.float32)


def rope(x, positions, theta):
    """Rotary positions over the whole head, rotate-half: x [T, n, hd]."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def block_mask(n, block):
    """[n, n] bool: position i sees j where j // block <= i // block."""
    b = jnp.arange(n) // block
    return b[None, :] <= b[:, None]


def attention(x, w, positions, mask, cfg, precision):
    T = x.shape[0]
    nq, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    u = rms_norm(x, w["ln_1.g"], eps)
    q = matmul(u, w["q.w"].astype(jnp.float32), precision).reshape(T, nq, hd)
    k = matmul(u, w["k.w"].astype(jnp.float32), precision).reshape(T, nkv, hd)
    v = matmul(u, w["v.w"].astype(jnp.float32), precision).reshape(T, nkv, hd)
    q = rope(rms_norm(q, w["q_norm.g"], eps), positions, cfg["rope_theta"])
    k = rope(rms_norm(k, w["k_norm.g"], eps), positions, cfg["rope_theta"])
    group = nq // nkv                      # query head h reads KV head h // g
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = jnp.einsum("qnd,knd->nqk", q, k, precision=HI) / math.sqrt(hd)
    s = jnp.where(mask[None], s, -jnp.inf)
    ctx = jnp.einsum("nqk,knd->qnd", jax.nn.softmax(s, -1), v, precision=HI)
    return x + matmul(ctx.reshape(T, nq * hd), w["o.w"].astype(jnp.float32),
                      precision)


def route(u, w, cfg, precision):
    """[T, E] float32: expert e's weight for each token, 0 where e is not
    among the token's k largest."""
    k = cfg["num_experts_per_tok"]
    p = jax.nn.softmax(matmul(u, w["router.w"].astype(jnp.float32),
                              precision), -1)
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
        a = jax.nn.silu(matmul(u, gate.astype(jnp.float32), precision)) \
            * matmul(u, up.astype(jnp.float32), precision)
        return y + we[:, None] * matmul(a, down.astype(jnp.float32),
                                        precision), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h),
                        (w["gate.w"], w["up.w"], w["down.w"], weight.T))
    return h + y


def layer(x, w, positions, mask, cfg, precision="f32"):
    """One decoder layer on x [T, H] float32 at `positions` [T] under
    `mask` [T, T]; `w` holds the layer's leaves under their bare names."""
    return experts(attention(x, w, positions, mask, cfg, precision), w, cfg,
                   precision)


def embed(w, ids):
    return w["embed"].astype(jnp.float32)[ids]


def head(w, x, cfg, precision="f32"):
    return matmul(rms_norm(x, w["norm_f.g"], cfg["rms_norm_eps"]),
                  w["head"].astype(jnp.float32), precision)


def logits_at(w, ids, read, cfg, precision="f32", positions=None, mask=None):
    """Logits [len(read), V] of one sequence ids [T] at the rows `read`
    (the distribution over the token AT each).  Default positions 0..T-1
    under the block mask of `cfg["block_length"]`."""
    T = ids.shape[0]
    if positions is None:
        positions = jnp.arange(T)
    if mask is None:
        mask = block_mask(T, cfg["block_length"])
    x = embed(w, ids)
    for i in range(cfg["num_hidden_layers"]):
        wl = {n: w[f"h{i}.{n}"] for n in LAYER_LEAVES}
        x = layer(x, wl, positions, mask, cfg, precision)
    return head(w, x[read], cfg, precision)


def num_transfer_tokens(block, steps):
    """Tokens a denoising step unmasks under the static strategy: block /
    steps, the remainder going to the first steps (as published)."""
    base, rem = divmod(block, steps)
    return [base + (1 if s < rem else 0) for s in range(steps)]


def choose(conf, masked, n_t, strategy, threshold):
    """Which masked positions a step unmasks, as a list of indices.
    static: the n_t most confident.  dynamic: every masked position over
    `threshold`, and never fewer than n_t.  Ties go to the lower index."""
    order = sorted((i for i in range(len(conf)) if masked[i]),
                   key=lambda i: (-conf[i], i))
    # DEPARTURE: the published top-k over a block with fewer masks than
    # n_t reaches known positions (confidence -inf) and overwrites them
    top = order[:n_t]
    if strategy == "low_confidence_dynamic":
        high = [i for i in order if conf[i] > threshold]
        return high if len(high) >= n_t else top
    if strategy != "low_confidence_static":
        raise ValueError(f"unknown remasking strategy {strategy!r}")
    return top


def block_diffusion_generate(w, prompt, cfg, gen_length, eos=None,
                             precision="f32"):
    """`block_diffusion_generate` as published, greedy.  Returns (tokens,
    steps, passes): the generated tokens (cut at `gen_length` and after
    the first `eos`), for each the denoising step of its block at which
    it was unmasked, and the model passes a block took (its steps and the
    final pass that the published code spends on writing its K/V)."""
    B, T = cfg["block_length"], cfg["denoising_steps"]
    mask_id = cfg["mask_token_id"]
    strategy = cfg.get("remasking_strategy", "low_confidence_static")
    threshold = cfg.get("confidence_threshold", 0.85)
    n_transfer = num_transfer_tokens(B, T)
    L = len(prompt)
    n_blocks = -(-(L + gen_length) // B)
    x = list(prompt) + [mask_id] * (n_blocks * B - L)
    step_of = [-1] * len(x)
    fwd = jax.jit(lambda ids, read: logits_at(w, ids, read, cfg, precision))
    passes = []
    # the prompt's whole blocks are context; its last L mod B tokens open
    # the first generated block as known tokens
    for nb in range(L // B, n_blocks):
        lo, hi = nb * B, (nb + 1) * B
        n_pass = 0
        for step in range(T + 1):
            masked = [t == mask_id for t in x[lo:hi]]
            n_pass += 1
            if not any(masked):
                # DEPARTURE: the published code runs the model once more
                # here to write the block's K/V into its cache; without a
                # cache the pass computes nothing that is kept
                break
            # DEPARTURE: no cache, the whole sequence up to the block's
            # end runs again under the block mask
            lg = fwd(jnp.asarray(x[:hi], jnp.int32), jnp.arange(lo, hi))
            lp = jax.nn.log_softmax(lg.astype(jnp.float32), -1)
            x0 = [int(t) for t in jnp.argmax(lp, -1)]   # DEPARTURE: greedy
            conf = [float(c) for c in jnp.exp(lp.max(-1))]
            for i in choose(conf, masked, n_transfer[step], strategy,
                            threshold):
                x[lo + i], step_of[lo + i] = x0[i], step
        passes.append(n_pass)
        # DEPARTURE: stop at the first eos at or after the prompt's end
        # (the published code stops after a block that holds a stop token
        # and returns the whole block)
        if eos is not None and eos in x[max(lo, L):hi]:
            break
    out = x[L:L + gen_length]
    steps = step_of[L:L + gen_length]
    if mask_id in out:                      # stopped early on eos
        cut = out.index(mask_id)
        out, steps = out[:cut], steps[:cut]
    if eos is not None and eos in out:
        cut = out.index(eos) + 1
        out, steps = out[:cut], steps[:cut]
    return out, steps, passes
