"""Plain reference for GPT-2-style decoders (GPT-2, Cerebras-GPT).

Written from the published description (Radford et al. 2019; the GPT-2
`config.json` keys): learned token and position embeddings, pre-LayerNorm
blocks of causal multi-head attention and a 4x GELU MLP, a final LayerNorm
and a head tied to the token embedding.  Straightforward `jax.numpy`: no
kernels, no cache, no batching tricks.  It imports nothing of the program
under test and takes nothing the program made: the weights come from
`init_weights`, from the seed.

Departures from the published model, as the configuration files list them
under `changed`: the vocabulary is padded to a multiple of 128 and GELU is
the exact (erf) form where GPT-2 has the tanh approximation.

`precision` selects how matrix products are computed:
  "f32"   float32 operands, `Precision.HIGHEST` (the reference proper)
  "bf16"  operands rounded to bfloat16, float32 accumulation
  "int8"  what a bfloat16 configuration would become with 8-bit integer
          matrix products: operands rounded to int8 (activations per row,
          weights per output column, absmax scale), float32 accumulation,
          the product rounded to bfloat16 as the configuration has it:
  "fp8"   8-bit floating point matrix products, forward and backward:
          operands (and, in the backward pass, the incoming gradient)
          rounded to float8 e4m3 under one absmax scale a tensor, float32
          accumulation, the product rounded to bfloat16: the control of a
          bfloat16 configuration ("int8 or fp8 for bfloat16")
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

PRECISIONS = ("f32", "bf16", "int8", "fp8")


def key_from_seed(seed: int):
    """A PRNG key from any non-negative whole number (seeds pass 2**31)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed % (2 ** 31))
    return jax.random.fold_in(key, seed // (2 ** 31))


LAYER_LEAVES = ("ln_1.g", "ln_1.b", "qkv.w", "qkv.b", "proj.w", "proj.b",
                "ln_2.g", "ln_2.b", "fc1.w", "fc1.b", "fc2.w", "fc2.b")


def leaf_shapes(cfg):
    """{name: (shape, kind)}; kind is "matrix", "bias", "gain"."""
    V, H, L = cfg["vocab_size"], cfg["hidden_size"], cfg["num_layers"]
    F, P = cfg["ffn_hidden_size"], cfg["max_position_embeddings"]
    layer = {"ln_1.g": ((H,), "gain"), "ln_1.b": ((H,), "bias"),
             "qkv.w": ((H, 3 * H), "matrix"), "qkv.b": ((3 * H,), "bias"),
             "proj.w": ((H, H), "matrix"), "proj.b": ((H,), "bias"),
             "ln_2.g": ((H,), "gain"), "ln_2.b": ((H,), "bias"),
             "fc1.w": ((H, F), "matrix"), "fc1.b": ((F,), "bias"),
             "fc2.w": ((F, H), "matrix"), "fc2.b": ((H,), "bias")}
    out = {"wte": ((V, H), "matrix"), "wpe": ((P, H), "matrix"),
           "ln_f.g": ((H,), "gain"), "ln_f.b": ((H,), "bias")}
    for i in range(L):
        out.update({f"h{i}.{n}": layer[n] for n in LAYER_LEAVES})
    return out


def init_weights(key, cfg, dtype=jnp.float32, std=0.02):
    """Every leaf from one key: matrices and biases N(0, std), LayerNorm
    gains 1 + N(0, std), so that no bias or gain path is trivially inert.
    Values are drawn in float32 and rounded to `dtype`.  One draw a kind
    of leaf, over all the layers at once: a draw a leaf makes a program
    that takes most of a minute to compile."""
    shapes = leaf_shapes(cfg)
    L = cfg["num_layers"]

    def draw(i, shape, kind):
        x = std * jax.random.normal(jax.random.fold_in(key, i), shape,
                                    jnp.float32)
        return ((1.0 + x) if kind == "gain" else x).astype(dtype)

    out = {n: draw(i, *shapes[n])
           for i, n in enumerate(("wte", "wpe", "ln_f.g", "ln_f.b"))}
    for j, n in enumerate(LAYER_LEAVES):
        shape, kind = shapes[f"h0.{n}"]
        stacked = draw(16 + j, (L,) + shape, kind)
        out.update({f"h{i}.{n}": stacked[i] for i in range(L)})
    return out


def _quant8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    q = jnp.clip(jnp.round(x / scale), -127, 127) * scale
    # straight-through: the backward pass sees the identity
    return x + jax.lax.stop_gradient(q - x)


def _fp8(x):
    scale = jnp.max(jnp.abs(x)) / 448.0          # e4m3's largest finite
    scale = jnp.where(scale == 0, 1.0, scale)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


@jax.custom_vjp
def _matmul_fp8(a, b):
    return _bf16(jnp.matmul(_fp8(a), _fp8(b),
                            precision=jax.lax.Precision.HIGHEST))


def _matmul_fp8_fwd(a, b):
    return _matmul_fp8(a, b), (a, b)


def _matmul_fp8_bwd(res, g):
    a, b = res
    g8, hi = _fp8(g), jax.lax.Precision.HIGHEST
    da = jnp.matmul(g8, _fp8(b).T, precision=hi)
    db = jnp.matmul(_fp8(a).reshape(-1, a.shape[-1]).T,
                    g8.reshape(-1, g.shape[-1]), precision=hi)
    return _bf16(da), _bf16(db)


_matmul_fp8.defvjp(_matmul_fp8_fwd, _matmul_fp8_bwd)


def matmul(a, b, precision):
    if precision == "fp8":
        return _matmul_fp8(a, b)
    if precision == "f32":
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)
    if precision == "bf16":
        return jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    if precision == "int8":
        out = jnp.matmul(_quant8(a, -1), _quant8(b, -2),
                         precision=jax.lax.Precision.HIGHEST)
        return out + jax.lax.stop_gradient(
            out.astype(jnp.bfloat16).astype(jnp.float32) - out)
    raise ValueError(f"unknown precision {precision!r}")


def layer_norm(x, g, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def block(x, w, cfg, precision):
    """One decoder block on x [T, H] (one sequence); w holds the block's
    own leaves."""
    T, H = x.shape
    nh = cfg["num_heads"]
    hd = H // nh
    eps = cfg["layer_norm_epsilon"]
    h = layer_norm(x, w["ln_1.g"], w["ln_1.b"], eps)
    qkv = matmul(h, w["qkv.w"], precision) + w["qkv.b"]
    q, k, v = (qkv[:, i * H:(i + 1) * H].reshape(T, nh, hd)
               for i in range(3))
    s = jnp.einsum("qnd,knd->nqk", q, k,
                   precision=jax.lax.Precision.HIGHEST) / math.sqrt(hd)
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    a = jnp.einsum("nqk,knd->qnd", jax.nn.softmax(s, -1), v,
                   precision=jax.lax.Precision.HIGHEST).reshape(T, H)
    x = x + matmul(a, w["proj.w"], precision) + w["proj.b"]
    h = layer_norm(x, w["ln_2.g"], w["ln_2.b"], eps)
    h = matmul(h, w["fc1.w"], precision) + w["fc1.b"]
    h = jax.nn.gelu(h, approximate=False)
    return x + matmul(h, w["fc2.w"], precision) + w["fc2.b"]


def hidden(w, ids, cfg, precision="f32", remat=False):
    """Final hidden states [T, H] of one sequence ids [T].  The blocks
    are alike, so they run as one scanned block over the layers' leaves
    stacked (a program a twelfth or a twenty-fourth the size)."""
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    x = w["wte"][ids] + w["wpe"][:ids.shape[0]]
    layers = {n: jnp.stack([w[f"h{i}.{n}"]
                            for i in range(cfg["num_layers"])])
              for n in LAYER_LEAVES}

    def one(x_, leaves):
        return block(x_, leaves, cfg, precision), None

    x, _ = jax.lax.scan(jax.checkpoint(one) if remat else one, x, layers)
    return layer_norm(x, w["ln_f.g"], w["ln_f.b"],
                      cfg["layer_norm_epsilon"])


def logits_at(w, ids, positions, cfg, precision="f32"):
    """Logits [len(positions), V] of one sequence at the given positions
    (the distribution over the token that follows each)."""
    h = hidden(w, ids, cfg, precision)[positions]
    return matmul(h, w["wte"].astype(jnp.float32).T, precision)


def sequence_loss(w, ids, cfg, precision="f32"):
    """Sum of next-token cross-entropies of ids [T + 1] (inputs ids[:-1],
    targets ids[1:]) and the token count."""
    h = hidden(w, ids[:-1], cfg, precision, remat=True)
    lg = matmul(h, w["wte"].astype(jnp.float32).T, precision)
    lp = jax.nn.log_softmax(lg, -1)
    nll = -jnp.take_along_axis(lp, ids[1:, None], 1)[:, 0]
    return jnp.sum(nll), nll.shape[0]


def batch_loss_and_grad(w, rows, cfg, precision="f32"):
    """Mean loss over rows [B, T + 1] and its gradient, accumulated row
    by row so that one row's logits are all that is ever live."""
    n_tok = rows.shape[0] * (rows.shape[1] - 1)
    vg = jax.value_and_grad(
        lambda w_, row: sequence_loss(w_, row, cfg, precision)[0] / n_tok)

    def body(carry, row):
        loss, grad = carry
        l, g = vg(w, row)
        return (loss + l, jax.tree_util.tree_map(jnp.add, grad, g)), None

    zero = jax.tree_util.tree_map(jnp.zeros_like, w)
    (loss, grad), _ = jax.lax.scan(body, (jnp.float32(0), zero), rows)
    return loss, grad


def adamw_step(w, m, v, grad, t, opt):
    """Decoupled weight decay (Loshchilov & Hutter 2019) on every leaf."""
    b1, b2, eps = opt["beta1"], opt["beta2"], opt["epsilon"]
    lr, wd = opt["learning_rate"], opt["weight_decay"]
    m = jax.tree_util.tree_map(lambda a, g: b1 * a + (1 - b1) * g, m, grad)
    v = jax.tree_util.tree_map(lambda a, g: b2 * a + (1 - b2) * g * g,
                               v, grad)
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t

    def upd(p, a, b):
        return p - lr * ((a / c1) / (jnp.sqrt(b / c2) + eps) + wd * p)

    return jax.tree_util.tree_map(upd, w, m, v), m, v


def leaf_norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for k, x in tree.items()}


SAMPLE = 4096


def sample_indices(cfg):
    """{leaf: indices into the flattened leaf}: up to SAMPLE fixed places
    a leaf, the same for every seed, at which the first gradient's
    direction is compared (the whole gradient would be half a gigabyte
    held through the window)."""
    import numpy as np

    out = {}
    for i, (name, (shape, _)) in enumerate(leaf_shapes(cfg).items()):
        size = math.prod(shape)
        if size <= SAMPLE:
            out[name] = np.arange(size, dtype=np.int32)
        else:
            out[name] = np.sort(np.random.default_rng([0xD1FF, i]).choice(
                size, SAMPLE, replace=False)).astype(np.int32)
    return out


def sample_leaves(tree, indices):
    return {k: tree[k].reshape(-1)[indices[k]].astype(jnp.float32)
            for k in indices}


def train_steps(w0, batches, cfg, opt, precision="f32"):
    """Follow `len(batches)` AdamW steps from w0 (float32).  Returns the
    loss of each step, the per-leaf norm of the first gradient, the
    per-leaf norm of the parameters' change over all the steps, and the
    first gradient at `sample_indices`."""
    step = jax.jit(lambda w, m, v, rows, t: _one_step(
        w, m, v, rows, t, cfg, opt, precision))
    w = w0
    m = jax.tree_util.tree_map(jnp.zeros_like, w0)
    v = jax.tree_util.tree_map(jnp.zeros_like, w0)
    losses, g1, g1_at = [], None, None
    idx = sample_indices(cfg)
    at = jax.jit(lambda tree: sample_leaves(tree, idx))  # one program, not
    for t, rows in enumerate(batches, 1):                # a gather a leaf
        w, m, v, loss, gn = step(w, m, v, rows, jnp.float32(t))
        losses.append(loss)
        if t == 1:
            # Adam's first moment after one step is (1 - beta1) * gradient
            g1 = gn
            g1_at = {k: x / (1 - opt["beta1"]) for k, x in at(m).items()}
    delta = jax.jit(lambda a, b: leaf_norms(
        jax.tree_util.tree_map(jnp.subtract, a, b)))(w, w0)
    return ([float(x) for x in losses],
            {k: float(x) for k, x in g1.items()},
            {k: float(x) for k, x in delta.items()},
            jax.device_get(g1_at))


def _one_step(w, m, v, rows, t, cfg, opt, precision):
    loss, grad = batch_loss_and_grad(w, rows, cfg, precision)
    w2, m2, v2 = adamw_step(w, m, v, grad, t, opt)
    return w2, m2, v2, loss, leaf_norms(grad)
