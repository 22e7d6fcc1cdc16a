#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py               one TPU chip: device, kernels, train, serve
    python chip_smoke.py --chips 4     four chips: Model.fit on a 2x2 mesh against
                                       one device of the same process, nothing else
    python chip_smoke.py --rehearse    the same phases at a tiny size on whatever
                                       jax.devices() gives (tests, CPU rehearsal)

One process drives GPT-2 124M as `GPTConfig()` defines it (vocab 50304, hidden
768, 12 layers, 12 heads, 1024 positions; weights random from --seed) through
the entry points a user calls: `paddle.Model.fit` fed by a `DataLoader`, then
the same weights in a `GenerationEngine` behind `ServingServer`, reached
through `ServingClient`.  Every phase prints one JSON line and checks its own
output; a phase that fails raises, so the script exits non-zero and prints no
result line.  The last line of a full run on a chip is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Without an accelerator the script fails before it prints anything: it sets no
platform and falls back to none.  A rehearsal never prints that line; its
last line carries "rehearsal" and the platform it really ran on.  The timings
it prints are observations of one run, not a benchmark.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import threading
import time

PHASES = ("device", "kernels", "train", "serve", "train_mesh")

# Real sizes: GPTConfig() defaults for the model, a geometry a user would run
# for the trainer and the server.  Tiny sizes keep every branch of the same
# code inside a few seconds on the CPU.
REAL = dict(
    model={}, batch=8, seq=1024, steps=10, lr=6e-4,
    # the paged kernel at GPT-2's heads (pages no DMA can cut out of the
    # pool: the grid walks them) and at heads whose pages the kernel copies
    # itself in a loop of its own (16 of 128, the chat cell's)
    kernels=dict(B=8, S=1024, NH=12, HD=64, H=768, V=50304,
                 slots=16, page=16, walk_NH=16, walk_HD=128),
    serve=dict(slots=16, max_seq_len=1024, page=16, buckets="128,256,512",
               new_tokens=64, prompt_len=128, long_len=384, prefix_len=256))
TINY = dict(
    model=dict(vocab_size=512, hidden_size=64, num_layers=2, num_heads=2,
               max_position_embeddings=128),
    batch=4, seq=64, steps=4, lr=3e-3,
    kernels=dict(B=1, S=64, NH=2, HD=32, H=128, V=384,
                 slots=2, page=8, walk_NH=2, walk_HD=128),
    serve=dict(slots=4, max_seq_len=128, page=8, buckets="16,64",
               new_tokens=8, prompt_len=16, long_len=48, prefix_len=32))

# A greedy token may differ between two bf16 paths (paged kernel against
# dense cache, flash prefill against prefix prefill) only where a float32
# reference holds its two best logits closer than this.
MARGIN_TOL = 0.25
# bf16 losses near 10 are 0.0625 apart; sharded and one-device sums round
# in different orders
MESH_LOSS_TOL = 0.15


class SmokeFailure(Exception):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def emit(phase, **fields):
    print(json.dumps({"phase": phase, **fields}), flush=True)


class CompileMeter:
    """Executables jax built or loaded, and the seconds that took, from
    jax's own monitoring events (a persistent-cache hit is counted as an
    executable obtained, with the time it took to load)."""

    def __init__(self, jax):
        self._lock = threading.Lock()
        self.executables = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.executables += 1
                self.seconds += secs

    def read(self):
        with self._lock:
            return self.executables, self.seconds


def zipf_tokens(rs, shape, vocab):
    """Token ids with p(id) ~ 1/(id+1): a unigram law a model can start to
    learn in ten steps (entropy ~7.8 nats at vocab 50304, against ln V =
    10.83 for uniform ids)."""
    import numpy as np

    ids = np.exp(rs.random_sample(shape) * math.log(vocab)).astype(np.int64) - 1
    return np.clip(ids, 0, vocab - 1).astype(np.int32)


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------
def phase_device(args, jax):
    from importlib import metadata

    import jaxlib

    from paddle_tpu import core
    from paddle_tpu.framework import flags

    devs = jax.devices()
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = None
    info = dict(
        platform=devs[0].platform, kind=devs[0].device_kind, count=len(devs),
        jax=jax.__version__, jaxlib=jaxlib.__version__, libtpu=libtpu,
        x64=bool(jax.config.jax_enable_x64),
        compile_cache_dir=flags.apply_jit_cache(),
        native_core=core.available(), rehearsal=args.rehearse)
    emit("device", **info)
    check(len(devs) >= args.chips,
          f"--chips {args.chips} needs {args.chips} devices, "
          f"jax.devices() = {devs}")
    return info


# ---------------------------------------------------------------------------
# kernels: each Pallas kernel ops/fused.py dispatches, against plain float32
# jax.numpy at the model's shapes
# ---------------------------------------------------------------------------
def phase_kernels(args, jax, sizes):
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops.pallas import interpret_default
    from paddle_tpu.ops.pallas.flash_attention import flash_attention
    from paddle_tpu.ops.pallas.layer_norm import layer_norm
    from paddle_tpu.ops.pallas.paged_attention import paged_decode_attention
    from paddle_tpu.ops.pallas.softmax_xent import softmax_xent

    interpret = interpret_default()
    check(args.rehearse or interpret is False,
          "kernels would run interpreted on this backend")
    k = sizes["kernels"]
    B, S, NH, HD, H, V = (k[n] for n in ("B", "S", "NH", "HD", "H", "V"))
    slots, page = k["slots"], k["page"]
    pps = S // page
    PLANE = 1   # the paged kernel reads one plane of the stacked pools
    f32, bf16 = jnp.float32, jnp.bfloat16
    rs = np.random.RandomState(args.seed)

    def rand(shape, dtype, scale=1.0):
        return jnp.asarray(rs.standard_normal(shape) * scale, dtype)

    def up(*xs):
        return [x.astype(f32) if jnp.issubdtype(x.dtype, jnp.floating)
                else x for x in xs]

    def ref_attention(q, kk, v, mask=None, causal=False):
        q, kk, v = up(q, kk, v)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kk) / math.sqrt(HD)
        if causal:
            s = jnp.where(jnp.tril(jnp.ones(s.shape[-2:], bool)), s, -1e30)
        if mask is not None:
            s = jnp.where(mask, s, -1e30)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)

    def ref_layer_norm(x, w, b):
        x, w, b = up(x, w, b)
        mu = x.mean(-1, keepdims=True)
        var = jnp.square(x - mu).mean(-1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + 1e-5) * w + b

    def ref_xent(z, lab):
        lp = jax.nn.log_softmax(z.astype(f32), -1)
        return -jnp.take_along_axis(lp, lab[:, None], 1)[:, 0]

    def ref_paged(q, kp, vp, rows, pos):
        q, kp, vp = up(q, kp[PLANE], vp[PLANE])
        nh, hd = q.shape[1:]
        kg = kp[jnp.clip(rows, 0)].reshape(slots, pps * page, nh, hd)
        vg = vp[jnp.clip(rows, 0)].reshape(slots, pps * page, nh, hd)
        s = jnp.einsum("bnd,bsnd->bns", q, kg) / math.sqrt(hd)
        s = jnp.where((jnp.arange(pps * page)[None] <= pos[:, None])[:, None],
                      s, -1e30)
        return jnp.einsum("bns,bsnd->bnd", jax.nn.softmax(s, -1), vg)

    qkv = [rand((B, S, NH, HD), bf16) for _ in range(3)]
    pad = jnp.asarray(rs.random_sample((B, 1, 1, S)) > 0.15).at[..., 0].set(True)
    # ragged page table: each lane owns a random number of shuffled pages,
    # its position somewhere inside the last one, the rest unmapped (-1)
    n_pages = slots * pps + 1
    rows = np.full((slots, pps), -1, np.int32)
    pos = np.zeros((slots,), np.int32)
    perm = rs.permutation(n_pages - 1) + 1
    for lane in range(slots):
        used = 1 + rs.randint(pps)
        rows[lane, :used] = perm[lane * pps:lane * pps + used]
        pos[lane] = used * page - 1 - rs.randint(page)
    def paged_args(nh, hd):
        return [rand((slots, nh, hd), bf16),
                rand((3, n_pages, page, nh, hd), bf16),
                rand((3, n_pages, page, nh, hd), bf16),
                jnp.asarray(rows), jnp.asarray(pos)]

    labels = jnp.asarray(rs.randint(0, V, (B * S,)), jnp.int32)

    # name -> (kernel, reference, args, differentiated args, tolerance as a
    # share of the reference's largest magnitude: bf16 keeps 8 bits)
    cases = {
        "flash_causal": (
            lambda q, kk, v: flash_attention(q, kk, v, causal=True),
            lambda q, kk, v: ref_attention(q, kk, v, causal=True),
            qkv, (0, 1, 2), 2e-2),
        "flash_masked": (
            lambda q, kk, v, m: flash_attention(q, kk, v, mask=m),
            lambda q, kk, v, m: ref_attention(q, kk, v, mask=m),
            qkv + [pad], (0, 1, 2), 2e-2),
        "layer_norm": (
            layer_norm, ref_layer_norm,
            [rand((B, S, H), f32), rand((H,), f32), rand((H,), f32)],
            (0, 1, 2), 1e-4),
        "softmax_xent": (
            softmax_xent, ref_xent,
            [rand((B * S, V), bf16), labels], (0,), 2e-2),
        "paged_decode": (
            lambda *a: paged_decode_attention(*a, S, PLANE), ref_paged,
            paged_args(NH, HD), (), 2e-2),
        "paged_decode_walk": (
            lambda *a: paged_decode_attention(*a, S, PLANE), ref_paged,
            paged_args(k["walk_NH"], k["walk_HD"]), (), 2e-2),
    }

    def timed(f, xs):
        jax.block_until_ready(f(*xs))
        t0 = time.perf_counter()
        out = jax.block_until_ready(f(*xs))
        return out, (time.perf_counter() - t0) * 1e3

    def rel_err(got, ref):
        got, ref = jnp.asarray(got, f32), jnp.asarray(ref, f32)
        return float(jnp.abs(got - ref).max() / (jnp.abs(ref).max() + 1e-30))

    results = {}
    for name, (kern, ref, xs, diff, tol) in cases.items():
        fwd = jax.jit(kern)
        out, ms = timed(fwd, xs)
        check(interpret or "tpu_custom_call" in fwd.lower(*xs).as_text(),
              f"{name}: no Mosaic kernel in the lowered module")
        res = {"fwd_err": rel_err(out, jax.jit(ref)(*xs)),
               "fwd_ms": round(ms, 3), "tol": tol}
        if diff:
            # the cotangent rides in as an argument: a closed-over array
            # becomes a constant inside the executable (and the cache)
            ct = rand(out.shape, f32)

            def vjp(f):
                return jax.jit(jax.grad(
                    lambda c, *a: (f(*a).astype(f32) * c).sum(),
                    tuple(i + 1 for i in diff)))

            got, ms = timed(vjp(kern), [ct] + xs)
            want = vjp(ref)(ct, *xs)
            res["bwd_err"] = max(rel_err(g, w) for g, w in zip(got, want))
            res["fwd_bwd_ms"] = round(ms, 3)   # jax.grad runs both
        results[name] = res
        err = max(res["fwd_err"], res.get("bwd_err", 0.0))
        check(math.isfinite(err) and err <= tol,
              f"kernel {name}: error {err:.3e} above tolerance {tol:.0e} "
              f"(share of the float32 reference's largest magnitude)")
    emit("kernels", interpret=interpret, shapes=k, kernels=results,
         error_is="max |kernel - float32 reference| / max |reference|")


# ---------------------------------------------------------------------------
# train: Model.fit, AdamW, bf16 autocast, DataLoader
# ---------------------------------------------------------------------------
def fit_gpt(args, jax, paddle, sizes, *, mesh=None):
    """`steps` iterations of Model.fit on seeded token data.  Returns
    (network, per-step losses, per-step seconds, and under a mesh the
    census taken on the last step, while the engine's state was live)."""
    import numpy as np

    from paddle_tpu.io import DataLoader, Dataset
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    cfg = GPTConfig(dropout=0.0, attn_dropout=0.0, **sizes["model"])
    batch, seq, steps = sizes["batch"], sizes["seq"], sizes["steps"]
    tokens = zipf_tokens(np.random.RandomState(args.seed),
                         (batch * steps, seq + 1), cfg.vocab_size)

    class Tokens(Dataset):
        def __len__(self):
            return len(tokens)

        def __getitem__(self, i):
            return tokens[i, :-1], tokens[i, 1:]

    class StepClock(paddle.callbacks.Callback):
        def __init__(self):
            super().__init__()
            self.losses, self.stamps, self.census = [], [], None

        def on_train_begin(self, logs=None):
            self.stamps.append(time.perf_counter())

        def on_train_batch_end(self, step, logs=None):
            self.losses.append(float(logs["loss"]))   # fetched: a device sync
            self.stamps.append(time.perf_counter())
            if mesh is not None and len(self.losses) == steps:
                self.census = mesh_census(jax, self.model._engine.state)

    paddle.seed(args.seed)
    net = GPTForCausalLM(cfg)
    model = paddle.Model(net)
    model.prepare(
        paddle.optimizer.AdamW(learning_rate=sizes["lr"], weight_decay=0.01,
                               parameters=net.parameters()),
        paddle.nn.CrossEntropyLoss())
    loader = DataLoader(Tokens(), batch_size=batch, shuffle=False,
                        drop_last=True)
    clock = StepClock()
    shard = dict(mesh=mesh, layout=True) if mesh is not None else {}
    with paddle.amp.auto_cast(dtype="bfloat16"):
        model.fit(loader, epochs=1, num_iters=steps, verbose=0,
                  callbacks=[clock], **shard)
    check(len(clock.losses) == steps,
          f"fit ran {len(clock.losses)} steps, wanted {steps}")
    secs = [b - a for a, b in zip(clock.stamps, clock.stamps[1:])]
    return net, clock.losses, secs, clock.census


def loss_checks(losses, vocab):
    check(all(math.isfinite(v) for v in losses), f"loss not finite: {losses}")
    check(abs(losses[0] - math.log(vocab)) <= 0.3,
          f"first loss {losses[0]:.3f} not within 0.3 of "
          f"ln({vocab}) = {math.log(vocab):.3f}")
    check(losses[-1] < losses[0],
          f"loss did not fall: {losses[0]:.3f} -> {losses[-1]:.3f}")


def step_stats(secs, batch, seq):
    steady = sorted(secs[1:])
    median = steady[len(steady) // 2]
    return dict(first_step_seconds=round(secs[0], 3),
                step_ms_after_warmup=[round(s * 1e3, 2) for s in secs[1:]],
                step_ms_median=round(median * 1e3, 2),
                tokens_per_second=round(batch * seq / median, 1))


def peak_bytes(jax):
    stats = [d.memory_stats() for d in jax.devices()]
    return [s.get("peak_bytes_in_use") if s else None for s in stats]


def phase_train(args, jax, paddle, sizes, meter):
    from paddle_tpu.models import GPTConfig

    n0, s0 = meter.read()
    net, losses, secs, _ = fit_gpt(args, jax, paddle, sizes)
    loss_checks(losses, GPTConfig(**sizes["model"]).vocab_size)
    n1, s1 = meter.read()
    emit("train", model="gpt2-124m" if not sizes["model"] else sizes["model"],
         params=sum(math.prod(p.shape) for p in net.parameters()),
         batch=sizes["batch"], seq=sizes["seq"], amp="bfloat16",
         optimizer="AdamW", loss=[round(v, 4) for v in losses],
         compile_seconds=round(s1 - s0, 2), executables=n1 - n0,
         **step_stats(secs, sizes["batch"], sizes["seq"]),
         timed_with="a user callback: loss fetched and weights written "
                    "back each step",
         peak_bytes_in_use=peak_bytes(jax)[0])
    return net


# ---------------------------------------------------------------------------
# serve: the trained weights in bf16 behind ServingServer
# ---------------------------------------------------------------------------
def reference_margins(jax, paddle, ref_net, seqs):
    """Teacher-forced float32 logits of `ref_net` (XLA composites, no Pallas
    kernel, no autocast) over equal-length `seqs`: argmax and the gap
    between the two best logits at every position."""
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.nn.layer_base import functional_call, state_pytrees

    params, buffers = state_pytrees(ref_net)

    def logits(p, ids):
        out, _ = functional_call(ref_net, p, (paddle.Tensor(ids),),
                                 buffers=buffers)
        top = jax.lax.top_k(out.value.astype(jnp.float32), 2)
        return top[1][..., 0], top[0][..., 0] - top[0][..., 1]

    kernels_were = paddle.get_flags("FLAGS_use_pallas_kernels")
    paddle.set_flags({"FLAGS_use_pallas_kernels": False})
    try:
        best, gap = jax.jit(logits)(params, jnp.asarray(seqs, jnp.int32))
    finally:
        paddle.set_flags(kernels_were)
    return np.asarray(best), np.asarray(gap)


def first_difference(a, b):
    return next((i for i, (x, y) in enumerate(zip(a, b)) if x != y), None)


def phase_serve(args, jax, paddle, sizes, net, meter):
    import numpy as np

    from paddle_tpu.models import GPTForCausalLM
    from paddle_tpu.serving import GenerationEngine, ServingServer
    from paddle_tpu.serving.client import ServingClient

    sv = sizes["serve"]
    new, vocab = sv["new_tokens"], net.cfg.vocab_size
    # the float32 copy stays behind as the reference; the server gets bf16
    ref_net = GPTForCausalLM(net.cfg)
    ref_net.set_state_dict(net.state_dict())
    ref_net.eval()
    net.astype("bfloat16")
    net.eval()

    rs = np.random.RandomState(args.seed + 1)
    short = zipf_tokens(rs, (sv["prompt_len"],), vocab).tolist()
    prefix = zipf_tokens(rs, (sv["prefix_len"],), vocab).tolist()
    tail = sv["long_len"] - sv["prefix_len"]
    long_a = prefix + zipf_tokens(rs, (tail,), vocab).tolist()
    long_b = prefix + zipf_tokens(rs, (tail,), vocab).tolist()

    engine = GenerationEngine(
        net, max_slots=sv["slots"], max_seq_len=sv["max_seq_len"],
        prompt_buckets=sv["buckets"], page_size=sv["page"],
        prefix_cache=True)
    server = ServingServer(None, gen_engine=engine, port=0,
                           install_signal_handlers=False)
    n0, s0 = meter.read()
    t0 = time.perf_counter()
    server.start()
    warmup_s = time.perf_counter() - t0
    n1, s1 = meter.read()
    try:
        warm_compiles = engine.compile_count
        client = ServingClient(server.url, timeout=300.0)
        blocking = client.generate(short, new)
        streamed = [e["token"] for e in client.generate_stream(short, new)
                    if "token" in e]
        got_a = client.generate(long_a, new)   # fills the prefix cache
        got_b = client.generate(long_b, new)   # shares its first pages
        snap = engine.metrics.snapshot()
        n2, _ = meter.read()
    finally:
        drained = server.shutdown()
    check(drained, "server did not drain cleanly")
    served = {"short": blocking["tokens"], "long_a": got_a["tokens"],
              "long_b": got_b["tokens"]}
    for name, toks in list(served.items()) + [("streamed", streamed)]:
        check(len(toks) == new and all(0 <= t < vocab for t in toks),
              f"{name}: expected {new} token ids below {vocab}, got {toks}")
    check(n2 == n1 and engine.compile_count == warm_compiles,
          f"{n2 - n1} executables built after start()'s warm-up")
    check(snap["prefix_cache_hits"] >= 1,
          f"no prefix-cache hit among the requests: {snap}")

    # model.generate on the same prompts (rows repeated to a batch of 8 so
    # that every call in it tiles for the kernels), then the float32 margins
    def solo(prompts):
        rows = [p for p in prompts for _ in range(8 // len(prompts))]
        out = np.asarray(net.generate(np.asarray(rows, np.int32),
                                      max_new_tokens=new).value)
        return [out[i * (8 // len(prompts)), len(prompts[0]):].tolist()
                for i in range(len(prompts))]

    generated = dict(zip(("short", "long_a", "long_b"),
                         solo([short]) + solo([long_a, long_b])))
    prompts = {"short": short, "long_a": long_a, "long_b": long_b}

    def margins(names):
        seqs = [prompts[n] + served[n][:-1] for n in names]
        best, gap = reference_margins(jax, paddle, ref_net, seqs)
        at = len(prompts[names[0]]) - 1
        return {n: (best[i, at:], gap[i, at:]) for i, n in enumerate(names)}

    ref = {**margins(["short"]), **margins(["long_a", "long_b"])}
    agree_ref = agree_gen = checked = 0
    thin = []          # margins where two paths were allowed to differ
    for name, toks in served.items():
        best, gap = ref[name]
        for i, tok in enumerate(toks):
            if gap[i] > MARGIN_TOL:
                checked += 1
                check(tok == best[i],
                      f"{name}[{i}]: served {tok}, float32 reference "
                      f"{best[i]} with margin {gap[i]:.3f}")
            agree_ref += int(tok == best[i])
        others = [("model.generate", generated[name])]
        if name == "short":
            others.append(("streamed", streamed))
        for label, other in others:
            d = first_difference(toks, other)
            if label == "model.generate":
                agree_gen += new if d is None else d
            if d is not None:
                check(gap[d] <= MARGIN_TOL,
                      f"{name}[{d}]: served {toks[d]} but {label} "
                      f"{other[d]}, reference margin {gap[d]:.3f}")
                thin.append(round(float(gap[d]), 4))
    emit("serve", slots=sv["slots"], max_seq_len=sv["max_seq_len"],
         page_size=sv["page"], prompt_buckets=sv["buckets"],
         prompt_lens=[len(short), len(long_a), len(long_b)],
         shared_prefix=sv["prefix_len"], new_tokens=new, weights="bfloat16",
         warmup_seconds=round(warmup_s, 2),
         compile_seconds=round(s1 - s0, 2), executables_at_warmup=n1 - n0,
         executables_after_warmup=n2 - n1,
         stream_equals_blocking=streamed == served["short"],
         margin_tolerance=MARGIN_TOL,
         tokens_served=3 * new, positions_above_tolerance=checked,
         agree_with_float32_reference=agree_ref,
         agree_with_model_generate=agree_gen,
         margins_at_disagreements=thin,
         smallest_margin_at_disagreement=min(thin) if thin else None,
         prefix_cache_hits=snap["prefix_cache_hits"],
         prefix_cache_misses=snap["prefix_cache_misses"],
         ttft_ms={"short": blocking["ttft_ms"], "long_a": got_a["ttft_ms"],
                  "long_b_prefix_hit": got_b["ttft_ms"]},
         latency_ms={"short": blocking["latency_ms"],
                     "long_a": got_a["latency_ms"],
                     "long_b_prefix_hit": got_b["latency_ms"]},
         inter_token_p50_ms=snap["inter_token_p50_ms"],
         drained=drained, peak_bytes_in_use=peak_bytes(jax)[0])


# ---------------------------------------------------------------------------
# --chips 4: Model.fit(layout=...) on a 2x2 mesh against one device
# ---------------------------------------------------------------------------
def mesh_census(jax, state):
    """Where the engine's parameters and optimizer state live: the repo's
    buffer census (logical bytes against the largest shard's) and the bytes
    each device holds of them."""
    from paddle_tpu.monitor import perf

    owned = {"params": (state["trainable"], state["frozen"]),
             "opt_state": state["opt"]}
    census = perf.buffer_census(owners=owned)
    out = {}
    for tag, tree in owned.items():
        per_device = {}
        for leaf in jax.tree_util.tree_leaves(tree):
            for shard in leaf.addressable_shards:
                per_device[str(shard.device)] = \
                    per_device.get(str(shard.device), 0) + shard.data.nbytes
        out[tag] = dict(
            bytes=census["by_tag"].get(tag, 0),
            largest_shard_bytes=sum(b["shard_bytes"]
                                    for b in census["buckets"]
                                    if b["tag"] == tag),
            bytes_per_device=per_device)
    return out


def phase_train_mesh(args, jax, paddle, sizes, meter):
    from paddle_tpu.models import GPTConfig

    vocab = GPTConfig(**sizes["model"]).vocab_size
    mesh = {"fsdp": 2, "tp": 2}
    _, one, one_secs, _ = fit_gpt(args, jax, paddle, sizes)
    loss_checks(one, vocab)
    peak_one = peak_bytes(jax)
    _, many, many_secs, census = fit_gpt(args, jax, paddle, sizes, mesh=mesh)
    loss_checks(many, vocab)
    gaps = [abs(a - b) for a, b in zip(one, many)]
    check(max(gaps) <= MESH_LOSS_TOL,
          f"sharded and one-device losses differ by {max(gaps):.4f} > "
          f"{MESH_LOSS_TOL}: {one} vs {many}")
    for tag, c in census.items():
        held = c["bytes_per_device"]
        check(len(held) == 4 and min(held.values()) > 0,
              f"{tag} is not on four devices: {held}")
        check(max(held.values()) < 0.5 * c["bytes"],
              f"{tag}: one device holds {max(held.values())} of "
              f"{c['bytes']} bytes — not sharded")
    _, secs = meter.read()
    emit("train_mesh", mesh=mesh, layout="SpecLayout()", batch=sizes["batch"],
         seq=sizes["seq"], loss_one_device=[round(v, 4) for v in one],
         loss_mesh=[round(v, 4) for v in many],
         max_loss_gap=round(max(gaps), 4), loss_tolerance=MESH_LOSS_TOL,
         census=census, compile_seconds=round(secs, 2),
         one_device=step_stats(one_secs, sizes["batch"], sizes["seq"]),
         mesh_2x2=step_stats(many_secs, sizes["batch"], sizes["seq"]),
         peak_bytes_in_use_after_one_device=peak_one,
         peak_bytes_in_use=peak_bytes(jax))


# ---------------------------------------------------------------------------
def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only Model.fit on a 2x2 mesh against one device")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on whatever jax.devices() gives; never "
                         "prints the result line of a chip run")
    ap.add_argument("--rehearse-fail", choices=PHASES, default=None,
                    help="with --rehearse: fail this phase (the tests use it "
                         "to see the exit code)")
    args = ap.parse_args(argv)
    if args.rehearse_fail and not args.rehearse:
        ap.error("--rehearse-fail needs --rehearse")
    if args.rehearse:
        # the chip runs 32-bit; rehearse that regime, not the CPU's x64
        os.environ.setdefault("PADDLE_TPU_ENABLE_X64", "0")

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import jax

    platform = jax.devices()[0].platform
    if platform != "tpu" and not args.rehearse:
        sys.stderr.write(
            f"chip_smoke: no TPU: jax.devices() = {jax.devices()}; this "
            "script does not fall back (see --rehearse)\n")
        return 1
    import paddle_tpu as paddle
    from paddle_tpu.ops import fused

    sizes = TINY if args.rehearse else REAL
    meter = CompileMeter(jax)
    done = []

    def ran(phase):
        check(args.rehearse_fail != phase, f"{phase}: failure asked for")
        done.append(phase)

    info = phase_device(args, jax)
    ran("device")
    if args.chips == 4:
        phase_train_mesh(args, jax, paddle, sizes, meter)
        ran("train_mesh")
    else:
        phase_kernels(args, jax, sizes)
        ran("kernels")
        net = phase_train(args, jax, paddle, sizes, meter)
        ran("train")
        phase_serve(args, jax, paddle, sizes, net, meter)
        ran("serve")
    fallbacks = {"/".join(k): v
                 for k, v in fused.fallback_counter().values.items() if v}
    executables, secs = meter.read()
    emit("summary", phases=done, compile_seconds_total=round(secs, 2),
         executables_total=executables,
         paddle_pallas_fallbacks_total=sum(fallbacks.values()),
         fallbacks=fallbacks)
    check(not fallbacks, f"a kernel gave way to the composite: {fallbacks}")
    device = {"platform": info["platform"], "kind": info["kind"],
              "count": info["count"]}
    if args.rehearse:
        print(json.dumps({"rehearsal": True, "phases": done,
                          "device": device}), flush=True)
    else:
        print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
