"""Traffic kind `serve_blocks`: `serve`'s server, load generator and window
(imported, not copied) for a model that generates by diffusion over
blocks, with the comparison that decides `correct` for such a model.

`serve`'s check assumes one token from the row before it.  Here a block of
B positions starts masked, each denoising step unmasks some, and the
stream's last event says at which step each token was unmasked.  From
that the check rebuilds every block as the engine had it before each
step and asks the float32 reference two things at the positions the step
unmasked:
  served_gap  how far the served token's reference logit lies below the
              reference's best at that position
  order_gap   how far the reference's confidence (the log probability of
              its best token) at the chosen position lies below that of
              the most confident position still masked: 0 where the
              reference would have unmasked the same position
over a sample of finished requests (the longest and a seeded pick), a
seeded sample of their whole blocks, and every step of those.

One reference pass a request, no cache: under the block mask a committed
position's state depends on nothing after its block, so the request's
final sequence is the context of all its rebuilt blocks at once.  The
sequence is the final tokens followed by the rebuilt blocks as further
rows at their own positions, each seeing the final rows of earlier blocks
and its own rows (`positions` and `mask` are the reference's inputs).
The six float32 layers (17.4 GB) never lie on the chip together: the
layers are made from the seed and run one at a time.
"""
from __future__ import annotations

import time

import numpy as np

from benchmarks import common, stats, traffic as gen
from benchmarks.kinds import serve


def drive(run):
    from paddle_tpu.serving.kv_cache import CacheGeometry

    if not hasattr(CacheGeometry, "block_length"):
        # a program from before generation by blocks: fail cleanly, now,
        # not after 8.7 GB of weights are on the chip
        raise common.BenchFailure(
            "this program's GenerationEngine cannot generate by blocks")
    import jax

    spec, cfg = run.traffic, run.config["model"]
    seconds = min(run.seconds, spec["trace_seconds"]) if run.trace \
        else run.seconds
    ramp = spec["ramp_s"]
    served = serve.Served(run)
    ref, vocab = served.ref, served.vocab
    reqs = gen.serve_schedule(spec, run.seed, ramp + seconds, vocab)
    by_id = {r["id"]: r for r in reqs}
    try:
        w = served.window(reqs, seconds, ramp)
        run.device = common.device_info(jax, run.cell["chips"])
    finally:
        drained = served.close()
    t0, t1, client = w["t0"], w["t1"], w["client"]
    run.setup_s = t0 - common.T_PROCESS
    run.window = (t0, t1)
    run.client = client
    run.spans = w["spans"]
    run.counters = w["counters"]
    run.gauges = {"slot_occupancy": w["occupancy"]}
    records = client["records"]
    run.requests = by_id
    due = [r for r in records if t0 <= r["due"] < t1]
    run.attempted = len(due)
    run.failed = sum(1 for r in due if r["error"] or r["status"] != 200)
    tokens_in = sum(len([t for t in r["t"] if t0 <= t < t1])
                    for r in records)
    run.counts = {"client_tokens": tokens_in}
    fallbacks = served.adapter.pallas_fallbacks()
    from benchmarks.readers import client_percentile as cp

    ttft, itl = cp.samples(run, "ttft"), cp.samples(run, "itl")
    block_gaps = [g for g in itl if g > 1.0]    # the gaps between blocks
    counters = {k: v for k, v in run.counters.items()
                if not k.startswith("moe_assignments.")}
    common.note(window_s=t1 - t0, setup_s=run.setup_s, requests_due=len(due),
                failed=run.failed, tokens_received_in_window=tokens_in,
                ttft_ms=stats.summary(ttft), itl_ms=stats.summary(itl),
                block_gap_ms=stats.summary(block_gaps),
                counters=counters, fallbacks=fallbacks, drained=drained,
                setup_phases=run.phases.rows)
    if run.keep_records:
        serve.keep_records(run, records)

    # -- correctness: the program's state goes first, then the reference
    served.free()
    done = [r for r in records
            if r["done"] and not r["error"] and not r["cut"]
            and len(r["tokens"]) == by_id[r["id"]]["max_new"]
            and len(r["done"].get("steps") or ()) == len(r["tokens"])
            and t0 <= r["t"][-1] < client["collected_until"]]
    checks = run.checks
    need = spec["check_requests_traced" if run.trace else "check_requests"]
    checks.add("requests_finished_to_check", len(done), need,
               ok=len(done) >= need, note="at least as many as the sample")
    if len(done) >= need:
        rng = np.random.default_rng([int(run.seed), 0xB10C])
        longest = max(done, key=lambda r: len(by_id[r["id"]]["prompt"])
                      + len(r["tokens"]))
        rest = [r for r in done if r is not longest]
        pick = [longest] + [rest[i] for i in rng.permutation(len(rest))
                            [:need - 1]]
        triples = [(by_id[r["id"]]["prompt"], list(r["tokens"]),
                    list(r["done"]["steps"])) for r in pick]
        breaker = getattr(run, "break_served", None)
        if breaker:                 # tests: a token altered as if served so
            triples = breaker(triples)
        t_ref = time.monotonic()
        res = block_gaps_of(ref, cfg, ref.key_from_seed(run.seed),
                            spec["weights_dtype"], triples,
                            spec["engine"]["max_seq_len"],
                            spec["check_blocks"], rng, control=run.control)
        common.note(reference_seconds=time.monotonic() - t_ref,
                    checked_requests=len(pick), checked_blocks=res["blocks"],
                    checked_steps=res["steps"], checked_tokens=res["tokens"],
                    served_equal_best=res["equal_best"],
                    order_equal_best=res["order_equal"])
        for name, what in (
                ("served_gap", "widest gap by which a served token's "
                 "float32 reference logit lies below the reference's best "
                 "at its position, in the block as the engine had it"),
                ("order_gap", "widest gap by which the reference's "
                 "confidence at the position a step unmasked lies below "
                 "its confidence at the best position still masked")):
            value = res["control_" + name] if run.control else res[name]
            checks.add(f"{name}_max", value, spec["limits"][name], note=(
                f"CONTROL: what {run.control} puts first; the program's "
                f"own gap was {res[name]}" if run.control else what))
    checks.add("requests_failed_or_refused", run.failed, 0)
    checks.add("executables_built_in_window", w["compiles"], 0)
    checks.add("engine_compile_count_grew",
               run.counters.get("compile_count", 0), 0)
    checks.add("pallas_fallbacks", fallbacks, 0)
    checks.add("server_drained", 0 if drained else 1, 0)


def rebuilt_blocks(prompt, tokens, steps, block, n_blocks, rng):
    """A seeded sample of a request's whole generated blocks, each as the
    engine had it before every one of its denoising steps: [(block start,
    step, [known or None a position], [positions the step unmasked])]."""
    L, end = len(prompt), len(prompt) + len(tokens)
    seq = list(prompt) + list(tokens)
    step_at = [-1] * L + list(steps)
    whole = [b for b in range(L // block, end // block)
             if (b + 1) * block <= end]
    out = []
    for b in sorted(rng.permutation(whole)[:n_blocks].tolist()):
        lo = b * block
        for t in sorted({step_at[p] for p in range(lo, lo + block)} - {-1}):
            state = [seq[p] if step_at[p] < t else None
                     for p in range(lo, lo + block)]
            out.append((lo, t, state, [p - lo for p in range(lo, lo + block)
                                       if step_at[p] == t]))
    return out


def block_gaps_of(ref, cfg, key, dtype, triples, t_pad, n_blocks, rng,
                  control=None):
    """The two gaps over the sampled requests (see the module's text); with
    `control` (a lower precision) also the gaps of what that precision
    puts first.  One layer's weights at a time."""
    import jax
    import jax.numpy as jnp

    B, mask_id = cfg["block_length"], cfg["mask_token_id"]
    steps_max = cfg["denoising_steps"]
    extra = n_blocks * steps_max * B
    T = t_pad + extra
    seqs = []
    for prompt, tokens, steps in triples:
        cases = rebuilt_blocks(prompt, tokens, steps, B, n_blocks, rng)
        n = len(prompt) + len(tokens)
        ids = np.zeros((T,), np.int32)
        ids[:n] = list(prompt) + list(tokens)
        pos = np.arange(T, dtype=np.int32)
        blk = np.arange(T) // B
        mask = blk[None, :] <= blk[:, None]
        mask[:, t_pad:] = False
        mask[t_pad:, :] = False
        for c, (lo, _, state, _) in enumerate(cases):
            r0 = t_pad + c * B
            ids[r0:r0 + B] = [mask_id if s is None else s for s in state]
            pos[r0:r0 + B] = np.arange(lo, lo + B)
            mask[r0:r0 + B, :lo] = True          # the committed prefix
            mask[r0:r0 + B, r0:r0 + B] = True    # the block, both ways
        for r in range(t_pad + len(cases) * B, T):
            mask[r, r] = True                    # unused rows: no NaN
        seqs.append((cases, ids, pos, mask, list(prompt) + list(tokens)))

    precisions = ("f32",) + ((control,) if control else ())
    make_top = jax.jit(lambda k: ref.top_weights(k, cfg, jnp.dtype(dtype)))
    make_layer = jax.jit(
        lambda k, i: ref.layer_weights(k, cfg, i, jnp.dtype(dtype)))
    run_layer = {p: jax.jit(lambda x, w, pos, mask, p=p: ref.layer(
        x, w, pos, mask, cfg, p)) for p in precisions}
    top = make_top(key)
    xs = {(p, s): ref.embed(top, jnp.asarray(seq[1]))
          for p in precisions for s, seq in enumerate(seqs)}
    for i in range(cfg["num_hidden_layers"]):
        wl = make_layer(key, jnp.int32(i))
        for (p, s), x in xs.items():
            xs[p, s] = run_layer[p](x, wl, jnp.asarray(seqs[s][2]),
                                    jnp.asarray(seqs[s][3]))
        del wl
    heads = {p: jax.jit(lambda w, x, p=p: ref.head(w, x, cfg, p))
             for p in precisions}

    out = {"served_gap": 0.0, "order_gap": 0.0, "blocks": 0, "steps": 0,
           "tokens": 0, "equal_best": 0, "order_equal": 0}
    if control:
        out.update(control_served_gap=0.0, control_order_gap=0.0)
    for s, (cases, _, _, _, final) in enumerate(seqs):
        rows = slice(t_pad, t_pad + len(cases) * B)
        lg = {p: np.asarray(heads[p](top, xs[p, s][rows]), np.float32)
              for p in precisions}
        out["blocks"] += len({lo for lo, _, _, _ in cases})
        for c, (lo, _, state, chosen) in enumerate(cases):
            z = lg["f32"][c * B:(c + 1) * B]                 # [B, V]
            best = z.max(-1)
            conf = -np.log(np.exp(z - best[:, None]).sum(-1))
            masked = [i for i in range(B) if state[i] is None]
            top_conf = max(conf[i] for i in masked)
            out["steps"] += 1
            for i in chosen:
                served = final[lo + i]
                out["tokens"] += 1
                out["equal_best"] += int(z[i].argmax() == served)
                out["order_equal"] += int(conf[i] == top_conf)
                out["served_gap"] = max(out["served_gap"],
                                        float(best[i] - z[i, served]))
                out["order_gap"] = max(out["order_gap"],
                                       float(top_conf - conf[i]))
            if control:
                zc = lg[control][c * B:(c + 1) * B]
                cc = -np.log(np.exp(zc - zc.max(-1, keepdims=True)).sum(-1))
                # what the lower precision would unmask here, and with
                # which token, judged by the float32 reference
                order = sorted(masked, key=lambda i: (-cc[i], i))
                for i in order[:len(chosen)]:
                    out["control_served_gap"] = max(
                        out["control_served_gap"],
                        float(best[i] - z[i, zc[i].argmax()]))
                    out["control_order_gap"] = max(
                        out["control_order_gap"],
                        float(top_conf - conf[i]))
    return out
