"""Traffic kind `serve`: a `GenerationEngine` behind `ServingServer`,
reached over HTTP with SSE by the load generator, a child process that
imports no jax.  The traffic file's `mode` is `open` (requests sent on a
schedule, whatever came before) or `closed` (`clients` clients, each
sending its next request when its last one ended).

Set-up: weights on the device from the seed in one jitted call, in the
served dtype; `server.start()` (which compiles or loads every
executable); one warm-up request per compiled shape; the load
generator's ramp.  The window is `--seconds` long.  Afterwards the server
is drained and its device state freed, and only then does the reference
run: over a seeded sample of the requests the window finished, the
longest among them, it reads how far each served token's logit lies below
the reference's best.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import time

import numpy as np

from benchmarks import common, stats, traffic as gen

LOADGEN = os.path.join(common.HERE, "loadgen.py")


def loadgen(url, reqs, mode, tmp, tag, start_at=0.0, seconds=0.0, clients=1,
            drain=0.0):
    """Start the load generator on `reqs`; returns (process, out path)."""
    sched = os.path.join(tmp, f"{tag}-schedule.json")
    out = os.path.join(tmp, f"{tag}-records.json")
    with open(sched, "w") as f:
        json.dump(reqs, f)
    proc = subprocess.Popen(
        [sys.executable, LOADGEN, "--url", url, "--schedule", sched, "--out",
         out, "--mode", mode, "--start-at", repr(start_at), "--seconds",
         repr(seconds), "--clients", str(clients), "--drain", repr(drain)])
    return proc, out


def finish(proc, out, timeout):
    try:
        rc = proc.wait(timeout)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait()
        raise common.BenchFailure("the load generator did not end")
    if rc != 0:
        raise common.BenchFailure(f"the load generator exited with {rc}")
    with open(out) as f:
        return json.load(f)


class Served:
    """The program, started and warm: weights from the seed, the server
    listening, every compiled shape run once.  `window()` offers one
    stretch of load and may be called again (the rate sweep does)."""

    def __init__(self, run):
        import jax
        import jax.numpy as jnp

        self.run, self.spec = run, run.traffic
        cfg = run.config["model"]
        self.ref = common.plugin("reference", run.config["reference"])
        self.adapter = common.plugin("adapters", run.config["adapter"])
        self.vocab = cfg["vocab_size"]
        dtype = self.spec["weights_dtype"]
        self.make = jax.jit(lambda key: self.ref.init_weights(
            key, cfg, jnp.dtype(dtype)))
        weights = self.make(self.ref.key_from_seed(run.seed))
        self.net = self.adapter.build_network(cfg, weights, dtype)
        del weights
        self.server, self.engine = self.adapter.build_server(
            self.net, self.spec["engine"], trace_spans=run.trace)
        self.tmp = tempfile.TemporaryDirectory()
        self.drained = None
        run.phases.mark("weights_and_model")
        try:
            self.server.start()
            run.phases.mark("server_start_compiles")
            warm = gen.warmup_requests(self.spec, run.seed, self.vocab)
            recs = finish(*loadgen(self.server.url, warm, "serial",
                                   self.tmp.name, "warm"), timeout=300)
            bad = [r for r in recs["records"]
                   if r["error"] or len(r["tokens"]) != 4]
            if bad:
                raise common.BenchFailure(f"warm-up failed: {bad[:2]}")
            run.phases.mark("warm_up_requests")
        except BaseException:
            self.close()
            raise

    def window(self, reqs, seconds, ramp, tag="window"):
        """Offer `reqs` for ramp + seconds; the window is the last
        `seconds`.  Returns what the run's readers need."""
        import jax

        run, spec, adapter = self.run, self.spec, self.adapter
        start = time.monotonic() + spec["start_delay_s"]
        proc, out = loadgen(self.server.url, reqs, spec["mode"],
                            self.tmp.name, tag,
                            start_at=start, seconds=ramp + seconds,
                            clients=spec.get("clients", 1),
                            drain=spec["drain_s"])
        t0, t1 = start + ramp, start + ramp + seconds
        time.sleep(max(0.0, t0 - time.monotonic()))
        if run.trace:
            from benchmarks import trace

            trace.start(run)
        wall0 = time.time()
        c0 = adapter.engine_counters(self.engine)
        n0 = run.meter.read()[0]
        occupancy = []
        with jax.profiler.TraceAnnotation("bench.window"):
            t0 = time.monotonic()
            while time.monotonic() < t1:
                time.sleep(min(1.0, max(0.0, t1 - time.monotonic())))
                occupancy.append(adapter.slot_occupancy(self.engine))
            t1 = time.monotonic()
        c1 = adapter.engine_counters(self.engine)
        n1 = run.meter.read()[0]
        wall1 = time.time()
        if run.trace:
            trace.stop(run)
        client = finish(proc, out, timeout=spec["drain_s"] + 120)
        client["collected_until"] = client["end"] + spec["drain_s"]
        spans = [s for s in adapter.finished_spans()
                 if wall0 * 1e3 <= s["ts_ms"] < wall1 * 1e3]
        return dict(t0=t0, t1=t1, client=client, spans=spans,
                    counters={k: c1[k] - c0[k] for k in c1},
                    occupancy=occupancy, compiles=n1 - n0)

    def close(self):
        if self.drained is None:
            self.drained = self.server.shutdown()
            self.tmp.cleanup()
        return self.drained

    def free(self):
        self.adapter.free_server(self.server, self.engine, self.net)
        self.server = self.engine = self.net = None


def drive(run):
    import jax

    spec, cfg = run.traffic, run.config["model"]
    seconds = min(run.seconds, spec["trace_seconds"]) if run.trace \
        else run.seconds
    ramp = spec["ramp_s"]
    served = Served(run)
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

    # the medians and the other quantiles beside the metrics, not as metrics
    ttft, itl = cp.samples(run, "ttft"), cp.samples(run, "itl")
    common.note(window_s=t1 - t0, setup_s=run.setup_s, requests_due=len(due),
                failed=run.failed, tokens_received_in_window=tokens_in,
                ttft_ms=stats.summary(ttft), itl_ms=stats.summary(itl),
                counters=run.counters, fallbacks=fallbacks, drained=drained,
                setup_phases=run.phases.rows)
    if run.keep_records:
        keep_records(run, records)

    # -- correctness: the program's state goes first, then the reference
    served.free()
    done = [r for r in records
            if r["done"] and not r["error"] and not r["cut"]
            and len(r["tokens"]) == by_id[r["id"]]["max_new"]
            and t0 <= r["t"][-1] < client["collected_until"]]
    checks = run.checks
    # a traced run's window is short: it checks what finished, and needs
    # fewer to have finished
    need = spec["check_requests_traced" if run.trace else "check_requests"]
    checks.add("requests_finished_to_check", len(done), need,
               ok=len(done) >= need, note="at least as many as the sample")
    if len(done) >= need:
        rng = np.random.default_rng([int(run.seed), 0xC0C])
        longest = max(done, key=lambda r: len(by_id[r["id"]]["prompt"])
                      + len(r["tokens"]))
        rest = [r for r in done if r is not longest]
        pick = [longest] + [rest[i] for i in rng.permutation(len(rest))
                            [:spec["check_requests"] - 1]]
        pairs = [(by_id[r["id"]]["prompt"], list(r["tokens"]))
                 for r in pick]
        breaker = getattr(run, "break_served", None)
        if breaker:                 # tests: a token altered as if served so
            pairs = breaker(pairs)
        t_ref = time.monotonic()
        res = served_token_gaps(
            ref, cfg, served.make(ref.key_from_seed(run.seed)), pairs,
            spec["engine"]["max_seq_len"], spec["output"]["max"],
            control=run.control)
        common.note(reference_seconds=time.monotonic() - t_ref,
                    checked_requests=len(pick), checked_tokens=res["tokens"],
                    served_equal_best=res["equal_best"])
        gap = res["control_gap_max"] if run.control else res["gap_max"]
        checks.add("served_token_logit_gap_max", gap,
                   spec["limits"]["served_gap"],
                   note=(f"CONTROL: the token {run.control} puts first; the "
                         f"program's own gap was {res['gap_max']}"
                         if run.control else
                         "widest gap by which a served token's float32 "
                         "reference logit lies below the reference's best"))
    checks.add("requests_failed_or_refused", run.failed, 0)
    checks.add("executables_built_in_window", w["compiles"], 0)
    checks.add("engine_compile_count_grew",
               run.counters.get("compile_count", 0), 0)
    checks.add("pallas_fallbacks", fallbacks, 0)
    checks.add("server_drained", 0 if drained else 1, 0)


def keep_records(run, records):
    """`--keep-records DIR`: what the client stamped, without the tokens,
    for a look at a window after the run (times from the window's start)."""
    t0, t1 = run.window
    rows = [{"id": r["id"], "prompt": len(run.requests[r["id"]]["prompt"]),
             "max_new": run.requests[r["id"]]["max_new"],
             "due": r["due"] - t0,
             "sent": None if r["sent"] is None else r["sent"] - t0,
             "ended": None if r["ended"] is None else r["ended"] - t0,
             "status": r["status"], "error": r["error"], "cut": r["cut"],
             "engine_ttft_ms": (r["done"] or {}).get("ttft_ms"),
             "t": [round(t - t0, 5) for t in r["t"]]} for r in records]
    os.makedirs(run.keep_records, exist_ok=True)
    path = os.path.join(run.keep_records,
                        f"{run.cell['name']}.{run.seed}.records.json")
    with open(path, "w") as f:
        json.dump({"window_s": t1 - t0, "records": rows}, f)


def served_token_gaps(ref, cfg, w, pairs, t_pad, p_pad, control=None):
    """For each (prompt, served tokens): the float32 reference's logits at
    the served positions.  Returns the widest gap of a served token below
    the reference's best; with `control` (a lower precision) also the
    widest gap of the token that precision puts first."""
    import jax
    import jax.numpy as jnp

    def one(w_, ids, pos, served):
        lg = ref.logits_at(w_, ids, pos, cfg, "f32")
        best = lg.max(-1)
        out = {"gap": best - jnp.take_along_axis(lg, served[:, None], 1)[:, 0],
               "equal": lg.argmax(-1) == served}
        if control:
            low = ref.logits_at(w_, ids, pos, cfg, control).argmax(-1)
            out["control_gap"] = best - jnp.take_along_axis(
                lg, low[:, None], 1)[:, 0]
        return out

    fn = jax.jit(one)
    gap_max = ctl_max = 0.0
    n_tok = n_eq = 0
    for prompt, served in pairs:
        n = len(served)
        ids = np.zeros((t_pad,), np.int32)
        seq = list(prompt) + list(served[:-1])
        ids[:len(seq)] = seq
        pos = np.full((p_pad,), len(prompt) - 1, np.int32)
        pos[:n] = np.arange(len(prompt) - 1, len(seq))
        tok = np.zeros((p_pad,), np.int32)
        tok[:n] = served
        out = jax.device_get(fn(w, ids, pos, tok))
        gap_max = max(gap_max, float(out["gap"][:n].max()))
        n_tok += n
        n_eq += int(out["equal"][:n].sum())
        if control:
            ctl_max = max(ctl_max, float(out["control_gap"][:n].max()))
    res = {"gap_max": gap_max, "tokens": n_tok, "equal_best": n_eq}
    if control:
        res["control_gap_max"] = ctl_max
    return res
