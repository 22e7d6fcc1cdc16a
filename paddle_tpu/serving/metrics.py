"""Serving metrics: counters, histograms, and a Prometheus text endpoint.

Built on the shared, dependency-free registry in `utils/metrics.py`
(counters/gauges/histograms/reservoir quantiles, one lock, Prometheus
text exposition) — `Histogram` is re-exported from there unchanged, and
`ServingMetrics` is now a declaration of serving's metric catalog over a
private `MetricsRegistry` instance (private so multiple engines in one
process don't collide).  The exposition output is BYTE-IDENTICAL to the
pre-registry module — tests/test_monitor.py pins the golden text.

Quantiles (p50/p99) come from a bounded reservoir of recent request
latencies rather than histogram interpolation, so a smoke test scraping
`paddle_serving_p99_ms` reads an exact order statistic over the last
window instead of a bucket-boundary estimate.
"""
from __future__ import annotations

import collections
import time

from ..utils.metrics import Histogram, MetricsRegistry, Reservoir

__all__ = ["Histogram", "ServingMetrics", "GenerationMetrics",
           "RouterMetrics"]


class ServingMetrics:
    """All engine/server observability state, rendered as Prometheus text.

    Exposes (scraped by tools/serve_smoke.sh):
      paddle_serving_qps                    completions/s over the window
      paddle_serving_p50_ms / _p99_ms       request latency order stats
      paddle_serving_batch_size             batch-size histogram
      paddle_serving_queue_latency_ms       submit→dispatch wait histogram
      paddle_serving_padding_waste_ratio    padded slots / total slots
      paddle_serving_requests_total{...}    accepted/rejected/… counters
      paddle_serving_compile_count          predictor bucket compiles
    """

    QPS_WINDOW_S = 60.0
    RESERVOIR = 4096

    def __init__(self):
        self.registry = MetricsRegistry()
        # the registry's RLock is THE lock (one lock for batcher thread,
        # N HTTP handler threads, and the /metrics scraper); computed
        # gauges run under it at scrape time, hence the *_locked helpers
        self._lock = self.registry._lock
        self.started_at = time.monotonic()
        reg = self.registry
        reg.gauge("paddle_serving_qps",
                  "completed requests per second over the trailing window",
                  fn=self._qps_locked)
        reg.gauge("paddle_serving_p50_ms",
                  "request latency p50 in milliseconds",
                  fn=lambda: self._quantile_locked(0.50))
        reg.gauge("paddle_serving_p99_ms",
                  "request latency p99 in milliseconds",
                  fn=lambda: self._quantile_locked(0.99))
        reg.gauge("paddle_serving_padding_waste_ratio",
                  "padded input elements / dispatched input elements "
                  "(batch-slot AND sequence padding)",
                  fn=self._waste_locked)
        reg.gauge("paddle_serving_compile_count",
                  "predictor shape-bucket compilations since start",
                  fn=lambda: self.compile_count)
        self._requests = reg.counter(
            "paddle_serving_requests_total",
            "request outcomes by result", label="result",
            preset=("accepted", "responses", "rejected_queue_full",
                    "rejected_draining", "deadline_expired", "cancelled",
                    "errors"),
            fixed=True)
        self.batch_size_hist = reg.histogram(
            "paddle_serving_batch_size",
            "requests coalesced per dispatched batch",
            [1, 2, 4, 8, 16, 32, 64, 128])
        self.queue_latency_hist = reg.histogram(
            "paddle_serving_queue_latency_ms",
            "milliseconds a request waited in the batch queue",
            [0.5, 1, 2, 5, 10, 20, 50, 100, 250, 500, 1000, 5000])
        self.request_latency_hist = reg.histogram(
            "paddle_serving_request_latency_ms",
            "end-to-end request latency in milliseconds",
            [1, 2, 5, 10, 20, 50, 100, 250, 500, 1000, 5000])
        self._latencies = collections.deque(maxlen=self.RESERVOIR)
        self._completions = collections.deque()  # monotonic stamps
        self.batch_slots_total = 0
        self.padded_slots_total = 0
        self.compile_count = 0

    @property
    def counters(self):
        """The request-outcome counts, dict-like (tests/engine read
        `metrics.counters["errors"]` as before the registry migration)."""
        return self._requests.values

    # -- recording hooks (engine/server threads) ---------------------------
    def count(self, name: str, n: int = 1):
        self._requests.inc(name, n)

    def observe_batch(self, n_requests: int, bucket_batch: int,
                      real_elems: int = None, total_elems: int = None):
        """Waste is counted in input ELEMENTS when provided (covers both
        batch-slot padding and sequence padding); falls back to
        slot-level accounting otherwise."""
        if total_elems is None:
            real_elems, total_elems = n_requests, bucket_batch
        with self._lock:
            self.batch_size_hist._observe_locked(n_requests)
            self.batch_slots_total += total_elems
            self.padded_slots_total += total_elems - real_elems

    def observe_queue_wait(self, seconds: float):
        self.queue_latency_hist.observe(seconds * 1e3)

    def observe_completion(self, latency_s: float):
        now = time.monotonic()
        with self._lock:
            self._requests.inc("responses")
            self.request_latency_hist._observe_locked(latency_s * 1e3)
            self._latencies.append(latency_s * 1e3)
            self._completions.append(now)
            cutoff = now - self.QPS_WINDOW_S
            while self._completions and self._completions[0] < cutoff:
                self._completions.popleft()

    def set_compile_count(self, n: int):
        with self._lock:
            self.compile_count = int(n)

    # -- derived values ----------------------------------------------------
    def _quantile_locked(self, q: float):
        if not self._latencies:
            return 0.0
        xs = sorted(self._latencies)
        idx = min(len(xs) - 1, max(0, int(round(q * (len(xs) - 1)))))
        return xs[idx]

    def _qps_locked(self, now=None):
        now = time.monotonic() if now is None else now
        if not self._completions:
            return 0.0
        span = max(1e-9, min(now - self.started_at, self.QPS_WINDOW_S))
        # ignore stamps older than the window (popped on observe, but the
        # deque can go stale when traffic stops)
        live = sum(1 for t in self._completions
                   if t >= now - self.QPS_WINDOW_S)
        return live / span

    def _waste_locked(self):
        return (self.padded_slots_total / self.batch_slots_total
                if self.batch_slots_total else 0.0)

    def snapshot(self) -> dict:
        """Programmatic view (tests, examples)."""
        with self._lock:
            return {
                "qps": round(self._qps_locked(), 2),
                "p50_ms": round(self._quantile_locked(0.50), 3),
                "p99_ms": round(self._quantile_locked(0.99), 3),
                "padding_waste_ratio": round(self._waste_locked(), 4),
                "batches": self.batch_size_hist.total,
                "mean_batch_size": round(
                    self.batch_size_hist.sum / self.batch_size_hist.total, 2)
                    if self.batch_size_hist.total else 0.0,
                "compile_count": self.compile_count,
                **{k: v for k, v in sorted(self.counters.items())},
            }

    def prometheus_text(self) -> str:
        return self.registry.prometheus_text()


class GenerationMetrics:
    """Decode-path observability for the continuous-batching generation
    engine (same private-registry pattern as ServingMetrics, so several
    engines coexist in one process).

    Exposes (scraped by tools/serve_smoke.sh; `snapshot()` is read, for
    counters and slot occupancy, by benchmarks/adapters/gpt.py; the quantiles are an operator's view —
    the benchmark times at the client).  "window" is the trailing
    WINDOW_S seconds, so a scrape after warm-up stops reporting it:
      paddle_genserve_decode_tokens_per_sec  tokens streamed / s (window)
      paddle_genserve_ttft_p50_ms / _p99_ms  time-to-first-token (window)
      paddle_genserve_inter_token_p50_ms / _p99_ms
                                             gap between a slot's tokens
                                             (window)
      paddle_genserve_slot_occupancy         occupied / max_slots
      paddle_genserve_page_occupancy         KV pages in use / num_pages
      paddle_genserve_tokens_total           generated tokens
      paddle_genserve_requests_total{result} admitted/retired/preempted/…
      paddle_genserve_prefix_cache_hits_total / _misses_total
                                             prefix-cache admissions
      paddle_genserve_prefix_cache_hit_ratio hits / (hits + misses)
      paddle_genserve_spec_accept_ratio      accepted / proposed drafts
      paddle_genserve_prefill_chunks_total   chunked-prefill slices run
      paddle_genserve_block_steps_total      iterations of a block engine
      paddle_genserve_block_lane_steps_total{kind}
                                             live lanes over those steps:
                                             denoised (took a denoising
                                             step) or committed (the final
                                             pass that writes a block's K/V)
      paddle_genserve_block_tokens_total     tokens the blocks emitted
      paddle_genserve_compile_count          executables built at warmup
      paddle_genserve_steps_total            steps launched
      paddle_genserve_steps_launched_ahead_total
                                             of them, launched while the
                                             step before was still on the
                                             device (the loop keeps one
                                             step in flight)
      paddle_genserve_empty_steps_total      steps nobody took a result of
      paddle_genserve_paged_page_slots_total page slots of the decode
                                             steps' page tables: steps x
                                             slots x columns, a layer
      paddle_genserve_paged_pages_live_total of them, pages the live
                                             lanes' extents cover (their
                                             ratio is the share of a walk
                                             over the whole table that is
                                             work)
      paddle_genserve_loop_seconds_total{phase}
                                             the decode thread's seconds
                                             by phase of its loop (the
                                             engine's StepTimers; top-level
                                             phases sum to its wall time,
                                             `a/b` ran under `a`)
      paddle_genserve_loop_iterations_total  decode-loop iterations
    and, for an engine whose model has window layers (two page pools,
    serving/kv_cache.py), from the registers each decode step reports:
      paddle_genserve_kv_pages_in_use{pool}  pages off the free stack of
                                             the "full" and the "window"
                                             pool
      paddle_genserve_kv_pages_mapped{pool}  page-table entries the live
                                             lanes hold (shared prefix
                                             pages among them)
      paddle_genserve_kv_window_pages_released_total
                                             window-pool pages let go
                                             behind a lane's window
      paddle_genserve_kv_mapped_page_steps_total{pool}
                                             kv_pages_mapped summed over
                                             the steps: the mean a step
                                             is a ratio of two counters
    and, for an engine whose model has layers with a recurrent state
    (snapshots of it beside the page pool, serving/kv_cache.py):
      paddle_genserve_state_restores_total   admissions whose scan started
                                             from a restored snapshot
      paddle_genserve_state_snapshots_total  snapshots prompt passes left
      paddle_genserve_state_snapshot_evictions_total
                                             snapshots that went: their
                                             place reused, or with their
                                             entry's pages
      paddle_genserve_state_snapshots_live   snapshots the pool holds
      paddle_genserve_state_lane_steps_total live lanes summed over the
                                             decode steps: the lane
                                             states the steps updated
      paddle_genserve_state_scans_total      prompt passes (admissions
                                             and chunks) that scanned
      paddle_genserve_state_scan_tokens_total
                                             the true (unpadded) tokens
                                             they scanned
    """

    WINDOW_S = 60.0
    RESERVOIR = 4096

    def __init__(self, max_slots: int = 1, num_pages: int = 1,
                 window_pool: bool = False, state_pool: bool = False):
        self.registry = MetricsRegistry()
        self._lock = self.registry._lock
        self.started_at = time.monotonic()
        self.max_slots = max(1, int(max_slots))
        self.num_pages = max(1, int(num_pages))
        reg = self.registry
        reg.gauge("paddle_genserve_decode_tokens_per_sec",
                  "generated tokens per second over the trailing window",
                  fn=self._tps_locked)
        reg.gauge("paddle_genserve_ttft_p50_ms",
                  "time-to-first-token p50 in milliseconds",
                  fn=lambda: self._ttft.quantile_locked(0.50))
        reg.gauge("paddle_genserve_ttft_p99_ms",
                  "time-to-first-token p99 in milliseconds",
                  fn=lambda: self._ttft.quantile_locked(0.99))
        reg.gauge("paddle_genserve_inter_token_p50_ms",
                  "inter-token latency p50 in milliseconds",
                  fn=lambda: self._gaps.quantile_locked(0.50))
        reg.gauge("paddle_genserve_inter_token_p99_ms",
                  "inter-token latency p99 in milliseconds",
                  fn=lambda: self._gaps.quantile_locked(0.99))
        reg.gauge("paddle_genserve_slot_occupancy",
                  "occupied decode slots / max_slots",
                  fn=lambda: self._occupied / self.max_slots)
        reg.gauge("paddle_genserve_page_occupancy",
                  "KV cache pages in use (reserved + prefix-shared) / "
                  "num_pages",
                  fn=lambda: self._pages_in_use / self.num_pages)
        reg.gauge("paddle_genserve_prefix_cache_hit_ratio",
                  "prefix-cache hits / (hits + misses) since start",
                  fn=self._prefix_ratio_locked)
        reg.gauge("paddle_genserve_spec_accept_ratio",
                  "accepted / proposed speculative draft tokens since "
                  "start (greedy lanes only; 0 when not speculating)",
                  fn=self._spec_ratio_locked)
        reg.gauge("paddle_genserve_compile_count",
                  "decode/prefill/insert executables compiled at warmup "
                  "(must not grow under traffic)",
                  fn=lambda: self.compile_count)
        self._requests = reg.counter(
            "paddle_genserve_requests_total",
            "generation request outcomes by result", label="result",
            preset=("admitted", "retired", "preempted",
                    "rejected_queue_full", "rejected_draining",
                    "rejected_pages_exhausted", "deadline_expired",
                    "cancelled", "errors"),
            fixed=True)
        self._tokens = reg.counter(
            "paddle_genserve_tokens_total", "generated tokens streamed")
        self._prefix_hits = reg.counter(
            "paddle_genserve_prefix_cache_hits_total",
            "admissions that reused cached prefix pages")
        self._prefix_misses = reg.counter(
            "paddle_genserve_prefix_cache_misses_total",
            "admissions that found no cached prefix")
        self._chunks = reg.counter(
            "paddle_genserve_prefill_chunks_total",
            "prefill chunks streamed into slot pages")
        self._spec_accepted = reg.counter(
            "paddle_genserve_spec_accepted_total",
            "draft proposals the target verification accepted")
        self._spec_proposed = reg.counter(
            "paddle_genserve_spec_proposed_total",
            "draft proposals offered to target verification")
        self._block_steps = reg.counter(
            "paddle_genserve_block_steps_total",
            "iterations of generation by blocks (block_step runs)")
        self._block_lane_steps = reg.counter(
            "paddle_genserve_block_lane_steps_total",
            "live lanes over the block steps, by what the step was to the "
            "lane", label="kind", preset=("denoised", "committed"),
            fixed=True)
        self._block_tokens = reg.counter(
            "paddle_genserve_block_tokens_total",
            "tokens emitted by resolved blocks")
        self._steps = reg.counter(
            "paddle_genserve_steps_total",
            "decode steps launched (decode_step, spec_step or block_step "
            "runs)")
        self._steps_ahead = reg.counter(
            "paddle_genserve_steps_launched_ahead_total",
            "steps launched while the step before them had not finished "
            "on the device: over steps_total, the share of launches the "
            "chip did not wait for")
        self._empty_steps = reg.counter(
            "paddle_genserve_empty_steps_total",
            "steps nobody took a result of: every lane armed at the launch "
            "had ended in the step before it (the step ran with no lane "
            "armed) or was swept before the collect")
        self._page_slots = reg.counter(
            "paddle_genserve_paged_page_slots_total",
            "page-table slots of the one-token decode steps and block "
            "steps launched: steps x slots x table columns, summed over "
            "the layers (what a gather of the whole table, or a "
            "paged-attention grid over it, visits)")
        self._pages_live = reg.counter(
            "paddle_genserve_paged_pages_live_total",
            "of paged_page_slots_total, the pages the live lanes' extents "
            "cover (what paged attention has to read), from the lanes' "
            "lengths as the host holds them at the launch")
        self._loop_seconds = reg.counter(
            "paddle_genserve_loop_seconds_total",
            "decode-thread seconds by phase of its loop (top-level "
            "phases sum to the loop's wall time; a/b ran under a)",
            label="phase")
        self._loop_iterations = reg.counter(
            "paddle_genserve_loop_iterations_total",
            "iterations of the decode loop")
        self._pools = None
        if window_pool:
            self._pools = {"in_use": {"full": 0, "window": 0},
                           "mapped": {"full": 0, "window": 0}}
            reg.gauge("paddle_genserve_kv_pages_in_use",
                      "pages off each pool's free stack after the last "
                      "decode step", fn=lambda: self._pools["in_use"],
                      label="pool")
            reg.gauge("paddle_genserve_kv_pages_mapped",
                      "page-table entries the live lanes hold in each "
                      "pool after the last decode step (shared prefix "
                      "pages among them)",
                      fn=lambda: self._pools["mapped"], label="pool")
            self._window_released = reg.counter(
                "paddle_genserve_kv_window_pages_released_total",
                "window-pool pages the decode steps and prefill chunks "
                "let go behind a lane's window")
            self._mapped_steps = reg.counter(
                "paddle_genserve_kv_mapped_page_steps_total",
                "kv_pages_mapped summed over the decode steps",
                label="pool", preset=("full", "window"), fixed=True)
        self._state = None
        if state_pool:
            self._state = {"live": 0}
            reg.gauge("paddle_genserve_state_snapshots_live",
                      "state snapshots the pool holds",
                      fn=lambda: self._state["live"])
            self._state_restores = reg.counter(
                "paddle_genserve_state_restores_total",
                "admissions whose scan started from a restored snapshot")
            self._state_snapshots = reg.counter(
                "paddle_genserve_state_snapshots_total",
                "state snapshots prompt passes left at a shared page "
                "boundary")
            self._state_evictions = reg.counter(
                "paddle_genserve_state_snapshot_evictions_total",
                "state snapshots that went: their place reused for a newer "
                "one, or with their entry's pages")
            self._state_lane_steps = reg.counter(
                "paddle_genserve_state_lane_steps_total",
                "live lanes summed over the decode steps: the lane states "
                "the steps updated, as each step's report counted them")
            self._state_scans = reg.counter(
                "paddle_genserve_state_scans_total",
                "prompt passes (admissions and prefill chunks) that "
                "scanned")
            self._state_scan_tokens = reg.counter(
                "paddle_genserve_state_scan_tokens_total",
                "true (unpadded) tokens the prompt passes scanned")
        # the last RESERVOIR samples of the trailing WINDOW_S seconds
        self._ttft = Reservoir(self.RESERVOIR, self._lock, self.WINDOW_S)
        self._gaps = Reservoir(self.RESERVOIR, self._lock, self.WINDOW_S)
        self._token_stamps = collections.deque()   # (monotonic, count)
        self._occupied = 0
        self._pages_in_use = 0
        self.compile_count = 0

    @property
    def counters(self):
        return self._requests.values

    # -- recording hooks (decode thread + HTTP threads) --------------------
    def count(self, name: str, n: int = 1):
        self._requests.inc(name, n)

    def observe_tokens(self, n: int):
        now = time.monotonic()
        self._tokens.inc(n)
        with self._lock:
            self._token_stamps.append((now, n))
            cutoff = now - self.WINDOW_S
            while self._token_stamps and self._token_stamps[0][0] < cutoff:
                self._token_stamps.popleft()

    def observe_ttft(self, seconds: float):
        self._ttft.observe(seconds * 1e3)

    def observe_inter_token(self, seconds: float):
        self._gaps.observe(seconds * 1e3)

    def observe_loop(self, totals: dict):
        """Once an iteration of the decode loop: its StepTimers totals
        ({phase: seconds since the engine started})."""
        with self._lock:
            self._loop_seconds.set_totals(totals)
            self._loop_iterations.inc()

    def set_occupancy(self, occupied: int):
        with self._lock:
            self._occupied = int(occupied)

    def set_page_occupancy(self, pages_in_use: int):
        with self._lock:
            self._pages_in_use = int(pages_in_use)

    def count_prefix(self, hit: bool):
        (self._prefix_hits if hit else self._prefix_misses).inc()

    def count_chunk(self, n: int = 1):
        self._chunks.inc(n)

    def observe_spec(self, accepted: int, proposed: int):
        self._spec_accepted.inc(accepted)
        self._spec_proposed.inc(proposed)

    def count_step(self, ahead: bool):
        """One step launched; `ahead` when the step before it was still
        running on the device."""
        self._steps.inc()
        if ahead:
            self._steps_ahead.inc()

    def observe_page_walk(self, slots: int, live: int):
        """One one-token decode step or block step launched: the page
        slots of its page tables and the pages its live lanes' extents
        cover (``CacheGeometry.page_walk``)."""
        self._page_slots.inc(slots)
        self._pages_live.inc(live)

    def count_empty_step(self):
        self._empty_steps.inc()

    def observe_block_step(self, denoised: int, committed: int,
                           emitted: int):
        """One iteration of a block engine: its live lanes by kind, and
        the tokens its resolved blocks emitted."""
        self._block_steps.inc()
        self._block_lane_steps.inc("denoised", denoised)
        self._block_lane_steps.inc("committed", committed)
        self._block_tokens.inc(emitted)

    def observe_pools(self, in_use, w_in_use, mapped, w_mapped, released):
        """One decode step of a window engine: its report's registers."""
        with self._lock:
            self._pools = {"in_use": {"full": in_use, "window": w_in_use},
                           "mapped": {"full": mapped, "window": w_mapped}}
        self._window_released.inc(released - self._window_released.value)
        self._mapped_steps.inc("full", mapped)
        self._mapped_steps.inc("window", w_mapped)

    def observe_scan(self, tokens: int, restored: bool):
        """One prompt pass of an engine with state layers: the true tokens
        it scans, and whether its scan starts from a restored snapshot."""
        self._state_scans.inc()
        self._state_scan_tokens.inc(tokens)
        if restored:
            self._state_restores.inc()

    def observe_state_step(self, lanes: int):
        """One decode step of an engine with state layers: the live lanes
        whose state it updated."""
        self._state_lane_steps.inc(lanes)

    def set_state_snapshots(self, taken: int, evicted: int, live: int):
        """The prefix cache's running counts of its snapshots."""
        self._state_snapshots.inc(taken - self._state_snapshots.value)
        self._state_evictions.inc(evicted - self._state_evictions.value)
        with self._lock:
            self._state["live"] = int(live)

    def set_compile_count(self, n: int):
        with self._lock:
            self.compile_count = int(n)

    # -- derived values ----------------------------------------------------
    def _prefix_ratio_locked(self):
        hits = self._prefix_hits.value
        total = hits + self._prefix_misses.value
        return hits / total if total else 0.0

    def _spec_ratio_locked(self):
        proposed = self._spec_proposed.value
        return self._spec_accepted.value / proposed if proposed else 0.0

    def _tps_locked(self, now=None):
        now = time.monotonic() if now is None else now
        if not self._token_stamps:
            return 0.0
        span = max(1e-9, min(now - self.started_at, self.WINDOW_S))
        live = sum(n for t, n in self._token_stamps
                   if t >= now - self.WINDOW_S)
        return live / span

    def snapshot(self) -> dict:
        """Programmatic view (benchmarks/adapters/gpt.py, tests)."""
        with self._lock:
            return {
                "decode_tokens_per_sec": round(self._tps_locked(), 2),
                "ttft_p50_ms": round(
                    self._ttft.quantile_locked(0.50), 3),
                "ttft_p99_ms": round(
                    self._ttft.quantile_locked(0.99), 3),
                "inter_token_p50_ms": round(
                    self._gaps.quantile_locked(0.50), 3),
                "inter_token_p99_ms": round(
                    self._gaps.quantile_locked(0.99), 3),
                "slot_occupancy": round(self._occupied / self.max_slots, 3),
                "page_occupancy": round(
                    self._pages_in_use / self.num_pages, 3),
                "prefix_cache_hits": self._prefix_hits.value,
                "prefix_cache_misses": self._prefix_misses.value,
                "prefix_cache_hit_ratio": round(
                    self._prefix_ratio_locked(), 4),
                "spec_accept_ratio": round(self._spec_ratio_locked(), 4),
                "spec_proposed": self._spec_proposed.value,
                "prefill_chunks": self._chunks.value,
                "block_steps": self._block_steps.value,
                "block_lane_steps_denoised":
                    self._block_lane_steps.values["denoised"],
                "block_lane_steps_committed":
                    self._block_lane_steps.values["committed"],
                "block_tokens_emitted": self._block_tokens.value,
                "steps": self._steps.value,
                "steps_launched_ahead": self._steps_ahead.value,
                "empty_steps": self._empty_steps.value,
                "paged_page_slots": self._page_slots.value,
                "paged_pages_live": self._pages_live.value,
                "compile_count": self.compile_count,
                **{k: v for k, v in sorted(self.counters.items())},
                **({} if self._pools is None else {
                    "kv_pages_in_use": dict(self._pools["in_use"]),
                    "kv_pages_mapped": dict(self._pools["mapped"]),
                    "kv_window_pages_released":
                        self._window_released.value,
                    "kv_mapped_page_steps":
                        dict(self._mapped_steps.values)}),
                **({} if self._state is None else {
                    "state_restores": self._state_restores.value,
                    "state_snapshots": self._state_snapshots.value,
                    "state_snapshot_evictions":
                        self._state_evictions.value,
                    "state_snapshots_live": self._state["live"],
                    "state_lane_steps": self._state_lane_steps.value,
                    "state_scans": self._state_scans.value,
                    "state_scan_tokens": self._state_scan_tokens.value}),
            }

    def prometheus_text(self) -> str:
        return self.registry.prometheus_text()


class RouterMetrics:
    """Fleet-router observability (`serving/router.py`): per-replica
    routing decisions, backpressure, and replica health in one private
    registry, co-exposed through the router's /metrics (and embeddable
    in a `MonitorServer(extra_registries=...)` when the router rides an
    existing monitoring process).

    Routing reasons (the `reason` label on requests_total):
      prefix_hit       affinity table says this replica owns the
                       prompt's page-aligned prefix
      least_loaded     no affinity — picked the replica with the fewest
                       inflight requests
      health_failover  affinity replica was dead/draining, rerouted

    A 429 from a replica is BACKPRESSURE, not death: it bumps
    `paddle_router_backpressure_total{replica}` and the request retries
    elsewhere, but the replica's health-probe failure count is untouched
    (a loaded replica must not flap in and out of the fleet)."""

    def __init__(self):
        self.registry = MetricsRegistry()
        self._lock = self.registry._lock
        reg = self.registry
        self._requests = reg.counter(
            "paddle_router_requests_total",
            "requests routed, by target replica and routing reason",
            label=("replica", "reason"))
        self._backpressure = reg.counter(
            "paddle_router_backpressure_total",
            "429s absorbed per replica (request retried elsewhere; "
            "not a health-probe failure)", label="replica")
        self._failovers = reg.counter(
            "paddle_router_failovers_total",
            "requests re-dispatched to a survivor, by trigger "
            "(mid_stream = SSE resumed after a replica died under the "
            "stream; dispatch = the initial proxy attempt failed; "
            "hedge = a hedged duplicate was issued)",
            label="reason",
            preset=("mid_stream", "dispatch", "hedge"), fixed=True)
        self._budget_exhausted = reg.counter(
            "paddle_router_retry_budget_exhausted_total",
            "retries suppressed because the retry budget was empty "
            "(the request failed fast with 503 instead of storming a "
            "sick fleet)")
        self._deadline_rejected = reg.counter(
            "paddle_router_deadline_rejected_total",
            "requests rejected at admission because the estimated "
            "queue wait already exceeded their deadline")
        self._hedges = reg.counter(
            "paddle_router_hedges_total",
            "hedged non-streaming dispatches by outcome (won = the "
            "hedge finished first, lost = the primary did)",
            label="outcome", preset=("won", "lost"), fixed=True)
        self._healthy = 0
        self._inflight = 0
        self._epoch = 0
        self._ok = 0
        self._failed = 0
        self._recovery_ms = 0.0
        reg.gauge("paddle_router_replicas_healthy",
                  "replicas currently passing health probes",
                  fn=lambda: self._healthy)
        reg.gauge("paddle_router_inflight",
                  "requests currently being proxied",
                  fn=lambda: self._inflight)
        reg.gauge("paddle_router_membership_epoch",
                  "last fleet-coordinator membership epoch the router "
                  "applied (0 when running from a static replica list)",
                  fn=lambda: self._epoch)
        reg.gauge("paddle_fleet_availability_ratio",
                  "requests that returned a complete answer (failovers "
                  "included) over all finished requests; 1.0 = zero "
                  "client-visible failures",
                  fn=lambda: (self._ok / (self._ok + self._failed)
                              if (self._ok + self._failed) else 1.0))
        reg.gauge("paddle_router_failover_recovery_ms",
                  "last mid-stream failover's loss-to-resumed gap: "
                  "replica death detected under the stream to the "
                  "survivor's connection accepted, milliseconds",
                  fn=lambda: self._recovery_ms)

    def count_routed(self, replica: str, reason: str):
        self._requests.inc((str(replica), str(reason)))

    def count_backpressure(self, replica: str):
        self._backpressure.inc(str(replica))

    def count_failover(self, reason: str):
        self._failovers.inc(str(reason))

    def count_budget_exhausted(self):
        self._budget_exhausted.inc()

    def count_deadline_rejected(self):
        self._deadline_rejected.inc()

    def count_hedge(self, outcome: str):
        self._hedges.inc(str(outcome))

    def count_outcome(self, ok: bool):
        """One finished client request — the availability denominator.
        A failed-over request that eventually completed counts `ok`;
        only client-visible failures (5xx, dead stream) count failed."""
        with self._lock:
            if ok:
                self._ok += 1
            else:
                self._failed += 1

    def set_healthy(self, n: int):
        with self._lock:
            self._healthy = int(n)

    def set_epoch(self, n: int):
        with self._lock:
            self._epoch = int(n)

    def set_recovery_ms(self, ms: float):
        with self._lock:
            self._recovery_ms = round(float(ms), 3)

    def add_inflight(self, delta: int):
        with self._lock:
            self._inflight += int(delta)

    def snapshot(self) -> dict:
        with self._lock:
            denom = self._ok + self._failed
            return {
                "replicas_healthy": self._healthy,
                "inflight": self._inflight,
                "membership_epoch": self._epoch,
                "availability_ratio": (self._ok / denom) if denom else 1.0,
                "requests_ok": self._ok,
                "requests_failed": self._failed,
                "routed": {"|".join(k): v
                           for k, v in sorted(self._requests.values.items())},
                "backpressure": dict(sorted(
                    self._backpressure.values.items())),
                "failovers": dict(sorted(self._failovers.values.items())),
                "retry_budget_exhausted": self._budget_exhausted.value,
                "deadline_rejected": self._deadline_rejected.value,
                "hedges": dict(sorted(self._hedges.values.items())),
                "failover_recovery_ms": self._recovery_ms,
            }

    def prometheus_text(self) -> str:
        return self.registry.prometheus_text()
