"""Global runtime flag registry.

Reference parity: paddle/fluid/platform/flags.cc (gflags FLAGS_* registry,
env-overridable) + pybind/global_value_getter_setter.cc (paddle.set_flags /
get_flags).  TPU-native: a plain python registry; flags that controlled CUDA
allocator/cudnn behavior are accepted but inert, flags that map to XLA behavior
are applied (e.g. check_nan_inf wraps jitted steps with debug checks).
"""
from __future__ import annotations

import os
from typing import Any

_REGISTRY: dict[str, Any] = {}


def define_flag(name: str, default: Any, help_: str = ""):
    env = os.environ.get(name.upper(), os.environ.get(name))
    if env is not None:
        if isinstance(default, bool):
            default = env.lower() in ("1", "true", "yes")
        elif isinstance(default, int):
            default = int(env)
        elif isinstance(default, float):
            default = float(env)
        else:
            default = env
    _REGISTRY[name] = default


# Mirrors of the reference's commonly used flags (platform/flags.cc:33-565).
define_flag("FLAGS_jit_cache_dir",
            os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__)))), ".jax_cache"),
            "persistent XLA compilation cache directory; '' disables. "
            "Compiled executables are reused ACROSS processes, so the "
            "second run of the same model skips XLA compilation entirely. "
            "The default is one fixed directory beside the package (the "
            "path is part of the cache key, so it must not move).  Where "
            "JAX_COMPILATION_CACHE_DIR is set, jax keeps its cache there "
            "and this flag sets no directory")
define_flag("FLAGS_jit_cache_min_compile_secs", 0.5,
            "only persist executables whose compile took at least this "
            "long (0 caches everything)")
define_flag("FLAGS_check_nan_inf", False, "per-op nan/inf checks in debug mode")
define_flag("FLAGS_benchmark", False, "sync after each op for timing")
define_flag("FLAGS_eager_delete_tensor_gb", 0.0, "inert: XLA owns memory")
define_flag("FLAGS_fraction_of_gpu_memory_to_use", 0.92, "inert on TPU")
define_flag("FLAGS_use_pallas_kernels", True, "swap in Pallas fused kernels (TPU)")
define_flag("FLAGS_cudnn_deterministic", False, "inert; XLA is deterministic")
define_flag("FLAGS_sort_sum_gradient", False, "grad accumulation order")
define_flag("FLAGS_max_inplace_grad_add", 0, "inert")
define_flag("FLAGS_selected_gpus", "", "inert; device selection via set_device")
define_flag("FLAGS_selected_tpus", "",
            "comma-separated local accelerator ids for this trainer; set "
            "per rank by the distributed launcher, read by Env to pick "
            "the default device id")
define_flag("FLAGS_mesh_shape", "",
            "default SPMD mesh for Model.fit when no mesh= argument or "
            "ambient mesh_guard is active: 'dp=8', 'dp=2,mp=4', or a bare "
            "axis name for the all-devices wildcard ('dp'); '' = "
            "single-device engine")
# -- serving (paddle_tpu.serving adaptive batcher) ------------------------
define_flag("FLAGS_serving_max_batch", 8,
            "largest batch the serving engine coalesces (upper bucket)")
define_flag("FLAGS_serving_timeout_ms", 5.0,
            "adaptive-batch flush deadline: a partial batch is dispatched "
            "once its oldest request has waited this long")
define_flag("FLAGS_serving_queue_depth", 256,
            "bounded request queue; submit() raises QueueFullError beyond "
            "this (backpressure, not unbounded buffering)")
define_flag("FLAGS_serving_buckets", "",
            "serving shape-bucket grid, 'B1,B2,...' or 'B1,B2xS1,S2,...' "
            "(batch x sequence); '' = powers of two up to "
            "FLAGS_serving_max_batch, no sequence bucketing")
# -- generation serving (paddle_tpu.serving.generation) --------------------
define_flag("FLAGS_genserve_max_slots", 4,
            "in-flight sequences per decode iteration (the continuous-"
            "batching lane count; one decode executable spans all slots)")
define_flag("FLAGS_genserve_max_seq_len", 256,
            "per-slot KV-cache length S_max; prompt + max_new_tokens of "
            "every request must fit inside it")
define_flag("FLAGS_genserve_prompt_buckets", "16,32,64",
            "admitted prompt-length grid 'S1,S2,...'; one prefill+insert "
            "executable pair is AOT-compiled per bucket at start()")
define_flag("FLAGS_genserve_queue_depth", 128,
            "bounded generation admission queue; submit() raises "
            "QueueFullError beyond this")
define_flag("FLAGS_genserve_page_size", 16,
            "tokens per KV-cache page; the page pool is allocated as "
            "[layers, num_pages, page_size, heads, head_dim]")
define_flag("FLAGS_genserve_num_pages", 0,
            "KV page-pool capacity; 0 sizes it dense-equivalently "
            "(max_slots * ceil(max_seq_len / page_size)) — smaller pools "
            "oversubscribe slots against actual footprint and queue "
            "admissions the pool cannot reserve")
define_flag("FLAGS_genserve_prefix_cache", 1,
            "1 shares identical tokenized prompt prefixes as refcounted "
            "read-only KV pages (hits skip prefill for shared pages); "
            "0 disables sharing")
define_flag("FLAGS_genserve_spec_tokens", 4,
            "speculative-decode draft proposals per iteration (k); only "
            "read when a draft model is attached — each iteration drafts "
            "k tokens and the target verifies all k+1 in one step")
define_flag("FLAGS_genserve_prefill_chunk", 0,
            "chunked-prefill slice length in tokens (page_size multiple, "
            "<= largest prompt bucket); prompts whose un-shared suffix "
            "exceeds it prefill one chunk per decode iteration instead of "
            "stalling every lane; 0 disables chunking")
# -- sparse / recommender (paddle_tpu.sparse) ------------------------------
define_flag("FLAGS_sparse_admission_threshold", 2,
            "minimum count-min-estimated id frequency (inclusive) before "
            "an id earns a dedicated embedding row; below it ids share "
            "the OOV row")
define_flag("FLAGS_sparse_evict_after", 0,
            "batches an id may go unseen before VocabAdmission.evict() "
            "recycles its row; 0 disables eviction")
# -- fleet router (paddle_tpu.serving.router) ------------------------------
define_flag("FLAGS_router_probe_interval_s", 0.5,
            "seconds between router health probes of each replica's "
            "/healthz")
define_flag("FLAGS_router_dead_after", 3,
            "consecutive failed health probes before a replica is routed "
            "around (429 backpressure never counts as a failure)")
define_flag("FLAGS_router_healthy_after", 2,
            "consecutive successful probes before a dead replica is "
            "marked healthy again (flap damping; a single lucky probe "
            "must not re-admit a sick replica)")
define_flag("FLAGS_router_retry_budget_ratio", 0.1,
            "retry-budget deposit per successful request: retries are "
            "capped at this fraction of recent successful traffic so a "
            "sick fleet degrades to fast 503s instead of a retry storm")
define_flag("FLAGS_router_retry_budget_min", 5.0,
            "retry-budget floor (and initial balance): a cold or "
            "low-traffic router can still retry this many times")
define_flag("FLAGS_router_breaker_threshold", 3,
            "consecutive request failures that trip a replica's circuit "
            "breaker (dispatch stops before the health probe catches up)")
define_flag("FLAGS_router_breaker_cooldown_s", 2.0,
            "seconds a tripped circuit breaker holds before one trial "
            "request may probe the replica again")
define_flag("FLAGS_router_hedge_floor_ms", 0.0,
            "hedged dispatch for non-streaming requests: when > 0, a "
            "duplicate is sent to a second replica once the first has "
            "been outstanding max(this floor, observed p99 latency); "
            "first answer wins, the loser is discarded; 0 disables")
define_flag("FLAGS_router_replica_slots", 4,
            "per-replica concurrent-decode lanes the deadline-aware "
            "admission estimator assumes when computing queue wait "
            "(matches the replicas' --slots in the smoke fixture)")
define_flag("FLAGS_fleet_respawn_backoff_s", 0.5,
            "base delay before the replica supervisor respawns a "
            "crashed replica (jittered exponential backoff from here)")
define_flag("FLAGS_fleet_membership_poll_s", 0.1,
            "router poll interval against the fleet coordinator's "
            "membership epoch; an epoch delta evicts dead replicas "
            "faster than the probe timeout")
# -- runtime telemetry (paddle_tpu.monitor) --------------------------------
define_flag("FLAGS_telemetry_dir", "",
            "directory for the per-step JSONL training event log "
            "(append-only, rotating, safe to tail) and on-demand "
            "jax.profiler trace captures; '' disables the event log")
define_flag("FLAGS_monitor_port", -1,
            "port for the training MonitorServer (/metrics /healthz "
            "/debug/trace); 0 picks a free port (logged), -1 disables")
define_flag("FLAGS_telemetry_rotate_mb", 64.0,
            "rotate the JSONL event log when it exceeds this many MB "
            "(old segments keep a bounded .N suffix chain)")
define_flag("FLAGS_device_peak_flops", 0.0,
            "per-device peak FLOP/s for the MFU gauge; 0 = look the "
            "device kind up in monitor.PEAKS (TPU generations + a "
            "nominal CPU row so CPU runs read a nonzero MFU)")
define_flag("FLAGS_device_peak_bw", 0.0,
            "per-device HBM bytes/s for the op-table roofline "
            "(monitor/perf.py); 0 = look the device kind up in "
            "monitor.PEAKS (TPU generations + a nominal CPU row)")
define_flag("FLAGS_perf_ops_top", 48,
            "op-table rows kept before rolling the tail into one "
            "'(other)' row (sums stay exact); /debug/perf and "
            "engine.op_report() share this bound")
define_flag("FLAGS_trace_steps", 3,
            "how many steps a SIGUSR1-armed jax.profiler capture spans "
            "(the headless /debug/trace?steps=N equivalent)")
define_flag("FLAGS_trace_sample_rate", 0.01,
            "head-sampling probability for request-scoped spans "
            "(monitor/tracing.py): the decision is derived from the "
            "trace_id itself, so client and server independently agree; "
            "0 disables the tracer, 1 traces every request.  Training "
            "fits are few, so any nonzero rate records their spans")
define_flag("FLAGS_trace_buffer_spans", 2048,
            "bounded ring of finished spans the tracer retains for "
            "/debug/spans and chrome-trace export (oldest evicted first)")
define_flag("FLAGS_metrics_window_s", 0.0,
            "when > 0, utils.metrics Reservoir quantiles (e.g. the "
            "paddle_train_step_ms p50/p99 gauges) cover only the last "
            "this-many seconds instead of the whole run; 0 keeps the "
            "lifetime-cumulative default")
define_flag("FLAGS_flightrec_records", 512,
            "bounded ring of recent spans/windows/ckpt/NaN events the "
            "crash flight recorder (monitor/flightrec.py) dumps to "
            "FLAGS_telemetry_dir/flightrec-<pid>.json on watchdog exit "
            "86, durability exit 91, SIGTERM, or uncaught crash")
# -- durable checkpointing (distributed/checkpoint.py) --------------------
define_flag("FLAGS_ckpt_async", True,
            "fit(resume=/fault_tolerant=) writes interval/epoch "
            "checkpoints on a background thread (host snapshot on the "
            "training thread, disk IO off it); False = synchronous saves")
define_flag("FLAGS_ckpt_max_failures", 3,
            "consecutive failed checkpoint generations tolerated before "
            "fit aborts with resilience.DURABILITY_EXIT_CODE (degrade-"
            "then-escalate: warn and keep training until then)")


def set_flags(flags: dict[str, Any]):
    for k, v in flags.items():
        _REGISTRY[k] = v
    if "FLAGS_jit_cache_dir" in flags \
            or "FLAGS_jit_cache_min_compile_secs" in flags:
        apply_jit_cache(force=True)
    # mirror into the native runtime core so C++ components see the same
    # registry (platform/flags.cc role; no-op without the native lib)
    try:
        from .. import core as _native
        if _native.available():
            for k, v in flags.items():
                _native.flag_set(k, v)
    except Exception:
        pass


_jit_cache_dir_applied = None


def apply_jit_cache(force: bool = False):
    """Turn on jax's persistent compilation cache.

    Called once at paddle_tpu import (and again from set_flags when the
    flag changes).  With the cache on, every process that compiles the
    same jitted step (same HLO, same backend) after the first reads the
    executable from disk instead of re-running XLA.

    The directory can be placed from outside: where the environment sets
    JAX_COMPILATION_CACHE_DIR, jax itself reads it and no directory is set
    in code.  Otherwise it is FLAGS_jit_cache_dir.  Returns the directory
    in use, or None when the cache is off."""
    global _jit_cache_dir_applied

    import jax

    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") or ""
    d = env_dir or os.path.expanduser(_REGISTRY.get("FLAGS_jit_cache_dir")
                                      or "")
    if not force and d == _jit_cache_dir_applied:
        return d or None
    if not env_dir:
        if d:
            os.makedirs(d, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", d or None)
    if d:
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs",
            float(_REGISTRY.get("FLAGS_jit_cache_min_compile_secs", 0.5)))
        # no size floor: tiny-but-slow-to-compile entries still count
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    _jit_cache_dir_applied = d
    return d or None


def get_flags(keys):
    if isinstance(keys, str):
        keys = [keys]
    return {k: _REGISTRY.get(k) for k in keys}


def flag(name: str, default=None):
    return _REGISTRY.get(name, default)
