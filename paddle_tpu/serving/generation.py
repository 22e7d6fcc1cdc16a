"""Continuous-batching autoregressive generation engine.

The serving-side counterpart of the training engine's donation
discipline: the reference stack served autoregressive traffic through
fused_multi_transformer's CacheKV decode behind AnalysisPredictor's
per-request generation loop; this module is that path rebuilt for XLA's
shape discipline, in the Orca iteration-level-scheduling shape:

  * **prefill/decode split** — each admitted prompt runs ONE prefill
    (compiled per prompt-length bucket through the same AOT machinery as
    the Predictor's shape buckets) that seeds its slot's pages of the
    device-resident PAGED KV cache; then a single donated, jitted
    **decode step** advances ALL in-flight sequences one token per
    iteration, allocating fresh tail pages in-graph off the free-list
    register as lanes cross page boundaries.
  * **continuous batching** — the scheduler admits queued requests into
    free slots at iteration boundaries (no waiting for the batch to
    drain) once the page pool can reserve their worst case, retires
    lanes on EOS/max_new_tokens (their private pages return to the pool
    in the same decode step), and preempts lanes on
    deadline/cancellation; a request admitted mid-decode produces tokens
    bitwise-identical to running alone (tested).
  * **prefix sharing** — identical tokenized prompt prefixes occupy the
    pool ONCE (serving/prefix_cache.py): a hit maps the cached
    read-only pages into the slot's page table and prefills only the
    suffix, attending over the cached prefix K/V.
  * **zero steady-state compiles, zero cache round-trips** — every
    executable (decode, release, reclaim, per-bucket prefill/insert) is
    AOT lowered+compiled at ``start()`` via ``inference.aot_compile``;
    the decode state pytree (serving/kv_cache.py) is donated on every
    transition, so the KV pool lives on device across iterations and
    only the sampled token ids are fetched (under ``host_fetch()``).
  * **layout-aware** — pass ``mesh=`` (+ an optional PR-8 ``SpecLayout``)
    and the engine serves a tensor-parallel model from one process:
    params resolve through the layout's PartitionSpec table, the page
    pool's head axis shards over ``tp``, and every executable is
    compiled with NamedSharding in/out (out-shardings pinned to
    in-shardings, so donation holds under GSPMD).

  * **generation by blocks** — a model whose ``cfg`` gives a
    ``block_length`` B (models/sdar.py) generates by diffusion over
    blocks: the engine builds ``block_step`` in place of ``decode_step``.
    A lane holds a block of B positions that starts masked; each
    iteration runs the block over the committed prefix with the block
    visible both ways and unmasks its most confident positions; when no
    mask is left one more pass writes the block's final K/V and the next
    block starts.  A block's tokens reach the stream together, in the
    iteration that resolves its last mask.

Per-slot sampling (greedy / temperature / top-k, per-request seed)
reproduces ``GPTForCausalLM.generate``'s exact PRNG chain — one
``split`` at admission, one per decode iteration — which is what makes
engine output comparable token-for-token with the solo path.
"""
from __future__ import annotations

import collections
import contextlib
import logging
import queue
import threading
import time
from dataclasses import replace

import numpy as np

from ..framework import flags as _flags
from ..framework.transfer import host_fetch
from ..monitor import tracing as _tracing
from ..utils import chaos
from ..utils.profiler import StepTimers, startup
from .engine import (DeadlineExceededError, EngineStoppedError,
                     QueueFullError)
from .kv_cache import (SCAN_FROM_SLOT, SCAN_FROM_ZERO, CacheGeometry,
                       HybridKV, LaneStates, PagedKV, PrefixKV, PromptStates,
                       admit_slot, initial_states, make_state, push_pages,
                       put_states, reclaim_pages, release_slots, slide_window,
                       state_specs, take_pages, write_prompt)
from .metrics import GenerationMetrics
from .prefix_cache import PrefixCache
from .scheduler import SlotScheduler

logger = logging.getLogger("paddle_tpu.serving")

__all__ = ["GenerationEngine", "GenerationHandle"]

_WAKE = object()   # queue sentinel: wakes an idle-blocked decode loop
_END = object()    # handle sentinel: no more tokens

# a step in flight: its iteration number, the (slot, request) pairs armed
# at its launch, and the device arrays of its report, not yet fetched
_Step = collections.namedtuple("_Step", "iter lanes out")


class GenerationHandle:
    """Per-request streaming face: tokens arrive as the decode loop
    produces them; iterate (``for tok in handle``), poll
    (``next_token``), or block for everything (``result``)."""

    def __init__(self, prompt_len: int, max_new_tokens: int):
        self.prompt_len = prompt_len
        self.max_new_tokens = max_new_tokens
        self.tokens: list[int] = []       # appended by the decode thread
        # a block engine's: the denoising step at which each token of
        # `tokens` was unmasked (None where tokens come one a step)
        self.steps: list[int] | None = None
        self._q: queue.Queue = queue.Queue()
        self._done = threading.Event()
        self._error: BaseException | None = None
        self._req = None                  # backref set by the engine
        self.t_submit = time.monotonic()
        self.t_first_token = None

    # -- consuming ---------------------------------------------------------
    def next_token(self, timeout=None):
        """Next generated token id, or None when the stream has ended
        (raises the request's error, if it failed)."""
        if self._done.is_set() and self._q.empty():
            if self._error is not None:
                raise self._error
            return None
        try:
            item = self._q.get(timeout=timeout)
        except queue.Empty:
            raise TimeoutError(
                f"no token within {timeout:g}s") from None
        if item is _END:
            if self._error is not None:
                raise self._error
            return None
        return item

    def __iter__(self):
        while True:
            tok = self.next_token()
            if tok is None:
                return
            yield tok

    def result(self, timeout=None) -> list[int]:
        """Block until the request finishes; the generated token ids
        (prompt excluded).  Raises on deadline expiry / engine failure."""
        if not self._done.wait(timeout):
            raise TimeoutError(f"generation not finished in {timeout:g}s")
        if self._error is not None:
            raise self._error
        return list(self.tokens)

    # -- control -----------------------------------------------------------
    def cancel(self):
        """Ask the engine to preempt this request at the next iteration
        boundary (its slot is freed; tokens produced so far remain)."""
        req = self._req
        if req is not None:
            req.cancelled = True
            req.engine._wake()

    @property
    def done(self) -> bool:
        return self._done.is_set()

    @property
    def error(self):
        return self._error

    @property
    def ttft_ms(self):
        if self.t_first_token is None:
            return None
        return (self.t_first_token - self.t_submit) * 1e3

    # -- engine side -------------------------------------------------------
    def _push(self, tok: int):
        if self.t_first_token is None:
            self.t_first_token = time.monotonic()
        self.tokens.append(tok)
        self._q.put(tok)

    def _finish(self, error: BaseException | None = None):
        if self._done.is_set():
            return
        self._error = error
        self._done.set()
        self._q.put(_END)


class _GenRequest:
    __slots__ = ("prompt", "bucket", "max_new_tokens", "do_sample",
                 "temperature", "top_k", "seed", "resume_pos", "eos",
                 "deadline", "handle", "engine", "cancelled",
                 "t_last_token", "span", "own_span", "span_queue",
                 "span_decode", "prefilling", "prefill_cursor",
                 "chunk_row", "chunk_wrow", "j_hit", "pin_final",
                 "block_start", "block_resolved", "restore", "snap_pages")

    def __init__(self, engine, prompt, bucket, max_new_tokens, do_sample,
                 temperature, top_k, seed, eos, deadline, span=None,
                 own_span=False, resume_pos=0):
        self.engine = engine
        self.prompt = prompt               # np.int32 [L]
        self.bucket = bucket               # padded prompt length Sp
        self.max_new_tokens = max_new_tokens
        self.do_sample = do_sample
        self.temperature = temperature
        self.top_k = top_k
        self.seed = seed
        self.resume_pos = resume_pos       # tokens a dead replica emitted
        self.eos = eos                     # int; vocab_size == never
        self.deadline = deadline           # absolute monotonic or None
        self.cancelled = False
        self.t_last_token = None
        self.span = span                   # request span (sampled or None)
        self.own_span = own_span           # engine owns span's end()
        self.span_queue = None             # "gen.queued" child
        self.span_decode = None            # "gen.decode" child
        self.prefilling = False            # chunked prefill in flight
        self.prefill_cursor = 0            # tokens already prefilled
        self.chunk_row = None              # slot's page row so far (np)
        self.chunk_wrow = None             # its window pool's (or None)
        self.j_hit = 0                     # prefix-cache pages mapped
        self.pin_final = 0                 # pinned count once armed
        self.block_start = 0               # block engines: the open block
        # its last collected pass closed the block before block_start: the
        # pass after that one commits it
        self.block_resolved = False
        # an engine with state layers: the snapshot the admission restores
        # (none: the scan starts from zero), and the depth in pages at
        # which its prompt pass leaves one (0: none)
        self.restore = SCAN_FROM_ZERO
        self.snap_pages = 0
        self.handle = GenerationHandle(len(prompt), max_new_tokens)
        self.handle._req = self

    def end_spans(self, status: str):
        """Close any open child spans and settle the request span with a
        terminal status; the parent is ended here only when the engine
        owns it (direct submit — HTTP requests end theirs upstream)."""
        for s in (self.span_queue, self.span_decode):
            if s is not None:
                s.end(status=status)
        self.span_queue = self.span_decode = None
        if self.span is not None:
            self.span.set_attr("status", status)
            if self.own_span:
                self.span.end()
            self.span = None


class GenerationEngine:
    """Continuous-batching decode over a device-resident paged KV cache.

    Args:
      model: a causal-LM Layer exposing ``slot_prefill(ids, length)`` and
        ``slot_step(tokens, positions, kv, last=None)`` (models/gpt.py
        GPTForCausalLM; ``kv`` is a KV source of serving/kv_cache.py) and
        a ``cfg`` with num_layers / num_heads / hidden_size / vocab_size
        / max_position_embeddings (and, where they differ from
        hidden_size // num_heads and num_heads, head_dim and
        num_kv_heads).  A ``draft_model`` answers the same.  A ``cfg``
        with a ``block_length`` (and denoising_steps, mask_token_id,
        remasking_strategy, confidence_threshold) declares generation by
        blocks; with ``num_experts`` the model's ``slot_step`` takes
        ``live=`` and returns its routing counts as a third value.  A
        ``cfg`` with ``state_layers`` (and state_shape, conv_shape:
        models/granite_hybrid.py) declares layers that hold a recurrent
        state and no page: every slot then has a state beside its pages,
        a prefix hit restores one from a pool of snapshots, and the
        model's ``slot_step`` takes a ``HybridKV`` and its
        ``slot_prefill`` a third argument (the offset at which to leave
        a snapshot) and returns the states as a fourth value.
      max_slots: in-flight sequences per decode iteration
        (``FLAGS_genserve_max_slots``).
      max_seq_len: per-slot sequence cap S_max >= prompt + new tokens
        (``FLAGS_genserve_max_seq_len``).
      prompt_buckets: admitted prompt-length grid, list or "8,16,32"
        (``FLAGS_genserve_prompt_buckets``); one prefill+insert
        executable pair is AOT-compiled per bucket at start().
      queue_depth: bounded admission queue
        (``FLAGS_genserve_queue_depth``) — ``submit`` raises
        :class:`QueueFullError` beyond it.
      max_top_k: largest per-request top_k accepted (the sampling
        executable carries a static top-k width).
      page_size: tokens per KV page (``FLAGS_genserve_page_size``).
      num_pages: page-pool capacity (``FLAGS_genserve_num_pages``);
        0 sizes it dense-equivalently (max_slots * pages_per_slot) —
        smaller pools oversubscribe slots against actual footprint and
        the scheduler queues admissions that cannot reserve their
        worst case.  A model whose ``cfg`` gives ``layer_windows`` (one
        entry a layer, the window of keys it sees in tokens, 0 = all)
        gets a second pool for those layers, in which a lane holds only
        the pages that meet its window; its capacity follows from this
        one (``CacheGeometry.window_pages``).  Nothing selects the
        cache's kind: a model without windows gets the one pool it
        always got.
      prefix_cache: share identical tokenized prompt prefixes as
        refcounted read-only pages (``FLAGS_genserve_prefix_cache``);
        hits skip prefill for the shared pages.
      mesh: optional jax Mesh (or a {"tp": 2}-style dict) — serve a
        tensor-parallel model from one engine.
      layout: optional distributed.SpecLayout resolving param placements
        (defaults to ``SpecLayout()`` when a mesh is given).

    Lifecycle mirrors ServingEngine: ``start()`` compiles every
    executable (steady state never compiles), ``submit()`` returns a
    streaming :class:`GenerationHandle`, ``drain()`` finishes in-flight
    decodes and rejects new work, ``stop()`` kills the loop.
    """

    def __init__(self, model, *, max_slots=None, max_seq_len=None,
                 prompt_buckets=None, queue_depth=None, max_top_k=64,
                 page_size=None, num_pages=None, prefix_cache=None,
                 mesh=None, layout=None, draft_model=None,
                 spec_tokens=None, prefill_chunk=None):
        from ..hapi.model import Model as _HapiModel

        if isinstance(model, _HapiModel):
            model = model.network
        if isinstance(draft_model, _HapiModel):
            draft_model = draft_model.network
        for what, m in (("model", model), ("draft_model", draft_model)):
            for req_attr in ("slot_prefill", "slot_step", "cfg"):
                if m is not None and not hasattr(m, req_attr):
                    raise TypeError(
                        f"GenerationEngine needs a {what} with "
                        f"`{req_attr}` (a causal LM that steps over a KV "
                        "source, e.g. models.GPTForCausalLM); got "
                        f"{type(m).__name__}")
        self.model = model
        cfg = model.cfg
        self.max_slots = int(max_slots
                             or _flags.flag("FLAGS_genserve_max_slots", 4))
        self.max_seq_len = int(
            max_seq_len or _flags.flag("FLAGS_genserve_max_seq_len", 256))
        if self.max_seq_len > cfg.max_position_embeddings:
            raise ValueError(
                f"max_seq_len {self.max_seq_len} exceeds the model's "
                f"max_position_embeddings {cfg.max_position_embeddings}")
        if prompt_buckets is None:
            prompt_buckets = _flags.flag("FLAGS_genserve_prompt_buckets",
                                         "16,32,64")
        if isinstance(prompt_buckets, str):
            prompt_buckets = [int(s) for s in prompt_buckets.split(",")
                              if s.strip()]
        self.prompt_buckets = sorted(set(int(b) for b in prompt_buckets))
        if not self.prompt_buckets or self.prompt_buckets[0] < 1:
            raise ValueError(f"invalid prompt buckets {prompt_buckets!r}")
        if self.prompt_buckets[-1] >= self.max_seq_len:
            raise ValueError(
                f"largest prompt bucket {self.prompt_buckets[-1]} leaves "
                f"no room to generate within max_seq_len {self.max_seq_len}")
        self.queue_depth = int(
            queue_depth or _flags.flag("FLAGS_genserve_queue_depth", 128))
        self.max_top_k = int(max_top_k)
        page_size = int(page_size
                        or _flags.flag("FLAGS_genserve_page_size", 16))
        if num_pages is None:
            num_pages = int(_flags.flag("FLAGS_genserve_num_pages", 0))
        if prefix_cache is None:
            prefix_cache = bool(int(
                _flags.flag("FLAGS_genserve_prefix_cache", 1)))

        # speculative decode: a draft model proposes spec_tokens per
        # iteration, the target verifies them in one batched step
        if draft_model is not None:
            dcfg = draft_model.cfg
            if dcfg.vocab_size != cfg.vocab_size:
                raise ValueError(
                    f"draft vocab {dcfg.vocab_size} != target vocab "
                    f"{cfg.vocab_size}")
            if self.max_seq_len > dcfg.max_position_embeddings:
                raise ValueError(
                    f"max_seq_len {self.max_seq_len} exceeds the draft "
                    "model's max_position_embeddings "
                    f"{dcfg.max_position_embeddings}")
            if mesh is not None:
                raise ValueError(
                    "speculative decode under a mesh is not supported "
                    "yet — drop draft_model or mesh")
        self.draft_model = draft_model
        # generation by blocks: declared by the model, never by a flag
        self.block_length = int(getattr(cfg, "block_length", 0) or 0)
        if self.block_length and (draft_model is not None
                                  or mesh is not None):
            raise ValueError(
                "a block-generating model is served without a draft model "
                "and without a mesh (neither path is written yet)")
        if spec_tokens is None:
            spec_tokens = int(_flags.flag("FLAGS_genserve_spec_tokens", 4))
        self.spec_tokens = int(spec_tokens) if draft_model is not None \
            else 0
        if draft_model is not None and self.spec_tokens < 1:
            raise ValueError(
                f"spec_tokens must be >= 1 with a draft model, got "
                f"{self.spec_tokens}")

        # chunked prefill: long prompts stream into the cache
        # prefill_chunk tokens per decode iteration (0 = whole-prompt)
        if prefill_chunk is None:
            prefill_chunk = int(
                _flags.flag("FLAGS_genserve_prefill_chunk", 0))
        self.prefill_chunk = int(prefill_chunk)
        if self.prefill_chunk:
            if self.prefill_chunk % page_size:
                raise ValueError(
                    f"prefill_chunk {self.prefill_chunk} must be a "
                    f"multiple of page_size {page_size} (chunk cursors "
                    "resume at page boundaries)")
            if self.prefill_chunk > self.prompt_buckets[-1]:
                raise ValueError(
                    f"prefill_chunk {self.prefill_chunk} exceeds the "
                    f"largest prompt bucket {self.prompt_buckets[-1]}")

        draft_kw = {}
        if draft_model is not None:
            draft_kw = dict(
                draft_layers=dcfg.num_layers,
                draft_num_heads=dcfg.num_heads,
                draft_head_dim=dcfg.hidden_size // dcfg.num_heads)
        # the KV pool holds what the model computes: bf16 weights, bf16 pool
        import jax.numpy as jnp

        kv_dtype = next((str(p.dtype) for p in model.parameters()
                         if jnp.issubdtype(p.dtype, jnp.floating)),
                        "float32")
        # layers that see a window: declared by the model, never by a flag
        windows = tuple(getattr(cfg, "layer_windows", ()) or ())
        if any(windows) and mesh is not None:
            raise ValueError(
                "a model with window layers is served without a mesh (the "
                "window pool's sharding is not written yet)")
        # layers with a recurrent state: declared by the model too
        state_kw = {}
        if getattr(cfg, "state_layers", ()):
            if mesh is not None:
                raise ValueError(
                    "a model with state layers is served without a mesh "
                    "(the states' sharding is not written yet)")
            state_kw = dict(state_layers=tuple(cfg.state_layers),
                            state_shape=tuple(cfg.state_shape),
                            conv_shape=tuple(cfg.conv_shape),
                            state_chunk=int(cfg.state_chunk),
                            state_pack=int(cfg.state_pack))
        self.geometry = CacheGeometry(
            num_layers=cfg.num_layers, max_slots=self.max_slots,
            max_seq_len=self.max_seq_len, num_heads=cfg.num_heads,
            head_dim=(getattr(cfg, "head_dim", 0)
                      or cfg.hidden_size // cfg.num_heads),
            vocab_size=cfg.vocab_size, page_size=page_size,
            num_pages=int(num_pages), dtype=kv_dtype,
            num_kv_heads=getattr(cfg, "num_kv_heads", 0),
            block_length=self.block_length,
            num_experts=getattr(cfg, "num_experts", 0),
            windows=windows, **draft_kw, **state_kw)
        geom = self.geometry
        self.metrics = GenerationMetrics(
            max_slots=self.max_slots, num_pages=geom.num_pages,
            window_pool=bool(geom.windows),
            state_pool=bool(geom.state_layers))
        # window pages go by the ids past the full pool's in the prefix
        # cache, which counts the two kinds apart; room for the entries of
        # four whole prompts at least (a prompt registers one a page)
        self._prefix = (PrefixCache(
            page_size, capacity=max(1024, 4 * geom.pages_per_slot),
            split=geom.num_pages if geom.windows else None,
            snapshots=geom.state_snapshots)
            if prefix_cache else None)
        self._slot_pins: dict[int, list] = {}   # slot -> pinned page ids
        self._queue: queue.Queue = queue.Queue(self.queue_depth)
        self._backlog: collections.deque = collections.deque()
        self._sched = SlotScheduler(
            self.max_slots, num_pages=geom.num_pages,
            window_pages=geom.window_pages if geom.windows else None)
        if mesh is not None and not hasattr(mesh, "axis_names"):
            # {"tp": 2}-style dict: build a mesh over exactly the
            # devices the shape needs (the process may expose more)
            import jax

            from ..distributed.mesh import build_mesh

            dims = [int(v) for v in dict(mesh).values()]
            devices = None
            if all(d > 0 for d in dims):
                n = 1
                for d in dims:
                    n *= d
                devices = jax.devices()[:n]
            mesh = build_mesh(dict(mesh), devices=devices)
        self._mesh = mesh
        if layout is None and mesh is not None:
            from ..distributed.layout import SpecLayout

            layout = SpecLayout()
        self._layout = layout
        self._thread = None
        self._started = False
        self._draining = False
        self._stopped = False
        self._idle = threading.Event()
        self._idle.set()
        self._iter = 0
        self._flight = None             # the _Step launched, not collected
        # the decode loop's phases (see _run): annotations under
        # paddle.genserve/ in a profiler trace, totals in /metrics
        self.timers = StepTimers("paddle.genserve")
        self.compile_count = 0
        self._state = None
        self._params = None
        self._buffers = None
        self._decode_exec = None
        self.decode_temp_bytes = None   # set by start(), from the compiler
        self._spec_exec = None
        self._block_exec = None
        self._expert_counts = None      # block_step's last published copy
        self._release_exec = None
        self._reclaim_exec = None
        self._prefill_execs = {}
        self._insert_execs = {}
        self._insert_prefix_execs = {}
        self._chunk_execs = {}
        self._draft_params = None
        self._draft_buffers = None

    # -- warmup: build + AOT-compile every executable ----------------------
    def start(self) -> "GenerationEngine":
        """Everything between construction and the first request, under
        start-up's scope `genserve` (`utils.profiler.startup()`):
        `genserve/state` (parameters gathered, pools, tables and
        registers onto the device, specs built), one
        `genserve/build/<executable>` each, `genserve/publish` (memory
        analysis, providers, the decode thread)."""
        if self._started:
            return self
        import jax
        import jax.numpy as jnp

        from .. import inference
        from ..nn.layer_base import functional_call, state_pytrees
        from ..tensor import Tensor

        boot = startup()
        since = boot.mark()
        # the builds stay in this frame, each calling aot_compile itself:
        # behind a helper of this method, with the lowering under a lambda,
        # the prompt passes of an 8-layer window model took half again as
        # long to lower on the chip machine (PERF.md section 6, PR 36)
        with boot.scope("genserve"), contextlib.ExitStack() as phase:
            phase.enter_context(boot.scope("genserve/state"))
            self.model.eval()
            params, buffers = state_pytrees(self.model)
            geom = self.geometry
            V = geom.vocab_size
            k_max = min(self.max_top_k, V)
            ps, pps = geom.page_size, geom.pages_per_slot
            seq_cap = geom.max_seq_len
            # static prefix extent of the hit-path executables: the largest
            # full-page prefix any admitted prompt can share
            pfx_pages = min(pps, -(-self.prompt_buckets[-1] // ps))
            B = geom.block_length
            prefix_kw = {"block": B} if B else {}
            # a window engine: the layers' windows, and each pool's layers
            W = geom.windows
            counted = bool(geom.num_experts)
            # layers with a recurrent state: every admission then carries
            # where its scan starts and where it leaves a snapshot
            R = bool(geom.state_layers)

            def window_args(extra, w_pin):
                """A window engine's trailing admission arguments `extra` (the
                window pool's shared ids, the first column its row keeps) as
                (ids for the prefix gather, `write_prompt`'s keywords, what is
                left of `extra`); its own pages start at `w_pin`."""
                if not W:
                    return None, {}, extra
                wshared, w_from = extra
                return wshared, {"window": (
                    (geom.full_layers, geom.window_layers), wshared, w_from,
                    w_pin)}, ()

            def state_args(slot, extra):
                """The trailing admission arguments `extra` of an engine with
                state layers (where the scan starts: a snapshot's place,
                `SCAN_FROM_ZERO` or `SCAN_FROM_SLOT`; the offset in the pass
                at which it leaves a snapshot; that snapshot's place, -1 for
                none) as (what `suffix_prefill` scans by, the place for
                `put_states`, what is left of `extra`)."""
                if not R:
                    return None, -1, extra
                start, snap_at, snap_to = extra
                return (slot, start, snap_at), snap_to, ()

            # sharding plan: None entries (no mesh) keep today's lowering
            mesh, layout = self._mesh, self._layout
            if mesh is not None:
                from jax.sharding import NamedSharding
                from jax.sharding import PartitionSpec as P

                rep = NamedSharding(mesh, P())
                pool_sh = NamedSharding(mesh, layout.prune(
                    layout.kv_page_spec(), geom.pool_shape, mesh))
                kv_sh = NamedSharding(mesh, layout.prune(
                    P(None, None, layout.tp_axis, None),
                    (geom.num_layers, 1, geom.num_heads, geom.head_dim), mesh))
                pspecs = layout.resolve(
                    {n: np.shape(a) for n, a in params.items()}, mesh,
                    warn=False)
                params = {n: jax.device_put(a, NamedSharding(mesh, pspecs[n]))
                          for n, a in params.items()}
                buffers = {n: jax.device_put(a, rep)
                           for n, a in buffers.items()}
            else:
                rep = pool_sh = kv_sh = None
            self._params, self._buffers = params, buffers
            draft = self.draft_model
            K = self.spec_tokens
            if draft is not None:
                draft.eval()
                dparams, dbuffers = state_pytrees(draft)
                self._draft_params, self._draft_buffers = dparams, dbuffers
            else:
                dparams = dbuffers = None

            def sample_token(lg, key, do_sample, temp, top_k):
                """Per-lane sampling, chain-compatible with generate():
                greedy = argmax of raw logits; sampling = temperature scale,
                static-width top-k cutoff (dynamic k), categorical over the
                [1, V] row exactly as the solo path draws it."""
                greedy = jnp.argmax(lg).astype(jnp.int32)
                lg2 = lg / jnp.maximum(temp, 1e-6)
                vals = jax.lax.top_k(lg2, k_max)[0]
                kth = vals[jnp.clip(top_k - 1, 0, k_max - 1)]
                lg3 = jnp.where((top_k > 0) & (lg2 < kth),
                                jnp.finfo(lg2.dtype).min, lg2)
                samp = jax.random.categorical(
                    key, lg3[None, :])[0].astype(jnp.int32)
                return jnp.where(do_sample, samp, greedy)

            def resume_chain(seed, resume_pos):
                """Mid-stream failover (router re-admission): fast-forward
                the per-request PRNG chain past the ``resume_pos`` tokens a
                dead replica already emitted.  The chain is k_0=PRNGKey(seed)
                with (k_i, s_i)=split(k_{i-1}) and token i drawn from s_i, so
                after the fast-forward the admission split below yields
                exactly (k_{P+1}, s_{P+1}) — the first resumed sample is the
                token the uninterrupted run would have drawn next, and the
                chain state is identical thereafter.  resume_pos=0 is the
                normal (non-resumed) admission, bitwise today's behavior."""
                key = jax.random.PRNGKey(seed)
                return jax.lax.fori_loop(
                    0, resume_pos, lambda _, k: jax.random.split(k)[0], key)

            model, geometry = self.model, geom

            def target_prefill(params, ids, length, *snap_at):
                out, _ = functional_call(
                    model, params, (Tensor(ids), length) + snap_at,
                    buffers=buffers, mutable=False, method="slot_prefill")
                if R:       # (k, v, logits, the scan's four states)
                    return out[:3] + tuple(out[3])
                if B:
                    # a block engine samples nothing at admission: the
                    # logits go, and the compiler drops the head with them
                    return out[0], out[1], jnp.zeros((1,), out[2].dtype)
                return out                     # (k [L,Sp,nkv,hd], v, logits [V])

            if draft is None:
                prefill_step = target_prefill
            else:
                def prefill_step(params, dparams, ids, length):
                    # one executable fills BOTH pools: the draft's KV must
                    # cover the prompt so its proposal chain can attend it
                    k, v, lg = target_prefill(params, ids, length)
                    (dk, dv, _), _ = functional_call(
                        draft, dparams, (Tensor(ids), length),
                        buffers=dbuffers, mutable=False,
                        method="slot_prefill")
                    return k, v, lg, dk, dv

            def arm(state, slot, logits, length, seed, resume_pos, do_sample,
                    temp, top_k, stop_pos, eos, pinned, opening=(), active=True):
                """Arm lane ``slot`` after its prompt is in the pool: sample
                the first token from ``logits``; or, for a block engine, open
                the first block: ``opening`` = (the prompt's last L mod B
                tokens padded to B, how many they are), known from the start,
                the rest masked.  Returns (state, first token)."""
                key, sub = jax.random.split(resume_chain(seed, resume_pos))
                if not B:
                    tok1 = sample_token(logits, sub, do_sample, temp, top_k)
                    return admit_slot(state, slot, tok1, length, key, do_sample,
                                      temp, top_k, stop_pos, eos, pinned,
                                      active), tok1
                tail, n_known = opening
                state = admit_slot(state, slot, 0, length // B * B, key,
                                   do_sample, temp, top_k, stop_pos, eos, pinned,
                                   active)
                known = jnp.arange(B, dtype=jnp.int32) < n_known
                return dict(
                    state,
                    blk=state["blk"].at[slot].set(
                        jnp.where(known, tail, mask_id)),
                    blk_open=state["blk_open"].at[slot].set(~known),
                    blk_step=state["blk_step"].at[slot].set(-1),
                    step=state["step"].at[slot].set(0)), jnp.int32(0)

            def insert_step(state, slot, k_new, v_new, logits, length, seed,
                            resume_pos, do_sample, temp, top_k, stop_pos, eos,
                            pinned, *extra):
                # prefix-miss admission: every mapped page is freshly
                # allocated and written (shared_n = 0).  `extra` is the
                # draft's K/V (speculative) or the opening block (blocks)
                no_shared = jnp.full((pps,), -1, jnp.int32)
                ends, snap_to = (), -1
                if R:       # extra: the cold pass's states, the snapshot's place
                    ends, snap_to, extra = extra[:4], extra[4], ()
                if W:       # extra: the first column the window row keeps
                    _, win, extra = window_args((no_shared,) + extra,
                                                jnp.int32(0))
                else:
                    win = {}
                draft_kv, opening = ((), extra) if B else (extra, ())
                state, row = write_prompt(state, slot, k_new, v_new, length,
                                          no_shared, jnp.int32(0), *draft_kv,
                                          **win)
                state, tok1 = arm(state, slot, logits, length, seed, resume_pos,
                                  do_sample, temp, top_k, stop_pos, eos, pinned,
                                  opening)
                return put_states(state, slot, ends, snap_to), tok1, row

            def suffix_prefill(params, dparams, state, ids, shared_ids,
                               shared_n, length, wshared_ids=None, scan=None):
                # prefill ONLY the suffix, attending over the prefix already
                # resident in the pool(s) — shared by the prefix-hit admission
                # path and every prefill chunk.  The suffix tokens sit at
                # prefix_len + i; only the last real one's logits are wanted.
                # Returns (k, v, logits, the draft's K/V or (), the state
                # layers' states for `put_states` or ()).
                prefix_len = shared_n * ps
                positions = (prefix_len
                             + jnp.arange(ids.shape[1], dtype=jnp.int32))[None]
                last = jnp.asarray(length, jnp.int32) - prefix_len - 1

                def suffix(m, p, b, k_pool, v_pool):
                    prefix = PrefixKV.gather_windowed(
                        state, shared_ids, wshared_ids, shared_n, pfx_pages,
                        W) if W else PrefixKV.gather(
                            k_pool, v_pool, shared_ids[:pfx_pages], prefix_len,
                            **prefix_kw)
                    if scan is not None:
                        # the state layers scan the suffix from where `scan`
                        # says: (slot, start, the offset of the snapshot)
                        slot, start, snap_at = scan
                        prefix = HybridKV(prefix, PromptStates(
                            *initial_states(state, slot, start),
                            jnp.asarray(length, jnp.int32) - prefix_len,
                            jnp.asarray(snap_at, jnp.int32),
                            chunk=geom.state_chunk, pack=geom.state_pack))
                    (lg, kv), _ = functional_call(
                        m, p, (ids, positions, prefix, last),
                        buffers=b, mutable=False, method="slot_step")
                    if scan is not None:
                        return kv.kv.suffix_kv(), lg[0, 0], kv.states.ends()
                    return kv.suffix_kv(), lg[0, 0], ()

                (k_suf, v_suf), logits, ends = suffix(
                    model, params, buffers, state["kp"], state["vp"])
                if draft is None:
                    return k_suf, v_suf, logits, (), ends
                draft_kv, _, _ = suffix(draft, dparams, dbuffers,
                                        state["dkp"], state["dvp"])
                return k_suf, v_suf, logits, draft_kv, ends

            def _insert_prefix(params, dparams, state, slot, ids, shared_ids,
                               shared_n, length, seed, resume_pos, do_sample,
                               temp, top_k, stop_pos, eos, pinned, *opening):
                # prefix-hit admission: the shared pages are never
                # recomputed; the suffix pages in at the (page-aligned)
                # boundary
                # an engine with state layers scans the suffix from a
                # restored snapshot (or zero), hands the slot the state after
                # the last token and the pool the one at the page boundary the
                # pass crosses: all inside this one executable
                scan, snap_to, opening = state_args(slot, opening)
                # a window engine's own pages start where the shared ones end
                wshared, win, opening = window_args(opening, shared_n)
                k_suf, v_suf, logits, draft_kv, ends = suffix_prefill(
                    params, dparams, state, ids, shared_ids, shared_n,
                    length, wshared, scan)
                state, row = write_prompt(state, slot, k_suf, v_suf, length,
                                          shared_ids, shared_n, *draft_kv,
                                          **win)
                state, tok1 = arm(state, slot, logits, length, seed, resume_pos,
                                  do_sample, temp, top_k, stop_pos, eos, pinned,
                                  opening)
                return put_states(state, slot, ends, snap_to), tok1, row

            if draft is None:
                def insert_prefix_step(params, state, *a):
                    return _insert_prefix(params, None, state, *a)
            else:
                insert_prefix_step = _insert_prefix

            def _chunk(params, dparams, state, slot, ids, shared_ids,
                       shared_n, length, seed, resume_pos, do_sample, temp,
                       top_k, stop_pos, eos, pin_now, pin_final, arm_now,
                       *opening):
                # one prefill chunk: scatter this slice's K/V behind the
                # resumable cursor; ONLY the final chunk (arm_now) samples
                # a real first token and activates the lane.  Until then
                # ``pinned`` stays at the prefix-cache hit count (pin_now)
                # so a cancel/deadline sweep frees every privately written
                # chunk page — the stale-pinned leak this executable exists
                # to prevent; the final chunk raises it to pin_final to
                # protect the pages about to be registered as shared.
                # an engine with state layers: every chunk but the first
                # scans on from the slot's own state (`SCAN_FROM_SLOT`)
                scan, snap_to, opening = state_args(slot, opening)
                # a window engine: what an earlier chunk wrote (index >=
                # pin_now) and the window has passed goes back
                wshared, win, opening = window_args(opening, pin_now)
                k_suf, v_suf, logits, draft_kv, ends = suffix_prefill(
                    params, dparams, state, ids, shared_ids, shared_n,
                    length, wshared, scan)
                state, row = write_prompt(state, slot, k_suf, v_suf, length,
                                          shared_ids, shared_n, *draft_kv,
                                          **win)
                pinned = jnp.where(jnp.asarray(arm_now, bool), pin_final,
                                   pin_now)
                state, tok1 = arm(state, slot, logits, length, seed, resume_pos,
                                  do_sample, temp, top_k, stop_pos, eos, pinned,
                                  opening, active=arm_now)
                return put_states(state, slot, ends, snap_to), tok1, row

            if draft is None:
                def chunk_step(params, state, *a):
                    return _chunk(params, None, state, *a)
            else:
                chunk_step = _chunk

            def decode_step(params, state):
                lane = jnp.arange(geometry.max_slots)
                pos, active = state["pos"], state["active"]
                ptab = state["ptab"]
                # (1) pop a fresh tail page for lanes whose write position
                # crossed into an unmapped page — in-graph allocation off
                # the free-list register (host reserved the worst case)
                pidx = jnp.clip(pos // ps, 0, pps - 1)
                cur = ptab[lane, pidx]
                need = active & (cur < 0)
                pages, free_count = take_pages(state["free_stack"],
                                               state["free_count"], need)
                ptab = ptab.at[lane, pidx].set(jnp.where(need, pages, cur))
                win = {}
                if W:       # the window pool's tail page, off its own stack
                    wtab = state["wtab"]
                    wcur = wtab[lane, pidx]
                    wneed = active & (wcur < 0)
                    wpages, wfree_count = take_pages(
                        state["wfree_stack"], state["wfree_count"], wneed)
                    wtab = wtab.at[lane, pidx].set(
                        jnp.where(wneed, wpages, wcur))
                    win = dict(wk_pages=state["wkp"], wv_pages=state["wvp"],
                               wrows=wtab, windows=W)
                # (2) one paged-attention token per lane
                source = PagedKV(state["kp"], state["vp"], ptab, pos, active,
                                 seq_cap, **win)
                if R:
                    # the state layers update the live lanes' state in place
                    # (the live lanes first: a dead lane's is never read)
                    n_live = active.sum(dtype=jnp.int32)
                    source = HybridKV(source, LaneStates(
                        state["ssm"], state["conv"], active,
                        jnp.argsort(~active, stable=True).astype(jnp.int32),
                        n_live))
                out, _ = functional_call(
                    model, params,
                    (state["tok"][:, None], pos[:, None], source),
                    dict(live=active) if counted else {},
                    buffers=buffers, mutable=False, method="slot_step")
                logits, kv = out[0], out[1]
                if R:
                    kv, held = kv.kv, kv.states
                logits, kp, vp = logits[:, 0], kv.k_pages, kv.v_pages
                pair = jax.vmap(jax.random.split)(state["rng"])
                new_keys, subs = pair[:, 0], pair[:, 1]
                toks = jax.vmap(sample_token)(
                    logits, subs, state["do_sample"], state["temp"],
                    state["top_k"])
                toks = jnp.where(active, toks, state["tok"])
                new_pos = jnp.where(active, pos + 1, pos)
                finished = active & ((toks == state["eos"])
                                     | (new_pos + 1 >= state["stop_pos"]))
                # (3) retire in-graph: finished lanes' PRIVATE pages (table
                # index >= pinned) go back on the free stack; shared prefix
                # pages stay resident for the prefix cache
                col = jnp.arange(pps, dtype=jnp.int32)[None, :]
                freeable = finished[:, None] & (ptab >= 0) \
                    & (col >= state["pinned"][:, None])
                free_stack, free_count = push_pages(
                    state["free_stack"], free_count,
                    jnp.where(freeable, ptab, -1).reshape(-1))
                ptab = jnp.where(finished[:, None], -1, ptab)
                new_state = dict(state, kp=kp, vp=vp, ptab=ptab,
                                 free_stack=free_stack, free_count=free_count,
                                 tok=toks, pos=new_pos, rng=new_keys,
                                 active=active & ~finished)
                report = (toks, finished)
                if R:
                    # the report gains how many lanes' state the step updated
                    new_state.update(ssm=held.ssm, conv=held.conv)
                    report += (n_live,)
                if W:
                    # (4) the window pool: what lies wholly behind the next
                    # query's window leaves the row (the lane's own pages go
                    # back on the stack), then retirement as above.  The
                    # step's report gains the pools' registers: pages off
                    # each free stack, table entries the live lanes hold
                    # after the step, pages let go behind the window so far
                    live = active & ~finished
                    wtab, wfree_stack, wfree_count, gone = slide_window(
                        state, wtab, wfree_count, new_pos, live, geom.window)
                    wfree_stack, wfree_count = push_pages(
                        wfree_stack, wfree_count, jnp.where(
                            finished[:, None] & (wtab >= 0)
                            & (col >= state["pinned"][:, None]),
                            wtab, -1).reshape(-1))
                    new_state.update(
                        wkp=kv.wk_pages, wvp=kv.wv_pages,
                        wtab=jnp.where(finished[:, None], -1, wtab),
                        wfree_stack=wfree_stack, wfree_count=wfree_count,
                        w_released=state["w_released"] + gone)
                    report += (jnp.stack([
                        geom.num_pages - new_state["free_count"],
                        geom.window_pages - new_state["wfree_count"],
                        (live[:, None] & (new_state["ptab"] >= 0)).sum(
                            dtype=jnp.int32),
                        (live[:, None] & (new_state["wtab"] >= 0)).sum(
                            dtype=jnp.int32),
                        new_state["w_released"]]),)
                if counted:
                    per, touched = out[2]
                    new_state["moe_counts"] = state["moe_counts"] + per
                    new_state["moe_touched"] = state["moe_touched"] + touched
                    # copies of their own, as block_step's
                    report += ((new_state["moe_counts"] + 0,
                                new_state["moe_touched"] + 0),)
                return (new_state,) + report

            def spec_step(params, dparams, state):
                """ONE speculative iteration: the draft model chains K
                greedy proposals, the target scores the committed token +
                all K proposals in one batched verify step, and each greedy
                lane emits the longest agreeing run + the target's first
                divergent token (1..K+1 tokens).  Sampling lanes ride the
                same executable emitting exactly one token from the verify
                chunk's position-0 logits with the unchanged per-lane PRNG
                chain — bitwise the non-speculative distribution.

                Rejected proposals need no rollback: their pages stay
                mapped inside the lane's reservation and the next
                iteration's chain/verify scatter overwrites the dead K/V at
                those positions before any emitted query can attend it.
                """
                lane = jnp.arange(geometry.max_slots)
                pos, active = state["pos"], state["active"]
                stop_pos = state["stop_pos"]
                greedy_lane = ~state["do_sample"]
                ptab = state["ptab"]
                # (1) map every page covering [pos, hi] in one take — the
                # speculation window never writes past the slot's reserved
                # extent (positions clamp at stop_pos - 1)
                hi = jnp.minimum(pos + K, stop_pos - 1)
                col = jnp.arange(pps, dtype=jnp.int32)[None, :]
                need = active[:, None] & (ptab < 0) \
                    & (col >= (pos // ps)[:, None]) \
                    & (col <= (hi // ps)[:, None])
                pages, free_count = take_pages(
                    state["free_stack"], state["free_count"],
                    need.reshape(-1))
                ptab = jnp.where(need, pages.reshape(ptab.shape), ptab)
                # (2) draft chain: K+1 sequential one-token steps.  Step i
                # writes chain token c_i's draft K/V at pos+i and (i < K)
                # proposes c_{i+1} = argmax; step K only closes the draft
                # cache for a fully accepted run (its logits are discarded).
                dkv = PagedKV(state["dkp"], state["dvp"], ptab, pos, active,
                              seq_cap)
                t = state["tok"]
                chain = [t]
                for i in range(K + 1):
                    p_i = jnp.minimum(pos + i, stop_pos - 1)
                    (dlg, dkv), _ = functional_call(
                        draft, dparams,
                        (t[:, None], p_i[:, None], replace(dkv, positions=p_i)),
                        buffers=dbuffers, mutable=False, method="slot_step")
                    if i < K:
                        t = jnp.argmax(dlg[:, 0], axis=-1).astype(jnp.int32)
                        chain.append(t)
                tokens = jnp.stack(chain, axis=1)        # [slots, K+1]
                # (3) target verification: score all K+1 candidates at once
                P = jnp.minimum(
                    pos[:, None] + jnp.arange(K + 1, dtype=jnp.int32)[None],
                    (stop_pos - 1)[:, None])
                (logits, kv), _ = functional_call(
                    model, params,
                    (tokens, P,
                     PagedKV(state["kp"], state["vp"], ptab, P, active,
                             seq_cap)),
                    buffers=buffers, mutable=False, method="slot_step")
                # (4) accept/emit: outs[:, i] is what the target generates
                # after consuming c_0..c_i; position 0 goes through the
                # full sampling path (== argmax for greedy lanes) so the
                # PRNG chain advances exactly once per iteration
                pair = jax.vmap(jax.random.split)(state["rng"])
                new_keys, subs = pair[:, 0], pair[:, 1]
                outs = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                out0 = jax.vmap(sample_token)(
                    logits[:, 0], subs, state["do_sample"], state["temp"],
                    state["top_k"])
                outs = outs.at[:, 0].set(out0)
                # emitted_i: outs[:, i] is produced this iteration — needs
                # the previous emission alive (not finished) and draft
                # proposal c_i to match what the target just generated;
                # fin_i mirrors the non-speculative stop arithmetic for the
                # equivalent iteration at write position pos + i
                em = active
                emitted, fins = [], []
                for i in range(K + 1):
                    if i > 0:
                        em = em & ~fins[i - 1] & greedy_lane \
                            & (tokens[:, i] == outs[:, i - 1])
                    fin = (outs[:, i] == state["eos"]) \
                        | (pos + i + 2 >= stop_pos)
                    emitted.append(em)
                    fins.append(fin)
                emitted = jnp.stack(emitted, axis=1)     # [slots, K+1]
                fins = jnp.stack(fins, axis=1)
                n_emit = emitted.sum(axis=1).astype(jnp.int32)
                new_tok = outs[lane, jnp.maximum(n_emit - 1, 0)]
                new_tok = jnp.where(active, new_tok, state["tok"])
                new_pos = jnp.where(active, pos + n_emit, pos)
                finished = active & (emitted & fins).any(axis=1)
                # (5) retire in-graph, same as the plain decode step
                freeable = finished[:, None] & (ptab >= 0) \
                    & (col >= state["pinned"][:, None])
                free_stack, free_count = push_pages(
                    state["free_stack"], free_count,
                    jnp.where(freeable, ptab, -1).reshape(-1))
                ptab = jnp.where(finished[:, None], -1, ptab)
                new_state = dict(state, kp=kv.k_pages, vp=kv.v_pages,
                                 dkp=dkv.k_pages, dvp=dkv.v_pages,
                                 ptab=ptab, free_stack=free_stack,
                                 free_count=free_count, tok=new_tok,
                                 pos=new_pos, rng=new_keys,
                                 active=active & ~finished)
                return new_state, outs, emitted, finished

            if B:
                cfg = model.cfg
                mask_id = int(cfg.mask_token_id)
                steps = int(cfg.denoising_steps)
                if not 1 <= steps <= B:
                    raise ValueError(
                        f"{steps} denoising steps for a block of {B}")
                # tokens a step unmasks under the static strategy: B / steps,
                # the remainder going to the first steps
                n_transfer = jnp.asarray(
                    [B // steps + (1 if t < B % steps else 0)
                     for t in range(steps)], jnp.int32)
                strategy = getattr(cfg, "remasking_strategy",
                                   "low_confidence_static")
                if strategy not in ("low_confidence_static",
                                    "low_confidence_dynamic"):
                    raise ValueError(
                        f"unknown remasking strategy {strategy!r}")
                threshold = float(getattr(cfg, "confidence_threshold", 0.85))

            def block_step(params, state):
                """ONE iteration of generation by blocks.  A lane holds the
                block [start, start + B) (``pos`` is its start).  Every live
                lane's B tokens run at their positions over the paged pool,
                each query seeing the committed prefix and the whole block;
                the block's K/V are written EVERY pass at the block's own
                positions, where nothing but the block's own queries can see
                them before the final pass overwrites them (as ``spec_step``
                argues for rejected proposals).  A lane with masks left takes
                a token and its probability at every masked position and
                unmasks by its strategy; a lane with none left has just
                written its block's final K/V: it moves to the next block,
                all masked, or retires on eos or at ``stop_pos``.

                Returns (state, out [slots, 2B + 3] int32: the block's tokens
                and the step at which each was unmasked, then three flags:
                the lane resolved its last mask this pass, the pass was the
                lane's final (committing) one, the lane retired; and the
                routed-assignment counters, as buffers of their own)."""
                lane = jnp.arange(geometry.max_slots)
                start, active = state["pos"], state["active"]
                ptab = state["ptab"]
                # (1) map the page under the block (B divides the page size)
                pidx = jnp.clip(start // ps, 0, pps - 1)
                cur = ptab[lane, pidx]
                need = active & (cur < 0)
                pages, free_count = take_pages(state["free_stack"],
                                               state["free_count"], need)
                ptab = ptab.at[lane, pidx].set(jnp.where(need, pages, cur))
                # (2) the block's B tokens a lane, the block visible both ways
                off = jnp.arange(B, dtype=jnp.int32)[None]
                P = start[:, None] + off
                # one last key a lane, the block's end: the queries of a
                # lane share their keys, and PagedKV walks the lane's pages
                out, _ = functional_call(
                    model, params,
                    (state["blk"], P,
                     PagedKV(state["kp"], state["vp"], ptab, P, active,
                             seq_cap, limits=start + B - 1)),
                    dict(live=active) if counted else {},
                    buffers=buffers, mutable=False, method="slot_step")
                logits, kv = out[0].astype(jnp.float32), out[1]
                # (3) denoise: the token and its probability at each position
                x0 = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                conf = 1.0 / jnp.exp(
                    logits - logits.max(-1, keepdims=True)).sum(-1)
                blk, is_open, step = state["blk"], state["blk_open"], \
                    state["step"]
                denoise = active & is_open.any(axis=1)
                commit = active & ~is_open.any(axis=1)
                n_t = n_transfer[jnp.clip(step, 0, steps - 1)][:, None]
                # the n_t most confident masked positions, ties to the lower
                # index; a known position is never chosen
                order = jnp.argsort(jnp.where(is_open, -conf, 2.0), axis=1,
                                    stable=True)
                rank = jnp.argsort(order, axis=1, stable=True)
                pick = is_open & (rank < n_t)
                if strategy == "low_confidence_dynamic":
                    high = is_open & (conf > threshold)
                    pick = jnp.where(high.sum(1, keepdims=True) >= n_t, high,
                                     pick)
                pick = pick & denoise[:, None]
                blk = jnp.where(pick, x0, blk)
                is_open = is_open & ~pick
                blk_step = jnp.where(pick, step[:, None], state["blk_step"])
                resolved = denoise & ~is_open.any(axis=1)
                step = jnp.where(denoise, step + 1, step)
                # (4) commit: the next block, or retirement on an eos among
                # the block's generated tokens inside the budget, or at it
                stop_pos = state["stop_pos"]
                eos_in = ((blk_step >= 0) & (P < stop_pos[:, None])
                          & (blk == state["eos"][:, None])).any(axis=1)
                finished = commit & (eos_in | (start + B >= stop_pos))
                nxt = commit & ~finished
                report = jnp.concatenate(
                    [blk, blk_step,
                     jnp.stack([resolved, commit, finished], 1).astype(
                         jnp.int32)], axis=1)
                blk = jnp.where(nxt[:, None], mask_id, blk)
                is_open = is_open | nxt[:, None]
                blk_step = jnp.where(nxt[:, None], -1, blk_step)
                step = jnp.where(nxt, 0, step)
                start = jnp.where(nxt, start + B, start)
                # (5) retire in-graph, as the plain decode step does
                col = jnp.arange(pps, dtype=jnp.int32)[None, :]
                freeable = finished[:, None] & (ptab >= 0) \
                    & (col >= state["pinned"][:, None])
                free_stack, free_count = push_pages(
                    state["free_stack"], free_count,
                    jnp.where(freeable, ptab, -1).reshape(-1))
                ptab = jnp.where(finished[:, None], -1, ptab)
                new_state = dict(state, kp=kv.k_pages, vp=kv.v_pages, ptab=ptab,
                                 free_stack=free_stack, free_count=free_count,
                                 pos=start, blk=blk, blk_open=is_open,
                                 blk_step=blk_step, step=step,
                                 active=active & ~finished)
                counts = ()
                if counted:
                    per, touched = out[2]
                    new_state["moe_counts"] = state["moe_counts"] + per
                    new_state["moe_touched"] = state["moe_touched"] + touched
                    # copies of their own: read from other threads while the
                    # loop donates the state
                    counts = (new_state["moe_counts"] + 0,
                              new_state["moe_touched"] + 0)
                return new_state, report, counts

            def release_step(state, mask):
                return release_slots(state, mask)

            def reclaim_step(state, pages, *wpages):
                return reclaim_pages(state, pages, *wpages)

            self._state = make_state(geom)
            if mesh is not None:
                state_sh = {k: (pool_sh if k in ("kp", "vp") else rep)
                            for k in self._state}
                self._state = {k: jax.device_put(a, state_sh[k])
                               for k, a in self._state.items()}
            else:
                state_sh = None
            sspec = state_specs(self._state, shardings=state_sh)
            if mesh is not None:
                pspec = {n: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                 sharding=a.sharding)
                         for n, a in params.items()}

                def sds(shape, dtype, sh=rep):
                    return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)
            else:
                pspec = inference.spec_tree(params)

                def sds(shape, dtype, sh=None):
                    return jax.ShapeDtypeStruct(shape, dtype)
            dpspec = (inference.spec_tree(dparams)
                      if draft is not None else None)  # draft => no mesh
            i32 = sds((), np.int32)
            f32 = sds((), np.float32)
            b1 = sds((), np.bool_)
            pvec = sds((pps,), np.int32)
            out_state = state_sh if mesh is not None else None

            def outs(*tail):
                # out-shardings pinned to in-shardings (donation contract);
                # None (no mesh) keeps the default lowering
                if mesh is None:
                    return None
                return (out_state,) + tail

            chunk_bucket = (self._bucket_for(self.prefill_chunk)
                            if self.prefill_chunk else 0)
            phase.close()               # `genserve/state` ends here
            if K:
                with boot.executable("genserve/build/spec_step"):
                    self._spec_exec = inference.aot_compile(
                        spec_step, (pspec, dpspec, sspec),
                        donate_argnums=(2,))
            elif B:
                with boot.executable("genserve/build/block_step"):
                    self._block_exec = inference.aot_compile(
                        block_step, (pspec, sspec), donate_argnums=(1,))
            else:
                with boot.executable("genserve/build/decode_step"):
                    self._decode_exec = inference.aot_compile(
                        decode_step, (pspec, sspec), donate_argnums=(1,),
                        out_shardings=outs(rep, rep))
            with boot.executable("genserve/build/release_step"):
                self._release_exec = inference.aot_compile(
                    release_step, (sspec, sds((self.max_slots,), np.bool_)),
                    donate_argnums=(0,), out_shardings=out_state)
            if self._prefix is not None:
                with boot.executable("genserve/build/reclaim_step"):
                    self._reclaim_exec = inference.aot_compile(
                        reclaim_step, (sspec, pvec) + ((pvec,) if W else ()),
                        donate_argnums=(0,), out_shardings=out_state)
            dpre = (dpspec,) if draft is not None else ()
            for sp in self.prompt_buckets:
                ids = sds((1, sp), np.int32)
                with boot.executable(f"genserve/build/prefill.{sp}"):
                    self._prefill_execs[sp] = inference.aot_compile(
                        prefill_step,
                        (pspec,) + dpre + (ids, i32) + ((i32,) if R else ()),
                        out_shardings=(kv_sh, kv_sh, rep)
                        if mesh is not None else None)
                # insert takes what prefill gives, in the model's own
                # dtypes (bf16 weights hand over bf16 K/V and logits)
                pre = self._prefill_execs[sp].out_info
                kv = sds(pre[0].shape, pre[0].dtype, kv_sh)
                lg = sds(pre[2].shape, pre[2].dtype)
                dkv_in = tuple(sds(a.shape, a.dtype) for a in pre[3:])
                # a block engine's admissions carry the opening block, a
                # window engine's the window pool's shared ids and the
                # first column its row keeps
                # an engine with state layers' where the scan starts, the
                # snapshot's offset and its place in the pool
                opening = (sds((B,), np.int32), i32) if B else \
                    (pvec, i32) if W else (i32, i32, i32) if R else ()
                with boot.executable(f"genserve/build/insert.{sp}"):
                    self._insert_execs[sp] = inference.aot_compile(
                        insert_step,
                        (sspec, i32, kv, kv, lg, i32, i32, i32, b1, f32, i32,
                         i32, i32, i32) + dkv_in
                        + opening[2 if R else 1 if W else 0:],
                        donate_argnums=(0,), out_shardings=outs(rep, rep))
                tail = (i32, ids, pvec, i32, i32, i32, i32, b1, f32, i32,
                        i32, i32, i32)
                if self._prefix is not None:
                    with boot.executable(f"genserve/build/insert_prefix.{sp}"):
                        self._insert_prefix_execs[sp] = inference.aot_compile(
                            insert_prefix_step,
                            (pspec,) + dpre + (sspec,) + tail + opening,
                            donate_argnums=(1 + len(dpre),),
                            out_shardings=outs(rep, rep))
                if self.prefill_chunk and sp <= chunk_bucket:
                    with boot.executable(f"genserve/build/chunk.{sp}"):
                        self._chunk_execs[sp] = inference.aot_compile(
                            chunk_step,
                            (pspec,) + dpre + (sspec,) + tail[:-1]
                            + (i32, i32, b1) + opening,
                            donate_argnums=(1 + len(dpre),),
                            out_shardings=outs(rep, rep))
            phase.enter_context(boot.scope("genserve/publish"))
            # the decode step rewrites the donated pools in place: what it
            # holds beside them stays far under one layer's plane of one pool
            # (a copied plane or pool would show here before any chip run)
            mem = (self._spec_exec or self._block_exec
                   or self._decode_exec).memory_analysis()
            self.decode_temp_bytes = (int(mem.temp_size_in_bytes)
                                      if mem is not None else None)
            # publish introspection surfaces (monitor/perf.py): the decode
            # op table over /debug/perf, and owner tags so the buffer
            # census attributes the KV cache and weights ("latest engine
            # wins" — one process, one serving engine in practice)
            from ..monitor import perf as _perf

            _perf.register_provider("decode", self.op_report)
            _perf.register_owner("params", lambda: self._params)
            _perf.register_owner("kv_pages", lambda: self._state)

            self._started = True
            self._thread = threading.Thread(target=self._run, daemon=True,
                                            name="paddle-genserve-decode")
            self._thread.start()
        # one source for the count: the rows this start built
        self.compile_count = sum(
            r["built"] for r in boot.table(["genserve/build"], since))
        self.metrics.set_compile_count(self.compile_count)
        if logger.isEnabledFor(logging.INFO):
            geom, mesh = self.geometry, self._mesh
            logger.info(
                "generation start-up: slots=%d S_max=%d prompt buckets=%s "
                "pages=%dx%d cache=%.1f MB decode temps=%s MB%s\n%s",
                self.max_slots, self.max_seq_len, self.prompt_buckets,
                geom.num_pages, geom.page_size, geom.kv_bytes() / 1048576,
                "n/a" if self.decode_temp_bytes is None
                else f"{self.decode_temp_bytes / 1048576:.1f}",
                f" mesh={dict(zip(mesh.axis_names, mesh.devices.shape))}"
                if mesh is not None else "",
                boot.report("genserve", since))
        return self

    def op_report(self, *, measured_step_ms=None, trace_dir=None):
        """Per-op attribution of the AOT-compiled decode step
        (monitor/perf.py).  Measured time defaults to the inter-token
        p50 — in steady state one decode iteration IS the inter-token
        gap.  Reads only the compiled executable's HLO; never touches
        the live (donated) decode state."""
        exe = self._spec_exec or self._block_exec or self._decode_exec
        if exe is None:
            raise RuntimeError("op_report() before start()")
        ca = exe.cost_analysis()
        ca = ca[0] if isinstance(ca, (list, tuple)) else (ca or {})
        if measured_step_ms is None:
            # the gaps are kept in milliseconds; 0.0 means none yet
            measured_step_ms = self.metrics._gaps.quantile(0.50) or None
        from ..monitor import perf as _perf

        return _perf.build_report(exe, name="decode",
                                  cost_analysis=dict(ca),
                                  measured_step_ms=measured_step_ms,
                                  trace_dir=trace_dir)

    # -- request intake ----------------------------------------------------
    def _bucket_for(self, n: int) -> int:
        for b in self.prompt_buckets:
            if b >= n:
                return b
        raise ValueError(
            f"prompt length {n} exceeds the largest prompt bucket "
            f"{self.prompt_buckets[-1]}")

    def submit(self, prompt, max_new_tokens=32, *, do_sample=False,
               temperature=1.0, top_k=0, seed=0, resume_pos=0,
               eos_token_id=None, deadline_ms=None,
               span=None) -> GenerationHandle:
        """Enqueue one prompt (1-D int token ids).  Returns a streaming
        :class:`GenerationHandle`.  Raises QueueFullError under
        backpressure, EngineStoppedError once draining/stopped, and
        ValueError for requests the cache geometry cannot hold.

        `span`: an open request span to hang the engine's gen.queued /
        gen.prefill / gen.decode children from (the HTTP server passes
        its adopted server.generate span); without one, a sampled root
        span is started when the process tracer is enabled."""
        if self._draining or self._stopped:
            self.metrics.count("rejected_draining")
            raise EngineStoppedError("generation engine is draining — no "
                                     "new requests accepted")
        if not self._started:
            raise EngineStoppedError("generation engine not started — "
                                     "call start()")
        prompt = np.array(prompt, np.int32).reshape(-1)
        L = int(prompt.shape[0])
        if L < 1:
            raise ValueError("empty prompt")
        max_new_tokens = int(max_new_tokens)
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        bucket = self._bucket_for(L)
        if L + max_new_tokens > self.max_seq_len:
            raise ValueError(
                f"prompt {L} + max_new_tokens {max_new_tokens} exceeds "
                f"max_seq_len {self.max_seq_len}")
        worst_pages = self.geometry.pages_for(L + max_new_tokens)
        if worst_pages > self.geometry.num_pages:
            # could NEVER be admitted: even an empty pool is too small
            self.metrics.count("rejected_pages_exhausted")
            raise ValueError(
                f"request needs {worst_pages} KV pages worst-case; the "
                f"pool holds {self.geometry.num_pages} (raise num_pages "
                f"or page_size)")
        if self.geometry.windows and self.geometry.window_pages \
                < self._window_need(L, max_new_tokens, 0):
            self.metrics.count("rejected_pages_exhausted")
            raise ValueError(
                "the window pool is too small for this request even when "
                f"empty ({self.geometry.window_pages} pages; raise "
                "num_pages)")
        top_k = int(top_k)
        if top_k > self.max_top_k:
            raise ValueError(f"top_k {top_k} exceeds max_top_k "
                             f"{self.max_top_k}")
        resume_pos = int(resume_pos)
        if resume_pos < 0:
            raise ValueError("resume_pos must be >= 0")
        if self.block_length and (do_sample or resume_pos):
            raise ValueError(
                "generation by blocks is greedy and does not resume "
                "mid-stream yet (do_sample and resume_pos are not served)")
        eos = self.geometry.vocab_size if eos_token_id is None \
            else int(eos_token_id)
        deadline = (time.monotonic() + deadline_ms / 1e3
                    if deadline_ms is not None else None)
        own_span = False
        if span is not None and not span.sampled:
            span = None
        elif span is None:
            tracer = _tracing.default_tracer()
            if tracer.enabled:
                root = tracer.start_span(
                    "genserve.request",
                    attrs={"prompt_len": L,
                           "max_new_tokens": max_new_tokens})
                if root.sampled:
                    span, own_span = root, True
        req = _GenRequest(self, prompt, bucket, max_new_tokens,
                          bool(do_sample), float(temperature), top_k,
                          int(seed), eos, deadline, span=span,
                          own_span=own_span, resume_pos=resume_pos)
        if self.block_length:
            req.handle.steps = []
        if span is not None:
            # attached BEFORE enqueue: the decode thread may admit the
            # request (and close this child) before put_nowait returns
            req.span_queue = span.child("gen.queued", bucket=bucket)
        try:
            self._queue.put_nowait(req)
        except queue.Full:
            self.metrics.count("rejected_queue_full")
            req.end_spans("rejected_queue_full")
            raise QueueFullError(
                f"generation queue at capacity ({self.queue_depth}); "
                "retry with backoff") from None
        self._idle.clear()
        self.metrics.count("admitted")
        return req.handle

    def generate(self, prompt, max_new_tokens=32, timeout=None, **kw):
        """Synchronous convenience: submit + result."""
        return self.submit(prompt, max_new_tokens, **kw).result(timeout)

    # -- the decode loop ---------------------------------------------------
    def _wake(self):
        try:
            self._queue.put_nowait(_WAKE)
        except queue.Full:
            pass

    def _run(self):
        """The decode thread.  An iteration keeps ONE step in flight:
        ``pull``, ``sweep``, ``admit``, ``chunk``; then step k+1 is
        launched for the lanes armed now (``_launch``: dispatch only);
        and only then is step k, launched an iteration earlier, collected
        (``_collect``: ``fetch`` blocks until the device has run it, with
        step k+1 already queued behind it, and ``distribute`` hands its
        tokens to the lanes that were armed at ITS launch).  The compiled
        step reads nothing the host learns from the fetch (token,
        position, stop and eos are registers of the donated state; a
        lane that ends is deactivated and its pages freed in the graph),
        so the chip goes from one step to the next without the host.  A
        step's tokens reach the client one ``fetch`` after the step
        ends, as they always did.  With no lane armed nothing is
        launched, and what is in flight is collected before the loop
        blocks in ``wait`` or returns."""
        try:
            # Every statement of an iteration lies in one top-level
            # phase of self.timers (wait, pull, sweep, admit, chunk,
            # decode | spec_decode | block_step, fetch, distribute), so the phases'
            # totals sum to the loop's wall time; README "Reading a
            # trace" lists them with their children.
            scope = self.timers.scope
            while True:
                self._pull_requests()
                with scope("sweep"):
                    self._sweep_backlog()
                self._admit_ready()
                with scope("sweep"):
                    self._preempt_swept()
                    occupied = self._sched.occupied
                    self.metrics.set_occupancy(len(occupied))
                    self.metrics.set_page_occupancy(
                        self.geometry.num_pages
                        - self._sched.pages_available)
                    self.metrics.observe_loop(self.timers.totals)
                if occupied and not self._stopped:
                    # at most ONE prefill chunk per iteration, then a
                    # decode step for the armed lanes — a long prompt
                    # streams in without stalling in-flight streams
                    if self._sched.prefilling():
                        with scope("chunk"):
                            self._advance_chunk()
                    ahead = None
                    if len(self._sched.occupied) > self._sched.prefilling():
                        ahead = self._launch()
                    self._collect()
                    self._flight = ahead
                    continue
                self._collect()
                with scope("pull"):
                    if self._queue.empty() and not self._backlog:
                        self._idle.set()
                        if self._draining or self._stopped:
                            return
        except BaseException as e:  # pragma: no cover - last-resort:
            # never die silently
            logger.exception("generation decode loop crashed")
            try:
                from ..monitor import perf as _perf

                if _perf.is_oom(e):
                    # the decode thread CAUGHT the failure, so the
                    # crash excepthook will never see it — dump the
                    # census + op table postmortem here
                    _perf.oom_postmortem(e)
            except Exception:  # noqa: BLE001 - never mask the crash
                pass
            self._stopped = True
            try:
                # a step launched before the failure ran: its tokens
                # are its lanes', as they were when nothing ran ahead
                self._collect()
            except Exception:  # noqa: BLE001 - it fails with the rest
                logger.exception("the step in flight failed too")
            self._fail_everything(EngineStoppedError(
                "generation decode loop crashed"))
            self._idle.set()
            raise

    def _pull_requests(self):
        """Move queued requests to the backlog; block only when idle."""
        scope = self.timers.scope
        if (not self._sched.occupied and not self._backlog
                and self._flight is None
                and not (self._draining or self._stopped)):
            # no lane waits for a token: time here is nobody's latency
            with scope("wait"):
                req = self._queue.get()
            if req is not _WAKE:
                self._backlog.append(req)
        with scope("pull"):
            while True:
                try:
                    req = self._queue.get_nowait()
                except queue.Empty:
                    return
                if req is not _WAKE:
                    self._backlog.append(req)

    def _sweep_backlog(self):
        now = time.monotonic()
        keep = collections.deque()
        for req in self._backlog:
            if req.cancelled:
                self.metrics.count("cancelled")
                req.end_spans("cancelled")
                req.handle._finish()
            elif req.deadline is not None and now > req.deadline:
                self.metrics.count("deadline_expired")
                req.end_spans("deadline_expired")
                req.handle._finish(DeadlineExceededError(
                    "request deadline passed while queued"))
            else:
                keep.append(req)
        self._backlog = keep

    def _admit_ready(self):
        # with no free lane nothing can be admitted, so the head of the
        # backlog is not even looked up: an `admit` phase is an
        # admission (or, rarely, a head that pages cannot hold yet)
        while (self._backlog and not self._stopped
               and self._sched.has_free()):
            with self.timers.scope("admit"):
                if not self._admit_head():
                    return

    def _admit_head(self) -> bool:
        """Admit the head of the backlog if the pool can hold it; False
        when it has to wait for pages."""
        scope = self.timers.scope
        with scope("admit/lookup"):
            req = self._backlog[0]
            j_hit, shared = (self._prefix.lookup(req.prompt)
                             if self._prefix is not None else (0, ()))
            if self.geometry.state_layers:
                j_hit, shared = self._plan_scan(req, j_hit, shared)
            need = self.geometry.pages_for(
                len(req.prompt) + req.max_new_tokens) - j_hit
            if self.geometry.windows:
                need = (need, self._window_need(
                    len(req.prompt), req.max_new_tokens, j_hit))
            if not self._sched.can_admit(need):
                # page-pressure escape hatch BEFORE queuing: when a
                # free lane exists and idle prefix-cache residents are
                # what exhausts the pool, evict LRU entries until the
                # head's reservation fits — otherwise a stream of
                # distinct prompts parks one-reader prefixes over the
                # whole pool and the backlog never drains
                short = self._sched.short_of(need)
                if (self._prefix is not None and len(self._prefix)
                        and short > 0):
                    self._reclaim(self._prefix.evict_idle(short))
                    self._sync_resident()
                if not self._sched.can_admit(need):
                    # the pool cannot reserve the worst case even
                    # after eviction — FIFO head-of-line wait until a
                    # retirement frees pages (admit-and-crash is not
                    # an option)
                    return False
            self._backlog.popleft()
            slot = self._sched.admit(req, n_pages=need)
        try:
            suffix_len = len(req.prompt) - j_hit * self.geometry.page_size
            if self.prefill_chunk and suffix_len > self.prefill_chunk:
                self._admit_chunked(req, slot, j_hit, shared)
            else:
                self._admit(req, slot, j_hit, shared)
        except Exception as e:  # noqa: BLE001 - fail THIS request,
            # keep the decode loop alive for the others
            logger.exception("generation admission failed")
            if req.snap_pages:      # the snapshot its pass was to leave
                self._prefix.drop_snapshot_of(req.prompt, req.snap_pages)
            self.metrics.count("errors")
            self._host_retire(slot)
            req.end_spans("error")
            req.handle._finish(e)
        return True

    def _plan_scan(self, req: _GenRequest, j_match: int, shared):
        """An engine with state layers: a prefix hit is worth only as deep
        as a snapshot of the state lies.  Of the ``j_match`` pages the
        lookup matched, the admission shares those up to the deepest
        snapshot (none: the scan starts from zero, and the prompt pass
        computes the pages again); where the match is deeper than the
        snapshot, the pass leaves a snapshot at the matched depth, so the
        next request with this prefix restores there.  Returns the (hit
        pages, shared ids) the admission goes on with."""
        with self.timers.scope("admit/restore"):
            j, place = (self._prefix.lookup_state(req.prompt, j_match)
                        if j_match else (0, -1))
            req.restore = place if j else SCAN_FROM_ZERO
            req.snap_pages = j_match if j_match > j else 0
        return j, shared[:j]

    def _scan_args(self, req: _GenRequest, cur: int, end: int, first=True):
        """The trailing arguments of a suffix pass over tokens [cur, end)
        of an engine with state layers, () for any other: where the scan
        starts (the snapshot to restore or ``SCAN_FROM_ZERO``; behind an
        earlier chunk ``SCAN_FROM_SLOT``, the slot's own state), the offset
        in the pass of the snapshot it leaves, and the snapshot's place in
        the pool (-1: none)."""
        if not self.geometry.state_layers:
            return ()
        start = req.restore if first else SCAN_FROM_SLOT
        at = req.snap_pages * self.geometry.page_size
        snap_at, snap_to = 0, -1
        if req.snap_pages and cur < at <= end:
            with self.timers.scope("admit/snapshot"):
                snap_at = at - cur
                snap_to = self._prefix.take_snapshot(req.prompt,
                                                     req.snap_pages)
        self.metrics.observe_scan(end - cur, restored=first and start >= 0)
        return np.int32(start), np.int32(snap_at), np.int32(snap_to)

    def _sync_snapshots(self):
        if self.geometry.state_layers and self._prefix is not None:
            self.metrics.set_state_snapshots(
                self._prefix.snapshots_taken, self._prefix.snapshots_evicted,
                self._prefix.snapshots_live)

    def _admit(self, req: _GenRequest, slot: int, j_hit: int, shared):
        """Prefill + insert: map the slot's cache pages (reusing any
        cached prefix pages) and arm the lane with its first sampled
        token — the request joins the in-flight batch at this iteration
        boundary."""
        geom = self.geometry
        L = len(req.prompt)
        if req.span_queue is not None:
            req.span_queue.end(status="ok")
            req.span_queue = None
        j_reg = self._shareable(L, j_hit)
        pinned = max(j_hit, j_reg)
        hit_ids = shared        # the lookup's, of both pools where two
        shared, win = self._window_args(j_hit, shared, L)
        sp_prefill = (req.span.child("gen.prefill", bucket=req.bucket,
                                     prompt_len=L, slot=slot,
                                     prefix_pages=j_hit, iter=self._iter)
                      if req.span is not None else None)
        stop = np.int32(L + req.max_new_tokens)
        dpre = ((self._draft_params,)
                if self.draft_model is not None else ())
        opening = self._opening(req.prompt)
        scope = self.timers.scope
        scan = self._scan_args(req, j_hit * geom.page_size, L)
        with scope("prefill"):
            if j_hit > 0:
                # prefix hit: prefill ONLY the suffix
                suffix = req.prompt[j_hit * geom.page_size:]
                sb = self._bucket_for(len(suffix))
                ids = np.zeros((1, sb), np.int32)
                ids[0, :len(suffix)] = suffix
                shared_vec = np.full((geom.pages_per_slot,), -1, np.int32)
                shared_vec[:j_hit] = shared[:j_hit]
                state, tok1, row = self._insert_prefix_execs[sb](
                    self._params, *dpre, self._state, np.int32(slot),
                    ids, shared_vec, np.int32(j_hit), np.int32(L),
                    np.int32(req.seed), np.int32(req.resume_pos),
                    np.bool_(req.do_sample),
                    np.float32(req.temperature), np.int32(req.top_k),
                    stop, np.int32(req.eos), np.int32(pinned), *opening,
                    *win, *scan)
            else:
                ids = np.zeros((1, req.bucket), np.int32)
                ids[0, :L] = req.prompt
                out = self._prefill_execs[req.bucket](
                    self._params, *dpre, ids, np.int32(L), *scan[1:2])
                k_new, v_new, logits = out[:3]
                state, tok1, row = self._insert_execs[req.bucket](
                    self._state, np.int32(slot), k_new, v_new, logits,
                    np.int32(L), np.int32(req.seed),
                    np.int32(req.resume_pos),
                    np.bool_(req.do_sample), np.float32(req.temperature),
                    np.int32(req.top_k), stop, np.int32(req.eos),
                    np.int32(pinned), *out[3:], *opening, *win[1:],
                    *scan[2:])
        self._state = state
        with scope("admit/fetch"), host_fetch():
            # blocks until the device has run the prefill and insert
            t1 = int(np.array(tok1, copy=True))
            row_np = np.array(row, copy=True)
        if self._prefix is not None:
            with scope("admit/register"):
                self.metrics.count_prefix(hit=j_hit > 0)
                # behind a hit a window engine registers nothing, and its
                # window row no longer maps all it shares: pin the hit's
                pin_pages = list(hit_ids) if pinned == j_hit else \
                    self._prefix.ids(row_np, pinned)
                self._prefix.pin(pin_pages)
                self._slot_pins[slot] = pin_pages
                self._reclaim(self._prefix.register(req.prompt, row_np,
                                                    j_hit, j_reg))
                self._sync_resident()
                self._sync_snapshots()
        with scope("admit/push"):
            if sp_prefill is not None:
                sp_prefill.end(status="ok")
            self._push_first(req, slot, t1)

    def _shareable(self, L: int, j_hit: int) -> int:
        """Pages of an L-token prompt the admission registers as shared.
        A window engine registers a prompt only where it keeps its window
        layers' K/V whole, which is on a miss: behind a hit its own pages
        slide, and nothing deeper than the hit is registered."""
        if self._prefix is None:
            return 0
        if self.geometry.windows and j_hit:
            return j_hit
        return self._prefix.shareable_pages(L)

    def _opening(self, prompt):
        """A block engine's extra admission arguments: the prompt's last
        L mod B tokens padded to a block, and how many they are (they
        open the first generated block as known tokens)."""
        B = self.block_length
        if not B:
            return ()
        n = len(prompt) % B
        tail = np.zeros((B,), np.int32)
        tail[:n] = prompt[len(prompt) - n:]
        return tail, np.int32(n)

    def _push_first(self, req: _GenRequest, slot: int, t1: int):
        """The first token goes to the handle (TTFT observed); the lane
        retires at once on eos / max_new_tokens == 1.  A block engine has
        no token yet: its first come from ``block_step``."""
        if self.block_length:
            req.block_start = len(req.prompt) // self.block_length \
                * self.block_length
            if req.span is not None:
                req.span_decode = req.span.child("gen.decode", slot=slot)
            return
        now = time.monotonic()
        req.t_last_token = now
        req.handle._push(t1)
        if req.span is not None:
            req.span.event("first_token", slot=slot, iter=self._iter)
        self.metrics.observe_ttft(now - req.handle.t_submit)
        self.metrics.observe_tokens(1)
        if req.max_new_tokens == 1 or t1 == req.eos:
            self._release([slot])
            self._retire_ended(slot, req)
        elif req.span is not None:
            req.span_decode = req.span.child("gen.decode", slot=slot)

    def _admit_chunked(self, req: _GenRequest, slot: int, j_hit: int,
                       shared):
        """Admit a long prompt WITHOUT prefilling it: the slot occupies
        the scheduler (worst-case pages reserved up front) while
        ``_advance_chunk`` streams ``prefill_chunk``-token slices into
        its pages, one per decode iteration.  Only the final chunk arms
        the lane."""
        geom = self.geometry
        L = len(req.prompt)
        if req.span_queue is not None:
            req.span_queue.end(status="ok")
            req.span_queue = None
        j_reg = self._shareable(L, j_hit)
        req.j_hit = j_hit
        req.pin_final = max(j_hit, j_reg)
        req.prefilling = True
        req.prefill_cursor = j_hit * geom.page_size
        row = np.full((geom.pages_per_slot,), -1, np.int32)
        if j_hit > 0:
            row[:j_hit] = shared[:j_hit]
        req.chunk_row = row
        win = self._window_args(j_hit, shared, 0)[1]
        req.chunk_wrow = win[0] if win else None
        if self._prefix is not None:
            with self.timers.scope("admit/register"):
                self.metrics.count_prefix(hit=j_hit > 0)
                # pin the cache-shared head NOW: it must stay resident
                # for every later chunk's prefix gather (LRU cannot
                # evict it)
                pin_pages = [int(p) for p in shared]
                self._prefix.pin(pin_pages)
                self._slot_pins[slot] = pin_pages
                self._sync_resident()
        if req.span is not None:
            req.span_decode = req.span.child(
                "gen.prefill", bucket=req.bucket, prompt_len=L,
                slot=slot, prefix_pages=j_hit, chunked=True,
                iter=self._iter)

    def _advance_chunk(self):
        """Advance ONE prefilling slot by one chunk — bounded work per
        decode iteration, so armed lanes' inter-token gap stays flat
        while a long prompt streams in."""
        if not self.prefill_chunk:
            return
        slot = req = None
        for s, r in self._sched.occupied.items():
            if r.prefilling:
                slot, req = s, r
                break
        if req is None:
            return
        geom = self.geometry
        L = len(req.prompt)
        cur = req.prefill_cursor
        end = min(cur + self.prefill_chunk, L)
        arm = end >= L
        chunk = req.prompt[cur:end]
        sb = self._bucket_for(len(chunk))
        ids = np.zeros((1, sb), np.int32)
        ids[0, :len(chunk)] = chunk
        shared_vec = np.array(req.chunk_row, np.int32)
        win = ()
        if geom.windows:
            keep_all = self._prefix is not None and req.j_hit == 0
            win = (np.array(req.chunk_wrow, np.int32),
                   np.int32(0 if keep_all else geom.first_col(end)))
        dpre = ((self._draft_params,)
                if self.draft_model is not None else ())
        scope = self.timers.scope
        scan = self._scan_args(req, cur, end,
                               first=cur == req.j_hit * geom.page_size)
        with scope("prefill_chunk"):
            state, tok1, row = self._chunk_execs[sb](
                self._params, *dpre, self._state, np.int32(slot), ids,
                shared_vec, np.int32(cur // geom.page_size),
                np.int32(end), np.int32(req.seed),
                np.int32(req.resume_pos),
                np.bool_(req.do_sample), np.float32(req.temperature),
                np.int32(req.top_k),
                np.int32(L + req.max_new_tokens), np.int32(req.eos),
                np.int32(req.j_hit), np.int32(req.pin_final),
                np.bool_(arm), *self._opening(req.prompt), *win, *scan)
        self._state = state
        with scope("chunk/fetch"), host_fetch():
            t1 = int(np.array(tok1, copy=True))
            row_np = np.array(row, copy=True)
        if geom.windows:
            req.chunk_row, req.chunk_wrow = row_np
        else:
            req.chunk_row = row_np
        req.prefill_cursor = end
        self.metrics.count_chunk()
        if req.span_decode is not None:
            req.span_decode.event("chunk", end=end, iter=self._iter)
        if arm:
            self._arm_chunked(req, slot, row_np, t1)

    def _arm_chunked(self, req: _GenRequest, slot: int, row_np, t1: int):
        """Final chunk ran: register the prompt's shareable prefix,
        deliver the first token, and hand the lane to the decode step
        (or retire immediately on eos / max_new_tokens == 1)."""
        req.prefilling = False
        j_hit = req.j_hit
        if self._prefix is not None:
            j_reg = self._shareable(len(req.prompt), j_hit)
            # the cache-hit head was pinned at admission; pin the
            # freshly registered tail
            tail = self._prefix.ids(row_np, req.pin_final, j_hit)
            self._prefix.pin(tail)
            self._slot_pins[slot] = self._slot_pins.get(slot, []) + tail
            self._reclaim(self._prefix.register(req.prompt, row_np,
                                                j_hit, j_reg))
            self._sync_resident()
            self._sync_snapshots()
        if req.span_decode is not None:
            req.span_decode.end(status="ok")
            req.span_decode = None
        self._push_first(req, slot, t1)

    def _release(self, slots):
        mask = np.zeros((self.max_slots,), np.bool_)
        for s in slots:
            mask[s] = True
        self._state = self._release_exec(self._state, mask)

    def _host_retire(self, slot: int):
        """Host-side retirement: drop the slot's scheduler reservation
        and its prefix-cache pins, reclaiming shared pages whose
        refcount hit zero.  The device-side page free happened in-graph
        (decode/release).  Returns the slot's request."""
        req = self._sched.retire(slot)
        pages = self._slot_pins.pop(slot, None)
        if pages and self._prefix is not None:
            self._reclaim(self._prefix.unpin(pages))
        if self._prefix is not None:
            self._sync_resident()
        return req

    def _sync_resident(self):
        """Tell the scheduler what the prefix cache holds resident (of
        each pool, for a window engine)."""
        if self.geometry.windows:
            self._sched.set_shared_resident(
                self._prefix.resident_pages - self._prefix.resident_high,
                self._prefix.resident_high)
        else:
            self._sched.set_shared_resident(self._prefix.resident_pages)

    def _window_need(self, L: int, max_new: int, j_hit: int) -> int:
        """The most pages of the window pool a request can come to hold
        of its own: what its window meets, and on a miss under a prefix
        cache the prompt's full pages as well, which are then kept whole
        for later requests to share."""
        geom = self.geometry
        own = min(geom.pages_for(L + max_new) - j_hit, geom.window_cols)
        if self._prefix is not None and j_hit == 0:
            own += self._prefix.shareable_pages(L)
        return own

    def _window_args(self, j_hit: int, shared, end: int):
        """A window engine's extra arguments of an admission (or a chunk
        that ends at token ``end``): the shared pages split by pool, and
        the first column the window pool's row keeps: 0 where the prompt
        is being kept whole to be shared (a miss under a prefix cache),
        else the first page the next query's window meets.  Returns
        (full pool's shared ids, (window pool's ids [pps], first column))
        or (shared, ()) for an engine without windows."""
        geom = self.geometry
        if not geom.windows:
            return shared, ()
        wvec = np.full((geom.pages_per_slot,), -1, np.int32)
        wvec[:j_hit] = [p - geom.num_pages for p in shared[j_hit:]]
        keep_all = self._prefix is not None and j_hit == 0
        return shared[:j_hit], (
            wvec, np.int32(0 if keep_all else geom.first_col(end)))

    def _reclaim(self, pages):
        """Return evicted/orphaned prefix-cache pages to the device free
        stack (chunked through the fixed-width reclaim executable)."""
        if not pages:
            return
        pps = self.geometry.pages_per_slot

        def vecs(ids):
            for i in range(0, len(ids), pps):
                vec = np.full((pps,), -1, np.int32)
                vec[:len(ids[i:i + pps])] = ids[i:i + pps]
                yield vec

        if not self.geometry.windows:
            for vec in vecs(pages):
                self._state = self._reclaim_exec(self._state, vec)
            return
        import itertools

        n = self.geometry.num_pages
        none = np.full((pps,), -1, np.int32)
        for vec, wvec in itertools.zip_longest(
                vecs([p for p in pages if p < n]),
                vecs([p - n for p in pages if p >= n]), fillvalue=none):
            self._state = self._reclaim_exec(self._state, vec, wvec)

    def _preempt_swept(self):
        swept = self._sched.sweep()
        if not swept:
            return
        self._release([slot for slot, _, _ in swept])
        for slot, req, reason in swept:
            self._host_retire(slot)
            self.metrics.count(reason)
            self.metrics.count("preempted")
            req.end_spans(reason)
            req.handle._finish(
                None if reason == "cancelled" else DeadlineExceededError(
                    "request deadline passed mid-decode"))

    def _launch(self):
        """Dispatch ONE step (``decode_step`` | ``spec_step`` |
        ``block_step``) for the lanes armed now and fetch nothing: every
        armed lane advances a token (1..spec_tokens+1 when speculating; a
        block engine runs each lane's block once).  The state pytree is
        donated to the compiled executable (the KV page pool is rewritten
        on device, never fetched); what ``_collect`` fetches an iteration
        later is the step's small report.  Returns the ``_Step`` to
        collect: the iteration number, the (slot, request) pairs armed at
        this launch, and the report's device arrays."""
        spec, block = self._spec_exec, self._block_exec
        with self.timers.scope("spec_decode" if spec is not None else
                               "block_step" if block is not None else
                               "decode"):
            lanes = [(s, r) for s, r in self._sched.occupied.items()
                     if not r.prefilling]
            self._iter += 1
            chaos.on_step(self._iter)   # fault-injection seam (utils/chaos)
            before = self._flight
            self.metrics.count_step(
                ahead=before is not None and not before.out[0].is_ready())
            if spec is None:
                # where each lane attends from, of host integers, for the
                # share of the page tables that the paged kernel walks
                flying = () if before is None else \
                    {id(r) for _, r in before.lanes}
                self.metrics.observe_page_walk(*self.geometry.page_walk(
                    self._attends_from(r, id(r) in flying)
                    for _, r in lanes))
            if spec is not None:
                state, *out = spec(self._params, self._draft_params,
                                   self._state)
            else:
                state, *out = (block or self._decode_exec)(self._params,
                                                           self._state)
            self._state = state
            if self.geometry.num_experts:
                # the routed-assignment counters stay on the device
                self._expert_counts = out.pop()
            elif block is not None:
                out.pop()
        return _Step(self._iter, lanes, out)

    def _attends_from(self, req: _GenRequest, flying: bool):
        """The last position the lane's queries see in the step being
        launched, from what the host has been handed (``flying``: the
        step in flight is the lane's own too), or None for a lane whose
        budget is spent: it has ended on the device.  One-token engine:
        its prompt, the tokens it has been handed, and one more if
        flying.  Block engine: the end of the block the device holds,
        which is the one before ``block_start`` while the pass that
        commits it is still to launch."""
        L, B = len(req.prompt), self.block_length
        if not B:
            n = len(req.handle.tokens) + flying
            return L + n - 1 if n < req.max_new_tokens else None
        start = req.block_start
        if req.block_resolved and not flying:
            start -= B
        return start + B - 1 if start < L + req.max_new_tokens else None

    def _collect(self):
        """Fetch and hand out the step in flight, if there is one: only
        its report crosses to host, under host_fetch().  A lane that
        ended, was cancelled or swept since the launch takes nothing, and
        a request admitted into its slot since is not among the step's
        lanes: neither reads a token that is not its own."""
        step, self._flight = self._flight, None
        if step is None:
            return
        with self.timers.scope("fetch"), host_fetch():
            # blocks until the device has run the step; the one launched
            # after it is queued behind it already
            out = [np.array(a, copy=True) for a in step.out]
        with self.timers.scope("distribute"):
            lanes = [(s, r) for s, r in step.lanes
                     if self._sched.occupied.get(s) is r]
            if not lanes:
                self.metrics.count_empty_step()
            if self._spec_exec is not None:
                self._distribute_spec(step.iter, lanes, *out)
            elif self._block_exec is not None:
                self._distribute_block(step.iter, lanes, *out)
            else:
                self._distribute(step.iter, lanes, *out)

    def expert_counts(self):
        """What the device has counted of the live lanes' routed
        assignments since start: {"assignments": [layers, experts],
        "touched": [layers] (experts with at least one assignment, summed
        over steps)}, zeros before the first block step.  Reads a copy
        that ``block_step`` published, never the donated state, so any
        thread may call it."""
        geom = self.geometry
        counts = self._expert_counts
        if counts is None:
            return {"assignments": np.zeros(
                        (geom.num_layers, geom.num_experts), np.int64),
                    "touched": np.zeros((geom.num_layers,), np.int64)}
        with host_fetch():
            return {"assignments": np.array(counts[0], np.int64),
                    "touched": np.array(counts[1], np.int64)}

    def _retire_ended(self, slot: int, req: _GenRequest):
        """A lane that ended by eos or at its budget: the device freed
        its private pages in the graph (or ``_release`` did); this drops
        the host's bookkeeping and ends the stream."""
        self._host_retire(slot)
        self.metrics.count("retired")
        req.end_spans("ok")
        req.handle._finish()

    def _distribute_block(self, it, lanes, report):
        """A block's tokens go to the stream together, in the collect of
        the step that resolved its last mask, cut at eos and at
        max_new_tokens.  The gap histogram records what a client sees:
        one gap a block and zeros inside it."""
        B = self.block_length
        now = time.monotonic()
        denoised = committed = emitted = 0
        for slot, req in lanes:
            resolved, commit, fin = (bool(f) for f in report[slot, 2 * B:])
            committed += commit
            denoised += not commit
            req.block_resolved = resolved
            if resolved:
                L = len(req.prompt)
                lo = max(req.block_start, L) - req.block_start
                hi = min(req.block_start + B,
                         L + req.max_new_tokens) - req.block_start
                for i in range(lo, hi):
                    tok = int(report[slot, i])
                    if req.t_last_token is None:
                        self.metrics.observe_ttft(now - req.handle.t_submit)
                        if req.span is not None:
                            req.span.event("first_token", slot=slot,
                                           iter=it)
                    else:
                        self.metrics.observe_inter_token(
                            now - req.t_last_token)
                    req.t_last_token = now
                    req.handle.steps.append(int(report[slot, B + i]))
                    req.handle._push(tok)
                    emitted += 1
                    if req.span_decode is not None:
                        req.span_decode.event(
                            "token", i=len(req.handle.tokens), iter=it)
                    if tok == req.eos:
                        break
                req.block_start += B
            if fin:
                self._retire_ended(slot, req)
        self.metrics.observe_tokens(emitted)
        self.metrics.observe_block_step(denoised, committed, emitted)

    def _distribute_spec(self, it, lanes, outs_np, emitted_np, fin_np):
        now = time.monotonic()
        emitted_total = accepted = proposed = 0
        for slot, req in lanes:
            n = int(emitted_np[slot].sum())
            if n <= 0:
                continue
            emitted_total += n
            if not req.do_sample:
                # n - 1 of this run's tokens came from accepted draft
                # proposals (the last one is the target's own next
                # token, free either way)
                accepted += n - 1
                proposed += self.spec_tokens
            gap = ((now - req.t_last_token) / n
                   if req.t_last_token is not None else None)
            for i in range(n):
                if gap is not None:
                    self.metrics.observe_inter_token(gap)
                req.handle._push(int(outs_np[slot, i]))
                if req.span_decode is not None:
                    req.span_decode.event("token",
                                          i=len(req.handle.tokens), iter=it)
            req.t_last_token = now
            if bool(fin_np[slot]):
                self._retire_ended(slot, req)
        self.metrics.observe_tokens(emitted_total)
        if proposed:
            self.metrics.observe_spec(accepted, proposed)

    def _distribute(self, it, lanes, toks_np, fin_np, *more):
        """`more`: what the step's report adds for an engine with state
        layers (how many lanes' state it updated), then for one with window
        layers (the pools' registers)."""
        now = time.monotonic()
        if self.geometry.state_layers:
            n_live, *more = more
            self.metrics.observe_state_step(int(n_live))
        if more:
            self.metrics.observe_pools(*(int(x) for x in more[0]))
        self.metrics.observe_tokens(len(lanes))
        for slot, req in lanes:
            if req.t_last_token is not None:
                self.metrics.observe_inter_token(now - req.t_last_token)
            req.t_last_token = now
            req.handle._push(int(toks_np[slot]))
            if req.span_decode is not None:
                # host ints only, of the step that made the token
                req.span_decode.event("token", i=len(req.handle.tokens),
                                      iter=it)
            if bool(fin_np[slot]):
                self._retire_ended(slot, req)

    def _fail_everything(self, exc):
        for dq in (self._backlog,):
            while dq:
                req = dq.popleft()
                req.end_spans("error")
                req.handle._finish(exc)
        while True:
            try:
                req = self._queue.get_nowait()
            except queue.Empty:
                break
            if req is not _WAKE:
                req.end_spans("error")
                req.handle._finish(exc)
        for slot in list(self._sched.occupied):
            req = self._sched.retire(slot)
            self._slot_pins.pop(slot, None)
            req.end_spans("error")
            req.handle._finish(exc)

    # -- shutdown ----------------------------------------------------------
    def drain(self, timeout=None) -> bool:
        """Graceful: reject new work, finish every queued and in-flight
        generation, stop the decode loop.  True when fully drained."""
        self._draining = True
        if self._thread is None:
            return True
        self._wake()
        deadline = (time.monotonic() + timeout
                    if timeout is not None else None)
        drained = self._idle.wait(timeout)
        self._thread.join(None if deadline is None
                          else max(0.0, deadline - time.monotonic()))
        alive = self._thread.is_alive()
        if not alive:
            self._thread = None
        # a submit racing the drain flag can slip a request in after the
        # loop's final empty-check — fail it, never strand its handle
        while True:
            try:
                req = self._queue.get_nowait()
            except queue.Empty:
                break
            if req is _WAKE:
                continue
            drained = False
            if not req.handle.done:
                req.end_spans("rejected_draining")
                req.handle._finish(EngineStoppedError(
                    "request arrived during drain"))
        return drained and not alive

    def stop(self):
        """Hard stop: fail everything queued and in-flight."""
        self._stopped = True
        self._draining = True
        thread = self._thread
        if thread is not None:
            self._wake()
            thread.join(5.0)
            if not thread.is_alive():
                self._thread = None
        self._fail_everything(EngineStoppedError("engine stopped"))

    @property
    def draining(self) -> bool:
        return self._draining

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        if exc[0] is None:
            self.drain(timeout=30.0)
        self.stop()
        return False


def main(argv=None):
    """Standalone generation server over a randomly initialized GPT —
    the tools/serve_smoke.sh concurrent-decode fixture (real deployments
    build a GenerationEngine around trained weights, or call
    ``Model.serve_generate()``)."""
    import argparse

    parser = argparse.ArgumentParser(
        description="paddle_tpu generation server (continuous-batching "
                    "decode with a device-resident paged KV cache)")
    parser.add_argument("--layers", type=int, default=2)
    parser.add_argument("--hidden", type=int, default=64)
    parser.add_argument("--heads", type=int, default=4)
    parser.add_argument("--vocab", type=int, default=211)
    parser.add_argument("--max-seq-len", type=int, default=64)
    parser.add_argument("--slots", type=int, default=4)
    parser.add_argument("--prompt-buckets", default="8,16")
    parser.add_argument("--page-size", type=int, default=16)
    parser.add_argument("--num-pages", type=int, default=0,
                        help="KV page pool size; 0 = dense-equivalent "
                             "(slots * pages_per_slot)")
    parser.add_argument("--prefix-cache", type=int, default=1,
                        help="1 shares identical prompt prefixes as "
                             "read-only pages; 0 disables")
    parser.add_argument("--draft-layers", type=int, default=0,
                        help="layers of the speculative draft model; "
                             "0 disables speculative decode")
    parser.add_argument("--spec-tokens", type=int, default=4,
                        help="draft proposals per speculative iteration")
    parser.add_argument("--prefill-chunk", type=int, default=0,
                        help="tokens per prefill chunk (multiple of "
                             "page-size); 0 prefills whole prompts")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--host", default="127.0.0.1")
    parser.add_argument("--port", type=int, default=8867,
                        help="0 picks a free port (printed on stdout)")
    args = parser.parse_args(argv)

    import logging as _logging

    _logging.basicConfig(level=_logging.INFO)
    import paddle_tpu as paddle
    from ..models.gpt import GPTConfig, GPTForCausalLM
    from .server import ServingServer

    paddle.seed(args.seed)
    cfg = GPTConfig(vocab_size=args.vocab, hidden_size=args.hidden,
                    num_layers=args.layers, num_heads=args.heads,
                    max_position_embeddings=args.max_seq_len,
                    dropout=0.0, attn_dropout=0.0)
    model = GPTForCausalLM(cfg)
    model.eval()
    draft = None
    if args.draft_layers > 0:
        dcfg = GPTConfig(vocab_size=args.vocab, hidden_size=args.hidden,
                         num_layers=args.draft_layers,
                         num_heads=args.heads,
                         max_position_embeddings=args.max_seq_len,
                         dropout=0.0, attn_dropout=0.0)
        draft = GPTForCausalLM(dcfg)
        # seed the draft from the target's first layers + embeddings so
        # the random-weight smoke still accepts some proposals
        tgt = dict(model.state_dict())
        dsd = draft.state_dict()
        for name in list(dsd):
            if name in tgt and tuple(dsd[name].shape) \
                    == tuple(tgt[name].shape):
                dsd[name] = tgt[name]
        draft.set_state_dict(dsd)
        draft.eval()
    engine = GenerationEngine(model, max_slots=args.slots,
                              max_seq_len=args.max_seq_len,
                              prompt_buckets=args.prompt_buckets,
                              page_size=args.page_size,
                              num_pages=args.num_pages,
                              prefix_cache=bool(args.prefix_cache),
                              draft_model=draft,
                              spec_tokens=args.spec_tokens,
                              prefill_chunk=args.prefill_chunk)
    server = ServingServer(None, gen_engine=engine, host=args.host,
                           port=args.port).start()
    # parse-friendly readiness line (tools/serve_smoke.sh greps it)
    print(f"paddle_tpu.serving listening on {server.url}", flush=True)

    # elastic fleet membership: when launched under a replica supervisor
    # (serving/fleet.py exports PADDLE_POD_COORD + PADDLE_POD_RANK) the
    # replica registers its URL in the coordinator KV and heartbeats so
    # the router evicts it on the epoch delta — faster than its probe
    # timeout — when it dies or partitions.  A REPLICA_PARTITION chaos
    # drill silences the heartbeats while the HTTP server keeps serving.
    from ..distributed.podcoord import PodClient

    pod = PodClient.from_env()
    if pod is not None:
        from ..utils import chaos as _chaos

        pod.kv_set(f"serving/replica/{pod.rank}/url",
                   server.url.encode("utf-8"))
        pod.start_heartbeats()
        _chaos.register_partition_hook(pod.stop_heartbeats)
        logger.info("replica rank %d registered with fleet coordinator",
                    pod.rank)
    return server.wait()


if __name__ == "__main__":
    import sys

    sys.exit(main())
