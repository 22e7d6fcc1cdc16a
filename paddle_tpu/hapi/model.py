"""High-level Model API (fit/evaluate/predict).

Reference parity: python/paddle/hapi/model.py (Model:810 — fit:1299,
evaluate:1515, predict, train_batch:896; StaticGraphAdapter:224 vs
DynamicGraphAdapter:609).

TPU-native: there is only ONE adapter — every train/eval batch runs through a
jit-compiled pure step function (params/buffers/opt-state pytrees in, new
state out).  This is what the reference's StaticGraphAdapter approximated
with Program caching, but with autodiff + XLA fusion over the whole step, and
it subsumes the DynamicGraphAdapter too (the layer's eager state is rebound
to the new device arrays after each step, so dygraph-style inspection still
works between batches).
"""
from __future__ import annotations

import contextlib
import functools
import logging
import os
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from .. import amp as amp_mod
from ..framework import random as _random
from ..io import DataLoader, Dataset
from ..metric import Metric
from ..nn.layer_base import Layer, functional_call, state_pytrees
from ..tensor import Tensor, unwrap
from ..utils.profiler import StepTimers, startup
from .engine import (TrainEngine, build_pure_train_step, fetch_floats,
                     host_fetch)

logger = logging.getLogger("paddle_tpu.hapi")


def _to_list(x):
    if x is None:
        return []
    return list(x) if isinstance(x, (list, tuple)) else [x]


def _as_tensor(x):
    return x if isinstance(x, Tensor) else Tensor(np.asarray(x))


def _phase_line(timers, totals0, counts0):
    """`<phase>_ms=<mean> <phase>_max_ms=<longest>@<count>` for every
    phase that ran since the snapshot (`totals0`, `counts0`); the
    longest run is since `timers.maxima` was last cleared."""
    parts = []
    for name, total in timers.totals.items():
        n = timers.counts[name] - counts0.get(name, 0)
        if n <= 0:
            continue
        mean = (total - totals0.get(name, 0.0)) / n * 1e3
        parts.append(f"{name}_ms={mean:.3f}")
        if name in timers.maxima:
            longest, at = timers.maxima[name]
            parts.append(f"{name}_max_ms={longest * 1e3:.3f}@{at}")
    return " ".join(parts)


def _fit_is_startup(fit):
    """Start-up's scope `fit` (`utils.profiler.startup()`) opens at
    `Model.fit`'s entry; `fit` closes it when its first step has been
    dispatched (`self._fit_startup.close()`), and it is closed here
    should `fit` end or raise before that."""
    @functools.wraps(fit)
    def wrapped(self, *args, **kwargs):
        with contextlib.ExitStack() as first:
            first.enter_context(startup().scope("fit"))
            self._fit_startup = first
            return fit(self, *args, **kwargs)
    return wrapped


class Model:
    def __init__(self, network: Layer, inputs=None, labels=None):
        self.network = network
        self._inputs = inputs
        self._labels = labels
        self._optimizer = None
        self._loss = None
        self._metrics = []
        self._train_step_fn = None
        self._eval_fn = None
        self._engine = None
        self.stop_training = False

    # -- setup -------------------------------------------------------------
    def prepare(self, optimizer=None, loss=None, metrics=None,
                amp_configs=None):
        self._optimizer = optimizer
        self._loss = loss
        self._metrics = [m for m in _to_list(metrics)
                         if isinstance(m, Metric)]
        self._train_step_fn = None
        self._eval_fn = None
        self._engine = None
        return self

    # -- compiled steps ----------------------------------------------------
    def _split_params(self):
        params, buffers = state_pytrees(self.network)
        named = dict(self.network.named_parameters())
        trainable = {k: v for k, v in params.items()
                     if not named[k].stop_gradient}
        frozen = {k: v for k, v in params.items() if named[k].stop_gradient}
        return trainable, frozen, buffers

    def _build_train_step(self):
        # the step MATH lives in engine.build_pure_train_step — one body
        # shared with the donated TrainEngine, so the engine's bitwise
        # equivalence to this eager path holds by construction
        return jax.jit(build_pure_train_step(self.network, self._loss,
                                             self._optimizer))

    def _build_eval_step(self):
        network, loss_layer = self.network, self._loss

        @jax.jit
        def step(params, buffers, rng, inputs, labels):
            outs, _ = functional_call(network, params, tuple(inputs), {},
                                      buffers=buffers, rng=rng)
            outs_l = _to_list(outs)
            if loss_layer is not None and labels:
                lv = loss_layer(*(outs_l + list(labels)))
                return outs, jnp.mean(unwrap(lv))
            return outs, jnp.zeros(())

        return step

    def _write_back(self, trainable, buffers):
        named = dict(self.network.named_parameters())
        for k, v in trainable.items():
            named[k]._value = v
        bmap = dict(self.network.named_buffers())
        for k, v in buffers.items():
            bmap[k]._value = v

    # -- batch-level API ---------------------------------------------------
    def train_batch(self, inputs, labels=None, update=True):
        self.network.train()
        if self._train_step_fn is None:
            self._train_step_fn = self._build_train_step()
        inputs = [_as_tensor(x) for x in _to_list(inputs)]
        labels = [_as_tensor(x) for x in _to_list(labels)]
        trainable, frozen, buffers = self._split_params()
        opt = self._optimizer
        opt_state = getattr(self, "_opt_state", None)
        if opt_state is None:
            opt_state = opt.init_pytree(trainable)
        opt._step_count += 1
        rng = _random.split_key()
        new_params, new_buffers, new_opt_state, loss_val, outs = \
            self._train_step_fn(
                trainable, frozen, buffers, opt_state,
                jnp.asarray(opt.get_lr(), jnp.float32),
                jnp.asarray(opt._step_count, jnp.int32), rng,
                inputs, labels)
        self._write_back(new_params, new_buffers)
        self._opt_state = new_opt_state
        metrics_out = [float(loss_val)]
        for m in self._metrics:
            m.update(unwrap(m.compute(*( _to_list(outs) + labels))))
        return metrics_out if len(metrics_out) > 1 else metrics_out[0]

    def eval_batch(self, inputs, labels=None):
        self.network.eval()
        if self._eval_fn is None:
            self._eval_fn = self._build_eval_step()
        inputs = [_as_tensor(x) for x in _to_list(inputs)]
        labels = [_as_tensor(x) for x in _to_list(labels)]
        params, buffers = state_pytrees(self.network)
        rng = _random.split_key()
        outs, loss_val = self._eval_fn(params, buffers, rng, inputs, labels)
        return outs, float(loss_val)

    def predict_batch(self, inputs):
        self.network.eval()
        inputs = [_as_tensor(x) for x in _to_list(inputs)]
        outs, _ = self.eval_batch_no_loss(inputs)
        return outs

    def eval_batch_no_loss(self, inputs):
        if self._eval_fn is None:
            self._eval_fn = self._build_eval_step()
        params, buffers = state_pytrees(self.network)
        rng = _random.split_key()
        outs, lv = self._eval_fn(params, buffers, rng, inputs, [])
        return outs, lv

    # -- fault tolerance ---------------------------------------------------
    def _vocab_layers(self):
        """(path, layer) pairs carrying checkpointable sparse-vocab
        state (duck-typed: `sparse.ShardedEmbeddingTable` with an
        admission policy attached).  The id→row mapping is host-side
        Python state the array checkpoint cannot see — it rides the
        manifest meta beside the table leaf so resume keeps it."""
        out = []
        for name, sub in self.network.named_sublayers(include_self=True):
            if callable(getattr(sub, "vocab_state_dict", None)) \
                    and callable(getattr(sub, "load_vocab_state_dict",
                                         None)):
                out.append((name or "<root>", sub))
        return out

    def _ft_state(self, it_count):
        """Checkpointable training state: trainable params + buffers +
        optimizer slots + loop counters, as one pytree of arrays.  When
        the device-resident engine is live its state is authoritative
        (the Layer tree is only synced where it has a reader) and must be
        MATERIALIZED to host — the engine donates those buffers on the
        next dispatch, which would race an async save.  This host copy
        IS the async checkpointer's double buffer: it happens on the
        training thread, the disk write does not."""
        eng = self._engine
        if eng is not None and eng.active:
            snap = eng.ft_state(it_count)
        else:
            trainable, _frozen, buffers = self._split_params()
            opt_state = getattr(self, "_opt_state", None)
            if opt_state is None:
                opt_state = self._optimizer.init_pytree(trainable)
            snap = {"params": trainable, "buffers": buffers,
                    "opt": opt_state,
                    "meta": {"it": jnp.int32(it_count),
                             "opt_steps": jnp.int32(
                                 self._optimizer._step_count)}}
        sched = self._optimizer._lr_scheduler
        if sched is not None:
            # lr-schedule reconciliation on (elastic) resume: the
            # scheduler's epoch counter travels with the checkpoint
            snap["meta"]["lr_last_epoch"] = np.array(
                int(sched.last_epoch), np.int32)
        return snap

    def _ft_template(self):
        """Structure-only mirror of `_ft_state` (None leaves): restore
        matches checkpoint leaves BY KEYPATH and takes dtype/shape from
        the manifest, so the template never needs values — building it
        from the live state would device→host copy the whole model just
        to throw the bytes away."""
        def none_of(tree):
            return jax.tree_util.tree_map(lambda _: None, tree)
        eng = self._engine
        if eng is not None and eng.active:
            st = eng.state
            snap = {"params": {k: None for k in st["trainable"]},
                    "buffers": {k: None for k in st["buffers"]},
                    "opt": none_of(st["opt"])}
        else:
            trainable, _frozen, buffers = self._split_params()
            opt_state = getattr(self, "_opt_state", None)
            if opt_state is None:
                opt_state = self._optimizer.init_pytree(trainable)
            snap = {"params": {k: None for k in trainable},
                    "buffers": {k: None for k in buffers},
                    "opt": none_of(opt_state)}
        snap["meta"] = {"it": None, "opt_steps": None}
        if self._optimizer._lr_scheduler is not None:
            snap["meta"]["lr_last_epoch"] = None
        return snap

    def _ft_save(self, mgr, saver, it_count, force=False, sync=False):
        """One durable checkpoint of the current training state.  With
        an AsyncCheckpointer the host snapshot is taken here (training
        thread — donation makes that mandatory) and the write happens in
        the background; emergency/final saves pass sync=True.

        The whole call is the checkpoint-induced TRAINING-THREAD stall
        (host snapshot + submit, or the full write on the sync path) —
        telemetry records it as `paddle_ckpt_step_stall_ms`, the number
        the async writer exists to keep small."""
        from ..monitor import flightrec as _flightrec

        t0 = time.perf_counter()
        fit_span = getattr(self, "_fit_span", None)
        sp_ckpt = (fit_span.child("train.ckpt_stall", step=it_count,
                                  sync=bool(sync))
                   if fit_span is not None else None)
        try:
            self._ft_save_inner(mgr, saver, it_count, force=force,
                                sync=sync)
        finally:
            stall_ms = (time.perf_counter() - t0) * 1e3
            if sp_ckpt is not None:
                sp_ckpt.end()
            telem = getattr(self, "_telemetry", None)
            if telem is not None:
                telem.ckpt_stall(stall_ms)
            _flightrec.record("ckpt", step=it_count,
                              stall_ms=round(stall_ms, 3),
                              sync=bool(sync))

    def _ft_save_inner(self, mgr, saver, it_count, force=False, sync=False):
        from .engine import mesh_meta

        eng = self._engine
        meta = {"mesh": mesh_meta(eng.mesh if eng is not None else None)}
        sched = self._optimizer._lr_scheduler
        if sched is not None:
            # full scheduler state rides in the (JSON) manifest: stateful
            # schedulers like ReduceOnPlateau keep decision state
            # (best/num_bad_epochs/last_lr) that a bare epoch counter
            # cannot reconstruct
            meta["lr_sched"] = sched.state_dict()
        vocabs = {}
        for name, sub in self._vocab_layers():
            state = sub.vocab_state_dict()
            if state:
                vocabs[name] = state
        if vocabs:
            # sparse admission vocabs: the id→row mapping (JSON) rides
            # beside the sharded table leaf, so an elastic resume maps
            # incoming ids to the same rows the restored table trained
            meta["sparse_vocab"] = vocabs
        if saver is not None and not sync:
            saver.submit(it_count, self._ft_state(it_count), force=force,
                         meta=meta)
        else:
            skip_disk_write = False
            if saver is not None:
                # never race a background write of the same generation
                # with a synchronous emergency save — but BOUND the
                # wait: a writer stalled on a dead mount must not eat
                # the whole SIGTERM grace window (the newest durable
                # generation then stands as the recovery point)
                if not saver.flush(timeout=30.0):
                    logger.error(
                        "emergency checkpoint skipped: background "
                        "writer stalled >30s; resuming from the latest "
                        "durable generation instead")
                    if jax.process_count() == 1:
                        return
                    # multi-host: a stalled process returning here
                    # while its peers (whose writers drained instantly
                    # — non-writer saves are no-ops) proceed into
                    # _ft_state's allgather would deadlock the pod.
                    # Join the collective below, but do NOT touch the
                    # manager: its lock is held by the stalled writer
                    # and would block past the grace window.
                    skip_disk_write = True
            if sync and jax.process_count() == 1 \
                    and mgr.latest_step() == it_count:
                # this step is already durably committed (an interval
                # save this same iteration, or the flushed async write
                # above): a force-save would re-write the committed
                # generation — spending the SIGTERM grace window on a
                # duplicate.  Single-process only: latest_step reads
                # shared storage, and on a multi-host pod a process
                # skipping here while its peers enter _ft_state's
                # allgather would deadlock the pod (the duplicate
                # write is the cheaper failure mode).
                return
            snap = self._ft_state(it_count)
            if skip_disk_write:
                return
            try:
                mgr.save(it_count, snap, force=force, meta=meta)
                self._ft_sync_failures = 0
            except OSError as e:
                # degrade-then-escalate for the SYNCHRONOUS path, the
                # mirror of AsyncCheckpointer's policy: a failed
                # generation must not crash fit with a raw OSError (the
                # launcher would see a generic crash and burn restarts
                # on a full disk) — warn, keep training, and let the
                # fit loop escalate with the distinct durability code
                # after K consecutive failures
                if sync:
                    # emergency save on the way to a preempted exit: the
                    # newest durable generation is the recovery point,
                    # and a failed save must never mask the distinct
                    # preempted exit code
                    logger.error(
                        "emergency checkpoint failed (%s: %s) — the "
                        "latest durable generation stands as the "
                        "recovery point", type(e).__name__, e)
                    return
                self._ft_sync_failures += 1
                logger.warning(
                    "checkpoint generation %s failed (%s: %s) — "
                    "training continues WITHOUT durability (%d/%d "
                    "consecutive failures before escalation)", it_count,
                    type(e).__name__, e, self._ft_sync_failures,
                    self._ft_max_failures)

    def _ft_restore(self, mgr):
        """Auto-resume from the newest VALID generation (the corruption
        cascade lives in CheckpointManager.restore_latest).  When the
        device-resident engine is live, the saved state is routed
        through `restore(shardings=)` with the CURRENT mesh's
        NamedShardings — a checkpoint saved at dp=N lands directly on a
        dp=M mesh (elastic resume).  Returns the iteration to
        fast-forward to."""
        template = self._ft_template()
        eng = self._engine if (self._engine is not None
                               and self._engine.active) else None
        shardings = (eng.ft_restore_shardings(template)
                     if eng is not None else None)
        step0, back = mgr.restore_latest(template=template,
                                         shardings=shardings)
        if step0 is None:
            return 0
        if eng is not None:
            eng.adopt_ft_state(back)
            # Layer tree + model._opt_state follow the restored state
            # (single-device de-shard), so callbacks/eval between epochs
            # observe the resumed weights, not the fresh init
            eng.write_back(copy=True)
        else:
            self._write_back(back["params"], back["buffers"])
            self._opt_state = back["opt"]
            self._optimizer._step_count = int(back["meta"]["opt_steps"])
        sched = self._optimizer._lr_scheduler
        man = mgr.last_restore_manifest or {}
        sched_state = (man.get("meta") or {}).get("lr_sched")
        if sched is not None and sched_state:
            # full state from the manifest (covers stateful schedulers:
            # ReduceOnPlateau's best/num_bad_epochs/last_lr survive)
            sched.set_state_dict(sched_state)
        elif sched is not None and "lr_last_epoch" in back["meta"]:
            # older checkpoints: step(epoch=) rather than assigning
            # last_epoch — it also recomputes last_lr, which __call__
            # serves from cache; assignment alone would train at the
            # fresh-init lr until the next scheduler step
            sched.step(epoch=int(back["meta"]["lr_last_epoch"]))
        vocabs = (man.get("meta") or {}).get("sparse_vocab") or {}
        if vocabs:
            for name, sub in self._vocab_layers():
                state = vocabs.get(name)
                if state:
                    sub.load_vocab_state_dict(state)
        restart = os.environ.get("PADDLE_RESTART_COUNT", "0")
        saved_mesh = (man.get("meta") or {}).get("mesh") or {}
        saved_dp = saved_mesh.get("dp")
        cur_meta = {"dp": 1, "devices": 1, "axes": {}}
        if eng is not None and eng.mesh is not None:
            from .engine import mesh_meta

            cur_meta = mesh_meta(eng.mesh)
        cur_dp = cur_meta["dp"]
        # ANY axis-geometry change is an elastic reshard — dp2×fsdp4 →
        # dp2×fsdp2×tp2 keeps dp=2 but still re-lands every shard — so
        # compare the full axes dict when the manifest carries one
        # (older manifests only recorded dp)
        saved_axes = saved_mesh.get("axes")
        changed = (saved_dp is not None and int(saved_dp) != cur_dp)
        if saved_axes is not None:
            changed = ({str(k): int(v) for k, v in saved_axes.items()}
                       != cur_meta["axes"])
        if changed:
            def _fmt(axes, dp):
                return "×".join(f"{a}{n}" for a, n in axes.items()) \
                    or f"dp{dp}"
            logger.info("fit: ELASTIC resume — checkpoint saved at "
                        "dp=%s (%s), restoring onto dp=%s (%s) "
                        "(reconciled step=%d)", saved_dp,
                        _fmt(saved_axes or {}, saved_dp), cur_dp,
                        _fmt(cur_meta["axes"], cur_dp),
                        int(back["meta"]["opt_steps"]))
        logger.info("fit: resumed from checkpoint at iteration %d "
                    "(restart #%s)", step0, restart)
        return int(back["meta"]["it"])

    # -- loop-level API ----------------------------------------------------
    @_fit_is_startup
    def fit(self, train_data=None, eval_data=None, batch_size=1, epochs=1,
            eval_freq=1, log_freq=10, save_dir=None, save_freq=1, verbose=2,
            drop_last=False, shuffle=True, num_workers=0, callbacks=None,
            accumulate_grad_batches=1, num_iters=None, fault_tolerant=False,
            resume=None, checkpoint_interval=None, mesh=None,
            sharding_rule=None, layout=None, recompute=None, accum_steps=1,
            pod=None):
        """[what the Layer tree holds during a fit] The train state
        lives once, in the engine (hapi/engine.py), and the network's
        parameters are brought up to date at an epoch's end only when
        something reads them there: an evaluate (`eval_data`,
        `eval_freq`), a `ModelCheckpoint` that saves that epoch
        (`save_dir`, `save_freq`), or any callback that is not a
        `ProgBarLogger`, an `LRScheduler` or a `ModelCheckpoint` (with
        such a callback also after every batch, as before).  Between
        such boundaries `network.parameters()` holds the values of the
        last sync (the start of `fit`, or the last epoch that had a
        reader): valid arrays, never a donated buffer.  When `fit`
        returns or unwinds, the tree and `model._opt_state` are the
        engine's last state; only after a dispatch that failed having
        donated the state does the tree keep its last sync, which may
        be older than the last epoch (`fault_tolerant=` checkpoints the
        live state for runs that must not lose one).

        [fault tolerance — opt-in] `resume=<dir>` (or `resume=True`
        with `save_dir`) auto-resumes from the newest checkpoint in that
        directory and checkpoints every `checkpoint_interval` iterations
        (default: each epoch end).  `fault_tolerant=True` additionally
        latches SIGTERM/SIGINT, finishes the in-flight batch, writes an
        emergency checkpoint, and exits with
        `distributed.PREEMPTED_EXIT_CODE` so a launcher started with
        `--max_restarts` relaunches and resumes — see
        distributed/resilience.py.  Resume is bitwise-exact when data
        order and seeding are deterministic (`shuffle=False` +
        `paddle.seed`).

        [SPMD scaling — opt-in] `mesh=` a `jax.sharding.Mesh`, a shape
        dict like `{"dp": 8}`, or nothing: an ambient
        `distributed.mesh_guard` (or `FLAGS_mesh_shape`) is picked up
        automatically.  The engine then compiles ONE global step with
        NamedSharding in/out shardings: params/opt-state replicated over
        `dp` (per-param placement via `sharding_rule(name, param) ->
        PartitionSpec` or `distributed.annotate` for an `mp` axis), the
        global batch split over `dp`, XLA inserting the collectives
        (GSPMD) — so `batch_size` is the GLOBAL batch and throughput
        scales with the dp degree.  All single-chip fit contracts
        (donation, sync-free stepping, compile cache, checkpoints,
        callbacks) are preserved; see README "Scaling".

        [3D parallelism — opt-in] `layout=` a `distributed.SpecLayout`
        (or `True` for the canonical transformer table) shards params
        AND optimizer slots over the mesh's `fsdp`/`tp` axes (ZeRO
        semantics; the batch additionally splits over fsdp), with
        unmatched params replicated + warned.  `recompute=` (True, a
        policy name like "dots", or a jax.checkpoint_policies callable)
        rematerializes activations in the backward pass; `accum_steps=k`
        (alias: the Paddle-named `accumulate_grad_batches`) accumulates
        gradients over k microbatches via a lax.scan INSIDE the one
        donated step, so `batch_size` stays the GLOBAL batch.  See
        MIGRATION §5a-ii for the fleet-strategy mapping.

        [elastic pod — opt-in] `pod=` a `distributed.elastic.PodRuntime`
        (under the elastic supervisor, `PodRuntime.from_env()`): every
        rank feeds the FULL global batch; the runtime strides it over
        the live membership, syncs grads cross-process through the pod
        coordinator, snapshots in-memory per step, and on a mid-step
        rank loss rolls back and REPLAYS the step under the shrunk
        membership — training continues without a restart or a disk
        restore.  See README "Pod runtime & elasticity" and MIGRATION
        §5a-iii."""
        from .callbacks import config_callbacks

        if accumulate_grad_batches != 1 and accum_steps == 1:
            # Paddle's fleet name for the same knob — one implementation
            accum_steps = accumulate_grad_batches

        loader = train_data if isinstance(train_data, DataLoader) else \
            DataLoader(train_data, batch_size=batch_size, shuffle=shuffle,
                       drop_last=drop_last, num_workers=num_workers)
        try:
            steps = len(loader)
        except TypeError:
            steps = None
        self._save_dir = save_dir
        self.stop_training = False
        cbks = config_callbacks(
            callbacks, model=self, batch_size=batch_size, epochs=epochs,
            steps=steps, log_freq=log_freq, verbose=verbose,
            save_freq=save_freq, save_dir=save_dir,
            metrics=[m._name for m in self._metrics])
        from .callbacks import LRScheduler as _LRCb
        from .callbacks import ModelCheckpoint as _CkptCb
        from .callbacks import ProgBarLogger as _PBCb

        # metric.accumulate() is host-side work — only compute per-batch
        # when a log step fires or a user callback might consume it
        user_cbs = any(not isinstance(c, (_PBCb, _LRCb, _CkptCb))
                       for c in cbks)
        ckpt_cbs = [c for c in cbks if isinstance(c, _CkptCb)]

        def tree_readers(epoch):
            """Who reads the Layer tree at this epoch's end, as `(any,
            one that reads the optimizer's slots too)`: a user callback
            (anything but a logger, an LR scheduler or a checkpointer),
            a `ModelCheckpoint` that saves this epoch, an evaluate
            (parameters and buffers only)."""
            saves = any(c.save_dir and (epoch + 1) % c.save_freq == 0
                        for c in ckpt_cbs)
            evals = (eval_data is not None
                     and (epoch + 1) % eval_freq == 0)
            return user_cbs or saves or evals, user_cbs or saves
        # Device-resident engine (hapi/engine.py): ONE state snapshot per
        # fit, donated buffers, no per-step host sync.  When user
        # callbacks or metrics need fresh per-batch values the loop
        # drains the loss ring every step (same observable behavior as
        # the old train_batch loop); otherwise losses are fetched in one
        # batch at log_freq boundaries and epoch ends.  The engine
        # begins BEFORE any checkpoint restore so an elastic resume can
        # land the saved state directly on the resolved mesh.
        if self._engine is None:
            self._engine = TrainEngine(self)
        engine = self._engine
        _step_fn_before = engine._step_fn
        boot = startup()
        since = boot.mark()
        with boot.scope("fit/begin"):
            engine.begin(mesh=mesh, sharding_rule=sharding_rule,
                         layout=layout, recompute=recompute,
                         accum_steps=accum_steps,
                         grad_sync=pod.grad_sync if pod is not None
                         else None)
        if pod is not None:
            # pod chaos (RANK_KILL/RANK_SLOW/RANK_PARTITION) must fire on
            # the same step boundary whether or not fault tolerance is on
            from ..utils import chaos as _pod_chaos

        ft_mgr = None
        ft_saver = None
        start_it = 0
        guard = None
        if fault_tolerant or resume:  # resume=False/None/"" ⇒ off
            from ..framework import flags as _fl
            from ..distributed import resilience as _res
            from ..distributed.checkpoint import (AsyncCheckpointer,
                                                  CheckpointManager)
            from ..utils import chaos as _chaos

            ckpt_dir = resume if isinstance(resume, str) else save_dir
            if not ckpt_dir:
                raise ValueError("fault_tolerant/resume needs a checkpoint "
                                 "directory: pass resume=<dir> or save_dir=")
            ckpt_dir = os.path.join(ckpt_dir, "resilient")
            ft_mgr = CheckpointManager(ckpt_dir, max_to_keep=2)
            # degrade-then-escalate bookkeeping for the SYNC save path
            # (FLAGS_ckpt_async=False); the async path's lives in the
            # AsyncCheckpointer
            self._ft_sync_failures = 0
            self._ft_max_failures = int(
                _fl.flag("FLAGS_ckpt_max_failures"))
            try:
                start_it = self._ft_restore(ft_mgr)
                if _fl.flag("FLAGS_ckpt_async"):
                    # non-blocking durable saves: host snapshot on the
                    # training thread, disk IO on a background writer
                    ft_saver = AsyncCheckpointer(
                        ft_mgr, max_failures=self._ft_max_failures)
                if fault_tolerant:
                    guard = _res.PreemptionGuard()
                    guard.__enter__()
            except BaseException:
                if ft_saver is not None:
                    ft_saver.close()
                ft_mgr.close()
                raise

        # Runtime telemetry (paddle_tpu.monitor), flag-gated: with both
        # FLAGS_telemetry_dir and FLAGS_monitor_port off this is (None,
        # None) and every telemetry hook below is skipped — the hot loop
        # is unchanged.  When on: per-step trace polling + step marks,
        # window emission at log/epoch boundaries (loss, lr, phase times,
        # samples/s, MFU, device memory → registry gauges + one JSONL
        # line), SIGUSR1-armed bounded jax.profiler capture, and a
        # donation-fallback warning counter.  Installed AFTER the
        # fault-tolerance setup (which can raise before the main
        # try/finally exists to uninstall the hooks) — like the
        # placement hook below.
        from ..monitor import fit_monitor, install_sigusr1
        from ..monitor import flightrec as _flightrec
        from ..monitor import tracing as _tracing

        telem, _mon_srv = fit_monitor()
        self._telemetry = telem
        _restore_usr1 = None
        _unhook_warn = None
        if telem is not None:
            from .engine import mesh_meta as _mesh_meta

            telem.on_fit_begin(
                {"epochs": epochs, "batch_size": batch_size,
                 "mesh": _mesh_meta(engine.mesh)},
                compiled=engine._step_fn is not _step_fn_before)
            _restore_usr1 = install_sigusr1(telem)
            _unhook_warn = telem.install_warning_hook()

        # request-scoped tracing: the fit gets a FORCE-sampled span (fits
        # are few — head sampling is for serving traffic) with epoch /
        # step / ckpt-stall children, so a training stall is attributable
        # from /debug/spans the same way a slow request is
        _tracer = _tracing.default_tracer()
        _fit_span = None
        if _tracer.enabled:
            _fit_span = _tracer.start_span(
                "train.fit", sampled=True,
                attrs={"epochs": epochs, "batch_size": batch_size})
        self._fit_span = _fit_span
        _epoch_span = None

        # the placement hook goes on LAST: everything above can still
        # raise (missing ckpt dir, restore errors), and an exception
        # there must not leak a mesh-bound placement onto the user's
        # DataLoader — only the main try/finally below restores it
        prev_placement = None
        if engine.mesh is not None:
            # the prefetch thread device-puts each global batch straight
            # to its dp sharding, overlapping host→device transfer of
            # batch N+1 with device compute of batch N
            from functools import partial as _partial

            from ..framework.transfer import shard_batch
            prev_placement = loader.placement
            loader.placement = _partial(shard_batch, mesh=engine.mesh,
                                        axis=engine.batch_axes)
        eager_sync = user_cbs or bool(self._metrics)
        # the loop's phases: top level data, prepare, dispatch, sync,
        # metrics, callbacks, ckpt, write_back, eval; nothing between
        # two `data` scopes is unnamed, so the totals sum to fit's wall
        # time.  engine.step() records dispatch/lr, /rng, /shard, /call
        # under `dispatch` on the same recorder.
        timers = engine.timers = StepTimers()
        self._last_fit_timers = timers
        _END = object()

        history = {"loss": []}
        it_count = 0
        first_step = True
        # telemetry step-window bookkeeping: wall time + StepTimers
        # snapshots since the last emitted window
        _win_t0 = time.perf_counter()
        _win_it0 = 0
        _win_totals: dict = {}
        _win_counts: dict = {}
        # local completion sentinel — sys.exc_info() is THREAD-wide, so
        # a caller running fit inside an `except` block would make it
        # non-None for the whole call and silently disable every
        # success-path-only branch in the finally below
        fit_ok = False
        try:
            cbks.on_train_begin({})
            for epoch in range(epochs):
                with timers.scope("prepare"):
                    self.network.train()
                    for m in self._metrics:
                        m.reset()
                    cbks.on_epoch_begin(epoch, {})
                    if _fit_span is not None:
                        _epoch_span = _fit_span.child("train.epoch",
                                                      epoch=epoch)
                    # fold user writes to Layer params/buffers (epoch-end
                    # callbacks: SWA/EMA write-back, re-init, pruning)
                    # back into the device-resident state
                    engine.refresh_from_layers()
                    losses = []
                    _ep_totals = dict(timers.totals)
                    _ep_counts = dict(timers.counts)
                with timers.scope("data"):
                    data_iter = iter(loader)
                step_i = -1
                while True:
                    with timers.scope("data"):
                        batch = next(data_iter, _END)
                    if batch is _END:
                        break
                    step_i += 1
                    if it_count < start_it:
                        # fast-forward over already-trained batches,
                        # consuming one rng key each to keep the stream
                        # aligned with the uninterrupted run.  A SIGTERM
                        # here exits immediately — nothing new to save,
                        # the restored checkpoint is still the newest
                        with timers.scope("prepare"):
                            if guard is not None and guard.preempted:
                                _flightrec.dump("preempt")
                                raise SystemExit(_res.PREEMPTED_EXIT_CODE)
                            _random.split_key()
                            it_count += 1
                            if telem is not None:
                                # fast-forwarded batches dispatched
                                # nothing: they must not count into a
                                # step window
                                _win_t0 = time.perf_counter()
                                _win_it0 = it_count
                        continue
                    with timers.scope("prepare"):
                        if telem is not None:
                            # start/advance/stop an armed jax.profiler
                            # capture — on the training thread, at a
                            # step boundary
                            telem.poll_trace()
                        cbks.on_train_batch_begin(step_i, {})
                        if ft_mgr is not None:
                            # fault-injection hook (crash/preempt/slow)
                            # so the fit() recovery paths are
                            # chaos-testable too
                            _chaos.on_step(it_count + 1)
                        elif pod is not None:
                            _pod_chaos.on_step(it_count + 1)
                        batch = _to_list(batch)
                        inputs, labels = self._split_batch(batch)
                        inputs = [_as_tensor(x) for x in inputs]
                        labels = [_as_tensor(x) for x in labels]
                        if pod is not None:
                            # every rank holds the FULL global batch; the
                            # pod runtime strides it over the live
                            # membership (and re-strides on replay after
                            # a shrink)
                            _pod_raw = (inputs, labels)
                            inputs = pod.stride(inputs)
                            labels = pod.stride(labels)
                        if user_cbs:
                            # per-batch weight mutations (WGAN-style
                            # clipping callbacks) only possible with user
                            # callbacks — identity-scan for them before
                            # dispatching
                            engine.refresh_from_layers()
                        if telem is not None:
                            # idempotent anchor so the FIRST interval
                            # (the one containing the compile) is
                            # measured too
                            telem.mark_start()
                        _sp_step = (_epoch_span.child("train.step",
                                                      step=it_count + 1)
                                    if _epoch_span is not None else None)
                        if pod is not None:
                            # in-memory rollback point for a mid-step
                            # shrink
                            pod.before_step(engine, it_count)
                    if first_step:
                        # the fit's first step traces, lowers and
                        # compiles (or loads) the step: start-up's row
                        # `fit/build/step`, and the end of its `fit`
                        self._fit_startup.enter_context(
                            boot.executable("fit/build/step"))
                    with timers.scope("dispatch"):
                        outs = engine.step(inputs, labels)
                    if first_step:
                        first_step = False
                        self._fit_startup.close()
                        if logger.isEnabledFor(logging.INFO):
                            logger.info("%s", boot.report("fit", since))
                    if pod is not None:
                        # sync point + shrink check: on a mid-step rank
                        # loss the runtime rolls back to its in-memory
                        # snapshot and replays under the new membership
                        with timers.scope("sync"):
                            _pod_losses, _ = pod.after_step(
                                engine, _pod_raw[0], _pod_raw[1],
                                it_count + 1)
                            losses.extend(_pod_losses)
                    with timers.scope("callbacks"):
                        if telem is not None:
                            telem.step_mark()
                        if _sp_step is not None:
                            # covers dispatch only: the async engine
                            # returns futures, so device time lands in
                            # the sync scope
                            _sp_step.end()
                    it_count += 1
                    log_step = bool(log_freq) and step_i % log_freq == 0
                    if eager_sync or log_step:
                        with timers.scope("sync"):
                            losses.extend(engine.drain())
                    if user_cbs:
                        # full eager semantics for custom callbacks: they
                        # see CURRENT weights in on_train_batch_end (the
                        # old loop wrote back every batch; vanilla runs
                        # keep the async no-copy path).  Opt slots sync
                        # only at boundaries — callbacks observe weights
                        with timers.scope("write_back"):
                            engine.write_back(copy=True, sync_opt=False)
                    if self._metrics:
                        with timers.scope("metrics"), host_fetch():
                            for m in self._metrics:
                                m.update(unwrap(m.compute(
                                    *(_to_list(outs) + labels))))
                    with timers.scope("callbacks"):
                        logs = {"loss": (losses[-1] if losses
                                         else float("nan")),
                                "batch_size": batch_size}
                        if user_cbs or log_step:
                            for m in self._metrics:
                                logs[m._name] = np.mean(
                                    _to_list(m.accumulate()))
                        cbks.on_train_batch_end(step_i, logs)
                        if telem is not None and log_step \
                                and it_count > _win_it0:
                            _win_t0, _win_it0, _win_totals, _win_counts \
                                = self._telemetry_window(
                                    telem, engine, timers, epoch,
                                    it_count, batch_size, losses, inputs,
                                    labels, _win_t0, _win_it0,
                                    _win_totals, _win_counts)
                    if ft_mgr is not None:
                        with timers.scope("ckpt"):
                            if (checkpoint_interval
                                    and it_count % checkpoint_interval == 0):
                                self._ft_save(ft_mgr, ft_saver, it_count)
                            if ((ft_saver is not None and ft_saver.fatal)
                                    or self._ft_sync_failures
                                    >= max(1, self._ft_max_failures)):
                                # degrade-then-escalate: K consecutive
                                # failed generations means the job has
                                # been training WITHOUT durability —
                                # abort with the distinct code so the
                                # launcher alerts instead of restarting
                                # blindly
                                _flightrec.dump("durability")
                                raise SystemExit(
                                    _res.DURABILITY_EXIT_CODE)
                            if guard is not None and guard.preempted:
                                # in-flight batch done: emergency
                                # checkpoint (synchronous — we are about
                                # to exit), then the distinct "preempted"
                                # exit so the launcher restarts us
                                self._ft_save(ft_mgr, ft_saver, it_count,
                                              force=True, sync=True)
                                ft_mgr.wait()
                                _flightrec.dump("preempt")
                                raise SystemExit(_res.PREEMPTED_EXIT_CODE)
                    if num_iters is not None and it_count >= num_iters:
                        break
                with timers.scope("sync"):
                    losses.extend(engine.drain())
                if telem is not None and it_count > _win_it0:
                    # close the epoch's partial window (inputs/labels are
                    # the last dispatched batch — it_count > _win_it0
                    # guarantees one exists)
                    with timers.scope("callbacks"):
                        _win_t0, _win_it0, _win_totals, _win_counts = \
                            self._telemetry_window(
                                telem, engine, timers, epoch, it_count,
                                batch_size, losses, inputs, labels,
                                _win_t0, _win_it0, _win_totals,
                                _win_counts)
                # epoch boundary: the Layer tree is brought up to date
                # only for a reader that is due here (device COPIES, one
                # dispatch, while the engine keeps donating its own
                # buffers); with none the tree keeps its last sync and
                # nothing is put on the device that nobody reads
                with timers.scope("write_back"):
                    reader, of_opt = tree_readers(epoch)
                    if reader:
                        with timers.scope("write_back/copy"):
                            engine.write_back(copy=True, sync_opt=of_opt)
                    else:
                        engine.refresh_from_layers()
                if ft_mgr is not None and not checkpoint_interval \
                        and it_count > start_it:
                    with timers.scope("ckpt"):
                        self._ft_save(ft_mgr, ft_saver, it_count,
                                      force=True)
                with timers.scope("metrics"):
                    # losses can be empty when resume fast-forwarded the
                    # epoch
                    history["loss"].append(
                        float(np.mean(losses)) if losses else float("nan"))
                    epoch_logs = {"loss": history["loss"][-1]}
                    for m in self._metrics:
                        epoch_logs[m._name] = np.mean(
                            _to_list(m.accumulate()))
                if eval_data is not None and (epoch + 1) % eval_freq == 0:
                    with timers.scope("eval"):
                        cbks.on_eval_begin({})
                        eval_res = self.evaluate(eval_data,
                                                 batch_size=batch_size,
                                                 verbose=0)
                        history.setdefault("eval_loss", []).append(
                            eval_res.get("loss"))
                        epoch_logs.update({f"eval_{k}": v
                                           for k, v in eval_res.items()})
                        cbks.on_eval_end(eval_res)
                with timers.scope("callbacks"):
                    cbks.on_epoch_end(epoch, epoch_logs)
                    if _epoch_span is not None:
                        _epoch_span.end(status="ok")
                        _epoch_span = None
                    # the epoch's phases: the mean of each and its
                    # longest single run, which names a stalled step
                    # that a mean hides; the maxima start anew
                    if logger.isEnabledFor(logging.INFO):
                        # and how often the Layer tree had a reader:
                        # write_back scopes that copied over all so far
                        logger.info(
                            "fit epoch %d phases: %s tree_copies=%d/%d",
                            epoch,
                            _phase_line(timers, _ep_totals, _ep_counts),
                            timers.counts.get("write_back/copy", 0),
                            timers.counts.get("write_back", 0))
                    timers.maxima.clear()
                # SIGTERM during epoch-end eval/callbacks must still turn
                # into a clean preempted exit (not a SIGKILL after the
                # grace window); a final-epoch latch just finishes the run
                if guard is not None and guard.preempted \
                        and epoch + 1 < epochs:
                    with timers.scope("ckpt"):
                        if it_count > start_it:
                            self._ft_save(ft_mgr, ft_saver, it_count,
                                          force=True, sync=True)
                            ft_mgr.wait()
                        _flightrec.dump("preempt")
                        raise SystemExit(_res.PREEMPTED_EXIT_CODE)
                if self.stop_training:
                    break
                if num_iters is not None and it_count >= num_iters:
                    break
            fit_ok = True
        finally:
            if not fit_ok:
                # OOM postmortem BEFORE the engine unwinds: the census
                # must see the allocations that were resident when the
                # step failed.  Covers callers that catch the exception
                # themselves (the crash excepthook never fires then)
                try:
                    import sys as _sys

                    from ..monitor import perf as _perf

                    _exc = _sys.exc_info()[1]
                    if _perf.is_oom(_exc):
                        _perf.oom_postmortem(_exc)
                except Exception:  # noqa: BLE001 - never mask the error
                    pass
            # final write-back: the engine's device-resident state becomes
            # the Layer tree's state again (single source of truth for
            # train_batch/save/parameters after fit returns) — even when
            # fit is unwinding on an exception/preemption
            with timers.scope("write_back"):
                if fit_ok:
                    # success path: a failed final write-back means the
                    # Layer tree holds stale weights — that must
                    # surface, not pass
                    engine.finish()
                else:
                    try:
                        engine.finish()
                    except Exception:  # noqa: BLE001 - don't mask the
                        pass           # real error
            if engine.mesh is not None:
                loader.placement = prev_placement
            # a crash mid-fit must still flush/close callback resources
            cbks.on_train_end({})
            if _fit_span is not None:
                _status = "ok" if fit_ok else (
                    "preempted" if guard is not None and guard.preempted
                    else "error")
                if _epoch_span is not None:
                    _epoch_span.end(status=_status)
                    _epoch_span = None
                _fit_span.set_attr("it", it_count)
                _fit_span.end(status=_status)
                self._fit_span = None
            if telem is not None:
                # a capture armed for more steps than remained must still
                # produce a valid trace artifact
                telem.finish_trace()
                telem.on_fit_end({"it": it_count, "ok": fit_ok})
                if _restore_usr1 is not None:
                    _restore_usr1()
                if _unhook_warn is not None:
                    _unhook_warn()
            if guard is not None:
                guard.__exit__(None, None, None)
            if ft_saver is not None:
                # drain the background writer so every submitted
                # generation is durably on disk before fit returns —
                # with a budget matched to HOW fit is exiting: patient
                # on a clean return (a large final generation on a slow
                # disk is a healthy write, not a stall), zero on a
                # preemption unwind (the emergency save already spent
                # its bounded 30s wait, and the SIGTERM grace window
                # must reach the distinct exit code before SIGKILL),
                # bounded on a crash unwind.  A drain that times out
                # logs an error inside close() and the newest durable
                # generation stands.
                if fit_ok:
                    drain_s = 300.0
                elif guard is not None and guard.preempted:
                    drain_s = 0.0
                else:
                    drain_s = 30.0
                ft_saver.close(timeout=drain_s)
                if ft_saver.fatal:
                    logger.error(
                        "fit: checkpoint durability was LOST during this "
                        "run (%d consecutive failed generations; last: "
                        "%s)", ft_saver.consecutive_failures,
                        ft_saver.last_error)
            if ft_mgr is not None:
                ft_mgr.wait()
                ft_mgr.close()
            durability_lost = (
                (ft_saver is not None and ft_saver.fatal)
                or (ft_mgr is not None and self._ft_sync_failures
                    >= max(1, self._ft_max_failures)))
            if durability_lost and fit_ok:
                # the K-th consecutive failure can land during the final
                # drain (async) or the epoch-end save (sync), after the
                # in-loop check: the run must STILL exit with the
                # distinct durability code, not a clean 0 — but never
                # mask an exception already unwinding (_res is bound
                # whenever ft_mgr is)
                _flightrec.dump("durability")
                raise SystemExit(_res.DURABILITY_EXIT_CODE)
        return history

    def _telemetry_window(self, telem, engine, timers, epoch, it_count,
                          batch_size, losses, inputs, labels,
                          win_t0, win_it0, win_totals, win_counts):
        """Close one telemetry step window (monitor.TrainTelemetry):
        resolve flops-per-step once per fit from the compiled step's XLA
        cost analysis, hand the per-window StepTimers deltas over, and
        return the fresh window anchors."""
        now = time.perf_counter()
        telem.ensure_flops(
            lambda: engine.step_cost_analysis(inputs, labels))
        from ..monitor import perf as _perf

        # publish introspection surfaces against the live engine: the
        # op table over /debug/perf (re-registered each window so the
        # provider always lowers against a current batch) and owner
        # tags so the buffer census can split params/opt state/buffers
        # from activations.  engine.finish() drops the device state at
        # fit exit (write-back rebinds the buffers into the Layer tree
        # and model._opt_state), so each supplier falls back there —
        # a census scraped between fits still claims the weights.
        network, model_obj = self.network, self
        _perf.register_provider(
            "train", lambda: engine.op_report(inputs, labels))

        def _own_params():
            if engine.state is not None:
                return (engine.state["trainable"], engine.state["frozen"])
            return [p.value for p in network.parameters()]

        def _own_opt():
            if engine.state is not None:
                return engine.state["opt"]
            return model_obj._opt_state

        def _own_buffers():
            if engine.state is not None:
                return engine.state["buffers"]
            return [getattr(b, "value", None) for b in network.buffers()]

        _perf.register_owner("params", _own_params)
        _perf.register_owner("opt_state", _own_opt)
        _perf.register_owner("buffers", _own_buffers)
        deltas = {
            name: (timers.totals.get(name, 0.0)
                   - win_totals.get(name, 0.0),
                   timers.counts.get(name, 0) - win_counts.get(name, 0))
            for name in timers.totals}
        # a phase's longest run of this epoch, where it fell inside
        # this window (else an earlier window's line has it)
        longest = {name: seconds
                   for name, (seconds, at) in timers.maxima.items()
                   if at > win_counts.get(name, 0)}
        telem.window(step=it_count, epoch=epoch,
                     steps=it_count - win_it0, wall_s=now - win_t0,
                     batch_size=batch_size,
                     loss=(losses[-1] if losses else None),
                     lr=self._optimizer.get_lr(), phase_deltas=deltas,
                     phase_maxima=longest)
        from ..monitor import flightrec as _flightrec

        _flightrec.record(
            "window", step=it_count, epoch=epoch,
            steps=it_count - win_it0, wall_s=round(now - win_t0, 3),
            loss=(float(losses[-1]) if losses else None))
        return now, it_count, dict(timers.totals), dict(timers.counts)

    def _split_batch(self, batch):
        n_label = len(_to_list(self._labels)) or 1
        if len(batch) == 1:
            return batch, []
        return batch[:-n_label], batch[-n_label:]

    def evaluate(self, eval_data, batch_size=1, log_freq=10, verbose=2,
                 num_workers=0, callbacks=None, num_samples=None):
        loader = eval_data if isinstance(eval_data, DataLoader) else \
            DataLoader(eval_data, batch_size=batch_size, shuffle=False,
                       num_workers=num_workers)
        for m in self._metrics:
            m.reset()
        # hoisted once per evaluate (the old loop re-split the Layer tree
        # and synced float(loss) on every batch); losses stay on device
        # and are fetched in one batched transfer at the end
        self.network.eval()
        if self._eval_fn is None:
            self._eval_fn = self._build_eval_step()
        params, buffers = state_pytrees(self.network)
        losses_dev = []
        for batch in loader:
            batch = _to_list(batch)
            inputs, labels = self._split_batch(batch)
            inputs = [_as_tensor(x) for x in inputs]
            labels = [_as_tensor(x) for x in labels]
            rng = _random.split_key()
            outs, loss = self._eval_fn(params, buffers, rng, inputs, labels)
            losses_dev.append(loss)
            if self._metrics:
                with host_fetch():
                    for m in self._metrics:
                        m.update(unwrap(m.compute(*(_to_list(outs) +
                                                    labels))))
        losses = fetch_floats(losses_dev)
        res = {"loss": float(np.mean(losses)) if losses else 0.0}
        for m in self._metrics:
            res[m._name] = m.accumulate()
        if verbose:
            # verbose=1 stdout contract, like ProgBarLogger
            print("Eval:", res, flush=True)  # noqa: PTA006
        return res

    def predict(self, test_data, batch_size=1, num_workers=0, stack_outputs=False,
                verbose=1, callbacks=None):
        loader = test_data if isinstance(test_data, DataLoader) else \
            DataLoader(test_data, batch_size=batch_size, shuffle=False,
                       num_workers=num_workers)
        outputs = []
        for batch in loader:
            batch = _to_list(batch)
            inputs, _ = self._split_batch(batch)
            outs, _ = self.eval_batch_no_loss([_as_tensor(x) for x in inputs])
            outputs.append(outs)
        if stack_outputs and outputs:
            from .. import tensor_ops as T

            if isinstance(outputs[0], Tensor):
                return [T.concat(outputs, axis=0)]
        return outputs

    # -- persistence -------------------------------------------------------
    def save(self, path, training=True):
        from ..framework.io_state import save as fsave

        fsave(self.network.state_dict(), path + ".pdparams")
        if training and self._optimizer is not None:
            opt_state = getattr(self, "_opt_state", None)
            payload = {"step_count": self._optimizer._step_count}
            if opt_state is not None:
                payload["opt_state"] = jax.tree_util.tree_map(np.asarray,
                                                              opt_state)
            fsave(payload, path + ".pdopt")

    def load(self, path, skip_mismatch=False, reset_optimizer=False):
        from ..framework.io_state import load as fload

        self.network.set_state_dict(fload(path + ".pdparams"))
        opt_path = path + ".pdopt"
        if not reset_optimizer and os.path.exists(opt_path):
            payload = fload(opt_path)
            if self._optimizer is not None:
                self._optimizer._step_count = payload.get("step_count", 0)
            if "opt_state" in payload:
                self._opt_state = jax.tree_util.tree_map(
                    jnp.asarray, payload["opt_state"])
        return self

    def serve(self, host="127.0.0.1", port=8866, *, input_spec=None,
              max_batch_size=None, batch_timeout_ms=None, buckets=None,
              queue_depth=None, blocking=True,
              install_signal_handlers=True):
        """Serve this model over HTTP with adaptive batching
        (paddle_tpu.serving): concurrent /predict requests are coalesced
        into padded shape-bucket batches, every bucket is AOT-warmed
        before the port opens, and SIGTERM drains gracefully.

        `input_spec` (or the Model's constructor `inputs`) provides the
        per-input (shape, dtype) used for warmup — dims of -1/None are
        serving-variable (batch, and sequence when `buckets` carries a
        seq grid).  With `blocking=False` returns the started
        `ServingServer` (use `.url`, `.shutdown()`); otherwise blocks
        until SIGTERM and returns the drain exit code (0 = clean).
        """
        from ..serving import ServingEngine, ServingServer

        self.network.eval()
        spec = input_spec if input_spec is not None else self._inputs
        engine = ServingEngine(
            self.network, max_batch_size=max_batch_size,
            batch_timeout_ms=batch_timeout_ms, buckets=buckets,
            queue_depth=queue_depth,
            input_specs=_to_list(spec) if spec is not None else None)
        server = ServingServer(
            engine, host=host, port=port,
            install_signal_handlers=install_signal_handlers).start()
        if blocking:
            # operator-facing notice on the blocking serve() path
            print(f"serving on {server.url} "  # noqa: PTA006
                  f"(SIGTERM drains gracefully)", flush=True)
            return server.wait()
        return server

    def serve_generate(self, host="127.0.0.1", port=8866, *,
                       max_slots=None, max_seq_len=None,
                       prompt_buckets=None, queue_depth=None,
                       page_size=None, num_pages=None, prefix_cache=None,
                       mesh=None, layout=None,
                       blocking=True, install_signal_handlers=True):
        """Serve autoregressive generation over HTTP with continuous
        batching (paddle_tpu.serving.generation): prefill seeds a
        device-resident PAGED KV cache, one donated decode executable
        advances every in-flight request a token per iteration, and POST
        /generate streams tokens as they decode (SSE).  The network must
        expose the serving protocol (``slot_prefill`` / ``slot_step`` over
        a KV source, e.g. models.GPTForCausalLM).

        ``page_size`` / ``num_pages`` size the KV page pool (0 pages =
        dense-equivalent), ``prefix_cache`` shares identical tokenized
        prompt prefixes as read-only pages, and ``mesh``/``layout``
        (a ``{"tp": 2}``-style dict or jax Mesh + optional SpecLayout)
        serve a tensor-parallel model from this one process — all
        forwarded to :class:`serving.generation.GenerationEngine`.

        With `blocking=False` returns the started `ServingServer` (use
        `.url`, `.shutdown()`); otherwise blocks until SIGTERM and
        returns the drain exit code (0 = clean).
        """
        from ..serving import ServingServer
        from ..serving.generation import GenerationEngine

        self.network.eval()
        engine = GenerationEngine(
            self.network, max_slots=max_slots, max_seq_len=max_seq_len,
            prompt_buckets=prompt_buckets, queue_depth=queue_depth,
            page_size=page_size, num_pages=num_pages,
            prefix_cache=prefix_cache, mesh=mesh, layout=layout)
        server = ServingServer(
            None, host=host, port=port,
            install_signal_handlers=install_signal_handlers,
            gen_engine=engine).start()
        if blocking:
            # operator-facing notice on the blocking serve path
            print(f"serving generation on {server.url} "  # noqa: PTA006
                  f"(SIGTERM drains gracefully)", flush=True)
            return server.wait()
        return server

    def parameters(self, *args, **kwargs):
        return self.network.parameters()

    def summary(self, input_size=None, dtype=None):
        """Parameter summary (hapi Model.summary)."""
        return summary(self.network, input_size, dtype)


def summary(net, input_size=None, dtypes=None):
    lines = []
    total = 0
    for name, p in net.named_parameters():
        n = int(np.prod(p.shape))
        total += n
        lines.append(f"{name:60s} {str(p.shape):20s} {n}")
    out = "\n".join(lines) + f"\nTotal params: {total}"
    # Model.summary() prints the table by API contract (hapi parity)
    print(out)  # noqa: PTA006
    return {"total_params": total}


def flops(net, input_size, custom_ops=None, print_detail=False):
    """Forward FLOPs of a network (hapi/dynamic_flops.py).  TPU-native:
    XLA's own cost model counts them — jit-compile the forward on zero
    inputs of `input_size` and read compiled cost_analysis, which covers
    every op the hardware will actually run (the reference hand-counts a
    per-layer table)."""
    import jax
    import jax.numpy as jnp

    from ..nn.layer_base import functional_call, state_pytrees
    from ..tensor import Tensor

    sizes = input_size if isinstance(input_size[0], (list, tuple)) \
        else [input_size]
    # preserve PER-SUBLAYER modes (a blanket net.train() would flip
    # deliberately-frozen sublayers back to training)
    modes = [(l, l.training) for l in net.sublayers(include_self=True)] \
        if hasattr(net, "sublayers") else [(net, net.training)]
    net.eval()
    try:
        params, buffers = state_pytrees(net)

        def fwd(params, *xs):
            out, _ = functional_call(net, params,
                                     tuple(Tensor(x) for x in xs),
                                     buffers=buffers)
            outs = out if isinstance(out, (tuple, list)) else [out]
            return tuple(o.value for o in outs)

        xs = [jnp.zeros(tuple(s), jnp.float32) for s in sizes]
        compiled = jax.jit(fwd).lower(params, *xs).compile()
        ca = compiled.cost_analysis()
        ca = ca[0] if isinstance(ca, (list, tuple)) else (ca or {})
        if "flops" not in ca:
            import warnings

            warnings.warn(
                "flops(): this backend's compiled cost_analysis() does "
                "not report a 'flops' key; returning 0", stacklevel=2)
        total = int(ca.get("flops", 0.0))
        if print_detail:
            # print_detail=True is the flops() API contract
            print(f"XLA-analyzed forward FLOPs for "  # noqa: PTA006
                  f"input {input_size}: {total:,}")
        return total
    finally:
        for layer, mode in modes:
            layer.training = mode
