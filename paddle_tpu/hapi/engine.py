"""Device-resident async training engine behind Model.fit/evaluate.

Why this exists (the framework tax the old hot loop paid per step):
  * `_split_params()` + `dict(named_parameters())` rebuilt python dicts
    from the Layer tree every batch;
  * the jitted step had no `donate_argnums`, so XLA allocated fresh
    output buffers for params/buffers/opt-state (a full copy of ~3x the
    model per step) instead of updating in place;
  * `float(loss_val)` forced a host round-trip each step, serializing
    dispatch against device execution (no async overlap);
  * every array was written back into Layer `_value`s each batch; and
  * `jnp.asarray(lr)` / `jnp.asarray(step)` re-uploaded host scalars.

The engine removes all of it.  On `begin()` the whole training state —
`(trainable, frozen, buffers, opt_state, lr, step)` — is snapshotted ONCE
into a single pytree that stays on device for the whole run.  The jitted
step takes that pytree with `donate_argnums=(0,)` (XLA aliases every
input buffer onto the matching output, reusing memory in place — the
reference gets the same effect from fluid's inplace op buffers), and the
fit loop dispatches steps without ever blocking: loss scalars stay in
flight inside `_LossRing` and are fetched in one batched `device_get`
only at `log_freq` boundaries, epoch ends, and checkpoints.  Write-back
into Layer `_value`s happens at `fit()` exit and at the epoch ends
where something will read the Layer tree (an evaluate, a checkpoint, a
user callback: `Model.fit` knows them), as ONE jitted copy of the whole
tree; a copy that nothing reads is not kept on the device.  The
single-call `Model.train_batch` contract is untouched.

Every DELIBERATE device→host fetch goes through `host_fetch()`, which
opens an explicit `jax.transfer_guard_device_to_host("allow")` scope —
so a fit loop runs clean under `jax.transfer_guard_device_to_host(
"disallow")` and any hidden sync that sneaks into the step path fails
loudly (tests/test_train_engine.py pins this).

SPMD sharding (GSPMD, Xu et al.): `begin(mesh=...)` makes the SAME
donated step mesh-aware — params/buffers/opt-state are placed with
`NamedSharding` (replicated over `dp`; optionally split over `mp` via a
per-param sharding rule or `distributed.annotate` dist_specs), the
global batch is split over the `dp` axis, and XLA's partitioner inserts
the grad all-reduces the reference hand-rolled in
`DataParallel.apply_collective_grads` (fluid/dygraph/parallel.py:314).
Every single-chip contract survives: donation (out_shardings are pinned
to the in shardings so XLA aliases every state buffer), the sync-free
loss ring, the persistent compile cache, and callback write-back (the
Layer tree always receives SINGLE-device arrays, so eval/train_batch/
save after a sharded fit stay mesh-free).  Numerics: a `dp=1` mesh is
bitwise-identical to the unsharded engine, and resume-at-the-same-dp is
bitwise round-trip; across DIFFERENT dp degrees XLA reassociates batch
reductions (partial sums + all-reduce), so dp=1 vs dp=8 agree to
float32 ULP, not bit-for-bit (tests/test_spmd_fit.py pins both).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from ..framework import flags as _flags
from ..framework import random as _random
from ..framework.transfer import (fetch_floats, host_fetch, in_host_fetch,
                                  shard_batch)
from ..nn.layer_base import functional_call
from ..tensor import Tensor
from ..utils.profiler import StepTimers, startup

__all__ = ["TrainEngine", "build_pure_train_step", "host_fetch",
           "in_host_fetch", "fetch_floats", "resolve_mesh", "mesh_meta"]


def mesh_meta(mesh):
    """JSON-serializable description of a mesh for checkpoint manifests:
    the elastic-resume path reads it back to log the dp transition it is
    performing (saved at dp=N → restoring onto dp=M)."""
    if mesh is None:
        return {"dp": 1, "devices": 1, "axes": {}}
    axes = {str(name): int(size)
            for name, size in zip(mesh.axis_names, mesh.devices.shape)}
    return {"dp": int(axes.get("dp", 1)), "devices": int(mesh.size),
            "axes": axes}


def resolve_mesh(mesh=None):
    """fit()'s mesh resolution chain: explicit argument (a Mesh or a
    `{"dp": 8}`-style shape dict) → ambient mesh from an ACTIVE
    `mesh_guard` scope (honored only when it spans >1 device; a global
    mesh left behind by `set_mesh`/`ensure_mesh` — eager collectives
    call the latter as a side effect — is deliberately ignored, so
    unrelated code can never silently reshard a fit) →
    `FLAGS_mesh_shape` → None (single-device engine, the PR-2 fast
    path, bit-for-bit unchanged)."""
    from ..distributed.mesh import (build_mesh, get_mesh, in_mesh_guard,
                                    parse_mesh_shape)

    def from_shape(shape):
        # a concrete shape smaller than the machine takes the leading
        # device prefix ({"dp": 1} on an 8-device host is a valid —
        # and parity-testable — degenerate mesh)
        dims = [int(v) for v in shape.values()]
        if -1 not in dims:
            n = int(np.prod(dims))
            if n <= len(jax.devices()):
                return build_mesh(shape, devices=jax.devices()[:n])
        return build_mesh(shape)

    if isinstance(mesh, dict):
        return from_shape(mesh)
    if mesh is not None:
        return mesh
    if in_mesh_guard() and get_mesh() is not None:
        # an ACTIVE guard always outranks the flag — including a
        # deliberate 1-device guard (force-single-device debugging must
        # not be resharded by a launcher's FLAGS_mesh_shape)
        ambient = get_mesh()
        return ambient if ambient.size > 1 else None
    shape = parse_mesh_shape(_flags.flag("FLAGS_mesh_shape"))
    if shape:
        return from_shape(shape)
    return None


def _to_list(x):
    if x is None:
        return []
    return list(x) if isinstance(x, (list, tuple)) else [x]


class _LossRing:
    """In-flight device loss scalars awaiting a batched fetch.

    Append never blocks (the scalar is an async XLA computation result);
    `drain()` performs ONE device_get for everything pending and returns
    python floats in step order."""

    def __init__(self):
        self._pending = []

    def append(self, dev_scalar):
        self._pending.append(dev_scalar)

    def __len__(self):
        return len(self._pending)

    def drain(self):
        out = fetch_floats(self._pending)
        self._pending = []
        return out


@jax.jit
def _copy_tree(tree):
    # a device-side copy of the whole tree in ONE dispatch (async, at
    # most once an epoch — NOT per step): the engine donates its state
    # buffers, so anything the Layer tree keeps referencing must be a
    # distinct buffer or the next dispatch would invalidate it under the
    # user's feet.  `jnp.copy` lowers to a copy XLA may not elide, and
    # the inputs are not donated, so no output aliases an input
    # (tests/test_train_engine.py reads the buffers' addresses).  One
    # executable a tree structure: begin()'s state, and write_back()'s
    # tree with and without the optimizer's slots.
    return jax.tree_util.tree_map(jnp.copy, tree)


def _tree_deleted(tree):
    """True when any leaf is a donated-and-consumed (deleted) jax array —
    the state a failed dispatch leaves behind."""
    for a in jax.tree_util.tree_leaves(tree):
        if getattr(a, "is_deleted", None) is not None and a.is_deleted():
            return True
    return False


def build_pure_train_step(network, loss_layer, opt):
    """THE train-step math, as one pure function
    `(trainable, frozen, buffers, opt_state, lr, t, rng, inputs, labels)
    -> (new_params, new_buffers, new_opt_state, loss, outs)`.

    Single source of truth: `Model._build_train_step` jits it as-is (the
    eager `train_batch` contract) and `TrainEngine` wraps it in the
    donated state-pytree step — the engine's bitwise-equivalence
    guarantee to `train_batch` holds by construction, not by keeping two
    hand-synced copies of the loss/grad/update body."""

    def step(trainable, frozen, buffers, opt_state, lr, t, rng, inputs,
             labels):
        def loss_fn(tr):
            all_params = {**tr, **frozen}
            outs, new_buffers = functional_call(
                network, all_params, tuple(inputs), {}, buffers=buffers,
                rng=rng)
            outs_l = _to_list(outs)
            if callable(loss_layer):
                lv = loss_layer(*(outs_l + list(labels)))
            else:
                raise RuntimeError("prepare() a loss before fit()")
            lv = lv.value if isinstance(lv, Tensor) else jnp.asarray(lv)
            return jnp.mean(lv), (outs, new_buffers)

        (loss_val, (outs, new_buffers)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(trainable)
        new_params, new_opt_state = opt.apply_pytree(
            trainable, grads, opt_state, lr=lr, step=t)
        return new_params, new_buffers, new_opt_state, loss_val, outs

    return step


class TrainEngine:
    """Owns the device-resident state for one Model across fit() runs.

    Lifecycle: `begin()` snapshots Layer state → N x `step()` (donated,
    sync-free) → `write_back()` at the boundaries where the Layer tree
    has a reader → `finish()` at fit exit.  The compiled step function
    is cached on the instance, and the instance is cached on the Model,
    so repeated fit() calls (and the persistent XLA compilation cache
    across processes — FLAGS_jit_cache_dir) skip recompilation.
    """

    def __init__(self, model):
        self.model = model
        self.state = None
        self.ring = _LossRing()
        self._step_fn = None
        self._param_refs = None
        self._buffer_refs = None
        self._lr_host = None
        self._host_step = 0
        self.mesh = None
        self._sharding_rule = None
        self._state_sharding = None
        self._step_key = None  # (mesh, rule) the cached jit was built for
        self._cost_cache = None  # cost_analysis of the live _step_fn
        self._cost_cache_fn = None
        self._compiled_cache = None  # AOT-compiled step (op_report)
        self._example_batch = None   # last (inputs, labels) seen by
        # step_cost_analysis — lets op_report() run without a batch
        self._layout = None
        self._recompute = None
        self._accum = 1
        self.batch_axes = "dp"  # str or tuple — shard_batch's split axes
        # phases of step(); Model.fit hands in its own recorder, whose
        # `dispatch` scope they then run under
        self.timers = StepTimers()

    @property
    def active(self):
        return self.state is not None

    # -- lifecycle ---------------------------------------------------------
    def begin(self, mesh=None, sharding_rule=None, layout=None,
              recompute=None, accum_steps=1, grad_sync=None):
        m = self.model
        if m._optimizer is None or m._loss is None:
            raise RuntimeError("prepare() an optimizer and a loss before "
                               "fit()")
        trainable, frozen, buffers = m._split_params()
        opt_state = getattr(m, "_opt_state", None)
        if opt_state is None:
            opt_state = m._optimizer.init_pytree(trainable)
        self._param_refs = dict(m.network.named_parameters())
        self._buffer_refs = dict(m.network.named_buffers())
        self._lr_host = float(m._optimizer.get_lr())
        self._host_step = int(m._optimizer._step_count)
        self.mesh = resolve_mesh(mesh)
        self._sharding_rule = sharding_rule
        from ..distributed import layout as _layout_mod

        if layout is True:
            layout = _layout_mod.SpecLayout()
        self._layout = layout
        self._layout_unmatched = set()
        # validate the policy NAME eagerly (a typo'd fit(recompute=) must
        # fail here, not after a 6-minute trace)
        _layout_mod.resolve_policy(recompute)
        self._recompute = recompute
        self._accum = int(accum_steps)
        if self._accum < 1:
            raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")
        # cross-PROCESS dp grad sync (the pod/DCN seam): a host callable
        # `grads_pytree -> grads_pytree` spliced between the grad
        # computation and the optimizer update via jax.pure_callback.
        # The in-graph mesh collectives cover intra-process devices; this
        # covers the axis XLA cannot see (other OS processes), and its
        # membership can SHRINK between dispatches without retracing —
        # the compiled step closes over the callable, not the world size.
        self._grad_sync = grad_sync
        if self.mesh is not None and layout is not None:
            self.batch_axes = layout.batch_axes(self.mesh)
        else:
            # the PR-4 call shape, bit for bit: dp-only meshes must keep
            # the exact shard_batch spec (and jit cache key) they had
            self.batch_axes = "dp"
        raw = {
            "trainable": trainable,
            "frozen": frozen,
            "buffers": buffers,
            "opt": opt_state,
            "lr": jnp.asarray(self._lr_host, jnp.float32),
            "step": jnp.asarray(self._host_step, jnp.int32),
        }
        # copy ONCE per fit: the Layer tree keeps its own buffers, the
        # engine exclusively owns (and donates) these.  The copy must
        # come BEFORE device_put: device_put onto an equal sharding can
        # return the SAME buffer, and donating an aliased buffer would
        # invalidate the Layer tree's arrays under the user's feet.
        if self.mesh is None:
            self._state_sharding = None
            self.state = _copy_tree(raw)
            step_key = None
        else:
            self._state_sharding = self._build_state_sharding(raw)
            if self._layout_unmatched:
                _layout_mod.warn_unmatched(self._layout_unmatched)
            self.state = jax.device_put(_copy_tree(raw),
                                        self._state_sharding)
            self._warn_if_mesh_unused()
            # key on the RESOLVED sharding tree, not the rule object:
            # a dist_spec annotated between fits changes the placement
            # under the same (mesh, rule) — the cached jit's pinned
            # out_shardings would silently force the old layout — and
            # conversely a fresh-but-identical lambda rule must not
            # bust the cache and retrace
            leaves, treedef = jax.tree_util.tree_flatten(
                self._state_sharding)
            step_key = (self.mesh, treedef, tuple(leaves))
        # the step BODY now also depends on accum/remat/batch axes; a
        # policy callable keys by identity (a fresh-but-equal lambda
        # retraces — the safe direction)
        rec = self._recompute
        rec_key = rec if (rec is None or isinstance(rec, (str, bool))) \
            else id(rec)
        step_key = (step_key, self._accum, rec_key, self.batch_axes,
                    self._layout is not None,
                    id(grad_sync) if grad_sync is not None else None)
        self._record_synced_ids()
        self.ring = _LossRing()
        if self._step_fn is None or step_key != self._step_key:
            self._step_fn = self._build_step()
            self._step_key = step_key
        return self

    def _warn_if_mesh_unused(self):
        """A mesh whose axes shard NOTHING (no `dp` axis for the batch,
        no rule/annotation sharding a param) replicates the whole
        computation: every device runs the identical step at N× the
        chip cost while losses look perfectly healthy.  Almost always a
        typo'd axis name (FLAGS_mesh_shape='data=8') — say so."""
        if "dp" in self.mesh.axis_names:
            return
        shardings = [*self._state_sharding["trainable"].values(),
                     *self._state_sharding["frozen"].values()]
        if any(s.spec != PartitionSpec() for s in shardings):
            return
        import warnings

        warnings.warn(
            f"fit(mesh=...) got a mesh with axes "
            f"{tuple(self.mesh.axis_names)} but no 'dp' axis and no "
            "sharding_rule/dist_spec shards any param: every device "
            "will replicate the full computation (no speedup). Name "
            "the data-parallel axis 'dp', or provide a sharding_rule.",
            UserWarning, stacklevel=3)

    # -- sharding ----------------------------------------------------------
    def _param_spec(self, name) -> PartitionSpec:
        """PartitionSpec for one named param: the fit(sharding_rule=)
        hook wins, then a `distributed.annotate` dist_spec on the
        Parameter, then the fit(layout=) SpecLayout table (pattern-
        matched by name/shape, replicated fallback with an aggregated
        warning), else replicated.  Axis names outside the mesh are
        dropped (same leniency as meta_parallel.shard_constraint), so an
        mp-annotated model still fits on a pure-dp mesh."""
        p = self._param_refs.get(name)
        spec = None
        if self._sharding_rule is not None:
            spec = self._sharding_rule(name, p)
        if spec is None and p is not None:
            spec = getattr(p, "dist_spec", None)
        if spec is None and self._layout is not None and p is not None:
            shape = tuple(p.shape)
            spec = self._layout.spec_for(name, shape)
            if spec is None:
                self._layout_unmatched.add(name)
                return PartitionSpec()
            # layout pruning is per-dim divisibility-aware (a tuple
            # entry degrades axis by axis), stronger than the bare
            # axis-presence filter below
            return self._layout.prune(spec, shape, self.mesh)
        if spec is None:
            return PartitionSpec()
        axes = self.mesh.axis_names

        def known(entry):
            # a spec entry may be an axis name OR a tuple of axis names
            # (P(("dp", "mp")) shards one dim over both axes)
            if isinstance(entry, (tuple, list)):
                return all(a in axes for a in entry)
            return entry in axes

        return PartitionSpec(*[a if (a is None or known(a)) else None
                               for a in spec])

    def _build_state_sharding(self, raw):
        """NamedSharding pytree mirroring the state: params follow
        `_param_spec`, each opt slot inherits its param's spec when the
        shapes match (Adam-family moments — ZeRO semantics: slots live
        on their param's fsdp shards) and replicates otherwise.
        Scalar/0-d/1-element slots ALWAYS replicate: on a 1-element
        param the shapes-match heuristic would otherwise pin a step
        counter or beta-power slot to the param's spec
        (tests/test_layout3d.py regression-pins this)."""
        mesh = self.mesh
        rep = NamedSharding(mesh, PartitionSpec())

        def psh(name):
            return NamedSharding(mesh, self._param_spec(name))

        def inherits(v, ref):
            shp = getattr(v, "shape", None)
            return (ref is not None and shp == ref.shape
                    and shp is not None
                    and int(np.prod(shp, dtype=np.int64)) > 1)

        opt_sh = {}
        for name, slots in raw["opt"].items():
            if not isinstance(slots, dict):
                # wrapper optimizers (Lookahead/EMA/ModelAverage) keep
                # non-per-param entries (scalars, nested trees) at the
                # top level: replicate them — a sharding is a valid
                # pytree PREFIX, so `rep` covers whole subtrees too
                opt_sh[name] = rep
                continue
            ref = raw["trainable"].get(name)
            ps = psh(name)
            opt_sh[name] = {
                slot: (ps if inherits(v, ref) else rep)
                for slot, v in slots.items()}
        return {
            "trainable": {k: psh(k) for k in raw["trainable"]},
            "frozen": {k: psh(k) for k in raw["frozen"]},
            "buffers": {k: rep for k in raw["buffers"]},
            "opt": opt_sh,
            "lr": rep,
            "step": rep,
        }

    def _record_synced_ids(self):
        # the array OBJECT each Layer slot held when the engine last
        # synced with it — a later `is` mismatch means user code
        # (callback, set_value) wrote the slot and the device state must
        # be refreshed.  Holding the object (not a bare id()) matters:
        # a freed array's id can be reused by a later allocation (ABA),
        # which would silently mask a double mutation between syncs
        self._synced = {k: p._value for k, p in self._param_refs.items()}
        self._synced.update((f"buffer::{k}", b._value)
                            for k, b in self._buffer_refs.items())

    def refresh_from_layers(self):
        """Fold user writes to Layer params/buffers (SWA/EMA write-back,
        weight clipping, pruning masks — anything via `set_value`) back
        into the device-resident state.  Identity comparison only: costs
        a dict scan per call, uploads only dirty entries (as copies — the
        engine still donates its own buffers).  Returns the number of
        refreshed slots."""
        if self.state is None:
            return 0
        dirty = 0
        st = self.state
        sh = self._state_sharding

        def place(v, tgt, k):
            # mesh mode: re-shard the fresh copy onto the state's own
            # sharding — a committed single-device upload mixed into the
            # mesh-resident state would fail the next dispatch
            if sh is not None:
                return jax.device_put(v, sh[tgt][k])
            return v

        for k, p in self._param_refs.items():
            if p._value is not self._synced.get(k):
                v = jnp.array(p._value, copy=True)
                tgt = ("trainable" if k in st["trainable"] else "frozen")
                st[tgt][k] = place(v, tgt, k)
                self._synced[k] = p._value
                dirty += 1
        for k, b in self._buffer_refs.items():
            if b._value is not self._synced.get(f"buffer::{k}"):
                st["buffers"][k] = place(jnp.array(b._value, copy=True),
                                         "buffers", k)
                self._synced[f"buffer::{k}"] = b._value
                dirty += 1
        return dirty

    def _sync_grads(self, grads):
        """Route grads through the cross-process grad_sync host callable
        (pure_callback keeps the step one donated jitted dispatch; the
        callback's pod membership is read at EXECUTION time, so an
        elastic shrink needs no retrace)."""
        if self._grad_sync is None:
            return grads
        shapes = jax.tree_util.tree_map(
            lambda g: jax.ShapeDtypeStruct(g.shape, g.dtype), grads)
        return jax.pure_callback(self._grad_sync, shapes, grads)

    def _build_step(self):
        if (self._accum > 1 or self._recompute is not None
                or (self._layout is not None and self.mesh is not None)
                or self._grad_sync is not None):
            return self._build_featured_step()
        m = self.model
        pure = build_pure_train_step(m.network, m._loss, m._optimizer)

        def step(state, rng, inputs, labels):
            t = state["step"] + 1
            new_params, new_buffers, new_opt, loss_val, outs = pure(
                state["trainable"], state["frozen"], state["buffers"],
                state["opt"], state["lr"], t, rng, inputs, labels)
            # every input leaf reappears structurally in the output so
            # XLA's input-output aliasing consumes ALL donated buffers
            # (params/opt in place, frozen/lr pass through)
            new_state = {"trainable": new_params, "frozen": state["frozen"],
                         "buffers": new_buffers, "opt": new_opt,
                         "lr": state["lr"], "step": t}
            return new_state, loss_val, outs

        if self.mesh is None:
            return jax.jit(step, donate_argnums=(0,))
        # mesh mode: ONE global jitted step, partitioned by XLA.  Output
        # shardings are PINNED to the input state shardings — that is
        # what (a) keeps donation aliasing every state buffer (in/out
        # shardings must match for XLA to alias) and (b) prevents the
        # partitioner from drifting the state layout between steps,
        # which would force a re-trace on the second dispatch.  The loss
        # lands replicated; model outputs stay wherever propagation puts
        # them (batch-sharded over dp).
        rep = NamedSharding(self.mesh, PartitionSpec())
        return jax.jit(step, donate_argnums=(0,),
                       out_shardings=(self._state_sharding, rep, None))

    def _build_featured_step(self):
        """The 3D-parallel step: same donated `(state, rng, inputs,
        labels)` contract as `_build_step`, plus (any combination of)

          * rematerialization — the per-microbatch loss is wrapped in
            `jax.checkpoint` with the fit(recompute=) policy
            (distributed.layout.remat; subsumes the RecomputeOptimizer
            port).  Inside the accumulation scan prevent_cse is off —
            the scan barrier already blocks XLA from CSE-ing the
            recompute away;
          * microbatch gradient accumulation — fit(accum_steps=k) runs
            a `lax.scan` over k equal microbatches INSIDE this one
            donated jitted step (distributed.layout.microbatch_scan;
            subsumes GradientMergeOptimizer): grads/loss accumulate in
            the carry, buffers thread sequentially, rng splits per
            microbatch, and XLA sees one psum of the merged grad — the
            collective fires once per step, not once per microbatch;
          * activation constraints — with a layout on a mesh, batch
            leaves (and each scan slice of them) are re-pinned to the
            data axes with `with_sharding_constraint` so GSPMD keeps
            intermediates on the layout instead of gathering them.

        This builder is only reached when a feature is ON: the default
        path compiles the exact PR-4 step, byte for byte (dp-only jit
        cache keys are unchanged)."""
        from ..distributed import layout as _layout_mod

        m = self.model
        network, loss_layer, opt = m.network, m._loss, m._optimizer
        k = self._accum
        use_remat = self._recompute is not None \
            and self._recompute is not False
        policy = _layout_mod.resolve_policy(
            None if self._recompute is True else self._recompute)
        constrain = None
        if self._layout is not None and self.mesh is not None:
            constrain = _layout_mod.batch_constrainer(self.mesh,
                                                      self.batch_axes)

        def forward(trainable, frozen, buffers, rng, inputs, labels):
            if constrain is not None:
                inputs = constrain(inputs)
            all_params = {**trainable, **frozen}
            outs, new_buffers = functional_call(
                network, all_params, tuple(inputs), {}, buffers=buffers,
                rng=rng)
            outs_l = _to_list(outs)
            if callable(loss_layer):
                lv = loss_layer(*(outs_l + list(labels)))
            else:
                raise RuntimeError("prepare() a loss before fit()")
            lv = lv.value if isinstance(lv, Tensor) else jnp.asarray(lv)
            return jnp.mean(lv), (outs, new_buffers)

        def step(state, rng, inputs, labels):
            t = state["step"] + 1
            frozen = state["frozen"]

            def loss_fn(trainable, buffers, mb_rng, mb_in, mb_lab):
                return forward(trainable, frozen, buffers, mb_rng,
                               mb_in, mb_lab)

            body = loss_fn
            if use_remat:
                body = jax.checkpoint(loss_fn, policy=policy,
                                      prevent_cse=(k == 1))
            grad_fn = jax.value_and_grad(body, has_aux=True)
            if k == 1:
                (loss_val, (outs, new_buffers)), grads = grad_fn(
                    state["trainable"], state["buffers"], rng, inputs,
                    labels)
            else:
                loss_val, grads, outs, new_buffers = \
                    _layout_mod.microbatch_scan(
                        grad_fn, state["trainable"], state["buffers"],
                        rng, inputs, labels, k, constrain=constrain)
            grads = self._sync_grads(grads)
            new_params, new_opt = opt.apply_pytree(
                state["trainable"], grads, state["opt"], lr=state["lr"],
                step=t)
            new_state = {"trainable": new_params, "frozen": frozen,
                         "buffers": new_buffers, "opt": new_opt,
                         "lr": state["lr"], "step": t}
            return new_state, loss_val, outs

        if self.mesh is None:
            return jax.jit(step, donate_argnums=(0,))
        rep = NamedSharding(self.mesh, PartitionSpec())
        return jax.jit(step, donate_argnums=(0,),
                       out_shardings=(self._state_sharding, rep, None))

    def step(self, inputs, labels):
        """Dispatch one donated train step WITHOUT syncing.  The loss
        lands in the ring; returns the (device-resident) model outputs
        for metric computation."""
        scope = self.timers.scope
        opt = self.model._optimizer
        with scope("dispatch/lr"):
            lr = opt.get_lr()
            if lr != self._lr_host:
                # host-side LRScheduler advanced: refresh the device
                # scalar (an async host→device upload, not a sync)
                self._lr_host = lr
                new_lr = jnp.asarray(lr, jnp.float32)
                if self._state_sharding is not None:
                    new_lr = jax.device_put(new_lr,
                                            self._state_sharding["lr"])
                self.state["lr"] = new_lr
        with scope("dispatch/rng"):
            rng = _random.split_key()
        if self.mesh is not None:
            # the DataLoader prefetch thread normally pre-shards batches
            # (io.DataLoader.placement); this is the idempotent fallback
            # for direct engine callers and odd-sized tail batches
            # (device_put onto the sharding an array already has is free)
            with scope("dispatch/shard"):
                inputs = shard_batch(inputs, self.mesh,
                                     axis=self.batch_axes)
                labels = shard_batch(labels, self.mesh,
                                     axis=self.batch_axes)
            from ..distributed.mesh import mesh_guard

            # ambient mesh during trace/dispatch so in-model
            # shard_constraint / eager collectives resolve axis names
            with scope("dispatch/call"), mesh_guard(self.mesh):
                self.state, loss_val, outs = self._step_fn(
                    self.state, rng, inputs, labels)
        else:
            with scope("dispatch/call"):
                self.state, loss_val, outs = self._step_fn(
                    self.state, rng, inputs, labels)
        self.ring.append(loss_val)
        self._host_step += 1
        opt._step_count = self._host_step  # host mirror of state["step"]
        return outs

    def lower_step(self, inputs, labels):
        """Lower (but do not execute) the jitted step for the engine's
        current state — XLA cost-analysis / HLO introspection without
        consuming a donation.  `lowered.compile().cost_analysis()` gives
        PER-DEVICE numbers for SPMD modules, which is what the dp
        scaling tests and bench assert on."""
        rng = jax.random.PRNGKey(0)
        if self.mesh is not None:
            inputs = shard_batch(inputs, self.mesh, axis=self.batch_axes)
            labels = shard_batch(labels, self.mesh, axis=self.batch_axes)
            from ..distributed.mesh import mesh_guard

            # same ambient scope as step(): in-model shard_constraint /
            # axis-name resolution must see the mesh the step will
            # actually run under, or the lowered program (and its cost
            # analysis) describes a different computation
            with mesh_guard(self.mesh):
                return self._step_fn.lower(self.state, rng, inputs, labels)
        return self._step_fn.lower(self.state, rng, inputs, labels)

    def step_cost_analysis(self, inputs, labels):
        """XLA cost analysis of the compiled train step ({'flops': ...,
        per-DEVICE for SPMD modules}) — the number the MFU gauge divides
        by wall time.  Cached against the live jitted step, so repeated
        fits of the same model pay the AOT lower+compile once (and even
        that hits the persistent compilation cache — same HLO the jit
        path just built).  Returns {} when the backend reports
        nothing."""
        self._example_batch = (inputs, labels)
        if self._cost_cache is not None \
                and self._cost_cache_fn is self._step_fn:
            return dict(self._cost_cache)
        # the step lowered and compiled a second time: start-up's row
        # `cost_analysis`
        boot = startup()
        with boot.executable("cost_analysis"):
            with boot.scope("cost_analysis/lower"):
                lowered = self.lower_step(inputs, labels)
            with boot.scope("cost_analysis/compile"):
                compiled = lowered.compile()
        ca = compiled.cost_analysis()
        ca = ca[0] if isinstance(ca, (list, tuple)) else (ca or {})
        self._cost_cache = dict(ca) if ca else {}
        self._cost_cache_fn = self._step_fn
        self._compiled_cache = compiled
        return dict(self._cost_cache)

    def op_report(self, inputs=None, labels=None, *,
                  measured_step_ms=None, trace_dir=None):
        """Per-op attribution of the compiled train step
        (monitor/perf.py): analytic flops/bytes per entry HLO
        instruction joined with measured times from a bounded profiler
        capture (``trace_dir``), or — absent a capture — the measured
        step wall (``measured_step_ms``, defaulting to the telemetry
        reservoir's p50) attributed by roofline share.  Reuses the
        AOT-compiled executable step_cost_analysis() built; never
        consumes a donation.  With no arguments, lowers against the
        last batch step_cost_analysis() saw."""
        if inputs is None:
            if self._example_batch is None:
                raise ValueError(
                    "op_report() without a batch needs a prior "
                    "step_cost_analysis()/op_report(inputs, labels)")
            inputs, labels = self._example_batch
        ca = self.step_cost_analysis(inputs, labels)
        compiled = self._compiled_cache
        if compiled is None or self._cost_cache_fn is not self._step_fn:
            compiled = self.lower_step(inputs, labels).compile()
            self._compiled_cache = compiled
        if measured_step_ms is None:
            from ..utils.metrics import default_registry

            q = default_registry().reservoir(
                "paddle_train_step_ms").quantile(0.5)
            measured_step_ms = q if q > 0 else None
        from ..monitor import perf as _perf

        return _perf.build_report(compiled, name="train",
                                  cost_analysis=ca,
                                  measured_step_ms=measured_step_ms,
                                  trace_dir=trace_dir)

    def drain(self):
        """Batched fetch of every pending loss (the sanctioned sync)."""
        return self.ring.drain()

    # -- state egress ------------------------------------------------------
    def write_back(self, copy=True, sync_opt=True):
        """Re-bind the device-resident state into the Layer tree (and the
        optimizer's opt-state slot).  With copy=True (mid-run: an epoch
        boundary that has a reader, a user callback's batch) the Layer
        tree receives device-side COPIES, made by one dispatch over the
        whole tree (`_copy_tree`), so the engine can keep donating its
        own buffers; copy=False hands over the buffers themselves (fit
        exit — no further donation).  `Model.fit` calls it at an epoch's
        end only when something will read the tree there; between such
        boundaries the tree holds the values of the last sync.

        User writes since the last sync (e.g. a weight-clip after the
        LAST batch of an epoch) are folded into the state first, so a
        boundary write-back can never clobber them.

        sync_opt=False skips the opt-state copy/rebind (the dominant
        bytes for Adam-family slots): the per-batch write-back of the
        custom-callback path and an epoch's end whose only reader is an
        evaluate use it, since those observe params/buffers —
        `model._opt_state` stays at its last full sync until the next
        one, and fault-tolerance checkpoints read the live engine state
        directly.

        Mesh mode always DE-SHARDS: the Layer tree receives single-
        device arrays (one replica pulled off the mesh — a gather for
        mp-split params), so evaluate/train_batch/save and user
        callbacks after or between sharded epochs never see a
        multi-device committed array.  device_put onto the mesh's first
        device ALIASES the replica already living there (no copy) — and
        the engine donates that buffer on the next dispatch — so the
        de-sharded tree is always copied, even with copy=False."""
        st = self.state
        if st is None:
            return
        self.refresh_from_layers()
        tree = {"trainable": st["trainable"], "buffers": st["buffers"]}
        if sync_opt:
            tree["opt"] = st["opt"]
        if self.mesh is not None:
            tree = _copy_tree(
                jax.device_put(tree, self.mesh.devices.flat[0]))
        elif copy:
            tree = _copy_tree(tree)
        for k, v in tree["trainable"].items():
            self._param_refs[k]._value = v
        for k, v in tree["buffers"].items():
            self._buffer_refs[k]._value = v
        m = self.model
        if sync_opt:
            m._opt_state = tree["opt"]
        m._optimizer._step_count = self._host_step
        self._record_synced_ids()

    def ft_state(self, it_count):
        """Checkpointable snapshot of the device-resident state,
        MATERIALIZED (copied) to host numpy.  The copy matters twice
        over: the AsyncCheckpointer writes it to disk on a background
        thread, and the engine donates these exact buffers on the next
        dispatch — handing the writer live device arrays would race the
        donation."""
        from ..distributed.resilience import materialize

        st = self.state
        return {"params": materialize(st["trainable"]),
                "buffers": materialize(st["buffers"]),
                "opt": materialize(st["opt"]),
                "meta": {"it": np.array(it_count, np.int32),
                         "opt_steps": np.array(self._host_step,
                                               np.int32)}}

    def ft_restore_shardings(self, template):
        """NamedSharding pytree mirroring an `ft_state`-shaped template,
        built from THIS engine's resolved state shardings — the elastic
        hook: a checkpoint saved at any dp degree device_puts straight
        onto the CURRENT mesh's placements (params keep their rule/
        dist_spec specs, everything else replicates).  None on a
        single-device engine."""
        if self._state_sharding is None:
            return None
        sh = self._state_sharding
        rep = NamedSharding(self.mesh, PartitionSpec())

        def expand(node, s):
            # mirror the template's nesting; `s` may be a single
            # sharding standing for a whole subtree (wrapper-optimizer
            # slots) — broadcast it down
            if isinstance(node, dict):
                return {k: expand(v, s[k] if isinstance(s, dict)
                                  and k in s else
                                  (s if not isinstance(s, dict) else rep))
                        for k, v in node.items()}
            if isinstance(node, (list, tuple)):
                items = [expand(v, s[i] if isinstance(s, (list, tuple))
                                else s) for i, v in enumerate(node)]
                return tuple(items) if isinstance(node, tuple) else items
            return s if not isinstance(s, (dict, list, tuple)) else rep

        return {
            "params": expand(template["params"],
                             {**sh["trainable"], **sh["frozen"]}),
            "buffers": expand(template["buffers"], sh["buffers"]),
            "opt": expand(template["opt"], sh["opt"]),
            "meta": expand(template["meta"], rep),
        }

    def adopt_ft_state(self, snap):
        """Install a restored checkpoint snapshot into the live
        device-resident state (the elastic-resume landing): leaves are
        already device_put onto this engine's shardings by the restore
        (ft_restore_shardings), so the cached jitted step — whose
        out_shardings are pinned to the in shardings — keeps hitting
        without a retrace, and donation consumes the new buffers exactly
        like the ones begin() created.  Reconciles the step counter both
        on device (state['step']) and on host (_host_step /
        optimizer._step_count); call write_back afterwards to sync the
        Layer tree."""
        st = self.state
        for k, v in snap["params"].items():
            tgt = "trainable" if k in st["trainable"] else "frozen"
            st[tgt][k] = v
        for k, v in snap["buffers"].items():
            st["buffers"][k] = v
        st["opt"] = snap["opt"]
        opt_steps = int(snap["meta"]["opt_steps"])
        step_dev = jnp.asarray(opt_steps, jnp.int32)
        if self._state_sharding is not None:
            step_dev = jax.device_put(step_dev,
                                      self._state_sharding["step"])
        st["step"] = step_dev
        self._host_step = opt_steps
        self.model._optimizer._step_count = opt_steps

    def finish(self):
        """Final write-back at fit() exit; deactivates the engine (the
        next fit re-snapshots from the Layer tree).

        If a dispatch failed AFTER donating the state (XLA runtime
        error, OOM), the engine holds deleted buffers — rebinding those
        would clobber the valid arrays the Layer tree still has, so a
        poisoned state is dropped instead.  The tree then keeps its last
        sync: the start of the fit, or the last epoch's end that had a
        reader (an evaluate, a checkpoint, a user callback), which may
        be older than the last epoch.  A run that must not lose an epoch
        to a failed dispatch checkpoints the live state with
        `fit(fault_tolerant=True)` / `resume=`."""
        if self.state is None:
            return
        if not _tree_deleted(self.state):
            self.write_back(copy=False)
        self.state = None
