"""Traffic kind `fit`: `paddle.Model.fit` fed by a `DataLoader`.

One `fit` call does everything.  Its first epoch compiles the step and is
the checked one: the losses of its first steps, the first gradient (from
Adam's first moment after one step) and the parameters' change go to the
comparison with the plain reference.  The window is made of whole epochs
of `steps_per_epoch` steps: `fit` fetches its losses at every epoch's end
(and at `log_freq` steps), so the device is drained there by `fit` itself
and the benchmark adds no fetch of its own.  The window opens at the end
of the last warm epoch and closes at the first epoch end `--seconds` or
more later; the rate is all its tokens over all its seconds.
"""
from __future__ import annotations

import math
import statistics
import time

import numpy as np

from benchmarks import common, traffic as gen


def drive(run):
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.io import DataLoader, Dataset
    from paddle_tpu.ops import fused

    spec, cfg = run.traffic, run.config["model"]
    ref = common.plugin("reference", run.config["reference"])
    adapter = common.plugin("adapters", run.config["adapter"])
    B, S, k = spec["batch"], spec["seq"], spec["steps_per_epoch"]
    n_check = spec["checked_steps"]
    vocab = cfg["vocab_size"]
    seconds = min(run.seconds, spec["trace_seconds"]) if run.trace \
        else run.seconds

    make = jax.jit(lambda key: ref.init_weights(key, cfg, jnp.float32))
    if run.control:
        return control_only(run, ref, make)
    weights = make(ref.key_from_seed(run.seed))
    net = adapter.build_network(cfg, weights, "float32")
    model = adapter.build_trainer(net, spec["optimizer"])
    initial = {adapter.program_name(n): v for n, v in weights.items()}
    del weights
    run.phases.mark("weights_and_model")

    class Rows(Dataset):
        epoch = 0

        def __len__(self):
            return k * B

        def __getitem__(self, i):
            row = gen.train_rows(run.seed, self.epoch * k * B + i, 1, S,
                                 vocab)[0]
            return row[:-1], row[1:]

    rows = Rows()
    norms = jax.jit(lambda tree: {n: jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) for n, x in tree.items()})
    delta = jax.jit(lambda a, b: {n: jnp.sqrt(jnp.sum(jnp.square(
        a[n].astype(jnp.float32) - b[n].astype(jnp.float32)))) for n in b})
    idx = {adapter.program_name(n): i
           for n, i in ref.sample_indices(cfg).items()}
    sample = jax.jit(lambda tree: {n: tree[n].reshape(-1)[i].astype(
        jnp.float32) for n, i in idx.items()})
    seen = dict(loss=[], logged=[], m1=None, m1_at=None, delta=None, t0=None,
                t1=None,
                epochs=0, compiles0=None, compiles1=None, timers0=None,
                timers1=None, ann=None)

    class Probe(paddle.callbacks.ProgBarLogger):
        """A logger, which is what `fit` takes it for: it fetches nothing.
        It reads `logs` and, in the checked epoch, device values that are
        not waited for until the epoch has ended."""

        def __init__(self):
            super().__init__(log_freq=k, verbose=0)

        def on_train_begin(self, logs=None):
            pass

        def on_epoch_begin(self, epoch, logs=None):
            rows.epoch = self.epoch = epoch

        def on_train_batch_end(self, step, logs=None):
            if step % k == 0:
                seen["logged"].append(float(logs["loss"]))
            if self.epoch or step >= n_check:
                return
            # a step that `fit` just fetched has its loss in `logs`;
            # otherwise it is still on the device
            pend = adapter.pending_loss(self.model)
            seen["loss"].append(pend if pend is not None
                                else float(logs["loss"]))
            trainable, opt = adapter.engine_state(self.model)
            if step == 0:
                seen["m1"] = norms(adapter.first_moment(opt))
                seen["m1_at"] = sample(adapter.first_moment(opt))
            if step == n_check - 1:
                seen["delta"] = delta(trainable, initial)
                initial.clear()     # the arrays go when the jit has run

        def on_epoch_end(self, epoch, logs=None):
            now = time.monotonic()
            timers = dict(self.model._last_fit_timers.totals)
            if epoch + 1 == spec["warm_epochs"]:
                jax.block_until_ready(adapter.engine_state(self.model)[0])
                run.phases.mark("compile_and_warm_epochs")
                if run.trace:
                    from benchmarks import trace

                    trace.start(run)
                seen["ann"] = jax.profiler.TraceAnnotation("bench.window")
                seen["ann"].__enter__()
                seen.update(t0=time.monotonic(), timers0=timers,
                            compiles0=run.meter.read()[0])
            elif seen["t0"] is not None and seen["t1"] is None:
                seen["epochs"] += 1
                if now - seen["t0"] >= seconds:
                    jax.block_until_ready(
                        adapter.engine_state(self.model)[0])
                    seen.update(t1=time.monotonic(), timers1=timers,
                                compiles1=run.meter.read()[0])
                    seen["ann"].__exit__(None, None, None)
                    self.model.stop_training = True

        def on_eval_end(self, logs=None):
            pass

    loader = DataLoader(rows, batch_size=B, shuffle=False, drop_last=True)
    with paddle.amp.auto_cast(dtype=spec["autocast"]):
        model.fit(loader, epochs=10 ** 6, log_freq=k, verbose=0,
                  callbacks=[Probe()])
    if run.trace:
        from benchmarks import trace

        trace.stop(run)
    run.setup_s = seen["t0"] - common.T_PROCESS
    run.window = (seen["t0"], seen["t1"])
    steps = seen["epochs"] * k
    run.counts = {"train_tokens": steps * B * S, "train_steps": steps}
    run.attempted, run.failed = steps, 0
    t0, t1 = seen["timers0"], seen["timers1"]
    run.steptimers = {n: t1.get(n, 0.0) - t0.get(n, 0.0) for n in t1}
    fallbacks = sum(fused.fallback_counter().values.values())
    common.note(window_s=seen["t1"] - seen["t0"], steps=steps,
                tokens=steps * B * S, setup_s=run.setup_s,
                logged_losses=seen["logged"], setup_phases=run.phases.rows,
                steptimers=run.steptimers, fallbacks=fallbacks)
    run.device = common.device_info(jax, run.cell["chips"])

    # -- correctness, outside the window and after the program's state
    # is freed, so that the peak above stays the program's
    got_loss = [float(x) for x in seen["loss"]]
    b1 = spec["optimizer"]["beta1"]
    got_g = {n: float(v) / (1 - b1) for n, v in seen["m1"].items()}
    got_d = {n: float(v) for n, v in seen["delta"].items()}
    got_at = {n: np.asarray(v) / (1 - b1) for n, v in seen["m1_at"].items()}
    adapter.free_trainer(model)
    del model, net, initial
    compare(run, ref, make, got_loss, got_g, got_d, got_at,
            adapter.program_name, seen["logged"])
    run.checks.add("executables_built_in_window",
                   seen["compiles1"] - seen["compiles0"], 0)
    run.checks.add("pallas_fallbacks", fallbacks, 0)


def control_only(run, ref, make):
    """The control: the reference in the precision below the
    configuration's, put in the program's place (no window, no metrics).
    `correct` has to come out false."""
    import jax
    import jax.numpy as jnp

    spec, cfg = run.traffic, run.config["model"]
    B, S, n = spec["batch"], spec["seq"], spec["checked_steps"]
    batches = [jnp.asarray(gen.train_rows(run.seed, i * B, B, S,
                                          cfg["vocab_size"]))
               for i in range(n)]
    loss, g, d, at = ref.train_steps(
        make(ref.key_from_seed(run.seed)), batches, cfg, spec["optimizer"],
        run.control)
    run.device = common.device_info(jax, run.cell["chips"])
    run.setup_s, run.window = time.monotonic() - common.T_PROCESS, None
    common.note(control=run.control)
    compare(run, ref, make, loss, g, d, at, lambda name: name,
            [loss[0], 0.0])


def compare(run, ref, make, got_loss, got_g, got_d, got_at, name_of, logged):
    """The program's (or the control's) first steps against the plain
    float32 reference's."""
    import jax.numpy as jnp

    spec, cfg = run.traffic, run.config["model"]
    B, S, n_check = spec["batch"], spec["seq"], spec["checked_steps"]
    vocab = cfg["vocab_size"]
    w0 = make(ref.key_from_seed(run.seed))
    batches = [jnp.asarray(gen.train_rows(run.seed, i * B, B, S, vocab))
               for i in range(n_check)]
    t_ref = time.monotonic()
    want_loss, want_g, want_d, want_at = ref.train_steps(
        w0, batches, cfg, spec["optimizer"], "f32")
    common.note(reference_seconds=time.monotonic() - t_ref)
    lim = spec["limits"]
    checks = run.checks
    for i, (a, b) in enumerate(zip(got_loss, want_loss)):
        checks.add(f"loss_gap_step{i + 1}", abs(a - b), lim["loss_gap"],
                   note=f"program {a:.4f}, reference {b:.4f}")
    checks.add("first_loss_from_ln_vocab",
               abs(got_loss[0] - math.log(vocab)), lim["first_loss_from_ln"])
    checks.add("loss_fell", logged[-1] - logged[0], 0.0,
               ok=all(map(math.isfinite, logged)) and logged[-1] < logged[0],
               note="last logged loss below the first, all finite")
    checks.add("grad_norm_gap_worst_leaf",
               worst_leaf_gap(got_g, want_g, name_of), lim["grad_norm_gap"])
    checks.add("grad_difference_worst_leaf",
               worst_leaf_difference(got_at, want_at, name_of),
               lim["grad_difference"])
    checks.add("param_change_norm_gap_worst_leaf",
               worst_leaf_gap(got_d, want_d, name_of), lim["delta_norm_gap"])


def worst_leaf(errors, scales, what):
    """max over leaves of error / max(the reference's scale of that leaf,
    the median leaf's scale): some gradients are all but zero."""
    floor = statistics.median(scales.values())
    worst, at = 0.0, None
    for name, err in errors.items():
        gap = err / max(scales[name], floor)
        if not gap <= worst:            # a NaN is the worst
            worst, at = gap, name
    common.note(worst_leaf=at, of=what, gap=worst)
    return worst


def worst_leaf_gap(got, want, name_of):
    """The gap between the program's norm and the reference's, leaf by
    leaf (not the norm of their difference)."""
    return worst_leaf({n: abs(got[name_of(n)] - w) for n, w in want.items()},
                      want, "norm gap")


def worst_leaf_difference(got, want, name_of):
    """The rms of program - reference over the reference's rms: the first
    gradient at `sample_indices`, which separates precisions where the
    gap between two norms does not (rounding noise hardly moves a norm)."""
    def rms(x):
        return float(np.sqrt(np.mean(np.square(x))))

    return worst_leaf({n: rms(got[name_of(n)] - w) for n, w in want.items()},
                      {n: rms(w) for n, w in want.items()}, "difference")
