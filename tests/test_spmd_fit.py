"""SPMD-sharded TrainEngine (hapi/engine.py mesh mode): Model.fit scales
to every chip on the mesh.

Pins the contracts the mesh-aware engine introduces on the 8 virtual CPU
devices the conftest forces:

  * dp scaling shape — ONE global jitted step; per-device compiled work
    constant as dp grows (XLA cost analysis), grad sync present as a dp
    all-reduce in the partitioned module (engine path, complementing
    test_dp_scaling.py's hand-rolled step);
  * numerics — a dp=1 mesh is BITWISE the unsharded engine; dp=8 agrees
    with dp=1 to float32 ULP (XLA reassociates batch reductions into
    partial sums + all-reduce, so cross-dp-degree equality is exact to
    the ULP, not bit-for-bit — the probe that pinned this is described
    in hapi/engine.py's module docstring);
  * donation under sharding — with NamedShardings attached the donated
    state is actually consumed (no silent donation fallback);
  * amp.auto_cast(bf16) composes with the partitioned step;
  * preemption-resume round-trips BITWISE at a fixed dp degree;
  * the data path (transfer.shard_batch + DataLoader.placement)
    pre-shards batches on the prefetch thread;
  * legacy DataParallel routes through the ambient mesh (deprecation).

Run standalone via tools/dp_smoke.sh.
"""
import warnings

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import paddle_tpu as paddle
from paddle_tpu import amp
from paddle_tpu.distributed.mesh import (build_mesh, get_mesh, mesh_guard,
                                         parse_mesh_shape)
from paddle_tpu.framework.transfer import shard_batch
from paddle_tpu.hapi import Model
from paddle_tpu.hapi.engine import TrainEngine, resolve_mesh
from paddle_tpu.io import DataLoader, TensorDataset

pytestmark = pytest.mark.dp

needs8 = pytest.mark.skipif(jax.device_count() < 8,
                            reason="needs the 8-virtual-device conftest mesh")


def _model_and_data(n=24, lr=0.01):
    paddle.seed(0)
    net = paddle.nn.Sequential(paddle.nn.Linear(4, 8), paddle.nn.ReLU(),
                               paddle.nn.Linear(8, 2))
    rs = np.random.RandomState(0)
    x = rs.randn(n, 4).astype("float32")
    y = (x.sum(1) > 0).astype("int64")
    ds = TensorDataset([x, y])
    model = Model(net)
    model.prepare(
        paddle.optimizer.Adam(learning_rate=lr,
                              parameters=net.parameters()),
        paddle.nn.CrossEntropyLoss())
    return model, ds


def _weights(model):
    return {k: np.asarray(p._value)
            for k, p in model.network.named_parameters()}


def _fit(mesh=None, epochs=2, **kw):
    model, ds = _model_and_data()
    hist = model.fit(ds, batch_size=8, epochs=epochs, shuffle=False,
                     verbose=0, log_freq=1, mesh=mesh, **kw)
    return model, hist


# -- parity ----------------------------------------------------------------
@needs8
class TestDpParity:
    def test_dp1_mesh_bitwise_matches_unsharded_engine(self):
        """The degenerate single-device mesh runs the partitioned
        pipeline but must not change a single bit vs the PR-2 engine."""
        m0, h0 = _fit(mesh=None)
        m1, h1 = _fit(mesh={"dp": 1})
        np.testing.assert_array_equal(h0["loss"], h1["loss"])
        w0, w1 = _weights(m0), _weights(m1)
        for k in w0:
            np.testing.assert_array_equal(w0[k], w1[k], err_msg=k)

    @staticmethod
    def _per_step_losses(dp, steps=6, B=16):
        """Drive the engine directly: SAME global batch at both dp
        degrees, per-STEP losses off the ring."""
        paddle.seed(0)
        model, _ = _model_and_data()
        rs = np.random.RandomState(7)
        x = rs.randn(steps * B, 4).astype("float32")
        y = (x.sum(1) > 0).astype("int64")
        eng = TrainEngine(model).begin(mesh={"dp": dp})
        model.network.train()
        for i in range(steps):
            lo, hi = i * B, (i + 1) * B
            eng.step([paddle.to_tensor(x[lo:hi])],
                     [paddle.to_tensor(y[lo:hi])])
        losses = eng.drain()
        eng.finish()
        return losses, _weights(model)

    def test_dp8_per_step_losses_match_dp1_to_ulp(self):
        """Same global batch split over 8 devices: per-step losses agree
        with dp=1 to float32 ULP (the all-reduce reassociates the batch
        reductions; anything past ~1e-6 relative would mean a REAL
        divergence — wrong loss scaling, double-averaged grads...)."""
        la, wa = self._per_step_losses(1)
        lb, wb = self._per_step_losses(8)
        assert len(la) == len(lb) == 6
        np.testing.assert_allclose(la, lb, rtol=2e-6, atol=1e-7)
        for k in wa:
            np.testing.assert_allclose(wa[k], wb[k], rtol=1e-5,
                                       atol=1e-7, err_msg=k)

    def test_dp8_fit_loop_matches_dp1(self):
        """The same parity through the full fit() loop (loader
        placement, epoch means)."""
        ma, ha = _fit(mesh={"dp": 1})
        mb, hb = _fit(mesh={"dp": 8})
        np.testing.assert_allclose(ha["loss"], hb["loss"],
                                   rtol=2e-6, atol=1e-7)
        wa, wb = _weights(ma), _weights(mb)
        for k in wa:
            np.testing.assert_allclose(wa[k], wb[k], rtol=1e-5,
                                       atol=1e-7, err_msg=k)

    def test_global_batch_semantics(self):
        """batch_size is the GLOBAL batch: each device sees B/dp
        samples — the engine's input sharding splits dim 0 over dp."""
        model, ds = _model_and_data()
        eng = TrainEngine(model).begin(mesh={"dp": 8})
        rs = np.random.RandomState(0)
        x = paddle.to_tensor(rs.randn(16, 4).astype("float32"))
        y = paddle.to_tensor(rs.randint(0, 2, (16,)).astype("int64"))
        sx = shard_batch([x], eng.mesh)[0]
        assert sx._value.sharding.spec == P("dp")
        shard_shapes = {s.data.shape
                        for s in sx._value.addressable_shards}
        assert shard_shapes == {(2, 4)}
        eng.finish()


# -- scaling shape ---------------------------------------------------------
@needs8
class TestDpScalingShape:
    def _compiled(self, dp):
        model, ds = _model_and_data()
        eng = TrainEngine(model).begin(mesh={"dp": dp})
        rs = np.random.RandomState(0)
        B = 2 * dp
        x = paddle.to_tensor(rs.randn(B, 4).astype("float32"))
        y = paddle.to_tensor(rs.randint(0, 2, (B,)).astype("int64"))
        compiled = eng.lower_step([x], [y]).compile()
        eng.finish()
        return compiled

    @staticmethod
    def _flops(compiled):
        ca = compiled.cost_analysis()
        ca = ca[0] if isinstance(ca, (list, tuple)) else ca
        return float(ca.get("flops", 0.0))

    def test_constant_per_device_work_and_dp_all_reduce(self):
        """With per-device batch held constant the ENGINE's compiled
        step does constant per-device flops dp=1 -> dp=8 (XLA reports
        per-device numbers for SPMD modules) — the throughput model
        behind linear scaling.  The dp grad sync must exist as an
        all-reduce in the dp=8 module and must not exist at dp=1."""
        c1, c8 = self._compiled(1), self._compiled(8)
        f1, f8 = self._flops(c1), self._flops(c8)
        assert f1 > 0 and f8 > 0
        assert f8 / f1 < 1.15, (f1, f8)
        assert "all-reduce" in c8.as_text()
        assert "all-reduce" not in c1.as_text()


# -- donation --------------------------------------------------------------
@needs8
class TestDonationUnderSharding:
    def test_no_silent_donation_fallback(self):
        """With NamedShardings attached (in inferred from the committed
        state, out PINNED by the engine) XLA must still alias every
        state buffer: zero donation-fallback warnings, and every leaf of
        the pre-step state is consumed (deleted) by the dispatch."""
        model, ds = _model_and_data()
        eng = TrainEngine(model).begin(mesh={"dp": 8})
        refs = [v for tree in (eng.state["trainable"], eng.state["opt"],
                               eng.state["buffers"])
                for v in jax.tree_util.tree_leaves(tree)]
        rs = np.random.RandomState(0)
        x = paddle.to_tensor(rs.randn(16, 4).astype("float32"))
        y = paddle.to_tensor(rs.randint(0, 2, (16,)).astype("int64"))
        with warnings.catch_warnings():
            warnings.filterwarnings("error", message=".*donated buffers.*")
            eng.step([x], [y])
        undonated = [v for v in refs if not v.is_deleted()]
        assert not undonated, f"{len(undonated)} state buffers survived " \
                              "the donated dispatch (silent fallback)"
        assert eng.drain()
        eng.finish()

    def test_sharded_state_stays_layout_stable(self):
        """Pinned out_shardings: a second fit at the same placement
        reuses the cached jit (key = resolved sharding tree, so an
        identical-but-fresh rule doesn't retrace), while an annotation
        added between fits rebuilds it (stale pinned out_shardings
        would silently force the old layout)."""
        model, ds = _model_and_data()
        model.fit(ds, batch_size=8, epochs=1, shuffle=False, verbose=0,
                  mesh={"dp": 8})
        eng = model._engine
        fn = eng._step_fn
        model.fit(ds, batch_size=8, epochs=1, shuffle=False, verbose=0,
                  mesh={"dp": 8}, sharding_rule=lambda n, p: None)
        assert eng._step_fn is fn  # same resolved shardings → cache hit
        model.fit(ds, batch_size=8, epochs=1, shuffle=False, verbose=0,
                  mesh={"dp": 8},
                  sharding_rule=lambda n, p: (P(None, "dp")
                                              if n == "0.weight" else None))
        assert eng._step_fn is not fn  # placement changed → rebuilt


# -- amp -------------------------------------------------------------------
@needs8
class TestAmpComposition:
    def test_auto_cast_bf16_inside_partitioned_step(self):
        """amp.auto_cast(bf16) at trace time must land INSIDE the
        partitioned computation (bf16 dots in the module) and train to
        finite losses on the dp=8 mesh."""
        model, ds = _model_and_data()
        with amp.auto_cast(enable=True, dtype="bfloat16"):
            hist = model.fit(ds, batch_size=8, epochs=1, shuffle=False,
                             verbose=0, log_freq=1, mesh={"dp": 8})
        assert hist["loss"] and np.all(np.isfinite(hist["loss"]))
        # dtype policy honored inside the compiled partitioned step
        eng = model._engine
        eng.begin(mesh={"dp": 8})
        rs = np.random.RandomState(0)
        x = paddle.to_tensor(rs.randn(16, 4).astype("float32"))
        y = paddle.to_tensor(rs.randint(0, 2, (16,)).astype("int64"))
        with amp.auto_cast(enable=True, dtype="bfloat16"):
            txt = eng.lower_step([x], [y]).as_text()
        eng.finish()
        assert "bf16" in txt

    def test_bf16_losses_track_fp32(self):
        ma, _ = _model_and_data()
        ha = ma.fit(_model_and_data()[1], batch_size=8, epochs=1,
                    shuffle=False, verbose=0, log_freq=1, mesh={"dp": 8})
        mb, _ = _model_and_data()
        with amp.auto_cast(enable=True, dtype="bfloat16"):
            hb = mb.fit(_model_and_data()[1], batch_size=8, epochs=1,
                        shuffle=False, verbose=0, log_freq=1,
                        mesh={"dp": 8})
        np.testing.assert_allclose(ha["loss"], hb["loss"], rtol=0.1)


# -- fault tolerance -------------------------------------------------------
@needs8
class TestShardedResume:
    def test_resume_bitwise_at_fixed_dp(self, tmp_path):
        """Checkpoint mid-fit on the dp=8 mesh (materialize gathers the
        sharded state to host), restore re-shards — bitwise vs the
        uninterrupted dp=8 run.  Same-dp resume has no reassociation
        anywhere, so this is exact."""
        ma, ds = _model_and_data(n=32)
        ma.fit(ds, batch_size=8, epochs=4, shuffle=False, verbose=0,
               mesh={"dp": 8})
        ref = _weights(ma)

        mb, ds = _model_and_data(n=32)
        mb.fit(ds, batch_size=8, epochs=2, shuffle=False, verbose=0,
               mesh={"dp": 8}, resume=str(tmp_path), checkpoint_interval=3)
        mc, ds = _model_and_data(n=32)
        mc.fit(ds, batch_size=8, epochs=4, shuffle=False, verbose=0,
               mesh={"dp": 8}, resume=str(tmp_path), checkpoint_interval=3)
        got = _weights(mc)
        assert set(got) == set(ref)
        for k in ref:
            np.testing.assert_array_equal(got[k], ref[k], err_msg=k)

    @pytest.mark.chaos
    def test_sigterm_preempt_resume_bitwise_under_sharding(self, tmp_path):
        """SIGTERM mid-fit on the mesh: emergency checkpoint from the
        sharded donated state, restart resumes to the same bits as a
        never-preempted dp=8 run."""
        from paddle_tpu.distributed.resilience import PREEMPTED_EXIT_CODE
        from paddle_tpu.utils import chaos

        ma, ds = _model_and_data(n=32)
        ma.fit(ds, batch_size=8, epochs=3, shuffle=False, verbose=0,
               mesh={"dp": 8})
        ref = _weights(ma)

        mb, ds = _model_and_data(n=32)
        with chaos.inject(preempt_at_step=5):
            with pytest.raises(SystemExit) as ei:
                mb.fit(ds, batch_size=8, epochs=3, shuffle=False,
                       verbose=0, mesh={"dp": 8}, fault_tolerant=True,
                       resume=str(tmp_path))
        assert ei.value.code == PREEMPTED_EXIT_CODE
        chaos.reset()
        mc, ds = _model_and_data(n=32)
        mc.fit(ds, batch_size=8, epochs=3, shuffle=False, verbose=0,
               mesh={"dp": 8}, resume=str(tmp_path))
        got = _weights(mc)
        for k in ref:
            np.testing.assert_array_equal(got[k], ref[k], err_msg=k)


# -- data path -------------------------------------------------------------
@needs8
class TestShardedDataPath:
    def test_shard_batch_splits_and_replicates(self):
        mesh = build_mesh({"dp": 8})
        rs = np.random.RandomState(0)
        batch = [paddle.to_tensor(rs.randn(16, 4).astype("float32")),
                 rs.randint(0, 2, (16,)).astype("int64"),
                 np.float32(3.0),          # scalar → replicated
                 rs.randn(13, 4).astype("float32")]  # 13 % 8 → replicated
        out = shard_batch(batch, mesh)
        assert out[0]._value.sharding.spec == P("dp")  # Tensor re-wrapped
        assert out[1].sharding.spec == P("dp")
        assert out[2].sharding.spec == P()
        assert out[3].sharding.spec == P()
        np.testing.assert_array_equal(np.asarray(out[0]._value),
                                      np.asarray(batch[0]._value))
        # idempotent: re-placing is a no-op, not a copy storm
        again = shard_batch(out, mesh)
        assert again[1] is out[1]

    def test_dataloader_placement_runs_on_prefetch_thread(self):
        """fit(mesh=) installs DataLoader.placement; batches arrive at
        the loop already dp-sharded, placed by the prefetch thread."""
        import threading

        mesh = build_mesh({"dp": 8})
        rs = np.random.RandomState(0)
        ds = TensorDataset([rs.randn(16, 4).astype("float32")])
        loader = DataLoader(ds, batch_size=8, shuffle=False)
        seen_threads = []
        main = threading.get_ident()

        def placement(batch):
            seen_threads.append(threading.get_ident())
            return shard_batch(batch, mesh)

        loader.placement = placement
        batches = list(loader)
        assert len(batches) == 2
        for b in batches:
            assert b[0]._value.sharding.spec == P("dp")
        assert seen_threads and all(t != main for t in seen_threads)

    def test_fit_restores_placement_hook(self):
        model, ds = _model_and_data()
        loader = DataLoader(ds, batch_size=8, shuffle=False)
        model.fit(loader, epochs=1, verbose=0, mesh={"dp": 8})
        assert loader.placement is None


# -- mesh resolution -------------------------------------------------------
class TestMeshResolution:
    def test_parse_mesh_shape(self):
        assert parse_mesh_shape("") is None
        assert parse_mesh_shape(None) is None
        assert parse_mesh_shape("dp=8") == {"dp": 8}
        assert parse_mesh_shape("dp:2,mp:4") == {"dp": 2, "mp": 4}
        assert parse_mesh_shape("dp") == {"dp": -1}
        assert parse_mesh_shape({"dp": 2}) == {"dp": 2}
        with pytest.raises(ValueError, match="dp=x8"):
            parse_mesh_shape("dp=x8")  # names the bad token
        with pytest.raises(ValueError, match="positive"):
            parse_mesh_shape("dp=0")

    @needs8
    def test_mesh_without_dp_axis_warns(self):
        """A typo'd axis name ('data=8') replicates the whole step on
        every device — that must warn, not silently burn 8× the
        chips."""
        model, ds = _model_and_data()
        with pytest.warns(UserWarning, match="no 'dp' axis"):
            model.fit(ds, batch_size=8, epochs=1, shuffle=False,
                      verbose=0, mesh={"data": 8})

    @needs8
    def test_ambient_mesh_guard_is_picked_up(self):
        mesh = build_mesh({"dp": 8})
        with mesh_guard(mesh):
            model, hist = _fit(epochs=1)  # no mesh= argument
        assert model._engine.mesh is mesh
        assert np.all(np.isfinite(hist["loss"]))

    @needs8
    def test_flags_mesh_shape_is_picked_up(self):
        from paddle_tpu.framework import flags as F

        old = F.flag("FLAGS_mesh_shape")
        try:
            paddle.set_flags({"FLAGS_mesh_shape": "dp=8"})
            model, hist = _fit(epochs=1)
            assert model._engine.mesh is not None
            assert model._engine.mesh.shape["dp"] == 8
        finally:
            paddle.set_flags({"FLAGS_mesh_shape": old})

    @needs8
    def test_leftover_global_mesh_is_ignored(self):
        """set_mesh/ensure_mesh side effects (eager collectives set the
        global mesh) must NOT silently reshard a fit — only an ACTIVE
        mesh_guard scope counts as ambient."""
        from paddle_tpu.distributed.mesh import set_mesh

        prev = get_mesh()
        try:
            set_mesh(build_mesh({"dp": 8}))
            assert resolve_mesh(None) is None
            model, hist = _fit(epochs=1)
            assert model._engine.mesh is None
        finally:
            set_mesh(prev)

    @needs8
    def test_guard_scope_outranks_flag(self):
        """An EXPLICIT mesh_guard — even a deliberate 1-device one for
        debugging — must not be overridden by FLAGS_mesh_shape."""
        from paddle_tpu.framework import flags as F

        old = F.flag("FLAGS_mesh_shape")
        try:
            paddle.set_flags({"FLAGS_mesh_shape": "dp=8"})
            with mesh_guard(build_mesh({"dp": 1},
                                       devices=jax.devices()[:1])):
                assert resolve_mesh(None) is None
        finally:
            paddle.set_flags({"FLAGS_mesh_shape": old})

    def test_no_mesh_means_single_device_engine(self):
        # outside any mesh_guard scope resolution is None regardless of
        # leftover global-mesh state (see test_leftover_global_mesh_*)
        assert resolve_mesh(None) is None
        model, hist = _fit(epochs=1)
        assert model._engine.mesh is None

    @needs8
    def test_explicit_mesh_object(self):
        mesh = build_mesh({"dp": 4}, devices=jax.devices()[:4])
        model, hist = _fit(mesh=mesh, epochs=1)
        assert model._engine.mesh is mesh
        assert np.all(np.isfinite(hist["loss"]))


# -- per-param sharding rule (mp hook) -------------------------------------
@needs8
class TestShardingRule:
    def test_rule_shards_large_params_over_mp(self):
        """A per-param rule places a big layer over the mp axis; the
        step still runs and the param's state sharding honors the
        rule."""
        paddle.seed(0)
        net = paddle.nn.Sequential(paddle.nn.Linear(4, 16),
                                   paddle.nn.ReLU(),
                                   paddle.nn.Linear(16, 2))
        model = Model(net)
        model.prepare(
            paddle.optimizer.Adam(learning_rate=0.01,
                                  parameters=net.parameters()),
            paddle.nn.CrossEntropyLoss())

        def rule(name, param):
            if name == "0.weight":  # (4, 16): split the wide dim over mp
                return P(None, "mp")
            return None

        rs = np.random.RandomState(0)
        ds = TensorDataset([rs.randn(16, 4).astype("float32"),
                            rs.randint(0, 2, (16,)).astype("int64")])
        hist = model.fit(ds, batch_size=8, epochs=1, shuffle=False,
                         verbose=0, mesh={"dp": 2, "mp": 4},
                         sharding_rule=rule)
        assert np.all(np.isfinite(hist["loss"]))
        eng = model._engine
        eng.begin(mesh={"dp": 2, "mp": 4}, sharding_rule=rule)
        assert eng._state_sharding["trainable"]["0.weight"].spec \
            == P(None, "mp")
        # Adam moments inherit the param's placement (same shape)
        for slot, sh in eng._state_sharding["opt"]["0.weight"].items():
            if sh.spec == P(None, "mp"):
                break
        else:
            pytest.fail("no opt slot inherited the mp sharding")
        eng.finish()

    def test_rule_vs_replicated_losses_match(self):
        def rule(name, param):
            return P(None, "mp") if name == "0.weight" else None

        def run(rule_):
            paddle.seed(0)
            net = paddle.nn.Sequential(paddle.nn.Linear(4, 16),
                                       paddle.nn.ReLU(),
                                       paddle.nn.Linear(16, 2))
            model = Model(net)
            model.prepare(
                paddle.optimizer.Adam(learning_rate=0.01,
                                      parameters=net.parameters()),
                paddle.nn.CrossEntropyLoss())
            rs = np.random.RandomState(0)
            ds = TensorDataset([rs.randn(16, 4).astype("float32"),
                                rs.randint(0, 2, (16,)).astype("int64")])
            return model.fit(ds, batch_size=8, epochs=1, shuffle=False,
                             verbose=0, log_freq=1,
                             mesh={"dp": 2, "mp": 4}, sharding_rule=rule_)

        ha, hb = run(None), run(rule)
        np.testing.assert_allclose(ha["loss"], hb["loss"],
                                   rtol=2e-6, atol=1e-7)


# -- post-fit contracts ----------------------------------------------------
@needs8
class TestPostFitContracts:
    def test_layer_tree_is_single_device_after_sharded_fit(self):
        """write_back de-shards: the Layer tree never holds multi-device
        committed arrays, so evaluate/train_batch/save after a sharded
        fit stay mesh-free."""
        model, ds = _model_and_data()
        model.fit(ds, batch_size=8, epochs=1, shuffle=False, verbose=0,
                  mesh={"dp": 8})
        for k, p in model.network.named_parameters():
            assert len(p._value.sharding.device_set) == 1, k
        res = model.evaluate(ds, batch_size=8, verbose=0)
        assert np.isfinite(res["loss"])
        rs = np.random.RandomState(1)
        model.train_batch(
            [paddle.to_tensor(rs.randn(8, 4).astype("float32"))],
            [paddle.to_tensor(rs.randint(0, 2, (8,)).astype("int64"))])

    def test_epoch_end_callback_sees_valid_weights(self):
        from paddle_tpu.hapi.callbacks import Callback

        seen = []

        class Peek(Callback):
            def on_epoch_end(self, epoch, logs=None):
                seen.append({k: np.asarray(p._value) for k, p in
                             self.model.network.named_parameters()})

        model, ds = _model_and_data()
        model.fit(ds, batch_size=8, epochs=3, shuffle=False, verbose=0,
                  mesh={"dp": 8}, callbacks=[Peek()])
        assert len(seen) == 3
        assert any(not np.array_equal(seen[0][k], seen[2][k])
                   for k in seen[0])

    @pytest.mark.parametrize("dp", [1, 8])
    def test_no_reader_copies_nothing_and_ends_current(self, dp):
        """With nobody to read the Layer tree between epochs nothing is
        de-sharded there; when fit returns the tree is single-device
        and holds the last step's weights and slots."""
        plain, _ = _fit(mesh=None, epochs=3)
        model, _ = _fit(mesh={"dp": dp}, epochs=3)
        counts = model._last_fit_timers.counts
        assert counts.get("write_back/copy", 0) == 0
        assert counts["write_back"] == 4
        for k, p in model.network.named_parameters():
            assert len(p._value.sharding.device_set) == 1, k
            assert not p._value.is_deleted(), k
        for leaf in jax.tree_util.tree_leaves(model._opt_state):
            assert len(leaf.sharding.device_set) == 1
        want, got = _weights(plain), _weights(model)
        for k in want:
            if dp == 1:
                np.testing.assert_array_equal(got[k], want[k], err_msg=k)
            else:
                np.testing.assert_allclose(got[k], want[k], rtol=1e-5,
                                           atol=1e-7, err_msg=k)
        for a, b in zip(jax.tree_util.tree_leaves(model._opt_state),
                        jax.tree_util.tree_leaves(plain._opt_state)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-7)


# -- legacy DataParallel routing -------------------------------------------
@needs8
class TestDataParallelMeshRouting:
    def test_scale_loss_uses_ambient_mesh_dp_degree(self):
        import paddle_tpu.distributed.parallel as par

        dp = par.DataParallel(paddle.nn.Linear(2, 2))
        mesh = build_mesh({"dp": 4, "mp": 2})
        par._mesh_subsumed_warned = False
        try:
            with mesh_guard(mesh):
                with pytest.warns(DeprecationWarning,
                                  match="subsumes DataParallel"):
                    out = dp.scale_loss(paddle.to_tensor(8.0))
                # warn ONCE: the second call is silent
                with warnings.catch_warnings():
                    warnings.simplefilter("error")
                    out2 = dp.scale_loss(paddle.to_tensor(8.0))
            assert float(out.numpy()) == pytest.approx(2.0)   # / dp=4
            assert float(out2.numpy()) == pytest.approx(2.0)
        finally:
            par._mesh_subsumed_warned = False

    def test_scale_loss_without_mesh_uses_world_size(self):
        import paddle_tpu.distributed.parallel as par
        from paddle_tpu.distributed.mesh import set_mesh

        prev = get_mesh()
        try:
            set_mesh(None)  # pin: no global mesh from earlier tests
            dp = par.DataParallel(paddle.nn.Linear(2, 2))
            out = dp.scale_loss(paddle.to_tensor(8.0))  # world_size 1 → id
            assert float(out.numpy()) == pytest.approx(8.0)
        finally:
            set_mesh(prev)
