"""Language-model family tests: GPT, BERT, MoE, 3D-hybrid-parallel GPT."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.distributed.mesh import build_mesh, mesh_guard
from paddle_tpu.models import GPTConfig, GPTForCausalLM, BertConfig, \
    BertForPretraining
from paddle_tpu.models import gpt_hybrid
from paddle_tpu.nn.layer_base import functional_call, state_pytrees
from paddle_tpu.nn.layer.moe import MoELayer


def _tiny_gpt(**kw):
    base = dict(vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
                max_position_embeddings=32, dropout=0.0, attn_dropout=0.0)
    base.update(kw)
    return GPTConfig(**base)


class TestGPT:
    def test_recompute_loss_and_grad_parity(self):
        """GPTConfig.recompute wraps each block in jax.checkpoint; loss
        and EVERY per-parameter gradient must match the non-remat model —
        this is what lets a 1.3B model train on one chip."""
        import jax

        ids_np = np.random.RandomState(1).randint(0, 64, (2, 16))
        results = {}
        for remat in (False, True):
            paddle.seed(3)
            model = GPTForCausalLM(_tiny_gpt(recompute=remat))
            model.train()
            params, buffers = state_pytrees(model)

            def loss_fn(p):
                out, _ = functional_call(
                    model, p, (paddle.to_tensor(ids_np, "int64"),),
                    buffers=buffers, method="loss")
                return out.value if hasattr(out, "value") else out

            loss, grads = jax.value_and_grad(loss_fn)(params)
            results[remat] = (float(loss), grads)
        np.testing.assert_allclose(results[False][0], results[True][0],
                                   rtol=1e-5)
        g0, g1 = results[False][1], results[True][1]
        assert set(g0) == set(g1)
        for name in g0:  # per-leaf: permuted/compensating errors fail
            np.testing.assert_allclose(
                np.asarray(g0[name]), np.asarray(g1[name]),
                rtol=1e-4, atol=1e-6, err_msg=name)

    def test_forward_and_loss(self):
        paddle.seed(0)
        model = GPTForCausalLM(_tiny_gpt())
        ids = paddle.to_tensor(
            np.random.RandomState(0).randint(0, 64, (2, 16)), "int64")
        logits = model(ids)
        assert tuple(logits.shape) == (2, 16, 64)
        loss = model.loss(ids)
        assert np.isfinite(float(loss))

    def test_training_reduces_loss(self):
        paddle.seed(0)
        model = GPTForCausalLM(_tiny_gpt())
        model.train()
        params, buffers = state_pytrees(model)
        opt = paddle.optimizer.AdamW(learning_rate=1e-3)
        state = opt.init_pytree(params)
        ids = jnp.asarray(
            np.random.RandomState(1).randint(0, 64, (4, 16)), jnp.int32)

        @jax.jit
        def step(params, state, ids):
            def loss_fn(p):
                out, _ = functional_call(
                    model, p, (paddle.Tensor(ids),),
                    kwargs={"labels": paddle.Tensor(ids)}, buffers=buffers,
                    rng=jax.random.PRNGKey(0))
                return out[1].value

            loss, g = jax.value_and_grad(loss_fn)(params)
            p2, s2 = opt.apply_pytree(params, g, state, lr=1e-3, step=1)
            return p2, s2, loss

        losses = []
        for _ in range(8):
            params, state, loss = step(params, state, ids)
            losses.append(float(loss))
        assert losses[-1] < losses[0]

    def test_tensor_parallel_runs_on_mesh(self):
        mesh = build_mesh({"dp": 2, "mp": 4})
        with mesh_guard(mesh):
            paddle.seed(0)
            model = GPTForCausalLM(_tiny_gpt(tensor_parallel=True))
            model.eval()
            params, buffers = state_pytrees(model)
            ids = jnp.asarray(
                np.random.RandomState(0).randint(0, 64, (4, 16)), jnp.int32)

            def fwd(p, ids):
                out, _ = functional_call(model, p, (paddle.Tensor(ids),),
                                         buffers=buffers)
                return out.value

            lowered = jax.jit(fwd).lower(params, ids)
            hlo = lowered.compile().as_text()
            assert "all-reduce" in hlo or "all-gather" in hlo
            out = jax.jit(fwd)(params, ids)
            assert out.shape == (4, 16, 64)


class TestBert:
    def test_pretraining_loss(self):
        paddle.seed(0)
        cfg = BertConfig(vocab_size=100, hidden_size=32, num_layers=2,
                         num_heads=4, intermediate_size=64,
                         max_position_embeddings=32, dropout=0.0)
        model = BertForPretraining(cfg)
        model.eval()
        rs = np.random.RandomState(0)
        ids = paddle.to_tensor(rs.randint(0, 100, (2, 16)), "int64")
        mlm_labels = paddle.to_tensor(
            np.where(rs.rand(2, 16) < 0.15, rs.randint(0, 100, (2, 16)),
                     -100), "int64")
        nsp = paddle.to_tensor(rs.randint(0, 2, (2,)), "int64")
        loss = model.loss(ids, mlm_labels, nsp)
        assert np.isfinite(float(loss))

    def test_ernie_defaults(self):
        from paddle_tpu.models import ErnieModel

        m = ErnieModel(hidden_size=32, num_layers=1, num_heads=4,
                       intermediate_size=64, max_position_embeddings=16,
                       dropout=0.0)
        assert m.cfg.vocab_size == 18000 and m.cfg.type_vocab_size == 4


class TestMoE:
    def test_single_expert_equals_ffn(self):
        paddle.seed(0)
        moe = MoELayer(16, 32, num_experts=1, top_k=1, capacity_factor=8.0)
        moe.eval()
        x = paddle.to_tensor(
            np.random.RandomState(0).randn(2, 8, 16).astype("float32"))
        out = moe(x)
        # reference: the single expert's FFN applied to every token
        xv = x.numpy()
        w1 = np.asarray(moe.w1.value)[0]
        b1 = np.asarray(moe.b1.value)[0]
        w2 = np.asarray(moe.w2.value)[0]
        b2 = np.asarray(moe.b2.value)[0]
        h = xv @ w1 + b1
        h = np.asarray(jax.nn.gelu(jnp.asarray(h)))
        ref = h @ w2 + b2  # gate prob == 1 for a single expert
        np.testing.assert_allclose(out.numpy(), ref, rtol=1e-4, atol=1e-5)
        assert np.isfinite(float(moe.l_aux))

    def test_top2_shapes_and_aux(self):
        paddle.seed(0)
        moe = MoELayer(16, 32, num_experts=4, top_k=2)
        x = paddle.to_tensor(
            np.random.RandomState(1).randn(2, 8, 16).astype("float32"))
        out = moe(x)
        assert tuple(out.shape) == (2, 8, 16)
        assert float(moe.l_aux) >= 0.0

    def test_capacity_drops_tokens(self):
        paddle.seed(0)
        # capacity 1 token per expert: most tokens dropped -> output mostly 0
        moe = MoELayer(8, 16, num_experts=2, top_k=1, capacity_factor=0.01)
        x = paddle.to_tensor(
            np.random.RandomState(2).randn(1, 32, 8).astype("float32"))
        out = moe(x).numpy()
        zero_rows = np.sum(np.all(out == 0.0, axis=-1))
        assert zero_rows >= 28  # 32 tokens, 2 slots


class TestHybridGPT:
    def _dense_reference(self, cfg, params, ids):
        """Single-device forward with the SAME pytree (blocks unstacked)."""
        D = cfg.hidden_size
        eps = cfg.layer_norm_epsilon

        def ln(x, w, b):
            mu = x.mean(-1, keepdims=True)
            var = ((x - mu) ** 2).mean(-1, keepdims=True)
            return (x - mu) * jax.lax.rsqrt(var + eps) * w + b

        x = jnp.take(params["wte"], ids, axis=0) + params["wpe"][:ids.shape[1]]
        b = params["blocks"]
        pp, Lp = b["ln1_w"].shape[:2]
        for s in range(pp):
            for l in range(Lp):  # noqa: E741
                p = {k: v[s, l] for k, v in b.items()}
                h = ln(x, p["ln1_w"], p["ln1_b"])
                qkv = h @ p["wqkv"] + p["bqkv"]
                B, S = qkv.shape[0], qkv.shape[1]
                hd = D // cfg.num_heads
                # head-major qkv layout (see gpt_hybrid._make_block)
                qkv = qkv.reshape(B, S, cfg.num_heads, 3, hd)
                q, k, v = qkv[..., 0, :], qkv[..., 1, :], qkv[..., 2, :]
                sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) / (hd ** 0.5)
                mask = jnp.tril(jnp.ones((S, S), bool))
                sc = jnp.where(mask, sc, -1e30)
                ctx = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), v)
                x = x + ctx.reshape(B, S, D) @ p["wo"] + p["bo"]
                h2 = ln(x, p["ln2_w"], p["ln2_b"])
                x = x + jax.nn.gelu(h2 @ p["w1"] + p["b1"]) @ p["w2"] + p["b2"]
        x = ln(x, params["ln_f_w"], params["ln_f_b"])
        logits = x @ params["wte"].T
        logp = jax.nn.log_softmax(logits[:, :-1], -1)
        picked = jnp.take_along_axis(logp, ids[:, 1:, None], -1)[..., 0]
        return -picked.mean()

    def test_loss_and_grads_match_dense(self):
        cfg = _tiny_gpt(hidden_size=16, num_layers=2, num_heads=2,
                        vocab_size=32, max_position_embeddings=16)
        mesh = build_mesh({"dp": 2, "pp": 2, "mp": 2})
        params = gpt_hybrid.init_params(cfg, pp=2, seed=0)
        ids = jnp.asarray(
            np.random.RandomState(0).randint(0, 32, (4, 8)), jnp.int32)

        loss_fn = gpt_hybrid.make_loss_fn(cfg, mesh, n_microbatches=2,
                                          remat=False)
        loss, grads = jax.jit(jax.value_and_grad(loss_fn))(params, ids)
        ref_loss, ref_grads = jax.jit(jax.value_and_grad(
            lambda p, i: self._dense_reference(cfg, p, i)))(params, ids)

        np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-4)
        flat = gpt_hybrid._flatten(grads)
        flat_ref = gpt_hybrid._flatten(ref_grads)
        for k in flat_ref:
            np.testing.assert_allclose(
                np.asarray(flat[k]), np.asarray(flat_ref[k]),
                rtol=5e-3, atol=1e-4, err_msg=k)

    def test_train_step_runs_sharded(self):
        cfg = _tiny_gpt(hidden_size=16, num_layers=2, num_heads=2,
                        vocab_size=32, max_position_embeddings=16)
        mesh = build_mesh({"dp": 2, "pp": 2, "mp": 2})
        params = gpt_hybrid.init_params(cfg, pp=2, seed=0)
        opt = paddle.optimizer.AdamW(learning_rate=1e-3)
        step, init_state, (p_sh, s_sh, d_sh) = gpt_hybrid.make_train_step(
            cfg, mesh, opt, n_microbatches=2, lr=1e-3)
        params = jax.device_put(params, p_sh)
        state = jax.device_put(init_state(params), s_sh)
        ids = jax.device_put(jnp.asarray(
            np.random.RandomState(0).randint(0, 32, (4, 8)), jnp.int32), d_sh)
        l0 = None
        for i in range(5):
            params, state, loss = step(params, state, ids)
            l0 = float(loss) if l0 is None else l0
        assert float(loss) < l0


class TestS2DStem:
    def test_s2d_stem_matches_standard_resnet(self):
        # exact rewrite (vision/models/resnet.py _s2d_stem_conv): same
        # checkpoint, same outputs
        import numpy as np

        import paddle_tpu as paddle
        from paddle_tpu.vision.models import resnet18

        paddle.seed(0)
        a = resnet18(num_classes=7)
        b = resnet18(num_classes=7, s2d_stem=True)
        b.set_state_dict(a.state_dict())
        a.eval()
        b.eval()
        x = paddle.to_tensor(
            np.random.RandomState(0).randn(2, 3, 64, 64).astype(np.float32))
        np.testing.assert_allclose(np.asarray(a(x).numpy()),
                                   np.asarray(b(x).numpy()),
                                   rtol=1e-4, atol=1e-5)

    def test_norm_buffers_are_f32_under_x64(self):
        # BN running stats created without an explicit dtype became f64
        # whenever x64 is enabled (CPU policy) and poisoned every
        # downstream conv to f64 — the round-3 f64-poisoning bug class
        import paddle_tpu as paddle

        bn = paddle.nn.BatchNorm2D(4)
        assert str(bn._mean.dtype).endswith("float32")
        assert str(bn._variance.dtype).endswith("float32")

    def test_s2d_resnet_exports_and_serves(self, tmp_path):
        # the weight-transform inside forward must trace into the AOT
        # export (StableHLO) and serve identically
        import numpy as np

        import paddle_tpu as paddle
        from paddle_tpu.inference import (load_inference_model,
                                          save_inference_model)
        from paddle_tpu.vision.models import resnet18

        paddle.seed(0)
        net = resnet18(num_classes=4, s2d_stem=True)
        net.eval()
        x = np.random.RandomState(0).randn(1, 3, 32, 32).astype(np.float32)
        prefix = str(tmp_path / "s2drn")
        save_inference_model(prefix, net, example_inputs=[x])
        pred = load_inference_model(prefix)
        out, = pred.run([x])
        expect = np.asarray(net(paddle.to_tensor(x)).numpy())
        np.testing.assert_allclose(out, expect, rtol=1e-4, atol=1e-5)
