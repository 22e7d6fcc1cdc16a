"""Serving: StableHLO AOT export + Predictor, ONNX interchange, and the
adaptive-batching ServingEngine (concurrent clients, zero steady-state
compiles, responses equal to single-request runs to a few ulp).

Run: python examples/bert_serving.py   (add JAX_PLATFORMS=cpu off-TPU)
"""
import tempfile
import threading

import numpy as np

import paddle_tpu as paddle
from paddle_tpu import inference, onnx, serving
from paddle_tpu.models import BertConfig, BertModel
from paddle_tpu.static import InputSpec


def main():
    paddle.seed(0)
    model = BertModel(BertConfig(vocab_size=400, hidden_size=48,
                                 num_layers=2, num_heads=4,
                                 intermediate_size=96,
                                 max_position_embeddings=64, dropout=0.0))
    model.eval()
    rs = np.random.RandomState(0)
    ids = rs.randint(0, 400, (4, 16)).astype(np.int32)
    want = np.asarray(model(paddle.to_tensor(ids))[0].numpy())

    with tempfile.TemporaryDirectory() as td:
        # 1) StableHLO artifact: symbolic batch, no python model code
        prefix = td + "/bert"
        inference.save_inference_model(
            prefix, model, input_spec=[InputSpec([-1, 16], "int32")],
            example_inputs=[ids])
        pred = inference.create_predictor(inference.Config(prefix))
        got, *_ = pred.run([ids])
        assert np.allclose(np.asarray(got), want, atol=1e-4)
        one, *_ = pred.run([ids[:1]])  # symbolic batch: same artifact
        assert np.asarray(one).shape[0] == 1
        print("StableHLO predictor OK (batch 4 and 1 from one artifact)")

        # 2) ServingEngine: N concurrent client threads through the
        # adaptive batcher, with zero compiles after the startup warmup.
        # A request coalesced into a batch of 2, 4 or 8 runs another
        # executable than its direct run at batch 1, so a response equals
        # the direct run to a few float32 ulp of the output's size (1e-6
        # of it), not bitwise
        engine = serving.ServingEngine(pred, batch_timeout_ms=2,
                                       buckets="1,2,4,8x16")
        engine.start()
        compiles_after_warmup = pred.compile_count
        n_clients, per_client = 4, 6
        outs = {}

        def client(cid):
            rs = np.random.RandomState(100 + cid)
            for r in range(per_client):
                req = rs.randint(0, 400, (16,)).astype(np.int32)
                got = engine.predict([req], timeout=30)
                outs[(cid, r)] = (req, got[0])

        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(n_clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        engine.drain(timeout=30)
        assert len(outs) == n_clients * per_client
        for req, got in outs.values():
            direct, *_ = pred.run([req[None]])
            np.testing.assert_allclose(
                got, direct[0], rtol=1e-6,
                atol=1e-6 * np.abs(direct[0]).max(),
                err_msg="serving != direct run")
        assert pred.compile_count == compiles_after_warmup, \
            "serving recompiled after warmup"
        snap = engine.metrics.snapshot()
        print(f"ServingEngine OK ({snap['responses']} responses, "
              f"mean batch {snap['mean_batch_size']}, "
              f"p99 {snap['p99_ms']}ms, all == direct run to 1e-6, "
              f"0 recompiles)")

        # 3) ONNX artifact with a dynamic batch dim
        f = onnx.export(model, td + "/bert_onnx",
                        input_spec=[InputSpec([-1, 16], "int32")],
                        example_inputs=[ids])
        got2 = onnx.ONNXModel(f).run([ids])[0]
        assert np.allclose(got2, want, atol=5e-4)
        print("ONNX round-trip OK")
    print("OK bert_serving")


if __name__ == "__main__":
    main()
