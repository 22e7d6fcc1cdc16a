"""Serving a model whose layers mix a sliding window with full attention:
a tiny Mellum decoder (window and full layers 3:1, YaRN on the full
layers, routed experts, grouped KV heads) behind ServingServer.

Run: python examples/mellum_serving.py   (add JAX_PLATFORMS=cpu off-TPU)
The model declares its layers' windows through its cfg (`layer_windows`):
GenerationEngine then keeps the window layers' K/V in a page pool of their
own, in which a lane holds only the pages that meet its window; the decode
step hands back what falls behind.  Nothing selects the cache's kind: a
model without windows gets the one pool it always got.
"""
import http.client
import json

import numpy as np

import paddle_tpu as paddle
from paddle_tpu.models.mellum import MellumConfig, MellumForCausalLM
from paddle_tpu.serving import GenerationEngine, ServingServer


def generate(server, prompt, max_new_tokens):
    """POST /generate with stream=true; the streamed tokens."""
    host, port = server.url.split("//")[1].split(":")
    conn = http.client.HTTPConnection(host, int(port), timeout=120)
    conn.request("POST", "/generate", json.dumps(
        {"prompt": prompt, "max_new_tokens": max_new_tokens,
         "stream": True}), {"Content-Type": "application/json"})
    resp = conn.getresponse()
    assert resp.status == 200, resp.read()
    tokens = [json.loads(line[6:]).get("token") for line in resp
              if line.startswith(b"data: ")]
    conn.close()
    return [t for t in tokens if t is not None]


def main():
    paddle.seed(0)
    cfg = MellumConfig(vocab_size=300, hidden_size=64, num_layers=4,
                       num_heads=4, num_kv_heads=2, head_dim=16,
                       moe_intermediate_size=32, num_experts=8,
                       num_experts_per_tok=2, max_position_embeddings=256,
                       sliding_window=16)
    model = MellumForCausalLM(cfg)
    model.eval()
    print("layer windows:", cfg.layer_windows)
    engine = GenerationEngine(model, max_slots=2, max_seq_len=128,
                              prompt_buckets=[16, 32, 64], page_size=8)
    server = ServingServer(None, gen_engine=engine, port=0,
                           install_signal_handlers=False).start()
    try:
        shared = np.random.RandomState(0).randint(0, 299, 48).tolist()
        first = generate(server, shared + [7, 8, 9], 40)
        again = generate(server, shared + [1, 2, 3, 4], 40)   # a prefix hit
        assert len(first) == len(again) == 40
        snap = engine.metrics.snapshot()
        print(f"prefix hits {snap['prefix_cache_hits']}, window pages let go "
              f"behind the window {snap['kv_window_pages_released']}")
        print("mean page-table entries a step, full pool / window pool:",
              {k: round(v / snap["steps"], 1)
               for k, v in snap["kv_mapped_page_steps"].items()})
        # a context of 90 tokens under a window of 16: the window pool's
        # rows stay short while the full pool's grow with the context
        steps = snap["kv_mapped_page_steps"]
        assert snap["prefix_cache_hits"] == 1
        assert snap["kv_window_pages_released"] > 0
        assert steps["window"] < steps["full"] / 2
        counts = engine.expert_counts()["assignments"]
        print("routed assignments a layer:", counts.sum(axis=1).tolist())
    finally:
        server.shutdown()
    print("OK mellum_serving")


if __name__ == "__main__":
    main()
