"""Serving a block-generating model: a tiny SDAR-MoE decoder (routed
experts without a capacity, grouped KV heads) behind ServingServer,
streamed by blocks.

Run: python examples/sdar_block_serving.py   (add JAX_PLATFORMS=cpu off-TPU)
The model declares generation by diffusion over blocks through its cfg
(block_length, denoising_steps, ...): GenerationEngine then runs
`block_step` in place of `decode_step`.  A block of 4 positions starts
masked, each step unmasks its most confident position, and the block's
tokens reach the stream together; the last event says at which denoising
step each token was unmasked.
"""
import http.client
import json

import numpy as np

import paddle_tpu as paddle
from paddle_tpu.models.sdar import SDARConfig, SDARForCausalLM
from paddle_tpu.serving import GenerationEngine, ServingServer


def stream(server, prompt, max_new_tokens):
    """POST /generate with stream=true; yields the stream's events."""
    host, port = server.url.split("//")[1].split(":")
    conn = http.client.HTTPConnection(host, int(port), timeout=120)
    conn.request("POST", "/generate", json.dumps(
        {"prompt": prompt, "max_new_tokens": max_new_tokens,
         "stream": True}), {"Content-Type": "application/json"})
    resp = conn.getresponse()
    assert resp.status == 200, resp.read()
    for line in resp:
        if line.startswith(b"data: "):
            yield json.loads(line[6:])
    conn.close()


def main():
    paddle.seed(0)
    cfg = SDARConfig(vocab_size=300, hidden_size=64, num_layers=2,
                     num_heads=4, num_kv_heads=2, head_dim=16,
                     moe_intermediate_size=32, num_experts=8,
                     num_experts_per_tok=2, max_position_embeddings=128,
                     mask_token_id=299)
    model = SDARForCausalLM(cfg)
    model.eval()
    engine = GenerationEngine(model, max_slots=2, max_seq_len=64,
                              prompt_buckets=[16], page_size=8)
    server = ServingServer(None, gen_engine=engine, port=0,
                           install_signal_handlers=False).start()
    try:
        prompt = np.random.RandomState(0).randint(0, 299, 10).tolist()
        tokens, done = [], None
        for ev in stream(server, prompt, 10):
            if "token" in ev:
                tokens.append(ev["token"])
            else:
                done = ev
        # prompt of 10 = two whole blocks and a tail of 2 that opens the
        # first generated block: 2 + 4 + 4 tokens
        for i, part in enumerate((slice(0, 2), slice(2, 6), slice(6, 10))):
            print(f"block {i}: tokens {tokens[part]} unmasked at steps "
                  f"{done['steps'][part]}")
        assert len(tokens) == 10 and sorted(done["steps"][2:6]) == [0, 1, 2, 3]
        snap = engine.metrics.snapshot()
        print(f"{snap['block_steps']} block steps, "
              f"{snap['block_tokens_emitted']} tokens")
    finally:
        server.shutdown()
    print("OK sdar_block_serving")


if __name__ == "__main__":
    main()
