"""`costs/paged_gqa_decode.py` counts the work the model needs: KV heads'
bytes (not query heads'), all of the context in a full layer, the last
WINDOW of it in a window layer."""
from types import SimpleNamespace

from benchmarks.costs import paged_gqa_decode as cost

SZ = {"NH": 32, "NKV": 4, "HD": 128, "WINDOW": 1024, "L_FULL": 2,
      "L_WINDOW": 6}


def test_one_token_at_a_context_past_the_window():
    # one request, prompt 3000, three tokens in the window (the first came
    # from the prefill): two decode steps, at contexts 3001 and 3002
    run = SimpleNamespace(
        window=(0.0, 10.0), requests={7: {"prompt": [0] * 3000}},
        client={"records": [{"id": 7, "t": [1.0, 2.0, 3.0]}]})
    c = cost.for_window(run, calls=16, sz=SZ)
    full, win, lanes = 3001 + 3002, 2 * 1024, 2
    kv_tok = 2 * 4 * 128 * 2                    # K and V, 4 KV heads, bf16
    io = 2 * lanes * 32 * 128 * 2               # q and the output
    assert c["bytes"] == 2 * (full * kv_tok + io) + 6 * (win * kv_tok + io)
    assert c["ops"] == 4 * 32 * 128 * (2 * full + 6 * win)


def test_a_short_context_reads_all_of_it_in_every_layer():
    run = SimpleNamespace(
        window=(0.0, 10.0), requests={1: {"prompt": [0] * 100}},
        client={"records": [{"id": 1, "t": [1.0, 2.0, 11.0]}]})
    c = cost.for_window(run, calls=8, sz=SZ)
    assert c["ops"] == 4 * 32 * 128 * 8 * 101   # one step, at context 101
