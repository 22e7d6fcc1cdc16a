"""The kernel cost functions against numbers worked by hand."""
import pytest

from benchmarks.costs import flash, gpt_train, paged_decode, softmax_xent


def test_flash_by_hand():
    # B=1, S=4, NH=1, HD=2, bf16: forward 2 products over half of 4x4:
    # 2 * 2 * 1*1*4*4*2 / 2 = 64 operations; tensors of 1*4*1*2*2 = 16 B,
    # four of them plus 4 float32 log-sum-exps = 64 + 16 = 80 B
    c = flash.cost(1, 4, 1, 2)
    assert c == {"ops": 64, "bytes": 80}
    b = flash.cost(1, 4, 1, 2, backward=True)
    assert b == {"ops": 160, "bytes": 8 * 16 + 16}
    # the cell's shape: 16 x 1024 x 12 x 64 forward = 25.8 GFLOP
    assert flash.cost(16, 1024, 12, 64)["ops"] == pytest.approx(25.77e9,
                                                                rel=1e-3)


def test_softmax_xent_by_hand():
    # N=2 rows of V=8 bf16 logits: 4*16 = 64 operations; forward reads
    # 2*8*2 = 32 B and writes 3 float32 a row = 24 B
    assert softmax_xent.cost(2, 8) == {"ops": 64, "bytes": 56}
    assert softmax_xent.cost(2, 8, backward=True) == {"ops": 64,
                                                      "bytes": 64 + 24}


def test_paged_decode_by_hand():
    # 2 live lanes of 10 cached tokens, 1 head of 4: QK^T and PV are
    # 2 * 2 * 2*1*4*10 = 320 operations; K and V 2 * 2*10*1*4*2 = 320 B,
    # q and the output 2 * 2*1*4*2 = 32 B
    assert paged_decode.cost(2, 10, 1, 4) == {"ops": 320, "bytes": 352}


def test_gpt_train_ops_per_token():
    cfg = dict(vocab_size=50304, hidden_size=768, num_layers=12,
               ffn_hidden_size=3072)
    n = gpt_train.params_without_positions(cfg)
    # 38.6M embedding + 12 * 7.09M a layer + the final norm
    assert n == 50304 * 768 + 12 * 7087872 + 1536
    assert gpt_train.ops_per_token(cfg, 1024) == 6 * n + 12 * 12 * 1024 * 768


def test_kernel_sizes_come_from_data():
    """`trace_kernel` takes its placeholders from the `kernel_sizes` of the
    configuration and traffic files and the metric's own `products`."""
    from types import SimpleNamespace

    from benchmarks.readers import trace_kernel

    run = SimpleNamespace(config={"kernel_sizes": {"NH": 12, "HD": 64}},
                          traffic={"kernel_sizes": {"B": 16, "S": 1024}})
    sz = trace_kernel.sizes(run, {"BH": ["B", "NH"], "N": ["B", "S"]})
    assert sz == {"NH": 12, "HD": 64, "B": 16, "S": 1024, "BH": 192,
                  "N": 16384}
    assert trace_kernel.sizes(SimpleNamespace(config={}, traffic={}), {}) == {}
