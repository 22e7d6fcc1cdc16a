"""GPT autoregressive generation: KV-cache decode (prefill + lax.scan)
must reproduce full-forward greedy decoding exactly, and the sampling
path must be seed-deterministic.  Reference analog: the beam_search /
sampling decode ops (operators/beam_search_op.cc, sampling_id_op.cc) —
here a single static-shape XLA program."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import GPTConfig, GPTForCausalLM

rs = np.random.RandomState(0)


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=211, hidden_size=48, num_layers=2,
                    num_heads=4, max_position_embeddings=64,
                    dropout=0.0, attn_dropout=0.0)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


def _full_forward_greedy(m, prompt, n):
    ids = prompt.copy()
    for _ in range(n):
        logits = np.asarray(m(paddle.to_tensor(ids)).numpy())
        nxt = logits[:, -1].argmax(-1).astype(np.int32)
        ids = np.concatenate([ids, nxt[:, None]], axis=1)
    return ids


def test_greedy_matches_full_forward(model):
    prompt = rs.randint(0, 211, (2, 7)).astype(np.int32)
    out = np.asarray(model.generate(paddle.to_tensor(prompt),
                                    max_new_tokens=6).numpy())
    assert out.shape == (2, 13)
    assert (out[:, :7] == prompt).all()
    np.testing.assert_array_equal(out, _full_forward_greedy(model, prompt, 6))


def test_single_token_edge(model):
    prompt = rs.randint(0, 211, (1, 3)).astype(np.int32)
    out = np.asarray(model.generate(paddle.to_tensor(prompt),
                                    max_new_tokens=1).numpy())
    assert out.shape == (1, 4)
    np.testing.assert_array_equal(out, _full_forward_greedy(model, prompt, 1))


def test_sampling_deterministic_per_seed(model):
    prompt = rs.randint(0, 211, (2, 5)).astype(np.int32)
    kw = dict(max_new_tokens=5, do_sample=True, top_k=5, temperature=0.8)
    a = np.asarray(model.generate(paddle.to_tensor(prompt), seed=3,
                                  **kw).numpy())
    b = np.asarray(model.generate(paddle.to_tensor(prompt), seed=3,
                                  **kw).numpy())
    c = np.asarray(model.generate(paddle.to_tensor(prompt), seed=4,
                                  **kw).numpy())
    np.testing.assert_array_equal(a, b)
    assert (a[:, :5] == prompt).all() and a.shape == (2, 10)
    assert not (a == c).all()  # different seed explores a different path
    assert (a < 211).all() and (a >= 0).all()


def test_top_k_restricts_support(model):
    """With top_k=1, sampling degenerates to greedy regardless of seed."""
    prompt = rs.randint(0, 211, (2, 4)).astype(np.int32)
    greedy = np.asarray(model.generate(paddle.to_tensor(prompt),
                                       max_new_tokens=4).numpy())
    k1 = np.asarray(model.generate(paddle.to_tensor(prompt),
                                   max_new_tokens=4, do_sample=True,
                                   top_k=1, seed=9).numpy())
    np.testing.assert_array_equal(greedy, k1)


def test_context_overflow_raises(model):
    prompt = rs.randint(0, 211, (1, 60)).astype(np.int32)
    with pytest.raises(ValueError, match="max_position_embeddings"):
        model.generate(paddle.to_tensor(prompt), max_new_tokens=10)


def test_beam_width_one_is_exactly_greedy(model):
    """A width-1 beam IS greedy decoding: the top-1 joint candidate each
    step is the argmax token of the single live beam — a sound invariant
    (unlike greedy-vs-wide-beam score dominance, which pruning can
    break).  Exercises _beam_traced directly since generate() routes
    num_beams=1 to the cheaper greedy decoder."""
    from paddle_tpu.nn.layer_base import functional_call, state_pytrees

    prompt = rs.randint(0, 211, (2, 5)).astype(np.int32)
    greedy = np.asarray(model.generate(paddle.to_tensor(prompt),
                                       max_new_tokens=5).numpy())
    params, buffers = state_pytrees(model)
    beam1, _ = functional_call(
        model, params, (paddle.to_tensor(prompt), 5, 1, None),
        buffers=buffers, mutable=False, method="_beam_traced")
    np.testing.assert_array_equal(np.asarray(beam1), greedy)


def test_beam_search_well_formed(model):
    prompt = rs.randint(0, 211, (2, 5)).astype(np.int32)
    beam = np.asarray(model.generate(paddle.to_tensor(prompt),
                                     max_new_tokens=5, num_beams=4).numpy())
    assert beam.shape == (2, 10)
    assert (beam[:, :5] == prompt).all()
    assert (beam >= 0).all() and (beam < 211).all()


def test_eos_pads_greedy_path(model):
    """Set eos to the token greedy emits at the first new position: every
    subsequent token must be eos (finished sequences emit only eos)."""
    prompt = rs.randint(0, 211, (2, 6)).astype(np.int32)
    base = np.asarray(model.generate(paddle.to_tensor(prompt),
                                     max_new_tokens=4).numpy())
    eos = int(base[0, 6])  # row 0's first generated token
    out = np.asarray(model.generate(paddle.to_tensor(prompt),
                                    max_new_tokens=4,
                                    eos_token_id=eos).numpy())
    assert (out[0, 6:] == eos).all(), out[0]
    # row 1 (if it never hit eos) must be unaffected by row 0 finishing
    if eos not in base[1, 6:]:
        np.testing.assert_array_equal(out[1], base[1])


def test_beam_and_sampling_exclusive(model):
    prompt = rs.randint(0, 211, (1, 3)).astype(np.int32)
    with pytest.raises(ValueError, match="exclusive"):
        model.generate(paddle.to_tensor(prompt), num_beams=2,
                       do_sample=True)


def test_training_mode_prefill_raises(model):
    model.train()
    try:
        with pytest.raises(RuntimeError, match="eval-only"):
            model.gpt(
                paddle.to_tensor(rs.randint(0, 211, (1, 4)).astype(np.int32)),
                return_kv=True)
    finally:
        model.eval()


def test_tensor_parallel_generate_on_mesh():
    """The TP decode path (shard_constraint on q/kv caches) must compile
    and run under a dp x mp mesh and agree with the single-device model
    (replicated weights, deterministic greedy)."""
    from paddle_tpu.distributed.mesh import build_mesh, mesh_guard

    paddle.seed(4)
    cfg = dict(vocab_size=101, hidden_size=32, num_layers=2, num_heads=4,
               max_position_embeddings=32, dropout=0.0, attn_dropout=0.0)
    ref = GPTForCausalLM(GPTConfig(**cfg))
    ref.eval()
    paddle.seed(4)  # identical init
    tp = GPTForCausalLM(GPTConfig(**cfg, tensor_parallel=True))
    tp.eval()
    prompt = rs.randint(0, 101, (2, 4)).astype(np.int32)
    want = np.asarray(ref.generate(paddle.to_tensor(prompt),
                                   max_new_tokens=4).numpy())
    mesh = build_mesh({"dp": 2, "mp": 4})
    with mesh_guard(mesh):
        got = np.asarray(tp.generate(paddle.to_tensor(prompt),
                                     max_new_tokens=4).numpy())
    np.testing.assert_array_equal(got, want)


def test_compiled_programs_cached_per_shape(model):
    """Two shapes coexist in the jit cache — alternating calls must not
    evict each other (one compile per shape, then reuse)."""
    getattr(model, "_gen_cache", {}).clear()
    p1 = rs.randint(0, 211, (1, 4)).astype(np.int32)
    p2 = rs.randint(0, 211, (2, 6)).astype(np.int32)
    model.generate(paddle.to_tensor(p1), max_new_tokens=2)
    model.generate(paddle.to_tensor(p2), max_new_tokens=2)
    n = len(model._gen_cache)
    model.generate(paddle.to_tensor(p1), max_new_tokens=2)
    model.generate(paddle.to_tensor(p2), max_new_tokens=2)
    assert len(model._gen_cache) == n == 2
