"""Granite 4.0-H (Mamba-2 layers beside attention without positions, one
layer in four here, dense gated FFN, tied head) served one token a lane
over two kinds of state, at `tiny-granite` sizes (both layer kinds, two
periods) with seeded weights, against the plain float32 reference
(`benchmarks/reference/granite_hybrid.py`, which imports nothing of the
program): the model's forward pass, prefill then decode through the cache,
the chunked scan against the recurrence, and the engine end to end: a
snapshot restored against a cold admission, chunked prefill, a slot reused,
a padded bucket, a preempted lane resumed; and that the other models'
engines build what they built before this model came."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmarks.adapters import granite_hybrid as adapter
from benchmarks.reference import granite_hybrid as ref
from paddle_tpu.nn.layer_base import functional_call, state_pytrees
from paddle_tpu.ops import fused
from paddle_tpu.ops.pallas import DoesNotTile, ssm
from paddle_tpu.serving import GenerationEngine
from paddle_tpu.serving import kv_cache as kc
from paddle_tpu.serving.prefix_cache import PrefixCache
from paddle_tpu.utils.profiler import startup

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(_REPO, "benchmarks", "configs",
                       "tiny-granite.json")) as f:
    CFG = json.load(f)["model"]
PAGE = 16


def build(seed=5, dtype="float32"):
    w = ref.init_weights(ref.key_from_seed(seed), CFG, jnp.dtype(dtype))
    net = adapter.build_network(CFG, w, dtype)
    net.eval()
    return w, net


@pytest.fixture(scope="module")
def tiny():
    return build()


def engine_of(net, **kw):
    kw = {**dict(max_slots=4, max_seq_len=160, page_size=PAGE,
                 prompt_buckets=[32, 64, 96], prefix_cache=True), **kw}
    return GenerationEngine(net, **kw).start()


def prompt_of(n, seed=0):
    return np.random.default_rng(seed).integers(0, 500, (n,)).astype(np.int32)


def reference_logits(w, prompt, toks):
    seq = np.concatenate([prompt, toks[:-1]]).astype(np.int32)
    return np.asarray(ref.logits_at(
        w, jnp.asarray(seq), jnp.arange(len(prompt) - 1, len(seq)), CFG))


def served_gap(w, prompt, toks):
    lg = reference_logits(w, prompt, toks)
    return float((lg.max(-1) - lg[np.arange(len(toks)), toks]).max())


# -- the model ---------------------------------------------------------------
def test_forward_matches_the_reference(tiny):
    w, net = tiny
    ids = prompt_of(45)
    want = np.asarray(ref.logits_at(w, jnp.asarray(ids), jnp.arange(45), CFG))
    got = np.asarray(net(paddle.to_tensor(ids[None])).value)[0]
    # float32 both sides; the program scans by chunks of 8 where the
    # reference goes token by token, another order of the same sums:
    # logits are O(1), 2e-5 is a few ulps of accumulated rounding
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)
    assert np.abs(want).max() > 0.1


def test_config_declares_both_kinds_of_state():
    cfg = adapter.program_config(CFG)
    assert cfg.state_layers == (0, 1, 3, 4, 5, 7)
    assert cfg.state_shape == (2, 16, 128) and cfg.conv_shape == (3, 288)
    geom = kc.CacheGeometry(
        num_layers=8, max_slots=12, max_seq_len=64, num_heads=8,
        head_dim=cfg.head_dim, vocab_size=512,
        num_kv_heads=cfg.num_kv_heads, state_layers=cfg.state_layers,
        state_shape=cfg.state_shape, conv_shape=cfg.conv_shape,
        state_chunk=cfg.state_chunk, state_pack=cfg.state_pack)
    # pages for the two attention layers alone (the 2 KV heads of 16 side
    # by side as one of 32), a state for the other six, one snapshot for
    # every six slots
    assert (cfg.kv_pack, cfg.num_kv_heads, cfg.head_dim) == (2, 1, 32)
    assert geom.full_layers == (2, 6) and geom.pool_shape[0] == 2 \
        and geom.pool_shape[3:] == (1, 32)
    assert geom.state_snapshots == 2
    st = kc.make_state(geom)
    assert st["ssm"].shape == (6, 12, 2, 16, 128) \
        and st["ssm"].dtype == jnp.float32
    assert st["snap_ssm"].shape == (6, 2, 2, 16, 128)
    assert st["conv"].shape == (6, 12, 3 * 288)
    assert geom.state_bytes() == 6 * (4 * 2 * 16 * 128 + 4 * 3 * 288)
    with pytest.raises(ValueError, match="recurrent state"):
        kc.CacheGeometry(num_layers=2, max_slots=2, max_seq_len=32,
                         num_heads=2, head_dim=8, vocab_size=8,
                         state_layers=(0,), state_shape=(1, 8, 8),
                         conv_shape=(3, 8), state_chunk=8, block_length=4)


# -- the scan ----------------------------------------------------------------
def scan_case(T, H=8, P=64, N=128, seed=0):
    rng = np.random.default_rng(seed)
    f32 = jnp.float32
    return dict(
        x=jnp.asarray(rng.normal(size=(T, H, P)), f32),
        dt=jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(0.1),
                                          (T, H))), f32),
        A=-jnp.arange(1, H + 1, dtype=f32),
        B=jnp.asarray(rng.normal(size=(T, N)), f32),
        C=jnp.asarray(rng.normal(size=(T, N)), f32),
        state0=jnp.asarray(rng.normal(size=(H, P, N)), f32))


def recurrence(c, upto=None):
    """Token by token, as the reference has it."""
    def token(h, inp):
        x_t, b_t, c_t, d_t = inp
        h = jnp.exp(d_t * c["A"])[:, None, None] * h \
            + (d_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
        return h, jnp.einsum("hpn,n->hp", h, c_t)

    n = c["x"].shape[0] if upto is None else upto
    return jax.lax.scan(token, c["state0"],
                        (c["x"][:n], c["B"][:n], c["C"][:n], c["dt"][:n]))


@pytest.mark.parametrize("path", ["composite", "kernel"])
@pytest.mark.parametrize("T", [37, 128, 300])
def test_chunked_scan_equals_the_recurrence_from_a_nonzero_state(path, T):
    """Lengths that no chunk divides, one chunk exactly, and chunks with a
    ragged last one; the initial state is not zero."""
    c = scan_case(T)
    h_ref, y_ref = recurrence(c)
    if path == "kernel":
        y, end, starts = ssm.ssd_chunk_scan(**c, chunk=128, interpret=True)
    else:
        y, end, starts = fused.ssd_chunk_scan(**c, chunk=128)
    # float32; y is O(40): the chunked form sums a chunk's 128 terms in
    # another order than the recurrence, 1e-4 absolute is ~3e-6 relative
    np.testing.assert_allclose(y, y_ref, atol=1e-4, rtol=0)
    np.testing.assert_allclose(end, h_ref, atol=1e-5, rtol=0)
    np.testing.assert_allclose(starts[0], c["state0"], atol=0, rtol=0)
    # the state after any token, from the chunks' starts
    for at in {0, 5, min(T, 128), T // 2, T}:
        got = fused.ssd_state_at(c["x"], c["dt"], c["A"], c["B"], starts, at,
                                 128)
        want = recurrence(c, at)[0] if at else c["state0"]
        np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)


def test_a_step_of_zero_leaves_the_state_untouched():
    c = scan_case(64)
    c["dt"] = c["dt"].at[40:].set(0.0)          # padding behind token 40
    _, end, _ = fused.ssd_chunk_scan(**c, chunk=16)
    np.testing.assert_allclose(end, recurrence(c, 40)[0], atol=1e-5, rtol=0)


def test_scan_kernel_refuses_by_shape():
    c = scan_case(32, N=16)
    with pytest.raises(DoesNotTile):
        ssm.ssd_chunk_scan(**c, chunk=128, interpret=True)
    with pytest.raises(DoesNotTile):
        ssm.ssd_chunk_scan(**scan_case(32), chunk=8, interpret=True)


@pytest.mark.parametrize("live", [[1, 0, 1, 1, 0, 0], [0] * 6, [1] * 6])
def test_one_token_update_touches_the_live_lanes_alone(live):
    rng = np.random.default_rng(1)
    L, slots, H, P, N = 3, 6, 32, 64, 128
    r = fused.ssm_pack(H, P)
    assert r == 2
    f32 = jnp.float32
    held = jnp.asarray(rng.normal(size=(L, slots, H // r, N, r * P)), f32)
    x = jnp.asarray(rng.normal(size=(slots, H, P)), f32)
    dt = jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(0.1),
                                        (slots, H))), f32)
    A = -jnp.arange(1, H + 1, dtype=f32)
    B = jnp.asarray(rng.normal(size=(slots, N)), f32)
    C = jnp.asarray(rng.normal(size=(slots, N)), f32)
    active = np.array(live, bool)
    lanes = jnp.argsort(~jnp.asarray(active), stable=True).astype(jnp.int32)
    # the recurrence, a head's [P, N] state at a time
    h = fused.ssm_unpack_state(held[1], r)
    want = jnp.exp(dt * A)[:, :, None, None] * h \
        + (dt[:, :, None] * x)[..., None] * B[:, None, None, :]
    y_want = jnp.einsum("shpn,sn->shp", want, C)
    # the composite and the kernel: the live lanes alone
    y, new = fused.ssm_decode_update(held, 1, lanes, active.sum(), x, dt, A,
                                     B, C)
    np.testing.assert_allclose(fused.ssm_unpack_state(new[1], r)[active],
                               want[active], atol=1e-5, rtol=0)
    np.testing.assert_allclose(np.asarray(y)[active], y_want[active],
                               atol=1e-4, rtol=0)
    decay = jnp.broadcast_to(jnp.exp(dt * A)[:, :, None],
                             x.shape).reshape(slots, H // r, r * P)
    dtx = (dt[:, :, None] * x).reshape(slots, H // r, r * P)
    yk, newk = ssm.ssm_decode_update(held, 1, lanes, int(active.sum()),
                                     decay, dtx, B, C, interpret=True)
    np.testing.assert_allclose(newk[1][active], new[1][active], atol=1e-6,
                               rtol=0)
    np.testing.assert_allclose(yk.reshape(slots, H, P)[active],
                               np.asarray(y)[active], atol=1e-4, rtol=0)
    # a dead lane's state and the other layers' are as they were, bit for bit
    np.testing.assert_array_equal(newk[1][~active], held[1][~active])
    np.testing.assert_array_equal(new[1][~active], held[1][~active])
    np.testing.assert_array_equal(newk[0], held[0])
    np.testing.assert_array_equal(newk[2], held[2])


def test_held_layout_round_trips():
    h = jnp.asarray(np.random.default_rng(2).normal(size=(3, 8, 32, 16)))
    for pack in (1, 2, 4):
        s = fused.ssm_pack_state(h, pack)
        assert s.shape == (3, 8 // pack, 16, pack * 32)
        np.testing.assert_array_equal(fused.ssm_unpack_state(s, pack), h)
    assert fused.ssm_pack(64, 64) == 2 and fused.ssm_pack(8, 32) == 4 \
        and fused.ssm_pack(3, 256) == 1


# -- prefill then decode through the cache -------------------------------------
def test_prefill_then_decode_logits_match_the_references_full_forward(tiny):
    """The cold prompt pass over a padded bucket, its pages and states put
    into a slot, then one token a step through `HybridKV`: the logits of
    every step against the reference's full forward pass over the whole
    sequence, float32."""
    w, net = tiny
    cfg = net.cfg
    params, buffers = state_pytrees(net)
    geom = kc.CacheGeometry(
        num_layers=cfg.num_layers, max_slots=3, max_seq_len=96,
        num_heads=cfg.num_heads, head_dim=cfg.head_dim,
        vocab_size=cfg.vocab_size, page_size=PAGE,
        num_kv_heads=cfg.num_kv_heads, state_layers=cfg.state_layers,
        state_shape=cfg.state_shape, conv_shape=cfg.conv_shape,
        state_chunk=cfg.state_chunk, state_pack=cfg.state_pack)
    state = kc.make_state(geom)
    L, n_new, slot = 21, 9, 1
    seq = prompt_of(L + n_new, seed=3)
    ids = np.zeros((1, 32), np.int32)
    ids[0, :L] = seq[:L]
    (k, v, lg0, ends), _ = functional_call(
        net, params, (paddle.Tensor(jnp.asarray(ids)), jnp.int32(L),
                      jnp.int32(16)),
        buffers=buffers, mutable=False, method="slot_prefill")
    none = jnp.full((geom.pages_per_slot,), -1, jnp.int32)
    state, _ = kc.write_prompt(state, slot, k, v, L, none, 0)
    state = kc.put_states(state, slot, ends, jnp.int32(0))
    active = jnp.zeros((3,), bool).at[slot].set(True)
    got = [np.asarray(lg0)]
    for i in range(n_new - 1):
        pos = jnp.zeros((3,), jnp.int32).at[slot].set(L + i)
        tok = jnp.zeros((3,), jnp.int32).at[slot].set(int(seq[L + i]))
        pidx = (L + i) // PAGE
        if int(state["ptab"][slot, pidx]) < 0:      # the next tail page
            pages, fc = kc.take_pages(state["free_stack"],
                                      state["free_count"], active)
            state = dict(state, free_count=fc,
                         ptab=state["ptab"].at[:, pidx].set(
                             jnp.where(active, pages, -1)))
        src = kc.HybridKV(
            kc.PagedKV(state["kp"], state["vp"], state["ptab"], pos, active,
                       geom.max_seq_len),
            kc.LaneStates(state["ssm"], state["conv"], active,
                          jnp.argsort(~active, stable=True).astype(jnp.int32),
                          active.sum(dtype=jnp.int32)))
        (lg, src), _ = functional_call(
            net, params, (tok[:, None], pos[:, None], src), buffers=buffers,
            mutable=False, method="slot_step")
        state = dict(state, kp=src.kv.k_pages, vp=src.kv.v_pages,
                     ssm=src.states.ssm, conv=src.states.conv)
        got.append(np.asarray(lg[slot, 0]))
    want = np.asarray(ref.logits_at(
        w, jnp.asarray(seq), jnp.arange(L - 1, L + n_new - 1), CFG))
    # float32 both sides; the chunked scan and the paged softmax sum in
    # another order than the reference: a few ulps of O(1) logits
    np.testing.assert_allclose(np.stack(got), want, atol=3e-5, rtol=0)
    # the snapshot the pass left at token 16 is the state a pass of 16 ends in
    (_, _, _, short), _ = functional_call(
        net, params, (paddle.Tensor(jnp.asarray(ids)), jnp.int32(16),
                      jnp.int32(0)),
        buffers=buffers, mutable=False, method="slot_prefill")
    np.testing.assert_allclose(ends[2], short[0], atol=1e-6, rtol=0)
    np.testing.assert_array_equal(ends[3], short[1])


# -- the engine --------------------------------------------------------------
@pytest.fixture(scope="module")
def engine(tiny):
    eng = engine_of(tiny[1])
    yield eng
    eng.stop()


def shared_prompts(n_own=(9, 13, 21), seed=7):
    head = prompt_of(48, seed)
    return [np.concatenate([head, prompt_of(n, seed + 1 + i)])
            for i, n in enumerate(n_own)]


def test_a_restored_admission_serves_what_a_cold_one_serves(tiny, engine):
    """Three prompts behind one 48-token prefix: the first finds nothing,
    the second matches three pages and no state, so it scans from zero and
    leaves a snapshot at page 3, the third restores it and scans its own
    part alone.  Each serves what an engine without a prefix cache (every
    admission cold) serves, and what the reference puts first."""
    w, net = tiny
    prompts = shared_prompts()
    before = engine.metrics.snapshot()
    served = [engine.generate(p, 14, timeout=120) for p in prompts]
    after = engine.metrics.snapshot()
    assert after["state_restores"] - before["state_restores"] == 1
    assert after["state_snapshots"] - before["state_snapshots"] == 1
    assert after["state_snapshots_live"] >= 1
    # the restored pass scanned 21 tokens, not 69
    assert after["state_scan_tokens"] - before["state_scan_tokens"] \
        == 57 + 61 + 21
    cold = engine_of(net, prefix_cache=False)
    try:
        for p, toks in zip(prompts, served):
            assert cold.generate(p, 14, timeout=120) == toks
            # 0: the served token is the float32 reference's own best
            assert served_gap(w, p, np.asarray(toks)) < 1e-4
    finally:
        cold.stop()


def test_chunked_prefill_on_serves_what_off_serves(tiny, engine):
    _, net = tiny
    prompts = shared_prompts((11, 30, 17), seed=21)
    whole = [engine.generate(p, 10, timeout=120) for p in prompts]
    chunked = engine_of(net, prefill_chunk=32)
    try:
        got = [chunked.generate(p, 10, timeout=120) for p in prompts]
        snap = chunked.metrics.snapshot()
    finally:
        chunked.stop()
    assert got == whole
    # the chunks scanned on from the slot's own state, the second prompt's
    # left the snapshot in the chunk that crossed page 3, the third restored
    assert snap["prefill_chunks"] >= 4 and snap["state_snapshots"] == 1 \
        and snap["state_restores"] == 1


def kernel_sized():
    """`tiny-granite` with state layers that both kernels tile (heads of 64
    two to a row, a state of 128, chunks of 128), over one period."""
    cfg = {**CFG, "num_hidden_layers": 4,
           "layer_types": ["mamba", "mamba", "attention", "mamba"],
           "mamba_n_heads": 4, "mamba_d_head": 64, "mamba_d_state": 128,
           "mamba_chunk_size": 128}
    w = ref.init_weights(ref.key_from_seed(9), cfg, jnp.float32)
    net = adapter.build_network(cfg, w, "float32")
    net.eval()
    return net


@pytest.mark.parametrize("path", ["jnp", "kernel"])
def test_a_chunked_prefill_beside_a_decoding_lane(path, tiny, monkeypatch):
    """A slot between two chunks of its prompt holds the state its next
    chunk scans on from, and the decode steps of the OTHER lanes run in
    between: they may write neither its tail nor its state.  One lane
    decodes a long answer while a second prompt comes in by chunks of 32,
    on the composite and on the kernels (interpreted); the second serves
    what an engine that takes the prompt whole serves."""
    if path == "kernel":
        monkeypatch.setattr(fused, "_use_pallas", lambda: True)
        net = kernel_sized()
    else:
        net = tiny[1]
    fell = fused.fallback_counter()
    before = {k: fell.values.get((k, "shape"), 0)
              for k in ("ssm_decode_update", "ssd_chunk_scan")}
    long_answer, late = prompt_of(20, 71), prompt_of(150, 72)
    kw = dict(prefix_cache=False, max_seq_len=256, prompt_buckets=[32, 160])

    def held(eng):      # every slot's state and tail as the engine left them
        return [np.asarray(eng._state[k], np.float32).swapaxes(0, 1)
                for k in ("ssm", "conv")]

    whole = engine_of(net, **kw)
    try:
        want = whole.generate(late, 10, timeout=300)
    finally:
        whole.stop()
    ssm_want, conv_want = (a[0] for a in held(whole))       # its one lane's
    chunked = engine_of(net, prefill_chunk=32, **kw)
    try:
        first = chunked.submit(long_answer, 120)
        first.next_token(timeout=300)               # the lane is decoding
        steps = chunked.metrics.snapshot()["state_lane_steps"]
        got = chunked.generate(late, 10, timeout=300)
        snap = chunked.metrics.snapshot()
        still = len(first.tokens)
        first.result(timeout=300)
    finally:
        chunked.stop()
    # the prompt came in by five chunks, and between them the other lane
    # went on decoding (a step after every chunk)
    assert snap["prefill_chunks"] >= 5
    assert snap["state_lane_steps"] - steps >= 5 and still < 120
    assert got == want
    # greedy tokens of random weights hardly feel a tail one token off (on
    # the composite they came out the same): the state the lane ended in
    # does.  Float32 both sides, chunks of another length sum in another
    # order: 1e-6 of the largest entry read; a junk token shifted into the
    # tail between chunks moved the state by more than its largest entry
    for got_k, want_k in zip(held(chunked), (ssm_want, conv_want)):
        off = np.abs(got_k - want_k).reshape(len(got_k), -1).max(1)
        assert off.min() < 1e-4 * np.abs(want_k).max()
    if path == "kernel":        # and the kernels ran, they did not fall back
        assert {k: fell.values.get((k, "shape"), 0) for k in before} == before


def test_a_slot_reused_after_release_serves_what_a_fresh_engine_serves(tiny):
    _, net = tiny
    a, b = prompt_of(40, 31), prompt_of(23, 32)
    one = engine_of(net, max_slots=1, prefix_cache=False)
    try:
        one.generate(a, 12, timeout=120)        # leaves its state in slot 0
        reused = one.generate(b, 12, timeout=120)
    finally:
        one.stop()
    fresh = engine_of(net, max_slots=1, prefix_cache=False)
    try:
        assert fresh.generate(b, 12, timeout=120) == reused
    finally:
        fresh.stop()


def test_a_padded_bucket_serves_what_the_exact_length_serves(tiny):
    _, net = tiny
    p = prompt_of(41, 41)
    padded = engine_of(net, prompt_buckets=[64], prefix_cache=False)
    exact = engine_of(net, prompt_buckets=[41], prefix_cache=False)
    try:
        assert padded.generate(p, 12, timeout=120) \
            == exact.generate(p, 12, timeout=120)
    finally:
        padded.stop()
        exact.stop()


def test_a_preempted_lane_resumes_from_its_deepest_snapshot(tiny, engine):
    """A lane cancelled mid-stream and admitted again with what it had
    emitted (the router's re-admission): no state can be rebuilt from a
    page table, so the prompt and the emitted tokens are prefilled again,
    from the deepest snapshot under them."""
    w, _ = tiny
    prompts = shared_prompts((10, 12, 15), seed=51)
    for p in prompts[:2]:               # the second leaves the snapshot
        engine.generate(p, 4, timeout=120)
    p = prompts[2]
    whole = engine.generate(p, 24, timeout=120)
    handle = engine.submit(p, 24)
    first = [handle.next_token(timeout=60) for _ in range(6)]
    handle.cancel()
    handle.result(timeout=60)
    emitted = list(handle.tokens)
    assert emitted[:6] == first == whole[:6]
    before = engine.metrics.snapshot()["state_restores"]
    rest = engine.generate(np.concatenate([p, emitted]).astype(np.int32),
                           24 - len(emitted), resume_pos=len(emitted),
                           timeout=120)
    assert emitted + rest == whole
    assert engine.metrics.snapshot()["state_restores"] == before + 1


def test_submit_refuses_nothing_other_models_accept(tiny, engine):
    p = prompt_of(30, 61)
    sampled = engine.generate(p, 8, do_sample=True, temperature=0.8,
                              top_k=20, seed=3, timeout=120)
    assert len(sampled) == 8
    assert engine.generate(p, 8, do_sample=True, temperature=0.8, top_k=20,
                           seed=3, timeout=120) == sampled
    assert len(engine.generate(p, 5, eos_token_id=int(sampled[2]),
                               do_sample=True, temperature=0.8, top_k=20,
                               seed=3, timeout=120)) == 3


def test_state_registers_are_on_metrics(engine):
    text = engine.metrics.prometheus_text()
    for name in ("paddle_genserve_state_restores_total",
                 "paddle_genserve_state_snapshots_total",
                 "paddle_genserve_state_snapshot_evictions_total",
                 "paddle_genserve_state_lane_steps_total",
                 "paddle_genserve_state_snapshots_live",
                 "paddle_genserve_state_scan_tokens_total"):
        assert name in text, name
    phases = engine.timers.totals
    assert "admit/restore" in phases and "admit/snapshot" in phases


# -- the snapshots' bookkeeping ------------------------------------------------
def test_prefix_cache_keeps_a_snapshot_with_its_pages():
    pc = PrefixCache(4, capacity=3, snapshots=2)
    a, b, c = (np.arange(i, i + 9, dtype=np.int32) for i in (0, 100, 200))
    row = np.arange(8)
    pc.register(a, row, 0, 2)
    assert pc.lookup(a)[0] == 2 and pc.lookup_state(a, 2) == (0, -1)
    place = pc.take_snapshot(a, 2)
    assert pc.lookup_state(a, 2) == (2, place) and pc.snapshots_live == 1
    assert pc.lookup_state(a, 1) == (0, -1)      # no deeper than asked
    pc.register(b, row + 10, 0, 1)
    other = pc.take_snapshot(b, 1)
    assert other != place and pc.snapshots_live == 2
    # the pool is full: the one restored longest ago gives its place
    pc.lookup_state(a, 2)
    pc.register(c, row + 20, 0, 1)      # capacity 3: a's first entry goes
    third = pc.take_snapshot(c, 1)
    assert third == other and pc.snapshots_evicted == 1
    assert pc.lookup_state(b, 1) == (0, -1)
    # evicting an entry's pages takes its snapshot along
    pc.evict_idle(10 ** 6)
    assert pc.snapshots_live == 0 and len(pc) == 0
    assert pc.snapshots_taken == 3 and pc.snapshots_evicted == 3


# -- only this model pays ------------------------------------------------------
def _built(net, **kw):
    boot = startup()
    since = boot.mark()
    eng = GenerationEngine(net, **kw).start()
    try:
        names = sorted(r["name"].split("/", 2)[2] for r in
                       boot.table(["genserve/build"], since))
        return names, set(eng._state), eng
    except BaseException:
        eng.stop()
        raise


KW = dict(max_slots=2, max_seq_len=64, page_size=16, prompt_buckets=[16, 32],
          prefix_cache=True)
COMMON = ["insert.16", "insert.32", "insert_prefix.16", "insert_prefix.32",
          "prefill.16", "prefill.32", "reclaim_step", "release_step"]
LANES = {"kp", "vp", "ptab", "free_stack", "free_count", "pinned", "tok",
         "pos", "active", "rng", "do_sample", "temp", "top_k", "eos",
         "stop_pos"}


def _gpt():
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    return GPTForCausalLM(GPTConfig(
        vocab_size=128, hidden_size=32, num_layers=2, num_heads=2,
        max_position_embeddings=64, dropout=0.0, attn_dropout=0.0)), \
        ["decode_step"], set()


def _sdar():
    from paddle_tpu.models.sdar import SDARConfig, SDARForCausalLM

    return SDARForCausalLM(SDARConfig(
        vocab_size=128, hidden_size=32, num_layers=2, num_heads=4,
        num_kv_heads=2, head_dim=8, moe_intermediate_size=16, num_experts=4,
        num_experts_per_tok=2, max_position_embeddings=64,
        mask_token_id=127)), \
        ["block_step"], {"blk", "blk_open", "blk_step", "step", "moe_counts",
                         "moe_touched"}


def _mellum():
    from paddle_tpu.models.mellum import MellumConfig, MellumForCausalLM

    return MellumForCausalLM(MellumConfig(
        vocab_size=128, hidden_size=32, num_layers=4, num_heads=4,
        num_kv_heads=2, head_dim=8, moe_intermediate_size=16, num_experts=4,
        num_experts_per_tok=2, max_position_embeddings=64,
        sliding_window=16)), \
        ["decode_step"], {"moe_counts", "moe_touched", "wkp", "wvp", "wtab",
                          "wfree_stack", "wfree_count", "w_released"}


@pytest.mark.parametrize("make", [_gpt, _sdar, _mellum])
def test_other_models_build_what_they_built(make):
    """By name and count, and over a decode state with the keys it had: the
    state's arguments exist only where `CacheGeometry` has state layers."""
    net, step, extra = make()
    net.eval()
    names, keys, eng = _built(net, **KW)
    try:
        assert names == sorted(COMMON + step)
        assert keys == LANES | extra
        assert eng.compile_count == len(names)
        assert not eng.geometry.state_layers \
            and eng.geometry.state_snapshots == 0
        assert "state_restores" not in eng.metrics.snapshot()
        assert "paddle_genserve_state" not in eng.metrics.prometheus_text()
        assert eng.generate(np.arange(1, 12, dtype=np.int32), 4,
                            timeout=120)
    finally:
        eng.stop()


def test_this_model_builds_the_same_executables_over_a_wider_state(tiny):
    names, keys, eng = _built(tiny[1], **KW)
    eng.stop()
    assert names == sorted(COMMON + ["decode_step"])
    assert keys == LANES | {"ssm", "conv", "snap_ssm", "snap_conv"}
