"""SDAR-MoE served by blocks, at tiny sizes with seeded weights, against the
plain float32 reference (`benchmarks/reference/sdar.py`, which imports
nothing of the program): the model under the block mask, the dropless
expert layer (kernel in interpret mode and composite), grouped KV heads
through the KV sources, and `GenerationEngine` in block mode end to end:
served tokens AND the denoising step of each equal the published
`block_diffusion_generate`."""
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmarks.adapters import sdar as adapter
from benchmarks.reference import sdar as ref
from paddle_tpu.ops import fused
from paddle_tpu.ops.pallas import moe_gmm as mg
from paddle_tpu.serving import GenerationEngine, kv_cache
from paddle_tpu.serving.kv_cache import CacheGeometry, PagedKV, PrefixKV

CFG = dict(vocab_size=256, hidden_size=128, num_hidden_layers=2,
           num_attention_heads=4, num_key_value_heads=2, head_dim=32,
           moe_intermediate_size=128, num_experts=8, num_experts_per_tok=3,
           norm_topk_prob=True, rms_norm_eps=1e-6, rope_theta=1e6,
           max_position_embeddings=128, block_length=4, denoising_steps=4,
           mask_token_id=255, initializer_range=0.02)


def build(cfg, seed=3):
    w = ref.init_weights(ref.key_from_seed(seed), cfg)
    net = adapter.build_network(cfg, w, "float32")
    net.eval()
    return w, net


@pytest.fixture(scope="module")
def tiny():
    return build(CFG)


def prompt_of(n, seed=0):
    return np.random.default_rng(seed).integers(0, 250, (n,)).astype(np.int32)


# -- the model under the block mask ------------------------------------------
def test_forward_under_the_block_mask_matches_the_reference(tiny):
    w, net = tiny
    ids = prompt_of(24)
    want = ref.logits_at(w, jnp.asarray(ids), jnp.arange(24), CFG)
    got = np.asarray(net(paddle.to_tensor(ids[None])).value)[0]
    # float32 both sides, the same formulas in another order of sums:
    # logits are O(1), so 1e-5 is a few ulps of accumulated rounding
    np.testing.assert_allclose(got, np.asarray(want), atol=1e-5)


def test_a_position_sees_its_whole_block_and_no_later_one(tiny):
    w, net = tiny
    ids = prompt_of(16)
    base = np.asarray(net(paddle.to_tensor(ids[None])).value)[0]
    later = ids.copy()
    later[9] = (later[9] + 1) % 250          # block 2 changes
    moved = np.asarray(net(paddle.to_tensor(later[None])).value)[0]
    assert np.array_equal(base[:8], moved[:8])        # blocks 0, 1 unmoved
    assert np.abs(base[8] - moved[8]).max() > 1e-6    # 8 sees 9: same block


# -- the expert layer: no capacity, no dropped token -------------------------
def loop_over_experts(x, ids, wts, wg, wu, wd):
    out = np.zeros_like(x)
    for n in range(x.shape[0]):
        for e, p in zip(ids[n], wts[n]):
            a = x[n] @ wg[e]
            out[n] += p * ((a / (1 + np.exp(-a)) * (x[n] @ wu[e])) @ wd[e])
    return out


@pytest.fixture(scope="module")
def uneven():
    """40 tokens, top-3 of 8: expert 5 takes no token, expert 2 most."""
    rng = np.random.default_rng(1)
    N, K, E, H, F = 40, 3, 8, 128, 128
    ids = np.stack([rng.choice([0, 1, 3, 4, 6, 7], K, replace=False)
                    for _ in range(N)]).astype(np.int32)
    ids[:30, 0] = 2
    wts = rng.random((N, K)).astype(np.float32)
    wts /= wts.sum(-1, keepdims=True)
    x = rng.normal(size=(N, H)).astype(np.float32)
    wg, wu = (0.1 * rng.normal(size=(E, H, F)).astype(np.float32)
              for _ in range(2))
    wd = 0.1 * rng.normal(size=(E, F, H)).astype(np.float32)
    assert (ids == 5).sum() == 0 and (ids == 2).sum() == 30
    return x, ids, wts, wg, wu, wd


def test_layout_groups_every_assignment_by_expert(uneven):
    _, ids, *_ = uneven
    tm = 16
    dest, src, te, n_used, rows = (np.asarray(a) for a in fused.moe_layout(
        jnp.asarray(ids), 8, tm))
    assert len(set(dest.ravel().tolist())) == ids.size     # none dropped
    assert np.array_equal(src[dest], np.arange(ids.size).reshape(
        ids.shape) // ids.shape[1])
    assert np.array_equal(te[dest // tm], ids)     # a tile is one expert's
    assert rows[5] == 0 and rows[2] == 32 and rows.sum() == n_used * tm
    assert (rows % tm == 0).all() and te.shape == (src.shape[0] // tm,)


@pytest.mark.parametrize("path", ["composite", "kernel_interpret"])
def test_dropless_experts_match_the_loop_over_experts(uneven, path,
                                                      monkeypatch):
    x, ids, wts, wg, wu, wd = uneven
    calls = []
    if path == "kernel_interpret":
        real = mg.moe_gmm
        monkeypatch.setattr(fused, "_use_pallas", lambda: True)
        monkeypatch.setattr(mg, "moe_gmm", lambda *a, **k: (
            calls.append(1), real(*a, interpret=True))[1])
    got = fused.moe_dropless(*(jnp.asarray(a) for a in uneven))
    assert bool(calls) == (path == "kernel_interpret")
    want = loop_over_experts(x, ids, wts, wg, wu, wd)
    # float32 products summed in another order: outputs are O(1)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-4)


def test_kernel_refuses_by_shape_and_the_refusal_is_counted(uneven,
                                                            monkeypatch):
    x, ids, wts, wg, wu, wd = (jnp.asarray(a) for a in uneven)
    with pytest.raises(mg.DoesNotTile):
        mg.moe_gmm(x[:, :64], wg[:, :64], wu[:, :64], wd[:, :, :64],
                   jnp.zeros((3,), jnp.int32), 1, 16, interpret=True)
    monkeypatch.setattr(fused, "_use_pallas", lambda: True)
    before = sum(fused.fallback_counter().values.values())
    with pytest.warns(RuntimeWarning, match="moe_gmm"):
        fused.moe_dropless(x[:, :64], ids, wts, wg[:, :64], wu[:, :64],
                           wd[:, :, :64])
    assert sum(fused.fallback_counter().values.values()) == before + 1


def test_the_layer_counts_only_the_rows_it_is_told_to():
    from paddle_tpu.nn.layer.moe import DroplessMoE

    paddle.seed(0)
    layer = DroplessMoE(128, 128, 8, 3)
    layer.eval()
    x = paddle.to_tensor(np.random.default_rng(0).normal(
        size=(2, 4, 128)).astype(np.float32))
    live = jnp.asarray([[True] * 4, [False] * 4])
    y, per, touched = layer(x, live)
    assert np.asarray(per.value).sum() == 4 * 3
    assert int(touched.value) == (np.asarray(per.value) > 0).sum()
    np.testing.assert_allclose(np.asarray(y.value)[0],
                               np.asarray(layer(x).value)[0])
    # the rows it is not told to count fetch no expert of their own: all
    # of the layer's rows together touch what the counted rows touch
    _, per_all, touched_all = layer(x, jnp.ones((2, 4), bool))
    assert int(touched_all.value) > int(touched.value)
    _, per_dead_first, _ = layer(
        x, jnp.asarray([[False] * 4, [True] + [False] * 3]))
    assert np.asarray(per_dead_first.value).sum() == 3


# -- grouped heads through the KV sources ------------------------------------
def ref_attention(q, k, v, mask):
    """q [T, nq, hd], k/v [T, nkv, hd]; head h reads KV head h // g."""
    g = q.shape[1] // k.shape[1]
    k, v = np.repeat(k, g, 1), np.repeat(v, g, 1)
    s = np.einsum("qnd,knd->nqk", q, k) / np.sqrt(q.shape[-1])
    s = np.where(mask[None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    return np.einsum("nqk,knd->qnd", p / p.sum(-1, keepdims=True), v)


def test_paged_source_reads_grouped_heads_to_the_blocks_end():
    rng = np.random.default_rng(2)
    nq, nkv, hd, ps, T = 4, 2, 16, 8, 12            # prefix 8, a block of 4
    q, k, v = (rng.normal(size=(T, n, hd)).astype(np.float32)
               for n in (nq, nkv, nkv))
    geom = CacheGeometry(num_layers=1, max_slots=2, max_seq_len=16,
                         num_heads=nq, num_kv_heads=nkv, head_dim=hd,
                         vocab_size=8, page_size=ps, block_length=4)
    assert geom.pool_shape == (1, 4, ps, nkv, hd)
    assert geom.page_bytes() == 2 * ps * nkv * hd * 4
    st = kv_cache.make_state(geom)
    kp = st["kp"].at[0, 3].set(k[:8])               # lane 1's first page
    vp = st["vp"].at[0, 3].set(v[:8])
    rows = jnp.asarray([[-1, -1], [3, 1]], jnp.int32)
    P = jnp.asarray([[0, 1, 2, 3], [8, 9, 10, 11]], jnp.int32)
    src = PagedKV(kp, vp, rows, P, jnp.asarray([False, True]), 16,
                  limits=jnp.full((2, 4), 11, jnp.int32))
    pad = np.zeros((4, nq, hd), np.float32)
    ctx, src = src.attend(
        0, jnp.asarray(np.stack([pad, q[8:]])),
        jnp.asarray(np.stack([pad[:, :nkv], k[8:]])),
        jnp.asarray(np.stack([pad[:, :nkv], v[8:]])))
    want = ref_attention(q, k, v, np.ones((T, T), bool))[8:]
    np.testing.assert_allclose(np.asarray(ctx)[1], want, atol=1e-5)
    # the block's K/V went to the lane's second page; lane 0 wrote nothing
    np.testing.assert_array_equal(np.asarray(src.k_pages)[0, 1, :4], k[8:])
    assert not np.asarray(src.k_pages)[0, [0, 2]].any()


# -- a block step's queries through the paged walk ---------------------------
# `PagedKV` with ONE last key a lane (`limits` [slots], what `block_step`
# hands) reads the lane's mapped pages through the paged kernel, the C x g
# queries of a KV head as rows of one product; interpreted here.  A lane is
# (the block's start, active, mapped, pages): `pages` are the lane's table
# row, None leaving it unmapped.
WALK_NQ, WALK_NKV, WALK_HD, WALK_PS, WALK_B, WALK_CAP = 8, 2, 16, 8, 4, 48
WALK_CASES = {
    # extents of one page, three pages and the table's whole width
    "lanes_of_different_extents": [(4, True, [5, -1, -1, -1, -1, -1]),
                                   (20, True, [9, 2, 7, -1, -1, -1]),
                                   (44, True, [1, 3, 4, 6, 8, 10])],
    # the block's end in the middle of its page, and on its last key
    "an_extent_that_ends_mid_page": [(8, True, [11, 12, -1, -1, -1, -1]),
                                     (12, True, [13, 14, -1, -1, -1, -1])],
    # neither writes; the unmapped one walks nothing and reads zero
    "an_inactive_lane_and_an_unmapped_row": [
        (16, False, [5, 6, 7, -1, -1, -1]),
        (8, True, [2, 3, -1, -1, -1, -1]),
        (0, False, None)],
    # no committed prefix at all: the block sees itself alone
    "a_block_in_the_first_page": [(0, True, [4, -1, -1, -1, -1, -1]),
                                  (4, True, [9, -1, -1, -1, -1, -1])],
}


def walk_source(lanes, seed, limits="lane"):
    """A pool of random keys, the lanes' tables, and each lane's block:
    (source, q, k, v) with `limits` one a lane, one a query (the same
    values, broadcast) or None."""
    rng = np.random.default_rng(seed)
    n, C = len(lanes), WALK_B
    pool = (1, 16, WALK_PS, WALK_NKV, WALK_HD)
    kp, vp = (jnp.asarray(rng.normal(size=pool).astype(np.float32))
              for _ in range(2))
    q, k, v = (jnp.asarray(rng.normal(size=(n, C, h, WALK_HD)).astype(
        np.float32)) for h in (WALK_NQ, WALK_NKV, WALK_NKV))
    rows = jnp.asarray([r or [-1] * 6 for _, _, r in lanes], jnp.int32)
    start = jnp.asarray([s for s, _, _ in lanes], jnp.int32)
    P = start[:, None] + jnp.arange(C, dtype=jnp.int32)[None]
    last = start + C - 1
    lim = {"lane": last, "query": jnp.broadcast_to(last[:, None], P.shape),
           None: None}[limits]
    src = PagedKV(kp, vp, rows, P, jnp.asarray([a for _, a, _ in lanes]),
                  WALK_CAP, limits=lim)
    return src, q, k, v


def lane_reference(kp, vp, q, start, row, mask):
    """`ref_attention` of one lane's block of queries `q` at positions
    `start...` over the keys its table `row` maps in planes `kp`/`vp`;
    `mask(pos)` gives the [T, T] mask from the positions 0..T-1.  Returns
    (context of the block's queries, the lane's keys)."""
    T = start + WALK_B
    pages = [p for p in row if p >= 0][:-(-T // WALK_PS)]
    keys, vals = (a[pages].reshape(-1, WALK_NKV, WALK_HD)[:T]
                  for a in (kp, vp))
    qs = np.zeros((T, WALK_NQ, WALK_HD), np.float32)
    qs[start:] = q
    return ref_attention(qs, keys, vals, mask(np.arange(T)))[start:], keys


def everything(pos):
    return np.ones((len(pos), len(pos)), bool)


@pytest.fixture
def paged_calls(monkeypatch):
    """The kernels on (interpreted), and every call of the paged kernel's
    wrapper recorded by its query's shape."""
    from paddle_tpu.ops.pallas import paged_attention as pa

    calls, real = [], pa.paged_gqa_decode_attention

    def spy(q, *a, **kw):
        calls.append(q.shape)
        return real(q, *a, **kw)

    monkeypatch.setattr(fused, "_use_pallas", lambda: True)
    monkeypatch.setattr(pa, "paged_gqa_decode_attention", spy)
    return calls


@pytest.mark.parametrize("case", list(WALK_CASES))
def test_block_queries_walk_the_lanes_pages_and_equal_the_gather(
        case, paged_calls, monkeypatch):
    lanes = WALK_CASES[case]
    src, q, k, v = walk_source(lanes, seed=len(case))
    before = sum(fused.fallback_counter().values.values())
    ctx, out = src.attend(0, q, k, v)
    assert paged_calls == [(len(lanes), WALK_B, WALK_NQ, WALK_HD)]
    assert sum(fused.fallback_counter().values.values()) == before
    monkeypatch.setattr(fused, "_use_pallas", lambda: False)
    dense, dense_out = src.attend(0, q, k, v)
    # the same writes: the block's K/V at its own positions, active lanes'
    np.testing.assert_array_equal(np.asarray(out.k_pages),
                                  np.asarray(dense_out.k_pages))
    np.testing.assert_array_equal(np.asarray(out.v_pages),
                                  np.asarray(dense_out.v_pages))
    kp, vp = np.asarray(out.k_pages)[0], np.asarray(out.v_pages)[0]
    for i, (start, active, row) in enumerate(lanes):
        got = np.asarray(ctx)[i]
        if row is None:
            assert not got.any()
            continue
        np.testing.assert_allclose(got, np.asarray(dense)[i], atol=2e-5)
        # and the plain reference over the lane's own keys, every query
        # seeing all of them to the block's end
        want, keys = lane_reference(kp, vp, np.asarray(q)[i], start, row,
                                    everything)
        np.testing.assert_allclose(got, want, atol=2e-5)
        if active:
            np.testing.assert_array_equal(keys[start:], np.asarray(k)[i])
    written = (np.asarray(out.k_pages) != np.asarray(src.k_pages)).any(
        axis=(0, 2, 3, 4))
    assert set(np.flatnonzero(written)) == {
        row[start // WALK_PS] for start, active, row in lanes if active}


@pytest.mark.parametrize("form", ["a_limit_a_query", "no_limits_causal",
                                  "a_window_layer"])
def test_other_chunks_keep_the_gather_and_count_no_fallback(form,
                                                            paged_calls):
    """What `spec_step` sends (a last key a QUERY: causal inside the
    chunk), `limits` [slots, C], and a chunk on a window layer gather as
    they did, by design: the kernel is not asked, and nothing is counted
    as a fallback."""
    lanes = WALK_CASES["lanes_of_different_extents"]
    src, q, k, v = walk_source(
        lanes, seed=9, limits={"a_limit_a_query": "query",
                               "no_limits_causal": None,
                               "a_window_layer": "lane"}[form])
    window = 0
    if form == "a_window_layer":
        from dataclasses import replace

        window = 16
        src = replace(src, wk_pages=src.k_pages, wv_pages=src.v_pages,
                      wrows=src.rows, windows=(window,))
    before = sum(fused.fallback_counter().values.values())
    ctx, out = src.attend(0, q, k, v)
    assert paged_calls == []
    assert sum(fused.fallback_counter().values.values()) == before
    kp, vp = (np.asarray(a)[0] for a in (
        (out.wk_pages, out.wv_pages) if window else
        (out.k_pages, out.v_pages)))

    def mask(pos):
        m = everything(pos) if form != "no_limits_causal" \
            else pos[None] <= pos[:, None]
        return m & (pos[None] > pos[:, None] - window) if window else m

    for i, (start, _, row) in enumerate(lanes):
        want, _ = lane_reference(kp, vp, np.asarray(q)[i], start, row, mask)
        np.testing.assert_allclose(np.asarray(ctx)[i], want, atol=2e-5)


def test_prefix_source_masks_the_suffix_by_blocks():
    rng = np.random.default_rng(3)
    nq, nkv, hd, T = 4, 2, 16, 16                    # prefix 8, suffix 8
    q, k, v = (rng.normal(size=(T, n, hd)).astype(np.float32)
               for n in (nq, nkv, nkv))
    pool_k = jnp.asarray(k[:8].reshape(1, 1, 8, nkv, hd))
    pool_v = jnp.asarray(v[:8].reshape(1, 1, 8, nkv, hd))
    src = PrefixKV.gather(pool_k, pool_v, jnp.asarray([0, -1]), 8, block=4)
    ctx, src = src.attend(0, jnp.asarray(q[None, 8:]),
                          jnp.asarray(k[None, 8:]), jnp.asarray(v[None, 8:]))
    blk = np.arange(T) // 4
    want = ref_attention(q, k, v, blk[None, :] <= blk[:, None])[8:]
    np.testing.assert_allclose(np.asarray(ctx)[0], want, atol=1e-5)
    assert src.suffix_kv()[0].shape == (1, 8, nkv, hd)


def test_geometry_refuses_heads_and_blocks_that_do_not_divide():
    kw = dict(num_layers=1, max_slots=1, max_seq_len=16, head_dim=8,
              vocab_size=8, page_size=8)
    with pytest.raises(ValueError, match="KV heads"):
        CacheGeometry(num_heads=6, num_kv_heads=4, **kw)
    with pytest.raises(ValueError, match="block length"):
        CacheGeometry(num_heads=4, block_length=3, **kw)


# -- prefill and blocks through the page pool, in logits ----------------------
def test_pool_path_logits_match_the_references_full_forward(tiny):
    """A prompt of 11 (two whole blocks and a tail of 3) prefilled into a
    page pool, then its open block [8, 12) with one mask left run over
    the pool: the logits of the reference's full forward."""
    from paddle_tpu.nn.layer_base import functional_call, state_pytrees

    w, net = tiny
    params, buffers = state_pytrees(net)
    ids = prompt_of(11)
    padded = np.zeros((1, 16), np.int32)
    padded[0, :11] = ids
    (k, v, _), _ = functional_call(
        net, params, (paddle.Tensor(jnp.asarray(padded)), jnp.int32(11)),
        buffers=buffers, mutable=False, method="slot_prefill")
    geom = CacheGeometry(num_layers=2, max_slots=1, max_seq_len=16,
                         num_heads=4, num_kv_heads=2, head_dim=32,
                         vocab_size=256, page_size=8, block_length=4)
    st, _ = kv_cache.write_prompt(
        kv_cache.make_state(geom), 0, k, v, 11,
        jnp.full((2,), -1, jnp.int32), 0)
    block = np.append(ids[8:], CFG["mask_token_id"]).astype(np.int32)
    P = jnp.arange(8, 12, dtype=jnp.int32)[None]
    (lg, _), _ = functional_call(
        net, params,
        (jnp.asarray(block[None]), P,
         PagedKV(st["kp"], st["vp"], st["ptab"], P, jnp.asarray([True]), 16,
                 limits=jnp.full((1, 4), 11, jnp.int32))),
        buffers=buffers, mutable=False, method="slot_step")
    want = ref.logits_at(w, jnp.asarray(np.append(ids, block[-1])),
                         jnp.arange(8, 12), CFG)
    np.testing.assert_allclose(np.asarray(lg)[0], np.asarray(want),
                               atol=1e-5)


# -- the engine, end to end --------------------------------------------------
def engine_for(net, **kw):
    args = dict(max_slots=4, max_seq_len=96, prompt_buckets=[16, 32],
                page_size=8, prefix_cache=True)
    args.update(kw)
    return GenerationEngine(net, **args).start()


def kernel_engine_for(net, **kw):
    """`engine_for` with the Pallas kernels on (interpreted here) while
    `start()` traces and builds every executable; none is traced later."""
    real = fused._use_pallas
    fused._use_pallas = lambda: True
    try:
        return engine_for(net, **kw)
    finally:
        fused._use_pallas = real


@pytest.fixture(scope="module", params=["composite", "kernels"])
def engine(tiny, request):
    """The engine every way it is built: XLA's composites (what the CPU
    takes), and the kernels, where a block step's attention is the paged
    walk and its experts the grouped product."""
    eng = (kernel_engine_for if request.param == "kernels"
           else engine_for)(tiny[1])
    yield eng
    eng.stop()


@pytest.mark.parametrize("L,n", [(8, 4), (8, 5), (11, 4), (11, 5), (11, 64),
                                 (3, 6), (16, 64)])
def test_served_tokens_and_steps_equal_the_published_procedure(
        tiny, engine, L, n):
    prompt = prompt_of(L, seed=L * 100 + n)
    h = engine.submit(prompt, n)
    want_t, want_s, _ = ref.block_diffusion_generate(
        tiny[0], prompt.tolist(), CFG, n)
    assert h.result(120) == want_t and len(want_t) == n
    assert h.steps == want_s


def test_eos_inside_a_block_cuts_the_stream_there(tiny, engine):
    prompt = prompt_of(9, seed=5)
    free, _, _ = ref.block_diffusion_generate(tiny[0], prompt.tolist(), CFG,
                                              24)
    eos = free[6]                      # third token of the second block
    want_t, want_s, _ = ref.block_diffusion_generate(
        tiny[0], prompt.tolist(), CFG, 24, eos=eos)
    assert want_t[-1] == eos and len(want_t) <= 7
    h = engine.submit(prompt, 24, eos_token_id=eos)
    assert h.result(120) == want_t and h.steps == want_s
    assert h.done


def test_two_lanes_admitted_in_different_iterations(tiny, engine):
    pa, pb = prompt_of(13, seed=7), prompt_of(10, seed=8)
    ha = engine.submit(pa, 40)
    while not ha.tokens:               # a is past its first blocks
        pass
    hb = engine.submit(pb, 24)
    for h, p, n in ((ha, pa, 40), (hb, pb, 24)):
        want_t, want_s, _ = ref.block_diffusion_generate(
            tiny[0], p.tolist(), CFG, n)
        assert h.result(120) == want_t and h.steps == want_s


# -- one step in flight: block_step k+1 is launched before k is handed out ----
@pytest.mark.parametrize("device_ms", [0, 6])
def test_sixteen_staggered_requests_equal_the_published_procedure(
        tiny, slow_steps, device_ms):
    """Unequal prompts and lengths, four times the slots, admitted while
    others are mid-block; with steps that outlast the host's part too."""
    import time

    eng = engine_for(tiny[1])
    try:
        if device_ms:
            slow_steps(eng, device_ms / 1e3)
        jobs = []
        for i in range(16):
            p, n = prompt_of(3 + (7 * i) % 14, seed=40 + i), 4 + (5 * i) % 23
            jobs.append((eng.submit(p, n), p, n))
            time.sleep(0.004)
        for h, p, n in jobs:
            want_t, want_s, _ = ref.block_diffusion_generate(
                tiny[0], p.tolist(), CFG, n)
            assert h.result(120) == want_t and h.steps == want_s
        assert eng.drain(timeout=60) and eng._flight is None
        snap = eng.metrics.snapshot()
        assert snap["retired"] == 16
        assert snap["block_steps"] == snap["steps"] == eng._iter
    finally:
        eng.stop()


@pytest.mark.parametrize("ending", ["max_new_tokens", "eos"])
def test_an_ended_lanes_slot_takes_nothing_of_the_block_step_after(
        tiny, slow_steps, ending):
    """One slot: A's last block is committed in step k with step k+1 out
    already; B takes A's slot before k+1 is collected.  Neither reads it."""
    pa, pb = prompt_of(9, seed=5), prompt_of(6, seed=6)
    free, _, _ = ref.block_diffusion_generate(tiny[0], pa.tolist(), CFG, 24)
    kw = {"eos_token_id": free[6]} if ending == "eos" else {}
    n_a = 24 if kw else 10
    want_a = ref.block_diffusion_generate(tiny[0], pa.tolist(), CFG, n_a,
                                          eos=kw.get("eos_token_id"))
    want_b = ref.block_diffusion_generate(tiny[0], pb.tolist(), CFG, 7)
    eng = engine_for(tiny[1], max_slots=1)
    try:
        slow_steps(eng)
        a, b = eng.submit(pa, n_a, **kw), eng.submit(pb, 7)
        assert a.result(120) == want_a[0] and a.steps == want_a[1]
        assert b.result(120) == want_b[0] and b.steps == want_b[1]
        assert eng.drain(timeout=60)
        snap = eng.metrics.snapshot()
        assert snap["empty_steps"] == 2 and snap["retired"] == 2
        assert snap["steps_launched_ahead"] / snap["steps"] > 0.8
    finally:
        eng.stop()


def test_dynamic_strategy_follows_the_reference():
    cfg = dict(CFG, remasking_strategy="low_confidence_dynamic",
               confidence_threshold=0.012)
    w, net = build(cfg, seed=4)
    eng = engine_for(net, max_slots=2)
    try:
        passes = []
        for L, n in ((8, 24), (11, 17)):
            prompt = prompt_of(L, seed=L)
            want_t, want_s, p = ref.block_diffusion_generate(
                w, prompt.tolist(), cfg, n)
            h = eng.submit(prompt, n)
            assert h.result(120) == want_t and h.steps == want_s
            passes += p
        # the threshold is passed by several positions of some steps (a
        # block then takes fewer passes than T + 1) and by none of others
        assert min(passes) < 5 and max(passes) == 5
    finally:
        eng.stop()


def test_prefix_hit_and_chunked_prefill_serve_what_a_whole_prefill_serves(
        tiny):
    """Pages of 8 are two blocks: a shared prefix ends on a block's end,
    so the suffix pass under the block mask (prefix-cache hit) and the
    chunked prefill write the K/V of a whole prefill, and every later
    block reads the same logits: the tokens and steps do not move."""
    w, net = tiny
    shared = prompt_of(16, seed=21)
    a = np.concatenate([shared, prompt_of(7, seed=22)])
    b = np.concatenate([shared, prompt_of(13, seed=23)])
    want = {n: ref.block_diffusion_generate(w, p.tolist(), CFG, 12)[:2]
            for n, p in (("a", a), ("b", b))}
    for kw in (dict(), dict(prefill_chunk=8)):
        eng = engine_for(net, **kw)
        try:
            for n, p in (("a", a), ("b", b), ("a", a)):
                h = eng.submit(p, 12)
                assert (h.result(120), h.steps) == want[n], (kw, n)
            snap = eng.metrics.snapshot()
            assert snap["prefix_cache_hits"] == 2
            assert (snap["prefill_chunks"] > 0) == bool(kw)
        finally:
            eng.stop()


def test_counters_and_gaps_say_what_a_client_sees(tiny):
    eng = engine_for(tiny[1], max_slots=1)
    try:
        eng.generate(prompt_of(8), 16, timeout=120)     # four whole blocks
        assert eng.drain(timeout=60)
        snap = eng.metrics.snapshot()
        # T + 1 a block, and the step launched before the host knew the
        # last block had been committed, which ran with no lane armed
        assert snap["block_steps"] == 21 and snap["empty_steps"] == 1
        assert snap["block_lane_steps_denoised"] == 16
        assert snap["block_lane_steps_committed"] == 4
        assert snap["block_tokens_emitted"] == 16
        gaps = sorted(eng.metrics._gaps.values)
        assert len(gaps) == 15 and gaps[11] == 0.0 and gaps[12] > 0.0
        counts = eng.expert_counts()
        assert counts["assignments"].shape == (2, 8)
        assert counts["assignments"].sum() == 20 * 4 * 3 * 2
        assert (counts["touched"] <= 20 * 8).all()
        text = eng.metrics.prometheus_text()
        assert "paddle_genserve_block_steps_total 21" in text
        assert 'paddle_genserve_block_lane_steps_total{kind="committed"} 4' \
            in text
    finally:
        eng.stop()


def test_block_steps_feed_the_page_walk_counters_and_nothing_falls_back(
        tiny):
    """`paged_pages_live / paged_page_slots` is the share of the page
    tables that a block step's paged kernel walks, from the host's own
    bookkeeping: a lane attends to its block's end in every pass of the
    block (one a mask and the committing one), over the pages up to that
    one.  With the kernels on, a served request counts no fallback."""
    B, ps, layers, slots, cap = 4, 8, 2, 2, 96
    before = sum(fused.fallback_counter().values.values())
    eng = kernel_engine_for(tiny[1], max_slots=slots)
    try:
        asked = [(prompt_of(11, seed=1), 14), (prompt_of(8, seed=2), 32),
                 (prompt_of(17, seed=3), 5)]
        hs = [eng.submit(p, n) for p, n in asked]
        for h, (p, n) in zip(hs, asked):
            want_t, want_s, _ = ref.block_diffusion_generate(
                tiny[0], p.tolist(), CFG, n)
            assert h.result(120) == want_t and h.steps == want_s
        assert eng.drain(timeout=60)
        snap = eng.metrics.snapshot()
        live = 0
        for p, n in asked:
            L = len(p)
            for start in range(L // B * B, L + n, B):
                # the prompt's tail is known: a pass a mask left, and one
                passes = B - max(L - start, 0) + 1
                live += passes * ((start + B - 1) // ps + 1)
        assert snap["paged_pages_live"] == layers * live
        assert snap["paged_page_slots"] \
            == snap["steps"] * layers * slots * (cap // ps)
        assert 0 < snap["paged_pages_live"] < snap["paged_page_slots"]
        text = eng.metrics.prometheus_text()
        assert "paddle_genserve_paged_page_slots_total " \
            f"{snap['paged_page_slots']}" in text
        assert "paddle_genserve_paged_pages_live_total " \
            f"{snap['paged_pages_live']}" in text
        assert sum(fused.fallback_counter().values.values()) == before
    finally:
        eng.stop()


def test_a_block_engine_refuses_what_it_does_not_serve(tiny, engine):
    with pytest.raises(ValueError, match="greedy"):
        engine.submit(prompt_of(5), 4, do_sample=True)
    with pytest.raises(ValueError, match="block length"):
        GenerationEngine(tiny[1], max_slots=1, max_seq_len=30,
                         prompt_buckets=[8], page_size=8)


def test_import_paddle_tpu_loads_neither_the_model_nor_the_kernel():
    code = ("import sys, paddle_tpu; "
            "bad = [m for m in sys.modules if m.endswith(('models.sdar', "
            "'pallas.moe_gmm'))]; print(bad); sys.exit(bool(bad))")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env={**__import__("os").environ,
                                         "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stdout + out.stderr


def test_flash_attention_raises_on_heads_it_was_not_given_expanded():
    """Grouped heads are expanded by the caller (the prompt pass above) or
    it is an error: never a silent composite."""
    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    q = jnp.zeros((1, 16, 4, 8))
    kv = jnp.zeros((1, 16, 2, 8))
    with pytest.raises(ValueError, match="expand grouped KV heads"):
        flash_attention(q, kv, kv, causal=True, interpret=True)


def test_every_leaf_is_drawn_from_the_seed_and_a_layer_can_be_made_alone():
    """Another seed is another draw of every leaf (no value is shared), and
    the chip's check can make one layer without the others."""
    a = ref.init_weights(ref.key_from_seed(1), CFG)
    b = ref.init_weights(ref.key_from_seed(2), CFG)
    assert set(a) == set(b)
    for name in a:
        assert not np.array_equal(a[name], b[name]), name
    again = ref.init_weights(ref.key_from_seed(1), CFG)
    alone = ref.layer_weights(ref.key_from_seed(1), CFG, 1)
    for name in a:
        assert np.array_equal(a[name], again[name])
    for name, leaf in alone.items():
        assert np.array_equal(a[f"h1.{name}"], leaf)
