"""Mellum (window and full layers 3:1, two rotary laws, routed experts)
served one token a lane over two page pools, at `tiny-mellum` sizes with
seeded weights, against the plain float32 reference
(`benchmarks/reference/mellum.py`, which imports nothing of the program):
the model's forward pass, the two rotary laws, the engine end to end
(whole prefill, prefix hits deeper and shallower than the window, chunked
prefill across the window), and the window pool's invariants."""
import json
import math
import os
import subprocess
import sys
import time

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmarks.adapters import mellum as adapter
from benchmarks.reference import mellum as ref
from paddle_tpu.serving import GenerationEngine
from paddle_tpu.serving.kv_cache import CacheGeometry, make_state

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(_REPO, "benchmarks", "configs",
                       "tiny-mellum.json")) as f:
    CFG = json.load(f)["model"]
W, PAGE = CFG["sliding_window"], 16          # 32 tokens, two pages


def build(seed=5, dtype="float32"):
    w = ref.init_weights(ref.key_from_seed(seed), CFG, jnp.dtype(dtype))
    net = adapter.build_network(CFG, w, dtype)
    net.eval()
    return w, net


@pytest.fixture(scope="module")
def tiny():
    return build()


def engine_of(net, **kw):
    kw = {**dict(max_slots=4, max_seq_len=384, page_size=PAGE,
                 prompt_buckets=[32, 64, 128, 256], prefix_cache=True), **kw}
    return GenerationEngine(net, **kw).start()


@pytest.fixture(scope="module")
def engine(tiny):
    eng = engine_of(tiny[1])
    yield eng
    eng.stop()


def prompt_of(n, seed=0):
    return np.random.default_rng(seed).integers(0, 500, (n,)).astype(np.int32)


def served_gap(w, prompt, toks, cfg=CFG):
    """How far each served token's reference logit lies below the
    reference's best at its position (0 where the program and the float32
    reference agree on the token), and the program's agreement count."""
    seq = np.concatenate([prompt, toks[:-1]]).astype(np.int32)
    lg = np.asarray(ref.logits_at(
        w, jnp.asarray(seq), jnp.arange(len(prompt) - 1, len(seq)), cfg))
    got = lg[np.arange(len(toks)), np.asarray(toks)]
    return float((lg.max(-1) - got).max()), int((lg.argmax(-1) == toks).sum())


# -- the model ---------------------------------------------------------------
def test_forward_matches_the_reference(tiny):
    w, net = tiny
    ids = prompt_of(150)
    want = ref.logits_at(w, jnp.asarray(ids), jnp.arange(150), CFG)
    got = np.asarray(net(paddle.to_tensor(ids[None])).value)[0]
    # float32 both sides, the same formulas in another order of sums (the
    # program's attention goes by blocks of queries over grouped heads):
    # logits are O(1), so 2e-5 is a few ulps of accumulated rounding, and
    # bfloat16 in place of float32 reads 1e-2
    np.testing.assert_allclose(got, np.asarray(want), atol=2e-5)


def test_the_window_and_the_rotary_laws_are_in_the_forward_pass(tiny):
    """The reference with the window left out, and with the default law on
    the full layers, is another function: the agreement above is not blind
    to either."""
    w, net = tiny
    ids = prompt_of(150)
    got = np.asarray(net(paddle.to_tensor(ids[None])).value)[0]
    no_window = dict(CFG, sliding_window=10 ** 6)
    rp = CFG["rope_parameters"]
    no_yarn = dict(CFG, rope_parameters=dict(
        rp, full_attention=rp["sliding_attention"]))
    for other in (no_window, no_yarn):
        lg = np.asarray(ref.logits_at(w, jnp.asarray(ids), jnp.arange(150),
                                      other))
        assert np.abs(lg - got).max() > 1e-2
        # before the window is full and YaRN's frequencies part, the same
    lg = np.asarray(ref.logits_at(w, jnp.asarray(ids), jnp.arange(W),
                                  no_window))
    np.testing.assert_allclose(got[:W], lg, atol=2e-5)


@pytest.mark.parametrize("kind", ["sliding_attention", "full_attention"])
def test_rotary_law_against_the_written_formula(kind):
    """Both laws as ISSUE 34 writes them, in float64, at positions past
    `original_max_position_embeddings`; the program's float32 rotation of a
    unit vector agrees to float32's rounding of an angle of a few hundred
    radians (1e-4), where the other law is 0.1 and more away."""
    from paddle_tpu.models.mellum import MellumAttention

    hd, theta = CFG["head_dim"], 500000.0
    p = CFG["rope_parameters"]["full_attention"]
    d = np.arange(hd // 2, dtype=np.float64)
    e = theta ** (-2 * d / hd)
    if kind == "full_attention":
        def c(r):
            return hd * math.log(p["original_max_position_embeddings"]
                                 / (2 * math.pi * r)) / (2 * math.log(theta))
        low = max(math.floor(c(p["beta_fast"])), 0)
        high = min(math.ceil(c(p["beta_slow"])), hd - 1)
        ramp = np.clip((d - low) / (high - low), 0, 1)
        inv, scale = e / p["factor"] * ramp + e * (1 - ramp), \
            p["attention_factor"]
        assert scale == pytest.approx(0.1 * math.log(p["factor"]) + 1)
    else:
        inv, scale = e, 1.0
    pos = np.array([[0, 1, 63, 64, 65, 200, 383]])
    x = np.random.default_rng(1).normal(size=(1, 7, 2, hd))
    ang = pos[0][:, None] * inv
    cos, sin = (np.concatenate([f(ang)] * 2, -1)[None, :, None] * scale
                for f in (np.cos, np.sin))
    want = x * cos + np.concatenate([-x[..., hd // 2:], x[..., :hd // 2]],
                                    -1) * sin
    attn = MellumAttention(adapter.program_config(CFG), kind)
    got = np.asarray(attn.rotary(jnp.asarray(x, jnp.float32),
                                 jnp.asarray(pos)))
    np.testing.assert_allclose(got, want, atol=2e-4)
    other = MellumAttention(
        adapter.program_config(CFG),
        "sliding_attention" if kind == "full_attention"
        else "full_attention")
    assert np.abs(np.asarray(other.rotary(
        jnp.asarray(x, jnp.float32), jnp.asarray(pos))) - want).max() > 0.1


# -- the engine against the reference ----------------------------------------
# Float32 weights, float32 pools: the served token is the reference's best
# at every position unless two logits tie to 2e-5 (the forward pass's
# tolerance above); a wrong mask, window, rotary law or stale page moves
# logits by 1e-2 and more (the test above), and bfloat16 weights read gaps
# of 1e-3 to 1e-2 at this size (test_bfloat16_is_told_apart).
GAP = 5e-5


def test_whole_prefill_then_decode_through_both_pools(tiny, engine):
    w, _ = tiny
    prompt = prompt_of(150, seed=1)
    toks = engine.generate(prompt, 110, timeout=300)
    gap, equal = served_gap(w, prompt, toks)
    assert gap <= GAP and equal >= 109, (gap, equal)


@pytest.mark.parametrize("shared,own", [(96, 40), (16, 60)],
                         ids=["deeper_than_window", "shallower_than_window"])
def test_prefix_hit_then_decode(tiny, engine, shared, own):
    """A hit `shared` tokens deep (three windows; half a window): the
    suffix pass reads the window layers' K/V of the last window before the
    hit point from the shared window pages."""
    w, _ = tiny
    base = prompt_of(150, seed=10 + shared)
    engine.generate(base, 4, timeout=300)                      # registers
    hits = engine.metrics.snapshot()["prefix_cache_hits"]
    prompt = np.concatenate([base[:shared], prompt_of(own, seed=shared)])
    toks = engine.generate(prompt, 110, timeout=300)
    assert engine.metrics.snapshot()["prefix_cache_hits"] == hits + 1
    gap, equal = served_gap(w, prompt, toks)
    assert gap <= GAP and equal >= 109, (gap, equal)


def test_chunked_prefill_across_the_window(tiny):
    """Chunks of one page: the prompt crosses the window four times, each
    chunk reading the last window from the lane's own pages and letting go
    of what fell behind."""
    w, net = tiny
    eng = engine_of(net, prefill_chunk=PAGE, prefix_cache=False)
    try:
        prompt = prompt_of(150, seed=3)
        toks = eng.generate(prompt, 110, timeout=300)
        snap = eng.metrics.snapshot()
        assert snap["prefill_chunks"] == 10
        gap, equal = served_gap(w, prompt, toks)
        assert gap <= GAP and equal >= 109, (gap, equal)
        # chunks let pages go as the prompt passed them, steps the rest
        assert snap["kv_window_pages_released"] >= (150 + 110 - W) // PAGE - 1
        assert eng.drain(timeout=60)
        st = eng._state
        assert int(st["wfree_count"]) == eng.geometry.window_pages
        assert int(st["free_count"]) == eng.geometry.num_pages
    finally:
        eng.stop()


def test_whole_hit_and_chunked_prefill_give_the_same_logits(tiny):
    """(c): the first decoded token's logits after a whole prefill, after a
    prefix hit and after a chunked prefill of the same prompt agree up to
    float reassociation (1e-5 on logits of O(1)); read as the reference's
    logit of each path's first 24 served tokens, which are the same
    tokens."""
    w, net = tiny
    prompt = prompt_of(140, seed=4)
    whole = engine_of(net, prefix_cache=False)
    chunked = engine_of(net, prefix_cache=False, prefill_chunk=2 * PAGE)
    hit = engine_of(net)
    try:
        a = whole.generate(prompt, 24, timeout=300)
        b = chunked.generate(prompt, 24, timeout=300)
        hit.generate(np.concatenate([prompt[:112], prompt_of(9, 99)]), 2,
                     timeout=300)
        c = hit.generate(prompt, 24, timeout=300)
        assert hit.metrics.snapshot()["prefix_cache_hits"] == 1
        assert a == b == c
        assert served_gap(w, prompt, a)[0] <= GAP
    finally:
        for e in (whole, chunked, hit):
            e.stop()


def test_bfloat16_is_told_apart(tiny):
    """The tolerance is tight enough: the same engine on bfloat16 weights
    serves tokens whose float32 reference logit lies further below the best
    than GAP allows."""
    w, _ = tiny
    _, net16 = build(dtype="bfloat16")
    eng = engine_of(net16)
    try:
        prompt = prompt_of(150, seed=1)
        toks = eng.generate(prompt, 110, timeout=300)
        assert served_gap(w, prompt, toks)[0] > GAP
    finally:
        eng.stop()


# -- the window pool's invariants --------------------------------------------
def _rows(eng):
    st = eng._state
    return (np.asarray(st["ptab"]), np.asarray(st["wtab"]),
            np.asarray(st["pos"]), np.asarray(st["active"]),
            np.asarray(st["pinned"]))


def test_a_live_lane_maps_its_window_and_little_more(tiny):
    """(a): after any step a live lane maps, in the window pool, every page
    that meets [pos - W + 1, pos] and at most W / page + 2 pages of its
    own; the full pool's row maps the whole context."""
    _, net = tiny
    eng = engine_of(net, prefix_cache=True)
    seen = []
    collect = eng._collect

    def spy():
        collect()
        if eng._flight is None:          # nothing in flight: state is whole
            seen.append(_rows(eng))

    try:
        eng.generate(prompt_of(150, seed=7), 4, timeout=300)
        eng._collect = spy
        handles = [eng.submit(np.concatenate(
            [prompt_of(150, seed=7)[:64], prompt_of(20 + 9 * i, seed=i)]),
            60 + 10 * i) for i in range(3)]
        for h in handles:
            h.result(timeout=300)
        eng._collect = collect
        assert eng.drain(timeout=60)
    finally:
        eng.stop()
    checked = 0
    for ptab, wtab, pos, active, pinned in seen:
        for lane in np.flatnonzero(active):
            p = int(pos[lane])
            need = range(max(p - W + 1, 0) // PAGE, p // PAGE + 1)
            # the page under `pos` is mapped by the step that writes it
            assert all(wtab[lane, c] >= 0 for c in need if c * PAGE < p), \
                (lane, p, wtab[lane])
            own = sum(1 for c in np.flatnonzero(wtab[lane] >= 0)
                      if c >= pinned[lane])
            assert own <= W // PAGE + 2
            assert all(ptab[lane, c] >= 0 for c in range(p // PAGE))
            checked += 1
    assert checked > 20


def test_a_drained_engine_has_every_page_back(tiny, engine):
    """(b): every private page of both pools is back on its free stack and
    every shared page is accounted for by the prefix cache."""
    eng, geom = engine, engine.geometry
    for i in range(3):
        eng.generate(prompt_of(100 + 20 * i, seed=40 + i), 50, timeout=300)
    while eng._sched.occupied or eng._flight is not None:
        pass
    st, cache = eng._state, eng._prefix
    shared_w = cache.resident_high
    shared = cache.resident_pages - shared_w
    assert int(st["free_count"]) == geom.num_pages - shared
    assert int(st["wfree_count"]) == geom.window_pages - shared_w
    assert (np.asarray(st["ptab"]) == -1).all()
    assert (np.asarray(st["wtab"]) == -1).all()
    free = np.asarray(st["wfree_stack"])[:int(st["wfree_count"])]
    held = {p - geom.num_pages for p in cache._rc if p >= geom.num_pages}
    assert len(set(free.tolist())) == len(free)
    assert set(free.tolist()).isdisjoint(held)
    assert set(free.tolist()) | held == set(range(geom.window_pages))
    snap = eng.metrics.snapshot()
    assert snap["kv_pages_mapped"] == {"full": 0, "window": 0}
    assert snap["kv_pages_in_use"] == {"full": shared, "window": shared_w}


def test_window_layers_never_read_behind_the_window(tiny):
    """(d): every window-pool page that no live lane maps is poisoned
    between steps (1e3 in every K and V: finite, since a masked column's
    probability is exactly 0 and 0 x NaN would not be); a read at or before
    pos - W, or through a stale table entry, would swamp the softmax and
    the served tokens would leave the reference's."""
    w, net = tiny
    eng = engine_of(net, prefix_cache=False)
    launch = eng._launch

    def poisoned_launch():
        st = eng._state
        mapped = np.asarray(st["wtab"])
        keep = np.zeros((eng.geometry.window_pages,), bool)
        keep[mapped[mapped >= 0]] = True
        keep = jnp.asarray(keep)[None, :, None, None, None]
        eng._state = dict(st, wkp=jnp.where(keep, st["wkp"], 1e3),
                          wvp=jnp.where(keep, st["wvp"], 1e3))
        return launch()

    try:
        eng._launch = poisoned_launch
        prompt = prompt_of(150, seed=8)
        toks = eng.generate(prompt, 110, timeout=300)
        gap, equal = served_gap(w, prompt, toks)
        assert gap <= GAP and equal >= 109, (gap, equal)
        assert eng.metrics.snapshot()["kv_window_pages_released"] >= 6
    finally:
        eng.stop()


def test_admission_reserves_in_both_pools(tiny):
    """Where the window pool binds before the full pool does, the request
    that does not fit queues until a lane retires; nothing is refused,
    nothing underflows.  With 40 pages the window pool holds
    min(40, 4 lanes x 4 + 40 // 4) = 26; a 100-token prompt that misses
    is kept whole to be shared (6 pages) beside the lane's window (4), so
    three of them ask for 30 there and for 3 x 9 = 27 of 40 in the full
    pool: two lanes run, the third waits."""
    _, net = tiny
    eng = engine_of(net, num_pages=40)
    try:
        geom = eng.geometry
        assert (geom.num_pages, geom.window_pages) == (40, 26)
        hs = [eng.submit(prompt_of(100, seed=i), 40) for i in range(3)]
        lanes = 0
        while not all(h.done for h in hs):
            lanes = max(lanes, len(eng._sched.occupied))
            time.sleep(0.002)
        assert lanes == 2, lanes
        for h in hs:
            assert len(h.result(timeout=300)) == 40
        assert eng.drain(timeout=60)
        kept = eng._prefix.resident_high
        assert int(eng._state["wfree_count"]) == geom.window_pages - kept
    finally:
        eng.stop()


def test_geometry_without_windows_is_what_it_was():
    geom = CacheGeometry(num_layers=2, max_slots=2, max_seq_len=32,
                         num_heads=2, head_dim=8, vocab_size=11)
    assert geom.windows == () and geom.window == 0
    assert geom.pool_shape[0] == 2 and geom.window_pages == 0
    assert not any(k.startswith("w") for k in make_state(geom))
    zeros = CacheGeometry(num_layers=2, max_slots=2, max_seq_len=32,
                          num_heads=2, head_dim=8, vocab_size=11,
                          windows=(0, 0))
    assert zeros == geom
    with pytest.raises(ValueError, match="multiple of the page size"):
        CacheGeometry(num_layers=2, max_slots=2, max_seq_len=32, num_heads=2,
                      head_dim=8, vocab_size=11, windows=(24, 0))


@pytest.mark.parametrize("pos, full, window", [
    (0, 1, 1),        # one key: one page of each kind
    (15, 2, 2),       # shorter than the window: both from column 0
    (16, 3, 3),       # key 0 has left the window, column 0 holds 1..7
    (23, 3, 2),       # keys 8..23: column 0 is behind the window
    (40, 6, 3),       # keys 25..40 meet columns 3, 4, 5
    (63, 8, 2),       # the table's last key: 48..63 are columns 6, 7
    (200, 8, 0),      # past the table: bounded by its width, and the
                      # window's first column lies beyond it
])
def test_page_walk_counts_a_window_layers_pages_from_the_window(pos, full,
                                                                window):
    """`CacheGeometry.page_walk` (the engine's paged_page_slots /
    paged_pages_live counters): a full layer's extent runs from column 0,
    a window layer's from the first column that meets the window; the
    slots are a lane's whole table a full layer and a window's columns a
    window layer."""
    geom = CacheGeometry(num_layers=4, max_slots=3, max_seq_len=64,
                         num_heads=2, head_dim=8, vocab_size=11, page_size=8,
                         windows=(16, 16, 16, 0))
    slots, live = geom.page_walk([pos])
    assert slots == 3 * (1 * 8 + 3 * 3)     # (16 - 2) // 8 + 2 columns
    assert live == 1 * full + 3 * window
    assert geom.page_walk([pos, pos]) == (slots, 2 * live)
    assert geom.page_walk([]) == (slots, 0)


@pytest.mark.parametrize("window", [1, 8, 16, 20])
def test_slide_window_keeps_the_first_column_the_paged_walk_reads(window):
    """The paged kernel's walk reads a lane as released (zeros) when the
    first column its window meets is unmapped, whatever lies after it,
    where the dense gather would attend the later pages: `slide_window`
    must never let go of that column.  At every position of the table,
    lanes active and not, a pinned prefix and none: the column of
    `_first_col(pos)` stays mapped, every column before it is let go, and
    an inactive lane's row is left alone."""
    from paddle_tpu.ops.pallas.paged_attention import _first_col
    from paddle_tpu.serving.kv_cache import slide_window

    ps, cols = 8, 8
    pos = jnp.arange(ps * cols, dtype=jnp.int32)
    lanes = pos.shape[0]
    wtab = jnp.arange(lanes * cols, dtype=jnp.int32).reshape(lanes, cols)
    state = {"wkp": jnp.zeros((1, lanes * cols, ps, 1, 1)),
             "pinned": (pos % 3).astype(jnp.int32),
             "wfree_stack": jnp.full((lanes * cols,), -1, jnp.int32)}
    active = (pos % 5) != 4
    out, _, _, pushed = slide_window(state, wtab, jnp.int32(0), pos, active,
                                     window)
    out, first = np.asarray(out), np.asarray(_first_col(pos, window, ps))
    np.testing.assert_array_equal(
        first, np.maximum(np.asarray(pos) - window + 1, 0) // ps)
    col = np.arange(cols)[None, :]
    live = np.asarray(active)[:, None]
    assert (out[np.arange(lanes), first] >= 0).all()
    assert (out[live & (col < first[:, None])] == -1).all()
    assert (out[~live[:, 0]] == np.asarray(wtab)[~live[:, 0]]).all()
    assert int(pushed) == int((live & (col < first[:, None])
                               & (col >= np.asarray(state["pinned"])[:, None])
                               ).sum())


def test_expert_counts_and_pool_gauges_are_published(tiny, engine):
    eng = engine
    before = eng.expert_counts()["assignments"].sum()
    eng.generate(prompt_of(60, seed=77), 20, timeout=300)
    after = eng.expert_counts()
    k, layers = CFG["num_experts_per_tok"], CFG["num_hidden_layers"]
    # 19 decode steps of one live lane (the first token is the prefill's)
    assert after["assignments"].sum() - before == 19 * k * layers
    text = eng.metrics.prometheus_text()
    for name in ('paddle_genserve_kv_pages_in_use{pool="full"}',
                 'paddle_genserve_kv_pages_in_use{pool="window"}',
                 'paddle_genserve_kv_pages_mapped{pool="window"}',
                 "paddle_genserve_kv_window_pages_released_total",
                 'paddle_genserve_kv_mapped_page_steps_total{pool="full"}'):
        assert name in text, name


def test_import_paddle_tpu_loads_no_mellum():
    code = ("import sys, paddle_tpu; "
            "bad = [m for m in sys.modules if m.endswith(('models.mellum', "
            "'models.sdar'))]; assert not bad, bad")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr[-800:]


def test_a_request_the_window_pool_can_never_hold_is_refused(tiny):
    """One slot over 24 pages: the window pool holds min(24, 4 + 6) = 10.
    A 256-token prompt that misses would be kept whole (16 pages) beside
    the lane's window (4): it can never be admitted, and `submit` says so
    at once, as it does for the full pool."""
    _, net = tiny
    eng = engine_of(net, max_slots=1, num_pages=24)
    try:
        assert eng.geometry.window_pages == 10
        with pytest.raises(ValueError, match="window pool is too small"):
            eng.submit(prompt_of(256), 60)
        assert eng.metrics.snapshot()["rejected_pages_exhausted"] == 1
    finally:
        eng.stop()
