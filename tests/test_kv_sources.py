"""The KV sources a model's attention layers attend over
(serving/kv_cache.py ``PagedKV`` / ``PrefixKV``, models/gpt.py ``DenseKV``):
each, handed the new tokens' q, k, v, must give exactly what the one
masked attention (ops/fused.py ``masked_attention``) gives over the
[B, S] key/value view assembled here by hand, and must come back holding
the new rows where its layout says they go."""
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models.gpt import DenseKV
from paddle_tpu.ops import fused
from paddle_tpu.serving.kv_cache import PagedKV, PrefixKV

LAYERS, LAYER, PAGES, PS, NH, HD = 3, 1, 6, 8, 2, 16
SEQ_CAP = 16
ROWS = np.asarray([[1, 4], [2, -1]], np.int32)   # lane 1 has one page mapped


def _randn(rs, *shape):
    return rs.randn(*shape).astype(np.float32)


def _pools(rs):
    return (_randn(rs, LAYERS, PAGES, PS, NH, HD),
            _randn(rs, LAYERS, PAGES, PS, NH, HD))


def _paged(chunk):
    rs = np.random.RandomState(chunk)
    kp, vp = _pools(rs)
    pos = np.asarray([9, 3], np.int32)
    if chunk > 1:
        pos = pos[:, None] + np.arange(chunk, dtype=np.int32)[None]
    q, k, v = (_randn(rs, 2, chunk, NH, HD) for _ in range(3))
    src = PagedKV(*map(jnp.asarray, (kp, vp, ROWS, pos,
                                     np.asarray([True, True]))), SEQ_CAP)
    # the view: lane b's pages laid end to end (an unmapped -1 reads page
    # 0, past the mask), the new rows written where their positions say
    pos2 = pos.reshape(2, -1)
    keys, values = (np.stack([
        np.concatenate([pool[LAYER, max(p, 0)] for p in ROWS[b]])[:SEQ_CAP]
        for b in range(2)]) for pool in (kp, vp))
    for b in range(2):
        for i, p in enumerate(pos2[b]):
            keys[b, p], values[b, p] = k[b, i], v[b, i]
    valid = np.arange(SEQ_CAP)[None, None, :] <= pos2[:, :, None]

    def held(out):
        for got, was, new in ((out.k_pages, kp, k), (out.v_pages, vp, v)):
            want = was.copy()
            for b in range(2):
                for i, p in enumerate(pos2[b]):
                    want[LAYER, ROWS[b, p // PS], p % PS] = new[b, i]
            np.testing.assert_array_equal(np.asarray(got), want)

    return src, (q, k, v), (keys, values, valid), held


def _prefix():
    rs = np.random.RandomState(7)
    kp, vp = _pools(rs)
    page_ids, prefix_len, S = np.asarray([3, -1], np.int32), PS, 5
    q, k, v = (_randn(rs, 1, S, NH, HD) for _ in range(3))
    src = PrefixKV.gather(jnp.asarray(kp), jnp.asarray(vp),
                          jnp.asarray(page_ids), prefix_len)
    C = len(page_ids) * PS
    keys, values = (np.concatenate(
        [pool[LAYER, 3], pool[LAYER, 0], new[0]])[None]
        for pool, new in ((kp, k), (vp, v)))
    j = np.arange(C + S)[None, :]
    valid = ((j < prefix_len)
             | ((j >= C) & (j - C <= np.arange(S)[:, None])))[None]

    def held(out):
        ks, vs = out.suffix_kv()
        np.testing.assert_array_equal(np.asarray(ks), k)   # one layer so far
        np.testing.assert_array_equal(np.asarray(vs), v)

    return src, (q, k, v), (keys, values, valid), held


def _dense():
    rs = np.random.RandomState(11)
    B, S_max, pos = 2, 12, 5
    caches = [(_randn(rs, B, S_max, NH, HD), _randn(rs, B, S_max, NH, HD))
              for _ in range(LAYERS)]
    q, k, v = (_randn(rs, B, 1, NH, HD) for _ in range(3))
    src = DenseKV(tuple((jnp.asarray(a), jnp.asarray(b)) for a, b in caches),
                  jnp.asarray(pos, jnp.int32))
    keys, values = caches[LAYER][0].copy(), caches[LAYER][1].copy()
    keys[:, pos], values[:, pos] = k[:, 0], v[:, 0]
    valid = (np.arange(S_max) <= pos)[None, None, :]

    def held(out):
        for i, (kc, vc) in enumerate(out.caches):
            want = (keys, values) if i == LAYER else caches[i]
            np.testing.assert_array_equal(np.asarray(kc), want[0])
            np.testing.assert_array_equal(np.asarray(vc), want[1])

    return src, (q, k, v), (keys, values, valid), held


@pytest.mark.parametrize("case", [
    pytest.param(lambda: _paged(1), id="paged-decode"),
    pytest.param(lambda: _paged(2), id="paged-chunk-of-2"),
    pytest.param(_prefix, id="prefix"),
    pytest.param(_dense, id="dense"),
])
def test_source_equals_masked_attention_over_its_view(case):
    src, (q, k, v), (keys, values, valid), held = case()
    ctx, out = src.attend(LAYER, *map(jnp.asarray, (q, k, v)))
    want = fused.masked_attention(*map(jnp.asarray, (q, keys, values, valid)))
    assert ctx.dtype == jnp.float32 and ctx.shape == q.shape
    np.testing.assert_array_equal(np.asarray(ctx), np.asarray(want))
    held(out)


def test_masked_attention_is_softmax_attention():
    """The arithmetic itself, against a float64 softmax."""
    rs = np.random.RandomState(3)
    q, keys, values = _randn(rs, 2, 3, NH, HD), _randn(rs, 2, 9, NH, HD), \
        _randn(rs, 2, 9, NH, HD)
    valid = rs.rand(2, 3, 9) < 0.6
    valid[..., 0] = True                       # no row without a key
    got = np.asarray(fused.masked_attention(
        *map(jnp.asarray, (q, keys, values, valid))))
    s = np.einsum("bqnd,bsnd->bnqs", q.astype("f8"), keys.astype("f8")) \
        / np.sqrt(HD)
    s = np.where(valid[:, None], s, -np.inf)
    w = np.exp(s - s.max(-1, keepdims=True))
    w /= w.sum(-1, keepdims=True)
    want = np.einsum("bnqs,bsnd->bqnd", w, values.astype("f8"))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
