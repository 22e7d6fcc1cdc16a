"""Pallas fused kernels vs XLA reference (OpTest contract: numpy/XLA
reference + gradient comparison, SURVEY.md §4 op unit tests).

On CPU the kernels run in pallas interpret mode; the same code compiles via
Mosaic on TPU (tests/test_mosaic_compile.py compiles them for a described
v5e; chip_smoke.py runs them on one)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle

from paddle_tpu.ops.pallas import DoesNotTile
from paddle_tpu.ops.pallas.flash_attention import flash_attention
from paddle_tpu.ops.pallas.layer_norm import layer_norm


def _attn_ref(q, k, v, causal):
    qh, kh, vh = [jnp.swapaxes(x, 1, 2) for x in (q, k, v)]
    s = jnp.einsum("bhsd,bhtd->bhst", qh, kh) / np.sqrt(q.shape[-1])
    if causal:
        m = jnp.tril(jnp.ones(s.shape[-2:], bool))
        s = jnp.where(m, s, -1e30)
    w = jax.nn.softmax(s, -1)
    return jnp.swapaxes(jnp.einsum("bhst,bhtd->bhsd", w, vh), 1, 2)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_fwd_bwd(causal):
    rs = np.random.RandomState(0)
    q, k, v = [jnp.asarray(rs.randn(2, 128, 2, 64), jnp.float32)
               for _ in range(3)]
    out = flash_attention(q, k, v, causal=causal)
    ref = _attn_ref(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)

    g1 = jax.grad(lambda *a: (flash_attention(*a, causal=causal) ** 2).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda *a: (_attn_ref(*a, causal) ** 2).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-4)


def test_flash_attention_jit_and_bf16():
    rs = np.random.RandomState(1)
    q, k, v = [jnp.asarray(rs.randn(1, 128, 2, 64), jnp.bfloat16)
               for _ in range(3)]
    out = jax.jit(lambda *a: flash_attention(*a, causal=True))(q, k, v)
    ref = _attn_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                    v.astype(jnp.float32), True)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               rtol=5e-2, atol=5e-2)


def test_flash_attention_fallback_shapes():
    q = jnp.zeros((1, 129, 2, 64))  # 129 % 128 != 0
    with pytest.raises(DoesNotTile):
        flash_attention(q, q, q)


def test_layer_norm_fwd_bwd():
    rs = np.random.RandomState(2)
    x = jnp.asarray(rs.randn(8, 16, 256), jnp.float32)
    w = jnp.asarray(rs.randn(256), jnp.float32)
    b = jnp.asarray(rs.randn(256), jnp.float32)

    def ref(x, w, b, eps=1e-5):
        m = jnp.mean(x, -1, keepdims=True)
        v = jnp.mean((x - m) ** 2, -1, keepdims=True)
        return (x - m) * jax.lax.rsqrt(v + eps) * w + b

    np.testing.assert_allclose(np.asarray(layer_norm(x, w, b)),
                               np.asarray(ref(x, w, b)),
                               rtol=1e-5, atol=1e-5)
    g1 = jax.grad(lambda *a: (layer_norm(*a) ** 2).sum(),
                  argnums=(0, 1, 2))(x, w, b)
    g2 = jax.grad(lambda *a: (ref(*a) ** 2).sum(), argnums=(0, 1, 2))(x, w, b)
    for a, bb in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb),
                                   rtol=1e-4, atol=1e-3)


def test_fused_op_dispatch_falls_back_cleanly(monkeypatch):
    """ops.fused attempts pallas, hits DoesNotTile on an untileable
    shape, and falls back to the XLA path with a correct result."""
    import paddle_tpu as paddle
    from paddle_tpu.ops import fused

    monkeypatch.setattr(fused, "_use_pallas", lambda: True)
    x = paddle.randn([2, 129, 4, 16])  # 129 % 128 != 0 → pallas raises
    out = fused.scaled_dot_product_attention(x, x, x)
    assert out.shape == [2, 129, 4, 16]
    ref = _attn_ref(x.value, x.value, x.value, False)
    np.testing.assert_allclose(np.asarray(out.value), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


class TestFusedLinearCrossEntropy:
    """Chunked LM-head matmul + xent vs the direct computation."""

    def _direct(self, h, w, labels):
        z = (h.astype(np.float64) @ w.astype(np.float64))
        m = z.max(-1, keepdims=True)
        lse = np.log(np.exp(z - m).sum(-1)) + m[:, 0]
        picked = z[np.arange(len(labels)), labels]
        return lse - picked

    def test_forward_matches_direct(self):
        from paddle_tpu.ops import fused
        rs = np.random.RandomState(0)
        N, H, V = 12, 16, 1000
        h = rs.randn(N, H).astype("f")
        w = (rs.randn(H, V) * 0.1).astype("f")
        labels = rs.randint(0, V, N)
        out = fused.fused_linear_cross_entropy(
            paddle.to_tensor(h), paddle.to_tensor(w),
            paddle.to_tensor(labels), chunk_size=128)
        np.testing.assert_allclose(np.asarray(out.numpy()),
                                   self._direct(h, w, labels), rtol=1e-4)

    def test_vocab_not_multiple_of_chunk(self):
        from paddle_tpu.ops import fused
        rs = np.random.RandomState(1)
        N, H, V = 6, 8, 37  # 37 not divisible by 16
        h = rs.randn(N, H).astype("f")
        w = (rs.randn(H, V) * 0.1).astype("f")
        labels = rs.randint(0, V, N)
        out = fused.fused_linear_cross_entropy(
            paddle.to_tensor(h), paddle.to_tensor(w),
            paddle.to_tensor(labels), chunk_size=16)
        np.testing.assert_allclose(np.asarray(out.numpy()),
                                   self._direct(h, w, labels), rtol=1e-4)

    def test_gradients_match_direct(self):
        from paddle_tpu.ops import fused
        import jax
        import jax.numpy as jnp
        rs = np.random.RandomState(2)
        N, H, V = 8, 12, 300
        h = rs.randn(N, H).astype("f")
        w = (rs.randn(H, V) * 0.1).astype("f")
        labels = jnp.asarray(rs.randint(0, V, N))

        def fused_loss(hh, ww):
            return fused._flce(hh, ww, labels, 64).mean()

        def direct_loss(hh, ww):
            z = (hh @ ww).astype(jnp.float32)
            lp = jax.nn.log_softmax(z, -1)
            return -jnp.take_along_axis(lp, labels[:, None], 1).mean()

        gh1, gw1 = jax.grad(fused_loss, (0, 1))(jnp.asarray(h),
                                                jnp.asarray(w))
        gh2, gw2 = jax.grad(direct_loss, (0, 1))(jnp.asarray(h),
                                                 jnp.asarray(w))
        np.testing.assert_allclose(np.asarray(gh1), np.asarray(gh2),
                                   rtol=1e-3, atol=1e-6)
        np.testing.assert_allclose(np.asarray(gw1), np.asarray(gw2),
                                   rtol=1e-3, atol=1e-6)

    def test_batched_leading_shape(self):
        from paddle_tpu.ops import fused
        rs = np.random.RandomState(3)
        B, S, H, V = 2, 5, 8, 50
        h = rs.randn(B, S, H).astype("f")
        w = (rs.randn(H, V) * 0.1).astype("f")
        labels = rs.randint(0, V, (B, S))
        out = fused.fused_linear_cross_entropy(
            paddle.to_tensor(h), paddle.to_tensor(w),
            paddle.to_tensor(labels), chunk_size=16)
        assert tuple(out.shape) == (B, S)
        flat = self._direct(h.reshape(-1, H), w, labels.reshape(-1))
        np.testing.assert_allclose(np.asarray(out.numpy()).reshape(-1),
                                   flat, rtol=1e-4)


# ===========================================================================
# PR 15 kernel suite: masked flash + VJP, paged
# decode, softmax-xent, bias-gelu, GSPMD composition, dispatch telemetry
# ===========================================================================
def _attn_ref_masked(q, k, v, causal=False, mask=None, sm_scale=None):
    qh, kh, vh = [jnp.swapaxes(x, 1, 2) for x in (q, k, v)]
    s = jnp.einsum("bhsd,bhtd->bhst", qh, kh) * (
        sm_scale or 1 / np.sqrt(q.shape[-1]))
    if causal:
        m = jnp.tril(jnp.ones(s.shape[-2:], bool))
        s = jnp.where(m, s, -1e30)
    if mask is not None:
        m = mask
        if m.dtype == jnp.bool_:
            s = jnp.where(m, s, -1e30)
        else:
            s = s + m
    w = jax.nn.softmax(s, -1)
    return jnp.swapaxes(jnp.einsum("bhst,bhtd->bhsd", w, vh), 1, 2)


def _qkv(rs, b=2, s=128, h=2, d=64):
    return [jnp.asarray(rs.randn(b, s, h, d), jnp.float32) for _ in range(3)]


@pytest.mark.kernels
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("kind", ["bool_pad", "additive", "per_head"])
def test_flash_attention_masked_fwd_bwd(causal, kind):
    """Bool padding masks, additive biases, and per-head biases all run
    through the kernel — forward AND gradient parity vs the XLA softmax."""
    rs = np.random.RandomState(3)
    q, k, v = _qkv(rs)
    b, s, h, _ = q.shape
    if kind == "bool_pad":
        # [B, 1, 1, S] key-padding mask (True = attend), MHA's shape
        mask = jnp.asarray(rs.rand(b, 1, 1, s) > 0.2)
        mask = mask.at[:, :, :, :8].set(True)  # no fully-masked rows
    elif kind == "additive":
        mask = jnp.asarray(rs.randn(b, 1, s, s), jnp.float32)
    else:
        mask = jnp.asarray(rs.randn(b, h, s, s), jnp.float32)

    out = flash_attention(q, k, v, causal=causal, mask=mask)
    ref = _attn_ref_masked(q, k, v, causal, mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)

    g1 = jax.grad(
        lambda *a: (flash_attention(*a, causal=causal, mask=mask) ** 2).sum(),
        argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(
        lambda *a: (_attn_ref_masked(*a, causal, mask) ** 2).sum(),
        argnums=(0, 1, 2))(q, k, v)
    for a, bb in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb),
                                   rtol=1e-3, atol=1e-4)


# the shapes `pick_blocks` tells apart: (s_q, s_k, d, causal, padding mask,
# explicit block_q = block_k or None[, sm_scale]).  The tile on the diagonal
# is walked by strips of 128 owned rows (`_strip_rows`) where the call is
# causal over equal sequences in square tiles of whole strips and has no
# mask to slice; every other call computes it whole.
FLASH_SHAPES = {
    "s1024_d64_causal": (1024, 1024, 64, True, False, None),
    "s768_d128_causal_512_does_not_divide": (768, 768, 128, True, False,
                                             None),
    "s512_d64_padding_mask": (512, 512, 64, False, True, None),
    "s64_d64_shorter_than_any_block": (64, 64, 64, False, False, None),
    "s64_d64_causal": (64, 64, 64, True, False, None),
    "sq256_sk512": (256, 512, 64, False, False, None),
    "sq512_sk256_causal": (512, 256, 64, True, False, None),
    "s512_d64_causal_blocks_of_128": (512, 512, 64, True, False, 128),
    "s384_d64_causal_mask_blocks_of_128": (384, 384, 64, True, True, 128),
    # by strips: one, two and four tiles a side, of one, two and four strips
    "s256_d64_causal_one_tile_of_two_strips": (256, 256, 64, True, False,
                                               None),
    "s512_d128_causal_one_tile_of_four_strips": (512, 512, 128, True, False,
                                                 None),
    "s512_d64_causal_two_tiles_a_side": (512, 512, 64, True, False, 256),
    "s1024_d128_causal_four_tiles_a_side": (1024, 1024, 128, True, False,
                                            256),
    "s384_d128_causal_three_tiles_of_one_strip": (384, 384, 128, True, False,
                                                  None),
    "s256_d64_causal_odd_scale_stays_on_the_scores": (256, 256, 64, True,
                                                      False, None, 0.2),
    # whole, though causal in square tiles: unequal sequences, a mask
    "sq256_sk512_causal_square_tiles": (256, 512, 64, True, False, 256),
    "sq512_sk256_causal_square_tiles": (512, 256, 64, True, False, 256),
    "s512_d64_causal_mask_square_tiles": (512, 512, 64, True, True, 256),
    "s512_d64_full_square_tiles": (512, 512, 64, False, False, 256),
}


@pytest.mark.kernels
@pytest.mark.parametrize("shape", list(FLASH_SHAPES))
def test_flash_attention_parity_over_block_choices(shape):
    """Forward, dQ, dK and dV against the plain float32 reference at each
    kind of shape the block function separates, one head a call."""
    s_q, s_k, d, causal, padded, block, *scale = FLASH_SHAPES[shape]
    sm_scale = scale[0] if scale else None
    rs = np.random.RandomState(11)
    q = jnp.asarray(rs.randn(1, s_q, 1, d), jnp.float32)
    k, v = [jnp.asarray(rs.randn(1, s_k, 1, d), jnp.float32)
            for _ in range(2)]
    mask = None
    if padded:
        mask = jnp.asarray(rs.rand(1, 1, 1, s_k) > 0.3)
        mask = mask.at[:, :, :, :8].set(True)  # no fully-masked rows
    w = jnp.asarray(rs.randn(1, s_q, 1, d), jnp.float32)

    def kernel(*a):
        return flash_attention(*a, causal=causal, mask=mask, block_q=block,
                               block_k=block, sm_scale=sm_scale)

    def ref(*a):
        return _attn_ref_masked(*a, causal, mask, sm_scale)

    np.testing.assert_allclose(np.asarray(kernel(q, k, v)),
                               np.asarray(ref(q, k, v)),
                               rtol=1e-4, atol=1e-5)
    g1 = jax.grad(lambda *a: (kernel(*a) * w).sum(), argnums=(0, 1, 2))(
        q, k, v)
    g2 = jax.grad(lambda *a: (ref(*a) * w).sum(), argnums=(0, 1, 2))(q, k, v)
    for name, a, bb in zip(("dq", "dk", "dv"), g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb),
                                   rtol=1e-3, atol=1e-4, err_msg=name)


def _assert_matches_reference(out, grads, ref, w, q, k, v):
    """The kernel's weighted sum `out` and its (dq, dk, dv) against the
    float32 reference's, at the tolerances of the parity tests above."""
    ref_out, ref_grads = jax.value_and_grad(
        lambda *a: (ref(*a) * w).sum(), argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(float(out), float(ref_out), rtol=1e-4)
    for name, a, bb in zip(("dq", "dk", "dv"), grads, ref_grads):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb),
                                   rtol=1e-3, atol=1e-4, err_msg=name)


# which body a call takes and whether its scale leaves the score tile, both
# static facts of the call: (s_q, s_k, heads, d, causal, padding mask,
# block_q, block_k, sm_scale or None) -> (rows of a strip or 0, folded).
# Heads of 3 and 5: shapes no other test of this file traces, so that the
# jitted calls are traced here and the kernels' arguments seen.
FLASH_CHOICES = {
    "hd64_one_tile": ((256, 256, 3, 64, True, False, None, None, None),
                      (128, True)),
    "hd128_scale_is_no_power_of_two": (
        (256, 256, 3, 128, True, False, None, None, None), (128, False)),
    "hd64_odd_scale_of_the_caller": (
        (256, 256, 5, 64, True, False, None, None, 0.2), (128, False)),
    "hd32_scale_of_the_caller_is_a_quarter": (
        (256, 256, 3, 32, True, False, None, None, 0.25), (128, True)),
    "two_tiles_a_side": ((512, 512, 3, 64, True, False, 256, 256, None),
                         (128, True)),
    "unequal_tile_sides": ((512, 512, 3, 64, True, False, 256, 128, None),
                           (0, True)),
    "unequal_sequences": ((256, 512, 3, 64, True, False, 256, 256, None),
                          (0, True)),
    "not_causal": ((256, 256, 5, 64, False, False, None, None, None),
                   (0, True)),
    "padding_mask": ((256, 256, 3, 64, True, True, None, None, None),
                     (0, True)),
    "tile_of_no_whole_strip": ((64, 64, 3, 64, True, False, None, None, None),
                               (0, True)),
}


@pytest.mark.kernels
@pytest.mark.parametrize("case", list(FLASH_CHOICES))
def test_flash_static_choices(case, monkeypatch, caplog):
    """The diagonal's walk and the scale's place are read off the call:
    all three kernels are handed the same `strip` and `fold`, `_layout`'s
    debug line says which walk it is, and either way the call holds to
    the float32 reference."""
    import logging

    from paddle_tpu.ops.pallas import flash_attention as fa

    (s_q, s_k, h, d, causal, padded, bq, bk, sm_scale), (strip, fold) = \
        FLASH_CHOICES[case]
    assert fa._folds_scale(sm_scale or 1 / np.sqrt(d)) == fold
    seen = {}
    for name in ("_fwd_kernel", "_dq_kernel", "_dkv_kernel"):
        def spy(*a, _name=name, _kernel=getattr(fa, name), **kw):
            seen[_name] = (kw["strip"], kw["fold"])
            return _kernel(*a, **kw)
        monkeypatch.setattr(fa, name, spy)

    rs = np.random.RandomState(5)
    q = jnp.asarray(rs.randn(1, s_q, h, d), jnp.float32)
    k, v = [jnp.asarray(rs.randn(1, s_k, h, d), jnp.float32)
            for _ in range(2)]
    mask = None
    if padded:
        mask = jnp.asarray(rs.rand(1, 1, 1, s_k) > 0.3).at[..., :8].set(True)
    w = jnp.asarray(rs.randn(1, s_q, h, d), jnp.float32)

    def kernel(*a):
        return flash_attention(*a, causal=causal, mask=mask, block_q=bq,
                               block_k=bk, sm_scale=sm_scale)

    def ref(*a):
        return _attn_ref_masked(*a, causal, mask, sm_scale)

    with caplog.at_level(logging.DEBUG, logger=fa.__name__):
        out, g1 = jax.value_and_grad(
            lambda *a: (kernel(*a) * w).sum(), argnums=(0, 1, 2))(q, k, v)
    assert seen == dict.fromkeys(
        ("_fwd_kernel", "_dq_kernel", "_dkv_kernel"), (strip, fold))
    walks = [r.getMessage().rsplit("the tile on the diagonal ", 1)[1]
             for r in caplog.records if "the tile on the diagonal" in
             r.getMessage()]
    assert walks == [f"by strips of {strip} owned rows" if strip
                     else "whole"] * 3
    _assert_matches_reference(out, g1, ref, w, q, k, v)


@pytest.mark.kernels
@pytest.mark.parametrize("walk", ["by_strips", "whole", "whole_under_a_mask"])
def test_flash_attention_parity_over_resident_spans(walk, monkeypatch,
                                                    caplog):
    """A walked axis that outgrows the VMEM budget is kept resident a span
    at a time (here: 512 rows in two spans of 256, under a budget cut for
    the test): the statistics and accumulators pass from span to span,
    spans above the diagonal have no work, and the tile on the diagonal
    lies in one span of several."""
    import logging

    from paddle_tpu.ops.pallas import flash_attention as fa

    monkeypatch.setattr(fa, "_VMEM_BUDGET", 2_500_000)
    rs = np.random.RandomState(17)
    q, k, v, w = [jnp.asarray(rs.randn(1, 512, 7, 64), jnp.float32)
                  for _ in range(4)]
    mask = None
    if walk == "whole_under_a_mask":
        mask = jnp.asarray(rs.rand(1, 1, 1, 512) > 0.3).at[..., :8].set(True)

    def kernel(*a):
        return flash_attention(*a, causal=True, mask=mask, block_q=128,
                               block_k=128 if walk != "whole" else 64)

    def ref(*a):
        return _attn_ref_masked(*a, True, mask)

    with caplog.at_level(logging.DEBUG, logger=fa.__name__):
        out, g1 = jax.value_and_grad(
            lambda *a: (kernel(*a) * w).sum(), argnums=(0, 1, 2))(q, k, v)
    lines = [r.getMessage() for r in caplog.records
             if "the tile on the diagonal" in r.getMessage()]
    # forward and dQ at least (dK/dV owns the tile's other side)
    assert len(lines) == 3 and all(", 2)" in line for line in lines[:2]), \
        lines
    assert all(line.endswith("by strips of 128 owned rows"
                             if walk == "by_strips" else "whole")
               for line in lines), lines
    _assert_matches_reference(out, g1, ref, w, q, k, v)


@pytest.mark.kernels
def test_flash_block_function():
    """`pick_blocks` and `_span` are arithmetic on shapes: the blocks
    divide their sequences, one grid step's estimate fits the budget, and
    no shape that tiled with blocks of min(128, s) stops tiling."""
    from paddle_tpu.ops.pallas import flash_attention as fa

    seqs = (8, 64, 100, 128, 256, 384, 512, 640, 768, 1024, 1152, 2048,
            4096, 8192, 32768)
    for s_q in seqs:
        for s_k in seqs:
            for d in (64, 128, 256):
                for has_bias in (False, True):
                    for itemsize in (2, 4):
                        bq, bk = fa.pick_blocks(s_q, s_k, d, has_bias,
                                                itemsize)
                        args = (d, has_bias, itemsize)
                        assert bq >= 8 and bk >= 8
                        assert s_q % bq == 0 and s_k % bk == 0
                        # the old rule's blocks still divide the new ones
                        assert bq % min(128, s_q) == 0
                        assert bk % min(128, s_k) == 0
                        for own, tile, s in ((bq, bk, s_k), (bk, bq, s_q)):
                            span = fa._span(s, own, tile, *args)
                            assert span % tile == 0 and s % span == 0
                            assert fa._vmem_bytes(own, tile, span, *args) \
                                <= fa._VMEM_BUDGET, (s_q, s_k, *args)
    # the train cell's call keeps a whole head resident
    bq, bk = fa.pick_blocks(1024, 1024, 64, False, 2)
    assert fa._span(1024, bq, bk, 64, False, 2) == 1024
    # 129 tiled with no block and still does not
    with pytest.raises(DoesNotTile):
        flash_attention(jnp.zeros((1, 129, 2, 64)), jnp.zeros((1, 129, 2, 64)),
                        jnp.zeros((1, 129, 2, 64)))


@pytest.mark.kernels
def test_flash_layers_of_one_shape_trace_their_kernel_once(monkeypatch):
    """A model's layers call the kernel at one shape: the jitted inner
    call is traced (and lowered) for the first and reused by the rest, so
    start-up does not pay for the kernel once a layer."""
    from paddle_tpu.ops.pallas import flash_attention as fa

    traced = []
    kernel = fa._fwd_kernel
    monkeypatch.setattr(
        fa, "_fwd_kernel",
        lambda *a, **kw: (traced.append(1), kernel(*a, **kw))[1])

    def three_layers(x):
        for _ in range(3):
            x = flash_attention(x, x, x, causal=True)
        return x

    # a shape no other test of this file uses: nothing is cached for it
    out = jax.jit(three_layers)(jnp.ones((1, 128, 3, 32), jnp.float32))
    assert out.shape == (1, 128, 3, 32)
    assert len(traced) == 1


@pytest.mark.kernels
def test_flash_attention_mask_shapes_and_fallback():
    rs = np.random.RandomState(4)
    q, k, v = _qkv(rs, b=1, s=128)
    # 2D [S, S] additive mask broadcasts
    m2 = jnp.asarray(rs.randn(128, 128), jnp.float32)
    out = flash_attention(q, k, v, mask=m2)
    ref = _attn_ref_masked(q, k, v, mask=m2[None, None])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)
    # non-broadcastable mask raises (dispatch falls back, counted)
    with pytest.raises(DoesNotTile):
        flash_attention(q, k, v, mask=jnp.zeros((3, 1, 128, 128)))


@pytest.mark.kernels
def test_flash_attention_invisible_under_remat():
    """jax.checkpoint over the kernel (cfg.recompute wraps blocks in
    remat): same values, same gradients — the custom VJP must not leak
    residuals the remat pass can't rematerialize."""
    rs = np.random.RandomState(5)
    q, k, v = _qkv(rs)
    mask = jnp.asarray(rs.rand(2, 1, 1, 128) > 0.2)

    def f(q, k, v):
        return (flash_attention(q, k, v, causal=True, mask=mask) ** 2).sum()

    g_plain = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    g_remat = jax.grad(jax.checkpoint(f), argnums=(0, 1, 2))(q, k, v)
    for a, bb in zip(g_plain, g_remat):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb),
                                   rtol=1e-6, atol=1e-6)


@pytest.mark.kernels
def test_sharded_flash_attention_tp2_parity():
    """shard_map composition over dp×tp (SpecLayout's axes, 8 virtual
    devices): each shard runs the kernel on its LOCAL heads; results
    match the single-device kernel and the XLA reference."""
    from paddle_tpu.distributed.mesh import build_mesh
    from paddle_tpu.ops.pallas.flash_attention import sharded_flash_attention

    rs = np.random.RandomState(6)
    q, k, v = _qkv(rs, b=4, s=128, h=2, d=64)
    mask = jnp.asarray(rs.randn(4, 1, 128, 128), jnp.float32)
    mesh = build_mesh({"dp": 4, "tp": 2})
    out = sharded_flash_attention(q, k, v, mesh, head_axis="tp",
                                  batch_axes=("dp",), causal=True, mask=mask)
    ref = _attn_ref_masked(q, k, v, True, mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)
    # heads not divisible by tp -> clean refusal for the dispatch gate
    with pytest.raises(DoesNotTile):
        sharded_flash_attention(q[:, :, :1], k[:, :, :1], v[:, :, :1],
                                mesh, head_axis="tp")


@pytest.mark.kernels
def test_sdpa_dispatch_routes_masked_through_pallas(monkeypatch):
    """fused.scaled_dot_product_attention with a mask no longer falls
    back: pallas result == XLA composite, and the fallback counter stays
    flat."""
    from paddle_tpu.ops import fused

    rs = np.random.RandomState(7)
    q = paddle.to_tensor(rs.randn(2, 128, 4, 16).astype("f"))
    mask = paddle.to_tensor(rs.randn(2, 1, 128, 128).astype("f"))
    ref = fused.scaled_dot_product_attention(q, q, q, attn_mask=mask,
                                             is_causal=True)
    before = dict(fused.fallback_counter().values)
    monkeypatch.setattr(fused, "_use_pallas", lambda: True)
    out = fused.scaled_dot_product_attention(q, q, q, attn_mask=mask,
                                             is_causal=True)
    np.testing.assert_allclose(np.asarray(out.value), np.asarray(ref.value),
                               rtol=1e-4, atol=1e-5)
    assert dict(fused.fallback_counter().values) == before

    # an ambient mesh whose axes do NOT divide this call (dp=8, B=2 —
    # what init_parallel_env leaves behind) must shed the axes and stay
    # on the kernel path, not fall back
    from paddle_tpu.distributed.mesh import build_mesh, mesh_guard

    with mesh_guard(build_mesh({"dp": 8})):
        out_m = fused.scaled_dot_product_attention(q, q, q, attn_mask=mask,
                                                   is_causal=True)
    np.testing.assert_allclose(np.asarray(out_m.value), np.asarray(ref.value),
                               rtol=1e-4, atol=1e-5)
    assert dict(fused.fallback_counter().values) == before


@pytest.mark.kernels
def test_fallback_counter_and_warn_once(monkeypatch):
    """Satellite: the silent-fallback gate warns once per (kernel,
    reason) site and counts every occurrence in the shared registry."""
    import warnings

    from paddle_tpu.ops import fused
    from paddle_tpu.utils.metrics import default_registry

    monkeypatch.setattr(fused, "_use_pallas", lambda: True)
    monkeypatch.setattr(fused, "_warned_sites", set())
    counter = fused.fallback_counter()
    key = ("flash_attention", "dropout")
    base = counter.values.get(key, 0)
    x = paddle.randn([1, 16, 2, 8])
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        fused.scaled_dot_product_attention(x, x, x, dropout_p=0.5,
                                           training=True)
        fused.scaled_dot_product_attention(x, x, x, dropout_p=0.5,
                                           training=True)
    msgs = [str(r.message) for r in w
            if issubclass(r.category, RuntimeWarning)
            and "flash_attention" in str(r.message)]
    assert len(msgs) == 1, msgs  # warned ONCE
    assert counter.values[key] == base + 2  # counted TWICE
    assert "paddle_pallas_fallbacks_total" in msgs[0]
    # and the shared registry renders it for /metrics
    text = default_registry().prometheus_text()
    assert 'paddle_pallas_fallbacks_total{kernel="flash_attention"' \
           ',reason="dropout"}' in text


# the pool is the engine's stacked one, [layers, pages, page_size, nh, hd],
# with every plane different: a kernel that read another plane than the
# one asked for would not match the dense gather of that plane
_POOL_LAYERS = 5
_PLANES = [0, _POOL_LAYERS // 2, _POOL_LAYERS - 1]


def _dense_paged_ref(q, kp, vp, rows, pos, seq_cap, layer):
    """The dense gather of plane `layer` (PagedKV.attend's fallback math)."""
    slots, nh, hd = q.shape
    num_pages, ps = kp.shape[1], kp.shape[2]
    gidx = jnp.clip(rows, 0, num_pages - 1)
    kg = kp[layer, gidx].reshape(slots, -1, nh, hd)[:, :seq_cap]
    vg = vp[layer, gidx].reshape(slots, -1, nh, hd)[:, :seq_cap]
    s = jnp.einsum("bnd,bsnd->bns", q, kg) / np.sqrt(hd)
    valid = jnp.arange(seq_cap)[None, :] <= pos[:, None]
    s = jnp.where(valid[:, None, :], s, -1e30)
    w = jax.nn.softmax(s, -1)
    return jnp.einsum("bns,bsnd->bnd", w, vg)


@pytest.mark.kernels
@pytest.mark.parametrize("layer", _PLANES)
def test_paged_decode_attention_ragged_parity(layer):
    """Ragged page-table rows (different lengths, -1 tails, one lane
    exactly at a page boundary, one mid-page) vs the dense-gather
    reference PagedKV.attend used before this kernel, on plane `layer` of
    a pool whose planes differ."""
    from paddle_tpu.ops.pallas.paged_attention import paged_decode_attention

    rs = np.random.RandomState(8)
    slots, ps, nh, hd = 4, 8, 2, 16
    num_pages = 12
    seq_cap = 32
    q = jnp.asarray(rs.randn(slots, nh, hd), jnp.float32)
    kp = jnp.asarray(rs.randn(_POOL_LAYERS, num_pages, ps, nh, hd),
                     jnp.float32)
    vp = jnp.asarray(rs.randn(_POOL_LAYERS, num_pages, ps, nh, hd),
                     jnp.float32)
    rows = jnp.asarray([[2, 5, -1, -1],    # two pages, mid-page pos
                        [7, 1, 3, 9],      # full table
                        [4, -1, -1, -1],   # single page
                        [6, 8, -1, -1]],   # pos exactly at page boundary
                       jnp.int32)
    pos = jnp.asarray([11, 26, 3, 15], jnp.int32)

    ref = _dense_paged_ref(q, kp, vp, rows, pos, seq_cap, layer)
    out = paged_decode_attention(q, kp, vp, rows, pos, seq_cap, layer)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    # jit (engine decode executables wrap it) — same result
    out_j = jax.jit(lambda *a: paged_decode_attention(*a, seq_cap, layer))(
        q, kp, vp, rows, pos)
    np.testing.assert_allclose(np.asarray(out_j), np.asarray(out),
                               rtol=0, atol=0)


@pytest.mark.kernels
@pytest.mark.parametrize("layer", _PLANES)
def test_paged_decode_attention_unmapped_tail(layer):
    """A lane whose table ends in unmapped (-1) entries inside the walked
    extent, and one with nothing but its first page: the dead pages clamp
    to page 0 OF PLANE `layer` and contribute nothing."""
    from paddle_tpu.ops.pallas.paged_attention import paged_decode_attention

    rs = np.random.RandomState(18)
    slots, ps, nh, hd = 3, 8, 2, 16
    q = jnp.asarray(rs.randn(slots, nh, hd), jnp.float32)
    kp = jnp.asarray(rs.randn(_POOL_LAYERS, 7, ps, nh, hd), jnp.float32)
    vp = jnp.asarray(rs.randn(_POOL_LAYERS, 7, ps, nh, hd), jnp.float32)
    rows = jnp.asarray([[3, -1, -1, -1], [6, 2, -1, -1], [1, 4, 5, -1]],
                       jnp.int32)
    pos = jnp.asarray([0, 9, 23], jnp.int32)
    out = paged_decode_attention(q, kp, vp, rows, pos, 32, layer)
    ref = _dense_paged_ref(q, kp, vp, rows, pos, 32, layer)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def _dense_gqa_ref(q, kp, vp, rows, pos, layer, window):
    """Plane `layer` gathered densely for nh query heads over nkv KV heads
    (query head h reads KV head h // g), a lane seeing the keys at
    (pos - window, pos] (all up to pos without a window)."""
    slots, nh, hd = q.shape
    num_pages, ps, nkv = kp.shape[1], kp.shape[2], kp.shape[3]
    g = nh // nkv
    gidx = jnp.clip(rows, 0, num_pages - 1)
    kg = jnp.repeat(kp[layer, gidx].reshape(slots, -1, nkv, hd), g, axis=2)
    vg = jnp.repeat(vp[layer, gidx].reshape(slots, -1, nkv, hd), g, axis=2)
    s = jnp.einsum("bnd,bsnd->bns", q, kg) / np.sqrt(hd)
    tok = jnp.arange(kg.shape[1])[None, :]
    valid = (tok <= pos[:, None]) & jnp.repeat(rows >= 0, ps, axis=1)
    if window:
        valid = valid & (tok > pos[:, None] - window)
    s = jnp.where(valid[:, None, :], s, -1e30)
    return jnp.einsum("bns,bsnd->bnd", jax.nn.softmax(s, -1), vg)


@pytest.mark.kernels
@pytest.mark.parametrize("window", [0, 16, 24], ids=lambda w: f"window{w}")
@pytest.mark.parametrize("g", [8, 1], ids=lambda g: f"g{g}")
def test_paged_gqa_decode_attention_parity(g, window):
    """The grouped kernel against the dense gather: g query heads a KV head
    (8, and 1 where it does the ungrouped kernel's work), with and without
    a window (one that is whole pages and one that is not), ragged rows:
    a lane mid-page, one at a page boundary, one shorter than the window,
    one whose pages behind the window are unmapped as the engine leaves
    them, and unmapped tails."""
    from paddle_tpu.ops.pallas.paged_attention import \
        paged_gqa_decode_attention

    rs = np.random.RandomState(80 + g + window)
    slots, ps, nkv, hd, seq_cap, layer = 5, 8, 2, 16, 48, 1
    nh = nkv * g
    q = jnp.asarray(rs.randn(slots, nh, hd), jnp.float32)
    kp = jnp.asarray(rs.randn(3, 20, ps, nkv, hd), jnp.float32)
    vp = jnp.asarray(rs.randn(3, 20, ps, nkv, hd), jnp.float32)
    rows = np.array([[2, 5, 11, -1, -1, -1],     # mid-page
                     [7, 1, 3, 9, 12, 19],       # full table
                     [4, -1, -1, -1, -1, -1],    # shorter than any window
                     [6, 8, -1, -1, -1, -1],     # pos at a page boundary
                     [13, 14, 15, 16, 17, -1]], np.int32)
    pos = np.array([19, 47, 3, 15, 38], np.int32)
    if window:
        # the engine unmaps what lies wholly behind the window
        behind = (np.arange(6)[None] + 1) * ps <= (pos - window + 1)[:, None]
        rows = np.where(behind, -1, rows)
    rows, pos = jnp.asarray(rows), jnp.asarray(pos)
    ref = _dense_gqa_ref(q, kp, vp, rows, pos, layer, window)
    out = paged_gqa_decode_attention(q, kp, vp, rows, pos, seq_cap, layer,
                                     window)
    # float32 both sides; the kernel's running softmax sums in another order
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    out_j = jax.jit(lambda *a: paged_gqa_decode_attention(
        *a, seq_cap, layer, window))(q, kp, vp, rows, pos)
    np.testing.assert_allclose(np.asarray(out_j), np.asarray(out),
                               rtol=0, atol=0)


@pytest.mark.kernels
def test_paged_gqa_decode_reads_nothing_behind_the_window():
    """Poison (1e4) in every key and value at or before pos - window, in
    mapped pages too: the kernel's answer does not move."""
    from paddle_tpu.ops.pallas.paged_attention import \
        paged_gqa_decode_attention

    rs = np.random.RandomState(3)
    slots, ps, nkv, g, hd, window = 2, 8, 2, 4, 16, 16
    q = jnp.asarray(rs.randn(slots, nkv * g, hd), jnp.float32)
    kp = jnp.asarray(rs.randn(1, 8, ps, nkv, hd), jnp.float32)
    vp = jnp.asarray(rs.randn(1, 8, ps, nkv, hd), jnp.float32)
    rows = jnp.asarray([[0, 1, 2, 3], [4, 5, 6, 7]], jnp.int32)
    pos = jnp.asarray([29, 20], jnp.int32)
    clean = paged_gqa_decode_attention(q, kp, vp, rows, pos, 32, 0, window)
    tok = jnp.arange(4 * ps).reshape(4, ps)
    for lane in range(slots):
        bad = (tok <= pos[lane] - window)[:, :, None, None]
        ids = rows[lane]
        kp = kp.at[0, ids].set(jnp.where(bad, 1e4, kp[0, ids]))
        vp = vp.at[0, ids].set(jnp.where(bad, 1e4, vp[0, ids]))
    out = paged_gqa_decode_attention(q, kp, vp, rows, pos, 32, 0, window)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(clean))


# -- the walk inside the kernel ----------------------------------------------
# Both calls walk a lane's pages in blocks of `_block_pages` pages (16 of 8
# keys here: 128 keys a block, or the table's width where that is less)
# that the kernel copies itself; `_walk_case` builds a pool whose planes
# differ, a table and positions, and asks both calls and the dense gather.
def _walk_case(rows, pos, ps=8, nkv=2, g=1, hd=16, num_pages=None, window=0,
               layer=1, seed=0, cols=None):
    from paddle_tpu.ops.pallas.paged_attention import (
        paged_decode_attention, paged_gqa_decode_attention)

    rows, pos = np.asarray(rows, np.int32), np.asarray(pos, np.int32)
    rs = np.random.RandomState(seed)
    num_pages = num_pages or int(rows.max()) + 2
    q = jnp.asarray(rs.randn(rows.shape[0], nkv * g, hd), jnp.float32)
    kp = jnp.asarray(rs.randn(3, num_pages, ps, nkv, hd), jnp.float32)
    vp = jnp.asarray(rs.randn(3, num_pages, ps, nkv, hd), jnp.float32)
    seq_cap = (cols or rows.shape[1]) * ps
    ref = _dense_gqa_ref(q, kp, vp, jnp.asarray(rows), jnp.asarray(pos),
                         layer, window)
    # a lane that sees no key (released, or never armed) reads zero
    dead = (pos < 0) | (np.take_along_axis(
        rows, np.clip((np.maximum(pos - window + 1, 0) // ps if window
                       else 0 * pos)[:, None], 0, rows.shape[1] - 1),
        axis=1)[:, 0] < 0)
    ref = jnp.where(jnp.asarray(dead)[:, None, None], 0.0, ref)
    outs = [paged_gqa_decode_attention(q, kp, vp, rows, pos, seq_cap, layer,
                                       window)]
    if g == 1 and not window:
        outs.append(paged_decode_attention(q, kp, vp, rows, pos, seq_cap,
                                           layer))
    return [np.asarray(o) for o in outs], np.asarray(ref)


@pytest.fixture(params=["walk", "grid"])
def page_walk(request, monkeypatch):
    """Both of the file's walks, interpreted: the loop inside the kernel,
    and the grid's (`_block_pages` says 0), which off the CPU serves the
    pools whose page no DMA can cut out (GPT-2's 12 heads of 64)."""
    if request.param == "grid":
        from paddle_tpu.ops.pallas import paged_attention as pa
        monkeypatch.setattr(pa, "_block_pages", lambda *a, **k: 0)
    return request.param


def _table(lengths, cols, ps=8, start=1):
    """A page table whose lane i maps the pages that hold `lengths[i]`
    keys (ids counted up from `start`), -1 beyond."""
    rows, nxt = np.full((len(lengths), cols), -1, np.int32), start
    for i, n in enumerate(lengths):
        need = -(-n // ps)
        rows[i, :need] = np.arange(nxt, nxt + need)
        nxt += need
    return rows


# a block is 16 pages of 8 keys: positions on a block's first key (128),
# its last key (127, 255), one past a page boundary (136), and a page's
# first and last key inside a block
@pytest.mark.kernels
@pytest.mark.parametrize("pos", [0, 7, 8, 127, 128, 136, 255, 256, 300])
def test_paged_walk_position_on_a_block_and_page_edge(pos):
    outs, ref = _walk_case(_table([pos + 1], 40), [pos], seed=pos)
    for out in outs:
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


@pytest.mark.kernels
@pytest.mark.parametrize("g", [1, 4], ids=lambda g: f"g{g}")
def test_paged_walk_one_page_and_the_full_width_in_one_call(g, page_walk):
    """A lane one page deep beside a lane that fills the table (three
    blocks and a tail), a table width (37) that is no multiple of the
    block (16), and lanes between them."""
    cols = 37
    lengths = [3, cols * 8, 8, 129, 17 * 8]
    outs, ref = _walk_case(_table(lengths, cols), [n - 1 for n in lengths],
                           g=g, seed=11)
    for out in outs:
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


@pytest.mark.kernels
@pytest.mark.parametrize("g", [1, 4], ids=lambda g: f"g{g}")
def test_paged_walk_released_lanes_read_zero_and_move_nothing(g, page_walk):
    """Released lanes (`release_slots`: the row all -1, the position
    stale) and a lane never armed (position -1) beside live ones: zeros,
    and the live lanes' rows bit-equal to a call without them."""
    live_rows = _table([200, 9, 140], 32)
    dead = np.full((1, 32), -1, np.int32)
    rows = np.concatenate([dead, live_rows[:1], dead, live_rows[1:], dead])
    pos = np.array([150, 199, 3, 8, 139, -1], np.int32)
    outs, ref = _walk_case(rows, pos, g=g, seed=5)
    for out in outs:
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)
        assert not out[[0, 2, 5]].any()
    from paddle_tpu.ops.pallas.paged_attention import \
        paged_gqa_decode_attention
    rs = np.random.RandomState(5)
    q = jnp.asarray(rs.randn(6, 2 * g, 16), jnp.float32)
    kp = jnp.asarray(rs.randn(3, 60, 8, 2, 16), jnp.float32)
    vp = jnp.asarray(rs.randn(3, 60, 8, 2, 16), jnp.float32)
    both = paged_gqa_decode_attention(q, kp, vp, rows, pos, 256, 1)
    live = np.array([1, 3, 4])
    only = paged_gqa_decode_attention(q[live], kp, vp, rows[live], pos[live],
                                      256, 1)
    np.testing.assert_array_equal(np.asarray(both)[live], np.asarray(only))


@pytest.mark.kernels
@pytest.mark.parametrize("hole", ["middle", "tail", "block_start"])
def test_paged_walk_skips_an_unmapped_page_in_the_walked_range(hole,
                                                               page_walk):
    """A -1 between mapped pages, at the head of a block, and an unmapped
    tail before the position: the page is not fetched (its id is no page
    of the pool) and its keys weigh nothing."""
    rows = _table([300], 40)
    if hole == "middle":
        rows[0, 5] = rows[0, 20] = -1
    elif hole == "block_start":
        rows[0, 16] = rows[0, 32] = -1
    else:
        rows[0, 30:] = -1
    outs, ref = _walk_case(rows, [299], seed=9)
    for out in outs:
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


@pytest.mark.kernels
@pytest.mark.parametrize("window", [16, 40, 136])
def test_paged_walk_behind_a_window(window, page_walk):
    """Window layers: the walk starts at the first column that meets the
    window; the pages before it are unmapped as the engine leaves them,
    and one lane keeps them mapped (a shared prefix)."""
    lengths = [300, 300, 50, 7]
    rows = _table(lengths, 40)
    pos = np.array([n - 1 for n in lengths], np.int32)
    behind = (np.arange(40)[None] + 1) * 8 <= (pos - window + 1)[:, None]
    behind[1] = False
    outs, ref = _walk_case(np.where(behind, -1, rows), pos, g=4,
                           window=window, seed=window)
    np.testing.assert_allclose(outs[0], ref, rtol=1e-5, atol=1e-5)


@pytest.mark.kernels
def test_paged_walk_lanes_share_their_prefix_pages():
    """Two lanes map the same prefix pages (the prefix cache) and their
    own tails; a third maps the prefix alone."""
    rows = np.full((3, 24), -1, np.int32)
    rows[:, :18] = np.arange(1, 19)
    rows[0, 18:21] = [19, 20, 21]
    rows[1, 18:20] = [22, 23]
    outs, ref = _walk_case(rows, [165, 153, 143], seed=21)
    for out in outs:
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


@pytest.mark.kernels
@pytest.mark.parametrize("g", [1, 4], ids=lambda g: f"g{g}")
def test_paged_walk_reads_the_pools_last_page_in_a_tail_block(g):
    """The pool's last page id sits in a tail block whose other columns
    are past the position: a copy whose source were clamped, or taken
    from a column past the extent, would read another page and miss the
    reference."""
    cols, num_pages = 20, 21
    rows = np.full((1, cols), -1, np.int32)
    rows[0, :17] = np.arange(num_pages - 17, num_pages)[::-1]
    rows[0, 16] = num_pages - 1
    rows[0, 0] = 3
    outs, ref = _walk_case(rows, [16 * 8 + 2], g=g, num_pages=num_pages,
                           seed=4)
    for out in outs:
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


@pytest.mark.kernels
def test_paged_walk_at_gpt2s_heads(page_walk):
    """GPT-2's pages, [16, 12, 64]: interpreted, the loop inside the
    kernel takes them; on the chip such a page is no whole tile of the
    pool and the grid's walk serves (test_mosaic_compile compiles it)."""
    lengths = [40, 300, 16]
    outs, ref = _walk_case(_table(lengths, 20, ps=16),
                           [n - 1 for n in lengths], ps=16, nkv=12, hd=64,
                           seed=12)
    for out in outs:
        np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


@pytest.mark.kernels
@pytest.mark.parametrize("shape, cols, pages", [
    ((16, 16, 128), 64, 8),      # chat: pages of 16 keys, 16 heads of 128
    ((64, 4, 128), 128, 4),      # repochat's full layers: pages of 64 keys
    ((64, 4, 128), 17, 4),       # ... and what a window of 1024 can meet
    ((16, 16, 128), 5, 5),       # no longer than the walk
    ((128, 4, 128), 64, 2),      # as many as the VMEM budget holds
    ((256, 4, 128), 32, 1),      # a page of 256 keys is a block
    ((8, 2, 128), 64, 16),       # small pages: 128 keys
])
def test_paged_walk_block_comes_from_the_calls_shapes(shape, cols, pages):
    from paddle_tpu.ops.pallas.paged_attention import _block_pages

    pool = jax.ShapeDtypeStruct((2, 8) + shape, jnp.bfloat16)
    assert _block_pages(pool, cols, interpret=False) == pages


@pytest.mark.kernels
@pytest.mark.parametrize("rows, pages", [(8, 8), (16, 8), (32, 16),
                                         (64, 16)])
def test_paged_walk_block_doubles_its_keys_from_32_rows_a_product(rows,
                                                                  pages):
    """The blockgen cell's pool (pages of 16 keys x 4 KV heads of 128):
    8 pages a block for a one-token step's 8 rows a product, 16 for a
    block step's 32 (PERF.md section 6, PR 37), the VMEM budget's 16 at
    most."""
    from paddle_tpu.ops.pallas.paged_attention import _block_pages

    pool = jax.ShapeDtypeStruct((6, 1280, 16, 4, 128), jnp.bfloat16)
    assert _block_pages(pool, 80, interpret=False, rows=rows) == pages


# -- several queries a lane, one last key ------------------------------------
# A block-generating step brings C queries a lane that all see the lane's
# keys up to `pos`: the grouped call takes them [slots, C, nh, hd] and lays
# the C x g queries of a KV head as the rows of one product.
@pytest.mark.kernels
@pytest.mark.parametrize("g", [1, 4, 8], ids=lambda g: f"g{g}")
def test_paged_gqa_takes_a_lanes_queries_as_rows_of_one_product(g,
                                                                page_walk):
    """Against the one-token call asked once a query (the same keys, the
    same mask) and the dense gather: a lane of one page, one that fills
    the table (blocks and a tail), one at a block's edge, a released lane;
    at g = 8 the 32 rows a product walk blocks of twice the keys."""
    from paddle_tpu.ops.pallas.paged_attention import \
        paged_gqa_decode_attention

    C, ps, nkv, hd, cols, layer = 4, 8, 2, 16, 37, 1
    lengths = [3, cols * ps, 128, 17 * ps + 4, 0]
    rows = _table(lengths, cols)
    pos = np.asarray([n - 1 for n in lengths], np.int32)
    rs = np.random.RandomState(20 + g)
    q = jnp.asarray(rs.randn(len(lengths), C, nkv * g, hd), jnp.float32)
    kp = jnp.asarray(rs.randn(3, int(rows.max()) + 2, ps, nkv, hd),
                     jnp.float32)
    vp = jnp.asarray(rs.randn(*kp.shape), jnp.float32)
    out = np.asarray(paged_gqa_decode_attention(
        q, kp, vp, rows, pos, cols * ps, layer))
    assert out.shape == q.shape
    assert not out[-1].any()
    for c in range(C):
        one = paged_gqa_decode_attention(q[:, c], kp, vp, rows, pos,
                                         cols * ps, layer)
        np.testing.assert_allclose(out[:, c], np.asarray(one),
                                   rtol=1e-5, atol=1e-5)
        ref = _dense_gqa_ref(q[:, c], kp, vp, jnp.asarray(rows),
                             jnp.asarray(pos), layer, 0)
        np.testing.assert_allclose(out[:-1, c], np.asarray(ref)[:-1],
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.kernels
def test_paged_gqa_refuses_a_window_over_a_lanes_queries():
    """A window is measured from each query's own position: a mask a row,
    which one last key a lane is not."""
    from paddle_tpu.ops.pallas.paged_attention import \
        paged_gqa_decode_attention

    q = jnp.zeros((2, 4, 4, 16))
    kp = jnp.zeros((1, 4, 8, 2, 16))
    with pytest.raises(ValueError, match="window"):
        paged_gqa_decode_attention(q, kp, kp, jnp.zeros((2, 2), jnp.int32),
                                   jnp.zeros((2,), jnp.int32), 16, 0,
                                   window=8)


@pytest.mark.kernels
@pytest.mark.parametrize("shape, dtype", [
    ((16, 12, 64), jnp.bfloat16),     # GPT-2: heads of 64, 12 of them
    ((16, 12, 128), jnp.float32),     # 12 heads: no whole tiles
    ((16, 8, 64), jnp.bfloat16),      # heads of 64
])
def test_paged_pages_no_copy_can_cut_out_take_the_grid_walk(shape, dtype):
    """Off the CPU a page that is no whole tiles of the pool is walked by
    the grid (`_block_pages` 0); interpreted, the loop takes any page."""
    from paddle_tpu.ops.pallas.paged_attention import _block_pages

    pool = jax.ShapeDtypeStruct((2, 8) + shape, dtype)
    assert _block_pages(pool, 64, interpret=False) == 0
    assert _block_pages(pool, 64, interpret=True) >= 1


@pytest.mark.kernels
def test_paged_walk_refuses_pages_over_its_vmem_budget():
    from paddle_tpu.ops.pallas.paged_attention import _block_pages

    pool = jax.ShapeDtypeStruct((2, 8, 1024, 64, 128), jnp.bfloat16)
    with pytest.raises(DoesNotTile, match="MiB of VMEM"):
        _block_pages(pool, 64, interpret=False)


@pytest.mark.kernels
def test_paged_decode_attention_refusals():
    from paddle_tpu.ops.pallas.paged_attention import paged_decode_attention

    q = jnp.zeros((2, 2, 16))
    kp = jnp.zeros((3, 4, 8, 2, 16))
    rows = jnp.zeros((2, 2), jnp.int32)
    pos = jnp.zeros((2,), jnp.int32)
    with pytest.raises(DoesNotTile):  # table too narrow
        paged_decode_attention(q, kp, kp, rows, pos, seq_cap=64, layer=1)
    with pytest.raises(DoesNotTile):  # head mismatch
        paged_decode_attention(q, kp[..., :1, :], kp[..., :1, :], rows, pos,
                               16, 1)
    with pytest.raises(ValueError, match="whole pools"):  # a sliced plane
        paged_decode_attention(q, kp[1], kp[1], rows, pos, 16, 0)
    with pytest.raises(ValueError, match="outside a pool of 3"):
        paged_decode_attention(q, kp, kp, rows, pos, 16, 3)


@pytest.mark.kernels
@pytest.mark.parametrize("layer", _PLANES)
def test_sharded_paged_decode_tp2_parity(layer):
    from paddle_tpu.distributed.mesh import build_mesh
    from paddle_tpu.ops.pallas.paged_attention import (
        paged_decode_attention, sharded_paged_decode_attention)

    rs = np.random.RandomState(9)
    slots, ps, nh, hd = 2, 8, 4, 16
    q = jnp.asarray(rs.randn(slots, nh, hd), jnp.float32)
    kp = jnp.asarray(rs.randn(_POOL_LAYERS, 6, ps, nh, hd), jnp.float32)
    vp = jnp.asarray(rs.randn(_POOL_LAYERS, 6, ps, nh, hd), jnp.float32)
    rows = jnp.asarray([[1, 3], [5, -1]], jnp.int32)
    pos = jnp.asarray([12, 5], jnp.int32)
    mesh = build_mesh({"dp": 4, "tp": 2})
    out = sharded_paged_decode_attention(q, kp, vp, rows, pos, 16, layer,
                                         mesh, "tp")
    ref = paged_decode_attention(q, kp, vp, rows, pos, 16, layer)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(out),
        np.asarray(_dense_paged_ref(q, kp, vp, rows, pos, 16, layer)),
        rtol=1e-5, atol=1e-5)


def _decode_pages_case(layers=3):
    from paddle_tpu.models.gpt import GPTAttention, GPTConfig

    cfg = GPTConfig(hidden_size=32, num_heads=2, num_layers=1,
                    vocab_size=64, dropout=0.0, attn_dropout=0.0)
    attn = GPTAttention(cfg)
    attn.eval()
    rs = np.random.RandomState(10)
    ps, nh, hd = 8, 2, 16
    return attn, dict(
        x=rs.randn(2, 1, 32).astype("f"),
        kp=rs.randn(layers, 6, ps, nh, hd).astype("f"),
        vp=rs.randn(layers, 6, ps, nh, hd).astype("f"),
        rows=np.asarray([[1, 4], [2, -1]], np.int32),
        pos=np.asarray([9, 3], np.int32),
        active=np.asarray([True, True]))


def _paged_attend(attn, x, kp, vp, rows, pos, active, layer):
    """The attention layer over a PagedKV source (serving/kv_cache.py):
    pos [slots] is the decode step, [slots, C] a verified chunk."""
    from paddle_tpu.serving.kv_cache import PagedKV
    from paddle_tpu.tensor import Tensor

    return attn(Tensor(jnp.asarray(x)),
                PagedKV(*map(jnp.asarray, (kp, vp, rows, pos, active)), 16),
                layer)


def _run_decode_pages(attn, c, layer, **over):
    from paddle_tpu.tensor import unwrap

    c = {**c, **over}
    o, kv = _paged_attend(attn, c["x"], c["kp"].copy(), c["vp"].copy(),
                          c["rows"], c["pos"], c["active"], layer)
    return [np.asarray(unwrap(t)) for t in (o, kv.k_pages, kv.v_pages)]


@pytest.mark.kernels
def test_decode_pages_kernel_vs_dense_token_path(monkeypatch):
    """GPTAttention over a PagedKV source with the kernel produces the same
    context (to f32 tolerance) and the SAME page-pool contents as the
    dense-gather path, and the kernel call does not add steady-state
    recompiles (same jitted callable serves different table contents)."""
    from paddle_tpu.ops import fused
    from paddle_tpu.tensor import unwrap

    attn, c = _decode_pages_case()
    x, kp, vp, rows, pos, active = (c[k] for k in
                                    ("x", "kp", "vp", "rows", "pos", "active"))
    o_ref, k_ref, v_ref = _run_decode_pages(attn, c, 1)
    monkeypatch.setattr(fused, "_use_pallas", lambda: True)
    o_pal, k_pal, v_pal = _run_decode_pages(attn, c, 1)
    np.testing.assert_allclose(o_pal, o_ref, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(k_pal, k_ref)  # scatter untouched
    np.testing.assert_array_equal(v_pal, v_ref)
    assert k_pal.shape == kp.shape               # the whole pool comes back

    # compile tripwire: one jitted decode fn serves changed rows/pos
    calls = jax.jit(lambda r, p: unwrap(
        _paged_attend(attn, x, kp, vp, r, p, active, 1)[0]))
    calls(jnp.asarray(rows), jnp.asarray(pos))
    calls(jnp.asarray([[0, 5], [3, -1]], jnp.int32),
          jnp.asarray([14, 7], jnp.int32))
    assert calls._cache_size() == 1


@pytest.mark.kernels
@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["dense", "kernel"])
@pytest.mark.parametrize("chunk", [1, 2], ids=["decode", "verify"])
@pytest.mark.parametrize("layer", [0, 1, 2])
def test_pages_of_other_layers_come_back_untouched(monkeypatch, layer,
                                                   chunk, use_kernel):
    """The step writes plane `layer` and nothing else: the other planes of
    both pools come back bitwise as they went in, plane `layer` differs
    from its input in exactly the rows the live lanes wrote, and an
    inactive lane (it aims one past the pool) writes nowhere."""
    from paddle_tpu.ops import fused

    monkeypatch.setattr(fused, "_use_pallas", lambda: use_kernel)
    attn, c = _decode_pages_case()
    over = {"active": np.asarray([True, False])}
    if chunk == 2:                      # a chunk of 2 candidates a lane
        over["x"] = np.concatenate([c["x"], c["x"] * 0.5], axis=1)
        over["pos"] = np.stack([c["pos"], c["pos"] + 1], axis=1)
    _, kk, vv = _run_decode_pages(attn, c, layer, **over)
    ps = c["kp"].shape[2]
    for got, was in ((kk, c["kp"]), (vv, c["vp"])):
        others = [i for i in range(was.shape[0]) if i != layer]
        np.testing.assert_array_equal(got[others], was[others])
        changed = np.argwhere((got[layer] != was[layer]).any(axis=(-1, -2)))
        want = [[c["rows"][0, p // ps], p % ps]
                for p in np.atleast_1d(over.get("pos", c["pos"])[0])]
        assert changed.tolist() == sorted(want), (changed, want)


# (rows, vocabulary, ignored rows): every branch of the loss kernel's walk
XENT_SHAPES = {
    "one_chunk_v2": (16, 2, "first"),
    "one_chunk_v128": (16, 128, "first"),
    "whole_chunks": (256, 512, "first"),          # 4 chunks of 128 columns
    "tail_chunk_128x7": (64, 896, "first"),       # one chunk of 512, 384 left
    "tail_chunk_128x131": (16, 128 * 131, "first"),
    "padded_vocab_1000": (32, 1000, "first"),
    "padded_vocab_50257": (16, 50257, "first"),
    "rows_off_the_sublanes": (37, 1000, "first"),
    "several_row_blocks": (264, 256, "first"),    # 264 = 3 x 88 = 33 x 8
    "all_ignored_blocks": (264, 256, "half"),
    "every_row_ignored": (16, 384, "all"),
}


def _xent_ref(z, lab, ignore_index=-100):
    lp = jax.nn.log_softmax(z.astype(jnp.float32), -1)
    pick = jnp.take_along_axis(lp, lab[:, None].clip(0), 1)[:, 0]
    return jnp.where(lab == ignore_index, 0.0, -pick)


@pytest.mark.kernels
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("shape", list(XENT_SHAPES))
def test_softmax_xent_fwd_bwd_parity(shape, dtype):
    """Fused loss kernel vs the XLA composite: loss and gradient over one
    chunk, whole chunks, a tail chunk, a padded vocabulary (vocab % 128),
    rows off the sublane multiple and off the block, ignore_index rows
    (loss 0, gradient exactly 0) down to whole blocks of them.  bf16 is
    held to the composite on the same logits in float32, rounded once."""
    from paddle_tpu.ops.pallas.softmax_xent import softmax_xent

    n, v, ignored = XENT_SHAPES[shape]
    rs = np.random.RandomState(11)
    z = jnp.asarray(rs.randn(n, v), dtype)
    lab = jnp.asarray(rs.randint(0, v, n), jnp.int32)
    gone = {"first": slice(0, 1), "half": slice(0, n // 2),
            "all": slice(0, n)}[ignored]
    lab = lab.at[gone].set(-100)
    # a rounding of the result to bf16 is half a unit in its 8th bit
    tol = {"float32": dict(rtol=1e-5, atol=1e-5),
           "bfloat16": dict(rtol=2 ** -7, atol=1e-5)}[dtype]
    gtol = {"float32": dict(rtol=1e-4, atol=1e-5),
            "bfloat16": dict(rtol=2 ** -7, atol=1e-5)}[dtype]

    out = softmax_xent(z, lab)
    assert out.dtype == z.dtype and out.shape == (n,)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(_xent_ref(z, lab)), **tol)
    g1 = jax.grad(lambda zz: softmax_xent(zz, lab).astype(
        jnp.float32).sum())(z)
    g2 = jax.grad(lambda zz: _xent_ref(zz, lab).sum())(
        z.astype(jnp.float32))
    assert g1.dtype == z.dtype
    np.testing.assert_allclose(np.asarray(g1, np.float32), np.asarray(g2),
                               **gtol)
    # ignored rows: loss 0 and exactly zero gradient
    assert float(jnp.abs(out[gone]).max()) == 0.0
    assert float(jnp.abs(g1[gone]).max()) == 0.0


@pytest.mark.kernels
def test_softmax_xent_ignore_index_that_is_a_column():
    """An ignore_index inside the vocabulary ignores the rows that carry
    it and nothing else."""
    from paddle_tpu.ops.pallas.softmax_xent import softmax_xent

    rs = np.random.RandomState(12)
    z = jnp.asarray(rs.randn(24, 300), jnp.float32)
    lab = jnp.asarray(rs.randint(1, 300, 24), jnp.int32).at[::3].set(0)
    out = softmax_xent(z, lab, ignore_index=0)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_xent_ref(z, lab, 0)),
                               rtol=1e-5, atol=1e-5)
    g = jax.grad(lambda zz: softmax_xent(zz, lab, ignore_index=0).sum())(z)
    assert float(jnp.abs(g[::3]).max()) == 0.0
    assert float(jnp.abs(g[1::3]).min()) > 0.0


# (n, v, itemsize) as the wrapper pads them; the callers of the tree:
# GPT-2's cell whole and over dp=4, BERT's MLM and NSP heads, ResNet's
XENT_CALLS = [(16384, 50304, 2), (4096, 50304, 2), (16384, 50304, 4),
              (4096, 30592, 4), (4096, 30592, 2), (4096, 128, 4),
              (256, 1024, 4), (1008, 50304, 2), (40, 1024, 4), (16, 128, 2),
              (8, 128, 4), (2048, 131072, 2)]


@pytest.mark.kernels
@pytest.mark.parametrize("backward", [False, True], ids=["fwd", "bwd"])
@pytest.mark.parametrize("n,v,itemsize", XENT_CALLS)
def test_softmax_xent_pick_blocks(n, v, itemsize, backward):
    """The row block divides n, is whole native tiles of the type, and
    fits the budget by the module's own arithmetic (or is the narrowest
    there is, under a scoped limit raised to hold it); the chunk is whole
    lane groups of the vocabulary."""
    from paddle_tpu.ops.pallas import softmax_xent as sx

    rows, chunk = sx.pick_blocks(n, v, itemsize, backward)
    sub = {2: 16, 4: 8}[itemsize]
    assert n % rows == 0 and rows % sub == 0
    assert chunk % 128 == 0 and 128 <= chunk <= v
    assert rows * 128 <= sx._TILE_ELEMS
    need = sx._vmem_bytes(rows, v, itemsize, backward)
    limit = sx._vmem_limit(rows, v, itemsize, backward)
    if need <= sx._VMEM_BUDGET:
        assert limit == sx._VMEM_SCOPED
        # and no larger block would have done
        widest = min(n, sx._TILE_ELEMS // 128)
        assert not [r for r in range(rows + sub, widest + 1, sub)
                    if n % r == 0 and sx._vmem_bytes(
                        r, v, itemsize, backward) <= sx._VMEM_BUDGET]
    else:
        assert rows == sub and need < limit <= sx._VMEM_CEILING
    # what is counted covers the resident blocks
    assert need > (4 if backward else 2) * rows * v * itemsize


@pytest.mark.kernels
def test_softmax_xent_blocks_of_the_train_cell():
    """GPT-2 124M's step, 16384 x 50304 bf16: 32 rows a grid step forward
    and 16 backward, grids of 512 and 1,024 steps."""
    from paddle_tpu.ops.pallas import softmax_xent as sx

    n, v = 16 * 1024, 50304
    fwd, _ = sx.pick_blocks(n, v, 2, backward=False)
    bwd, _ = sx.pick_blocks(n, v, 2, backward=True)
    assert (n // fwd, n // bwd) == (512, 1024)


@pytest.mark.kernels
def test_softmax_xent_vocabulary_too_wide_takes_the_composite(monkeypatch):
    """A vocabulary whose narrowest row block outgrows VMEM: DoesNotTile
    from the shapes, before anything is traced, for the backward's sake
    too; ops/fused.py counts a fallback and gives the composite's loss."""
    from paddle_tpu.ops import fused
    from paddle_tpu.ops.pallas import softmax_xent as sx

    wide = 128 * 2048
    with pytest.raises(DoesNotTile):
        sx.pick_blocks(16, wide, 2, backward=True)
    with pytest.raises(DoesNotTile):
        jax.eval_shape(sx.softmax_xent,
                       jax.ShapeDtypeStruct((16, wide), jnp.bfloat16),
                       jax.ShapeDtypeStruct((16,), jnp.int32))
    # the same way out at a size a test can run: the ceiling pulled down
    monkeypatch.setattr(sx, "_VMEM_CEILING", sx._VMEM_SCOPED)
    monkeypatch.setattr(sx, "_VMEM_BUDGET", 64 * 1024)
    rs = np.random.RandomState(13)
    z = paddle.to_tensor(rs.randn(16, 4096).astype("f"))
    lab = paddle.to_tensor(rs.randint(0, 4096, 16))
    ref = fused.softmax_cross_entropy(z, lab)
    monkeypatch.setattr(fused, "_use_pallas", lambda: True)
    monkeypatch.setattr(fused, "_warned_sites", set())
    counter = fused.fallback_counter()
    key = ("softmax_xent", "shape")
    base = counter.values.get(key, 0)
    with pytest.warns(RuntimeWarning, match="softmax_xent"):
        out = fused.softmax_cross_entropy(z, lab)
    assert counter.values[key] == base + 1
    np.testing.assert_array_equal(np.asarray(out.value),
                                  np.asarray(ref.value))


@pytest.mark.kernels
def test_cross_entropy_gate_reaches_kernel(monkeypatch):
    """nn.functional.cross_entropy -> ops/fused gate -> pallas kernel:
    same loss as the flag-off composite, batched [B, S, V] logits."""
    import paddle_tpu.nn.functional as F
    from paddle_tpu.ops import fused

    rs = np.random.RandomState(12)
    logits = paddle.to_tensor(rs.randn(2, 16, 1000).astype("f"))
    labels = paddle.to_tensor(rs.randint(0, 1000, (2, 16)))
    ref = F.cross_entropy(logits, labels, reduction="none")
    monkeypatch.setattr(fused, "_use_pallas", lambda: True)
    out = F.cross_entropy(logits, labels, reduction="none")
    np.testing.assert_allclose(np.asarray(out.value), np.asarray(ref.value),
                               rtol=1e-5, atol=1e-5)


def _bias_gelu(x, b):
    from paddle_tpu.ops import fused

    return fused.unwrap(fused.bias_gelu(x, b))


def _exact_bias_gelu(x, b):
    return jax.nn.gelu(x.astype(jnp.float32) + b.astype(jnp.float32),
                       approximate=False)


# rows the Pallas pass refused (7; one) are ordinary to the composite
@pytest.mark.kernels
@pytest.mark.parametrize("shape", [(16, 8, 256), (7, 256), (1, 1, 3072),
                                   (128, 3072)],
                         ids=lambda s: "x".join(map(str, s)))
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_bias_gelu_fwd_bwd_parity(dtype, shape):
    """fused.bias_gelu against float32 `jax.nn.gelu(x + b)`: the forward,
    and both gradients against its autodiff.  float32 holds the
    tolerances the Pallas pass was held to; bfloat16 rounds once, at the
    end (8 bits)."""
    rs = np.random.RandomState(13)
    x = jnp.asarray(rs.randn(*shape) * 1.5, dtype)
    b = jnp.asarray(rs.randn(shape[-1]), dtype)
    # a cotangent that the result's dtype holds exactly
    c = jnp.asarray(rs.randn(*shape), dtype).astype(jnp.float32)
    fwd, grad = ((1e-5, 1e-6), (1e-4, 1e-4)) if dtype == "float32" \
        else ((1e-2, 1e-2), (1e-2, 1e-2))

    got = _bias_gelu(x, b)
    assert got.dtype == x.dtype and got.shape == x.shape
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(_exact_bias_gelu(x, b)),
                               rtol=fwd[0], atol=fwd[1])

    def loss(f):
        return lambda x, b: (f(x, b).astype(jnp.float32) * c).sum()

    g1 = jax.grad(loss(_bias_gelu), (0, 1))(x, b)
    g2 = jax.grad(loss(_exact_bias_gelu), (0, 1))(x, b)
    for a, bb in zip(g1, g2):
        assert a.dtype == bb.dtype == jnp.dtype(dtype)
        scale = float(jnp.abs(bb.astype(jnp.float32)).max())
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(bb, np.float32),
                                   rtol=grad[0], atol=grad[1] * max(scale, 1))


@pytest.mark.kernels
def test_bias_gelu_backward_keeps_the_preactivation_alone():
    """The backward recomputes from what the forward was given: the vjp's
    closure holds x and b and no third array of the activation's shape
    (plain autodiff of `jax.nn.gelu` keeps three, erf's value among
    them, which XLA then stores at the activation's width)."""
    x = jnp.ones((8, 3072), jnp.bfloat16)
    b = jnp.ones((3072,), jnp.float32)
    _, vjp = jax.vjp(_bias_gelu, x, b)
    kept = sorted((v.shape, str(v.dtype))
                  for v in jax.tree_util.tree_leaves(vjp))
    assert kept == [((8, 3072), "bfloat16"), ((3072,), "float32")]
    _, plain = jax.vjp(_exact_bias_gelu, x, b)
    assert sum(v.shape == x.shape
               for v in jax.tree_util.tree_leaves(plain)) > 1


@pytest.mark.kernels
def test_bias_gelu_float64_keeps_its_precision():
    """x64 is on on the CPU: a float64 input is computed in float64 with
    `lax.erf`, not through the float32 polynomial (4.5e-7 off)."""
    rs = np.random.RandomState(5)
    x = jnp.asarray(rs.randn(7, 256) * 1.5, jnp.float64)
    b = jnp.asarray(rs.randn(256), jnp.float64)

    def exact(x, b):
        return jax.nn.gelu(x + b, approximate=False)

    got = _bias_gelu(x, b)
    assert got.dtype == jnp.float64
    np.testing.assert_allclose(np.asarray(got), np.asarray(exact(x, b)),
                               rtol=1e-12, atol=1e-13)
    g1 = jax.grad(lambda x, b: _bias_gelu(x, b).sum(), (0, 1))(x, b)
    g2 = jax.grad(lambda x, b: exact(x, b).sum(), (0, 1))(x, b)
    for a, bb in zip(g1, g2):
        assert a.dtype == jnp.float64
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb),
                                   rtol=1e-11, atol=1e-12)


@pytest.mark.kernels
def test_gpt_mlp_and_encoder_ffn_route_fused(monkeypatch):
    """GPTMLP and TransformerEncoderLayer hit fused.linear_bias_gelu with
    no model changes: flag-on output == flag-off output."""
    from paddle_tpu.models.gpt import GPTConfig, GPTMLP
    from paddle_tpu.nn.layer.transformer import TransformerEncoderLayer
    from paddle_tpu.ops import fused

    rs = np.random.RandomState(14)
    mlp = GPTMLP(GPTConfig(hidden_size=64, dropout=0.0))
    mlp.eval()
    x = paddle.to_tensor(rs.randn(2, 8, 64).astype("f"))
    ref = mlp(x)
    enc = TransformerEncoderLayer(64, 4, 128, dropout=0.0,
                                  activation="gelu", attn_dropout=0.0,
                                  act_dropout=0.0)
    enc.eval()
    src = paddle.to_tensor(rs.randn(2, 16, 64).astype("f"))
    enc_ref = enc(src)
    monkeypatch.setattr(fused, "_use_pallas", lambda: True)
    np.testing.assert_allclose(np.asarray(mlp(x).value),
                               np.asarray(ref.value),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(enc(src).value),
                               np.asarray(enc_ref.value),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.kernels
def test_masked_training_step_through_kernels(monkeypatch):
    """End-to-end flag-on masked+causal training step: grads flow through
    the flash kernel, the xent kernel, and bias-gelu with ZERO fallbacks
    recorded — the op_report/fallback contract at unit scale (at GPT-2
    124M's scale on the chip: chip_smoke.py's kernels and train phases)."""
    import paddle_tpu.nn.functional as F
    from paddle_tpu.ops import fused
    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    rs = np.random.RandomState(15)
    B, S, H, D, V = 2, 128, 2, 64, 512
    q = jnp.asarray(rs.randn(B, S, H, D), jnp.float32)
    w_out = jnp.asarray(rs.randn(H * D, V) * 0.05, jnp.float32)
    bias = jnp.asarray(rs.randn(V) * 0.05, jnp.float32)
    mask = jnp.asarray(rs.rand(B, 1, 1, S) > 0.1)
    labels = jnp.asarray(rs.randint(0, V, (B, S)), jnp.int32)
    monkeypatch.setattr(fused, "_use_pallas", lambda: True)
    before = dict(fused.fallback_counter().values)

    from paddle_tpu.ops.pallas.softmax_xent import softmax_xent

    def loss_fn(q, w, b):
        ctx = flash_attention(q, q, q, causal=True, mask=mask)
        h = _bias_gelu(ctx.reshape(B * S, H * D) @ w, b)
        return softmax_xent(h.reshape(B, S, V), labels).mean()

    loss, grads = jax.value_and_grad(loss_fn, (0, 1, 2))(q, w_out, bias)
    assert np.isfinite(float(loss))
    assert all(bool(jnp.isfinite(g).all()) for g in grads)
    assert dict(fused.fallback_counter().values) == before
