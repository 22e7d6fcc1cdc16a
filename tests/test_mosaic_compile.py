"""The Pallas kernels of the main path, compiled by Mosaic for a described
TPU v5e at GPT-2 124M shapes (B=8, S=1024, 12 heads x 64, H=768, FFN=3072,
V=50304; 16 slots x 16-token pages).

Nothing runs: the chip is described, not attached (`on-chip-measurement`
guide, section 2), so a pass says the chip's compiler accepts the kernel
and nothing about its results or speed.  Interpret mode (the rest of the
kernel tests) cannot see what is checked here: unsupported primitives,
layouts Mosaic has no cast for, dot dimension orders, scoped-VMEM limits.
x64 is off as it is on the chip."""
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import pytest

import paddle_tpu  # noqa: F401 - the package decides the process's x64 mode
from paddle_tpu.ops.pallas import (bias_gelu as bg, flash_attention as fa,
                                   layer_norm as ln, paged_attention as pa,
                                   softmax_xent as sx)

B, S, NH, HD, H, FFN, V = 8, 1024, 12, 64, 768, 3072, 50304
SLOTS, PAGE = 16, 16
PAGES_PER_SLOT = S // PAGE
BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler in this install
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep the cache out of it
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    with jax.enable_x64(False):
        yield topo
    jax.config.update("jax_enable_compilation_cache", cache_was)
    cc.reset_cache()


def _sum32(f):
    return lambda *a: f(*a).astype(F32).sum()


QKV = [((B, S, NH, HD), BF16)] * 3
PAD_MASK = ((B, 1, 1, S), jnp.bool_)
LN_F32 = [((B, S, H), F32), ((H,), F32), ((H,), F32)]      # fit, autocast
LN_BF16 = [((SLOTS, 1, H), BF16), ((H,), BF16), ((H,), BF16)]  # bf16 decode
GELU_ARGS = [((B, S, FFN), BF16), ((FFN,), BF16)]
XENT_BF16 = [((B * S, V), BF16), ((B * S,), I32)]
XENT_F32 = [((2048, 50257), F32), ((2048,), I32)]   # BERT/HF vocab, padded
PAGED_ARGS = [((SLOTS, NH, HD), BF16),
              ((SLOTS * PAGES_PER_SLOT + 1, PAGE, NH, HD), BF16),
              ((SLOTS * PAGES_PER_SLOT + 1, PAGE, NH, HD), BF16),
              ((SLOTS, PAGES_PER_SLOT), I32), ((SLOTS,), I32)]


def _flash(causal):
    def f(q, k, v, *mask):
        return fa.flash_attention(q, k, v, causal=causal, interpret=False,
                                  mask=mask[0] if mask else None)
    return f


def _ln(x, w, b):
    return ln.layer_norm(x, w, b, interpret=False)


def _gelu(x, b):
    return bg.bias_gelu(x, b, interpret=False)


def _xent(z, lab):
    return sx.softmax_xent(z, lab, interpret=False)


def _paged(q, kp, vp, rows, pos):
    return pa.paged_decode_attention(q, kp, vp, rows, pos, S,
                                     interpret=False)


def _bwd(f, n):
    return jax.grad(_sum32(f), argnums=tuple(range(n)))


CASES = {
    "flash_causal_fwd": (_flash(True), QKV),
    "flash_causal_bwd": (_bwd(_flash(True), 3), QKV),
    "flash_masked_fwd": (_flash(False), QKV + [PAD_MASK]),
    "flash_masked_bwd": (_bwd(_flash(False), 3), QKV + [PAD_MASK]),
    "layer_norm_f32_fwd": (_ln, LN_F32),
    "layer_norm_f32_bwd": (_bwd(_ln, 3), LN_F32),
    "layer_norm_bf16_decode_rows": (_ln, LN_BF16),
    "bias_gelu_fwd": (_gelu, GELU_ARGS),
    "bias_gelu_bwd": (_bwd(_gelu, 2), GELU_ARGS),
    "softmax_xent_bf16_fwd": (_xent, XENT_BF16),
    "softmax_xent_bf16_bwd": (_bwd(_xent, 1), XENT_BF16),
    "softmax_xent_f32_v50257_fwd": (_xent, XENT_F32),
    "softmax_xent_f32_v50257_bwd": (_bwd(_xent, 1), XENT_F32),
    "paged_decode": (_paged, PAGED_ARGS),
}


_TEXT = {}      # case -> the compiled HLO text, compiled once a module


def _compiled_text(v5e, name):
    if name not in _TEXT:
        f, args = CASES[name]
        one_chip = jax.sharding.SingleDeviceSharding(v5e.devices[0])
        shapes = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                  for s, d in args]
        _TEXT[name] = jax.jit(f).lower(*shapes).compile().as_text()
    return _TEXT[name]


@pytest.mark.kernels
@pytest.mark.parametrize("name", list(CASES))
def test_kernel_compiles_for_v5e(v5e, name):
    assert "tpu_custom_call" in _compiled_text(v5e, name)


# every `pallas_call` of ops/pallas has a `name=`: the compiled
# custom-call instruction carries it (`%jvp_paddle_flash_fwd_.1 = ...`),
# and that instruction's text names the kernel's events on a profiler
# trace's `XLA Ops` line (README.md "Reading a trace")
KERNEL_NAMES = {
    "paddle_flash_fwd": "flash_causal_fwd",
    "paddle_flash_dq": "flash_causal_bwd",
    "paddle_flash_dkv": "flash_causal_bwd",
    "paddle_softmax_xent_fwd": "softmax_xent_bf16_fwd",
    "paddle_softmax_xent_bwd": "softmax_xent_bf16_bwd",
    "paddle_layer_norm_fwd": "layer_norm_f32_fwd",
    "paddle_bias_gelu_fwd": "bias_gelu_fwd",
    "paddle_bias_gelu_bwd": "bias_gelu_bwd",
    "paddle_paged_decode_fwd": "paged_decode",
}


@pytest.mark.kernels
@pytest.mark.parametrize("kernel", list(KERNEL_NAMES))
def test_kernel_name_is_on_the_compiled_call(v5e, kernel):
    import re

    calls = [line.split(" = ", 1)[0].strip().removeprefix("ROOT ")
             for line in _compiled_text(v5e, KERNEL_NAMES[kernel]).splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    # jax wraps the name in the transformations it traced the call under
    assert any(re.fullmatch(rf"%(\w+_)?{kernel}_*(\.\d+)?", c)
               for c in calls), calls


@pytest.mark.kernels
def test_kernels_compose_with_a_2x2_mesh(v5e):
    """GSPMD cannot partition a Mosaic kernel (the lowering raises
    NotImplementedError), so under a mesh every kernel call goes through
    shard_map, the way ops/fused.py composes them."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from paddle_tpu.ops import fused

    mesh = Mesh(np.array(v5e.devices).reshape(2, 2), ("fsdp", "tp"))
    rows = NamedSharding(mesh, P("fsdp"))
    whole = NamedSharding(mesh, P())

    def step(x, w, b, q):
        y = fused._rows_sharded(_ln, mesh, ("fsdp",), x, w, b)
        o = fa.sharded_flash_attention(q, q, q, mesh, head_axis="tp",
                                       batch_axes=("fsdp",), causal=True,
                                       interpret=False)
        return y.astype(F32).sum() + o.astype(F32).sum()

    args = [jax.ShapeDtypeStruct(s, d, sharding=sh) for (s, d), sh in
            zip(LN_F32 + QKV[:1], (rows, whole, whole, rows))]
    with pytest.raises(NotImplementedError, match="shard_map"):
        jax.jit(_ln).lower(*args[:3])
    compiled = jax.jit(jax.grad(step, argnums=(0, 3))).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
