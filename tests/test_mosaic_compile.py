"""The Pallas kernels of the main path, compiled by Mosaic for a described
TPU v5e at GPT-2 124M shapes (B=8, S=1024, 12 heads x 64, H=768, FFN=3072,
V=50304), flash attention and the loss at the benchmark cells' own sizes
(the train cell's B=16; the chat cell's prefill buckets), and the paged
decode calls at the chat and repochat cells' (16 slots; pages of 16 keys
and 16 heads of 128; pages of 64 keys and 4 KV heads of 128).

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
from paddle_tpu.ops.pallas import (flash_attention as fa, layer_norm as ln,
                                   moe_gmm as mg, paged_attention as pa,
                                   softmax_xent as sx, ssm)

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


# flash attention as the cells call it: gpt2-124m.train (16 x 1024, 12
# heads of 64) and cerebras-gpt-1.3b.chat's `jit_target_prefill` (one
# prompt padded to a bucket, 16 heads of 128)
FLASH_B = 16
QKV = [((FLASH_B, S, NH, HD), BF16)] * 3
PAD_MASK = ((FLASH_B, 1, 1, S), jnp.bool_)
CHAT_BUCKETS, CHAT_NH, CHAT_HD = (256, 512, 768), 16, 128
LN_F32 = [((B, S, H), F32), ((H,), F32), ((H,), F32)]      # fit, autocast
LN_BF16 = [((SLOTS, 1, H), BF16), ((H,), BF16), ((H,), BF16)]  # bf16 decode
XENT_BF16 = [((B * S, V), BF16), ((B * S,), I32)]
# the loss as gpt2-124m.train calls it: 16 x 1024 rows a step
XENT_N = 16 * S
XENT_TRAIN = [((XENT_N, V), BF16), ((XENT_N,), I32)]
XENT_F32 = [((2048, 50257), F32), ((2048,), I32)]   # BERT/HF vocab, padded
# a vocabulary whose narrowest row block is over the kernel's VMEM budget:
# it runs under a raised scoped limit (softmax_xent.pick_blocks)
XENT_WIDE = [((2048, 131072), BF16), ((2048,), I32)]
# the engine's stacked pool, all layers of it: the kernel reads one plane.
# The paged call as cerebras-gpt-1.3b.chat's decode step makes it: 16 slots
# x 1024 in pages of 16, 16 heads of 128, 24 planes; and as GPT-2 124M's
# makes it (12 planes, 12 heads of 64: pages that are no whole tiles of the
# pool, which the grid walks: paged_attention._grid_kernel)
POOL_LAYERS, PAGED_LAYER = 24, 5
POOL = ((POOL_LAYERS, SLOTS * PAGES_PER_SLOT, PAGE, CHAT_NH, CHAT_HD), BF16)
PAGED_ARGS = [((SLOTS, CHAT_NH, CHAT_HD), BF16), POOL, POOL,
              ((SLOTS, PAGES_PER_SLOT), I32), ((SLOTS,), I32)]
GPT2_POOL = ((12, SLOTS * PAGES_PER_SLOT + 1, PAGE, NH, HD), BF16)
PAGED_GPT2_ARGS = [((SLOTS, NH, HD), BF16), GPT2_POOL, GPT2_POOL,
                   ((SLOTS, PAGES_PER_SLOT), I32), ((SLOTS,), I32)]


def _flash(causal):
    def f(q, k, v, *mask):
        return fa.flash_attention(q, k, v, causal=causal, interpret=False,
                                  mask=mask[0] if mask else None)
    return f


def _ln(x, w, b):
    return ln.layer_norm(x, w, b, interpret=False)


def _xent(z, lab):
    return sx.softmax_xent(z, lab, interpret=False)


def _paged(q, kp, vp, rows, pos):
    return pa.paged_decode_attention(q, kp, vp, rows, pos, S, PAGED_LAYER,
                                     interpret=False)


# the grouped call as a decode step of `configs/mellum2-12b-a2.5b.json`
# makes it at 16 slots x 8192 (PERF.md section 4, item 4):
# 16 lanes of 32 query heads over 4 KV heads of 128, 8192 positions in
# pages of 64; plane 1 of the 2 full layers' pool (2048 pages), and plane 4
# of the 6 window layers' pool (800 pages) under a window of 1024
GQA_SEQ, GQA_NH, GQA_NKV, GQA_HD, GQA_WINDOW = 8192, 32, 4, 128, 1024
GQA_PAGE = 64


def _gqa_case(planes, pages, layer, window):
    pool = ((planes, pages, GQA_PAGE, GQA_NKV, GQA_HD), BF16)

    def f(q, kp, vp, rows, pos):
        return pa.paged_gqa_decode_attention(
            q, kp, vp, rows, pos, GQA_SEQ, layer, window, interpret=False)

    return f, [((SLOTS, GQA_NH, GQA_HD), BF16), pool, pool,
               ((SLOTS, GQA_SEQ // GQA_PAGE), I32), ((SLOTS,), I32)]


# the grouped call as a block step of `configs/sdar-30b-a3b.json` makes it
# at 16 slots x 1280 (PERF.md section 4, item 3): a block of 4 queries a
# lane, 32 query heads over 4 KV heads of 128, all seeing the lane's keys
# to the block's end: the 4 x 8 queries of a KV head are the rows of one
# product; 1280 positions in pages of 16, plane 3 of the 6 layers' pool
BLOCK_C, BLOCK_SEQ, BLOCK_LAYERS = 4, 1280, 6
BLOCK_POOL = ((BLOCK_LAYERS, SLOTS * BLOCK_SEQ // PAGE, PAGE, GQA_NKV,
               GQA_HD), BF16)


def _gqa_block(q, kp, vp, rows, pos):
    return pa.paged_gqa_decode_attention(q, kp, vp, rows, pos, BLOCK_SEQ, 3,
                                         interpret=False)


GQA_BLOCK_ARGS = [((SLOTS, BLOCK_C, GQA_NH, GQA_HD), BF16), BLOCK_POOL,
                  BLOCK_POOL, ((SLOTS, BLOCK_SEQ // PAGE), I32),
                  ((SLOTS,), I32)]


# the grouped expert FFN as sdar-30b-a3b.blockgen calls it: 128 experts of
# 2048 x 768 (gate, up) and 768 x 2048 (down); a block step's 512
# assignments (16 lanes x 4 positions x 8 experts) and a 768-token
# prefill's 6144, each in the padded grouped layout of ops/fused.moe_layout
MOE_E, MOE_H, MOE_F = 128, 2048, 768


def _gmm_case(assignments):
    tm = mg.pick_tile_rows(assignments, MOE_E)
    rows = -(-min(assignments + MOE_E * (tm - 1), assignments * tm)
             // tm) * tm

    def f(x, wg, wu, wd, te, n_used):
        return mg.moe_gmm(x, wg, wu, wd, te, n_used, tm, interpret=False)

    return f, [((rows, MOE_H), BF16), ((MOE_E, MOE_H, MOE_F), BF16),
               ((MOE_E, MOE_H, MOE_F), BF16), ((MOE_E, MOE_F, MOE_H), BF16),
               ((rows // tm,), I32), ((), I32)]


# the selective scan as granite-4.0-h-micro.toolchat calls it: 64 heads of
# [64, 128] float32 a layer; the prompt pass's buckets of 256 and 1280
# tokens in chunks of 256 (bf16 operands); the one-token update over the
# whole array of held states, 36 layers x 48 slots x [32, 128, 128]
# (two heads side by side on the lanes), rewritten in place
SSM_H, SSM_P, SSM_N, SSM_CHUNK, SSM_LAYERS, SSM_SLOTS = 64, 64, 128, 256, 36, 48
SSM_HELD = (SSM_LAYERS, SSM_SLOTS, SSM_H // 2, SSM_N, 2 * SSM_P)


def _ssd_case(tokens):
    def f(x, dt, a, b, c, s0):
        return ssm.ssd_chunk_scan(x, dt, a, b, c, s0, SSM_CHUNK,
                                  interpret=False)

    return f, [((tokens, SSM_H, SSM_P), BF16), ((tokens, SSM_H), F32),
               ((SSM_H,), F32), ((tokens, SSM_N), BF16),
               ((tokens, SSM_N), BF16), ((SSM_H, SSM_P, SSM_N), F32)]


def _ssm_update(held, lanes, n_live, decay, dtx, b, c):
    return ssm.ssm_decode_update(held, 7, lanes, n_live, decay, dtx, b, c,
                                 interpret=False)


SSM_UPDATE_ARGS = [(SSM_HELD, F32), ((SSM_SLOTS,), I32), ((), I32),
                   ((SSM_SLOTS, SSM_H // 2, 2 * SSM_P), F32),
                   ((SSM_SLOTS, SSM_H // 2, 2 * SSM_P), F32),
                   ((SSM_SLOTS, SSM_N), F32), ((SSM_SLOTS, SSM_N), F32)]


def _bwd(f, n):
    return jax.grad(_sum32(f), argnums=tuple(range(n)))


CASES = {
    "flash_causal_fwd": (_flash(True), QKV),
    "flash_causal_bwd": (_bwd(_flash(True), 3), QKV),
    "flash_masked_fwd": (_flash(False), QKV + [PAD_MASK]),
    "flash_masked_bwd": (_bwd(_flash(False), 3), QKV + [PAD_MASK]),
    **{f"flash_chat_prefill_{s}": (
        _flash(True), [((1, s, CHAT_NH, CHAT_HD), BF16)] * 3)
       for s in CHAT_BUCKETS},
    "layer_norm_f32_fwd": (_ln, LN_F32),
    "layer_norm_f32_bwd": (_bwd(_ln, 3), LN_F32),
    "layer_norm_bf16_decode_rows": (_ln, LN_BF16),
    "softmax_xent_bf16_fwd": (_xent, XENT_BF16),
    "softmax_xent_bf16_bwd": (_bwd(_xent, 1), XENT_BF16),
    "softmax_xent_train_fwd": (_xent, XENT_TRAIN),
    "softmax_xent_train_bwd": (_bwd(_xent, 1), XENT_TRAIN),
    "softmax_xent_f32_v50257_fwd": (_xent, XENT_F32),
    "softmax_xent_f32_v50257_bwd": (_bwd(_xent, 1), XENT_F32),
    "softmax_xent_bf16_v131072_fwd": (_xent, XENT_WIDE),
    "softmax_xent_bf16_v131072_bwd": (_bwd(_xent, 1), XENT_WIDE),
    "paged_decode": (_paged, PAGED_ARGS),
    "paged_decode_gpt2": (_paged, PAGED_GPT2_ARGS),
    "paged_gqa_decode_full": _gqa_case(2, 2048, 1, 0),
    "paged_gqa_decode_window": _gqa_case(6, 800, 4, GQA_WINDOW),
    "paged_gqa_block_step": (_gqa_block, GQA_BLOCK_ARGS),
    "moe_gmm_block_step_512": _gmm_case(512),
    "moe_gmm_prefill_6144": _gmm_case(6144),
    "ssd_chunk_scan_256": _ssd_case(256),
    "ssd_chunk_scan_1280": _ssd_case(1280),
    "ssm_decode_update": (_ssm_update, SSM_UPDATE_ARGS),
}


_COMPILED = {}      # case -> the compiled executable, compiled once a module


def _compiled(v5e, name):
    if name not in _COMPILED:
        f, args = CASES[name]
        one_chip = jax.sharding.SingleDeviceSharding(v5e.devices[0])
        shapes = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                  for s, d in args]
        _COMPILED[name] = jax.jit(f).lower(*shapes).compile()
    return _COMPILED[name]


def _compiled_text(v5e, name):
    return _compiled(v5e, name).as_text()


# opcodes that only name a buffer another instruction produced
NAMES_A_BUFFER = ("parameter", "tuple", "get-tuple-element", "bitcast")


def _outputs_holding(text, *shapes):
    """(opcode, line) of every instruction of the HLO `text` whose result
    (or an element of whose tuple result) is an array of one of `shapes`,
    each given as its dimensions in brackets."""
    import re

    found = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = (.*?) ([\w-]+)\(", line)
        if m and any(s in m.group(1) for s in shapes):
            found.append((m.group(2), line.strip()))
    return found


def _fusion_body(text, fusion_line):
    """The text of the computation a fusion instruction calls."""
    import re

    called = re.search(r"calls=(%[\w.-]+)", fusion_line).group(1)
    return text.split(f"\n{called} (", 1)[1].split("\n}", 1)[0]


@pytest.mark.kernels
@pytest.mark.parametrize("name", list(CASES))
def test_kernel_compiles_for_v5e(v5e, name):
    assert "tpu_custom_call" in _compiled_text(v5e, name)


# every `pallas_call` of ops/pallas has a `name=`: the compiled
# custom-call instruction carries it (`%jvp_paddle_softmax_xent_fwd_.1 = ...`),
# and that instruction's text names the kernel's events on a profiler
# trace's `XLA Ops` line (README.md "Reading a trace")
KERNEL_NAMES = {
    "paddle_flash_fwd": "flash_causal_fwd",
    "paddle_flash_dq": "flash_causal_bwd",
    "paddle_flash_dkv": "flash_causal_bwd",
    "paddle_softmax_xent_fwd": "softmax_xent_bf16_fwd",
    "paddle_softmax_xent_bwd": "softmax_xent_bf16_bwd",
    "paddle_layer_norm_fwd": "layer_norm_f32_fwd",
    "paddle_paged_decode_fwd": "paged_decode",
    "paddle_paged_gqa_decode_fwd": "paged_gqa_decode_window",
    "paddle_moe_gmm": "moe_gmm_block_step_512",
    "paddle_ssd_chunk_scan": "ssd_chunk_scan_256",
    "paddle_ssm_decode_update": "ssm_decode_update",
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


def _compiled_calls(v5e, name):
    """The Pallas calls of a compiled case, each as a trace's `XLA Ops`
    line prints its instruction (operand shapes and all) and
    `benchmarks.trace.short_name` cuts it."""
    from benchmarks import trace

    try:
        from jax._src.lib import _jax
        options = _jax.HloPrintOptions()
        options.print_operand_shape = True
        options.include_layout_in_shapes = True
    except (ImportError, AttributeError) as e:
        pytest.skip(f"this jaxlib prints no operand shapes: {e}")
    module, = _compiled(v5e, name).runtime_executable().hlo_modules()
    return [trace.short_name(line.strip().removeprefix("ROOT "))
            for line in module.to_string(options).splitlines()
            if 'custom_call_target="tpu_custom_call"' in line]


def _assert_patterns_find(metric, sizes, calls):
    """Each pattern of benchmarks/layer_metrics/<metric>.json, with the
    cell's sizes filled in, matches exactly one of `calls`, and between
    them they match every call."""
    import json
    import pathlib
    import re

    from benchmarks import trace

    spec = json.loads((pathlib.Path(trace.__file__).parent / "layer_metrics"
                       / f"{metric}.json").read_text())
    matched = set()
    for part in spec["args"]["parts"]:
        pattern = part["pattern"]
        for key, value in sizes.items():
            pattern = pattern.replace("{" + key + "}", str(value))
        hits = [c for c in calls if re.search(pattern, c)]
        assert len(hits) == 1, (part["what"], calls)
        matched.add(hits[0])
    assert len(matched) == len(calls), calls


@pytest.mark.kernels
def test_flash_roofline_patterns_find_the_compiled_calls(v5e):
    """The benchmark's `flash_roofline` knows the three flash calls by the
    text of their instructions.  Each of its patterns, with the train
    cell's sizes filled in, has to match exactly one of the calls compiled
    for that cell's shape, and between them they match all three."""
    # the gradient's executable holds the forward call beside dQ and dK/dV
    calls = _compiled_calls(v5e, "flash_causal_bwd")
    assert len(calls) == 3, calls
    _assert_patterns_find("flash_roofline",
                          {"BH": FLASH_B * NH, "S": S, "HD": HD}, calls)


@pytest.mark.kernels
@pytest.mark.parametrize("metric, case", [
    ("ssm_decode_roofline", "ssm_decode_update"),
    ("ssd_prefill_roofline", "ssd_chunk_scan_256"),
    ("ssd_prefill_roofline", "ssd_chunk_scan_1280")])
def test_ssm_roofline_patterns_find_the_compiled_calls(v5e, metric, case):
    """The benchmark knows both calls of the selective scan by their
    `pallas_call` names: each metric's pattern matches the one call of its
    compiled case."""
    calls = _compiled_calls(v5e, case)
    assert len(calls) == 1, calls
    _assert_patterns_find(metric, {}, calls)


@pytest.mark.kernels
def test_ssm_decode_update_rewrites_the_held_states_in_place(v5e):
    """The whole array of held states (3.6 GB at the cell's size) is the
    call's operand and, aliased, its result: donated, the compiled program
    holds no second copy of it."""
    f, args = CASES["ssm_decode_update"]
    one_chip = jax.sharding.SingleDeviceSharding(v5e.devices[0])
    shapes = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in args]
    mem = jax.jit(f, donate_argnums=(0,)).lower(*shapes).compile() \
        .memory_analysis()
    held = 4 * SSM_LAYERS * SSM_SLOTS * SSM_H * SSM_P * SSM_N
    assert mem.alias_size_in_bytes >= held
    assert mem.temp_size_in_bytes < held // SSM_LAYERS


@pytest.mark.kernels
def test_xent_roofline_patterns_find_the_compiled_calls(v5e):
    """`xent_roofline` knows the loss kernel's two calls by their operands
    and results: the lane-replicated [N, 128] rows and the [N, V] logits.
    At the train cell's N = 16384, V = 50304 each pattern matches exactly
    one call of the compiled gradient, which holds both."""
    calls = _compiled_calls(v5e, "softmax_xent_train_bwd")
    assert len(calls) == 2, calls
    _assert_patterns_find("xent_roofline", {"N": XENT_N, "V": V}, calls)


@pytest.mark.kernels
@pytest.mark.parametrize("case", ["paged_gqa_decode_full",
                                  "paged_gqa_decode_window"])
def test_paged_gqa_roofline_pattern_finds_the_compiled_call(v5e, case):
    """`paged_gqa_decode_roofline` (and `attn_decode_share`) know the
    grouped paged call by its name and its result [slots, KV heads, group,
    head]: at the cell's sizes the pattern matches the one call of each
    compiled case, the full layers' and the window layers'."""
    calls = _compiled_calls(v5e, case)
    assert len(calls) == 1, calls
    _assert_patterns_find(
        "paged_gqa_decode_roofline",
        {"SLOTS": SLOTS, "NKV": GQA_NKV, "G": GQA_NH // GQA_NKV,
         "HD": GQA_HD}, calls)


# what `paged_decode_roofline` (benchmarks/layer_metrics) knows the chat
# cell's call by on a trace: its result and its first three operands
@pytest.mark.kernels
def test_paged_roofline_pattern_finds_the_compiled_call(v5e):
    calls = _compiled_calls(v5e, "paged_decode")
    assert len(calls) == 1, calls
    _assert_patterns_find(
        "paged_decode_roofline",
        {"SLOTS": SLOTS, "NH": CHAT_NH, "HD": CHAT_HD}, calls)


@pytest.mark.kernels
@pytest.mark.parametrize("case, kernel, lane", [
    ("paged_decode", "paddle_paged_decode_fwd", (CHAT_NH, CHAT_HD)),
    ("paged_decode_gpt2", "paddle_paged_decode_fwd", (NH, HD)),
    ("paged_gqa_decode_full", "paddle_paged_gqa_decode_fwd",
     (GQA_NKV, GQA_NH // GQA_NKV, GQA_HD)),
    ("paged_gqa_decode_window", "paddle_paged_gqa_decode_fwd",
     (GQA_NKV, GQA_NH // GQA_NKV, GQA_HD)),
    # a block step's: the same call with the block's queries among the rows
    ("paged_gqa_block_step", "paddle_paged_gqa_decode_fwd",
     (GQA_NKV, BLOCK_C * GQA_NH // GQA_NKV, GQA_HD)),
])
def test_paged_calls_keep_their_operands_and_result(v5e, case, kernel, lane):
    """The custom call's operands in order (the page table, the positions,
    the lanes' queries, then the two WHOLE pools and nothing else) and its
    result, as the benchmark's readers and `tools/trace_ops.py` find
    them: the walk's buffers and semaphores are scratch, not operands."""
    import re

    (call,) = _compiled_calls(v5e, case)
    pool = CASES[case][1][1][0]
    if not pa._page_is_tiles(jax.ShapeDtypeStruct(pool, BF16)):
        # the grid's walk takes the planes end to end
        pool = (pool[0] * pool[1],) + pool[2:]
    pool = ",".join(map(str, pool))
    cols = CASES[case][1][3][0][1]
    q = ",".join(map(str, (SLOTS,) + lane))
    assert re.match(
        rf"%{kernel}\S* = bf16\[{q}\]\S* custom-call\("
        rf"s32\[{SLOTS},{cols}\]\S* %\S+, s32\[{SLOTS}\]\S* %\S+, "
        rf"bf16\[{q}\]\S* %\S+, bf16\[{pool}\]\S* %\S+, "
        rf"bf16\[{pool}\]\S* %\S+\)", call), call


@pytest.mark.kernels
def test_paged_call_walks_pages_no_copy_can_cut_out_by_the_grid(v5e):
    """GPT-2's pages, [16, 12, 64]: a slice of the pool that is no whole
    tile is refused by Mosaic where a DMA makes it ("must be aligned to
    tiling"), so from the shapes the call takes the grid's walk there (a
    page a grid step, through a BlockSpec), which compiles as it did, and
    the loop inside the kernel where a page is whole tiles."""
    assert pa._block_pages(jax.ShapeDtypeStruct(*GPT2_POOL), 64, False) == 0
    assert pa._block_pages(jax.ShapeDtypeStruct(*POOL), 64, False) == 8
    # a block step's 32 rows a product share a turn among twice the keys
    assert pa._block_pages(jax.ShapeDtypeStruct(*BLOCK_POOL), 80, False) == 8
    assert pa._block_pages(jax.ShapeDtypeStruct(*BLOCK_POOL), 80, False,
                           rows=32) == 16
    (call,) = _compiled_calls(v5e, "paged_decode_gpt2")
    planes, pages, *page = GPT2_POOL[0]
    pool = ",".join(map(str, [planes * pages] + page))
    assert call.count(f"bf16[{pool}]") == 2, call


# -- the FFN's bias-GELU rides in its products' fusions ------------------------
# `fused.linear_bias_gelu` writes gelu(x @ w1 + b1) as jnp under a custom_vjp
# that keeps (pre-activation, bias): the chip's compiler puts the forward's
# arithmetic into fc1's fusion and the backward's, with db's row sum, into
# the fusion of the product that makes its cotangent.  As a Pallas pass of
# its own it was 13% of the train cell's step (PERF.md section 6, PR 42).
@pytest.mark.kernels
def test_ffn_bias_gelu_rides_in_the_products_fusions(v5e):
    """An FFN layer forward and backward at the train cell's 16384 rows,
    768 -> 3072 -> 768 (float32 weights under bf16 autocast): no
    `paddle_bias_gelu` custom call, no Mosaic call at all, and every
    instruction of the entry computation whose result holds a
    [16384, 3072] array is a fusion that contains a convolution: the
    activation crosses HBM only as a product's result, never in a pass of
    its own."""
    from paddle_tpu import amp
    from paddle_tpu.ops import fused

    rows = 16 * S

    def ffn(x, w1, b1, w2, b2, ct):
        with amp.auto_cast(dtype="bfloat16"):
            h = fused.unwrap(fused.linear_bias_gelu(x, w1, b1))
            y = jnp.matmul(*amp.white_cast(h, w2)) + b2.astype(BF16)
        return (y.astype(F32) * ct.astype(F32)).sum()

    one_chip = jax.sharding.SingleDeviceSharding(v5e.devices[0])
    shapes = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in [
        ((rows, H), BF16), ((H, FFN), F32), ((FFN,), F32), ((FFN, H), F32),
        ((H,), F32), ((rows, H), BF16)]]
    text = jax.jit(jax.grad(ffn, argnums=(0, 1, 2, 3, 4))) \
        .lower(*shapes).compile().as_text()
    assert "paddle_bias_gelu" not in text
    assert "tpu_custom_call" not in text
    wide = [(op, line) for op, line in _outputs_holding(
        text[text.index("\nENTRY"):], f"[{rows},{FFN}]")
        if op not in NAMES_A_BUFFER]
    assert len(wide) >= 2, wide              # forward and backward
    for op, line in wide:
        assert op == "fusion", line[:200]
        assert " convolution(" in _fusion_body(text, line), line[:200]


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


# -- the decode step holds its KV pools in place -----------------------------
# `GPTForCausalLM.slot_step` threads a `PagedKV` source (the two stacked
# pools) through its blocks: each scatters its token's rows into its own
# plane of the donated pool and the paged kernel reads that plane where it
# lies.  A
# `k_pages[i]` handed to the kernel, or a `jnp.stack` of the planes at the
# end, compiles to copies of planes and pools (72% of the chat cell's
# decode step on a v5e, PERF.md PR 25).  Read the compiled step for them.
def _decode_step_compiled(device, layers, slots, seq, page, nh, hd, pages,
                          dtype, backend=None, pallas=None):
    """The decode step (`slot_step` over a `PagedKV`) of a small GPT, the
    pools donated, compiled for `device`.  Returns (compiled, pool shape)."""
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    from paddle_tpu.nn.layer_base import functional_call
    from paddle_tpu.ops import fused
    from paddle_tpu.serving.kv_cache import PagedKV
    from paddle_tpu.tensor import unwrap

    net = GPTForCausalLM(GPTConfig(
        vocab_size=512, hidden_size=nh * hd, num_layers=layers,
        num_heads=nh, max_position_embeddings=seq, dropout=0.0,
        attn_dropout=0.0))
    net.eval()
    pool = (layers, pages, page, nh, hd)
    sharding = jax.sharding.SingleDeviceSharding(device)

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)

    def step(params, tok, pos, active, kp, vp, rows):
        (logits, kv), _ = functional_call(
            net, params,
            (tok[:, None], pos[:, None],
             PagedKV(kp, vp, rows, pos, active, seq)),
            mutable=False, method="slot_step")
        return logits[:, 0], kv.k_pages, kv.v_pages

    # the step is traced over shapes: the weights it is given are of the
    # served dtype whatever the constructor drew
    args = ({n: sds(unwrap(p).shape, jnp.dtype(dtype))
             for n, p in net.named_parameters()},
            sds((slots,), I32), sds((slots,), I32), sds((slots,), jnp.bool_),
            sds(pool, jnp.dtype(dtype)), sds(pool, jnp.dtype(dtype)),
            sds((slots, seq // page), I32))
    # the program asks the process which backend and flag it runs under;
    # the test answers in their place while the step is traced
    real_backend, real_flag = jax.default_backend, fused._use_pallas
    if backend is not None:
        jax.default_backend = lambda: backend
    if pallas is not None:
        fused._use_pallas = lambda: pallas
    try:
        lowered = jax.jit(step, donate_argnums=(4, 5)).lower(*args)
    finally:
        jax.default_backend, fused._use_pallas = real_backend, real_flag
    return lowered.compile(), pool


def _pool_shaped_outputs(text, pool):
    """(opcode, line) of every instruction of the optimised HLO whose
    result (or an element of whose tuple result) is a whole pool or one
    layer's plane of it."""
    dims = ",".join(map(str, pool))
    return _outputs_holding(text, f"[{dims}]", f"[{dims.split(',', 1)[1]}]")


def _fusion_root_opcode(text, fusion_line):
    """The opcode of the root of the computation a fusion calls."""
    import re

    return re.search(r"\n\s*ROOT %\S+ = .*? ([\w-]+)\(",
                     _fusion_body(text, fusion_line)).group(1)


def _assert_pools_stay_in_place(compiled, pool, itemsize):
    layers = pool[0]
    text = compiled.as_text()
    outputs = _pool_shaped_outputs(text, pool)
    # the two donated pools come in as parameters (of the entry and of the
    # scatters' fusions) and go out through the scatters; beside what only
    # names a buffer, nothing else may produce a pool or a plane: no
    # slice, copy, dynamic-update-slice, concatenate or broadcast of one
    extra = [line for op, line in outputs
             if op not in NAMES_A_BUFFER + ("scatter", "fusion")]
    assert not extra, [line[:200] for line in extra]
    for line in (ln for op, ln in outputs if op == "fusion"):
        assert _fusion_root_opcode(text, line) == "scatter", line[:200]
    scatters = [line for op, line in outputs if op == "scatter"]
    assert len(scatters) == 2 * layers                  # K and V, a layer
    mem = compiled.memory_analysis()
    plane = itemsize
    for d in pool[1:]:
        plane *= d
    # both pools are rewritten where they lie ...
    assert mem.alias_size_in_bytes >= 2 * layers * plane
    # ... and the step holds less than one plane beside them (sliced and
    # stacked, this 4-layer step holds 3.25 planes of temporaries)
    assert mem.temp_size_in_bytes < plane, (mem.temp_size_in_bytes, plane)


@pytest.mark.kernels
def test_decode_step_keeps_the_donated_pools_in_place_on_v5e(v5e):
    """Four layers at the chat cell's head geometry (heads of 128, pages
    of 16 tokens, 16 slots x 1024, a plane of 16.8 MB): the compiled step
    has one paged kernel a layer, each reading the whole pool, and the
    only instructions that output a pool are the in-place scatters."""
    compiled, pool = _decode_step_compiled(
        v5e.devices[0], layers=4, slots=16, seq=1024, page=16, nh=4, hd=128,
        pages=16 * 64, dtype="bfloat16", backend="tpu", pallas=True)
    text = compiled.as_text()
    dims = ",".join(map(str, pool))
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line
             and "paddle_paged_decode_fwd" in line.split(" = ", 1)[0]]
    assert len(calls) == 4
    # rows, pos, q, then the two WHOLE pools (what the benchmark's
    # `paged_decode_roofline` pattern matches on a trace), no plane
    operands = ("operand_layout_constraints={s32[16,64]{1,0}, s32[16]{0}, "
                "bf16[16,4,128]{2,1,0}, "
                f"bf16[{dims}]{{4,3,2,1,0}}, bf16[{dims}]{{4,3,2,1,0}}}}")
    for call in calls:
        assert operands in call, call[:160]
        assert call.lstrip().startswith(
            "%paddle_paged_decode_fwd"), call[:80]
    _assert_pools_stay_in_place(compiled, pool, 2)


def test_decode_step_keeps_the_donated_pools_in_place_on_cpu():
    """The twin of the v5e test where no TPU compiler can be described: the
    CPU backend at tiny sizes, the dense-gather attention (no kernel here),
    a pool four times what the slots can map so that a lane's gathered view
    is not the shape of a plane."""
    slots, seq, page = 2, 64, 8
    compiled, pool = _decode_step_compiled(
        jax.devices("cpu")[0], layers=4, slots=slots, seq=seq, page=page,
        nh=2, hd=16, pages=4 * slots * seq // page, dtype="float32",
        pallas=False)
    _assert_pools_stay_in_place(compiled, pool, 4)


# -- the block step walks the pool where it lies ------------------------------
# `block_step` (serving/generation.py) hands `PagedKV` one last key a lane,
# so a block's queries read the lane's mapped pages through the paged call;
# gathered, every layer copied all 1,280 page slots of the 16 lanes' tables,
# K and V (`bf16[1280,16,4,128] fusion(bf16[6,1280,16,4,128], s32[1280])`,
# a fifth of the blockgen cell's step: PERF.md section 6, PR 37).
def _block_step_compiled(device):
    """The engine's own `block_step`, as `GenerationEngine.start()` builds
    it, compiled for `device`: a small SDAR model at the blockgen cell's
    attention geometry (6 layers, 32 query heads over 4 KV heads of 128,
    blocks of 4; 16 slots x 1280 in pages of 16, bf16).  Returns
    (compiled, pool shape)."""
    import paddle_tpu as paddle
    from paddle_tpu import inference
    from paddle_tpu.models.sdar import SDARConfig, SDARForCausalLM
    from paddle_tpu.serving import GenerationEngine

    net = SDARForCausalLM(SDARConfig(
        vocab_size=512, hidden_size=256, num_layers=BLOCK_LAYERS,
        num_heads=GQA_NH, num_kv_heads=GQA_NKV, head_dim=GQA_HD,
        moe_intermediate_size=128, num_experts=8, num_experts_per_tok=2,
        max_position_embeddings=BLOCK_SEQ, block_length=BLOCK_C,
        denoising_steps=BLOCK_C, mask_token_id=511))
    net.eval()
    for p in net.parameters():
        p._value = p._value.astype(BF16)
    eng = GenerationEngine(net, max_slots=SLOTS, max_seq_len=BLOCK_SEQ,
                           page_size=PAGE, prompt_buckets=[256])
    sharding = jax.sharding.SingleDeviceSharding(device)
    built = []

    class Built(Exception):
        pass

    def aot(fn, arg_specs, *, donate_argnums=(), out_shardings=None):
        # the first executable `start()` builds; the others are not wanted
        assert fn.__name__ == "block_step", fn.__name__
        specs = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=sharding),
            tuple(arg_specs))
        built.append(jax.jit(fn, donate_argnums=donate_argnums)
                     .lower(*specs).compile())
        raise Built

    # the program asks the process which backend it runs under; the test
    # answers in its place while the step is traced
    real_aot, real_backend = inference.aot_compile, jax.default_backend
    inference.aot_compile, jax.default_backend = aot, lambda: "tpu"
    try:
        with pytest.raises(Built):
            eng.start()
    finally:
        inference.aot_compile, jax.default_backend = real_aot, real_backend
        eng.stop()
    return built[0], BLOCK_POOL[0]


@pytest.mark.kernels
def test_block_step_walks_the_donated_pools_in_place_on_v5e(v5e):
    """The compiled `block_step` at the blockgen cell's attention geometry
    holds one paged call a layer, each given the page table, one last key
    a lane, the lanes' queries laid [slots, KV heads, block x group, head]
    and the two WHOLE pools; the only instructions that output a pool or
    a plane of one are the in-place scatters of the block's K/V: no gather
    of the table's pages, no copy of a pool around the calls."""
    compiled, pool = _block_step_compiled(v5e.devices[0])
    text = compiled.as_text()
    assert text.startswith("HloModule jit_block_step")
    dims = ",".join(map(str, pool))
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line
             and "paddle_paged_gqa_decode_fwd" in line.split(" = ", 1)[0]]
    assert len(calls) == BLOCK_LAYERS
    rows = BLOCK_C * GQA_NH // GQA_NKV
    q = f"bf16[{SLOTS},{GQA_NKV},{rows},{GQA_HD}]"
    operands = (f"operand_layout_constraints={{s32[{SLOTS},"
                f"{BLOCK_SEQ // PAGE}]{{1,0}}, s32[{SLOTS}]{{0}}, "
                f"{q}{{3,2,1,0}}, "
                f"bf16[{dims}]{{4,3,2,1,0}}, bf16[{dims}]{{4,3,2,1,0}}}}")
    for call in calls:
        assert operands in call, call[:160]
        assert call.lstrip().startswith(
            f"%paddle_paged_gqa_decode_fwd"), call[:80]
        assert call.split(" = ", 1)[1].startswith(q), call[:160]
    _assert_pools_stay_in_place(compiled, pool, 2)
