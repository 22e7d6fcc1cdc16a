"""chip_smoke.py rehearsed on the CPU, and the no-hidden-fallback rules it
relies on: an accelerator place without an accelerator raises, an unknown
device kind has no peak, the compile cache is placed from outside, and a
kernel that fails to lower raises through ops/fused.py while a kernel that
says it does not tile still takes the composite and is counted."""
import functools
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from conftest import cpu_subprocess_env

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


RUNS = {
    # name: (arguments, virtual CPU devices)
    "rehearsal": (("--rehearse",), 1),
    "mesh": (("--rehearse", "--chips", "4"), 4),
    "failed_phase": (("--rehearse", "--rehearse-fail", "device"), 1),
    "no_chip": ((), 1),
}


@pytest.fixture(scope="module")
def smoke():
    """Every chip_smoke.py process these tests read, started together (they
    are independent and the suite's time is short): name -> (completed
    process, its JSON lines)."""
    procs = {}
    for name, (args, devices) in RUNS.items():
        env = cpu_subprocess_env()
        env["XLA_FLAGS"] = \
            f"--xla_force_host_platform_device_count={devices}"
        env.pop("PADDLE_TPU_ENABLE_X64", None)
        procs[name] = subprocess.Popen(
            [sys.executable, SMOKE, *args], env=env, cwd=REPO,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    runs = {}
    try:
        for name, p in procs.items():
            out, err = p.communicate(timeout=600)
            runs[name] = (
                subprocess.CompletedProcess(p.args, p.returncode, out, err),
                [json.loads(ln) for ln in out.splitlines()
                 if ln.startswith("{")])
    finally:
        for p in procs.values():
            if p.poll() is None:
                p.kill()
    return runs


# -- the rehearsal: one subprocess, one case per line it must print ----------
@pytest.fixture(scope="module")
def rehearsal(smoke):
    r, lines = smoke["rehearsal"]
    assert r.returncode == 0, r.stderr[-3000:]
    assert [ln.get("phase") for ln in lines[:-1]] == \
        ["device", "kernels", "train", "serve", "summary"]
    return r, {ln.get("phase", "last"): ln for ln in lines}


def _device(ln):
    assert ln["platform"] == "cpu" and ln["x64"] is False
    assert ln["compile_cache_dir"] and "native_core" in ln


def _kernels(ln):
    assert ln["interpret"] is True      # no chip: never "compiled"
    assert set(ln["kernels"]) == {
        "flash_causal", "flash_masked", "layer_norm", "softmax_xent",
        "paged_decode", "paged_decode_walk"}
    assert all(k["fwd_err"] <= k["tol"] for k in ln["kernels"].values())


def _train(ln):
    assert len(ln["loss"]) == 4 and ln["loss"][-1] < ln["loss"][0]


def _serve(ln):
    assert ln["executables_after_warmup"] == 0
    assert ln["prefix_cache_hits"] >= 1 and ln["drained"] is True
    assert ln["stream_equals_blocking"] is True


def _summary(ln):
    assert ln["paddle_pallas_fallbacks_total"] == 0


def _last(ln):
    # a rehearsal is never the result line of a chip run
    assert ln["rehearsal"] is True and "ok" not in ln
    assert ln["device"]["platform"] == "cpu"


@pytest.mark.parametrize("phase,holds", [
    ("device", _device), ("kernels", _kernels), ("train", _train),
    ("serve", _serve), ("summary", _summary), ("last", _last)],
    ids=lambda v: v if isinstance(v, str) else "")
def test_rehearsal_line(rehearsal, phase, holds):
    holds(rehearsal[1][phase])


def test_rehearsal_never_claims_a_tpu(rehearsal):
    assert '"platform": "tpu"' not in rehearsal[0].stdout


@pytest.fixture(scope="module")
def mesh_rehearsal(smoke):
    r, lines = smoke["mesh"]
    assert r.returncode == 0, r.stderr[-3000:]
    return lines


def test_mesh_rehearsal_runs_only_the_mesh_path(mesh_rehearsal):
    assert [ln.get("phase") for ln in mesh_rehearsal[:-1]] == \
        ["device", "train_mesh", "summary"]
    last = mesh_rehearsal[-1]
    assert last["device"]["count"] == 4 and "ok" not in last


def test_mesh_rehearsal_losses_agree_with_one_device(mesh_rehearsal):
    mesh = mesh_rehearsal[1]
    assert len(mesh["loss_mesh"]) == len(mesh["loss_one_device"]) == 4
    assert mesh["max_loss_gap"] <= mesh["loss_tolerance"]


@pytest.mark.parametrize("tag", ["params", "opt_state"])
def test_mesh_rehearsal_state_is_on_four_devices(mesh_rehearsal, tag):
    census = mesh_rehearsal[1]["census"][tag]
    held = census["bytes_per_device"]
    assert len(held) == 4 and max(held.values()) < 0.5 * census["bytes"]


def test_a_failed_phase_exits_nonzero_and_prints_no_result(smoke):
    r, lines = smoke["failed_phase"]
    assert r.returncode != 0
    assert [ln.get("phase") for ln in lines] == ["device"]
    assert "SmokeFailure" in r.stderr


def test_without_a_chip_it_fails_and_prints_nothing(smoke):
    r, lines = smoke["no_chip"]
    assert r.returncode != 0
    assert r.stdout == "" and "no TPU" in r.stderr


# -- the compile cache can be placed from outside ----------------------------
@pytest.mark.parametrize("placed", [True, False])
def test_compile_cache_directory(tmp_path, placed):
    code = (
        "import json, jax, paddle_tpu as paddle\n"
        "from paddle_tpu.framework import flags\n"
        "first = jax.config.jax_compilation_cache_dir\n"
        "paddle.set_flags({'FLAGS_jit_cache_dir': %r})\n"
        "print(json.dumps([first, jax.config.jax_compilation_cache_dir,"
        " flags.apply_jit_cache()]))\n" % str(tmp_path / "by_flag"))
    env = cpu_subprocess_env()
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.pop("FLAGS_JIT_CACHE_DIR", None)
    if placed:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "by_env")
    r = subprocess.run([sys.executable, "-c", code], env=env, cwd=tmp_path,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    first, after_flag, applied = json.loads(r.stdout.splitlines()[-1])
    if placed:
        # jax read the environment; no repo code sets another directory
        assert first == after_flag == applied == str(tmp_path / "by_env")
    else:
        # one fixed path inside the checkout, whatever the cwd, home or pid
        assert first == os.path.join(REPO, ".jax_cache")
        assert after_flag == applied == str(tmp_path / "by_flag")


# -- an explicit device request is never rewritten ---------------------------
@pytest.mark.parametrize("ask", [
    lambda: paddle.TPUPlace(0).jax_device(),
    lambda: paddle.CUDAPlace(0).jax_device(),
    lambda: paddle.CPUPlace(99).jax_device(),
    lambda: paddle.set_device("tpu"),
    lambda: paddle.set_device("gpu:3"),
], ids=["TPUPlace0", "CUDAPlace0", "CPUPlace99", "set_device_tpu",
        "set_device_gpu3"])
def test_a_place_with_no_such_device_raises(ask):
    before = paddle.get_device()
    with pytest.raises(RuntimeError, match="names no device"):
        ask()
    assert paddle.get_device() == before
    assert paddle.CPUPlace(0).jax_device().platform == "cpu"


class _Device:
    def __init__(self, kind):
        self.device_kind = kind


@pytest.mark.parametrize("lookup,known", [
    ("peak_flops_per_device", 197e12), ("peak_bw_per_device", 819e9)])
def test_unknown_device_kind_has_no_peak(lookup, known):
    from paddle_tpu.monitor import perf

    fn = getattr(perf, lookup)
    assert fn(_Device("TPU v5 lite")) == known
    with pytest.raises(KeyError, match="tpu v9 lite"):
        fn(_Device("TPU v9 lite"))


# -- ops/fused.py: only the kernel's own refusal chooses the composite -------
def _dispatch_layer_norm():
    from paddle_tpu.ops import fused

    x = paddle.randn([8, 16])
    return fused.layer_norm(x, paddle.ones([16]), paddle.zeros([16]))


def _dispatch_softmax_xent():
    from paddle_tpu.ops import fused

    return fused.softmax_cross_entropy(
        paddle.randn([8, 128]), paddle.to_tensor(np.arange(8, dtype="int32")))


def _dispatch_flash():
    from paddle_tpu.ops import fused

    q = paddle.randn([1, 16, 2, 8])
    return fused.scaled_dot_product_attention(q, q, q, is_causal=True)


@functools.lru_cache(maxsize=None)
def _tiny_gpt():
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    net = GPTForCausalLM(GPTConfig(
        vocab_size=64, hidden_size=16, num_layers=1, num_heads=2,
        max_position_embeddings=32, dropout=0.0, attn_dropout=0.0))
    net.eval()
    return net


def _dispatch_paged():
    # through the model and its KV source, which keeps the dense gather
    # beside the kernel
    from paddle_tpu.serving.kv_cache import PagedKV

    net = _tiny_gpt()
    pool = jnp.zeros((1, 4, 8, 2, 8), jnp.float32)
    rows = jnp.asarray([[0, 1], [2, 3]], jnp.int32)
    pos = jnp.asarray([3, 9])
    return net.slot_step(
        jnp.asarray([[1], [2]]), pos[:, None],
        PagedKV(pool, pool, rows, pos, jnp.asarray([True, True]), 16))[0]


DISPATCH = {
    # counter label: (kernel module, function in it, a call that reaches it)
    "layer_norm": ("layer_norm", "layer_norm", _dispatch_layer_norm),
    "softmax_xent": ("softmax_xent", "softmax_xent", _dispatch_softmax_xent),
    "flash_attention": ("flash_attention", "flash_attention",
                        _dispatch_flash),
    "paged_attention": ("paged_attention", "paged_decode_attention",
                        _dispatch_paged),
}


def _break_kernel(monkeypatch, kernel, exc):
    import importlib

    from paddle_tpu.ops import fused

    module, fn, _ = DISPATCH[kernel]
    mod = importlib.import_module(f"paddle_tpu.ops.pallas.{module}")

    def broken(*a, **k):
        raise exc

    monkeypatch.setattr(mod, fn, broken)
    monkeypatch.setattr(fused, "_use_pallas", lambda: True)
    return fused


@pytest.mark.kernels
@pytest.mark.parametrize("kernel", list(DISPATCH))
@pytest.mark.parametrize("exc", [
    # what the Pallas TPU lowering raises for a primitive it lacks
    NotImplementedError("Unimplemented primitive in Pallas TPU lowering: erf"),
    RuntimeError("Mosaic failed to compile TPU kernel"),
], ids=["lowering_NotImplementedError", "compile_error"])
def test_kernel_error_propagates(monkeypatch, kernel, exc):
    fused = _break_kernel(monkeypatch, kernel, exc)
    def counted():
        return {k: v for k, v in fused.fallback_counter().values.items()
                if k[0] == kernel}

    before = counted()
    with pytest.raises(type(exc), match=str(exc)[:20]):
        DISPATCH[kernel][2]()
    assert counted() == before


@pytest.mark.kernels
@pytest.mark.parametrize("kernel", list(DISPATCH))
def test_does_not_tile_takes_the_composite_and_is_counted(monkeypatch,
                                                          kernel):
    from paddle_tpu.ops.pallas import DoesNotTile

    reference = np.asarray(paddle.to_tensor(DISPATCH[kernel][2]()).numpy())
    paddle.seed(42)     # the calls draw their inputs from the global stream
    fused = _break_kernel(monkeypatch, kernel,
                          DoesNotTile(f"{kernel}: rows 3 not divisible by 8"))
    monkeypatch.setattr(fused, "_warned_sites", set())
    key = (kernel, "shape")
    before = fused.fallback_counter().values.get(key, 0)
    with pytest.warns(RuntimeWarning, match="fell back"):
        out = np.asarray(paddle.to_tensor(DISPATCH[kernel][2]()).numpy())
    assert fused.fallback_counter().values[key] > before
    np.testing.assert_allclose(out, reference, rtol=1e-5, atol=1e-6)
