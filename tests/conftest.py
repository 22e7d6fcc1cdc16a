"""Test config: force a deterministic 8-device CPU mesh (SURVEY.md §4 —
multi-process NCCL tests are replaced by virtual-device mesh tests)."""
import os
import time

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

# Tests are CPU-only.  Should something have imported jax before this file
# (the env var is read at import), the config update still holds it there.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: multi-process / long-running test, excluded from "
        "tier-1 (`-m 'not slow'`)")
    config.addinivalue_line(
        "markers", "chaos: deterministic fault-injection test of the "
        "resilience runtime (run via tools/chaos.sh)")
    config.addinivalue_line(
        "markers", "perf: performance introspection test (persistent compile "
        "cache, op table, buffer census, /debug/perf) — runs in tier-1")
    config.addinivalue_line(
        "markers", "serving: adaptive-batching serving engine test "
        "(paddle_tpu.serving) — run via tools/serve_smoke.sh")
    config.addinivalue_line(
        "markers", "genserve: continuous-batching generation serving test "
        "(paddle_tpu.serving.generation) — run via tools/serve_smoke.sh")
    config.addinivalue_line(
        "markers", "dp: SPMD-sharded TrainEngine test (Model.fit on a "
        "dp mesh of the 8 virtual devices) — run via tools/dp_smoke.sh")
    config.addinivalue_line(
        "markers", "monitor: runtime telemetry test (paddle_tpu.monitor "
        "+ utils.metrics) — run via tools/obs_smoke.sh")
    config.addinivalue_line(
        "markers", "lint: static-analysis suite test (paddle_tpu.analysis "
        "rules PTA001-006) — run via tools/lint.sh")
    config.addinivalue_line(
        "markers", "mesh3d: 3D-parallel layout/remat/accumulation test "
        "(SpecLayout over dp×fsdp×tp on the 8 virtual devices) — run via "
        "tools/mesh3d_smoke.sh")
    config.addinivalue_line(
        "markers", "trace: request-scoped tracing / flight recorder / "
        "goodput ledger test (monitor.tracing, monitor.flightrec, "
        "distributed.goodput) — run via tools/obs_smoke.sh")
    config.addinivalue_line(
        "markers", "kernels: Pallas fused-kernel parity/dispatch test "
        "(masked flash, paged decode, softmax-xent; the bias-gelu "
        "composite; CPU interpret mode) — tests/test_pallas_kernels.py")
    config.addinivalue_line(
        "markers", "pod: multi-process pod test (N real OS processes via "
        "distributed.podtest — coordinated jax.distributed bring-up or "
        "the elastic shrink supervisor) — run via tools/pod_smoke.sh")
    config.addinivalue_line(
        "markers", "specdec: speculative decode / chunked prefill / fleet "
        "router test (serving.generation draft path, serving.router) — "
        "run via tools/serve_smoke.sh")
    config.addinivalue_line(
        "markers", "sparse: sharded embedding table / vocab admission / "
        "streaming recommender data plane test (paddle_tpu.sparse) — run "
        "via tools/sparse_smoke.sh")
    config.addinivalue_line(
        "markers", "fleetchaos: fault-tolerant serving fleet test "
        "(elastic membership, mid-stream failover, retry budgets, "
        "serving chaos drills) — run via tools/serve_smoke.sh")


@pytest.fixture(autouse=True)
def _chaos_reset():
    """Chaos state is process-global; never let one test's fault plan
    leak into the next."""
    from paddle_tpu.utils import chaos

    chaos.reset()
    yield
    chaos.reset()


def cpu_subprocess_env(repo_on_path=True):
    """Env for spawning a python subprocess of a test: forces the CPU
    backend (a chip, where there is one, belongs to one process at a time)
    and puts the repo on the path.  Use this instead of hand-rolling it in
    each test file."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    if repo_on_path:
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    return env


@pytest.fixture(autouse=True)
def _seed():
    import paddle_tpu as paddle

    paddle.seed(42)
    yield


class _Late:
    """A step's output that is done on the "device" `delay` seconds after
    its launch: `is_ready()` says so without blocking, `np.array` waits."""

    def __init__(self, value, ready_at):
        self.value, self.ready_at = value, ready_at

    def is_ready(self):
        return time.monotonic() >= self.ready_at

    def __array__(self, dtype=None, copy=None):
        time.sleep(max(0.0, self.ready_at - time.monotonic()))
        return np.asarray(self.value, dtype=dtype)


@pytest.fixture()
def slow_steps():
    """slow_steps(engine, delay=0.02): every step of a started
    `GenerationEngine` takes `delay` seconds on the "device" while its
    launch returns at once, as on a chip, so that a step IS in flight when
    the decode loop launches the next."""
    def patch(eng, delay=0.02):
        attr = next(a for a in ("_spec_exec", "_block_exec", "_decode_exec")
                    if getattr(eng, a) is not None)
        fast, done = getattr(eng, attr), [0.0]

        def launch(*args):
            state, *out = fast(*args)
            # one step at a time, in order, as the device runs them
            ready_at = done[0] = max(done[0], time.monotonic()) + delay
            return (state, *[_Late(a, ready_at) if hasattr(a, "shape") else a
                             for a in out])

        setattr(eng, attr, launch)

    return patch
