"""Local pod harness: spawn N REAL OS processes as a CPU pod, for tests.

Two modes mirror the two pod runtimes:

  coordinated  — ranks call `jax.distributed.initialize` against rank 0's
      coordination service (PADDLE_TRAINER_* env, same as the launcher
      sets).  This is the die-together mode: `jax.process_count() > 1`
      is REAL, so the multi-host checkpoint gates (writer quarantine,
      single-process-gated dedup/flush-timeout) and the coordination-KV
      collectives (podcoll.JaxCoordTransport) run exactly as they would
      on a pod — but any rank death aborts every survivor from C++
      (pjrt client.h:80), so chaos drills that must SURVIVE a death use
      elastic mode instead.
  elastic      — ranks run under the shrink-and-continue supervisor
      (elastic.launch_elastic): no jax.distributed at all; membership,
      collectives, and failure detection live in the supervisor's pod
      coordinator, so a SIGKILLed rank shrinks the pod instead of
      killing it.

Rank programs are plain python source strings (the test keeps them
inline).  Ranks report structured results by printing ``PODOUT <json>``
lines — `emit()` here, `PodResult.records()` on the harness side —
because on a CPU pod there is no cross-process device path to gather
through; stdout is the one channel a SIGKILLed rank's survivors still
have.

jax note: the CPU backend rejects multiprocess XLA computations
("Multiprocess computations aren't implemented on the CPU backend"), so
coordinated-mode programs jit over their LOCAL devices only and do
cross-process work through the coordination KV store / podcoll.
"""
from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import tempfile
import textwrap
import time

__all__ = ["free_port", "coordinated_env", "run_pod", "run_elastic_pod",
           "PodResult", "emit", "PRELUDE"]

# repo root, so rank programs import paddle_tpu regardless of their cwd
_REPO_ROOT = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def emit(**kv):
    """Rank-side: report one structured record to the harness."""
    sys.stdout.write("PODOUT " + json.dumps(kv, default=float) + "\n")
    sys.stdout.flush()


# importable by rank programs: `from paddle_tpu.distributed.podtest
# import emit` works in the child because the harness runs children with
# the repo on sys.path (inherited cwd/PYTHONPATH).
PRELUDE = textwrap.dedent("""\
    import json, os, sys
    RANK = int(os.environ.get("PADDLE_POD_RANK",
                              os.environ.get("PADDLE_TRAINER_ID", "0")))
    WORLD = int(os.environ.get("PADDLE_POD_WORLD",
                               os.environ.get("PADDLE_TRAINERS_NUM", "1")))
    def emit(**kv):
        sys.stdout.write("PODOUT " + json.dumps(kv, default=float) + "\\n")
        sys.stdout.flush()
""")


def coordinated_env(rank: int, world: int, port: int,
                    local_devices: int = 1) -> dict:
    """The PADDLE_TRAINER_* contract for one coordinated-mode rank, CPU
    platform pinned and `local_devices` host CPU devices forced."""
    eps = ",".join(f"127.0.0.1:{port + i}" for i in range(world))
    return {
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": ("--xla_force_host_platform_device_count=%d"
                      % int(local_devices)),
        "PADDLE_TRAINER_ID": str(rank),
        "PADDLE_TRAINERS_NUM": str(world),
        "PADDLE_TRAINER_ENDPOINTS": eps,
        "PADDLE_MASTER": f"127.0.0.1:{port}",
        # keep child runs hermetic and quick
        "JAX_ENABLE_COMPILATION_CACHE": "false",
        "PADDLE_INIT_RETRY_DELAY": "0.1",
    }


class PodResult:
    def __init__(self, rcs, outs, cmdline=""):
        self.rcs = list(rcs)
        self.outs = list(outs)
        self.cmdline = cmdline

    @property
    def ok(self) -> bool:
        return all(rc == 0 for rc in self.rcs)

    def records(self, rank: int) -> list[dict]:
        recs = []
        for line in (self.outs[rank] or "").splitlines():
            if line.startswith("PODOUT "):
                recs.append(json.loads(line[len("PODOUT "):]))
        return recs

    def record(self, rank: int, key: str):
        """Last PODOUT value for `key` from `rank` (None if absent)."""
        val = None
        for rec in self.records(rank):
            if key in rec:
                val = rec[key]
        return val

    def assert_ok(self):
        if not self.ok:
            raise AssertionError(
                "pod ranks failed (rcs=%s)\n%s" % (
                    self.rcs,
                    "\n".join(f"--- rank {r} ---\n{out}"
                              for r, out in enumerate(self.outs))))
        return self


def _write_program(source: str, tmpdir: str) -> str:
    path = os.path.join(tmpdir, "pod_rank.py")
    with open(path, "w", encoding="utf-8") as f:
        f.write(PRELUDE + textwrap.dedent(source))
    return path


def run_pod(source: str, world: int = 2, *, timeout: float = 180.0,
            env: dict = None, local_devices: int = 1) -> PodResult:
    """COORDINATED mode: spawn `world` ranks running `source` (prelude:
    RANK/WORLD/emit) with a real jax.distributed bring-up contract in
    env.  Blocks until all exit; kills the pod on timeout."""
    port = free_port()
    with tempfile.TemporaryDirectory(prefix="podtest-") as td:
        prog = _write_program(source, td)
        procs = []
        for r in range(world):
            e = dict(os.environ)
            e.update(coordinated_env(r, world, port,
                                     local_devices=local_devices))
            e["PYTHONPATH"] = _REPO_ROOT + (
                os.pathsep + e["PYTHONPATH"] if e.get("PYTHONPATH") else "")
            if env:
                e.update(env)
            procs.append(subprocess.Popen(
                [sys.executable, prog], env=e, stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT, text=True, cwd=td))
        outs = [""] * world
        deadline = time.monotonic() + timeout
        try:
            for r, p in enumerate(procs):
                left = max(1.0, deadline - time.monotonic())
                try:
                    outs[r], _ = p.communicate(timeout=left)
                except subprocess.TimeoutExpired:
                    p.kill()
                    outs[r], _ = p.communicate()
                    outs[r] = (outs[r] or "") + "\n[pod harness: timeout]"
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
        return PodResult([p.returncode for p in procs], outs,
                         cmdline=prog)


def run_elastic_pod(source: str, world: int = 2, *, timeout: float = 180.0,
                    env: dict = None, heartbeat_timeout_s: float = 3.0,
                    telemetry_dir: str = None, local_devices: int = 1):
    """ELASTIC mode: run `source` under the shrink-and-continue
    supervisor.  Returns (ElasticResult, PodResult) — rank stdout goes
    through the supervisor's workerlog files so PODOUT records survive a
    SIGKILL of their neighbors."""
    from .elastic import launch_elastic

    with tempfile.TemporaryDirectory(prefix="podtest-") as td:
        prog = _write_program(source, td)
        log_dir = os.path.join(td, "logs")
        base = {"JAX_PLATFORMS": "cpu",
                "XLA_FLAGS": ("--xla_force_host_platform_device_count=%d"
                              % int(local_devices)),
                "JAX_ENABLE_COMPILATION_CACHE": "false",
                "PYTHONPATH": _REPO_ROOT + (
                    os.pathsep + os.environ["PYTHONPATH"]
                    if os.environ.get("PYTHONPATH") else "")}
        if env:
            base.update(env)
        res = launch_elastic(
            [sys.executable, prog], world, env=base,
            heartbeat_timeout_s=heartbeat_timeout_s, log_dir=log_dir,
            telemetry_dir=telemetry_dir, timeout_s=timeout)
        outs = []
        for r in range(world):
            try:
                with open(os.path.join(log_dir, f"workerlog.{r}"),
                          encoding="utf-8", errors="replace") as f:
                    outs.append(f.read())
            except OSError:
                outs.append("")
        return res, PodResult(res.returncodes, outs, cmdline=prog)
