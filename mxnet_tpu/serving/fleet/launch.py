"""Spawn and supervise fleet worker subprocesses.

`spawn_worker` launches `python -m mxnet_tpu.serving.fleet.worker`
with a JSON spec written to a temp file, waits for the worker's
`FLEET_WORKER_READY {json}` line (model build + warmup included —
readiness means the steady-state programs are compiled), and returns a
`WorkerProc` handle that can kill (SIGKILL — the chaos tests' murder
weapon), terminate, and reap the process. `spawn_fleet` brings up a
whole topology and tears it down as a context manager.
"""
from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import tempfile
import threading
import time

from ...base import MXNetError

__all__ = ["WorkerProc", "spawn_worker", "spawn_fleet", "FleetProcs"]

_REPO_ROOT = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", ".."))

# Every worker is told its platform explicitly, never left to inherit
# the spawner's: a chip belongs to one process, so a parent that holds
# one (or N workers sharing one host) would leave a child that asks for
# it hanging. This launcher has no way to hand each child a chip of its
# own, so the only platform it gives out is the CPU; TPU replicas run as
# engines inside the one process that owns the chips.
_WORKER_PLATFORM = "cpu"


class WorkerProc:
    """One spawned worker subprocess + its READY announcement."""

    def __init__(self, proc, url, role, worker_id, spec_path):
        self.proc = proc
        self.url = url
        self.role = role
        self.worker_id = worker_id
        self.pid = proc.pid
        self._spec_path = spec_path

    @property
    def alive(self):
        return self.proc.poll() is None

    def kill(self):
        """SIGKILL — no goodbye, no flushing; the router must notice
        via connection loss, exactly like a real machine loss."""
        if self.alive:
            try:
                self.proc.kill()
            except OSError:
                pass
        self.wait(10)

    def terminate(self):
        if self.alive:
            try:
                self.proc.send_signal(signal.SIGTERM)
            except OSError:
                pass

    def wait(self, timeout=30):
        try:
            self.proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            pass
        self._cleanup()

    def _cleanup(self):
        try:
            os.unlink(self._spec_path)
        except OSError:
            pass

    def __repr__(self):
        return (f"WorkerProc(pid={self.pid}, url={self.url}, "
                f"role={self.role}, alive={self.alive})")


def _drain_output(proc, sink):
    """Keep reading the child's combined stdout/stderr after READY so
    the pipe never fills and blocks it (and keep a bounded tail for
    post-mortems)."""
    def run():
        for line in proc.stdout:
            sink.append(line.rstrip("\n"))
            del sink[:-200]
    threading.Thread(target=run, daemon=True,
                     name=f"mx-fleet-drain:{proc.pid}").start()


def spawn_worker(spec, role="mixed", host="127.0.0.1", port=0,
                 ship_payload=True, warmup=True, env=None,
                 ready_timeout_s=600.0):
    """Launch one worker process and block until it is READY (or dead).
    Returns a WorkerProc. The spec travels via a temp file, so big
    engine configs never hit argv limits."""
    asked = (env or {}).get("JAX_PLATFORMS", _WORKER_PLATFORM)
    if asked != _WORKER_PLATFORM:
        raise MXNetError(
            f"fleet worker asked for JAX_PLATFORMS={asked!r}: this "
            "launcher cannot give each worker process a chip of its own "
            "(one process holds a chip at a time), so workers run on "
            f"{_WORKER_PLATFORM!r} only — serve TPU replicas from one "
            "process instead")
    fd, spec_path = tempfile.mkstemp(prefix="mx_fleet_spec_",
                                     suffix=".json")
    with os.fdopen(fd, "w", encoding="utf-8") as f:
        json.dump(spec, f)
    cmd = [sys.executable, "-m", "mxnet_tpu.serving.fleet.worker",
           "--spec", spec_path, "--role", role,
           "--host", host, "--port", str(port)]
    if not ship_payload:
        cmd.append("--no-ship-payload")
    if not warmup:
        cmd.append("--no-warmup")
    child_env = dict(os.environ)
    child_env["PYTHONPATH"] = _REPO_ROOT + os.pathsep \
        + child_env.get("PYTHONPATH", "")
    child_env.update(env or {})
    child_env["JAX_PLATFORMS"] = _WORKER_PLATFORM
    proc = subprocess.Popen(
        cmd, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True, cwd=_REPO_ROOT, env=child_env)
    tail = []
    deadline = time.monotonic() + float(ready_timeout_s)
    while time.monotonic() < deadline:
        line = proc.stdout.readline()
        if not line:
            if proc.poll() is not None:
                raise MXNetError(
                    "fleet worker died before READY (rc="
                    f"{proc.returncode}):\n" + "\n".join(tail[-40:]))
            time.sleep(0.01)
            continue
        line = line.rstrip("\n")
        tail.append(line)
        del tail[:-200]
        if line.startswith("FLEET_WORKER_READY "):
            info = json.loads(line[len("FLEET_WORKER_READY "):])
            wp = WorkerProc(proc, info["url"], info.get("role", role),
                            info.get("worker_id"), spec_path)
            wp.output_tail = tail
            _drain_output(proc, tail)
            return wp
    proc.kill()
    raise MXNetError(
        f"fleet worker not READY within {ready_timeout_s}s:\n"
        + "\n".join(tail[-40:]))


class FleetProcs:
    """A spawned topology: `workers` in spawn order. Context manager;
    exit SIGKILLs anything still alive."""

    def __init__(self, workers):
        self.workers = list(workers)

    @property
    def urls(self):
        return [w.url for w in self.workers]

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    def close(self):
        for w in self.workers:
            w.kill()


def spawn_fleet(spec, roles=("mixed", "mixed"), **kw):
    """Bring up one worker per role entry (serially — model build is
    memory-hungry enough that parallel cold starts thrash small
    hosts). Returns a FleetProcs."""
    procs = []
    try:
        for role in roles:
            procs.append(spawn_worker(spec, role=role, **kw))
    except Exception:
        for p in procs:
            p.kill()
        raise
    return FleetProcs(procs)
