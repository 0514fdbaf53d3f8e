"""jax.distributed lifecycle + a single-machine multi-process test harness.

The fleet engine scales the instance axis across hosts by letting the 1-D
``fleet`` mesh span *processes*: ``jax.devices()`` is global once
``jax.distributed`` is initialized, so ``sharding.specs.fleet_mesh()``
already covers every process's devices — what this module adds is the
lifecycle around it:

* ``initialize()`` / ``shutdown()`` — idempotent wrappers over
  ``jax.distributed.initialize`` that (a) default the coordinator address,
  process count and process id from the ``REPRO_DIST_*`` environment the
  local-cluster harness sets, and (b) select the ``gloo`` CPU collectives
  layer so cross-process gathers (``gather=True`` readbacks) work on
  CPU-only hosts.  Call ``initialize()`` before the first touch of
  ``jax.devices()``.
* ``run_local_cluster()`` — the ``REPRO_FORCE_PROCESSES=N`` analogue of the
  forced-device trick: spawn N subprocess workers on one machine, each a
  full JAX process with its own ``--xla_force_host_platform_device_count``
  CPU devices, all joined to one coordinator on a freshly-picked local
  port.  Used by ``tests/test_multihost.py`` and the ``multihost_scaling``
  kernel-bench row to prove N-process == 1-process bit-identity without
  real multi-host hardware.

Workers NEVER inherit the parent's JAX runtime: each one is a fresh
``sys.executable`` subprocess, so the parent process (e.g. pytest) can stay
single-process and compute reference results in-process.
"""
from __future__ import annotations

import os
import socket
import subprocess
import sys
import time
from typing import Dict, List, Optional, Sequence

# Environment keys the harness sets for each worker and ``initialize()``
# reads back.  REPRO_FORCE_PROCESSES only sets the harness's default
# process count (mirroring REPRO_FORCE_DEVICES for devices).
ENV_COORD = "REPRO_DIST_COORDINATOR"
ENV_NPROCS = "REPRO_DIST_NUM_PROCESSES"
ENV_PID = "REPRO_DIST_PROCESS_ID"
ENV_FORCE_PROCESSES = "REPRO_FORCE_PROCESSES"

_STATE = {"initialized": False}


def initialize(coordinator_address: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None, *,
               cpu_collectives: str = "gloo") -> bool:
    """Bring up the multi-process JAX runtime.  Arguments default from the
    ``REPRO_DIST_*`` environment (set by ``run_local_cluster`` or a real
    launcher); with no arguments and no environment this is a no-op that
    returns False, so single-process callers can call it unconditionally.

    Returns True iff a multi-process runtime is (now) initialized.
    Idempotent: a second call is a no-op returning the current state.
    """
    if _STATE["initialized"]:
        return True
    coordinator_address = coordinator_address or os.environ.get(ENV_COORD)
    if num_processes is None and ENV_NPROCS in os.environ:
        num_processes = int(os.environ[ENV_NPROCS])
    if process_id is None and ENV_PID in os.environ:
        process_id = int(os.environ[ENV_PID])
    if coordinator_address is None or not (num_processes or 0) > 1:
        return False
    import jax
    # CPU collectives must be picked before the backend initializes; gloo
    # is what makes cross-process psum/allgather work on CPU-only hosts.
    jax.config.update("jax_cpu_collectives_implementation", cpu_collectives)
    jax.distributed.initialize(coordinator_address=coordinator_address,
                               num_processes=num_processes,
                               process_id=process_id)
    _STATE["initialized"] = True
    return True


def shutdown() -> None:
    """Tear down the multi-process runtime started by ``initialize()``.
    Idempotent; a no-op when single-process."""
    if not _STATE["initialized"]:
        return
    import jax
    jax.distributed.shutdown()
    _STATE["initialized"] = False


def is_initialized() -> bool:
    return _STATE["initialized"]


def pick_free_port() -> int:
    """An OS-assigned free TCP port on localhost (bind port 0, read it
    back).  Raceable in principle; in practice the coordinator binds it
    within milliseconds and the harness retries are the workers' own
    jax.distributed connection retries."""
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("127.0.0.1", 0))
        return int(s.getsockname()[1])


def default_num_processes(fallback: int = 2) -> int:
    """Harness default process count: ``REPRO_FORCE_PROCESSES`` if set,
    else ``fallback``."""
    return int(os.environ.get(ENV_FORCE_PROCESSES, str(fallback)))


def _src_root() -> str:
    # .../src/repro/sharding/distributed.py -> .../src
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def worker_env(coordinator_address: str, num_processes: int, process_id: int,
               devices_per_process: int = 1,
               extra_env: Optional[Dict[str, str]] = None) -> Dict[str, str]:
    """The environment for one local-cluster worker: coordinator wiring via
    ``REPRO_DIST_*``, ``devices_per_process`` forced CPU devices, CPU
    platform pinned, and ``src`` on PYTHONPATH."""
    env = dict(os.environ)
    env.update(extra_env or {})
    env[ENV_COORD] = coordinator_address
    env[ENV_NPROCS] = str(num_processes)
    env[ENV_PID] = str(process_id)
    env["JAX_PLATFORMS"] = "cpu"
    kept = [f for f in env.get("XLA_FLAGS", "").split()
            if "xla_force_host_platform_device_count" not in f]
    kept.append(f"--xla_force_host_platform_device_count={devices_per_process}")
    env["XLA_FLAGS"] = " ".join(kept)
    path = env.get("PYTHONPATH", "")
    src = _src_root()
    if src not in path.split(os.pathsep):
        env["PYTHONPATH"] = src + (os.pathsep + path if path else "")
    return env


def run_local_cluster(worker_argv: Sequence[str],
                      n_processes: Optional[int] = None, *,
                      devices_per_process: int = 1,
                      timeout: float = 600.0,
                      cwd: Optional[str] = None,
                      extra_env: Optional[Dict[str, str]] = None) -> List[str]:
    """Run ``python *worker_argv`` as an ``n_processes``-process local JAX
    cluster and return each worker's stdout (index == process id).

    Every worker gets the same argv and a ``worker_env(...)`` environment;
    workers discover their role via ``repro.sharding.distributed
    .initialize()`` (no arguments).  On ANY worker failure or timeout the
    whole cluster is killed before raising, so no orphan workers hold the
    coordinator port across tests.
    """
    n = n_processes if n_processes is not None else default_num_processes()
    port = pick_free_port()
    coord = f"127.0.0.1:{port}"
    procs: List[subprocess.Popen] = []
    try:
        for pid in range(n):
            env = worker_env(coord, n, pid, devices_per_process, extra_env)
            procs.append(subprocess.Popen(
                [sys.executable, *worker_argv], env=env, cwd=cwd,
                stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True))
        deadline = time.monotonic() + timeout
        outs: List[str] = []
        errs: List[str] = []
        for pid, p in enumerate(procs):
            left = deadline - time.monotonic()
            if left <= 0:
                raise subprocess.TimeoutExpired(p.args, timeout)
            out, err = p.communicate(timeout=left)
            outs.append(out)
            errs.append(err)
        bad = [pid for pid, p in enumerate(procs) if p.returncode != 0]
        if bad:
            tails = "\n".join(
                f"--- worker {pid} (rc={procs[pid].returncode}) stderr tail ---\n"
                + "\n".join(errs[pid].splitlines()[-15:]) for pid in bad)
            raise RuntimeError(
                f"local cluster workers {bad} failed (n={n}, coord={coord})\n{tails}")
        return outs
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        for p in procs:
            if p.poll() is None:
                try:
                    p.wait(timeout=10)
                except Exception:
                    pass
