"""Emulated multi-process meshes on one host (CPU, ``jax.distributed``).

The paper's runs span up to 128 GPUs; CI has one host. This module gives the
closest faithful stand-in: N real OS processes, each a ``jax.distributed``
participant with its own fake CPU devices, coordinating through the gloo CPU
collectives backend. Collectives genuinely cross process boundaries, a rank
can genuinely die (``os._exit``), and the survivors genuinely have to restart
from a checkpoint — the failure modes the resilience subsystem exists for,
none of which a single-process fake-device mesh can produce.

Topology is carried in ``REPRO_MP_*`` environment variables because the XLA
flags that create fake devices must be set *before* ``jax`` is imported:
the parent builds the env (``worker_env``), spawns plain ``python -c``
children (``launch_workers``), and each child calls ``init_from_env()`` as
its first jax-touching act.

Typical worker body::

    from repro.runtime import multiproc
    pid, nprocs = multiproc.init_from_env()   # joins the coordinator
    mesh = multiproc.global_mesh("data")       # spans ALL processes' devices
    ...train, checkpoint per-rank, maybe os._exit(1) on cue...
"""
from __future__ import annotations

import os
import socket
import subprocess
import sys
from typing import Dict, List, Optional, Sequence, Tuple

ENV_COORD = "REPRO_MP_COORD"
ENV_NPROCS = "REPRO_MP_NPROCS"
ENV_PID = "REPRO_MP_PID"


def distributed_available() -> Tuple[bool, str]:
    """(ok, reason): can this interpreter run localhost multi-process jax?

    Checked without initializing anything, so callers (tests, CI) can skip
    gracefully — and log why — on builds without ``jax.distributed`` or the
    gloo CPU collectives backend.
    """
    try:
        import jax
    except ImportError as e:  # pragma: no cover - jax is a hard dep elsewhere
        return False, f"jax not importable: {e}"
    if not hasattr(jax, "distributed"):
        return False, "jax.distributed missing in this jax build"
    if "jax_cpu_collectives_implementation" not in jax.config.values:
        return False, "no jax_cpu_collectives_implementation config (gloo unavailable)"
    return True, "ok"


def free_port() -> int:
    """An OS-assigned free TCP port for the jax.distributed coordinator."""
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def worker_env(num_processes: int, process_id: int, coordinator_port: int,
               local_devices: int = 1,
               base_env: Optional[Dict[str, str]] = None) -> Dict[str, str]:
    """The environment for one spawned worker: CPU-only platform, fake-device
    count (must precede jax import — hence env, not API), and the REPRO_MP_*
    topology ``init_from_env`` reads."""
    env = dict(os.environ if base_env is None else base_env)
    env["JAX_PLATFORMS"] = "cpu"
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if not f.startswith("--xla_force_host_platform_device_count")]
    flags.append(f"--xla_force_host_platform_device_count={local_devices}")
    env["XLA_FLAGS"] = " ".join(flags)
    env[ENV_COORD] = f"localhost:{coordinator_port}"
    env[ENV_NPROCS] = str(num_processes)
    env[ENV_PID] = str(process_id)
    return env


def init_from_env(timeout_ms: int = 60_000) -> Tuple[int, int]:
    """Join the coordinator described by REPRO_MP_*. Call before any other jax
    use in a spawned worker. Returns (process_id, num_processes)."""
    import jax

    coord = os.environ[ENV_COORD]
    nprocs = int(os.environ[ENV_NPROCS])
    pid = int(os.environ[ENV_PID])
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=coord, num_processes=nprocs, process_id=pid,
        initialization_timeout=max(1, timeout_ms // 1000))
    return pid, nprocs


def global_mesh(axis: str = "data"):
    """A 1-D mesh over every device of every participating process (the global
    device list ``jax.devices()`` — NOT the process-local subset)."""
    import jax
    from jax.sharding import Mesh

    import numpy as np

    return Mesh(np.array(jax.devices()), (axis,))


def launch_workers(worker_src: str, num_processes: int, *,
                   local_devices: int = 1, timeout: float = 240.0,
                   extra_env: Optional[Dict[str, str]] = None,
                   pythonpath: Optional[str] = None):
    """Spawn ``num_processes`` children running ``python -c worker_src`` with a
    shared fresh coordinator port; wait for all; return the list of
    ``CompletedProcess``-like results (returncode, stdout, stderr per rank).

    Workers that exit non-zero are NOT an error here — killing ranks is the
    point. A worker that outlives ``timeout`` is killed and reported with
    returncode ``-9``.
    """
    port = free_port()
    procs: List[subprocess.Popen] = []
    for pid in range(num_processes):
        env = worker_env(num_processes, pid, port, local_devices)
        if pythonpath:
            env["PYTHONPATH"] = pythonpath + os.pathsep + env.get("PYTHONPATH", "")
        env.update(extra_env or {})
        procs.append(subprocess.Popen(
            [sys.executable, "-c", worker_src], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True))
    results = []
    for p in procs:
        try:
            out, err = p.communicate(timeout=timeout)
            rc = p.returncode
        except subprocess.TimeoutExpired:
            p.kill()
            out, err = p.communicate()
            rc = -9
        results.append(subprocess.CompletedProcess(p.args, rc, out, err))
    return results
