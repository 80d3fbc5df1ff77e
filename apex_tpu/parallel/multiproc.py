"""Multi-host runtime: real ``jax.distributed`` launch + the spawner.

On TPU pods the model is ONE process per host, each seeing its local
chips, coordinated by ``jax.distributed.initialize`` (reference
``apex/parallel/multiproc.py:12-34`` spawns N single-GPU workers; the
TPU analog spawns one worker per host).  Two layers live here
(ISSUE 12):

* :func:`initialize` — the per-process entry: coordinator address /
  process id / process count autodetected from the environment
  (``JAX_COORDINATOR_ADDRESS``/``JAX_PROCESS_ID``/``JAX_NUM_PROCESSES``,
  the torchrun-style ``MASTER_ADDR``+``MASTER_PORT``/``RANK``/
  ``WORLD_SIZE``, or cloud-TPU metadata via jax's own autodetect),
  idempotent, with gloo CPU collectives enabled so the SAME code path
  runs on a CPU CI box (``docker/run_matrix.sh``'s 2-process lane and
  the ``bench.py`` multi-host fixture are real multi-process runs).
  After it returns, ``jax.devices()`` spans every process and a
  :class:`~apex_tpu.parallel.mesh.MeshPlan` built from it is the
  per-process view of one global mesh.
* :func:`main` — the local spawner (``python -m
  apex_tpu.parallel.multiproc --nproc N train.py ...``): N workers on
  THIS machine with the env above set, rank>0 stdout to
  ``TPU_<i>.log``.  It emulates N hosts on the CPU backend and refuses
  ``N > 1`` anywhere else: a chip belongs to one process at a time and
  nothing here binds a worker to its own chips, so on one TPU host the
  supported shape is ONE process driving all local chips (run the
  script directly); on a pod, the scheduler starts one process per host
  with the env above.

:func:`process_identity` / :func:`is_coordinator` are the single
source of process identity for the rest of the stack —
``CheckpointManager`` per-host shard writes and telemetry run stamps
read THEM instead of ad-hoc ``jax.process_index()`` calls, so a worker
that has not (yet) initialized the distributed runtime still shards
and stamps correctly from its environment.
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys
from typing import Optional, Tuple

_STATE = {"initialized": False, "procs": None}

#: env spellings accepted for each field, first hit wins (jax-native
#: first, then the torchrun/reference convention the spawner sets).
_ENV_COORD = ("JAX_COORDINATOR_ADDRESS", "COORDINATOR_ADDRESS")
_ENV_NPROC = ("JAX_NUM_PROCESSES", "WORLD_SIZE")
_ENV_PID = ("JAX_PROCESS_ID", "RANK")


def _env_int(names) -> Optional[int]:
    for n in names:
        v = os.environ.get(n)
        if v is not None and v.strip():
            try:
                return int(v)
            except ValueError:
                raise ValueError(f"env {n}={v!r} is not an integer")
    return None


def _env_coordinator() -> Optional[str]:
    for n in _ENV_COORD:
        v = os.environ.get(n)
        if v:
            return v
    host, port = os.environ.get("MASTER_ADDR"), os.environ.get("MASTER_PORT")
    if host and port:
        return f"{host}:{port}"
    return None


def initialize(coordinator_address: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None,
               local_device_ids=None) -> Tuple[int, int]:
    """Join the distributed runtime; returns ``(process_id, count)``.

    Every argument defaults from the environment (see module
    docstring).  Single-process (no env, no args, or count 1) is a
    no-op returning ``(0, 1)`` — safe to call unconditionally at the
    top of every entry point.  Idempotent: a second call returns the
    established identity without re-initializing (jax raises on double
    init; schedulers restart entry points).

    On CPU backends the gloo collectives implementation is enabled
    first (config is a no-op where jaxlib lacks the knob), so
    multi-process CPU runs exchange REAL collectives — the bench
    fixture's parity gate depends on it.
    """
    if _STATE["initialized"]:
        return _STATE["procs"]
    if coordinator_address is None:
        coordinator_address = _env_coordinator()
    if num_processes is None:
        num_processes = _env_int(_ENV_NPROC)
    if process_id is None:
        process_id = _env_int(_ENV_PID)

    if (num_processes is None or num_processes <= 1) \
            and coordinator_address is None:
        _STATE["initialized"] = True
        _STATE["procs"] = (0, 1)
        return _STATE["procs"]

    import jax

    try:
        # Cross-process CPU collectives (no-op on TPU jaxlibs without
        # the flag; TPU pods use ICI natively).
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    except Exception:                                # pragma: no cover
        pass
    kw = {}
    if local_device_ids is not None:
        kw["local_device_ids"] = local_device_ids
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id, **kw)
    _STATE["initialized"] = True
    _STATE["procs"] = (int(jax.process_index()), int(jax.process_count()))  # jaxlint: disable=J001 -- process identity is a host-side distributed-setup constant, not a device value
    return _STATE["procs"]


def process_identity() -> Tuple[int, int]:
    """``(process_index, process_count)`` of this host — THE identity
    the checkpoint shard writer and telemetry run stamps use.

    Resolution order: an :func:`initialize`-established identity; the
    live jax distributed state when someone else initialized it; the
    launcher environment (a spawned worker that has not called
    :func:`initialize` yet still owns its shard); single-process
    ``(0, 1)``."""
    if _STATE["initialized"]:
        return _STATE["procs"]
    try:
        import jax
        from jax._src import distributed as _dist
        if getattr(_dist.global_state, "client", None) is not None:
            return (int(jax.process_index()), int(jax.process_count()))  # jaxlint: disable=J001 -- process identity is a host-side distributed-setup constant, not a device value
    except Exception:                                # pragma: no cover
        pass
    pid, n = _env_int(_ENV_PID), _env_int(_ENV_NPROC)
    if pid is not None and n is not None and n > 1:
        if not 0 <= pid < n:
            raise ValueError(f"process id {pid} not in [0, {n}) "
                             f"(check RANK/WORLD_SIZE env)")
        return (pid, n)
    try:
        import jax
        return (int(jax.process_index()), int(jax.process_count()))  # jaxlint: disable=J001 -- process identity is a host-side distributed-setup constant, not a device value
    except Exception:                                # pragma: no cover
        return (0, 1)


def is_coordinator() -> bool:
    """True on the elected coordinator (process 0) — gate single-writer
    work (run stamps, manifest extras, log lines) on THIS instead of
    re-deriving rank conventions per call site."""
    return process_identity()[0] == 0


def docstring_hack():
    """Multiproc file which will launch a set of processes locally for
    multi-host training (reference docstring parity)."""
    pass


def worker_env(rank: int, nproc: int, coordinator: str,
               base: Optional[dict] = None) -> dict:
    """The environment one spawned worker needs — shared by
    :func:`main` and the test/bench fixtures so the spawner and the
    autodetect in :func:`initialize` can never drift."""
    env = dict(os.environ if base is None else base)
    env.update(RANK=str(rank), WORLD_SIZE=str(nproc),
               JAX_COORDINATOR_ADDRESS=coordinator,
               JAX_NUM_PROCESSES=str(nproc),
               JAX_PROCESS_ID=str(rank))
    return env


def main(argv=None):
    argv = argv if argv is not None else sys.argv[1:]
    parser = argparse.ArgumentParser(add_help=False)
    parser.add_argument("--nproc", type=int,
                        default=int(os.environ.get("WORLD_SIZE", "1")))
    parser.add_argument("--coordinator", type=str, default="127.0.0.1:12355")
    args, rest = parser.parse_known_args(argv)
    if args.nproc > 1 \
            and os.environ.get("JAX_PLATFORMS", "").lower() != "cpu":
        parser.error(
            f"--nproc {args.nproc} starts {args.nproc} processes on this "
            f"machine and nothing binds each to its own chips: on a TPU "
            f"host every one of them would ask for all the chips, and a "
            f"chip belongs to one process.  One process drives all local "
            f"chips — run the script directly.  Local multi-process runs "
            f"emulate hosts on the CPU backend only: set "
            f"JAX_PLATFORMS=cpu.")

    workers = []
    for rank in range(args.nproc):
        env = worker_env(rank, args.nproc, args.coordinator)
        cmd = [sys.executable] + rest + ["--rank", str(rank)]
        stdout = None if rank == 0 else open("TPU_{}.log".format(rank), "w")
        workers.append(subprocess.Popen(cmd, env=env, stdout=stdout))

    rc = 0
    for w in workers:
        w.wait()
        rc = rc or w.returncode
    return rc


if __name__ == "__main__":
    sys.exit(main())
