"""paddle.distributed.spawn — multiprocessing launch alternative.

ref: python/paddle/distributed/spawn.py (spawn(func, args, nprocs,
join): per-rank subprocesses with the trainer env contract, error
collection, join semantics). On TPU one process drives all local chips,
so spawn is the CPU-backend/test-harness path; forked workers get
PADDLE_TRAINER_ID/PADDLE_TRAINERS_NUM and a reset parallel context.
"""
from __future__ import annotations

import multiprocessing as _mp
import os
import traceback

__all__ = ["spawn"]


def _worker(rank, nprocs, func, args, err_q):
    os.environ["PADDLE_TRAINER_ID"] = str(rank)
    os.environ["PADDLE_TRAINERS_NUM"] = str(nprocs)
    os.environ["PADDLE_LOCAL_RANK"] = str(rank)
    from . import parallel

    parallel._parallel_env = None  # forked copy must re-read the env
    try:
        func(*args)
    except Exception:
        err_q.put((rank, traceback.format_exc()))
        raise SystemExit(1)


def spawn(func, args=(), nprocs=-1, join=True, daemon=False, **options):
    """Launch ``func(*args)`` in ``nprocs`` processes with the trainer
    env contract (ref spawn.py). Returns the context (list of processes)
    when join=False; raises if any worker fails."""
    if nprocs <= 0:
        nprocs = int(os.environ.get("PADDLE_TRAINERS_NUM", "1"))
    from jax._src import xla_bridge

    from ..core.device import on_tpu

    if xla_bridge.backends_are_initialized() and on_tpu():
        # a chip belongs to one process: forked workers of a parent that
        # holds it would fail or hang at their first jax call
        raise RuntimeError(
            "distributed.spawn cannot fork workers from a process that "
            "already holds the TPU; start them with `python -m "
            "paddle_tpu.distributed.launch` (its parent stays off JAX) "
            "or call spawn before this process touches JAX"
        )
    ctx = _mp.get_context("fork")
    err_q = ctx.Queue()
    procs = [
        ctx.Process(
            target=_worker, args=(r, nprocs, func, args, err_q),
            daemon=daemon,
        )
        for r in range(nprocs)
    ]
    for p in procs:
        p.start()
    if not join:
        return procs
    for p in procs:
        p.join()
    bad = {r: p.exitcode for r, p in enumerate(procs) if p.exitcode}
    failures = []
    # one traceback is queued per failed worker; empty()-polling races
    # the queue feeder, so get with a timeout per expected failure. A
    # worker killed before queuing (segfault, SIGKILL) leaves the queue
    # short — Empty then means nothing more is coming.
    import queue as _queue

    for _ in bad:
        try:
            failures.append(err_q.get(timeout=2))
        except _queue.Empty:
            break
    if bad:
        # every failure in ONE error: the first worker to die is often
        # a victim (e.g. of a peer's torn collective), and raising only
        # its traceback hides the actual culprit
        parts = [
            f"worker {rank} failed:\n{tb}"
            for rank, tb in sorted(failures)
        ]
        silent = sorted(set(bad) - {rank for rank, _ in failures})
        if silent:
            parts.append(
                "worker(s) exited nonzero without a traceback: "
                + ", ".join(
                    f"rank {r} (exitcode {bad[r]})" for r in silent
                )
            )
        raise RuntimeError(
            f"spawn: {len(bad)} of {nprocs} worker(s) failed\n"
            + "\n".join(parts)
        )
    return None
