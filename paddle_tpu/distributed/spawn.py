"""paddle.distributed.spawn parity.

Reference: python/paddle/distributed/spawn.py — spawn(fn, args, nprocs):
multiprocessing entry that forks N workers with the trainer env contract
set (SURVEY.md §2.4 "spawn").

TPU-native note: on a real TPU host a single process drives all local
chips, so nprocs defaults to 1.  Multi-process spawn is for
CPU-SIMULATED multi-host tests only (each child gets its own JAX
runtime): a chip belongs to one process at a time, and a parent that
has touched JAX holds the chip — children spawned from it that need
the chip fail or hang.
Uses the 'spawn' start method — fork would inherit an initialized,
multithreaded JAX runtime.
"""

from __future__ import annotations

import multiprocessing as mp
import os
from typing import Optional, Tuple

__all__ = ["spawn"]


def _worker(fn, rank: int, nprocs: int, args: Tuple, env: dict):
    os.environ.update(env)
    os.environ["PADDLE_TRAINER_ID"] = str(rank)
    os.environ["PADDLE_TRAINERS_NUM"] = str(nprocs)
    os.environ["PADDLE_LOCAL_RANK"] = str(rank)
    fn(*args)


def spawn(func, args: Tuple = (), nprocs: int = 1, join: bool = True,
          daemon: bool = False, **options):
    """Spawn ``nprocs`` workers running ``func(*args)``; returns the
    context (list of Process) when join=False."""
    ctx = mp.get_context("spawn")
    env = {k: v for k, v in os.environ.items()
           if k.startswith(("PADDLE_", "JAX_", "XLA_"))}
    procs = []
    for rank in range(nprocs):
        p = ctx.Process(target=_worker,
                        args=(func, rank, nprocs, args, env),
                        daemon=daemon)
        p.start()
        procs.append(p)
    if not join:
        return procs
    codes = []
    for p in procs:
        p.join()
        codes.append(p.exitcode)
    if any(c != 0 for c in codes):
        raise RuntimeError(f"spawn workers failed with exit codes {codes}")
    return procs
