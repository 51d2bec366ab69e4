"""The comparison that decides ``correct``.

The system's guarantee is exactness: every reconciliation learns exactly
A xor B.  Once the window has closed, every reconciliation of it is held to
that: its result must report success, and its diff must equal A xor B as
numpy's ``setxor1d`` gives it for the sets the reconciliation was handed.
The rounds and bytes a result took are metrics, not part of the check, so
a protocol that needs fewer of them is still correct.

Two numbers are compared, each with the limit 0 (an exact comparison):

* ``unfinished`` -- reconciliations that gave no result (evicted, raised,
  or never returned);
* ``inexact``    -- results whose diff is not A xor B, or that report
  failure.

``run_reference`` runs the numpy copy of the protocol in
``reference/`` (it imports nothing of the program) in worker processes
started with ``spawn`` that never import JAX.  A traced run takes binning's
per-round shapes from it for the roofline; the control (``control.py``)
puts its results in the program's place.
"""
from __future__ import annotations

import multiprocessing
import os
from concurrent.futures import ProcessPoolExecutor

import numpy as np

LIMITS = {"unfinished": 0, "inexact": 0}


def faults(recon, truth) -> list[str]:
    """The compared numbers this reconciliation adds to; ``truth`` is its
    sorted A xor B."""
    if recon.served is None:
        return ["unfinished"]
    success, diff = recon.served[:2]
    if not success or not np.array_equal(diff, truth):
        return ["inexact"]
    return []


def compare(recons, truth_of) -> tuple[dict, int]:
    """The compared numbers over every reconciliation of the window, and
    how many reconciliations failed any of them."""
    counts = dict.fromkeys(LIMITS, 0)
    failed = 0
    for r in recons:
        found = faults(r, truth_of(r))
        for k in found:
            counts[k] += 1
        failed += bool(found)
    return counts, failed


def correct(counts: dict) -> bool:
    return all(counts[k] <= lim for k, lim in LIMITS.items())


def reference_one(task) -> tuple:
    """(key, result tuple, per-round binning shapes) of the reference on
    one task ``(key, a, b, PBSConfig keywords, d_known)``."""
    from loadgen import served_tuple
    from reference.pbs import PBSConfig, reconcile

    key, a, b, kwargs, d_known = task
    work: list = []
    res = reconcile(a, b, PBSConfig(**kwargs), d_known=d_known, work=work)
    return key, served_tuple(res), work


def _reference_chunk(tasks) -> list:
    return [reference_one(t) for t in tasks]


def run_reference(groups) -> dict:
    """key -> (result tuple, work) for every task of ``groups``, an
    iterable of task lists (one step each, so that only one step's sets are
    in memory at a time), over ``spawn`` workers."""
    out: dict = {}
    workers = max(1, min(8, (os.cpu_count() or 2) - 2))
    ctx = multiprocessing.get_context("spawn")
    with ProcessPoolExecutor(max_workers=workers, mp_context=ctx) as pool:
        for tasks in groups:
            futures = [pool.submit(_reference_chunk, tasks[i::workers])
                       for i in range(min(workers, len(tasks)))]
            out.update((key, (res, work))
                       for f in futures for key, res, work in f.result())
    return out
