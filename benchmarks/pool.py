"""Process-pool sweep execution: run independent sweep cells in parallel.

One sweep cell = one fully-specified spec variant + its own workload seed;
cells share nothing at runtime (each worker process deploys a
:class:`repro.serving.stepcache.ReplayEngine` against the calibration
payload the parent measured once), so they parallelize embarrassingly.

Contract:

  * **deterministic order** — results come back indexed by cell position,
    regardless of completion order; a ``--jobs 8`` run emits the same rows
    in the same order as ``--jobs 1``;
  * **serial fallback** — ``jobs <= 1`` runs cells inline in this process
    (no pool, no pickling), which is also the degenerate path CI's quick
    jobs take;
  * **merge on join** — each worker returns its cell's
    :class:`~repro.energy.meter.EnergyMeter`; :func:`merge_meters` folds
    them into one fleet-level meter with per-cell provenance and asserts
    joule+gram conservation across the merge (the same invariant the
    in-process fleet merge is tested for).

Workers must be module-level functions and cell payloads picklable (specs
travel as JSON, calibration as a plain dict — see ``bench_simperf``).
"""

from __future__ import annotations

import concurrent.futures
import multiprocessing
from typing import Callable, List, Sequence, Tuple

import jax

from repro.energy.meter import EnergyMeter


def _cpu_only() -> None:
    # a chip belongs to one process, and the parent holds it: a worker that
    # initialised the TPU backend would fail or hang, so pin it to the CPU
    jax.config.update("jax_platforms", "cpu")


def run_cells(worker: Callable, cells: Sequence, jobs: int) -> List:
    """Run ``worker(cell)`` for every cell; results in cell order.

    ``jobs <= 1`` executes inline; otherwise a ``ProcessPoolExecutor``
    fans the cells out and the indexed collection restores submission
    order no matter which worker finishes first.
    """
    if jobs <= 1:
        return [worker(c) for c in cells]
    out: List = [None] * len(cells)
    # forkserver, not fork: the parent has a multithreaded XLA client by
    # the time the sweep starts, and forking a multithreaded process can
    # deadlock; forkserver workers start from a clean exec'd interpreter
    ctx = multiprocessing.get_context("forkserver")
    with concurrent.futures.ProcessPoolExecutor(
            max_workers=jobs, mp_context=ctx, initializer=_cpu_only) as ex:
        futures = {ex.submit(worker, c): i for i, c in enumerate(cells)}
        for fut in concurrent.futures.as_completed(futures):
            out[futures[fut]] = fut.result()
    return out


def merge_meters(meters: Sequence[EnergyMeter], *,
                 active_power_w: float,
                 idle_power_w: float) -> Tuple[EnergyMeter, dict]:
    """Fold per-cell meters into one, with conservation receipts.

    The fold is joule-preserving (``EnergyMeter.merge``'s contract), so the
    merged total must equal the sum of the parts to float tolerance — in
    joules AND grams.  Returns ``(merged, receipt)`` where the receipt is a
    JSON-ready dict recording both sides of each equality; an imbalance
    raises immediately (a silently-leaking parallel sweep would poison
    every grid built on it).
    """
    merged = EnergyMeter(active_power_w=active_power_w,
                         idle_power_w=idle_power_w)
    sum_j = sum_g = 0.0
    for i, m in enumerate(meters):
        sum_j += m.total_j
        sum_g += m.total_g
        merged.merge(m, source=f"cell{i}")
    tol_j = 1e-6 * max(sum_j, 1.0)
    tol_g = 1e-6 * max(sum_g, 1.0)
    if abs(merged.total_j - sum_j) > tol_j:
        raise AssertionError(
            f"joule conservation broken across pool join: merged "
            f"{merged.total_j} != sum of cells {sum_j}")
    if abs(merged.total_g - sum_g) > tol_g:
        raise AssertionError(
            f"gram conservation broken across pool join: merged "
            f"{merged.total_g} != sum of cells {sum_g}")
    receipt = {
        "cells": len(list(meters)),
        "merged_total_j": merged.total_j,
        "sum_cell_j": sum_j,
        "merged_total_g": merged.total_g,
        "sum_cell_g": sum_g,
        "joules_conserved": True,
        "grams_conserved": True,
    }
    return merged, receipt
