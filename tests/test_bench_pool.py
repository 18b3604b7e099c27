"""The process-pool sweep helpers must keep two promises: deterministic
result order (``--jobs N`` emits the same rows as ``--jobs 1``) and
joule+gram conservation across the merge-on-join.

The workers here are trivial top-level functions so the suite stays fast and
pool-free (``jobs=1`` exercises the inline path, which is the contract the
parallel path is pinned against elsewhere by the cell-order indexing).
"""

import jax
import pytest

from benchmarks.pool import merge_meters, run_cells
from repro.energy.meter import EnergyMeter


def _square(x):
    return x * x


def test_run_cells_serial_preserves_cell_order():
    assert run_cells(_square, [3, 1, 4, 1, 5], jobs=1) == [9, 1, 16, 1, 25]


def test_run_cells_empty():
    assert run_cells(_square, [], jobs=1) == []


def _platforms(_):
    return jax.config.jax_platforms


def test_pool_workers_are_pinned_to_cpu():
    """Workers never claim the chip the parent process holds."""
    assert run_cells(_platforms, [0, 1], jobs=2) == ["cpu", "cpu"]


def _mk_meter(active_s: float, idle_s: float) -> EnergyMeter:
    m = EnergyMeter(active_power_w=100.0, idle_power_w=20.0)
    m.record_active(active_s, rids=[0], tokens=4)
    m.record_idle(idle_s)
    return m


def test_merge_meters_conserves_joules_and_grams():
    meters = [_mk_meter(1.0, 0.5), _mk_meter(2.0, 0.0), _mk_meter(0.0, 3.0)]
    sum_j = sum(m.total_j for m in meters)
    sum_g = sum(m.total_g for m in meters)
    merged, receipt = merge_meters(meters, active_power_w=100.0,
                                   idle_power_w=20.0)
    assert merged.total_j == pytest.approx(sum_j, rel=1e-9)
    assert merged.total_g == pytest.approx(sum_g, rel=1e-9)
    assert receipt["cells"] == 3
    assert receipt["joules_conserved"] and receipt["grams_conserved"]
    assert receipt["merged_total_j"] == pytest.approx(receipt["sum_cell_j"],
                                                      rel=1e-9)
    assert receipt["merged_total_g"] == pytest.approx(receipt["sum_cell_g"],
                                                      rel=1e-9)


def test_merge_meters_empty_is_zero():
    merged, receipt = merge_meters([], active_power_w=100.0,
                                   idle_power_w=20.0)
    assert merged.total_j == 0.0
    assert receipt["cells"] == 0
