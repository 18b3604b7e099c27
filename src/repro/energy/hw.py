"""Chip constants keyed by the ``device_kind`` JAX reports, and host power.

TPU v5e peaks are Google Cloud's published figures ("TPU v5e"): 197 TFLOP/s
bf16, 16 GB of HBM at 819 GB/s; ~50 GB/s per ICI link.  The power bins are
derived from the public TDP (~215 W) and feed only the analytic roofline
estimator: nothing here is a measurement, and serving still bills at the host
constants below, on the chip as on the CPU.  A device kind missing from
:data:`CHIPS` is an error (:func:`chip_spec`), never a default.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class ChipSpec:
    name: str
    peak_flops_bf16: float          # FLOP/s
    hbm_bw: float                   # B/s
    ici_bw_per_link: float          # B/s per link
    ici_links: int                  # links per chip in a 2D torus
    hbm_bytes: int
    power_peak_w: float             # compute-bound sustained
    power_membound_w: float         # HBM-bound sustained
    power_idle_w: float

    @property
    def vmem_bytes(self) -> int:
        return 128 * 1024 * 1024  # ~128 MiB VMEM (v5e)


TPU_V5E = ChipSpec(
    name="tpu-v5e",
    peak_flops_bf16=197e12,
    hbm_bw=819e9,
    ici_bw_per_link=50e9,
    ici_links=4,
    hbm_bytes=16 * 1024**3,
    power_peak_w=215.0,
    power_membound_w=150.0,
    power_idle_w=65.0,
)

# device_kind (as jax.devices()[0].device_kind reports it) -> spec
CHIPS = {"TPU v5 lite": TPU_V5E}


def chip_spec(device_kind: str) -> ChipSpec:
    """The spec of an attached chip; raises for a kind not in :data:`CHIPS`."""
    try:
        return CHIPS[device_kind]
    except KeyError:
        raise KeyError(f"no ChipSpec for device kind {device_kind!r}; "
                       f"known: {sorted(CHIPS)}") from None


# Assumed host package power that serving bills measured step times at
# (no RAPL access): an indicative proxy, not the chip's draw.
HOST_CPU_POWER_W = 65.0

# Idle package draw as a fraction of active draw: a provisioned endpoint that
# is not computing still burns power (the SI4 'pay for the abstraction' cost).
HOST_CPU_IDLE_FRACTION = 0.3
HOST_CPU_IDLE_POWER_W = HOST_CPU_POWER_W * HOST_CPU_IDLE_FRACTION

# Global-average grid carbon intensity (IEA 2023), g CO2e per kWh.  The
# constant now lives with the carbon-intensity signals (it is the
# ConstantSignal default); re-exported here for legacy importers.
from repro.carbon.signal import CARBON_G_PER_KWH  # noqa: E402,F401
