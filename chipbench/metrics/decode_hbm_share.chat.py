"""Bytes the traced decode steps need (weights but the embedding table, and
the keys and values of live positions) over their device time at the HBM
peak (layer: model step)."""

from harness import ops


def read(run):
    got = run.traced_device_times("decode", "decode_fn")
    if got is None:
        return None
    calls, times = got
    need = sum(ops.decode_bytes(run.cfg, c.lengths, run.cfg["weights"])
               for c in calls)
    return 100.0 * need / (sum(times) * run.peaks["hbm_bytes_per_s"])
