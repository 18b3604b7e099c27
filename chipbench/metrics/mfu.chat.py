"""Model FLOPs of the traced decode steps over their device time at the bf16
peak: the decode step's share of the chip (layer: model step)."""

from harness import ops


def read(run):
    got = run.traced_device_times("decode", "decode_fn")
    if got is None:
        return None
    calls, times = got
    flops = sum(ops.decode_flops(run.cfg, c.lengths) for c in calls)
    return 100.0 * flops / (sum(times) * run.peaks["bf16_flops"])
