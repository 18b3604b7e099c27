"""Device time of the jitted prefill per 1000 prompt tokens, in the traced
slice (layer: engine)."""


def read(run):
    got = run.traced_device_times("prefill", "prefill_fn")
    if got is None:
        return None
    calls, times = got
    return 1e3 * sum(times) / (sum(c.rows for c in calls) / 1e3)
