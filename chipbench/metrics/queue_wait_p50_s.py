"""Median wall time from due to the start of the request's prefill
(layer: scheduler)."""

from harness.record import percentile


def read(run):
    return percentile(run.queue_waits(), 50)
