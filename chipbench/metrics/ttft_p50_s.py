"""Median, over every request due in the window, of the wall time from
when it was due to its first token."""

from harness.record import percentile


def read(run):
    return percentile(run.ttfts(), 50)
