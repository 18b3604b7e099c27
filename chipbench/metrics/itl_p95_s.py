"""95th percentile, over every output token after a request's first, of the
wall time since that request's previous token."""

from harness.record import percentile


def read(run):
    return percentile(run.gaps(), 95)
