"""Mean host time of the served path's token readback after a decode step:
the ``serve.readback`` spans wholly inside the traced slice, one read of a
live slot's token each (layer: scheduler)."""

import numpy as np

from harness import host


def read(run):
    evs = host.events(run)
    spans = [] if evs is None else host.within(evs, "serve.readback")
    return float(np.mean([s.dur for s in spans])) / 1e6 if spans else None
