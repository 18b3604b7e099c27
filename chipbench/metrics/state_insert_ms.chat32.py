"""Mean host time of copying an admitted request's recurrent state into its
slot: the program's ``serve.insert.state`` spans wholly inside the traced
slice (layer: scheduler).  A program without that span gives nothing."""

import numpy as np

from harness import host


def read(run):
    evs = host.events(run)
    spans = [] if evs is None else host.within(evs, "serve.insert.state")
    return float(np.mean([s.dur for s in spans])) / 1e6 if spans else None
