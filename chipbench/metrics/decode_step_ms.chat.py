"""Mean device time of one jitted decode launch, in the traced slice
(layer: engine)."""

import numpy as np


def read(run):
    got = run.traced_device_times("decode", "decode_fn")
    return None if got is None else 1e3 * float(np.mean(got[1]))
