"""Share of the traced window in which no operation ran on the device while
the host was inside the served path's own spans (``serve.*``).
``device_idle_share.chat`` less this is idle outside the program: the
benchmark's driver and the profiler (layer: device)."""

from harness import host


def read(run):
    evs = host.events(run)
    if evs is None:
        return None
    share = host.idle_in_program(evs)
    return None if share is None else 100.0 * share
