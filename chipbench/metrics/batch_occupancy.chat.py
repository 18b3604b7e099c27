"""Live slots over slots, summed over every decode step of the run: the
program's ``live_slot_steps`` / ``slot_steps`` counters, read off the
decode calls the benchmark stamps, since the step cache is off and every
step calls the engine (layer: scheduler)."""


def read(run):
    steps = [c for c in run.calls if c.kind == "decode"]
    slots = sum(c.rows for c in steps)
    return 100.0 * sum(len(c.lengths) for c in steps) / slots if slots \
        else None
