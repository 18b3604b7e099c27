"""Host spans on the profiler's clock.

The served path's own spans (``serve.*``): scheduling, engine calls, the
waits on the device, token readbacks, billing, retirement, compiles and the
deploy's registry round trip.  Each is a ``jax.profiler.TraceAnnotation``,
so a profiler trace (``jax.profiler.trace``) puts it on its host plane, on
the same clock as the device's operations, and names what the host was
doing in every gap the device sat idle.  With no profiler running a span
costs about a microsecond and records nothing.

They stay out of :class:`~repro.serving.telemetry.recorder.TraceRecorder`
on purpose: that recorder stamps the simulator's virtual clock, and its
traces must be reproducible to the bit, which wall-clock spans never are.
"""

from __future__ import annotations

import jax


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """A host span ``name`` whose keyword ``args`` (``rid``, ``slot``,
    ``live``, ...) become stats on the trace event.  Nested spans follow
    the call tree, so a span's self time is its duration less the part its
    children cover."""
    return jax.profiler.TraceAnnotation(name, **args)
