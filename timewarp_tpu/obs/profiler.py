"""Optional ``jax.profiler`` integration.

The telemetry counters (obs/telemetry.py) answer *what the emulation
did*; the XLA profiler answers *where the chip time went*. This module
wraps the latter so callers can always write ``with
profile_session(logdir):`` — ``logdir=None`` is a no-op session; a
directory that was asked for and a session that cannot start is an
error (a profile run that profiled nothing must not exit 0). Nothing
here ever imports at engine-construction time; the zero-overhead law
is untouched.
"""

from __future__ import annotations

from contextlib import contextmanager
from typing import Optional

__all__ = ["profile_session", "annotate"]


@contextmanager
def profile_session(logdir: Optional[str]):
    """A ``jax.profiler`` trace session writing to ``logdir`` (view
    with TensorBoard or xprof). ``logdir=None`` yields a plain no-op
    session. A session that was asked for and cannot start raises:
    ``timewarp-tpu profile`` fails instead of exiting 0 with no
    trace."""
    if not logdir:
        yield None
        return
    import jax.profiler as _jp
    _jp.start_trace(logdir)
    try:
        yield logdir
    finally:
        _jp.stop_trace()


def annotate(name: str):
    """A named ``TraceAnnotation`` context (shows up as a labeled span
    in the device profile)."""
    from jax.profiler import TraceAnnotation
    return TraceAnnotation(name)
