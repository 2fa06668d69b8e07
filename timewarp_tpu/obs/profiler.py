"""Optional ``jax.profiler`` integration.

The telemetry counters (obs/telemetry.py) answer *what the emulation
did*; the XLA profiler answers *where the chip time went*. This module
wraps the latter so callers can always write ``with
profile_session(logdir):`` — ``logdir=None`` is a no-op session; a
directory that was asked for and a session that cannot start is an
error (a profile run that profiled nothing must not exit 0).

:func:`span` is the one host-side span primitive: the drivers open
``tw.<driver>`` / ``tw.dispatch`` / ``tw.wait`` / ``tw.guard`` with it
(``RunStatsMixin._driver_call``), the sweep service ``tw.sweep.bucket``.
On the device the superstep's stages carry the ``jax.named_scope``
names of ``common.STAGES``. Both are the profiler's own: with no
session open a span is TraceMe's inactive path, and a scope is
metadata of the compiled program. Nothing here records, writes or
switches anything.
"""

from __future__ import annotations

import threading
from contextlib import contextmanager
from typing import Optional

__all__ = ["profile_session", "span"]

_open = threading.local()       # names of the spans open on this thread


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
    import jax
    import jax.profiler as _jp
    # the stage names a profile shows are the executable's, and the
    # persistent compile cache keys on the program less its metadata:
    # an executable cached before a scope was named would be served
    # without it. What compiles under a session keys on metadata too.
    key = "jax_compilation_cache_include_metadata_in_key"
    was = getattr(jax.config, key)
    jax.config.update(key, True)
    _jp.start_trace(logdir)
    try:
        yield logdir
    finally:
        _jp.stop_trace()
        jax.config.update(key, was)


@contextmanager
def span(name: str, **attrs):
    """A host span in the profile: a ``jax.profiler.TraceAnnotation``
    named ``name`` whose start and end are the profiler's, with
    ``attrs`` and ``cause`` (the name of the span it was opened in;
    none at the top) as stats on the event."""
    from jax.profiler import TraceAnnotation
    names = _open.__dict__.setdefault("names", [])
    if names:
        attrs["cause"] = names[-1]
    with TraceAnnotation(name, **attrs):
        names.append(name)
        try:
            yield
        finally:
            names.pop()
