"""Entry points: seconds of set-up under ``tw.trace``, JAX tracing a
function to a jaxpr (the union of the spans; a moment also under a
later-begun lowering or compile is that one's). From the program's own record
(``setup_reduce.py``, README_setup.md); set-up ends where the window's
first driver call starts. ``None`` from a program that keeps no such
record, or where the trace cannot be paired with it."""

import setup_reduce


def read(trace, run):
    return setup_reduce.seconds(trace, "trace")
