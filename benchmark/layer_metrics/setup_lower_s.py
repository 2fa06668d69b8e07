"""Entry points: seconds of set-up under ``tw.lower``, a jaxpr lowered
to an MLIR module (the union; a moment under two phases goes to the one
that began last, so trace, lower and backend are disjoint).
From the program's own record
(``setup_reduce.py``, README_setup.md); set-up ends where the window's
first driver call starts. ``None`` from a program that keeps no such
record, or where the trace cannot be paired with it."""

import setup_reduce


def read(trace, run):
    return setup_reduce.seconds(trace, "lower")
