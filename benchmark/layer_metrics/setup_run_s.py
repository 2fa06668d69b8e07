"""Entry points: self time, in seconds, of the driver calls before the
window (``tw.run``, ``tw.run_quiet`` less the compile-path and live
spans inside them): the first job's and the warm-up jobs' dispatch,
device time and wait. From the program's own record
(``setup_reduce.py``, README_setup.md); set-up ends where the window's
first driver call starts. ``None`` from a program that keeps no such
record, or where the trace cannot be paired with it."""

import setup_reduce


def read(trace, run):
    return setup_reduce.seconds(trace, "run")
