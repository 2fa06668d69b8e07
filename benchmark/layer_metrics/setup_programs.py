"""Entry points: the ``tw.compile`` spans of set-up: the executables
set-up builds or fetches, the drivers and the small programs around
them. From the program's own record
(``setup_reduce.py``, README_setup.md); set-up ends where the window's
first driver call starts. ``None`` from a program that keeps no such
record, or where the trace cannot be paired with it."""

import setup_reduce


def read(trace, run):
    return setup_reduce.count(trace, "programs")
