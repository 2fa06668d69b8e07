"""Entry points: self time, in seconds, of the live spans
``tw.scenario``, ``tw.engine.init`` and ``tw.init_state`` before the
window (their union, less the compile-path spans they cover): a
scenario's tables, numpy, device puts, the launches of
``init_state``. From the program's own record
(``setup_reduce.py``, README_setup.md); set-up ends where the window's
first driver call starts. ``None`` from a program that keeps no such
record, or where the trace cannot be paired with it."""

import setup_reduce


def read(trace, run):
    return setup_reduce.seconds(trace, "engine")
