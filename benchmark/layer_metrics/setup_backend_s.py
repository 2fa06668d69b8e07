"""Entry points: seconds of set-up under ``tw.compile``: the backend's
compile of a program, or the persistent cache's answer in its place
(``setup_cache_fetch_s`` is that part). From the program's own record
(``setup_reduce.py``, README_setup.md); set-up ends where the window's
first driver call starts. ``None`` from a program that keeps no such
record, or where the trace cannot be paired with it."""

import setup_reduce


def read(trace, run):
    return setup_reduce.seconds(trace, "backend")
