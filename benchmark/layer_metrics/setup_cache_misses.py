"""Entry points: the ``tw.compile`` spans of set-up whose ``cache`` is
``miss``: programs compiled because the persistent cache did not have
them. 0 says the run was warm. From the program's own record
(``setup_reduce.py``, README_setup.md); set-up ends where the window's
first driver call starts. ``None`` from a program that keeps no such
record, or where the trace cannot be paired with it."""

import setup_reduce


def read(trace, run):
    return setup_reduce.count(trace, "cache_misses")
