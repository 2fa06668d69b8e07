"""Entry points: the part of ``setup_backend_s``, in seconds, under
``tw.cache_fetch``: reading and deserialising executables the
persistent compile cache had. From the program's own record
(``setup_reduce.py``, README_setup.md); set-up ends where the window's
first driver call starts. ``None`` from a program that keeps no such
record, or where the trace cannot be paired with it."""

import setup_reduce


def read(trace, run):
    return setup_reduce.seconds(trace, "cache_fetch")
