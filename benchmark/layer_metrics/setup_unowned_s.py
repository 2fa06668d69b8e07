"""Entry points: set-up's length less the seven durations that
partition it (``setup_before_program_s``, ``_import_s``, ``_engine_s``,
``_trace_s``, ``_lower_s``, ``_backend_s``, ``_run_s``): the builder's
own programs running, readbacks, ``gc.collect()``, ``start_trace``.
From the program's own record
(``setup_reduce.py``, README_setup.md); set-up ends where the window's
first driver call starts. ``None`` from a program that keeps no such
record, or where the trace cannot be paired with it."""

import setup_reduce


def read(trace, run):
    return setup_reduce.seconds(trace, "unowned")
