"""Entry points: seconds from the process's start (the kernel's start
time of the process, ``obs.profiler.process_start_ns``) to the first
line of ``timewarp_tpu/__init__.py``: the interpreter, ``run.py``'s
imports, ``import jax`` and reaching the chip, all of which
``run.py`` ``prepare`` does before the program is imported.
From the program's own record
(``setup_reduce.py``, README_setup.md); set-up ends where the window's
first driver call starts. ``None`` from a program that keeps no such
record, or where the trace cannot be paired with it."""

import setup_reduce


def read(trace, run):
    return setup_reduce.seconds(trace, "before_program")
