"""Entry points: seconds from the first line of
``timewarp_tpu/__init__.py`` to the first ``tw.scenario`` or
``tw.engine.init``, less any compile-path span in them: the program's
own imports and the builder's. From the program's own record
(``setup_reduce.py``, README_setup.md); set-up ends where the window's
first driver call starts. ``None`` from a program that keeps no such
record, or where the trace cannot be paired with it."""

import setup_reduce


def read(trace, run):
    return setup_reduce.seconds(trace, "import")
