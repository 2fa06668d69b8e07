"""Drivers: of the device-idle milliseconds between one job's main
program and the next's (``sync_gap_ms``), the part in which the host
was in no ``tw.`` span: the caller's code (in the benchmark the
builder's state program, its readback, its gates), mean over the traced
jobs. See ``gap_in_run_ms`` for the clock."""

import span_reduce


def read(trace, run):
    owners = span_reduce.gap_owners_ms(trace, run)
    if owners is None:
        return None
    return owners.get(span_reduce.CLIENT, 0.0)
