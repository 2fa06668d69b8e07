"""Drivers: of the device-idle milliseconds between one job's main
program and the next's, the part in which the host was in no span of
the program: the caller's code (in the benchmark the builder's state
programs, its readback, its gates), mean over the traced jobs. See
``idle_in_dispatch_ms`` for the record and the clock."""

import record_reduce


def read(trace, run):
    return record_reduce.owner_ms(trace, "client")
