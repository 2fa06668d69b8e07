"""Drivers: of the device-idle milliseconds between one job's main
program and the next's, the part in which the host was inside
``tw.wait`` (the blocking read that ends the call: completion and the
readback's transfer), mean over the traced jobs. See
``idle_in_dispatch_ms`` for the record and the clock."""

import record_reduce


def read(trace, run):
    return record_reduce.owner_ms(trace, "wait")
