"""Drivers: of the device-idle milliseconds between one job's main
program and the next's, the part in which the host was in the driver's
own code (``tw.run_quiet`` or ``tw.run`` outside ``tw.dispatch`` and
``tw.wait``) or in ``tw.guard``, mean over the traced jobs. See
``idle_in_dispatch_ms`` for the record and the clock."""

import record_reduce


def read(trace, run):
    return record_reduce.owner_ms(trace, "driver")
