"""Drivers: of the device-idle milliseconds between one job's main
program and the next's (``sync_gap_ms``), the part in which the host
was inside a ``tw.`` span of the program (``tw.dispatch``, ``tw.wait``,
``tw.guard``, or the driver's own code between them), mean over the
traced jobs. Host spans are put on the device's clock at the middle of
``span_reduce.clock_bracket``: the bracket's width is the uncertainty."""

import span_reduce


def read(trace, run):
    owners = span_reduce.gap_owners_ms(trace, run)
    if owners is None:
        return None
    return sum(v for k, v in owners.items() if k != span_reduce.CLIENT)
