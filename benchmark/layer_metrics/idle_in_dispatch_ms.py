"""Drivers: of the device-idle milliseconds between one job's main
program and the next's (``sync_gap_ms``), the part in which the host
was inside ``tw.dispatch`` (argument handling and the enqueue of the
driver's program), mean over the traced jobs. The spans are the
program's own record of its driver calls (``record_reduce.py``), put
on the device's clock at the middle of the bracket its causal pairs
give; ``span_clock_slack_ms`` is the bracket's width. ``None`` from a
program that keeps no record."""

import record_reduce


def read(trace, run):
    return record_reduce.owner_ms(trace, "dispatch")
