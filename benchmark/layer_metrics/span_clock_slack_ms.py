"""Drivers: width, in milliseconds, of the bracket that ties the clock
of the program's record to the device's (``record_reduce.bracket``:
every traced ``tw.dispatch`` began before its program started, every
``tw.wait`` ended after it ended). The record's spans are placed at the
bracket's middle, so a boundary between two owners of an idle gap may
lie half of this from where the ``idle_in_*_ms`` metrics put it.
``None`` from a program that keeps no record."""

import record_reduce


def read(trace, run):
    red = record_reduce.of_trace(trace)
    return None if red is None else red["slack_ms"]
