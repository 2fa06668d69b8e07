"""Median wall time of one job, from dispatch to the readback of its
counters."""

import statistics


def read(window):
    return statistics.median(j["ms"] for j in window["jobs"])
