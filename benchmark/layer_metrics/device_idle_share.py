"""Device: share of the traced window in which no operation ran on the
device (1 - union of the leaf operations' intervals / window), in
percent, averaged over the chips used."""

import trace_reduce


def read(trace, run):
    busy, window = trace_reduce.busy_and_window(trace)
    return 100.0 * (1.0 - busy / window)
