"""Drivers: programs the device executed (``XLA Modules`` events) over
the traced jobs: the driver's own and whatever the caller launches
around it. Each is a dispatch, and each boundary between two a chance
for the device to wait for the host."""


def read(trace, run):
    if not trace.jobs:
        return None
    return len(trace.modules) / len(trace.jobs)
