"""Drivers: device-idle milliseconds from the end of one job's main
program to the start of the next job's (the host's readback, gates and
dispatch), mean over the traced jobs."""

import trace_reduce


def read(trace, run):
    gaps = trace_reduce.gaps_between_jobs(
        trace.modules, trace.ops[0] + trace.asyncs[0])
    if not gaps:
        return None
    return sum(gaps) / len(gaps) / 1e6
