"""Sharding: the fullest bucket of the traced jobs' calls over the
bucket's capacity, in percent (``last_run_stats`` ``bucket_fill_peak``,
counted before the cut at ``bucket_cap``, so over 100 says by how much
the capacity was short; the jobs' gates have failed by then). The rest
of a bucket is padding that is exchanged, sorted and inserted all the
same. Nothing to read from a program that does not count it."""

import steady_x4_reduce


def read(trace, run):
    peaks = steady_x4_reduce.counted(run, "bucket_fill_peak")
    cap = run["facts"].get("bucket_cap")
    if peaks is None or not cap:
        return None
    return 100.0 * max(peaks) / cap
