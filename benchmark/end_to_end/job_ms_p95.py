"""95th percentile of a job's wall time over all jobs of the window;
the sample count goes on a line of its own before the result."""

import statistics


def read(window):
    ms = [j["ms"] for j in window["jobs"]]
    print(f"job_ms_p95 over {len(ms)} jobs")
    if len(ms) < 20:
        return None
    return statistics.quantiles(ms, n=20, method="inclusive")[18]
