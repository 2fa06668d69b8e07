"""Device: idle microseconds a superstep inside the executions of the
main program (no operation running, no copy in flight): what
``device_idle_share`` holds less what ``sync_gap_ms`` holds."""

import span_reduce


def read(trace, run):
    steps = span_reduce.supersteps(run)
    if not steps:
        return None
    return span_reduce.loop_idle_ns(
        trace.ops[0], trace.asyncs[0], trace.modules) / steps / 1e3
