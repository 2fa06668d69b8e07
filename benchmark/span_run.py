"""A traced run that keeps its trace long enough to read the program's
own names from it: what ``run.py --trace 1`` prints, and beside it the
metrics of ``span_reduce.py`` (device time by superstep stage, the
owner of every idle gap between programs), the clock's bracket and the
three identities that hold the new numbers to the old.

    python benchmark/span_run.py --workload <cell> --seed <n> --seconds <s>

``run.py`` deletes the trace before a reader runs and hands the readers
a ``Trace`` alone, so it cannot read these yet (PERF.md, Open
questions, has the two lines it would take). Until then this is the
command PERF.md's stage and gap-owner numbers come from. It drives the
cell through ``run.py``'s own ``prepare``, ``set_up`` and ``drive``;
like ``run.py`` it refuses anything but the chips the cell asks for.

The compile cache keys on metadata here: by default it keys on the
program less its metadata, and an executable cached before the stages
were named would be served without them.
"""

import argparse
import importlib
import json
import os
import shutil
import sys

import run
import span_reduce
import trace_reduce

SPAN_METRICS = ("stage_next_event_us", "stage_deliver_us", "stage_fire_us",
                "stage_rebase_us", "stage_route_us", "stage_finish_us",
                "stage_unscoped_share", "gap_in_run_ms", "gap_in_client_ms")


def identities(trace, ctx, metrics):
    """The three sums that hold the new numbers to the old ones, each
    as ``(left, right)`` in the unit named."""
    out = {}
    steps = span_reduce.supersteps(ctx)
    busy_ns, window_ns = trace_reduce.busy_and_window(trace)
    acc = span_reduce.stages(trace, ctx)
    if acc is not None and steps:
        out["stages_sum_us = busy_us_a_superstep"] = (
            sum(acc.values()) / steps / 1e3, busy_ns / steps / 1e3)
    if metrics.get("gap_in_run_ms") is not None:
        out["gap_in_run_ms + gap_in_client_ms = sync_gap_ms"] = (
            metrics["gap_in_run_ms"] + metrics["gap_in_client_ms"],
            metrics["sync_gap_ms"])
    if metrics.get("loop_idle_us") is not None \
            and metrics.get("sync_gap_ms") is not None:
        out["loop_idle_us*supersteps + sync_gap_ms*jobs = idle_ms"] = (
            (metrics["loop_idle_us"] * steps / 1e3
             + metrics["sync_gap_ms"] * len(trace.jobs)),
            (window_ns - busy_ns) / 1e6)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    a = ap.parse_args(argv)
    try:
        cell, config, traffic, watch, devices, peaks = run.prepare(a.workload)
    except run.Refused as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    import jax
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    run.set_up(cell, traffic, abs(a.seed))
    compile_seconds = watch.seconds
    logdir = os.path.join(run.OUT, f"spans_{a.workload}_{a.seed}")
    shutil.rmtree(logdir, ignore_errors=True)
    os.makedirs(logdir)
    jax.profiler.start_trace(logdir)
    try:
        jobs, _ = run.drive(cell, min(a.seconds, float(
            traffic["trace_seconds"])), watch)
    finally:
        jax.profiler.stop_trace()
    path = trace_reduce.find_xplane(logdir)
    trace = trace_reduce.load(path)
    spans = span_reduce.load(path, trace)
    shutil.rmtree(logdir, ignore_errors=True)
    ctx = {"jobs": jobs, "compile_seconds": compile_seconds, "peaks": peaks,
           "facts": cell.facts(), "config": config, "traffic": traffic,
           "spans": spans}
    names = [n for n, _ in run.metrics_of(a.workload, "per_layer")]
    metrics = {n: importlib.import_module(f"layer_metrics.{n}").read(
        trace, ctx) for n in names + [m for m in SPAN_METRICS
                                      if m not in names]}
    lo, hi = span_reduce.clock(spans)
    acc = span_reduce.stages(trace, ctx) or {}
    steps = span_reduce.supersteps(ctx)
    unscoped = {}
    for (_, d, name), scope in zip(trace.ops[0], spans.scopes[0]):
        if span_reduce.stage_of(scope) == span_reduce.UNSCOPED:
            unscoped[name, scope] = unscoped.get((name, scope), 0) + d
    print(json.dumps({
        "failed": sum(1 for j in jobs if j["failed"]), "jobs": len(jobs),
        "supersteps": steps, "metrics": metrics,
        "clock_offset_ns": [lo, hi], "clock_slack_ms": (hi - lo) / 1e6,
        "scope_us_a_superstep": {k: v / steps / 1e3 for k, v in sorted(
            acc.items(), key=lambda kv: -kv[1])},
        "nested_scope_us_a_superstep": {
            k: v / steps / 1e3 for k, v in sorted(span_reduce.stage_ns(
                trace.ops[0], spans.scopes[0], depth=2).items())
            if "/" in k},
        "gap_owners_ms": span_reduce.gap_owners_ms(trace, ctx),
        "unscoped_ops": [[trace_reduce.short_name(n), scope, ns / 1e9]
                         for (n, scope), ns in sorted(
                             unscoped.items(), key=lambda kv: -kv[1])[:8]],
        "identities": identities(trace, ctx, metrics),
        "stats": cell.engine.last_run_stats,
        "breakdown": trace_reduce.breakdown(trace)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
