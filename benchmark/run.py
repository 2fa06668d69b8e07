"""The benchmark's one command: one process, one cell, one result line.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell is found by name, nothing is named
here: ``workloads/<cell>.json`` (the traffic mix and the configuration
it runs on), ``configs/<config>.json`` (sizes, guarantees, the builder
and the reference by name), ``builders/<builder>.py`` (engine, state
from the seed, one job, its gates, the comparison), ``reference/
<reference>.py`` (the plain reference), ``end_to_end/<metric>.py`` and
``layer_metrics/<metric>.py`` (one reader each). ``BENCHMARK.json``
says which metrics a cell reports. README.md has the page on each.

A run: refuse anything but the chips the cell asks for; build the cell
and warm up every program it will use (set-up, with the compile time
JAX reports); drive jobs one after another, closed loop, one client,
until ``--seconds`` have passed (a job begun inside the window is
finished and counted, and the window ends with it); then, outside both
set-up and window, hold what the jobs produced to the plain reference.
With ``--trace 1`` a slice of the window runs under ``jax.profiler`` and
the per-layer metrics are read from the trace instead. A compile inside
the window is an error, not a number.
"""

import time

_T0 = time.perf_counter()        # process start, as near as Python gets

import argparse
import gc
import importlib
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT = os.path.join(HERE, "out")

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class CompileWatch:
    """Seconds JAX spends tracing, lowering and compiling (or fetching
    from the persistent cache), and how many such events fall inside
    the measured window (there must be none)."""

    def __init__(self):
        self.seconds = 0.0
        self.in_window = False
        self.window_events = []

    def __call__(self, event, secs, **_):
        if event in _COMPILE_EVENTS:
            self.seconds += secs
            if self.in_window:
                self.window_events.append((event, secs))


def _load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(name, extra_dir=None):
    """``(traffic, config)`` of the cell ``name``: ``workloads/<name>
    .json`` and the ``configs/<config>.json`` it names, looked for
    under ``extra_dir`` first where one is given (the tests' cells)."""
    def find(kind, stem):
        for base in filter(None, (extra_dir, HERE)):
            path = os.path.join(base, kind, stem + ".json")
            if os.path.exists(path):
                return _load_json(path)
        raise SystemExit(f"benchmark: no {kind}/{stem}.json")
    traffic = find("workloads", name)
    return traffic, find("configs", traffic["config"])


def metrics_of(cell, section):
    """Names and units of the ``section`` metrics that ``cell``
    reports, from BENCHMARK.json: those with no ``workloads`` key and
    those that list the cell. A cell BENCHMARK.json does not know is
    offered every metric (a reader that finds nothing returns None)."""
    bench = _load_json(ROOT, "BENCHMARK.json")
    known = any(w["name"] == cell for w in bench["workloads"])
    return [(m["name"], m["unit"]) for m in bench[section]
            if not known or cell in m.get("workloads", [cell])]


class Refused(Exception):
    """The run may not start: wrong device, too few chips."""


def prepare(workload, *, on_chip=True, extra_dir=None):
    """Everything up to the cell object, built and not yet set up:
    ``(cell, config, traffic, watch, devices, peaks)``. Puts the
    benchmark's directories on ``sys.path``, points the persistent
    compile cache at its fixed place, and refuses (``Refused``) any
    device but the chips the cell asks for."""
    for p in (ROOT, HERE):
        if p not in sys.path:
            sys.path.insert(0, p)
    traffic, config = load_cell(workload, extra_dir)
    chips = int(traffic["chips"])

    import jax
    devices = jax.devices()
    peaks = None
    if on_chip:
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            # fixed inside the checkout: the path is part of the key
            jax.config.update("jax_compilation_cache_dir",
                              os.path.join(ROOT, ".jax_cache"))
        # keep every program, the sub-second ones too: a warm run
        # should find all of them
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        if devices[0].platform != "tpu":
            raise Refused(f"JAX found {devices[0].platform!r}, not a TPU: "
                          "nothing is timed")
        if len(devices) < chips:
            raise Refused(f"the cell asks for {chips} chips, JAX found "
                          f"{len(devices)}")
        import kernel_costs
        peaks = kernel_costs.device_peaks(devices[0].device_kind)
    watch = CompileWatch()
    jax.monitoring.register_event_duration_secs_listener(watch)
    builder = importlib.import_module(f"builders.{config['builder']}")
    cell = builder.Cell(config, traffic, interpret=not on_chip)
    return cell, config, traffic, watch, devices[:chips], peaks


def set_up(cell, traffic, seed):
    """The cell's state from the seed and its first job, which compiles
    every program a job uses; then the rest of the traffic's
    ``warm_up_jobs``, because the first jobs after a compile or a cache
    load run up to a tenth slower than the steady ones (PERF.md, PR 23)
    and would otherwise open the window. Ends with a full garbage
    collection, so that none falls into the window."""
    warm = [cell.set_up(seed)]
    warm += [cell.job(0) for _ in range(int(traffic["warm_up_jobs"]) - 1)]
    for res in warm:
        if res["failed"]:
            raise SystemExit(f"benchmark: a warm-up job failed its gates: "
                             f"{res['failed']}")
    # tracing a driver leaves millions of objects on the heap, and a
    # full collection over them takes tenths of a second: collect now,
    # and keep what set-up built out of the collector's later passes,
    # so that none falls into the window
    gc.collect()
    gc.freeze()


def drive(cell, seconds, watch):
    """The window: jobs one after another, closed loop, one client,
    each timed from its dispatch to the readback that ends it, until
    ``seconds`` have passed; a job begun inside the window is finished
    and counted, and the window ends with it. Returns the jobs and the
    window's wall seconds. A compile inside the window is ``Refused``."""
    import jax
    import trace_reduce
    jobs = []
    watch.in_window = True
    t0 = now = time.perf_counter()
    while now - t0 < seconds:
        with jax.profiler.TraceAnnotation(trace_reduce.JOB_SPAN):
            res = cell.job(len(jobs) + 1)
        t = time.perf_counter()
        res["ms"] = (t - now) * 1e3
        jobs.append(res)
        now = t
    watch.in_window = False
    if watch.window_events:
        raise Refused(f"{len(watch.window_events)} compile events inside "
                      f"the window: {watch.window_events[:4]}")
    return jobs, now - t0


def judge(rows, failed_jobs):
    """Print each number compared beside its limit (and the jobs that
    failed their gates); true if no job failed and no number passes
    its limit."""
    for j in failed_jobs[:8]:
        print(f"failed job: {j['failed']}")
    correct = not failed_jobs
    for name, value, limit in rows:
        print(f"compared {name}: {value} (limit "
              f"{'none, not compared' if limit is None else limit})")
        correct = correct and (limit is None or value <= limit)
    return correct


def run_cell(workload, seed, seconds, trace, *, on_chip=True,
             extra_dir=None):
    """Run one cell and print its result line; returns the exit code.
    ``on_chip=False`` is the rehearsal's entry (``tests/``): it skips
    the refusal of a non-TPU backend and asks kernels for the Pallas
    interpreter. Nothing it prints is a device number."""
    try:
        return _run_cell(workload, seed, seconds, trace, on_chip, extra_dir)
    except Refused as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2


def _run_cell(workload, seed, seconds, trace, on_chip, extra_dir):
    cell, config, traffic, watch, devices, peaks = prepare(
        workload, on_chip=on_chip, extra_dir=extra_dir)
    import jax
    import trace_reduce

    # -- set-up ---------------------------------------------------------
    set_up(cell, traffic, seed)
    compile_seconds = watch.seconds
    if trace:
        logdir = os.path.join(OUT, f"trace_{workload}_{seed}")
        shutil.rmtree(logdir, ignore_errors=True)
        os.makedirs(logdir)
        jax.profiler.start_trace(logdir)
        seconds = min(seconds, float(traffic["trace_seconds"]))
    set_up_seconds = time.perf_counter() - _T0

    # -- the window, then what it produced against the reference ----------
    try:
        jobs, window_s = drive(cell, seconds, watch)
    finally:
        if trace:
            jax.profiler.stop_trace()
    failed = [j for j in jobs if j["failed"]]
    reference = importlib.import_module(f"reference.{config['reference']}")
    t_ref = time.perf_counter()
    correct = judge(cell.compare(reference), failed)
    print(f"reference and comparison: {time.perf_counter() - t_ref:.2f} s, "
          "outside set-up and window")
    print(f"jobs: {len(jobs)} in {window_s:.3f} s; set-up "
          f"{set_up_seconds:.2f} s of which compile {compile_seconds:.2f} s")
    ms = sorted(j["ms"] for j in jobs)
    print("job ms, min / quartiles / max: " + " / ".join(
        f"{ms[int(q * (len(ms) - 1))]:.2f}" for q in (0, .25, .5, .75, 1))
        + f"; supersteps a job {min(j['supersteps'] for j in jobs)}"
        f"-{max(j['supersteps'] for j in jobs)}")

    # -- the result line ----------------------------------------------------
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": max(
                  (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                  for d in devices)}
    result = {"correct": bool(correct), "attempted": len(jobs),
              "failed": len(failed), "metrics": {}, "device": device}
    if trace:
        tr = trace_reduce.load(trace_reduce.find_xplane(logdir))
        shutil.rmtree(logdir, ignore_errors=True)
        package, section = "layer_metrics", "per_layer"
        args = (tr, {"jobs": jobs, "compile_seconds": compile_seconds,
                     "peaks": peaks, "facts": cell.facts(),
                     "config": config, "traffic": traffic})
        busy_ns, window_ns = trace_reduce.busy_and_window(tr)
        device["busy_s"] = busy_ns / 1e9
        device["window_s"] = window_ns / 1e9
        result["breakdown"] = trace_reduce.breakdown(tr)
    else:
        package, section = "end_to_end", "end_to_end"
        args = ({"jobs": [j for j in jobs if not j["failed"]],
                 "window_s": window_s, "set_up_seconds": set_up_seconds},)
    for name, unit in metrics_of(workload, section):
        value = importlib.import_module(f"{package}.{name}").read(*args)
        if value is not None:
            result["metrics"][name] = {"value": value, "unit": unit}
    print(json.dumps(result))
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    return run_cell(a.workload, abs(a.seed), a.seconds, bool(a.trace))


if __name__ == "__main__":
    sys.exit(main())
