"""The eleven set-up metrics for each of the nine builders, at toy size
on XLA:CPU through ``run.py``'s test-only entry: the program's record
of a whole run (imports, scenario, engine, ``init_state``, the compile
path, the warm-up jobs) against a "trace" made of the window's own
calls, as ``test_record_reduce.py`` makes one (device clock = record
clock + a known offset). Every reader reads something, the eight
durations sum to set-up's length, nothing is negative. The pure
functions on hand-made tuples are held in tier-1
(``tests/test_setup_record.py``). Semantics only: nothing printed here
is a device number."""

import importlib
import os
import time

# four virtual devices for the three meshes, asked for before any test
# of the session builds the CPU backend (conftest.py here asks for none)
if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=4").strip()

import jax
import pytest

import record_reduce
import run
import setup_reduce
import toy
import toy_fleet
import toy_fleet_x4
import toy_observer
import toy_praos
import toy_steady
import toy_steady_x4
import toy_x4
import trace_reduce
from timewarp_tpu.obs import profiler

OFFSET = 5_000_000_000
SECONDS = ("setup_before_program_s", "setup_import_s", "setup_engine_s",
           "setup_trace_s", "setup_lower_s", "setup_backend_s",
           "setup_run_s", "setup_unowned_s")
OTHERS = ("setup_cache_fetch_s", "setup_cache_misses", "setup_programs")
#: the builder, its devices, and a window in seconds that holds two of
#: its jobs and more on a loaded host (a toy fleet's job takes 0.5-1.5 s)
BUILDERS = {"fused_ring": (toy.ring, 1, 1.0),
            "gossip_wave": (toy.wave, 1, 1.0),
            "gossip_fleet": (toy_fleet.fleet, 1, 4.0),
            "gossip_steady": (toy_steady.rounds, 1, 1.0),
            "praos_slots": (toy_praos.slots, 1, 1.0),
            "observer_ring": (toy_observer.observer, 1, 1.0),
            "sharded_ring": (toy_x4.dense, 4, 1.0),
            "gossip_fleet_x4": (toy_fleet_x4.fleet, 4, 4.0),
            "gossip_steady_x4": (toy_steady_x4.rounds, 4, 1.0)}


def _read(trace):
    return {name: importlib.import_module(f"layer_metrics.{name}").read(
        trace, {}) for name in SECONDS + OTHERS}


def _trace_of(window):
    """Each call's program "ran" from 40 % into its first dispatch to
    50 us before its last wait's end (a tenth of the wait where that is
    less): a bracket as tight as the chip's, so that the jitter of a
    few long toy jobs tells the one pairing from the others."""
    modules, ops = [], []
    for i, (d0, w1) in record_reduce.driver_calls(window):
        rec = window[i]
        d1 = min(s[2] for s in rec["spans"] if s[0] == "tw.dispatch")
        w0 = max(s[1] for s in rec["spans"] if s[0] == "tw.wait")
        start = d0 + (d1 - d0) * 4 // 10 + OFFSET
        end = w1 - min((w1 - w0) // 10, 50_000) + OFFSET
        modules.append((start, end - start, "jit__run_while(1)"))
        ops.append((start, end - start, "%fusion.1 = fusion()"))
    return trace_reduce.Trace(ops=[ops], asyncs=[[]], modules=modules,
                              jobs=[])


@pytest.mark.parametrize("builder", BUILDERS)
def test_every_builder_reports_the_eleven(builder, tmp_path, capsys,
                                          monkeypatch):
    make, chips, seconds = BUILDERS[builder]
    if len(jax.devices()) < chips:
        pytest.skip("the CPU backend was built with fewer than four "
                    "devices before this file asked for them")
    # one cell a process on the chip; here the session's earlier cells
    # left their calls in the record, and a pairing must not find them
    began = time.perf_counter_ns()
    monkeypatch.setattr(record_reduce, "records", lambda: [
        r for r in profiler.calls() if r["spans"][-1][1] >= began])
    marks = {}
    drive = run.drive

    def marked(*args):
        time.sleep(0.3)          # where a profile would start
        marks["began"] = time.perf_counter_ns()
        try:
            return drive(*args)
        finally:
            marks["ended"] = time.perf_counter_ns()
    monkeypatch.setattr(run, "drive", marked)
    rc = run.run_cell(make(tmp_path), 3_000_000_019, seconds, False,
                      on_chip=False, extra_dir=str(tmp_path))
    assert rc == 0, capsys.readouterr().out
    window = [r for r in record_reduce.records() if r["run"] is not None
              and marks["began"] <= r["spans"][-1][1]
              and r["spans"][-1][2] <= marks["ended"]]
    trace = _trace_of(window)
    assert len(trace.modules) >= 2, "a window of one job pairs with nothing"

    got = _read(trace)
    out = capsys.readouterr().out
    assert all(v is not None for v in got.values()), got
    assert all(v >= 0 for v in got.values()), got
    # set-up ends where the window's first driver call starts
    acc = setup_reduce.of_trace(trace)
    begins = min(s[1] for s in window[0]["spans"])
    assert acc["length_ns"] == begins - profiler.process_start_ns()
    assert sum(got[name] for name in SECONDS) == pytest.approx(
        acc["length_ns"] / 1e9, abs=1e-6)
    # a builder makes its scenario, its engine and its state, compiles
    # its programs with no cache to ask, and runs its warm-up jobs
    assert got["setup_engine_s"] > 0 and got["setup_run_s"] > 0
    assert got["setup_trace_s"] > 0 and got["setup_backend_s"] > 0
    assert got["setup_programs"] >= 1 and got["setup_cache_misses"] == 0
    assert got["setup_cache_fetch_s"] == 0.0
    assert "tw.dispatch" in acc["by_cause"]
    # the account is printed once, before the result line would be
    assert out.count("set-up by phase: ") == 1
    assert out.count("set-up longest programs: ") == 1
    assert "set-up compile path under tw.dispatch: " in out


def test_a_program_without_the_record_reads_nothing(monkeypatch):
    """The parent of PR 51 keeps its driver calls and no phases."""
    monkeypatch.delattr(profiler, "phases")
    trace = trace_reduce.Trace(ops=[[]], asyncs=[[]], modules=[], jobs=[])
    monkeypatch.setattr(record_reduce, "of_trace", lambda t: {"shift": 0})
    assert set(_read(trace).values()) == {None}
