"""Set-up in the program's own record (ISSUE 51): the live spans
``tw.scenario``, ``tw.engine.init``, ``tw.init_state`` and JAX's own
compile events as ``tw.trace``, ``tw.lower``, ``tw.compile`` in
``obs.profiler.phases()``, beside the driver calls and not in them; a
call's ``compile_seconds`` and ``cache_misses``; and the pure functions
of ``benchmark/setup_reduce.py`` that cut set-up among its owners.

One engine for the file (ROADMAP D19): a 24-node ring on the edge
engine, compiled once.
"""

import functools
import os
import sys
import time

import pytest

import jax
import jax.numpy as jnp

from timewarp_tpu.interp.jax_engine.edge_engine import EdgeEngine
from timewarp_tpu.interp.jax_engine.sharded import ShardedEdgeEngine
from timewarp_tpu.models.token_ring import token_ring
from timewarp_tpu.net.delays import FixedDelay
from timewarp_tpu.obs import MetricsRegistry, profiler, validate_line
from timewarp_tpu.parallel.mesh import make_mesh

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                "benchmark"))
import setup_reduce as sr  # noqa: E402

COMPILE_PATH = ("tw.trace", "tw.lower", "tw.compile")
MS = 1_000_000


def _ring(n=24):
    sc = token_ring(n, n_tokens=4, think_us=2000, bootstrap_us=1000,
                    end_us=120_000, with_observer=False, mailbox_cap=8)
    return sc, FixedDelay(500)


def _since(mark):
    return [p for p in profiler.phases() if p[1] >= mark]


@functools.cache
def _first_two_calls():
    """The file's engine, built and run twice: ``(phases of the
    building, the two calls' records, their stats, phases of the
    calls)``."""
    mark = time.perf_counter_ns()
    eng = EdgeEngine(*_ring(), lint="off")
    st = eng.init_state()
    built = _since(mark)
    mark = time.perf_counter_ns()
    stats = []
    for _ in range(2):
        eng.run_quiet(4, st)
        stats.append(eng.last_run_stats)
    return built, profiler.calls()[-2:], stats, _since(mark)


# -- the listener ------------------------------------------------------------

def test_a_fresh_jit_leaves_its_three_spans_and_a_second_call_none():
    def fresh_for_this_test(x):
        return x * 3 + 1
    fn = jax.jit(fresh_for_this_test)
    mark = time.perf_counter_ns()
    fn(jnp.ones(4)).block_until_ready()
    mine = [p for p in _since(mark)
            if "fresh_for_this_test" in p[4].get("fun", "")]
    assert [p[0] for p in mine] == list(COMPILE_PATH)
    assert [p[4]["fun"] for p in mine] == [
        "fresh_for_this_test", "jit(fresh_for_this_test)",
        "jit(fresh_for_this_test)"]
    for (_, a0, a1, cause, _), (_, b0, _, _, _) in zip(mine, mine[1:]):
        assert a0 < a1 <= b0 and cause is None
    # the tests' cache is off: nothing was asked
    assert mine[-1][4]["cache"] == "none" and "run" not in mine[-1][4]
    # the `multiply` and the `add` traced inside it are part of its trace
    inside = [p for p in _since(mark) if p[0] == "tw.trace"
              and mine[0][1] < p[1] and p[2] < mine[0][2]]
    assert inside == []
    mark = time.perf_counter_ns()
    fn(jnp.ones(4)).block_until_ready()
    assert _since(mark) == []


def test_registering_twice_leaves_one_listener():
    profiler.listen()
    profiler.listen()
    event = "/jax/core/compile/backend_compile_duration"
    mark = time.perf_counter_ns()
    jax.monitoring.record_event("/jax/compilation_cache/cache_misses")
    jax.monitoring.record_scalar(event, time.time(), fun_name="jit(twice)")
    time.sleep(0.002)
    jax.monitoring.record_event_duration_secs(event, 0.001,
                                              fun_name="jit(twice)")
    (name, t0, t1, cause, attrs), = _since(mark)
    assert (name, cause, attrs) == (
        "tw.compile", None, {"fun": "jit(twice)", "cache": "miss"})
    assert t1 - t0 == MS
    # what the cache said is said of one compile, not of the next
    jax.monitoring.record_event_duration_secs(event, 0.0, fun_name="jit(b)")
    assert profiler.phases()[-1][4]["cache"] == "none"


def test_the_phases_are_bounded():
    assert len(profiler.phases()) <= profiler.MAX_PHASES
    assert profiler._phases.maxlen == profiler.MAX_PHASES


# -- a driver call's compile path --------------------------------------------

def test_a_compile_inside_a_call_carries_its_run_and_cause():
    _, (first, second), _, noted = _first_two_calls()
    # the record of the call is what it was: its own three spans
    for rec in (first, second):
        assert [s[0] for s in rec["spans"]] == [
            "tw.dispatch", "tw.wait", "tw.run_quiet"]
    mine = [p for p in noted if p[4].get("run") == first["run"]]
    driver = [p for p in mine if "_run_while" in p[4].get("fun", "")]
    assert [p[0] for p in driver] == list(COMPILE_PATH)
    assert {p[3] for p in driver} == {"tw.dispatch"}
    dispatch = first["spans"][0]
    assert all(dispatch[1] <= p[1] and p[2] <= dispatch[2] for p in driver)
    assert not [p for p in noted if p[4].get("run") == second["run"]]


def test_compile_seconds_in_the_first_call_and_none_in_the_second():
    _, (first, second), stats, noted = _first_two_calls()
    assert first["counts"] == stats[0] and second["counts"] == stats[1]
    mine = [(p[1], p[2]) for p in noted if p[4].get("run") == first["run"]]
    assert stats[0]["compile_seconds"] == sr.length(mine) / 1e9 > 0
    assert stats[0]["compile_seconds"] < stats[0]["wall_seconds"]
    assert stats[0]["compiles"] == 1 and stats[0]["cache_misses"] == 0
    assert stats[1]["compile_seconds"] == 0.0
    assert type(stats[1]["compile_seconds"]) is float
    assert (stats[1]["compiles"], stats[1]["cache_misses"]) == (0, 0)
    assert profiler.compile_account() == (0.0, 0)        # outside any call


def test_a_chunked_run_sums_both_and_run_summary_carries_them():
    eng = EdgeEngine.__new__(EdgeEngine)
    chunk = {"supersteps": 2, "wall_seconds": .5, "compiles": 1,
             "compile_seconds": .25, "cache_misses": 3}
    eng._stats_merge([chunk, {**chunk, "compiles": 0, "compile_seconds": 0.0,
                              "cache_misses": 0},
                      {"supersteps": 1, "wall_seconds": .1, "compiles": 0}])
    merged = eng.last_run_stats
    assert (merged["compile_seconds"], merged["cache_misses"]) == (.25, 3)
    reg = MetricsRegistry()
    reg.run_summary("a", merged)
    line = reg.lines[-1]
    assert (line["compile_seconds"], line["cache_misses"]) == (.25, 3)
    for bad in ({"compile_seconds": "0.25"}, {"compile_seconds": True},
                {"cache_misses": 1.5}):
        with pytest.raises(ValueError, match=next(iter(bad))):
            validate_line({**line, **bad})
    # an older archive's line has neither, and stays valid
    validate_line({k: v for k, v in line.items()
                   if k not in ("compile_seconds", "cache_misses")})


# -- the live spans ----------------------------------------------------------

def test_building_a_run_leaves_its_live_spans():
    built, _, _, _ = _first_two_calls()
    live = [p for p in built if p[0] in sr.LIVE]
    assert [(p[0], p[3]) for p in live] == [
        ("tw.scenario", None), ("tw.engine.init", None),
        ("tw.init_state", None)]
    scenario, init, state = live
    assert scenario[4] == {"model": "token_ring"}
    assert init[4] == state[4] == {"engine": "EdgeEngine", "n_nodes": 24}
    assert scenario[2] <= init[1] < init[2] <= state[1] < state[2]
    # what `init_state` compiled is noted under it
    under = [p for p in built if p[3] == "tw.init_state"]
    assert all(state[1] <= p[1] and p[2] <= state[2] for p in under)
    # and none of it is a record among the driver calls
    assert not [r for r in profiler.calls()
                for s in r["spans"] if s[0] in sr.LIVE]


def test_a_sharded_engine_is_one_engine_init_and_one_init_state():
    sc, link = _ring()
    mark = time.perf_counter_ns()
    eng = ShardedEdgeEngine(sc, link, make_mesh(4), lint="off")
    eng.init_state()
    live = [(p[0], p[4]) for p in _since(mark) if p[0] in sr.LIVE]
    attrs = {"engine": "ShardedEdgeEngine", "n_nodes": 24}
    assert live == [("tw.engine.init", attrs), ("tw.init_state", attrs)]


def test_the_process_began_before_the_package_did():
    assert profiler.process_start_ns() <= profiler.package_start_ns() \
        < time.perf_counter_ns()
    # the interpreter and the tests' own imports: seconds, not hours
    assert profiler.package_start_ns() - profiler.process_start_ns() \
        < 3600 * 1000 * MS


def test_the_listener_and_a_live_span_cost_microseconds():
    """Host-only and generous (a loaded test host); CHANGES.md has the
    measured numbers."""
    event = "/jax/core/compile/jaxpr_trace_duration"
    n = 2000
    t0 = time.perf_counter()
    for _ in range(n):
        jax.monitoring.record_scalar(event, 0.0, fun_name="f")
        jax.monitoring.record_event_duration_secs(event, 1e-6, fun_name="f")
    assert (time.perf_counter() - t0) / n < 200e-6
    t0 = time.perf_counter()
    for _ in range(n):
        with profiler.phase("tw.test.phase", engine="E", n_nodes=1):
            pass
    assert (time.perf_counter() - t0) / n < 500e-6


# -- benchmark/setup_reduce.py: pure functions over tuples --------------------

def _p(name, t0, t1, cause=None, **attrs):
    return (name, t0 * MS, t1 * MS, cause, attrs)


def _record(phases, calls=(), process=0, package=100):
    return {"process_start_ns": process * MS, "package_start_ns": package * MS,
            "phases": list(phases),
            "calls": [{"run": i + 1, "spans": ((
                "tw.run_quiet", t0 * MS, t1 * MS, None, {"run": i + 1}),)}
                for i, (t0, t1) in enumerate(calls)]}


def _nested():
    """A scenario, an engine whose constructor compiles a small
    program, an `init_state`, a first call that traces, lowers and
    fetches its driver, a warm-up call; set-up ends at 1000."""
    phases = [
        _p("tw.scenario", 200, 230, model="gossip"),
        _p("tw.trace", 250, 260, "tw.engine.init", fun="iota"),
        _p("tw.lower", 260, 270, "tw.engine.init", fun="jit(iota)"),
        _p("tw.compile", 270, 300, "tw.engine.init", fun="jit(iota)",
           cache="miss"),
        _p("tw.engine.init", 240, 340, engine="E", n_nodes=4),
        _p("tw.init_state", 340, 400, engine="E", n_nodes=4),
        # a lowering that begins inside the trace is the lowering's
        _p("tw.trace", 420, 600, "tw.dispatch", fun="_run_while", run=1),
        _p("tw.lower", 580, 700, "tw.dispatch", fun="jit(_run_while)",
           run=1),
        _p("tw.cache_fetch", 710, 790, "tw.dispatch", run=1),
        _p("tw.compile", 700, 800, "tw.dispatch", fun="jit(_run_while)",
           cache="hit", run=1),
        # after set-up's end: the window's, not counted
        _p("tw.compile", 1200, 1300, None, fun="jit(late)", cache="miss"),
    ]
    return _record(phases, calls=[(410, 850), (860, 900), (1000, 1040)])


def _case_nested():
    acc = sr.account(_nested(), 1000 * MS)
    ms = {k: v // MS for k, v in acc["parts_ns"].items()}
    assert ms == {"before_program": 100, "import": 100,
                  "engine": 30 + (100 - 50) + 60,
                  "trace": 10 + 160, "lower": 10 + 120,
                  "backend": 30 + 100,
                  "run": (440 - 380) + 40}
    assert acc["cache_fetch_ns"] == 80 * MS
    assert (acc["programs"], acc["cache_misses"]) == (2, 1)
    # 230-240, 400-410, 850-860, 900-1000
    assert acc["unowned_ns"] == 130 * MS
    assert sum(acc["parts_ns"].values()) + acc["unowned_ns"] \
        == acc["length_ns"] == 1000 * MS
    assert acc["by_cause"] == {
        "tw.engine.init": {"trace": 10 * MS, "lower": 10 * MS,
                           "backend": 30 * MS, "programs": 1, "misses": 1},
        "tw.dispatch": {"trace": 160 * MS, "lower": 120 * MS,
                        "backend": 100 * MS, "programs": 1, "misses": 0}}
    # the fetch has no name of its own: its compile's
    assert acc["by_program"] == {"iota": 50 * MS, "_run_while": 380 * MS}
    text = sr.lines(acc)
    assert "programs 2, cache misses 1" in text[0]
    assert text[1].startswith("set-up compile path under tw.dispatch: "
                              "trace 0.160 s, lower 0.120 s, backend 0.100")
    assert text[-1] == ("set-up longest programs: _run_while 0.380 s, "
                        "iota 0.050 s")


def _case_overlapping():
    # two threads compile at once: a moment under both is counted once,
    # and goes to the one that began last
    a = _p("tw.compile", 0, 100, fun="jit(a)", cache="miss")
    b = _p("tw.compile", 60, 160, fun="jit(b)", cache="miss")
    c = _p("tw.trace", 150, 200, fun="c")
    pieces = sr.newest_owner([a, b, c])
    assert [(t0 // MS, t1 // MS, s[4]["fun"]) for t0, t1, s in pieces] == [
        (0, 60, "jit(a)"), (60, 100, "jit(b)"), (100, 150, "jit(b)"),
        (150, 160, "c"), (160, 200, "c")]
    assert sum(t1 - t0 for t0, t1, _ in pieces) \
        == sr.length([(s[1], s[2]) for s in (a, b, c)]) == 200 * MS
    assert sr.union([(5, 9), (0, 3), (3, 4), (8, 12), (20, 20)]) \
        == [(0, 4), (5, 12)]
    assert sr.clip([(0, 10), (20, 30)], 5, 25) == [(5, 10), (20, 25)]
    assert sr.subtract([(0, 10), (20, 30)], [(2, 4), (8, 22)]) \
        == [(0, 2), (4, 8), (22, 30)]
    # the first layer that covers a moment owns it
    assert sr.partition([[(0, 10)], [(5, 20)], [(30, 50)]], 0, 40) \
        == ([10, 10, 10], 10)


def _case_self_time():
    # a span's self time is its length less what its children cover,
    # children that overlap each other counted once
    assert sr.self_ns((0, 100), [(10, 30), (20, 40), (90, 120)]) == 60
    assert sr.self_ns((0, 100), []) == 100
    assert sr.self_ns((0, 100), [(0, 100)]) == 0


def _case_empty():
    # a program that compiled nothing and built nothing before its
    # first call: its imports reach to set-up's end
    acc = sr.account(_record([]), 400 * MS)
    assert acc["parts_ns"] == {**dict.fromkeys(sr.PARTS, 0),
                               "before_program": 100 * MS,
                               "import": 300 * MS}
    assert (acc["unowned_ns"], acc["programs"], acc["cache_misses"]) \
        == (0, 0, 0)
    assert acc["by_cause"] == acc["by_program"] == {}
    assert sr.lines(acc)[-1] == "set-up longest programs: "


def _case_no_pairing(monkeypatch):
    # the trace's programs cannot be tied to the record's calls (or the
    # program keeps no record): the readers say nothing
    trace = object()
    monkeypatch.setattr(sr.record_reduce, "of_trace", lambda t: None)
    assert sr.of_trace(trace) is None
    assert sr.seconds(trace, "trace") is None
    assert sr.count(trace, "programs") is None
    monkeypatch.setattr(sr.record_reduce, "of_trace",
                        lambda t: {"shift": 0})
    monkeypatch.setattr(sr, "record", lambda: None)       # the parent
    assert sr.seconds(object(), "unowned") is None


@pytest.mark.parametrize("case", [
    _case_nested, _case_overlapping, _case_self_time, _case_empty,
    _case_no_pairing], ids=lambda c: c.__name__[6:])
def test_setup_reduce(case, monkeypatch):
    if case is _case_no_pairing:
        case(monkeypatch)
    else:
        case()


def test_the_readers_read_the_live_record(monkeypatch):
    """The file's own two calls as a run: the second is the window's
    first, so set-up ends where it starts."""
    _, (first, second), _, _ = _first_two_calls()
    calls = profiler.calls()
    at = next(i for i, r in enumerate(calls) if r["run"] == second["run"])
    monkeypatch.setattr(sr.record_reduce, "of_trace",
                        lambda t: {"shift": len(sr.record_reduce.driver_calls(
                            calls[:at]))})
    trace = object()
    acc = sr.of_trace(trace)
    assert sr.of_trace(trace) is acc                   # shared, printed once
    end = second["spans"][-1][1]
    assert acc["length_ns"] == end - profiler.process_start_ns()
    assert sum(acc["parts_ns"].values()) + acc["unowned_ns"] \
        == acc["length_ns"]
    assert acc["unowned_ns"] >= 0 and acc["programs"] >= 1
    assert sr.seconds(trace, "before_program") == (
        profiler.package_start_ns() - profiler.process_start_ns()) / 1e9
    assert sr.seconds(trace, "trace") > 0 and sr.seconds(trace, "run") > 0
    assert sr.count(trace, "cache_misses") == sum(
        p[0] == "tw.compile" and p[4]["cache"] == "miss" and p[2] <= end
        for p in profiler.phases())
