"""One record a driver call, kept by the program (ISSUE 35): every
driver call leaves its spans and its counts in ``obs.profiler.calls()``
(one ``run`` number a call, process-wide; bounded); the record's clock
and a profile's differ by one constant over a session; the routing
stage's counts every ``JaxEngine`` loop carries beside its state
(``rung_lanes``, ``sender_lanes``, ``rung_steps``) equal the sums of
the telemetry's per-superstep rows, solo and fleet, and change no state
bit; ``benchmark/record_reduce.py`` pairs the record with a trace's
main programs and cuts the idle gaps by owner.

The staging's counts and the sharded engines' are
tests/test_record_staging_counts.py.

(Named test_zz* to sort after the whole existing suite.)
"""

import glob
import hashlib
import os
import statistics
import sys
import time

import numpy as np
import pytest

import jax

from record_laws import FLEET, N, _steady
from timewarp_tpu.interp.jax_engine.edge_engine import EdgeEngine
from timewarp_tpu.interp.jax_engine.engine import JaxEngine
from timewarp_tpu.interp.jax_engine.fused_ring import FusedRingEngine
from timewarp_tpu.models.gossip import gossip
from timewarp_tpu.models.token_ring import token_ring
from timewarp_tpu.net.delays import FixedDelay, Quantize, UniformDelay
from timewarp_tpu.obs import MetricsRegistry, profiler, validate_line
from timewarp_tpu.obs.profiler import profile_session, span
from timewarp_tpu.trace.events import assert_states_equal

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                "benchmark"))
import record_reduce as rr  # noqa: E402

RUNGS = JaxEngine._sender_rungs(N)


def _gossip(n=64):
    sc = gossip(n, fanout=3, burst=True, end_us=150_000, mailbox_cap=16)
    return sc, Quantize(UniformDelay(3000, 9000), 1000)


def _ring(n=16):
    sc = token_ring(n, n_tokens=4, think_us=2000, bootstrap_us=1000,
                    end_us=120_000, with_observer=False, mailbox_cap=8)
    return sc, FixedDelay(500)


def _names(rec):
    return [s[0] for s in rec["spans"]]




def test_a_driver_call_leaves_one_record():
    eng = JaxEngine(*_gossip(), window="auto", lint="off")
    before = len(profiler.calls())
    eng.run_quiet(3)
    recs = profiler.calls()
    assert len(recs) == min(before + 1, profiler.MAX_CALLS)
    rec = recs[-1]
    # spans close inside out: the call's own span is the last noted
    assert _names(rec) == ["tw.dispatch", "tw.wait", "tw.run_quiet"]
    dispatch, wait, quiet = rec["spans"]
    assert quiet[1] <= dispatch[1] <= dispatch[2] <= wait[1] <= wait[2] \
        <= quiet[2]
    # one `run` for all, `cause` as the profile's stats have it
    # (test_driver_spans_carry_run_and_cause)
    assert {s[4]["run"] for s in rec["spans"]} == {rec["run"]}
    assert [s[3] for s in rec["spans"]] == ["tw.run_quiet"] * 2 + [None]
    assert (rec["engine"], rec["n_nodes"]) == ("JaxEngine", 64)
    assert rec["counts"] == eng.last_run_stats
    assert (rec["counts"]["dispatches"], rec["counts"]["readbacks"]) == (1, 1)
    # plain tuples and dicts, copied: the caller's to keep
    rec["counts"]["supersteps"] = -1
    assert profiler.calls()[-1]["counts"]["supersteps"] == 3


def test_two_engines_never_share_a_run():
    a = JaxEngine(*_gossip(), window="auto", lint="off")
    b = EdgeEngine(*_ring(), lint="off")
    a.run_quiet(2)
    b.run(2)
    a.run(2)
    runs = [r["run"] for r in profiler.calls()[-3:]]
    assert runs == [runs[0], runs[0] + 1, runs[0] + 2]
    assert [r["engine"] for r in profiler.calls()[-3:]] == \
        ["JaxEngine", "EdgeEngine", "JaxEngine"]


def test_a_guard_adds_its_span_to_the_record():
    eng = JaxEngine(*_gossip(), window="auto", lint="off", verify="guard")
    eng.run_quiet(5)
    rec = profiler.calls()[-1]
    assert _names(rec) == ["tw.dispatch", "tw.wait", "tw.guard",
                           "tw.run_quiet"]
    assert rec["counts"]["readbacks"] == 2


def test_a_span_under_no_call_is_a_record_of_its_own():
    eng = JaxEngine(*_gossip(), window="auto", lint="off")
    with span("tw.sweep.bucket", bucket=7):
        eng.run(3)
    run_, bucket = profiler.calls()[-2:]
    assert _names(run_)[-1] == "tw.run"
    assert run_["spans"][-1][3] == "tw.sweep.bucket"     # its cause
    assert (bucket["run"], bucket["counts"]) == (None, {})
    (name, t0, t1, cause, attrs), = bucket["spans"]
    assert (name, cause, attrs) == ("tw.sweep.bucket", None, {"bucket": 7})
    assert t0 <= run_["spans"][-1][1] and run_["spans"][-1][2] <= t1


@pytest.mark.parametrize("which", ["edge", "ring"])
def test_engines_with_no_ladder_record_spans_and_the_counts_they_have(which):
    if which == "edge":
        eng = EdgeEngine(*_ring(), lint="off")
    else:
        sc = token_ring(8192, n_tokens=8192, think_us=0, bootstrap_us=1000,
                        end_us=1 << 40, with_observer=False, mailbox_cap=4)
        eng = FusedRingEngine(sc, FixedDelay(500), cap=2, interpret=True)
    eng.run_quiet(4)
    rec = profiler.calls()[-1]
    assert _names(rec) == ["tw.dispatch", "tw.wait", "tw.run_quiet"]
    assert rec["counts"]["supersteps"] == 4
    assert "rung_lanes" not in rec["counts"]


def test_the_record_is_bounded():
    for _ in range(profiler.MAX_CALLS + 5):
        with span("tw.test.filler"):
            pass
    recs = profiler.calls()
    assert len(recs) == profiler.MAX_CALLS
    assert {_names(r)[0] for r in recs} == {"tw.test.filler"}


def test_the_record_costs_microseconds_a_call():
    """Host-only: three spans and a record, as a driver call opens
    them. Generous (a loaded test host); PERF.md has the measured
    number. One call is made before the clock starts: a process's
    first pays a lazy import (it read 1.69 ms a call once: ROADMAP
    D27)."""
    def a_call():
        with profiler.call("tw.test.call") as rec:
            with span("tw.dispatch", run=rec["run"]):
                pass
            with span("tw.wait", run=rec["run"]):
                pass
    a_call()
    t0 = time.perf_counter()
    for _ in range(200):
        a_call()
    assert (time.perf_counter() - t0) / 200 < 500e-6




def test_the_records_clock_and_the_profiles_differ_by_a_constant(tmp_path):
    """What ``record_reduce`` rests on: ``jax.profiler`` times a
    session's events from the session's start, so a record's span and
    the same span's ``TraceAnnotation`` differ by one number all
    session long. Held by quartiles (a preempted thread between the two
    clock reads is an outlier, not a drift)."""
    from jax.profiler import ProfileData
    with profile_session(str(tmp_path)):
        for i in range(48):
            with span("tw.test.tick", i=i):
                time.sleep(0.002)
    ticks = [r["spans"][0] for r in profiler.calls()[-48:]]
    assert [s[4]["i"] for s in ticks] == list(range(48))
    path, = glob.glob(f"{tmp_path}/plugins/profile/*/*.xplane.pb")
    seen = sorted(
        (int(e.start_ns), int(e.duration_ns)) for plane in
        ProfileData.from_file(path).planes if plane.name.startswith(
            "/host:CPU") for line in plane.lines for e in line.events
        if e.name == "tw.test.tick")
    assert len(seen) == 48
    offsets = [t0 - s for (s, _), (_, t0, _, _, _) in zip(seen, ticks)]
    q1, _, q3 = statistics.quantiles(offsets, n=4)
    assert q3 - q1 < 50_000, (q1, q3)
    # and no drift from the session's first half to its second
    assert abs(statistics.median(offsets[:24])
               - statistics.median(offsets[24:])) < 50_000
    # the spans themselves are as long on both clocks
    lengths = [(t1 - t0) - d for (_, d), (_, t0, t1, _, _)
               in zip(seen, ticks)]
    assert abs(statistics.median(lengths)) < 50_000




#: sha256 over the final state's leaves after ``run_quiet(60)``, as
#: the parent commit (5b8bdb1, before any loop carried a count) left it
PARENT_DIGEST = {
    "solo": "21575f0bde9ca92fdf01cf94a1c458b4c7b6cc4274a29afcccbe20c68790c149",
    "fleet": "bb84124808af3235f5bcf97daa5791b512259e8ecebe3d67a3c2f15e74c5041f",
}


def _digest(state) -> str:
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(state):
        a = np.ascontiguousarray(np.asarray(leaf))
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("which", ["solo", "fleet"])
def test_quiet_counts_equal_the_telemetrys_sums(which):
    sc, link = _steady()
    batch = FLEET if which == "fleet" else None
    traced = JaxEngine(sc, link, window="auto", telemetry="counters",
                       batch=batch)
    st0 = traced.init_state()
    fin_run, _ = traced.run(60, st0)
    frames = traced.last_run_telemetry
    frames = frames if isinstance(frames, list) else [frames]
    # one rung for all the worlds of an iteration, chosen for the
    # busiest world's senders
    rung = np.max([f.data["rung"] for f in frames], axis=0)
    senders = np.max([f.data["active_senders"] for f in frames], axis=0)
    assert all((f.data["rung"] == rung).all() for f in frames)
    want = {"rung_lanes": int(rung.sum()),
            "sender_lanes": int(senders.sum()),
            "rung_steps": [int((rung == r).sum()) for r in RUNGS]}
    assert len(rung) == 60 and len(set(rung.tolist())) > 1   # a ramp
    assert want["rung_lanes"] > want["sender_lanes"] > 0

    def counts(eng):
        return {k: eng.last_run_stats[k] for k in want}
    assert counts(traced) == want
    quiet = JaxEngine(sc, link, window="auto", batch=batch)
    fin = quiet.run_quiet(60, st0)
    assert counts(quiet) == want
    assert profiler.calls()[-1]["counts"] == quiet.last_run_stats
    assert (quiet.last_run_stats["dispatches"],
            quiet.last_run_stats["readbacks"]) == (1, 1)
    assert_states_equal(fin_run, fin, "run against run_quiet")
    assert _digest(fin) == PARENT_DIGEST[which]


def test_counts_stop_where_a_solo_scan_is_quiet():
    """A traced scan runs on to its padded length; the iterations after
    the last event count nothing (a fleet's:
    test_rung_lanes_stops_where_the_fleet_is_quiet)."""
    sc = gossip(N, fanout=4, think_us=700, burst=True, end_us=60_000,
                mailbox_cap=16)
    link = Quantize(UniformDelay(3_000, 9_000), 1_000)
    eng = JaxEngine(sc, link, window=3_000, telemetry="counters")
    eng.run(64)
    st = eng.last_run_stats
    assert st["supersteps"] < 64 and sum(st["rung_steps"]) == st["supersteps"]
    assert st["rung_lanes"] == int(eng.last_run_telemetry.data["rung"].sum())
    quiet = JaxEngine(sc, link, window=3_000)
    quiet.run_quiet(64)
    assert quiet.last_run_stats["rung_lanes"] == st["rung_lanes"]
    assert quiet.last_run_stats["rung_steps"] == st["rung_steps"]


def test_routing_without_the_ladder_counts_its_full_width_in_one_bin():
    sc, link = _steady(512)
    eager = JaxEngine(sc, link)              # window 1, one slot: eager
    assert not eager._adaptive_regime()
    eager.run_quiet(10)
    st = eager.last_run_stats
    assert (st["rung_lanes"], st["sender_lanes"], st["rung_steps"]) == \
        (10 * 512, 10 * 512, [10])


def test_chunked_fleet_says_how_wide_it_routed():
    """``_stats_merge`` keeps what it dropped (ROADMAP D3)."""
    sc, link = _steady()
    eng = JaxEngine(sc, link, window="auto", batch=FLEET)
    whole = JaxEngine(sc, link, window="auto", batch=FLEET)
    whole.run_quiet(40)
    st = eng.init_state()
    chunks = []
    for _ in range(4):
        st, _ = eng.run(10, st)
        chunks.append(eng.last_run_stats)
    merged = eng._stats_merge(chunks)
    for key in ("rung_lanes", "sender_lanes", "rung_steps",
                "fleet_iterations", "world_supersteps", "supersteps",
                "dense_stage_steps", "wide_tail_steps"):
        assert merged[key] == whole.last_run_stats[key], key




def test_run_summary_writes_the_calls_counts():
    sc, link = _steady()
    eng = JaxEngine(sc, link, window="auto", batch=FLEET)
    eng.run_quiet(20)
    reg = MetricsRegistry()
    reg.run_summary("fleet", eng.last_run_stats)
    line = reg.lines[-1]
    for key in ("dispatches", "readbacks", "rung_lanes", "sender_lanes",
                "rung_steps", "fleet_iterations", "dense_stage_steps",
                "wide_tail_steps"):
        assert line[key] == eng.last_run_stats[key]
    ring = EdgeEngine(*_ring(), lint="off")
    ring.run(4)
    reg.run_summary("ring", ring.last_run_stats)
    assert "rung_lanes" not in reg.lines[-1]
    assert reg.lines[-1]["readbacks"] == 1
    for bad in ({"rung_lanes": 1.5}, {"rung_steps": [1, "2"]},
                {"rung_steps": 3}, {"readbacks": True},
                {"wide_tail_steps": 0.5}):
        with pytest.raises(ValueError, match="run_summary"):
            validate_line({**line, **bad})


def test_metrics_span_takes_its_times_from_the_one_primitive():
    reg = MetricsRegistry()
    with pytest.raises(RuntimeError):
        with reg.span("unit-span", what="x"):
            time.sleep(0.003)
            raise RuntimeError("the body's")
    line = reg.lines[-1]
    (name, t0, t1, _, attrs), = profiler.calls()[-1]["spans"]
    assert (name, attrs) == ("unit-span", {"what": "x"})
    assert line["wall_s"] == round((t1 - t0) / 1e9, 6) >= 0.003




MS = 1_000_000


def _made(n_before=3, n_window=4, offset=7_000 * MS, job=30 * MS,
          pause=400 * MS, after=0):
    """``n_window`` programs on the device's clock and the calls of a
    run on the host's (device = host + ``offset``): ``n_before`` warm-up
    calls, a pause (the profile starts), the window's calls, another
    pause and ``after`` calls. A call dispatches for 1 ms, its program
    starts 0.4 ms into that and runs 25 ms, the wait returns 0.6 ms
    after the program ended."""
    calls, programs = [], []
    t = 100 * MS
    for k in range(n_before + n_window + after):
        if k in (n_before, n_before + n_window):
            t += pause
        start = t + 400_000
        end = start + 25 * MS
        calls.append((t, end + 600_000))
        if n_before <= k < n_before + n_window:
            programs.append((start + offset, end + offset))
        t += job
    return programs, calls


def _records(calls, run0=1):
    recs = []
    for i, (t0, t1) in enumerate(calls):
        run = run0 + i
        recs.append({"run": run, "engine": "JaxEngine", "n_nodes": 1000,
                     "spans": (("tw.dispatch", t0, t0 + MS, "tw.run_quiet",
                                {"run": run}),
                               ("tw.wait", t0 + MS + 50_000, t1,
                                "tw.run_quiet", {"run": run}),
                               ("tw.run_quiet", t0 - 100_000, t1 + 200_000,
                                None, {"run": run})),
                     "counts": {"supersteps": 10, "rung_lanes": 2_000,
                                "sender_lanes": 1_500}})
    return recs


@pytest.mark.parametrize("n_before,after", [(0, 0), (3, 0), (3, 2), (0, 2)])
def test_the_shift_is_found(n_before, after):
    programs, calls = _made(n_before=n_before, after=after)
    assert rr.find_shift(programs, calls) == n_before
    lo, hi = rr.bracket(programs, calls, n_before)
    assert (lo, hi) == (7_000 * MS - 600_000, 7_000 * MS + 400_000)
    assert rr.delay_spread_ns(programs, calls, n_before) == 0


def test_a_stall_inside_one_dispatch_breaks_no_pairing():
    programs, calls = _made()
    # the third traced program started 130 ms late: its call and all
    # later ones moved with it on both clocks
    late = 130 * MS
    programs = programs[:2] + [(s + late, e + late) for s, e in programs[2:]]
    calls = calls[:5] + [(calls[5][0], calls[5][1] + late)] + \
        [(s + late, e + late) for s, e in calls[6:]]
    assert rr.find_shift(programs, calls) == 3
    assert rr.delay_spread_ns(programs, calls, 3) == late


def test_a_wrong_count_gives_none():
    programs, calls = _made()
    assert rr.find_shift(programs, calls[:3]) is None    # fewer calls
    assert rr.find_shift(programs[:1], calls) is None    # nothing to pair by
    # calls so regular that two shifts fit: no pause before the window
    programs, calls = _made(pause=0)
    assert rr.find_shift(programs, calls) is None
    assert rr.reduction([(s, e - s, "main") for s, e in programs], [],
                        _records(calls)) is None


def test_contradicting_pairs_raise():
    programs, calls = _made(n_before=0)
    with pytest.raises(ValueError, match="contradict"):
        # a wait that returned before its program ended
        rr.bracket(programs, [(s, e - 2 * MS) for s, e in calls], 0)
    with pytest.raises(ValueError, match="free of contradiction"):
        rr.find_shift(programs, [(s, e - 2 * MS) for s, e in calls])


def test_the_four_owners_sum_to_the_gaps():
    programs, calls = _made()
    recs = _records(calls)
    # a lone span of the program between two calls, and one other
    # program of the job before each main program
    recs.insert(5, {"run": None, "spans": (("tw.sweep.bucket",
                    calls[4][1] + MS, calls[4][1] + 2 * MS, None, {}),),
                    "counts": {}})
    modules = [(s, e - s, "jit__run_while(1)") for s, e in programs]
    modules += [(s - 300_000, 100_000, "jit_state(2)") for s, _ in programs]
    events = [(s, e - s, "%fusion.1 = fusion()") for s, e in programs]
    events += [(s - 300_000, 100_000, "%copy.1 = copy()")
               for s, _ in programs]
    red = rr.reduction(modules, events, recs)
    assert (red["paired"], red["shift"]) == (4, 3)
    assert red["slack_ms"] == 1.0
    owners = red["owners_ms"]
    assert set(owners) == set(rr.OWNERS)
    gaps = rr.gaps_between_programs(modules, events)
    assert sum(owners.values()) == pytest.approx(
        sum(d for _, d in gaps) / 3 / 1e6)
    # a gap is 30 ms less the 25 ms program and the 0.1 ms state
    # program: 4.9 ms, of which the wait holds what is left of it after
    # the program (0.6 ms less the bracket's middle, 0.1 ms off)
    assert sum(owners.values()) == pytest.approx(4.9)
    assert owners["wait"] == pytest.approx(0.5)
    assert owners["driver"] > 0 and owners["client"] > 0
    assert owners["dispatch"] == pytest.approx(0.5 - 0.1)
    assert red["lanes"] == {"rung_lanes": 8_000, "sender_lanes": 6_000,
                            "full_lanes": 40_000}


def test_the_readers_read_the_programs_record_or_nothing(monkeypatch):
    import importlib
    programs, calls = _made()
    modules = [(s, e - s, "jit__run_while(1)") for s, e in programs]

    class Trace:
        ops = [[(s, e - s, "%fusion.1 = fusion()") for s, e in programs]]
        asyncs = [[]]
    Trace.modules = modules
    names = ["idle_in_dispatch_ms", "idle_in_wait_ms", "idle_in_driver_ms",
             "idle_in_client_ms", "span_clock_slack_ms", "rung_lane_share",
             "rung_lane_occupancy"]
    readers = {n: importlib.import_module(f"layer_metrics.{n}")
               for n in names}
    # a program with no record (the parent): nothing, and no raise
    monkeypatch.setattr(rr, "records", lambda: None)
    assert [r.read(Trace(), {}) for r in readers.values()] == [None] * 7
    monkeypatch.setattr(rr, "records", lambda: _records(calls))
    tr = Trace()
    got = {n: r.read(tr, {}) for n, r in readers.items()}
    assert all(v is not None for v in got.values()), got
    assert got["span_clock_slack_ms"] == 1.0
    assert got["rung_lane_share"] == pytest.approx(20.0)
    assert got["rung_lane_occupancy"] == pytest.approx(75.0)
    assert sum(got[n] for n in names[:4]) == pytest.approx(5.0)
