"""``record_reduce.py`` and its seven readers against a live program, at
toy size on the CPU: a general engine's driver calls, a "trace" made of
the calls' own times (device clock = record clock + a known offset), the
pairing found through warm-up calls, the owners summing to the gaps and
to ``sync_gap_ms``'s reading, the lane shares equal to the engine's own
counts. The pure functions on hand-made tuples are held in tier-1
(``tests/test_zzzzzzzzzzzzzzzrecord.py``)."""

import time

import pytest

import record_reduce as rr
import trace_reduce as tr
from layer_metrics import (idle_in_client_ms, idle_in_dispatch_ms,
                           idle_in_driver_ms, idle_in_wait_ms,
                           rung_lane_occupancy, rung_lane_share,
                           span_clock_slack_ms, sync_gap_ms)
from timewarp_tpu.interp.jax_engine.engine import JaxEngine
from timewarp_tpu.models.gossip import gossip
from timewarp_tpu.net.delays import Quantize, UniformDelay
from timewarp_tpu.obs import profiler

OFFSET = 5_000_000_000


@pytest.fixture(scope="module")
def run():
    n = 2048
    sc = gossip(n, fanout=1, think_us=1_000, gossip_interval=1_000,
                end_us=60_000, steady=True, mailbox_cap=8)
    eng = JaxEngine(sc, Quantize(UniformDelay(500, 4_500), 1_000),
                    window="auto")
    for _ in range(2):                   # warm-up: the compile, one more
        eng.run_quiet(30)
    time.sleep(0.3)                      # where a profile would start
    mark = len(profiler.calls())
    stats = []
    for _ in range(4):
        eng.run_quiet(30)
        stats.append(eng.last_run_stats)
        time.sleep(0.01)                 # the client's own work
    window = profiler.calls()[mark:]
    # each call's program "ran" from 40 % into its dispatch to 10 %
    # before its wait's end
    modules, ops = [], []
    for rec in window:
        (d0, d1), (w0, w1) = [
            next(s[1:3] for s in rec["spans"] if s[0] == name)
            for name in ("tw.dispatch", "tw.wait")]
        start = d0 + (d1 - d0) * 4 // 10 + OFFSET
        end = w1 - (w1 - w0) // 10 + OFFSET
        modules.append((start, end - start, "jit__run_while(1)"))
        ops.append((start, end - start, "%fusion.1 = fusion()"))
    trace = tr.Trace(ops=[ops], asyncs=[[]], modules=modules, jobs=[])
    return trace, stats, n


def test_the_readers_pair_the_trace_with_the_programs_record(run):
    trace, stats, n = run
    red = rr.of_trace(trace)
    assert red["paired"] == 4
    owners = [m.read(trace, {}) for m in (
        idle_in_dispatch_ms, idle_in_wait_ms, idle_in_driver_ms,
        idle_in_client_ms)]
    assert all(v is not None and v >= 0 for v in owners)
    assert sum(owners) == pytest.approx(sync_gap_ms.read(trace, {}))
    assert owners[3] >= 10.0             # the client slept 10 ms a job
    assert span_clock_slack_ms.read(trace, {}) == red["slack_ms"] > 0
    lanes = sum(s["rung_lanes"] for s in stats)
    assert rung_lane_share.read(trace, {}) == pytest.approx(
        100.0 * lanes / (n * sum(s["supersteps"] for s in stats)))
    assert rung_lane_occupancy.read(trace, {}) == pytest.approx(
        100.0 * sum(s["sender_lanes"] for s in stats) / lanes)


def test_a_program_with_no_record_reads_nothing(run, monkeypatch):
    trace = run[0]._replace(jobs=[(0, 1, "bench_job")])    # another object
    monkeypatch.setattr(rr, "records", lambda: None)
    for m in (idle_in_dispatch_ms, idle_in_wait_ms, idle_in_driver_ms,
              idle_in_client_ms, span_clock_slack_ms, rung_lane_share,
              rung_lane_occupancy):
        assert m.read(trace, {}) is None
