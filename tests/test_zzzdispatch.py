"""Online adaptive dispatch (timewarp_tpu/dispatch/, docs/dispatch.md).

The laws under test:

- **replay law** — a controller-driven run re-executed from its
  decision trace is bit-identical on states, traces, digests, and
  checkpoints; solo, batched (with the recorded per-world slack
  reduction), and under fault schedules whose degradation windows
  undercut the link floor.
- **per-chunk static equivalence** — every chunk of a (degradation-
  free) controlled run is bit-identical to a static engine built with
  that chunk's window, run for that chunk's budget from the same
  state.
- **zero recompiles across adaptations** — knob values are traced
  scalars and chunk lengths resolve through the pow2-padded
  executable cache, so adaptation never retraces; the (fixed)
  per-chunk compile accounting of ``last_run_stats`` proves it chunk
  by chunk.
- ``window="auto"`` edge cases: FOREVER-delay links, degradation
  undercutting the declared floor, the batched fleet-wide floor.
- sweep integration: decisions journaled before a kill are replayed
  (never re-made) on resume, and the survival law's solo twin replays
  the bucket's decision chain.

Here: the zero-recompile accounting, the ``window="auto"`` edge cases,
the controller's refusals and its journal. The replay law and the
per-chunk equivalence on a solo run are tests/test_dispatch_replay_law.py,
fleets and the sweep tests/test_dispatch_fleets.py
(tests/dispatch_laws.py has what the three share).

(Named test_zzz* to sort after the whole suite — the tier-1 time
window truncates, so new tests must not displace existing dots.)
"""

import json

import numpy as np
import pytest

from dispatch_laws import (BUDGET, _auto_engine, _ctrl_pack, _shrink_sched,
                           _wave)
from timewarp_tpu.core.time import FOREVER
from timewarp_tpu.dispatch import (Decision, DecisionTrace, DispatchController,
                                   DispatchTraceError)
from timewarp_tpu.faults.schedule import FaultFleet, FaultSchedule
from timewarp_tpu.interp.jax_engine.batched import BatchSpec
from timewarp_tpu.interp.jax_engine.common import DynDispatch
from timewarp_tpu.interp.jax_engine.engine import JaxEngine
from timewarp_tpu.net.delays import FixedDelay
from timewarp_tpu.trace.events import assert_states_equal, assert_traces_equal


def test_rung_pin_is_result_identical():
    """A pinned rung floor (max(computed, pin)) selects a wider rung
    — results must be bit-identical to the unpinned ladder."""
    sc, link = _wave(n=2048, end_us=120_000)
    eng = _auto_engine(sc, link)
    rungs = eng._sender_rungs(sc.n_nodes)
    assert len(rungs) > 1, "need a real ladder for this test"
    st0 = eng.init_state()
    top = len(rungs) - 1
    a, tr_a = eng.run(12, state=st0, _dyn=DynDispatch(
        window=np.int64(eng.window), rung_pin=np.int32(-1)))
    b, tr_b = eng.run(12, state=st0, _dyn=DynDispatch(
        window=np.int64(eng.window), rung_pin=np.int32(top)))
    assert_traces_equal(tr_a, tr_b, "unpinned", "pinned")
    assert_states_equal(a, b, "rung pin result-identity")


def test_zero_recompiles_across_adaptations():
    sc, link = _wave()
    eng = _auto_engine(sc, link)
    eng.run_controlled(BUDGET)
    stats = eng.last_run_stats
    assert stats["chunks"] == len(eng.last_run_decisions)
    assert stats["compiles"] == sum(stats["per_chunk_compiles"])
    # every compile is the FIRST use of a pow2 pad; a revisited chunk
    # length must hit the cache
    from timewarp_tpu.interp.jax_engine.common import scan_pad
    seen, recompiles = set(), 0
    for d, c in zip(eng.last_run_decisions,
                    stats["per_chunk_compiles"]):
        pad = scan_pad(d.chunk_len)
        if pad in seen:
            recompiles += c
        seen.add(pad)
    assert recompiles == 0, \
        f"adaptation recompiled an already-built pad: {stats}"
    # a second controlled run replays the same decisions: every pad is
    # cached, so ZERO compiles anywhere
    eng.run_controlled(BUDGET)
    assert eng.last_run_stats["compiles"] == 0, eng.last_run_stats


def test_run_stream_per_chunk_compile_accounting():
    """The satellite fix: a chunked run used to report only the FINAL
    chunk's stats — compiles on earlier chunks vanished."""
    sc, link = _wave(n=32, end_us=120_000)
    eng = JaxEngine(sc, link, window="auto", lint="off",
                    batch=BatchSpec(seeds=(0, 1)))
    eng.run_stream([400, 200], chunk=16)
    stats = eng.last_run_stats
    assert "per_chunk_compiles" in stats and stats["chunks"] >= 2
    assert stats["compiles"] == sum(stats["per_chunk_compiles"])
    assert stats["compiles"] >= 1, \
        "the first chunk's compile must be attributed somewhere"




def test_window_auto_forever_delay_link():
    """A FOREVER-delay link declares an astronomical floor; auto must
    resolve the widest REPRESENTABLE window, not refuse."""
    from timewarp_tpu.interp.jax_engine.common import I32MAX
    sc, _ = _wave(n=16, end_us=50_000)
    eng = JaxEngine(sc, FixedDelay(FOREVER), window="auto", lint="off")
    assert eng.window == I32MAX - 1
    final, _ = eng.run(4)  # runs; deliveries clamp into bad_delay
    assert int(final.steps) >= 1


def test_window_auto_degradation_undercuts_floor():
    sc, link = _wave(n=16)
    sched = _shrink_sched()
    # static: auto must resolve the DEGRADED schedule-wide floor
    st = JaxEngine(sc, link, window="auto", faults=sched, lint="off")
    assert st.window == sched.min_delay_floor(link.min_delay_us) == \
        2_000
    # an explicit window above the degraded floor refuses loudly
    with pytest.raises(ValueError, match="min_delay_us"):
        JaxEngine(sc, link, window=8_000, faults=sched, lint="off")
    # controller: the bound is the UNDEGRADED floor; the device clamp
    # carries exactness (test_replay_law_batched_faulted asserts
    # short_delay == 0 end-to-end)
    ctl = _auto_engine(sc, link, faults=sched)
    assert ctl.window == 8_000
    # host-side per-window floor: full outside, undercut inside
    assert sched.min_delay_floor_in(8_000, 0, 10_000) == 8_000
    assert sched.min_delay_floor_in(8_000, 50_000, 60_000) == 2_000


def test_window_auto_batched_fleet_floor():
    """Batched auto = min over every world's link floor, degraded by
    the fleet's schedules for static engines."""
    sc, link = _wave(n=16)
    spec = BatchSpec(seeds=(0, 1),
                     link_params={"inner.floor_us": [8_000, 4_000]})
    eng = JaxEngine(sc, link, window="auto", batch=spec, lint="off")
    assert eng.window == 4_000  # min over world links
    fleet = FaultFleet((FaultSchedule(()), _shrink_sched()))
    faulted = JaxEngine(sc, link, window="auto", batch=spec,
                        faults=fleet, lint="off")
    assert faulted.window == fleet.min_delay_floor(4_000) == 1_000




def test_edge_engine_controller_chunk_only():
    from timewarp_tpu.interp.jax_engine.edge_engine import EdgeEngine
    from timewarp_tpu.models.token_ring import (token_ring,
                                                token_ring_links)
    sc = token_ring(24, n_tokens=3, think_us=2_000, bootstrap_us=1000,
                    end_us=80_000, with_observer=False, mailbox_cap=8)
    link = token_ring_links(24)
    eng = EdgeEngine(sc, link, telemetry="counters", lint="off",
                     controller=DispatchController(chunk=8,
                                                   chunk_max=16))
    assert not eng._dyn_ok
    final, trace = eng.run_controlled(500)
    # chunk boundaries cannot change results: ≡ the one-shot run
    ref = EdgeEngine(sc, link, lint="off")
    rfinal, rtrace = ref.run(500)
    assert_traces_equal(rtrace, trace, "one-shot", "controlled")
    assert_states_equal(rfinal, final, "edge chunk-only controller")
    assert all(d.window_us == 1 and d.rung_pin == -1
               for d in eng.last_run_decisions)




def test_decision_trace_validation_is_loud(tmp_path):
    with pytest.raises(DispatchTraceError, match="gapless"):
        DecisionTrace.of([Decision(1, 8, -1, 4)])
    with pytest.raises(DispatchTraceError, match="window_us"):
        Decision(0, 0, -1, 4)
    p = tmp_path / "bad.jsonl"
    p.write_text('{"schema": 1, "kind": "decision", "chunk": 0}\n')
    with pytest.raises(DispatchTraceError, match="missing field"):
        DecisionTrace.load(str(p))
    p.write_text("not json\n")
    with pytest.raises(DispatchTraceError, match="not JSON"):
        DecisionTrace.load(str(p))


def test_replay_exhaustion_and_bound_violations():
    sc, link = _wave(n=16)
    short = DecisionTrace.of([Decision(0, 8_000, -1, 2)])
    eng = JaxEngine(sc, link, window="auto", lint="off",
                    controller=DispatchController(mode="replay",
                                                  replay=short))
    with pytest.raises(DispatchTraceError, match="exhausted"):
        eng.run_controlled(BUDGET)
    # a trace recorded for a wider bound refuses at begin()
    wide = DecisionTrace.of([Decision(0, 1 << 20, -1, 8)])
    eng2 = JaxEngine(sc, link, window="auto", lint="off",
                     controller=DispatchController(mode="replay",
                                                   replay=wide))
    with pytest.raises(DispatchTraceError, match="bound"):
        eng2.run_controlled(BUDGET)


def test_controller_requires_telemetry_for_auto():
    sc, link = _wave(n=16)
    with pytest.raises(ValueError, match="telemetry"):
        JaxEngine(sc, link, window="auto", lint="off",
                  controller=DispatchController())
    # replay mode runs with telemetry off (it reads nothing)
    JaxEngine(sc, link, window="auto", lint="off",
              controller=DispatchController(
                  mode="replay",
                  replay=DecisionTrace.of([Decision(0, 8_000, -1,
                                                    8)])))




def test_metrics_decision_kind_validates(tmp_path):
    from timewarp_tpu.obs.metrics import (MetricsRegistry,
                                          validate_metrics_file)
    path = str(tmp_path / "m.jsonl")
    reg = MetricsRegistry(path=path)
    reg.emit("decision", label="x", chunk=0, window_us=8_000,
             rung_pin=-1, chunk_len=16)
    reg.close()
    assert validate_metrics_file(path) == 1
    with open(path, "a") as f:
        f.write(json.dumps({"schema": 2, "kind": "decision",
                            "chunk": 0, "window_us": "wide",
                            "rung_pin": -1, "chunk_len": 4}) + "\n")
    with pytest.raises(ValueError, match="window_us"):
        validate_metrics_file(path)
    with pytest.raises(ValueError, match="decision"):
        reg.emit("decision", chunk=0)  # missing required fields


def test_controller_decisions_stream_to_metrics(tmp_path):
    from timewarp_tpu.obs.metrics import (MetricsRegistry,
                                          validate_metrics_file)
    sc, link = _wave(n=32, end_us=120_000)
    eng = _auto_engine(sc, link)
    path = str(tmp_path / "m.jsonl")
    eng.metrics = MetricsRegistry(path=path)
    eng.run_controlled(BUDGET)
    eng.metrics.close()
    assert validate_metrics_file(path) >= 1
    kinds = [json.loads(x)["kind"]
             for x in open(path) if x.strip()]
    assert kinds.count("decision") == len(eng.last_run_decisions)


def test_controller_config_solo_twin_requires_decisions():
    from timewarp_tpu.sweep import SweepConfigError, solo_result
    pack = _ctrl_pack()
    with pytest.raises(SweepConfigError, match="decision"):
        solo_result(pack.by_id("gc0"), lint="off")


def test_controller_bucket_key_separates_and_forces_telemetry():
    from timewarp_tpu.sweep import build_bucket_engine, plan_buckets
    pack = _ctrl_pack()
    buckets = plan_buckets(pack.configs)
    by_ids = {b.run_ids: b for b in buckets}
    assert ("gc0", "gc1") in by_ids and ("goff",) in by_ids, by_ids
    ctrl_bucket = by_ids[("gc0", "gc1")]
    assert ctrl_bucket.controller
    from timewarp_tpu.dispatch import DispatchController
    eng = build_bucket_engine(ctrl_bucket, lint="off",
                              controller=DispatchController())
    assert eng.telemetry == "counters", \
        "controller buckets must force the sensor layer on"


def test_journal_refuses_conflicting_decisions(tmp_path):
    from timewarp_tpu.sweep import SweepJournal, SweepJournalError
    j = SweepJournal(str(tmp_path / "jj"))
    rec = {"schema": 1, "kind": "decision", "chunk": 0,
           "window_us": 8_000, "rung_pin": -1, "chunk_len": 16,
           "obs": {}}
    j.append({"ev": "dispatch_decision", "bucket": "b0",
              "decision": rec})
    j.append({"ev": "dispatch_decision", "bucket": "b0",
              "decision": {**rec, "window_us": 4_000}})
    j.close()
    with pytest.raises(SweepJournalError, match="DIFFERENT dispatch"):
        j.scan()
