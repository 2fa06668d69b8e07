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

(Named test_zzz* to sort after the whole suite — the tier-1 time
window truncates, so new tests must not displace existing dots.)
"""

import json

import numpy as np
import pytest

import jax

from timewarp_tpu.core.time import FOREVER
from timewarp_tpu.dispatch import (Decision, DecisionTrace,
                                   DispatchController,
                                   DispatchTraceError)
from timewarp_tpu.faults.schedule import (FaultFleet, FaultSchedule,
                                          LinkWindow)
from timewarp_tpu.interp.jax_engine.batched import BatchSpec, world_slice
from timewarp_tpu.interp.jax_engine.common import DynDispatch
from timewarp_tpu.interp.jax_engine.engine import JaxEngine
from timewarp_tpu.models.gossip import gossip, gossip_links
from timewarp_tpu.net.delays import FixedDelay, Quantize
from timewarp_tpu.trace.events import (assert_states_equal,
                                       assert_traces_equal)

BUDGET = 1 << 14


def _wave(n=64, end_us=200_000, mailbox_cap=16):
    sc = gossip(n, fanout=4, think_us=2_000, burst=True,
                end_us=end_us, mailbox_cap=mailbox_cap)
    link = Quantize(gossip_links(median_us=20_000, sigma=0.6,
                                 floor_us=8_000), 1_000)
    return sc, link


def _shrink_sched():
    """A degradation window that UNDERCUTS the link's declared 8 ms
    floor (2 ms inside [40 ms, 90 ms))."""
    return FaultSchedule((LinkWindow(None, None, 40_000, 90_000,
                                     scale=0.25),))


def _auto_engine(sc, link, **kw):
    return JaxEngine(sc, link, window="auto", telemetry="counters",
                     lint="off",
                     controller=DispatchController(chunk=8,
                                                   chunk_max=32),
                     **kw)


def _replay_engine(sc, link, decisions, **kw):
    return JaxEngine(sc, link, window="auto", lint="off",
                     controller=DispatchController(
                         mode="replay",
                         replay=DecisionTrace.of(decisions)), **kw)


# -- the replay law --------------------------------------------------------

def test_replay_law_solo_bit_identical(tmp_path):
    sc, link = _wave()
    eng = _auto_engine(sc, link)
    final, trace = eng.run_controlled(BUDGET)
    decs = eng.last_run_decisions
    assert len(decs) >= 2, "run too short to exercise adaptation"
    # trace file round-trip: what --decisions-out writes is what
    # --controller replay: loads
    path = str(tmp_path / "trace.jsonl")
    DecisionTrace.of(decs).save(path)
    rep = _replay_engine(sc, link, DecisionTrace.load(path).decisions)
    final2, trace2 = rep.run_controlled(BUDGET)
    assert_traces_equal(trace, trace2, "auto", "replay")
    assert_states_equal(final, final2, "replay law (solo)")
    assert [d.chunk for d in rep.last_run_decisions] == \
        [d.chunk for d in decs]


def test_replay_law_checkpoint_identical(tmp_path):
    """Checkpoints written mid-run by the two sides are bit-equal:
    drive both engines chunk-by-chunk over the same decisions and
    compare the state pytree after every chunk."""
    sc, link = _wave()
    eng = _auto_engine(sc, link)
    eng.run_controlled(BUDGET)
    decs = eng.last_run_decisions
    rep = _replay_engine(sc, link, decs)
    rep.controller.begin(rep)
    st_a, st_b = eng.init_state(), rep.init_state()
    for d in decs:
        dyn = eng.dyn_values(d)
        st_a, _ = eng.run(d.chunk_len, state=st_a, _dyn=dyn)
        st_b, _ = rep.run(d.chunk_len, state=st_b,
                          _dyn=rep.dyn_values(d))
        assert_states_equal(st_a, st_b,
                            f"checkpoint after chunk {d.chunk}")


def test_per_chunk_equals_static_run(tmp_path):
    """Each chunk of a (degradation-free) controlled run ≡ a STATIC
    engine constructed with that chunk's window, run for the same
    budget from the same state."""
    sc, link = _wave()
    eng = _auto_engine(sc, link)
    eng.run_controlled(BUDGET)
    decs = eng.last_run_decisions
    ctl = _replay_engine(sc, link, decs)
    ctl.controller.begin(ctl)
    st_c = ctl.init_state()
    st_s = None
    for d in decs:
        static = JaxEngine(sc, link, window=d.window_us, lint="off")
        if st_s is None:
            st_s = static.init_state()
        st_c, tr_c = ctl.run(d.chunk_len, state=st_c,
                             _dyn=ctl.dyn_values(d))
        st_s, tr_s = static.run(d.chunk_len, state=st_s)
        assert_traces_equal(tr_s, tr_c, "static", "chunk")
        assert_states_equal(st_s, st_c,
                            f"chunk {d.chunk} ≡ static "
                            f"window={d.window_us}")


def test_replay_law_batched_faulted_with_slack_reduction():
    """The world axis + per-world fault schedules, one of which
    undercuts the link floor: the fleet decision trace records the
    slack/load reductions, short_delay stays 0 (the device clamp
    held), and replay is bit-identical per world."""
    B = 3
    sc, link = _wave(n=48, end_us=150_000)
    fleet = FaultFleet((
        FaultSchedule(()),
        _shrink_sched(),
        FaultSchedule((LinkWindow(None, None, 20_000, 60_000,
                                  scale=0.5),)),
    ))
    spec = BatchSpec(seeds=(0, 1, 2))
    eng = _auto_engine(sc, link, batch=spec, faults=fleet)
    assert eng.window == 8_000, \
        "controller bound must be the UNDEGRADED fleet floor"
    final, traces = eng.run_controlled(BUDGET)
    assert int(np.asarray(final.short_delay).sum()) == 0, \
        "device window clamp failed under the degradation fleet"
    decs = eng.last_run_decisions
    agg = [d.obs.get("agg") for d in decs if "agg" in d.obs]
    assert any("min-over-worlds" in a for a in agg), \
        "fleet decisions must record the slack reduction"
    rep = _replay_engine(sc, link, decs, batch=spec, faults=fleet)
    final2, traces2 = rep.run_controlled(BUDGET)
    for b in range(B):
        assert_traces_equal(traces[b], traces2[b], f"auto w{b}",
                            f"replay w{b}")
    assert_states_equal(final, final2, "replay law (batched+faults)")
    # world-b slice ≡ solo replay with that world's schedule (the
    # batch exactness law composed with the replay law)
    b = 1
    solo = JaxEngine(sc, link, window="auto", lint="off",
                     seed=spec.seeds[b],
                     faults=fleet.world_schedule(b),
                     controller=DispatchController(
                         mode="replay",
                         replay=DecisionTrace.of(decs)))
    sfinal, strace = solo.run_controlled(BUDGET)
    assert_traces_equal(strace, traces[b], "solo replay", f"world {b}")
    assert_states_equal(sfinal, world_slice(final, b),
                        f"world {b} slice")


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


def test_sharded_batched_controller_matches_local_fleet():
    """The world-sharded engine under a controller: dyn scalars ride
    the shard_map as replicated operands, per-world budget vectors
    slice per device, and the run is bit-identical to the local
    batched fleet replaying the same decisions."""
    from timewarp_tpu.interp.jax_engine.sharded import (
        ShardedBatchedEngine, make_mesh)
    sc, link = _wave(n=32, end_us=120_000)
    spec = BatchSpec(seeds=tuple(range(4)))
    eng = ShardedBatchedEngine(
        sc, link, make_mesh(4, axis="worlds"), batch=spec,
        window="auto", telemetry="counters", lint="off",
        controller=DispatchController(chunk=8, chunk_max=32))
    final, traces = eng.run_controlled(1 << 12)
    decs = eng.last_run_decisions
    loc = _replay_engine(sc, link, decs, batch=spec)
    lfinal, ltraces = loc.run_controlled(1 << 12)
    for b in range(4):
        assert_traces_equal(ltraces[b], traces[b], f"local w{b}",
                            f"sharded w{b}")
    assert_states_equal(jax.device_get(lfinal),
                        jax.device_get(final),
                        "sharded ≡ local controller fleet")


# -- zero recompiles + per-chunk compile accounting ------------------------

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


# -- window="auto" edge cases (satellite) ----------------------------------

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


# -- chunk-length-only engines (edge / fused) ------------------------------

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


# -- the decision trace / controller object --------------------------------

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


# -- metrics schema (satellite) --------------------------------------------

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


# -- sweep integration -----------------------------------------------------

_GOSSIP = {"nodes": 24, "fanout": 3, "burst": True, "end_us": 90_000,
           "mailbox_cap": 16, "think_us": 700}


def _ctrl_pack():
    from timewarp_tpu.sweep import SweepPack
    return SweepPack.from_json([
        {"id": "gc0", "scenario": "gossip", "params": _GOSSIP,
         "link": "quantize:1000:uniform:3000:9000", "seed": 2,
         "window": "auto", "budget": 100, "controller": "auto"},
        {"id": "gc1", "scenario": "gossip", "params": _GOSSIP,
         "link": "quantize:1000:uniform:3000:9000", "seed": 5,
         "window": "auto", "budget": 60, "controller": "auto"},
        {"id": "goff", "scenario": "gossip", "params": _GOSSIP,
         "link": "quantize:1000:uniform:3000:9000", "seed": 9,
         "window": "auto", "budget": 100},
    ])


def test_sweep_controller_kill_resume_replays_decisions(tmp_path):
    from timewarp_tpu.sweep import SweepService, solo_result
    from timewarp_tpu.sweep.service import SweepKilled
    pack = _ctrl_pack()
    d = str(tmp_path / "j")
    svc = SweepService(pack, d, chunk=16, lint="off", inject="die:2")
    with pytest.raises(SweepKilled):
        svc.run()
    scan = svc.journal.scan()
    pre = {b: list(v) for b, v in scan.decisions.items()}
    assert sum(len(v) for v in pre.values()) >= 1, \
        "no decision was journaled before the kill"

    svc2 = SweepService.resume(d, chunk=16, lint="off")
    report = svc2.run()
    assert report.ok, report.to_json()
    scan2 = svc2.journal.scan()
    for b, recs in pre.items():
        post = {r["chunk"]: r for r in scan2.decisions[b]}
        for r in recs:
            assert post[r["chunk"]] == r, \
                f"pre-kill decision re-made differently: {r}"
    # the survival law, controller form: solo twin replays the chain
    for rid, res in report.done.items():
        cfg = pack.by_id(rid)
        decs = svc2.decisions_for_world(rid) \
            if cfg.controller == "auto" else None
        want = solo_result(cfg, lint="off", decisions=decs)
        assert want == res, f"{rid}:\n solo {want}\n strm {res}"


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
