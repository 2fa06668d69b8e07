"""Online adaptive dispatch on fleets (tests/test_zzzdispatch.py has the
laws' statements): the replay law over the world axis with per-world
fault schedules, one of which undercuts the link floor; the sharded
fleet under the controller against the local one; and the sweep
service replaying, never re-making, the decisions journaled before a
kill."""

import numpy as np
import pytest

import jax

from dispatch_laws import (BUDGET, _auto_engine, _ctrl_pack, _replay_engine,
                           _shrink_sched, _wave)
from timewarp_tpu.dispatch import DecisionTrace, DispatchController
from timewarp_tpu.faults.schedule import FaultFleet, FaultSchedule, LinkWindow
from timewarp_tpu.interp.jax_engine.batched import BatchSpec, world_slice
from timewarp_tpu.interp.jax_engine.engine import JaxEngine
from timewarp_tpu.trace.events import assert_states_equal, assert_traces_equal


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
