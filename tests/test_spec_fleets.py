"""Optimistic time-warp execution over worlds (tests/test_zzzzzzspec.py
has the laws' statements): the equivalence law for batched worlds and
under a fault fleet (degrade windows clamp the speculative horizon
on-device), and the sweep journaling no duplicate ``world_done`` across
a rollback and a kill."""

import pytest

from spec_laws import BUDGET, _sc, _tail_link
from timewarp_tpu.interp.jax_engine.batched import BatchSpec
from timewarp_tpu.interp.jax_engine.engine import JaxEngine
from timewarp_tpu.speculate import assert_spec_equiv, canonical_rows


def test_equivalence_law_batched_worlds():
    sc, link = _sc(), _tail_link()
    bspec = JaxEngine(sc, link, window="auto", lint="off",
                      speculate="auto", batch=BatchSpec(seeds=(0, 1)))
    bfin, btr = bspec.run_speculative(BUDGET, chunk=16)
    rows = canonical_rows(bfin, btr, B=2)
    for b, seed in enumerate((0, 1)):
        solo = JaxEngine(sc, link, window="auto", lint="off",
                         seed=seed)
        cfin, ctr = solo.run(BUDGET)
        got = dict(rows[b], world=0)
        assert_spec_equiv([got], canonical_rows(cfin, ctr),
                          f"world {b}")


def test_equivalence_law_under_fault_fleet():
    # a shrink-degradation window: the per-superstep device clamp
    # narrows the EFFECTIVE speculative window inside [40ms, 80ms]
    # (faults/apply.window_floor) — the speculative horizon and the
    # fault machinery interacting exactly as the static engines do
    from timewarp_tpu.faults.schedule import (FaultFleet, FaultSchedule,
                                              LinkWindow)
    sc, link = _sc(), _tail_link()
    sched = FaultSchedule((LinkWindow(None, None, 40_000, 80_000,
                                      scale=0.25),))
    fleet = FaultFleet((sched, FaultSchedule(())))
    spec = JaxEngine(sc, link, window="auto", lint="off",
                     speculate="auto", faults=fleet,
                     batch=BatchSpec(seeds=(3, 4)))
    sfin, strc = spec.run_speculative(BUDGET, chunk=16)
    rows = canonical_rows(sfin, strc, B=2)
    for b, (seed, ws) in enumerate(((3, sched),
                                    (4, FaultSchedule(())))):
        solo = JaxEngine(sc, link, window="auto", lint="off",
                         seed=seed, faults=ws)
        cfin, ctr = solo.run(BUDGET)
        got = dict(rows[b], world=0)
        assert_spec_equiv([got], canonical_rows(cfin, ctr),
                          f"faulted world {b}")


def test_sweep_no_duplicate_world_done_across_rollback_and_kill():
    import shutil
    import tempfile

    from timewarp_tpu.sweep import SweepPack, SweepService, solo_result
    from timewarp_tpu.sweep.service import SweepKilled

    params = {"nodes": 64, "fanout": 4, "burst": True,
              "end_us": 200_000, "mailbox_cap": 16, "think_us": 700}
    pack = SweepPack.from_json([
        {"id": "s0", "scenario": "gossip", "params": params,
         "link": "quantize:500:pareto:4000:1.2", "seed": 0,
         "window": "auto", "budget": 1500, "speculate": "fixed:16000"},
        {"id": "s1", "scenario": "gossip", "params": params,
         "link": "quantize:500:pareto:4000:1.2", "seed": 1,
         "window": "auto", "budget": 1500, "speculate": "fixed:16000"},
    ])
    d = tempfile.mkdtemp(prefix="tw_zzspec_sweep_")
    try:
        # kill mid-sweep (after the rollback has happened: the fixed
        # 16000 bet violates on the first message-bearing chunk), then
        # resume — the journal must hold exactly one world_done per
        # world and the streamed results must replay solo
        svc = SweepService(pack, d, chunk=8, lint="off",
                           inject="die:3")
        with pytest.raises(SweepKilled):
            svc.run()
        svc2 = SweepService.resume(d, chunk=8, lint="off")
        report = svc2.run()
        assert report.ok, report.to_json()
        scan = svc2.journal.scan()
        assert len(scan.spec_rollbacks) >= 1, \
            "the forced misspeculation never rolled back in-sweep"
        dones = [r for r in scan.events if r.get("ev") == "world_done"]
        per = {}
        for r in dones:
            per[r["result"]["run_id"]] = \
                per.get(r["result"]["run_id"], 0) + 1
        assert per == {"s0": 1, "s1": 1}, \
            f"duplicate world_done records: {per}"
        for rid, res in report.done.items():
            decs = svc2.decisions_for_world(rid, scan)
            want = solo_result(pack.by_id(rid), lint="off",
                               decisions=decs)
            assert want == res, f"survival law violated for {rid}"
        # and the committed results match the conservative twin on
        # the canonical surface: kill/resume straddled a rollback and
        # the equivalence law still holds end-to-end
        import dataclasses
        for rid in ("s0", "s1"):
            cfg = pack.by_id(rid)
            cons = solo_result(dataclasses.replace(cfg,
                                                   speculate="off"),
                               lint="off")
            got = report.done[rid]
            for c in ("delivered", "overflow", "bad_dst", "bad_delay",
                      "short_delay", "route_drop", "fault_dropped"):
                assert got[c] == cons[c], (rid, c, got[c], cons[c])
    finally:
        shutil.rmtree(d, ignore_errors=True)
