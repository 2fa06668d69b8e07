"""The state-integrity law's recovery half (tests/test_zzzzintegrity.py
has the detection law): a corruption that persists raises after its
rollbacks are spent, a rollback never anchors on a corrupt snapshot, a
checkpoint's leaf digests are verified on load, ``run_quiet``'s final
state guard speaks, ``run_verified`` writes valid integrity metrics,
and the sweep service journals a violation, recovers, and survives a
kill that straddles the rollback."""

import json
import os

import numpy as np
import pytest

import jax.numpy as jnp

from integrity_laws import BUDGET, CHUNK, _gossip, _pack
from timewarp_tpu.integrity import FlipInjector, IntegrityViolation, apply_flip
from timewarp_tpu.interp.jax_engine.engine import JaxEngine
from timewarp_tpu.trace.events import assert_states_equal


def test_persistent_corruption_raises_after_max_rollbacks():
    """A corruption that re-appears every re-run (bad memory cell /
    real logic bug) must raise loudly, never loop forever."""
    sc, link = _gossip()
    eng = JaxEngine(sc, link, window="auto", lint="off",
                    verify="digest")

    def always_corrupt(chunk_idx, state):
        if chunk_idx == 1:
            return apply_flip(state, seed=chunk_idx + 17,
                              plane="mb_rel")[0]
        return None
    with pytest.raises(IntegrityViolation, match="persistent"):
        eng.run_verified(BUDGET, chunk=CHUNK, inject=always_corrupt)






def test_checkpoint_load_verifies_leaf_digests(tmp_path):
    from timewarp_tpu.utils.checkpoint import load_state, save_state
    sc, link = _gossip()
    eng = JaxEngine(sc, link, window="auto", lint="off")
    st, _ = eng.run(8)
    p = str(tmp_path / "ck.npz")
    save_state(p, st, meta={"scenario": sc.name})
    # clean round trip still works (and the digests verified)
    s2, meta = load_state(p, eng.init_state())
    assert_states_equal(st, s2, "checkpoint round trip")
    # tamper one state array on disk, keep the recorded shas: the
    # load must die naming file, leaf, and both digests
    z = dict(np.load(p))
    a = z["leaf_2"].copy()
    a.reshape(-1)[0] ^= 1
    z["leaf_2"] = a
    np.savez(p, **z)
    with pytest.raises(ValueError) as ei:
        load_state(p, eng.init_state())
    msg = str(ei.value)
    assert "leaf 2" in msg and "sha256" in msg and p in msg
    assert "expected" in msg and "actual" in msg


def test_sweep_flip_journals_violation_and_recovers(tmp_path):
    from timewarp_tpu.sweep.service import SweepService
    from timewarp_tpu.sweep.spec import solo_result
    pack = _pack()
    d = str(tmp_path / "j")
    svc = SweepService(pack, d, chunk=8, lint="off",
                       inject="flip:9:2", verify="digest",
                       backoff_us=1000)
    rep = svc.run()
    assert rep.ok, rep.to_json()
    assert "flip:2" in svc.inject.fired
    evs = [json.loads(line)
           for line in open(os.path.join(d, "journal.jsonl"))]
    kinds = [e["ev"] for e in evs]
    assert "integrity_violation" in kinds and "retry" in kinds
    # the survival law carries the detection law: every streamed
    # result bit-identical to its solo run DESPITE the rollback
    for rid, res in rep.done.items():
        assert solo_result(pack.by_id(rid), lint="off") == res, rid
    # the journal scan surfaces the violation (sweep status's source)
    scan = svc.journal.scan()
    assert scan.integrity and scan.integrity[0]["bucket"]
    # and the bucket checkpoints are verified epochs: meta carries
    # the per-world state digests + chain
    import glob
    cks = glob.glob(os.path.join(d, "bucket-*.npz"))
    assert cks
    with np.load(cks[0]) as z:
        meta = json.loads(bytes(z["__meta__"].tobytes()).decode())
    assert "state_digests" in meta and "verify_chain" in meta
    assert len(meta["state_digests"]) == len(meta["verify_chain"])


def test_sweep_kill_resume_straddles_the_rollback(tmp_path):
    from timewarp_tpu.sweep.service import SweepKilled, SweepService
    from timewarp_tpu.sweep.spec import solo_result
    pack = _pack()
    d = str(tmp_path / "j2")
    svc = SweepService(pack, d, chunk=8, lint="off",
                       inject="flip:8:2;die:3", verify="digest",
                       backoff_us=1000)
    with pytest.raises(SweepKilled):
        svc.run()
    svc2 = SweepService.resume(d, chunk=8, lint="off",
                               verify="digest")
    rep = svc2.run()
    assert rep.ok, rep.to_json()
    for rid, res in rep.done.items():
        assert solo_result(pack.by_id(rid), lint="off") == res, rid


def test_run_quiet_final_state_guard_is_not_silent():
    # the traceless driver must not run a verify engine unverified:
    # a negative-counter corruption surfaces from run_quiet too
    sc, link = _gossip()
    eng = JaxEngine(sc, link, window="auto", lint="off",
                    verify="guard")
    st, _ = eng.run(4)
    clean = eng.run_quiet(6, state=st)           # clean passes
    assert int(clean.steps) >= int(st.steps)
    bad = st._replace(delivered=jnp.int64(-1_000_000))
    with pytest.raises(IntegrityViolation, match="delivered"):
        eng.run_quiet(6, state=bad)


def test_rollback_never_reanchors_on_corrupt_snapshot(monkeypatch):
    """In-place corruption (HBM bit rot) hits the live state AND the
    in-memory snapshot's shared buffers: rollback must verify the
    restored snapshot against the RECORDED digest and ESCALATE on
    mismatch — never silently adopt the corrupt snapshot as the new
    baseline (which would report a 'recovered' run with wrong
    results). Simulated by poisoning the digest view after the first
    verified epoch: the entry check fires, and the restored snapshot
    then fails its own record."""
    sc, link = _gossip()
    eng = JaxEngine(sc, link, window="auto", lint="off",
                    verify="digest")
    real = eng._state_digests
    calls = {"n": 0}

    def poisoned(state):
        calls["n"] += 1
        d = np.array(real(state))
        # calls: 1 = init record, 2 = chunk-0 entry, 3 = chunk-0
        # commit record; from chunk-1's entry on, every digest of the
        # resident state has moved (the in-place-rot view) — entry
        # mismatches the clean record, and so does the restored
        # snapshot
        if calls["n"] >= 4:
            d ^= np.uint32(1)
        return d
    monkeypatch.setattr(eng, "_state_digests", poisoned)
    with pytest.raises(IntegrityViolation, match="snapshot"):
        eng.run_verified(BUDGET, chunk=CHUNK)
    # exactly one rollback was attempted before escalation
    assert calls["n"] >= 4






def test_run_verified_emits_valid_integrity_metrics(tmp_path):
    from timewarp_tpu.obs.metrics import (MetricsRegistry,
                                          validate_metrics_file)
    sc, link = _gossip()
    eng = JaxEngine(sc, link, window="auto", lint="off",
                    verify="digest")
    path = str(tmp_path / "m.jsonl")
    eng.metrics = MetricsRegistry(path=path, run="integrity-test")
    inj = FlipInjector("flip:7:2")
    eng.run_verified(BUDGET, chunk=CHUNK, inject=inj)
    eng.metrics.close()
    assert validate_metrics_file(path) > 0
    kinds = [json.loads(line)["kind"] for line in open(path)]
    assert "integrity" in kinds
    events = [json.loads(line).get("event") for line in open(path)
              if json.loads(line)["kind"] == "integrity"]
    assert "rollback" in events and "verified" in events
