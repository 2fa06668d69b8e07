"""The state-integrity detection law (integrity/, ISSUE 10): every
injected ``flip:`` is detected within the configured cadence, and the
rolled-back run is bit-identical on states/traces/digests/checkpoints
to an uninjected run — solo, batched world axis, under fault fleets,
and across a sweep kill/resume straddling the rollback. Plus the
zero-false-positive half (shadow cross-checks pass clean, the
verify-off jaxpr IS the pre-knob jaxpr), the pinned guard diagnostic
format, the checkpoint digest verification, and the sweep service's
journal/rollback face.

(Named test_zzzz* to sort after test_zzz* — the tier-1 870 s window
truncates the suite, and new tests must not displace existing dots.)
"""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from timewarp_tpu.integrity import (FlipInjector, IntegrityViolation,
                                    apply_flip)
from timewarp_tpu.interp.jax_engine.batched import BatchSpec
from timewarp_tpu.interp.jax_engine.edge_engine import EdgeEngine
from timewarp_tpu.interp.jax_engine.engine import JaxEngine
from timewarp_tpu.models.gossip import gossip
from timewarp_tpu.models.token_ring import token_ring
from timewarp_tpu.net.delays import FixedDelay, Quantize, UniformDelay
from timewarp_tpu.trace.events import (assert_states_equal,
                                       assert_traces_equal)

N = 40
BUDGET = 50
CHUNK = 8


def _gossip():
    sc = gossip(N, fanout=3, burst=True, end_us=150_000,
                mailbox_cap=16)
    return sc, Quantize(UniformDelay(3000, 9000), 1000)


def _ring():
    sc = token_ring(16, n_tokens=4, think_us=2000,
                    bootstrap_us=1000, end_us=120_000,
                    with_observer=False, mailbox_cap=8)
    return sc, FixedDelay(500)


def _recovered_equal(clean_eng, injected_eng, flip_spec, **kw):
    """The law's core assertion: run both engines through the
    verified driver, flip only the second, and demand detection plus
    bit-identical recovery (states, traces, digest chains)."""
    fc, tc = clean_eng.run_verified(BUDGET, chunk=CHUNK, **kw)
    inj = FlipInjector(flip_spec)
    fi, ti = injected_eng.run_verified(BUDGET, chunk=CHUNK,
                                       inject=inj, **kw)
    assert inj.fired, "flip never fired — fewer than 2 chunks ran"
    ri = injected_eng.last_run_integrity
    assert ri["rollbacks"] >= 1 and ri["violations"], \
        f"injected flip went UNDETECTED ({inj.desc})"
    if isinstance(tc, list):
        for b in range(len(tc)):
            assert_traces_equal(tc[b], ti[b], "clean", f"recovered w{b}")
    else:
        assert_traces_equal(tc, ti, "clean", "recovered")
    assert_states_equal(fc, fi, "detection-law recovery")
    assert clean_eng.last_run_stats["digest_chain"] \
        == injected_eng.last_run_stats["digest_chain"]
    return fc, fi


# ---------------------------------------------------------------------------
# off mode is ABSENT, not cheap (the telemetry pin's integrity twin)
# ---------------------------------------------------------------------------

def test_verify_off_jaxpr_is_the_default_jaxpr():
    sc, link = _gossip()
    default = JaxEngine(sc, link, window="auto", lint="off")
    off = JaxEngine(sc, link, window="auto", lint="off", verify="off")
    guard = JaxEngine(sc, link, window="auto", lint="off",
                      verify="guard")
    jx_default = str(jax.make_jaxpr(
        lambda s: default._step_all(s, True))(default.init_state()))
    jx_off = str(jax.make_jaxpr(
        lambda s: off._step_all(s, True))(off.init_state()))
    jx_guard = str(jax.make_jaxpr(
        lambda s: guard._step_all(s, True))(guard.init_state()))
    assert jx_off == jx_default
    assert jx_guard != jx_off      # the law is not vacuous


def test_verify_knob_validated_loudly():
    sc, link = _gossip()
    with pytest.raises(ValueError, match="verify must be one of"):
        JaxEngine(sc, link, lint="off", verify="Guard")
    with pytest.raises(ValueError, match="verify must be one of"):
        EdgeEngine(*_ring(), lint="off", verify="on")


def test_fused_ring_refuses_verify_with_guidance():
    from timewarp_tpu.interp.jax_engine.fused_ring import \
        FusedRingEngine
    sc = token_ring(8192, n_tokens=8192, think_us=0,
                    bootstrap_us=1000, end_us=1 << 50,
                    with_observer=False, mailbox_cap=4)
    with pytest.raises(ValueError, match="EdgeEngine"):
        FusedRingEngine(sc, FixedDelay(500), verify="guard", interpret=True)


# ---------------------------------------------------------------------------
# zero false positives: guard/digest/shadow clean runs ≡ off
# ---------------------------------------------------------------------------

def test_guard_clean_run_bit_identical_to_off():
    sc, link = _gossip()
    f0, t0 = JaxEngine(sc, link, window="auto", lint="off").run(30)
    f1, t1 = JaxEngine(sc, link, window="auto", lint="off",
                       verify="guard").run(30)
    assert_traces_equal(t0, t1, "off", "guard")
    assert_states_equal(f0, f1, "guard clean")


@pytest.mark.parametrize("make", [
    lambda: JaxEngine(*_gossip(), window="auto", lint="off",
                      verify="shadow"),
    lambda: EdgeEngine(*_ring(), lint="off", verify="shadow"),
], ids=["general-gossip", "edge-ring"])
def test_shadow_cross_check_zero_false_positives(make):
    eng = make()
    fs, _ = eng.run_verified(BUDGET, chunk=CHUNK)
    ri = eng.last_run_integrity
    assert ri["rollbacks"] == 0 and not ri["violations"], ri
    assert ri["checks"] > 0
    # and the verified run IS the plain run, bit for bit
    ref = type(eng)(*(_gossip() if isinstance(eng, JaxEngine)
                      and not isinstance(eng, EdgeEngine)
                      else _ring()),
                    **({"window": "auto"} if isinstance(eng, JaxEngine)
                       and not isinstance(eng, EdgeEngine) else {}),
                    lint="off")
    f_ref, _ = ref.run(BUDGET)
    assert_states_equal(f_ref, fs, "shadow ≡ plain run")


# ---------------------------------------------------------------------------
# guard: the pinned diagnostic format (the TraceMismatch contract)
# ---------------------------------------------------------------------------

def test_guard_names_superstep_and_field_never_arrays():
    sc, link = _gossip()
    eng = JaxEngine(sc, link, window="auto", lint="off",
                    verify="guard")
    st, _ = eng.run(4)
    bad = st._replace(delivered=jnp.int64(-1_000_000))
    with pytest.raises(IntegrityViolation) as ei:
        eng.run(6, state=bad)
    msg = str(ei.value)
    assert "superstep 0" in msg and "t=" in msg
    assert "neg_counter" in msg and "verify=guard" in msg
    assert len(msg) < 300 and "\n" not in msg
    assert "array(" not in msg and "[" not in msg


def test_guard_detects_time_regression():
    sc, link = _gossip()
    eng = JaxEngine(sc, link, window="auto", lint="off",
                    verify="guard")
    st, _ = eng.run(4)
    bad = st._replace(time=st.time + (jnp.int64(1) << 40))
    with pytest.raises(IntegrityViolation, match="time_regress"):
        eng.run(6, state=bad)


def test_edge_guard_detects_negative_counter():
    eng = EdgeEngine(*_ring(), lint="off", verify="guard")
    st, _ = eng.run(5)
    bad = st._replace(delivered=jnp.int64(-1_000_000))
    with pytest.raises(IntegrityViolation, match="neg_counter"):
        eng.run(6, state=bad)


# ---------------------------------------------------------------------------
# the detection law: flip -> detected -> bit-exact rollback recovery
# ---------------------------------------------------------------------------

def test_detection_law_solo():
    sc, link = _gossip()
    _recovered_equal(
        JaxEngine(sc, link, window="auto", lint="off", verify="digest"),
        JaxEngine(sc, link, window="auto", lint="off", verify="digest"),
        "flip:7:2:mb_rel")


def test_detection_law_edge_engine():
    _recovered_equal(
        EdgeEngine(*_ring(), lint="off", verify="digest"),
        EdgeEngine(*_ring(), lint="off", verify="digest"),
        "flip:3:2:q_rel")


def test_detection_law_batched_world_axis():
    sc, link = _gossip()
    spec = BatchSpec(seeds=(0, 7))

    def make():
        return JaxEngine(sc, link, window="auto", lint="off",
                         batch=spec, verify="digest")
    _recovered_equal(make(), make(), "flip:11:2")


def test_detection_law_under_fault_fleet():
    """Rollback × faults (ISSUE 10 satellite): a flip landing inside
    a crash/restart window and inside a degradation window must
    recover bit-identically — the restored restart_done and
    fault_dropped ledgers are part of the verified state
    (assert_states_equal covers every field)."""
    from timewarp_tpu.faults.schedule import FaultFleet, parse_faults
    sc, link = _gossip()
    spec = BatchSpec(seeds=(0, 5))
    fleet = FaultFleet((
        parse_faults("crash:2:20ms:60ms:reset"),
        parse_faults("degrade:all:all:20ms:60ms:2.0"),
    ))

    def make():
        return JaxEngine(sc, link, window="auto", lint="off",
                         batch=spec, faults=fleet, verify="digest")
    # chunk 3 of CHUNK=8 supersteps sits inside the 20-60 ms windows
    # (~8 ms/superstep); flip the restart ledger itself in one leg
    # and a mailbox plane in the other
    fc, fi = _recovered_equal(make(), make(), "flip:5:3:restart_done")
    assert int(np.asarray(fc.fault_dropped).sum()) > 0 \
        or int(np.asarray(fc.restart_done).sum()) > 0, \
        "fault schedule never bit — the interaction case is vacuous"
    _recovered_equal(make(), make(), "flip:9:3:mb_payload")


def test_detection_law_with_sparse_shadow_cadence():
    """cadence > 1 gates only the expensive shadow re-execution; the
    cheap digest entry check still runs EVERY chunk — a flip landing
    on a non-shadow-sampled chunk must be detected at that chunk's
    own entry, never absorbed (integrity/runner.py: gating the
    digest check would let corruption launder into the next recorded
    digest)."""
    sc, link = _gossip()

    def make():
        return JaxEngine(sc, link, window="auto", lint="off",
                         verify="shadow")
    clean, injected = make(), make()
    fc, tc = clean.run_verified(BUDGET, chunk=4, cadence=2)
    inj = FlipInjector("flip:13:2:mb_src")   # chunk idx 1: unsampled
    fi, ti = injected.run_verified(BUDGET, chunk=4, cadence=2,
                                   inject=inj)
    assert inj.fired
    ri = injected.last_run_integrity
    assert ri["rollbacks"] >= 1
    assert ri["violations"][0]["kind"] == "entry_digest"
    assert_traces_equal(tc, ti, "clean", "recovered")
    assert_states_equal(fc, fi, "cadence-2 recovery")


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


# ---------------------------------------------------------------------------
# checkpoint digest verification (utils/checkpoint.py satellite)
# ---------------------------------------------------------------------------

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


# ---------------------------------------------------------------------------
# the sweep service face: journal + rollback + kill/resume straddle
# ---------------------------------------------------------------------------

def _pack():
    from timewarp_tpu.sweep.spec import SweepPack
    return SweepPack.from_json([
        {"id": "r0", "scenario": "token-ring",
         "params": {"nodes": 16, "n_tokens": 2, "think_us": 2000,
                    "end_us": 60000, "mailbox_cap": 8},
         "link": "uniform:1000:5000", "seed": 0, "budget": 40},
        {"id": "g0", "scenario": "gossip",
         "params": {"nodes": 24, "fanout": 3, "burst": True,
                    "end_us": 100000, "mailbox_cap": 16},
         "link": "quantize:1000:uniform:3000:9000", "seed": 1,
         "window": "auto", "budget": 50},
    ])


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


def test_sweep_refuses_shadow_mode_loudly():
    from timewarp_tpu.sweep.service import SweepService
    with pytest.raises(ValueError, match="run_verified"):
        SweepService(_pack(), "/tmp/never-used", verify="shadow")


def test_sweep_refuses_flip_without_digest_verify():
    # a flip the entry-digest check cannot see would corrupt streamed
    # results SILENTLY — refused loudly, mirroring the solo CLI guard
    from timewarp_tpu.sweep.service import SweepService
    for verify in ("off", "guard"):
        with pytest.raises(ValueError, match="state-verify digest"):
            SweepService(_pack(), "/tmp/never-used",
                         inject="flip:3:2", verify=verify)


def test_duplicate_flip_chunk_refused():
    from timewarp_tpu.sweep.service import InjectPlan
    from timewarp_tpu.sweep.spec import SweepConfigError
    with pytest.raises(SweepConfigError, match="duplicate flip"):
        InjectPlan("flip:3;flip:5")   # both default to chunk call 1


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


# ---------------------------------------------------------------------------
# observability: the integrity metrics kind
# ---------------------------------------------------------------------------

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
