"""The state-integrity detection law (integrity/, ISSUE 10): every
injected ``flip:`` is detected within the configured cadence, and the
rolled-back run is bit-identical on states/traces/digests/checkpoints
to an uninjected run — solo, batched world axis, under fault fleets,
and across a sweep kill/resume straddling the rollback. Plus the
zero-false-positive half (shadow cross-checks pass clean, the
verify-off jaxpr IS the pre-knob jaxpr), the pinned guard diagnostic
format, the checkpoint digest verification, and the sweep service's
journal/rollback face.

Here: the guards and the detection law. What follows a detection
(rollback limits, checkpoint digests, the sweep's journal, the metrics)
is tests/test_integrity_recovery.py.

(Named test_zzzz* to sort after test_zzz* — the tier-1 870 s window
truncates the suite, and new tests must not displace existing dots.)
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from integrity_laws import BUDGET, CHUNK, _gossip, _pack
from timewarp_tpu.integrity import FlipInjector, IntegrityViolation
from timewarp_tpu.interp.jax_engine.batched import BatchSpec
from timewarp_tpu.interp.jax_engine.edge_engine import EdgeEngine
from timewarp_tpu.interp.jax_engine.engine import JaxEngine
from timewarp_tpu.models.token_ring import token_ring
from timewarp_tpu.net.delays import FixedDelay
from timewarp_tpu.trace.events import assert_states_equal, assert_traces_equal


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
