"""Optimistic time-warp execution: the speculation laws (ISSUE 12).

Pins, in one place (named to sort after the whole suite — the 870 s
tier-1 window truncates from the END, so these must not displace
existing dots):

- the **equivalence law**: a committed speculative run is
  event-identical to the conservative run — bit-for-bit equal on the
  canonical surface (speculate/equiv.py: scenario-visible final
  state, never-silent counters, granularity-invariant trace
  aggregates) — solo, batched worlds, under fault fleets (degrade
  windows clamp the speculative horizon on-device), and across sweep
  kill/resume straddling a rollback;
- the **detection law**: every forced misspeculation is detected,
  the diagnostic is the pinned one-liner (superstep + committed
  horizon + offending delivery time, never arrays), and recovery is
  bit-identical;
- the **replay law**: replaying the emitted decision trace is
  bit-identical on states, traces, and digest chains;
- the **zero-overhead contract**: ``speculate="off"`` lowers a
  byte-identical jaxpr;
- the **rollback × streaming contract**: a rolled-back chunk never
  double-fires a quiesce callback or journals a duplicate
  ``world_done`` (run_speculative, run_verified, and the sweep).

The equivalence law over worlds (batched, under a fault fleet) and the
sweep's half of the last contract are tests/test_spec_fleets.py.
"""

import pytest

import jax

from spec_laws import BUDGET, _sc, _tail_link
from timewarp_tpu.interp.jax_engine.batched import BatchSpec
from timewarp_tpu.interp.jax_engine.engine import JaxEngine
from timewarp_tpu.speculate import (SpeculationViolation, assert_spec_equiv,
                                    canonical_rows)
from timewarp_tpu.trace.events import assert_states_equal


@pytest.fixture(scope="module")
def conservative():
    """The law's right-hand side, computed once: the conservative
    (widest provable static window) run of the shared config."""
    eng = JaxEngine(_sc(), _tail_link(), window="auto", lint="off")
    assert eng.window == 500        # the quantize grid IS the floor
    fin, tr = eng.run(BUDGET)
    assert int(fin.overflow) == 0   # inside the exactness regime
    return fin, tr






def test_equivalence_law_solo(conservative):
    cfin, ctr = conservative
    eng = JaxEngine(_sc(), _tail_link(), window="auto", lint="off",
                    speculate="auto")
    assert eng.spec_floor == 500
    sfin, strc = eng.run_speculative(BUDGET, chunk=16)
    assert_spec_equiv(canonical_rows(cfin, ctr),
                      canonical_rows(sfin, strc), "solo")
    # the win is structural and deterministic: committed wide windows
    # coalesce instants the 500 µs floor serializes
    assert len(strc) < len(ctr)
    si = eng.last_run_speculation
    assert si["chunks"] > 0 and si["floor_us"] == 500
    assert max(si["windows"]) > 500


def test_forced_misspeculation_detected_and_recovered(conservative):
    cfin, ctr = conservative
    # fixed:16000 over a link whose samples start at 4000: the first
    # message-bearing chunk MUST violate — detection + bit-identical
    # recovery at the floor
    eng = JaxEngine(_sc(), _tail_link(), window="auto", lint="off",
                    speculate="fixed:16000")
    sfin, strc = eng.run_speculative(BUDGET, chunk=16)
    si = eng.last_run_speculation
    assert si["rollbacks"] >= 1, "forced misspeculation never fired"
    assert si["violations"][0]["window_us"] == 16000
    # after the rollback the fixed bet is burned: everything commits
    # at the conservative floor
    assert si["windows"] == [500]
    roll = [d for d in eng.last_run_decisions
            if d.obs.get("rolled_back")]
    assert roll and roll[0].obs["tried_us"] == 16000
    assert_spec_equiv(canonical_rows(cfin, ctr),
                      canonical_rows(sfin, strc), "recovery")


def test_pinned_violation_diagnostic():
    eng = JaxEngine(_sc(), _tail_link(), window="auto", lint="off",
                    speculate="fixed:16000")
    with pytest.raises(SpeculationViolation) as ei:
        eng.run(BUDGET)     # a plain run surfaces it — loud, unhandled
    msg = str(ei.value)
    assert "\n" not in msg and "[" not in msg, \
        f"diagnostic is not one array-free line: {msg!r}"
    for needle in ("superstep", "committed horizon",
                   "flew shorter than the effective window",
                   "offending delivery", "docs/speculation.md"):
        assert needle in msg, f"{needle!r} missing from: {msg}"
    hit = ei.value.hit
    assert hit["count"] >= 1
    # the decoded hit carries the scalars every sink shares
    from timewarp_tpu.speculate import hit_scalars
    assert set(hit_scalars(hit)) >= {"superstep", "horizon",
                                     "straggler", "count"}


def test_run_quiet_never_silently_misspeculates():
    eng = JaxEngine(_sc(), _tail_link(), window="auto", lint="off",
                    speculate="fixed:16000")
    with pytest.raises(SpeculationViolation) as ei:
        eng.run_quiet(BUDGET)
    assert "short_delay" in str(ei.value)


def test_floor_violation_names_the_lying_link():
    # a link whose declared floor overstates its samples: UniformDelay
    # declares lo, but wrap it so the declaration lies
    from timewarp_tpu.net.delays import FnDelay

    class Liar(FnDelay):
        @property
        def min_delay_us(self):
            return 2_000        # samples are 100 µs — a false promise

        @property
        def can_drop(self):
            return False

    import jax.numpy as jnp
    liar = Liar(lambda s, d, t, k: (jnp.full(jnp.shape(d), 100,
                                             jnp.int64),
                                    jnp.zeros(jnp.shape(d), bool)))
    eng = JaxEngine(_sc(), liar, window="auto", lint="off",
                    speculate="auto")
    with pytest.raises(SpeculationViolation) as ei:
        eng.run_speculative(BUDGET, chunk=16)
    assert "conservative floor" in str(ei.value) \
        and "min_delay_us" in str(ei.value)






def test_replay_law_bit_identical_including_rollbacks():
    from timewarp_tpu.dispatch import DecisionTrace
    from timewarp_tpu.sweep.spec import DIGEST_ZERO, chain_digest
    sc, link = _sc(), _tail_link()
    a = JaxEngine(sc, link, window="auto", lint="off",
                  speculate="fixed:16000")
    afin, atr = a.run_speculative(BUDGET, chunk=16)
    assert a.last_run_speculation["rollbacks"] >= 1
    trace = DecisionTrace.of(a.last_run_decisions)
    b = JaxEngine(sc, link, window="auto", lint="off",
                  speculate="fixed:16000")
    bfin, btr = b.run_speculative(BUDGET, chunk=16, replay=trace)
    # LITERAL bit-identity — granularity included (same windows, same
    # chunking), and the committed chain replays with ZERO rollbacks
    assert b.last_run_speculation["rollbacks"] == 0
    assert_states_equal(afin, bfin, "speculation replay law")
    assert len(atr) == len(btr)
    assert all(atr.row(i) == btr.row(i) for i in range(len(atr)))
    assert chain_digest(DIGEST_ZERO, atr) \
        == chain_digest(DIGEST_ZERO, btr)


def test_auto_ladder_never_reproposes_a_violated_width():
    # a width that committed cleanly ONCE but violated LATER is a
    # ceiling, not a clean mark: stragglers are stochastic, so the
    # ladder must descend below it instead of paying a rollback every
    # time the distribution produces a short sample
    from timewarp_tpu.speculate.policy import SpeculationPolicy

    class Eng:
        spec_floor, window = 500, 1 << 20
    p = SpeculationPolicy(mode="auto", chunk=16)
    p.begin(Eng())
    assert p.decide(0, None, 0)[0].window_us == 1000
    assert p.decide(1, None, 0)[0].window_us == 2000   # 1000 clean
    p.rollback(1, {"count": 1})                        # 2000 violated
    assert p.made[1].window_us == 500                  # floor commit
    # 2000 committed cleanly NOWHERE below the ceiling now — every
    # later proposal stays strictly under it
    for ci in range(2, 8):
        w = p.decide(ci, None, 0)[0].window_us
        assert w < 2000, f"chunk {ci} re-proposed {w}"
    # and the late-violation case: a width clean at chunk 0 that
    # violates later must also become a ceiling
    p2 = SpeculationPolicy(mode="auto", chunk=16)
    p2.begin(Eng())
    p2.decide(0, None, 0)                              # 1000, clean
    p2.decide(1, None, 0)                              # 2000, clean
    p2.decide(2, None, 0)                              # 4000
    p2.rollback(2, {})                                 # 4000 violated
    p2.decide(3, None, 0)                              # hold at 2000
    p2.rollback(3, {})          # ...but 2000 violates later too
    for ci in range(4, 8):
        w = p2.decide(ci, None, 0)[0].window_us
        assert w < 2000, f"chunk {ci} re-proposed the violated {w}"






def test_speculate_off_jaxpr_byte_identical():
    sc, link = _sc(), _tail_link()
    e0 = JaxEngine(sc, link, window="auto", lint="off")
    e1 = JaxEngine(sc, link, window="auto", lint="off",
                   speculate="off")
    j0 = str(jax.make_jaxpr(lambda s: e0._superstep(s, True))(
        e0.init_state()))
    j1 = str(jax.make_jaxpr(lambda s: e1._superstep(s, True))(
        e1.init_state()))
    assert j0 == j1, "speculate='off' is not the pre-knob jaxpr"






def test_speculate_guards():
    from timewarp_tpu.dispatch import DispatchController
    sc, link = _sc(), _tail_link()
    with pytest.raises(ValueError, match="decision source"):
        JaxEngine(sc, link, window="auto", lint="off",
                  speculate="auto", telemetry="counters",
                  controller=DispatchController())
    with pytest.raises(ValueError, match="does not exceed"):
        JaxEngine(sc, link, window="auto", lint="off",
                  speculate="fixed:500")     # == the floor
    eng = JaxEngine(sc, link, window="auto", lint="off")
    with pytest.raises(ValueError, match="speculating engine"):
        eng.run_speculative(100)
    # a replayed trace recorded for a different configuration refuses
    from timewarp_tpu.dispatch.trace import (Decision,
                                             DispatchTraceError)
    spec = JaxEngine(sc, link, window="auto", lint="off",
                     speculate="fixed:8000")
    alien = [Decision(chunk=0, window_us=400, rung_pin=-1,
                      chunk_len=16)]         # below the floor
    with pytest.raises(DispatchTraceError, match="different "
                                                "configuration"):
        spec.run_speculative(100, replay=alien)






def test_on_quiesce_exactly_once_under_speculative_rollback():
    sc, link = _sc(), _tail_link()
    eng = JaxEngine(sc, link, window="auto", lint="off",
                    speculate="fixed:16000",
                    batch=BatchSpec(seeds=(0, 1)))
    calls = []
    fin, _ = eng.run_speculative(
        BUDGET, chunk=16,
        on_quiesce=lambda b, st: calls.append(b))
    assert eng.last_run_speculation["rollbacks"] >= 1
    assert sorted(calls) == [0, 1], \
        f"quiesce callback fired {calls} — must be exactly once per " \
        "world, rollbacks notwithstanding"


def test_on_quiesce_exactly_once_under_verified_rollback():
    from timewarp_tpu.integrity import FlipInjector
    from timewarp_tpu.net.delays import UniformDelay
    sc = _sc()
    eng = JaxEngine(sc, UniformDelay(1000, 5000), lint="off",
                    verify="digest", batch=BatchSpec(seeds=(0, 1)))
    calls = []
    inj = FlipInjector("flip:7:2")
    fin, _ = eng.run_verified(
        BUDGET, chunk=16, inject=inj,
        on_quiesce=lambda b, st: calls.append(b))
    assert inj.fired and eng.last_run_integrity["rollbacks"] >= 1
    assert sorted(calls) == [0, 1], \
        f"quiesce callback fired {calls} under a verified rollback"
