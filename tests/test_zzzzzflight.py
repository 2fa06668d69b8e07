"""The causal flight recorder (obs/flight.py, ISSUE 11): the record
exactness law — states/traces under ``record="deliveries"|"full"``
bit-identical to ``"off"``, and the off-mode jaxpr IS the default
engine's jaxpr — plus the debugging layer built on it: divergence
bisection's pinned one-line diagnostic (obs/bisect.py), causal
queries over recorded logs (obs/query.py), the schema'd JSONL event
log (METRICS_SCHEMA v4), Perfetto flow arrows + the empty-run guard,
and the sweep-side wiring (--record, status counts, --verify
auto-bisect).

Here: the exactness law on every engine. The readers (event log,
queries, Perfetto, CLI, sweep) are tests/test_flight_queries.py, the
bisection tests/test_flight_bisect.py (tests/flight_laws.py has what
the three share).

(Named test_zzzzz* to sort after the whole existing suite — the
tier-1 window truncates, and new tests must not displace existing
dots.)
"""

import pytest

import jax

from flight_laws import STEPS, _gossip, _ring, _steady_faulted
from timewarp_tpu.interp.jax_engine.batched import BatchSpec
from timewarp_tpu.interp.jax_engine.edge_engine import EdgeEngine
from timewarp_tpu.interp.jax_engine.engine import JaxEngine
from timewarp_tpu.obs.flight import EV_DELIVER, EV_FAULT, EV_SEND
from timewarp_tpu.trace.events import assert_states_equal, assert_traces_equal


def test_general_engine_record_modes_bit_identical():
    sc, link = _gossip()
    off = JaxEngine(sc, link, window="auto", lint="off")
    f0, t0 = off.run(STEPS)
    assert off.last_run_flight is None
    for mode in ("deliveries", "full"):
        eng = JaxEngine(sc, link, window="auto", lint="off",
                        record=mode)
        f1, t1 = eng.run(STEPS)
        assert_traces_equal(t0, t1, "off", mode)
        assert_states_equal(f0, f1, f"record={mode}")
        log = eng.last_run_flight
        assert log is not None and log.dropped == 0
        # honesty: one deliver event per delivered message
        deliv = int((log.kind == EV_DELIVER).sum())
        assert deliv == int(t1.recv_count.sum())
        # the quiet driver is record-free by contract, same emulation
        assert_states_equal(off.run_quiet(STEPS),
                            eng.run_quiet(STEPS),
                            f"run_quiet record={mode}")
    # full mode adds sends for every sent message
    assert int((log.kind == EV_SEND).sum()) \
        == int(t1.sent_count.sum())


def test_record_off_jaxpr_is_the_default_jaxpr():
    sc, link = _gossip()
    default = JaxEngine(sc, link, window="auto", lint="off")
    off = JaxEngine(sc, link, window="auto", lint="off", record="off")
    on = JaxEngine(sc, link, window="auto", lint="off",
                   record="deliveries")
    jx = [str(jax.make_jaxpr(lambda s, e=e: e._step_all(s, True))(
        e.init_state())) for e in (default, off, on)]
    # off == the knob never existed — equation for equation
    assert jx[1] == jx[0]
    # deliveries mode genuinely adds outputs (the law is not vacuous)
    assert jx[2] != jx[1]


def test_edge_engine_record_modes_bit_identical():
    sc, link = _ring()
    off = EdgeEngine(sc, link, lint="off")
    f0, t0 = off.run(STEPS)
    for mode in ("deliveries", "full"):
        eng = EdgeEngine(sc, link, lint="off", record=mode)
        f1, t1 = eng.run(STEPS)
        assert_traces_equal(t0, t1, "off", f"edge {mode}")
        assert_states_equal(f0, f1, f"edge record={mode}")
        log = eng.last_run_flight
        assert int((log.kind == EV_DELIVER).sum()) \
            == int(t1.recv_count.sum())


def test_faulted_record_modes_bit_identical_and_actions():
    sc, link, faults = _steady_faulted()
    off = JaxEngine(sc, link, lint="off", faults=faults)
    f0, t0 = off.run(60)
    eng = JaxEngine(sc, link, lint="off", faults=faults,
                    record="full", record_cap=1024)
    f1, t1 = eng.run(60)
    assert_traces_equal(t0, t1, "off", "full+faults")
    assert_states_equal(f0, f1, "faulted record")
    log = eng.last_run_flight
    assert log.dropped == 0
    from timewarp_tpu.obs.flight import (TAG_CUT, TAG_DEFER, TAG_DOWN)
    tags = set(log.tag[log.kind == EV_FAULT].tolist())
    # the schedule's three fault forms all leave provenance
    assert {TAG_DEFER, TAG_CUT, TAG_DOWN} <= tags


def test_batched_record_worlds_match_solo():
    sc, link = _gossip()
    spec = BatchSpec(seeds=(0, 1, 2))
    off = JaxEngine(sc, link, window="auto", lint="off", batch=spec)
    f0, tr0 = off.run(STEPS)
    eng = JaxEngine(sc, link, window="auto", lint="off", batch=spec,
                    record="full")
    f1, tr1 = eng.run(STEPS)
    for b in range(3):
        assert_traces_equal(tr0[b], tr1[b], "off", f"full w{b}")
    assert_states_equal(f0, f1, "batched record")
    logs = eng.last_run_flight
    assert isinstance(logs, list) and len(logs) == 3
    # batch exactness extends to the event plane: world b's log is
    # the solo run's log, event for event
    for b in (0, 2):
        solo = JaxEngine(sc, link, window="auto", lint="off", seed=b,
                         record="full")
        solo.run(STEPS)
        assert logs[b].keyset() == solo.last_run_flight.keyset(), \
            f"world {b} event plane != solo"


def test_sharded_batched_record_worlds_match_solo():
    # the fourth carrying engine (docs/engines.md matrix): the
    # [T, B_local, R] event planes gather over the world axis like
    # any trace leaf, and each world decodes to the solo run's log
    from timewarp_tpu.interp.jax_engine.sharded import (
        ShardedBatchedEngine, make_mesh)
    sc, link = _gossip()
    mesh = make_mesh(2, axis="worlds")
    spec = BatchSpec(seeds=(0, 1))
    off = ShardedBatchedEngine(sc, link, mesh, batch=spec,
                               window="auto", lint="off")
    f0, tr0 = off.run(16)
    eng = ShardedBatchedEngine(sc, link, mesh, batch=spec,
                               window="auto", lint="off",
                               record="full")
    f1, tr1 = eng.run(16)
    for b in range(2):
        assert_traces_equal(tr0[b], tr1[b], "off", f"record w{b}")
    assert_states_equal(f0, f1, "sharded-batched record")
    logs = eng.last_run_flight
    assert isinstance(logs, list) and len(logs) == 2
    for b in range(2):
        solo = JaxEngine(sc, link, window="auto", lint="off", seed=b,
                         record="full")
        solo.run(16)
        assert logs[b].keyset() == solo.last_run_flight.keyset(), \
            f"sharded world {b} event plane != solo"


def test_record_cap_overflow_counted_never_silent():
    sc, link = _gossip()
    eng = JaxEngine(sc, link, window="auto", lint="off",
                    record="full", record_cap=2)
    _, t1 = eng.run(STEPS)
    log = eng.last_run_flight
    assert log.dropped > 0                      # counted
    assert len(log) <= 2 * len(t1)              # bounded by the cap
    # the bounded log is still bit-exact emulation
    off = JaxEngine(sc, link, window="auto", lint="off")
    assert_traces_equal(off.run(STEPS)[1], t1, "off", "cap=2")


def test_record_knob_validated_loudly():
    sc, link = _gossip()
    with pytest.raises(ValueError, match="record must be one of"):
        JaxEngine(sc, link, lint="off", record="Deliveries")
    with pytest.raises(ValueError, match="record_cap"):
        JaxEngine(sc, link, lint="off", record="full", record_cap=0)


def test_verified_driver_carries_the_record_plane():
    # run_verified (integrity/runner.py) drains only VERIFIED chunks
    # and still assembles the whole-run log
    sc, link = _gossip()
    eng = JaxEngine(sc, link, window="auto", lint="off",
                    verify="digest", record="deliveries")
    _, tr = eng.run_verified(STEPS, chunk=8)
    log = eng.last_run_flight
    assert int((log.kind == EV_DELIVER).sum()) \
        == int(tr.recv_count.sum())
    assert eng.last_run_integrity["rollbacks"] == 0
