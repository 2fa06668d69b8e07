"""The telemetry exactness law (obs/, ISSUE 7): for every engine,
digests/traces/states under ``telemetry="counters"|"full"`` are
bit-identical to ``"off"``, and the off-mode jaxpr contains no
telemetry ops (it IS the default engine's jaxpr). Plus the host side:
frames decode, metrics schema, Perfetto export, the uniform
``last_run_stats``, the CLI surface, and the sweep service's
utilization records.

Here: the law and the frames. The registry, the Perfetto export, the
CLI and the sweep are tests/test_telemetry_surfaces.py.

(Named test_zz* to sort after the whole existing suite — the tier-1
window truncates, and new tests must not displace existing dots.)
"""

import numpy as np
import pytest

import jax

from timewarp_tpu.interp.jax_engine.batched import BatchSpec
from timewarp_tpu.interp.jax_engine.edge_engine import EdgeEngine
from timewarp_tpu.interp.jax_engine.engine import JaxEngine
from timewarp_tpu.models.gossip import gossip
from timewarp_tpu.models.token_ring import token_ring
from timewarp_tpu.net.delays import FixedDelay, Quantize, UniformDelay
from timewarp_tpu.trace.events import assert_states_equal, assert_traces_equal


N = 48


def _gossip():
    sc = gossip(N, fanout=3, burst=True, end_us=150_000,
                mailbox_cap=16)
    return sc, Quantize(UniformDelay(3000, 9000), 1000)


STEPS = 30


def _ring():
    # bootstrap_us must undercut end_us or the ring quiesces after
    # the bootstrap superstep (the default bootstrap is 1 s)
    sc = token_ring(16, n_tokens=4, think_us=2000,
                    bootstrap_us=1000, end_us=120_000,
                    with_observer=False, mailbox_cap=8)
    return sc, FixedDelay(500)






def test_general_engine_modes_bit_identical():
    sc, link = _gossip()
    off = JaxEngine(sc, link, window="auto", lint="off")
    f0, t0 = off.run(STEPS)
    for mode in ("counters", "full"):
        eng = JaxEngine(sc, link, window="auto", lint="off",
                        telemetry=mode)
        f1, t1 = eng.run(STEPS)
        assert_traces_equal(t0, t1, "off", mode)
        assert_states_equal(f0, f1, f"telemetry={mode}")
        # the quiet driver too (no rows there, but the program must
        # still be the same emulation)
        assert_states_equal(off.run_quiet(STEPS),
                            eng.run_quiet(STEPS),
                            f"run_quiet telemetry={mode}")


def test_edge_engine_modes_bit_identical():
    sc, link = _ring()
    off = EdgeEngine(sc, link, lint="off")
    f0, t0 = off.run(STEPS)
    for mode in ("counters", "full"):
        eng = EdgeEngine(sc, link, lint="off", telemetry=mode)
        f1, t1 = eng.run(STEPS)
        assert_traces_equal(t0, t1, "off", mode)
        assert_states_equal(f0, f1, f"edge telemetry={mode}")


def test_batched_modes_bit_identical_per_world():
    sc, link = _gossip()
    spec = BatchSpec(seeds=(0, 1, 2))
    off = JaxEngine(sc, link, window="auto", lint="off", batch=spec)
    f0, tr0 = off.run(STEPS)
    eng = JaxEngine(sc, link, window="auto", lint="off", batch=spec,
                    telemetry="full")
    f1, tr1 = eng.run(STEPS)
    for b in range(3):
        assert_traces_equal(tr0[b], tr1[b], "off", f"full w{b}")
    assert_states_equal(f0, f1, "batched telemetry")
    frames = eng.last_run_telemetry
    assert isinstance(frames, list) and len(frames) == 3
    for b in range(3):
        assert len(frames[b]) == len(tr1[b])


def test_sharded_edge_full_mode_bit_identical():
    # covers the mesh path of the full-mode occupancy plane
    # (MeshComm.all_max) — its only caller
    from timewarp_tpu.interp.jax_engine.sharded import (
        ShardedEdgeEngine, make_mesh)
    sc = token_ring(32, n_tokens=8, think_us=2000, bootstrap_us=1000,
                    end_us=150_000, with_observer=False,
                    mailbox_cap=8)
    mesh = make_mesh(4)
    off = ShardedEdgeEngine(sc, FixedDelay(500), mesh, lint="off")
    f0, t0 = off.run(24)
    eng = ShardedEdgeEngine(sc, FixedDelay(500), mesh, lint="off",
                            telemetry="full")
    f1, t1 = eng.run(24)
    assert len(t1) > 4, "ring quiesced too early to exercise the law"
    assert_traces_equal(t0, t1, "off", "sharded-edge full")
    assert_states_equal(f0, f1, "sharded-edge telemetry=full")
    fr = eng.last_run_telemetry
    assert (fr.data["mb_peak"] >= 0).all()
    assert (fr.data["active_senders"] <= 32).all()


def test_sharded_general_full_mode_bit_identical():
    from timewarp_tpu.interp.jax_engine.sharded import (ShardedEngine,
                                                        make_mesh)
    sc, link = _gossip()
    mesh = make_mesh(4)
    off = ShardedEngine(sc, link, mesh, window="auto", lint="off")
    f0, t0 = off.run(16)
    eng = ShardedEngine(sc, link, mesh, window="auto", lint="off",
                        telemetry="full")
    f1, t1 = eng.run(16)
    assert_traces_equal(t0, t1, "off", "sharded full")
    assert_states_equal(f0, f1, "sharded telemetry=full")


def test_sharded_batched_modes_bit_identical():
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
                               telemetry="counters")
    f1, tr1 = eng.run(16)
    for b in range(2):
        assert_traces_equal(tr0[b], tr1[b], "off", f"counters w{b}")
    assert_states_equal(f0, f1, "sharded-batched telemetry")






def test_off_mode_jaxpr_is_the_default_jaxpr():
    sc, link = _gossip()
    default = JaxEngine(sc, link, window="auto", lint="off")
    off = JaxEngine(sc, link, window="auto", lint="off",
                    telemetry="off")
    on = JaxEngine(sc, link, window="auto", lint="off",
                   telemetry="counters")
    jx_default = str(jax.make_jaxpr(
        lambda s: default._step_all(s, True))(default.init_state()))
    jx_off = str(jax.make_jaxpr(
        lambda s: off._step_all(s, True))(off.init_state()))
    jx_on = str(jax.make_jaxpr(
        lambda s: on._step_all(s, True))(on.init_state()))
    # off == the knob never existed — equation for equation
    assert jx_off == jx_default
    # counters mode genuinely adds outputs (the law is not vacuous)
    assert jx_on != jx_off
    assert off.run(8)[1].times.shape == default.run(8)[1].times.shape
    assert off.last_run_telemetry is None
    assert on.run(8) is not None and on.last_run_telemetry is not None


def test_mode_knob_validated_loudly():
    sc, link = _gossip()
    with pytest.raises(ValueError, match="telemetry must be one of"):
        JaxEngine(sc, link, lint="off", telemetry="Counters")
    with pytest.raises(ValueError, match="telemetry must be one of"):
        EdgeEngine(*_ring(), lint="off", telemetry="on")


def test_fused_ring_refuses_telemetry_with_guidance():
    from timewarp_tpu.interp.jax_engine.fused_ring import \
        FusedRingEngine
    sc = token_ring(8192, n_tokens=8192, think_us=0,
                    bootstrap_us=1000, end_us=1 << 50,
                    with_observer=False, mailbox_cap=4)
    with pytest.raises(ValueError, match="EdgeEngine"):
        FusedRingEngine(sc, FixedDelay(500), telemetry="counters",
                        interpret=True)






def test_frame_content_ranges():
    sc, link = _gossip()
    eng = JaxEngine(sc, link, window="auto", lint="off",
                    telemetry="full")
    _, trace = eng.run(STEPS)
    fr = eng.last_run_telemetry
    assert len(fr) == len(trace)
    a = fr.data["active_senders"]
    assert (a >= 0).all() and (a <= N).all()
    # single-chip windowed gossip runs the adaptive ladder; at
    # N < 1024 the ladder is one rung = n
    assert set(np.unique(fr.data["rung"])) <= {-1, N}
    assert (fr.data["route_drop"] == 0).all()
    assert (fr.data["fault_dropped"] == 0).all()
    # slack: -1 exactly on the final (quiescing) superstep, else the
    # virtual gap to the next event
    q = fr.data["qslack_us"]
    assert (q >= -1).all()
    assert q[-1] == -1 or q[-1] >= 0
    assert (fr.data["mb_peak"] <= sc.mailbox_cap).all()
    assert (fr.data["mb_fill"] >= fr.data["mb_peak"]).all()
    # counters mode carries no mailbox plane (it is the cheap tier)
    eng2 = JaxEngine(sc, link, window="auto", lint="off",
                     telemetry="counters")
    eng2.run(8)
    assert "mb_fill" not in eng2.last_run_telemetry.data


def test_fault_dropped_counter_bites():
    from timewarp_tpu.faults.schedule import parse_faults
    sc, link = _ring()
    faults = parse_faults("crash:3:5ms:40ms")
    off = JaxEngine(sc, link, lint="off", faults=faults)
    eng = JaxEngine(sc, link, lint="off", faults=faults,
                    telemetry="counters")
    f0, t0 = off.run(STEPS)
    f1, t1 = eng.run(STEPS)
    assert_traces_equal(t0, t1, "off", "counters+faults")
    assert_states_equal(f0, f1, "faulted telemetry")
    fr = eng.last_run_telemetry
    # the per-step deltas must sum to the state's never-silent total
    assert fr.data["fault_dropped"].sum() == int(f1.fault_dropped)






_STATS_KEYS = {"supersteps", "wall_seconds", "compiles", "compile_seconds",
               "cache_misses", "dispatches", "readbacks"}


def test_last_run_stats_uniform_across_engines():
    sc, link = _ring()
    engines = [JaxEngine(sc, link, lint="off"),
               EdgeEngine(sc, link, lint="off")]
    for eng in engines:
        _, trace = eng.run(STEPS)
        st = eng.last_run_stats
        # the general engine counts its routing stage beside them
        # (ISSUE 35): one bin and the full width, the ring has no ladder;
        # this ring's inbox is commutative, so its insertion stages by
        # rank and counts its tail too (ISSUE 44: the list grew by three)
        routed = {"rung_lanes", "sender_lanes", "rung_steps",
                  "dense_stage_steps", "wide_tail_steps",
                  "dense_lanes", "tail_lanes", "net_rows"} \
            if isinstance(eng, JaxEngine) else set()
        assert set(st) == _STATS_KEYS | routed
        if routed:
            assert st["rung_steps"] == [len(trace)]
            assert st["rung_lanes"] == st["sender_lanes"] \
                == len(trace) * sc.n_nodes
        assert (st["dispatches"], st["readbacks"]) == (1, 1)
        assert st["supersteps"] == len(trace)
        assert st["wall_seconds"] > 0
        assert st["compiles"] >= 0
    # the oracle carries the same surface (host Python: compiles 0)
    from timewarp_tpu.interp.ref.superstep import SuperstepOracle
    orc = SuperstepOracle(sc, link, lint="off")
    trace = orc.run(STEPS)
    st = orc.last_run_stats
    assert set(st) == _STATS_KEYS
    assert st["supersteps"] == len(trace) and st["compiles"] == 0
    assert (st["dispatches"], st["readbacks"]) == (0, 0)


def test_stats_count_compiles_via_pow2_bucket():
    sc, link = _ring()
    eng = JaxEngine(sc, link, lint="off")
    eng.run(20)
    first = eng.last_run_stats["compiles"]
    assert first >= 1
    # same pow2 bucket -> the cached executable, zero new compiles
    eng.run(25)
    assert eng.last_run_stats["compiles"] == 0
