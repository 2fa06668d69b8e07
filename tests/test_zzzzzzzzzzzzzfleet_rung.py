"""One routing rung for the whole fleet (ISSUE 28): a fleet's
superstep holds the ladder as ONE conditional on an index that is not
batched (the largest active-sender count over the worlds), where a
per-world index would lower to a select over every branch; the rung is
result-invisible, so every slice stays bit-equal to its solo run, with
worlds of very different activity, under faults and with the worlds
sharded over a mesh (each device its own rung, and no collective in
the superstep); ``last_run_stats["rung_lanes"]`` sums the rungs taken
and costs no readback.

Under faults and over a mesh: tests/test_fleet_rung_sharded.py.

(Named test_zz* to sort after the whole existing suite.)
"""

import numpy as np

import jax

from fleet_rung_laws import (N, RUNGS, SLOW, _eqns, _ladder_conds, _named_axes,
                             _shared_rung, _steady)
from timewarp_tpu.interp.jax_engine.batched import BatchSpec, world_slice
from timewarp_tpu.interp.jax_engine.engine import JaxEngine
from timewarp_tpu.models.gossip import gossip
from timewarp_tpu.net.delays import Quantize, UniformDelay
from timewarp_tpu.trace.events import assert_states_equal, assert_traces_equal


def test_fleet_superstep_holds_the_ladder_as_one_conditional():
    n = 4096
    rungs = JaxEngine._sender_rungs(n)
    assert len(rungs) == 3
    sc, link = _steady(n)
    fleet = JaxEngine(sc, link, window="auto",
                      batch=BatchSpec(seeds=(0, 1)))
    jx = jax.make_jaxpr(lambda s: fleet._step_all(s, False))(
        fleet.init_state())
    assert len(_ladder_conds(jx, rungs)) == 1
    # the reduction over the worlds is vmap's own positional one: no
    # named axis is left for a mesh to resolve
    pmax = [e for e in _eqns(jx) if e.primitive.name == "pmax"]
    assert len(pmax) == 1 and not _named_axes(pmax[0])


def test_solo_superstep_reduces_over_nothing():
    n = 4096
    sc, link = _steady(n)
    solo = JaxEngine(sc, link, window="auto")
    jx = jax.make_jaxpr(lambda s: solo._step_all(s, False))(
        solo.init_state())
    assert len(_ladder_conds(jx, JaxEngine._sender_rungs(n))) == 1
    assert not [e for e in _eqns(jx)
                if e.primitive.name in ("pmax", "pmin", "psum")]




def test_rung_lanes_sums_the_rungs_taken_at_no_readback():
    sc, link = _steady()
    eng = JaxEngine(sc, link, window="auto", telemetry="counters",
                    batch=BatchSpec(seeds=(0, 1)))
    eng.run(60)
    st = eng.last_run_stats
    rung = _shared_rung(eng.last_run_telemetry)
    assert len(rung) == st["fleet_iterations"] == 60
    assert st["rung_lanes"] == sum(rung)
    assert N * 60 > st["rung_lanes"] > RUNGS[0] * 60   # the ramp crossed
    assert (st["dispatches"], st["readbacks"]) == (1, 1)
    # the quiet driver carries the same count beside its state
    quiet = JaxEngine(sc, link, window="auto",
                      batch=BatchSpec(seeds=(0, 1)))
    quiet.run_quiet(60)
    qs = quiet.last_run_stats
    assert qs["rung_lanes"] == st["rung_lanes"]
    assert (qs["dispatches"], qs["readbacks"]) == (1, 1)


def test_rung_lanes_stops_where_the_fleet_is_quiet():
    """A traced scan runs on to its padded length; the iterations after
    the last world went quiet count nothing, as in
    ``fleet_iterations``."""
    sc = gossip(N, fanout=4, think_us=700, burst=True, end_us=60_000,
                mailbox_cap=16)
    link = Quantize(UniformDelay(3_000, 9_000), 1_000)
    eng = JaxEngine(sc, link, window=3_000, telemetry="counters",
                    batch=BatchSpec(seeds=(0, 4)))
    eng.run(64)
    st = eng.last_run_stats
    assert st["fleet_iterations"] < 64           # quiet before the budget
    assert st["rung_lanes"] == sum(_shared_rung(eng.last_run_telemetry))
    quiet = JaxEngine(sc, link, window=3_000,
                      batch=BatchSpec(seeds=(0, 4)))
    quiet.run_quiet(64)
    assert quiet.last_run_stats["rung_lanes"] == st["rung_lanes"]


def test_a_fleet_without_the_ladder_counts_its_full_width():
    sc, link = _steady(512)                      # one rung: no switch
    eng = JaxEngine(sc, link, window="auto",
                    batch=BatchSpec(seeds=(0, 1)))
    eng.run_quiet(10)
    assert eng.last_run_stats["rung_lanes"] == 10 * 512


def test_a_solo_engine_counts_its_rung_lanes_too():
    """Solo and fleet differ by ``batch`` alone (ISSUE 35): a solo
    loop carries the same counts, in both drivers (tests/
    test_zzzzzzzzzzzzzzzrecord.py holds them to the telemetry)."""
    sc, link = _steady()
    eng = JaxEngine(sc, link, window="auto")
    eng.run_quiet(5)
    quiet = eng.last_run_stats
    assert quiet["rung_lanes"] == 5 * RUNGS[0]   # the ramp's first rounds
    assert quiet["rung_steps"] == [5] + [0] * (len(RUNGS) - 1)
    eng.run(5)
    assert {k: eng.last_run_stats[k] for k in
            ("rung_lanes", "sender_lanes", "rung_steps")} == \
        {k: quiet[k] for k in ("rung_lanes", "sender_lanes", "rung_steps")}




def test_worlds_of_very_different_activity_slice_bit_equal():
    sc, link = _steady()
    spec = BatchSpec(seeds=(3, 9), link_params=SLOW)
    eng = JaxEngine(sc, link, window="auto", telemetry="counters",
                    batch=spec)
    fin, traces = eng.run(40)
    frames = eng.last_run_telemetry
    active = np.stack([fr.data["active_senders"] for fr in frames])
    rung = _shared_rung(frames)
    # the quiet world rode the busy world's rung: at the top while its
    # own senders would have fitted the first
    assert any(r == N and a <= RUNGS[0]
               for r, a in zip(rung, active[1].tolist()))
    assert rung == [RUNGS[int(np.sum(a > np.asarray(RUNGS)))]
                    for a in active.max(axis=0).tolist()]
    for b in range(spec.B):
        solo = JaxEngine(sc, spec.world_link(link, b),
                         seed=spec.seeds[b], window=eng.window)
        solo_fin, solo_trace = solo.run(40)
        assert_traces_equal(solo_trace, traces[b], "solo", f"world{b}")
        assert_states_equal(solo_fin, world_slice(fin, b), f"world {b}")
