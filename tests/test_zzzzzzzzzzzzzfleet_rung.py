"""One routing rung for the whole fleet (ISSUE 28): a fleet's
superstep holds the ladder as ONE conditional on an index that is not
batched (the largest active-sender count over the worlds), where a
per-world index would lower to a select over every branch; the rung is
result-invisible, so every slice stays bit-equal to its solo run, with
worlds of very different activity, under faults and with the worlds
sharded over a mesh (each device its own rung, and no collective in
the superstep); ``last_run_stats["rung_lanes"]`` sums the rungs taken
and costs no readback.

(Named test_zz* to sort after the whole existing suite.)
"""

import numpy as np
import pytest

import jax

from timewarp_tpu.analysis.jaxpr_lint import _all_jaxprs
from timewarp_tpu.faults import (FaultFleet, FaultSchedule, LinkWindow,
                                 NodeCrash, Partition)
from timewarp_tpu.interp.jax_engine.batched import BatchSpec, world_slice
from timewarp_tpu.interp.jax_engine.engine import JaxEngine
from timewarp_tpu.models.gossip import gossip
from timewarp_tpu.net.delays import Quantize, UniformDelay
from timewarp_tpu.trace.events import (assert_states_equal,
                                       assert_traces_equal)

N = 2048
RUNGS = JaxEngine._sender_rungs(N)
#: per-world link bounds: world 1's links are four times slower, so its
#: ramp is still under the first rung when world 0's has filled the top
SLOW = {"inner.lo": [500, 4_000], "inner.hi": [4_500, 16_000]}


def _steady(n=N, end_us=60_000):
    """Steady gossip: the active set doubles a round, so a run crosses
    the ladder's rungs on its ramp."""
    sc = gossip(n, fanout=1, think_us=1_000, gossip_interval=1_000,
                end_us=end_us, steady=True, mailbox_cap=8)
    return sc, Quantize(UniformDelay(500, 4_500), 1_000)


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs in its parameters
    (loop bodies, branches, ``shard_map`` and ``pjit`` bodies)."""
    return [e for jx in _all_jaxprs(jaxpr.jaxpr) for e in jx.eqns]


def _named_axes(eqn) -> set:
    """The axis names an equation reduces or exchanges over."""
    names = set()
    for key in ("axes", "axis_name"):
        v = eqn.params.get(key, ())
        names |= {a for a in (v if isinstance(v, (tuple, list)) else (v,))
                  if isinstance(a, str)}
    return names


def _ladder_conds(jaxpr, rungs):
    return [e for e in _eqns(jaxpr) if e.primitive.name == "cond"
            and len(e.params["branches"]) == len(rungs)]


def _shared_rung(frames):
    """The ``rung`` column of a fleet's telemetry, by iteration. A
    world steps from the loop's first iteration until it is quiet or
    out of budget, so row ``i`` of its frames is iteration ``i``, and
    every world that stepped in an iteration recorded the same."""
    cols = sorted((fr.data["rung"].tolist() for fr in frames), key=len)
    for col in cols:
        assert col == cols[-1][:len(col)]
    return cols[-1]


# -- (a) the program ---------------------------------------------------------

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


# -- (b) the counter ---------------------------------------------------------

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


# -- (c) the exactness law where the worlds differ --------------------------

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


def test_faulted_fleet_on_the_ladder_slices_bit_equal():
    """``branch_faulted``: the sample-before-sort tail of every rung,
    under per-world schedules, on a ramp that crosses the rungs."""
    sc, link = _steady()
    half = N // 2
    fleet = FaultFleet(tuple(FaultSchedule((
        NodeCrash(b + 1, 4_000 + 1_000 * b, 30_000,
                  reset_state=(b % 2 == 0)),
        Partition((tuple(range(half)), tuple(range(half, N))),
                  8_000, 20_000 + 5_000 * b),
        LinkWindow(tuple(range(16)), None, 25_000, 40_000, scale=2.0,
                   extra_us=1_000),
    )) for b in range(2)))
    spec = BatchSpec(seeds=(0, 5))
    eng = JaxEngine(sc, link, window="auto", batch=spec, faults=fleet,
                    telemetry="counters")
    fin, traces = eng.run(40)
    assert len(set(_shared_rung(eng.last_run_telemetry))) > 1
    assert int(np.asarray(fin.fault_dropped).min()) > 0
    for b in range(spec.B):
        solo = JaxEngine(sc, link, window=eng.window, seed=spec.seeds[b],
                         faults=fleet.world_schedule(b))
        solo_fin, solo_trace = solo.run(40)
        assert_traces_equal(solo_trace, traces[b], "solo", f"world{b}")
        assert_states_equal(solo_fin, world_slice(fin, b), f"world {b}")


# -- (d) the worlds over a mesh ----------------------------------------------

@pytest.fixture(scope="module")
def sharded():
    from timewarp_tpu.interp.jax_engine.sharded import (
        ShardedBatchedEngine, make_mesh)
    sc, link = _steady()
    # two worlds a device: the fast pair on device 0, the slow on 1
    spec = BatchSpec(seeds=(3, 4, 9, 10), link_params={
        k: [v[0], v[0], v[1], v[1]] for k, v in SLOW.items()})
    eng = ShardedBatchedEngine(sc, link, make_mesh(2, axis="worlds"),
                               window="auto", telemetry="counters",
                               batch=spec)
    return eng, sc, link, spec


def test_sharded_fleet_takes_a_rung_a_device_and_slices_bit_equal(sharded):
    eng, sc, link, spec = sharded
    fin, traces = eng.run(40)
    frames = eng.last_run_telemetry
    by_device = [_shared_rung(frames[:2]), _shared_rung(frames[2:])]
    assert by_device[0] != by_device[1]
    assert eng.last_run_stats["rung_lanes"] == max(map(sum, by_device))
    for b in range(spec.B):
        solo = JaxEngine(sc, spec.world_link(link, b),
                         seed=spec.seeds[b], window=eng.window)
        solo_fin, solo_trace = solo.run(40)
        assert_traces_equal(solo_trace, traces[b], "solo", f"world{b}")
        assert_states_equal(solo_fin, world_slice(fin, b), f"world {b}")
    quiet = eng.run_quiet(40)
    assert_states_equal(fin, quiet, "sharded fleet run_quiet")
    assert eng.last_run_stats["rung_lanes"] == max(map(sum, by_device))


def test_sharded_fleets_superstep_names_no_mesh_collective(sharded):
    eng = sharded[0]
    st = eng.init_state()
    jx = jax.make_jaxpr(lambda s: type(eng)._run_scan(
        eng, s, 4, 4, None, eng._identity()))(st)
    assert len(_ladder_conds(jx, RUNGS)) == 1
    named = {e.primitive.name: _named_axes(e) for e in _eqns(jx)
             if _named_axes(e)}
    # the one use of the mesh axis is the slice of the worlds' identity
    assert set(named) <= {"axis_index"}, named
    # the quiet driver's only collective is its loop's liveness psum
    jq = jax.make_jaxpr(lambda s: type(eng)._run_while(
        eng, s, 4, eng._identity()))(st)
    named = [e.primitive.name for e in _eqns(jq)
             if _named_axes(e) and e.primitive.name != "axis_index"]
    assert named == ["psum"], named
