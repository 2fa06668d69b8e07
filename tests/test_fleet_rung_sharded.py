"""One routing rung for the whole fleet
(tests/test_zzzzzzzzzzzzzfleet_rung.py), under faults and with the
worlds sharded over a mesh: every slice stays bit-equal to its solo
run, each device takes its own rung, and no driver names a mesh
collective."""

import numpy as np
import pytest

import jax

from fleet_rung_laws import (N, RUNGS, SLOW, _eqns, _ladder_conds, _named_axes,
                             _shared_rung, _steady)
from timewarp_tpu.faults import (FaultFleet, FaultSchedule, LinkWindow,
                                 NodeCrash, Partition)
from timewarp_tpu.interp.jax_engine.batched import BatchSpec, world_slice
from timewarp_tpu.interp.jax_engine.engine import JaxEngine
from timewarp_tpu.trace.events import assert_states_equal, assert_traces_equal


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
    # the quiet driver names none either: its loop's condition reads
    # this device's worlds, and the devices meet at the readback
    jq = jax.make_jaxpr(lambda s: type(eng)._run_while(
        eng, s, 4, eng._identity()))(st)
    named = [e.primitive.name for e in _eqns(jq)
             if _named_axes(e) and e.primitive.name != "axis_index"]
    assert named == [], named
