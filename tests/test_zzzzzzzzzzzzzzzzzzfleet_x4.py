"""The seed sweep laid over a mesh of four by worlds as a deployment
(ISSUE 46): ``ShardedBatchedEngine`` through the benchmark's builder
equals the plain reference world by world, and its control does not;
every world equals, bit for bit, the same world of the one-device
fleet and its solo run, whichever device holds it; every job is one
program, one dispatch and one readback on a state that stays four
slices on four devices; the call's record counts the mesh, the worlds
a device and each device's own rung and sender lanes. Since ISSUE 47
the quiet loop's condition reads a device's own worlds: no driver's
text holds a collective or the scope ``tw.liveness``, each device
leaves its loop when its own last world is quiet
(``device_iterations``), a device whose worlds are all quiet at entry
runs nothing, and every other driver lowers to the text the parent
lowered.

(Named test_zz* to sort after the whole existing suite.)
"""

import hashlib
import os
import re
import sys

import numpy as np
import pytest

import jax

from fleet_rung_laws import SLOW, _steady
from timewarp_tpu.interp.jax_engine.batched import BatchSpec, world_slice
from timewarp_tpu.interp.jax_engine.engine import JaxEngine
from timewarp_tpu.interp.jax_engine.sharded import (ShardedBatchedEngine,
                                                    ShardedEdgeEngine,
                                                    ShardedEngine)
from timewarp_tpu.models.token_ring import token_ring
from timewarp_tpu.net.delays import FixedDelay
from timewarp_tpu.obs import profiler
from timewarp_tpu.obs.metrics import MetricsRegistry, validate_line
from timewarp_tpu.parallel.mesh import make_mesh
from timewarp_tpu.trace.events import assert_states_equal

BENCHMARK = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCHMARK not in sys.path:
    sys.path.insert(0, BENCHMARK)

import run  # noqa: E402
from builders import gossip_fleet_x4  # noqa: E402
from builders.gossip_wave import scenario_and_link  # noqa: E402
from reference import gossip_fleet_ref  # noqa: E402

N, WORLDS, SHARDS, BUDGET = 512, 8, 4, 1 << 12
LOCAL = WORLDS // SHARDS
#: two orders of the worlds along the batch axis that move worlds
#: between devices: a rotation by one device's worlds and a reversal
ORDERS = {"rotated": (2, 3, 4, 5, 6, 7, 0, 1),
          "reversed": (7, 6, 5, 4, 3, 2, 1, 0)}


def _device_trips(by_world):
    """The trips of each device's own loop in a run to quiescence:
    its last world's supersteps."""
    return [max(by_world[d * LOCAL:(d + 1) * LOCAL]) for d in range(SHARDS)]


def _toy():
    """The committed cell's files at this file's size."""
    traffic, config = run.load_cell("gossip_100k_x4.fleet32")
    config["params"].update(n_nodes=N, worlds=WORLDS,
                            world_seeds=list(range(WORLDS)))
    return config, traffic


@pytest.fixture(scope="module")
def cell():
    """The benchmark's builder at toy size, set up (its first job
    compiles the driver) and two jobs on: what each left behind (the
    job's dict, the call's stats and record)."""
    c = gossip_fleet_x4.Cell(*_toy())
    made = []
    for i in range(3):
        res = c.set_up(46) if i == 0 else c.job(i)
        made.append({"res": res, "stats": dict(c.engine.last_run_stats),
                     "record": profiler.calls()[-1]})
    return c, made


@pytest.fixture(scope="module")
def fin(cell):
    """One more result of the cell's engine, as a job's is."""
    c, _ = cell
    return c.engine.run_quiet(BUDGET, c.state0)


# -- (a) the builder against the plain reference, and its control -----------

def test_the_cell_equals_the_reference_in_all_seven_rows(cell):
    c, made = cell
    assert [m["res"]["failed"] for m in made] == [""] * 3
    rows = c.compare(gossip_fleet_ref)
    assert [r[1:] for r in rows] == [(0, 0)] * 7, rows
    assert rows[0][0] == f"fleets_2x{WORLDS}.hop.nodes_that_differ"
    assert sorted(c.order) == list(range(WORLDS)) != list(c.order)


def test_the_control_fails_in_every_world(cell):
    c, _ = cell
    control = {name.partition(".")[2]: v
               for name, v, _ in c.control(gossip_fleet_ref)}
    # the bfloat16 lognormal moves hop counts in every world; whom the
    # rumor reaches and the deliveries are the push graph's
    assert control["hop.worlds_that_differ"] == WORLDS
    assert control["hop.nodes_that_differ"] > WORLDS
    assert control["infected.nodes_that_differ"] == 0
    assert control["delivered.worlds_that_differ"] == 0


def test_a_world_returned_by_another_chip_is_found(cell):
    c, _ = cell
    hop, got = c.fleets[0]
    swap = [LOCAL, 1] + list(range(2, WORLDS))
    swap[LOCAL] = 0                    # slot 0 and device 1's first
    c.fleets.append((hop[swap], [got[i] for i in swap]))
    try:
        rows = {name.partition(".")[2]: v
                for name, v, _ in c.compare(gossip_fleet_ref)}
    finally:
        c.fleets.pop()
    assert rows["slot.worlds_misplaced"] == 2
    assert rows["hop.worlds_that_differ"] == 2


# -- (b) the share ties to the whole -----------------------------------------

@pytest.fixture(scope="module")
def twins():
    """The one-device fleet of the same worlds, and what it and the
    four-device fleet leave behind under each order."""
    config, _ = _toy()
    sc, link = scenario_and_link(config["params"])
    spec = BatchSpec(seeds=tuple(range(WORLDS)))
    sharded = ShardedBatchedEngine(sc, link, make_mesh(SHARDS, "worlds"),
                                   batch=spec, window="auto")
    one = JaxEngine(sc, link, window="auto", batch=spec)
    out = {}
    for key, order in ORDERS.items():
        fins = []
        for eng in (sharded, one):
            assert eng.rebind_identity(BatchSpec(seeds=order))
            fins.append(eng.run_quiet(BUDGET, eng.init_state()))
        # and what the four-device call counted
        out[key] = fins + [dict(sharded.last_run_stats)]
    return sc, link, sharded.window, out


@pytest.mark.parametrize("order", sorted(ORDERS))
def test_every_world_equals_the_one_device_fleets(twins, order):
    got, want, _ = twins[3][order]
    for slot, seed in enumerate(ORDERS[order]):
        assert_states_equal(world_slice(want, slot), world_slice(got, slot),
                            f"world {seed} in slot {slot}")
    # and the same world under the other order, on another device
    other, = set(ORDERS) - {order}
    there = twins[3][other][0]
    for slot, seed in enumerate(ORDERS[order]):
        assert_states_equal(
            world_slice(there, ORDERS[other].index(seed)),
            world_slice(got, slot), f"world {seed} under both orders")


@pytest.mark.parametrize("seed", [0, 5])
def test_a_world_equals_its_solo_run(twins, seed):
    sc, link, window, out = twins
    want = JaxEngine(sc, link, seed=seed, window=window).run_quiet(BUDGET)
    for order, (got, _, _) in sorted(out.items()):
        assert_states_equal(want, world_slice(
            got, ORDERS[order].index(seed)), f"world {seed}, {order}")


@pytest.mark.parametrize("order", sorted(ORDERS))
def test_the_devices_leave_the_loop_each_at_its_own_last_world(twins,
                                                               order):
    """The fleets the three tests above hold to the one-device fleet
    and to the solo runs bit for bit ran their devices out of step:
    each left its ``while`` when its own last world was quiet."""
    got, want, stats = twins[3][order]
    assert_states_equal(want, got, f"the whole fleet, {order}")
    trips, by_world = stats["device_iterations"], stats["world_supersteps"]
    assert len(set(trips)) > 1, trips
    assert trips == _device_trips(by_world)
    assert stats["fleet_iterations"] == max(trips)
    assert by_world == np.asarray(want.steps).tolist()
    # a device counts the iterations it ran, at the rung it took
    # (this size has one rung, the full width)
    assert stats["device_rung_lanes"] == [N * t for t in trips]
    assert stats["rung_lanes"] == N * max(trips) \
        and stats["rung_steps"] == [max(trips)]


# -- (c) the gates -------------------------------------------------------------

@pytest.mark.parametrize("job", range(3))
def test_every_job_is_one_program_on_four_slices(cell, job):
    c, made = cell
    stats, res = made[job]["stats"], made[job]["res"]
    assert res["failed"] == ""
    assert (stats["dispatches"], stats["readbacks"]) == (1, 1)
    # set-up's job compiles the driver, no later one does
    assert stats["compiles"] == (1 if job == 0 else 0)
    assert res["supersteps"] == stats["fleet_iterations"] \
        == max(stats["world_supersteps"])
    assert res["device_rung_lanes"] == stats["device_rung_lanes"]
    assert made[job]["record"]["counts"] == stats
    assert made[job]["record"]["engine"] == "ShardedBatchedEngine"


def test_the_result_lives_as_four_slices_of_two_worlds(cell, fin):
    c, _ = cell
    assert c.engine.last_run_stats["compiles"] == 0
    assert c._placement(fin) == [] == c.renamed(fin)
    shards = fin.steps.addressable_shards
    assert {s.data.shape for s in shards} == {(LOCAL,)}
    assert sorted(s.index[0].start or 0 for s in shards) == [
        d * LOCAL for d in range(SHARDS)]
    assert len({s.device for s in shards}) == SHARDS


def test_a_result_that_left_its_slices_fails_the_gate(cell, fin):
    c, _ = cell
    gathered = fin._replace(steps=jax.device_put(
        fin.steps, jax.devices()[0]))
    why = c._placement(gathered)
    assert why[0] == (f"steps lives as 1 shards of [({WORLDS},)] at 1 "
                      "offsets on 1 devices")
    assert why[1] == ("leaves laid out otherwise than they went in: "
                      "['.steps']")
    assert c.renamed(gathered) == [".steps"]


def test_a_mesh_the_cells_chips_do_not_span_is_refused():
    config, traffic = _toy()
    config["params"]["mesh"]["shape"] = [2]
    with pytest.raises(SystemExit, match="is not the cell's 4 chips"):
        gossip_fleet_x4.Cell(config, traffic)


# -- (d) the counters ------------------------------------------------------------

RUNGS = [64, 128, 256, 512]


@pytest.fixture(scope="module")
def uneven():
    """A fleet whose devices take different rungs in one iteration:
    steady gossip on a ramp, the worlds of devices 2 and 3 on links
    four times slower, the ladder's floor patched down to this file's
    size before the engine's first trace. Calls of one scalar budget
    streamed on one state until a call finds every world quiet (the
    fast pair of devices is quiet two calls before the slow pair),
    one call from the fresh state to quiescence, then the scan driver
    over the first two calls' iterations and over the whole run (under
    one budget a world, all alike and with one world cut short)."""
    patch = pytest.MonkeyPatch()
    patch.setattr(JaxEngine, "_sender_rungs", staticmethod(
        lambda n: [r for r in RUNGS if r < n] + [n]))
    try:
        sc, link = _steady(N)
        spec = BatchSpec(seeds=tuple(range(WORLDS)), link_params={
            k: [v[0]] * (WORLDS // 2) + [v[1]] * (WORLDS // 2)
            for k, v in SLOW.items()})
        eng = ShardedBatchedEngine(sc, link, make_mesh(SHARDS, "worlds"),
                                   batch=spec, window="auto")
        calls, states = [], [eng.init_state()]
        while not calls or calls[-1]["supersteps"]:
            states.append(eng.run_quiet(12, states[-1]))
            calls.append(dict(eng.last_run_stats))
        fin = eng.run_quiet(BUDGET)
        whole = dict(eng.last_run_stats)
        eng.run(24)
        scan = dict(eng.last_run_stats)
        # one budget a world (one program for both): all the same,
        # then the first world of a slow device cut short
        eng.run(np.full(WORLDS, 128))
        whole_scan = dict(eng.last_run_stats)
        eng.run(np.array([128] * (WORLDS // 2) + [66] + [128] * (
            WORLDS // 2 - 1)))
        return eng, calls, scan, {
            "states": states, "fin": fin, "quiet": whole,
            "scan": whole_scan, "cut": dict(eng.last_run_stats)}
    finally:
        patch.undo()        # every trace of the fixture is made


@pytest.mark.parametrize("call", range(2))
def test_a_call_counts_each_devices_own_lanes(uneven, call):
    stats = uneven[1][call]
    assert (stats["shards"], stats["worlds_local"]) == (SHARDS, LOCAL)
    assert len(stats["device_rung_lanes"]) == SHARDS \
        == len(stats["device_sender_lanes"])
    assert stats["rung_lanes"] == max(stats["device_rung_lanes"])
    widest = int(np.argmax(stats["device_rung_lanes"]))
    assert stats["sender_lanes"] == stats["device_sender_lanes"][widest]
    # a rung holds the senders it was chosen for
    assert all(s <= r for s, r in zip(stats["device_sender_lanes"],
                                      stats["device_rung_lanes"]))
    # streamed on the state the first call returned: one program
    assert stats["compiles"] == (1 if call == 0 else 0)
    assert (stats["dispatches"], stats["readbacks"]) == (1, 1)


def test_two_devices_took_different_rungs(uneven):
    _, calls, scan, _ = uneven
    lanes = calls[1]["device_rung_lanes"]
    # the fast pair of devices ran ahead of the slow pair's ramp
    assert min(lanes[:2]) > max(lanes[2:])
    # the scan driver counts what the two streamed calls counted
    both = [a + b for a, b in zip(calls[0]["device_rung_lanes"], lanes)]
    assert scan["device_rung_lanes"] == both
    assert scan["rung_lanes"] == max(both)
    assert (scan["shards"], scan["worlds_local"]) == (SHARDS, LOCAL)
    # each world's own senders (PR 55), a world a row on whichever
    # device: at most its device's busiest's (which is one of them in
    # every iteration, so their sum is at least it), and the scan
    # driver's are the two streamed calls' sums
    for stats in (*calls[:2], scan):
        own = stats["world_sender_lanes"]
        assert len(own) == WORLDS
        assert all(own[b] <= stats["device_sender_lanes"][b // LOCAL]
                   for b in range(WORLDS))
        assert all(sum(own[d * LOCAL:(d + 1) * LOCAL])
                   >= stats["device_sender_lanes"][d]
                   for d in range(SHARDS))
    assert scan["world_sender_lanes"] == [
        a + b for a, b in zip(calls[0]["world_sender_lanes"],
                              calls[1]["world_sender_lanes"])]


@pytest.mark.parametrize("key", ["device_rung_lanes", "device_sender_lanes",
                                 "device_iterations"])
def test_the_chunked_drivers_merge_sums_them(uneven, key):
    eng, calls = uneven[:2]
    merged = eng._stats_merge(calls[:2])
    assert (merged["shards"], merged["worlds_local"]) == (SHARDS, LOCAL)
    assert merged[key] == [a + b for a, b in zip(
        calls[0][key], calls[1][key])]
    assert merged["rung_lanes"] >= max(merged["device_rung_lanes"])
    assert merged["fleet_iterations"] == max(merged["device_iterations"])


def test_a_device_whose_worlds_are_quiet_at_entry_runs_nothing(uneven):
    _, calls, _, more = uneven
    # the first streamed call that the fast pair of devices entered
    # with every world quiet while the slow pair ran on
    i = next(i for i, c in enumerate(calls)
             if c["device_iterations"][:2] == [0, 0]
             and min(c["device_iterations"][2:]) > 0)
    stats, came, went = calls[i], more["states"][i], more["states"][i + 1]
    fast = LOCAL * 2                   # the worlds of devices 0 and 1
    assert stats["world_supersteps"][:fast] == [0] * fast
    assert stats["device_rung_lanes"][:2] == [0, 0] \
        == stats["device_sender_lanes"][:2]
    # the others ran on, in the one program of the first call
    assert min(stats["world_supersteps"][fast:]) > 0
    assert stats["fleet_iterations"] == max(stats["device_iterations"])
    assert (stats["compiles"], stats["dispatches"]) == (0, 1)
    # every leaf of the quiet devices as it came, and where it came
    moved = 0
    for a, b in zip(jax.tree.leaves(came), jax.tree.leaves(went)):
        assert a.sharding == b.sharding
        a, b = np.asarray(a), np.asarray(b)
        assert np.array_equal(a[:fast], b[:fast])
        moved += not np.array_equal(a[fast:], b[fast:])
    assert moved > 0
    # and once every device is quiet a call runs no iteration at all
    assert calls[-1]["device_iterations"] == [0] * SHARDS
    assert_states_equal(more["states"][-2], more["states"][-1],
                        "a call on a quiet fleet")


def test_the_quiet_and_the_scan_driver_count_a_devices_own_iterations(
        uneven):
    eng, calls, _, more = uneven
    quiet, scan = more["quiet"], more["scan"]
    trips = _device_trips(quiet["world_supersteps"])
    assert len(set(trips)) > 1 and quiet["device_iterations"] == trips
    for key in ("device_iterations", "device_rung_lanes",
                "device_sender_lanes", "world_supersteps", "rung_lanes",
                "sender_lanes", "rung_steps", "fleet_iterations"):
        assert quiet[key] == scan[key], key
    # a frozen device no longer counts the smallest rung an iteration:
    # the widest device's rungs sum to its own trips, not the fleet's
    widest = int(np.argmax(quiet["device_rung_lanes"]))
    assert sum(quiet["rung_steps"]) == trips[widest] < max(trips)
    # a world cut short by a budget of its own stops counting there
    # (the scan goes on stepping it into the discard, so its device's
    # iterations count on): a device's counts are its widest world's,
    # not its first's, and the call's are still the widest device's
    cut = more["cut"]
    assert cut["world_supersteps"][WORLDS // 2] == 66 < trips[2] \
        <= cut["device_iterations"][2]
    assert cut["rung_lanes"] == max(cut["device_rung_lanes"])
    for key in ("device_iterations", "device_rung_lanes",
                "device_sender_lanes"):
        assert cut[key][:2] + cut[key][3:] == scan[key][:2] + scan[key][3:]
    # and streamed in calls the devices count the same, call by call
    merged = eng._stats_merge(calls)
    for key in ("device_iterations", "device_rung_lanes",
                "device_sender_lanes", "world_supersteps"):
        assert merged[key] == quiet[key], key
    assert_states_equal(more["states"][-1], more["fin"],
                        "streamed against one call")


def test_the_run_summary_line_carries_a_devices_iterations(uneven):
    quiet = uneven[3]["quiet"]
    reg = MetricsRegistry()
    reg.run_summary("fleet x4", quiet)
    line = reg.lines[-1]
    for key in ("shards", "worlds_local", "device_rung_lanes",
                "device_sender_lanes", "device_iterations"):
        assert line[key] == quiet[key], key
    for bad in (3, [1, "2"], [1.5] * SHARDS):
        with pytest.raises(ValueError, match="device_iterations"):
            validate_line({**line, "device_iterations": bad})


def _solo():
    eng = JaxEngine(*_steady(N), window="auto")
    eng.run_quiet(4)
    return eng


def _one_chip_fleet():
    eng = JaxEngine(*_steady(N), window="auto",
                    batch=BatchSpec(seeds=(0, 1)))
    eng.run_quiet(4)
    return eng


def _edge_engine():
    sc = token_ring(256, n_tokens=256, think_us=0, bootstrap_us=1000,
                    end_us=2**50, with_observer=False, mailbox_cap=4)
    return ShardedEdgeEngine(sc, FixedDelay(500), make_mesh(SHARDS), cap=2)


def _sharded_edge():
    eng = _edge_engine()
    eng.run_quiet(4)
    return eng


@pytest.mark.parametrize("build", [_solo, _one_chip_fleet, _sharded_edge])
def test_no_other_engine_counts_a_device(build):
    eng = build()
    stats = eng.last_run_stats
    for key in ("worlds_local", "device_rung_lanes", "device_sender_lanes",
                "device_iterations"):
        assert key not in stats, key
    # the mesh axis' size is the edge engine's too, as it was
    assert ("shards" in stats) == isinstance(eng, ShardedEdgeEngine)
    assert profiler.calls()[-1]["counts"] == stats
    merged = eng._stats_merge([stats, stats])
    assert "device_rung_lanes" not in merged \
        and "worlds_local" not in merged \
        and "device_iterations" not in merged


# -- (e) no driver meets another device --------------------------------------

def _fleet_spec():
    return BatchSpec(seeds=tuple(range(WORLDS)))


def _sharded_fleet():
    return ShardedBatchedEngine(*_steady(N), make_mesh(SHARDS, "worlds"),
                                batch=_fleet_spec(), window="auto")


def _world_sharded_quiet():
    eng = _sharded_fleet()
    return type(eng)._run_while.lower(eng, eng.init_state(), 16,
                                      eng._identity())


def _world_sharded_scan():
    eng = _sharded_fleet()
    return type(eng)._run_scan.lower(eng, eng.init_state(), 16, 16, None,
                                     eng._identity())


def _fleet_quiet():
    eng = JaxEngine(*_steady(N), window="auto", batch=_fleet_spec())
    return type(eng)._run_while.lower(eng, eng.init_state(), 16,
                                      eng._identity())


def _solo_quiet():
    eng = JaxEngine(*_steady(N), window="auto")
    return type(eng)._run_while.lower(eng, eng.init_state(), 16)


def _node_sharded_quiet():
    eng = ShardedEngine(*_steady(N), make_mesh(SHARDS), window="auto")
    return type(eng)._run_while.lower(eng, eng.init_state(), 16)


def _edge_sharded_quiet():
    eng = _edge_engine()
    return type(eng)._run_while.lower(eng, eng.init_state(), 16)


#: sha256 of each driver's lowering (``as_text()``: no names, no
#: locations) as the parent of PR 46 lowers it, but for the
#: world-sharded quiet driver's: PR 47 took the all-reduce out of its
#: loop's condition and re-pinned that one. PR 48 re-pinned the four
#: drivers that take the ladder (``window="auto"`` on ``LocalComm``:
#: ``world_sharded_quiet`` was 6708eebb0485…, ``world_sharded_scan``
#: 9d5a701b241d…, ``fleet_quiet`` 59e965122ffd…, ``solo_quiet``
#: 4cd9e69f8ce0…): the ladder's sender compaction went from a
#: one-operand sort of the node lanes to ``compress_lanes``, the
#: same array word for word (``tests/test_free_bits.py``). The
#: node-sharded and the edge-sharded driver never took the ladder
#: (a ``MeshComm``; no routing at all) and kept their constants
#: there. PR 49 re-pinned ``node_sharded_quiet`` (it was
#: 24939e824a42…): each shard of ``ShardedEngine`` now carries
#: its own ``remote_msgs`` and ``bucket_fill_peak`` beside the state
#: (two reductions over the lanes its exchange has sorted by shard,
#: no collective), and the shard's index is read once at the top of
#: ``_exchange``; the state it computes is the parent's
#: (``tests/test_zzzzzzzzzzzzzzzzzzzsteady_x4.py``), and the other
#: five drivers lower to the parent's text. PR 50 re-pinned
#: ``node_sharded_quiet`` again (it was 7d1edba5936b…): the
#: exchange's ``[shards, bucket_cap]`` buffers are slices of the
#: planes sorted by shard and no longer scatters, the same words
#: (``tests/test_exchange_bucket_law.py``); the other five drivers
#: run no sharded exchange and lower to the parent's text. PR 55
#: re-pinned the three fleet drivers (``fleet_quiet`` was
#: db301bff740d…, ``world_sharded_quiet`` 984d8edf41b9…,
#: ``world_sharded_scan`` 27b7df94d44f…): a fleet's carry holds one
#: count more, ``world_sender_lanes``, each world's own senders
#: before the ``pmax`` that picks the rung (a sum the ladder has made
#: already; the state it computes is the parent's:
#: ``tests/test_zzzzzzzzzzzzzzzrecord.py``); the three solo and
#: node-sharded drivers carry nothing new and kept their constants.
#: PR 56 re-pinned the four drivers that take the ladder
#: (``world_sharded_quiet`` was 8a96e6cec699…, ``world_sharded_scan``
#: 1ae8364d96e3…, ``fleet_quiet`` afabbf17cb9f…, ``solo_quiet``
#: d68a4763b001…): the ladder's top rung reads the outbox where it
#: lies and gathers no sender word, the rungs below lower to what
#: they lowered to, and the lanes after the sort are the same words
#: (``tests/test_top_rung_in_place_law.py``); the node-sharded and
#: the edge-sharded driver take no ladder and kept their constants.
#: PR 57 re-pinned ``node_sharded_quiet`` (it was 786fb1206f5e…): the
#: capped lazy path went with the keyword that chose it, and with
#: them the eager path's ``comm.all_sum`` of the constant zero that
#: stood for "nothing was capped": on a mesh one ``all_reduce`` of
#: ``constant dense<0>`` a superstep, whose result ``route_drop``
#: then added. The texts differ in that instruction, its operand and the
#: constant zero added in its place, and in nothing else (with the
#: SSA names made canonical: six lines out, one in); the other five
#: drivers lower to the parent's text.
#: A PR that changes what these drivers compute changes the
#: constants, and says so.
_PARENT_LOWERING = {
    "world_sharded_quiet":
        "41d0b00f1a1085a216a0025af98db9d5cdbd621e57d0128433fbfb18497de38a",
    "world_sharded_scan":
        "71cbea785631f01bb5883294a103d016309e61fdd0ea7c9862c2af26b6c3d704",
    "fleet_quiet":
        "be61e23f5f72dffc78264d55214e2635cc434d353c0fc5cc83427fe3d7737dc3",
    "solo_quiet":
        "9c72772f5f8dfbc227c8806cdc1ab925afbaee539264bb247056e6ab363dcf35",
    "node_sharded_quiet":
        "693ef08b19182c4a196f381d865c4a57f3d3194c29609a83c587cb667f42f71b",
    "edge_sharded_quiet":
        "fb22bbbdb766457edff1d5a6f0107e58663db37f44d10757fec769575fdf4def",
}
_LOWER = {"world_sharded_quiet": _world_sharded_quiet,
          "world_sharded_scan": _world_sharded_scan,
          "fleet_quiet": _fleet_quiet, "solo_quiet": _solo_quiet,
          "node_sharded_quiet": _node_sharded_quiet,
          "edge_sharded_quiet": _edge_sharded_quiet}


@pytest.fixture(scope="module")
def lowered():
    return {key: fn() for key, fn in _LOWER.items()}


@pytest.mark.parametrize("key", sorted(_LOWER))
def test_every_driver_lowers_to_the_parents_text(lowered, key):
    text = lowered[key].as_text()
    assert hashlib.sha256(text.encode()).hexdigest() \
        == _PARENT_LOWERING[key]


@pytest.mark.parametrize("key", sorted(_LOWER))
def test_the_liveness_scope_is_the_world_sharded_quiet_drivers(lowered,
                                                               key):
    """It was, until PR 47: now no driver's lowered text holds a
    ``tw.liveness`` name, no loop's condition a collective, and the
    fleets' and the solo engine's text no ``all_reduce`` at all (a
    node-sharded world's supersteps sum their counters over the mesh,
    in the loop's body)."""
    text = lowered[key].as_text(debug_info=True)
    names = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    assert [n for n in names.values() if "tw.liveness" in n] == []
    assert [n for n in names.values() if "while/cond" in n and re.search(
        r"psum|pmax|pmin|all_|ppermute", n)] == []
    if key in ("node_sharded_quiet", "edge_sharded_quiet"):
        return
    assert re.findall(r"stablehlo\.all_reduce", text) == []
    if key.startswith("world_sharded"):
        # the fleet over a mesh: no collective of any kind
        assert re.findall(r"stablehlo\.(all_\w+|collective_\w+|"
                          r"reduce_scatter)", text) == []
