"""The seed sweep laid over a mesh of four by worlds as a deployment
(ISSUE 46): ``ShardedBatchedEngine`` through the benchmark's builder
equals the plain reference world by world, and its control does not;
every world equals, bit for bit, the same world of the one-device
fleet and its solo run, whichever device holds it; every job is one
program, one dispatch and one readback on a state that stays four
slices on four devices; the call's record counts the mesh, the worlds
a device and each device's own rung and sender lanes; the liveness
reduction of the loop's condition has a name, ``tw.liveness``, in the
world-sharded quiet driver and in no other, and the name is a name and
nothing else: every driver lowers to the text the parent lowered.

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
        out[key] = fins
    return sc, link, sharded.window, out


@pytest.mark.parametrize("order", sorted(ORDERS))
def test_every_world_equals_the_one_device_fleets(twins, order):
    got, want = twins[3][order]
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
    for order, (got, _) in sorted(out.items()):
        assert_states_equal(want, world_slice(
            got, ORDERS[order].index(seed)), f"world {seed}, {order}")


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
    size before the engine's first trace. Two calls streamed on one
    state, then the scan driver."""
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
        calls, st = [], eng.init_state()
        for _ in range(2):
            st = eng.run_quiet(12, st)
            calls.append(dict(eng.last_run_stats))
        eng.run(24)
        return eng, calls, dict(eng.last_run_stats)
    finally:
        patch.undo()        # every trace of the fixture is made


@pytest.mark.parametrize("call", range(2))
def test_a_call_counts_each_devices_own_lanes(uneven, call):
    _, calls, _ = uneven
    stats = calls[call]
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
    _, calls, scan = uneven
    lanes = calls[1]["device_rung_lanes"]
    # the fast pair of devices ran ahead of the slow pair's ramp
    assert min(lanes[:2]) > max(lanes[2:])
    # the scan driver counts what the two streamed calls counted
    both = [a + b for a, b in zip(calls[0]["device_rung_lanes"], lanes)]
    assert scan["device_rung_lanes"] == both
    assert scan["rung_lanes"] == max(both)
    assert (scan["shards"], scan["worlds_local"]) == (SHARDS, LOCAL)


def test_the_chunked_drivers_merge_sums_them(uneven):
    eng, calls, _ = uneven
    merged = eng._stats_merge(calls)
    assert (merged["shards"], merged["worlds_local"]) == (SHARDS, LOCAL)
    for key in ("device_rung_lanes", "device_sender_lanes"):
        assert merged[key] == [a + b for a, b in zip(
            calls[0][key], calls[1][key])]
    assert merged["rung_lanes"] >= max(merged["device_rung_lanes"])


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
    for key in ("worlds_local", "device_rung_lanes", "device_sender_lanes"):
        assert key not in stats, key
    # the mesh axis' size is the edge engine's too, as it was
    assert ("shards" in stats) == isinstance(eng, ShardedEdgeEngine)
    assert profiler.calls()[-1]["counts"] == stats
    merged = eng._stats_merge([stats, stats])
    assert "device_rung_lanes" not in merged \
        and "worlds_local" not in merged


# -- (e) the name, and that it is nothing else -------------------------------

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
#: locations) as the parent of PR 46 lowers it. The scope is metadata:
#: the world-sharded quiet driver's text is the parent's too. A PR that
#: changes what these drivers compute changes the constants, and says
#: so.
_PARENT_LOWERING = {
    "world_sharded_quiet":
        "1dda21194f883fad0bd6cc6fc1134298e3579da3ebe343fa63abdfbe70c6fec4",
    "world_sharded_scan":
        "9d5a701b241d6804b25af34ab509808c7029a86dc5113a2e98dfb415c950040b",
    "fleet_quiet":
        "59e965122ffd4ce9bd90de2763ecbd0678839f7ac4129b6a2d07a68779297cc8",
    "solo_quiet":
        "4cd9e69f8ce0e5ba1103f651ab09b5cbd08ea9ee2f5b31ba1e964d868dd3a3e8",
    "node_sharded_quiet":
        "24939e824a423d6d39313d9a07fbb7550c039bc9970d0d92e4b00b1f275594a8",
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
    text = lowered[key].as_text(debug_info=True)
    names = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    scoped = [n for n in names.values() if "tw.liveness" in n.split("/")]
    if key != "world_sharded_quiet":
        assert scoped == []
        return
    # the one collective of the program, in the loop's condition
    assert len(re.findall(r'"stablehlo\.all_reduce"', text)) == 1
    assert [n for n in scoped if n.endswith("/psum")] == [
        "while/cond/tw.liveness/psum"]
    assert {n.partition("tw.liveness/")[0] for n in scoped} == {
        "while/cond/"}
