"""The fleet's cell on XLA:CPU at toy size (four worlds of 2048 nodes),
through ``run.py``'s test-only entry: the result line, the comparison
and its control, the law the cell rests on (every ``--seed`` gives a
job the same work), and the three readers over a hand-made trace.
Semantics only: nothing printed here is a device number."""

import importlib
import json

import numpy as np
import pytest

import fleet_reduce
import run
import toy_fleet
import trace_reduce
from layer_metrics import fleet_route_us, fleet_superstep_us, world_occupancy
from reference import gossip_fleet_ref, gossip_ref

KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _cell(tmp_path, **cuts):
    traffic, config = run.load_cell(toy_fleet.fleet(tmp_path, **cuts),
                                    str(tmp_path))
    builder = importlib.import_module("builders." + config["builder"])
    return builder.Cell(config, traffic), config


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    return _cell(tmp_path_factory.mktemp("fleet"))


def test_last_line_has_the_contracts_keys(tmp_path, capsys):
    name = toy_fleet.fleet(tmp_path)
    rc = run.run_cell(name, 3_000_000_019, 0.3, False, on_chip=False,
                      extra_dir=str(tmp_path))
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0, out
    res = json.loads(out[-1])
    assert KEYS <= set(res)
    assert res["correct"] is True and res["failed"] == 0, out
    assert res["attempted"] >= 1
    assert {"msgs_per_s", "job_ms_p50", "setup_s"} <= set(res["metrics"])
    rows = [line for line in out if line.startswith("compared ")]
    assert len(rows) == 7 and all("(limit 0)" in r for r in rows)
    assert any(line.startswith("worlds in slot order") for line in out)


def test_every_seed_gives_a_job_the_same_work(cell):
    """Two seeds put the worlds in two orders; every job of both runs
    the same iterations and delivers the same messages, each world the
    same supersteps wherever it sits; and the second seed compiles
    nothing."""
    c, _ = cell
    seen, orders = set(), []
    for seed in (11, 3_000_000_019):
        c.set_up(seed)
        if orders:
            assert c.engine.last_run_stats["compiles"] == 0
        jobs = [c.job(i) for i in (1, 2)]
        assert not any(j["failed"] for j in jobs), jobs
        orders.append(c.order)
        for j in jobs:
            assert j["supersteps"] == max(j["world_supersteps"])
            by_world = dict(zip(c.order, j["world_supersteps"]))
            seen.add((j["supersteps"], j["msgs"],
                      tuple(by_world[s] for s in c.seeds)))
    assert orders[0] != orders[1]
    assert sorted(orders[0]) == sorted(orders[1]) == sorted(c.seeds)
    assert len(seen) == 1, seen


def test_the_fleet_equals_the_reference_and_the_control_does_not(cell):
    c, config = cell
    c.set_up(5)
    assert not c.job(1)["failed"]
    assert [row[1] for row in c.compare(gossip_fleet_ref)] == [0] * 7
    # each world is the solo reference's wave with that world's seed
    hop, got = c.fleets[0]
    for b, seed in enumerate(c.order):
        want = gossip_ref.Graph(
            {**config["params"], "engine_seed": seed}).wave(0)
        assert np.array_equal(hop[b], want.pop("hop")) and got[b] == want
    control = {name.partition(".")[2]: v
               for name, v, _ in c.control(gossip_fleet_ref)}
    # the bfloat16 lognormal moves hop counts in every world; whom the
    # rumor reaches and the deliveries are the push graph's
    assert control["hop.worlds_that_differ"] == len(c.seeds)
    assert control["hop.nodes_that_differ"] > len(c.seeds)
    assert control["infected.nodes_that_differ"] == 0
    assert control["delivered.worlds_that_differ"] == 0


def test_a_world_in_the_wrong_slot_is_found(cell):
    c, _ = cell
    c.set_up(5)
    c.job(1)
    hop, got = c.fleets[0]
    c.fleets[0] = (hop[[1, 0, 2, 3]], [got[i] for i in (1, 0, 2, 3)])
    rows = {name.partition(".")[2]: v
            for name, v, _ in c.compare(gossip_fleet_ref)}
    assert rows["slot.worlds_misplaced"] == 2
    assert rows["hop.worlds_that_differ"] == 2


def _toy_trace():
    """Two iterations of a loop: a route fusion and a sort under
    ``vmap(tw.route)``, a fire fusion, a copy of the compiler's own."""
    ops, names = [], {}
    for i in range(2):
        t = 1000 * i
        for start, dur, hlo, scope in (
                (t, 300, "%fusion.1 = s32[4,64] fusion(...)",
                 "jit(_run_while)/while/body/vmap(tw.route)/insert/add"),
                (t + 300, 200, "%sort.2 = s32[4,64] sort(...)",
                 "jit(_run_while)/while/body/vmap(tw.route)/jit(sort)/sort"),
                (t + 500, 250, "%fusion.3 = s32[4,64] fusion(...)",
                 "jit(_run_while)/while/body/vmap(tw.fire)/mul"),
                (t + 750, 50, "%copy.4 = s32[4,64] copy(...)",
                 "jit(_run_while)/while")):
            ops.append((start, dur, hlo))
            names[hlo] = scope
    trace = trace_reduce.Trace(
        ops=[ops], asyncs=[[]], modules=[(0, 2000, "jit__run_while(1)")],
        jobs=[(0, 2500, trace_reduce.JOB_SPAN)])
    jobs = [{"supersteps": 2, "world_supersteps": [2, 1, 2, 1]}]
    return trace, jobs, names


def test_the_readers_over_a_toy_trace():
    trace, jobs, names = _toy_trace()
    ctx = {"jobs": jobs, "facts": {"op_names": names}}
    assert fleet_superstep_us.read(trace, ctx) == pytest.approx(0.8)
    assert fleet_route_us.read(trace, ctx) == pytest.approx(0.5)
    assert world_occupancy.read(trace, ctx) == pytest.approx(75.0)
    assert fleet_reduce.stage_ns(trace, ctx, 2) == {
        "tw.route/insert": 600, "tw.route": 400, "tw.fire": 500,
        "unscoped": 100}


def test_the_readers_find_nothing_without_names_or_counts():
    trace, jobs, names = _toy_trace()
    # no profile was there to read, or it names no stage
    assert fleet_route_us.read(trace, {"jobs": jobs, "facts": {
        "op_names": None}}) is None
    assert fleet_route_us.read(trace, {"jobs": jobs, "facts": {
        "op_names": {k: "jit(f)/while" for k in names}}}) is None
    # a program that does not count per world (the parent of PR 27)
    parent = [{"supersteps": 2, "world_supersteps": None}]
    assert world_occupancy.read(trace, {"jobs": parent}) is None
    assert fleet_superstep_us.read(trace, {"jobs": []}) is None
    assert fleet_reduce.traced_op_names("no_such.cell", 1) is None


def test_the_committed_cell_names_bench_pys_worlds():
    traffic, config = run.load_cell("gossip_100k.fleet8")
    p = config["params"]
    assert p["world_seeds"] == list(range(8)) and p["worlds"] == 8
    assert p["origin"] == 0 and traffic["chips"] == 1
    wave = run.load_cell("gossip_100k.wave")[1]["params"]
    assert {k: v for k, v in p.items()
            if k not in ("worlds", "world_seeds", "origin")} == {
        k: v for k, v in wave.items() if k != "engine_seed"}
