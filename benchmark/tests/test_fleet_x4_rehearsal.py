"""The world-sharded fleet's cell on XLA:CPU at toy size (eight worlds
of 1024 nodes over four virtual devices), through ``run.py``'s
test-only entry and ``control.py``'s: the result line, the gates, the
control, and the nine readers over hand-made four-plane traces and
records, ``None`` and never 0 where there is nothing to read. Semantics
only: nothing printed here is a device number."""

import json
import os

# four virtual devices for the mesh, asked for before any test of the
# session builds the CPU backend (conftest.py here asks for none)
if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=4").strip()

import jax
import pytest

import control
import fleet_x4_costs
import fleet_x4_reduce
import run
import toy_fleet_x4
import trace_reduce
from layer_metrics import (fleet_x4_collectives_per_iteration,
                           fleet_x4_exposed_liveness_us,
                           fleet_x4_liveness_us, fleet_x4_plane_skew,
                           fleet_x4_route_us, fleet_x4_rung_spread,
                           fleet_x4_superstep_roofline,
                           fleet_x4_superstep_us, fleet_x4_world_occupancy)

KEYS = {"correct", "attempted", "failed", "metrics", "device"}
READERS = (fleet_x4_superstep_us, fleet_x4_route_us, fleet_x4_liveness_us,
           fleet_x4_exposed_liveness_us, fleet_x4_collectives_per_iteration,
           fleet_x4_plane_skew, fleet_x4_rung_spread,
           fleet_x4_world_occupancy, fleet_x4_superstep_roofline)


@pytest.fixture
def four_devices():
    if len(jax.devices()) < 4:
        pytest.skip("the CPU backend was built with fewer than four "
                    "devices before this file asked for them")


def test_last_line_has_the_contracts_keys(four_devices, tmp_path, capsys):
    name = toy_fleet_x4.fleet(tmp_path)
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


def test_the_control_fails_where_the_program_passes(four_devices, tmp_path,
                                                    capsys):
    name = toy_fleet_x4.fleet(tmp_path)
    rc = control.main(["--workload", name, "--seconds", "0.2",
                       "--seeds", "5", "4600000007"],
                      on_chip=False, extra_dir=str(tmp_path))
    lines = [json.loads(line) for line in
             capsys.readouterr().out.splitlines() if line.startswith("{")]
    assert rc == 0 and len(lines) == 2
    for line in lines:
        assert line["correct"] and not line["control_correct"]
        assert line["failed"] == 0
        worlds, = {v for k, v in line["control"].items()
                   if k.endswith("hop.worlds_that_differ")}
        assert worlds == 8


# -- the readers over hand-made traces ----------------------------------------

LIVE = ("%psum.8 = s32[]{:T(128)} all-reduce(s32[]{:T(128)} "
        "%convert_element_type.8519), channel_id=1")
OTHER = "%psum.30 = s32[]{:T(128)} all-reduce(s32[]{:T(128)} %constant.4)"
FIRE = "%fusion.4 = s32[8,1024]{1,0} fusion(s32[8,1024]{1,0} %p.1)"
ROUTE = "%fusion.7 = s32[8,2,1024]{2,1,0} fusion(s32[8,2,1024]{2,1,0} %p.2)"
SORT = "%sort.9 = s32[8,2048]{1,0} sort(s32[8,2048]{1,0} %p.3)"
LOOP = "jit(_run_while)/shard_map/while/"
NAMES = {LIVE: LOOP + "cond/tw.liveness/psum",
         OTHER: "jit(counters)/reduce_sum",
         FIRE: LOOP + "body/vmap(tw.fire)/vmap()/add",
         ROUTE: LOOP + "body/vmap(tw.route)/insert/select_n",
         SORT: LOOP + "body/vmap(tw.route)/jit(sort)/sort"}


def _plane(route_ns, wait_ns):
    """Two iterations of one chip: a fire fusion, the route stage at
    the chip's own rung (``route_ns``), then the liveness reduction,
    in which the chip sits until the slowest arrives (``wait_ns``)."""
    ops = []
    for i in range(2):
        t = 1000 * i
        ops += [(t, 100, FIRE), (t + 100, route_ns, ROUTE),
                (t + 100 + route_ns, 50, SORT),
                (t + 150 + route_ns, wait_ns, LIVE)]
    return ops


def _x4_trace():
    # three chips at a narrow rung wait for the fourth at a wide one
    planes = [_plane(200, 310), _plane(200, 310), _plane(200, 310),
              _plane(500, 10)]
    return trace_reduce.Trace(
        ops=planes, asyncs=[[], [], [], []],
        modules=[(0, 2000, "jit__run_while(1)")],
        jobs=[(0, 2500, trace_reduce.JOB_SPAN)])


def _ctx(**job):
    return {"jobs": [{"supersteps": 2, "msgs": 4096,
                      "world_supersteps": [2, 1] * 4,
                      "device_rung_lanes": [128, 128, 128, 512], **job}],
            "peaks": {"hbm_gbps": 819.0},
            "facts": {"op_names": NAMES, "n_nodes": 1024, "mailbox_cap": 24,
                      "payload_width": 1, "worlds": 8, "worlds_local": 2}}


def test_which_operation_is_the_liveness_reduction():
    assert fleet_x4_reduce.is_liveness(LIVE, NAMES)
    # a collective under no such scope, and the scope's other operations
    assert not fleet_x4_reduce.is_liveness(OTHER, NAMES)
    assert not fleet_x4_reduce.is_liveness(FIRE, NAMES)
    assert not fleet_x4_reduce.is_liveness(LIVE, {})
    assert [fleet_x4_reduce.scope_of(NAMES[h]) for h in (
        LIVE, OTHER, FIRE, ROUTE, SORT)] == [
        "tw.liveness", "unscoped", "tw.fire", "tw.route", "tw.route"]


def test_the_readers_over_a_four_plane_trace():
    trace, ctx = _x4_trace(), _ctx()
    # busy an iteration: 660 ns on every plane, the wait included
    assert fleet_x4_superstep_us.read(trace, ctx) == pytest.approx(0.66)
    # the route fusion and its sort: 250 ns on three planes, 550
    assert fleet_x4_route_us.read(trace, ctx) == pytest.approx(0.325)
    # the reduction and the wait in it: 310 on three planes, 10
    assert fleet_x4_liveness_us.read(trace, ctx) == pytest.approx(0.235)
    # nothing runs beside it
    assert fleet_x4_exposed_liveness_us.read(trace, ctx) \
        == pytest.approx(0.235)
    assert fleet_x4_collectives_per_iteration.read(trace, ctx) == 1.0
    # every plane busy alike: the wait is busy time in the collective
    assert fleet_x4_plane_skew.read(trace, ctx) == 0.0
    # 1 - mean(128, 128, 128, 512) / 512
    assert fleet_x4_rung_spread.read(trace, ctx) == pytest.approx(56.25)
    assert fleet_x4_world_occupancy.read(trace, ctx) == pytest.approx(75.0)
    nbytes = fleet_x4_costs.fleet_x4_superstep_bytes(
        1024, 24, 1, 2, 4096 / 2 / 4)
    assert fleet_x4_superstep_roofline.read(trace, ctx) == pytest.approx(
        100 * nbytes / 819e3 / 0.66)


def test_a_collective_of_another_program_is_counted_and_not_timed():
    trace = _x4_trace()
    trace.ops[0].append((1900, 40, OTHER))
    ctx = _ctx()
    assert fleet_x4_collectives_per_iteration.read(trace, ctx) == 1.5
    assert fleet_x4_liveness_us.read(trace, ctx) == pytest.approx(0.235)
    # a chip that idles where the others wait inside the reduction
    lazy = trace_reduce.Trace(
        ops=trace.ops[:3] + [[e for e in trace.ops[3] if e[2] != LIVE]],
        asyncs=trace.asyncs, modules=trace.modules, jobs=trace.jobs)
    assert fleet_x4_plane_skew.read(lazy, ctx) == pytest.approx(
        100 * (1360 - 1300) / 1360)


def test_the_readers_find_nothing_where_nothing_is():
    # the parent of the PR that named the scope and counted a device:
    # the same trace, the reduction under the loop's own name
    parent = {**NAMES, LIVE: LOOP + "cond/psum"}
    trace = _x4_trace()
    ctx = _ctx(device_rung_lanes=None)
    ctx["facts"]["op_names"] = parent
    for reader in (fleet_x4_liveness_us, fleet_x4_exposed_liveness_us,
                   fleet_x4_rung_spread):
        assert reader.read(trace, ctx) is None, reader.__name__
    # what needs neither still reads
    assert fleet_x4_collectives_per_iteration.read(trace, ctx) == 1.0
    assert fleet_x4_route_us.read(trace, ctx) == pytest.approx(0.325)
    # no profile was there to read
    ctx["facts"]["op_names"] = None
    for reader in (fleet_x4_route_us, fleet_x4_liveness_us,
                   fleet_x4_exposed_liveness_us):
        assert reader.read(trace, ctx) is None, reader.__name__
    # a one-chip program: no collective, one plane, no mesh to count
    solo = trace_reduce.Trace(
        ops=[[(0, 100, FIRE), (100, 40, ROUTE)]], asyncs=[[]],
        modules=[(0, 200, "jit__run_while(1)")],
        jobs=[(0, 250, trace_reduce.JOB_SPAN)])
    one = {"jobs": [{"supersteps": 1, "msgs": 8}], "peaks": None,
           "facts": {"op_names": NAMES}}
    for reader in (fleet_x4_liveness_us, fleet_x4_exposed_liveness_us,
                   fleet_x4_collectives_per_iteration, fleet_x4_plane_skew,
                   fleet_x4_rung_spread, fleet_x4_world_occupancy,
                   fleet_x4_superstep_roofline):
        assert reader.read(solo, one) is None, reader.__name__
    # no iteration ran: nothing is a rate
    for reader in READERS:
        if reader is not fleet_x4_plane_skew:
            assert reader.read(_x4_trace(), {**_ctx(), "jobs": []}) is None, \
                reader.__name__


def test_the_bytes_of_a_chips_iteration():
    # hop, lcg, left int32; next, wake int64; 24 slots of a deliver
    # time and one payload word; a message's two words
    assert fleet_x4_costs.fleet_x4_superstep_bytes(1, 24, 1, 1, 0) == 440
    assert fleet_x4_costs.fleet_x4_superstep_bytes(1, 24, 1, 1, 3) == 464
    assert fleet_x4_costs.fleet_x4_superstep_bytes(
        1 << 17, 24, 1, 8, 0) == 461_373_440


def test_the_committed_cell_is_the_fleets_load_on_each_of_four_chips():
    traffic, config = run.load_cell("gossip_100k_x4.fleet32")
    fleet, fleet_config = run.load_cell("gossip_100k.fleet8")
    same = set(fleet) - {"name", "config", "traffic", "chips", "loop",
                         "seed_draws"}
    assert {k: traffic[k] for k in same} == {k: fleet[k] for k in same}
    assert traffic["chips"] == 4 and traffic["traffic"] == "fleet32"
    p = dict(config["params"])
    assert p.pop("mesh") == {"shape": [4], "axes": ["worlds"],
                             "source_shape": [8]}
    assert p.pop("worlds") == 32 and p.pop("world_seeds") == list(range(32))
    q = dict(fleet_config["params"])
    # a chip holds the source's own eight
    assert q.pop("worlds") * 4 == 32 and q.pop("world_seeds") == list(
        range(8))
    assert p == q
    assert config["reference"] == fleet_config["reference"] \
        == "gossip_fleet_ref"
    assert config["reduced"] == ["mesh", "worlds"]
    assert set(fleet_config["guarantees"]) | {"sharding"} == set(
        config["guarantees"])
    bench = run._load_json(run.ROOT, "BENCHMARK.json")
    # found by name: a later PR appends behind them
    entry, = [w for w in bench["workloads"] if w["name"] == traffic["name"]]
    assert (entry["config"], entry["chips"]) == (config["name"], 4)
    listed, = [c for c in bench["configs"] if c["name"] == config["name"]]
    assert listed["reduced"] == config["reduced"]
    assert listed["source"] == config["source"]
    # the driver's rule of form: 1 to 200 printable characters, on one line
    for line in (listed["source"], listed["why"], entry["why"]):
        assert 1 <= len(line) <= 200 and line.isascii() and line.isprintable()
    mine = [m for m in bench["per_layer"]
            if m.get("workloads") == ["gossip_100k_x4.fleet32"]]
    assert [m["name"] for m in mine] == [
        r.__name__.rpartition(".")[2] for r in READERS]
    assert {m["moves"] for m in mine} == {"msgs_per_s"}
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) * 2 <= len(bench["workloads"])
    # job_ms_p95 stays the one-chip ring's
    assert [m["name"] for m in bench["end_to_end"]
            if traffic["name"] in m.get("workloads", [traffic["name"]])] == [
        "msgs_per_s", "job_ms_p50", "setup_s"]
