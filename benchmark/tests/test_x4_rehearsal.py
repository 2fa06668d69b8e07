"""The four-chip ring's cell on XLA:CPU at toy size (4096 nodes over
four virtual devices), through ``run.py``'s test-only entry and
``control.py``'s: the result line, the gates, the control, and the
seven readers over hand-made four-plane traces, one of them with no
collective. Semantics only: nothing printed here is a device number."""

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
import ring_x4_costs
import run
import toy_x4
import trace_reduce
import x4_reduce
from builders import sharded_ring
from layer_metrics import (x4_boundary_msgs, x4_collective_us,
                           x4_collectives_per_superstep, x4_exchange_us,
                           x4_exposed_collective_us, x4_superstep_roofline,
                           x4_superstep_us)

KEYS = {"correct", "attempted", "failed", "metrics", "device"}
READERS = (x4_superstep_us, x4_collective_us, x4_exposed_collective_us,
           x4_collectives_per_superstep, x4_exchange_us, x4_boundary_msgs,
           x4_superstep_roofline)


@pytest.fixture
def four_devices():
    if len(jax.devices()) < 4:
        pytest.skip("the CPU backend was built with fewer than four "
                    "devices before this file asked for them")


def test_last_line_has_the_contracts_keys(four_devices, tmp_path, capsys):
    name = toy_x4.dense(tmp_path)
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
    assert len(rows) == 18 and all("(limit 0)" in r for r in rows)
    assert "supersteps a job 12-12" in "\n".join(out)


def test_the_control_fails_where_the_program_passes(four_devices, tmp_path,
                                                    capsys):
    name = toy_x4.dense(tmp_path)
    rc = control.main(["--workload", name, "--seconds", "0.2",
                       "--seeds", "5", "4100000007"],
                      on_chip=False, extra_dir=str(tmp_path))
    lines = [json.loads(line) for line in
             capsys.readouterr().out.splitlines() if line.startswith("{")]
    assert rc == 0 and len(lines) == 2
    for line in lines:
        assert line["correct"] and not line["control_correct"]
        assert line["failed"] == 0
        assert line["control"]["window_end.val.mismatches"] == 4096
        assert line["control"]["first_job.in_flight.mismatches"] == 4096


def test_a_state_that_left_its_slices_fails_the_gate(four_devices, tmp_path):
    name = toy_x4.dense(tmp_path)
    traffic, config = run.load_cell(name, str(tmp_path))
    cell = sharded_ring.Cell(config, traffic)
    st = cell.engine.init_state()
    assert cell._placement(st) == []
    gathered = st._replace(wake=jax.device_put(st.wake, jax.devices()[0]))
    assert cell._placement(gathered) == [
        "wake lives as 1 shards of [(4096,)] at 1 offsets on 1 devices"]
    # a mesh the cell's chips do not span is refused before anything runs
    config["params"]["mesh"]["shape"] = [2]
    with pytest.raises(SystemExit, match="is not the cell's 4 chips"):
        sharded_ring.Cell(config, traffic)


# -- the readers over hand-made traces ----------------------------------------

START = ("%collective-permute-start.1 = (s32[2,1]{1,0:T(2,128)S(1)}, "
         "s32[2,1]{1,0:T(2,128)S(1)}, u32[]{:S(2)}, u32[]{:S(2)}) "
         "collective-permute-start(s32[2,1]{1,0} %slice.153)")
DONE = ("%collective-permute-done.1 = s32[2,1]{1,0:T(2,128)S(1)} "
        "collective-permute-done((s32[2,1]{1,0}, s32[2,1]{1,0}, u32[], "
        "u32[]) %collective-permute-start.1)")
PSUM = "%psum.30 = s32[]{:T(128)} all-reduce(s32[]{:T(128)} %constant.411)"
FIRE = "%fusion.4 = s32[1024]{0} fusion(s32[1024]{0} %p.1)"
ROUTE = "%fusion.7 = s32[2,1024]{1,0} fusion(s32[2,1024]{1,0} %p.2)"
BODY = "jit(_run_while)/shard_map/while/body/"
NAMES = {START: BODY + "tw.route/exchange/ppermute",
         DONE: BODY + "tw.route/exchange/ppermute",
         PSUM: BODY + "tw.finish/psum",
         FIRE: BODY + "tw.fire/vmap(jit(step))/add",
         ROUTE: BODY + "tw.route/select_n"}


def _plane(done_ns):
    """Two supersteps of one chip: a fire fusion, the hop started, a
    route fusion while it is in flight, the wait for it (``done_ns``),
    a counter's ``psum``; on the async line the hop from start to
    done."""
    ops, asyncs = [], []
    for i in range(2):
        t = 1000 * i
        ops += [(t, 100, FIRE), (t + 100, 10, START),
                (t + 110, 40, ROUTE), (t + 150, done_ns, DONE),
                (t + 150 + done_ns, 30, PSUM)]
        asyncs.append((t + 100, 50 + done_ns, START))
    return ops, asyncs


def _x4_trace():
    planes = [_plane(50), _plane(50), _plane(50), _plane(100)]
    # a v5e's profile holds the async line for its first chip alone
    return trace_reduce.Trace(
        ops=[p[0] for p in planes],
        asyncs=[planes[0][1], [], [], []],
        modules=[(0, 2000, "jit__run_while(1)")],
        jobs=[(0, 2500, trace_reduce.JOB_SPAN)])


def _ctx(**facts):
    return {"jobs": [{"supersteps": 2, "boundary_msgs": 8}],
            "peaks": {"hbm_gbps": 819.0},
            "facts": {"op_names": NAMES, "superstep_bytes": 0, **facts}}


def test_which_operations_are_collectives():
    assert [x4_reduce.opcode(h) for h in (START, DONE, PSUM, FIRE)] == [
        "collective-permute-start", "collective-permute-done",
        "all-reduce", "fusion"]
    assert [x4_reduce.is_collective(h) for h in (START, DONE, PSUM, FIRE,
                                                 ROUTE)] == [
        True, True, True, False, False]
    ag = "%all-gather.2 = s64[4]{0} all-gather(s64[1]{0} %x), dimensions={0}"
    assert x4_reduce.is_collective(ag)
    assert not x4_reduce.is_collective("%copy.1 = s32[4]{0} copy(s32[4] %y)")


def test_a_hop_in_flight_is_rebuilt_from_its_two_halves():
    ops, asyncs = _plane(50)
    # what the async line holds, from the chip's own operations
    assert x4_reduce.in_flight(ops) == asyncs
    assert x4_reduce.in_flight([e for e in ops if e[2] != DONE]) == []
    # the same reading with the async line and without it
    assert x4_reduce.collective_ns(ops, asyncs) \
        == x4_reduce.collective_ns(ops, []) == 2 * 130
    assert x4_reduce.exposed_ns(ops, asyncs) \
        == x4_reduce.exposed_ns(ops, []) == 2 * 90


def test_the_readers_over_a_four_plane_trace():
    trace = _x4_trace()
    nbytes = ring_x4_costs.x4_superstep_bytes(1 << 10)
    ctx = _ctx(superstep_bytes=nbytes)
    # busy a superstep: 230 ns on three planes, 280 on the fourth
    assert x4_superstep_us.read(trace, ctx) == pytest.approx(0.2425)
    # the hop from its start to its done and the psum behind it
    assert x4_collective_us.read(trace, ctx) == pytest.approx(0.1425)
    # less the 40 ns the route fusion covers
    assert x4_exposed_collective_us.read(trace, ctx) == pytest.approx(0.1025)
    # an async collective counts once: the hop and the psum
    assert x4_collectives_per_superstep.read(trace, ctx) == 2.0
    # the hop's two halves: 10 + 50 ns, on the fourth plane 10 + 100
    assert x4_exchange_us.read(trace, ctx) == pytest.approx(0.0725)
    assert x4_boundary_msgs.read(trace, ctx) == 4.0
    assert x4_superstep_roofline.read(trace, ctx) == pytest.approx(
        100 * nbytes / 819e3 / 0.2425)
    got = {r.__name__: r.read(trace, ctx) for r in READERS}
    assert got["layer_metrics.x4_exposed_collective_us"] \
        <= got["layer_metrics.x4_collective_us"] \
        <= got["layer_metrics.x4_superstep_us"]


def test_the_readers_find_nothing_where_nothing_is():
    # a one-chip program: no collective anywhere, no scope, no counter
    ops = [(0, 100, FIRE), (100, 40, ROUTE), (1000, 100, FIRE),
           (1100, 40, ROUTE)]
    trace = trace_reduce.Trace(
        ops=[ops], asyncs=[[]], modules=[(0, 2000, "jit__run_while(1)")],
        jobs=[(0, 2500, trace_reduce.JOB_SPAN)])
    parent = {k: v.replace("tw.route/", "").replace("tw.fire/", "")
              for k, v in NAMES.items()}
    ctx = {"jobs": [{"supersteps": 2}], "peaks": None,
           "facts": {"op_names": parent}}
    for reader in (x4_collective_us, x4_exposed_collective_us,
                   x4_collectives_per_superstep, x4_exchange_us,
                   x4_boundary_msgs, x4_superstep_roofline):
        assert reader.read(trace, ctx) is None, reader.__name__
    assert x4_superstep_us.read(trace, ctx) == pytest.approx(0.14)
    # the scope is there and the builder brought no names; no supersteps
    assert x4_exchange_us.read(_x4_trace(), _ctx(op_names=None)) is None
    for reader in READERS:
        assert reader.read(_x4_trace(), {**_ctx(), "jobs": []}) is None
    # a program that counts no boundary messages (the parent)
    assert x4_boundary_msgs.read(_x4_trace(), {
        **_ctx(), "jobs": [{"supersteps": 2, "boundary_msgs": None}]}) is None


def test_the_bytes_of_a_shards_superstep():
    # cnt, val int32; send_at, wake int64; two slots of a deliver time
    # and two payload words
    assert ring_x4_costs.x4_node_bytes(2) == 48
    assert ring_x4_costs.x4_superstep_bytes(1) == 96
    assert ring_x4_costs.x4_superstep_bytes(1 << 18) == 25_165_824


def test_the_committed_cell_is_the_one_chip_rings_traffic_on_four_chips():
    traffic, config = run.load_cell("ring_1m_x4.dense")
    ring, ring_config = run.load_cell("ring_1m.dense")
    same = set(ring) - {"name", "config", "chips", "loop"}
    assert {k: traffic[k] for k in same} == {k: ring[k] for k in same}
    assert traffic["chips"] == 4 and traffic["supersteps_per_job"] == 256
    p = dict(config["params"])
    assert p.pop("mesh") == {"shape": [4], "axes": ["nodes"],
                             "source_shape": [8]}
    assert p == ring_config["params"]
    assert config["reference"] == "ring_ref"
    assert config["reduced"] == ["mesh", "with_observer"]
    bench = run._load_json(run.ROOT, "BENCHMARK.json")
    # found by name: a later PR appends behind them
    entry, = [w for w in bench["workloads"] if w["name"] == traffic["name"]]
    assert (entry["config"], entry["chips"]) == (config["name"], 4)
    listed, = [c for c in bench["configs"] if c["name"] == config["name"]]
    assert listed["reduced"] == config["reduced"]
    assert listed["source"] == config["source"]
    mine = [m for m in bench["per_layer"]
            if m.get("workloads") == ["ring_1m_x4.dense"]]
    assert [m["name"] for m in mine] == [
        r.__name__.rpartition(".")[2] for r in READERS]
    assert {m["moves"] for m in mine} == {"msgs_per_s"}
    # job_ms_p95 stays the one-chip ring's
    assert [m["name"] for m in bench["end_to_end"]
            if "ring_1m_x4.dense" in m.get("workloads",
                                           ["ring_1m_x4.dense"])] == [
        "msgs_per_s", "job_ms_p50", "setup_s"]
