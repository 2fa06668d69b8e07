"""The node-sharded steady cell on XLA:CPU at toy size (4096 nodes over
four virtual devices), through ``run.py``'s test-only entry and
``control.py``'s: the result line, the gates, the three controls, and
the twelve readers over hand-made four-plane traces, one with the
``all_to_all`` as ``-start`` / ``-done`` pairs, one with it synchronous,
one with nothing to read. Semantics only: nothing printed here is a
device number."""

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
import run
import steady_costs
import steady_x4_costs
import steady_x4_reduce
import toy_steady_x4
import trace_reduce
import x4_reduce
from builders import gossip_steady_x4
from layer_metrics import (a2a_bucket_fill, a2a_bucket_us, a2a_collective_us,
                           a2a_collectives_per_superstep, a2a_exchange_bytes,
                           a2a_exchange_us, a2a_exposed_collective_us,
                           a2a_insert_us, a2a_remote_msgs, a2a_sort_us,
                           a2a_superstep_roofline, a2a_superstep_us)

KEYS = {"correct", "attempted", "failed", "metrics", "device"}
READERS = (a2a_superstep_us, a2a_exchange_us, a2a_bucket_us,
           a2a_collective_us, a2a_exposed_collective_us,
           a2a_collectives_per_superstep, a2a_sort_us, a2a_insert_us,
           a2a_remote_msgs, a2a_bucket_fill, a2a_exchange_bytes,
           a2a_superstep_roofline)


@pytest.fixture
def four_devices():
    if len(jax.devices()) < 4:
        pytest.skip("the CPU backend was built with fewer than four "
                    "devices before this file asked for them")


def test_last_line_has_the_contracts_keys(four_devices, tmp_path, capsys):
    name = toy_steady_x4.rounds(tmp_path)
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
    # the steady cell's twenty and its mailbox row, then the exchange's
    assert len(rows) == 25 and sum("(limit 0)" in r for r in rows) == 23
    assert "largest_in_flight_to_one_node" in rows[20] \
        and "(limit 24)" in rows[20]
    assert [r.split()[1].rstrip(":") for r in rows[21:]] == [
        "first_job.remote_msgs.off_by", "window.remote_msgs.off_by",
        "window.bucket_fill_peak.off_by", "reference.largest_bucket"]
    assert "(limit 320)" in rows[-1]
    assert "supersteps a job 16-16" in "\n".join(out)


def test_a_capacity_under_the_largest_bucket_fails_the_gates(
        four_devices, tmp_path, capsys):
    name = toy_steady_x4.rounds(tmp_path, bucket_cap=256)
    with pytest.raises(SystemExit, match="overflow="):
        run.run_cell(name, 7, 0.2, False, on_chip=False,
                     extra_dir=str(tmp_path))
    capsys.readouterr()


def test_the_three_controls_fail_where_the_program_passes(
        four_devices, tmp_path, capsys):
    name = toy_steady_x4.rounds(tmp_path)
    rc = control.main(["--workload", name, "--seconds", "0.2",
                       "--seeds", "5", "4100000007"],
                      on_chip=False, extra_dir=str(tmp_path))
    lines = [json.loads(line) for line in
             capsys.readouterr().out.splitlines() if line.startswith("{")]
    assert rc == 0 and len(lines) == 2
    for line in lines:
        assert line["correct"] and not line["control_correct"]
        assert line["failed"] == 0
        got = line["control"]
        assert got["low_word.window_end.in_flight_count.mismatches"] > 0
        assert got["small_mailbox.first_job.overflow"] > 0
        assert got["small_bucket.first_job.overflow"] > 0
        assert got["small_bucket.first_job.in_flight_count.mismatches"] > 0
        assert got["small_bucket.first_job.delivered.mismatches"] == 1


def test_a_control_that_passes_does_not_hide_behind_the_others(
        four_devices, tmp_path, capsys):
    # a control capacity that holds every bucket cuts nothing
    name = toy_steady_x4.rounds(tmp_path, control_bucket_cap=1024)
    rc = control.main(["--workload", name, "--seconds", "0.2",
                       "--seeds", "5"],
                      on_chip=False, extra_dir=str(tmp_path))
    out = capsys.readouterr().out
    line, = [json.loads(x) for x in out.splitlines() if x.startswith("{")]
    assert rc == 1 and line["control_correct"]
    assert "the control small_bucket passed the comparison" in out
    assert set(line["control"]) == {
        r for r in line["sound"] if r.startswith("first_job.")
        and "remote" not in r} | {"first_job.overflow"}


def test_a_state_that_left_its_slices_fails_the_gate(four_devices, tmp_path):
    name = toy_steady_x4.rounds(tmp_path)
    traffic, config = run.load_cell(name, str(tmp_path))
    cell = gossip_steady_x4.Cell(config, traffic)
    st = cell.engine.init_state()
    assert cell._placement(st) == []
    gathered = st._replace(wake=jax.device_put(st.wake, jax.devices()[0]))
    assert cell._placement(gathered) == [
        "wake lives as 1 shards of [(4096,)] at 1 offsets on 1 devices",
        "leaves laid out otherwise than a fresh state's: ['.wake']"]
    # a mesh the cell's chips do not span is refused before anything runs
    config["params"]["devices"] = 2
    with pytest.raises(SystemExit, match="are not the cell's 4 chips"):
        gossip_steady_x4.Cell(config, traffic)


def test_a_program_that_does_not_count_its_exchange_ends_at_once(
        four_devices, tmp_path, monkeypatch):
    from timewarp_tpu.interp.jax_engine.engine import JaxEngine
    from timewarp_tpu.interp.jax_engine.sharded import ShardedEngine
    # the parent of PR 49: the general engine's counts and no more
    monkeypatch.setattr(ShardedEngine, "_counted", JaxEngine._counted)
    name = toy_steady_x4.rounds(tmp_path)
    traffic, config = run.load_cell(name, str(tmp_path))
    with pytest.raises(SystemExit, match="does not count what its "
                                         "exchange hands over"):
        gossip_steady_x4.Cell(config, traffic)


# -- the readers over hand-made traces ----------------------------------------

A2A_START = ("%all-to-all-start.3 = (s32[4,1,320]{2,1,0:T(1,128)S(1)}, "
             "s32[4,1,320]{2,1,0:T(1,128)S(1)}) "
             "all-to-all-start(s32[4,1,320]{2,1,0} %bitcast.7)")
A2A_DONE = ("%all-to-all-done.3 = s32[4,1,320]{2,1,0:T(1,128)S(1)} "
            "all-to-all-done((s32[4,1,320]{2,1,0}, s32[4,1,320]{2,1,0}) "
            "%all-to-all-start.3)")
A2A_SYNC = ("%all_to_all.192 = s32[4,1,320]{2,1,0:T(1,128)S(1)} "
            "all-to-all(s32[4,1,320]{2,1,0} %bitcast.376), channel_id=1")
PSUM = "%psum.30 = s32[]{:T(128)} all-reduce(s32[]{:T(128)} %constant.411)"
FIRE = "%fusion.4 = s32[1024]{0} fusion(s32[1024]{0} %p.1)"
BUCKET = "%sort.5 = (s32[1024]{0}, s32[1024]{0}) sort(s32[1024]{0} %p.2)"
SCAT = "%fusion.6 = s32[4,320]{1,0} fusion(s32[1024]{0} %p.3)"
SORT = "%sort.8 = (s32[1280]{0}, s32[1280]{0}) sort(s32[1280]{0} %p.4)"
INSERT = "%fusion.9 = s32[24,1024]{1,0} fusion(s32[1280]{0} %p.5)"
BODY = "jit(_run_while)/shard_map/while/body/"
NAMES = {A2A_START: BODY + "tw.route/exchange/swap/all_to_all",
         A2A_DONE: BODY + "tw.route/exchange/swap/all_to_all",
         A2A_SYNC: BODY + "tw.route/exchange/swap/all_to_all",
         PSUM: BODY + "tw.route/exchange/psum",
         FIRE: BODY + "tw.fire/vmap(jit(step))/add",
         BUCKET: BODY + "tw.route/exchange/bucket/sort",
         SCAT: BODY + "tw.route/exchange/bucket/scatter",
         SORT: BODY + "tw.route/sort/sort",
         INSERT: BODY + "tw.route/insert/scatter"}


def _plane(wait_ns, sync):
    """Two supersteps of one chip: a fire fusion, the sort by shard and
    a scatter, the ``all_to_all`` (async: started, the overflow's
    ``psum`` while it is in flight, the wait for it; synchronous: one
    operation of ``40 + wait_ns``, then the ``psum``), the sort by
    destination, the insertion."""
    ops = []
    for i in range(2):
        t = 1000 * i
        ops += [(t, 100, FIRE), (t + 100, 80, BUCKET), (t + 180, 60, SCAT)]
        if sync:
            ops += [(t + 240, 40 + wait_ns, A2A_SYNC),
                    (t + 280 + wait_ns, 30, PSUM)]
        else:
            ops += [(t + 240, 10, A2A_START), (t + 250, 30, PSUM),
                    (t + 280, wait_ns, A2A_DONE)]
        ops += [(t + 310 + wait_ns, 120, SORT),
                (t + 430 + wait_ns, 90, INSERT)]
    return ops


def _trace(sync=False):
    waits = (50, 50, 50, 100)            # the fourth chip waits longest
    return trace_reduce.Trace(
        ops=[_plane(w, sync) for w in waits], asyncs=[[], [], [], []],
        modules=[(0, 2000, "jit__run_while(1)")],
        jobs=[(0, 2500, trace_reduce.JOB_SPAN)])


def _ctx(**facts):
    nbytes = steady_x4_costs.superstep_bytes(1024, 24, 1, 4, 320)
    return {"jobs": [{"supersteps": 2, "remote_msgs": 6150,
                      "bucket_fill_peak": 296, "exchange_lanes": 1280}],
            "peaks": {"hbm_gbps": 819.0},
            "facts": {"op_names": NAMES, "superstep_bytes": nbytes,
                      "bucket_cap": 320, "payload_width": 1, **facts}}


def test_which_operations_are_collectives():
    ops = (A2A_START, A2A_DONE, A2A_SYNC, PSUM, FIRE, BUCKET)
    assert [x4_reduce.opcode(h) for h in ops] == [
        "all-to-all-start", "all-to-all-done", "all-to-all", "all-reduce",
        "fusion", "sort"]
    assert [steady_x4_reduce.is_collective(h) for h in ops] == [
        True, True, True, True, False, False]
    # the reader the benchmark had sees no all-to-all: why this one is
    assert [x4_reduce.is_collective(h) for h in ops[:4]] == [
        False, False, False, True]
    ag = "%all-gather.2 = s64[4]{0} all-gather(s64[1]{0} %x), dimensions={0}"
    assert steady_x4_reduce.is_collective(ag)


def test_an_all_to_all_in_flight_is_rebuilt_from_its_two_halves():
    ops = _plane(50, sync=False)
    assert steady_x4_reduce.in_flight(ops) == [
        (240, 90, A2A_START), (1240, 90, A2A_START)]
    assert steady_x4_reduce.in_flight(_plane(50, sync=True)) == []
    # from the start to the done's end, the psum inside it: 90 ns; of
    # which nothing else covers anything (the psum is a collective too)
    assert steady_x4_reduce.collective_ns(ops, []) == 2 * 90
    assert steady_x4_reduce.exposed_ns(ops, []) == 2 * 90
    assert steady_x4_reduce.executed(ops) == 4
    sync = _plane(50, sync=True)
    assert steady_x4_reduce.collective_ns(sync, []) == 2 * 120
    assert steady_x4_reduce.executed(sync) == 4


@pytest.mark.parametrize("sync", [False, True])
def test_the_readers_over_a_four_plane_trace(sync):
    trace, ctx = _trace(sync), _ctx()
    extra = 30 if sync else 0            # the psum behind, not inside
    # busy a superstep: 540 ns on three planes, 590 on the fourth
    busy = (552.5 + extra) / 1e3
    assert a2a_superstep_us.read(trace, ctx) == pytest.approx(busy)
    # the whole scope: bucket 140, swap 60 (110), the psum 30
    assert a2a_exchange_us.read(trace, ctx) == pytest.approx(
        (242.5 + extra) / 1e3)
    assert a2a_bucket_us.read(trace, ctx) == pytest.approx(0.14)
    assert a2a_collective_us.read(trace, ctx) == pytest.approx(
        (102.5 + extra) / 1e3)
    assert a2a_exposed_collective_us.read(trace, ctx) == pytest.approx(
        (102.5 + extra) / 1e3)
    assert a2a_collectives_per_superstep.read(trace, ctx) == 2.0
    assert a2a_sort_us.read(trace, ctx) == pytest.approx(0.12)
    assert a2a_insert_us.read(trace, ctx) == pytest.approx(0.09)
    assert a2a_remote_msgs.read(trace, ctx) == 3075.0
    assert a2a_bucket_fill.read(trace, ctx) == pytest.approx(92.5)
    assert a2a_exchange_bytes.read(trace, ctx) == 1280 * 25
    assert a2a_superstep_roofline.read(trace, ctx) == pytest.approx(
        100 * ctx["facts"]["superstep_bytes"] / 819e3 / busy)


def test_the_readers_find_nothing_where_nothing_is():
    # the parent of PR 49: one scope for the exchange, no counter
    trace = _trace()
    parent = {k: v.replace("exchange/bucket/", "exchange/")
              .replace("exchange/swap/", "exchange/")
              for k, v in NAMES.items()}
    ctx = _ctx(op_names=parent)
    ctx["jobs"] = [{"supersteps": 2, "remote_msgs": None,
                    "bucket_fill_peak": None, "exchange_lanes": None}]
    for reader in (a2a_bucket_us, a2a_remote_msgs, a2a_bucket_fill,
                   a2a_exchange_bytes):
        assert reader.read(trace, ctx) is None, reader.__name__
    assert a2a_exchange_us.read(trace, ctx) == pytest.approx(0.2425)
    # a one-chip program: no collective anywhere
    ops = [(0, 100, FIRE), (100, 120, SORT), (220, 90, INSERT)]
    solo = trace_reduce.Trace(
        ops=[ops], asyncs=[[]], modules=[(0, 1000, "jit__run_while(1)")],
        jobs=[(0, 1500, trace_reduce.JOB_SPAN)])
    one = {"jobs": [{"supersteps": 1}], "peaks": None,
           "facts": {"op_names": NAMES}}
    for reader in (a2a_collective_us, a2a_exposed_collective_us,
                   a2a_collectives_per_superstep, a2a_exchange_us,
                   a2a_bucket_us, a2a_remote_msgs, a2a_bucket_fill,
                   a2a_exchange_bytes, a2a_superstep_roofline):
        assert reader.read(solo, one) is None, reader.__name__
    assert a2a_sort_us.read(solo, one) == pytest.approx(0.12)
    # no profile was there to read; no supersteps
    assert a2a_bucket_us.read(trace, _ctx(op_names=None)) is None
    for reader in READERS:
        if reader not in (a2a_bucket_fill, a2a_exchange_bytes):
            assert reader.read(trace, {**_ctx(), "jobs": []}) is None


def test_the_bytes_of_a_shards_superstep_and_of_its_exchange():
    # an int8 and six int32 words a lane at one payload word
    assert steady_x4_costs.lane_bytes(1) == 25
    assert steady_x4_costs.exchange_bytes(4, 73_728) == 7_372_800
    assert steady_x4_costs.superstep_bytes(1 << 18, 24, 1, 4, 73_728) \
        == steady_costs.steady_superstep_bytes(1 << 18, 24) \
        + 4 * 7_372_800 == 146_931_712


def test_the_committed_cell_is_the_one_chip_cells_traffic_on_four_chips():
    traffic, config = run.load_cell("gossip_steady_1m_x4.rounds")
    solo, solo_config = run.load_cell("gossip_steady_1m.rounds")
    same = set(solo) - {"name", "config", "chips", "loop"}
    assert {k: traffic[k] for k in same} == {k: solo[k] for k in same}
    assert traffic["chips"] == 4 and traffic["supersteps_per_job"] == 16
    p = dict(config["params"])
    assert (p.pop("devices"), p.pop("axis")) == (4, "nodes")
    cap = p.pop("bucket_cap")
    assert cap % 8192 == 0 and 65_536 < cap < 262_144
    assert config["control"]["bucket_cap"] == 65_536
    assert p == solo_config["params"]
    assert config["reference"] == "gossip_steady_x4_ref"
    assert config["reduced"] == ["mesh"]
    assert set(config["guarantees"]) == {"delivery", "saturation",
                                         "exactness", "placement"}
    bench = run._load_json(run.ROOT, "BENCHMARK.json")
    # found by name: a later PR appends behind them
    entry, = [w for w in bench["workloads"] if w["name"] == traffic["name"]]
    assert (entry["config"], entry["chips"]) == (config["name"], 4)
    assert f"{cap:,}".replace(",", " ") in entry["why"]
    listed, = [c for c in bench["configs"] if c["name"] == config["name"]]
    assert listed["reduced"] == config["reduced"]
    assert listed["source"] == config["source"]
    mine = [m for m in bench["per_layer"]
            if m.get("workloads") == ["gossip_steady_1m_x4.rounds"]]
    assert [m["name"] for m in mine] == [
        r.__name__.rpartition(".")[2] for r in READERS]
    assert {m["moves"] for m in mine} == {"msgs_per_s"}
    assert [m["name"] for m in bench["end_to_end"]
            if traffic["name"] in m.get("workloads", [traffic["name"]])] == [
        "msgs_per_s", "job_ms_p50", "setup_s"]
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 3 \
        and len(bench["workloads"]) == 9
