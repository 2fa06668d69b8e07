"""The praos fleet's cell on XLA:CPU at toy size (four worlds of 2 048
nodes on the source's four medians), through ``run.py``'s test-only
entry and ``control.py``'s: the result line, the equal-work line, the
final and the mid-flood rows, the three controls, and the eight readers
over a hand-made trace and with nothing to read. Semantics only:
nothing printed here is a device number."""

import json

import pytest

import control
import praos_costs
import praos_fleet_costs
import run
import toy_praos_fleet
import trace_reduce
from layer_metrics import (praos_fleet_fire_us, praos_fleet_memory_share,
                           praos_fleet_route_us, praos_fleet_rung_fit,
                           praos_fleet_sample_us,
                           praos_fleet_superstep_roofline,
                           praos_fleet_superstep_us,
                           praos_fleet_world_occupancy)

KEYS = {"correct", "attempted", "failed", "metrics", "device"}
EIGHT = ["praos_fleet_superstep_us", "praos_fleet_route_us",
         "praos_fleet_fire_us", "praos_fleet_sample_us",
         "praos_fleet_world_occupancy", "praos_fleet_rung_fit",
         "praos_fleet_memory_share", "praos_fleet_superstep_roofline"]


def test_last_line_has_the_contracts_keys(tmp_path, capsys):
    name = toy_praos_fleet.fleet(tmp_path)
    rc = run.run_cell(name, 5_500_000_017, 0.3, False, on_chip=False,
                      extra_dir=str(tmp_path))
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0, out
    res = json.loads(out[-1])
    assert KEYS <= set(res)
    assert res["correct"] is True and res["failed"] == 0, out
    assert {"msgs_per_s", "job_ms_p50", "setup_s"} <= set(res["metrics"])
    rows = [line for line in out if line.startswith("compared ")]
    assert len(rows) == 22 and sum("(limit 0)" in r for r in rows) == 18
    assert sum("(limit 24)" in r for r in rows[-4:]) == 4
    assert sum(r.startswith("compared mid_22.") for r in rows) == 9
    assert any("slot.worlds_misplaced: 0" in r for r in rows)
    # the equal-work law: one line of supersteps by world and messages
    work = [line for line in out if line.startswith("worlds in slot order")]
    assert len(work) == 1 and work[0].count(";") == 1, work
    # and the reference's reading of every world, a line each
    assert sum(line.startswith("reference, world ") for line in out) == 4


def test_the_controls_fail_where_the_program_passes(tmp_path, capsys):
    name = toy_praos_fleet.fleet(tmp_path)
    rc = control.main(["--workload", name, "--seconds", "0.2",
                       "--seeds", "5"],
                      on_chip=False, extra_dir=str(tmp_path))
    line, = [json.loads(line) for line in
             capsys.readouterr().out.splitlines() if line.startswith("{")]
    assert rc == 0 and line["correct"] and not line["control_correct"]
    moved = {part: line["control"][f"{part}.mid_22.worlds_that_differ"]
             for part in ("low_precision", "swapped_medians",
                          "no_link_params")}
    assert moved == {"low_precision": 4, "swapped_medians": 2,
                     "no_link_params": 3}


def _toy_trace():
    """Two iterations of a fleet's loop: the horizon, the sender
    compaction, the link's draw in a rung, the rung's sort, an insert
    fusion, the entropy in the fire, a fire fusion, a copy of the
    compiler's own."""
    ops, names = [], {}
    body = "jit(_run_while)/while/body/"
    for i in range(2):
        t = 1000 * i
        for start, dur, hlo, scope in (
                (t, 40, "%fusion.1 = s64[4,64] fusion(...)",
                 body + "vmap(tw.next_event)/min"),
                (t + 40, 60, "%fusion.2 = s32[4,64] fusion(...)",
                 body + "vmap(tw.route)/senders/add"),
                (t + 100, 30, "%fusion.3 = f32[4,64] fusion(...)",
                 body + "vmap(tw.route)/cond/branch_0_fun/sample/mul"),
                (t + 130, 200, "%sort.4 = s32[4,64] sort(...)",
                 body + "vmap(tw.route)/cond/branch_0_fun/sort"),
                (t + 330, 300, "%fusion.5 = s32[4,64] fusion(...)",
                 body + "vmap(tw.route)/cond/branch_0_fun/insert/scatter"),
                (t + 630, 20, "%fusion.6 = u32[4,64] fusion(...)",
                 body + "vmap(tw.fire)/entropy/xor"),
                (t + 650, 100, "%fusion.7 = s32[4,64] fusion(...)",
                 body + "vmap(tw.fire)/vmap(jit(step))/add"),
                (t + 750, 50, "%copy.8 = s32[4,64] copy(...)",
                 "jit(_run_while)/while")):
            ops.append((start, dur, hlo))
            names[hlo] = scope
    trace = trace_reduce.Trace(
        ops=[ops], asyncs=[[]], modules=[(0, 2000, "jit__run_while(1)")],
        jobs=[(0, 2500, trace_reduce.JOB_SPAN)])
    jobs = [{"supersteps": 2, "msgs": 800, "world_supersteps": [1, 2, 2, 2],
             "rung_lanes": 2 * 1024, "sender_lanes": 900,
             "world_sender_lanes": [300, 500, 450, 350]}]
    return trace, jobs, names


def test_the_readers_over_a_toy_trace():
    trace, jobs, names = _toy_trace()
    facts = {"op_names": names, "n_nodes": 64, "worlds": 4,
             "mailbox_cap": 24, "payload_width": 2,
             "memory_peak_bytes": 4_000_000_000}
    ctx = {"jobs": jobs, "peaks": {"hbm_gbps": 819.0, "hbm_gb": 16.0},
           "facts": facts}
    assert praos_fleet_superstep_us.read(trace, ctx) == pytest.approx(0.8)
    assert praos_fleet_route_us.read(trace, ctx) == pytest.approx(0.59)
    assert praos_fleet_fire_us.read(trace, ctx) == pytest.approx(0.12)
    assert praos_fleet_sample_us.read(trace, ctx) == pytest.approx(0.03)
    assert praos_fleet_world_occupancy.read(trace, ctx) == pytest.approx(
        100 * 7 / 8)
    assert praos_fleet_rung_fit.read(trace, ctx) == pytest.approx(
        100 * 1600 / (4 * 2048))
    assert praos_fleet_memory_share.read(trace, ctx) == pytest.approx(25.0)
    nbytes = praos_fleet_costs.praos_fleet_superstep_bytes(
        64, 4, 24, 2, 800 / 2 / 4)
    assert praos_fleet_superstep_roofline.read(trace, ctx) == pytest.approx(
        100 * nbytes / 819e3 / 0.8)


def test_the_readers_find_nothing_on_a_program_that_lacks_it():
    trace, jobs, names = _toy_trace()
    # the parent: no world's own senders in the record; a CPU: no
    # memory statistics; a program whose draw kept no name of its own
    parent = [{k: v for k, v in jobs[0].items()
               if k != "world_sender_lanes"}]
    bare = {k: v.replace("/sample/", "/") for k, v in names.items()}
    ctx = {"jobs": parent, "peaks": {"hbm_gbps": 819.0, "hbm_gb": 16.0},
           "facts": {"op_names": bare, "memory_peak_bytes": None}}
    assert praos_fleet_rung_fit.read(trace, ctx) is None
    assert praos_fleet_memory_share.read(trace, ctx) is None
    assert praos_fleet_sample_us.read(trace, ctx) is None
    assert praos_fleet_superstep_roofline.read(trace, ctx) is None
    assert praos_fleet_route_us.read(trace, ctx) == pytest.approx(0.59)
    assert praos_fleet_world_occupancy.read(trace, ctx) == pytest.approx(87.5)
    # no profile was there to read, no iterations, no peaks
    none = {"jobs": jobs, "peaks": None, "facts": {"op_names": None}}
    for reader in (praos_fleet_route_us, praos_fleet_fire_us,
                   praos_fleet_sample_us, praos_fleet_memory_share,
                   praos_fleet_superstep_roofline):
        assert reader.read(trace, none) is None
    for reader in (praos_fleet_superstep_us, praos_fleet_world_occupancy,
                   praos_fleet_rung_fit):
        assert reader.read(trace, {"jobs": [], "facts": {}}) is None


def test_the_bytes_of_an_iteration_are_four_worlds_of_the_solo_cells():
    solo = praos_costs.praos_superstep_bytes(1 << 20, 24, 2, 1000.0)
    assert praos_fleet_costs.praos_fleet_superstep_bytes(
        1 << 20, 4, 24, 2, 1000.0) == 4 * solo
    assert praos_fleet_costs.praos_fleet_superstep_bytes(
        1 << 20, 4, 24, 2, 0) == 2_684_354_560


def test_the_committed_cell_is_bench_pys_row():
    traffic, config = run.load_cell("praos_1m.fleet4")
    _, solo = run.load_cell("praos_1m.slots")
    p = config["params"]
    # praos_1m's parameters letter for letter, plus the four worlds
    assert {k: v for k, v in p.items() if k not in (
        "worlds", "world_seeds", "link_params")} == {
        k: v for k, v in solo["params"].items() if k != "engine_seed"}
    assert p["worlds"] == 4 and p["world_seeds"] == [0, 1, 2, 3]
    assert p["link_params"] == {
        "inner.median_us": [18000, 20000, 22000, 24000]}
    assert p["n_nodes"] == 1 << 20 and p["mailbox_cap"] == 24
    assert config["reduced"] == [] and config["architecture"] is None
    assert traffic["chips"] == 1 and traffic["slots_per_job"] == 1
    assert traffic["warm_up_jobs"] == 2
    assert 2 <= traffic["trace_seconds"] <= 10
    assert set(config["control"]) >= {"link_precision", "swapped_worlds"}
    assert set(config["guarantees"]) == {
        "delivery", "quiescence", "growth", "exactness", "placement"}
    bench = run._load_json(run.ROOT, "BENCHMARK.json")
    cell, = [w for w in bench["workloads"] if w["name"] == traffic["name"]]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        config["name"], "fleet4", 1)
    entry, = [c for c in bench["configs"] if c["name"] == config["name"]]
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == []
    mine = [m for m in bench["per_layer"]
            if m.get("workloads") == ["praos_1m.fleet4"]]
    assert [m["name"] for m in mine] == EIGHT
    assert {m["layer"] for m in mine} == {"superstep XLA", "drivers",
                                          "device"}
    assert {m["moves"] for m in mine} == {"msgs_per_s"}


def test_metrics_of_offers_the_cell_its_eight_and_the_listless_ones():
    names = [n for n, _ in run.metrics_of("praos_1m.fleet4", "per_layer")]
    bench = run._load_json(run.ROOT, "BENCHMARK.json")
    listless = [m["name"] for m in bench["per_layer"]
                if "workloads" not in m]
    assert listless == ["compile_s", "device_idle_share", "loop_idle_us",
                        "programs_per_job"]
    assert sorted(names) == sorted(EIGHT + listless)
    assert [n for n, _ in run.metrics_of(
        "praos_1m.fleet4", "end_to_end")] == [
        "msgs_per_s", "job_ms_p50", "setup_s"]
    # and no other cell is offered the eight
    for cell in ("praos_1m.slots", "gossip_100k.fleet8",
                 "gossip_100k_chaos.fleet8"):
        assert not set(EIGHT) & {n for n, _ in run.metrics_of(
            cell, "per_layer")}
