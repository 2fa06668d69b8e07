"""The chaos fleet's cell on XLA:CPU at toy size (eight worlds of 512
nodes, each under the source's schedule written at that size), through
``run.py``'s test-only entry and ``control.py``'s: the result line, the
equal-work line, the three controls, and the six readers over a
hand-made trace and with nothing to read. Semantics only: nothing
printed here is a device number."""

import json

import pytest

import chaos_costs
import chaos_reduce
import control
import run
import toy_chaos
import trace_reduce
from builders import gossip_chaos
from layer_metrics import (chaos_fault_dropped, chaos_fault_table_lanes,
                           chaos_fault_us, chaos_route_us,
                           chaos_superstep_roofline, chaos_superstep_us)

KEYS = {"correct", "attempted", "failed", "metrics", "device"}
SIX = ["chaos_superstep_us", "chaos_route_us", "chaos_fault_us",
       "chaos_fault_table_lanes", "chaos_fault_dropped",
       "chaos_superstep_roofline"]


def test_last_line_has_the_contracts_keys(tmp_path, capsys):
    name = toy_chaos.fleet(tmp_path)
    rc = run.run_cell(name, 5_300_000_017, 0.3, False, on_chip=False,
                      extra_dir=str(tmp_path))
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0, out
    res = json.loads(out[-1])
    assert KEYS <= set(res)
    assert res["correct"] is True and res["failed"] == 0, out
    assert {"msgs_per_s", "job_ms_p50", "setup_s"} <= set(res["metrics"])
    rows = [line for line in out if line.startswith("compared ")]
    assert len(rows) == 11 and sum("(limit 0)" in r for r in rows) == 10
    assert "(limit 40)" in rows[-1]
    for cause in gossip_chaos.CAUSES:
        assert any(f".{cause}.worlds_that_differ: 0" in r for r in rows)
    # the equal-work law: one line of supersteps by world and messages
    work = [line for line in out if line.startswith("worlds in slot order")]
    assert len(work) == 1 and work[0].count(";") == 1, work


def test_the_controls_fail_where_the_program_passes(tmp_path, capsys):
    name = toy_chaos.fleet(tmp_path, n=256)
    rc = control.main(["--workload", name, "--seconds", "0.2",
                       "--seeds", "5"],
                      on_chip=False, extra_dir=str(tmp_path))
    line, = [json.loads(line) for line in
             capsys.readouterr().out.splitlines() if line.startswith("{")]
    assert rc == 0 and line["correct"] and not line["control_correct"]
    for part in ("low_word", "swapped_schedules", "no_faults"):
        assert any(v for k, v in line["control"].items()
                   if k.startswith(part + ".fleets")), part


def _toy_trace():
    """Two iterations of a fleet's loop: the deferral in the horizon,
    the partition's cut, the link windows inside the sample, the
    ladder's sort, an insert fusion, the reboot's reset in the fire, a
    fire fusion, a copy of the compiler's own."""
    ops, names = [], {}
    body = "jit(_run_while)/while/body/"
    for i in range(2):
        t = 1000 * i
        for start, dur, hlo, scope in (
                (t, 40, "%fusion.1 = s64[8,64] fusion(...)",
                 body + "vmap(tw.next_event)/fault/max"),
                (t + 40, 60, "%fusion.2 = pred[8,64] fusion(...)",
                 body + "vmap(tw.route)/fault/gather"),
                (t + 100, 30, "%fusion.3 = s64[8,64] fusion(...)",
                 body + "vmap(tw.route)/cond/branch_0_fun/sample/fault/mul"),
                (t + 130, 200, "%sort.4 = s32[8,64] sort(...)",
                 body + "vmap(tw.route)/cond/branch_0_fun/sort"),
                (t + 330, 300, "%fusion.5 = s32[8,64] fusion(...)",
                 body + "vmap(tw.route)/cond/branch_0_fun/insert/scatter"),
                (t + 630, 20, "%fusion.6 = s32[8,64] fusion(...)",
                 body + "vmap(tw.fire)/fault/select_n"),
                (t + 650, 100, "%fusion.7 = s32[8,64] fusion(...)",
                 body + "vmap(tw.fire)/vmap(jit(step))/add"),
                (t + 750, 50, "%copy.8 = s32[8,64] copy(...)",
                 "jit(_run_while)/while")):
            ops.append((start, dur, hlo))
            names[hlo] = scope
    trace = trace_reduce.Trace(
        ops=[ops], asyncs=[[]], modules=[(0, 2000, "jit__run_while(1)")],
        jobs=[(0, 2500, trace_reduce.JOB_SPAN)])
    jobs = [{"supersteps": 2, "fault_table_lanes": 2 * 4608,
             "fault_dropped": 1700}]
    return trace, jobs, names


def test_the_readers_over_a_toy_trace():
    trace, jobs, names = _toy_trace()
    nbytes = chaos_costs.chaos_superstep_bytes(64, 8, 40)
    ctx = {"jobs": jobs, "peaks": {"hbm_gbps": 819.0},
           "facts": {"op_names": names, "superstep_bytes": nbytes}}
    assert chaos_superstep_us.read(trace, ctx) == pytest.approx(0.8)
    assert chaos_route_us.read(trace, ctx) == pytest.approx(0.59)
    assert chaos_fault_us.read(trace, ctx) == pytest.approx(0.15)
    assert chaos_fault_table_lanes.read(trace, ctx) == 4608
    assert chaos_fault_dropped.read(trace, ctx) == 1700
    assert chaos_superstep_roofline.read(trace, ctx) == pytest.approx(
        100 * nbytes / 819e3 / 0.8)
    assert chaos_reduce.under_fault(
        "jit(f)/while/body/vmap(tw.route)/sample/fault/mul")
    assert not chaos_reduce.under_fault("jit(f)/fault/tw.route/mul")
    assert not chaos_reduce.under_fault("jit(f)/tw.route/default/mul")


def test_the_readers_find_nothing_on_a_program_without_the_scope():
    trace, jobs, names = _toy_trace()
    # the parent: the same operations, no `fault` in any name, no counts
    parent = {k: v.replace("/fault/", "/") for k, v in names.items()}
    ctx = {"jobs": [{"supersteps": 2}], "peaks": {"hbm_gbps": 819.0},
           "facts": {"op_names": parent, "superstep_bytes": 1}}
    assert chaos_fault_us.read(trace, ctx) is None
    assert chaos_fault_table_lanes.read(trace, ctx) is None
    assert chaos_fault_dropped.read(trace, ctx) is None
    assert chaos_route_us.read(trace, ctx) == pytest.approx(0.59)
    # no profile was there to read, no iterations, no peaks
    none = {"jobs": jobs, "peaks": None, "facts": {"op_names": None}}
    for reader in (chaos_route_us, chaos_fault_us, chaos_superstep_roofline):
        assert reader.read(trace, none) is None
    for reader in (chaos_superstep_us, chaos_fault_table_lanes,
                   chaos_fault_dropped):
        assert reader.read(trace, {"jobs": [], "facts": {}}) is None


def test_the_bytes_of_a_full_width_iteration():
    # a world a node: 28 bytes of planes and 40 slots of two int32 words,
    # read and written, one message's two words, one partition's group
    assert chaos_costs.chaos_superstep_bytes(1, 1, 40) \
        == 2 * (28 + 320) + 8 + 4
    assert chaos_costs.chaos_superstep_bytes(1 << 17, 8, 40) == 742_391_808


def test_the_committed_cell_is_bench_pys_row_with_40_slots():
    traffic, config = run.load_cell("gossip_100k_chaos.fleet8")
    p = config["params"]
    assert p["n_nodes"] == 1 << 17 and p["window"] == "auto"
    assert p["worlds"] == 8 and p["world_seeds"] == list(range(8))
    assert (p["fanout"], p["think_us"], p["gossip_interval_us"],
            p["bootstrap_us"]) == (1, 1000, 1000, 1000) and p["steady"]
    assert p["link"] == {"model": "uniform", "lo_us": 500, "hi_us": 4500,
                         "quantum_us": 1000}
    # the schedules are the source's, letter for letter, at this size
    assert p["faults"] == gossip_chaos.schedules(1 << 17)
    assert p["mailbox_cap"] == 40 and p["end_us"] == 160_000
    assert config["reduced"] == ["end_us"] and "end_us" in \
        config["reduced_why"]
    assert config["architecture"] is None and traffic["chips"] == 1
    assert set(config["control"]) >= {"link_word_bits", "swapped_worlds"}
    bench = run._load_json(run.ROOT, "BENCHMARK.json")
    entry, = [c for c in bench["configs"] if c["name"] == config["name"]]
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    mine = [m for m in bench["per_layer"]
            if m.get("workloads") == ["gossip_100k_chaos.fleet8"]]
    assert [m["name"] for m in mine] == SIX
    assert {m["layer"] for m in mine} == {"superstep XLA", "faults"}


def test_metrics_of_offers_the_cell_its_six_and_the_listless_ones():
    names = [n for n, _ in run.metrics_of("gossip_100k_chaos.fleet8",
                                          "per_layer")]
    bench = run._load_json(run.ROOT, "BENCHMARK.json")
    listless = [m["name"] for m in bench["per_layer"]
                if "workloads" not in m]
    assert sorted(names) == sorted(SIX + listless)
    # a traced window holds one 22 s job, so the readers that pair the
    # record with the trace find nothing: those sixteen list the cells
    # older than this one (README_chaos.md)
    assert listless == ["compile_s", "device_idle_share", "loop_idle_us",
                        "programs_per_job"]
    paired = [m for m in bench["per_layer"]
              if m["name"].startswith(("idle_in_", "setup_"))
              or m["name"] == "span_clock_slack_ms"]
    older = [w["name"] for w in bench["workloads"]][:9]
    assert len(paired) == 16 and all(m["workloads"] == older for m in paired)
    assert [n for n, _ in run.metrics_of(
        "gossip_100k_chaos.fleet8", "end_to_end")] == [
        "msgs_per_s", "job_ms_p50", "setup_s"]
    # and no other cell is offered the six
    for cell in ("gossip_100k.fleet8", "gossip_steady_1m.rounds"):
        assert not set(SIX) & {n for n, _ in run.metrics_of(
            cell, "per_layer")}


def test_the_source_s_schedules_are_bench_pys():
    # bench.py:452-467 at n nodes, built by the library's dataclasses,
    # against the grammar strings the configuration holds
    from timewarp_tpu.faults import (FaultSchedule, LinkWindow, NodeCrash,
                                     Partition, parse_faults)
    n, half = 512, 256
    for b, text in enumerate(gossip_chaos.schedules(n)):
        part_end, crash_up = 70_000 + 2_000 * b, 60_000 + 5_000 * b
        assert parse_faults(text) == FaultSchedule((
            NodeCrash((7 * b + 3) % n, 20_000, crash_up, reset_state=True),
            NodeCrash((11 * b + half + 5) % n, 30_000, crash_up + 10_000),
            Partition((tuple(range(half)), tuple(range(half, n))),
                      25_000, part_end),
            LinkWindow(None, None, 80_000, 120_000, scale=2.0 + 0.25 * b)))
