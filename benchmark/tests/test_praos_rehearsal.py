"""The Praos cell on XLA:CPU at toy size (2048 nodes, one slot a job),
through ``run.py``'s test-only entry and ``control.py``'s: the result
line, the gates, the controls, and the four readers over a hand-made
trace and with nothing to read. Semantics only: nothing printed here is
a device number."""

import json

import pytest

import control
import praos_costs
import run
import toy_praos
import trace_reduce
from layer_metrics import (praos_fire_us, praos_route_us,
                           praos_superstep_roofline, praos_superstep_us)

KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def test_last_line_has_the_contracts_keys(tmp_path, capsys):
    name = toy_praos.slots(tmp_path)
    rc = run.run_cell(name, 3_300_000_019, 0.3, False, on_chip=False,
                      extra_dir=str(tmp_path))
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0, out
    res = json.loads(out[-1])
    assert KEYS <= set(res)
    assert res["correct"] is True and res["failed"] == 0, out
    assert res["attempted"] >= 1
    assert {"msgs_per_s", "job_ms_p50", "setup_s"} <= set(res["metrics"])
    rows = [line for line in out if line.startswith("compared ")]
    assert len(rows) == 7 and sum("(limit 0)" in r for r in rows) == 6
    assert rows[-1].endswith("15 (limit 24)")
    assert any(line.startswith("reference: blocks minted a slot [2];")
               for line in out)
    assert "supersteps a job 46-46" in "\n".join(out)


def test_sixteen_slots_lose_tips_and_fail_the_gates(tmp_path, capsys):
    # bench.py's former cap, at a size where 17 tips are in flight to
    # one node; 2^20 has 20 (PERF.md, PR 33)
    name = toy_praos.slots(tmp_path, n_nodes=1 << 15, mailbox_cap=16)
    with pytest.raises(SystemExit, match="overflow="):
        run.run_cell(name, 7, 0.2, False, on_chip=False,
                     extra_dir=str(tmp_path))
    capsys.readouterr()


def test_two_slots_a_job_are_a_cut_of_the_traffic_file(tmp_path, capsys):
    name = toy_praos.slots(tmp_path, slots_per_job=2)
    rc = run.run_cell(name, 5, 0.2, False, on_chip=False,
                      extra_dir=str(tmp_path))
    out = capsys.readouterr().out
    assert rc == 0 and '"correct": true' in out
    assert "blocks minted a slot [2, 1];" in out
    assert "supersteps a job 94-94" in out


def test_the_controls_fail_where_the_program_passes(tmp_path, capsys):
    name = toy_praos.slots(tmp_path)
    rc = control.main(["--workload", name, "--seconds", "0.2",
                       "--seeds", "5", "4100000007"],
                      on_chip=False, extra_dir=str(tmp_path))
    lines = [json.loads(line) for line in
             capsys.readouterr().out.splitlines() if line.startswith("{")]
    assert rc == 0 and len(lines) == 2
    for line in lines:
        assert line["correct"] and not line["control_correct"]
        assert line["control"]["small_mailbox.job.overflow"] > 0
        assert line["control"][
            "low_precision.job.time.jobs_that_differ"] == 1
    # the control's result does not move with the genesis length
    assert lines[0]["control"] == lines[1]["control"]


def _toy_trace():
    """Two supersteps of a solo loop: a sort of the route stage's own, an
    insert fusion, two fusions of the step (the entropy is inside one:
    the chip's compiler fuses it there), a copy of the compiler's own."""
    ops, names = [], {}
    for i in range(2):
        t = 1000 * i
        for start, dur, hlo, scope in (
                (t, 200, "%sort.1 = s32[64] sort(...)",
                 "jit(_run_while)/while/body/tw.route/sort"),
                (t + 200, 300, "%fusion.2 = s32[64] fusion(...)",
                 "jit(_run_while)/while/body/tw.route/insert/scatter"),
                (t + 500, 100, "%fusion.3 = u32[64] fusion(...)",
                 "jit(_run_while)/while/body/tw.fire/vmap(jit(remainder))/rem"),
                (t + 600, 150, "%fusion.4 = s32[64] fusion(...)",
                 "jit(_run_while)/while/body/tw.fire/vmap(jit(step))/add"),
                (t + 750, 50, "%copy.5 = s32[64] copy(...)",
                 "jit(_run_while)/while")):
            ops.append((start, dur, hlo))
            names[hlo] = scope
    trace = trace_reduce.Trace(
        ops=[ops], asyncs=[[]], modules=[(0, 2000, "jit__run_while(1)")],
        jobs=[(0, 2500, trace_reduce.JOB_SPAN)])
    return trace, [{"supersteps": 2, "msgs": 10}], names


def _run_of(jobs, names, **facts):
    return {"jobs": jobs, "peaks": {"hbm_gbps": 819.0},
            "facts": {"op_names": names, "n_nodes": 64, "mailbox_cap": 24,
                      "payload_width": 2, **facts}}


def test_the_readers_over_a_toy_trace():
    trace, jobs, names = _toy_trace()
    ctx = _run_of(jobs, names)
    assert praos_superstep_us.read(trace, ctx) == pytest.approx(0.8)
    assert praos_route_us.read(trace, ctx) == pytest.approx(0.5)
    assert praos_fire_us.read(trace, ctx) == pytest.approx(0.25)
    nbytes = praos_costs.praos_superstep_bytes(64, 24, 2, 5.0)
    assert praos_superstep_roofline.read(trace, ctx) == pytest.approx(
        100 * nbytes / 819e3 / 0.8)


def test_the_readers_find_nothing_where_there_is_nothing_to_read():
    trace, jobs, names = _toy_trace()
    # no profile was there to read, no supersteps, no peaks
    none = {"jobs": jobs, "peaks": None, "facts": {"op_names": None}}
    for reader in (praos_route_us, praos_fire_us, praos_superstep_roofline):
        assert reader.read(trace, none) is None
    assert praos_superstep_us.read(trace, {"jobs": []}) is None
    assert praos_superstep_roofline.read(
        trace, {**_run_of(jobs, names), "jobs": []}) is None


def test_the_bytes_of_one_pass_over_the_state():
    # per node: 32 bytes of planes and 24 slots of three int32 words,
    # read and written; a message is three words
    assert praos_costs.praos_superstep_bytes(1, 24, 2, 0) == 2 * (32 + 288)
    assert praos_costs.praos_superstep_bytes(1 << 20, 24, 2, 0) == 671_088_640
    assert praos_costs.praos_superstep_bytes(1 << 20, 24, 2, 1000) \
        == 671_088_640 + 12_000


def test_the_committed_cell_is_bench_pys_row_with_24_slots():
    traffic, config = run.load_cell("praos_1m.slots")
    p = config["params"]
    assert p["n_nodes"] == 1 << 20 and p["window"] == "auto"
    assert p["fanout"] == 8 and p["leaders_per_slot"] == 4 and p["burst"]
    assert p["mailbox_cap"] == 24 and config["control"] == {
        "link_precision": "bfloat16", "mailbox_cap": 16}
    assert p["link"] == {"model": "lognormal", "median_us": 20000,
                         "sigma": 0.6, "floor_us": 8000, "cap_us": 150000,
                         "quantum_us": 1000}
    assert traffic["chips"] == 1 and traffic["slots_per_job"] == 1
    assert traffic["warm_up_jobs"] == 2 and config["reduced"] == []
    bench = run._load_json(run.ROOT, "BENCHMARK.json")
    mine = [m for m in bench["per_layer"]
            if m.get("workloads") == ["praos_1m.slots"]]
    assert [m["name"] for m in mine] == [
        "praos_superstep_us", "praos_route_us", "praos_fire_us",
        "praos_superstep_roofline"]
    assert bench["workloads"][-1]["name"] == "praos_1m.slots"
    assert bench["configs"][-1]["file"] == "benchmark/configs/praos_1m.json"
