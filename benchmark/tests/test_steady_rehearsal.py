"""The steady-mongering cell on XLA:CPU at toy size (4096 nodes, a ramp
of 64), through ``run.py``'s test-only entry and ``control.py``'s: the
result line, the gates of the ramp, the control, and the five readers
over a hand-made trace and with nothing to read. Semantics only: nothing
printed here is a device number."""

import json

import pytest

import control
import run
import steady_costs
import steady_reduce
import toy_steady
import trace_reduce
from layer_metrics import (steady_insert_us, steady_route_us, steady_sort_us,
                           steady_superstep_roofline, steady_superstep_us)

KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def test_last_line_has_the_contracts_keys(tmp_path, capsys):
    name = toy_steady.rounds(tmp_path)
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
    assert len(rows) == 21 and sum("(limit 0)" in r for r in rows) == 20
    assert "(limit 24)" in rows[-1]
    assert any("every node held the rumor after superstep" in line
               for line in out)
    assert "supersteps a job 16-16" in "\n".join(out)


def test_a_ramp_that_ends_before_saturation_stops_the_run(tmp_path, capsys):
    name = toy_steady.rounds(tmp_path, ramp_supersteps=24)
    with pytest.raises(SystemExit, match="without the rumor after the "
                                         "ramp's 24 supersteps"):
        run.run_cell(name, 7, 0.2, False, on_chip=False,
                     extra_dir=str(tmp_path))
    capsys.readouterr()


def test_the_source_s_eight_slots_fail_the_gates(tmp_path, capsys):
    name = toy_steady.rounds(tmp_path, mailbox_cap=8)
    with pytest.raises(SystemExit, match="overflow="):
        run.run_cell(name, 7, 0.2, False, on_chip=False,
                     extra_dir=str(tmp_path))
    capsys.readouterr()


def test_the_control_fails_where_the_program_passes(tmp_path, capsys):
    name = toy_steady.rounds(tmp_path)
    rc = control.main(["--workload", name, "--seconds", "0.2",
                       "--seeds", "5", "4100000007"],
                      on_chip=False, extra_dir=str(tmp_path))
    lines = [json.loads(line) for line in
             capsys.readouterr().out.splitlines() if line.startswith("{")]
    assert rc == 0 and len(lines) == 2
    for line in lines:
        assert line["correct"] and not line["control_correct"]
        assert line["control"]["small_mailbox.first_job.overflow"] > 0
        assert line["control"][
            "low_word.window_end.in_flight_count.mismatches"] > 0


def _toy_trace():
    """Two supersteps of a solo loop: the eager path's sort, an insert
    fusion, a fusion of the route stage's own, a fire fusion, a copy of
    the compiler's own."""
    ops, names = [], {}
    for i in range(2):
        t = 1000 * i
        for start, dur, hlo, scope in (
                (t, 200, "%sort.1 = s32[64] sort(...)",
                 "jit(_run_while)/while/body/tw.route/sort/sort"),
                (t + 200, 300, "%fusion.2 = s32[64] fusion(...)",
                 "jit(_run_while)/while/body/tw.route/insert/scatter"),
                (t + 500, 100, "%fusion.3 = s32[64] fusion(...)",
                 "jit(_run_while)/while/body/tw.route/mul"),
                (t + 600, 150, "%fusion.4 = s32[64] fusion(...)",
                 "jit(_run_while)/while/body/tw.fire/vmap(jit(step))/add"),
                (t + 750, 50, "%copy.5 = s32[64] copy(...)",
                 "jit(_run_while)/while")):
            ops.append((start, dur, hlo))
            names[hlo] = scope
    trace = trace_reduce.Trace(
        ops=[ops], asyncs=[[]], modules=[(0, 2000, "jit__run_while(1)")],
        jobs=[(0, 2500, trace_reduce.JOB_SPAN)])
    return trace, [{"supersteps": 2}], names


def test_the_readers_over_a_toy_trace():
    trace, jobs, names = _toy_trace()
    nbytes = steady_costs.steady_superstep_bytes(64, 24)
    ctx = {"jobs": jobs, "peaks": {"hbm_gbps": 819.0},
           "facts": {"op_names": names, "superstep_bytes": nbytes}}
    assert steady_superstep_us.read(trace, ctx) == pytest.approx(0.8)
    assert steady_route_us.read(trace, ctx) == pytest.approx(0.6)
    assert steady_sort_us.read(trace, ctx) == pytest.approx(0.2)
    assert steady_insert_us.read(trace, ctx) == pytest.approx(0.3)
    assert steady_superstep_roofline.read(trace, ctx) == pytest.approx(
        100 * nbytes / 819e3 / 0.8)


def test_the_readers_find_nothing_on_a_program_without_the_scope():
    trace, jobs, names = _toy_trace()
    # the parent of PR 31: the sort's time is the route stage's own
    parent = {k: v.replace("tw.route/sort/", "tw.route/")
              for k, v in names.items()}
    ctx = {"jobs": jobs, "peaks": {"hbm_gbps": 819.0},
           "facts": {"op_names": parent, "superstep_bytes": 1}}
    assert steady_sort_us.read(trace, ctx) is None
    assert steady_route_us.read(trace, ctx) == pytest.approx(0.6)
    assert steady_insert_us.read(trace, ctx) == pytest.approx(0.3)
    # no profile was there to read, no supersteps, no peaks
    none = {"jobs": jobs, "peaks": None, "facts": {"op_names": None}}
    for reader in (steady_route_us, steady_sort_us, steady_insert_us,
                   steady_superstep_roofline):
        assert reader.read(trace, none) is None
    assert steady_superstep_us.read(trace, {"jobs": []}) is None
    assert steady_reduce.scope_us(trace, {**ctx, "jobs": []},
                                  "tw.route") is None


def test_the_bytes_of_a_full_width_superstep():
    # per node: 28 bytes of planes and 24 slots of two int32 words, read
    # and written, and one message's two words
    assert steady_costs.steady_superstep_bytes(1, 24) == 2 * (28 + 192) + 8
    assert steady_costs.steady_superstep_bytes(1 << 20, 24) == 469_762_048


def test_the_committed_cell_is_bench_pys_row_with_24_slots():
    traffic, config = run.load_cell("gossip_steady_1m.rounds")
    p = config["params"]
    assert p["n_nodes"] == 1 << 20 and p["window"] == 1
    assert p["mailbox_cap"] == 24 and config["control"]["mailbox_cap"] == 8
    assert p["link"] == {"model": "uniform", "lo_us": 500, "hi_us": 4500,
                         "quantum_us": 1000}
    assert traffic["chips"] == 1 and traffic["supersteps_per_job"] == 16
    assert traffic["ramp_supersteps"] == 128 and config["reduced"] == []
    bench = run._load_json(run.ROOT, "BENCHMARK.json")
    mine = [m for m in bench["per_layer"]
            if m.get("workloads") == ["gossip_steady_1m.rounds"]]
    assert [m["name"] for m in mine] == [
        "steady_superstep_us", "steady_route_us", "steady_sort_us",
        "steady_insert_us", "steady_superstep_roofline"]
