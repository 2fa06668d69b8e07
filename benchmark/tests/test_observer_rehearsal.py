"""The observer-ring cell on XLA:CPU at toy size (256 ring nodes and
their hub), through ``run.py``'s test-only entry and ``control.py``'s:
the result line, the gates on an overflow that is not zero, both
controls, and the seven readers over a hand-made trace and with nothing
to read. Semantics only: nothing printed here is a device number."""

import json

import pytest

import control
import hub_costs
import run
import toy_observer
import trace_reduce
from layer_metrics import (hub_fan_in_peak, hub_fire_us, hub_insert_us,
                           hub_order_us, hub_route_us,
                           hub_superstep_roofline, hub_superstep_us)

KEYS = {"correct", "attempted", "failed", "metrics", "device"}
READERS = (hub_superstep_us, hub_order_us, hub_route_us, hub_insert_us,
           hub_fire_us, hub_fan_in_peak, hub_superstep_roofline)


def test_last_line_has_the_contracts_keys(tmp_path, capsys):
    name = toy_observer.observer(tmp_path)
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
    # four states, fourteen facts each, every one exact
    assert len(rows) == 56 and all("(limit 0)" in r for r in rows)
    assert {r.split()[1].split(".")[0] for r in rows} == {
        "first_job", "window_end", "tokens_in_flight", "hub_inbox"}
    assert "supersteps a job 96-96" in "\n".join(out)


def test_a_job_counts_what_the_hub_drops_and_is_held_to_it(tmp_path):
    name = toy_observer.observer(tmp_path)
    cell, _, traffic, _, _, _ = run.prepare(name, on_chip=False,
                                            extra_dir=str(tmp_path))
    first = cell.set_up(11)
    assert first["failed"] == "" and first["supersteps"] == 96
    assert first["msgs"] == 32 * (256 + 8) and first["fan_in_peak"] == 256
    assert int(cell.state.overflow) == 32 * (256 - 8)
    # a program from before the counter is held to everything else
    stats = cell.engine.last_run_stats
    del stats["fan_in_peak"]
    stats["compiles"] = 0
    cell.engine.run_quiet = lambda k, st: cell.state
    cell.counted = {"delivered": int(cell.state.delivered) - first["msgs"],
                    "overflow": int(cell.state.overflow) - cell.dropped,
                    "steps": 0}
    again = cell.job(1)
    assert again["failed"] == "" and again["fan_in_peak"] is None


def test_a_ring_of_another_shape_is_refused(tmp_path):
    for cuts, why in (({"supersteps_per_job": 95}, "whole number of ring"),
                      ({"n_tokens": 1}, "whole number of ring"),
                      ({"with_observer": False}, "observer hub")):
        name = toy_observer.observer(tmp_path, **cuts)
        with pytest.raises(SystemExit, match=why):
            run.prepare(name, on_chip=False, extra_dir=str(tmp_path))


def test_both_controls_fail_where_the_program_passes(tmp_path, capsys):
    name = toy_observer.observer(tmp_path)
    rc = control.main(["--workload", name, "--seconds", "0.2",
                       "--seeds", "5", "4100000007"],
                      on_chip=False, extra_dir=str(tmp_path))
    lines = [json.loads(line) for line in
             capsys.readouterr().out.splitlines() if line.startswith("{")]
    assert rc == 0 and len(lines) == 2
    for line in lines:
        assert line["correct"] and not line["control_correct"]
        assert set(line["sound"].values()) == {0}
        ctl = line["control"]
        assert ctl["int16_values.window_end.val.mismatches"] == 256
        assert ctl["hub_descending.window_end.hub_prev.mismatches"] == 1
        assert ctl["hub_descending.window_end.hub_errs.mismatches"] == 1
        assert ctl["hub_descending.hub_inbox.mailbox_src.mismatches"] == 8
        assert ctl["hub_descending.window_end.val.mismatches"] == 0


def _toy_trace():
    """Two supersteps of a solo loop: the inbox's sort, a fire fusion,
    the compaction and its count, a rung's sort (the route stage's own)
    and its insert fusion inside the ladder's switch, a copy of the
    compiler's own."""
    ops, names = [], {}
    body = "jit(_run_while)/while/body/"
    for i in range(2):
        t = 1000 * i
        for start, dur, hlo, scope in (
                (t, 100, "%sort.1 = s32[8,64] sort(...)",
                 body + "tw.deliver/sort/sort"),
                (t + 100, 150, "%fusion.2 = s32[64] fusion(...)",
                 body + "tw.fire/vmap(jit(step))/while/body/add"),
                (t + 250, 120, "%sort.3 = s32[8,64] sort(...)",
                 body + "tw.rebase/compact/sort"),
                (t + 370, 30, "%fusion.4 = s32[64] fusion(...)",
                 body + "tw.rebase/compact/reduce_sum"),
                (t + 400, 200, "%sort.5 = s32[128] sort(...)",
                 body + "tw.route/cond/branch_6_fun/sort"),
                (t + 600, 250, "%fusion.6 = s32[64] fusion(...)",
                 body + "tw.route/cond/branch_6_fun/insert/scatter"),
                (t + 850, 50, "%copy.7 = s32[64] copy(...)",
                 "jit(_run_while)/while")):
            ops.append((start, dur, hlo))
            names[hlo] = scope
    trace = trace_reduce.Trace(
        ops=[ops], asyncs=[[]], modules=[(0, 2000, "jit__run_while(1)")],
        jobs=[(0, 2500, trace_reduce.JOB_SPAN)])
    return trace, [{"supersteps": 2, "fan_in_peak": 64}], names


def test_the_readers_over_a_toy_trace():
    trace, jobs, names = _toy_trace()
    nbytes = hub_costs.hub_superstep_bytes(65, 8, 2, 24)
    ctx = {"jobs": jobs, "peaks": {"hbm_gbps": 819.0},
           "facts": {"op_names": names, "superstep_bytes": nbytes}}
    assert hub_superstep_us.read(trace, ctx) == pytest.approx(0.9)
    assert hub_order_us.read(trace, ctx) == pytest.approx(0.25)
    assert hub_route_us.read(trace, ctx) == pytest.approx(0.45)
    assert hub_insert_us.read(trace, ctx) == pytest.approx(0.25)
    assert hub_fire_us.read(trace, ctx) == pytest.approx(0.15)
    assert hub_fan_in_peak.read(trace, ctx) == 64
    assert hub_superstep_roofline.read(trace, ctx) == pytest.approx(
        100 * nbytes / 819e3 / 0.9)


def test_the_readers_find_nothing_on_a_program_without_the_scopes():
    trace, jobs, names = _toy_trace()
    # the parent of PR 39: the sorts' time is their stages' own, and
    # no call counts the fan-in
    parent = {k: v.replace("tw.deliver/sort/", "tw.deliver/")
              .replace("tw.rebase/compact/", "tw.rebase/")
              for k, v in names.items()}
    ctx = {"jobs": [{"supersteps": 2, "fan_in_peak": None}],
           "peaks": {"hbm_gbps": 819.0},
           "facts": {"op_names": parent, "superstep_bytes": 1}}
    assert hub_order_us.read(trace, ctx) is None
    assert hub_fan_in_peak.read(trace, ctx) is None
    assert hub_route_us.read(trace, ctx) == pytest.approx(0.45)
    assert hub_insert_us.read(trace, ctx) == pytest.approx(0.25)
    assert hub_fire_us.read(trace, ctx) == pytest.approx(0.15)
    # one of the two scopes alone is no reading of the ordered inbox
    half = {k: v.replace("tw.rebase/compact/", "tw.rebase/")
            for k, v in names.items()}
    assert hub_order_us.read(trace, {**ctx, "facts": {
        "op_names": half, "superstep_bytes": 1}}) is None
    # no profile was there to read, no supersteps, no peaks
    none = {"jobs": jobs, "peaks": None, "facts": {"op_names": None}}
    for reader in READERS:
        if reader not in (hub_superstep_us, hub_fan_in_peak):
            assert reader.read(trace, none) is None
    assert hub_superstep_us.read(trace, {"jobs": []}) is None
    assert hub_fan_in_peak.read(trace, {"jobs": []}) is None


def test_the_committed_cell_reports_its_seven_and_the_listless_ones():
    traffic, config = run.load_cell("ring_64k.observer")
    assert config["builder"] == "observer_ring"
    assert config["reference"] == "observer_ring_ref"
    assert config["control"] == {"value_dtype": "int16",
                                 "hub_order": "descending"}
    names = [n for n, _ in run.metrics_of("ring_64k.observer", "per_layer")]
    assert names[-7:] == [r.__name__.rpartition(".")[2] for r in READERS]
    assert {"compile_s", "device_idle_share", "loop_idle_us",
            "programs_per_job", "idle_in_dispatch_ms", "idle_in_wait_ms",
            "idle_in_driver_ms", "idle_in_client_ms",
            "span_clock_slack_ms"} == set(names[:-7])
    assert [n for n, _ in run.metrics_of("ring_64k.observer", "end_to_end")] \
        == ["msgs_per_s", "job_ms_p50", "setup_s"]
