"""The reduction's pure functions on hand-made ``(start, duration,
name)`` tuples, overlapping and nested ones among them, and the bytes
and peaks the roofline shares rest on."""

import json
import os

import pytest

import kernel_costs
import trace_reduce as tr
from layer_metrics import (device_idle_share, ring_kernel_us,
                           ring_superstep_roofline, superstep_us,
                           sync_gap_ms)

# a while loop [0, 100) whose body runs a [10, 30), b [30, 40), then a
# conditional [50, 90) that holds c [55, 60) and d [70, 90); after the
# loop, e [120, 130) overlapped by an equal twin
LOOP = [(0, 100, "%while.1 = ... while(...)"),
        (10, 20, "%a = s32[8]{0} fusion(...)"),
        (30, 10, "%b = s32[8]{0} copy(...)"),
        (50, 40, "%cond.2 = (s32[]) conditional(...)"),
        (55, 5, "%c = s32[8]{0} sort(...)"),
        (70, 20, "%a = s32[8]{0} fusion(...)"),
        (120, 10, "%e = s32[] custom-call(...)"),
        (120, 10, "%e = s32[] custom-call(...)")]


def test_leaves_drop_every_enclosing_event():
    got = tr.leaves(LOOP)
    assert [(s, d) for s, d, _ in got] == [
        (10, 20), (30, 10), (55, 5), (70, 20), (120, 10)]
    # order of the input does not matter
    assert tr.leaves(reversed(LOOP)) == got


def test_union_counts_overlapping_and_nested_once():
    assert tr.union_ns(LOOP) == 110            # the while covers [0, 100)
    assert tr.union_ns(tr.leaves(LOOP)) == 65
    assert tr.union_ns([(0, 10, "x"), (5, 10, "y"), (30, 5, "z")]) == 20
    assert tr.union_ns([(0, 10, "x"), (2, 3, "y")]) == 10
    assert tr.union_ns([]) == 0
    # clipped to a window
    assert tr.union_ns(tr.leaves(LOOP), 20, 75) == 10 + 10 + 5 + 5
    assert tr.union_ns(tr.leaves(LOOP), 200, 300) == 0


def test_sums_by_name_and_named():
    sums = tr.sums_by_name(tr.leaves(LOOP))
    assert sums[0] == ("%a = s32[8]{0} fusion(...)", 40, 2)
    assert {n for n, _, _ in sums} == {e[2] for e in LOOP[1:3] + LOOP[4:7]}
    assert len(tr.named(LOOP, "fusion(")) == 2
    assert tr.named(LOOP, "no such") == []


def test_idle_gaps_longest_first():
    gaps = tr.idle_gaps(tr.leaves(LOOP), 0, 140)
    assert gaps[0] == (90, 30)
    assert sorted(gaps) == [(0, 10), (40, 15), (60, 10), (90, 30), (130, 10)]
    assert sum(d for _, d in gaps) == 140 - 65


def test_gap_between_jobs_on_the_devices_clock():
    """Three jobs: the main program runs [0, 100), [130, 230), [300,
    400); between the first two a small program is busy for 10, which
    is not idle time."""
    modules = [(0, 100, "jit_main(1)"), (105, 10, "jit_small(2)"),
               (130, 100, "jit_main(1)"), (300, 100, "jit_main(1)")]
    ops = [(0, 100, "x"), (105, 10, "y"), (130, 100, "x"), (300, 100, "x")]
    assert tr.main_program(modules) == "jit_main(1)"
    assert tr.gaps_between_jobs(modules, ops) == [20, 70]
    assert tr.gaps_between_jobs(modules[:1], ops) == []


def test_short_name():
    assert tr.short_name(
        "%copy.29 = s32[10,1024,1024]{2,1,0:T(8,128)S(1)} copy(s32[10,1024,"
        "1024]{2,1,0:T(8,128)} %get-tuple-element.461)") == "%copy.29 copy"
    assert tr.short_name(
        "%body.7 = (s32[10,1024,1024]{2,1,0:T(8,128)}, s32[2,8,128]{2,1,0:"
        "T(8,128)S(1)}) custom-call(s32[6]{0:T(128)S(1)} %concatenate.6), "
        'custom_call_target="tpu_custom_call"') == "%body.7 custom-call"
    assert tr.short_name("%w = (s32[], (u32[], u32[])) while((s32[]) %t), "
                         "condition=%c, body=%b") == "%w while"
    assert tr.short_name("no equals sign here") == "no equals sign here"


def _trace():
    # two jobs of 2 supersteps: kernel 40 + copy 40 + reduce 10 each
    ops = []
    for base in (0, 300):
        for s in (0, 100):
            ops += [(base + s, 40, '%k = s32[4] custom-call(), custom_call_'
                     'target="tpu_custom_call"'),
                    (base + s + 40, 40, "%c = s32[4] copy(s32[4] %x)"),
                    (base + s + 85, 10, "%r = s32[] fusion(s32[4] %x)")]
    modules = [(0, 200, "jit_run(1)"), (300, 200, "jit_run(1)")]
    jobs = [(1000, 250, tr.JOB_SPAN), (1250, 250, tr.JOB_SPAN)]
    return tr.Trace(ops=[ops], asyncs=[[(0, 20, "%cs = copy-start()")]],
                    modules=modules, jobs=jobs)


def test_layer_metrics_on_a_hand_made_trace():
    t = _trace()
    run = {"jobs": [{"supersteps": 2}, {"supersteps": 2}],
           "facts": {"kernel_event": "tpu_custom_call",
                     "kernel_bytes": 819 * 45},
           "peaks": {"hbm_gbps": 819.0}}
    assert tr.busy_and_window(t) == (360.0, 500)
    assert device_idle_share.read(t, run) == pytest.approx(28.0)
    assert ring_kernel_us.read(t, run) == pytest.approx(0.040)
    assert superstep_us.read(t, run) == pytest.approx(0.090)
    assert sync_gap_ms.read(t, run) == pytest.approx(100e-6)
    # least time 45 ns a superstep, busy 90 ns a superstep
    assert ring_superstep_roofline.read(t, run) == pytest.approx(50.0)
    # a cell with no such kernel: nothing to read
    none = {**run, "facts": {}}
    assert ring_kernel_us.read(t, none) is None
    assert ring_superstep_roofline.read(t, none) is None
    bd = tr.breakdown(t)
    assert ["%k custom-call", 160e-9] in bd["device_ops"][:2]
    assert bd["idle_gaps"][0] == ["between programs, before jit_run", 105e-9]


def test_ring_bytes_and_peaks():
    assert kernel_costs.ring_superstep_bytes(1 << 20) == 83_886_080
    assert kernel_costs.device_peaks("TPU v5 lite")["hbm_gbps"] == 819.0
    with pytest.raises(SystemExit, match="no published peak"):
        kernel_costs.device_peaks("TPU v9 imaginary")


def test_benchmark_json_names_files_that_exist():
    """Every configuration, cell and metric BENCHMARK.json names has
    its file, the cells' files agree with it, and names, units and
    lengths are inside the contract's limits."""
    import re
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(os.path.dirname(here), "BENCHMARK.json")) as f:
        bench = json.load(f)
    name_ok = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    unit_ok = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}$")
    for c in bench["configs"]:
        assert name_ok.match(c["name"]) and len(c["source"]) <= 200
        with open(os.path.join(os.path.dirname(here), c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        for kind, key in (("builders", "builder"), ("reference", "reference")):
            assert os.path.exists(os.path.join(here, kind, cfg[key] + ".py"))
    for w in bench["workloads"]:
        assert name_ok.match(w["name"]) and len(w["why"]) <= 200, w["name"]
        with open(os.path.join(here, "workloads", w["name"] + ".json")) as f:
            t = json.load(f)
        assert (t["config"], t["traffic"], t["chips"]) == (
            w["config"], w["traffic"], w["chips"])
    e2e = {m["name"] for m in bench["end_to_end"]}
    for section, kind in (("end_to_end", "end_to_end"),
                          ("per_layer", "layer_metrics")):
        for m in bench[section]:
            assert name_ok.match(m["name"]) and unit_ok.match(m["unit"])
            assert os.path.exists(os.path.join(here, kind, m["name"] + ".py"))
            if section == "per_layer":
                assert m["moves"] in e2e
