"""Fleet mission control (ISSUE 13): the persistent run ledger
(obs/ledger.py), noise-aware cross-run regression gates + anomaly
detectors (obs/regress.py), and the live read-only sweep watch
(obs/watch.py + `sweep watch`).

The acceptance laws under test:

- ``ledger compare`` deterministically flags a doctored 2x wall-time
  regression (exit 1, one pinned line naming config_key + metric +
  delta) and exits 0 on byte-identical re-ingest of the same run;
- ``sweep watch`` attached to a live injected-chaos sweep never
  perturbs the journal (the post-run survival-law verify still
  passes) and its final aggregates equal ``sweep status --json``.

(Named test_zzzzzzzledger to sort after the existing suite — the
tier-1 time window truncates, so new tests must not displace
existing dots.)
"""

import json
import os
import threading
import time

import pytest

from timewarp_tpu.obs.ledger import (LedgerError, RunLedger,
                                     derive_config_key)
from timewarp_tpu.obs.regress import (compare_runs, compare_selections,
                                      detect_anomalies)
from timewarp_tpu.obs.watch import SweepWatch, TailReader
from timewarp_tpu.sweep.journal import JournalState, status_fields

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _line(value=100.0, *, config="gossip_100k", n=2048, **over):
    out = {"schema": 2, "config": config,
           "config_key": f"{config}|n{n}|s16384|cpu",
           "metric": f"gossip wave @{n} nodes", "value": value,
           "unit": "msg/s", "platform": "cpu", "device_kind": "cpu",
           "jax_version": "0.9", "git_sha": "cafe0123"}
    out.update(over)
    return out


# -- ledger core ----------------------------------------------------------

def test_ledger_layout_and_roundtrip(tmp_path):
    led = RunLedger(str(tmp_path / "led"))
    rid = led.add_bench_line(_line(), batch="b0001", source="test")
    assert rid == "r0001"
    # JSONL index + per-run artifact dir (record.json keeps the raw
    # source line; the index line stays slim)
    assert os.path.exists(str(tmp_path / "led" / "index.jsonl"))
    rec = led.get(rid)
    assert rec["line"]["value"] == 100.0
    assert rec["config_key"] == "gossip_100k|n2048|s16384|cpu"
    assert rec["git_sha"] == "cafe0123"
    idx = led.index()
    assert len(idx) == 1 and "line" not in idx[0]
    assert idx[0]["value"] == 100.0
    # monotone run ids, one batch per ingest session
    assert led.add_bench_line(_line(), batch=led.new_batch()) == "r0002"
    assert led.batches() == ["b0001", "b0002"]


def test_ledger_unknown_run_and_bad_line_are_loud(tmp_path):
    led = RunLedger(str(tmp_path / "led"))
    with pytest.raises(LedgerError, match="empty ledger"):
        led.get("r0042")
    with pytest.raises(LedgerError, match="not a bench line"):
        led.add_bench_line({"value": 3.0})
    with pytest.raises(LedgerError, match="JSON object"):
        led.add_bench_line(["not", "a", "dict"])


def test_ledger_index_crash_model(tmp_path):
    led = RunLedger(str(tmp_path / "led"))
    led.add_bench_line(_line(), batch="b0001")
    led.add_bench_line(_line(110.0), batch="b0001")
    # a torn FINAL line (crash mid-append) is dropped: the run simply
    # is not in the ledger
    with open(led.index_path, "a") as f:
        f.write('{"run_id": "r9999", "torn')
    assert [r["run_id"] for r in led.index()] == ["r0001", "r0002"]
    # ... and the next add reuses the uncommitted id cleanly
    assert led.add_bench_line(_line(), batch="b0002") == "r0003"
    # mid-file damage is external corruption, refused loudly
    lines = open(led.index_path).read().splitlines()
    lines[0] = lines[0][:-10]
    with open(led.index_path, "w") as f:
        f.write("\n".join(lines) + "\n")
    with pytest.raises(LedgerError, match="corrupt mid-file"):
        led.index()


def test_ledger_never_reclaims_an_orphan_run_dir(tmp_path):
    """A crash between record.json and the index append leaves an
    orphan run dir (that run is simply not in the ledger) — the next
    ingest must claim a FRESH id, never overwrite the orphan; the
    mkdir claim also makes concurrent writers collision-free."""
    led = RunLedger(str(tmp_path / "led"))
    led.add_bench_line(_line(), batch="b0001")
    orphan = os.path.join(str(tmp_path / "led"), "runs", "r0002")
    os.makedirs(orphan)                      # the crashed ingest
    # a fresh handle (no in-memory counter) must skip past it
    rid = RunLedger(str(tmp_path / "led")).add_bench_line(
        _line(), batch="b0002")
    assert rid == "r0003"
    assert not os.path.exists(os.path.join(orphan, "record.json"))


def test_compare_zero_baseline_still_gates():
    """A 0-second baseline must not neutralize the wall gate (the
    ratio is undefined, not +0.0%): 0 -> 10 s is a regression; a
    0-rate baseline means the BASELINE was broken, so a nonzero
    candidate rate only improves on it."""
    def rec(run, **m):
        return {"kind": "bench", "run_id": run, "config_key": "k",
                "git_sha": "g", **m}
    rep = compare_runs([rec("r1", seconds=0.0)],
                       [rec("r2", seconds=10.0)])
    [bad] = rep.regressions
    assert bad.rel is None and bad.metric == "seconds"
    assert "REGRESSION" in bad.line() and "ratio undefined" in bad.line()
    assert bad.to_json()["rel"] is None
    # 0 -> 0 is a clean zero-delta pass
    assert compare_runs([rec("r1", seconds=0.0)],
                        [rec("r2", seconds=0.0)]).to_json()["ok"]
    # broken-baseline rate: candidate can only improve
    assert compare_runs([rec("r1", value=0.0)],
                        [rec("r2", value=50.0)]).to_json()["ok"]


def test_config_key_derivation_v1_vs_v2():
    # v2 lines stamp their own key — passthrough, never re-derived
    assert derive_config_key(_line()) == "gossip_100k|n2048|s16384|cpu"
    # v1 archive lines get a deterministic slug: metric text minus
    # the unit boilerplate, plus platform (unknown for r01–r03)
    v1 = {"metric": "token-ring dense delivered-messages/sec/chip "
                    "@65536 nodes", "value": 1.0, "unit": "msg/s"}
    assert derive_config_key(v1) == "token-ring-dense-65536-nodes|unknown"
    assert derive_config_key(dict(v1, platform="tpu")) \
        == "token-ring-dense-65536-nodes|tpu"
    # derivation is shape-separating: different node counts never join
    v1b = dict(v1, metric=v1["metric"].replace("65536", "1048576"))
    assert derive_config_key(v1b) != derive_config_key(v1)


def _write_v1_wrappers(dirpath):
    """Five schema-1 wrapper artifacts (``BENCH_r01``–``r05.json``) of
    the shape the driver of the early rounds wrote: ``{"n", "cmd",
    "rc", "tail", "parsed": <bench line>}``, no ``schema``, no
    ``git_sha``, ``calib`` from the fourth on. The values are made up
    (the records they imitate went in PR 21); r02 -> r03 stays inside
    the 30% rate gate."""
    ring = "token-ring dense delivered-messages/sec/chip @{} nodes"
    rows = [(ring.format(65536), 1.0e6, None),
            (ring.format(1048576), 1.5e9, None),
            (ring.format(1048576), 1.1e9, None),
            (ring.format(1048576), 1.3e9, 0.18),
            (ring.format(1048576).replace(
                "dense", "dense (fused pallas superstep)"), 6.5e9, 0.16)]
    files = []
    for i, (metric, value, calib) in enumerate(rows, start=1):
        line = {"metric": metric, "value": value, "unit": "msg/s",
                "vs_baseline": round(value / 1e8, 4)}
        if calib is not None:
            line["calib"] = {"kernel": "sort_1m_int32_x64",
                             "seconds": calib}
        path = os.path.join(dirpath, f"BENCH_r0{i}.json")
        with open(path, "w") as f:
            json.dump({"n": i, "cmd": "python bench.py", "rc": 0,
                       "tail": json.dumps(line) + "\n",
                       "parsed": line}, f)
        files.append(path)
    return files


def test_ledger_import_seeds_the_historical_trajectory(tmp_path):
    """Five v1 wrapper artifacts (the ``BENCH_r0*.json`` shape) ingest
    as ledger history (ISSUE 13 satellite): `ledger list` starts with
    the r01–r05 trajectory, each under its file-stem batch."""
    led_dir = str(tmp_path / "led")
    from timewarp_tpu.cli import main
    files = _write_v1_wrappers(str(tmp_path))
    rc = main(["ledger", "import", "--ledger", led_dir] + files)
    assert rc == 0
    led = RunLedger(led_dir)
    runs = led.index()
    assert [r["batch"] for r in runs] \
        == [f"BENCH_r0{i}" for i in range(1, 6)]
    assert all(r["kind"] == "bench" for r in runs)
    assert all(r["bench_schema"] in (None, 1) for r in runs)
    # schema-1 lines carry no git_sha — honestly unknown, never faked
    assert all(r["git_sha"] == "unknown" for r in runs)
    # the r02 -> r03 dense-ring delta is within the 30% rate gate:
    # the real trajectory compares clean end-to-end
    rep = compare_selections(led, "BENCH_r02", "BENCH_r03")
    assert rep.to_json()["ok"], [d.line() for d in rep.deltas]


# -- cross-run comparison -------------------------------------------------

def test_compare_identical_reingest_is_zero_delta(tmp_path):
    led = RunLedger(str(tmp_path / "led"))
    led.add_bench_line(_line(), batch="b0001")
    led.add_bench_line(_line(), batch="b0002")   # byte-identical
    rep = compare_selections(led, "b0001", "b0002")
    assert rep.to_json()["ok"] and len(rep.deltas) == 1
    assert rep.deltas[0].rel == 0.0


def test_compare_flags_doctored_2x_wall_time(tmp_path):
    """THE acceptance gate: a smoke line doctored 2x slower must fail
    deterministically with one pinned line naming config_key, metric,
    and delta."""
    smoke = {"schema": 2, "config": "praos_1m",
             "config_key": "praos_1m|n2048|s24|cpu",
             "metric": "praos @2048", "smoke": True, "ok": True,
             "seconds": 8.0, "platform": "cpu", "git_sha": "aaa111"}
    led = RunLedger(str(tmp_path / "led"))
    led.add_bench_line(smoke, batch="b0001")
    led.add_bench_line(dict(smoke, seconds=16.0, git_sha="bbb222"),
                       batch="b0002")
    rep = compare_selections(led, "b0001", "b0002")
    assert not rep.to_json()["ok"]
    [bad] = rep.regressions
    line = bad.line()
    assert line.startswith("REGRESSION praos_1m|n2048|s24|cpu "
                           "seconds: 8 -> 16 (+100.0%")
    assert "aaa111" in line and "bbb222" in line
    # the CLI face: exit 1, the pinned line on stdout
    from timewarp_tpu.cli import main
    assert main(["ledger", "compare", "--ledger",
                 str(tmp_path / "led"), "b0001", "b0002"]) == 1
    # ... and the un-doctored direction still exits 0
    assert main(["ledger", "compare", "--ledger",
                 str(tmp_path / "led"), "b0001", "b0001"]) == 0


def test_compare_rate_gate_and_spread_bands(tmp_path):
    led = RunLedger(str(tmp_path / "led"))
    # beyond the 30% rate gate with disjoint bands -> regression
    led.add_bench_line(_line(100.0, min=95.0, max=105.0, reps=3),
                       batch="b0001")
    led.add_bench_line(_line(60.0, min=57.0, max=63.0, reps=3),
                       batch="b0002")
    rep = compare_selections(led, "b0001", "b0002")
    assert len(rep.regressions) == 1
    # beyond the gate but with OVERLAPPING min/max bands -> the
    # measured spread could explain it: a note, never a failure
    led.add_bench_line(_line(60.0, min=55.0, max=99.0, reps=3),
                       batch="b0003")
    rep = compare_selections(led, "b0001", "b0003")
    assert rep.to_json()["ok"]
    assert rep.deltas[0].within_spread
    # an IMPROVEMENT never fails, bands or not
    led.add_bench_line(_line(250.0), batch="b0004")
    assert compare_selections(led, "b0001", "b0004").to_json()["ok"]


def test_compare_gates_packing_rollups(tmp_path):
    """The packing rollups (sweep/journal.py util_rollup) ride the
    bench line into the index and gate like rates: budget_efficiency
    regresses DOWN, pad_waste_frac regresses UP; lines without the
    fields stay inert."""
    led = RunLedger(str(tmp_path / "led"))
    led.add_bench_line(_line(100.0, budget_efficiency=0.80,
                             pad_waste_frac=0.05), batch="b0001")
    # efficiency collapses 40% -> a regression on that metric alone
    led.add_bench_line(_line(100.0, budget_efficiency=0.48,
                             pad_waste_frac=0.05), batch="b0002")
    rep = compare_selections(led, "b0001", "b0002")
    assert [d.metric for d in rep.regressions] == \
        ["budget_efficiency"]
    # pad waste balloons 10x -> lower-is-better gates on the INCREASE
    led.add_bench_line(_line(100.0, budget_efficiency=0.80,
                             pad_waste_frac=0.50), batch="b0003")
    rep = compare_selections(led, "b0001", "b0003")
    assert [d.metric for d in rep.regressions] == ["pad_waste_frac"]
    # improvements never fail; rollup-less lines compare clean
    led.add_bench_line(_line(100.0, budget_efficiency=0.95,
                             pad_waste_frac=0.0), batch="b0004")
    assert compare_selections(led, "b0001", "b0004").to_json()["ok"]
    led.add_bench_line(_line(100.0), batch="b0005")
    assert compare_selections(led, "b0001", "b0005").to_json()["ok"]


def test_compare_join_and_selectors(tmp_path):
    led = RunLedger(str(tmp_path / "led"))
    led.add_bench_line(_line(config="gossip_100k"), batch="b0001")
    led.add_bench_line(_line(config="praos_1m"), batch="b0001")
    led.add_bench_line(_line(config="gossip_100k"), batch="b0002")
    rep = compare_selections(led, "b0001", "b0002")
    # unmatched config_keys are notes, never failures
    assert rep.unmatched_a == ["praos_1m|n2048|s16384|cpu"]
    assert rep.to_json()["ok"]
    # run_id and config_key-substring selectors resolve too
    assert compare_selections(led, "r0001", "r0003").to_json()["ok"]
    assert compare_selections(led, "gossip_100k",
                              "gossip_100k").to_json()["ok"]
    with pytest.raises(LedgerError, match="matches no run_id"):
        compare_selections(led, "b0001", "nonesuch")


def test_compare_runs_skips_non_bench_records():
    bench = {"kind": "bench", "run_id": "r0001", "config_key": "k",
             "value": 10.0, "git_sha": "x"}
    sweep = {"kind": "sweep", "run_id": "r0002", "config_key": "k"}
    rep = compare_runs([bench, sweep], [bench])
    assert len(rep.deltas) == 1 and rep.to_json()["ok"]


# -- anomaly detectors ----------------------------------------------------

def _scan(**over):
    st = JournalState()
    for k, v in over.items():
        setattr(st, k, v)
    return st


def test_rollback_storm_detectors():
    # speculation: 6 rollbacks vs 2 committed decisions -> storm
    st = _scan(spec_rollbacks=[{"chunk": i} for i in range(6)],
               decisions={"b0": [{"chunk": 0, "rung_pin": 1,
                                  "window_us": 500, "chunk_len": 8},
                                 {"chunk": 1, "rung_pin": 1,
                                  "window_us": 500, "chunk_len": 8}]})
    [a] = detect_anomalies(scan=st)
    assert a.kind == "rollback-storm" and "6 causality" in a.detail
    # the same count against many commits is a healthy ladder
    many = {"b0": [{"chunk": i, "rung_pin": 1, "window_us": 500,
                    "chunk_len": 8} for i in range(40)]}
    assert detect_anomalies(scan=_scan(
        spec_rollbacks=[{"chunk": i} for i in range(6)],
        decisions=many)) == []
    # integrity: repeated detected corruptions -> SDC-prone host
    [a] = detect_anomalies(scan=_scan(
        integrity=[{"chunk": i} for i in range(3)]))
    assert a.kind == "rollback-storm" and a.severity == "error"
    assert detect_anomalies(scan=_scan(integrity=[{"chunk": 1}])) == []


def test_rung_thrash_detector():
    flip = [{"chunk": i, "rung_pin": i % 2, "window_us": 500,
             "chunk_len": 8} for i in range(12)]
    [a] = detect_anomalies(scan=_scan(decisions={"b3": flip}))
    assert a.kind == "rung-thrash" and "bucket b3" in a.subject
    steady = [dict(d, rung_pin=2) for d in flip]
    assert detect_anomalies(scan=_scan(decisions={"b3": steady})) == []
    # below the minimum decision count the signal is too thin to call
    assert detect_anomalies(scan=_scan(decisions={"b3": flip[:4]})) == []


def test_bucket_util_collapse_detector():
    good = {"budget_efficiency": 0.83, "worlds_active_mean": 0.91}
    bad = {"budget_efficiency": 0.12, "worlds_active_mean": 0.9}
    [a] = detect_anomalies(scan=_scan(util={"b0": good, "b1": bad}))
    assert a.kind == "bucket-util-collapse" and "bucket b1" in a.subject
    assert "budget_efficiency 0.120" in a.detail


def test_quiescence_straggler_detector():
    done = {f"w{i}": {"supersteps": 40} for i in range(5)}
    done["w9"] = {"supersteps": 400}
    [a] = detect_anomalies(scan=_scan(done=done))
    assert a.kind == "quiescence-straggler" and "w9" in a.subject
    # under 4 worlds a median is too thin — never fires
    assert detect_anomalies(scan=_scan(
        done={"a": {"supersteps": 4}, "b": {"supersteps": 400}})) == []


def test_metrics_stream_detectors(tmp_path):
    p = tmp_path / "m.jsonl"
    rows = [{"schema": 5, "kind": "speculation", "label": "x",
             "chunk": i, "window_us": 16000, "outcome": "rollback"}
            for i in range(5)]
    rows += [{"schema": 2, "kind": "decision", "chunk": i,
              "window_us": 500, "rung_pin": i % 2, "chunk_len": 8}
             for i in range(10)]
    p.write_text("".join(json.dumps(r) + "\n" for r in rows))
    kinds = {a.kind for a in detect_anomalies(metrics_path=str(p))}
    assert kinds == {"rollback-storm", "rung-thrash"}
    assert detect_anomalies(metrics_path=str(p),
                            rollback_rate=1.0, thrash_frac=1.0) == []
    with pytest.raises(ValueError, match="unknown anomaly thresholds"):
        detect_anomalies(metrics_path=str(p), nope=1)
    # a torn FINAL line is the live-writer crash model: tolerated
    with open(p, "a") as f:
        f.write('{"schema": 5, "kind": "specul')
    assert {a.kind for a in detect_anomalies(metrics_path=str(p))} \
        == kinds
    # mid-file damage must REFUSE, not under-count (never-silent)
    text = p.read_text().splitlines()
    text[2] = text[2][:-15]
    p.write_text("\n".join(text) + "\n")
    with pytest.raises(ValueError, match="corrupt mid-file"):
        detect_anomalies(metrics_path=str(p))


def test_anomalies_cli_refuses_bench_runs(tmp_path):
    """`ledger anomalies <bench run>` must refuse loudly — a bench
    line carries no telemetry, and silently analyzing its source as
    a metrics file would report a healthy nothing."""
    from timewarp_tpu.cli import main
    led_dir = str(tmp_path / "led")
    RunLedger(led_dir).add_bench_line(_line(), batch="b0001",
                                      source="bench.py")
    with pytest.raises(SystemExit, match="is a bench line"):
        main(["ledger", "anomalies", "r0001", "--ledger", led_dir])


# -- the live watch -------------------------------------------------------

def test_tail_reader_is_torn_tail_tolerant(tmp_path):
    p = tmp_path / "t.jsonl"
    tr = TailReader(str(p))
    assert tr.poll() == []              # absent file: keep waiting
    with open(p, "w") as f:
        f.write('{"a": 1}\n{"b": 2')    # one whole line + a torn tail
    assert tr.poll() == [{"a": 1}]
    assert tr.poll() == []              # the tail stays unconsumed
    with open(p, "a") as f:
        f.write('2}\n')                 # the append completes it
    assert tr.poll() == [{"b": 22}]
    # a COMPLETE unparsable line is counted, never raised — a watcher
    # must keep watching
    with open(p, "a") as f:
        f.write('not json\n{"c": 3}\n')
    assert tr.poll() == [{"c": 3}]
    assert tr.parse_errors == 1


def test_sweep_watch_live_chaos_never_perturbs(tmp_path):
    """The acceptance law: a watcher attached to a LIVE
    injected-chaos sweep (a) never perturbs the journal — the
    post-run survival-law verify still passes — and (b) reports
    final aggregates equal to `sweep status --json`."""
    from timewarp_tpu.sweep import SweepPack, SweepService, solo_result

    ring = {"nodes": 20, "n_tokens": 3, "think_us": 2000,
            "end_us": 70000, "mailbox_cap": 8}
    pack = SweepPack.from_json([
        {"id": "ring-a", "scenario": "token-ring", "params": ring,
         "link": "uniform:1000:5000", "seed": 0, "budget": 60},
        {"id": "ring-b", "scenario": "token-ring", "params": ring,
         "link": "uniform:2000:7000", "seed": 3, "budget": 90},
    ])
    d = str(tmp_path / "j")
    watcher = SweepWatch(d)
    snaps, stop = [], threading.Event()

    def tail():
        while not stop.is_set():
            snaps.append(watcher.poll())
            time.sleep(0.05)

    t = threading.Thread(target=tail)
    t.start()
    try:
        # injected chaos: one transient failure -> the retry path
        svc = SweepService(pack, d, chunk=16, lint="off",
                           inject="fail:1")
        report = svc.run()
    finally:
        stop.set()
        t.join()
    assert report.ok and report.retries >= 1
    # (a) the journal is unperturbed: every streamed result is still
    # bit-identical to its solo run (the survival law — what `sweep
    # resume --verify` asserts)
    for rid, res in report.done.items():
        want = solo_result(pack.by_id(rid), lint="off")
        assert want == res, f"watcher perturbed world {rid}"
    # (b) the watcher's FINAL aggregates equal `sweep status --json`
    # — same fold, same assembly, pinned here end-to-end
    final = watcher.poll()
    from timewarp_tpu.sweep.journal import SweepJournal
    expect = status_fields(SweepJournal(d).scan(), len(pack.configs))
    shared = {k: v for k, v in final.items() if k != "watch"}
    assert shared == expect
    assert final["watch"]["finished"]
    assert final["watch"]["parse_errors"] == 0
    assert final["events"]["dispatch_decision"] == 0
    # the live tail actually saw the sweep in flight
    assert any(s["completed"] < len(pack.configs) for s in snaps)
    # the text render is one plain line (keybinds-free contract)
    line = watcher.render(final)
    assert line.startswith("sweep DONE | worlds 2/2 done")
    assert "\x1b" not in line and "\n" not in line


def test_sweep_watch_cli_once_and_status_events_block(tmp_path, capsys):
    """`sweep watch --once` against a finished journal (the CI leg)
    and the `sweep status --json` events block (ISSUE 13 satellite):
    watch and status must report identical numbers."""
    from timewarp_tpu.sweep import SweepPack, SweepService
    from timewarp_tpu.sweep.cli import sweep_main

    ring = {"nodes": 20, "n_tokens": 3, "think_us": 2000,
            "end_us": 70000, "mailbox_cap": 8}
    pack = SweepPack.from_json([
        {"id": "ring-a", "scenario": "token-ring", "params": ring,
         "link": "uniform:1000:5000", "seed": 0, "budget": 60},
    ])
    d = str(tmp_path / "j")
    assert SweepService(pack, d, chunk=16, lint="off").run().ok
    capsys.readouterr()
    assert sweep_main(["status", "--journal", d]) == 0
    status = json.loads(capsys.readouterr().out)
    assert set(status["events"]) == {"dispatch_decision",
                                     "spec_rollback",
                                     "integrity_violation",
                                     "pack_decision"}
    assert sweep_main(["watch", "--journal", d, "--once",
                       "--json"]) == 0
    snap = json.loads(capsys.readouterr().out)
    assert {k: v for k, v in snap.items() if k != "watch"} == status
    # the text form exits 0 too and stays escape-code-free
    assert sweep_main(["watch", "--journal", d, "--once"]) == 0
    out = capsys.readouterr().out
    assert out.startswith("sweep DONE") and "\x1b" not in out
    # a dir with no journal refuses loudly
    with pytest.raises(SystemExit, match="no sweep journal"):
        sweep_main(["watch", "--journal", str(tmp_path / "nope"),
                    "--once"])
    with pytest.raises(SystemExit, match="interval"):
        sweep_main(["watch", "--journal", d, "--interval", "0"])


def test_sweep_ingest_records_status_fields(tmp_path):
    """`ledger add <journal-dir>` captures the status/watch block —
    the chip-round measurement ledger's sweep face."""
    from timewarp_tpu.sweep import SweepPack, SweepService

    ring = {"nodes": 20, "n_tokens": 3, "think_us": 2000,
            "end_us": 70000, "mailbox_cap": 8}
    pack = SweepPack.from_json([
        {"id": "ring-a", "scenario": "token-ring", "params": ring,
         "link": "uniform:1000:5000", "seed": 0, "budget": 60},
    ])
    d = str(tmp_path / "j")
    assert SweepService(pack, d, chunk=16, lint="off").run().ok
    led = RunLedger(str(tmp_path / "led"))
    [rid] = led.add_source(d)
    rec = led.get(rid)
    assert rec["kind"] == "sweep"
    assert rec["config_key"].startswith("sweep|")
    assert rec["sweep"]["completed"] == 1
    assert rec["sweep"]["events"] == {"dispatch_decision": 0,
                                      "spec_rollback": 0,
                                      "integrity_violation": 0,
                                      "pack_decision": 0}
    # the per-world (features, budget, supersteps) rows `pack fit`
    # trains on (pack/predict.py training_rows) ride the ingest —
    # every archived run is predictor history
    [row] = rec["sweep"]["pack_stats"]
    assert row["family"] == "token-ring" and row["budget"] == 60
    assert 0 < row["supersteps"] <= 60
    from timewarp_tpu.pack.predict import fit_from_ledger
    art = fit_from_ledger(str(tmp_path / "led"))
    assert art["rows"] == 1 and art["sha"]
    with pytest.raises(LedgerError, match="no sweep journal"):
        led.add_sweep(str(tmp_path / "empty"))


def test_bench_ledger_flag_auto_appends(tmp_path, monkeypatch):
    """`bench.py --ledger DIR` appends every emitted line (BENCH
    SCHEMA v2: config_key + git_sha stamped) under one batch."""
    import sys

    import bench
    monkeypatch.setenv("TW_BENCH_CONFIG", "token_ring_dense")
    monkeypatch.setenv("TW_BENCH_NODES", "256")
    monkeypatch.setenv("TW_BENCH_STEPS", "32")
    led_dir = str(tmp_path / "led")
    monkeypatch.setattr(sys, "argv",
                        ["bench.py", "--ledger", led_dir])
    monkeypatch.setattr(bench, "_LEDGER", None)
    # the ledger plumbing is the subject, not the numbers: the
    # measured path's chip refusal (tests/test_bench_configs.py) is
    # steered aside here, in the test
    monkeypatch.setattr(bench, "_require_chip", lambda what: None)
    bench.main()
    runs = RunLedger(led_dir).index()
    assert len(runs) == 1
    assert runs[0]["config_key"] == "token_ring_dense|n256|s32|cpu"
    assert runs[0]["bench_schema"] == bench.BENCH_SCHEMA
    assert runs[0]["unit"] == "msg/s" and runs[0]["value"] > 0
    assert runs[0]["batch"] == "b0001"
    # a second invocation lands in a fresh batch -> comparable pair
    monkeypatch.setattr(bench, "_LEDGER", None)
    bench.main()
    led = RunLedger(led_dir)
    assert led.batches() == ["b0001", "b0002"]
    # same config re-run: compare joins on the key (noise-gated)
    rep = compare_selections(led, "b0001", "b0002", rate_gate=100.0)
    assert len(rep.deltas) == 1
