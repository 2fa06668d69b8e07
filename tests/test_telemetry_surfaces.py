"""Telemetry's host side beyond the frames (tests/test_zztelemetry.py
has the exactness law): the metrics registry and its loud validation,
the Perfetto export, the CLI surface (digests equal to ``off``, the
guards, ``profile``), and the sweep service's utilization records."""

import json

import pytest

from timewarp_tpu.interp.jax_engine.engine import JaxEngine
from timewarp_tpu.models.gossip import gossip
from timewarp_tpu.net.delays import Quantize, UniformDelay


N = 48


def _gossip():
    sc = gossip(N, fanout=3, burst=True, end_us=150_000,
                mailbox_cap=16)
    return sc, Quantize(UniformDelay(3000, 9000), 1000)


def test_metrics_registry_roundtrip(tmp_path):
    from timewarp_tpu.obs import MetricsRegistry, validate_metrics_file
    sc, link = _gossip()
    eng = JaxEngine(sc, link, window="auto", lint="off",
                    telemetry="counters")
    path = str(tmp_path / "m.jsonl")
    reg = MetricsRegistry(path=path, run="test")
    eng.metrics = reg
    _, trace = eng.run(20)          # auto chunk-flush via the engine
    reg.run_summary("test", eng.last_run_stats)
    with reg.span("unit-span", what="x"):
        pass
    reg.event("marker")
    reg.close()
    n = validate_metrics_file(path)
    assert n == len(reg.lines) == 4
    kinds = [r["kind"] for r in reg.lines]
    assert kinds == ["supersteps", "run_summary", "span", "event"]
    sup = reg.lines[0]
    assert sup["supersteps"] == len(trace)
    assert sup["route_drop"] == 0


def test_metrics_validation_is_loud(tmp_path):
    from timewarp_tpu.obs import (MetricsRegistry, validate_line,
                                  validate_metrics_file)
    with pytest.raises(ValueError, match="unknown metrics kind"):
        validate_line({"schema": 2, "kind": "nope"})
    with pytest.raises(ValueError, match="schema"):
        validate_line({"schema": 99, "kind": "event", "name": "x"})
    with pytest.raises(ValueError, match="wall_s"):
        validate_line({"schema": 2, "kind": "span", "name": "s",
                       "wall_s": "fast"})
    # emit refuses to write an invalid line at the source
    reg = MetricsRegistry()
    with pytest.raises(ValueError):
        reg.emit("span", name="missing wall_s")
    # file validation names file and line
    p = tmp_path / "bad.jsonl"
    p.write_text('{"schema": 2, "kind": "event", "name": "ok"}\n'
                 '{"schema": 2, "kind": "mystery"}\n')
    with pytest.raises(ValueError, match=r"bad\.jsonl:2"):
        validate_metrics_file(str(p))


def test_perfetto_trace_builder(tmp_path):
    from timewarp_tpu.obs import TraceBuilder
    sc, link = _gossip()
    eng = JaxEngine(sc, link, window="auto", lint="off",
                    telemetry="full")
    _, trace = eng.run(20)
    tb = TraceBuilder(process="unit")
    # spans reach the timeline through the registry's mirror: the one
    # span primitive times them (obs/profiler.py, ISSUE 35)
    from timewarp_tpu.obs import MetricsRegistry
    with MetricsRegistry(tracer=tb).span("outer"):
        tb.instant("mark")
    tb.add_superstep_track(eng.last_run_telemetry, trace)
    tb.compile_marks("unit", eng.last_run_stats["compiles"])
    path = tb.save(str(tmp_path / "t.json"))
    doc = json.loads(open(path).read())
    evs = doc["traceEvents"]
    assert any(e.get("ph") == "M" for e in evs)      # process names
    assert any(e.get("ph") == "X" and e["name"] == "outer"
               for e in evs)
    counters = [e for e in evs if e.get("ph") == "C"
                and e["name"] == "superstep"]
    assert len(counters) == len(trace)
    # counter timestamps ride VIRTUAL time
    assert counters[0]["ts"] == int(trace.times[0])






def _run_cli(argv):
    from timewarp_tpu.cli import main
    return main(argv)


def test_cli_telemetry_digests_match_off(tmp_path, capsys):
    args = ["gossip", "--nodes", "32", "--steps", "25", "--burst",
            "--window", "auto", "--link",
            "quantize:1000:uniform:3000:9000", "--lint", "off"]
    off_csv = str(tmp_path / "off.csv")
    full_csv = str(tmp_path / "full.csv")
    m = str(tmp_path / "m.jsonl")
    assert _run_cli(args + ["--trace-csv", off_csv]) == 0
    line_off = json.loads(capsys.readouterr().out.strip())
    assert _run_cli(args + ["--trace-csv", full_csv, "--telemetry",
                            "full", "--metrics-out", m,
                            "--trace-out",
                            str(tmp_path / "t.json")]) == 0
    line_full = json.loads(capsys.readouterr().out.strip())
    # the CI telemetry-smoke law, in-process: bit-identical traces
    assert open(off_csv).read() == open(full_csv).read()
    assert line_off["delivered"] == line_full["delivered"]
    assert line_full["telemetry"]["mode"] == "full"
    from timewarp_tpu.obs import validate_metrics_file
    assert validate_metrics_file(m) >= 2
    doc = json.loads(open(tmp_path / "t.json").read())
    assert doc["traceEvents"]


def test_cli_guards(tmp_path):
    with pytest.raises(SystemExit, match="--telemetry"):
        _run_cli(["gossip", "--nodes", "8", "--steps", "4",
                  "--metrics-out", str(tmp_path / "x.jsonl")])
    with pytest.raises(SystemExit, match="oracle"):
        _run_cli(["gossip", "--nodes", "8", "--steps", "4",
                  "--engine", "oracle", "--telemetry", "counters"])


def test_profile_subcommand(tmp_path, capsys):
    from timewarp_tpu.cli import main
    out = str(tmp_path / "p.json")
    rc = main(["profile", "token-ring", "--out", out, "--nodes", "8",
               "--steps", "16", "--lint", "off"])
    assert rc == 0
    lines = capsys.readouterr().out.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["trace"] == out
    doc = json.loads(open(out).read())
    assert doc["traceEvents"]






def test_sweep_telemetry_utilization_and_survival(tmp_path):
    from timewarp_tpu.obs import validate_metrics_file
    from timewarp_tpu.sweep import (SweepJournal, SweepPack,
                                    SweepService, solo_result)
    ring = {"nodes": 16, "n_tokens": 2, "think_us": 2000,
            "end_us": 60_000, "mailbox_cap": 8}
    pack = SweepPack.from_json([
        {"id": "r0", "scenario": "token-ring", "params": ring,
         "link": "uniform:1000:5000", "seed": 0, "budget": 40},
        {"id": "r1", "scenario": "token-ring", "params": ring,
         "link": "uniform:1000:5000", "seed": 1, "budget": 24},
    ])
    d = str(tmp_path / "j")
    svc = SweepService(pack, d, chunk=8, lint="off",
                       telemetry="counters")
    report = svc.run()
    assert report.ok
    # the survival law is telemetry-mode-independent
    for rid, res in report.done.items():
        assert solo_result(pack.by_id(rid), lint="off") == res
    # metrics stream exists and validates
    assert validate_metrics_file(f"{d}/metrics.jsonl") >= 1
    # the Perfetto trace was written with attempt spans
    doc = json.loads(open(svc.trace_path).read())
    assert any(e.get("cat") == "attempt"
               for e in doc["traceEvents"])
    scan = SweepJournal(d).scan()
    # bucket_util journaled alongside world_done (the SCALE-Sim-style
    # packing report) with sane efficiency numbers
    assert scan.util, "no bucket_util record journaled"
    u = next(iter(scan.util.values()))
    assert u["worlds"] == 2
    assert 0 < u["budget_efficiency"] <= 1
    assert 0 <= u["pad_waste_frac"] < 1
    assert 0 < u["worlds_active_mean"] <= 1
    # world_done carries wall/attempts OUTSIDE result (resume-safe:
    # the survival-law compare surface stays bit-deterministic)
    wd = [e for e in scan.events if e.get("ev") == "world_done"]
    assert wd and all("wall_s" in e and "attempts" in e for e in wd)
    assert all("wall_s" not in e["result"] for e in wd)


def test_sweep_status_surfaces_utilization(tmp_path, capsys):
    from timewarp_tpu.sweep.cli import sweep_main
    ring = {"nodes": 16, "n_tokens": 2, "think_us": 2000,
            "end_us": 60_000, "mailbox_cap": 8}
    pack = tmp_path / "pack.json"
    pack.write_text(json.dumps([
        {"id": "w0", "scenario": "token-ring", "params": ring,
         "link": "uniform:1000:5000", "seed": 0, "budget": 24}]))
    d = str(tmp_path / "j")
    assert sweep_main(["run", str(pack), "--journal", d,
                       "--chunk", "8", "--lint", "off"]) == 0
    capsys.readouterr()
    assert sweep_main(["status", "--journal", d]) == 0
    status = json.loads(capsys.readouterr().out.strip())
    assert "utilization" in status
    assert status["completed"] == 1
    (util,) = status["utilization"].values()
    assert util["world_supersteps"] <= util["scan_supersteps"]
