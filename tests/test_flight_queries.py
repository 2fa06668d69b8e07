"""The causal flight recorder's readers (tests/test_zzzzzflight.py has
the record exactness law): the schema'd JSONL event log (METRICS_SCHEMA
v4) written and loaded back, causal queries over recorded logs
(obs/query.py), Perfetto flow arrows and the empty-run guard, the CLI's
``--record`` and ``explain``, and the sweep-side wiring (``--record``,
status counts, ``--verify`` auto-bisect)."""

import json

import numpy as np
import pytest

from flight_laws import STEPS, _gossip, _run_cli, _steady_faulted
from timewarp_tpu.interp.jax_engine.engine import JaxEngine
from timewarp_tpu.obs.flight import EV_DELIVER, FlightWriter, load_flight_jsonl


def test_writer_loader_roundtrip(tmp_path):
    from timewarp_tpu.obs.metrics import validate_metrics_file
    sc, link = _gossip()
    eng = JaxEngine(sc, link, window="auto", lint="off",
                    record="full")
    eng.run(STEPS)
    log = eng.last_run_flight
    path = str(tmp_path / "ev.jsonl")
    w = FlightWriter(path, run="unit")
    assert w.write(log) == len(log)
    w.close()
    assert validate_metrics_file(path) == len(log)
    back = load_flight_jsonl(path)
    assert back.keyset() == log.keyset()
    assert (np.sort(back.superstep) == np.sort(log.superstep)).all()
    # loading a filtered-to-nothing view is loud, naming the file
    with pytest.raises(ValueError, match="holds no flight events"):
        load_flight_jsonl(path, run_id="nope")
    # the overflow evidence crosses the file boundary: a log with
    # dropped events round-trips its count (a reloaded truncated log
    # must not look complete — never silent)
    import dataclasses
    lossy = dataclasses.replace(log, dropped=7)
    path2 = str(tmp_path / "lossy.jsonl")
    w2 = FlightWriter(path2, run="unit")
    w2.write(lossy)
    w2.close()
    assert load_flight_jsonl(path2).dropped == 7


def test_metrics_v4_flight_event_form():
    from timewarp_tpu.obs.metrics import METRICS_SCHEMA, validate_line
    # v4 introduced the flight event form; later purely-additive
    # bumps (v5 = the speculation kind) must keep validating it
    assert METRICS_SCHEMA >= 4
    good = {"schema": 4, "kind": "event", "name": "flight",
            "ev": "deliver", "superstep": 3, "src": 1, "dst": 2,
            "send_t_us": -1, "t_us": 5000}
    validate_line(good)
    bad = dict(good)
    del bad["src"]
    with pytest.raises(ValueError, match="flight event.*'src'"):
        validate_line(bad)
    # a non-flight event line carries no such obligation
    validate_line({"schema": 4, "kind": "event", "name": "marker"})


def test_metrics_validate_empty_file_is_actionable(tmp_path):
    from timewarp_tpu.obs.metrics import validate_metrics_file
    p = tmp_path / "empty.jsonl"
    p.write_text("")
    with pytest.raises(ValueError, match=r"empty\.jsonl.*no metrics "
                                         r"records"):
        validate_metrics_file(str(p))
    p2 = tmp_path / "blank.jsonl"
    p2.write_text("\n\n   \n")
    with pytest.raises(ValueError, match="no metrics records"):
        validate_metrics_file(str(p2))






def test_explain_reconstructs_crash_partition_degrade_chain():
    from timewarp_tpu.obs.query import (chain_lines, explain_delivery,
                                        find_deliveries)
    sc, link, faults = _steady_faulted()
    eng = JaxEngine(sc, link, lint="off", faults=faults,
                    record="full", record_cap=1024)
    eng.run(200)
    log = eng.last_run_flight
    assert log.dropped == 0
    hits = find_deliveries(log, dst=3)
    assert len(hits) > 5
    # a delivery due after the crash window carries the full chain:
    # the send, the degrade window, the crash overlap, the deferral
    res = explain_delivery(log, dst=3, nth=4, faults=faults)
    steps = [c["step"] for c in res["chain"]]
    assert steps[0] == "send" and steps[-1] == "deliver"
    assert "degrade" in steps
    assert "crash_window" in steps
    assert "defer" in steps
    assert res["send_t_us"] is not None
    lines = chain_lines(res)
    assert len(lines) == len(steps)
    assert lines[0].startswith("send")
    # an early delivery sees only the degrade window
    res0 = explain_delivery(log, dst=3, nth=0, faults=faults)
    steps0 = [c["step"] for c in res0["chain"]]
    assert "crash_window" not in steps0 and "degrade" in steps0


def test_explain_deliveries_only_log_is_honest():
    from timewarp_tpu.obs.query import explain_delivery
    sc, link = _gossip()
    eng = JaxEngine(sc, link, window="auto", lint="off",
                    record="deliveries")
    eng.run(STEPS)
    log = eng.last_run_flight
    dst = int(log.dst[log.kind == EV_DELIVER][0])
    res = explain_delivery(log, dst=dst)
    send = res["chain"][0]
    assert send["step"] == "send" and send.get("unknown")
    assert "record='full'" in send["why"]


def test_explain_no_match_is_loud():
    from timewarp_tpu.obs.query import explain_delivery
    sc, link = _gossip()
    eng = JaxEngine(sc, link, window="auto", lint="off",
                    record="deliveries")
    eng.run(STEPS)
    with pytest.raises(ValueError, match="no delivery to node 9999"):
        explain_delivery(eng.last_run_flight, dst=9999)


def test_flow_arrows_on_the_virtual_timeline(tmp_path):
    from timewarp_tpu.obs import TraceBuilder
    from timewarp_tpu.obs.query import add_flight_flows
    sc, link = _gossip()
    eng = JaxEngine(sc, link, window="auto", lint="off",
                    record="full")
    eng.run(STEPS)
    tb = TraceBuilder(process="unit")
    n = add_flight_flows(tb, eng.last_run_flight, limit=16)
    assert 0 < n <= 16
    doc = json.loads(open(tb.save(str(tmp_path / "f.json"))).read())
    starts = [e for e in doc["traceEvents"] if e.get("ph") == "s"]
    ends = [e for e in doc["traceEvents"] if e.get("ph") == "f"]
    assert len(starts) == len(ends) == n
    assert {e["id"] for e in starts} == {e["id"] for e in ends}


def test_perfetto_empty_run_guard(tmp_path):
    from timewarp_tpu.obs import TraceBuilder
    tb = TraceBuilder(process="empty")
    # zero-superstep inputs add nothing and never crash
    tb.add_superstep_track(None)
    doc = tb.to_json()
    # the file holds a visible marker, not a blank/invalid trace
    assert any(e.get("ph") == "i" and "empty run" in e["name"]
               for e in doc["traceEvents"])
    path = tb.save(str(tmp_path / "e.json"))
    assert json.loads(open(path).read())["traceEvents"]


def test_cli_record_run_and_explain(tmp_path, capsys):
    ev = str(tmp_path / "ev.jsonl")
    args = ["token-ring", "--nodes", "8", "--steps", "40",
            "--lint", "off"]
    assert _run_cli(args + ["--record", "full",
                            "--record-out", ev]) == 0
    line = json.loads(capsys.readouterr().out.strip())
    assert line["flight"]["mode"] == "full"
    assert line["flight"]["events"] > 0
    assert line["flight"]["dropped"] == 0
    # off-mode summary carries no flight block, same results
    assert _run_cli(args) == 0
    off = json.loads(capsys.readouterr().out.strip())
    assert "flight" not in off
    assert off["delivered"] == line["delivered"]
    # explain a recorded delivery end-to-end
    log = load_flight_jsonl(ev)
    dst = int(log.dst[log.kind == EV_DELIVER][0])
    assert _run_cli(["explain", ev, "--dst", str(dst),
                     "--json"]) == 0
    res = json.loads(capsys.readouterr().out.strip())
    assert res["chain"][-1]["step"] == "deliver"


def test_cli_record_guards(tmp_path):
    with pytest.raises(SystemExit, match="--record deliveries"):
        _run_cli(["gossip", "--nodes", "8", "--steps", "4",
                  "--record-out", str(tmp_path / "e.jsonl")])
    with pytest.raises(SystemExit, match="--record-cap"):
        _run_cli(["gossip", "--nodes", "8", "--steps", "4",
                  "--record-cap", "64"])
    with pytest.raises(SystemExit, match="cannot carry"):
        _run_cli(["gossip", "--nodes", "8", "--steps", "4",
                  "--engine", "oracle", "--record", "full"])


_RING = {"nodes": 16, "n_tokens": 2, "think_us": 2000,
         "bootstrap_us": 1000, "end_us": 60_000, "mailbox_cap": 8}


def test_sweep_record_streams_and_status(tmp_path, capsys):
    from timewarp_tpu.obs.metrics import validate_metrics_file
    from timewarp_tpu.sweep.cli import sweep_main
    pack = tmp_path / "pack.json"
    pack.write_text(json.dumps([
        {"id": "w0", "scenario": "token-ring", "params": _RING,
         "link": "uniform:1000:5000", "seed": 0, "budget": 24},
        {"id": "w1", "scenario": "token-ring", "params": _RING,
         "link": "uniform:1000:5000", "seed": 1, "budget": 24}]))
    d = str(tmp_path / "j")
    assert sweep_main(["run", str(pack), "--journal", d, "--chunk",
                       "8", "--lint", "off", "--record", "full",
                       "--verify"]) == 0
    out = json.loads(capsys.readouterr().out.strip())
    assert out["ok"] and out["flight_events"] > 0
    ev = f"{d}/events.jsonl"
    assert out["events"] == ev
    assert validate_metrics_file(ev) == out["flight_events"]
    # per-world filtering works on the shared log
    log = load_flight_jsonl(ev, run_id="w0")
    assert len(log) > 0
    # an unfiltered load of the shared log refuses loudly — a merged
    # FlightLog would join causal chains across unrelated runs
    with pytest.raises(ValueError, match="2 runs"):
        load_flight_jsonl(ev)
    assert sweep_main(["status", "--journal", d]) == 0
    status = json.loads(capsys.readouterr().out.strip())
    assert set(status["flight_events"]) == {"w0", "w1"}
    assert sum(status["flight_events"].values()) \
        == out["flight_events"]


def test_sweep_verify_auto_bisects_injected_flip(tmp_path, capsys):
    from timewarp_tpu.sweep.cli import sweep_main
    pack = tmp_path / "pack.json"
    pack.write_text(json.dumps([
        {"id": "w0", "scenario": "token-ring", "params": _RING,
         "link": "uniform:1000:5000", "seed": 0, "budget": 24}]))
    d = str(tmp_path / "j")
    rc = sweep_main(["run", str(pack), "--journal", d, "--chunk",
                     "8", "--lint", "off", "--verify",
                     "--inject", "flip:2:2:time"])
    assert rc == 1
    out = json.loads(capsys.readouterr().out.strip())
    (mm,) = out["verify_mismatches"]
    d1 = mm["first_divergence"]
    # the auto-bisect names the diverging chunk: the flip landed
    # before chunk call 2 (1-based), i.e. journaled chunk index 1
    assert d1 is not None and d1["chunk"] == 1
    assert d1["supersteps"] == [8, 16]
    assert d1["streamed"] != d1["solo"]


def test_sweep_flip_without_any_verify_is_refused(tmp_path):
    from timewarp_tpu.sweep.cli import sweep_main
    pack = tmp_path / "pack.json"
    pack.write_text(json.dumps([
        {"id": "w0", "scenario": "token-ring", "params": _RING,
         "link": "uniform:1000:5000", "seed": 0, "budget": 24}]))
    with pytest.raises(SystemExit, match="auto-bisects"):
        sweep_main(["run", str(pack), "--journal",
                    str(tmp_path / "j"), "--inject", "flip:1:1"])
