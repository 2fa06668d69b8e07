"""The causal flight recorder (obs/flight.py, ISSUE 11): the record
exactness law — states/traces under ``record="deliveries"|"full"``
bit-identical to ``"off"``, and the off-mode jaxpr IS the default
engine's jaxpr — plus the debugging layer built on it: divergence
bisection's pinned one-line diagnostic (obs/bisect.py), causal
queries over recorded logs (obs/query.py), the schema'd JSONL event
log (METRICS_SCHEMA v4), Perfetto flow arrows + the empty-run guard,
and the sweep-side wiring (--record, status counts, --verify
auto-bisect).

(Named test_zzzzz* to sort after the whole existing suite — the
tier-1 window truncates, and new tests must not displace existing
dots.)
"""

import json

import numpy as np
import pytest

import jax

from timewarp_tpu.interp.jax_engine.batched import BatchSpec
from timewarp_tpu.interp.jax_engine.edge_engine import EdgeEngine
from timewarp_tpu.interp.jax_engine.engine import JaxEngine
from timewarp_tpu.models.gossip import gossip
from timewarp_tpu.models.token_ring import token_ring
from timewarp_tpu.net.delays import FixedDelay, Quantize, UniformDelay
from timewarp_tpu.obs.flight import (EV_DELIVER, EV_FAULT, EV_SEND,
                                     FlightWriter, load_flight_jsonl)
from timewarp_tpu.trace.events import (assert_states_equal,
                                       assert_traces_equal)

N = 32
STEPS = 25


def _gossip():
    sc = gossip(N, fanout=3, burst=True, end_us=150_000,
                mailbox_cap=16)
    return sc, Quantize(UniformDelay(3000, 9000), 1000)


def _ring():
    sc = token_ring(16, n_tokens=4, think_us=2000,
                    bootstrap_us=1000, end_us=120_000,
                    with_observer=False, mailbox_cap=8)
    return sc, FixedDelay(500)


def _steady_faulted():
    """The worked causal-chain scenario (README, CI): steady gossip
    under a crash + a degraded-link window + a partition — deliveries
    into node 3 after the crash window carry the full chain."""
    from timewarp_tpu.faults.schedule import parse_faults
    sc = gossip(16, fanout=3, steady=True, end_us=300_000,
                mailbox_cap=16)
    link = Quantize(UniformDelay(3000, 9000), 1000)
    faults = parse_faults("crash:3:50000:120000;"
                          "degrade:all:3:0:300000:2.0:500;"
                          "partition:0-7|8-15:20000:40000")
    return sc, link, faults


# ---------------------------------------------------------------------------
# the record exactness law, engine by engine
# ---------------------------------------------------------------------------

def test_general_engine_record_modes_bit_identical():
    sc, link = _gossip()
    off = JaxEngine(sc, link, window="auto", lint="off")
    f0, t0 = off.run(STEPS)
    assert off.last_run_flight is None
    for mode in ("deliveries", "full"):
        eng = JaxEngine(sc, link, window="auto", lint="off",
                        record=mode)
        f1, t1 = eng.run(STEPS)
        assert_traces_equal(t0, t1, "off", mode)
        assert_states_equal(f0, f1, f"record={mode}")
        log = eng.last_run_flight
        assert log is not None and log.dropped == 0
        # honesty: one deliver event per delivered message
        deliv = int((log.kind == EV_DELIVER).sum())
        assert deliv == int(t1.recv_count.sum())
        # the quiet driver is record-free by contract, same emulation
        assert_states_equal(off.run_quiet(STEPS),
                            eng.run_quiet(STEPS),
                            f"run_quiet record={mode}")
    # full mode adds sends for every sent message
    assert int((log.kind == EV_SEND).sum()) \
        == int(t1.sent_count.sum())


def test_record_off_jaxpr_is_the_default_jaxpr():
    sc, link = _gossip()
    default = JaxEngine(sc, link, window="auto", lint="off")
    off = JaxEngine(sc, link, window="auto", lint="off", record="off")
    on = JaxEngine(sc, link, window="auto", lint="off",
                   record="deliveries")
    jx = [str(jax.make_jaxpr(lambda s, e=e: e._step_all(s, True))(
        e.init_state())) for e in (default, off, on)]
    # off == the knob never existed — equation for equation
    assert jx[1] == jx[0]
    # deliveries mode genuinely adds outputs (the law is not vacuous)
    assert jx[2] != jx[1]


def test_edge_engine_record_modes_bit_identical():
    sc, link = _ring()
    off = EdgeEngine(sc, link, lint="off")
    f0, t0 = off.run(STEPS)
    for mode in ("deliveries", "full"):
        eng = EdgeEngine(sc, link, lint="off", record=mode)
        f1, t1 = eng.run(STEPS)
        assert_traces_equal(t0, t1, "off", f"edge {mode}")
        assert_states_equal(f0, f1, f"edge record={mode}")
        log = eng.last_run_flight
        assert int((log.kind == EV_DELIVER).sum()) \
            == int(t1.recv_count.sum())


def test_faulted_record_modes_bit_identical_and_actions():
    sc, link, faults = _steady_faulted()
    off = JaxEngine(sc, link, lint="off", faults=faults)
    f0, t0 = off.run(60)
    eng = JaxEngine(sc, link, lint="off", faults=faults,
                    record="full", record_cap=1024)
    f1, t1 = eng.run(60)
    assert_traces_equal(t0, t1, "off", "full+faults")
    assert_states_equal(f0, f1, "faulted record")
    log = eng.last_run_flight
    assert log.dropped == 0
    from timewarp_tpu.obs.flight import (TAG_CUT, TAG_DEFER, TAG_DOWN)
    tags = set(log.tag[log.kind == EV_FAULT].tolist())
    # the schedule's three fault forms all leave provenance
    assert {TAG_DEFER, TAG_CUT, TAG_DOWN} <= tags


def test_batched_record_worlds_match_solo():
    sc, link = _gossip()
    spec = BatchSpec(seeds=(0, 1, 2))
    off = JaxEngine(sc, link, window="auto", lint="off", batch=spec)
    f0, tr0 = off.run(STEPS)
    eng = JaxEngine(sc, link, window="auto", lint="off", batch=spec,
                    record="full")
    f1, tr1 = eng.run(STEPS)
    for b in range(3):
        assert_traces_equal(tr0[b], tr1[b], "off", f"full w{b}")
    assert_states_equal(f0, f1, "batched record")
    logs = eng.last_run_flight
    assert isinstance(logs, list) and len(logs) == 3
    # batch exactness extends to the event plane: world b's log is
    # the solo run's log, event for event
    for b in (0, 2):
        solo = JaxEngine(sc, link, window="auto", lint="off", seed=b,
                         record="full")
        solo.run(STEPS)
        assert logs[b].keyset() == solo.last_run_flight.keyset(), \
            f"world {b} event plane != solo"


def test_sharded_batched_record_worlds_match_solo():
    # the fourth carrying engine (docs/engines.md matrix): the
    # [T, B_local, R] event planes gather over the world axis like
    # any trace leaf, and each world decodes to the solo run's log
    from timewarp_tpu.interp.jax_engine.sharded import (
        ShardedBatchedEngine, make_mesh)
    sc, link = _gossip()
    mesh = make_mesh(2, axis="worlds")
    spec = BatchSpec(seeds=(0, 1))
    off = ShardedBatchedEngine(sc, link, mesh, batch=spec,
                               window="auto", lint="off")
    f0, tr0 = off.run(16)
    eng = ShardedBatchedEngine(sc, link, mesh, batch=spec,
                               window="auto", lint="off",
                               record="full")
    f1, tr1 = eng.run(16)
    for b in range(2):
        assert_traces_equal(tr0[b], tr1[b], "off", f"record w{b}")
    assert_states_equal(f0, f1, "sharded-batched record")
    logs = eng.last_run_flight
    assert isinstance(logs, list) and len(logs) == 2
    for b in range(2):
        solo = JaxEngine(sc, link, window="auto", lint="off", seed=b,
                         record="full")
        solo.run(16)
        assert logs[b].keyset() == solo.last_run_flight.keyset(), \
            f"sharded world {b} event plane != solo"


def test_record_cap_overflow_counted_never_silent():
    sc, link = _gossip()
    eng = JaxEngine(sc, link, window="auto", lint="off",
                    record="full", record_cap=2)
    _, t1 = eng.run(STEPS)
    log = eng.last_run_flight
    assert log.dropped > 0                      # counted
    assert len(log) <= 2 * len(t1)              # bounded by the cap
    # the bounded log is still bit-exact emulation
    off = JaxEngine(sc, link, window="auto", lint="off")
    assert_traces_equal(off.run(STEPS)[1], t1, "off", "cap=2")


def test_record_knob_validated_loudly():
    sc, link = _gossip()
    with pytest.raises(ValueError, match="record must be one of"):
        JaxEngine(sc, link, lint="off", record="Deliveries")
    with pytest.raises(ValueError, match="record_cap"):
        JaxEngine(sc, link, lint="off", record="full", record_cap=0)


def test_verified_driver_carries_the_record_plane():
    # run_verified (integrity/runner.py) drains only VERIFIED chunks
    # and still assembles the whole-run log
    sc, link = _gossip()
    eng = JaxEngine(sc, link, window="auto", lint="off",
                    verify="digest", record="deliveries")
    _, tr = eng.run_verified(STEPS, chunk=8)
    log = eng.last_run_flight
    assert int((log.kind == EV_DELIVER).sum()) \
        == int(tr.recv_count.sum())
    assert eng.last_run_integrity["rollbacks"] == 0


# ---------------------------------------------------------------------------
# the JSONL event log (METRICS_SCHEMA v4)
# ---------------------------------------------------------------------------

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


# ---------------------------------------------------------------------------
# causal queries
# ---------------------------------------------------------------------------

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


# ---------------------------------------------------------------------------
# divergence bisection
# ---------------------------------------------------------------------------

def test_chain_bisect_units():
    from timewarp_tpu.obs.bisect import chain_bisect
    assert chain_bisect(["a", "b", "c"], ["a", "b", "c"]) is None
    assert chain_bisect(["a", "b", "c"], ["a", "x", "y"]) == 1
    assert chain_bisect(["x"], ["y"]) == 0
    # a strict prefix diverges at its end (one side kept running)
    assert chain_bisect(["a", "b"], ["a", "b", "c"]) == 2
    assert chain_bisect([], []) is None


def test_bisect_pinned_diagnostic_on_injected_flip():
    """The pinned contract (tests/test_zzdiag.py's TraceMismatch
    style, extended): an injected flip: divergence is ONE line naming
    chunk, superstep, field, and the event delta — never arrays."""
    from timewarp_tpu.integrity import FlipInjector
    from timewarp_tpu.obs.bisect import bisect_engines
    sc = gossip(N, fanout=4, burst=True, end_us=400_000,
                mailbox_cap=16)
    link = Quantize(UniformDelay(3000, 9000), 1000)

    def make(record="off"):
        return JaxEngine(sc, link, seed=0, window="auto", lint="off",
                         record=record, record_cap=4096)

    rep = bisect_engines(make, make, 60, chunk=16,
                         names=("clean", "corrupt"),
                         inject_b=lambda: FlipInjector("flip:1:2:mb_rel"),
                         basis="state")
    assert rep is not None
    line = rep.line()
    assert "\n" not in line                       # ONE line
    assert "array" not in line and "[[" not in line
    assert f"chunk {rep.chunk} " in line
    assert rep.chunk == 1                         # deterministic
    assert rep.superstep is not None
    assert f"superstep {rep.superstep}" in line
    assert "clean != corrupt" in line
    assert rep.fields                             # the field clause
    assert rep.only_a + rep.only_b > 0            # the event delta
    assert rep.first_delta and rep.first_delta in line
    # re-running the bisection is bit-deterministic
    rep2 = bisect_engines(make, make, 60, chunk=16,
                          names=("clean", "corrupt"),
                          inject_b=lambda: FlipInjector("flip:1:2:mb_rel"),
                          basis="state")
    assert rep2.line() == line


def test_bisect_identical_runs_report_none():
    from timewarp_tpu.obs.bisect import bisect_engines
    sc, link = _ring()

    def mk_gen(record="off"):
        return JaxEngine(sc, link, seed=0, lint="off", record=record)

    def mk_edge(record="off"):
        return EdgeEngine(sc, link, seed=0, lint="off", record=record)

    # engine vs engine on the ring: bit-identical, trace basis
    assert bisect_engines(mk_gen, mk_edge, 30, chunk=8,
                          basis="trace") is None


def test_first_trail_divergence_names_the_chunk():
    from timewarp_tpu.obs.bisect import first_trail_divergence
    from timewarp_tpu.sweep.spec import DIGEST_ZERO, chain_digest
    sc, link = _ring()
    eng = JaxEngine(sc, link, seed=0, lint="off")
    _, tr = eng.run(24)
    assert len(tr) >= 16

    class _Slice:
        def __init__(self, t, a, b):
            self.t, self.a, self.b = t, a, b

        def __len__(self):
            return self.b - self.a

        def row(self, i):
            return self.t.row(self.a + i)

    trail, cur = [], DIGEST_ZERO
    for hi in (8, 16, len(tr)):
        cur = chain_digest(cur, _Slice(tr, trail[-1][0] if trail
                                       else 0, hi))
        trail.append([hi, cur])
    assert first_trail_divergence(trail, tr) is None
    bad = [list(e) for e in trail]
    bad[1][1] = "f" * 64
    d = first_trail_divergence(bad, tr)
    assert d["chunk"] == 1 and d["supersteps"] == [8, 16]
    assert d["streamed"] == "f" * 64 and d["solo"] == trail[1][1]


# ---------------------------------------------------------------------------
# CLI surface
# ---------------------------------------------------------------------------

def _run_cli(argv):
    from timewarp_tpu.cli import main
    return main(argv)


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


def test_cli_bisect_names_the_chunk(capsys):
    rc = _run_cli(["bisect", "gossip", "--nodes", "32", "--steps",
                   "60", "--chunk", "16", "--burst",
                   "--link", "quantize:1000:uniform:3000:9000",
                   "--window", "auto",
                   "--inject-flip", "flip:1:2:mb_rel", "--json"])
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip())
    d = out["divergence"]
    assert d["chunk"] == 1 and d["superstep"] is not None
    assert "clean != corrupt" in d["line"]


def test_cli_bisect_refuses_nothing_to_bisect():
    with pytest.raises(SystemExit, match="nothing to bisect"):
        _run_cli(["bisect", "gossip", "--nodes", "8"])
    # --engine-b + --inject-flip is ambiguous: the cross-engine trace
    # basis cannot see a payload-plane flip (a wrong all-clear)
    with pytest.raises(SystemExit, match="mutually exclusive"):
        _run_cli(["bisect", "gossip", "--nodes", "8", "--engine-b",
                  "edge", "--inject-flip", "flip:1:1"])


# ---------------------------------------------------------------------------
# sweep-side wiring
# ---------------------------------------------------------------------------

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
