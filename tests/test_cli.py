"""Scenario-runner CLI (python -m timewarp_tpu): every engine/scenario
combination the flags advertise, link-spec parsing, trace CSV export,
and checkpoint save/resume with seed adoption."""

import csv
import json

import pytest

from timewarp_tpu.cli import main, parse_link
from timewarp_tpu.net.delays import (FixedDelay, LogNormalDelay, Quantize,
                                     UniformDelay, WithDrop)


def run_cli(capsys, *args):
    assert main(list(args)) == 0
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


def test_parse_link_specs():
    assert parse_link("fixed:500") == FixedDelay(500)
    assert parse_link("uniform:100:900") == UniformDelay(100, 900)
    assert parse_link("lognormal:20000:0.6") == LogNormalDelay(20000, 0.6)
    assert parse_link("drop:0.1:fixed:500") == WithDrop(FixedDelay(500), 0.1)
    q = parse_link("quantize:1000:drop:0.2:uniform:1:9")
    assert q == Quantize(WithDrop(UniformDelay(1, 9), 0.2), 1000)
    with pytest.raises(SystemExit):
        parse_link("bogus:1")


def test_cli_oracle_and_engines_agree(capsys):
    common = ["token-ring", "--nodes", "32", "--steps", "200",
              "--tokens", "4", "--think-us", "10000",
              "--link", "uniform:1000:5000"]
    rows = {eng: run_cli(capsys, *common, "--engine", eng)
            for eng in ("oracle", "general", "edge")}
    assert (rows["oracle"]["delivered"] == rows["general"]["delivered"]
            == rows["edge"]["delivered"])
    assert rows["general"]["supersteps"] == rows["edge"]["supersteps"]


def test_cli_windowed_burst_oracle_engine_agree(capsys):
    common = ["gossip", "--nodes", "48", "--burst", "--fanout", "4",
              "--window", "2000",
              "--link", "quantize:1000:uniform:2000:8000",
              "--steps", "300", "--end-us", "300000"]
    rows = {
        "oracle": run_cli(capsys, *common, "--engine", "oracle"),
        "general": run_cli(capsys, *common, "--engine", "general"),
    }
    assert rows["oracle"]["delivered"] == rows["general"]["delivered"]
    assert rows["oracle"]["supersteps"] == rows["general"]["supersteps"]


def test_cli_rejects_ignored_knobs():
    import pytest

    from timewarp_tpu.cli import main
    with pytest.raises(SystemExit, match="general engines only"):
        main(["token-ring", "--engine", "edge", "--window", "3000"])


def test_cli_sharded_engines(capsys):
    r = run_cli(capsys, "gossip", "--nodes", "64", "--engine", "sharded",
                "--devices", "8", "--steps", "150",
                "--link", "uniform:1000:5000", "--end-us", "300000")
    assert r["engine"] == "sharded" and r["delivered"] > 0
    r2 = run_cli(capsys, "token-ring", "--nodes", "64",
                 "--engine", "sharded-edge", "--devices", "8",
                 "--steps", "100", "--tokens", "8",
                 "--think-us", "5000")
    assert r2["engine"] == "sharded-edge" and r2["delivered"] > 0


def test_cli_trace_csv_and_checkpoint_roundtrip(tmp_path, capsys):
    csv_path = tmp_path / "t.csv"
    ck = tmp_path / "ck.npz"
    r1 = run_cli(capsys, "praos", "--nodes", "32", "--steps", "150",
                 "--slots", "2", "--seed", "5",
                 "--link", "uniform:2000:9000",
                 "--trace-csv", str(csv_path), "--save", str(ck))
    with open(csv_path) as f:
        rows = list(csv.reader(f))
    assert rows[0][0] == "t_us" and len(rows) - 1 == r1["supersteps"]
    # resume adopts the checkpoint's seed (no --seed passed here):
    # splitting a seed-5 run at the checkpoint must compose to exactly
    # the uninterrupted seed-5 run — a regression to the default seed 0
    # would diverge the RNG stream and break the composition
    r2 = run_cli(capsys, "praos", "--nodes", "32", "--steps", "100",
                 "--slots", "2", "--link", "uniform:2000:9000",
                 "--resume", str(ck))
    assert r2["steps"] == r1["steps"] + r2["supersteps"]
    r_full = run_cli(capsys, "praos", "--nodes", "32", "--steps", "250",
                     "--slots", "2", "--seed", "5",
                     "--link", "uniform:2000:9000")
    assert r1["supersteps"] + r2["supersteps"] == r_full["supersteps"]
    assert r1["delivered"] + r2["delivered"] == r_full["delivered"]


def test_cli_oracle_rejects_checkpoint_flags(tmp_path):
    with pytest.raises(SystemExit):
        main(["token-ring", "--engine", "oracle",
              "--save", str(tmp_path / "x.npz")])


def test_cli_batched_run_and_guards(capsys, tmp_path):
    """--batch/--seeds: a 2-world fleet on the general engine reports
    per-world counters; engines without the world axis reject the
    flags with an actionable error (same never-silent guard style as
    the other engine-compat checks)."""
    common = ["gossip", "--nodes", "48", "--steps", "120", "--burst",
              "--fanout", "4", "--end-us", "200000",
              "--link", "quantize:1000:uniform:2000:8000"]
    r = run_cli(capsys, *common, "--batch", "2")
    assert r["worlds"] == 2 and r["seeds"] == [0, 1]
    assert len(r["delivered"]) == 2 and len(r["supersteps"]) == 2
    # --seeds a:b names the worlds; world seeds must match solo runs
    r2 = run_cli(capsys, *common, "--seeds", "7:9")
    assert r2["seeds"] == [7, 8]
    solo = run_cli(capsys, *common, "--seed", "7")
    assert r2["delivered"][0] == solo["delivered"]
    assert r2["supersteps"][0] == solo["supersteps"]
    # batched trace CSV carries the world column
    csv_path = tmp_path / "fleet.csv"
    r3 = run_cli(capsys, *common, "--batch", "2",
                 "--trace-csv", str(csv_path))
    with open(csv_path) as f:
        rows = list(csv.reader(f))
    assert rows[0][0] == "world"
    assert len(rows) - 1 == sum(r3["supersteps"])
    # world-axis guards: actionable, never silent
    for eng in ("oracle", "edge", "sharded"):
        with pytest.raises(SystemExit, match="world axis"):
            main([*common, "--engine", eng, "--batch", "2"])
    with pytest.raises(SystemExit, match="world axis"):
        main([*common, "--engine", "edge", "--seeds", "0:2"])
    with pytest.raises(SystemExit, match="needs --batch"):
        main([*common, "--engine", "sharded-batched"])
    with pytest.raises(SystemExit, match="solo-run debug ring"):
        main([*common, "--batch", "2", "--record-events", "16"])
    with pytest.raises(SystemExit, match="disagrees"):
        main([*common, "--batch", "3", "--seeds", "0:2"])


def test_cli_sharded_batched_matches_general_batched(capsys):
    common = ["token-ring", "--nodes", "32", "--steps", "100",
              "--tokens", "4", "--think-us", "10000",
              "--link", "uniform:1000:5000", "--seeds", "1:5"]
    loc = run_cli(capsys, *common)  # general engine carries the fleet
    sh = run_cli(capsys, *common, "--engine", "sharded-batched",
                 "--devices", "4")
    assert sh["engine"] == "sharded-batched"
    assert sh["delivered"] == loc["delivered"]
    assert sh["supersteps"] == loc["supersteps"]
    assert sh["virtual_time_us"] == loc["virtual_time_us"]


def test_cli_batched_checkpoint_seed_fleet_pinned(capsys, tmp_path):
    """A fleet checkpoint resumes only under ITS seed fleet — silently
    adopting different worlds would diverge every RNG stream."""
    ck = tmp_path / "fleet.npz"
    common = ["token-ring", "--nodes", "32", "--steps", "80",
              "--tokens", "4", "--think-us", "10000",
              "--link", "uniform:1000:5000"]
    run_cli(capsys, *common, "--seeds", "3:5", "--save", str(ck))
    with pytest.raises(SystemExit, match="matching --batch/--seeds"):
        main([*common, "--seeds", "0:2", "--resume", str(ck)])
    r = run_cli(capsys, *common, "--seeds", "3:5", "--resume", str(ck))
    assert r["seeds"] == [3, 4]


def test_parse_link_malformed_specs_name_the_grammar():
    # these used to die with a raw IndexError / ValueError
    for bad in ("uniform:5", "fixed:x", "lognormal:1000",
                "drop:0.1", "quantize:5", "fixed:1:2",
                "uniform:1:2:3", "drop:x:fixed:5"):
        with pytest.raises(SystemExit) as ei:
            parse_link(bad)
        assert "grammar" in str(ei.value), bad
    with pytest.raises(SystemExit) as ei:
        parse_link("bogus:1")
    assert "grammar" in str(ei.value)
    # a malformed INNER spec of a wrapper also names the grammar
    with pytest.raises(SystemExit) as ei:
        parse_link("drop:0.5:uniform:7")
    assert "grammar" in str(ei.value)


def test_cli_lint_subcommand_all_models_clean(capsys):
    # the CI gate: every shipped model + program twin, zero errors
    assert main(["lint", "--json", "--nodes", "32", "--no-probe"]) == 0
    rep = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rep["errors"] == 0
    assert rep["subjects"] >= 14


def test_cli_lint_subcommand_family_filter_with_probe(capsys):
    assert main(["lint", "gossip", "--json", "--nodes", "32"]) == 0
    rep = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rep["errors"] == 0 and rep["subjects"] == 4


def test_cli_lint_subcommand_rejects_unknown_family():
    with pytest.raises(SystemExit):
        main(["lint", "no-such-scenario"])


def test_cli_lint_flag_modes_run_identically(capsys):
    common = ["token-ring", "--nodes", "16", "--steps", "80",
              "--think-us", "10000", "--link", "fixed:2000"]
    base = run_cli(capsys, *common)
    for mode in ("warn", "error", "off"):
        r = run_cli(capsys, *common, "--lint", mode)
        assert r == base        # lint never changes run behavior
