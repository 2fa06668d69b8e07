"""Every bench.py config must run end-to-end at tiny scale — the
driver executes bench.py at round end, so a rotted config means a
missing headline number. The rows are dealt over six files
(tests/bench_rows.py ``ROWS``); here the rings, the plain waves, steady
and praos, and ``bench.main``'s own contract."""

import json

import pytest

import bench
from bench_rows import ROWS, bench_config_runs


@pytest.mark.parametrize("cfg", ROWS[""])
def test_bench_config_runs(cfg, monkeypatch):
    bench_config_runs(cfg, monkeypatch)


def test_bench_main_refuses_without_a_chip(capsys, monkeypatch):
    """The measured path times a TPU or nothing: on the CPU platform
    the tests run on, ``bench.main()`` exits before any config runs
    and prints no line (a CPU or interpreter timing is never written
    as speed)."""
    monkeypatch.setenv("TW_BENCH_CONFIG", "token_ring_dense")
    monkeypatch.setenv("TW_BENCH_NODES", "256")
    monkeypatch.setattr("sys.argv", ["bench.py"])
    monkeypatch.setattr(bench, "_run_config", lambda *a: pytest.fail(
        "a config ran on a non-TPU backend"))
    with pytest.raises(SystemExit, match="refusing to time"):
        bench.main()
    assert capsys.readouterr().out == ""


def test_bench_main_prints_one_json_line(capsys, monkeypatch):
    monkeypatch.setenv("TW_BENCH_CONFIG", "token_ring_dense")
    monkeypatch.setenv("TW_BENCH_NODES", "256")
    monkeypatch.setenv("TW_BENCH_STEPS", "32")
    monkeypatch.setattr("sys.argv", ["bench.py"])
    # the line's shape is the subject here, not its numbers: steer
    # the chip refusal aside in the test (256 nodes runs the XLA ring)
    monkeypatch.setattr(bench, "_require_chip", lambda what: None)
    bench.main()
    out = capsys.readouterr().out.strip().splitlines()
    assert len(out) == 1
    row = json.loads(out[0])
    assert set(row) == {"config", "config_key", "metric", "value",
                        "unit", "vs_baseline", "schema", "platform",
                        "device_kind", "jax_version", "git_sha",
                        "calib"}
    assert row["unit"] == "msg/s"
    # environment provenance (ISSUE 7 satellite): the artifact line
    # itself says where it ran, so CPU-only rounds are visible
    assert row["schema"] == bench.BENCH_SCHEMA
    assert row["platform"] == "cpu"   # conftest pins the platform
    assert isinstance(row["device_kind"], str) and row["device_kind"]
    assert isinstance(row["jax_version"], str) and row["jax_version"]
    # cross-run join provenance (BENCH_SCHEMA v2, ISSUE 13): the
    # stable config_key (name + requested shape + platform) and the
    # producing commit, so the run ledger joins unambiguously
    assert row["config"] == "token_ring_dense"
    assert row["config_key"] == "token_ring_dense|n256|s32|cpu"
    assert isinstance(row["git_sha"], str) and row["git_sha"]
    # the self-calibration fingerprint: frozen kernel, positive timing
    assert row["calib"]["kernel"] == "sort_1m_int32_x64"
    assert row["calib"]["seconds"] > 0
