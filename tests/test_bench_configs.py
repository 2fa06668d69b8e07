"""Every bench.py config must run end-to-end at tiny scale — the
driver executes bench.py at round end, so a rotted config means a
missing headline number."""

import json

import pytest

import bench


@pytest.mark.parametrize("cfg", sorted(bench.CONFIGS))
def test_bench_config_runs(cfg, monkeypatch):
    # the --smoke path: gates on, the ring kernel under the Pallas
    # interpreter, rates discarded — the measured path refuses to
    # time anything but a TPU (test_bench_main_refuses_without_a_chip)
    monkeypatch.setattr(bench, "_SMOKE", True)
    n = {"token_ring_dense": 512, "token_ring_dense_xla": 512,
         "token_ring_observer": 256,
         "gossip_100k": 512,
         "gossip_100k_b8": 512, "gossip_100k_chaos": 512,
         "gossip_100k_auto": 512, "gossip_100k_spec": 512,
         "gossip_100k_verify": 512,
         "gossip_100k_record": 512,
         "gossip_steady_1m": 512,
         "praos_1m": 512,
         "praos_1m_b4": 512, "sweep_hetero": 256,
         "sweep_hetero_auto": 256, "search_gossip": 64,
         "serve_gossip": 256, "lint_sweep": 64}[cfg]
    # the gossip waves run to quiescence and assert they got there;
    # the sweep-service configs take per-world budgets, not a window;
    # the search config's steps are a per-evaluation budget
    steps = 20_000 if cfg.startswith("gossip_100k") else \
        96 if cfg.startswith("sweep_hetero") else \
        300 if cfg == "search_gossip" else \
        96 if cfg == "serve_gossip" else 48
    metric, rate, extra = bench._run_config(cfg, n, steps)
    assert rate > 0
    assert str(n) in metric
    if cfg == "gossip_100k_chaos":
        # the chaos config's never-silent world-axis counters ride
        # the JSON line: every world's schedule must actually bite
        assert all(v > 0 for v in extra["fault_dropped"])
        assert all(v == 0 for v in extra["route_drop"])
    if cfg == "gossip_100k_spec":
        # the optimistic-execution win gate (speculate/): a real
        # superstep gain over the conservative floor AND an honest
        # misspeculation ledger on the line (satellite 6 + the
        # in-bench equivalence gate ran inside the config itself)
        assert extra["speculation_gain_frac"] > 0
        assert extra["supersteps_spec"] \
            < extra["supersteps_conservative"]
        assert 0.0 <= extra["rollback_rate"] <= 1.0
        assert extra["rollbacks"] >= 0
    if cfg == "serve_gossip":
        # the serving-layer config's in-bench extended-survival-law
        # AND zero-recompile gates already ran; the line must carry
        # the honest latency/admission numbers plus the build/compile
        # counters — ONE 8-slot bucket, ONE engine build across every
        # mid-bucket admission (identity rides as traced operands)
        assert extra["worlds"] == 8
        assert extra["buckets"] == 1
        assert extra["engine_builds"] == 1
        assert extra["compiles"] >= 0
        assert extra["admit_per_s"] > 0
        assert 0 <= extra["submit_p50_s"] <= extra["submit_p95_s"]
        assert extra["delivered_per_s"] > 0
    if cfg == "search_gossip":
        # the chaos-search config's three in-bench gates already ran
        # (found + repro re-fail + fork saving); the line must carry
        # the honest numbers
        assert extra["found"] is True
        assert extra["fork_saving_frac"] > 0
        assert extra["minimized"] and extra["minimized_events"] >= 1
        assert extra["evaluations"] > 0
    if cfg == "lint_sweep":
        # the static pre-flight verification config: all three pass
        # families actually swept (subjects counted, never zero), the
        # doomed refusal corpus stayed refused (the in-config gate
        # already asserted it), and the per-surface splits are honest
        assert extra["lint_subjects"] > 0
        assert extra["jaxpr_subjects"] > 0
        assert extra["pack_files"] >= 2
        assert extra["pack_configs"] > extra["pack_files"]
        assert all(extra[k] >= 0 for k in
                   ("sanitizer_s", "plan_s", "jaxpr_s"))
    if cfg == "gossip_100k_record":
        # the flight-recorder config reports honest per-mode numbers
        # (obs/flight.py): both modes measured, events recorded, and
        # drops — if any — counted, never silent
        assert set(extra["record_overhead_frac"]) \
            == {"deliveries", "full"}
        assert extra["record_events"]["deliveries"]["events"] > 0
        assert extra["record_events"]["full"]["events"] \
            > extra["record_events"]["deliveries"]["events"]


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
