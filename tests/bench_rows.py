"""Every bench.py config must run end-to-end at tiny scale — the
driver executes bench.py at round end, so a rotted config means a
missing headline number.

No test lives here: ``bench_config_runs`` is one row's run and what its
line must carry, and ``ROWS`` deals the rows to the test files that
call it (tests/test_bench_configs.py and its ``_planes``, ``_gates``,
``_sweeps``, ``_services``, ``_serve``): a row compiles the engines of
its config, the heaviest take a worker over a minute, and a file is one
worker's.
"""

import bench

#: test file (tests/test_bench_configs<key>.py) -> its rows
ROWS = {
    "": ["token_ring_dense", "token_ring_dense_xla", "token_ring_observer",
         "gossip_100k", "gossip_100k_b8", "gossip_steady_1m", "praos_1m",
         "praos_1m_b4"],
    "_planes": ["gossip_100k_auto", "gossip_100k_chaos", "gossip_100k_spec"],
    "_gates": ["gossip_100k_record", "gossip_100k_verify"],
    "_sweeps": ["sweep_hetero", "sweep_hetero_auto"],
    "_services": ["search_gossip", "lint_sweep"],
    "_serve": ["serve_gossip"],
}
# a config that bench.py gains is dealt to a file here, or no file
# collects
assert sorted(sum(ROWS.values(), [])) == sorted(bench.CONFIGS)


def bench_config_runs(cfg, monkeypatch):
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
    # the gossip waves run to quiescence and assert they got there
    # (the longest, gossip_100k_spec's conservative twin at its 500 us
    # window, in 436 supersteps, and its traced scan runs the budget
    # out);
    # the sweep-service configs take per-world budgets, not a window;
    # the search config's steps are a per-evaluation budget
    steps = 2_000 if cfg.startswith("gossip_100k") else \
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
