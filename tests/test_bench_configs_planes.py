"""bench.py's configs at tiny scale: the controller, chaos and speculation rows
(tests/bench_rows.py has the run and what each line must carry)."""

import pytest

from bench_rows import ROWS, bench_config_runs


@pytest.mark.parametrize("cfg", ROWS["_planes"])
def test_bench_config_runs(cfg, monkeypatch):
    bench_config_runs(cfg, monkeypatch)
