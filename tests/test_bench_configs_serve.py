"""bench.py's configs at tiny scale: the serving layer's row, the
longest of the eighteen (tests/bench_rows.py has the run and what each
line must carry)."""

import pytest

from bench_rows import ROWS, bench_config_runs


@pytest.mark.parametrize("cfg", ROWS["_serve"])
def test_bench_config_runs(cfg, monkeypatch):
    bench_config_runs(cfg, monkeypatch)
