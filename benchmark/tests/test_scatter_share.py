"""``hub_scatter_lane_share`` (PR 43): the reader over hand-made
records, over a program that does not count its scatters' lanes (the
parent of PR 43), and over the toy observer ring's own record on
XLA:CPU. Counts only: nothing here is a device number."""

import pytest

import record_reduce
import run
import toy_observer
from layer_metrics import hub_scatter_lane_share as reader


def _rec(scatter, rung, max_out=2):
    counts = {"rung_lanes": rung}
    if scatter is not None:
        counts["scatter_lanes"] = scatter
    rec = {"counts": counts, "spans": ()}
    if max_out:
        rec["max_out"] = max_out
    return rec


def test_the_share_is_scatter_lanes_over_the_rungs_lanes():
    # a job of the cell: 32 cycles of 16 384 + 65 536 + 2 048 lanes
    # scattered where the rungs hold 2 x (65 536 + 65 536 + 1 024)
    job = _rec(2_686_976, 4_227_072)
    assert reader.share([job, job]) == pytest.approx(31.7829, abs=1e-4)
    assert reader.share([_rec(8_454_144, 4_227_072)]) == 100.0


@pytest.mark.parametrize("records", [
    [_rec(None, 4_227_072)],                       # no counter: the parent
    [_rec(10, 20), _rec(None, 20)],                # one call without it
    [_rec(10, 20, max_out=None)],                  # a record without slots
    [{"counts": {"scatter_lanes": 3}, "max_out": 2}],
    []], ids=["parent", "mixed", "no-max-out", "no-rungs", "none"])
def test_nothing_to_read_is_none_and_never_raises(records):
    assert reader.share(records) is None


def test_read_pairs_the_traced_calls_and_finds_nothing_without_a_pairing(
        monkeypatch):
    recs = [{"counts": {}, "spans": (("tw.sweep.bucket", 0, 1, None, {}),)}] \
        + [{**_rec(30 * (i + 1), 50), "spans": (
            ("tw.dispatch", 10 * i, 10 * i + 1, None, {}),
            ("tw.wait", 10 * i + 2, 10 * i + 3, None, {}))}
           for i in range(4)]
    monkeypatch.setattr(record_reduce, "records", lambda: recs)
    # the traced window holds the second and third driver calls
    monkeypatch.setattr(record_reduce, "of_trace",
                        lambda trace: {"shift": 1, "paired": 2})
    assert reader.read(object(), {}) == pytest.approx(
        100.0 * (60 + 90) / (2 * 100))
    monkeypatch.setattr(record_reduce, "of_trace", lambda trace: None)
    assert reader.read(object(), {}) is None
    monkeypatch.setattr(record_reduce, "records", lambda: None)
    assert reader.read(object(), {}) is None


def test_the_toy_rings_own_record_reads_its_three_widths(tmp_path):
    name = toy_observer.observer(tmp_path)
    cell, *_ = run.prepare(name, on_chip=False, extra_dir=str(tmp_path))
    assert not cell.set_up(11)["failed"]
    rec = record_reduce.records()[-1]
    assert rec["max_out"] == 2 and rec["n_nodes"] == 257
    # 257 nodes: one rung of 257 senders, 514 lanes, under the
    # program's threshold: every superstep scatters the rung whole
    assert rec["counts"]["scatter_lanes"] == 96 * 514
    assert reader.share([rec]) == 100.0


def test_the_committed_entry_names_the_cell_and_its_layer():
    names = dict(run.metrics_of("ring_64k.observer", "per_layer"))
    assert names["hub_scatter_lane_share"] == "%"
    assert "hub_scatter_lane_share" not in dict(
        run.metrics_of("gossip_100k.wave", "per_layer"))
