"""``stage_tail_lane_share`` (PR 44): the reader over hand-made
records, over a program that does not count its staging's tail (the
parent of PR 44), and over the toy steady cell's own record on
XLA:CPU. Counts only: nothing here is a device number."""

import pytest

import record_reduce
import run
import toy_steady
from layer_metrics import stage_tail_lane_share as reader


def _rec(tail, dense):
    counts = {"rung_lanes": 16 << 20}
    if tail is not None:
        counts.update(tail_lanes=tail, dense_lanes=dense)
    return {"counts": counts, "spans": ()}


def test_the_share_is_tail_lanes_over_the_dense_lanes():
    # a job of the steady cell: 16 supersteps of 2^20 lanes, the tail
    # at an eighth; PR 36's form at a half
    job = _rec(16 << 17, 16 << 20)
    assert reader.share([job, job]) == 12.5
    assert reader.share([_rec(16 << 19, 16 << 20)]) == 50.0
    assert reader.share([job, _rec(0, 0)]) == 12.5


@pytest.mark.parametrize("records", [
    [_rec(None, None)],                            # no counter: the parent
    [_rec(10, 20), _rec(None, None)],              # one call without it
    [{"counts": {"tail_lanes": 3}}],
    [_rec(0, 0)],                                  # nothing staged densely
    []], ids=["parent", "mixed", "no-dense-lanes", "never-dense", "none"])
def test_nothing_to_read_is_none_and_never_raises(records):
    assert reader.share(records) is None


def test_read_pairs_the_traced_calls_and_finds_nothing_without_a_pairing(
        monkeypatch):
    recs = [{"counts": {}, "spans": (("tw.sweep.bucket", 0, 1, None, {}),)}] \
        + [{**_rec(10 * (i + 1), 100), "spans": (
            ("tw.dispatch", 10 * i, 10 * i + 1, None, {}),
            ("tw.wait", 10 * i + 2, 10 * i + 3, None, {}))}
           for i in range(4)]
    monkeypatch.setattr(record_reduce, "records", lambda: recs)
    # the traced window holds the second and third driver calls
    monkeypatch.setattr(record_reduce, "of_trace",
                        lambda trace: {"shift": 1, "paired": 2})
    assert reader.read(object(), {}) == pytest.approx(
        100.0 * (20 + 30) / 200)
    monkeypatch.setattr(record_reduce, "of_trace", lambda trace: None)
    assert reader.read(object(), {}) is None
    monkeypatch.setattr(record_reduce, "records", lambda: None)
    assert reader.read(object(), {}) is None


def test_the_toy_cells_own_record_reads_the_form_under_the_lane_count(
        tmp_path):
    name = toy_steady.rounds(tmp_path)
    cell, *_ = run.prepare(name, on_chip=False, extra_dir=str(tmp_path))
    assert not cell.set_up(11)["failed"]
    counts = record_reduce.records()[-1]["counts"]
    # 4096 lanes for 4096 nodes, under the program's 2^15: PR 36's
    # form, one row and the tail at half the lanes, every superstep
    steps = counts["supersteps"]
    assert counts["dense_stage_steps"] == steps
    assert (counts["dense_lanes"], counts["tail_lanes"],
            counts["net_rows"]) == (steps * 4096, steps * 2048, steps)
    assert reader.share([record_reduce.records()[-1]]) == 50.0


def test_the_committed_entry_names_its_three_cells_and_its_layer():
    for cell in ("gossip_steady_1m.rounds", "gossip_100k.wave",
                 "praos_1m.slots"):
        assert dict(run.metrics_of(cell, "per_layer"))[
            "stage_tail_lane_share"] == "%"
    for cell in ("gossip_100k.fleet8", "ring_64k.observer", "ring_1m.dense"):
        assert "stage_tail_lane_share" not in dict(
            run.metrics_of(cell, "per_layer"))
