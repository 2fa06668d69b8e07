"""Praos at the headline size as a deployment (ISSUE 33): the general
engine as ``praos --burst`` builds it (``window="auto"``, the adaptive
ladder) takes a world from genesis through two slots to quiescence and
equals the benchmark's plain reference node for node and count for
count, whatever the genesis chain length; the cap that holds every tip
holds it and a smaller one overflows; the firing entropy has a scope
of its own (``tw.fire/entropy``), which is a name and nothing else:
the praos, steady, wave and fleet drivers lower to the text they had.
Here: what runs a compiled cell. What needs none (the lowered texts
among it) is tests/test_praos_lowering.py.

(Named test_zz* to sort after the whole existing suite.)
"""

import pytest

from praos_laws import _cell, _nested_scopes    # puts benchmark/ on the path
from reference import praos_ref


@pytest.fixture(scope="module")
def cells():
    made = {}
    return lambda n: made.get(n) or made.setdefault(n, _cell(n))


@pytest.mark.parametrize("n, seed", [
    (2048, 1), (2048, 3_300_000_001), (8192, 7), (8192, 2**31 + 5)])
def test_two_slots_from_a_seeded_genesis_equal_the_reference(cells, n, seed):
    c = cells(n)
    assert not c.set_up(seed)["failed"]
    jobs = [c.job(i) for i in (1, 2)]
    assert not any(j["failed"] for j in jobs), jobs
    assert len({h0 for h0, _, _ in c.runs}) == 2
    rows = c.compare(praos_ref)
    assert len(rows) == 7
    *exact, (name, largest, cap) = rows
    assert [v for _, v, _ in exact] == [0] * 6, rows
    assert [limit for _, _, limit in exact] == [0] * 6
    assert name.startswith("reference.largest") and largest + 2 <= cap == 24


def test_the_genesis_length_moves_every_result_and_no_count(cells):
    c = cells(2048)
    c.set_up(11)
    first, second = c.job(1), c.job(2)
    assert first == second and not first["failed"]
    (h0, a, fa), (h1, b, fb) = c.runs
    assert h0 != h1 and fa == fb
    assert fa["supersteps"] == first["supersteps"]
    assert (a["best"] - h0 == b["best"] - h1).all()
    assert (a["best"] != b["best"]).all()
    # and the second set-up compiled nothing new
    c.set_up(3_000_000_019)
    assert c.engine.last_run_stats["compiles"] == 0


@pytest.mark.parametrize("n", [2048, 8192])
def test_both_controls_fail_the_comparison(cells, n):
    c = cells(n)
    c.set_up(4_100_000_007)
    assert not c.job(1)["failed"]
    sound = c.compare(praos_ref)
    assert all(v <= limit for _, v, limit in sound), sound
    rows = {name: v for name, v, _ in c.control(praos_ref)}
    assert {name.partition(".")[0] for name in rows} == {
        "low_precision", "small_mailbox"}
    # bfloat16 moves arrivals by whole quanta: when the last push lands
    # and how many windows the instants fill. What a node ends with
    # does not move: it floods once a slot whenever the tip comes
    assert (rows["low_precision.job.supersteps.jobs_that_differ"]
            + rows["low_precision.job.time.jobs_that_differ"]) >= 1
    assert rows["low_precision.job.lcg.nodes_that_differ"] == 0
    assert rows["small_mailbox.job.overflow"] > 0
    assert rows["small_mailbox.job.delivered.jobs_that_differ"] == 1


def test_a_control_that_passes_is_returned_alone(cells, monkeypatch):
    c = cells(2048)
    c.set_up(5)
    c.job(1)
    # a "control" that is the configuration itself passes, and must
    # not hide behind the other
    monkeypatch.setitem(c.control_of, "mailbox_cap", 24)
    rows = c.control(praos_ref)
    assert all(name.startswith("job.") for name, _, _ in rows)
    assert all(v <= limit for _, v, limit in rows)


def test_the_firing_entropy_has_a_scope_in_a_praos_program(cells):
    eng = cells(2048).engine
    assert eng.scenario.needs_key and eng._adaptive_regime()
    nested = _nested_scopes(eng)
    assert "tw.fire/entropy" in nested
    assert {"tw.route/insert", "tw.route/sample"} <= nested
