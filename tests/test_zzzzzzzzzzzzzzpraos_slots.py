"""Praos at the headline size as a deployment (ISSUE 33): the general
engine as ``praos --burst`` builds it (``window="auto"``, the adaptive
ladder) takes a world from genesis through two slots to quiescence and
equals the benchmark's plain reference node for node and count for
count, whatever the genesis chain length; the cap that holds every tip
holds it and a smaller one overflows; the firing entropy has a scope
of its own (``tw.fire/entropy``), which is a name and nothing else:
the praos, steady, wave and fleet drivers lower to the text they had.

(Named test_zz* to sort after the whole existing suite.)
"""

import hashlib
import json
import os
import re
import sys

import numpy as np
import pytest

from timewarp_tpu.interp.jax_engine.batched import BatchSpec
from timewarp_tpu.interp.jax_engine.engine import JaxEngine

BENCHMARK = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCHMARK not in sys.path:
    sys.path.insert(0, BENCHMARK)

import fleet_reduce  # noqa: E402
import span_reduce  # noqa: E402
from builders import gossip_steady, gossip_wave, praos_slots  # noqa: E402
from reference import praos_ref  # noqa: E402


def _load(kind, name):
    with open(os.path.join(BENCHMARK, kind, name + ".json")) as f:
        return json.load(f)


def _cell(n, control_cap=8, mailbox_cap=24):
    traffic = _load("workloads", "praos_1m.slots")
    config = _load("configs", traffic["config"])
    config["params"].update(n_nodes=n, mailbox_cap=mailbox_cap)
    # the committed cell runs one slot a job (two take 2.8 s on the
    # chip); here two, so that a chain grows over one it already has
    traffic["slots_per_job"] = 2
    # 16 slots hold every tip at these sizes (15 and 16 in flight)
    config["control"]["mailbox_cap"] = control_cap
    return praos_slots.Cell(config, traffic)


@pytest.fixture(scope="module")
def cells():
    made = {}
    return lambda n: made.get(n) or made.setdefault(n, _cell(n))


def _params(n):
    return {**_load("configs", "praos_1m")["params"], "n_nodes": n,
            "n_slots": 2}


def _lowered(eng, **kw):
    return type(eng)._run_while.lower(
        eng, eng.init_state(), eng._coerce_budget(8)[0],
        eng._identity()).as_text(**kw)


def _nested_scopes(eng) -> set:
    names = re.findall(r'loc\("(jit\(_run_while\)[^"]*)"',
                       _lowered(eng, debug_info=True))
    return {span_reduce.stage_of(fleet_reduce.unwrap(n), 2) for n in names}


# -- the program against the plain reference ----------------------------------

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


def test_the_reference_is_plain_and_shifts_with_the_genesis_length():
    with open(praos_ref.__file__) as f:
        source = f.read()
    assert "import timewarp_tpu" not in source
    assert "from timewarp_tpu" not in source
    chain = praos_ref.Chain(_params(2048))
    zero, high = chain.run(0), chain.run(2**30 - 1)
    assert (high["best"] - (2**30 - 1) == zero["best"]).all()
    for f in ("slot", "lcg"):
        assert (high[f] == zero[f]).all()
    for f in ("delivered", "supersteps", "time", "minted",
              "largest_in_flight"):
        assert high[f] == zero[f]
    assert zero["minted"] == [2, 1] and zero["best"].max() == 2
    assert (zero["slot"] == 2).all()
    # every flood is fanout pushes less the repeated draws
    assert 7 * 2 * 2048 < zero["delivered"] <= 8 * (2 * 2048 + 3)
    assert zero["time"] > 2_000_000 and zero["largest_in_flight"] == 15


# -- the cap, and the controls ---------------------------------------------------

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


def test_a_mailbox_too_small_fails_the_jobs_gates():
    assert "overflow=" in _cell(2048, mailbox_cap=8).set_up(5)["failed"]


def test_bench_pys_row_has_the_cap_that_holds_every_tip():
    root = os.path.dirname(BENCHMARK)
    if root not in sys.path:
        sys.path.insert(0, root)
    import bench
    sc, link = bench._praos_consensus(2048)
    p = _load("configs", "praos_1m")["params"]
    assert sc.mailbox_cap == p["mailbox_cap"] == 24
    assert sc.max_out == p["fanout"] == 8 and sc.needs_key
    assert link.min_delay_us == p["link"]["floor_us"] == 8000


# -- the scope, and the programs it does not reach ----------------------------

def test_the_firing_entropy_has_a_scope_in_a_praos_program(cells):
    eng = cells(2048).engine
    assert eng.scenario.needs_key and eng._adaptive_regime()
    nested = _nested_scopes(eng)
    assert "tw.fire/entropy" in nested
    assert {"tw.route/insert", "tw.route/sample"} <= nested


def _wave_engine(n, **kw):
    p = _load("configs", "gossip_100k")["params"]
    sc, link = gossip_wave.scenario_and_link({**p, "n_nodes": n})
    return JaxEngine(sc, link, window="auto", insert="xla", **kw)


def _steady_engine(n):
    p = _load("configs", "gossip_steady_1m")["params"]
    return gossip_steady.engine_of({**p, "n_nodes": n})


@pytest.mark.parametrize("make", [
    lambda: _wave_engine(1024, seed=0),
    lambda: _wave_engine(1024, batch=BatchSpec(seeds=(0, 1))),
    lambda: _steady_engine(1024)], ids=["wave", "fleet", "steady"])
def test_a_scenario_without_a_key_has_no_entropy_scope(make):
    eng = make()
    assert not eng.scenario.needs_key
    assert "tw.fire/entropy" not in _nested_scopes(eng)


#: sha256 of the quiet driver's lowering (``as_text()``: no names, no
#: locations) at 2^11 nodes, as PR 36 lowers it (PR 34's loop, which
#: carries its successor's event horizon, ``tests/test_loop_edge.py``,
#: with the routing stage's five counts in its carry:
#: ``tests/test_zzzzzzzzzzzzzzzrecord.py``; the arrivals staged in
#: the dense form, steady's on every superstep, praos' in both rungs:
#: ``tests/test_insert_law.py``). Until then they were PR 35's
#: (steady 019784a05692…, praos 23c5c22aee01…). PR 44 changed both
#: (they were 56417b93abea… and 02f9e0df7c22…): the carry of a solo
#: engine that stages by rank holds three counts more
#: (``dense_lanes``, ``tail_lanes``, ``net_rows``); the staging
#: itself is PR 36's text at these widths (under
#: ``_TAIL_LADDER_LANES``: ``tests/test_stage_tail_law.py``). The wave's and the
#: fleet's are pinned in ``test_zzzzzzzzzzzzzsteady_mongering.py``. A
#: PR that changes what these drivers compute changes the constants,
#: and says so.
_PARENT_LOWERING = {
    "steady": "2cf72c06d42d1eb8125ab0d9dec3910692af50485db9ce4a878fd040f5866712",
    "praos": "8aa4cb7fa8dca73defc011f7891d702d4dd469eaeb590fc414d1269549c3efb9",
}


@pytest.mark.parametrize("key, make", [
    ("steady", lambda: _steady_engine(1 << 11)),
    ("praos", lambda: praos_slots.engine_of(
        {**_load("configs", "praos_1m")["params"], "n_nodes": 1 << 11}, 2))],
    ids=["steady", "praos"])
def test_the_drivers_lower_to_the_parents_text(key, make):
    text = _lowered(make())
    assert hashlib.sha256(text.encode()).hexdigest() == _PARENT_LOWERING[key]


def test_the_ladder_has_eleven_rungs_at_the_timed_size():
    rungs = list(JaxEngine._sender_rungs(1 << 20))
    assert len(rungs) == 11 and rungs[0] == 1024 and rungs[-1] == 1 << 20
    assert len(list(JaxEngine._sender_rungs(1 << 17))) == 8
