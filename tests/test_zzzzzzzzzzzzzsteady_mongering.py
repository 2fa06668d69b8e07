"""Steady rumor mongering as a deployment (ISSUE 31): the general engine
as ``JaxEngine(sc, link)`` builds it for one outbox slot takes the eager
routing path, whose one variadic sort now has a scope of its own
(``tw.route/sort``); streamed in jobs through ``run_quiet`` it equals the
benchmark's plain reference number for number, loses nothing with 24
mailbox slots and overflows with the source's 8; and the adaptive
drivers (the wave's, the fleet's) lower to the text they had.

(Named test_zz* to sort after the whole existing suite.)
"""

import hashlib
import json
import os
import re
import sys

import pytest

import jax.numpy as jnp

from timewarp_tpu.interp.jax_engine.batched import BatchSpec
from timewarp_tpu.interp.jax_engine.engine import JaxEngine
from timewarp_tpu.trace.events import assert_states_equal

BENCHMARK = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCHMARK not in sys.path:
    sys.path.insert(0, BENCHMARK)

import span_reduce  # noqa: E402
from builders import gossip_steady, gossip_wave  # noqa: E402
from reference import gossip_steady_ref  # noqa: E402


def _load(kind, name):
    with open(os.path.join(BENCHMARK, kind, name + ".json")) as f:
        return json.load(f)


def _cell(n, ramp=64):
    traffic = _load("workloads", "gossip_steady_1m.rounds")
    config = _load("configs", traffic["config"])
    config["params"]["n_nodes"] = n
    traffic["ramp_supersteps"] = ramp
    return gossip_steady.Cell(config, traffic)


@pytest.fixture(scope="module")
def cells():
    made = {}
    return lambda n: made.get(n) or made.setdefault(n, _cell(n))


def _op_names(eng) -> set:
    text = type(eng)._run_while.lower(
        eng, eng.init_state(), eng._coerce_budget(8)[0],
        eng._identity()).as_text(debug_info=True)
    return set(re.findall(r'loc\("(jit\(_run_while\)[^"]*)"', text))


def _wave_engine(n, **kw):
    p = _load("configs", "gossip_100k")["params"]
    sc, link = gossip_wave.scenario_and_link({**p, "n_nodes": n})
    return JaxEngine(sc, link, window="auto", insert="xla", **kw)


# -- the program against the plain reference ----------------------------------

@pytest.mark.parametrize("n, seed", [
    (1024, 1), (1024, 3_100_000_001), (2048, 7), (4096, 2**31 + 5)])
def test_ramp_and_streamed_jobs_equal_the_reference(cells, n, seed):
    c = cells(n)
    assert not c.set_up(seed)["failed"]
    jobs = [c.job(i) for i in (0, 1, 2, 3)]
    assert not any(j["failed"] for j in jobs), jobs
    assert {j["supersteps"] for j in jobs} == {16}
    assert int(c.first.steps) == 64 + 3 * 16
    assert int(c.state.steps) == 64 + 5 * 16
    rows = c.compare(gossip_steady_ref)
    assert len(rows) == 21
    *exact, (name, largest, cap) = rows
    assert [v for _, v, _ in exact] == [0] * 20, rows
    assert name.startswith("reference.largest") and largest < cap == 24


def test_two_seeds_draw_two_origins_and_compile_nothing_new(cells):
    c = cells(1024)
    origins = set()
    for seed in (11, 3_000_000_019):
        c.set_up(seed)
        origins.add(c.origin)
        if len(origins) > 1:
            assert c.engine.last_run_stats["compiles"] == 0
    assert len(origins) == 2


def test_four_jobs_of_16_equal_one_run_of_64(cells):
    c = cells(1024)
    c.set_up(5)
    start = c.state
    for i in range(4):
        assert not c.job(i + 1)["failed"]
    assert_states_equal(c.engine.run_quiet(64, start), c.state,
                        "one run of 64 against four jobs of 16")


# -- the regime, and the scope -------------------------------------------------

def test_the_configurations_engine_routes_eagerly(cells):
    eng = cells(1024).engine
    assert eng.window == 1 and eng.scenario.max_out == 1
    assert not eng._adaptive_regime()
    text = type(eng)._run_while.lower(
        eng, eng.init_state(), eng._coerce_budget(8)[0],
        eng._identity()).as_text()
    # no ladder: the one conditional is the dense staging's choice of
    # its tail's width, half the lanes or all (PR 36)
    assert text.count('"stablehlo.case"') == 1 and "conditional" not in text
    nested = {span_reduce.stage_of(n, 2) for n in _op_names(eng)}
    assert {"tw.route/sort", "tw.route/insert"} <= nested
    # no ladder, so no sampling scope: the link is drawn on every slot
    assert "tw.route/sample" not in nested


@pytest.mark.parametrize("kw", [
    {"seed": 0}, {"batch": BatchSpec(seeds=(0, 1))}], ids=["solo", "fleet"])
def test_the_adaptive_drivers_have_no_sort_scope(kw):
    import fleet_reduce
    eng = _wave_engine(1024, **kw)
    assert eng._adaptive_regime()
    nested = {span_reduce.stage_of(fleet_reduce.unwrap(n), 2)
              for n in _op_names(eng)}
    assert "tw.route/insert" in nested and "tw.route/sort" not in nested


#: sha256 of the quiet driver's lowering (``as_text()``: no names, no
#: locations) at 2^11 nodes, as PR 36 lowers it: the ``while`` of
#: PR 34 (the state's event horizon carried beside it, a condition
#: on scalars, a solo body that selects nothing by liveness:
#: ``tests/test_loop_edge.py``) with the routing stage's five counts
#: in its carry, solo and fleet alike (``engine.py`` ``RouteCounts``;
#: ``tests/test_zzzzzzzzzzzzzzzrecord.py`` holds the final states to
#: PR 34's, bit for bit), and in the solo driver every rung's staging
#: in the dense form (8192 lanes and more for 2048 nodes:
#: ``tests/test_insert_staging_law.py`` holds it to the scatters). Until then
#: they were PR 35's (solo a591ccb6659a…, fleet 9e610618e0c2…), whose
#: carry held three counts. PR 44 changed the solo constant (it was
#: b341ad0cc880…): a solo engine's commutative inbox stages by rank,
#: and its carry holds three counts more (``dense_lanes``,
#: ``tail_lanes``, ``net_rows``); at these widths (16 384 lanes and
#: fewer, under ``_TAIL_LADDER_LANES``) the staging itself is PR 36's
#: text (``tests/test_stage_tail_law.py``), and the fleet's driver,
#: which stages nothing, lowers to what it lowered to. PR 48 changed
#: both (they were 76f791da8f01… and d9883414d933…): both drivers
#: take the ladder, and the ladder's sender compaction is no longer
#: a one-operand sort of the node lanes but ``compress_lanes``, a
#: prefix count and a log N shift network, whose output is the
#: sort's word for word (``tests/test_free_bits.py``; the final
#: states of ``tests/test_zzzzzzzzzzzzzzzrecord.py`` are unmoved).
#: PR 55 changed the fleet's (it was 2948d0bc4acc…): a fleet's carry
#: holds one count more, ``world_sender_lanes`` (each world's own
#: senders before the ``pmax`` that picks the rung); the solo
#: driver carries nothing new and keeps its constant. PR 56 changed
#: both (they were f738f2c7dadc… and 1bcc81c31b5c…): the ladder's
#: top rung (2^11 of the two here) reads the outbox where it lies
#: and gathers no sender word; the rung below lowers to what it
#: lowered to, and the lanes after the sort are the same words
#: (``tests/test_top_rung_in_place_law.py``; the final states of
#: ``tests/test_zzzzzzzzzzzzzzzrecord.py`` are unmoved). A
#: PR that changes what these drivers compute changes the
#: constants, and says so.
_PARENT_LOWERING = {
    "solo": "b3aae876281b43e4cb24128a1066eda165037787099a5170b238bdd533efec4f",
    "fleet": "094e6b1b24f9f94916275f7f0c6cca6bda22aae6aa1feed9305b1cc1da6cd8ea",
}


@pytest.mark.parametrize("kw, key", [
    ({"seed": 0}, "solo"),
    ({"batch": BatchSpec(seeds=tuple(range(8)))}, "fleet")],
    ids=["solo", "fleet"])
def test_the_waves_and_the_fleets_drivers_lower_to_the_parents_text(kw, key):
    eng = _wave_engine(1 << 11, **kw)
    text = type(eng)._run_while.lower(
        eng, eng.init_state(), eng._coerce_budget(8)[0],
        eng._identity()).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == _PARENT_LOWERING[key]


# -- the cap, and the controls ---------------------------------------------------

def test_eight_slots_overflow_and_fail_and_24_hold_every_message(cells):
    c = cells(4096)
    c.set_up(5)
    assert not c.job(1)["failed"]
    small = {name.partition(".")[2]: v
             for name, v, _ in c._small_mailbox(gossip_steady_ref)}
    assert small["overflow"] > 0
    assert small["in_flight_count.mismatches"] > 0
    assert small["delivered.mismatches"] == 1
    # what the nodes hold is untouched by a lost push once all are
    # infected: the loss shows in flight and in the count, as ISSUE 31 says
    assert small["steps.mismatches"] == small["time.mismatches"] == 0
    sound = c.compare(gossip_steady_ref)
    assert all(v <= limit for _, v, limit in sound), sound


def test_both_controls_fail_the_comparison(cells):
    c = cells(4096)
    c.set_up(4_100_000_007)
    assert not c.job(1)["failed"] and not c.job(2)["failed"]
    rows = {name: v for name, v, _ in c.control(gossip_steady_ref)}
    assert {n.partition(".")[0] for n in rows} == {"low_word",
                                                   "small_mailbox"}
    for tag in ("first_job", "window_end"):
        assert rows[f"low_word.{tag}.in_flight_count.mismatches"] > 4096
        assert rows[f"low_word.{tag}.lcg.mismatches"] > 0
        # the rounds are the same rounds whatever the link draws
        assert rows[f"low_word.{tag}.steps.mismatches"] == 0
    assert rows["small_mailbox.first_job.overflow"] > 0


def test_a_control_that_passes_is_returned_alone(cells, monkeypatch):
    c = cells(1024)
    c.set_up(5)
    c.job(1)
    # a "control" that is the configuration itself passes, and must
    # not hide behind the other
    monkeypatch.setitem(c.control_of, "mailbox_cap", 24)
    rows = c.control(gossip_steady_ref)
    assert all(name.startswith("first_job.") for name, _, _ in rows)
    assert all(v <= limit for _, v, limit in rows)


def test_the_reference_keeps_what_a_mailbox_would_have_to_hold():
    p = _load("configs", "gossip_steady_1m")["params"]
    m = gossip_steady_ref.Mongering({**p, "n_nodes": 2048}, origin=9)
    facts = m.run_to(96)
    assert facts["steps"] == 96 and facts["time"] == 96_000
    assert m.saturation_step() < 64
    count = jnp.asarray(facts["in_flight_count"])
    # after saturation some three pushes are in flight to a node
    assert 2.5 < float(count.sum()) / 2048 < 3.5
    assert facts["largest_in_flight"] >= int(count.sum(axis=0).max())
    assert int(m.history[:, 0].sum()) == facts["delivered"]
    with pytest.raises(ValueError):
        m.run_to(95)
    with pytest.raises(ValueError):
        gossip_steady_ref.Mongering({**p, "think_us": 1500}, origin=0)
