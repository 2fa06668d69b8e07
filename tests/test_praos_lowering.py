"""Praos at the headline size as a deployment, what needs no compiled
cell (tests/test_zzzzzzzzzzzzzzpraos_slots.py has the cells against the
reference): the reference is plain, a mailbox too small fails the job's
gates, ``bench.py``'s row has the cap that holds every tip, the firing
entropy's scope is a name and nothing else (the praos, steady, wave and
fleet drivers lower to the text they had), and the ladder's rungs at
the timed size."""

import hashlib
import os
import sys

import pytest

from praos_laws import (BENCHMARK, _cell, _load, _lowered,    # puts
                        _nested_scopes)         # benchmark/ on the path
from builders import gossip_steady, gossip_wave, praos_slots
from reference import praos_ref
from timewarp_tpu.interp.jax_engine.batched import BatchSpec
from timewarp_tpu.interp.jax_engine.engine import JaxEngine


def _params(n):
    return {**_load("configs", "praos_1m")["params"], "n_nodes": n,
            "n_slots": 2}


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
#: ``tests/test_insert_staging_law.py``). Until then they were PR 35's
#: (steady 019784a05692…, praos 23c5c22aee01…). PR 44 changed both
#: (they were 56417b93abea… and 02f9e0df7c22…): the carry of a solo
#: engine that stages by rank holds three counts more
#: (``dense_lanes``, ``tail_lanes``, ``net_rows``); the staging
#: itself is PR 36's text at these widths (under
#: ``_TAIL_LADDER_LANES``: ``tests/test_stage_tail_law.py``). The wave's and the
#: fleet's are pinned in ``test_zzzzzzzzzzzzzsteady_mongering.py``.
#: PR 48 changed praos' (it was 8aa4cb7fa8dc…): its driver takes the
#: ladder, whose sender compaction went from a one-operand sort of
#: the node lanes to ``compress_lanes`` (the same array, word for
#: word: ``tests/test_free_bits.py``); steady's driver takes the
#: eager path, never compacted its senders, and keeps its constant.
#: PR 56 changed praos' (it was 2655443c024a…): the ladder's top rung
#: (2^11 of the two here) reads the outbox where it lies and gathers
#: no sender word; the rung below lowers to what it lowered to, and
#: the lanes after the sort are the same words
#: (``tests/test_top_rung_in_place_law.py``). Steady's keeps its
#: constant: the eager path has no ladder. A
#: PR that changes what these drivers compute changes the constants,
#: and says so.
_PARENT_LOWERING = {
    "steady": "2cf72c06d42d1eb8125ab0d9dec3910692af50485db9ce4a878fd040f5866712",
    "praos": "756f016e125d243e511de9d42383762c71472edfb5c5bd4404590cf127330543",
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
