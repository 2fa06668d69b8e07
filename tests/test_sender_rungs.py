"""The adaptive routing ladder's edges and its telemetry contract
(ISSUE 8 satellite): `_sender_rungs` shapes at the boundaries (n below
the first rung, non-pow2 tops), rung *selection* at exact-boundary
active counts, the fleet's one shared rung, and — end-to-end — that the
recorded ``rung`` telemetry column equals the rung the ``lax.switch``
actually took for the superstep's recorded active-sender count (the
rung is recorded where the decision is made, engine.py
``_route_adaptive``; this pins that they can never drift). Since PR 48
also what puts the active senders in front of the rungs: no sort of
the node lanes, a compaction under the scope ``tw.route/senders``."""

import re

import numpy as np
import pytest

from timewarp_tpu.interp.jax_engine.engine import BatchSpec, JaxEngine
from timewarp_tpu.models.gossip import gossip
from timewarp_tpu.net.delays import Quantize, UniformDelay


def _selected(rungs, n_active):
    """Host mirror of the engine's selection line
    (``idx = sum(n_active > rungs)``): the smallest rung that fits
    the active-sender count."""
    return rungs[int(np.sum(n_active > np.asarray(rungs)))]


def test_ladder_shape_edges():
    rungs = JaxEngine._sender_rungs
    # n below the first rung: a single rung of exactly n (no ladder)
    assert rungs(512) == [512]
    assert rungs(1) == [1]
    # n exactly the first rung
    assert rungs(1024) == [1024]
    # geometric x2 with the top pinned to n — drop-free by construction
    assert rungs(4096) == [1024, 2048, 4096]
    # non-pow2 n: the top rung is n itself, not the next pow2
    assert rungs(3000) == [1024, 2048, 3000]
    for n in (1024, 3000, 4096, 1 << 17):
        r = rungs(n)
        assert r[-1] == n
        assert all(b == 2 * a for a, b in zip(r[:-2], r[1:-1]))


def test_selection_exact_boundary_counts():
    """Exact-rung-boundary semantics: a count equal to a rung fits
    that rung; one more active sender takes the next."""
    rungs = JaxEngine._sender_rungs(4096)
    assert _selected(rungs, 0) == 1024
    assert _selected(rungs, 1024) == 1024      # boundary: fits
    assert _selected(rungs, 1025) == 2048      # boundary + 1: next
    assert _selected(rungs, 2048) == 2048
    assert _selected(rungs, 2049) == 4096
    assert _selected(rungs, 4096) == 4096      # the top always fits


def _steady(n, end_us=60_000):
    sc = gossip(n, fanout=1, think_us=1_000, gossip_interval=1_000,
                end_us=end_us, steady=True, mailbox_cap=8)
    return sc, Quantize(UniformDelay(500, 4_500), 1_000)


def test_recorded_rung_matches_switch():
    """End-to-end over a ramping workload (steady gossip: the active
    set doubles per round, so the run crosses rungs): every recorded
    rung must equal the ladder selection for that superstep's recorded
    active-sender count. This scenario emits only in-range,
    uncut destinations, so `active_senders` (any valid outbox lane)
    IS the ladder's compacted count."""
    n = 4096
    sc, link = _steady(n)
    eng = JaxEngine(sc, link, window="auto", telemetry="counters")
    eng.run(160)
    fr = eng.last_run_telemetry
    assert len(fr) > 0
    rungs = JaxEngine._sender_rungs(n)
    active = fr.data["active_senders"]
    rung = fr.data["rung"]
    assert (rung > 0).all()  # the adaptive path ran every superstep
    for a, r in zip(active.tolist(), rung.tolist()):
        assert r == _selected(rungs, a), \
            f"recorded rung {r} != ladder selection for {a} active"
    # the ramp actually exercised more than one rung
    assert len(set(rung.tolist())) > 1, \
        "workload never crossed a rung boundary — widen the ramp"


def test_single_rung_n_below_first():
    """n below the first rung: the ladder degenerates to one pinned
    rung of exactly n (no switch is compiled) and telemetry records
    it."""
    n = 512
    sc, link = _steady(n)
    eng = JaxEngine(sc, link, window="auto", telemetry="counters")
    eng.run(40)
    fr = eng.last_run_telemetry
    assert set(fr.data["rung"].tolist()) == {n}


def test_batched_shares_one_rung():
    """The world axis shares ONE rung a superstep (the index of a
    vmapped lax.switch must not be batched, or the switch lowers to a
    select over ALL the branches — engine.py): every world records the
    same rung, the smallest that holds the largest active-sender count
    over the worlds, and on the ramp that is below n."""
    n = 2048
    sc, link = _steady(n)
    eng = JaxEngine(sc, link, window="auto", telemetry="counters",
                    batch=BatchSpec(seeds=(0, 1)))
    eng.run(60)
    frames = eng.last_run_telemetry
    assert len(frames) == 2
    rungs = JaxEngine._sender_rungs(n)
    rung = [fr.data["rung"] for fr in frames]
    active = np.stack([fr.data["active_senders"] for fr in frames])
    assert np.array_equal(rung[0], rung[1])
    # the worlds do differ, so the largest count is a choice
    assert (active[0] != active[1]).any()
    for i, r in enumerate(rung[0].tolist()):
        assert r == _selected(rungs, active[:, i].max()), f"superstep {i}"
    # the ramp's early supersteps ran below the top rung, the full
    # supersteps at it
    assert set(rung[0].tolist()) == set(rungs)
    assert active.min() < 1024 < active.max()


# -- the sender compaction (PR 48) ---------------------------------------------

def _one_operand_sorts(text):
    """The operand type of every single-operand ``sort`` of a lowered
    text."""
    return re.findall(
        r'"stablehlo\.sort"\(%[^,)]*\).*?\}\) : \((tensor<[^>]*>)\)',
        text, re.S)


def test_the_reader_finds_a_one_operand_sort():
    """What the ladder ran until PR 48, lowered alone: the reader
    below must see it."""
    import jax
    import jax.numpy as jnp
    text = jax.jit(lambda m: jax.lax.sort(jnp.where(
        m, jnp.arange(2048, dtype=jnp.int32), 2048))).lower(
            jax.ShapeDtypeStruct((2048,), bool)).as_text()
    assert _one_operand_sorts(text) == ["tensor<2048xi32>"]


@pytest.mark.parametrize("kw, lanes", [
    ({}, "2048xi32"), ({"batch": BatchSpec(seeds=(0, 1))}, "2x2048xi32")],
    ids=["wave", "fleet"])
def test_no_sort_of_the_node_lanes_compacts_the_senders(kw, lanes):
    """The quiet driver of a ladder engine, solo and fleet: no
    one-operand sort over the n node lanes (the rungs' variadic sorts
    of their own lanes stay), and the scope ``tw.route/senders``
    around the network that took its place (``compress_lanes``)."""
    n = 2048
    eng = JaxEngine(*_steady(n), window="auto", **kw)
    assert eng._adaptive_regime() and len(eng._sender_rungs(n)) > 1
    args = (eng.init_state(), eng._coerce_budget(8)[0])
    if eng.batch is not None:
        args += (eng._identity(),)
    text = type(eng)._run_while.lower(eng, *args).as_text(debug_info=True)
    sorts = _one_operand_sorts(text)
    assert f"tensor<{lanes}>" not in sorts, sorts
    assert "stablehlo.sort" in text          # the rungs' own
    scopes = set(re.findall(r'loc\("(jit\(_run_while\)[^"]*)"', text))
    assert any("tw.route" in name and "/senders/" in name + "/"
               for name in scopes), "no tw.route/senders scope"
