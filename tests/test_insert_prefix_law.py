"""The mailbox-insertion law, the ranked insertion cut to the prefix
that can land.

Since PR 43 an ordered inbox's ranked insertion, solo and on one
device, cuts its scatters to a prefix of its lanes, by a ladder of
four static widths from ``_PREFIX_SCATTER_LANES`` lanes on
(``_scatter_widths``); since PR 52 the prefix ends at the last valid
lane of rank under K, a function of the ranks alone, and the gather of
the destinations' kept counts runs inside the width's branch. With the
constant patched down to 64 lanes, one call on built lanes (no lane
fits, the prefix on a width's edge and one past it, every lane fits,
one hub of 8 slots taking every lane, overloaded destinations between
fitting ones, full mailboxes under arrivals spread past a width's
edge, a hub whose K-th rank lies on a width's edge) is held to the
one-scatter form and to a plain numpy insertion word for word, with
the width it must take; the observer
ring runs on a rung and on the eager path against the oracle and the
unpatched engine, leaf for leaf; and under the constant an ordered
engine's driver lowers to the one-scatter text.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from insertion_laws import (INBOX, SITE, _observer_ring, _ordered_lanes,
                            oracle_catches_up, pair)
from timewarp_tpu.interp.jax_engine import engine as engine_module
from timewarp_tpu.interp.jax_engine.engine import JaxEngine
from timewarp_tpu.models.token_ring import token_ring
from timewarp_tpu.net.delays import FixedDelay, UniformDelay
from timewarp_tpu.trace.events import assert_states_equal


# -- the ranked insertion, cut to the prefix that can land -------------------

_PATCHED_LANES = 64


def plain_ranked_insertion(K, P, mb_rel, mb_src, mb_payload, sd, ok,
                           drel, src, pay, counts):
    """An ordered inbox's insertion, lane by lane: a valid lane takes
    the slot after its destination's kept messages and earlier
    arrivals, or is counted. Returns the planes, ``overflow``, the
    largest fan-in, the lane after the last that landed and the lane
    after the last that can land whatever the mailbox keeps (valid,
    of rank under ``K`` among its destination's arrivals)."""
    mb_rel, mb_src, mb_payload = (np.array(x) for x in
                                  (mb_rel, mb_src, mb_payload))
    used, over, hi, can = counts.astype(np.int64), 0, 0, 0
    for lane in np.flatnonzero(ok):
        d = sd[lane]
        if used[d] - counts[d] < K:
            can = lane + 1
        if used[d] < K:
            mb_rel[used[d], d] = drel[lane]
            mb_src[used[d], d] = src[lane]
            for p in range(P):
                mb_payload[used[d], p, d] = pay[p][lane]
            hi = lane + 1
        else:
            over += 1
        used[d] += 1
    fan_in = int((used - counts).max())
    return mb_rel, mb_src, mb_payload, over, fan_in, hi, can


@functools.lru_cache(maxsize=None)
def _ranked_insertions(n, P):
    """``_insert_sorted`` of an ordered engine of ``n`` nodes, jitted
    in both forms on ``2 n`` lanes: cut to its prefix (the constant
    patched down for the trace) and as one scatter a field."""
    import dataclasses
    sc = dataclasses.replace(_observer_ring(n, 8), payload_width=P)
    eng = JaxEngine(sc, UniformDelay(1_000, 5_000), window="auto",
                    lint="off")
    assert eng._cuts_scatters() and eng._scatter_widths(2 * n) == (2 * n,)

    def insert(*lanes):
        return eng._insert_sorted(*lanes[:-1], None, lanes[-1])

    def patched(*lanes):
        was = engine_module._PREFIX_SCATTER_LANES
        engine_module._PREFIX_SCATTER_LANES = _PATCHED_LANES
        try:
            assert len(eng._scatter_widths(2 * n)) == 4
            return insert(*lanes)
        finally:
            engine_module._PREFIX_SCATTER_LANES = was
    return eng, jax.jit(patched), jax.jit(insert)


#: case -> which of the four widths (L/8, L/4, L/2, L) it must take
PREFIXES = {"nothing-fits": 2, "edge-of-a-width": 1,
            "one-past-the-edge": 2, "every-lane-fits": 3,
            "one-hub-takes-every-lane": 0,
            "overloaded-between-fitting": 3,
            "full-under-arrivals-past-an-edge": 2,
            "hub-rank-on-an-edge": 1}


@pytest.mark.parametrize("P", [1, 2], ids="P{}".format)
@pytest.mark.parametrize("n", [1024, 1000], ids="n{}".format)
@pytest.mark.parametrize("case", list(PREFIXES))
def test_the_prefix_scatters_equal_the_one_scatter_word_for_word(case, n, P):
    """One call of the ranked insertion cut to its prefix, against the
    one-scatter form and a plain insertion in numpy: every plane,
    ``overflow`` and the fan-in the same, and the width taken the
    smallest of the four that holds the last valid lane of rank under
    ``K``: the ranks alone pick it, whatever the mailboxes keep."""
    K, L = 8, 2 * n
    eng, cut, one = _ranked_insertions(n, P)
    lanes = _ordered_lanes(case, n, K, P, L,
                           np.random.default_rng(len(case) + n + P))
    got, ref = cut(*lanes), one(*lanes)
    *want, hi, can = plain_ranked_insertion(K, P, *lanes)
    widths = [-(-L // d) for d in (8, 4, 2, 1)]
    assert hi <= can
    assert int(got[5]) == min(w for w in widths if w >= can) \
        == widths[PREFIXES[case]]
    assert int(ref[5]) == L
    for name, x, y, z in zip(("mb_rel", "mb_src", "mb_payload", "overflow",
                              "fan_in"), got, ref, want):
        assert np.array_equal(x, y) and np.array_equal(x, z), (case, name)
    if case == "nothing-fits":
        # wider than the lanes that fit would ask for (none: L/8)
        assert hi == 0 and can > L // 4 and int(got[3]) == L // 3
    if case == "full-under-arrivals-past-an-edge":
        # five lanes land, at the front; the full mailboxes' arrivals,
        # one a node, reach one lane past L/4 and every one is counted
        assert hi == 5 and can == L // 4 + 1
        assert int(got[3]) == L // 4 + 1 - 5 and int(got[4]) == 1
    if case == "hub-rank-on-an-edge":
        # the hub keeps 3: its ranks 0-4 land, 5-7 could and do not,
        # and rank 7 is the last lane of the width
        assert (hi, can) == (L // 4 - 3, L // 4)
        assert int(got[3]) == L // 2 - (K - 3) and int(got[4]) == L // 2
    if case == "one-hub-takes-every-lane":
        assert hi == K and int(got[3]) == L - K and int(got[4]) == L
    if case == "overloaded-between-fitting":
        # dropped lanes all along it, and it is still most of the lanes
        assert int(got[3]) > n // 10 and hi > L - K - 8


@pytest.mark.parametrize("n", [1024, 1000], ids="n{}".format)
@pytest.mark.parametrize("site", ["adaptive", "eager"])
def test_the_prefix_scatters_on_a_rung_and_on_the_eager_path(
        site, n, monkeypatch):
    """The observer ring, every node a token, a hub of 8 slots, with
    the constant patched down: against the oracle at two horizons, and
    every leaf of the state against the unpatched engine's. On the
    ladder a cycle's three supersteps take an eighth of the ring's
    rung (8 notes fit), half of it (every sender's token) and an
    eighth of the hub's; the eager path scatters fewer lanes than its
    one scatter would."""
    sc = token_ring(n - 1, n_tokens=n - 1, think_us=1_000,
                    bootstrap_us=1_000, with_observer=True, mailbox_cap=8)
    # the cell's link and window: a cycle is three supersteps
    relink, _, adaptive = SITE[site]
    link = relink(FixedDelay(500))
    plain = JaxEngine(sc, link, lint="off")
    want = []
    st = plain.init_state()
    for k in (9, 9):
        st, _ = plain.run(k, st)
        want.append(jax.device_get(st))
    full = plain.last_run_stats["scatter_lanes"]
    monkeypatch.setattr(engine_module, "_PREFIX_SCATTER_LANES",
                        _PATCHED_LANES)
    eng, orc = pair(sc, link)
    assert eng._adaptive_regime() == adaptive and eng._cuts_scatters()
    st = eng.init_state()
    for k, ref in zip((9, 9), want):
        st, etr = eng.run(k, st)
        oracle_catches_up(f"prefix-{site}-n{n}", orc, k, st, etr)
        assert_states_equal(st, ref, f"prefix-{site}-n{n} +{k}")
    assert int(st.overflow) > n
    cut = eng.last_run_stats["scatter_lanes"]
    L = 2 * n
    assert full == 9 * L
    if adaptive:
        # one rung at these sizes: the tail runs at the nodes' width
        assert eng._sender_rungs(n) == [n]
        assert cut == 3 * (L // 8 + L // 2 + L // 8)
    else:
        assert cut < full // 2


def test_under_the_constant_an_ordered_driver_lowers_to_one_scatter(
        monkeypatch):
    """Fewer lanes than ``_PREFIX_SCATTER_LANES``: the quiet driver of
    an ordered engine is the one-scatter form's text, operation for
    operation, with no conditional of widths in it; patched down, every
    rung gains one."""
    sc = _observer_ring(1024, 8)
    link = INBOX["ordered"][1]

    def text():
        eng = JaxEngine(sc, link, window="auto", lint="off")
        return type(eng)._run_while.lower(
            eng, eng.init_state(), jnp.int64(4), None).as_text(), eng
    under, eng = text()
    assert all(len(eng._scatter_widths(2 * a)) == 1
               for a in eng._sender_rungs(1024))
    monkeypatch.setattr(engine_module, "_PREFIX_SCATTER_LANES", 1 << 30)
    assert text()[0] == under
    monkeypatch.setattr(engine_module, "_PREFIX_SCATTER_LANES",
                        _PATCHED_LANES)
    cut = text()[0]
    cases = lambda t: t.count("stablehlo.case")
    assert cases(cut) == cases(under) + len(eng._sender_rungs(1024))
    assert cut.count('"stablehlo.scatter"') \
        == 4 * under.count('"stablehlo.scatter"')
