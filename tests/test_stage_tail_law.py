"""The dense staging's tail law (PR 44): ``_stage_dense`` places its
arrivals rank by rank.

From ``_TAIL_LADDER_LANES`` lanes on, the dense form of
``_stage_by_rank`` (engine.py) sends the ranks under ``R`` to their
rows through one ``expand_lanes`` over ``R * n`` lanes and scatters
what is left at the smallest of four static widths that holds it
(``_dense_plan``); under that lane count it keeps PR 36's text. Of
tier-1's engines one has the lanes (the ladder of 20 000 nodes in
tests/test_insert_slot_law.py, whose rungs of 8192 senders and over are
32 768 lanes and more), so every test here patches the constant down
to 64 *before* the engine's first trace.

- One call of ``_stage_by_rank`` on built lanes against a scatter a
  field (``tests/insertion_laws.py`` ``parent_stage_by_rank``), word
  for word, with the width it must take counted by hand: Poisson
  arrivals, a tail of exactly each width and one lane over it, a run
  of the last row that ends at the lanes' last lane (L = n/4, n/2, n,
  2n: one row and two, the lanes fewer than the rows' and more), a
  tail whose slice is clamped at the lanes' end, more than
  K at one node, invalid lanes, nothing valid; n 1024 and 1000, one
  and two payload words, with and without sender ids.
- The lowered text: one sort, the program's own; every scatter
  declared sorted and unique; no gather; one conditional of four
  branches. Under the constant: the text of PR 36's form (a copy of
  it is kept here), operation for operation.
- A ladder and an eager engine, leaf for leaf against the engine
  that inserts the parent's way (``ParentInsert``), with
  ``dense_lanes``, ``tail_lanes`` and ``net_rows`` as counted by hand
  from the lanes every superstep staged.
"""

import dataclasses
import functools
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from insertion_laws import (ParentInsert, _STEADY_LINK, _WAVE_LINK, _burst,
                            _observer_ring, _steady, parent_stage_by_rank)
from timewarp_tpu.interp.jax_engine import engine as engine_module
from timewarp_tpu.interp.jax_engine.batched import BatchSpec
from timewarp_tpu.interp.jax_engine.common import I32MAX, group_rank
from timewarp_tpu.interp.jax_engine.engine import JaxEngine
from timewarp_tpu.models.gossip import gossip
from timewarp_tpu.net.delays import Quantize, UniformDelay
from timewarp_tpu.obs.metrics import MetricsRegistry
from timewarp_tpu.ops.numeric import expand_lanes

GATE = 64
K = 24
# the plan's two rules, as docs/engines.md states them ("Staging by
# rank: two forms"): row 1 beside row 0 where the call has at least
# half a lane a node; the widths an eighth, a quarter, a half and all
PLAN_ROWS = lambda n, L: 2 if 2 * L >= n else 1
PLAN_DIVISORS = (8, 4, 2, 1)


@pytest.fixture(autouse=True)
def _gate_patched_down(monkeypatch):
    monkeypatch.setattr(engine_module, "_TAIL_LADDER_LANES", GATE)


def plan_by_hand(n, L, cap=K):
    """``_dense_plan`` as the docs state it: the rows through the
    network and the tail's widths."""
    if L < engine_module._TAIL_LADDER_LANES:
        return 1, (L // 2, L)
    return (min(PLAN_ROWS(n, L), cap),
            tuple(-(-L // d) for d in PLAN_DIVISORS))


def staged_by_hand(n, sd, ok, cap=K):
    """What one dense call must count: its rows, the width its tail
    takes and that width's index."""
    L = len(sd)
    R, widths = plan_by_hand(n, L, cap)
    rank = np.asarray(group_rank(jnp.asarray(sd)))
    fits = ok & (rank < cap)
    tail = int((fits & (rank >= R)).sum())
    took = sum(tail > w for w in widths[:-1])
    return R, widths[took], took, tail


# -- one call, on lanes built for it ------------------------------------------

def _with_tail(n, L, R, tail, rng):
    """Destinations with exactly ``tail`` fitting arrivals of rank
    ``R`` and over: nodes of K arrivals (K - R of the tail each), one
    node for the remainder, and single arrivals elsewhere."""
    full, rest = divmod(tail, K - R)
    nodes = rng.permutation(n)
    dst = [np.repeat(nodes[:full], K)]
    used = full
    if rest:
        dst.append(np.full(R + rest, nodes[used]))
        used += 1
    lanes = full * K + (R + rest if rest else 0)
    singles = min(n - used, L - lanes) * 3 // 4
    dst.append(nodes[used:used + singles])
    return np.concatenate(dst)


def _destinations(case, n, L, rng):
    R, widths = plan_by_hand(n, L)
    uniform = lambda m: rng.integers(0, n, m)
    if case == "poisson":
        return uniform(L)
    if case.startswith("tail-fills-width-"):
        return _with_tail(n, L, R, widths[int(case[-1])], rng)
    if case.startswith("tail-one-over-width-"):
        return _with_tail(n, L, R, widths[int(case[-1])] + 1, rng)
    if case == "last-row-ends-at-the-last-lane":
        # every lane fits and the last one is of rank R - 1
        return np.repeat(rng.permutation(n)[:L // R], R)
    if case == "tail-slice-clamped":
        # single arrivals, and R + 1 at one node: a tail of one lane
        # that starts at the lanes' last lane
        nodes = rng.permutation(n)
        return np.concatenate([np.full(R + 1, nodes[0]),
                               nodes[1:min(n, L - R)]])
    if case == "more-than-K-at-one-node":
        return np.concatenate([np.full(K + 5, 7), uniform(L - K - 5)])
    if case == "invalid-lanes":
        return uniform(L // 3)
    if case == "wide-tail":
        return np.repeat(rng.permutation(n)[:L // 8], 8)
    if case == "nothing-valid":
        return uniform(0)
    raise KeyError(case)


@functools.lru_cache(maxsize=None)
def _staging(n, P, inbox_src):
    """The engine and its two jitted forms: ``_stage_by_rank`` (traced
    under the patched constant: the cache is filled by tests only) and
    the scatter a field."""
    sc = dataclasses.replace(_burst(n, K), payload_width=P,
                             inbox_src=inbox_src)
    eng = JaxEngine(sc, _WAVE_LINK, window="auto", lint="off")
    return (eng, jax.jit(eng._stage_by_rank),
            jax.jit(functools.partial(parent_stage_by_rank, eng)))


def _built(case, n, L, P):
    rng = np.random.default_rng(len(case) * 1000 + n + L + P)
    dst = _destinations(case, n, L, rng)
    assert len(dst) <= L
    sd = np.concatenate([np.sort(dst), np.full(L - len(dst), n)]
                        ).astype(np.int32)
    i32 = lambda: rng.integers(-2**31, 2**31, L).astype(np.int32)
    return (sd, sd < n, rng.integers(0, 10**6, L).astype(np.int32),
            i32(), tuple(i32() for _ in range(P)))


_EDGES = [f"tail-{edge}-width-{i}" for i in range(3)
          for edge in ("fills", "one-over")]
#: (case, the lanes in quarters of the nodes, payload words, sender
#: ids): a quarter is one row, a half and over two
ONE_CALL = (
    [("poisson", 4, P, src) for P in (1, 2) for src in (False, True)]
    + [(case, 4, 1 + i % 2, i % 4 > 1) for i, case in enumerate(_EDGES)]
    + [("tail-fills-width-0", 1, 1, False),
       ("tail-one-over-width-0", 1, 1, False),
       ("tail-fills-width-0", 2, 1, False),
       ("tail-one-over-width-0", 2, 2, True),
       ("tail-fills-width-1", 8, 2, True),
       ("tail-one-over-width-1", 8, 1, False)]
    + [("last-row-ends-at-the-last-lane", q, P, src)
       for q, P, src in ((1, 1, False), (2, 1, False), (4, 2, True),
                         (8, 1, False))]
    + [("tail-slice-clamped", 4, 2, False),
       ("tail-slice-clamped", 2, 1, False),
       ("tail-slice-clamped", 1, 1, False),
       ("more-than-K-at-one-node", 4, 1, True),
       ("invalid-lanes", 8, 2, True), ("wide-tail", 4, 2, False),
       ("poisson", 8, 1, False), ("poisson", 2, 2, True),
       ("poisson", 1, 1, False), ("nothing-valid", 4, 1, False)])


@pytest.mark.parametrize("n", [1024, 1000], ids="n{}".format)
@pytest.mark.parametrize(
    "case,quarters,P,inbox_src", ONE_CALL,
    ids=[f"{c}-L{q}n/4-P{P}-{'src' if s else 'nosrc'}"
         for c, q, P, s in ONE_CALL])
def test_one_dense_staging_equals_the_scatters(case, quarters, P,
                                               inbox_src, n):
    """Every staged buffer word for word, ``over`` the same number,
    and the width taken the one the lanes say."""
    L = n * quarters // 4
    eng, stage, scatter = _staging(n, P, inbox_src)
    assert eng._stages_dense(L) and L >= GATE
    assert eng._dense_plan(L) == plan_by_hand(n, L)
    lanes = _built(case, n, L, P)
    sd, ok = lanes[0], lanes[1]
    R, width, took, tail = staged_by_hand(n, sd, ok)
    *got, got_took = stage(*lanes)
    want = scatter(*lanes)
    assert int(got_took) == took
    for name, x, y in zip(("rel", "src", "pay", "over"), got, want):
        for a, b in zip(jax.tree.leaves(x), jax.tree.leaves(y)):
            assert np.array_equal(a, b), (case, name)
    rank = np.asarray(group_rank(jnp.asarray(sd)))
    assert int(got[3]) == int((ok & (rank >= K)).sum())
    assert (int(got[3]) > 0) == (case == "more-than-K-at-one-node")
    assert int((np.asarray(got[0]) != I32MAX).sum()) \
        == int((ok & (rank < K)).sum())
    # the case is the case its name says
    widths = plan_by_hand(n, L)[1]
    if case.startswith("tail-fills"):
        assert tail == width == widths[int(case[-1])]
    elif case.startswith("tail-one-over"):
        assert tail == widths[int(case[-1])] + 1 and took == int(case[-1]) + 1
    elif case == "last-row-ends-at-the-last-lane":
        assert ok.all() and rank[-1] == R - 1 and tail == 0
    elif case == "tail-slice-clamped":
        assert tail == 1 and int((ok & (rank < R)).sum()) + widths[0] > L
    elif case == "wide-tail":
        assert took == len(widths) - 1
    elif case == "nothing-valid":
        assert not ok.any() and took == 0


def test_a_mailbox_of_one_slot_sends_one_row():
    """R never passes K: a plan of two rows in a one-slot mailbox
    would write past the buffer."""
    sc = dataclasses.replace(_burst(1024, 1), payload_width=1,
                             inbox_src=False)
    eng = JaxEngine(sc, _WAVE_LINK, window="auto", lint="off")
    assert eng._dense_plan(1024)[0] == 1
    lanes = _built("poisson", 1024, 1024, 1)
    *got, took = jax.jit(eng._stage_by_rank)(*lanes)
    want = jax.jit(functools.partial(parent_stage_by_rank, eng))(*lanes)
    for x, y in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert np.array_equal(x, y)
    assert int(took) == staged_by_hand(1024, lanes[0], lanes[1], cap=1)[2]


# -- the lowered text -----------------------------------------------------------

def pr36_stage_dense(self, sd, ok_s, rank, fits, drel_s, src_s, pay_s):
    """``_stage_dense`` as it stood at f0d882f (PR 36's form, the
    parent of PR 44): rank 0 through the network, every later rank
    scattered at half the lanes or all of them."""
    sc = self.scenario
    K, P = sc.mailbox_cap, sc.payload_width
    n = self.comm.n_local
    L = sd.shape[0]
    half = L // 2
    fields = (drel_s,) + ((src_s,) if sc.inbox_src else ()) \
        + tuple(pay_s[:P])
    nothing = (I32MAX,) + (0,) * (len(fields) - 1)
    flat = jnp.where(
        fits, rank * jnp.int32(n) + sd,
        jnp.int32(K * n) + jnp.arange(L, dtype=jnp.int32))
    c0 = jnp.sum(fits & (rank == 0), dtype=jnp.int32)
    wide = jnp.sum(fits, dtype=jnp.int32) - c0 > half
    flat, *fields = jax.lax.sort((flat,) + fields, num_keys=1)

    def head(x):
        if L >= n:
            return x[:n]
        return jnp.concatenate([x, jnp.zeros((n - L,), x.dtype)])
    row0 = expand_lanes(head(flat), c0, [head(x) for x in fields],
                        nothing)

    def tail(width):
        def scatter():
            at = jax.lax.dynamic_slice_in_dim(flat, c0, width)
            return tuple(
                jnp.full((K * n,), e, x.dtype).at[at].set(
                    jax.lax.dynamic_slice_in_dim(x, c0, width),
                    mode="drop", indices_are_sorted=True,
                    unique_indices=True)
                for x, e in zip(fields, nothing))
        return scatter
    bufs = jax.lax.cond(wide, tail(L), tail(half))
    bufs = [jax.lax.dynamic_update_slice_in_dim(b, r, 0, 0)
            for b, r in zip(bufs, row0)]
    over = jnp.sum(ok_s & (rank >= K), dtype=jnp.int32)
    return (bufs[0], bufs[1] if sc.inbox_src else None,
            tuple(bufs[len(bufs) - P:]), over, wide.astype(jnp.int32))


class Pr36Staging(JaxEngine):
    _stage_dense = pr36_stage_dense


def _staging_text(eng, L, P):
    lane = jax.ShapeDtypeStruct((L,), np.int32)
    return jax.jit(eng._stage_by_rank).lower(
        lane, jax.ShapeDtypeStruct((L,), bool), lane, lane,
        (lane,) * P).as_text()


@pytest.mark.parametrize("quarters", [1, 2, 4, 8], ids="L{}n/4".format)
def test_every_scatter_is_declared_and_nothing_is_sorted_twice(quarters):
    """One sort, the program's own; no gather; one conditional, of
    four branches, each a declared scatter a field."""
    n, P = 1024, 2
    eng = _staging(n, P, True)[0]
    text = _staging_text(eng, n * quarters // 4, P)
    assert len(re.findall(r"stablehlo\.sort", text)) == 1
    assert "stablehlo.gather" not in text
    assert text.count('"stablehlo.case"') == 1
    scatters = re.findall(r'"stablehlo\.scatter".*?<\{(.*?)\}>', text,
                          flags=re.S)
    assert len(scatters) == 4 * (2 + P)
    for attrs in scatters:
        assert "indices_are_sorted = true" in attrs, attrs
        assert "unique_indices = true" in attrs, attrs


@pytest.mark.parametrize("quarters", [1, 2, 4, 8], ids="L{}n/4".format)
def test_under_the_constant_the_dense_staging_is_pr36s_text(
        quarters, monkeypatch):
    n, P = 1024, 2
    L = n * quarters // 4
    sc = dataclasses.replace(_burst(n, K), payload_width=P, inbox_src=True)
    monkeypatch.setattr(engine_module, "_TAIL_LADDER_LANES", 1 << 15)
    eng = JaxEngine(sc, _WAVE_LINK, window="auto", lint="off")
    assert eng._dense_plan(L) == (1, (L // 2, L))
    under = _staging_text(eng, L, P)
    assert under == _staging_text(
        Pr36Staging(sc, _WAVE_LINK, window="auto", lint="off"), L, P)
    monkeypatch.setattr(engine_module, "_TAIL_LADDER_LANES", GATE)
    assert _staging_text(eng, L, P) != under


# -- whole engines ----------------------------------------------------------------

class Spied(JaxEngine):
    """The engine, telling the host the lanes of every staging."""
    seen = None

    def _stage_by_rank(self, sd, ok_s, *fields):
        jax.debug.callback(
            lambda sd, ok: self.seen.append((np.asarray(sd),
                                             np.asarray(ok))), sd, ok_s)
        return super()._stage_by_rank(sd, ok_s, *fields)


def _ramp(n):
    """Steady gossip from one origin: the active set doubles a round,
    so a windowed run crosses the ladder's rungs
    (tests/test_zzzzzzzzzzzzzzzrecord.py)."""
    sc = gossip(n, fanout=1, think_us=1_000, gossip_interval=1_000,
                end_us=60_000, steady=True, mailbox_cap=8)
    return sc, Quantize(UniformDelay(500, 4_500), 1_000)


ENGINES = {
    # one slot a node, 8192 nodes: rungs of 1024 lanes (the scatters a
    # field), 2048 (dense, one row), 4096 and 8192 (two rows)
    "ladder": (lambda: _ramp(8192), {"window": "auto"}, 40),
    # the steady cell's program at 1024 nodes: every superstep at
    # L = n, two rows
    "eager": (lambda: (_steady(1024, K), _STEADY_LINK), {}, 24),
}


@pytest.mark.parametrize("which", list(ENGINES))
def test_an_engine_equals_the_parents_and_counts_its_tail(which):
    make, kw, steps = ENGINES[which]
    sc, link = make()
    n, cap = sc.n_nodes, sc.mailbox_cap
    eng = Spied(sc, link, lint="off", **kw)
    eng.seen = []
    got = jax.device_get(eng.run_quiet(steps))
    jax.effects_barrier()
    ref = ParentInsert(sc, link, lint="off", **kw)
    want = jax.device_get(ref.run_quiet(steps))
    for name, a, b in zip(got._fields, got, want):
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            assert np.array_equal(x, y), name
    stats = dict(eng.last_run_stats)
    assert len(eng.seen) == stats["supersteps"] == steps
    dense = [(sd, ok) for sd, ok in eng.seen if eng._stages_dense(len(sd))]
    by_hand = [staged_by_hand(n, sd, ok, cap) for sd, ok in dense]
    assert stats["dense_stage_steps"] == len(dense)
    assert stats["dense_lanes"] == sum(len(sd) for sd, _ in dense)
    assert stats["net_rows"] == sum(R for R, *_ in by_hand)
    assert stats["tail_lanes"] == sum(w for _, w, *_ in by_hand)
    assert stats["wide_tail_steps"] == sum(
        w == len(sd) for (sd, _), (_, w, *_) in zip(dense, by_hand)) == 0
    rows = {len(sd): R for (sd, _), (R, *_) in zip(dense, by_hand)}
    if which == "ladder":
        assert eng._sender_rungs(n) == [1024, 2048, 4096, 8192]
        assert rows == {2048: 1, 4096: 2, 8192: 2} \
            == {L: PLAN_ROWS(n, L) for L in rows}
        assert len(dense) < steps
        # the ramp's arrivals are few a node: the narrowest width
        assert {w * 8 // len(sd) for (sd, _), (_, w, *_)
                in zip(dense, by_hand)} == {1}
    else:
        assert rows == {n: PLAN_ROWS(n, n)} and len(dense) == steps
        assert stats["tail_lanes"] < stats["dense_lanes"] // 2
    # the scan driver counts what the quiet one counted
    eng.run(steps)
    for key in ("dense_lanes", "tail_lanes", "net_rows"):
        assert eng.last_run_stats[key] == stats[key], key
    merged = eng._stats_merge([stats, stats])
    reg = MetricsRegistry()
    reg.run_summary(which, stats)
    for key in ("dense_lanes", "tail_lanes", "net_rows"):
        assert merged[key] == 2 * stats[key]
        assert reg.lines[-1][key] == stats[key]


@pytest.mark.parametrize("which", ["fleet", "ordered"])
def test_an_engine_that_never_stages_carries_no_tail_counts(which):
    """``_stages_by_rank`` false: the three counters are None, an
    empty node of the carry, and the driver's text is what it was."""
    if which == "fleet":
        eng = JaxEngine(_burst(1024, K), _WAVE_LINK, window="auto",
                        lint="off", batch=BatchSpec(seeds=(0, 4)))
    else:
        eng = JaxEngine(_observer_ring(1024, 8), UniformDelay(1_000, 5_000),
                        window="auto", lint="off")
    assert not eng._stages_by_rank()
    counts = jax.eval_shape(eng._counted, eng.init_state())[1]
    assert counts.dense_lanes is counts.tail_lanes is counts.net_rows is None
    solo = JaxEngine(_burst(1024, K), _WAVE_LINK, window="auto", lint="off")
    counts = jax.eval_shape(solo._counted, solo.init_state())[1]
    assert counts.dense_lanes.shape == counts.net_rows.shape == ()
