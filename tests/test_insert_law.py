"""The mailbox-insertion law: ``JaxEngine`` against ``SuperstepOracle``.

``_insert_sorted`` (engine.py) is the one insertion form, reached from
three call sites of the superstep's routing stage. Every case here runs
the engine and the host oracle side by side and compares the *state* at
two horizons (every node's scenario state and wake time, the clock, the
mailbox contents message by message, the never-silent counters) and the
trace over both. The state comparison is what the trace laws elsewhere
do not make: a message in the wrong slot, or a payload word scattered
to a neighbour, shows in the mailbox one superstep before it shows in
a digest.

The matrix is the one the removed kernel tests walked against the XLA
form (PR 29; they are at 193bc01), walked against the oracle instead:

- call site: ``adaptive`` (windowed, drop-free link: the ladder's
  tail), ``eager`` (a ``WithDrop`` link), ``lazy`` (``route_cap`` above
  the load);
- inbox: commutative (the gossip burst: the r-th message takes the
  destination's r-th hole; and the same at two words of holes, below)
  and ordered (the observer token ring,
  ``max_out`` 2: append after the kept messages);
- mailbox: fits, and too small for the fan-in (``overflow`` > 0 is
  asserted, and the surviving messages must still be the oracle's);
- n: 1024, and 1000 (a width that is no multiple of a lane or a tile).

Since PR 30 a commutative inbox's holes are ``ceil(K/32)`` uint32
words a node, and since PR 32 its arrivals are staged by rank and
every node fills its holes from them (ops/numeric.py ``fill_holes``;
tests/test_free_bits.py is the primitive's own law). So the matrix
has a third inbox, a wave of fanout 40 into mailboxes of two words,
fitting (64 slots, 52 used) and not (40). And the *slot* is held too,
which no observer could tell apart if it differed: runs on the
ladder, on the eager path and as a fleet are replayed by an engine
that inserts the parent's way (the hole words gathered onto the
message lanes, the rank-th set bit as the slot: a test-local copy),
every leaf of the state compared bit for bit, the stale words in
holes included; and one call of the insertion on lanes built to hold
every case of the overflow (ranks past K at one node, fewer holes
than arrivals, none) against the same copy.

Since PR 36 the staging itself has two forms, chosen by the call's
shapes (``_stages_dense``: the lanes at least ``_DENSE_STAGE_RATIO`` of the
nodes): a scatter a
field, and one sort by staged index with rank 0 expanded on the node
lanes and the tail scattered declared sorted. At these widths nearly
every case above takes the second; ``parent_stage_by_rank`` is the
first, kept here as the reference, and one call of ``_stage_by_rank``
on built lanes is held to it word for word in both forms (skewed
destinations, more than K at one node, invalid lanes, a tail over half
the lanes, a slice clamped at the lanes' end, lanes under the
threshold), as is a ladder whose first rung is under the threshold
and whose others are over it.

Since PR 43 an ordered inbox's ranked insertion, solo and on one
device, cuts its scatters to the prefix that ends at the last lane
that fits, by a ladder of four static widths from
``_PREFIX_SCATTER_LANES`` lanes on (``_scatter_widths``). With the
constant patched down to 64 lanes, one call on built lanes (no lane
fits, the prefix on a width's edge and one past it, every lane fits,
one hub of 8 slots taking every lane, overloaded destinations between
fitting ones) is held to the one-scatter form and to a plain numpy
insertion word for word, with the width it must take; the observer
ring runs on a rung and on the eager path against the oracle and the
unpatched engine, leaf for leaf; and under the constant an ordered
engine's driver lowers to the one-scatter text.

Then praos (``needs_key``, payload width 2, a lognormal link), the
socket-state hub (1023 clients into one mailbox), a two-world faulted
fleet (world b's slice against the solo oracle under
``fleet.world_schedule(b)``), the ladder's first, a middle and its top
rung (read back from telemetry's ``rung`` column), a checkpoint handed
from a solo run to a fleet, and the three refusals that are left of
the ``insert=`` selection.

Left out as covered: small-n trace parity of the token ring, ping-pong
and invalid destinations (test_parity.py); windowed against classic
semantics, ``route_cap`` over the load and the sharded forms
(test_windowed.py); mixed fault schedules on the solo engines and the
fleet-slice-against-solo-*engine* law (test_zfault_parity.py,
test_world_batch.py, whose reference is another engine configuration,
never the oracle at these widths).
"""

import functools
import math
import os
from typing import Any, NamedTuple

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from timewarp_tpu.core.scenario import NEVER
from timewarp_tpu.faults import (FaultFleet, FaultSchedule, NodeCrash,
                                 Partition)
from timewarp_tpu.interp.jax_engine.batched import BatchSpec, world_slice
from timewarp_tpu.interp.jax_engine.common import I32MAX, group_rank
from timewarp_tpu.interp.jax_engine import engine as engine_module
from timewarp_tpu.interp.jax_engine.engine import (_DENSE_STAGE_RATIO,
                                                   JaxEngine)
from timewarp_tpu.interp.ref.superstep import SuperstepOracle
from timewarp_tpu.models.gossip import gossip
from timewarp_tpu.models.praos import praos
from timewarp_tpu.models.socket_state import socket_state
from timewarp_tpu.models.token_ring import token_ring
from timewarp_tpu.net.delays import (FixedDelay, LogNormalDelay, Quantize,
                                     UniformDelay, WithDrop)
from timewarp_tpu.ops.numeric import nth_set_bit
from timewarp_tpu.trace.events import (assert_states_equal,
                                       assert_traces_equal)


# ---------------------------------------------------------------------------
# one view of a state, from either side
# ---------------------------------------------------------------------------

class View(NamedTuple):
    """What both executors must agree on after the same supersteps.
    The mailbox is ``[n, K]``, a node's pending messages first: in
    arrival order for an ordered inbox (the engine keeps it in slot
    order, the oracle in list order), sorted by (time, src, payload)
    for a commutative one (slot order is unobservable there)."""
    states: Any
    wake: Any
    time: Any
    mb_time: Any
    mb_src: Any
    mb_pay: Any
    overflow: Any
    bad_dst: Any
    short_delay: Any
    fault_dropped: Any


def _mailbox(sc, t, src, pay):
    """Canonical ``[n, K]`` mailbox from per-slot arrays (``t`` is
    NEVER in an empty slot)."""
    n, K = t.shape
    empty = t >= NEVER
    src = np.where(empty | (not sc.inbox_src), 0, src)
    pay = np.where(empty[:, :, None], 0, pay)
    node = np.repeat(np.arange(n), K)
    if sc.commutative_inbox:
        keys = tuple(pay[:, :, p].ravel() for p in
                     reversed(range(pay.shape[2]))) \
            + (src.ravel(), t.ravel(), node)
    else:
        keys = (np.tile(np.arange(K), n), empty.ravel(), node)
    order = np.lexsort(keys)
    return (t.ravel()[order].reshape(n, K),
            src.ravel()[order].reshape(n, K),
            pay.reshape(n * K, -1)[order].reshape(n, K, -1))


def engine_view(sc, st) -> View:
    st = jax.device_get(st)
    rel = np.asarray(st.mb_rel).T                       # [n, K]
    t = np.where(rel == I32MAX, NEVER,
                 int(st.time) + rel.astype(np.int64))
    mb = _mailbox(sc, t, np.asarray(st.mb_src).T,
                  np.asarray(st.mb_payload).transpose(2, 0, 1))
    return View({k: np.asarray(v) for k, v in st.states.items()},
                np.asarray(st.wake), int(st.time), *mb,
                int(st.overflow), int(st.bad_dst), int(st.short_delay),
                int(st.fault_dropped))


def oracle_view(o: SuperstepOracle) -> View:
    sc = o.scenario
    n, K, P = sc.n_nodes, sc.mailbox_cap, sc.payload_width
    t = np.full((n, K), NEVER, np.int64)
    src = np.zeros((n, K), np.int32)
    pay = np.zeros((n, K, P), np.int32)
    for i, box in enumerate(o.mailbox):
        assert len(box) <= K
        for j, (dt, s, p) in enumerate(box):
            t[i, j], src[i, j], pay[i, j] = dt, s, p
    return View({k: np.asarray(v) for k, v in o.states.items()},
                np.asarray(o.wake, np.int64), int(o.time),
                *_mailbox(sc, t, src, pay),
                o.overflow_total, o.bad_dst_total, o.short_delay_total,
                o.fault_dropped_total)


def oracle_catches_up(tag, orc, k, st, etr):
    """Step the oracle the ``k`` supersteps the engine just ran (to
    the solo state ``st``, over the trace ``etr``) and hold both to
    it. Returns the oracle's trace."""
    otr = orc.run(k)
    assert_states_equal(oracle_view(orc), engine_view(orc.scenario, st),
                        f"{tag} +{k}")
    assert_traces_equal(otr, etr, f"oracle-{tag}+{k}", f"engine-{tag}+{k}")
    return otr


def hold_to_oracle(tag, eng, orc, horizons):
    """Engine and oracle over ``horizons`` (supersteps, each from the
    last): views equal at every one, the traces equal over each.
    Returns the engine's last state."""
    st, delivered = eng.init_state(), 0
    for k in horizons:
        st, etr = eng.run(k, st)
        delivered += oracle_catches_up(tag, orc, k, st,
                                       etr).total_delivered()
    assert delivered == int(st.delivered)
    assert int(st.route_drop) == 0 and int(st.bad_delay) == 0
    return st


def pair(sc, link, *, seed=0, **kw):
    """The engine and its oracle, on one window."""
    eng = JaxEngine(sc, link, seed=seed, lint="off", **kw)
    return eng, SuperstepOracle(sc, link, seed=seed, lint="off",
                                window=eng.window)


# ---------------------------------------------------------------------------
# the matrix: call site x inbox x mailbox x n
# ---------------------------------------------------------------------------

def _burst(n, K):
    return gossip(n, fanout=8, think_us=2_000, burst=True,
                  end_us=1_000_000, mailbox_cap=K)


def _wide_burst(n, K):
    """Fanout 40: up to 52 messages pending at one node of 1024, so a
    mailbox's holes past row 31 (its second uint32 word of free
    slots, PR 30) are taken."""
    return gossip(n, fanout=40, think_us=2_000, burst=True,
                  end_us=1_000_000, mailbox_cap=K)


def _observer_ring(n, K):
    sc = token_ring(n - 1, n_tokens=64, think_us=1_000,
                    bootstrap_us=1_000, with_observer=True,
                    mailbox_cap=K)
    assert not sc.commutative_inbox and sc.max_out == 2
    return sc


_WAVE_LINK = Quantize(UniformDelay(8_000, 30_000), 1_000)

#: inbox -> (scenario of n nodes and K slots, its drop-free link, the
#: mailbox that fits, the one that does not)
INBOX = {
    "commutative": (_burst, _WAVE_LINK, 24, 2),
    "commutative-two-words": (_wide_burst, _WAVE_LINK, 64, 40),
    "ordered": (_observer_ring, UniformDelay(1_000, 5_000), 96, 2),
}

#: call site -> (the link the engine gets, its keywords, whether
#: ``_route_adaptive`` is the routing tail)
SITE = {
    "adaptive": (lambda link: link, lambda sc: {}, True),
    "eager": (lambda link: WithDrop(link, 0.1), lambda sc: {}, False),
    "lazy": (lambda link: link,
             lambda sc: {"route_cap": sc.n_nodes * sc.max_out}, False),
}


@pytest.mark.parametrize("n", [1024, 1000], ids="n{}".format)
@pytest.mark.parametrize("mailbox", ["fits", "overflows"])
@pytest.mark.parametrize("inbox", sorted(INBOX))
@pytest.mark.parametrize("site", sorted(SITE))
def test_insertion_equals_oracle(site, inbox, mailbox, n):
    make, link, fits, small = INBOX[inbox]
    sc = make(n, fits if mailbox == "fits" else small)
    assert sc.commutative_inbox == inbox.startswith("commutative")
    relink, kw, adaptive = SITE[site]
    eng, orc = pair(sc, relink(link), window="auto", **kw(sc))
    assert eng.window > 1 and eng._adaptive_regime() == adaptive
    st = hold_to_oracle(f"{site}-{inbox}-{mailbox}-n{n}", eng, orc, (8, 8))
    assert int(st.delivered) > 64       # the load is there
    assert (int(st.overflow) > 0) == (mailbox == "overflows")
    if inbox == "commutative-two-words":
        assert -(-sc.mailbox_cap // 32) == 2
        if mailbox == "fits":
            # the second word was needed: some node holds a message
            # past row 31 while an earlier row of it is a hole again
            used = np.asarray(st.mb_rel) != I32MAX
            assert (used[32:].any(axis=0) & ~used[:32].all(axis=0)).any()


# ---------------------------------------------------------------------------
# the slot itself: the parent's program, slot for slot
# ---------------------------------------------------------------------------

def parent_insert_sorted(self, mb_rel, mb_src, mb_payload, sd, ok_s,
                         drel_s, src_s, pay_s, holes, counts):
    """``_insert_sorted``'s commutative branch as it stood at 5d73265
    (PR 30's form): the destination's hole words by one 1D gather a
    word on the message lanes, the rank-th set bit of them as the
    slot, flat scatters into the mailbox's own planes. The plain
    reference of what slot a message takes, and of what a hole that
    gets nothing keeps."""
    sc = self.scenario
    K, P = sc.mailbox_cap, sc.payload_width
    n = self.comm.n_local
    rank = group_rank(sd)
    sdc = jnp.clip(sd, 0, n - 1)
    prow = nth_set_bit([w[sdc] for w in holes], rank, K)
    fits = ok_s & (prow < K)
    col = jnp.clip(prow, 0, K - 1)
    flat = jnp.where(fits, col * jnp.int32(n) + sd, jnp.int32(K * n))
    mb_rel = mb_rel.reshape(-1).at[flat].set(
        drel_s, mode="drop").reshape(K, n)
    if sc.inbox_src:
        mb_src = mb_src.reshape(-1).at[flat].set(
            src_s, mode="drop").reshape(K, n)
    mb_payload = mb_payload.reshape(-1)
    for p in range(P):
        flat_p = jnp.where(
            fits, (col * jnp.int32(P) + p) * jnp.int32(n) + sd,
            jnp.int32(K * P * n))
        mb_payload = mb_payload.at[flat_p].set(pay_s[p], mode="drop")
    mb_payload = mb_payload.reshape(K, P, n)
    overflow = jnp.sum(ok_s & ~fits, dtype=jnp.int32)
    return mb_rel, mb_src, mb_payload, overflow


class ParentInsert(JaxEngine):
    """The engine with the parent's insertion at all three call sites:
    the eager and the lazy path call ``_insert_sorted``; a ladder rung
    calls ``_stage_by_rank`` and the nodes ``_fill_staged`` after the
    switch, so here the rung inserts into the mailbox ``_route_adaptive``
    was handed and the fill passes that through."""
    traced = 0

    def _insert_sorted(self, *a):
        type(self).traced += 1
        return parent_insert_sorted(self, *a)

    def _route_adaptive(self, out, out_valid, now_vec, t, mb_rel, mb_src,
                        mb_payload, holes, counts, *a):
        self._mailbox = (mb_rel, mb_src, mb_payload, holes, counts)
        return super()._route_adaptive(out, out_valid, now_vec, t, mb_rel,
                                       mb_src, mb_payload, holes, counts, *a)

    def _stage_by_rank(self, *lanes):
        return self._insert_sorted(*self._mailbox[:3], *lanes,
                                   *self._mailbox[3:]) + (jnp.int32(0),)

    def _fill_staged(self, mb_rel, mb_src, mb_payload, holes, *inserted):
        return inserted


def _steady(n, K):
    """One slot, ``window`` 1: ``_adaptive_regime()`` is false and the
    eager path inserts at full width (the steady cell's program)."""
    return gossip(n, fanout=1, think_us=1_000, gossip_interval=1_000,
                  end_us=200_000, steady=True, mailbox_cap=K)


_STEADY_LINK = Quantize(UniformDelay(1_000, 5_000), 1_000)

#: id -> (scenario, link, engine keywords, the two horizons, whether
#: ``overflow`` must be positive at the second)
SLOT_CASES = {
    "one-word": (lambda: _burst(1024, 24), _WAVE_LINK,
                 {"window": "auto"}, (10, 6), False),
    "two-words": (lambda: _wide_burst(1024, 40), _WAVE_LINK,
                  {"window": "auto"}, (10, 6), True),
    "n1000": (lambda: _burst(1000, 24), _WAVE_LINK,
              {"window": "auto"}, (10, 6), False),
    "holes-fewer-than-arrivals": (lambda: _burst(1024, 6), _WAVE_LINK,
                                  {"window": "auto"}, (10, 6), True),
    "fleet-of-three": (lambda: _burst(1024, 24), _WAVE_LINK,
                       {"window": "auto",
                        "batch": BatchSpec(seeds=(0, 4, 9))},
                       (10, 6), False),
    "eager": (lambda: _steady(1024, 24), _STEADY_LINK, {}, (24, 24),
              False),
    # 20 000 nodes, fanout 4: the ladder's first rung (1024 senders,
    # 4096 lanes, under a quarter of the nodes) stages by scatters,
    # the rung of 8192 senders in the dense form; delays inside one
    # window, a generation every other superstep: those of 1, 4, ...
    # 964 senders take the first rung, the next two (3284, 7347) the
    # rungs of 4096 and 8192
    "ladder-of-both-stagings": (
        lambda: gossip(20_000, fanout=4, think_us=2_000, burst=True,
                       end_us=1_000_000, mailbox_cap=24),
        Quantize(UniformDelay(8_000, 9_000), 1_000),
        {"window": "auto"}, (16, 4), False),
    "eager-overflows": (lambda: _steady(1000, 3), _STEADY_LINK, {},
                        (24, 24), True),
}


@pytest.mark.parametrize("case", list(SLOT_CASES))
def test_every_slot_is_the_one_the_parent_gave(case):
    """Bit-equal, slot for slot: the raw mailbox arrays (holes' stale
    words included) and every other leaf of the state, at two
    horizons, against the engine that inserts the parent's way
    (``ParentInsert``): on the ladder and on the eager path, with and
    without overflow; and a fleet, which keeps the parent's form in
    the program too (``_stages_by_rank``) and so is held to this
    file's copy of it."""
    make, link, kw, horizons, overflows = SLOT_CASES[case]
    sc = make()
    eng = JaxEngine(sc, link, lint="off", **kw)
    assert eng._adaptive_regime() == ("window" in kw)
    assert eng._stages_by_rank() == ("batch" not in kw)
    states = []
    st = eng.init_state()
    for k in horizons:
        st, _ = eng.run(k, st)
        states.append(jax.device_get(st))
    before = ParentInsert.traced
    ref = ParentInsert(sc, link, lint="off", **kw)
    st = ref.init_state()
    for k, want in zip(horizons, states):
        st, _ = ref.run(k, st)
        got = jax.device_get(st)
        for name, a, b in zip(got._fields, got, want):
            for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
                assert np.array_equal(x, y), (name, k)
    assert ParentInsert.traced > before, "the parent's form never ran"
    assert (np.asarray(want.mb_rel) != I32MAX).sum() > sc.n_nodes
    assert (int(np.max(want.overflow)) > 0) == overflows
    if case == "ladder-of-both-stagings":
        eng.run_quiet(sum(horizons))
        stats = eng.last_run_stats
        dense = [eng._stages_dense(a * sc.max_out)
                 for a in eng._sender_rungs(sc.n_nodes)]
        assert not dense[0] and dense[3] and stats["rung_steps"][0] > 0
        assert 0 < stats["dense_stage_steps"] == sum(
            k for k, d in zip(stats["rung_steps"], dense) if d) \
            < stats["supersteps"]


# -- one call of the insertion, on lanes built for it ----------------------

def _lanes(n, K, P, S, seed):
    """A mailbox and one superstep's sorted arrivals with every case
    of the overflow in it: node 1 gets ``K + 5`` arrivals (ranks past
    K: no mailbox holds them), node 2 has two holes and five arrivals
    (fewer holes than arrivals, more than none), node 3 no hole and
    three arrivals, node 4 all holes and exactly K arrivals, node 5
    holes and nothing; every other node 0-3 arrivals into a random
    mailbox. ``S`` lanes, the invalid ones (row ``n``) last."""
    rng = np.random.default_rng(seed)
    keep = rng.random((K, n)) < rng.random((1, n))
    keep[:, 1] = rng.random(K) < 0.5
    keep[:, 2] = True
    keep[rng.choice(K, 2, replace=False), 2] = False
    keep[:, 3] = True
    keep[:, 4] = False
    keep[:, 5] = rng.random(K) < 0.5
    arrivals = rng.integers(0, 4, n)
    arrivals[1:6] = (K + 5, 5, 3, K, 0)
    sd = np.repeat(np.arange(n), arrivals)[:S]
    sd = np.concatenate([sd, np.full(S - len(sd), n)]).astype(np.int32)
    mb_rel = np.where(keep, rng.integers(0, 10**6, (K, n)),
                      I32MAX).astype(np.int32)
    i32 = lambda *shape: rng.integers(-2**31, 2**31, shape).astype(np.int32)
    return (mb_rel, i32(K, n), i32(K, P, n), sd, sd < n,
            rng.integers(0, 10**6, S).astype(np.int32), i32(S),
            tuple(i32(S) for _ in range(P)), keep)


@pytest.mark.parametrize("inbox_src", [False, True], ids=["nosrc", "src"])
@pytest.mark.parametrize("P", [1, 2], ids="P{}".format)
@pytest.mark.parametrize("n", [1024, 1000], ids="n{}".format)
@pytest.mark.parametrize("K", [24, 40], ids=["one-word", "two-words"])
def test_one_insertion_equals_the_parents(K, n, P, inbox_src):
    """``_insert_sorted`` on built lanes against the parent's form:
    the three planes bit-equal and ``overflow`` the same number, which
    here is known: the arrivals past each node's holes."""
    import dataclasses
    from timewarp_tpu.ops.numeric import free_bits
    sc = dataclasses.replace(_burst(n, K), payload_width=P,
                             inbox_src=inbox_src)
    eng = JaxEngine(sc, _WAVE_LINK, window="auto", lint="off")
    *lanes, keep = _lanes(n, K, P, 2 * n, seed=K + n + P)
    sd = lanes[3]
    arrivals = np.bincount(sd[sd < n], minlength=n)
    lost = np.maximum(arrivals - (~keep).sum(axis=0), 0)
    assert lost[1] >= 5 and lost[2] == 3 and lost[3] == 3 and lost[4] == 0

    def both(*lanes):
        holes = free_bits(jnp.asarray(keep))
        return (eng._insert_sorted(*lanes, holes, None),
                parent_insert_sorted(eng, *lanes, holes, None))
    got, want = jax.jit(both)(*lanes)
    for name, x, y in zip(("mb_rel", "mb_src", "mb_payload", "overflow"),
                          got, want):
        assert np.array_equal(x, y), name
    assert int(got[3]) == lost.sum() > 0
    assert (np.asarray(got[1]) == lanes[1]).all() == (not inbox_src)


# -- the two forms of staging by rank ---------------------------------------

def parent_stage_by_rank(self, sd, ok_s, drel_s, src_s, pay_s):
    """``_stage_by_rank`` as it stood at 1ac92c9 (PR 32's form, the
    one the program keeps where the lanes are few for the nodes): a
    flat 1D scatter a field into fresh buffers, the indices as they
    come."""
    sc = self.scenario
    K, P = sc.mailbox_cap, sc.payload_width
    n = self.comm.n_local
    rank = group_rank(sd)
    fits = ok_s & (rank < K)
    flat = jnp.where(fits, rank * jnp.int32(n) + sd,
                     jnp.int32(K * n))

    def stage(x, nothing):
        return jnp.full((K * n,), nothing, x.dtype).at[flat].set(
            x, mode="drop")
    rel = stage(drel_s, I32MAX)
    src = stage(src_s, 0) if sc.inbox_src else None
    pay = tuple(stage(pay_s[p], 0) for p in range(P))
    over = jnp.sum(ok_s & (rank >= K), dtype=jnp.int32)
    return rel, src, pay, over


def _destinations(case, n, K, rng):
    """The valid lanes' destinations (unsorted) and the lane count."""
    uniform = lambda m: rng.integers(0, n, m)
    least = math.ceil(_DENSE_STAGE_RATIO * n)   # the dense form's lanes
    if case == "uniform":
        return uniform(n), n
    if case == "skewed":
        # the fourth power of a uniform draw: a few low nodes take
        # most, node 0 a sixth of the lanes (far past K)
        return (n * rng.random(n) ** 4).astype(np.int64), n
    if case == "more-than-K-at-one-node":
        return np.concatenate([np.full(K + 5, 7), uniform(n - K - 5)]), n
    if case == "invalid-lanes":
        return uniform(n // 2), 2 * n
    if case == "wide-tail":
        # three arrivals at every third node: two thirds are the tail
        return np.repeat(np.arange(0, n, 3)[:n // 3], 3), n
    if case == "rung-of-four-lanes-a-node":
        return uniform(4 * n - 11), 4 * n
    if case == "clamped-slice":
        # one arrival at every node: no tail, and a slice at the
        # rank-0 count would start at the lanes' end
        return rng.permutation(n), n
    if case == "nothing-valid":
        return uniform(0), n
    if case == "under-the-threshold":
        return uniform(least // 2 - 3), least // 2
    if case == "just-under-the-threshold":
        return uniform(least - 1), least - 1
    if case == "at-the-threshold":
        return uniform(least), least
    raise KeyError(case)


#: case -> whether the dense form takes it, and its tail at full width
STAGINGS = {
    "uniform": (True, False), "skewed": (True, False),
    "more-than-K-at-one-node": (True, False),
    "invalid-lanes": (True, False), "wide-tail": (True, True),
    "rung-of-four-lanes-a-node": (True, True),
    "clamped-slice": (True, False), "nothing-valid": (True, False),
    "under-the-threshold": (False, False),
    "just-under-the-threshold": (False, False),
    "at-the-threshold": (True, False),
}


@functools.lru_cache(maxsize=None)
def _staging_engine(n, K, P, inbox_src):
    import dataclasses
    sc = dataclasses.replace(_burst(n, K), payload_width=P,
                             inbox_src=inbox_src)
    return JaxEngine(sc, _WAVE_LINK, window="auto", lint="off")


def _staging_lanes(case, n, K, P):
    rng = np.random.default_rng(len(case) * 1000 + n + P)
    dst, L = _destinations(case, n, K, rng)
    sd = np.concatenate([np.sort(dst), np.full(L - len(dst), n)]
                        ).astype(np.int32)
    i32 = lambda: rng.integers(-2**31, 2**31, L).astype(np.int32)
    return (sd, sd < n, rng.integers(0, 10**6, L).astype(np.int32),
            i32(), tuple(i32() for _ in range(P)))


@pytest.mark.parametrize("inbox_src", [False, True], ids=["nosrc", "src"])
@pytest.mark.parametrize("P", [1, 2], ids="P{}".format)
@pytest.mark.parametrize("n", [1024, 1000], ids="n{}".format)
@pytest.mark.parametrize("case", list(STAGINGS))
def test_one_staging_equals_the_scatters(case, n, P, inbox_src):
    """``_stage_by_rank`` on built lanes, in whichever form the lane
    count selects, against a scatter a field: every staged buffer word
    for word, ``over`` the same number, and ``wide`` what the lanes
    say (the arrivals of rank 1 and over against half the lanes)."""
    K = 24
    eng = _staging_engine(n, K, P, inbox_src)
    lanes = _staging_lanes(case, n, K, P)
    sd, ok = lanes[0], lanes[1]
    dense, wide = STAGINGS[case]
    assert eng._stages_dense(len(sd)) == dense
    rank = np.asarray(group_rank(jnp.asarray(sd)))
    fits = ok & (rank < K)
    tail = int((fits & (rank > 0)).sum())
    assert (tail > len(sd) // 2) == wide
    *got, got_wide = jax.jit(eng._stage_by_rank)(*lanes)
    want = jax.jit(functools.partial(parent_stage_by_rank, eng))(*lanes)
    assert int(got_wide) == (dense and wide)
    assert (got[1] is None) == (not inbox_src) and len(got[2]) == P
    for name, x, y in zip(("rel", "src", "pay", "over"), got, want):
        for a, b in zip(jax.tree.leaves(x), jax.tree.leaves(y)):
            assert np.array_equal(a, b), (case, name)
    assert int(got[3]) == int((ok & (rank >= K)).sum())
    assert (int(got[3]) > 0) == (
        case in ("more-than-K-at-one-node", "skewed"))
    assert int((np.asarray(got[0]) != I32MAX).sum()) == int(fits.sum())


def _staging_text(fn, n, L, P):
    lane = jax.ShapeDtypeStruct((L,), np.int32)
    return jax.jit(fn).lower(
        lane, jax.ShapeDtypeStruct((L,), bool), lane, lane,
        (lane,) * P).as_text()


def test_the_dense_form_declares_every_scatter_sorted():
    """One sort, the program's own, and every scatter after it with
    ``indices_are_sorted`` and ``unique_indices``: the flags are what
    keep the compiler from sorting ``(indices, updates)`` again in
    front of each scatter (docs/engines.md "Random delivery")."""
    import re
    n, P = 1024, 2
    eng = _staging_engine(n, 24, P, True)
    text = _staging_text(eng._stage_by_rank, n, 2 * n, P)
    assert len(re.findall(r"stablehlo\.sort", text)) == 1
    scatters = re.findall(r'"stablehlo\.scatter".*?<\{(.*?)\}>', text,
                          flags=re.S)
    # a field a branch of the tail's conditional
    assert len(scatters) == 2 * (2 + P)
    for attrs in scatters:
        assert "indices_are_sorted = true" in attrs, attrs
        assert "unique_indices = true" in attrs, attrs
    assert "stablehlo.gather" not in text


def test_under_the_threshold_staging_lowers_to_the_parents_text():
    """Few lanes for the nodes: the scatters, as the parent lowered
    them, operation for operation."""
    n, P = 1024, 2
    eng = _staging_engine(n, 24, P, True)
    few = math.ceil(_DENSE_STAGE_RATIO * n) // 2
    assert not eng._stages_dense(few)
    text = _staging_text(lambda *a: eng._stage_by_rank(*a)[:4], n, few, P)
    assert text == _staging_text(
        lambda *a: parent_stage_by_rank(eng, *a), n, few, P)
    assert "stablehlo.sort" not in text


# -- the ranked insertion, cut to the prefix that can land -------------------

_PATCHED_LANES = 64


def _ordered_lanes(case, n, K, P, L, rng):
    """A mailbox with its kept messages closed up (``counts`` a node)
    and ``L`` destination-sorted lanes, the invalid ones (row ``n``)
    last; ``K`` is 8. Returns the insertion's operands."""
    counts = rng.integers(0, K // 2, n)
    if case == "nothing-fits":
        # every arrival at a node whose slots are all kept
        dst = np.sort(rng.integers(0, n // 2, L // 3))
        counts[:n // 2] = K
    elif case == "edge-of-a-width":
        dst = np.arange(L // 4)          # the last fitting lane: L/4 - 1
        counts[:] = 0
    elif case == "one-past-the-edge":
        dst = np.arange(L // 4 + 1)
        counts[:] = 0
    elif case == "every-lane-fits":
        dst = np.arange(L) // 2          # two a node, no lane invalid
        counts[:] = 0
    elif case == "one-hub-takes-every-lane":
        dst = np.full(L, n - 1)          # the cell's notes: 8 of L fit
        counts[n - 1] = 0
    elif case == "overloaded-between-fitting":
        # every seventh node gets K + 3 arrivals on top of what it
        # keeps, the others one or two: lanes that do not fit all
        # along a prefix that ends near the last valid lane
        per = np.where(np.arange(n) % 7 == 3, K + 3, rng.integers(1, 3, n))
        dst = np.repeat(np.arange(n), per)[:L - 5]
    else:
        raise KeyError(case)
    sd = np.concatenate([dst, np.full(L - len(dst), n)]).astype(np.int32)
    kept = np.arange(K)[:, None] < counts[None, :]
    mb_rel = np.where(kept, rng.integers(0, 10**6, (K, n)),
                      I32MAX).astype(np.int32)
    i32 = lambda *shape: rng.integers(-2**31, 2**31, shape).astype(np.int32)
    return (mb_rel, i32(K, n), i32(K, P, n), sd, sd < n,
            rng.integers(0, 10**6, L).astype(np.int32), i32(L),
            tuple(i32(L) for _ in range(P)), counts.astype(np.int32))


def plain_ranked_insertion(K, P, mb_rel, mb_src, mb_payload, sd, ok,
                           drel, src, pay, counts):
    """An ordered inbox's insertion, lane by lane: a valid lane takes
    the slot after its destination's kept messages and earlier
    arrivals, or is counted. Returns the planes, ``overflow``, the
    largest fan-in and the lane after the last that landed."""
    mb_rel, mb_src, mb_payload = (np.array(x) for x in
                                  (mb_rel, mb_src, mb_payload))
    used, over, hi = counts.astype(np.int64), 0, 0
    for lane in np.flatnonzero(ok):
        d = sd[lane]
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
    return mb_rel, mb_src, mb_payload, over, fan_in, hi


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
PREFIXES = {"nothing-fits": 0, "edge-of-a-width": 1,
            "one-past-the-edge": 2, "every-lane-fits": 3,
            "one-hub-takes-every-lane": 0,
            "overloaded-between-fitting": 3}


@pytest.mark.parametrize("P", [1, 2], ids="P{}".format)
@pytest.mark.parametrize("n", [1024, 1000], ids="n{}".format)
@pytest.mark.parametrize("case", list(PREFIXES))
def test_the_prefix_scatters_equal_the_one_scatter_word_for_word(case, n, P):
    """One call of the ranked insertion cut to its prefix, against the
    one-scatter form and a plain insertion in numpy: every plane,
    ``overflow`` and the fan-in the same, and the width taken the
    smallest of the four that holds the last lane that fits."""
    K, L = 8, 2 * n
    eng, cut, one = _ranked_insertions(n, P)
    lanes = _ordered_lanes(case, n, K, P, L,
                           np.random.default_rng(len(case) + n + P))
    got, ref = cut(*lanes), one(*lanes)
    *want, hi = plain_ranked_insertion(K, P, *lanes)
    widths = [-(-L // d) for d in (8, 4, 2, 1)]
    assert int(got[5]) == min(w for w in widths if w >= hi) \
        == widths[PREFIXES[case]]
    assert int(ref[5]) == L
    for name, x, y, z in zip(("mb_rel", "mb_src", "mb_payload", "overflow",
                              "fan_in"), got, ref, want):
        assert np.array_equal(x, y) and np.array_equal(x, z), (case, name)
    if case == "nothing-fits":
        assert hi == 0 and int(got[3]) == L // 3
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


# ---------------------------------------------------------------------------
# shapes the matrix does not have
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [1024, 1000], ids="n{}".format)
def test_praos_needs_key_payload2_equals_oracle(n):
    """Leadership draws from the firing key, payload width 2, slot
    timers and diffusion bursts under the lognormal link's 8 ms
    window: at 2 supersteps and at 12."""
    sc = praos(n, slot_us=100_000, n_slots=30, leader_prob=4.0 / n,
               fanout=8, burst=True, mailbox_cap=8)
    assert sc.needs_key and sc.payload_width == 2
    link = Quantize(LogNormalDelay(20_000, 0.6, cap_us=150_000,
                                   floor_us=8_000), 1_000)
    eng, orc = pair(sc, link, window="auto")
    st = hold_to_oracle(f"praos-n{n}", eng, orc, (2, 10))
    assert int(st.delivered) > n


def test_socket_state_hub_fan_in_equals_oracle():
    """1023 clients into the server's one mailbox: ranks far past the
    mailbox's depth at one destination. Every scheduled ping is
    delivered or counted in ``overflow``, as the oracle has it."""
    sc = socket_state(n_clients=1023, seed=1, send_interval_us=20_000,
                      server_life_us=2_000_000, mailbox_cap=64)
    link = Quantize(UniformDelay(3_000, 9_000), 1_000)
    eng, orc = pair(sc, link, window=3_000)
    st = hold_to_oracle("socket-hub", eng, orc, (32, 32))
    assert int(st.overflow) > 1023 - 64


# -- the two-world faulted fleet ------------------------------------------

_FLEET_SEEDS = (0, 1)


@functools.lru_cache(maxsize=None)
def _faulted_fleet():
    """One run of the fleet for both of its cases: the states at the
    two horizons and the per-world traces between them."""
    n, half = 1024, 512
    fleet = FaultFleet(tuple(
        FaultSchedule((
            NodeCrash((7 * b + 3) % n, 20_000, 60_000 + 5_000 * b,
                      reset_state=True),
            Partition((tuple(range(half)), tuple(range(half, n))),
                      25_000, 70_000 + 2_000 * b),
        )) for b in range(len(_FLEET_SEEDS))))
    sc = gossip(n, fanout=1, think_us=1_000, gossip_interval=1_000,
                end_us=200_000, steady=True, mailbox_cap=8)
    link = Quantize(UniformDelay(500, 4_500), 1_000)
    eng = JaxEngine(sc, link, window="auto", lint="off", faults=fleet,
                    batch=BatchSpec(seeds=_FLEET_SEEDS))
    st, runs = eng.init_state(), []
    for k in (8, 32):
        st, trs = eng.run(k, st)
        runs.append((k, st, trs))
    return sc, link, fleet, eng.window, runs


@pytest.mark.parametrize("b", range(len(_FLEET_SEEDS)), ids="world{}".format)
def test_faulted_fleet_world_equals_solo_oracle(b):
    """World b of a fleet under per-world crashes (with state loss)
    and partitions, sliced out of the batched state, against the solo
    oracle with that world's seed under ``fleet.world_schedule(b)``:
    the insertion runs under ``vmap`` with every fault mask around
    it."""
    sc, link, fleet, window, runs = _faulted_fleet()
    orc = SuperstepOracle(sc, link, seed=_FLEET_SEEDS[b], lint="off",
                          window=window, faults=fleet.world_schedule(b))
    for k, st, trs in runs:
        oracle_catches_up(f"world{b}", orc, k, world_slice(st, b), trs[b])
    assert orc.fault_dropped_total > 0, "the schedule never bit"


# -- the ladder's rungs ----------------------------------------------------

@functools.lru_cache(maxsize=None)
def _ladder():
    """A synchronized wave at 4096 nodes (fanout 8, delays inside one
    window): generations of 1, 8, 64, 470 senders, then 2127, then
    1336. The engine with telemetry on, and the rung it took at every
    superstep."""
    sc = gossip(4096, fanout=8, think_us=2_000, burst=True,
                end_us=1_000_000, mailbox_cap=24)
    link = Quantize(UniformDelay(8_000, 9_000), 1_000)
    eng = JaxEngine(sc, link, window="auto", telemetry="counters",
                    lint="off")
    eng.run(16)
    fr = eng.last_run_telemetry
    return (sc, link, eng, fr.data["rung"].tolist(),
            fr.data["active_senders"].tolist())


@pytest.mark.parametrize("which", ["first", "middle", "top"])
def test_every_rung_equals_oracle(which):
    """``_route_adaptive`` runs ``_insert_sorted`` at the rung's
    width, so each width is its own compiled insertion. For the
    ladder's first, a middle and its top rung: the busiest superstep
    that took it (telemetry's ``rung`` column says which did), and the
    state right after that superstep against the oracle's."""
    sc, link, eng, rungs, senders = _ladder()
    ladder = eng._sender_rungs(sc.n_nodes)
    assert ladder == [1024, 2048, 4096]
    want = ladder[{"first": 0, "middle": 1, "top": -1}[which]]
    took = [i for i, r in enumerate(rungs) if r == want]
    assert took, f"no superstep took rung {want}: {rungs}"
    k = max(took, key=lambda i: senders[i])
    below = ladder[ladder.index(want) - 1] if want != ladder[0] else 0
    assert below < senders[k] <= want
    orc = SuperstepOracle(sc, link, lint="off", window=eng.window)
    st = hold_to_oracle(f"rung-{want}", eng, orc, (k + 1,))
    assert eng.last_run_telemetry.data["rung"].tolist()[k] == want
    assert int(st.overflow) == 0


# -- a checkpoint, from solo runs to a fleet -------------------------------

def test_checkpoint_from_solo_runs_resumes_in_a_fleet(tmp_path):
    """``EngineState`` is the same pytree solo and batched but for the
    world axis: two solo runs' checkpoints, stacked, are a fleet's
    state, and the fleet resumes each world to where the oracle of
    that world's seed gets in one run."""
    from timewarp_tpu.utils.checkpoint import load_state, save_state
    seeds = (3, 5)
    sc = _burst(1024, 24)
    link = INBOX["commutative"][1]
    loaded, oracles = [], []
    for s in seeds:
        eng, orc = pair(sc, link, seed=s, window="auto")
        mid = hold_to_oracle(f"solo-{s}", eng, orc, (8,))
        path = str(tmp_path / f"seed{s}.npz")
        save_state(path, mid, meta={"scenario": sc.name})
        got, _ = load_state(path, eng.init_state(),
                            expect_meta={"scenario": sc.name})
        loaded.append(jax.device_get(got))
        oracles.append(orc)
    fleet = JaxEngine(sc, link, window="auto", lint="off",
                      batch=BatchSpec(seeds=seeds))
    st = jax.tree.map(lambda *xs: np.stack(xs), *loaded)
    st, trs = fleet.run(8, st)
    for b, orc in enumerate(oracles):
        oracle_catches_up(f"resumed-seed{seeds[b]}", orc, 8,
                          world_slice(st, b), trs[b])


# ---------------------------------------------------------------------------
# what is left of the selection: refusals
# ---------------------------------------------------------------------------

def _small():
    return _burst(64, 8), INBOX["commutative"][1]


def _driver_jaxpr(eng) -> str:
    return str(jax.make_jaxpr(lambda s: eng._step_all(s, True))(
        eng.init_state()))


@pytest.mark.parametrize("mode", ["pallas", "interpret", "xla2d"])
def test_removed_insert_modes_are_refused_in_one_line(mode):
    with pytest.raises(ValueError, match="removed in PR 29") as ei:
        JaxEngine(*_small(), insert=mode)
    assert "\n" not in str(ei.value) and "193bc01" in str(ei.value)


def test_insert_xla_is_the_default_engine():
    """``insert="xla"`` is accepted because the benchmark's builders
    pass it (ROADMAP D2'): the same attributes, the same program."""
    sc, link = _small()
    a = JaxEngine(sc, link, window="auto", lint="off")
    b = JaxEngine(sc, link, window="auto", lint="off", insert="xla")
    assert sorted(vars(a)) == sorted(vars(b))
    assert not [k for k in vars(b) if "insert" in k]
    assert _driver_jaxpr(a) == _driver_jaxpr(b)


@pytest.mark.parametrize("var,value", [("TW_INSERT", "interpret"),
                                       ("TW_INSERT", "xla2d"),
                                       ("TW_FLAT_SCATTER", "0")])
def test_the_environment_selects_nothing(monkeypatch, var, value):
    sc, link = _small()
    for v in ("TW_INSERT", "TW_FLAT_SCATTER"):
        monkeypatch.delenv(v, raising=False)
    base = _driver_jaxpr(JaxEngine(sc, link, window="auto", lint="off"))
    monkeypatch.setenv(var, value)
    assert os.environ[var] == value
    assert _driver_jaxpr(JaxEngine(sc, link, window="auto",
                                   lint="off")) == base


@pytest.mark.parametrize("argv", [
    ["--engine", "fused-sparse"], ["--engine", "sharded-fused"],
    ["--insert", "xla"], ["--insert-cap", "64"], ["--max-batch", "64"]],
    ids=lambda a: a[0].lstrip("-") + "-" + a[1])
def test_cli_refuses_what_went(argv, capsys):
    from timewarp_tpu.cli import main
    with pytest.raises(SystemExit) as ei:
        main(["gossip", "--nodes", "64", "--steps", "4", *argv])
    assert ei.value.code == 2           # argparse's usage error
    err = capsys.readouterr().err
    assert "invalid choice" in err or "unrecognized arguments" in err
