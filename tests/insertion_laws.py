"""What the mailbox-insertion laws share: no test lives here.

``_insert_sorted`` (engine.py) is the one insertion form, reached from
the two call sites of the superstep's routing stage (the ladder's rungs
and the eager path). The laws run the engine and the host oracle side
by side and compare the *state* at two horizons (every node's scenario
state and wake time, the clock, the mailbox contents message by
message, the never-silent counters) and the trace over both. The state comparison is what the trace laws elsewhere
do not make: a message in the wrong slot, or a payload word scattered
to a neighbour, shows in the mailbox one superstep before it shows in
a digest.

Here: the one view of a state from either side (``View``,
``engine_view``, ``oracle_view``) and the comparisons over it
(``oracle_catches_up``, ``hold_to_oracle``, ``pair``); the scenarios,
links and call sites of the matrix (``INBOX``, ``SITE``); the parent's
forms kept as references (``parent_insert_sorted`` and ``ParentInsert``,
PR 30's insertion; ``parent_stage_by_rank``, PR 32's staging); and the
builders of lanes for one call of the insertion or the staging.

The laws, a file a section:

- tests/test_insert_oracle_adaptive.py, ``_eager.py``: call site x
  inbox x mailbox x n against the oracle, a file a call site;
- tests/test_insert_slot_law.py, ``_slot_both_stagings.py``,
  ``_slot_on_lanes.py``: the slot itself, against the parent's
  program and on built lanes;
- tests/test_insert_staging_law.py: the two forms of staging by rank;
- tests/test_insert_prefix_law.py: an ordered inbox's ranked insertion
  cut to the prefix that can land (PR 43);
- tests/test_insert_scenarios.py: praos, the socket hub, a faulted
  fleet, the ladder's rungs, a checkpoint, and the refusals left of the
  ``insert=`` selection;
- tests/test_stage_tail_law.py: the dense staging's tail (PR 44).

Left out as covered: small-n trace parity of the token ring, ping-pong
and invalid destinations (test_parity.py); windowed against classic
semantics and the sharded forms (test_windowed.py); mixed fault
schedules on the solo engines and the fleet-slice-against-solo-*engine*
law (test_zfault_parity.py,
test_world_batch.py, whose reference is another engine configuration,
never the oracle at these widths).
"""

import math
from typing import Any, NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

from timewarp_tpu.core.scenario import NEVER
from timewarp_tpu.interp.jax_engine.batched import BatchSpec
from timewarp_tpu.interp.jax_engine.common import I32MAX, group_rank
from timewarp_tpu.interp.jax_engine.engine import (_DENSE_STAGE_RATIO,
                                                   JaxEngine)
from timewarp_tpu.interp.ref.superstep import SuperstepOracle
from timewarp_tpu.models.gossip import gossip
from timewarp_tpu.models.token_ring import token_ring
from timewarp_tpu.net.delays import Quantize, UniformDelay, WithDrop
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
# the scenarios and the call sites
# ---------------------------------------------------------------------------

def _burst(n, K):
    return gossip(n, fanout=8, think_us=2_000, burst=True,
                  end_us=1_000_000, mailbox_cap=K)


def _wide_burst(n, K):
    """Fanout 30: up to 43 messages pending at one node of 1024 (41 of
    1000; 40 and 41 under the eager site's drops), so a mailbox's
    holes past row 31 (its second uint32 word of free slots, PR 30)
    are taken. No wider than that takes: the program's size, and with
    it XLA:CPU's time to compile it, follows the fanout and the
    mailbox's rows."""
    return gossip(n, fanout=30, think_us=2_000, burst=True,
                  end_us=1_000_000, mailbox_cap=K)


def _observer_ring(n, K):
    sc = token_ring(n - 1, n_tokens=64, think_us=1_000,
                    bootstrap_us=1_000, with_observer=True,
                    mailbox_cap=K)
    assert not sc.commutative_inbox and sc.max_out == 2
    return sc


def _steady(n, K):
    """One slot, ``window`` 1: ``_adaptive_regime()`` is false and the
    eager path inserts at full width (the steady cell's program)."""
    return gossip(n, fanout=1, think_us=1_000, gossip_interval=1_000,
                  end_us=200_000, steady=True, mailbox_cap=K)


_STEADY_LINK = Quantize(UniformDelay(1_000, 5_000), 1_000)

_WAVE_LINK = Quantize(UniformDelay(8_000, 30_000), 1_000)

#: inbox -> (scenario of n nodes and K slots, its drop-free link, the
#: mailbox that fits, the one that does not)
INBOX = {
    "commutative": (_burst, _WAVE_LINK, 24, 2),
    "commutative-two-words": (_wide_burst, _WAVE_LINK, 48, 36),
    "ordered": (_observer_ring, UniformDelay(1_000, 5_000), 96, 2),
}

#: call site -> (the link the engine gets, its keywords, whether
#: ``_route_adaptive`` is the routing tail)
SITE = {
    "adaptive": (lambda link: link, lambda sc: {}, True),
    "eager": (lambda link: WithDrop(link, 0.1), lambda sc: {}, False),
}


# ---------------------------------------------------------------------------
# the matrix: call site x inbox x mailbox x n
# ---------------------------------------------------------------------------
#
# The matrix is the one the removed kernel tests walked against the XLA
# form (PR 29; they are at 193bc01), walked against the oracle instead:
#
# - call site: ``adaptive`` (windowed, drop-free link: the ladder's
#   tail), ``eager`` (a ``WithDrop`` link);
# - inbox: commutative (the gossip burst: the r-th message takes the
#   destination's r-th hole; and the same at two words of holes, below)
#   and ordered (the observer token ring,
#   ``max_out`` 2: append after the kept messages);
# - mailbox: fits, and too small for the fan-in (``overflow`` > 0 is
#   asserted, and the surviving messages must still be the oracle's);
# - n: 1024, and 1000 (a width that is no multiple of a lane or a tile).
#
# Since PR 30 a commutative inbox's holes are ``ceil(K/32)`` uint32
# words a node, and since PR 32 its arrivals are staged by rank and
# every node fills its holes from them (ops/numeric.py ``fill_holes``;
# tests/test_free_bits.py is the primitive's own law). So the matrix
# has a third inbox, a wave whose fan-in passes 32 into mailboxes of two
# words, fitting and not (``INBOX`` has the sizes).

def insertion_equals_oracle(site, inbox, mailbox, n):
    """One case of the matrix (tests/test_insert_oracle_adaptive.py
    and ``_eager.py``: a file a call site)."""
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
# the parent's forms, kept as references
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
    """The engine with the parent's insertion at both call sites:
    the eager path calls ``_insert_sorted``; a ladder rung
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


# ---------------------------------------------------------------------------
# the slot itself: whole runs replayed the parent's way
# ---------------------------------------------------------------------------

#: id -> (scenario, link, engine keywords, the two horizons, whether
#: ``overflow`` must be positive at the second). Two horizons of one
#: length are one scan program an engine (``run`` compiles a power of
#: two of supersteps), and every case here compiles two engines
SLOT_CASES = {
    "one-word": (lambda: _burst(1024, 24), _WAVE_LINK,
                 {"window": "auto"}, (8, 8), False),
    "two-words": (lambda: _wide_burst(1024, 36), _WAVE_LINK,
                  {"window": "auto"}, (8, 8), True),
    "n1000": (lambda: _burst(1000, 24), _WAVE_LINK,
              {"window": "auto"}, (8, 8), False),
    "holes-fewer-than-arrivals": (lambda: _burst(1024, 6), _WAVE_LINK,
                                  {"window": "auto"}, (8, 8), True),
    "fleet-of-three": (lambda: _burst(1024, 24), _WAVE_LINK,
                       {"window": "auto",
                        "batch": BatchSpec(seeds=(0, 4, 9))},
                       (8, 8), False),
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


def every_slot_is_the_one_the_parent_gave(case):
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


# ---------------------------------------------------------------------------
# lanes built for one call
# ---------------------------------------------------------------------------

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


def _staging_lanes(case, n, K, P):
    rng = np.random.default_rng(len(case) * 1000 + n + P)
    dst, L = _destinations(case, n, K, rng)
    sd = np.concatenate([np.sort(dst), np.full(L - len(dst), n)]
                        ).astype(np.int32)
    i32 = lambda: rng.integers(-2**31, 2**31, L).astype(np.int32)
    return (sd, sd < n, rng.integers(0, 10**6, L).astype(np.int32),
            i32(), tuple(i32() for _ in range(P)))


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
    elif case == "full-under-arrivals-past-an-edge":
        # one arrival a node to one lane past L/4, every mailbox full
        # but the first five: what lands ends at lane 5, what the
        # ranks allow at L/4 + 1
        dst = np.arange(L // 4 + 1)
        counts[5:] = K
    elif case == "hub-rank-on-an-edge":
        # one arrival a node, then L/2 to a hub that keeps 3 of its 8
        # slots: its rank K - 1 is lane L/4 - 1, the edge of a width
        dst = np.concatenate([np.arange(L // 4 - K),
                              np.full(L // 2, n - 1)])
        counts[:] = 0
        counts[n - 1] = 3
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
