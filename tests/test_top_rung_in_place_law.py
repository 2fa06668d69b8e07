"""The ladder's top rung reads the outbox where it lies (PR 56): at
``A == n`` ``_route_adaptive``'s ``gather`` takes no sender gather, the
lanes are the node lanes themselves. Here that form, reached as every
engine of at most 1 024 nodes reaches it (one rung, the top one), is
held to a plain numpy compaction of the same outbox: the live senders
ascending, their lanes slot-major, each message sampled, degraded and
dropped by the elementwise specification (``link.sample``,
``faults/apply.py`` ``cut_mask``, ``degrade``, ``down_mask``), sorted
by (destination, in-window offset, sender-major rank). Word for word
after the sort on every ``ok_s`` lane, and in every count the rung
returns, over 0, 1, n/2, n - 1 and n live senders; solo and a fleet of
three; plain and faulted in both shapes ``_fault_reads`` gives the
look-up (before the compaction with the verdicts riding the
destination, inside the rung with the sender's bits riding its
offset). One more case holds ``record="full"``'s captured send rows of
a faulted run, the one reader of lanes before the sort, to the
oracle's, superstep by superstep and in order."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from timewarp_tpu.core.rng import msg_bits, seed_words
from timewarp_tpu.core.scenario import Outbox
from timewarp_tpu.faults import FaultFleet, parse_faults
from timewarp_tpu.faults.apply import cut_mask, degrade, down_mask
from timewarp_tpu.interp.jax_engine.batched import BatchSpec
from timewarp_tpu.interp.jax_engine.common import I32MAX, thi, tlo, u32sum
from timewarp_tpu.interp.jax_engine.engine import JaxEngine
from timewarp_tpu.interp.ref.superstep import SuperstepOracle
from timewarp_tpu.models.gossip import gossip
from timewarp_tpu.net.delays import Quantize, UniformDelay
from timewarp_tpu.obs.flight import EV_FAULT, EV_SEND, TAG_DOWN
from timewarp_tpu.trace.hashing import SENT, mix32_jnp

N = 96                 # under 1 024: one rung, and it is the top one
T0 = 30_000            # the superstep's epoch, inside every window below
LINK = Quantize(UniformDelay(8_000, 30_000), 1_000)
SEEDS = (0, 4, 9)

#: world -> schedule, by the shape ``_fault_reads`` gives the look-up.
#: "early": a partition row, so the destination's packed word is looked
#: up on the outbox lanes and the link rows' verdicts ride ``pdst``.
#: "late": link rows alone, so the sender's bits ride its offset and the
#: word is looked up on the rung's lanes. Node 5 (7, 11) is down while
#: some of the superstep's messages are due.
FAULTS = {
    "early": ("crash:5:36ms:50ms; partition:0-47|48-95:25ms:70ms; "
              "degrade:0-31:16-63:10ms:120ms:2.0; "
              "degrade:all:40-63:20ms:100ms:0.5",
              "crash:7:40ms:55ms; partition:0-31|32-95:10ms:90ms; "
              "degrade:8-71:all:10ms:120ms:1.5; "
              "degrade:all:0-23:20ms:100ms:0.5",
              "crash:11:38ms:52ms; partition:0-63|64-95:28ms:33ms; "
              "degrade:0-95:48-95:10ms:120ms:3.0; "
              "degrade:all:5-50:20ms:100ms:0.5"),
    "late": ("crash:5:36ms:50ms; degrade:0-31:16-63:10ms:120ms:2.0; "
             "degrade:all:40-63:20ms:100ms:1.5:300",
             "crash:7:40ms:55ms; degrade:8-71:all:10ms:120ms:1.5; "
             "degrade:all:0-23:20ms:100ms:2.5",
             "crash:11:38ms:52ms; degrade:0-95:48-95:10ms:120ms:3.0; "
             "degrade:all:5-50:20ms:100ms:1.25:40"),
}


class Lanes(JaxEngine):
    """The engine with the insertion taken out: a rung returns the
    sorted lanes it would have inserted, in the mailbox's four places,
    and after them what every rung returns."""

    def _stages_by_rank(self):
        return False

    def _insert_sorted(self, mb_rel, mb_src, mb_payload, sd, ok_s,
                       drel_s, src_s, pay_s, holes, counts):
        return sd, ok_s, drel_s, (src_s,) + tuple(pay_s)


def _engine(M, P, W, fleet, faults):
    sc = dataclasses.replace(
        gossip(N, fanout=M, burst=True, mailbox_cap=8),
        max_out=M, payload_width=P)
    kw = {"batch": BatchSpec(seeds=SEEDS)} if fleet else {"seed": SEEDS[0]}
    if faults:
        scheds = tuple(map(parse_faults, FAULTS[faults]))
        kw["faults"] = FaultFleet(scheds) if fleet else scheds[0]
    eng = Lanes(sc, LINK, window=W, lint="off", **kw)
    assert len(eng._sender_rungs(N)) == 1
    if faults:
        assert eng._fault_reads() == (2, faults == "early")
    return eng


def _outbox(rng, M, P, W, live):
    """One superstep's outbox with exactly ``live`` live senders (every
    one a valid message in slot 0; the other slots at random, a tenth
    of them out of range, a fifth to the nodes the schedules crash), the
    rest silent but for messages to nowhere (counted, never live), and
    each node's own instant in the window."""
    senders = np.zeros(N, bool)
    senders[rng.choice(N, live, replace=False)] = True
    valid = rng.random((M, N)) < np.where(senders, 0.6, 0.3)
    valid[0, senders] = True
    dst = rng.integers(0, N, (M, N)).astype(np.int32)
    crowd = rng.random((M, N)) < 0.2      # a fifth to the nodes that crash
    dst[crowd] = rng.choice([5, 7, 11], int(crowd.sum()))
    nowhere = valid & (rng.random((M, N)) < 0.1)
    nowhere[0, senders] = False
    nowhere |= valid & ~senders
    dst[nowhere] = rng.choice([-2, N, N + 7], int(nowhere.sum()))
    pay = rng.integers(-2**31, 2**31, (M, P, N)).astype(np.int32)
    now = T0 + rng.integers(0, W, N).astype(np.int64)
    return valid, dst, pay, now


def _want(eng, b, faults, valid, dst, pay, now):
    """The plain compaction of one world's outbox and what the rung
    must return for it: the kept lanes sorted, and the counts. What is
    elementwise a message (the link's draw, the schedule's masks) is
    taken on the outbox planes, one shape for every case; the
    compaction and the sort are numpy's."""
    M, P, W = eng.scenario.max_out, eng.scenario.payload_width, eng.window
    ft = None
    if faults:
        sched = parse_faults(FAULTS[faults][b])
        ft = jax.tree.map(jnp.asarray, sched.tables(N))
    src = np.broadcast_to(np.arange(N, dtype=np.int32), (M, N))
    slot = np.broadcast_to(np.arange(M, dtype=np.int32)[:, None], (M, N))
    t = np.broadcast_to(now, (M, N))
    in_range = (dst >= 0) & (dst < N)
    counts = {"bad_dst": int((valid & ~in_range).sum()), "cut": 0,
              "down": 0, "degraded": 0}
    ok = valid & in_range
    to = np.where(in_range, dst, 0)
    s0, s1 = seed_words(SEEDS[b])
    delay, _ = eng.link.sample(src, to, t, msg_bits(s0, s1, src, to, t, slot))
    down = np.zeros((M, N), bool)
    if faults:
        cut = ok & np.asarray(cut_mask(ft, src, to, t))
        counts["cut"] = int(cut.sum())
        ok &= ~cut
        slowed = degrade(ft, delay, src, to, t)
        counts["degraded"] = int(np.sum(ok & np.asarray(slowed != delay)))
        delay = slowed
    flight = np.maximum(np.asarray(delay), 1)
    if faults:
        down = ok & np.asarray(down_mask(ft, to, t + flight))
        counts["down"] = int(down.sum())
    woff = (t - T0).astype(np.int32)
    drel = woff + flight
    counts["bad_delay"] = int((ok & (drel > I32MAX - 1)).sum())
    counts["short"] = int((ok & (flight < W)).sum()) if W > 1 else 0
    keep = ok & ~down
    counts["sent"] = int(keep.sum())
    due = t + flight
    counts["hash"] = int(u32sum(jnp.where(keep, mix32_jnp(
        SENT, src, to, tlo(due), thi(due), pay[:, 0, :]), 0)))
    # the compaction: the live senders ascending, their lanes
    # slot-major, the messages the schedule let through
    ids = np.flatnonzero(ok.any(axis=0))
    take = keep[:, ids].ravel()
    lanes = [x[:, ids].ravel()[take] for x in (
        dst, drel.astype(np.int32), src, *(pay[:, p, :] for p in range(P)))]
    rank, off = (x[:, ids].ravel()[take] for x in (src * M + slot, woff))
    order = np.lexsort((rank, off, lanes[0]))
    return counts, [x[order] for x in lanes], ids


def _route(eng):
    """The rung on one outbox (a fleet: one a world, under the engine's
    own ``vmap``), run eagerly: every case of a shape shares the
    operations' programs."""
    node_ids = eng.comm.node_ids()

    def world(valid, dst, pay, now):
        return eng._route_adaptive(
            Outbox(valid, dst, pay), valid, now, jnp.int64(T0), None,
            None, None, None, None, node_ids, True)
    if eng.batch is None:
        return world
    return lambda *a: eng._each_world(world, eng._world_context(), *a)


def _held_to(got, want):
    counts, lanes, _ = want
    sd, ok_s, drel_s, words, bad_dst, bad_delay, short, route_drop, \
        sent, sent_hash, *faults = got
    k = counts["sent"]
    assert int(np.sum(ok_s)) == k and bool(np.all(ok_s[:k]))
    assert bool(np.all(sd[k:] == N))             # the invalid lanes last
    for got_x, want_x in zip((sd, drel_s, *words), lanes, strict=True):
        np.testing.assert_array_equal(got_x[:k], want_x)
    assert (int(bad_dst), int(bad_delay), int(short), int(route_drop),
            int(sent), int(sent_hash)) == (
        counts["bad_dst"], counts["bad_delay"], counts["short"], 0, k,
        counts["hash"])
    if faults:
        assert tuple(map(int, faults[0])) == (
            counts["cut"], counts["down"], counts["degraded"])
    else:
        assert not counts["cut"] and not counts["down"]


@pytest.mark.parametrize("faults", [None, "early", "late"])
@pytest.mark.parametrize("fleet", [False, True], ids=["solo", "fleet3"])
@pytest.mark.parametrize("M, P, W", [(1, 1, 1000), (3, 1, 1), (3, 2, 1000),
                                     (1, 2, 1000)])
def test_in_place_lanes_equal_the_plain_compaction(M, P, W, fleet, faults):
    eng = _engine(M, P, W, fleet, faults)
    route = _route(eng)
    rng = np.random.default_rng(1000 * M + 100 * P + W + 7 * bool(faults))
    seen = {"cut": 0, "down": 0, "degraded": 0, "short": 0, "bad_dst": 0}
    for live in (0, 1, N // 2, N - 1, N):
        worlds = [_outbox(rng, M, P, W, live) for _ in SEEDS[:3 if fleet
                                                              else 1]]
        box = [np.stack(x) for x in zip(*worlds)] if fleet else worlds[0]
        got = jax.device_get(route(*map(jnp.asarray, box)))
        for b, world in enumerate(worlds):
            want = _want(eng, b, faults, *world)
            if not faults:
                assert len(want[2]) == live
            _held_to(jax.tree.map(lambda x: x[b], got) if fleet else got,
                     want)
            for name in seen:
                seen[name] += want[0][name]
    # the draws exercise what they are meant to
    assert seen["bad_dst"] > 0
    assert (seen["down"] > 0) == (seen["degraded"] > 0) == bool(faults)
    assert (seen["cut"] > 0) == (faults == "early")
    # (a delay under the window is refused when the engine is built:
    # `short` is held at 0)
    assert not seen["short"]


def test_the_top_rungs_send_capture_is_the_oracles_in_order():
    # record="full" reads the faulted rung's lanes BEFORE the sort: in
    # place they lie slot-major then node ascending with the dead lanes
    # between, and `flight.compact` keeps lane order, so the captured
    # rows are the oracle's sends of the superstep, sender ascending
    # (one slot a node here); a send due inside its destination's down
    # window is captured as a fault, which the oracle counts
    sc = gossip(64, fanout=1, steady=True, end_us=160_000, mailbox_cap=40)
    link = Quantize(UniformDelay(3_000, 9_000), 1_000)
    sched = parse_faults("crash:3:20ms:60ms; partition:0-31|32-63:25ms:70ms;"
                         " degrade:0-31:16-63:10ms:120ms:2.0")
    eng = JaxEngine(sc, link, window="auto", seed=3, faults=sched,
                    record="full", record_cap=256, lint="off")
    assert eng._adaptive_regime() and len(eng._sender_rungs(64)) == 1
    orc = SuperstepOracle(sc, link, seed=3, window=eng.window, faults=sched,
                          record_events=True, lint="off")
    steps = 48
    eng.run(steps)
    log = eng.last_run_flight
    assert log.dropped == 0
    assert eng.last_run_stats["inplace_rung_steps"] == steps \
        == eng.last_run_stats["rung_steps"][-1]
    sends = 0
    for k in range(steps):
        before = len(orc.events)
        orc.run(1)
        want = sorted((e for e in orc.events[before:] if e[0] == "sent"),
                      key=lambda e: e[2])
        rows = (log.superstep == k) & (log.kind == EV_SEND)
        got = list(zip(log.send_t[rows].tolist(), log.src[rows].tolist(),
                       log.dst[rows].tolist(), log.t[rows].tolist()))
        assert got == [e[1:5] for e in want], k
        sends += len(got)
    assert sends > 10 * steps
    down = (log.kind == EV_FAULT) & (log.tag == TAG_DOWN)
    assert int(down.sum()) == orc.fault_counts["down"] > 0
