"""Cross-world parity legs beyond token-ring:
ping-pong and gossip — each baseline scenario executed as a
generator program over the full net stack (dialog/transfer over the
emulated byte fabric, under the pure DES) AND as its batched twin
(oracle + XLA engine), under ONE seeded random link model, with the
event streams equal µs-for-µs.

With the random-leg machinery of item 3 (``SeededHashUniform`` — a
(dst, t)-keyed draw, the reference's `Delays` contract — plus the
fabric's ``endpoint_ids`` mapping), these worlds share nothing but
the link model and the protocol: no RNG stream position, no think-time
translation (ping-pong replies and gossip relays are instant-exact in
both worlds by construction).

Together with tests/test_cross_world.py (token-ring, fixed + random)
this gives FOUR of the five baseline configs cross-world legs
(ping-pong, gossip, and praos here; socket-state's reconnect
machinery has no batched twin).
"""

import pytest

from timewarp_tpu import run_emulation
from timewarp_tpu.interp.jax_engine.engine import JaxEngine
from timewarp_tpu.interp.ref.superstep import SuperstepOracle
from timewarp_tpu.models.gossip import gossip
from timewarp_tpu.models.gossip_net import (gossip_net,
                                            gossip_net_ports,
                                            host_lcg_peers, lcg_init)
from timewarp_tpu.models.ping_pong import ping_pong
from timewarp_tpu.models.ping_pong_net import ping_pong_net
from timewarp_tpu.models.praos import praos
from timewarp_tpu.models.praos_net import (leader_schedule, praos_net,
                                           praos_net_ports)
from timewarp_tpu.net.backend import EmulatedBackend, endpoint_id
from timewarp_tpu.net.delays import FixedDelay, SeededHashUniform
from timewarp_tpu.trace.events import assert_traces_equal

RND = SeededHashUniform(3_000, 9_000, 7)


# ---------------------------------------------------------------- ping-pong

PP_ROUNDS = 40
PP_START = 50_000
PP_PING_PORT, PP_PONG_PORT = 4444, 5555


def _pp_endpoint_map():
    # batched node 0 = pinger (listens at ping_port), 1 = ponger
    return {f"127.0.0.1:{PP_PING_PORT}": 0,
            f"pong-host:{PP_PONG_PORT}": 1}


def _pp_closed_form():
    """T_1 = START; ping_v reaches the ponger one (dst=1, T_v)-draw
    later; the pong one (dst=0, ·)-draw after that; the next ping
    leaves at the pong's arrival instant."""
    def draw(dst, t):
        return int(RND.sample(0, dst, t, None)[0])

    pongs_got, pings_got = [], []
    t = PP_START
    for _ in range(PP_ROUNDS):
        a = t + draw(1, t)
        pongs_got.append(a)
        b = a + draw(0, a)
        pings_got.append(b)
        t = b
    return pongs_got, pings_got


@pytest.fixture(scope="module")
def pp_net_world():
    events = []
    backend = EmulatedBackend(RND, connect_delays=FixedDelay(500),
                              seed=0, endpoint_ids=_pp_endpoint_map())
    run_emulation(ping_pong_net(
        backend, ping_port=PP_PING_PORT, pong_port=PP_PONG_PORT,
        warmup_us=PP_START, rounds=PP_ROUNDS, send_at=True,
        prewarm=True, events_out=events))
    return events


@pytest.fixture(scope="module")
def pp_batched_world():
    sc = ping_pong(rounds=PP_ROUNDS, start_us=PP_START)
    oracle = SuperstepOracle(sc, RND, record_events=True)
    otrace = oracle.run(2000)
    engine = JaxEngine(sc, RND)
    state, etrace = engine.run(2000)
    return oracle, otrace, state, etrace


def test_ping_pong_net_matches_closed_form(pp_net_world):
    pongs_got = [t for tag, t in pp_net_world if tag == "pong-got-ping"]
    pings_got = [t for tag, t in pp_net_world if tag == "ping-got-pong"]
    exp_pong, exp_ping = _pp_closed_form()
    assert pongs_got == exp_pong
    assert pings_got == exp_ping


def test_ping_pong_cross_world_identical(pp_net_world,
                                         pp_batched_world):
    oracle, _, _, _ = pp_batched_world
    recvs = [e for e in oracle.events if e[0] == "recv"]
    bat_pong = [dt for (_, t, i, src, dt, pay) in recvs if i == 1]
    bat_ping = [dt for (_, t, i, src, dt, pay) in recvs if i == 0]
    assert bat_pong == [t for tag, t in pp_net_world
                        if tag == "pong-got-ping"]
    assert bat_ping == [t for tag, t in pp_net_world
                        if tag == "ping-got-pong"]


def test_ping_pong_engine_matches_oracle(pp_batched_world):
    _, otrace, state, etrace = pp_batched_world
    assert_traces_equal(otrace, etrace)
    assert int(state.overflow) == 0


# ------------------------------------------------------------------ gossip

G_N = 16
G_FANOUT = 4
G_THINK = 700
G_BOOT = 100_000
G_DUR = 900_000


@pytest.fixture(scope="module")
def gossip_net_world():
    # precondition of the dst-keyed model: gossip exchanges no acks,
    # so the only endpoint names on the wire are the mapped listen
    # ports — but guard anyway that no plausible ephemeral name could
    # crc-collide into the mapped id range [0, G_N]
    for port in range(49152, 49152 + 4 * G_N + 16):
        assert endpoint_id(f"127.0.0.1:{port}") > G_N
    receipts = []
    backend = EmulatedBackend(RND, connect_delays=FixedDelay(500),
                              seed=0, endpoint_ids=gossip_net_ports(G_N))
    run_emulation(gossip_net(
        backend, G_N, fanout=G_FANOUT, think_us=G_THINK,
        bootstrap_us=G_BOOT, duration_us=G_DUR, prewarm=True,
        receipts=receipts))
    return sorted((t, i) for t, i in receipts if t < G_DUR)


@pytest.fixture(scope="module")
def gossip_batched_world():
    sc = gossip(G_N, fanout=G_FANOUT, think_us=G_THINK, burst=True,
                bootstrap_us=G_BOOT, end_us=G_DUR, mailbox_cap=16)
    oracle = SuperstepOracle(sc, RND, record_events=True)
    otrace = oracle.run(4000)
    engine = JaxEngine(sc, RND)
    state, etrace = engine.run(4000)
    return oracle, otrace, state, etrace


def test_gossip_closed_form_diffusion(gossip_net_world):
    """Independent prediction of the first wave front: node 0's flood
    at G_BOOT reaches its four LCG peers one (dst, G_BOOT)-draw later
    — computed from the shared host LCG replica and the seeded model,
    touching neither world's executor."""
    _, dsts = host_lcg_peers(lcg_init(0), 0, G_N, G_FANOUT)
    front = [(G_BOOT + int(RND.sample(0, d, G_BOOT, None)[0]), d)
             for d in dsts]
    # second-hop rumors (infected at the earliest front arrivals,
    # flooding think_us later) legitimately interleave with the tail
    # of the seed's own front, so assert membership, not prefix; the
    # EARLIEST receipt is always the front's minimum
    assert set(front) <= set(gossip_net_world)
    assert gossip_net_world[0] == min(front)


def test_gossip_cross_world_identical(gossip_net_world,
                                      gossip_batched_world):
    """The diffusion timeline — every delivered rumor's (time, node) —
    is identical µs-for-µs across the two worlds."""
    oracle, _, state, _ = gossip_batched_world
    recvs = sorted((e[4], e[2]) for e in oracle.events
                   if e[0] == "recv" and e[4] < G_DUR)
    assert recvs == gossip_net_world
    assert len(recvs) >= G_N  # the wave actually spread
    assert int(state.overflow) == 0


def test_gossip_engine_matches_oracle(gossip_batched_world):
    _, otrace, state, etrace = gossip_batched_world
    assert_traces_equal(otrace, etrace)
    assert int(state.overflow) == 0


# ------------------------------------------------------------------- praos

P_N = 24
P_SLOT = 200_000
P_SLOTS = 4
P_PROB = 0.1
P_FAN = 3
P_DUR = (P_SLOTS + 1) * P_SLOT


@pytest.fixture(scope="module")
def praos_net_world():
    for port in range(49152, 49152 + 30 * P_N + 16):
        assert endpoint_id(f"127.0.0.1:{port}") > P_N
    receipts = []
    backend = EmulatedBackend(RND, connect_delays=FixedDelay(500),
                              seed=0, endpoint_ids=praos_net_ports(P_N))
    best = run_emulation(praos_net(
        backend, P_N, seed=0, slot_us=P_SLOT, n_slots=P_SLOTS,
        leader_prob=P_PROB, fanout=P_FAN, receipts=receipts))
    return best, sorted((t, i, ln) for t, i, ln in receipts
                        if t < P_DUR)


@pytest.fixture(scope="module")
def praos_batched_world():
    sc = praos(P_N, slot_us=P_SLOT, n_slots=P_SLOTS,
               leader_prob=P_PROB, fanout=P_FAN, burst=True,
               mailbox_cap=16)
    oracle = SuperstepOracle(sc, RND, record_events=True)
    otrace = oracle.run(4000)
    engine = JaxEngine(sc, RND)
    state, etrace = engine.run(4000)
    return oracle, otrace, state, etrace


def test_praos_tie_preconditions(praos_net_world):
    """The worlds are only comparable when no node faces two
    same-instant events whose fold order matters (module docstring of
    models/praos_net.py): same-(node, instant) arrivals must carry
    equal lengths, and no leader's slot boundary may coincide with an
    arrival. Asserted, not assumed."""
    _, receipts = praos_net_world
    sched = leader_schedule(0, P_N, P_SLOTS, P_SLOT, P_PROB)
    by_key = {}
    for t, i, ln in receipts:
        by_key.setdefault((t, i), set()).add(ln)
    assert all(len(v) == 1 for v in by_key.values())
    for (t, i) in by_key:
        assert not (t in sched and i in sched[t])


def test_praos_cross_world_identical(praos_net_world,
                                     praos_batched_world):
    """Every delivered tip's (time, node, chain length) — and the
    final per-node chain lengths — identical across the worlds. The
    leadership schedule is shared by construction (the same
    counter-RNG draw, host-callable), so the worlds share only the
    seed, the link model, and the protocol."""
    import numpy as np
    best, receipts = praos_net_world
    oracle, _, state, _ = praos_batched_world
    recvs = sorted((e[4], e[2], e[5]) for e in oracle.events
                   if e[0] == "recv" and e[4] < P_DUR)
    assert recvs == receipts
    assert len(recvs) > P_N  # tips actually diffused
    bat_best = np.asarray(state.states["best"])
    assert [best[i] for i in range(P_N)] == bat_best.tolist()
    assert int(state.overflow) == 0


def test_praos_engine_matches_oracle(praos_batched_world):
    _, otrace, state, etrace = praos_batched_world
    assert_traces_equal(otrace, etrace)
    assert int(state.overflow) == 0
