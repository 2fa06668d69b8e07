"""Cross-world trace parity — the strongest form of the acceptance law.

The framework has two authoring worlds:

- the *generator-program* world: ``models/token_ring_net.py`` — the
  reference's own shape (worker/server threads, RPC calls, lively
  sockets) run under ``PureEmulation`` over the ``EmulatedBackend``
  byte fabric (≙ `/root/reference/examples/token-ring/Main.hs:79-85`,
  the emulated-network run);
- the *batched-scenario* world: ``models/token_ring.py`` — the explicit
  state machine run by ``SuperstepOracle`` and ``JaxEngine``.

Until this test they were two disjoint systems bridged only by
hand-written twin models. Here the SAME behavioral scenario — a 64-node
token ring over ≥20 s of virtual time — is executed in both worlds with
provably aligned link models (fixed integer delays: token/ack hops D,
observer hops O), and the application-level event streams must agree
**µs-for-µs**:

- the observer's ``(virtual_time, value)`` note sequence,
- every node's ``(virtual_time, node, value)`` token-receipt event.

A third, closed-form prediction — derived by hand from the protocol,
touching neither ``scenario.step`` nor the DES — must match both,
breaking the shared-kernel blind spot: with
prewarmed connections and an at-anchored bootstrap, receipt v happens at

    R_v = bootstrap + D + (v-1) * (O + D + think + D)

(worker receives token; notes the observer: +O there, +D ack back;
thinks ``think``; forwards: +D) and the note lands at ``N_v = R_v + O``.
The batched twin absorbs the note round-trip into its think time
(``think_b = think + O + D``) — that is the *documented translation*
between the worlds, and this test is what makes it trustworthy.

Alignment preconditions (all load-bearing, all deliberate):
``prewarm=True`` keeps the connect handshake off the timing path;
``bootstrap_at=True`` anchors the first send at an absolute instant;
fixed integer delays make RNG-stream differences between the worlds
irrelevant.
"""

import jax.numpy as jnp
import pytest

from timewarp_tpu import run_emulation, sec
from timewarp_tpu.interp.jax_engine.engine import JaxEngine
from timewarp_tpu.interp.ref.superstep import SuperstepOracle
from timewarp_tpu.models.token_ring import NOTE, TOKEN, token_ring
from timewarp_tpu.models.token_ring_net import (OBSERVER_PORT,
                                               token_ring_net)
from timewarp_tpu.net.backend import EmulatedBackend, endpoint_id
from timewarp_tpu.net.delays import FnDelay, SeededHashUniform
from timewarp_tpu.trace.events import assert_traces_equal

N_RING = 64
B = 1_000_000        # bootstrap instant
D = 2_000            # every token/ack hop
O = 1_000            # every observer-bound hop
THINK = 3_000_000    # the reference's 3 s passing delay
DURATION = 22_000_000  # ≥ 20 s of virtual time


def _net_delays():
    """Net-world link model: observer-bound chunks take O, everything
    else D — fixed, so endpoint-id keyed entropy is irrelevant."""
    obs = endpoint_id(f"127.0.0.1:{OBSERVER_PORT}")

    def fn(src, dst, t, key):
        d = jnp.where(jnp.asarray(dst, jnp.uint32) == jnp.uint32(obs),
                      jnp.int64(O), jnp.int64(D))
        return d, jnp.zeros(jnp.shape(d), bool)

    return FnDelay(fn)


def _batched_links():
    """Batched-world link model: node id n_ring is the observer."""
    def fn(src, dst, t, key):
        d = jnp.where(dst == N_RING, jnp.int64(O), jnp.int64(D))
        return d, jnp.zeros(jnp.shape(d), bool)

    return FnDelay(fn)


def _closed_form():
    """The hand-derived protocol timeline (independent oracle — no
    scenario.step, no DES). Net-world node numbering (1-based)."""
    receipts, notes = [], []
    R, v = B + D, 1
    while R < DURATION:
        receipts.append((R, v % N_RING + 1, v))
        notes.append((R + O, v))
        R += O + D + THINK + D
        v += 1
    return receipts, notes


@pytest.fixture(scope="module")
def net_world():
    receipts = []
    backend = EmulatedBackend(_net_delays(), seed=0)
    notes, errors = run_emulation(token_ring_net(
        backend, N_RING, duration_us=DURATION,
        passing_delay_us=THINK, bootstrap_us=B,
        prewarm=True, bootstrap_at=True, receipts=receipts))
    return notes, errors, receipts


@pytest.fixture(scope="module")
def batched_world():
    # think_b absorbs the note round-trip the generator program performs
    # before its Wait (the documented cross-world translation)
    sc = token_ring(N_RING, think_us=THINK + O + D, bootstrap_us=B,
                    end_us=DURATION)
    link = _batched_links()
    oracle = SuperstepOracle(sc, link, record_events=True)
    otrace = oracle.run(800)
    engine = JaxEngine(sc, link)
    state, etrace = engine.run(800)
    return sc, oracle, otrace, engine, state, etrace


def test_net_world_matches_closed_form(net_world):
    notes, errors, receipts = net_world
    exp_receipts, exp_notes = _closed_form()
    assert errors == []
    assert receipts == exp_receipts
    assert notes == exp_notes
    assert len(notes) >= 6  # ≥ 20 s of progress actually happened


def test_batched_world_matches_closed_form(batched_world):
    _, oracle, _, _, _, _ = batched_world
    exp_receipts, exp_notes = _closed_form()
    recvs = [e for e in oracle.events if e[0] == "recv"]
    # ring-node token receipts, mapped to net numbering (node i ↔ i+1)
    got_receipts = [(t, i + 1, pay) for (_, t, i, src, dt, pay) in recvs
                    if i != N_RING and t < DURATION]
    got_notes = [(t, pay) for (_, t, i, src, dt, pay) in recvs
                 if i == N_RING and t < DURATION]
    assert got_receipts == exp_receipts
    assert got_notes == exp_notes


def test_cross_world_event_streams_identical(net_world, batched_world):
    """The headline assertion: generator-program world ≡ batched world
    on the application event stream, µs-for-µs over ≥20 s."""
    notes, _, receipts = net_world
    _, oracle, _, _, _, _ = batched_world
    recvs = [e for e in oracle.events if e[0] == "recv"]
    bat_receipts = [(t, i + 1, pay) for (_, t, i, src, dt, pay) in recvs
                    if i != N_RING and t < DURATION]
    bat_notes = [(t, pay) for (_, t, i, src, dt, pay) in recvs
                 if i == N_RING and t < DURATION]
    assert receipts == bat_receipts
    assert notes == bat_notes


def test_batched_engine_matches_oracle(batched_world):
    """Close the loop: the XLA engine reproduces the oracle's trace for
    this exact configuration, so net-world ≡ oracle ≡ engine."""
    _, _, otrace, _, state, etrace = batched_world
    assert_traces_equal(otrace, etrace)
    assert int(state.overflow) == 0
    assert int(state.bad_dst) == 0


def test_net_world_values_under_real_asyncio():
    """The third interpreter leg: the SAME generator program runs under
    real wall-clock asyncio (over the emulated fabric, scaled to ms so
    the test stays fast). Wall-clock jitter forbids µs assertions, but
    the application-level *value* stream and its monotone order — what
    the reference's observer checks (Main.hs:197-208) — must match the
    other two worlds exactly."""
    from timewarp_tpu import run_real_time

    receipts = []
    backend = EmulatedBackend(_net_delays(), seed=0)
    notes, errors = run_real_time(token_ring_net(
        # 1.2 s of wall time: the ring's periods leave room for 39
        # notes and the law asks four, so neighbours that take four
        # fifths of the core (tier-1's other workers, compiling) do
        # not starve it (at 0.3 s they did: 3 notes)
        backend, 8, duration_us=1_200_000,
        passing_delay_us=30_000, bootstrap_us=20_000,
        check_period_us=50_000, prewarm=True, receipts=receipts))
    assert errors == []
    assert [v for _, v in notes] == list(range(1, len(notes) + 1))
    assert len(notes) >= 4
    assert [v for _, _, v in receipts] == [v for _, v in notes]
    # receipt nodes walk the ring: value v lands on node (v mod 8) + 1
    assert all(node == v % 8 + 1 for _, node, v in receipts)


# ---------------------------------------------------------------------
# Random-link legs: the SAME law under a genuinely
# random network — the reference's own north-star configuration
# (examples/token-ring/Main.hs:60, 73-85 draws uniform 1-5 ms token
# delays from a seeded generator). Token hops draw a seeded uniform
# 1-5 ms keyed by (destination, send instant) — SeededHashUniform, the
# reference's `Delays` contract — while observer-bound hops stay O and
# ack hops (ephemeral-endpoint-bound responses, off the timing path)
# stay D, so the documented think-time translation is unchanged. The
# fabric's new `endpoint_ids` mapping feeds the link model the SAME
# node indices the batched world uses, which is what makes one seeded
# model bit-identical across worlds.

RND_LO, RND_HI, RND_SALT = 1_000, 5_000, 7


def _rnd():
    return SeededHashUniform(RND_LO, RND_HI, RND_SALT)


def _endpoint_map():
    ids = {f"127.0.0.1:{2000 + no}": no - 1
           for no in range(1, N_RING + 1)}
    ids[f"127.0.0.1:{OBSERVER_PORT}"] = N_RING
    return ids


def _net_delays_random():
    """dst-keyed mixed model: mapped ring nodes (ids 0..63) draw the
    seeded uniform; the observer (64) takes O; every unmapped id — the
    crc32 of an ephemeral client endpoint, i.e. an RPC response — the
    fixed ack D."""
    rnd = _rnd()

    def fn(src, dst, t, key):
        d32 = jnp.asarray(dst, jnp.uint32)
        du = rnd.sample(src, dst, t, None)[0]
        return jnp.where(
            d32 == jnp.uint32(N_RING), jnp.int64(O),
            jnp.where(d32 < jnp.uint32(N_RING), du, jnp.int64(D))), \
            jnp.zeros(jnp.shape(du), bool)

    return FnDelay(fn)


def _batched_links_random():
    rnd = _rnd()

    def fn(src, dst, t, key):
        du = rnd.sample(src, dst, t, None)[0]
        return jnp.where(dst == N_RING, jnp.int64(O), du), \
            jnp.zeros(jnp.shape(du), bool)

    return FnDelay(fn)


def _closed_form_random():
    """Hand-derived timeline with the random token hops: receipt v at
    R_v, note at R_v + O, next send at R_v + O + D + THINK, next
    receipt one (dst, t)-keyed draw later — the same protocol algebra
    as _closed_form with d_v = SeededHashUniform(dst_idx, t_send)."""
    rnd = _rnd()

    def draw(dst_idx, t_send):
        return int(rnd.sample(0, dst_idx, t_send, None)[0])

    receipts, notes = [], []
    v, t_send = 1, B
    R = t_send + draw(1 % N_RING, t_send)
    while R < DURATION:
        receipts.append((R, v % N_RING + 1, v))
        notes.append((R + O, v))
        t_send = R + O + D + THINK
        v += 1
        R = t_send + draw(v % N_RING, t_send)
    return receipts, notes


@pytest.fixture(scope="module")
def net_world_random():
    # precondition of the dst-keyed mixed model: no ephemeral endpoint
    # name may crc-collide into the mapped id range [0, N_RING]
    for port in range(49152, 49152 + 4 * N_RING + 16):
        assert endpoint_id(f"127.0.0.1:{port}") > N_RING
    receipts = []
    backend = EmulatedBackend(_net_delays_random(), seed=0,
                              endpoint_ids=_endpoint_map())
    notes, errors = run_emulation(token_ring_net(
        backend, N_RING, duration_us=DURATION,
        passing_delay_us=THINK, bootstrap_us=B,
        prewarm=True, bootstrap_at=True, receipts=receipts))
    return notes, errors, receipts


@pytest.fixture(scope="module")
def batched_world_random():
    sc = token_ring(N_RING, think_us=THINK + O + D, bootstrap_us=B,
                    end_us=DURATION)
    link = _batched_links_random()
    oracle = SuperstepOracle(sc, link, record_events=True)
    otrace = oracle.run(800)
    engine = JaxEngine(sc, link)
    state, etrace = engine.run(800)
    return oracle, otrace, state, etrace


def test_net_world_random_matches_closed_form(net_world_random):
    notes, errors, receipts = net_world_random
    exp_receipts, exp_notes = _closed_form_random()
    assert errors == []
    assert receipts == exp_receipts
    assert notes == exp_notes
    assert len(notes) >= 6


def test_cross_world_random_links_identical(net_world_random,
                                            batched_world_random):
    """The headline random-leg assertion: generator-program world ≡
    batched world µs-for-µs when the token hops are genuinely random —
    the worlds share only the seeded (dst, t)-keyed model and the
    endpoint-id mapping, not an RNG stream position."""
    notes, _, receipts = net_world_random
    oracle, _, _, _ = batched_world_random
    recvs = [e for e in oracle.events if e[0] == "recv"]
    bat_receipts = [(t, i + 1, pay) for (_, t, i, src, dt, pay) in recvs
                    if i != N_RING and t < DURATION]
    bat_notes = [(t, pay) for (_, t, i, src, dt, pay) in recvs
                 if i == N_RING and t < DURATION]
    assert receipts == bat_receipts
    assert notes == bat_notes


def test_batched_engine_matches_oracle_random(batched_world_random):
    _, otrace, state, etrace = batched_world_random
    assert_traces_equal(otrace, etrace)
    assert int(state.overflow) == 0
    assert int(state.bad_dst) == 0


def test_hand_rolled_trace_matches_both_engines_and_oracle():
    """Engine-independent oracle for the dense 64-ring: predict the FULL superstep trace — times, counts, and
    digests — by hand from the protocol (no ``scenario.step``, no
    engine, no SuperstepOracle in the prediction; only the public hash
    functions), then demand all three executors reproduce it.

    Dense ring mechanics, derived on paper: every node holds a token at
    bootstrap ``B``; with zero think time a received token is forwarded
    in the same firing; every hop takes exactly ``D``. So superstep k
    happens at ``B + k·D`` with all 64 nodes firing; step 0 receives
    nothing and sends value 1; step k ≥ 1 receives value k from the
    predecessor and sends value k+1 — until the ``end_us`` deadline
    mutes the sends.
    """
    from timewarp_tpu.interp.jax_engine.edge_engine import EdgeEngine
    from timewarp_tpu.net.delays import FixedDelay
    from timewarp_tpu.trace.events import SuperstepTrace
    from timewarp_tpu.trace.hashing import (FIRED, RECV, SENT, combine_py,
                                            mix32_py)

    n, BB, DD, E = 64, 10_000, 700, 16_000
    mask = (1 << 32) - 1

    rows = []
    k = 0
    while True:
        t = BB + k * DD
        fired_hash = combine_py(mix32_py(FIRED, i) for i in range(n))
        if k == 0:
            recv_count, recv_hash = 0, combine_py([])
        else:
            recv_count = n
            recv_hash = combine_py(
                mix32_py(RECV, i, (i - 1) % n, t & mask, t >> 32, k)
                for i in range(n))
        if t < E:
            dt = t + DD
            sent_count = n
            sent_hash = combine_py(
                mix32_py(SENT, i, (i + 1) % n, dt & mask, dt >> 32, k + 1)
                for i in range(n))
        else:
            sent_count, sent_hash = 0, combine_py([])
        rows.append((t, n, fired_hash, recv_count, recv_hash,
                     sent_count, sent_hash, 0))
        if t >= E:
            break
        k += 1
    expected = SuperstepTrace.from_rows(rows)

    sc = token_ring(n, n_tokens=n, think_us=0, bootstrap_us=BB,
                    end_us=E, with_observer=False, mailbox_cap=4)
    link = FixedDelay(DD)
    otrace = SuperstepOracle(sc, link).run(100)
    assert_traces_equal(expected, otrace, "hand-rolled", "oracle")
    _, jtrace = JaxEngine(sc, link).run(100)
    assert_traces_equal(expected, jtrace, "hand-rolled", "jax-engine")
    _, etrace = EdgeEngine(sc, link, cap=2).run(100)
    assert_traces_equal(expected, etrace, "hand-rolled", "edge-engine")
