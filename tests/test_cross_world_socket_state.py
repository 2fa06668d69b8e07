"""Socket-state's cross-world leg: the one baseline config that had no
presence outside the net-stack test suite gets its batched twin
(models/socket_state.py) tied to the generator-program world.

The law here is value-stream equality (socket_state.py module
docstring): under one no-drop link model, every ping the net world's
transport delivers and counts per socket, the batched world delivers
and counts per client — final counters and send counts equal; the
batched twin itself holds the bit-exact oracle ≡ engine trace law
like every other scenario (and appears in tools/parity_tpu.py /
PARITY_TPU.json; its 1023-way hub fan-in is a case of
tests/test_insert_scenarios.py)."""

import numpy as np
import pytest

from timewarp_tpu.interp.jax_engine.engine import JaxEngine
from timewarp_tpu.interp.ref.des import run_emulation
from timewarp_tpu.interp.ref.superstep import SuperstepOracle
from timewarp_tpu.models.socket_state import roulette_sends, socket_state
from timewarp_tpu.models.socket_state_net import socket_state_net
from timewarp_tpu.net.backend import EmulatedBackend
from timewarp_tpu.net.delays import FixedDelay, Quantize, UniformDelay
from timewarp_tpu.trace.events import assert_traces_equal

SEED = 3
LINK = FixedDelay(3_000)


@pytest.fixture(scope="module")
def net_world():
    res = run_emulation(socket_state_net(
        EmulatedBackend(LINK), seed=SEED))
    return res


@pytest.fixture(scope="module")
def batched_world():
    sc = socket_state(n_clients=3, seed=SEED)
    oracle = SuperstepOracle(sc, LINK)
    otrace = oracle.run(4000)
    engine = JaxEngine(sc, LINK)
    state, etrace = engine.run(4000)
    return sc, oracle, otrace, state, etrace


def test_roulette_matches_net_world(net_world):
    """The shared host roulette predicts the net world's send counts —
    the same draw stream both worlds schedule from."""
    sends = roulette_sends(3, SEED)
    assert net_world["client_sends"] == {
        cid: sends[cid - 1] for cid in (1, 2, 3)}
    assert sum(sends) > 0  # a seed where nobody sends proves nothing


def test_socket_state_cross_world_counters(net_world, batched_world):
    """Per-socket counters ≡ per-client counters: the transport's
    per-socket user state and the batched server's cnt[] agree ping
    for ping (a client that never sends opens no socket, so only
    active clients appear in the net world's list)."""
    _, _, _, state, _ = batched_world
    cnt = np.asarray(state.states["cnt"])[0]        # server row
    sends = roulette_sends(3, SEED)
    active = sorted(int(cnt[c]) for c in range(3) if sends[c] > 0)
    assert active == net_world["per_socket"]
    # zero-send clients counted nothing in either world
    assert all(int(cnt[c]) == 0 for c in range(3) if sends[c] == 0)
    # and nothing was lost on the way: counters == scheduled sends
    assert [int(v) for v in cnt] == sends


def test_socket_state_engine_matches_oracle(batched_world):
    _, _, otrace, state, etrace = batched_world
    assert_traces_equal(otrace, etrace)
    assert int(state.overflow) == 0
    assert int(state.bad_dst) == 0


def test_socket_state_deadline_stops_counting():
    """The listener deadline (≙ invoke (after life) stop): pings
    delivered past it fire the server but are not counted — in both
    interpreters identically."""
    sc = socket_state(n_clients=3, seed=24, send_interval_us=50_000,
                      server_life_us=120_000)
    link = Quantize(UniformDelay(3_000, 9_000), 1_000)
    oracle = SuperstepOracle(sc, link)
    otrace = oracle.run(4000)
    engine = JaxEngine(sc, link)
    state, etrace = engine.run(4000)
    assert_traces_equal(otrace, etrace)
    cnt = np.asarray(state.states["cnt"])[0]
    sends = roulette_sends(3, 24)
    # sends at 50/100/150... ms vs a 120 ms deadline: at most the
    # first two pings of each client can be counted
    assert [int(v) for v in cnt] == [min(s, 2) for s in sends]
    assert sum(sends) > sum(min(s, 2) for s in sends)  # gate did bite
