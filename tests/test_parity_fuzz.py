"""Property-based parity fuzz: randomized scenario families must match
the oracle bit-for-bit on every draw — the dual-interpreter law under
configurations nobody hand-picked.

Every example is a scenario of its own, so every example compiles its
own engines (7-14 s each on the CPU). Tier-1 walks a few, derandomized:
the same few in every run. The full random draw is the ``slow`` case of
the same test, which CI runs (pyproject.toml: its pytest invocations
apply no marker filter)."""

import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="property suite needs hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from timewarp_tpu.core.scenario import NEVER, Inbox, Outbox, Scenario
from timewarp_tpu.interp.jax_engine.edge_engine import EdgeEngine
from timewarp_tpu.interp.jax_engine.engine import JaxEngine
from timewarp_tpu.interp.ref.superstep import SuperstepOracle
from timewarp_tpu.net.delays import UniformDelay, WithDrop
from timewarp_tpu.trace.events import assert_traces_equal

N = 12  # fixed shape: keeps XLA recompiles per example cheap

#: (examples a test, derandomized): tier-1's few, the same in every
#: run, and the full random draw
DRAWS = [pytest.param(3, True, id="first3"),
         pytest.param(15, False, id="full-draw", marks=pytest.mark.slow)]


def _hold(law, examples, derandomize):
    """``law`` over ``examples`` draws of ``st.data()``."""
    settings(max_examples=examples, deadline=None, derandomize=derandomize)(
        given(data=st.data())(law))()


def _rand_scenario(periods, dsts, end_us, commutative):
    """Each node i sends to dsts[i] every periods[i] µs; inbox folds
    either commutatively (sum) or order-sensitively (hash chain)."""
    p_arr = np.asarray(periods, np.int64)
    d_arr = np.asarray(dsts, np.int32)

    def step(state, inbox: Inbox, now, i, key):
        if commutative:
            acc = state["acc"] + jnp.sum(
                jnp.where(inbox.valid, inbox.payload[:, 0], 0),
                dtype=jnp.int32)
        else:
            import jax

            def fold(c, j):
                m = c * jnp.int32(1000003) \
                    + inbox.payload[j, 0] * 31 + inbox.src[j]
                return jnp.where(inbox.valid[j], m, c), None

            acc, _ = jax.lax.scan(
                fold, state["acc"], jnp.arange(inbox.valid.shape[0]))
        alive = now < end_us
        due = (state["next"] <= now) & alive
        out = Outbox(valid=due[None], dst=jnp.asarray(d_arr)[i][None],
                     payload=jnp.stack(
                         [state["sent"] + i, jnp.int32(0)])[None])
        nxt = jnp.where(due, state["next"] + jnp.asarray(p_arr)[i],
                        state["next"])
        wake = jnp.where(alive, nxt, jnp.int64(NEVER))
        return {"acc": acc, "sent": state["sent"] + due.astype(jnp.int32),
                "next": nxt}, out, wake

    def init(i):
        return {"acc": jnp.int32(i), "sent": jnp.int32(0),
                "next": jnp.int64(int(p_arr[i]))}, int(p_arr[i])

    return Scenario(
        name="fuzz", n_nodes=N, step=step, init=init, payload_width=2,
        max_out=1, mailbox_cap=6,
        static_dst=d_arr.reshape(N, 1),
        commutative_inbox=commutative)


@pytest.mark.parametrize("examples, derandomize", DRAWS)
def test_randomized_scenario_parity(examples, derandomize):
    _hold(_scenario_parity, examples, derandomize)


def _scenario_parity(data):
    rng = np.random.default_rng(data.draw(st.integers(0, 2**31)))
    periods = rng.integers(500, 5_000, N)
    commutative = bool(data.draw(st.booleans()))
    lo = int(rng.integers(100, 2_000))
    hi = lo + int(rng.integers(1, 3_000))
    drop = float(data.draw(st.sampled_from([0.0, 0.15])))
    link = UniformDelay(lo, hi) if drop == 0.0 \
        else WithDrop(UniformDelay(lo, hi), drop)
    seed = int(data.draw(st.integers(0, 1000)))

    # general engine: arbitrary random destinations — exact parity
    # including per-node overflow accounting
    sc = _rand_scenario(periods, rng.integers(0, N, N), 25_000,
                        commutative)
    ot = SuperstepOracle(sc, link, seed=seed).run(4_000)
    _, gt = JaxEngine(sc, link, seed=seed).run(160)
    assert_traces_equal(ot, gt, "oracle", "general", limit=len(gt))

    # edge engine: random PERMUTATION destinations (in-degree exactly
    # 1, so its per-edge capacity coincides with the oracle's per-node
    # mailbox_cap — the engine's documented parity domain)
    sc2 = _rand_scenario(periods, rng.permutation(N), 25_000,
                         commutative)
    ot2 = SuperstepOracle(sc2, link, seed=seed).run(4_000)
    _, et = EdgeEngine(sc2, link, seed=seed, cap=6).run(160)
    assert_traces_equal(ot2, et, "oracle", "edge", limit=len(et))


@pytest.mark.parametrize("examples, derandomize", DRAWS)
def test_randomized_windowed_parity(examples, derandomize):
    """The windowed path under randomized timers/links: engine ≡
    windowed oracle bit-for-bit for any window ≤ the link's declared
    delay floor."""
    _hold(_windowed_parity, examples, derandomize)


def _windowed_parity(data):
    from timewarp_tpu.net.delays import Quantize

    rng = np.random.default_rng(data.draw(st.integers(0, 2**31)))
    periods = rng.integers(300, 4_000, N)
    commutative = bool(data.draw(st.booleans()))
    lo = int(rng.integers(2_000, 5_000))
    hi = lo + int(rng.integers(1, 6_000))
    link = Quantize(UniformDelay(lo, hi), 1_000)
    W = int(data.draw(st.sampled_from([2, 3])) ) * 1_000
    W = min(W, link.min_delay_us)
    seed = int(data.draw(st.integers(0, 1000)))

    sc = _rand_scenario(periods, rng.integers(0, N, N), 25_000,
                        commutative)
    ot = SuperstepOracle(sc, link, seed=seed, window=W).run(4_000)
    st_, gt = JaxEngine(sc, link, seed=seed, window=W).run(160)
    assert_traces_equal(ot, gt, "windowed-oracle", "windowed-general",
                        limit=len(gt))
    assert int(st_.short_delay) == 0
