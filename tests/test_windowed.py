"""Multi-instant windowed supersteps (engine.py ``JaxEngine.window``):

1. windowed engine ≡ windowed oracle, bit-for-bit trace parity;
2. windowed execution ≡ classic window=1 execution in *event semantics*
   — identical final states, delivered/overflow totals, and quiescence
   time — the exactness claim of the windowed design (a window only
   changes superstep granularity when link delays are ≥ window);
3. the preconditions are enforced: the constructor rejects windows
   beyond the link's declared ``min_delay_us``, and a link that lies
   about its bound is caught by the ``short_delay`` counter, never
   silent;
4. the sharded all_to_all engine reproduces the windowed trace on a
   virtual 8-device mesh.

This is the time-bucketed batching SURVEY.md §5.7/§7 names as the
sparse-regime answer, made exact.
"""

import numpy as np
import pytest

import jax

from timewarp_tpu.core.scenario import NEVER
from timewarp_tpu.interp.jax_engine.engine import JaxEngine
from timewarp_tpu.interp.jax_engine.sharded import ShardedEngine, make_mesh
from timewarp_tpu.interp.ref.superstep import SuperstepOracle
from timewarp_tpu.models.gossip import gossip
from timewarp_tpu.models.praos import praos
from timewarp_tpu.models.token_ring import token_ring
from timewarp_tpu.net.delays import (FnDelay, LogNormalDelay, Quantize,
                                     UniformDelay)
from timewarp_tpu.trace.events import assert_traces_equal

#: min_delay_us = 3000 (uniform lo) quantized up to 3000
LINK = Quantize(UniformDelay(3_000, 9_000), 1_000)
W = 3_000


def _praos_sparse(n=48):
    """Events spread over many sub-window instants: relay timers re-arm
    at 500 µs steps while links take >= 3 ms."""
    return praos(n, slot_us=20_000, n_slots=6, leader_prob=2.0 / n,
                 fanout=4, relay_interval=500, mailbox_cap=16)


def _gossip_sparse(n=64):
    return gossip(n, fanout=4, think_us=700, gossip_interval=500,
                  end_us=400_000, mailbox_cap=16)


@pytest.mark.parametrize("mk", [_praos_sparse, _gossip_sparse])
def test_windowed_engine_matches_windowed_oracle(mk):
    sc = mk()
    oracle = SuperstepOracle(sc, LINK, window=W)
    otrace = oracle.run(600)
    engine = JaxEngine(sc, LINK, window=W)
    state, etrace = engine.run(600)
    assert_traces_equal(otrace, etrace)
    assert otrace.total_delivered() > 0
    assert int(state.short_delay) == 0
    assert oracle.short_delay_total == 0
    # windows genuinely batched multiple instants (the point of the
    # feature): fewer supersteps than distinct event instants
    w1 = SuperstepOracle(sc, LINK).run(4000)
    assert len(otrace) < len(w1)


@pytest.mark.parametrize("mk", [_praos_sparse, _gossip_sparse])
def test_windowed_equals_classic_semantics(mk):
    """The exactness law: windowing changes superstep granularity, not
    event semantics. Run to quiescence both ways; everything observable
    must coincide. (Exactness additionally requires the classic run to
    be overflow-free — the deliver-then-insert overflow-boundary caveat
    in the JaxEngine docstring — which the overflow equality below
    also certifies for these workloads.)"""
    sc = mk()
    e1 = JaxEngine(sc, LINK, window=1)
    ew = JaxEngine(sc, LINK, window=W)
    s1 = e1.run_quiet(4000)
    sw = ew.run_quiet(4000)
    assert int(e1._next_event(s1)) >= NEVER, "w=1 run did not quiesce"
    assert int(ew._next_event(sw)) >= NEVER, "windowed run did not quiesce"
    assert int(s1.delivered) == int(sw.delivered)
    assert int(s1.overflow) == int(sw.overflow)
    assert int(s1.bad_dst) == int(sw.bad_dst)
    assert int(sw.short_delay) == 0
    # final epoch differs by design (it is the last *window start*, and
    # the last event instant lies inside that window)
    assert int(s1.time) - W < int(sw.time) <= int(s1.time)
    assert int(s1.steps) > int(sw.steps)  # windows actually batched
    for k in s1.states:
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(s1.states[k])),
            np.asarray(jax.device_get(sw.states[k])), err_msg=k)
    np.testing.assert_array_equal(np.asarray(jax.device_get(s1.wake)),
                                  np.asarray(jax.device_get(sw.wake)))


def test_window_one_is_bitwise_classic():
    """window=1 must be the classic engine exactly (same trace)."""
    sc = token_ring(16, think_us=5_000, bootstrap_us=1_000,
                    end_us=300_000, with_observer=False)
    link = UniformDelay(1_000, 5_000)
    _, t1 = JaxEngine(sc, link, window=1).run(300)
    oracle = SuperstepOracle(sc, link)
    assert_traces_equal(oracle.run(300), t1)


def test_window_beyond_link_bound_rejected():
    with pytest.raises(ValueError, match="min_delay_us"):
        JaxEngine(_gossip_sparse(), UniformDelay(1_000, 5_000),
                  window=2_000)
    with pytest.raises(ValueError, match="min_delay_us"):
        SuperstepOracle(_gossip_sparse(), UniformDelay(1_000, 5_000),
                        window=2_000)
    with pytest.raises(ValueError, match="window"):
        JaxEngine(_gossip_sparse(), LINK, window=0)


class _LyingLink(FnDelay):
    """Declares a 2 ms floor but samples 1 ms delays — the engine must
    catch the violation in ``short_delay``, never silently diverge."""

    @property
    def min_delay_us(self):
        return 2_000

    @property
    def needs_key(self):
        return False


def test_short_delay_counter_catches_lying_link():
    import jax.numpy as jnp

    link = _LyingLink(lambda src, dst, t, key: (
        jnp.full(jnp.shape(dst), 1_000, jnp.int64),
        jnp.zeros(jnp.shape(dst), bool)))
    sc = _gossip_sparse()
    engine = JaxEngine(sc, link, window=2_000)
    state = engine.run_quiet(500)
    assert int(state.short_delay) > 0
    oracle = SuperstepOracle(sc, link, window=2_000)
    oracle.run(500)
    assert oracle.short_delay_total > 0


def test_stake_weighted_burst_praos_windowed_parity():
    """Stake weighting composes with burst + window: whales
    mint, zero-stake nodes never do, and the trace stays bit-exact."""
    n = 48
    stake = np.zeros(n, np.int64)
    stake[:6] = 10
    sc = praos(n, slot_us=20_000, n_slots=6, leader_prob=0.02,
               stake=stake, fanout=4, burst=True, mailbox_cap=16)
    oracle = SuperstepOracle(sc, LINK, window=W)
    otrace = oracle.run(600)
    engine = JaxEngine(sc, LINK, window=W)
    state, etrace = engine.run(600)
    assert_traces_equal(otrace, etrace)
    assert otrace.total_delivered() > 0
    assert int(np.asarray(state.states["best"]).max()) > 0
    # stake gating, tested for real: an all-zero-stake network can
    # never mint, so no tip ever exists and nothing is ever relayed
    sc0 = praos(n, slot_us=20_000, n_slots=6, leader_prob=0.02,
                stake=np.zeros(n, np.int64), fanout=4, burst=True,
                mailbox_cap=16)
    st0 = JaxEngine(sc0, LINK, window=W).run_quiet(600)
    assert int(st0.delivered) == 0
    assert int(np.asarray(st0.states["best"]).max()) == 0


@pytest.mark.parametrize("mesh_spec", [
    pytest.param((8, None), id="1axis-8dev"),
    pytest.param(((2, 4), ("dcn", "ici")), id="2axis-dcn-ici"),
])
def test_windowed_sharded_parity(mesh_spec):
    """The all_to_all engine reproduces the windowed trace on a flat
    8-device mesh AND on a multi-slice (dcn, ici) mesh shape — the
    window offsets ride the exchange across both axes."""
    shape, axes = mesh_spec
    mesh = make_mesh(shape) if axes is None \
        else make_mesh(shape=shape, axes=axes)
    axis = "nodes" if axes is None else axes
    sc = _gossip_sparse(64)
    sharded = ShardedEngine(sc, LINK, mesh, axis=axis, window=W)
    _, strace = sharded.run(400)
    otrace = SuperstepOracle(sc, LINK, window=W).run(400)
    assert_traces_equal(otrace, strace)


def test_windowed_oracle_until_is_instant_granular():
    """`until` bounds firing *instants*, not just window starts: a
    window straddling the horizon fires only the nodes at or before
    it — matching window=1 semantics of the same horizon (the r4
    advisor finding). Verified by equality with a window=1 run of the
    same horizon, and by the windowed run actually having a window
    that straddles `until`."""
    from timewarp_tpu.interp.ref.superstep import SuperstepOracle
    from timewarp_tpu.models.gossip import gossip
    from timewarp_tpu.net.delays import Quantize, UniformDelay

    sc = gossip(48, fanout=4, think_us=700, burst=True,
                end_us=400_000, mailbox_cap=16)
    link = Quantize(UniformDelay(3_000, 9_000), 1_000)
    W = 3_000
    full = SuperstepOracle(sc, link, window=W).run(400)
    # pick a horizon strictly inside some window of the full run:
    # one past a window start, before that window's end
    t_mid = int(full.times[len(full.times) // 2])
    until = t_mid + 1
    o1 = SuperstepOracle(sc, link, window=1)
    o1.run(10_000, until=until)
    ow = SuperstepOracle(sc, link, window=W)
    ow.run(10_000, until=until)
    # same events executed: identical delivered totals and final time
    assert sum(1 for i in range(sc.n_nodes)
               if o1.wake[i] != ow.wake[i]) == 0
    assert o1.time <= until and ow.time <= until
    d1 = sum(len(m) for m in o1.mailbox)
    dw = sum(len(m) for m in ow.mailbox)
    assert d1 == dw
