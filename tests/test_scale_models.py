"""Gossip (BASELINE config 4) and Praos (config 5) scenarios: trace
parity at small n across oracle / 1-device general engine / 8-device
all_to_all sharded engine, plus behavioral sanity (the rumor actually
spreads; the chain actually grows). Gossip is here; Praos is
tests/test_scale_praos.py (tests/scale_laws.py has the three-way
comparison)."""

import numpy as np

import jax

from scale_laws import three_way
from timewarp_tpu.interp.jax_engine.engine import JaxEngine
from timewarp_tpu.interp.jax_engine.sharded import ShardedEngine, make_mesh
from timewarp_tpu.interp.ref.superstep import SuperstepOracle
from timewarp_tpu.models.gossip import gossip, gossip_links
from timewarp_tpu.models.praos import praos
from timewarp_tpu.net.delays import UniformDelay, WithDrop
from timewarp_tpu.trace.events import assert_traces_equal


def test_gossip_lognormal_parity_and_spread():
    """LogNormalDelay finally under parity load (float model; CPU
    parity per its documented contract)."""
    sc = gossip(64, fanout=6, think_us=3_000, gossip_interval=1_000,
                end_us=5_000_000)
    link = gossip_links(median_us=20_000, sigma=0.6)
    fst, lt = three_way(sc, link, 700)
    # every node heard the rumor
    hops = np.asarray(jax.device_get(fst.states["hop"]))
    assert (hops >= 0).all(), f"{(hops < 0).sum()} nodes never infected"
    assert int(fst.overflow) == 0
    assert lt.total_delivered() > 250  # most of the 64*6 sends landed


def test_gossip_with_drop_parity():
    sc = gossip(48, fanout=8, think_us=2_000, gossip_interval=1_500,
                end_us=3_000_000)
    link = WithDrop(UniformDelay(5_000, 40_000), 0.2)
    fst, _ = three_way(sc, link, 600)
    hops = np.asarray(jax.device_get(fst.states["hop"]))
    assert (hops >= 0).mean() > 0.9  # drops may strand a few


def test_sharded_general_run_quiet_matches_traced():
    """The general sharded engine's while_loop driver (the bench path)
    must agree with its traced scan driver."""
    sc = praos(64, slot_us=50_000, n_slots=2, leader_prob=0.05,
               fanout=4, relay_interval=1_000)
    link = UniformDelay(2_000, 9_000)
    eng = ShardedEngine(sc, link, make_mesh(8))
    traced_final, _ = eng.run(2000)
    quiet_final = eng.run_quiet(2000)
    for name in ("delivered", "steps", "time", "overflow", "bad_dst"):
        assert int(getattr(traced_final, name)) == \
            int(getattr(quiet_final, name)), name
    for k in traced_final.states:
        assert np.array_equal(
            np.asarray(jax.device_get(traced_final.states[k])),
            np.asarray(jax.device_get(quiet_final.states[k]))), k


def test_gossip_steady_mode_parity():
    """Rumor-mongering variant: relays never exhaust; parity vs oracle
    and the 8-device all_to_all engine, then quiesces at the deadline."""
    from timewarp_tpu.net.delays import Quantize

    sc = gossip(48, fanout=1, think_us=1_000, gossip_interval=1_000,
                end_us=40_000, steady=True, mailbox_cap=8)
    link = Quantize(UniformDelay(500, 2_500), 1_000)
    fst, lt = three_way(sc, link, 300)
    hops = np.asarray(jax.device_get(fst.states["hop"]))
    assert (hops >= 0).all()
    # steady state reached: far more deliveries than fanout-bounded
    assert lt.total_delivered() > 300
    # the deadline actually quiesces the run
    assert len(lt) < 300


def test_general_engine_overflow_parity_with_oracle():
    """Contract #6 under load: when mailboxes overflow, the general
    engine must drop exactly the messages the oracle drops — overflow
    counts AND the surviving trace stay bit-for-bit equal."""
    import jax.numpy as jnp
    from timewarp_tpu.core.scenario import NEVER, Outbox, Scenario
    from timewarp_tpu.net.delays import FixedDelay

    n = 8

    def step(state, inbox, now, i, key):
        got = jnp.sum(inbox.valid, dtype=jnp.int32)
        alive = now < 20_000
        is_sender = i > 0
        out = Outbox(valid=(is_sender & alive)[None],
                     dst=jnp.int32(0)[None],
                     payload=jnp.stack(
                         [state["sent"] + 1, jnp.int32(0)])[None])
        wake = jnp.where(is_sender & alive, now + 500,
                         jnp.where(now < 40_000, now + 7_000,
                                   jnp.int64(NEVER)))
        return {"seen": state["seen"] + got,
                "sent": state["sent"] + 1}, out, wake

    def init(i):
        return {"seen": jnp.int32(0), "sent": jnp.int32(0)}, \
            0 if i > 0 else 7_000

    # 7 senders × 1 msg / 500 µs into node 0, which only fires (and
    # drains) every 7 ms with mailbox_cap=4: heavy overflow
    sc = Scenario(name="overflow-hub", n_nodes=n, step=step, init=init,
                  payload_width=2, max_out=1, mailbox_cap=4,
                  commutative_inbox=True)
    link = FixedDelay(1_000)
    ot = SuperstepOracle(sc, link).run(3000)
    fst, lt = JaxEngine(sc, link).run(300)
    assert_traces_equal(ot, lt, "oracle", "engine", limit=len(lt))
    assert int(fst.overflow) > 0          # the test actually overflowed
    sst, st = ShardedEngine(sc, link, make_mesh(8)).run(300)
    assert_traces_equal(ot, st, "oracle", "sharded", limit=len(st))
    assert int(sst.overflow) == int(fst.overflow)
