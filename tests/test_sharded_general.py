"""Sharded general engine (all_to_all routing): an 8-device mesh run
must reproduce the oracle's and the 1-device engine's trace
**bit-for-bit**, a bucket too small for a shard's fan-in is counted,
never silent, a run resumes across the mesh, and a two-axis mesh
(DCN x ICI) holds the same law (tests/test_sharded.py has the edge
engine's half and what conftest.py's virtual 8-device platform is
for)."""

import numpy as np

import jax
import jax.numpy as jnp

from timewarp_tpu.core.scenario import NEVER, Inbox, Outbox, Scenario
from timewarp_tpu.interp.jax_engine.edge_engine import EdgeEngine
from timewarp_tpu.interp.jax_engine.sharded import ShardedEdgeEngine, make_mesh
from timewarp_tpu.interp.ref.superstep import SuperstepOracle
from timewarp_tpu.models.token_ring import token_ring
from timewarp_tpu.net.delays import FixedDelay, UniformDelay, WithDrop
from timewarp_tpu.trace.events import assert_traces_equal


def mesh8():
    assert jax.device_count() >= 8, "conftest should provide 8 devices"
    return make_mesh(8)


def run_three_way_general(sc, link, steps, bucket_cap=None,
                          oracle_steps=None):
    from timewarp_tpu.interp.jax_engine.engine import JaxEngine
    from timewarp_tpu.interp.jax_engine.sharded import ShardedEngine

    oracle = SuperstepOracle(sc, link)
    ot = oracle.run(oracle_steps or 10 * steps)
    local = JaxEngine(sc, link)
    lst, lt = local.run(steps)
    sharded = ShardedEngine(sc, link, mesh8(), bucket_cap=bucket_cap)
    sst, st = sharded.run(steps)
    return ot, (lst, lt), (sst, st)


def test_general_observer_ring_8dev_parity():
    """The observer token-ring: a dynamic hub with in-degree N — the
    exact topology class the ppermute engine rejects. 8-device
    all_to_all delivery must match the 1-device engine and the oracle
    bit-for-bit."""
    from timewarp_tpu.models.token_ring import token_ring_links

    sc = token_ring(63, n_tokens=8, think_us=3_000, bootstrap_us=1000,
                    end_us=200_000, with_observer=True, mailbox_cap=16)
    assert sc.n_nodes == 64  # 63 ring + observer, divisible by 8
    link = token_ring_links(63)
    ot, (_, lt), (sst, st) = run_three_way_general(sc, link, 400)
    assert_traces_equal(lt, st, "local", "sharded", limit=len(st))
    assert_traces_equal(ot, st, "oracle", "sharded", limit=len(st))
    assert int(sst.overflow) == 0
    assert st.total_delivered() > 100


def test_general_random_destinations_8dev_parity():
    """Fully dynamic destinations (pseudo-random per firing, derived
    from on-device state): nothing static to exploit — pure
    all_to_all routing, with drops."""
    n = 64

    def step(state, inbox: Inbox, now, i, key):
        seen = state["seen"] + jnp.sum(
            jnp.where(inbox.valid, inbox.payload[:, 0], 0),
            dtype=jnp.int32)
        # lcg on node state -> destination changes every firing
        lcg = state["lcg"] * jnp.int32(1103515245) + jnp.int32(12345)
        dst = jnp.abs(lcg) % jnp.int32(n)
        alive = now < 60_000
        due = (state["next"] <= now) & alive
        out = Outbox(valid=due[None], dst=dst[None],
                     payload=jnp.stack(
                         [state["sent"] + 1, jnp.int32(0)])[None])
        nxt = jnp.where(due, state["next"] + 2_000, state["next"])
        wake = jnp.where(alive, nxt, jnp.int64(NEVER))
        return {"seen": seen, "sent": state["sent"] + due.astype(jnp.int32),
                "lcg": lcg, "next": nxt}, out, wake

    def init(i):
        return {"seen": jnp.int32(0), "sent": jnp.int32(0),
                "lcg": jnp.int32(i * 7 + 3), "next": jnp.int64(0)}, 0

    sc = Scenario(name="rand-dst", n_nodes=n, step=step, init=init,
                  payload_width=2, max_out=1, mailbox_cap=16,
                  commutative_inbox=True)
    link = WithDrop(UniformDelay(300, 2_000), 0.2)
    ot, (_, lt), (sst, st) = run_three_way_general(sc, link, 300)
    assert_traces_equal(lt, st, "local", "sharded", limit=len(st))
    assert_traces_equal(ot, st, "oracle", "sharded", limit=len(st))
    assert int(sst.overflow) == 0
    assert st.total_delivered() > 200


def test_general_bucket_overflow_counted():
    """bucket_cap below the real per-shard fan-in: overflow must be
    counted, never silent. All 64 nodes send to node 0 every ms."""
    n = 64

    def step(state, inbox: Inbox, now, i, key):
        alive = now < 20_000
        due = alive & (i > 0)
        out = Outbox(valid=due[None], dst=jnp.int32(0)[None],
                     payload=jnp.zeros((1, 2), jnp.int32))
        wake = jnp.where(due, now + 1_000, jnp.int64(NEVER))
        return state, out, wake

    def init(i):
        return {"x": jnp.int32(0)}, 0 if i > 0 else NEVER

    from timewarp_tpu.interp.jax_engine.sharded import ShardedEngine
    sc = Scenario(name="hub-flood", n_nodes=n, step=step, init=init,
                  payload_width=2, max_out=1, mailbox_cap=32,
                  commutative_inbox=True)
    # the buckets let 24 a superstep through (3 a shard), node 0 takes
    # them all at the next instant: a mailbox of 32 never fills, so
    # what is counted is the buckets'
    eng = ShardedEngine(sc, FixedDelay(500), mesh8(), bucket_cap=3)
    st, _ = eng.run(60)
    # 7 senders/shard but bucket_cap=3: 4 messages/shard/step overflow
    assert int(st.overflow) > 0


def test_general_sharded_resume_parity():
    from timewarp_tpu.interp.jax_engine.sharded import ShardedEngine
    from timewarp_tpu.models.token_ring import token_ring_links

    sc = token_ring(63, n_tokens=4, think_us=2_000, bootstrap_us=1000,
                    end_us=150_000, with_observer=True, mailbox_cap=16)
    link = token_ring_links(63)
    eng = ShardedEngine(sc, link, mesh8())
    _, full = eng.run(200)
    mid, first = eng.run(80)
    _, rest = eng.run(120, state=mid)
    assert np.array_equal(
        np.concatenate([first.times, rest.times]), full.times)
    assert np.array_equal(
        np.concatenate([first.recv_hash, rest.recv_hash]), full.recv_hash)


def test_two_axis_mesh_dcn_ici():
    """Multi-slice deployment shape: a (2, 4) mesh named (dcn, ici)
    with the node axis sharded over the flattened product. Both the
    ppermute ring (edge engine) and the all_to_all exchange (general
    engine) must reproduce the 1-device traces bit-for-bit across the
    two-axis mesh."""
    from timewarp_tpu.interp.jax_engine.engine import JaxEngine
    from timewarp_tpu.interp.jax_engine.sharded import ShardedEngine
    from timewarp_tpu.models.gossip import gossip

    mesh2 = make_mesh(shape=(2, 4), axes=("dcn", "ici"))
    ax = ("dcn", "ici")

    sc = token_ring(64, n_tokens=16, think_us=1_000, bootstrap_us=1000,
                    end_us=120_000, with_observer=False, mailbox_cap=4)
    link = UniformDelay(300, 1_200)
    _, lt = EdgeEngine(sc, link).run(250)
    _, st = ShardedEdgeEngine(sc, link, mesh2, axis=ax).run(250)
    assert_traces_equal(lt, st, "1-device", "2x4-mesh")

    sc2 = gossip(64, fanout=4, think_us=2_000, gossip_interval=1_000,
                 end_us=300_000, mailbox_cap=8)
    _, glt = JaxEngine(sc2, link).run(250)
    _, gst = ShardedEngine(sc2, link, mesh2, axis=ax).run(250)
    assert_traces_equal(glt, gst, "1-device", "2x4-mesh-all2all",
                        limit=len(gst))
