"""Sharded edge engine: an 8-device mesh run must reproduce the
1-device trace **bit-for-bit** (the framework's core law extended
across the mesh boundary, SURVEY.md §5.8).

conftest.py pins a virtual 8-CPU-device platform, so every test here
exercises real `shard_map` + `ppermute` collectives without TPU
hardware — exactly how the driver validates multi-chip sharding.
The general engine's half (all_to_all routing) is
tests/test_sharded_general.py.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from timewarp_tpu.core.scenario import NEVER, Inbox, Outbox, Scenario
from timewarp_tpu.interp.jax_engine.edge_engine import EdgeEngine
from timewarp_tpu.interp.jax_engine.sharded import (MeshComm,
                                                    ShardedEdgeEngine,
                                                    make_mesh)
from timewarp_tpu.interp.ref.superstep import SuperstepOracle
from timewarp_tpu.models.token_ring import token_ring
from timewarp_tpu.net.delays import FixedDelay, FnDelay, UniformDelay, WithDrop
from timewarp_tpu.trace.events import assert_traces_equal


def mesh8():
    assert jax.device_count() >= 8, "conftest should provide 8 devices"
    return make_mesh(8)


def run_three_way(sc, link, steps, cap=2, oracle_steps=None):
    """oracle vs 1-device edge engine vs 8-device sharded edge engine."""
    oracle = SuperstepOracle(sc, link)
    ot = oracle.run(oracle_steps or 10 * steps)
    local = EdgeEngine(sc, link, cap=cap)
    lst, lt = local.run(steps)
    sharded = ShardedEdgeEngine(sc, link, mesh8(), cap=cap)
    sst, st = sharded.run(steps)
    return ot, (lst, lt), (sst, st)


def test_dense_ring_fixed_delay_8dev_parity():
    sc = token_ring(64, n_tokens=64, think_us=0, bootstrap_us=1000,
                    end_us=150_000, with_observer=False, mailbox_cap=4)
    ot, (lst, lt), (sst, st) = run_three_way(sc, FixedDelay(500), 400)
    assert_traces_equal(lt, st, "local", "sharded")
    assert_traces_equal(ot, st, "oracle", "sharded")
    assert int(sst.overflow) == 0
    assert int(sst.delivered) == int(lst.delivered)
    assert st.total_delivered() > 5_000


def test_ring_with_drop_uniform_8dev_parity():
    """Randomized delays + drops: the counter-based RNG must produce the
    identical stream on every shard (entropy is a pure function of
    (src, dst, t, slot), never of device layout)."""
    sc = token_ring(64, n_tokens=16, think_us=2_000, bootstrap_us=1000,
                    end_us=400_000, with_observer=False, mailbox_cap=6)
    link = WithDrop(UniformDelay(500, 1500), 0.3)
    ot, (_, lt), (sst, st) = run_three_way(sc, link, 1200, cap=3)
    assert_traces_equal(lt, st, "local", "sharded")
    assert_traces_equal(ot, st, "oracle", "sharded")
    assert int(sst.overflow) == 0


def _shift_scenario(n, shifts, end_us=40_000, commutative=True):
    """Each node sends on slot k to (i + shifts[k]) mod n every 1 ms."""
    dst = np.stack([(np.arange(n) + s) % n for s in shifts],
                   axis=1).astype(np.int32)
    K = len(shifts)

    def step(state, inbox: Inbox, now, i, key):
        seen = state["seen"] + jnp.sum(
            jnp.where(inbox.valid, inbox.payload[:, 0], 0),
            dtype=jnp.int32)
        alive = now < end_us
        due = (state["next"] <= now) & alive
        out = Outbox(
            valid=jnp.broadcast_to(due, (K,)),
            dst=jnp.asarray(dst)[i],
            payload=jnp.broadcast_to(
                jnp.stack([state["sent"] + 1, jnp.int32(0)]), (K, 2)))
        nxt = jnp.where(due, state["next"] + 1_000, state["next"])
        wake = jnp.where(alive, nxt, jnp.int64(NEVER))
        return {"seen": seen, "sent": state["sent"] + jnp.where(due, K, 0),
                "next": nxt}, out, wake

    def init(i):
        return {"seen": jnp.int32(0), "sent": jnp.int32(0),
                "next": jnp.int64(0)}, 0

    return Scenario(
        name=f"shift-{shifts}", n_nodes=n, step=step, init=init,
        payload_width=2, max_out=K, mailbox_cap=4 * K,
        static_dst=dst, commutative_inbox=commutative)


def test_shard_spanning_shifts_8dev_parity():
    """Shifts 1, 10, and 17 on n=64 over 8 shards (n_local=8): shift 10
    = one whole-shard ppermute + a 2-wide boundary slice; 17 = two
    whole + 1; exercises both branches of MeshComm.roll."""
    sc = _shift_scenario(64, [1, 10, 17])
    link = UniformDelay(100, 900)
    ot, (_, lt), (sst, st) = run_three_way(sc, link, 200, cap=6)
    assert_traces_equal(lt, st, "local", "sharded", limit=len(st))
    assert_traces_equal(ot, st, "oracle", "sharded", limit=len(st))
    assert int(sst.overflow) == 0
    assert st.total_delivered() > 200


def test_noncommutative_sort_path_8dev_parity():
    """Order-sensitive inbox (contract-#2 sort compiled in) under
    sharding: per-source mixed delays interleave supersteps."""
    sc = _shift_scenario(48, [1, 2], commutative=False)
    link = FnDelay(lambda s, d, t, k: (
        jnp.where(s % 2 == 0, jnp.int64(700), jnp.int64(1700)),
        jnp.zeros(jnp.shape(d), bool)))
    ot, (_, lt), (sst, st) = run_three_way(sc, link, 200, cap=8)
    assert_traces_equal(lt, st, "local", "sharded", limit=len(st))
    assert_traces_equal(ot, st, "oracle", "sharded", limit=len(st))


def test_run_quiet_matches_traced_run_8dev():
    sc = token_ring(64, n_tokens=8, think_us=1_000, bootstrap_us=1000,
                    end_us=100_000, with_observer=False, mailbox_cap=4)
    link = UniformDelay(200, 900)
    eng = ShardedEdgeEngine(sc, link, mesh8())
    traced_final, _ = eng.run(500)
    quiet_final = eng.run_quiet(500)
    for name in ("delivered", "steps", "time", "overflow"):
        assert int(getattr(traced_final, name)) == \
            int(getattr(quiet_final, name)), name
    for k in traced_final.states:
        assert np.array_equal(
            np.asarray(jax.device_get(traced_final.states[k])),
            np.asarray(jax.device_get(quiet_final.states[k]))), k


def test_sharded_resume_parity():
    sc = token_ring(64, n_tokens=8, think_us=1_000, bootstrap_us=1000,
                    end_us=150_000, with_observer=False, mailbox_cap=4)
    link = UniformDelay(200, 900)
    eng = ShardedEdgeEngine(sc, link, mesh8())
    _, full = eng.run(300)
    mid, first = eng.run(120)
    _, rest = eng.run(180, state=mid)
    assert np.array_equal(
        np.concatenate([first.times, rest.times]), full.times)
    assert np.array_equal(
        np.concatenate([first.recv_hash, rest.recv_hash]), full.recv_hash)


def test_state_lives_on_the_mesh():
    """Per-node arrays must actually be sharded over the 8 devices, not
    replicated — the whole point of the exercise."""
    sc = token_ring(64, n_tokens=8, think_us=1_000, bootstrap_us=1000,
                    end_us=100_000, with_observer=False, mailbox_cap=4)
    eng = ShardedEdgeEngine(sc, FixedDelay(500), mesh8())
    st = eng.init_state()
    shard_shapes = {s.data.shape for s in st.wake.addressable_shards}
    assert shard_shapes == {(8,)}          # 64 nodes / 8 devices
    qshards = {s.data.shape[-1] for s in st.q_rel.addressable_shards}
    assert qshards == {8}
    final = eng.run_quiet(200)
    assert {s.data.shape for s in final.wake.addressable_shards} == {(8,)}


def test_rejects_non_shift_topology():
    n = 16
    rng = np.random.default_rng(3)
    perm = rng.permutation(n).astype(np.int32).reshape(n, 1)

    def step(state, inbox, now, i, key):
        out = Outbox(valid=jnp.ones(1, bool), dst=jnp.asarray(perm)[i],
                     payload=jnp.zeros((1, 2), jnp.int32))
        return state, out, jnp.int64(NEVER)

    sc = Scenario(name="perm", n_nodes=n, step=step,
                  init=lambda i: ({"x": jnp.int32(0)}, 0),
                  payload_width=2, max_out=1, mailbox_cap=4,
                  static_dst=perm, commutative_inbox=True)
    with pytest.raises(ValueError, match="not pure shifts"):
        ShardedEdgeEngine(sc, FixedDelay(1), mesh8())


def test_rejects_indivisible_node_count():
    sc = token_ring(60, n_tokens=1, with_observer=False)
    with pytest.raises(ValueError, match="not divisible"):
        ShardedEdgeEngine(sc, FixedDelay(1), mesh8())


def test_meshcomm_roll_matches_global_roll():
    """MeshComm.roll under shard_map == jnp.roll on the gathered array,
    for every shift class (0, intra-shard, boundary, multi-shard)."""
    from functools import partial
    from jax.sharding import PartitionSpec as P

    from timewarp_tpu.parallel.mesh import _smap

    mesh = mesh8()
    n = 64
    x = jnp.arange(n, dtype=jnp.int32) * 3 + 1
    comm = MeshComm("nodes", n, 8)
    for s in (0, 1, 5, 8, 10, 17, 63):
        rolled = jax.jit(_smap(
            partial(comm.roll, s=s), mesh,
            P("nodes"), P("nodes")))(x)
        assert np.array_equal(np.asarray(rolled),
                              np.asarray(jnp.roll(x, s))), s
