"""Batched multi-world execution, the batch exactness law
(tests/test_world_batch.py) where the ladder is live and where the
worlds are sharded over a mesh: a fleet's one rung for all its worlds
is result-invisible, and ``ShardedBatchedEngine`` over 8 or 4 virtual
devices reproduces the local fleet, and hence every solo run,
bit-for-bit."""

import pytest

from timewarp_tpu.interp.jax_engine.batched import BatchSpec, world_slice
from timewarp_tpu.interp.jax_engine.engine import JaxEngine
from timewarp_tpu.models.gossip import gossip
from timewarp_tpu.models.token_ring import token_ring, token_ring_links
from timewarp_tpu.net.delays import Quantize, UniformDelay
from timewarp_tpu.trace.events import assert_states_equal, assert_traces_equal


def _ring(n=48):
    sc = token_ring(n, n_tokens=8, think_us=2_000, bootstrap_us=1000,
                    end_us=200_000, with_observer=True, mailbox_cap=16)
    return sc, token_ring_links(n)


def test_batched_shares_one_rung_exactly():
    """At n > 1024 the routing ladder is live in the solo engine and
    in the fleet alike: a solo world takes the smallest rung that
    holds its own senders, a fleet one rung for all its worlds, the
    smallest that holds the busiest's (engine.py ``_route_adaptive``).
    The law says rung choice is result-invisible, so the slices must
    still match bit-for-bit."""
    n = 2048
    sc = gossip(n, fanout=4, think_us=700, burst=True, end_us=60_000,
                mailbox_cap=16)
    link = Quantize(UniformDelay(3_000, 9_000), 1_000)
    assert len(JaxEngine._sender_rungs(n)) > 1  # ladder actually live
    eng = JaxEngine(sc, link, window=3_000, batch=BatchSpec(seeds=(0, 4)))
    fin = eng.run_quiet(8)
    # the wave's first supersteps fit the narrow rung: the fleet took it
    assert eng.last_run_stats["rung_lanes"] < 8 * n
    for b, s in enumerate((0, 4)):
        solo = JaxEngine(sc, link, seed=s, window=3_000).run_quiet(8)
        assert_states_equal(solo, world_slice(fin, b), f"world {b}")


@pytest.mark.parametrize("devices", [8, 4])
def test_sharded_batched_equals_local_fleet(devices):
    """ShardedBatchedEngine (worlds sharded over the mesh, nodes
    device-local): 8 worlds over 8 or 4 virtual CPU devices must
    reproduce the local batched engine — and hence every solo run —
    bit-for-bit, traced and quiet."""
    from timewarp_tpu.interp.jax_engine.sharded import (
        ShardedBatchedEngine, make_mesh)
    sc, link = _ring(32)
    spec = BatchSpec(seeds=tuple(range(8)))
    sh = ShardedBatchedEngine(sc, link,
                              make_mesh(devices, axis="worlds"),
                              batch=spec)
    local = JaxEngine(sc, link, batch=spec)
    shf, shtr = sh.run(100)
    lof, lotr = local.run(100)
    for b in range(8):
        assert_traces_equal(lotr[b], shtr[b], "local", f"sharded w{b}")
    assert_states_equal(lof, shf, "sharded fleet state")
    assert_states_equal(local.run_quiet(60), sh.run_quiet(60),
                        "sharded fleet run_quiet")
    # the eager regime's ``rung_steps`` has one bin, and a device's
    # row of it still sums to the trips of that device's loop
    st, each = sh.last_run_stats, 8 // devices
    assert len(st["rung_steps"]) == 1
    assert st["device_iterations"] == [
        max(st["world_supersteps"][d * each:(d + 1) * each])
        for d in range(devices)]
    assert st["fleet_iterations"] == max(st["device_iterations"])


def test_sharded_batched_rejects_indivisible_fleet():
    from timewarp_tpu.interp.jax_engine.sharded import (
        ShardedBatchedEngine, make_mesh)
    sc, link = _ring(32)
    with pytest.raises(ValueError, match="not divisible"):
        ShardedBatchedEngine(sc, link, make_mesh(4, axis="worlds"),
                             batch=BatchSpec(seeds=(0, 1, 2)))
