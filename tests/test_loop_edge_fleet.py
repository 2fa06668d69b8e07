"""The edge of the quiet loop, a fleet's half of the law
(tests/test_loop_edge.py has what the law is): each world's carried
horizon is its state's, a world out of budget keeps its state, and the
quiet loop lands where the scan's masks do under one budget and under
one per world, fresh and resumed."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from loop_edge_laws import N, UNI, _gossip, _gossip_faults, _same
from timewarp_tpu.faults import FaultFleet, FaultSchedule
from timewarp_tpu.interp.jax_engine.batched import BatchSpec
from timewarp_tpu.interp.jax_engine.engine import JaxEngine
from timewarp_tpu.trace.events import assert_states_equal


FLEET_SEEDS = (0, 1, 2)


@pytest.mark.parametrize("faulted", [False, True],
                         ids=["unfaulted", "faulted"])
def test_a_fleets_horizons_and_per_world_budgets(faulted):
    sc = _gossip(True)
    faults = None
    if faulted:
        base = _gossip_faults().events
        faults = FaultFleet((FaultSchedule(base), FaultSchedule(base[:2]),
                             FaultSchedule(base[2:])))
    eng = JaxEngine(sc, UNI, window="auto", lint="off", faults=faults,
                    batch=BatchSpec(seeds=FLEET_SEEDS))
    B = len(FLEET_SEEDS)

    @jax.jit
    def step(st, hz, in_budget):
        new, hz2 = eng._vstep(eng._superstep_carried,
                              eng._world_context(), st, hz, in_budget)
        return new, hz2, eng._horizon_all(new)
    plain = jax.jit(lambda st: eng._step_all(st, False)[0])
    st = eng.init_state()
    hz = jax.jit(eng._horizon_all)(st)
    assert hz.t.shape == (B,) and hz.node_next.shape == (B, N)
    frozen = np.array([False, True, False])     # world 1 out of budget
    for i in range(6):
        new, hz2, again = step(st, hz, jnp.asarray(~frozen))
        _same(hz2, again, f"horizons after iteration {i}")
        full = plain(st)
        for leaf_new, leaf_old, leaf_full in zip(
                jax.tree.leaves(new), jax.tree.leaves(st),
                jax.tree.leaves(full), strict=True):
            want = np.where(
                frozen.reshape((B,) + (1,) * (leaf_old.ndim - 1)),
                np.asarray(leaf_old), np.asarray(leaf_full))
            assert np.array_equal(np.asarray(leaf_new), want), i
        assert int(hz2.t[1]) == int(hz.t[1])
        st, hz = new, hz2
    # the drivers: the quiet loop against the scan's masks, under one
    # budget and under one per world, fresh and resumed
    for budget in (0, 1, 8, [3, 7, 8], [0, 8, 5], 64):
        quiet = eng.run_quiet(budget)
        assert_states_equal(quiet, eng.run(budget)[0], f"budget {budget}")
        world_steps = eng.last_run_stats["world_supersteps"]
        assert world_steps == np.asarray(quiet.steps).tolist()
    first = eng.run_quiet([3, 7, 8])
    for budget in ([5, 1, 0], 64):
        assert_states_equal(eng.run_quiet(budget, first),
                            eng.run(budget, first)[0],
                            f"budget {budget}, resumed")
    done = eng.run_quiet(64, first)
    assert not bool(np.asarray(eng.world_active(done)).any()) or faulted
    assert_states_equal(eng.run_quiet(64), done, "one run against two")
