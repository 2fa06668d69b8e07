"""What the files of the quiet loop's edge share (tests/test_loop_edge.py
and its ``_eager``, ``_adaptive`` and ``_fleet``): the scenarios and
fault schedules, the law's cases and its body. No test lives here."""

import numpy as np
import pytest

import jax
from jax.sharding import PartitionSpec as P

from timewarp_tpu.core.scenario import NEVER
from timewarp_tpu.faults import FaultSchedule, LinkWindow, NodeCrash, Partition
from timewarp_tpu.interp.jax_engine.edge_engine import EdgeEngine
from timewarp_tpu.interp.jax_engine.engine import Horizon, JaxEngine
from timewarp_tpu.interp.jax_engine.sharded import ShardedEdgeEngine, make_mesh
from timewarp_tpu.models.gossip import gossip
from timewarp_tpu.models.token_ring import token_ring, token_ring_links
from timewarp_tpu.net.delays import Quantize, UniformDelay
from timewarp_tpu.parallel.mesh import _smap
from timewarp_tpu.trace.events import assert_states_equal


N = 48          # gossip nodes
K = 12          # their mailbox slots
UNI = Quantize(UniformDelay(3000, 9000), 1000)


def _gossip(burst):
    return gossip(N, fanout=3, think_us=700, burst=burst, end_us=90_000,
                  mailbox_cap=K)


def _ring():
    # the observer hub: an ordered inbox, two outbox slots
    return token_ring(16, n_tokens=5, think_us=4_000, bootstrap_us=1_000,
                      end_us=120_000, mailbox_cap=8)


EDGE_N = 24     # the lean ring's nodes: three a shard on the mesh of eight
EDGE_END_US = 90_000
EDGE_LINK = UniformDelay(1000, 5000)


def _edge_ring():
    # no observer: a static topology (the edge engines' scenario),
    # a commutative inbox
    return token_ring(EDGE_N, n_tokens=8, think_us=4_000,
                      bootstrap_us=1_000, end_us=EDGE_END_US,
                      with_observer=False, mailbox_cap=8)


def _edge_faults():
    """``_gossip_faults``' shape on the lean ring: a restart in
    mid-run, a crash, a partition, and a restart past the ring's own
    end."""
    return FaultSchedule((
        NodeCrash(3, 20_000, 50_000, reset_state=True),
        NodeCrash(10, 10_000, 30_000),
        Partition((tuple(range(12)), tuple(range(12, EDGE_N))),
                  40_000, 60_000),
        NodeCrash(9, EDGE_END_US + 20_000, EDGE_END_US + 50_000,
                  reset_state=True),
    ))


def _gossip_faults(end_us=90_000):
    """A crash with a restart in mid-run, a partition, a degraded
    window, and a second restart whose ``t_up`` lies past the wave's
    own end: the state is then quiet but for the injected reboot,
    which the horizon holds and a bare minimum over the mailbox and
    the wake times does not."""
    return FaultSchedule((
        NodeCrash(3, 6_000, 30_000, reset_state=True),
        NodeCrash(17, 5_000, 20_000),
        Partition((tuple(range(24)), tuple(range(24, N))),
                  12_000, 40_000),
        LinkWindow(tuple(range(16)), None, 45_000, 70_000,
                   scale=2.0, extra_us=1_000),
        NodeCrash(9, end_us + 20_000, end_us + 50_000, reset_state=True),
    ))


def _ring_faults():
    return FaultSchedule((
        NodeCrash(3, 20_000, 60_000, reset_state=True),
        NodeCrash(5, 10_000, 30_000),
        Partition((tuple(range(8)), tuple(range(8, 16))), 40_000, 80_000),
    ))


#: (scenario, link, constructor keywords) in the two routing regimes,
#: for a commutative and an ordered inbox, at ``window`` 1 and
#: ``"auto"``.
CASES = {
    "eager-commutative-w1": (lambda: _gossip(False), UNI, {}),
    "eager-ordered-w1": (_ring, token_ring_links(16), {}),
    "eager-ordered-auto": (_ring, token_ring_links(16),
                           {"window": "auto"}),
    "adaptive-commutative-w1": (lambda: _gossip(True), UNI, {}),
    "adaptive-commutative-auto": (lambda: _gossip(True), UNI,
                                  {"window": "auto"}),
    "adaptive-ordered-auto": (_ring, UniformDelay(1000, 5000),
                              {"window": "auto"}),
    # the edge engines (per-edge queues, no ladder, window 1): on one
    # device, and node-sharded over the mesh of eight, which takes no
    # fault schedule
    "edge-commutative-w1": (_edge_ring, EDGE_LINK, {"cap": 4}),
    "edge-commutative-w1-mesh8": (_edge_ring, EDGE_LINK, {"cap": 4}),
}


def _case(name, faulted):
    make, link, kw = CASES[name]
    sc = make()
    if name.endswith("mesh8"):
        return ShardedEdgeEngine(sc, link, make_mesh(8), lint="off", **kw)
    if name.startswith("edge"):
        return EdgeEngine(sc, link, lint="off",
                          faults=_edge_faults() if faulted else None, **kw)
    faults = None
    if faulted:
        faults = _ring_faults() if sc.n_nodes == 17 else _gossip_faults()
    return JaxEngine(sc, link, lint="off", faults=faults, **kw)


def _regime(eng):
    if isinstance(eng, EdgeEngine):
        return "edge"
    return "adaptive" if eng._adaptive_regime() else "eager"


def _same(a, b, tag):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b), strict=True):
        assert np.array_equal(np.asarray(x), np.asarray(y)), tag


def _resets(eng):
    return sum(c.reset_state for c in eng.faults.crashes)


def _restarts(st):
    return int(np.asarray(st.restart_done).sum())


def _jit(eng, st, f, ins, outs):
    """``jax.jit(f)``; for an engine over a mesh, ``f`` under the
    engine's own ``shard_map`` (its superstep's collectives need the
    mesh's axis bound), ``ins`` and ``outs`` naming each argument and
    result: ``s`` a state like ``st``, ``h`` a horizon, ``.`` a scalar
    every device holds alike."""
    if not hasattr(eng, "mesh"):
        return jax.jit(f)
    spec = {"s": eng._state_specs(st), "h": Horizon(P(), P(eng.axis)),
            ".": P()}
    out_specs = tuple(spec[c] for c in outs)
    return jax.jit(_smap(
        f, eng.mesh, tuple(spec[c] for c in ins),
        out_specs if len(outs) > 1 else out_specs[0]))


def _walk(eng, limit=400):
    """The run superstep by superstep through ``_superstep_carried``,
    held at every one to the plain ``_superstep`` and to the horizon
    found again from the new state. Returns the states, the fresh one
    first, the quiet one last."""
    def step(st, hz):
        new, hz2 = eng._superstep_carried(st, hz)
        return (new, hz2, eng._superstep(st, False)[0],
                eng._horizon(new), eng.comm.all_min(eng._next_event(new)))
    st = eng.init_state()
    step = _jit(eng, st, step, "sh", "shsh.")
    hz = _jit(eng, st, eng._horizon, "s", "h")(st)
    assert int(hz.t) == int(eng._next_event(st))    # nothing deferred yet
    states = [st]
    while int(hz.t) < NEVER:
        assert len(states) < limit, "the run did not go quiet"
        st, hz, plain, again, bare = step(st, hz)
        i = len(states)
        assert_states_equal(st, plain, f"superstep {i}")
        _same(hz, again, f"horizon after superstep {i}")
        if not eng._faulted:
            assert int(hz.t) == int(bare), i
        states.append(st)
    return states


def law_cases(*regimes):
    """The law's parametrization over the cases of ``regimes``."""
    return pytest.mark.parametrize("name, faulted", [
        (name, faulted) for name in sorted(CASES) for faulted in (False, True)
        if name.split("-")[0] in regimes
        and not (faulted and "mesh8" in name)],
        ids=lambda v: v if isinstance(v, str) else ("unfaulted", "faulted")[v])


def carried_horizon_law(name, faulted):
    eng = _case(name, faulted)
    assert _regime(eng) == name.split("-")[0]
    assert eng.scenario.commutative_inbox == ("commutative" in name)
    states = _walk(eng)
    quiet = len(states) - 1
    assert quiet > 12, quiet
    if faulted:
        assert int(states[-1].fault_dropped) > 0
        assert _restarts(states[-1]) == _resets(eng)
    mid = quiet // 2
    for budget in (0, 1, mid, quiet + 40):
        got = eng.run_quiet(budget)
        assert_states_equal(got, states[min(budget, quiet)],
                            f"run_quiet({budget})")
    # the scan driver, masks and all, at one budget inside the run
    # and, from the state that call returned, past quiescence
    mid_state, _ = eng.run(mid)
    assert_states_equal(mid_state, states[mid], f"run({mid})")
    for more in (0, 1, 3, quiet):
        got = eng.run_quiet(more, mid_state)
        assert_states_equal(got, states[min(mid + more, quiet)],
                            f"run_quiet({more}) from superstep {mid}")
    assert_states_equal(eng.run(quiet + 40 - mid, mid_state)[0], states[-1],
                        "run past quiescence")
