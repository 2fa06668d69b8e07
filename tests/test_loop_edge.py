"""The edge of the quiet loop (ISSUE 34; the edge engines: ISSUE 38):
``run_quiet`` decides a superstep's liveness once.

The ``while`` carries, beside the state, the state's event horizon
(``engine.Horizon``: the next event time ``t`` and each node's next
event), which every superstep produces for its successor from what it
has just written. So the loop's condition reads scalars (a fleet:
``[B]`` vectors) and nothing of the mailbox's rank, and a solo body,
which runs only when the condition has just found ``t`` pending,
selects no leaf of the state by a liveness that is known; a fleet's
body selects each world's state once, by live and in budget together.

Two kinds of case. A jaxpr walk of ``_run_while`` holds the shape of
the loop (each case fails on the parent, whose condition reduced the
``[K, N]`` mailbox and whose body selected the whole state by
``live``, a fleet's twice). A law holds the exactness: at every
superstep the carried horizon is the horizon of the state beside it,
the carried superstep is the plain ``_superstep``, and ``run_quiet``
lands on the state that many plain supersteps reach, for budgets 0,
1, mid-run and past quiescence, from a fresh state and from one an
earlier call returned, under a scalar budget and one per world.

The edge engines (``EdgeEngine`` and, on the virtual mesh of eight,
``ShardedEdgeEngine``) are cases of the same tests since ISSUE 38:
their loop carries the same ``Horizon``, their planes are the per-edge
queues ``q_rel`` and ``q_pay``.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.extend.core import Literal

from timewarp_tpu.core.scenario import NEVER
from timewarp_tpu.faults import (FaultFleet, FaultSchedule, LinkWindow,
                                 NodeCrash, Partition)
from jax.sharding import PartitionSpec as P

from timewarp_tpu.interp.jax_engine.batched import BatchSpec
from timewarp_tpu.interp.jax_engine.edge_engine import EdgeEngine, EdgeState
from timewarp_tpu.interp.jax_engine.engine import (EngineState, Horizon,
                                                   JaxEngine)
from timewarp_tpu.interp.jax_engine.sharded import (ShardedBatchedEngine,
                                                    ShardedEdgeEngine,
                                                    ShardedEngine, make_mesh)
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


# -- the shape of the loop: a walk of _run_while's jaxpr -------------------------

def _sub_jaxprs(eqn):
    """``(jaxpr, invars of eqn that its invars stand for or None)`` of
    every jaxpr nested in ``eqn``."""
    for val in eqn.params.values():
        vals = val if isinstance(val, (tuple, list)) else (val,)
        for v in vals:
            inner = getattr(v, "jaxpr", v)
            if not hasattr(inner, "eqns"):
                continue
            if eqn.primitive.name in ("jit", "pjit", "closed_call"):
                outer = list(eqn.invars)
            elif eqn.primitive.name == "cond":
                outer = list(eqn.invars[1:])
            else:
                outer = None
            yield inner, outer


def _eqns(jaxpr):
    """Every equation of ``jaxpr``, nested ones included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for inner, _ in _sub_jaxprs(eqn):
            yield from _eqns(inner)


def _selects(jaxpr, origin=None):
    """``(shape the predicate had before it was broadcast, shape
    selected)`` of every ``select_n`` of ``jaxpr``, nested ones
    included: ``jnp.where(live, new, old)`` on a scalar ``live`` is a
    ``jit(_where)`` whose predicate is broadcast inside it."""
    origin = dict(origin or {})

    def shape_of(v):
        return v.aval.shape if isinstance(v, Literal) \
            else origin.get(v, v.aval.shape)
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in ("broadcast_in_dim", "convert_element_type",
                    "reshape"):
            origin[eqn.outvars[0]] = min(
                shape_of(eqn.invars[0]), eqn.outvars[0].aval.shape,
                key=lambda s: int(np.prod(s)))
        elif name == "select_n":
            yield shape_of(eqn.invars[0]), eqn.outvars[0].aval.shape
        for inner, outer in _sub_jaxprs(eqn):
            inner_origin = {}
            if outer is not None and len(outer) == len(inner.invars):
                inner_origin = {iv: shape_of(ov) for iv, ov in
                                zip(inner.invars, outer)}
            yield from _selects(inner, inner_origin)


def _the_while(eng):
    """``(cond jaxpr, body jaxpr)`` of the one ``while`` of the
    engine's quiet driver."""
    if isinstance(eng, EdgeEngine):
        traced = type(eng)._run_while.trace(eng, eng.init_state(), 8)
    else:
        traced = type(eng)._run_while.trace(
            eng, eng.init_state(), eng._coerce_budget(8)[0],
            eng._identity())
    whiles = [e for e in _eqns(traced.jaxpr.jaxpr)
              if e.primitive.name == "while"]
    # the loop over supersteps carries the whole state: the widest one
    loop = max(whiles, key=lambda e: len(e.outvars))
    return loop.params["cond_jaxpr"].jaxpr, loop.params["body_jaxpr"].jaxpr


ENGINES = {
    "solo-eager": lambda: JaxEngine(_gossip(False), UNI, lint="off"),
    "solo-adaptive": lambda: JaxEngine(_gossip(True), UNI, window="auto",
                                       lint="off"),
    "solo-ordered": lambda: JaxEngine(_ring(), token_ring_links(16),
                                      lint="off"),
    "solo-faulted": lambda: JaxEngine(_gossip(True), UNI, window="auto",
                                      faults=_gossip_faults(), lint="off"),
    "fleet": lambda: JaxEngine(_gossip(True), UNI, window="auto",
                               lint="off",
                               batch=BatchSpec(seeds=(0, 1, 2))),
    "sharded": lambda: ShardedEngine(_gossip(True), UNI, make_mesh(8),
                                     lint="off"),
    "sharded-fleet": lambda: ShardedBatchedEngine(
        _gossip(True), UNI, make_mesh(2, "worlds"), window="auto",
        lint="off", batch=BatchSpec(seeds=(0, 1, 2, 3))),
    "edge": lambda: EdgeEngine(_edge_ring(), EDGE_LINK, cap=4, lint="off"),
    "edge-faulted": lambda: EdgeEngine(_edge_ring(), EDGE_LINK, cap=4,
                                       faults=_edge_faults(), lint="off"),
    "sharded-edge": lambda: ShardedEdgeEngine(
        _edge_ring(), EDGE_LINK, make_mesh(8), cap=4, lint="off"),
}


@functools.lru_cache(maxsize=None)
def _engine(name):
    return ENGINES[name]()


def _worlds(eng):
    """The world axis as the loop sees it: none, B, or a device's
    share of B."""
    if eng.batch is None:
        return None
    return getattr(eng, "worlds_local", eng.batch.B)


@pytest.mark.parametrize("name", ENGINES)
def test_the_condition_reads_scalars_and_reduces_nothing_of_the_state(name):
    eng = _engine(name)
    cond, _ = _the_while(eng)
    B = _worlds(eng)
    small = {()} if B is None else {(), (B,)}
    for eqn in _eqns(cond):
        shapes = {v.aval.shape for v in eqn.invars
                  if not isinstance(v, Literal)}
        assert shapes <= small, (eqn.primitive.name, shapes)
        if B is None:
            # a solo condition: two compares and an `and`, no
            # reduction and no collective at all (the mesh's minimum
            # was taken where the horizon was produced)
            assert not eqn.primitive.name.startswith(
                ("reduce", "arg", "pmin", "psum", "all_", "ppermute")
            ), eqn.primitive.name


@pytest.mark.parametrize("name", [n for n in ENGINES if "fleet" not in n])
def test_a_solo_body_selects_no_mailbox_plane_by_a_scalar(name):
    eng = _engine(name)
    _, body = _the_while(eng)
    st = eng.init_state()
    nl = eng.comm.n_local
    if isinstance(eng, EdgeEngine):
        planes = {st.q_rel.shape[:-1] + (nl,), st.q_pay.shape[:-1] + (nl,)}
    else:
        planes = {(st.mb_rel.shape[0], nl),
                  (st.mb_payload.shape[0], st.mb_payload.shape[1], nl)}
    by_scalar = [(p, s) for p, s in _selects(body)
                 if p == () and s in planes]
    assert not by_scalar, by_scalar


@pytest.mark.parametrize("name", [n for n in ENGINES if "fleet" in n])
def test_a_fleets_body_selects_each_mailbox_plane_once_by_world(name):
    eng = _engine(name)
    _, body = _the_while(eng)
    st = eng.init_state()
    B = _worlds(eng)
    rel = (B,) + st.mb_rel.shape[1:]
    pay = (B,) + st.mb_payload.shape[1:]
    by_world = [s for p, s in _selects(body) if p == (B,)]
    # mb_rel and mb_src share a shape: one select each (the parent:
    # two each, by `live` in the superstep and by budget in the body)
    assert by_world.count(rel) == 2, by_world
    assert by_world.count(pay) == 1, by_world


@pytest.mark.parametrize("name", ["solo-adaptive", "edge", "sharded-edge"])
def test_the_scan_for_the_horizon_is_inside_the_one_program(name):
    eng = _engine(name)
    eng.run_quiet(5)
    assert eng.last_run_stats["dispatches"] == 1
    assert eng.last_run_stats["readbacks"] == 1
    assert EdgeState._fields == (
        "states", "wake", "q_rel", "q_step", "q_pay", "overflow",
        "unrouted", "misrouted", "bad_delay", "delivered", "steps", "time",
        "fault_dropped", "restart_done")
    assert EngineState._fields == (
        "states", "wake", "mb_rel", "mb_src", "mb_payload", "overflow",
        "bad_dst", "bad_delay", "short_delay", "route_drop", "delivered",
        "steps", "time", "ev_time", "ev_meta", "ev_count", "fault_dropped",
        "restart_done")


# -- the law: the carried horizon, and the drivers ------------------------------

#: (scenario, link, constructor keywords, fault schedule or None) in
#: the three routing regimes, for a commutative and an ordered inbox,
#: at ``window`` 1 and ``"auto"``. ``route_cap`` (the lazy regime)
#: takes no fault schedule (``JaxEngine.__init__`` refuses the pair).
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
    "lazy-commutative-w1": (lambda: _gossip(True), UNI,
                            {"route_cap": 4 * N}),
    "lazy-ordered-auto": (_ring, UniformDelay(1000, 5000),
                          {"window": "auto", "route_cap": 64}),
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
    if eng._adaptive_regime():
        return "adaptive"
    return "lazy" if eng.route_cap is not None else "eager"


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


@pytest.mark.parametrize("name, faulted", [
    (name, faulted) for name in sorted(CASES) for faulted in (False, True)
    if not (faulted and ("lazy" in name or "mesh8" in name))],
    ids=lambda v: v if isinstance(v, str) else ("unfaulted", "faulted")[v])
def test_the_carried_horizon_is_the_states_and_the_drivers_agree(
        name, faulted):
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


@pytest.mark.parametrize("name", ["adaptive-commutative-auto",
                                  "edge-commutative-w1"])
def test_the_pending_restart_keeps_the_quiet_loop_running(name):
    """What only the horizon sees: every wake time NEVER, every
    mailbox (every edge's queue) empty, one reboot still to come. The
    scan driver always fired it; the quiet loop's condition asked the
    bare minimum. The edge engine's probe of a state at rest
    (``world_active``, between a controlled driver's chunks) asks the
    horizon under a fault schedule, and reads such a state as active."""
    eng = _case(name, True)
    states = _walk(eng)
    lull = [i for i, st in enumerate(states[:-1])
            if int(eng._node_next(st).min()) >= NEVER]
    assert lull, "no state is quiet but for the restart"
    if isinstance(eng, EdgeEngine):
        assert bool(eng.world_active(states[lull[0]]))
        assert not bool(eng.world_active(states[-1]))
    past = eng.run_quiet(len(states) + 40)
    assert int(past.steps) == len(states) - 1 > lull[0]
    assert _restarts(past) == _resets(eng) == 2


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
