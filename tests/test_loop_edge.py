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

Here: the walk, the edge engines' cases of the law, and the restart
that only the horizon sees. The general engine's cases of the law are
tests/test_loop_edge_eager.py and ``_adaptive.py``, a fleet's
``_fleet.py`` (a file is one worker's, and a case compiles an engine's
five programs); tests/loop_edge_laws.py has what they share.
"""

import functools

import numpy as np
import pytest

from jax.extend.core import Literal

from loop_edge_laws import (EDGE_LINK, UNI, _case, _edge_faults, _edge_ring,
                            _gossip, _gossip_faults, _resets, _restarts, _ring,
                            _walk, carried_horizon_law, law_cases)
from timewarp_tpu.core.scenario import NEVER
from timewarp_tpu.interp.jax_engine.batched import BatchSpec
from timewarp_tpu.interp.jax_engine.edge_engine import EdgeEngine, EdgeState
from timewarp_tpu.interp.jax_engine.engine import EngineState, JaxEngine
from timewarp_tpu.interp.jax_engine.sharded import (ShardedBatchedEngine,
                                                    ShardedEdgeEngine,
                                                    ShardedEngine, make_mesh)
from timewarp_tpu.models.token_ring import token_ring_links


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


@law_cases("edge")
def test_the_carried_horizon_is_the_states_and_the_drivers_agree(
        name, faulted):
    carried_horizon_law(name, faulted)


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
