"""Jaxpr contract lints: check a scenario's step function *before* any
engine run.

The framework's determinism contract (core/scenario.py:28-47) is only
usable at scale if violations are caught statically — a host callback
or an int32 time truncation inside a user step function otherwise
surfaces as a parity digest mismatch thousands of supersteps into a
million-node run. This module traces ``Scenario.step`` abstractly with
``jax.make_jaxpr`` under the exact aval conventions the engines use
(inbox width ``mailbox_cap``, int64 ``now``, the threefry entropy pair
from core/rng.py) and checks:

- **TW101** host-escape primitives (``pure_callback`` / ``io_callback``
  / ``debug_callback`` …): arbitrary host IO has no deterministic
  virtual-time meaning (the same reason the pure emulator rejects
  ``AwaitIO``, interp/ref/des.py) and breaks oracle/engine parity.
- **TW102/TW103** time-dtype discipline: int64 time values (``now``,
  ``inbox.time``, int64 state leaves) must never be truncated to a
  narrower integer (TW102) or promoted to float (TW103) — found by
  taint-propagating through the jaxpr, including into
  scan/while/cond/pjit sub-jaxprs.
- **TW104** ``next_wake`` must be a scalar int64 (the engine compares
  it against ``NEVER = 2^62-1``, which no narrower dtype can hold).
- **TW105** outbox conformance: ``valid`` bool[max_out], ``dst``
  integer[max_out], ``payload`` int32[max_out, payload_width] — the
  shapes/dtypes the routing sorts and mailbox scatters are compiled
  for.
- **TW106** state pytree stability: ``step`` must return states with
  the structure/shape/dtype it was given (``lax.scan`` carries them).
- **TW107–TW110** declared-flag dataflow: ``needs_key=False`` ⇔ the
  key input has no consumers in the jaxpr, ``inbox_src=False`` ⇔
  ``inbox.src`` is unused. A false ``False`` is an error (the engine
  feeds ``None``/zeros — silent divergence); a conservative ``True``
  over an unused input is a perf warning (the engine derives entropy /
  scatters the src plane for nothing every superstep).

All checks are abstract — nothing is executed, so ``lint="warn"``
engine construction cannot change run behavior.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Set, Tuple

from ..utils import jaxconfig  # noqa: F401  (must precede jax use)

import jax
import jax.numpy as jnp

try:
    # the version-stable home of the jaxpr IR types (jax >= 0.6
    # removed them from jax.core; jax.extend.core carries them on both
    # sides of that line — same shim idea as parallel/mesh.py)
    from jax.extend import core as jcore
    _ = jcore.Var, jcore.Literal, jcore.Jaxpr, jcore.ClosedJaxpr
except (ImportError, AttributeError):  # pragma: no cover — old jax
    from jax import core as jcore

from ..core.scenario import Inbox, Scenario
from .report import ERROR, INFO, WARNING, Finding, LintReport

__all__ = ["lint_step_jaxpr", "HOST_ESCAPE_PRIMITIVES"]

#: primitives whose presence in a step jaxpr breaks the determinism
#: contract (host escapes have no virtual-time meaning)
HOST_ESCAPE_PRIMITIVES = frozenset({
    "pure_callback", "io_callback", "debug_callback", "callback",
    "outside_call", "host_callback_call", "infeed", "outfeed",
})

_I64 = (jnp.dtype(jnp.int64), jnp.dtype(jnp.uint64))


def _is_time_dtype(dt) -> bool:
    return jnp.dtype(dt) in _I64


def _is_time_var(v) -> bool:
    """64-bit-integer-typed var (False for drop vars / tokens — no
    isinstance on DropVar, which has no version-stable public home)."""
    dt = getattr(getattr(v, "aval", None), "dtype", None)
    return dt is not None and _is_time_dtype(dt)


# ----------------------------------------------------------------------
# higher-order eqn plumbing
# ----------------------------------------------------------------------

def _open(j):
    """ClosedJaxpr -> Jaxpr (identity for open jaxprs)."""
    return j.jaxpr if isinstance(j, jcore.ClosedJaxpr) else j


def _subjaxpr_maps(eqn) -> Optional[List[Tuple[
        Any, List[Optional[int]], List[Optional[int]],
        List[Optional[int]]]]]:
    """For a higher-order eqn, return ``[(jaxpr, invar_map, outvar_map,
    carry_map), ...]`` where ``invar_map[i]`` is the index into
    ``eqn.invars`` that feeds the sub-jaxpr's i-th invar (None = no
    direct feed), ``outvar_map[o]`` the index into ``eqn.outvars`` the
    o-th sub outvar produces, and ``carry_map[o]`` the sub-jaxpr
    *invar* index the o-th sub outvar loops back into (scan/while
    carries; None = no loop). Returns None for first-order eqns; ``[]``
    for an *unknown* higher-order primitive (callers must be
    conservative).
    """
    name = eqn.primitive.name
    params = eqn.params
    if name in ("pjit", "closed_call", "core_call", "remat", "remat2",
                "checkpoint", "custom_jvp_call", "custom_vjp_call"):
        j = params.get("jaxpr") or params.get("call_jaxpr")
        if j is None:
            return []
        jx = _open(j)
        return [(jx, list(range(len(jx.invars))),
                 list(range(len(jx.outvars))),
                 [None] * len(jx.outvars))]
    if name == "scan":
        jx = _open(params["jaxpr"])
        nc, ncar = params["num_consts"], params["num_carry"]
        # eqn.invars = consts + carry_init + xs; body invars align 1:1
        # (xs enter as per-iteration slices — same positions). Body
        # outvars = carry + ys align 1:1 with eqn.outvars; carry outvar
        # o feeds body invar nc + o on the next iteration.
        return [(jx, list(range(len(jx.invars))),
                 list(range(len(jx.outvars))),
                 [nc + o if o < ncar else None
                  for o in range(len(jx.outvars))])]
    if name == "while":
        cj, bj = _open(params["cond_jaxpr"]), _open(params["body_jaxpr"])
        cn, bn = params["cond_nconsts"], params["body_nconsts"]
        cmap = [i if i < cn else cn + bn + (i - cn)
                for i in range(len(cj.invars))]
        bmap = [cn + i if i < bn else cn + bn + (i - bn)
                for i in range(len(bj.invars))]
        # body outvars are the carry, which is eqn.outvars 1:1 and
        # loops back into body invar bn + o; the cond jaxpr produces
        # only the predicate
        return [(cj, cmap, [None] * len(cj.outvars),
                 [None] * len(cj.outvars)),
                (bj, bmap, list(range(len(bj.outvars))),
                 [bn + o for o in range(len(bj.outvars))])]
    if name == "cond":
        out = []
        for br in params["branches"]:
            jx = _open(br)
            out.append((jx, [1 + i for i in range(len(jx.invars))],
                        list(range(len(jx.outvars))),
                        [None] * len(jx.outvars)))
        return out
    # first-order unless the params hide a jaxpr we don't know how to map
    for v in params.values():
        if isinstance(v, (jcore.Jaxpr, jcore.ClosedJaxpr)):
            return []
        if isinstance(v, (tuple, list)) and any(
                isinstance(x, (jcore.Jaxpr, jcore.ClosedJaxpr)) for x in v):
            return []
    return None


def _all_jaxprs(jaxpr):
    """Every jaxpr reachable from ``jaxpr`` (itself included)."""
    yield jaxpr
    for eqn in jaxpr.eqns:
        for v in eqn.params.values():
            vs = v if isinstance(v, (tuple, list)) else (v,)
            for x in vs:
                if isinstance(x, (jcore.Jaxpr, jcore.ClosedJaxpr)):
                    yield from _all_jaxprs(_open(x))


# ----------------------------------------------------------------------
# invar consumption (flag dataflow)
# ----------------------------------------------------------------------

def _used_invar_positions(jaxpr, cache: Dict[int, Set[Any]]) -> Set[Any]:
    """The set of ``jaxpr`` vars that are *actually consumed* — fed to a
    first-order eqn, or fed to a sub-jaxpr invar that is itself
    consumed (so dead pass-through plumbing does not count as use)."""
    key = id(jaxpr)
    if key in cache:
        return cache[key]
    used: Set[Any] = set()
    cache[key] = used           # cycle guard (jaxprs are acyclic, but
    # a var flowing straight to an output IS consumed — a step that
    # returns its key (or inbox.src) in state observes it, and the
    # engine would feed None/zeros for the conservative flag
    used.update(v for v in jaxpr.outvars if isinstance(v, jcore.Var))
    for eqn in jaxpr.eqns:      # the cache doubles as memo
        maps = _subjaxpr_maps(eqn)
        if maps is None or maps == []:
            # first-order or unknown higher-order: every invar counts
            for v in eqn.invars:
                if isinstance(v, jcore.Var):
                    used.add(v)
            continue
        live_positions: Set[int] = set()
        for jx, invmap, _, _ in maps:
            inner_used = _used_invar_positions(jx, cache)
            for i, pos in enumerate(invmap):
                if pos is not None and jx.invars[i] in inner_used:
                    live_positions.add(pos)
        for pos in live_positions:
            v = eqn.invars[pos]
            if isinstance(v, jcore.Var):
                used.add(v)
    return used


# ----------------------------------------------------------------------
# time-dtype taint
# ----------------------------------------------------------------------

def _taint_jaxpr(jaxpr, tainted: Set[Any], emit) -> None:
    """Propagate int64-time taint through ``jaxpr`` eqns in order,
    calling ``emit(kind, eqn)`` on a truncating or float-promoting
    ``convert_element_type`` of a tainted value. Taint survives any
    first-order op whose output stays 64-bit integer; comparisons
    (bool) and legitimate narrow results drop it."""
    for eqn in jaxpr.eqns:
        tin = any(isinstance(v, jcore.Var) and v in tainted
                  for v in eqn.invars)
        if not tin:
            continue
        name = eqn.primitive.name
        if name == "convert_element_type":
            src = eqn.invars[0]
            new = jnp.dtype(eqn.params["new_dtype"])
            if isinstance(src, jcore.Var) and src in tainted:
                if jnp.issubdtype(new, jnp.floating):
                    emit("float", eqn)
                elif (jnp.issubdtype(new, jnp.integer)
                        and new.itemsize < 8):
                    emit("truncate", eqn)
        maps = _subjaxpr_maps(eqn)
        if maps:
            # seed inner taint from the mapped outer invars; iterate to
            # a fixpoint so loop-carried taint (scan/while carries)
            # propagates — bounded tiny (taint sets only grow)
            out_tainted: Set[int] = set()
            for jx, invmap, outmap, carrymap in maps:
                inner: Set[Any] = set()
                for i, pos in enumerate(invmap):
                    if pos is None:
                        continue
                    v = eqn.invars[pos]
                    if isinstance(v, jcore.Var) and v in tainted:
                        inner.add(jx.invars[i])
                while True:
                    before = len(inner)
                    _taint_jaxpr(jx, inner, emit)
                    for o, ov in enumerate(jx.outvars):
                        if isinstance(ov, jcore.Var) and ov in inner:
                            if outmap[o] is not None:
                                out_tainted.add(outmap[o])
                            # loop-carried taint: a tainted carry
                            # outvar re-enters at its carry invar
                            if carrymap[o] is not None:
                                inner.add(jx.invars[carrymap[o]])
                    if len(inner) == before:
                        break
            for pos in out_tainted:
                ov = eqn.outvars[pos]
                if _is_time_var(ov):
                    tainted.add(ov)
            continue
        # first-order (or unknown higher-order) default: 64-bit integer
        # outputs of a tainted computation stay tainted
        for ov in eqn.outvars:
            if _is_time_var(ov):
                tainted.add(ov)


# ----------------------------------------------------------------------
# entry point
# ----------------------------------------------------------------------

def _lint_avals(sc: Scenario):
    """The engines' aval conventions for one (vmapped-out) node."""
    K, P = sc.mailbox_cap, sc.payload_width
    state0, _ = sc.init(0)
    state0 = jax.tree.map(jnp.asarray, state0)
    inbox = Inbox(valid=jnp.zeros((K,), bool),
                  src=jnp.zeros((K,), jnp.int32),
                  time=jnp.zeros((K,), jnp.int64),
                  payload=jnp.zeros((K, P), jnp.int32))
    now = jnp.int64(0)
    nid = jnp.int32(0)
    key = (jnp.zeros((), jnp.uint32), jnp.zeros((), jnp.uint32))
    return state0, inbox, now, nid, key


def lint_step_jaxpr(sc: Scenario) -> LintReport:
    """Trace ``sc.step`` abstractly and run every jaxpr contract lint.
    Never executes the step; never raises on untraceable steps (the
    engine's own trace produces the authoritative error — TW100 marks
    the lint as unable to look inside)."""
    rep = LintReport()
    name = sc.name
    M, P = sc.max_out, sc.payload_width

    try:
        state0, inbox, now, nid, key = _lint_avals(sc)
    except Exception as e:  # noqa: BLE001 — lint must not crash callers
        rep.add(Finding("TW100", WARNING, name,
                        f"init(0) failed under lint ({e!r}); jaxpr "
                        "lints skipped"))
        return rep

    key_traced = True
    try:
        closed, out_shape = jax.make_jaxpr(sc.step, return_shape=True)(
            state0, inbox, now, nid, key)
    except Exception as e_with_key:  # noqa: BLE001
        if sc.needs_key:
            rep.add(Finding("TW100", WARNING, name,
                            "step is not traceable under the engine "
                            f"aval conventions ({e_with_key!r}); jaxpr "
                            "lints skipped"))
            return rep
        # needs_key=False engines pass key=None — some steps require it
        key, key_traced = None, False
        try:
            closed, out_shape = jax.make_jaxpr(
                sc.step, return_shape=True)(state0, inbox, now, nid, key)
        except Exception as e:  # noqa: BLE001
            rep.add(Finding("TW100", WARNING, name,
                            "step is not traceable under the engine "
                            f"aval conventions ({e!r}); jaxpr lints "
                            "skipped"))
            return rep

    jaxpr = closed.jaxpr

    # -- TW101: host-escape primitives ---------------------------------
    seen_escapes = []
    for jx in _all_jaxprs(jaxpr):
        for eqn in jx.eqns:
            if eqn.primitive.name in HOST_ESCAPE_PRIMITIVES:
                seen_escapes.append(eqn.primitive.name)
    for prim in sorted(set(seen_escapes)):
        rep.add(Finding(
            "TW101", ERROR, name,
            f"step contains host-escape primitive {prim!r} "
            f"(x{seen_escapes.count(prim)}): host callbacks have no "
            "deterministic virtual-time meaning and break oracle/"
            "engine parity — compute inside the step or precompute "
            "into state"))

    # -- invar layout ----------------------------------------------------
    state_leaves = jax.tree.flatten(state0)[0]
    ns = len(state_leaves)
    iv = jaxpr.invars
    # flatten order: state leaves, inbox(valid, src, time, payload),
    # now, node_id, key words
    v_src, v_time, v_now = iv[ns + 1], iv[ns + 2], iv[ns + 4]
    key_vars = list(iv[ns + 6:ns + 8]) if key_traced else []

    # -- TW107..TW110: declared-flag dataflow ----------------------------
    used = _used_invar_positions(jaxpr, {})
    if key_traced:
        key_used = any(v in used for v in key_vars)
        if key_used and not sc.needs_key:
            rep.add(Finding(
                "TW107", ERROR, name,
                "needs_key=False but the step consumes its key input; "
                "engines pass key=None for this flag, so the run would "
                "crash at trace time (or silently use garbage). Declare "
                "needs_key=True"))
        elif not key_used and sc.needs_key:
            rep.add(Finding(
                "TW108", WARNING, name,
                "needs_key=True but the key input has no consumers in "
                "the jaxpr: engines derive per-(node, instant) threefry "
                "entropy every superstep for nothing. Declare "
                "needs_key=False"))
    src_used = v_src in used
    if src_used and not sc.inbox_src:
        rep.add(Finding(
            "TW109", ERROR, name,
            "inbox_src=False but the step reads inbox.src; engines "
            "elide the src mailbox plane for this flag and present "
            "zeros — sender identity would silently diverge between "
            "interpreters. Declare inbox_src=True"))
    elif not src_used and sc.inbox_src:
        rep.add(Finding(
            "TW110", WARNING, name,
            "inbox.src has no consumers in the jaxpr but "
            "inbox_src=True: the engines scatter the mailbox src plane "
            "(~1/3 of the dense random-delivery cost floor, "
            "docs/engines.md) for a field the step never reads. Declare "
            "inbox_src=False"))

    # -- TW102/TW103: time-dtype taint ----------------------------------
    tainted: Set[Any] = {v_now, v_time}
    for i, leaf in enumerate(state_leaves):
        if _is_time_dtype(jnp.asarray(leaf).dtype):
            tainted.add(iv[i])
    # dedupe by eqn identity: the loop-carry fixpoint re-walks bodies
    hit_ids: Dict[str, Set[int]] = {"truncate": set(), "float": set()}

    def emit(kind, eqn):
        hit_ids[kind].add(id(eqn))

    _taint_jaxpr(jaxpr, tainted, emit)
    hits = {k: len(v) for k, v in hit_ids.items()}
    if hits["truncate"]:
        rep.add(Finding(
            "TW102", ERROR, name,
            f"int64 time value truncated to a narrower integer dtype "
            f"({hits['truncate']} conversion(s) in the step jaxpr): "
            "virtual time exceeds int32 after ~35 minutes; keep "
            "next_wake/inbox.time arithmetic in int64"))
    if hits["float"]:
        rep.add(Finding(
            "TW103", ERROR, name,
            f"int64 time value promoted to float "
            f"({hits['float']} conversion(s) in the step jaxpr): float "
            "time breaks the bit-exact cross-backend contract "
            "(core/time.py — int64 µs only). Check for python-float "
            "literals leaking into time arithmetic"))

    # -- output conformance ---------------------------------------------
    try:
        state_out, out, wake = out_shape
    except (TypeError, ValueError):
        rep.add(Finding(
            "TW105", ERROR, name,
            "step must return (state', outbox, next_wake); got "
            f"{jax.tree.structure(out_shape)}"))
        return rep

    # TW104: next_wake scalar int64
    wake_dt, wake_shape = jnp.dtype(wake.dtype), tuple(wake.shape)
    if wake_shape != () or wake_dt != jnp.dtype(jnp.int64):
        rep.add(Finding(
            "TW104", ERROR, name,
            f"next_wake must be a scalar int64 (got shape {wake_shape}, "
            f"dtype {wake_dt}): the engine clamps it against NEVER = "
            "2^62-1, which no narrower dtype can represent"))

    # TW105: outbox conformance
    ob = None
    if not (hasattr(out, "valid") and hasattr(out, "dst")
            and hasattr(out, "payload")):
        rep.add(Finding(
            "TW105", ERROR, name,
            "second return value must be an Outbox(valid, dst, "
            f"payload); got {type(out).__name__}"))
    else:
        ob = out
    if ob is not None:
        checks = [
            ("valid", ob.valid, (M,), (jnp.dtype(bool),)),
            ("dst", ob.dst, (M,),
             tuple(jnp.dtype(d) for d in (jnp.int32, jnp.int64,
                                          jnp.int16, jnp.int8))),
            ("payload", ob.payload, (M, P), (jnp.dtype(jnp.int32),)),
        ]
        for fname, leaf, want_shape, want_dts in checks:
            shape, dt = tuple(leaf.shape), jnp.dtype(leaf.dtype)
            if shape != want_shape:
                rep.add(Finding(
                    "TW105", ERROR, name,
                    f"outbox.{fname} shape {shape} != {want_shape} "
                    f"(max_out={M}, payload_width={P}): the routing "
                    "sorts and mailbox scatters are compiled for the "
                    "declared widths"))
            elif dt not in want_dts:
                rep.add(Finding(
                    "TW105", ERROR, name,
                    f"outbox.{fname} dtype {dt} is not "
                    f"{'/'.join(str(d) for d in want_dts)}: engines "
                    "scatter payloads into int32 mailbox planes and "
                    "read dst as an integer index"))
            elif fname == "dst" and dt != jnp.dtype(jnp.int32):
                rep.add(Finding(
                    "TW105", INFO, name,
                    f"outbox.dst dtype {dt}; engines convert to int32 "
                    "every superstep — emit int32 directly"))

    # TW106: state pytree stability
    in_td = jax.tree.structure(state0)
    out_td = jax.tree.structure(state_out)
    if in_td != out_td:
        rep.add(Finding(
            "TW106", ERROR, name,
            f"state pytree structure changes across step ({in_td} -> "
            f"{out_td}); lax.scan carries the state and requires a "
            "stable structure"))
    else:
        for i, (a, b) in enumerate(zip(state_leaves,
                                       jax.tree.flatten(state_out)[0])):
            a = jnp.asarray(a)
            if tuple(a.shape) != tuple(b.shape) \
                    or jnp.dtype(a.dtype) != jnp.dtype(b.dtype):
                rep.add(Finding(
                    "TW106", ERROR, name,
                    f"state leaf #{i} changes shape/dtype across step "
                    f"({a.shape}/{a.dtype} -> {b.shape}/{b.dtype}); "
                    "lax.scan requires shape/dtype-stable carries"))
    return rep
