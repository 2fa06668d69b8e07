"""What a fleet's profile needs beyond ``span_reduce.py``.

A fleet (``JaxEngine(batch=BatchSpec(...))``) traces its superstep
inside ``jax.vmap``, and JAX 0.9.0 writes a ``jax.named_scope`` entered
there as ``vmap(tw.route)`` in an operation's ``op_name``
(``jit(_run_while)/while/body/vmap(tw.route)/insert/jit(sort)/sort``:
scopes nested in the stage keep their names). ``span_reduce.stage_of``
takes a component that *starts with* ``tw.`` and counts ``\\w+(...)`` as
JAX's own, so every operation of a fleet reads as unscoped there.
:func:`unwrap` takes the wrapper off; the rest is ``span_reduce``'s.

``run.py --trace 1`` hands a reader a ``Trace`` (HLO text, no
``op_name``) and has deleted the profiler's file by then (README_spans.md).
It calls the builder's ``compare`` between ``stop_trace`` and that
deletion, though: a builder that wants stage times reads the file's
``op_name``s there (:func:`traced_op_names`) and hands them to the
readers through ``facts()``.
"""

import os
import re

import span_reduce
import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))

#: one path component that is nothing but a ``tw.`` scope inside the
#: name of a transformation, or of several: ``vmap(vmap(tw.fire))``
_WRAPPED = re.compile(r"(?:\w+\()+(tw\.[^()/]*)\)+")


def unwrap(op_name: str) -> str:
    """``op_name`` with every component ``vmap(tw.<stage>)`` written
    ``tw.<stage>``; every other component (``jit(sort)``, ``while``,
    ``insert``) as it was."""
    return "/".join(
        m.group(1) if (m := _WRAPPED.fullmatch(part)) else part
        for part in op_name.split("/"))


def traced_op_names(workload: str, seed: int):
    """``{HLO text: op_name}`` of the first chip's operations, from the
    profile ``run.py --trace 1`` wrote for this cell and seed and has
    not deleted yet; ``None`` where there is none (an untraced run)."""
    logdir = os.path.join(HERE, "out", f"trace_{workload}_{seed}")
    try:
        by_plane = span_reduce.op_names(trace_reduce.find_xplane(logdir))
    except FileNotFoundError:
        return None
    return by_plane[min(by_plane)] if by_plane else None


def stage_ns(trace, run, depth: int = 1):
    """Device nanoseconds of the first chip's leaf operations by
    ``tw.`` stage, wrappers off; ``None`` where the builder brought no
    ``op_name``s (no profile) or none of them names a stage."""
    names = run["facts"].get("op_names")
    if not names:
        return None
    ops = trace.ops[0]
    acc = span_reduce.stage_ns(
        ops, [unwrap(names.get(name, "")) for _, _, name in ops], depth)
    return None if set(acc) <= {span_reduce.UNSCOPED} else acc


def stage_us(trace, run, stage: str):
    """Device microseconds an iteration of the fleet's loop under the
    scope ``stage`` (a fleet job's ``supersteps`` is the loop's
    iterations: the largest of its worlds' counts)."""
    acc, n = stage_ns(trace, run), span_reduce.supersteps(run)
    if acc is None or not n or stage not in acc:
        return None
    return acc[stage] / n / 1e3
