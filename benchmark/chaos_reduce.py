"""Device time of a faulted fleet's fault masks, from the profile's
``op_name``s the builder brought (``facts()["op_names"]``, read in
``compare`` while ``run.py`` still has the file: README_fleet.md).

The engine enters ``jax.named_scope("fault")`` at every site that
reads the fault tables (engine.py: ``defer_next`` under
``tw.next_event``; ``cut_mask``, ``down_mask`` and ``degrade`` under
``tw.route``, the last inside ``sample``; the reboot's reset under
``tw.fire``), so the scope sits at no fixed depth: an operation counts
where any component of its path below a ``tw.`` stage is ``fault``.
The compiler fuses a mask into its consumer where it can, and a fusion
carries one operation's name: what is read is the time of the
operations that kept the scope's name, a floor of the masks' cost
(the twin without ``faults=`` gives the whole: README_chaos.md).
"""

import fleet_reduce
import span_reduce

SCOPE = "fault"


def under_fault(op_name: str) -> bool:
    """Whether ``op_name`` (wrappers off) lies under a ``fault`` scope
    inside a ``tw.`` stage."""
    parts = fleet_reduce.unwrap(op_name).split("/")[:-1]
    for i, part in enumerate(parts):
        if part.startswith(span_reduce.SPAN_PREFIX):
            return SCOPE in parts[i + 1:]
    return False


def fault_us(trace, run):
    """Device microseconds an iteration of the fleet's loop of the
    first chip's leaf operations under a ``fault`` scope; ``None``
    where the builder brought no names or the program has no such
    scope (a parent commit from before it, an engine without
    ``faults``)."""
    names = run["facts"].get("op_names")
    steps = span_reduce.supersteps(run)
    if not names or not steps:
        return None
    if not any(under_fault(n) for n in names.values()):
        return None
    ns = sum(d for _, d, name in trace.ops[0]
             if under_fault(names.get(name, "")))
    return ns / steps / 1e3
