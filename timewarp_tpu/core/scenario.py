"""State-machine scenario IR — the batchable form of a timed program.

This is *the* key design move of the TPU build (SURVEY.md §7): the
continuation that the reference captures at every ``wait``
(`/root/reference/src/Control/TimeWarp/Timed/TimedT.hs:343-355`) becomes
an explicit ``(state, next_wake)`` pair, and the per-node behavior is a
pure **step function** that XLA can ``vmap`` over a million nodes:

    step(state, inbox, now, node_id, key) -> (state', outbox, next_wake)

A scenario written this way runs under *both* interpreters and must
produce identical event traces:

- :class:`timewarp_tpu.interp.ref.superstep.SuperstepOracle` — the pure
  host reference executor (the oracle).
- :class:`timewarp_tpu.interp.jax_engine.engine.JaxEngine` — the batched
  XLA engine (``vmap`` + ``lax.scan``; sharded over the TPU mesh).

Superstep semantics (shared contract)
-------------------------------------

Virtual time advances to the *global* minimum next-event time each
superstep, and **all** nodes whose next event is at that instant fire
simultaneously (the reference pops one event at a time, TimedT.hs:
239-263; firing all-at-min is the batched equivalent and coincides with
it because co-temporal events cannot observe each other's effects —
messages take ≥ 1 µs, below).

Determinism contract (SURVEY.md §5.2 — explicit where the reference
leaned on heap internals):

1. A node's next event time = ``min(next_wake, earliest pending message
   deliver-time)``.
2. The inbox a firing node sees = all pending messages with
   ``deliver_time <= now``, ordered by ``(deliver_time, arrival order)``.
3. Messages are routed after all co-temporal fires, in sender-major
   order (node 0's outbox slot 0, slot 1, …, node 1's …) — globally,
   arrival order == chronological routing order.
4. A delivered message is in flight for ``max(sampled_delay, 1)`` µs —
   a zero-latency link still crosses a scheduling point, as in the
   reference where a 0-delay ``ConnectedIn`` message is still handled by
   a later event (examples/token-ring/Main.hs:73-77).
5. A fired node's new ``next_wake`` is clamped to ``> now`` (or NEVER);
   re-arming at the same instant would stall virtual time.
6. Mailboxes are bounded (``mailbox_cap``); overflowing messages are
   counted and dropped, never silently lost (SURVEY.md §7).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, NamedTuple, Optional, Tuple

from .time import FOREVER, Microsecond

#: next_wake sentinel: the node has no timer armed.
NEVER: Microsecond = FOREVER


class Inbox(NamedTuple):
    """Messages visible to one node at its firing instant.

    Arrays are fixed-width ``mailbox_cap`` (K); invalid slots padded.
    Slot order follows the determinism contract: (deliver_time, arrival).
    """
    valid: Any    # bool[K]
    src: Any      # int32[K]
    time: Any     # int64[K] — deliver time in µs
    payload: Any  # int32[K, P]


class Outbox(NamedTuple):
    """Messages one node emits from one firing; fixed width ``max_out``."""
    valid: Any    # bool[M]
    dst: Any      # int32[M]
    payload: Any  # int32[M, P]


#: step(state, inbox, now, node_id, key) -> (state', outbox, next_wake)
StepFn = Callable[[Any, Inbox, Any, Any, Any], Tuple[Any, Outbox, Any]]

#: init(node_id) -> (state pytree, first_wake) — host-level, per node.
InitFn = Callable[[int], Tuple[Any, Microsecond]]

#: init_batched(n) -> (stacked state pytree [N,...], wake int64[N])
InitBatchedFn = Callable[[int], Tuple[Any, Any]]


@dataclass
class Scenario:
    """A complete batchable scenario (≙ a whole multi-node program that
    the reference would run via fork-per-node, e.g. token-ring
    examples/token-ring/Main.hs:63-72).

    ``step`` must be a pure, jittable function of fixed-shape arrays —
    no Python control flow on traced values. ``init`` gives per-node
    initial state for the host oracle; ``init_batched`` (optional) gives
    the same states natively vectorized for million-node engine runs.
    """
    name: str
    n_nodes: int
    step: StepFn
    init: InitFn
    payload_width: int = 2
    max_out: int = 1
    mailbox_cap: int = 8
    init_batched: Optional[InitBatchedFn] = None
    #: whether ``step`` consumes its entropy argument (core/rng.py
    #: ``fire_bits`` pair); engines skip deriving it when False
    needs_key: bool = False
    #: static communication graph: int32 [N, M] destination of each
    #: outbox slot (-1 = slot never used), when the scenario only ever
    #: sends along fixed edges. Enables the sort/scatter-free edge
    #: engine (interp/jax_engine/edge_engine.py).
    static_dst: Optional[Any] = None
    #: True when ``step`` is insensitive to inbox slot *order* (it
    #: reduces over the inbox commutatively). Lets engines skip the
    #: contract-#2 inbox sort; parity still holds bit-for-bit because
    #: digests are order-independent and the step result is too.
    commutative_inbox: bool = False
    #: False when ``step`` never reads ``inbox.src`` (sender identity
    #: is not part of the scenario's semantics — e.g. a gossip adopt is
    #: a pure payload reduction). Engines then skip storing/scattering
    #: the mailbox src field (mailbox scatters are the dense
    #: random-delivery cost floor on TPU, docs/engines.md "Measured on a v5e"), ``inbox.src``
    #: reads as 0, and ALL interpreters hash src as 0 in the RECV
    #: digest — the parity law still pins every delivered message's
    #: (dst, time, payload), just not its sender.
    inbox_src: bool = True
    #: metadata for bench/trace tooling
    meta: dict = field(default_factory=dict)

    def __post_init__(self) -> None:
        """Declaration sanity — actionable errors at build time instead
        of shape mismatches (or silence) deep inside an engine. The
        deeper semantic checks (step dataflow, capacity proofs, flag
        validation) live in :mod:`timewarp_tpu.analysis`."""
        import numpy as _np
        for attr, why in (
                ("n_nodes", "a scenario needs at least one node"),
                ("mailbox_cap",
                 "every node needs at least one mailbox slot "
                 "(determinism contract #6 bounds, not eliminates, it)"),
                ("max_out",
                 "the outbox is fixed-width; width 0 could never send "
                 "(use valid=False lanes for silent firings)"),
                ("payload_width",
                 "payload arrays are fixed-width [max_out, "
                 "payload_width]; width 0 has no batchable layout")):
            v = getattr(self, attr)
            # numpy integer scalars (array shapes, loaded configs) are
            # fine; bools are not (True would silently mean 1)
            if isinstance(v, bool) \
                    or not isinstance(v, (int, _np.integer)) or v < 1:
                raise ValueError(
                    f"scenario {self.name!r}: {attr} must be an int "
                    f">= 1, got {v!r} — {why}")
        if self.static_dst is not None:
            shape = tuple(_np.shape(self.static_dst))
            want = (self.n_nodes, self.max_out)
            if shape != want:
                raise ValueError(
                    f"scenario {self.name!r}: static_dst shape {shape} "
                    f"must be [n_nodes, max_out] = {list(want)} — one "
                    "destination per outbox slot per node (-1 = slot "
                    "never used)")

    def empty_outbox(self, np_mod: Any) -> Outbox:
        """Convenience for step functions: an all-invalid outbox."""
        M, P = self.max_out, self.payload_width
        return Outbox(
            valid=np_mod.zeros((M,), dtype=bool),
            dst=np_mod.zeros((M,), dtype=np_mod.int32),
            payload=np_mod.zeros((M, P), dtype=np_mod.int32),
        )
