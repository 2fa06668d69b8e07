"""The batched XLA engine: whole-network emulation as one compiled program.

This is the third interpreter the reference never had (BASELINE.json
north star): the pure emulator's event loop
(`/root/reference/src/Control/TimeWarp/Timed/TimedT.hs:234-286`)
re-designed for the TPU's execution model:

- the priority event queue (TimedT.hs:109) becomes a per-node
  ``next_wake`` array plus bounded per-node mailboxes — the global
  "pop min" is an ``argmin``-free masked ``min`` reduction;
- threads-as-continuations (TimedT.hs:146-151) become explicit node
  states advanced by a ``vmap``-ed step function;
- virtual time is driven by ``lax.scan`` (traced once, compiled once;
  no data-dependent Python control flow);
- message delivery is a static-shape scatter with deterministic
  sender-major ranking (and, in the sharded engine, collectives over
  the TPU mesh — see sharded.py; static topologies skip the scatter
  entirely — see edge_engine.py).

All supersteps execute the *fire-all-at-min* semantics of
core/scenario.py, and the emitted trace must equal the host oracle's
bit-for-bit (tests/test_parity.py). Everything observable is integer;
time is int64 µs.

TPU cost notes (docs/engines.md "Measured on a v5e"): int64 scatters are
pathological and random scatters are the dominant real cost, so
mailbox deliver-times are stored as **int32 relative** to the rebased
epoch (``EngineState.time``), inbox ordering and mailbox compaction are
single variadic ``lax.sort`` calls instead of lexsort+gather chains,
the ladder's sender compaction is a prefix count and a log N shift
network on the node lanes and no sort at all (PR 48;
ops/numeric.py ``compress_lanes``), the ladder's top rung, whose width
is the node axis, reads the outbox where it lies and gathers nothing
(PR 56), and trace digests exist only in
the traced driver (``run``) — the ``run_quiet`` benchmark path
compiles them out.
"""

from __future__ import annotations

from functools import partial, reduce
from typing import Any, NamedTuple, Optional, Tuple

from ...utils import jaxconfig  # noqa: F401  (must precede jax use)

import jax
import jax.numpy as jnp
import numpy as np

from ...core.rng import fire_bits, msg_bits, seed_words
from ...core.scenario import NEVER, Inbox, Outbox, Scenario
from ...net.delays import LinkModel
from ...ops.numeric import (compress_lanes, expand_lanes, fill_holes,
                            free_bits, nth_set_bit)
from ...trace.events import SuperstepTrace
from ...trace.hashing import FIRED, RECV, SENT, mix32_jnp
from .batched import BatchSpec, WorldIdentity, rebind_link
from .common import I32MAX as _I32MAX
from .common import LocalComm, RunStatsMixin, Stages, StepOut as _StepOut
from .common import group_rank
from .common import padded_scan, scan_pad as _scan_pad
from .common import thi as _thi, tlo as _tlo, u32sum as _u32sum
from .controlled import ControlledRunMixin
from ...integrity.runner import VerifiedRunMixin
from ...obs.flight import FlightRecorderMixin
from ...speculate.runner import SpeculativeRunMixin

__all__ = ["JaxEngine", "EngineState", "Horizon", "RouteCounts",
           "FaultCounts",
           "BatchSpec"]

#: the name of the fleet's ``vmap`` axis (``_vstep``): what a world
#: reduces over when all worlds must agree (``_route_adaptive``'s
#: rung). Not ``ShardedBatchedEngine``'s mesh axis: a world-sharded
#: fleet reduces over a device's own worlds only, never over the mesh
_FLEET_AXIS = "tw_fleet"

#: ``_stage_by_rank`` takes its dense form (one sort by staged index,
#: rank 0 expanded on the node lanes, the tail scattered declared
#: sorted) where its lanes are at least this share of its nodes: the
#: network costs by the nodes, the scatters it replaces by the lanes.
#: Measured on one v5e (profiling/stage_micro_r06.py; PR 36; the table
#: is in docs/engines.md "Staging by rank, piece by piece"), us a call
#: of two fields, a scatter a field against the dense form:
#:
#:     lanes / nodes        1        1/2       1/4       1/8
#:     nodes 2^20     12 046/7 924 6 049/4 091 3 089/2 266 1 617/1 390
#:     nodes 2^17      1 329/945    717/553    418/350    251/249
#:
#: The dense form wins by 16-34 % down to a quarter and is a wash at
#: an eighth of 2^17 nodes. A dense rung of a ladder compiles one
#: variadic sort more (20-30 s) and one compiler-made sort a field
#: less (5-9 s each): the cells' cold compiles rose by 9-14 s.
_DENSE_STAGE_RATIO = 0.25

#: an ordered inbox's ranked insertion (``_insert_sorted``) cuts its
#: gather of the kept counts and its scatters to the prefix that ends
#: at the last valid lane of rank under K, by a ladder of four static
#: widths (``_scatter_widths``), where the call has at least this many
#: lanes; under it the gather and one scatter a field at the call's
#: width, as ever. The four scatters of one insertion into
#: the observer ring's [8, 65 537] planes, both forms, in us on a v5e
#: (profiling/prefix_scatter_micro_r07.py, PR 43; lanes: one / the
#: switch where 8 lanes fit (it takes L/8), where half fit (L/2),
#: where all fit (L)): 2^12: 191-196 / 177, 202, 248; 2^13: 270-276
#: / 176, 245, 332; 2^14: 436-439 / 198, 323, 495; 2^15: 754-761 /
#: 242, 482, 808; 2^17: 2 700-2 703 / 501, 1 450, 2 728. A scatter
#: costs what its lanes cost whatever lands (5 ns a lane over some
#: 100 us); the switch adds 45-57 us (26 at 2^17). At 2^13 the cut to
#: half gains 26 us where a full call loses 56; at 2^14 it gains 116
#: for 57, and the cut to an eighth 238. The same micro with the
#: gather (PR 52; ``counts[clip(sd)] + rank`` alone on the first w
#: lanes, w = L/8, L/4, L/2, L): 2^14: 83, 84, 119, 185; 2^15: 78,
#: 115, 180, 310; 2^16: 120, 183, 301, 538; 2^17: 183, 304, 544,
#: 1 009: 7.2 ns a lane over a floor of 65-80 us that is the micro's
#: loop's, not the gather's (in the observer ring's superstep the same
#: gather reads 14.9 us at 2 048 lanes, 116.6 at 16 384, 467.5 at
#: 65 536, 935 at 131 072: 7.1 ns a lane through the origin, and its
#: scatters 4.9 ns a lane through the origin too). The whole insertion,
#: the gather on all L lanes and the width from the lanes that fit
#: (PR 43) / the width from the ranks and the gather in its branch
#: (PR 52): where 8 lanes fit 2^14: 337 / 229, 2^15: 497 / 276, 2^16:
#: 811 / 400, 2^17: 1 448 / 614; where half fit 444 / 399, 735 / 613,
#: 1 283 / 1 057, 2 407 / 1 919; where all fit 613 / 627, 1 057 /
#: 1 053, 1 941 / 1 932, 3 681 / 3 662 (a wash); and the one case
#: where the ranks take the wider branch, half the lanes valid, one to
#: a node, every mailbox full (nothing fits: L/8 / L/2): 344 / 389,
#: 494 / 617, 811 / 1 049, 1 442 / 1 931: dearer by 45 to 488 us, and
#: 1 750 under the one-scatter form's 3 681 at 2^17.
_PREFIX_SCATTER_LANES = 1 << 14

#: ``_stage_by_rank``'s dense form places its arrivals rank by rank
#: (``_dense_plan``) where the call has at least this many lanes: the
#: ranks under R through one ``expand_lanes`` over R rows' lanes, the
#: rest scattered at the smallest of the static widths ``L /
#: _TAIL_DIVISORS`` that holds it. Under it PR 36's text (rank 0
#: through the network, the rest at half the lanes or all). R is 2
#: where the call's lanes are at least ``_NET_ROW_RATIO`` times its
#: nodes, else 1. Measured on one v5e (profiling/
#: stage_tail_micro_r08.py, PR 44; the whole table is in
#: docs/engines.md "The dense staging's tail, by how it is placed"),
#: us a call after the sort, uniform destinations, two fields / three:
#:
#:     lanes / nodes     PR 36's form    R = 1           R = 2
#:     2^20 nodes  2    21 144 / 31 646  21 157 / 31 663  11 067 / 16 502
#:                 1     5 761 /  8 588   5 764 /  8 587   2 076 /  3 068
#:                 1/2   3 185 /  4 732   1 907 /  2 820   1 402 /  2 049
#:                 1/4   1 890 /  2 784     949 /  1 350   1 063 /  1 568
#:     2^17 nodes  1       784 /  1 141     796 /  1 151     364 /    492
#:                 1/4     320 /    438     235 /    280     233 /    317
#:
#: The network takes 219, 333 and 466 us over one, two and three rows
#: of 2^20 lanes (107, 114, 133 at 2^17), a declared scatter into a
#: fresh ``[24 n]`` buffer 285, 461, 781 and 1 419 us at n/32 ... n/4
#: lanes: some 120 us and 5 ns a lane. On a burst whose tail takes the
#: full width (eight arrivals a node) the second row costs 178-266 us
#: of 10 877-16 252 and the switch nothing. 2^15 lanes is the least
#: the micro measured (2^17 nodes, a lane to four nodes) and the least
#: any cell stages densely. The second row wins down to half a lane
#: a node (by 17-27 % at both sizes) and is a wash or a loss at a
#: quarter: the ratio is 1/2. A third row at a lane a node with L/32
#: for L/4 wins on uniform lanes too (-40 %; a steady job 207.2 ->
#: 194.0 ms), but three rows of three fields at 2^20 lose 1.0 ms on
#: the burst (0.5 ms a row) and praos' and the wave's cells were not
#: measured with it: ROADMAP B1 (b).
_TAIL_LADDER_LANES = 1 << 15
_TAIL_DIVISORS = (8, 4, 2, 1)
_NET_ROW_RATIO = 0.5


class EngineState(NamedTuple):
    """The complete simulation state — one pytree, trivially
    checkpointable (SURVEY.md §5.4) and shardable over a mesh.

    Mailbox layout is ``[K, N]`` (minor dim = node axis — no lane
    padding, perfect VPU tiling; the [N, K] layout taxes every
    materialized intermediate ~128/K in memory traffic,
    docs/engines.md "Measured on a v5e"). Deliver-times are int32 µs
    relative to ``time`` (the epoch is rebased every superstep); delays
    ≥ 2^31 µs are clamped and counted in ``bad_delay``.
    """
    states: Any        # scenario pytree, leading dim N
    wake: jax.Array    # int64[N]
    #: int32[K, N] deliver time minus `time`; I32MAX = empty slot (real
    #: entries clamp to I32MAX-1), so validity is derived, never stored
    #: or scattered
    mb_rel: jax.Array
    mb_src: jax.Array      # int32[K, N]
    mb_payload: jax.Array  # int32[K, P, N]
    overflow: jax.Array    # int32[] — total overflowed messages
    bad_dst: jax.Array     # int32[] — total messages to invalid destinations
    bad_delay: jax.Array   # int32[] — delays >= 2^31 µs, clamped
    #: int32[] — delays < the superstep window (would violate the
    #: windowed-execution causality precondition; see JaxEngine.window)
    short_delay: jax.Array
    #: int32[] — zero on every path since PR 57 took the capped
    #: insertion stage away (neither routing regime can drop); the leaf
    #: stays for the carries and checkpoints that hold it (ROADMAP D28)
    route_drop: jax.Array
    delivered: jax.Array   # int64[] — total delivered messages
    steps: jax.Array       # int64[] — supersteps executed
    time: jax.Array        # int64[] — current virtual time == mailbox epoch
    #: device-side event ring (empty unless ``record_events`` > 0):
    #: per-event time (fire instant / deliver time) and [kind, node,
    #: src, payload0] columns; ``ev_count`` counts every event ever
    #: produced — entries beyond capacity are dropped, and
    #: ``ev_count > capacity`` IS the overflow evidence (never silent).
    #: int64: a single scalar, and an int32 count would wrap negative
    #: past ~2.1e9 recorded events, corrupting ring write positions
    #: — ring *indices* stay int32 (capacity bounds them).
    ev_time: jax.Array     # int64[E]
    ev_meta: jax.Array     # int32[4, E]
    ev_count: jax.Array    # int64[]
    #: int32[] — messages killed by the fault schedule (partition-cut
    #: sends, deliveries into a down node's window, mailbox entries a
    #: reset restart purged) — counted, never silent, mirroring the
    #: oracle's ``fault_dropped_total`` (faults/, round 9). Always
    #: present (0 and shape-[0] restart ledger when no faults) so the
    #: state pytree is engine-interchange stable.
    fault_dropped: jax.Array
    #: bool[C] — which crash rows' injected restart firings have been
    #: consumed (faults/apply.py module docstring: the one piece of
    #: state fault masks need)
    restart_done: jax.Array


class Horizon(NamedTuple):
    """A state's event horizon: when its next superstep fires, and
    which nodes may. A function of the state alone
    (``JaxEngine._horizon``), so it is no field of it: the quiet
    driver's loop carries it beside the state, each superstep
    producing its successor's from what it has just written, so that
    the loop's condition reads ``t`` and the body starts from
    ``node_next`` with nothing of the mailbox's rank reduced at the
    loop's edge (docs/engines.md "The quiet loop")."""
    t: jax.Array          # int64[] — global next event time; NEVER = quiet
    #: int64[N] — each node's next event (its wake or its earliest
    #: message; under a fault schedule slid past its down window, and
    #: a pending restart's ``t_up``); ``t`` is its minimum over all
    #: devices
    node_next: jax.Array


class FaultCounts(NamedTuple):
    """What the fault schedule did, summed over the iterations of a
    driver's loop (int64[] each; a fleet's lead with the world axis,
    a world's own): carried as :class:`RouteCounts`' last field by an
    engine built with ``faults`` and by no other. The first three sum
    to the growth of ``EngineState.fault_dropped``; every one is a
    reduction of a mask the superstep has made already."""
    cut: jax.Array        # sends across a live partition
    down: jax.Array       # sends due inside the destination's down window
    purged: jax.Array     # mailbox entries a reboot lost
    degraded: jax.Array   # sends whose delay a link window changed
    restarts: jax.Array   # reboots consumed (``restart_done`` rows)


class RouteCounts(NamedTuple):
    """What the routing stage did, summed over the iterations of a
    driver's loop: every driver loop carries it beside the state and
    the call reads it in its one transfer (``last_run_stats``, the
    call's record). The first three come from the two scalars
    ``_route_adaptive`` holds when it picks its branch (the active
    senders and the rung's index), the next two and the three of the
    staging from the one scalar ``_stage_by_rank``'s dense form picks
    its tail's width by: no
    pass over node- or mailbox-sized data is made for them
    (``fan_in_peak`` is one reduction over the message lanes that an
    ordered inbox's insertion has ranked already; the last two are
    two over the lanes the sharded exchange has sorted by shard). Where
    routing runs without the ladder every iteration counts the full
    width, in one bin. A fleet's leaves lead with the world axis like
    every state leaf (one rung for all the worlds of a superstep:
    every world of a device counts the same)."""
    rung_lanes: jax.Array     # int64[] — the rung taken, in senders
    #: int64[] — the active senders the rung was chosen for (a fleet:
    #: its busiest world's)
    sender_lanes: jax.Array
    rung_steps: jax.Array     # int32[R] — iterations by rung index
    #: int64[] — iterations whose arrivals were staged in the dense
    #: form (``_stage_by_rank``; a fleet's: none)
    dense_stage_steps: jax.Array
    #: int64[] — of those, the ones whose tail (the ranks that no
    #: row of the network took) was over half the lanes and went
    #: through the full-width scatter
    wide_tail_steps: jax.Array
    #: int32[] — the most arrivals to one destination in one superstep
    #: (``_insert_sorted``'s largest rank + 1 over its valid lanes): a
    #: maximum over the loop's iterations, not a sum. Carried by an
    #: engine whose inbox is ordered and by no other (``_ranks_fan_in``;
    #: None is an empty pytree node, so the loops of every other
    #: engine carry what they carried)
    fan_in_peak: Any = None
    #: int64[] — the lanes the ranked insertion handed to each of its
    #: scatters (``_insert_sorted``: the width its ladder took, the
    #: call's lanes where it has none), summed over the iterations.
    #: Carried by a solo engine on one device whose inbox is ordered
    #: and by no other (``_cuts_scatters``; None elsewhere, as above)
    scatter_lanes: Any = None
    #: int64[] each — the message lanes of every iteration staged in
    #: the dense form, the width its tail's scatters took and the rows
    #: that went through the network (``_dense_plan``), summed over
    #: the iterations. Carried where insertion stages by rank
    #: (``_stages_by_rank``; None elsewhere, as above)
    dense_lanes: Any = None
    tail_lanes: Any = None
    net_rows: Any = None
    #: int64[1] and int32[1], a device's own — the valid messages the
    #: exchange sent to another shard, summed over the iterations, and
    #: the most that one destination shard's bucket was asked to hold
    #: in one of them, counted before the cut at ``bucket_cap`` (a
    #: maximum, not a sum). Carried by the node-sharded general engine
    #: (sharded.py ``ShardedEngine``: one row a shard of what the call
    #: reads back, no collective) and by no other (None elsewhere, as
    #: above)
    remote_msgs: Any = None
    bucket_fill_peak: Any = None
    #: :class:`FaultCounts` — carried by an engine built with
    #: ``faults`` and by no other (None elsewhere, as above)
    faults: Any = None
    #: int64[B] — each world's OWN active senders (``n_active`` before
    #: the fleet's ``pmax``), summed over the iterations that world
    #: stepped: against ``rung_lanes``, what the lockstep's shared
    #: rung cost worlds that are out of step. Carried by a fleet and
    #: by no other (None solo, as above: a solo engine's senders are
    #: ``sender_lanes``)
    world_sender_lanes: Any = None


class JaxEngine(RunStatsMixin, ControlledRunMixin, VerifiedRunMixin,
                FlightRecorderMixin, SpeculativeRunMixin):
    """Single-chip batched engine for arbitrary (dynamic-destination)
    scenarios. ``run(max_steps)`` executes up to ``max_steps``
    supersteps under one ``lax.scan`` and returns the final
    :class:`EngineState` plus the trace; ``run_quiet`` drops the trace
    (pure ``lax.while_loop``, digests not compiled in) for
    benchmarking. Static-topology scenarios should prefer
    :class:`~timewarp_tpu.interp.jax_engine.edge_engine.EdgeEngine`.

    Multi-instant windowed supersteps (``window`` µs, default 1 =
    classic fire-all-at-min): one superstep fires *every* node whose
    next event lies in ``[t, t + window)``, each at its **own** instant
    (per-node ``now``; per-instant entropy; wake clamp past the node's
    own instant). This is *exact* — identical event semantics to
    window=1, superstep granularity aside — when every link delay is
    ≥ ``window`` (an in-window send then arrives at or past the window
    end, so in-window firings are causally independent) AND the
    window=1 run is overflow-free. The overflow caveat: a windowed
    superstep delivers before it inserts, so a mailbox that stands at
    capacity in the classic run until a later in-window firing drains
    it can reject a message under window=1 yet accept it windowed —
    overflow-*boundary* behavior, never event reordering; with zero
    overflow the two runs coincide message-for-message (the windowed
    oracle mirrors the same deliver-then-insert order, so
    engine ≡ oracle parity holds unconditionally). The constructor
    validates ``window <= link.min_delay_us`` (net/delays.py), and any
    dynamically sampled shorter delay is counted in
    ``EngineState.short_delay`` (a nonzero count marks the run as
    outside the exact regime — never silent). Sparse workloads whose
    events spread over many close-together instants (Praos slots,
    gossip waves — SURVEY.md §5.7 time-bucketed batching) gain up to
    window/grid × messages per superstep at the same superstep cost.

    The throughput knob for wide-outbox scenarios (burst diffusion —
    ``max_out`` ≥ 8 makes the S = N·max_out routing arrays dominate):
    ``commutative_inbox`` scenarios skip the contract-#2 inbox sort
    entirely (the step reduces over the inbox commutatively, so slot
    order is unobservable; digests are order-independent) — the same
    waiver the edge engine already exercises.

    Routing has two regimes, picked from what the engine sees
    (``_adaptive_regime``) and by no option. The eager one samples and
    sorts all S = N·max_out outbox lanes: a link that can drop, a mesh,
    and ``window == 1 and max_out == 1`` (S = N: nothing to compact).
    Adaptive sender-compacted routing (round 5) is every other
    engine's: when the link cannot drop, the engine is single-chip, and
    the workload is windowed or wide-outbox (``window > 1 or
    max_out > 1``), routing never touches the S = N·max_out flattened
    arrays. All ``max_out`` lanes of a sender
    share ``(src, send instant)``, so the engine compacts *senders*
    (the active node ids put in front in ascending order by a prefix
    count and a log N shift network on the node lanes, ops/numeric.py
    ``compress_lanes`` under the scope ``tw.route/senders`` — the
    only N-sized routing cost; until PR 48 one single-operand sort
    of N node ids, whose output it equals word for word), then
    gathers outbox lanes, sorts by ``(dst, window offset,
    sender-major rank)``, samples link delays, ranks and scatters at a
    **ladder-selected static width**: a `lax.switch` over geometric
    sender-count rungs (…, n/16, n/4, n) picks the smallest compiled
    variant that fits this superstep's device-computed active-sender
    count, so insertion cost tracks instantaneous load instead of the
    workload's peak. The top rung is always n — no message can ever be
    dropped, and there is no capacity to tune (the capped third regime
    and its keyword went in PR 57; ``EngineState.route_drop`` is 0 on
    every path) — and being the node axis itself it gathers
    nothing: the outbox planes are its lanes where they lie (the sort's
    last key, a message's own source and slot, makes the order the
    lanes come in immaterial; PR 56). Event semantics, arrival order
    (contract #3) and digests are identical to the eager path.

    Mailbox insertion has one form, ``_insert_sorted``, called by
    both regimes and held to the oracle in each by
    tests/insertion_laws.py (docs/engines.md "Mailbox insertion"). A
    commutative inbox's free slots are bit words
    (``ceil(mailbox_cap / 32)`` uint32 a node, built in
    ``tw.rebase``). A solo engine stages its arrivals by their rank
    at the destination in buffers of their own (one flat 1D scatter a
    field: the message lanes read nothing of the node side), and
    every node then moves its staged rows into its holes in ascending
    order, elementwise on its own lanes (``fill_holes``): each message
    ends in the slot the rank-th set bit of its destination's words
    names, with no sort along the mailbox's slots and no gather
    (tests/test_free_bits.py, tests/test_superstep_sorts.py). A fleet
    finds that slot on the message lanes (the words gathered at the
    destination, ``nth_set_bit``) and scatters into the mailbox, as
    an ordered inbox does after its kept messages
    (``_stages_by_rank`` says why).
    ``insert`` is a vestigial keyword: ``None`` and ``"xla"`` build
    the same engine, anything else is refused (ROADMAP D2').

    Batched multi-world execution (``batch=BatchSpec``, batched.py):
    a leading world axis B through the whole engine. ``_superstep`` is
    ``vmap``-ed over B independent worlds sharing one scenario but
    differing in seed and (optionally) link-model parameters; every
    ``EngineState`` leaf gains a leading B dim (so checkpoints,
    counters, and trace digests are per-world), the drivers mask
    quiescence and step budgets per world, and ``run`` returns one
    :class:`SuperstepTrace` per world. Slicing world b out of a
    batched run is **bit-identical** to the solo run with that seed
    and link — the batch exactness law (batched.py module docstring).
    One batched op serves B worlds, at ONE rung of the routing ladder
    for all of them: the smallest that holds the busiest world's
    senders (``_route_adaptive``; ``last_run_stats["rung_lanes"]``
    sums the rungs taken, solo and fleet alike). On a v5e eight gossip
    worlds deliver 0.74 of one solo wave's rate together ([ledger,
    PR 56]: 9.47 against 12.78×10⁶ msg/s; docs/engines.md
    "Multi-world batching"). ``record_events`` is
    solo-only (the ring decoder is
    a single-run debug artifact — record world b's events by running
    it solo, which is bit-identical by the law above).

    Scheduled fault injection (``faults=FaultSchedule``, faults/):
    deterministic time-varying chaos applied as pure masks inside the
    superstep — crash windows suppress firing and drop deliveries
    (``reset_state`` reboots the node at ``t_up`` with state loss),
    partitions drop cross-cut sends, degradation windows transform
    sampled delays, clock skews shift a node's view of time. All
    fault losses are counted in ``EngineState.fault_dropped`` (never
    silent) and the oracle applies the identical semantics, so chaos
    runs stay inside the trace-parity law. Batched: pass a
    ``FaultFleet`` (or one schedule, replicated to every world) —
    world b runs its own schedule, and the batch exactness law
    extends: world-b slice of a chaos fleet ≡ the solo run with
    ``fleet.world_schedule(b)`` (docs/faults.md).

    Online adaptive dispatch (``controller=DispatchController(...)``,
    dispatch/ + controlled.py, docs/dispatch.md): ``window`` then
    names the dynamic window's *bound* (resolve it with ``"auto"`` —
    the UNDEGRADED link floor; degradation windows clamp on-device
    per superstep, faults/apply.py ``window_floor``), and
    :meth:`run_controlled` executes chunk by chunk with the
    controller's per-chunk window/rung-pin values threaded as traced
    scalars — adapting never retraces, every decision is recorded,
    and replaying the decision trace is bit-identical on states,
    traces, digests, and checkpoints (the replay law,
    tests/test_zzzdispatch.py).
    """

    #: this engine threads the controller's dynamic window/rung
    #: scalars (controlled.py)
    _dyn_ok = True

    def __init__(self, scenario: Scenario, link: LinkModel, *,
                 seed: int = 0, window=1,
                 record_events: int = 0,
                 lint: str = "warn",
                 batch: Optional[BatchSpec] = None,
                 faults=None,
                 telemetry: str = "off",
                 insert: Optional[str] = None,
                 controller=None,
                 verify: str = "off",
                 record: str = "off",
                 record_cap: Optional[int] = None,
                 speculate: str = "off") -> None:
        # static scenario sanitizer (analysis/): "warn" logs findings,
        # "error" refuses to construct on contract violations, "off"
        # skips entirely (bit-for-bit the pre-lint behavior — the
        # checks are abstract and never execute the step)
        from ...analysis import check_scenario
        # opt-in telemetry (obs/): "off" lowers to the exact
        # telemetry-free jaxpr; "counters"/"full" thread per-superstep
        # counter planes through the traced scan, derived only from
        # values the superstep already computes — digests, traces, and
        # checkpoints are bit-identical in every mode
        from ...obs.telemetry import validate_mode
        self.telemetry = validate_mode(telemetry, type(self).__name__)
        # online state-integrity checking (integrity/,
        # docs/integrity.md): "off" lowers to the exact verify-free
        # jaxpr (the guard plane is a None StepOut field, like
        # telemetry); "guard" threads fixed-shape on-device invariant
        # checks through the traced scan; "digest"/"shadow" add the
        # per-chunk state digest / pow2-twin re-execution in the
        # run_verified driver (integrity/runner.py)
        self._bind_verify(verify)
        # the causal flight recorder (obs/flight.py,
        # docs/observability.md): "off" lowers to the exact
        # record-free jaxpr (the event plane is a None StepOut field,
        # like telemetry); "deliveries" records one event per
        # delivered message; "full" adds sends and fault actions
        # (defer/cut/down/purge/restart)
        self._bind_record(record, record_cap)
        # optimistic time-warp execution (speculate/,
        # docs/speculation.md): "off" lowers to the exact
        # speculation-free jaxpr (the violation plane is a None
        # StepOut field, like telemetry); "auto"/"fixed:W" permit a
        # window BOUND wider than the provable link floor and thread
        # the causality-violation plane — resolved below, once the
        # link floor is known
        from ...speculate.plane import parse_speculate
        self.speculate, self._spec_w = parse_speculate(
            speculate, type(self).__name__)
        #: attachable obs.metrics.MetricsRegistry: when set, every
        #: traced run flushes one aggregated `supersteps` line (per
        #: world, batched) under `metrics_label`
        self.metrics = None
        self.metrics_label = type(self).__name__
        self.last_run_telemetry = None
        self.lint = lint
        self.lint_report = check_scenario(scenario, lint,
                                          who=type(self).__name__)
        if scenario.n_nodes * scenario.max_out >= 2**31:
            raise ValueError(
                "n_nodes * max_out must fit int32 (sender-major rank)")
        if scenario.n_nodes * (scenario.mailbox_cap
                               + scenario.max_out) >= 2**31:
            raise ValueError(
                "n_nodes * (mailbox_cap + max_out) must fit int32 (a "
                "lane's staged index, `_stage_dense`)")
        if record_events < 0:
            raise ValueError("record_events must be >= 0")
        self.batch = batch
        if batch is not None:
            if not isinstance(batch, BatchSpec):
                raise ValueError(
                    f"batch must be a BatchSpec (got {batch!r}); build "
                    "one with BatchSpec(seeds=...) or BatchSpec.of()")
            if record_events:
                raise ValueError(
                    "record_events is a solo-run debug ring; to record "
                    "world b's events, run it solo (bit-identical by "
                    "the batch exactness law, batched.py)")
            #: per-world host-level links — what a solo run must use to
            #: reproduce world b, and the floor for window validation
            self._world_links = [batch.world_link(link, b)
                                 for b in range(batch.B)]
            link_floor = min(lk.min_delay_us for lk in self._world_links)
        else:
            self._world_links = None
            link_floor = link.min_delay_us
        self.scenario = scenario  # before faults: the restart-reset
        self.link = link          # template stacks Scenario.init
        self._setup_faults(faults, scenario, lint)
        if insert not in (None, "xla"):
            raise ValueError(
                f"insert={insert!r}: the flat XLA scatter is the one "
                "insertion form; the other strategies were removed in "
                "PR 29 (the kernels are at 193bc01)")
        if self._faulted:
            # a shrink-degradation window can undercut the link's
            # declared floor: windowed validation (and "auto") must
            # use the degraded worst case, never silently reorder.
            # Controller engines that thread the DYNAMIC window keep
            # the UNDEGRADED floor as their bound: the device-side
            # per-superstep clamp (faults/apply.py window_floor)
            # narrows the effective window for exactly the supersteps
            # a degradation window overlaps, so the whole run is not
            # forced onto the schedule-wide conservative floor
            # (docs/dispatch.md). Speculating engines keep
            # the undegraded floor the same way: run_speculative
            # always threads the dynamic window, so the device clamp
            # is in force (docs/speculation.md).
            if controller is None and self.speculate == "off":
                link_floor = self.faults.min_delay_floor(link_floor)
        if isinstance(window, str) and window != "auto":
            # a typo'd "Auto"/"8ms" from a library caller would
            # otherwise fall through to `window < 1` and raise an
            # opaque TypeError
            raise ValueError(
                f"window must be an int µs count or the string "
                f"'auto', got {window!r}")
        if window == "auto":
            # widest exact window the link model licenses: every delay
            # is declared >= min_delay_us, so instants within that
            # span are causally independent (class docstring). A
            # floor-less link (min 1) degenerates to the classic
            # engine — correct, just unbatched. Batched: the min over
            # every world's link, so the window is exact fleet-wide.
            # Clamped to int32: a FOREVER-delay link (e.g. --link
            # never) declares an astronomical floor, and "auto" must
            # resolve to the widest REPRESENTABLE window, not refuse
            window = max(1, min(int(link_floor), _I32MAX - 1))
        if window < 1:
            raise ValueError(f"window must be >= 1 µs, got {window}")
        if window > 1 and window > link_floor:
            # under speculation the window argument names the
            # CONSERVATIVE floor, so the actionable advice differs:
            # the speculative bound is the speculate spec's business
            hint = (
                "speculate= is already on and window= names its "
                "CONSERVATIVE floor, which must stay provable (<= "
                "the declared min); put the speculative bound in the "
                "spec instead — speculate='fixed:W', or 'auto' to "
                "ladder it (docs/speculation.md)"
            ) if self.speculate != "off" else (
                "to run wider than the provable floor, speculate: "
                "speculate='auto'|'fixed:W' detects and rolls back "
                "the violations statically ruled out here "
                "(docs/speculation.md)")
            raise ValueError(
                f"window={window} µs exceeds the link model's declared "
                f"min_delay_us={link_floor}"
                f"{' (min over the batch worlds)' if batch else ''}; "
                "windowed supersteps would reorder causally dependent "
                f"events (engine.py windowed-execution precondition) "
                f"— {hint}")
        # optimistic execution (speculate/, docs/speculation.md):
        # `window` validated above is the CONSERVATIVE floor — the
        # widest statically provable window; the engine's `window`
        # attribute becomes the speculative BOUND beyond it. The
        # causality-violation plane (SpecRow riding StepOut) is the
        # dynamic replacement for the static check just skipped:
        # every committed superstep proves flight >= its effective
        # window, which re-establishes the exactness precondition
        # chunk by chunk (run_speculative rolls back the rest).
        self.spec_floor = None
        if self.speculate != "off":
            if controller is not None:
                raise ValueError(
                    "speculate and controller are both per-chunk "
                    "window decision sources — an engine runs under "
                    "exactly one (docs/speculation.md)")
            self.spec_floor = int(window)
            if self.speculate == "fixed":
                if self._spec_w <= self.spec_floor:
                    raise ValueError(
                        f"speculate='fixed:{self._spec_w}' does not "
                        f"exceed the conservative floor "
                        f"{self.spec_floor} µs — at or below the "
                        "floor the static window already proves "
                        "exactness; nothing to speculate "
                        "(docs/speculation.md)")
                window = self._spec_w
            else:
                # auto: the bound is the widest representable window
                # — the ladder policy (speculate/policy.py) doubles
                # up from the floor and backs off below the first
                # width that violates, so the bound is a ceiling, not
                # a target
                window = _I32MAX - 1
        if window >= _I32MAX:
            raise ValueError("window must fit int32")
        # (self.scenario / self.link were assigned before _setup_faults)
        self.window = int(window)
        #: event-ring capacity (0 = recording off): with it on, every
        #: superstep appends per-event (time, kind, node, src,
        #: payload) records on-device — the engine-side mirror of
        #: ``SuperstepOracle(record_events=True)``, so a digest
        #: mismatch at scale is debuggable record-by-record without a
        #: host-oracle rerun (tests/test_event_ring.py asserts
        #: record-level equality)
        self.record_events = int(record_events)
        self.s0, self.s1 = seed_words(seed)
        if batch is not None:
            # per-world seed words + link-parameter vectors: the world
            # context the vmapped superstep maps over. batch.seeds
            # REPLACES the solo `seed` argument (world b's stream is
            # exactly JaxEngine(..., seed=batch.seeds[b])'s).
            sw = [seed_words(s) for s in batch.seeds]
            self._s0v = jnp.asarray([a for a, _ in sw], jnp.uint32)
            self._s1v = jnp.asarray([b for _, b in sw], jnp.uint32)
            self._lpv = {k: jnp.asarray(v) for k, v in
                         (batch.link_params or {}).items()}
        self.comm = LocalComm(scenario.n_nodes)
        # online adaptive dispatch (dispatch/, controlled.py): the
        # engine's `window` is then the dynamic knob's BOUND, and the
        # per-chunk values arrive as traced scalars (self._dyn) — no
        # retrace between adaptations. `_w_now` is the superstep's
        # effective window value, == self.window (a Python int, so the
        # controller-less jaxpr is unchanged) on the static path.
        self._dyn = None
        self._w_now = self.window
        # per-world identity as a traced operand (batched.py
        # WorldIdentity): the drivers bind the operand onto `self`
        # for the one trace jit performs — same pattern as `_dyn` —
        # so seeds/link values/fault tables are never baked into the
        # executable. None between driver calls (and always, solo).
        self._ident_in = None
        self._bind_controller(controller)

    # -- faults (faults/: scheduled chaos inside the superstep) ----------

    def _setup_faults(self, faults, scenario, lint) -> None:
        """Normalize/validate the ``faults`` argument and lower it to
        the :class:`~timewarp_tpu.faults.schedule.FaultTables` the
        superstep masks close over (solo: ``self._ft``) or ``vmap``
        (batched: ``self._ftv``, leading world axis). Runs the TW5xx
        fault lints under the same ``lint`` knob as the scenario
        sanitizer."""
        self.faults = faults
        self._faulted = faults is not None
        self._ft = None
        self._ftv = None
        self.fault_lint_report = None
        self._has_skew = self._has_reset = False
        self._n_restarts = 0
        if faults is None:
            return
        from ...faults.schedule import FaultFleet, FaultSchedule, as_fleet
        if self.batch is not None:
            faults = as_fleet(faults, self.batch.B)
        elif isinstance(faults, FaultFleet):
            raise ValueError(
                "a FaultFleet carries per-world schedules; it needs "
                "batch=BatchSpec (a solo run takes one FaultSchedule)")
        elif not isinstance(faults, FaultSchedule):
            raise ValueError(
                f"faults must be a FaultSchedule (or a FaultFleet "
                f"with batch=), got {faults!r}; build one with "
                "FaultSchedule((NodeCrash(...), ...)) or "
                "faults.parse_faults()")
        self.faults = faults
        from ...analysis import check_faults
        self.fault_lint_report = check_faults(
            faults, scenario, lint, who=type(self).__name__)
        self._has_skew = faults.has_skew
        self._has_reset = faults.has_reset
        self._n_restarts = faults.n_restarts
        tables = faults.tables(scenario.n_nodes)
        ftj = type(tables)(*(jnp.asarray(x) for x in tables))
        if self.batch is not None:
            self._ftv = ftj
        else:
            self._ft = ftj
        if self._has_reset:
            # the reboot template: Scenario.init's states, the same
            # arrays init_state stacks (seed-independent, so one
            # template serves every world of a fleet)
            self._reset_states, _ = self._init_states_wake()

    # -- initial state ---------------------------------------------------

    def _init_states_wake(self):
        """The scenario's stacked initial ``(states, wake)`` — shared
        by :meth:`init_state` and the fault subsystem's restart-reset
        template (one implementation, common.py)."""
        from .common import init_states_wake
        return init_states_wake(self.scenario)

    def init_state(self) -> EngineState:
        sc = self.scenario
        n, K, P = sc.n_nodes, sc.mailbox_cap, sc.payload_width
        states, wake = self._init_states_wake()
        st = EngineState(
            states=states,
            wake=wake,
            mb_rel=jnp.full((K, n), _I32MAX, jnp.int32),
            mb_src=jnp.zeros((K, n), jnp.int32),
            mb_payload=jnp.zeros((K, P, n), jnp.int32),
            overflow=jnp.int32(0),
            bad_dst=jnp.int32(0),
            bad_delay=jnp.int32(0),
            short_delay=jnp.int32(0),
            route_drop=jnp.int32(0),
            delivered=jnp.int64(0),
            steps=jnp.int64(0),
            time=jnp.int64(0),
            ev_time=jnp.zeros((self.record_events,), jnp.int64),
            ev_meta=jnp.zeros((4, self.record_events), jnp.int32),
            ev_count=jnp.int64(0),
            fault_dropped=jnp.int32(0),
            restart_done=jnp.zeros((self._n_restarts,), bool),
        )
        if self.batch is not None:
            # the world axis: every leaf gains a leading B dim. Worlds
            # share the scenario's (seed-independent) initial state;
            # they diverge from superstep 1 via per-world entropy.
            B = self.batch.B
            st = jax.tree.map(
                lambda x: jnp.repeat(x[None], B, axis=0), st)
        return st

    # -- one superstep ---------------------------------------------------

    @jax.named_scope("exchange")
    def _exchange(self, ok, drel, src_f, dst_f, smrank, woff, pay_cols):
        """Hand routed messages to the device that owns their
        destination, returning ``(ok, drel, src, local_row, smrank,
        woff, pay_cols, bucket_overflow)`` for the messages *this*
        device's nodes will receive. Single chip: identity — the global
        destination id is the local mailbox row. The sharded engine
        (sharded.py) overrides this with destination-shard bucketing +
        one ``lax.all_to_all``; bucket overflow is counted, never
        silent. ``dst_f`` is the global destination, already validated;
        ``smrank`` is the message's global sender-major rank
        (``src * max_out + slot``) and ``woff`` its in-window send
        offset — insertion sorts on (woff, smrank), so exchange order
        never matters."""
        return ok, drel, src_f, dst_f, smrank, woff, pay_cols, jnp.int32(0)

    def _adaptive_regime(self) -> bool:
        """Whether routing takes the adaptive sender-compacted path
        and not the eager one: a function of the link, the comm, the
        window and ``max_out`` alone. The ONE predicate shared by
        _superstep's routing dispatch and the dispatch controller's
        rung ladder (dispatch/controller.py ``begin``). Evaluated per
        call: the sharded subclasses replace ``comm`` once built."""
        return (not self.link.can_drop
                and type(self.comm) is LocalComm
                and (self.window > 1 or self.scenario.max_out > 1))

    @staticmethod
    def _sender_rungs(n: int):
        """Geometric x2 ladder of static sender-count widths for the
        adaptive routing switch: 1024, 2048, …, n. The top rung is
        always n, so the adaptive path can never drop a message (and
        being the node axis itself it reads the outbox in place,
        ``_route_adaptive`` ``gather``); the x2 spacing bounds
        gather/scatter overshoot at 2x the active count (the branch
        cost is linear in the rung)."""
        rungs = []
        a = 1024
        while a < n:
            rungs.append(a)
            a *= 2
        rungs.append(n)
        return rungs

    @jax.named_scope("sample")
    def _sample_nodrop(self, src, dst, tmsg, slot, woff, ok, aff=None):
        """The link-sampling tail of the no-drop routing path (the
        ladder's rungs): derive per-message entropy, apply the
        contract-#4 ``>= 1 µs`` flight clamp, saturate the epoch-
        relative deliver time to int32, and count the never-silent
        ``bad_delay`` / ``short_delay`` violations. One implementation
        so the faulted and the unfaulted branch cannot drift apart
        bit-wise."""
        mbits = msg_bits(self.s0, self.s1, src, dst, tmsg, slot) \
            if self.link.needs_key else None
        delay, _ = self.link.sample(src, dst, tmsg, mbits)
        degraded = None
        if self._faulted:
            # degradation windows transform the sampled delay BEFORE
            # the flight clamp (faults/apply.py; oracle order matches)
            delay, degraded = self._degrade(delay, src, dst, tmsg, ok,
                                            aff)
        flight = jnp.maximum(delay, jnp.int64(1))       # contract #4
        drel64 = woff.astype(jnp.int64) + flight
        bad = jnp.sum(ok & (drel64 > jnp.int64(_I32MAX - 1)),
                      dtype=jnp.int32)
        # `_w_now` is the superstep's EFFECTIVE window (the dynamic
        # clamp's output under a controller; the static int otherwise)
        # — a flight shorter than what actually ran this superstep is
        # the violation, not one shorter than the bound
        short = jnp.sum(ok & (flight < self._w_now), dtype=jnp.int32) \
            if self.window > 1 else jnp.int32(0)
        # the causality plane's straggler column (speculate/,
        # docs/speculation.md): earliest offending absolute delivery
        # time among this call's violations — None (no jaxpr
        # footprint) unless the engine speculates
        strag = None
        if self.speculate != "off" and self.window > 1:
            strag = jnp.min(jnp.where(ok & (flight < self._w_now),
                                      tmsg + flight, jnp.int64(NEVER)))
        drel = jnp.minimum(drel64,
                           jnp.int64(_I32MAX - 1)).astype(jnp.int32)
        return flight, drel, bad, short, strag, degraded

    @jax.named_scope("fault")
    def _degrade(self, delay, src, dst, tmsg, ok, aff=None):
        """The schedule's link windows on the sampled delays
        (faults/apply.py ``degrade_bits``: ``degrade`` with the
        verdicts of the first ``_fault_reads`` rows read from the
        lanes' own ``aff`` bits, where the caller brought them), and
        how many of the ``ok`` messages' delays a window changed: the
        call's ``fault_degraded``."""
        from ...faults.apply import degrade_bits
        slowed = degrade_bits(
            self._ft, delay, aff, 0 if aff is None else
            self._fault_reads()[0], src, dst, tmsg)
        return slowed, jnp.sum(ok & (slowed != delay), dtype=jnp.int32)

    def _fault_reads(self) -> Tuple[int, bool]:
        """``(rows, early)``: where the routing masks read the
        schedule's per-node tables, from shapes alone. The first
        ``rows`` link rows reach ``degrade`` as bits a message carries
        (``link_aff_bits``), the rest are looked up at both ends of a
        lane as ``degrade`` does. ``early``: the destination's packed
        word (``FaultTables.dst_word``) is looked up on the outbox
        lanes, before any compaction: wherever a partition row makes
        ``cut_mask`` look there anyway, and on the eager path, which
        compacts nothing; the bits then ride the destination id's
        spare high bits. Otherwise (the ladder with link rows alone)
        the word is looked up inside the rung, on the rung's lanes,
        and the sender's bits ride the spare bits of its in-window
        offset, which bound ``rows`` too."""
        from ...faults.schedule import dst_word_layout
        ft = self._ft if self._ftv is None else self._ftv
        Pn, L = ft.part_start.shape[-1], ft.link_start.shape[-1]
        rows = dst_word_layout(self.comm.n_global, L)[1]
        early = Pn > 0 or not self._adaptive_regime()
        if not early:
            rows = min(rows, 31 - (self.window - 1).bit_length())
        return rows, early

    def _fault_lanes(self, iterations: int, rung_lanes: int
                     ) -> Tuple[int, int]:
        """``last_run_stats`` ``fault_table_lanes`` and
        ``fault_gather_lanes`` of a call of ``iterations`` supersteps
        whose rungs sum to ``rung_lanes`` senders, from the tables'
        shapes alone (host arithmetic). The first: the lanes a world's
        masks met a table row at. On the node lanes every crash row
        (``defer_next`` in ``_horizon``, and once more where a reboot
        can fire: ``restart_fire``) and every packed link row's source
        bit and window (``src_link_bits``); on the outbox lanes every
        partition row (``cut_mask_at``) and, where the packed word is
        read there, the packed link rows (``link_aff_bits``); on the
        lanes of the rung taken every crash row (``down_mask``) and
        the link rows not yet met. The second: the lanes at which a
        table was read **through an index**: the destination's packed
        word, a row a partition row on the outbox lanes (or once in
        the rung), and both ends of every link row beyond the packed
        ones. Until PR 54 the masks gathered at both ends of every
        lane: ``n * Pn * 2 * M + rung * M * 2 * L`` an iteration."""
        ft = self._ft if self._ftv is None else self._ftv
        C, Pn, L = (getattr(ft, f).shape[-1] for f in (
            "crash_node", "part_start", "link_start"))
        n, M = self.comm.n_local, self.scenario.max_out
        rows, early = self._fault_reads()
        met_early = rows if early else 0   # link rows met on the outbox
        words = max(Pn, min(rows, 1))      # rows of the packed word
        table = iterations * n * (
            C * (1 + self._has_reset) + rows + M * (Pn + met_early)) \
            + rung_lanes * M * (C + L - met_early)
        gather = (iterations * n if early else rung_lanes) * M * words \
            + rung_lanes * M * 2 * (L - rows)
        return table, gather

    def _stages_dense(self, lanes: int) -> bool:
        """Whether ``_stage_by_rank`` takes its dense form for a call
        of ``lanes`` message lanes: a fact of the call's shapes
        (``_DENSE_STAGE_RATIO``), so a ladder's wide rungs and the
        eager path take it and the narrow rungs keep the scatters."""
        return lanes >= _DENSE_STAGE_RATIO * self.comm.n_local

    def _stage_by_rank(self, sd, ok_s, drel_s, src_s, pay_s):
        """A commutative inbox's insertion, the message lanes' half:
        the r-th arrival at node d this superstep (``group_rank`` of
        the sorted destinations) goes to ``r * n + d`` of a fresh flat
        buffer of ``K * n`` words a field. Nothing of the node side is
        read here: which slot the r-th arrival takes only the node
        needs to know (``_fill_staged``). A deliver time's "nothing"
        is the hole's own ``_I32MAX`` (a sampled one is clamped under
        it). Returns ``(rel, src or None, payload words, over,
        took)``; ``over`` counts the arrivals past the K-th at one
        node, which no mailbox can hold; ``took`` is the index of the
        width the dense form's tail was scattered at, of
        ``_dense_plan``'s (the top one: the full width).

        Two forms, one result, chosen by the call's shapes
        (``_stages_dense``). Few lanes for the nodes: one 1D scatter
        a field (the 2D [col, row] form costs ~7x on this chip,
        docs/engines.md per-op cost table); lanes that do not fit get
        an out-of-range index and are dropped. The compiler sorts
        ``(indices, updates)`` in front of every such scatter, the
        same indices once a field. So where the lanes are many
        (``_stage_dense``) the program sorts once, by the staged
        index, and that order buys the rest."""
        sc = self.scenario
        K, P = sc.mailbox_cap, sc.payload_width
        n = self.comm.n_local
        rank = group_rank(sd)
        fits = ok_s & (rank < K)
        if self._stages_dense(sd.shape[0]):
            return self._stage_dense(sd, ok_s, rank, fits, drel_s,
                                     src_s, pay_s)
        flat = jnp.where(fits, rank * jnp.int32(n) + sd,
                         jnp.int32(K * n))

        def stage(x, nothing):
            return jnp.full((K * n,), nothing, x.dtype).at[flat].set(
                x, mode="drop")
        rel = stage(drel_s, _I32MAX)
        # inbox_src=False skips this whole scatter — mailbox scatters
        # ARE the dense random-delivery cost floor (docs/engines.md
        # "Measured on a v5e"), so dropping an unread field is ~1/3
        # of it
        src = stage(src_s, 0) if sc.inbox_src else None
        pay = tuple(stage(pay_s[p], 0) for p in range(P))
        over = jnp.sum(ok_s & (rank >= K), dtype=jnp.int32)
        return rel, src, pay, over, jnp.int32(0)

    def _dense_plan(self, L: int) -> Tuple[int, Tuple[int, ...]]:
        """How ``_stage_dense`` places a call of ``L`` lanes: the rows
        that go through the network, and the static widths,
        ascending, of which the scatters of the rest take the
        smallest that holds them. Facts of the call's shapes. From
        ``_TAIL_LADDER_LANES`` lanes on: ``L/8``, ``L/4``,
        ``L/2``, ``L`` (``_TAIL_DIVISORS``, rounded up; the top one
        stays, no message may be lost), and row 1 beside row 0 where
        the lanes are ``_NET_ROW_RATIO`` times the nodes. Under it:
        row 0 and the two widths ``L // 2``, ``L`` of PR 36's form,
        whose text the dense staging then keeps."""
        if L < _TAIL_LADDER_LANES:
            return 1, (L // 2, L)
        rows = 2 if L >= _NET_ROW_RATIO * self.comm.n_local else 1
        return (min(rows, self.scenario.mailbox_cap),
                tuple(-(-L // d) for d in _TAIL_DIVISORS))

    def _tail_counts(self, lanes, idx, took):
        """What a superstep staged by ``_stage_by_rank`` at
        ``lanes[idx]`` message lanes adds to the drivers' counts
        (``_count_route``) beside whether that call is dense: whether
        its tail took the full width, its lanes, the width of its
        tail and its rows through the network (zeros where the call
        is not dense). ``lanes`` is static (a ladder: a rung's),
        ``idx`` and ``took`` (``_stage_by_rank``'s last) are scalars
        of the program: table reads, outside the routing switch."""
        plans = [self._dense_plan(L) if self._stages_dense(L)
                 else (0, (0,)) for L in lanes]
        most = max(len(w) for _, w in plans)
        row = jnp.asarray(
            [(R > 0, len(w) - 1, L * (R > 0), R) + w
             + w[-1:] * (most - len(w))
             for L, (R, w) in zip(lanes, plans)], jnp.int32)[idx]
        dense, top, L, R = row[:4]
        return (dense * (took == top).astype(jnp.int32), L,
                row[4:][took], R)

    def _stage_dense(self, sd, ok_s, rank, fits, drel_s, src_s, pay_s):
        """``_stage_by_rank`` where the lanes are many for the nodes.
        One variadic sort by the staged index ``rank * n + d`` (lanes
        that do not fit: distinct indices past ``K * n``) carries
        every field, and after it the arrivals stand rank by rank,
        each rank's run ascending in destination
        (``_dense_plan`` says how many rows ``R`` and which widths):

        - the arrivals of the ranks under ``R`` (in a steady
          superstep 1 - 1/e of the nodes get a first, 0.26 of them a
          second) stand first, compacted, ascending in staged index:
          rows 0 … R-1 are their monotone expansion over ``R * n``
          lanes (ops/numeric.py ``expand_lanes``), with no index at
          all, and one contiguous piece of each flat buffer;
        - the ranks from ``R`` on follow from lane ``c`` on, already
          in scatter order: one scalar, their count, picks the
          smallest of the static widths that holds them
          (``lax.switch``; under ``_TAIL_LADDER_LANES`` the
          ``lax.cond`` of two), and a ``dynamic_slice`` of that
          width is scattered, declared sorted and unique, both true
          by the sort, so the compiler puts no sort of its own in
          front. A slice clamped at the lanes' end takes lanes of
          the rows in with it; the rows are written over them.

        The last of the return is the index of the width taken (the
        top one: the full width). Word for word the buffers of the
        other form (tests/test_insert_law.py,
        tests/test_stage_tail_law.py)."""
        sc = self.scenario
        K, P = sc.mailbox_cap, sc.payload_width
        n = self.comm.n_local
        L = sd.shape[0]
        R, widths = self._dense_plan(L)
        fields = (drel_s,) + ((src_s,) if sc.inbox_src else ()) \
            + tuple(pay_s[:P])
        nothing = (_I32MAX,) + (0,) * (len(fields) - 1)
        flat = jnp.where(
            fits, rank * jnp.int32(n) + sd,
            jnp.int32(K * n) + jnp.arange(L, dtype=jnp.int32))
        c = jnp.sum(fits & (rank == 0 if R == 1 else rank < R),
                    dtype=jnp.int32)
        tail = jnp.sum(fits, dtype=jnp.int32) - c
        if len(widths) == 2:
            took = tail > widths[0]
        else:
            took = jnp.sum(tail > jnp.asarray(widths[:-1], jnp.int32),
                           dtype=jnp.int32)
        flat, *fields = jax.lax.sort((flat,) + fields, num_keys=1)

        def head(x):
            # the first R * n lanes: the rows' prefix is among them
            if L >= R * n:
                return x[:R * n]
            return jnp.concatenate(
                [x, jnp.zeros((R * n - L,), x.dtype)])
        rows = expand_lanes(head(flat), c, [head(x) for x in fields],
                            nothing)

        def scatter(width):
            def go():
                at = jax.lax.dynamic_slice_in_dim(flat, c, width)
                return tuple(
                    jnp.full((K * n,), e, x.dtype).at[at].set(
                        jax.lax.dynamic_slice_in_dim(x, c, width),
                        mode="drop", indices_are_sorted=True,
                        unique_indices=True)
                    for x, e in zip(fields, nothing))
            return go
        if len(widths) == 2:
            bufs = jax.lax.cond(took, scatter(widths[1]),
                                scatter(widths[0]))
        else:
            bufs = jax.lax.switch(took, [scatter(w) for w in widths])
        bufs = [jax.lax.dynamic_update_slice_in_dim(b, r, 0, 0)
                for b, r in zip(bufs, rows)]
        over = jnp.sum(ok_s & (rank >= K), dtype=jnp.int32)
        return (bufs[0], bufs[1] if sc.inbox_src else None,
                tuple(bufs[len(bufs) - P:]), over,
                took.astype(jnp.int32))

    def _fill_staged(self, mb_rel, mb_src, mb_payload, holes, rel, src,
                     pay, over):
        """The node lanes' half: every node moves its staged rows 0,
        1, 2, … into its holes in ascending order (ops/numeric.py
        ``fill_holes``: elementwise, no index), so each message ends
        in the slot the rank-th set bit of its destination's hole
        words names. A hole that gets nothing keeps its ``_I32MAX``
        and its stale source and payload words. A node's arrivals past
        its holes are the rest of the overflow count (their ranks have
        no gap, so the staged deliver times count them).

        Every row is a 1D array of its own here, cut from the flat
        view of its plane: a staged row is a contiguous piece of its
        buffer, and a row raised by the fill is another row named, not
        a shift along the sublanes of a tiled ``[K, n]`` array. Only
        the result goes back to the tiled form (``reshape``: the one
        relayout a flat scatter into the mailbox paid too)."""
        sc = self.scenario
        K, P = sc.mailbox_cap, sc.payload_width
        n = self.comm.n_local

        def rows(flat, stride=1, first=0):
            return [flat[(k * stride + first) * n:
                         (k * stride + first + 1) * n] for k in range(K)]
        staged, old = [rows(rel)], [rows(mb_rel.reshape(-1))]
        if sc.inbox_src:
            staged.append(rows(src))
            old.append(rows(mb_src.reshape(-1)))
        staged += [rows(w) for w in pay]
        old += [rows(mb_payload.reshape(-1), P, p) for p in range(P)]
        new = fill_holes(holes, staged, old, _I32MAX)
        if sc.inbox_src:
            mb_src = jnp.concatenate(new[1]).reshape(K, n)
        words = new[len(new) - P:]
        mb_payload = jnp.concatenate(
            [words[p][k] for k in range(K) for p in range(P)]
        ).reshape(K, P, n)
        arrived = sum((r != _I32MAX).astype(jnp.int32)
                      for r in staged[0])
        free = sum(jax.lax.population_count(w).astype(jnp.int32)
                   for w in holes)
        overflow = over + jnp.sum(jnp.maximum(arrived - free, 0),
                                  dtype=jnp.int32)
        # the new deliver times row by row, for the successor's
        # horizon (`_superstep_carried`): never inside a conditional,
        # the fill runs after the ladder's switch
        self._rel_rows = new[0]
        return (jnp.concatenate(new[0]).reshape(K, n), mb_src,
                mb_payload, overflow)

    def _stages_by_rank(self) -> bool:
        """Whether insertion takes the staged form (``_stage_by_rank``
        + ``_fill_staged``): a commutative inbox of a solo engine. A
        fleet keeps the form that asks the node side from the message
        lanes: under its world axis a row of the fill is a ``[B, n]``
        plane, and the flat views the staged form lives on are
        transpositions of the tiled ``[B, K, n]`` mailbox (on the
        chip a fleet's iteration took 15.0 ms staged against 10.1:
        PERF.md, Findings PR 32)."""
        return self.scenario.commutative_inbox and self.batch is None

    def _ranks_fan_in(self) -> bool:
        """Whether insertion appends after the kept messages by rank
        (an ordered inbox, solo or fleet) and so counts the call's
        ``fan_in_peak``. Static, like ``_stages_by_rank``: the drivers
        of a commutative inbox lower to the text they lowered to
        before the counter."""
        return not self.scenario.commutative_inbox

    def _cuts_scatters(self) -> bool:
        """Whether the ranked insertion may cut its scatters to the
        lanes that can land (``_scatter_widths``) and so counts the
        call's ``scatter_lanes``: an ordered inbox of a solo engine on
        one device. Under a fleet's ``vmap`` the width's index would
        be a world's own and the switch a select over every branch
        (``_route_adaptive`` says the same of its rungs), and the
        sharded engine's lanes are a device's share after the
        exchange; no cell runs either, so both keep the one scatter.
        Static, like ``_ranks_fan_in``."""
        return self._ranks_fan_in() and self.batch is None \
            and type(self.comm) is LocalComm

    def _scatter_widths(self, L: int) -> Tuple[int, ...]:
        """The static widths, ascending, of which the ranked insertion
        of ``L`` lanes gathers and scatters the smallest that holds
        every lane that can land (valid, of rank under K in its
        destination's group): ``L/8``, ``L/4``, ``L/2``, ``L``
        (rounded up) from ``_PREFIX_SCATTER_LANES`` lanes on, else
        ``L`` alone.
        Spaced by two, as the routing ladder's rungs are
        (``_sender_rungs``): the width taken is under twice the lanes
        that can land; under an eighth, seven eighths of the cost are
        gone and more widths would only be more to compile."""
        if not self._cuts_scatters() or L < _PREFIX_SCATTER_LANES:
            return (L,)
        return tuple(-(-L // d) for d in (8, 4, 2, 1))

    def _take_fan_in(self, ret):
        """``ret`` as ``_insert_sorted`` returns it (and whatever a
        rung put after it) without the largest fan-in and the width
        the scatters took, which are left on ``self._fan_in`` and
        ``self._scattered`` for the drivers' counts: taken outside
        the routing switch, where a rung's value may be kept."""
        if not self._ranks_fan_in():
            return ret
        self._fan_in = self.comm.all_max(ret[4])
        if not self._cuts_scatters():
            return ret[:4] + ret[5:]
        self._scattered = ret[5]
        return ret[:4] + ret[6:]

    @jax.named_scope("insert")
    def _insert_sorted(self, mb_rel, mb_src, mb_payload, sd, ok_s,
                       drel_s, src_s, pay_s, holes, counts):
        """Shared mailbox insertion for destination-sorted messages.
        A solo engine's commutative inbox stages its arrivals by rank
        in buffers of their own and lets every node fill its holes
        from them (``_stage_by_rank``, ``_fill_staged``: the message
        lanes read nothing of the node side). Otherwise:
        per-destination rank -> target slot (a fleet's commutative
        inbox: the r-th hole, the r-th set bit of the destination's
        ``holes`` words, ops/numeric.py ``nth_set_bit``; an ordered
        inbox: append-after-kept) -> flat 1D scatters into the
        mailbox; non-fitting lanes get an out-of-range index and are
        dropped. Where a solo engine on one device ranks
        (``_cuts_scatters``) and the call has the lanes for it, the
        gather of the destinations' kept counts and the scatters take
        only the prefix that ends at the last valid lane of rank
        under K, at the smallest of four static widths that holds it
        (``_scatter_widths``): the lanes left out cannot land
        whatever the mailbox keeps, so no word changes, and
        ``overflow`` is the valid lanes less those that landed. One
        algorithm whose width follows what the call's own lanes show:
        the width is that of the lanes that fit wherever the
        destinations have room; one hot destination costs K lanes;
        many distinct destinations that stand full under spread
        arrivals (the worst case) take the width of their arrivals,
        at most the full one, which is what every call paid before
        the cut (some 4 x 4.85 ns x 7L/8 over the narrowest width's
        scatters). Returns the updated arrays plus the local
        overflow count and, from an ordered inbox, the largest number
        of arrivals to one destination and (where it may cut them)
        the lanes its scatters took (``_take_fan_in`` takes both off
        again). Both commutative forms put every message in the same
        slot; held to the oracle at its two call sites (a ladder rung,
        the eager path), and to each other, by tests/insertion_laws.py."""
        sc = self.scenario
        K, P = sc.mailbox_cap, sc.payload_width
        n = self.comm.n_local
        if self._stages_by_rank():
            *staged, took = self._stage_by_rank(sd, ok_s, drel_s, src_s,
                                                pay_s)
            self._staged = (
                jnp.int32(self._stages_dense(sd.shape[0])),
                *self._tail_counts((sd.shape[0],), 0,
                                   self.comm.all_max(took)))
            return self._fill_staged(mb_rel, mb_src, mb_payload, holes,
                                     *staged)
        rank = group_rank(sd)
        L = sd.shape[0]
        widths = self._scatter_widths(L)
        if sc.commutative_inbox:
            # r-th incoming message takes the destination's r-th hole:
            # one 1D gather a word, then a bit select on these lanes
            # (K when the mailbox holds r holes or fewer)
            sdc = jnp.clip(sd, 0, n - 1)
            prow = nth_set_bit([w[sdc] for w in holes], rank, K)
            fits = ok_s & (prow < K)
            col = jnp.clip(prow, 0, K - 1)
            pos = jnp.where(fits, jnp.int32(0), jnp.int32(K))
            fan_in = ()
        else:
            if len(widths) == 1:
                pos = counts[jnp.clip(sd, 0, n - 1)] + rank
                fits = ok_s & (pos < K)
                col = jnp.clip(pos, 0, K - 1)
            fan_in = (jnp.max(jnp.where(ok_s, rank + 1, 0)),)

        def scatter(w, fits_w, col_w):
            # the first `w` lanes into the mailbox, a flat scatter a
            # field: static slices from lane 0, none at the full width
            def cut(x):
                return x if w == L else x[:w]
            sd_w = cut(sd)
            flat = jnp.where(fits_w, col_w * jnp.int32(n) + sd_w,
                             jnp.int32(K * n))
            rel = mb_rel.reshape(-1).at[flat].set(
                cut(drel_s), mode="drop").reshape(K, n)
            src = mb_src.reshape(-1).at[flat].set(
                cut(src_s), mode="drop").reshape(K, n) \
                if sc.inbox_src else None
            pay = mb_payload.reshape(-1)
            for p in range(P):
                flat_p = jnp.where(
                    fits_w,
                    (col_w * jnp.int32(P) + p) * jnp.int32(n) + sd_w,
                    jnp.int32(K * P * n))
                pay = pay.at[flat_p].set(cut(pay_s[p]), mode="drop")
            return rel, src, pay.reshape(K, P, n)
        if len(widths) == 1:
            rel, src, pay = scatter(L, fits, col)
            width = jnp.int32(L)
            overflow = jnp.sum(ok_s & (pos >= K), dtype=jnp.int32)
        else:
            # the routing sort put the valid lanes first, ordered by
            # destination and arrival, and `counts` is never negative:
            # a lane whose rank in its destination's group is K or
            # more cannot land whatever the mailbox keeps, nor can an
            # invalid one. So the lanes that can land end at `hi`, a
            # function of the ranks alone, and one scalar picks the
            # smallest width that holds them, as the ladder of rungs
            # picks its own (`_route_adaptive`), before anything is
            # gathered. The branch gathers its destinations' kept
            # counts on its own `w` lanes; a valid lane it leaves out
            # is a dropped one, so `overflow` is the valid lanes less
            # those that landed
            hi = jnp.max(jnp.where(
                ok_s & (rank < K),
                jnp.arange(1, L + 1, dtype=jnp.int32), 0))
            steps = jnp.asarray(widths, jnp.int32)
            idx = jnp.sum(hi > steps)

            def ranked(w):
                pos_w = counts[jnp.clip(sd[:w], 0, n - 1)] + rank[:w]
                fits_w = ok_s[:w] & (pos_w < K)
                return scatter(w, fits_w, jnp.clip(pos_w, 0, K - 1)) + (
                    jnp.sum(fits_w, dtype=jnp.int32),)
            rel, src, pay, landed = jax.lax.switch(
                idx, [partial(ranked, w) for w in widths])
            width = steps[idx]
            overflow = jnp.sum(ok_s, dtype=jnp.int32) - landed
        if sc.inbox_src:
            mb_src = src
        return (rel, mb_src, pay, overflow) + fan_in + (
            (width,) if self._cuts_scatters() else ())

    def _route_adaptive(self, out, out_valid, now_vec, t, mb_rel,
                        mb_src, mb_payload, holes, counts,
                        node_ids, with_trace):
        """Sender-compacted adaptive-width routing + insertion (class
        docstring): put the active sender ids in front with ONE
        order-preserving compaction of the N node lanes
        (``compress_lanes``: a prefix count and ``bit_length(N - 1)``
        shifts, scope ``tw.route/senders``; no N-sort since PR 48),
        then gather/sort/sample/rank/scatter at the smallest
        ladder rung that fits this superstep's active-sender count
        (``lax.switch`` — every branch is static-shape, so this is
        XLA-legal). The top rung (``A == n``; the only one of an
        engine of at most 1 024 nodes) gathers nothing: its lanes are
        the outbox planes where they lie, scope ``tw.route/inplace``
        (PR 56; ``last_run_stats["inplace_rung_steps"]``). All
        ``max_out`` lanes of a sender share its firing
        instant, so per-sender compaction preserves contract #3's
        (window offset, sender-major rank) arrival order exactly.
        Single-chip, no-drop links only; counters and digests match
        the eager path bit-for-bit."""
        sc = self.scenario
        K, M, P = sc.mailbox_cap, sc.max_out, sc.payload_width
        n = self.comm.n_local
        n_glob = self.comm.n_global
        W = self.window
        rec_full = with_trace and self.record == "full"
        # pack (validity, destination-range check) into ONE array so
        # the per-rung gather moves 1 + P arrays instead of 3 + P —
        # random-access volume is the branch's dominant cost on this
        # chip (~4.5 ns/element, docs/engines.md per-op cost table). Contract #6
        # corollary: out-of-range destinations are counted here,
        # globally, never silently dropped.
        dst32 = out.dst.astype(jnp.int32)                       # [M, N]
        dst_okf = (dst32 >= 0) & (dst32 < n_glob)
        bad_dst_step = jnp.sum(out_valid & ~dst_okf, dtype=jnp.int32)
        pdst = jnp.where(out_valid & dst_okf, dst32, -1)        # [M, N]
        fault_cut = jnp.int32(0)
        #: the link rows whose verdicts reach the rung as bits, and the
        #: word they ride through the compaction (``_fault_reads``):
        #: above a destination id's ``dbits`` where the destination's
        #: packed word is looked up here, above the sender's offset's
        #: ``wbits`` where it is looked up in the rung
        aff_rows = aff_early = 0
        dbits, wbits = (n_glob - 1).bit_length(), (W - 1).bit_length()
        if self._faulted:
            from ...faults.apply import (cut_mask_at, dst_words,
                                         link_aff_bits, src_link_bits)
            ft = self._ft
            aff_rows, aff_early = self._fault_reads()
            if aff_rows:
                with jax.named_scope("fault"):
                    # a link row's source bit AND its whole time test
                    # are facts of the sender: one bit a row and node,
                    # on the node lanes, no look-up
                    src_bits = src_link_bits(ft, node_ids, now_vec,
                                             aff_rows)
        if aff_early:
            # partition cuts are sample-independent: kill them before
            # compaction (counted; the oracle drops the same set). The
            # source is the node lanes themselves: its group is its
            # own packed word's, read in place; the destination's
            # word is the ONE look-up a message's masks make
            with jax.named_scope("fault"):
                at_dst = dst_words(ft, pdst)                 # [Pn, M, N]
                cutm = (pdst >= 0) & cut_mask_at(ft, node_ids, at_dst,
                                                 now_vec)
                fault_cut = jnp.sum(cutm, dtype=jnp.int32)
            self._rec_cut(rec_full, cutm, node_ids[None, :], pdst,
                          now_vec[None, :])
            pdst = jnp.where(cutm, jnp.int32(-1), pdst)
            if aff_rows:
                # every packed link row's verdict, from the same word:
                # the bits ride the destination id through the rung's
                # gather (a valid id is under n_global; -1 stays -1)
                with jax.named_scope("fault"):
                    aff = link_aff_bits(ft, src_bits, at_dst[0],
                                        aff_rows)
                    pdst = jnp.where(pdst >= 0, pdst | (aff << dbits),
                                     jnp.int32(-1))
        sender_live = jnp.any(pdst >= 0, axis=0)                # [N]
        n_active = jnp.sum(sender_live, dtype=jnp.int32)
        with jax.named_scope("senders"):
            # the live ids ascending, then n: what a sort of
            # where(sender_live, node_ids, n) gives, by a prefix count
            # and log N shifts on the node lanes (PR 48)
            sid_sorted = compress_lanes(sender_live, [node_ids],
                                        [jnp.int32(n)])[0]
        # precomputed int32 in-window offsets: the branches gather one
        # int32 word per sender instead of an int64
        woff_n = (now_vec - t).astype(jnp.int32)                # [N]
        if aff_rows and not aff_early:
            # no look-up before compaction to ride on: the sender's
            # bits ride its offset (under W) through that gather
            woff_n = woff_n | (src_bits << wbits)

        staged = self._stages_by_rank()

        def insert(sd, ok_s, drel_s, src_s, pay_s):
            # a rung's part of insertion. In the staged form a rung
            # stages its arrivals only, and the nodes fill their holes
            # once, after the switch (`filled`): no [K, N] mailbox
            # plane crosses the conditional, which moves its operands.
            # The other forms scatter into the mailbox inside the rung
            if not staged:
                return self._insert_sorted(
                    mb_rel, mb_src, mb_payload, sd, ok_s, drel_s,
                    src_s, pay_s, holes, counts)
            with jax.named_scope("insert"):
                return self._stage_by_rank(sd, ok_s, drel_s, src_s,
                                           pay_s)

        def filled(ret, dense, idx):
            # `ret` is the return of the rung taken, `idx` that
            # rung's index, `dense` whether that rung staged in the
            # dense form: that, and at which widths, is static a
            # rung, so only the index of the width taken (ret[4]) has
            # a place in the switch's return
            if not staged:
                return self._take_fan_in(ret)
            self._staged = (
                jnp.asarray(dense, jnp.int32),
                *self._tail_counts([A * M for A in rungs], idx, ret[4]))
            with jax.named_scope("insert"):
                return self._fill_staged(mb_rel, mb_src, mb_payload,
                                         holes, *ret[:4]) + ret[5:]

        def tail(A):
            def gather(A):
                if A == n:
                    # the top rung's width is the node axis itself:
                    # `sid_sorted[:n]` would only permute what lies on
                    # the node lanes already (dead senders last, as
                    # copies of node 0 that `ok` masks), in front of a
                    # sort whose last key, `smrank`, is unique on every
                    # valid lane. Read the outbox in place: the lanes
                    # are the nodes, a dead sender's hold no valid
                    # destination, and no word is gathered
                    with jax.named_scope("inplace"):
                        SA = n * M
                        dst_f = pdst.reshape(SA)
                        smrank = (jnp.arange(n, dtype=jnp.int32)[None, :]
                                  * jnp.int32(M)
                                  + jnp.arange(M, dtype=jnp.int32)[:, None]
                                  ).reshape(SA)
                        pay_f = tuple(out.payload[:, p, :].reshape(SA)
                                      for p in range(P))
                        return SA, woff_n, dst_f, dst_f >= 0, smrank, pay_f
                sids = jax.lax.slice_in_dim(sid_sorted, 0, A)
                real = sids < n
                sidc = jnp.where(real, sids, 0)  # safe gather index
                woff_a = woff_n[sidc]                           # [A]
                dst_a = jnp.take(pdst, sidc, axis=1)            # [M, A]
                pay_a = tuple(jnp.take(out.payload[:, p, :], sidc, axis=1)
                              for p in range(P))
                SA = A * M
                dst_f = dst_a.reshape(SA)
                ok = (dst_f >= 0) & jnp.broadcast_to(
                    real[None, :], (M, A)).reshape(SA)
                smrank = (jnp.broadcast_to(sidc[None, :] * jnp.int32(M),
                                           (M, A))
                          + jnp.arange(M, dtype=jnp.int32)[:, None]
                          ).reshape(SA)
                pay_f = tuple(p.reshape(SA) for p in pay_a)
                return SA, woff_a, dst_f, ok, smrank, pay_f

            def branch_faulted():
                # sample BEFORE the routing sort: the down-window drop
                # needs each message's deliver time, and insertion
                # ranks must count only genuinely inserted messages
                # (a post-sort mask would corrupt per-dst slot ranks).
                # Value-identical to sampling after the sort (the
                # unfaulted branch) — link entropy is keyed per
                # message, not per lane position.
                from ...faults.apply import down_mask
                SA, woff_a, dst_f, ok, smrank, pay_f = gather(A)
                aff = None
                if aff_rows and aff_early:
                    # the verdicts came with the destination
                    aff = jnp.where(dst_f >= 0, dst_f >> dbits, 0)
                    dst_f = jnp.where(
                        dst_f >= 0, dst_f & jnp.int32((1 << dbits) - 1),
                        jnp.int32(-1))
                elif aff_rows:
                    # the sender's bits came with its offset; the
                    # destination's word is looked up here, on the
                    # rung's lanes
                    with jax.named_scope("fault"):
                        aff = link_aff_bits(
                            ft, woff_a >> wbits,
                            dst_words(ft, dst_f)[0], aff_rows)
                    woff_a = woff_a & jnp.int32((1 << wbits) - 1)
                woff_f = jnp.broadcast_to(
                    woff_a[None, :], (M, A)).reshape(SA) \
                    if W > 1 else jnp.zeros((SA,), jnp.int32)
                src_l = smrank // jnp.int32(M)
                tmsg_l = t + woff_f.astype(jnp.int64)
                flight, drel, bad_delay_step, short_step, strag, \
                    degraded = self._sample_nodrop(
                        src_l, dst_f, tmsg_l, smrank % jnp.int32(M),
                        woff_f, ok, aff)
                with jax.named_scope("fault"):
                    downm = ok & down_mask(self._ft, dst_f,
                                           tmsg_l + flight)
                    fault_down = jnp.sum(downm, dtype=jnp.int32)
                ok2 = ok & ~downm
                sent_count = jnp.sum(ok2, dtype=jnp.int32)
                if with_trace:
                    dt_abs = tmsg_l + flight
                    sent_mix = mix32_jnp(SENT, src_l, dst_f,
                                         _tlo(dt_abs), _thi(dt_abs),
                                         pay_f[0])
                    sent_hash = _u32sum(jnp.where(ok2, sent_mix, 0))
                else:
                    sent_hash = jnp.uint32(0)
                sort_dst = jnp.where(ok2, dst_f, n)
                if W > 1:
                    ops = jax.lax.sort(
                        (sort_dst, woff_f, smrank, drel) + pay_f,
                        dimension=0, num_keys=3)
                    sd, smrank_s, drel_s = ops[0], ops[2], ops[3]
                    pay_s = ops[4:]
                else:
                    ops = jax.lax.sort(
                        (sort_dst, smrank, drel) + pay_f,
                        dimension=0, num_keys=2)
                    sd, smrank_s, drel_s = ops[0], ops[1], ops[2]
                    pay_s = ops[3:]
                ok_s = sd < n
                src_s = smrank_s // jnp.int32(M)
                ret = insert(sd, ok_s, drel_s, src_s, pay_s) + (
                    bad_dst_step, bad_delay_step, short_step,
                    jnp.int32(0), sent_count, sent_hash,
                    (fault_cut, fault_down, degraded))
                if strag is not None:
                    # the causality plane's straggler min rides the
                    # switch return like the send capture below (the
                    # one legal exit for a branch-scoped value)
                    ret += (strag,)
                if rec_full:
                    # send capture rides the switch return (the one
                    # legal exit for a branch-scoped value) — pre-down
                    # mask, so down-dropped sends are tagged, not lost
                    ret += (self._rec_sends(ok, downm, src_l, dst_f,
                                            tmsg_l, tmsg_l + flight),)
                return ret
            if self._faulted:
                return branch_faulted

            def branch():
                SA, woff_a, dst_f, ok, smrank, pay_f = gather(A)
                sort_dst = jnp.where(ok, dst_f, n)
                if W > 1:
                    woff_f = jnp.broadcast_to(
                        woff_a[None, :], (M, A)).reshape(SA)
                    ops = jax.lax.sort(
                        (sort_dst, woff_f, smrank) + pay_f,
                        dimension=0, num_keys=3)
                    sd, woff_s, smrank_s = ops[0], ops[1], ops[2]
                    pay_s = ops[3:]
                else:
                    ops = jax.lax.sort(
                        (sort_dst, smrank) + pay_f, dimension=0,
                        num_keys=2)
                    sd, smrank_s = ops[0], ops[1]
                    woff_s = jnp.zeros_like(sd)
                    pay_s = ops[2:]
                ok_s = sd < n
                src_s = smrank_s // jnp.int32(M)
                tmsg_s = t + woff_s.astype(jnp.int64)
                # sample only the rung's lanes; invalid lanes are fed
                # the sentinel and masked (`sample` is elementwise)
                flight_s, drel_s, bad_delay_step, short_step, strag, _ = \
                    self._sample_nodrop(src_s, sd, tmsg_s,
                                        smrank_s % jnp.int32(M),
                                        woff_s, ok_s)
                inserted = insert(sd, ok_s, drel_s, src_s, pay_s)
                sent_count = jnp.sum(ok, dtype=jnp.int32)
                if with_trace:
                    dt_abs = tmsg_s + flight_s
                    sent_mix = mix32_jnp(SENT, src_s, sd, _tlo(dt_abs),
                                         _thi(dt_abs), pay_s[0])
                    sent_hash = _u32sum(jnp.where(ok_s, sent_mix, 0))
                else:
                    sent_hash = jnp.uint32(0)
                # route_drop ≡ 0, as on the eager path: the slot feeds
                # EngineState.route_drop, which stays (ROADMAP D28)
                ret = inserted + (bad_dst_step, bad_delay_step,
                                  short_step, jnp.int32(0), sent_count,
                                  sent_hash)
                if strag is not None:
                    ret += (strag,)
                if rec_full:
                    ret += (self._rec_sends(ok_s, None, src_s, sd,
                                            tmsg_s,
                                            tmsg_s + flight_s),)
                return ret
            return branch

        rungs = self._sender_rungs(n)
        if len(rungs) == 1:
            if self.telemetry != "off":
                self._t_rung = jnp.int32(rungs[-1])
            return filled(tail(rungs[-1])(),
                          self._stages_dense(rungs[-1] * M), 0)
        if self.batch is not None:
            # a fleet takes ONE rung for all its worlds: the smallest
            # that holds the busiest world's senders. The pmax over
            # the vmap's axis (_vstep) is unbatched, so the switch
            # below stays a conditional with every branch batched; on
            # a per-world index it would lower to a select over ALL
            # the branches, every rung in every world. Any rung that
            # fits is result-identical by the ladder's construction,
            # and the largest count fits every world
            self._own_senders = n_active
            n_active = jax.lax.pmax(n_active, _FLEET_AXIS)
        idx = jnp.sum(n_active > jnp.asarray(rungs, jnp.int32))
        if self._dyn is not None:
            # controller rung pin (dispatch/): a traced FLOOR on the
            # selected index — max(computed, pin) can only pick a
            # wider rung, which is result-identical by the ladder's
            # own construction (any rung that fits is), so pinning
            # against thrash can never drop a message. -1 = unpinned.
            pin = jnp.clip(self._dyn.rung_pin, jnp.int32(-1),
                           jnp.int32(len(rungs) - 1))
            idx = jnp.maximum(idx, pin.astype(idx.dtype))
        # the rung the switch actually takes — recorded where the
        # decision is made, so telemetry and the drivers' counts
        # (``RouteCounts``, ``_count_route``) can never drift from it
        self._t_rung = jnp.asarray(rungs, jnp.int32)[idx]
        self._routed = (self._t_rung, n_active, idx.astype(jnp.int32))
        return filled(
            jax.lax.switch(idx, [tail(A) for A in rungs]),
            jnp.asarray([self._stages_dense(A * M) for A in rungs])[idx],
            idx)

    def _node_next(self, st: EngineState, nnr=None) -> jax.Array:
        """Each node's next event time as the state alone has it
        (int64[N]; the batched "pop min", TimedT.hs:241-245, before
        the minimum): its wake, or its earliest message. ``nnr`` is
        the per-node minimum of ``st.mb_rel`` where the caller holds
        it already (the staged insertion's rows), else one pass over
        the mailbox finds it."""
        if nnr is None:
            nnr = st.mb_rel.min(axis=0)
        return jnp.minimum(
            st.wake,
            jnp.where(nnr == _I32MAX, jnp.int64(NEVER),
                      st.time + nnr.astype(jnp.int64)))

    def _horizon(self, st: EngineState, nnr=None) -> Horizon:
        """The state's :class:`Horizon`. Under a fault schedule
        events inside a down window slide to its ``t_up`` and
        unconsumed reset rows inject the restart firing
        (faults/apply.py ``defer_next``), so the deferral is part of
        the horizon wherever it is computed. Integers and ``min``:
        the same values from a state whoever asks, so a superstep
        that takes the horizon it was handed is the superstep that
        finds it again, bit for bit."""
        node_next = self._node_next(st, nnr)
        if self._faulted:
            from ...faults.apply import defer_next
            with jax.named_scope("fault"):
                node_next = defer_next(self._ft, self.comm.node_ids(),
                                       node_next, st.restart_done)
        return Horizon(self.comm.all_min(node_next.min()), node_next)

    def _superstep(self, st: EngineState, with_trace: bool
                   ) -> Tuple[EngineState, Optional[_StepOut]]:
        """One superstep of a state on its own: finds the state's
        horizon, and returns the state unchanged once nothing is
        pending (``live``). What the scan driver, the chunked drivers
        and every caller outside the quiet loop step with."""
        with Stages() as stage:
            stage("tw.next_event")
            hz = self._horizon(st)
            return self._staged_superstep(st, hz, hz.t < NEVER,
                                          with_trace, stage)

    def _superstep_carried(self, st: EngineState, hz: Horizon,
                           in_budget=None
                           ) -> Tuple[EngineState, Horizon]:
        """The quiet loop's superstep: ``(state, horizon) -> (state',
        horizon')``. ``hz`` is ``st``'s horizon, found by the
        superstep before (``_quiet_loop``: or by the one scan before
        the loop). A solo loop's condition has decided on ``hz.t``
        itself that this superstep runs, so nothing is selected by
        liveness here (``in_budget`` None). A fleet's world steps
        while it is live and ``in_budget`` (its own budget, a traced
        bool): one select of its state by both.

        ``horizon'`` comes from what insertion has just written. In
        the staged form the new ``mb_rel`` is K rows before they are
        concatenated (``_fill_staged``), and the per-node minimum is
        an elementwise ``min`` of them; in the other forms it is one
        pass over the new ``mb_rel`` (a frozen world's: over the old,
        which gives its old horizon again)."""
        with Stages() as stage:
            stage("tw.next_event")
            act = None if in_budget is None else \
                (hz.t < NEVER) & in_budget
            new, _ = self._staged_superstep(st, hz, act, False, stage)
            stage("tw.next_event")
            nnr = None
            if act is None and self._rel_rows is not None:
                nnr = reduce(jnp.minimum, self._rel_rows)
            return new, self._horizon(new, nnr)

    def _staged_superstep(self, st, hz, live, with_trace, stage):
        """One superstep of ``st`` from its horizon ``hz``, each
        numbered part under the scope ``stage`` names for it
        (common.py ``STAGES``); the caller has opened
        ``tw.next_event``. ``live`` is whether the superstep applies
        (a traced bool: the result is ``st`` where it is false), or
        None where the caller's loop has decided that already."""
        sc, comm = self.scenario, self.comm
        K, M, P = sc.mailbox_cap, sc.max_out, sc.payload_width
        n = comm.n_local            # array width on this device
        n_glob = comm.n_global
        node_ids = comm.node_ids()  # global identities, int32[n]
        base = st.time
        #: flight-recorder side channels (obs/flight.py): compacted
        #: event buffers the capture sites below accumulate during
        #: this one trace, merged into the StepOut event plane by
        #: _finish_superstep — reset per trace, like ``_t_rung``. The
        #: quiet driver (with_trace=False) emits no rows, so nothing
        #: is captured there (run_quiet is record-free by contract).
        self._rec_extra = []
        #: the new ``mb_rel`` as K rows, where insertion made it so
        #: (``_fill_staged``): what ``_superstep_carried`` takes the
        #: successor's horizon from; reset per trace the same way
        self._rel_rows = None
        rec_full = with_trace and self.record == "full"

        # validity is the rel sentinel (I32MAX = empty slot)
        mb_live = st.mb_rel < _I32MAX                           # [K, N]
        W = self.window

        # 1. global next event time: the state's horizon (`_horizon`),
        # found by whoever made the state
        t, node_next = hz
        if self._faulted and rec_full:
            # fault action: a crash window slid the node's pending
            # event later (re-recorded every superstep the node
            # stays down — the query layer dedups host-side).
            # send_t carries the ORIGINAL pending instant, t the
            # deferred-to instant (obs/flight.py docstring)
            from ...obs import flight
            node_next_pre = self._node_next(st)
            dm = (node_next > node_next_pre) \
                & (node_next_pre < NEVER)
            self._rec_extra.append(flight.compact(
                self.record_cap, flight.EV_FAULT, dm, node_ids,
                node_ids, node_next_pre, node_next,
                flight.TAG_DEFER))
        # dynamic dispatch (controlled.py): the controller's requested
        # window arrives as a traced scalar, clamped to [1, bound] and
        # — under a fault schedule — to the per-superstep degraded
        # link floor over [t, t + request) (faults/apply.window_floor:
        # a degradation window that undercuts the declared floor
        # narrows exactly the supersteps it overlaps). Static engines
        # keep W the Python int it always was — jaxpr unchanged.
        if self._dyn is not None:
            Wv = jnp.clip(self._dyn.window, jnp.int64(1), jnp.int64(W))
            if self._faulted:
                from ...faults.apply import window_floor
                with jax.named_scope("fault"):
                    Wv = window_floor(self._ft, t, Wv, W)
        else:
            Wv = W
        self._w_now = Wv
        # windowed firing: every node with an event in [t, t+W) fires,
        # each at its OWN instant (W=1 degenerates to == t, since t is
        # the global min). In-window firings are causally independent
        # because link delays are >= W (validated in __init__; counted
        # in short_delay below when violated).
        fire = (node_next < NEVER) & (node_next - t < Wv)
        if live is not None:
            fire = fire & live
        #: per-node firing instant; t for non-fired (their results are
        #: masked, but the step function must see a sane `now`)
        now_vec = jnp.where(fire, node_next, t)                 # int64[N]
        shift32 = jnp.minimum(t - base,
                              jnp.int64(_I32MAX - 1)).astype(jnp.int32)
        #: per-node deliver horizon relative to the epoch
        nrel = jnp.minimum(now_vec - base,
                           jnp.int64(_I32MAX - 1)).astype(jnp.int32)

        # 1.5. restart bookkeeping: consume reset rows whose node
        # fires at its t_up this superstep; their state resets to the
        # init template below, and mailbox entries older than the
        # crash are purged (memory loss — counted, never delivered)
        restart_done = st.restart_done
        fault_purged = fault_restarts = jnp.int32(0)
        purge = reset_now = None
        if self._faulted and self._has_reset:
            from ...faults.apply import consume_restarts, restart_fire
            with jax.named_scope("fault"):
                reset_now, purge_before = restart_fire(
                    self._ft, fire, now_vec, node_ids, st.restart_done)
                restart_done = consume_restarts(
                    self._ft, fire, now_vec, node_ids, st.restart_done)
                purge = mb_live & (
                    (base + st.mb_rel.astype(jnp.int64))
                    < purge_before[None, :])
                fault_purged = comm.all_sum(
                    jnp.sum(purge, dtype=jnp.int32))
                fault_restarts = jnp.sum(
                    restart_done & ~st.restart_done, dtype=jnp.int32)
            if rec_full:
                # fault actions: the injected reboot firing, and every
                # mailbox entry the reboot's memory loss purged (the
                # purged message's src/deliver-time identify it)
                from ...obs import flight
                self._rec_extra.append(flight.compact(
                    self.record_cap, flight.EV_FAULT, reset_now,
                    node_ids, node_ids, jnp.int64(-1), now_vec,
                    flight.TAG_RESTART))
                self._rec_extra.append(flight.compact(
                    self.record_cap, flight.EV_FAULT, purge,
                    st.mb_src if sc.inbox_src
                    else jnp.zeros_like(st.mb_src),
                    jnp.broadcast_to(node_ids[None, :], (K, n)),
                    jnp.int64(-1),
                    st.mb_rel, flight.TAG_PURGE, t_off=base))

        stage("tw.deliver")
        # 2. deliverable messages: due at or before the node's own
        #    firing instant (== `<= shift32` when W == 1)
        deliver = mb_live & (st.mb_rel <= nrel[None, :]) & fire[None, :]
        if purge is not None:
            deliver = deliver & ~purge

        # 3. inbox: delivered slots first, ordered by (time, arrival slot)
        #    (determinism contract #2) — one variadic sort along K.
        #    Commutative-inbox scenarios waive the ordering (slot order
        #    is unobservable to a commutative reduction; digests are
        #    order-independent), so the [K, N] sort is skipped and the
        #    inbox is the raw mailbox under the deliver mask — the same
        #    waiver the edge engine exercises (edge_engine.py).
        slots = jnp.broadcast_to(
            jnp.arange(K, dtype=jnp.int32)[:, None], (K, n))
        if sc.commutative_inbox:
            inbox = Inbox(
                valid=deliver,
                src=jnp.where(deliver, st.mb_src, 0) if sc.inbox_src
                else jnp.zeros_like(st.mb_src),
                time=jnp.where(deliver,
                               base + st.mb_rel.astype(jnp.int64),
                               jnp.int64(NEVER)),
                payload=jnp.where(deliver[:, None, :], st.mb_payload, 0),
            )
        else:
            with jax.named_scope("sort"):
                rel_key = jnp.where(deliver, st.mb_rel, _I32MAX)
                ops = jax.lax.sort(
                    (~deliver, rel_key, slots, st.mb_src) + tuple(
                        st.mb_payload[:, p, :] for p in range(P)),
                    dimension=0, num_keys=3)
            ib_valid, ib_rel, ib_src = ~ops[0], ops[1], ops[3]
            ib_pay = jnp.stack(ops[4:4 + P], axis=1)            # [K, P, N]
            # pad invalid slots exactly like the oracle (src=0,
            # time=NEVER, payload=0) so an unmasked read in a user step
            # function cannot diverge between interpreters
            inbox = Inbox(
                valid=ib_valid,
                src=jnp.where(ib_valid, ib_src, 0) if sc.inbox_src
                else jnp.zeros_like(ib_src),
                time=jnp.where(ib_valid, base + ib_rel.astype(jnp.int64),
                               jnp.int64(NEVER)),
                payload=jnp.where(ib_valid[:, None, :], ib_pay, 0),
            )

        stage("tw.fire")
        # 4. fire every node simultaneously, each at its own instant;
        # mask non-fired results. Entropy is derived elementwise
        # (core/rng.py), keyed by the node's own firing instant — the
        # same bits a window=1 run derives for that (node, time) firing.
        # Batch axis is the *minor* dim for inbox and outbox leaves.
        # The derivation has a scope of its own (`tw.fire/entropy`): a
        # Threefry over all N lanes on every superstep of a scenario
        # that asks for it, and of no other. A name and nothing else:
        # the v5e's compiler fuses all of it into the step's fusion, so
        # a profile shows its time under the step's name (PERF.md, PR 33)
        bits = None
        if sc.needs_key:
            with jax.named_scope("entropy"):
                bits = fire_bits(self.s0, self.s1, node_ids, now_vec)
        states_in = st.states
        if reset_now is not None:
            # a rebooting node's step sees the scenario's first state
            with jax.named_scope("fault"):
                states_in = jax.tree.map(
                    lambda cur, init: jnp.where(
                        reset_now.reshape((n,) + (1,) * (cur.ndim - 1)),
                        init, cur),
                    st.states, self._reset_states)
        stepf = sc.step
        if self._faulted and self._has_skew:
            # the node's VIEW of time shifts; entropy keys, digests
            # and fault windows stay on true time (faults/apply.py)
            from ...faults.apply import skewed_step
            stepf = skewed_step(sc.step, self._ft.skew)
        new_states, out, new_wake = jax.vmap(
            stepf,
            in_axes=(0, Inbox(valid=-1, src=-1, time=-1, payload=-1),
                     0, 0, None if bits is None else 0),
            out_axes=(0, Outbox(valid=-1, dst=-1, payload=-1), 0))(
                states_in, inbox, now_vec, node_ids, bits)
        states = jax.tree.map(
            lambda a, b: jnp.where(
                fire.reshape((n,) + (1,) * (b.ndim - 1)), b, a),
            st.states, new_states)
        new_wake = jnp.where(new_wake >= NEVER, NEVER,
                             jnp.maximum(new_wake, now_vec + 1))  # contract #5
        wake = jnp.where(fire, new_wake, st.wake)
        out_valid = out.valid & fire[None, :]                   # [M, N]
        if self.telemetry != "off":
            # telemetry side channel (consumed by _finish_superstep in
            # this same trace): senders with >= 1 valid outbox message
            # — the event-density signal; the routing stage below
            # overrides the rung when it runs a ladder
            self._t_senders = comm.all_sum(jnp.sum(
                jnp.any(out_valid, axis=0), dtype=jnp.int32))
            self._t_rung = jnp.int32(-1)

        stage("tw.rebase")
        # 5. drop delivered messages and rebase surviving deliver-times
        #    to the new epoch t. Two regimes:
        #    - commutative inbox: slot order is unobservable, so freed
        #      slots become *holes* (elementwise — no [K, N] compaction
        #      sort, and no sort of the free rows either: a node's
        #      holes are ceil(K/32) uint32 words, and insertion lets
        #      the node fill them from its arrivals staged by rank,
        #      `_fill_staged`). Overflow semantics are bit-identical:
        #      rank >= #free ⇔ counts + rank >= K.
        #    - ordered inbox: the variadic compaction sort keeps arrival
        #      order materialized in slot order (contract #2's tiebreak).
        keep = mb_live & ~deliver
        if purge is not None:
            keep = keep & ~purge
        if sc.commutative_inbox:
            mb_rel = jnp.where(keep, st.mb_rel - shift32, _I32MAX)
            mb_src = st.mb_src          # stale in holes; validity is the
            mb_payload = st.mb_payload  # rel sentinel, never these
            #: holes[k // 32, i] bit k % 32 = row k of node i is free:
            #: 4 bytes a node (K <= 32), read on the node's own lanes
            #: after the routing switch (`_route_adaptive` `filled`;
            #: a fleet's: a routing-switch operand, and TPU
            #: conditionals move their operands)
            holes = free_bits(keep)
            counts = None
        else:
            with jax.named_scope("compact"):
                ops2 = jax.lax.sort(
                    (~keep, slots, st.mb_rel, st.mb_src) + tuple(
                        st.mb_payload[:, p, :] for p in range(P)),
                    dimension=0, num_keys=2)
                kept = ~ops2[0]
                counts = kept.sum(axis=0, dtype=jnp.int32)      # [N]
            mb_rel = jnp.where(kept, ops2[2] - shift32, _I32MAX)
            mb_src = ops2[3]
            mb_payload = jnp.stack(ops2[4:4 + P], axis=1)
            holes = None

        stage("tw.route")
        # 6. route outboxes — two regimes (``_adaptive_regime``).
        #    Adaptive sender-compacted routing (class docstring) never
        #    materializes the S = N·max_out flattened arrays; the eager
        #    path below flattens slot-major (the (window offset,
        #    sender-major rank) keys fix arrival order later, so the
        #    flatten order is free — no transpose of the [M, N] outbox).
        #    Each message is stamped with its sender's firing instant
        #    (== t for W == 1), which keys the link entropy.
        adaptive = self._adaptive_regime()
        #: what this superstep's routing adds to the drivers' counts
        #: (``_count_route``): the rung in senders, the senders it was
        #: chosen for, the rung's index. Routing without a choice of
        #: rung counts its full width; ``_route_adaptive`` puts its
        #: own where it takes one of several
        self._routed = (jnp.int32(n_glob), jnp.int32(n_glob), jnp.int32(0))
        #: a fleet's world's own senders, before the ``pmax`` that
        #: picks the rung for all of them (None solo)
        self._own_senders = None if self.batch is None \
            else jnp.int32(n_glob)
        #: and whether the arrivals were staged in the dense form, and
        #: its tail went wide (and, from there on, its lanes, its
        #: tail's width and its rows: ``_tail_counts``): the
        #: staged insertion puts its own (``_insert_sorted``; on the
        #: ladder ``_route_adaptive``)
        self._staged = (jnp.int32(0),) * (
            5 if self._stages_by_rank() else 2)
        #: and the most arrivals to one destination, where insertion
        #: ranks them for an ordered inbox (``_take_fan_in``)
        self._fan_in = None
        #: and the lanes it handed to its scatters, where it may cut
        #: them (``_cuts_scatters``)
        self._scattered = None
        #: and what the schedule did, where there is one: the messages
        #: it cut, dropped at a down node and purged at a reboot, those
        #: whose delay a window changed, the reboots consumed
        #: (``FaultCounts``)
        self._fault_step = None
        if adaptive:
            res = self._route_adaptive(
                out, out_valid, now_vec, t, mb_rel, mb_src,
                mb_payload, holes, counts, node_ids, with_trace)
            if rec_full:
                # the routing tail's send-event buffer rode the
                # return (it crosses a lax.switch boundary) — merge
                # it into this superstep's capture order
                self._rec_extra.append(res[-1])
                res = res[:-1]
            spec_strag = None
            if self.speculate != "off":
                # the causality plane's straggler min rode the switch
                # return the same way
                spec_strag = res[-1]
                res = res[:-1]
            (mb_rel, mb_src, mb_payload, overflow_step, bad_dst_step,
             bad_delay_step, short_step, route_drop_step, sent_count,
             sent_hash) = res[:10]
            # the faulted routing variant appends its fault counts
            # (partition cuts, down-window deliveries, delays a window
            # changed); the unfaulted tail returns the bare 10-tuple
            fault_route = jnp.int32(0)
            if self._faulted:
                cut, down, degraded = res[10]
                fault_route = cut + down
                self._fault_step = FaultCounts(
                    cut, down, fault_purged, degraded, fault_restarts)
            stage("tw.finish")
            return self._finish_superstep(
                st, live, states, wake, mb_rel, mb_src, mb_payload,
                deliver, fire, node_ids, t, base, now_vec,
                overflow_step, bad_dst_step, bad_delay_step, short_step,
                route_drop_step, sent_count, sent_hash, with_trace,
                fault_dropped_step=fault_purged + fault_route,
                restart_done=restart_done, spec_strag=spec_strag)
        S = n * M
        src_f = jnp.tile(node_ids, M)
        slot_f = jnp.repeat(jnp.arange(M, dtype=jnp.int32), n)
        tmsg = jnp.tile(now_vec, M)                             # int64[S]
        dst_f = out.dst.reshape(S).astype(jnp.int32)
        pay_cols = tuple(out.payload[:, p, :].reshape(S) for p in range(P))
        v_f = out_valid.reshape(S)
        dst_ok = (dst_f >= 0) & (dst_f < n_glob)
        # contract #6 corollary: a scenario emitting an out-of-range
        # destination is a bug — surfaced, never silently dropped
        bad_dst_step = comm.all_sum(
            jnp.sum(v_f & ~dst_ok, dtype=jnp.int32))
        # in-window send offset: deliver-times stay epoch(t)-relative
        woff = (tmsg - t).astype(jnp.int32)                     # [0, W)
        # global sender-major rank — contract #3's arrival order as a
        # sortable value (init guards n_glob * M < 2^31)
        smrank = src_f * jnp.int32(M) + slot_f

        #: routed messages the fault schedule killed this superstep
        fault_eager = jnp.int32(0)
        mbits = msg_bits(self.s0, self.s1, src_f, dst_f, tmsg,
                         slot_f) if self.link.needs_key else None
        delay, drop = self.link.sample(src_f, dst_f, tmsg, mbits)
        ok = v_f & ~drop & dst_ok
        if self._faulted:
            # partition cuts (send-time) before the flight clamp;
            # down-window drops (deliver-time) after — the same check
            # order as the oracle's routing loop. The lanes are the
            # node lanes M times over: a source's side of a table is
            # the table in place, the destination's packed word the
            # one look-up (``_fault_reads``: always early here)
            from ...faults.apply import (cut_mask_at, dst_words,
                                         link_aff_bits, src_link_bits)
            ft = self._ft
            aff_rows = self._fault_reads()[0]
            with jax.named_scope("fault"):
                at_dst = dst_words(ft, dst_f)               # [R, S]
                cutm = ok & cut_mask_at(ft, node_ids, at_dst, tmsg)
                fault_cut = jnp.sum(cutm, dtype=jnp.int32)
                aff = link_aff_bits(
                    ft, src_link_bits(ft, node_ids, now_vec, aff_rows),
                    at_dst[0], aff_rows) if aff_rows else None
            self._rec_cut(rec_full, cutm, src_f, dst_f, tmsg)
            ok = ok & ~cutm
            delay, degraded = self._degrade(delay, src_f, dst_f, tmsg,
                                            ok, aff)
        flight = jnp.maximum(delay, jnp.int64(1))  # contract #4
        drel64 = woff.astype(jnp.int64) + flight
        bad_delay_step = comm.all_sum(jnp.sum(
            ok & (drel64 > jnp.int64(_I32MAX - 1)), dtype=jnp.int32))
        # windowed-causality violation: a delay shorter than the window
        # means this message should have been visible to a node that
        # already fired in this very window — counted, never silent
        # (against the effective window, see _sample_nodrop)
        short_step = comm.all_sum(jnp.sum(
            ok & (flight < self._w_now), dtype=jnp.int32)) \
            if W > 1 else jnp.int32(0)
        # the causality plane's straggler column — same set as
        # short_step (post-cut, pre-down: a down-dropped straggler never
        # lands, but the detector stays conservative and flags the send
        # anyway, docs/speculation.md)
        spec_strag = None
        if self.speculate != "off" and W > 1:
            spec_strag = comm.all_min(jnp.min(jnp.where(
                ok & (flight < self._w_now), tmsg + flight,
                jnp.int64(NEVER))))
        drel = jnp.minimum(drel64,
                           jnp.int64(_I32MAX - 1)).astype(jnp.int32)
        if self._faulted:
            # deliver-time drop: the destination's NIC is off for the
            # whole down window, so a message landing inside it is lost
            # — before the exchange (it never ships) and before the
            # SENT digest (the oracle never hashes it either)
            from ...faults.apply import down_mask
            with jax.named_scope("fault"):
                downm = ok & down_mask(self._ft, dst_f, t + drel64)
                fault_down = jnp.sum(downm, dtype=jnp.int32)
            if rec_full:
                self._rec_extra.append(self._rec_sends(
                    ok, downm, src_f, dst_f, tmsg, tmsg + flight))
            cut, down, degraded = (comm.all_sum(x) for x in (
                fault_cut, fault_down, degraded))
            fault_eager = cut + down
            self._fault_step = FaultCounts(
                cut, down, fault_purged, degraded, fault_restarts)
            ok = ok & ~downm
        elif rec_full:
            self._rec_extra.append(self._rec_sends(
                ok, None, src_f, dst_f, tmsg, tmsg + flight))

        # 6.5. hand each message to the device that owns its destination
        # (identity single-chip; bucket + all_to_all sharded) — rows
        # come back device-local
        (ok_r, drel_r, src_r, row_r, smrank_r, woff_r, pay_r,
         bucket_ovf) = self._exchange(
            ok, drel, src_f, dst_f, smrank, woff, pay_cols)

        # 7. insert: ONE variadic sort by (destination, send instant,
        #    sender-major rank) — chronological routing order, contract
        #    #3 (for W == 1 all offsets are 0 and the key is elided);
        #    values ride along, replacing the argsort + gather chain.
        #    Sort operands are pruned to the minimum: validity is
        #    derived from the destination sentinel (sd < n ⇔ ok) and
        #    the sender from the rank key (src = smrank // M).
        sort_dst = jnp.where(ok_r, row_r, n)  # invalid -> row n
        with jax.named_scope("sort"):
            if W > 1:
                ops3 = jax.lax.sort(
                    (sort_dst, woff_r, smrank_r, drel_r) + pay_r,
                    dimension=0, num_keys=3)
                ops3 = ops3[:1] + ops3[2:]  # drop woff; layout below
            else:
                ops3 = jax.lax.sort(
                    (sort_dst, smrank_r, drel_r) + pay_r,
                    dimension=0, num_keys=2)
        sd, drel_s = ops3[0], ops3[2]
        ok_s = sd < n
        src_s = ops3[1] // jnp.int32(M)   # smrank = src * M + slot
        pay_s = ops3[3:]
        mb_rel, mb_src, mb_payload, overflow_local = self._take_fan_in(
            self._insert_sorted(
                mb_rel, mb_src, mb_payload, sd, ok_s, drel_s, src_s,
                pay_s, holes, counts))
        overflow_step = comm.all_sum(overflow_local) + bucket_ovf

        sent_count = sent_hash = None
        if with_trace:
            dt_abs = t + drel64  # send instant + flight time
            sent_mix = mix32_jnp(SENT, src_f, dst_f, _tlo(dt_abs),
                                 _thi(dt_abs), pay_cols[0])
            sent_hash = comm.all_sum(_u32sum(jnp.where(ok, sent_mix, 0)))
            sent_count = comm.all_sum(jnp.sum(ok, dtype=jnp.int32))
        stage("tw.finish")
        return self._finish_superstep(
            st, live, states, wake, mb_rel, mb_src, mb_payload,
            deliver, fire, node_ids, t, base, now_vec,
            overflow_step, bad_dst_step, bad_delay_step, short_step,
            jnp.int32(0), sent_count, sent_hash, with_trace,
            fault_dropped_step=fault_purged + fault_eager,
            restart_done=restart_done, spec_strag=spec_strag)

    def _finish_superstep(self, st, live, states, wake, mb_rel, mb_src,
                          mb_payload, deliver, fire, node_ids, t, base,
                          now_vec, overflow_step, bad_dst_step,
                          bad_delay_step, short_step, route_drop_step,
                          sent_count, sent_hash, with_trace,
                          fault_dropped_step=None, restart_done=None,
                          spec_strag=None):
        """Assemble the post-superstep state and (optionally) the trace
        row — shared by both routing regimes. ``sent_count`` /
        ``sent_hash`` are computed by the caller (their inputs live at
        regime-specific widths) and may be None when tracing is off."""
        sc, comm = self.scenario, self.comm
        K, n = sc.mailbox_cap, comm.n_local
        recv_count = comm.all_sum(jnp.sum(deliver, dtype=jnp.int32))
        ev_time, ev_meta, ev_count = st.ev_time, st.ev_meta, st.ev_count
        if self.record_events:
            if type(comm) is not LocalComm:
                raise ValueError(
                    "record_events is single-chip only (the ring is "
                    "an unsharded debug artifact)")
            # append per-event records: fires (ascending node id),
            # then deliveries (node-major, slot order) — each ring
            # slot is written at most once over the whole run, and
            # events past capacity are dropped while ev_count keeps
            # counting (the overflow evidence)
            E = self.record_events
            KN = K * n
            # ring write positions are int32 (capacity E bounds every
            # live slot); the int64 running count is clamped to E first
            # so a >2^31-event run cannot wrap the index arithmetic —
            # at ev_count >= E every write drops anyway
            base_i = jnp.minimum(ev_count, jnp.int64(E)).astype(jnp.int32)
            f32 = fire.astype(jnp.int32)
            pos_f = base_i + jnp.cumsum(f32, dtype=jnp.int32) - f32
            idx_f = jnp.where(fire, pos_f, jnp.int32(E))
            nf = jnp.sum(f32, dtype=jnp.int32)
            ev_time = ev_time.at[idx_f].set(now_vec, mode="drop")
            ev_meta = ev_meta.at[0, idx_f].set(1, mode="drop")
            ev_meta = ev_meta.at[1, idx_f].set(node_ids, mode="drop")
            dvT = deliver.T.reshape(KN)                  # node-major
            d32 = dvT.astype(jnp.int32)
            pos_r = base_i + nf + jnp.cumsum(d32, dtype=jnp.int32) - d32
            idx_r = jnp.where(dvT, pos_r, jnp.int32(E))
            dtime = (base + st.mb_rel.astype(jnp.int64)).T.reshape(KN)
            src_r = (st.mb_src if sc.inbox_src
                     else jnp.zeros_like(st.mb_src)).T.reshape(KN)
            ev_time = ev_time.at[idx_r].set(dtime, mode="drop")
            ev_meta = ev_meta.at[0, idx_r].set(2, mode="drop")
            ev_meta = ev_meta.at[1, idx_r].set(
                jnp.repeat(node_ids, K), mode="drop")
            ev_meta = ev_meta.at[2, idx_r].set(src_r, mode="drop")
            ev_meta = ev_meta.at[3, idx_r].set(
                st.mb_payload[:, 0, :].T.reshape(KN), mode="drop")
            ev_count = ev_count + nf + jnp.sum(d32, dtype=jnp.int32)
        new_st = EngineState(
            states=states, wake=wake,
            mb_rel=mb_rel, mb_src=mb_src, mb_payload=mb_payload,
            overflow=st.overflow + overflow_step,
            bad_dst=st.bad_dst + bad_dst_step,
            bad_delay=st.bad_delay + bad_delay_step,
            short_delay=st.short_delay + short_step,
            route_drop=st.route_drop + route_drop_step,
            delivered=st.delivered + recv_count.astype(jnp.int64),
            steps=st.steps + 1,
            time=t,
            ev_time=ev_time, ev_meta=ev_meta, ev_count=ev_count,
            fault_dropped=st.fault_dropped + (
                jnp.int32(0) if fault_dropped_step is None
                else fault_dropped_step),
            restart_done=st.restart_done if restart_done is None
            else restart_done,
        )
        # freeze everything once quiesced (or, a fleet's world in the
        # quiet loop, out of budget). `live` None: the caller's loop
        # has decided on this state's horizon that the superstep
        # runs, and selects nothing
        final = new_st if live is None else jax.tree.map(
            lambda a, b: jnp.where(live, b, a), st, new_st)
        if not with_trace:
            return final, None

        # 8. trace digests (order-independent — trace/hashing.py);
        # computed from the pre-sort deliver mask: the uint32 sum is
        # commutative, so this equals the sorted-inbox digest (and makes
        # the cross-device psum exact)
        fired_hash = comm.all_sum(
            _u32sum(jnp.where(fire, mix32_jnp(FIRED, node_ids), 0)))
        d_abs = base + jnp.where(deliver, st.mb_rel, 0).astype(jnp.int64)
        recv_mix = mix32_jnp(
            RECV, jnp.broadcast_to(node_ids[None, :], (K, n)),
            st.mb_src if sc.inbox_src else jnp.zeros_like(st.mb_src),
            _tlo(d_abs), _thi(d_abs),
            st.mb_payload[:, 0, :])
        recv_hash = comm.all_sum(_u32sum(jnp.where(deliver, recv_mix, 0)))

        telem = None
        if self.telemetry != "off":
            telem = self._telemetry_row(wake, mb_rel, t,
                                        route_drop_step,
                                        fault_dropped_step)
        rec = None
        if self.record != "off" and with_trace:
            # the flight-recorder event plane (obs/flight.py):
            # deliveries first (node-major, slot order — mirroring
            # the device event ring), then the capture sites'
            # compacted buffers in superstep order (defer, restart,
            # purge, cuts, sends). Derived only from values this
            # superstep already computed, so the emulation is
            # untouched — the record exactness law
            # (tests/test_zzzzzflight.py)
            from ...obs import flight as _flight
            d_src = (st.mb_src if sc.inbox_src
                     else jnp.zeros_like(st.mb_src)).T
            d_dst = jnp.broadcast_to(node_ids[:, None], (n, K))
            if self.record == "deliveries":
                # slim fast path: no fault/send captures to merge
                # (_rec_extra only fills in full mode), so the row is
                # one compaction with the constant planes elided
                rec = _flight.record_deliveries(
                    self.record_cap, deliver.T, d_src, d_dst,
                    st.mb_rel.T, t_off=base)
            else:
                row = _flight.record_masked(
                    _flight.empty_row(self.record_cap),
                    _flight.EV_DELIVER, deliver.T, d_src, d_dst,
                    jnp.int64(-1), st.mb_rel.T, 0, t_off=base)
                for comp in self._rec_extra:
                    row = _flight.record_compacted(row, comp)
                rec = row
        integ = None
        if self.verify != "off":
            # the guard invariant plane (integrity/checks.py):
            # violation counts over values this superstep already
            # computed — all-zero on any legitimate superstep, so the
            # checks cannot perturb the emulation (decoded host-side
            # by _capture_integrity; mode "off" carries None, keeping
            # the jaxpr byte-identical to the pre-knob engine)
            from ...integrity.checks import make_guard_row
            integ = make_guard_row(
                comm, t, st.time,
                (new_st.overflow, new_st.bad_dst, new_st.bad_delay,
                 new_st.short_delay, new_st.route_drop,
                 new_st.fault_dropped, new_st.delivered, new_st.steps,
                 new_st.time, new_st.ev_count),
                wake, jnp.int64(NEVER), (mb_rel,),
                st.restart_done, new_st.restart_done, self._faulted)
        spec = None
        if self.speculate != "off":
            # the causality-violation plane (speculate/plane.py):
            # violations ARE the short_delay step delta — the one
            # condition the windowed-exactness argument needs — plus
            # the committed horizon and the earliest offending
            # delivery time for the pinned diagnostic. Derived only
            # from values this superstep already computed, so the
            # emulation is untouched (the speculation off ≡ on
            # jaxpr/exactness law, tests/test_zzzzzzspec.py)
            from ...speculate.plane import SpecRow
            spec = SpecRow(
                violations=short_step,
                horizon=t + jnp.asarray(self._w_now, jnp.int64),
                straggler=(jnp.int64(NEVER) if spec_strag is None
                           else spec_strag),
            )
        yrow = _StepOut(
            valid=live, t=t,
            fired_count=comm.all_sum(jnp.sum(fire, dtype=jnp.int32)),
            fired_hash=fired_hash,
            recv_count=recv_count, recv_hash=recv_hash,
            sent_count=sent_count, sent_hash=sent_hash,
            overflow=overflow_step,
            telem=telem,
            integ=integ,
            rec=rec,
            spec=spec,
        )
        # mask the trace row too when not live
        yrow = jax.tree.map(
            lambda x: jnp.where(live, x, jnp.zeros_like(x)), yrow)
        return final, yrow

    def _telemetry_row(self, wake, mb_rel, t, route_drop_step,
                       fault_dropped_step):
        """The per-superstep telemetry counter plane (obs/telemetry.py)
        — derived ONLY from values this superstep already computed
        (post-step wake, post-insertion mailbox, the step's drop
        deltas, the routing side channels), so it cannot perturb the
        emulation: digests are bit-identical with telemetry on or off
        (tests/test_zztelemetry.py)."""
        from ...obs.telemetry import TelemetryRow
        comm = self.comm
        mmin = mb_rel.min()
        nxt = comm.all_min(jnp.minimum(
            wake.min(),
            jnp.where(mmin == _I32MAX, jnp.int64(NEVER),
                      t + mmin.astype(jnp.int64))))
        row = TelemetryRow(
            active_senders=self._t_senders,
            rung=self._t_rung,
            route_drop=route_drop_step,
            fault_dropped=(jnp.int32(0) if fault_dropped_step is None
                           else fault_dropped_step),
            qslack_us=jnp.where(nxt >= NEVER, jnp.int64(-1), nxt - t),
        )
        if self.telemetry == "full":
            # the mailbox occupancy plane: one extra [K, N] pass —
            # "full" mode's documented cost
            fill_node = jnp.sum(mb_rel < _I32MAX, axis=0,
                                dtype=jnp.int32)                # [N]
            row = row._replace(
                mb_fill=comm.all_sum(jnp.sum(fill_node,
                                             dtype=jnp.int32)),
                mb_peak=comm.all_max(fill_node.max()))
        return row

    # -- the world axis (batch=BatchSpec) --------------------------------

    def _each_world(self, f, ctx, *args):
        """``vmap`` of ``f`` over the leading world axis of ``args``,
        with each world's context ``ctx`` (``_world_context``: seed
        words, link parameters, fault tables) bound onto ``self`` for
        the single trace vmap performs — the traced values ARE the
        per-world tracers, so the compiled program maps them, and
        ``f`` reads ``self.s0``, ``self.link``, ``self._ft`` as a
        solo engine does. The ``vmap`` names its axis so that
        ``_route_adaptive`` can take one rung for all the worlds."""
        s0v, s1v, lpv, ftv = ctx

        def world(s0, s1, lp, ft, *a):
            prev = (self.s0, self.s1, self.link, self._ft)
            self.s0, self.s1 = s0, s1
            if lp:
                self.link = rebind_link(self.link, lp)
            if ft is not None:
                self._ft = ft
            try:
                return f(*a)
            finally:
                self.s0, self.s1, self.link, self._ft = prev
        return jax.vmap(
            world, in_axes=(0, 0, 0, None if ftv is None else 0)
            + (0,) * len(args), axis_name=_FLEET_AXIS)(
                s0v, s1v, lpv, ftv, *args)

    def _vstep(self, step, ctx, *args):
        """One superstep of every world: ``step`` (``_superstep`` with
        its ``with_trace`` bound, or the quiet loop's
        ``_superstep_carried``) under ``_each_world`` — ``_superstep``
        itself is unchanged (the whole point: one superstep
        implementation, solo or fleet). A stage's scope entered under
        ``vmap`` reads ``vmap(tw.route)`` in an operation's
        ``op_name`` (docs/observability.md). What routing did in
        every world is left on ``self._routed`` and ``self._staged``
        (int32[B] each, one value B times), ``self._fan_in`` (a
        world's own, or None) and ``self._own_senders`` (int32[B], a
        world's own) for the drivers' counts, as a solo superstep
        leaves its scalars there."""
        def world(*a):
            return step(*a), (self._routed, self._staged, self._fan_in,
                              self._fault_step, self._own_senders)
        out, (self._routed, self._staged, self._fan_in,
              self._fault_step, self._own_senders) = self._each_world(
                  world, ctx, *args)
        return out

    def _identity(self) -> Optional[WorldIdentity]:
        """The fleet's per-world identity operand (batched.py
        ``WorldIdentity``): what the drivers thread through ``jit``
        as traced device arrays. ``None`` solo — the solo jaxpr is
        unchanged (the zero-overhead-off pin)."""
        if self.batch is None:
            return None
        return WorldIdentity(self._s0v, self._s1v, dict(self._lpv),
                             self._ftv)

    def _world_context(self):
        """The fleet's ``(s0v, s1v, lpv, ftv)`` for ``_each_world``,
        from the driver-bound operand (``self._ident_in``), falling
        back to the constructor's host values outside a driver
        (trace-equivalent: the fallback holds the same arrays the
        operand carries). The world-sharded engine slices its
        device's worlds out (sharded.py)."""
        ident = self._ident_in
        if ident is None:
            ident = self._identity()
        return ident.s0v, ident.s1v, ident.lpv, ident.ftv

    def _step_all(self, st, with_trace: bool):
        """One driver step: the solo superstep, or the vmapped fleet."""
        if self.batch is None:
            return self._superstep(st, with_trace)
        return self._vstep(partial(self._superstep, with_trace=with_trace),
                           self._world_context(), st)

    def rebind_identity(self, batch: BatchSpec, faults=None) -> bool:
        """Swap this fleet's per-world identity IN PLACE — new seeds,
        link values, and/or fault schedules — without touching the
        compiled executables. Returns True when the new identity is
        *shape-compatible* (same B, same link-parameter paths/dtypes,
        fault tables absent on both sides or of identical padded
        shape with identical static gates): the jit caches key on
        this instance plus operand shapes, both unchanged, so the
        next run re-invokes the SAME executable with new device
        arrays — the serving layer's zero-recompile admission path
        (serve/worker.py). Returns False when the identity needs a
        different executable (world count, link-parameter structure,
        fault-table shape, or the ``has_skew``/``has_reset``/
        ``n_restarts`` trace gates changed) — the caller rebuilds.

        Raises ``ValueError`` for identities no engine of this shape
        could legally run (a window wider than the new fleet's link
        floor) — the same refusal ``__init__`` makes."""
        if self.batch is None:
            raise ValueError(
                "rebind_identity swaps a fleet's per-world identity; "
                "a solo engine has none (batch=BatchSpec)")
        if not isinstance(batch, BatchSpec):
            raise ValueError(
                f"batch must be a BatchSpec, got {batch!r}")
        if batch.B != self.batch.B:
            return False
        old_lp = self.batch.link_params or {}
        new_lp = batch.link_params or {}
        if set(old_lp) != set(new_lp):
            return False
        if any(np.asarray(new_lp[k]).dtype != np.asarray(old_lp[k]).dtype
               for k in new_lp):
            return False
        from ...faults.schedule import as_fleet
        fleet = None if faults is None else as_fleet(faults, batch.B)
        if (fleet is None) != (self.faults is None):
            return False
        tables = None
        if fleet is not None:
            if (fleet.has_skew, fleet.has_reset, fleet.n_restarts) != \
                    (self._has_skew, self._has_reset,
                     self._n_restarts):
                return False
            tables = fleet.tables(self.scenario.n_nodes)
            if any(np.asarray(getattr(tables, f)).shape
                   != tuple(getattr(self._ftv, f).shape)
                   for f in type(tables)._fields):
                return False
        # window re-validation against the NEW fleet's link floor —
        # the same precondition __init__ enforces, phrased for the
        # rebind venue. Speculating engines validate their
        # conservative floor (the bound is dynamically checked).
        world_links = [batch.world_link(self.link, b)
                       for b in range(batch.B)]
        link_floor = min(lk.min_delay_us for lk in world_links)
        if fleet is not None and self.controller is None \
                and self.speculate == "off":
            link_floor = fleet.min_delay_floor(link_floor)
        floor_ref = (self.spec_floor if self.speculate != "off"
                     else self.window)
        if floor_ref > 1 and floor_ref > link_floor:
            raise ValueError(
                f"rebind_identity: window={floor_ref} µs exceeds the "
                f"new fleet's declared min_delay_us={link_floor} (min "
                "over the batch worlds, fault-degraded where the "
                "engine has no dynamic clamp); windowed supersteps "
                "would reorder causally dependent events — this "
                "identity needs its own bucket (engine.py windowed-"
                "execution precondition)")
        # commit: identity attrs only — shapes/dtypes proved equal
        self.batch = batch
        sw = [seed_words(s) for s in batch.seeds]
        self._s0v = jnp.asarray([a for a, _ in sw], jnp.uint32)
        self._s1v = jnp.asarray([b for _, b in sw], jnp.uint32)
        self._lpv = {k: jnp.asarray(v) for k, v in new_lp.items()}
        self._world_links = world_links
        if fleet is not None:
            from ...analysis import check_faults
            self.fault_lint_report = check_faults(
                fleet, self.scenario, self.lint,
                who=type(self).__name__)
            self.faults = fleet
            self._ftv = type(tables)(*(jnp.asarray(x)
                                       for x in tables))
        return True

    def _counted(self, st):
        """What a driver's loop carries: the state and, beside it, the
        routing stage's counts from zero (:class:`RouteCounts`). A
        fleet's are world-leading like every state leaf, so the
        world-sharded drivers lay them out the same way; solo and
        fleet differ by that alone."""
        lanes = jnp.zeros_like(st.steps)
        n = self.comm.n_local
        bins = len(self._sender_rungs(n)) if self._adaptive_regime() else 1
        return st, RouteCounts(
            lanes, lanes, jnp.zeros(lanes.shape + (bins,), jnp.int32),
            lanes, lanes,
            jnp.zeros(lanes.shape, jnp.int32) if self._ranks_fan_in()
            else None,
            lanes if self._cuts_scatters() else None,
            *((lanes,) * 3 if self._stages_by_rank() else (None,) * 3),
            faults=FaultCounts(*(lanes,) * 5) if self._faulted else None,
            world_sender_lanes=None if self.batch is None else lanes)

    def _count_route(self, counts: RouteCounts, stepped=True,
                     world_stepped=True) -> RouteCounts:
        """``counts`` and what the superstep just traced did in
        routing (``self._routed``: the scalars ``_route_adaptive``
        chose its branch by; a fleet's ``_vstep`` returns them a
        world). ``stepped`` is whether the iteration counts at all (a
        traced bool where the caller's loop runs on past the last
        event); ``world_stepped`` which of a fleet's worlds stepped
        in it (bool[B]: a world that is quiet or out of budget rides
        the others' iterations and sends nothing of its own)."""
        rung, senders, idx = self._routed
        dense, wide, *tail = self._staged
        bins = counts.rung_steps.shape[-1]
        one = (idx[..., None] == jnp.arange(bins, dtype=jnp.int32)
               ) & stepped
        return RouteCounts(
            counts.rung_lanes + jnp.where(stepped, rung, 0),
            counts.sender_lanes + jnp.where(stepped, senders, 0),
            counts.rung_steps + one.astype(jnp.int32),
            counts.dense_stage_steps + jnp.where(stepped, dense, 0),
            counts.wide_tail_steps + jnp.where(stepped, wide, 0),
            None if counts.fan_in_peak is None else jnp.maximum(
                counts.fan_in_peak, jnp.where(stepped, self._fan_in, 0)),
            None if counts.scatter_lanes is None else
            counts.scatter_lanes + jnp.where(stepped, self._scattered, 0),
            *(c + jnp.where(stepped, x, 0) for c, x in zip(
                (counts.dense_lanes, counts.tail_lanes, counts.net_rows),
                tail)),
            *(None,) * (3 - len(tail)),
            faults=None if counts.faults is None else FaultCounts(*(
                c + jnp.where(stepped, x, 0)
                for c, x in zip(counts.faults, self._fault_step))),
            world_sender_lanes=None if counts.world_sender_lanes is None
            else counts.world_sender_lanes + jnp.where(
                stepped & world_stepped, self._own_senders, 0))

    def _step_counted(self, carry, with_trace: bool):
        """``_step_all`` on a driver loop's ``(state, counts)`` carry.
        An iteration counts where a superstep fired (a fleet: where
        some world's did, as in ``fleet_iterations``): a traced scan
        runs on to its padded length after the last event."""
        st, counts = carry
        new, y = self._step_all(st, with_trace)
        valid = True if y is None else y.valid
        stepped = True if y is None else jnp.any(valid)
        return (new, self._count_route(counts, stepped, valid)), y

    def _horizon_all(self, st) -> Horizon:
        """The horizon of a driver's state: the solo state's, or each
        world's (``t`` int64[B], ``node_next`` int64[B, N]). The one
        scan of the mailbox a quiet run makes for its next event;
        every later horizon is its superstep's product."""
        @jax.named_scope("tw.next_event")
        def horizon(st):
            return self._horizon(st)
        if self.batch is None:
            return horizon(st)
        # the scope is entered under the vmap, like a superstep's
        # stages: a fleet's reads ``vmap(tw.next_event)``
        return self._each_world(horizon, self._world_context(), st)

    def _while_cond_fn(self, start_steps, max_steps):
        """The run_quiet loop condition, on the carry ``(state, ...,
        horizon)``: the carried next event time against NEVER, and
        the step budget. Scalars (a fleet: ``[B]`` vectors): nothing
        of the mailbox's rank is read at the loop's edge. Batched: a
        world is active while it has events pending AND is inside its
        own step budget — both per world, so a finished world never
        runs past where its solo run would stop (the exactness law's
        driver half)."""
        def cond(carry):
            st, _, hz = carry
            active = (hz.t < NEVER) & \
                (st.steps - start_steps < max_steps)
            if self.batch is None:
                return active
            return jnp.any(active)
        return cond

    def _while_body_fn(self, start_steps, max_steps):
        """The run_quiet loop body: ``_superstep_carried`` on the
        carry ``(state, counts, horizon)``. Solo: the condition has
        just found this very ``horizon.t`` pending and the budget
        open, so the superstep runs unconditionally and no leaf is
        selected. Batched: the loop runs while ANY world is active, so
        each world's superstep selects its state once, by live and in
        budget together. Every iteration counts what its routing did
        (a frozen world ran at its device's rung too)."""
        def body(carry):
            st, counts, hz = carry
            if self.batch is None:
                new, hz = self._superstep_carried(st, hz)
                return new, self._count_route(counts), hz
            in_budget = st.steps - start_steps < max_steps      # [B]
            live = (hz.t < NEVER) & in_budget
            new, hz = self._vstep(self._superstep_carried,
                                  self._world_context(), st, hz,
                                  in_budget)
            return new, self._count_route(counts, True, live), hz
        return body

    def _quiet_loop(self, st, max_steps):
        """The quiet driver's ``while``, shared by the local and the
        sharded ``_run_while``: one scan for the state's horizon
        (under ``tw.next_event``, inside the same program), then the
        loop on ``(state, counts, horizon)``. Returns ``(state,
        counts)``; the last horizon is dropped (it is a function of
        the state: ``EngineState`` has no field for it, and the next
        call scans once again)."""
        start_steps = st.steps  # max_steps is per-call, same as run()
        hz = self._horizon_all(st)
        out = jax.lax.while_loop(
            self._while_cond_fn(start_steps, max_steps),
            self._while_body_fn(start_steps, max_steps),
            self._counted(st) + (hz,))
        return out[:2]

    # -- drivers ---------------------------------------------------------

    @partial(jax.jit, static_argnums=(0, 2))
    def _run_scan(self, st: EngineState, n_pad: int, max_steps,
                  dyn=None, ident=None):
        """Traced driver: ``n_pad`` (static) is the pow2-padded scan
        length (common.py ``scan_pad``), ``max_steps`` (traced) the
        real budget — the shared ``padded_scan`` body computes and
        discards the tail, so every budget in a pow2 bucket shares
        one executable. ``dyn`` (traced ``DynDispatch``, or None) is
        the controller's knob operand: bound onto ``self`` for the one
        trace this jit performs, so the scan body reads the traced
        scalars — new knob values re-invoke the SAME executable (the
        no-retrace-in-the-hot-loop contract, controlled.py). ``ident``
        (traced ``WorldIdentity``, or None solo) is the fleet's
        per-world identity operand, bound the same way — admissions
        swap seeds/link values/fault tables without a retrace (the
        serving layer's zero-recompile contract, docs/serving.md).
        Returns ``((state, counts), rows)``."""
        self._dyn = dyn
        self._ident_in = ident
        try:
            return padded_scan(self._step_counted, self._counted(st),
                               n_pad, max_steps)
        finally:
            self._dyn = None
            self._ident_in = None

    def _decode_traces(self, ys) -> list:
        """Per-world trace decode of batched scan output ([T, B]
        leaves): one :class:`SuperstepTrace` per world, each holding
        only the supersteps where that world actually fired."""
        valid = np.asarray(ys.valid)
        cols = [np.asarray(getattr(ys, f)) for f in
                ("t", "fired_count", "fired_hash", "recv_count",
                 "recv_hash", "sent_count", "sent_hash", "overflow")]
        traces = []
        for b in range(self.batch.B):
            m = valid[:, b]
            traces.append(SuperstepTrace.from_rows(
                list(zip(*(c[m, b] for c in cols)))))
        return traces

    def _coerce_budget(self, max_steps):
        """Normalize a step budget for the traced drivers: one int
        (solo, or fleet-wide), or — batched engines only — one budget
        per world (the sweep service's heterogeneous buckets, sweep/).
        Returns ``(traced_budget, top)`` where ``top`` is the host int
        the pow2 scan padding is derived from."""
        if isinstance(max_steps, (int, np.integer)):
            return jnp.asarray(max_steps, jnp.int64), int(max_steps)
        budgets = np.asarray(max_steps)
        if self.batch is None:
            raise ValueError(
                "per-world step budgets need batch=BatchSpec; a solo "
                f"run takes one int budget (got shape {budgets.shape})")
        if budgets.shape != (self.batch.B,) or budgets.dtype.kind not in "iu":
            raise ValueError(
                f"per-world budgets must be one int per world, shape "
                f"[{self.batch.B}]; got shape {budgets.shape} dtype "
                f"{budgets.dtype}")
        if budgets.size and int(budgets.min()) < 0:
            raise ValueError("step budgets must be >= 0")
        top = int(budgets.max()) if budgets.size else 0
        return jnp.asarray(budgets, jnp.int64), top

    def run(self, max_steps,
            state: Optional[EngineState] = None, *,
            _dyn=None) -> Tuple[EngineState, SuperstepTrace]:
        """Execute up to ``max_steps`` supersteps; returns final state
        and the trace of the supersteps that actually fired — batched
        engines return a **list** of per-world traces. Batched engines
        also accept a length-B sequence of per-world budgets: world b
        freezes after its own budget, bit-identical to the solo run
        with that budget (the sweep service's heterogeneous-budget
        buckets — padded_scan in common.py). ``_dyn`` is the
        controller drivers' traced knob operand (controlled.py /
        sweep/runner.py) — passing one requires a bound controller,
        so a stray caller cannot silently run off-spec knob values."""
        if _dyn is not None and self.controller is None \
                and self.speculate == "off":
            raise ValueError(
                "_dyn carries dispatch-controller knob values; build "
                "the engine with controller= (docs/dispatch.md) or "
                "speculate= (docs/speculation.md)")
        # _pad_mult = 2 is the shadow verify mode's pow2-cache twin
        # (integrity/runner.py): still a pow2 (the masked tail keeps
        # results bit-identical), but a DIFFERENT compiled executable
        with self._driver_call("run") as call:
            st = state if state is not None else self.init_state()
            budget, top = self._coerce_budget(max_steps)
            (final, counts), ys = call.dispatch(
                self._run_scan, st, _scan_pad(top) * self._pad_mult,
                budget, _dyn, self._identity())
            ys, = call.wait(st.steps, final.steps, ys, counts=counts)
        self._capture_telemetry(ys)
        self._capture_flight(ys, st)
        self._capture_integrity(ys)
        self._capture_spec(ys)
        if self.batch is not None:
            return final, self._decode_traces(ys)
        m = np.asarray(ys.valid)
        rows = list(zip(
            np.asarray(ys.t)[m], np.asarray(ys.fired_count)[m],
            np.asarray(ys.fired_hash)[m], np.asarray(ys.recv_count)[m],
            np.asarray(ys.recv_hash)[m], np.asarray(ys.sent_count)[m],
            np.asarray(ys.sent_hash)[m], np.asarray(ys.overflow)[m]))
        return final, SuperstepTrace.from_rows(rows)

    @jax.named_scope("tw.next_event")
    def _next_event(self, carry: EngineState) -> jax.Array:
        """This device's next event time as the state alone has it
        (NEVER = quiesced): the probe of a state at rest
        (``world_active``, the benchmarks' quiescence gates). No
        driver's loop asks it: the quiet loop carries each state's
        :class:`Horizon`, whose ``t`` is this value wherever no fault
        schedule defers an event."""
        mmin = carry.mb_rel.min()
        return jnp.minimum(
            carry.wake.min(),
            jnp.where(mmin == _I32MAX, jnp.int64(NEVER),
                      carry.time + mmin.astype(jnp.int64)))

    @partial(jax.jit, static_argnums=(0,))
    def _run_while(self, st: EngineState, max_steps, ident=None):
        """The quiet driver's one program: ``_quiet_loop`` (the scan
        for the first horizon is inside it, no launch of its own).
        ``max_steps`` is traced (a device scalar), so benchmarking
        with different budgets reuses one compiled executable;
        ``ident`` is the fleet identity operand, bound like
        ``_run_scan``'s. Returns ``(state, counts)``."""
        self._ident_in = ident
        try:
            return self._quiet_loop(st, jnp.asarray(max_steps, jnp.int64))
        finally:
            self._ident_in = None

    def run_quiet(self, max_steps,
                  state: Optional[EngineState] = None) -> EngineState:
        """Traceless driver for benchmarking: one ``while_loop``, no
        per-step host materialization and no digest work compiled in
        — telemetry planes included (per-superstep rows need the scan
        driver; ``last_run_stats`` is still populated).
        Accepts per-world budgets like :meth:`run` (batched only)."""
        with self._driver_call("run_quiet") as call:
            st = state if state is not None else self.init_state()
            budget, _ = self._coerce_budget(max_steps)
            final, counts = call.dispatch(
                self._run_while, st, budget, self._identity())
            call.wait(st.steps, final.steps, counts=counts)
            if self.verify != "off":
                # never silently unverified: the quiet driver has no
                # per-superstep rows, so the guard degrades to a
                # final-state host check (integrity/checks.py) — per-
                # superstep localization needs run()/run_verified
                from ...integrity.checks import final_state_guard
                with call.guard():
                    final_state_guard(final, type(self).__name__)
            if self.speculate != "off":
                # never silently mis-speculated: no per-superstep rows
                # here either, so the violation check degrades to the
                # short_delay counter delta (speculate/runner.py)
                with call.guard():
                    self._quiet_spec_guard(st, final)
        return final

    def _capture_telemetry(self, ys) -> None:
        """Host-side decode of one traced run's telemetry rows onto
        ``last_run_telemetry`` (+ a chunk flush to an attached
        metrics registry) — a no-op in off mode."""
        self.last_run_telemetry = None
        if self.telemetry == "off" or ys is None or ys.telem is None:
            return
        from ...obs.telemetry import decode_frames
        B = None if self.batch is None else self.batch.B
        self.last_run_telemetry = decode_frames(
            ys.telem, np.asarray(ys.valid), np.asarray(ys.t), B)
        if self.metrics is not None:
            self.metrics.superstep_chunk(self.metrics_label,
                                         self.last_run_telemetry)

    # -- streaming fleet driver (the sweep service's engine surface) -----

    def world_active(self, state) -> jax.Array:
        """Per-world liveness probe: True while world b still has a
        pending event (batched states; a scalar for solo states) —
        the same condition the quiet driver's while-loop tests, exposed
        so the sweep service (sweep/) can detect quiesced worlds
        between chunks without running a superstep."""
        if self.batch is None:
            return self._next_event(state) < NEVER
        return jax.vmap(self._next_event)(state) < NEVER

    def fleet_progress(self, state, budgets, start=0):
        """Host-side fleet bookkeeping shared by every chunked driver
        (:meth:`run_stream` here; the sweep service's BucketRunner
        drives the same law one chunk at a time): per-world
        ``(steps_done, remaining, active)`` where ``steps_done`` is
        measured from ``start`` (per-world or scalar), ``remaining``
        clips the budgets, and a world is active while it has a
        pending event AND budget left. One implementation, so the
        quiesce/budget law the sweep survival law leans on cannot
        drift between drivers."""
        steps_done = (np.asarray(jax.device_get(state.steps), np.int64)
                      - np.asarray(start, np.int64))
        remaining = np.maximum(np.asarray(budgets, np.int64)
                               - steps_done, 0)
        active = (np.asarray(jax.device_get(self.world_active(state)))
                  & (remaining > 0))
        return steps_done, remaining, active

    def run_stream(self, budgets, state: Optional[EngineState] = None,
                   *, chunk: int = 64, on_chunk=None, on_quiesce=None):
        """Chunked fleet driver with per-world budgets and quiesce
        callbacks. The fleet runs ``chunk`` supersteps at a time, each
        world capped at its own remaining budget; by the batch
        exactness law plus the driver resume contract this is
        bit-identical to one uninterrupted run, and world b's rows are
        bit-identical to its solo run. After every chunk
        ``on_chunk(state, chunk_traces)`` fires; ``on_quiesce(b,
        state)`` fires exactly once per world, the moment it has
        quiesced or exhausted its budget — results stream as worlds
        finish, not at fleet end. Returns ``(final_state,
        per_world_traces)`` like :meth:`run`. (The sweep service's
        BucketRunner needs chunk-level supervision — watchdog,
        checkpoint, retry — between calls, so it drives the same
        :meth:`fleet_progress` law one ``run`` chunk at a time rather
        than through this loop; tests/test_zsweep.py pins the two
        against each other.)"""
        if self.batch is None:
            raise ValueError(
                "run_stream drives a fleet; solo runs use run()")
        if chunk < 1:
            raise ValueError(f"chunk must be >= 1, got {chunk}")
        B = self.batch.B
        budgets = np.broadcast_to(
            np.asarray(budgets, np.int64), (B,)).copy()
        if budgets.size and int(budgets.min()) < 0:
            raise ValueError("step budgets must be >= 0")
        st = state if state is not None else self.init_state()
        start = np.asarray(jax.device_get(st.steps), np.int64)
        rows = [[] for _ in range(B)]
        emitted = np.zeros(B, bool)
        chunk_stats = []
        frame_chunks = []
        flight_chunks = []
        while True:
            _, remaining, active = self.fleet_progress(st, budgets,
                                                       start)
            for b in np.nonzero(~active & ~emitted)[0]:
                emitted[int(b)] = True
                if on_quiesce is not None:
                    on_quiesce(int(b), st)
            if not active.any():
                break
            vec = np.where(active, np.minimum(remaining, chunk), 0)
            st, traces = self.run(vec, state=st)
            chunk_stats.append(self.last_run_stats)
            frame_chunks.append(self.last_run_telemetry)
            flight_chunks.append(self.last_run_flight)
            if on_chunk is not None:
                on_chunk(st, traces)
            for b in range(B):
                rows[b].extend(traces[b].row(i)
                               for i in range(len(traces[b])))
        if self.telemetry != "off":
            # whole-run telemetry on last_run_telemetry, exactly like
            # run_controlled (controlled.py) — a chunked run must not
            # leave only its final chunk's frames behind
            from ...obs.telemetry import concat_frames
            self.last_run_telemetry = concat_frames(frame_chunks)
        if self.record != "off":
            # same whole-run contract for the flight log (superstep
            # indices are already run-global — decode's offset)
            from ...obs.flight import concat_flight
            self.last_run_flight = concat_flight(flight_chunks)
        if chunk_stats:
            # chunk-accurate driver accounting: each run() overwrote
            # last_run_stats, so the chunked run used to report only
            # its FINAL chunk — compiles landing on earlier chunks
            # (the first use of each pow2 scan pad) vanished. The
            # merged record keeps per-chunk compile attribution
            # (common.py _stats_merge).
            self._stats_merge(chunk_stats)
        return st, [SuperstepTrace.from_rows(r) for r in rows]

    def events(self, state: EngineState):
        """Decode the device-side event ring into host tuples —
        ``("fire", time, node)`` and ``("recv", deliver_time, node,
        src, payload0)`` — plus the count of events that did NOT fit
        the ring (0 = the record is complete). The engine-side mirror
        of ``SuperstepOracle(record_events=True).events``; recv ``src``
        is 0 for ``inbox_src=False`` scenarios (the field the whole
        stack elides)."""
        if not self.record_events:
            raise ValueError("engine built with record_events=0")
        ev_time = np.asarray(jax.device_get(state.ev_time))
        ev_meta = np.asarray(jax.device_get(state.ev_meta))
        total = int(state.ev_count)
        filled = min(total, self.record_events)
        out = []
        for j in range(filled):
            kind, node, src, pay = (int(ev_meta[0, j]),
                                    int(ev_meta[1, j]),
                                    int(ev_meta[2, j]),
                                    int(ev_meta[3, j]))
            if kind == 1:
                out.append(("fire", int(ev_time[j]), node))
            else:
                out.append(("recv", int(ev_time[j]), node, src, pay))
        return out, total - filled
