"""Shared machinery of the batched engines: trace-row container and
the device-communication abstraction that lets one superstep
implementation run single-chip or sharded over a mesh
(parallel/mesh.py). The integer primitives live in
:mod:`timewarp_tpu.ops` and are re-exported here for the engines."""

from __future__ import annotations

import time
from contextlib import ExitStack, contextmanager
from functools import wraps
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ...obs.profiler import call, compile_account, listen, phase, span
from ...ops.numeric import I32MAX, group_rank, thi, tlo, u32sum

__all__ = ["LocalComm", "StepOut", "I32MAX", "group_rank", "u32sum",
           "tlo", "thi", "padded_scan", "scan_pad",
           "init_states_wake", "RunStatsMixin", "DynDispatch",
           "STAGES", "Stages"]

#: the superstep's stages, in order, as ``jax.named_scope`` names on
#: the device work of every engine that adopts them (``engine.py``,
#: ``fused_ring.py``; the ring's kernel sits under ``tw.ring_kernel``).
#: A scope is metadata: it adds no equation, and a profile shows it in
#: each operation's ``op_name`` (benchmark/span_reduce.py ``stage_ns``).
#: Nested in ``tw.route`` (``engine.py``): ``sample`` (the adaptive
#: ladder's link draw), ``exchange`` (in the node-sharded general
#: engine's, sharded.py, ``bucket``: the sort by destination shard,
#: the ranks, the scatters into a bucket a shard; and ``swap``: the
#: ``all_to_all``s), ``sort`` (the eager regime's one variadic sort
#: by destination; the adaptive ladder's sorts are the stage's own)
#: and ``insert``; nested in ``tw.deliver``:
#: ``sort`` (an ordered inbox's variadic sort along the mailbox's
#: slots, due time then arrival slot), and in ``tw.rebase``:
#: ``compact`` (an ordered inbox's second one, which closes the gaps
#: of what was delivered and keeps arrival order in slot order, and
#: the kept messages a node): in the programs of scenarios that keep
#: the default ordered inbox and in no other; nested in ``tw.fire``:
#: ``entropy`` (``fire_bits`` over every lane, in the programs of
#: scenarios with ``needs_key`` and in no other; the v5e's compiler
#: fuses it into the step's fusion, so it is in the lowered text and
#: in no profile)
STAGES = ("tw.next_event", "tw.deliver", "tw.fire", "tw.rebase",
          "tw.route", "tw.finish")

#: the counts of a call on an engine built with ``faults`` (engine.py
#: ``FaultCounts``; a fleet's have a ``world_`` list each beside them),
#: and what its masks met and looked up (``JaxEngine._fault_lanes``)
_FAULT_COUNTS = ("fault_cut", "fault_down", "fault_purged",
                 "fault_degraded", "fault_restarts", "fault_table_lanes",
                 "fault_gather_lanes")

# whatever an engine traces, lowers and compiles from here on is in the
# program's record, by name (obs/profiler.py ``phases()``)
listen()


class Stages(ExitStack):
    """Walks one superstep through its stages: ``stage(name)`` leaves
    the scope it was in and enters ``name``; leaving the ``with``
    closes the last. So a long function with early returns is divided
    where its stages change, without an indent."""

    def __call__(self, name: str) -> None:
        self.close()
        self.enter_context(jax.named_scope(name))


class DynDispatch(NamedTuple):
    """The online-dispatch controller's per-chunk knob values
    (dispatch/), threaded into the traced scan drivers as ORDINARY
    TRACED OPERANDS — never compile-time constants — so a controller
    adapting them between chunks re-invokes the same executable with
    new scalars (zero recompiles by construction; the pow2 scan pad
    stays the drivers' only static input).

    ``window`` — requested superstep window width, int64 µs (clamped
    on-device to ``[1, engine.window]`` and, under a fault schedule,
    to the per-superstep degraded link floor — faults/apply.py
    ``window_floor``). ``rung_pin`` — a *floor* on the adaptive
    routing ladder's selected rung index, int32 (-1 = unpinned; the
    effective index is ``max(computed, pin)``, so a pin can only
    select a wider — always result-identical — rung, never drop a
    message)."""
    window: Any     # int64[] requested window µs
    rung_pin: Any   # int32[] ladder index floor, -1 = unpinned


def init_states_wake(scenario):
    """The scenario's stacked initial ``(states, wake)`` — ONE
    implementation shared by every engine's ``init_state`` and the
    fault subsystem's restart-reset template (a divergence here would
    silently split "fresh boot" from "reboot" semantics)."""
    n = scenario.n_nodes
    if scenario.init_batched is not None:
        states, wake = scenario.init_batched(n)
        wake = jnp.asarray(wake, jnp.int64)
    else:
        per = [scenario.init(i) for i in range(n)]
        states = jax.tree.map(
            lambda *xs: jnp.stack([jnp.asarray(x) for x in xs]),
            *[p[0] for p in per])
        wake = jnp.asarray([p[1] for p in per], jnp.int64)
    return states, wake


def scan_pad(max_steps: int) -> int:
    """Scan length for a ``max_steps`` budget: the next power of two.
    The scan length is the ONLY static compile input of the traced
    drivers, so rounding it up to a pow2 bucket (and masking the tail
    supersteps out — :func:`padded_scan`) collapses every budget in a
    bucket onto one executable — ``run(100)`` then ``run(120)`` reuse
    the 128-step program instead of recompiling
    (tests/test_world_batch.py pins the compile count). The masked
    tail still *executes* (its results are discarded), bounding the
    waste at <2x supersteps — cheap next to a 20-40 s TPU compile per
    distinct budget."""
    if max_steps <= 0:
        return 0
    return 1 << (max_steps - 1).bit_length()


def padded_scan(step_all, st, n_pad: int, max_steps):
    """The ONE pow2-padded masked-tail scan body every traced driver
    shares (local, edge, sharded — a single implementation so the
    run/freeze/zero contract cannot drift per driver): iterations at
    index >= ``max_steps`` (traced) compute and discard their
    superstep, freezing the carry and zeroing the trace row
    (valid=False, filtered host-side). ``step_all`` is the engine's
    one-driver-step hook ``(carry, with_trace) -> (carry', yrow)``.

    ``max_steps`` may also be an int64[B] vector of per-world budgets
    (batched engines only — the sweep service's heterogeneous-budget
    buckets, sweep/): world b freezes leaf-wise after its own budget,
    exactly as the quiescence mask freezes it, so a short-budget world
    stays bit-identical to its solo run while sibling worlds keep
    stepping. Trace rows are [B]-leading under the batch, so the same
    mask zeroes only the frozen worlds' rows."""
    per_world = getattr(max_steps, "ndim", 0) == 1

    def body(carry, i):
        new, y = step_all(carry, True)
        run = i < max_steps          # bool[] — or bool[B] per world

        def mask(a, b):
            r = run.reshape(run.shape + (1,) * (b.ndim - 1)) \
                if per_world else run
            return jnp.where(r, b, a)
        carry = jax.tree.map(mask, carry, new)
        # the same per-world trailing-dim broadcast for the trace row:
        # [B]-leading y leaves may carry plane dims beyond B (the
        # telemetry full mode's per-node columns, the flight
        # recorder's [B, R] event plane)
        y = jax.tree.map(
            lambda x: jnp.where(
                run.reshape(run.shape + (1,) * (x.ndim - 1))
                if per_world else run, x, jnp.zeros_like(x)), y)
        return carry, y
    return jax.lax.scan(body, st, jnp.arange(n_pad, dtype=jnp.int64))


class StepOut(NamedTuple):
    """Per-superstep trace row (valid=False once the scenario quiesced).

    ``telem`` is the opt-in telemetry counter plane
    (obs/telemetry.py ``TelemetryRow``) — ``None`` unless the engine
    was built with ``telemetry != "off"``. None is an empty pytree
    node, so the default adds zero scan outputs and zero jaxpr
    equations: the zero-overhead-when-off law holds at the type level.

    ``integ`` is the state-integrity guard plane (integrity/checks.py
    ``IntegrityRow``) — ``None`` unless ``verify != "off"``; the same
    None-default contract, so the verify-off jaxpr is byte-identical
    to the pre-knob engine (tests/test_zzzzintegrity.py).

    ``rec`` is the causal flight recorder's bounded event plane
    (obs/flight.py ``RecordRow``) — ``None`` unless ``record !=
    "off"``; the same None-default contract again
    (tests/test_zzzzzflight.py).

    ``spec`` is the optimistic-execution causality-violation plane
    (speculate/plane.py ``SpecRow``) — ``None`` unless ``speculate !=
    "off"``; the same None-default contract, so the speculate-off
    jaxpr is byte-identical to the pre-knob engine
    (tests/test_zzzzzzspec.py)."""
    valid: jax.Array
    t: jax.Array
    fired_count: jax.Array
    fired_hash: jax.Array
    recv_count: jax.Array
    recv_hash: jax.Array
    sent_count: jax.Array
    sent_hash: jax.Array
    overflow: jax.Array
    telem: Any = None
    integ: Any = None
    rec: Any = None
    spec: Any = None


class LocalComm:
    """Single-device communication: every "collective" is local. The
    sharded engines (sharded.py) substitute mesh collectives (pmin /
    psum / ppermute / all_to_all) behind the same operations, so one
    superstep implementation serves both."""

    def __init__(self, n_global: int) -> None:
        self.n_global = n_global
        self.n_local = n_global
        self.n_shards = 1

    def node_ids(self) -> jax.Array:
        """Global ids of the nodes this device owns."""
        return jnp.arange(self.n_local, dtype=jnp.int32)

    def all_min(self, x: jax.Array) -> jax.Array:
        return x

    def all_sum(self, x: jax.Array) -> jax.Array:
        return x

    def all_max(self, x: jax.Array) -> jax.Array:
        return x

    def roll(self, x: jax.Array, s: int) -> jax.Array:
        """Global roll by ``s`` along the (last) node axis."""
        return jnp.roll(x, s, axis=-1)

    def local_rows(self, table: np.ndarray) -> jax.Array:
        """This device's column block of a host table [..., N]."""
        return jnp.asarray(table)


class _DriverCall:
    """One driver call's boundary with the chip: what it launched, what
    it read back, and the host spans of both (``obs.profiler.span``,
    all carrying the call's ``run`` number). ``record`` is the call's
    record (``obs.profiler.call``): the spans note themselves there,
    :meth:`wait` puts the call's ``last_run_stats`` beside them."""

    def __init__(self, eng, record: dict):
        self.eng, self.record, self.run = eng, record, record["run"]
        self.t0, self.c0 = time.perf_counter(), eng._driver_compiles()
        self.dispatches = self.readbacks = 0

    def dispatch(self, fn, *args):
        """Launch one executable, under ``tw.dispatch``: from entry to
        the jitted driver's return (argument handling and enqueue)."""
        self.dispatches += 1
        with span("tw.dispatch", run=self.run):
            return fn(*args)

    def wait(self, steps_before, steps_after, *more, counts=None,
             crossed=None):
        """The blocking read that ends the device's work, under
        ``tw.wait``: the step counters, the routing stage's counts the
        driver's loop carried beside the state (``counts``: a
        ``(rung_lanes, sender_lanes, rung_steps, dense_stage_steps,
        wide_tail_steps, fan_in_peak, scatter_lanes, dense_lanes,
        tail_lanes, net_rows, remote_msgs, bucket_fill_peak)`` of
        device arrays, ``fan_in_peak`` and
        ``scatter_lanes`` None but from an ordered inbox (the second
        from a solo one on one device), the three of the staging None
        but from an insertion staged by rank, the last two None but
        from the node-sharded general engine (one row a shard), and
        then a ``FaultCounts`` from an engine built with ``faults``,
        None from any other, and last a fleet's ``world_sender_lanes``,
        None from a solo engine, ``engine.py``
        ``RouteCounts``; None from an engine with no ladder to count),
        a node-sharded edge engine's boundary messages (``crossed``:
        one count a shard, ``sharded.py`` ``ShardedEdgeEngine``; None
        from every other engine) and whatever else the driver reads
        back (``more``, returned on the host) in one transfer. Sets
        ``last_run_stats``, the record's ``counts``."""
        self.readbacks += 1
        with span("tw.wait", run=self.run):
            before, after, counts, crossed, *more = jax.device_get(
                (steps_before, steps_after, counts, crossed) + more)
        d = np.asarray(after, np.int64) - np.asarray(before, np.int64)
        compile_seconds, cache_misses = compile_account()
        stats = self.record["counts"] = self.eng.last_run_stats = {
            "supersteps": int(d.sum()),
            "wall_seconds": time.perf_counter() - self.t0,
            "compiles": self.eng._driver_compiles() - self.c0,
            "compile_seconds": compile_seconds,
            "cache_misses": cache_misses,
            "dispatches": self.dispatches, "readbacks": self.readbacks,
        }
        if d.ndim:
            # a fleet: the same transfer holds every world's count. The
            # loop steps every world until the last is quiet or out of
            # budget, so its iterations are the largest of them
            stats.update(world_supersteps=d.tolist(),
                         fleet_iterations=int(d.max()))
        if counts is not None:
            (*counts, fan_in, scattered, dense_lanes, tail_lanes, rows,
             remote, fill, faults, own) = counts
            if own is not None:
                # a fleet's: each world's own senders, beside the
                # busiest's (`sender_lanes`) that picked the rungs
                stats["world_sender_lanes"] = own.tolist()
            if faults is not None:
                # a world's own counts (a fleet: a list beside each
                # sum, as `world_supersteps` is beside `supersteps`)
                for name, x in zip(faults._fields, faults):
                    stats["fault_" + name] = int(np.sum(x))
                    if d.ndim:
                        stats["world_fault_" + name] = x.tolist()
                stats["fault_table_lanes"], stats["fault_gather_lanes"] = \
                    self.eng._fault_lanes(
                        int(np.max(counts[2].sum(axis=-1))),
                        int(np.max(counts[0])))
            if d.ndim:
                # one rung for all the worlds of a superstep: every
                # world counted the same (a world-sharded fleet: the
                # counts of the device whose rungs sum widest)
                b = int(np.argmax(counts[0]))
                local = getattr(self.eng, "worlds_local", None)
                if local is not None:
                    # a rung and a loop a device: each counts the
                    # iterations it ran, until its own last world was
                    # quiet or out of budget (the devices meet at the
                    # readback and nowhere before). The worlds of a
                    # device count the same but under the scan
                    # driver's per-world budgets, where a world stops
                    # counting at its own: a device's sums are the
                    # rows of its widest world, in the same transfer;
                    # its iterations are that ``rung_steps`` row
                    # summed (one bin where routing has no ladder)
                    rows = counts[0].reshape(-1, local).argmax(axis=1) \
                        + np.arange(0, len(d), local)
                    stats.update(
                        shards=len(d) // local, worlds_local=local,
                        device_rung_lanes=counts[0][rows].tolist(),
                        device_sender_lanes=counts[1][rows].tolist(),
                        device_iterations=counts[2][rows].sum(
                            axis=-1).tolist())
                counts = [c[b] for c in counts]
            lanes, senders, by_rung, dense, wide = counts
            stats.update(rung_lanes=int(lanes), sender_lanes=int(senders),
                         rung_steps=by_rung.tolist(),
                         dense_stage_steps=int(dense),
                         wide_tail_steps=int(wide))
            if self.eng._adaptive_regime():
                # the ladder's top rung is the node axis itself and
                # reads the outbox where it lies (`_route_adaptive`
                # `gather`, scope `inplace`): the last bin by its own
                # name, no count of its own in the loop's carry
                stats.update(inplace_rung_steps=int(by_rung[-1]))
            if fan_in is not None:
                # a fleet's: its worlds' largest
                stats.update(fan_in_peak=int(np.max(fan_in)))
            if scattered is not None:
                stats.update(scatter_lanes=int(scattered))
            if dense_lanes is not None:
                stats.update(dense_lanes=int(dense_lanes),
                             tail_lanes=int(tail_lanes),
                             net_rows=int(rows))
            if remote is not None:
                # counted on each shard beside its state: summed, and
                # the fullest bucket of any shard, here
                cap = self.eng.bucket_cap
                stats.update(shards=len(remote),
                             remote_msgs=int(remote.sum()),
                             bucket_fill_peak=int(fill.max()),
                             bucket_cap=cap,
                             exchange_lanes=len(remote) * cap)
        if crossed is not None:
            # counted on each shard beside its state, summed here
            stats.update(shards=len(crossed),
                         boundary_msgs=int(crossed.sum()))
        return more

    def guard(self):
        """``tw.guard`` around a verify or speculation guard that
        runs: one more readback, whatever it reads."""
        self.readbacks += 1
        self.eng.last_run_stats["readbacks"] = self.readbacks
        return span("tw.guard", run=self.run)


class RunStatsMixin:
    """Uniform host-side driver accounting for every engine: after any
    ``run``/``run_quiet``, ``engine.last_run_stats`` holds::

        {"supersteps": int,    # executed this call (fleet total)
         "wall_seconds": float,
         "compiles": int,      # driver executables compiled this call
         "compile_seconds": float,  # the call's seconds tracing,
                               # lowering and compiling (or fetching
                               # from the persistent cache) whatever
                               # it compiled, the driver's own and
                               # the small programs around it; 0.0
                               # in a call that compiled nothing
         "cache_misses": int,  # of those backend compiles, the ones
                               # the persistent cache did not have
                               # and now keeps (obs/profiler.py
                               # ``phases()`` names them)
         "dispatches": int,    # executables launched by the call
         "readbacks": int}     # blocking host reads by the call

    for a general engine (``JaxEngine`` and its sharded twins), solo
    or fleet, the routing stage's counts, summed over the iterations
    of the driver's loop::

        {"rung_lanes": int,    # the rung taken, in senders
         "sender_lanes": int,  # the active senders the rung was chosen
                               # for (a fleet: its busiest world's)
         "rung_steps": [int] * R,  # iterations by rung index
         "dense_stage_steps": int,  # iterations that staged their
                                    # arrivals in the dense form
         "wide_tail_steps": int}    # of those, with a full-width tail

    for one whose routing takes the ladder (``_adaptive_regime``)::

        {"inplace_rung_steps": int}  # iterations routed on the top
                                     # rung, whose width is the node
                                     # axis: the outbox read in place,
                                     # no sender gather (PR 56). It is
                                     # ``rung_steps[-1]``, and every
                                     # iteration where the ladder has
                                     # one rung (n_nodes <= 1024)

    for a general engine whose scenario keeps the default ordered
    inbox (``commutative_inbox=False``: insertion ranks the arrivals
    of every destination to append them in arrival order)::

        {"fan_in_peak": int}   # the most arrivals to one destination
                               # in one superstep of the call, kept
                               # and dropped alike (a fleet: of any
                               # world)

    for such an engine that is solo and on one device (its insertion
    may scatter a prefix of its lanes, engine.py ``_scatter_widths``)::

        {"scatter_lanes": int}  # the lanes handed to each of the
                                # insertion's scatters, summed over
                                # the iterations: the width taken (the
                                # smallest that holds the last valid
                                # lane of rank under K; the gather of
                                # the destinations' kept counts runs
                                # at it too, PR 52), the call's lanes
                                # where one scatter ran

    for a solo general engine with a commutative inbox (its
    insertion stages the arrivals by rank, engine.py
    ``_stage_by_rank``)::

        {"dense_lanes": int,  # the message lanes of the iterations
                              # staged in the dense form
         "tail_lanes": int,   # the width their tails' scatters took
         "net_rows": int}     # the rows they sent through the network

    for the world-sharded fleet (``ShardedBatchedEngine``: a rung and
    a loop a device, no collective; a device counts the iterations it
    ran, and stops counting when its own last world does)::

        {"shards": int,         # the mesh axis' size
         "worlds_local": int,   # worlds a device
         "device_rung_lanes": [int] * shards,    # each device's own
         "device_sender_lanes": [int] * shards,  # sums of the two
                                # above: ``rung_lanes`` is the widest
                                # device's, max(device_rung_lanes)
         "device_iterations": [int] * shards}    # the trips of each
                                # device's loop (its ``rung_steps``
                                # summed); all equal where the devices
                                # ran in step, their largest is
                                # ``fleet_iterations`` in a quiet run

    for the node-sharded edge engine (``ShardedEdgeEngine``)::

        {"shards": int,         # the mesh axis' size
         "boundary_msgs": int}  # messages delivered by the call whose
                                # sender lives on another shard (the
                                # dense ring: one a shard a superstep)

    for the node-sharded general engine (``ShardedEngine``: every
    message handed to its destination's shard by ``all_to_all``; a
    device counts what it hands over, no collective)::

        {"shards": int,            # the mesh axis' size
         "remote_msgs": int,       # valid messages of the call whose
                                   # destination's shard is not the
                                   # sender's
         "bucket_fill_peak": int,  # the most messages one (source
                                   # shard, destination shard) bucket
                                   # was asked to hold in one superstep
                                   # of the call, before the cut at
                                   # ``bucket_cap``: over it, by how
                                   # much the capacity was short
         "bucket_cap": int,        # the engine's, lanes a bucket
         "exchange_lanes": int}    # shards * bucket_cap: the lanes a
                                   # device receives, sorts and
                                   # inserts a superstep

    for a general engine built with ``faults`` (engine.py
    ``FaultCounts``; an engine without has none of the keys)::

        {"fault_cut": int,       # sends across a live partition
         "fault_down": int,      # sends due inside the destination's
                                 # down window
         "fault_purged": int,    # mailbox entries a reboot lost: the
                                 # three sum to ``fault_dropped``'s
                                 # growth over the call
         "fault_degraded": int,  # sends whose delay a link window
                                 # changed
         "fault_restarts": int,  # reboots consumed
         "fault_table_lanes": int,  # the lanes a world's masks met a
                                 # table row at, from the tables'
                                 # shapes and the rungs taken
                                 # (``JaxEngine._fault_lanes``)
         "fault_gather_lanes": int}  # and those at which they read a
                                 # table through an index: the
                                 # destination's packed word, once a
                                 # message (PR 54; until then both ends
                                 # of every lane, a row at a time)

    (a fleet's first five are sums over its worlds, with a
    ``world_fault_*`` list of each beside them)

    and, for a fleet (``batch=BatchSpec``) only::

        {"world_supersteps": [int] * B,  # executed by each world
         "fleet_iterations": int,        # the largest of them: what the
                                         # driver's loop ran, each at the
                                         # cost of all B worlds
         "world_sender_lanes": [int] * B}  # each world's OWN active
                                         # senders, summed over the
                                         # iterations it stepped
                                         # (``n_active`` before the
                                         # ``pmax`` that picks one rung
                                         # for all): each is at most
                                         # ``sender_lanes``, the
                                         # busiest's, and their sum over
                                         # B * ``rung_lanes`` is what
                                         # the worlds' own senders fill
                                         # of the rungs the lockstep
                                         # made them all take

    so ``supersteps / (B * fleet_iterations)`` is the share of the
    fleet's work spent on worlds that were still running,
    ``rung_lanes / (iterations * n_nodes)`` the share of the routing
    ladder's full width the call paid (engine.py ``_route_adaptive``;
    a fleet takes one rung for all the worlds of a superstep) and
    ``sender_lanes / rung_lanes`` how full the rungs ran. Where routing
    runs without the ladder both lane counts are ``n_nodes`` an
    iteration and ``rung_steps`` has one bin. The counts are carried
    beside the state in the driver's loop and read in the call's one
    transfer. The chunked drivers' merged record (``_stats_merge``)
    sums them.

    The same dict is the ``counts`` of the call's record
    (``obs.profiler.calls()``), beside the call's spans: every driver
    goes through :meth:`_driver_call`, so the spans and the record of
    docs/observability.md have one implementation.

    Compile counting reads the jitted drivers' ``_cache_size`` (the
    same probe tests/test_world_batch.py pins the pow2 bucketing
    with), so a run that silently retraced is visible in its stats.
    The host half compiles nothing in, and the stats exist in every
    telemetry mode including "off".
    """

    #: the jitted driver attributes whose compile caches count
    _DRIVER_FNS = ("_run_scan", "_run_while")

    last_run_stats = None

    #: the methods that build a run, and the live span around each
    _PHASES = {"__init__": "tw.engine.init", "init_state": "tw.init_state"}

    def __init_subclass__(cls, **kwargs):
        """Every engine class's constructor and ``init_state``, as the
        class resolves them (its own, or a mesh driver's beside it),
        run under their live span (:meth:`_phased`): one place for
        every engine there is and will be, and no line added to
        ``engine.py``, whose source locations some drivers' entries in
        the persistent compile cache are keyed on (the steady cell's
        and the observer ring's compile anew when they move: PERF.md
        section 7)."""
        super().__init_subclass__(**kwargs)
        for method, name in cls._PHASES.items():
            setattr(cls, method, cls._phased(name)(getattr(cls, method)))

    @staticmethod
    def _phased(name: str):
        """Decorator of an engine's constructor (``tw.engine.init``)
        and of its ``init_state`` (``tw.init_state``): the live span
        ``name`` around the method (``obs.profiler.phase``: the
        outermost only, so a subclass that calls its base class's, or
        a fused ring that builds its edge engine, is one span),
        carrying the engine's class and its scenario's ``n_nodes``."""
        def wrap(method):
            @wraps(method)
            def under(self, *args, **kwargs):
                # a constructor's scenario is its first argument
                sc = getattr(self, "scenario", None) or \
                    kwargs.get("scenario") or args[0]
                with phase(name, engine=type(self).__name__,
                           n_nodes=sc.n_nodes):
                    return method(self, *args, **kwargs)
            return under
        return wrap

    def _driver_compiles(self) -> int:
        n = 0
        for name in self._DRIVER_FNS:
            fn = getattr(type(self), name, None)
            cs = getattr(fn, "_cache_size", None)
            if cs is not None:
                n += cs()
        return n

    @contextmanager
    def _driver_call(self, driver: str):
        """One driver call: its record (``obs.profiler.call``, with the
        ``tw.<driver>`` span around the call) holding the engine's
        class, node count and outbox slots a node (what a rung's
        senders are multiplied by to give its lanes); yields the
        call's :class:`_DriverCall`."""
        with call("tw." + driver) as record:
            record.update(engine=type(self).__name__,
                          n_nodes=self.scenario.n_nodes,
                          max_out=self.scenario.max_out)
            yield _DriverCall(self, record)

    def _stats_merge(self, chunks) -> dict:
        """Fold per-chunk ``last_run_stats`` dicts into one run-level
        record for the chunked drivers (``run_stream``,
        ``run_controlled``). Before this existed, each chunk's
        ``run()`` overwrote ``last_run_stats``, so a chunked run
        reported only its FINAL chunk — every earlier chunk's compile
        (where the real compiles happen: the first use of each pow2
        scan pad) was silently lost. ``per_chunk_compiles`` keeps the
        attribution: entry i is the number of driver executables chunk
        i compiled, so "zero recompiles across controller adaptations"
        is testable per chunk, not just in aggregate. The routing
        counts and a fleet's per-world counts are summed where every
        chunk has them (elementwise: a chunked fleet's
        ``fleet_iterations`` is the sum of its chunks' loops; a
        world-sharded fleet's ``device_rung_lanes`` and
        ``device_iterations`` a device, so the merged ``rung_lanes``,
        each chunk's widest device, is at least their largest); the
        two peaks (``fan_in_peak``, ``bucket_fill_peak``) take their
        chunks' largest."""
        self.last_run_stats = {
            "supersteps": sum(c["supersteps"] for c in chunks),
            "wall_seconds": sum(c["wall_seconds"] for c in chunks),
            "compiles": sum(c["compiles"] for c in chunks),
            "compile_seconds": sum(c.get("compile_seconds", 0.0)
                                   for c in chunks),
            "cache_misses": sum(c.get("cache_misses", 0) for c in chunks),
            "dispatches": sum(c.get("dispatches", 0) for c in chunks),
            "readbacks": sum(c.get("readbacks", 0) for c in chunks),
            "chunks": len(chunks),
            "per_chunk_compiles": [c["compiles"] for c in chunks],
        }
        for key in ("shards", "worlds_local", "bucket_cap",
                    "exchange_lanes"):            # the mesh's, kept
            if chunks and all(key in c for c in chunks):
                self.last_run_stats[key] = chunks[0][key]
        for key in ("rung_lanes", "sender_lanes", "fleet_iterations",
                    "rung_steps", "inplace_rung_steps",
                    "world_supersteps", "world_sender_lanes",
                    *_FAULT_COUNTS, *("world_" + k for k in _FAULT_COUNTS[:5]),
                    "dense_stage_steps", "wide_tail_steps",
                    "scatter_lanes", "dense_lanes", "tail_lanes",
                    "net_rows", "boundary_msgs", "remote_msgs",
                    "device_rung_lanes",
                    "device_sender_lanes", "device_iterations"):
            if chunks and all(key in c for c in chunks):
                cols = [c[key] for c in chunks]
                self.last_run_stats[key] = sum(cols) \
                    if not isinstance(cols[0], list) \
                    else [sum(col) for col in zip(*cols)]
        for key in ("fan_in_peak", "bucket_fill_peak"):
            # a largest value, not a sum
            if chunks and all(key in c for c in chunks):
                self.last_run_stats[key] = max(c[key] for c in chunks)
        return self.last_run_stats
