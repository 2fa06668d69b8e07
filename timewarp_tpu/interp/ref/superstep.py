"""Host reference executor for state-machine scenarios — the oracle.

Runs a :class:`~timewarp_tpu.core.scenario.Scenario` sequentially on the
host, implementing the shared superstep semantics (core/scenario.py
docstring) with plain Python data structures: per-node mailbox *lists*,
a Python min-scan for the clock, Python loops for routing and overflow.
This is the direct descendant of the reference's event loop
(`/root/reference/src/Control/TimeWarp/Timed/TimedT.hs:234-286`): a
global clock advanced to the minimum pending event time, with per-node
mailboxes instead of a single continuation queue. The batched XLA
engine (interp/jax_engine) must reproduce this executor's trace
bit-for-bit — that law is the framework's acceptance gate (SURVEY.md §6).

The scenario's ``step`` and the link model's ``sample`` are the *same
jax functions* the engine uses — evaluated here through one ``vmap``
per superstep (vmap of a pure function is just map; batching cannot
change values) so the oracle stays fast enough to check thousand-node
runs. All *scheduling* decisions — who fires, what each inbox
contains, message ordering, capacity — are made by independent host
code, which is what makes this an oracle rather than a second copy of
the engine.
"""

from __future__ import annotations

from typing import List, Optional

from ...utils import jaxconfig  # noqa: F401  (must precede jax use)

import jax
import jax.numpy as jnp
import numpy as np

from ...core.rng import fire_bits, msg_bits, seed_words
from ...core.scenario import NEVER, Inbox, Scenario
from ...core.time import Microsecond
from ...net.delays import LinkModel
from ...trace.events import SuperstepTrace
from ...trace.hashing import FIRED, RECV, SENT, combine_py, mix32_py

__all__ = ["SuperstepOracle"]

_MASK32 = (1 << 32) - 1


class SuperstepOracle:
    """Sequential host executor; oracle for trace parity.

    ``window`` mirrors the engine's multi-instant windowed supersteps
    (interp/jax_engine/engine.py ``JaxEngine.window``): one superstep
    fires every node with an event in ``[t, t+window)``, each at its
    own instant, routing in chronological ``(instant, sender, slot)``
    order. Exact when link delays are ≥ window (validated here too;
    dynamic violations counted in ``short_delay_total``).
    """

    #: the uniform driver-accounting surface (populated by run())
    last_run_stats = None

    def __init__(self, scenario: Scenario, link: LinkModel, *,
                 seed: int = 0, record_events: bool = False,
                 window=1, lint: str = "warn", faults=None) -> None:
        # static scenario sanitizer — same knob contract as the
        # engines (analysis/check_scenario); the oracle is the
        # referee, so catching a contract violation here names it
        # before a digest mismatch would
        from ...analysis import check_scenario
        self.lint = lint
        self.lint_report = check_scenario(scenario, lint,
                                          who=type(self).__name__)
        link_floor = link.min_delay_us
        self._setup_faults(faults, scenario, lint)
        if self._faulted:
            # shrink-degradation windows lower the exact-window floor
            # (mirrors JaxEngine)
            link_floor = self.faults.min_delay_floor(link_floor)
        if isinstance(window, str) and window != "auto":
            # mirror JaxEngine: a typo'd "Auto"/"8ms" from a library
            # caller must fail clearly, not as `window < 1`'s opaque
            # str-vs-int TypeError
            raise ValueError(
                f"window must be an int µs count or the string "
                f"'auto', got {window!r}")
        if window == "auto":    # mirror JaxEngine: link floor = widest
            # exact window, int32-clamped exactly like the engine (a
            # FOREVER-delay link must resolve the same width in both
            # interpreters or windowed parity would silently split)
            from ..jax_engine.common import I32MAX
            window = max(1, min(int(link_floor), I32MAX - 1))
        if window < 1:
            raise ValueError(f"window must be >= 1 µs, got {window}")
        if window > 1 and window > link_floor:
            raise ValueError(
                f"window={window} µs exceeds the link model's declared "
                f"min_delay_us={link_floor}"
                f"{' (degradation-adjusted)' if self._faulted else ''}")
        self.scenario = scenario
        self.link = link
        self.window = int(window)
        self.s0, self.s1 = seed_words(seed)
        #: optional per-event debug log (SURVEY.md §5.1): tuples
        #: ("fire", t, node) / ("recv", t, node, src, deliver_t, pay0)
        #: / ("sent", t, src, dst, deliver_t, pay0) in execution order —
        #: the detail stream behind the aggregate digests, for
        #: pinpointing a divergence the parity checker reports.
        self.events: Optional[List[tuple]] = [] if record_events else None
        n = scenario.n_nodes
        per = [scenario.init(i) for i in range(n)]
        #: stacked numpy state pytree (row i = node i)
        self.states = jax.tree.map(
            lambda *xs: np.stack([np.asarray(x) for x in xs]),
            *[p[0] for p in per])
        self.wake: List[int] = [int(p[1]) for p in per]
        #: per-node arrival-ordered pending (deliver_time, src, payload)
        self.mailbox: List[List[tuple]] = [[] for _ in range(n)]
        self.overflow_total = 0
        self.bad_dst_total = 0
        self.short_delay_total = 0
        #: messages the fault schedule killed (cuts + down-window
        #: deliveries + reset purges) — mirrors
        #: ``EngineState.fault_dropped``
        self.fault_dropped_total = 0
        #: the same by cause (cut by a partition, due inside a down
        #: window, purged at a reboot; their sum is the total), and the
        #: sends whose delay a link window changed and the reboots
        #: consumed: the engines' ``last_run_stats`` ``fault_*`` counts
        self.fault_counts = dict.fromkeys(
            ("cut", "down", "purged", "degraded", "restarts"), 0)
        self.time: Microsecond = 0
        if self._faulted and self.faults.has_reset:
            # pristine reboot template (self.states is mutated in
            # place as the run progresses)
            self._reset_states = jax.tree.map(np.copy, self.states)

        ids = jnp.arange(n, dtype=jnp.int32)
        M = scenario.max_out
        src_f = jnp.repeat(ids, M)
        slot_f = jnp.tile(jnp.arange(M, dtype=jnp.int32), n)

        # one vmapped step per superstep — same fn the engine vmaps;
        # entropy derived elementwise (core/rng.py), no key arrays.
        # `now` is per-node (each fires at its own in-window instant;
        # all equal to t when window == 1). Clock skew wraps the SAME
        # step function the engine wraps (faults/apply.py), so skewed
        # behavior cannot diverge between interpreters.
        stepf = scenario.step
        if self._faulted and self.faults.has_skew:
            from ...faults.apply import skewed_step
            stepf = skewed_step(scenario.step,
                                jnp.asarray(self._ft.skew))

        def _vstep(states, inbox, now):
            if scenario.needs_key:
                bits = fire_bits(self.s0, self.s1, ids, now)
            else:
                bits = None
            return jax.vmap(
                stepf,
                in_axes=(0, 0, 0, 0, None if bits is None else 0))(
                    states, inbox, now, ids, bits)

        self._vstep = jax.jit(_vstep)

        # one batched link sample per superstep, keyed per
        # (src,dst,send-instant,slot); link models broadcast — no vmap.
        # Degradation windows transform the sampled delay here, with
        # the same integer helper the engines trace — identical bits.
        def _vsample(dst, tmsg):
            if link.needs_key:
                bits = msg_bits(self.s0, self.s1, src_f, dst, tmsg, slot_f)
            else:
                bits = None
            plain, drop = link.sample(src_f, dst, tmsg, bits)
            delay = plain
            if self._faulted:
                from ...faults.apply import degrade
                ftj = jax.tree.map(jnp.asarray, self._ft)
                delay = degrade(ftj, plain, src_f, dst, tmsg)
            return delay, drop, plain

        self._vsample = jax.jit(_vsample)

    # -- faults (host-side mirror of faults/apply.py) -------------------

    def _setup_faults(self, faults, scenario, lint) -> None:
        """Validate the ``faults`` argument and precompute the plain-
        Python crash/partition row lists the run loop's *independent*
        scheduling decisions use (the oracle shares only the jitted
        value functions — step, sample, degrade — with the engines;
        every who-fires/what-drops decision is re-made here in host
        code, which is what makes it an oracle)."""
        self.faults = faults
        self._faulted = faults is not None
        self._ft = None
        self.fault_lint_report = None
        if faults is None:
            return
        from ...faults.schedule import FaultFleet, FaultSchedule
        if isinstance(faults, FaultFleet):
            raise ValueError(
                "the oracle runs one world; pass one FaultSchedule "
                "(fleet.world_schedule(b) for a batched world's twin)")
        if not isinstance(faults, FaultSchedule):
            raise ValueError(
                f"faults must be a FaultSchedule, got {faults!r}")
        from ...analysis import check_faults
        self.fault_lint_report = check_faults(
            faults, scenario, lint, who=type(self).__name__)
        self._ft = faults.tables(scenario.n_nodes)
        #: (node, down, up, reset) for ACTIVE crash rows, with their
        #: table row index (the restart ledger key)
        self._crash_rows = [
            (int(self._ft.crash_node[c]), int(self._ft.crash_down[c]),
             int(self._ft.crash_up[c]), bool(self._ft.crash_reset[c]), c)
            for c in range(self._ft.crash_node.shape[0])
            if self._ft.crash_up[c] > self._ft.crash_down[c]]
        self._restart_done = [False] * self._ft.crash_node.shape[0]
        self._parts = [
            (self._ft.part_group[p], int(self._ft.part_start[p]),
             int(self._ft.part_end[p]))
            for p in range(self._ft.part_group.shape[0])
            if self._ft.part_end[p] > self._ft.part_start[p]]

    def _fault_next(self, i: int, x: int) -> int:
        """Crash-adjusted next-event time for node ``i`` (engine twin:
        ``defer_next``): defer an in-window event to its t_up, then
        min in any unconsumed restart injection."""
        ups = [u for (k, d, u, _r, _c) in self._crash_rows
               if k == i and d <= x < u]
        if ups:
            x = max(ups)
        inj = min((u for (k, _d, u, r, c) in self._crash_rows
                   if k == i and r and not self._restart_done[c]),
                  default=NEVER)
        return min(x, inj)

    def _cut(self, src: int, dst: int, t: int) -> bool:
        """Does a (src -> dst) message sent at ``t`` cross a live
        partition cut?"""
        for group, start, end in self._parts:
            if start <= t < end:
                gs, gd = int(group[src]), int(group[dst])
                if gs >= 0 and gd >= 0 and gs != gd:
                    return True
        return False

    def _down(self, node: int, t: int) -> bool:
        """Is ``node`` inside a crash window at time ``t``?"""
        return any(k == node and d <= t < u
                   for (k, d, u, _r, _c) in self._crash_rows)

    def _restart(self, i: int, ti: int) -> None:
        """Consume restart rows for node ``i`` firing at ``ti``; on a
        reset restart, reboot the state from the pristine template and
        purge mailbox entries older than the crash (memory loss,
        counted in ``fault_dropped_total``)."""
        purge_before, rebooted = 0, False
        for (k, d, u, r, c) in self._crash_rows:
            if r and not self._restart_done[c] and k == i and ti == u:
                self._restart_done[c] = True
                self.fault_counts["restarts"] += 1
                rebooted = True
                purge_before = max(purge_before, d)
        if rebooted:
            def _reset(cur, init):
                cur[i] = init[i]
                return cur
            self.states = jax.tree.map(_reset, self.states,
                                       self._reset_states)
            kept = [m for m in self.mailbox[i] if m[0] >= purge_before]
            self.fault_dropped_total += len(self.mailbox[i]) - len(kept)
            self.fault_counts["purged"] += len(self.mailbox[i]) - len(kept)
            self.mailbox[i] = kept

    # ------------------------------------------------------------------

    def _node_next(self, i: int) -> int:
        m = min((mm[0] for mm in self.mailbox[i]), default=NEVER)
        nxt = min(self.wake[i], m)
        if self._faulted:
            nxt = self._fault_next(i, nxt)
        return nxt

    # ------------------------------------------------------------------

    def run(self, max_steps: int = 1 << 30,
            until: Optional[Microsecond] = None) -> SuperstepTrace:
        import time as _time
        _wall0 = _time.perf_counter()
        sc = self.scenario
        n, M, K, P = sc.n_nodes, sc.max_out, sc.mailbox_cap, sc.payload_width
        W = self.window
        rows = []
        for _ in range(max_steps):
            nexts = [self._node_next(i) for i in range(n)]
            t = min(nexts)
            if t >= NEVER or (until is not None and t > until):
                break
            self.time = t
            # windowed firing: every node with an event in [t, t+W),
            # each at its own instant nexts[i] (== t for W == 1);
            # an `until` horizon bounds the *instants*, not just the
            # window start — a W > 1 window straddling `until` fires
            # only the nodes at or before it (matching the window=1
            # semantics of the same horizon)
            fired = [i for i in range(n)
                     if nexts[i] < NEVER and nexts[i] - t < W
                     and (until is None or nexts[i] <= until)]
            fired_hash = combine_py(mix32_py(FIRED, i) for i in fired)
            if self.events is not None:
                self.events.extend(("fire", nexts[i], i) for i in fired)
            if self._faulted:
                # restart firings: consume the injected reboot, reset
                # state from the template, purge pre-crash mailbox
                # memory — BEFORE inboxes are built (engine: the purge
                # mask is excluded from `deliver`)
                for i in fired:
                    self._restart(i, nexts[i])

            # build inboxes (host decision: contract #2 ordering);
            # deliverable = due at the node's own firing instant
            ib_valid = np.zeros((n, K), bool)
            ib_src = np.zeros((n, K), np.int32)
            ib_time = np.full((n, K), NEVER, np.int64)
            ib_pay = np.zeros((n, K, P), np.int32)
            recv_hashes: List[int] = []
            recv_count = 0
            for i in fired:
                ti = nexts[i]
                pend = self.mailbox[i]
                picked = sorted(
                    ((m, idx) for idx, m in enumerate(pend) if m[0] <= ti),
                    key=lambda mi: (mi[0][0], mi[1]))
                self.mailbox[i] = [m for m in pend if m[0] > ti]
                for j, (m, _) in enumerate(picked):
                    ib_valid[i, j] = True
                    ib_time[i, j] = m[0]
                    # inbox_src=False: sender identity is not part of
                    # the scenario semantics — all interpreters present
                    # (and hash) 0 (core/scenario.py)
                    src_word = m[1] if sc.inbox_src else 0
                    ib_src[i, j] = src_word
                    ib_pay[i, j] = m[2]
                    recv_hashes.append(mix32_py(
                        RECV, i, src_word, m[0] & _MASK32, m[0] >> 32,
                        int(m[2][0]) if P else 0))
                    if self.events is not None:
                        self.events.append(
                            ("recv", ti, i, int(m[1]), int(m[0]),
                             int(m[2][0]) if P else 0))
                recv_count += len(picked)

            # per-node firing instants (t for unfired — masked anyway)
            now_arr = np.full(n, t, np.int64)
            for i in fired:
                now_arr[i] = nexts[i]

            inbox = Inbox(valid=ib_valid, src=ib_src, time=ib_time,
                          payload=ib_pay)
            new_states, out, new_wake = self._vstep(
                self.states, inbox, jnp.asarray(now_arr))
            new_states = jax.tree.map(np.asarray, new_states)
            out_valid = np.asarray(out.valid)
            out_dst = np.asarray(out.dst, dtype=np.int32)
            out_pay = np.asarray(out.payload)
            new_wake = np.asarray(new_wake)

            # apply results for fired nodes only (host decision)
            fired_arr = np.asarray(fired, dtype=np.int64)
            def _apply(cur, new):
                cur[fired_arr] = new[fired_arr]
                return cur
            self.states = jax.tree.map(_apply, self.states, new_states)
            for i in fired:
                w = int(new_wake[i])
                # contract #5: clamp re-arm strictly past the node's now
                self.wake[i] = NEVER if w >= NEVER else max(w, nexts[i] + 1)

            # route in chronological (send instant, sender, slot) order
            # — contract #3; pure sender-major for W == 1. Link entropy
            # is keyed by each message's own send instant.
            delay, drop, plain = self._vsample(
                jnp.asarray(out_dst.reshape(-1)),
                jnp.asarray(np.repeat(now_arr, M)))
            delay = np.asarray(delay).reshape(n, M)
            plain = np.asarray(plain).reshape(n, M)
            drop = np.asarray(drop).reshape(n, M)
            sent_hashes: List[int] = []
            sent_count = 0
            overflow_step = 0
            for i in sorted(fired, key=lambda i: (nexts[i], i)):
                ti = nexts[i]
                for slot in range(M):
                    if not out_valid[i, slot]:
                        continue
                    dst = int(out_dst[i, slot])
                    if not (0 <= dst < n):
                        self.bad_dst_total += 1  # surfaced, never silent
                        continue
                    if drop[i, slot]:
                        continue
                    if self._faulted and self._cut(i, dst, ti):
                        # sent across a live partition cut: lost in
                        # transit — counted, never hashed (the engine
                        # kills the same set pre-insertion)
                        self.fault_dropped_total += 1
                        self.fault_counts["cut"] += 1
                        continue
                    flight = max(int(delay[i, slot]), 1)  # contract #4
                    if self._faulted and delay[i, slot] != plain[i, slot]:
                        self.fault_counts["degraded"] += 1
                    if W > 1 and flight < W:
                        # windowed-causality violation — counted loudly,
                        # mirroring EngineState.short_delay
                        self.short_delay_total += 1
                    dt = ti + flight
                    if self._faulted and self._down(dst, dt):
                        # would land inside the destination's down
                        # window: its NIC is off — counted, dropped
                        self.fault_dropped_total += 1
                        self.fault_counts["down"] += 1
                        continue
                    p0 = int(out_pay[i, slot, 0]) if P else 0
                    sent_count += 1
                    sent_hashes.append(mix32_py(
                        SENT, i, dst, dt & _MASK32, dt >> 32, p0))
                    if self.events is not None:
                        self.events.append(("sent", ti, i, dst, dt, p0))
                    if len(self.mailbox[dst]) >= K:
                        overflow_step += 1  # contract #6: counted, dropped
                    else:
                        self.mailbox[dst].append(
                            (dt, i, np.asarray(out_pay[i, slot], np.int32)))
            self.overflow_total += overflow_step

            rows.append((t, len(fired), fired_hash,
                         recv_count, combine_py(recv_hashes),
                         sent_count, combine_py(sent_hashes),
                         overflow_step))
        # the uniform driver-accounting surface every engine carries
        # (interp/jax_engine/common.py RunStatsMixin); the oracle is
        # host Python, so it compiles, launches and reads back nothing
        self.last_run_stats = {
            "supersteps": len(rows),
            "wall_seconds": _time.perf_counter() - _wall0,
            "compiles": 0, "compile_seconds": 0.0, "cache_misses": 0,
            "dispatches": 0, "readbacks": 0,
        }
        return SuperstepTrace.from_rows(rows)
