"""The verified chunked driver: detect, roll back, re-run, bit-exact.

``run_verified`` is ``run_stream``/``run_controlled``'s self-checking
sibling: the run executes one jitted chunk at a time, and around every
chunk the engine's ``verify`` mode is enforced —

1. **entry digest** (``digest``/``shadow``, every chunk): the state
   digest is recomputed and compared against the value recorded at
   the previous chunk's exit. The arrays did not legitimately change
   between chunks, so a mismatch is corruption of state at rest —
   caught before the corrupt state runs a superstep. This check is
   one elementwise pass over the state, so it is NOT cadence-gated:
   gating it would let a flip at an unchecked boundary be absorbed
   into the next recorded digest and go undetected forever. The
   detection window is therefore one chunk — the configured cadence
   unit of the detection law.
2. **guard** (all non-off modes): the chunk's traced scan carries the
   on-device invariant plane (checks.py); the engine's ``run`` raises
   :class:`~timewarp_tpu.integrity.checks.IntegrityViolation` naming
   the first violating superstep + field.
3. **shadow** (``shadow``, every ``cadence``-th chunk — the
   deterministic sampling knob for the one genuinely expensive
   check): the chunk re-executes from its pre-state through the
   pow2-cache twin — the
   same semantics compiled as a *different* executable (the scan pad
   is the drivers' only static input, so doubling it lands in a
   different jit cache entry while the masked tail keeps results
   bit-identical) — and the two post-states' digests must agree. By
   the exactness laws a disagreement is compute corruption (an SDC in
   one execution) or a real bug; either way it is never silent.

On any detection the driver **rolls back deterministically**: restore
the last verified snapshot (state + trace-row high-water marks),
discard the tainted rows, and re-run. The emulation is a pure
function of state and seed, so the recovered run is bit-identical —
states, traces, digests, checkpoints — to a run that was never
corrupted: the detection law (tests/test_zzzzintegrity.py). A
violation that survives ``max_rollbacks`` consecutive rollbacks of
the same chunk is persistent (bad memory cell, real logic bug) and
re-raises loudly rather than looping forever.

``verify="off"`` still runs the plain chunked loop (no checks, no
digests) — the apples-to-apples baseline the bench's
``verify_overhead_frac`` divides by.
"""

from __future__ import annotations

import numpy as np

__all__ = ["VerifiedRunMixin"]


class VerifiedRunMixin:
    """``verify=`` wiring + the self-verifying chunked driver (module
    docstring). Host state only: an engine with ``verify="off"``
    lowers byte-identical jaxprs to the pre-knob engine (the guard
    plane is a ``None`` StepOut field, exactly like telemetry)."""

    #: the engine's verify mode ("off" | "guard" | "digest" | "shadow")
    verify = "off"
    #: scan-pad multiplier for the pow2-cache shadow twin (always a
    #: pow2, so padded_scan's masked tail keeps results identical
    #: while the jit cache compiles a distinct executable)
    _pad_mult = 1
    #: the last run_verified call's integrity record (dict)
    last_run_integrity = None

    def _bind_verify(self, verify: str) -> None:
        from .checks import validate_verify
        self.verify = validate_verify(verify, type(self).__name__)

    def _capture_integrity(self, ys) -> None:
        """Host-side decode of a traced run's guard plane: raise the
        pinned TraceMismatch-style :class:`IntegrityViolation` on the
        FIRST violating superstep + field — loud, never silent, in
        any non-off mode (the ``run_verified`` driver catches it and
        rolls back; a plain ``run`` surfaces it to the caller)."""
        if self.verify == "off" or ys is None \
                or getattr(ys, "integ", None) is None:
            return
        from .checks import first_guard_violation, guard_violation_error
        batch = getattr(self, "batch", None)
        hit = first_guard_violation(
            ys.integ, np.asarray(ys.valid), np.asarray(ys.t),
            None if batch is None else batch.B)
        if hit is not None:
            raise guard_violation_error(hit, type(self).__name__)

    # -- digests ---------------------------------------------------------

    def _state_digests(self, state) -> np.ndarray:
        """uint32[1] (solo) / uint32[B] (batched) digest view."""
        from .digest import host_digests
        return host_digests(state, getattr(self, "batch", None))

    def _shadow_rerun(self, budget, pre_state):
        """Re-execute one chunk from ``pre_state`` through the
        pow2-cache twin; returns the twin's final state. The primary
        chunk's host-side artifacts (stats, telemetry, metrics
        stream) are shielded — the shadow is a check, not a run."""
        saved = (self.last_run_stats, self.last_run_telemetry,
                 getattr(self, "metrics", None),
                 getattr(self, "last_run_flight", None),
                 getattr(self, "flight_out", None))
        self.metrics = None
        self.flight_out = None
        self._pad_mult = 2
        try:
            fin, _ = self.run(budget, state=pre_state)
        finally:
            self._pad_mult = 1
            (self.last_run_stats, self.last_run_telemetry,
             self.metrics, self.last_run_flight,
             self.flight_out) = saved
        return fin

    # -- the driver ------------------------------------------------------

    def run_verified(self, budgets, state=None, *, chunk: int = 64,
                     cadence: int = 1, inject=None,
                     max_rollbacks: int = 3, on_quiesce=None):
        """Run to quiescence/budget under the engine's ``verify``
        mode, chunk by chunk, rolling back to the last verified
        snapshot on any detection (module docstring). Accepts the
        same budget forms as ``run`` (int; batched engines also a
        per-world vector) and returns ``(final_state, trace)`` —
        batched engines a per-world trace list — exactly like
        ``run``. ``inject`` is the deterministic-corruption test hook
        (integrity/inject.py ``FlipInjector``): called as
        ``inject(chunk_idx, state)`` between chunks, it may return a
        corrupted replacement state. ``on_quiesce(b, state)`` fires
        exactly once per world (``b=0`` solo), the moment the world
        has quiesced or exhausted its budget at a VERIFIED boundary —
        evaluated on committed states only and before the injection
        hook, so a rolled-back chunk can never fire (or double-fire)
        it: the rollback × streaming contract
        (tests/test_zzzzzzspec.py). The integrity record lands on
        ``last_run_integrity`` (and the digest chain on
        ``last_run_stats['digest_chain']``)."""
        from ..trace.events import SuperstepTrace
        from .checks import IntegrityViolation
        from .digest import VERIFY_CHAIN_ZERO, chain_state_digest
        mode = self.verify
        if chunk < 1:
            raise ValueError(f"chunk must be >= 1, got {chunk}")
        if cadence < 1:
            raise ValueError(f"cadence must be >= 1, got {cadence}")
        batch = getattr(self, "batch", None)
        nworld = 1 if batch is None else batch.B
        if batch is not None:
            budgets = np.broadcast_to(
                np.asarray(budgets, np.int64), (batch.B,)).copy()
        else:
            budgets = int(budgets)
        if np.min(budgets) < 0:
            raise ValueError("step budgets must be >= 0")
        st = state if state is not None else self.init_state()
        start = np.asarray(_get(st.steps), np.int64)
        rows = [[] for _ in range(nworld)]
        chunk_stats, frame_chunks, flight_chunks = [], [], []
        self.last_run_telemetry = None
        self.last_run_flight = None
        # cleared at entry: a run that RAISES (persistent corruption)
        # must not leave a previous run's record for callers to
        # misattribute
        self.last_run_integrity = None
        digest_on = mode in ("digest", "shadow")
        vdig = self._state_digests(st) if digest_on else None
        chain = [VERIFY_CHAIN_ZERO] * nworld
        #: last verified point: (state, per-world row counts)
        snap = (st, [0] * nworld)
        violations: list = []
        rollbacks = checks = 0
        consecutive = 0
        metrics = getattr(self, "metrics", None)

        def record(v: dict):
            violations.append(v)
            if metrics is not None:
                # "kind" would collide with the metrics line's own
                # kind field — the violation's kind rides as "check"
                metrics.event("integrity_violation",
                              label=self.metrics_label, **{
                                  ("check" if k == "kind" else k): val
                                  for k, val in v.items()
                                  if isinstance(val, (int, str))})

        def rollback(v: dict):
            nonlocal st, rollbacks, consecutive
            record(v)
            rollbacks += 1
            consecutive += 1
            if consecutive > max_rollbacks:
                raise IntegrityViolation(
                    f"{self.metrics_label}: chunk {v['chunk']} failed "
                    f"verification {consecutive} consecutive times "
                    f"({v.get('kind', 'guard')}) — the corruption is "
                    "persistent (bad memory / real bug), rollback "
                    "cannot converge (docs/integrity.md)")
            st = snap[0]
            for b in range(nworld):
                del rows[b][snap[1][b]:]
            if digest_on:
                # the restored snapshot must still MATCH the recorded
                # verified digest — never re-anchor the baseline from
                # it: an in-place corruption (HBM bit rot) hits the
                # live state and the snapshot's shared buffers alike,
                # and re-deriving vdig from the corrupt snapshot
                # would silently adopt the corruption as truth. A
                # snapshot that fails its own record is unrecoverable
                # in-memory — escalate to the on-disk verified-epoch
                # model (the sweep's, sweep/runner.py).
                from .digest import first_digest_mismatch
                hit = first_digest_mismatch(self._state_digests(st),
                                            vdig)
                if hit is not None:
                    bad, got_h, want_h = hit
                    raise IntegrityViolation(
                        f"{self.metrics_label}: chunk {v['chunk']} "
                        f"world {bad}: the last verified in-memory "
                        f"snapshot fails its recorded digest "
                        f"({got_h} != {want_h}) — resident state "
                        "corrupted in place; restore from an on-disk "
                        "verified checkpoint (sweep --state-verify "
                        "digest, docs/integrity.md)")
            if metrics is not None:
                metrics.emit("integrity", label=self.metrics_label,
                             mode=mode, chunk=int(v["chunk"]),
                             event="rollback")

        emitted = np.zeros(nworld, bool)
        ci = 0
        while True:
            _, remaining, active = self._controlled_progress(
                st, budgets, start)
            act = np.atleast_1d(np.asarray(active))
            newly = ~act & ~emitted
            if newly.any() and digest_on:
                # the emission below promises a VERIFIED state: an
                # in-place corruption since the last commit (the
                # digest mode's whole threat model — e.g. a corrupted
                # wake flipping world_active) must not fire the
                # exactly-once callback with a corrupt state, so the
                # entry digest check runs FIRST on quiesce
                # transitions (rare — once per world; the regular
                # every-chunk entry check below is untouched)
                from .digest import first_digest_mismatch
                hit = first_digest_mismatch(self._state_digests(st),
                                            vdig)
                if hit is not None:
                    bad, got_h, want_h = hit
                    rollback({
                        "chunk": ci, "kind": "entry_digest",
                        "world": bad if batch is not None else None,
                        "expected": want_h, "got": got_h})
                    continue
            for b in np.nonzero(newly)[0]:
                # `st` here is the last VERIFIED state (rollback
                # restores it before the loop re-enters, and the
                # digest guard above re-checks it at rest), so a
                # tainted chunk can never quiesce a world — and the
                # emitted ledger makes the callback exactly-once even
                # across rollbacks of later chunks
                emitted[int(b)] = True
                if on_quiesce is not None:
                    on_quiesce(int(b), st)
            if not np.any(active):
                break
            if inject is not None:
                mut = inject(ci, st)
                if mut is not None:
                    st = mut
            due = (ci % cadence == 0)
            if digest_on:
                checks += 1
                from .digest import first_digest_mismatch
                hit = first_digest_mismatch(self._state_digests(st),
                                            vdig)
                if hit is not None:
                    bad, got_h, want_h = hit
                    rollback({
                        "chunk": ci, "kind": "entry_digest",
                        "world": bad if batch is not None else None,
                        "expected": want_h, "got": got_h})
                    continue
            pre = st
            if batch is not None:
                budget = np.where(active,
                                  np.minimum(remaining, chunk), 0)
            else:
                budget = int(min(int(remaining), chunk))
            # shield the metrics stream AND the flight-event log
            # while the chunk runs: run() flushes its `supersteps`
            # lines (and drains recorded events) internally, but THIS
            # chunk is unverified — a chunk that fails the guard or
            # the shadow compare would leave tainted (and, after the
            # re-run, duplicated) lines behind. The flush happens at
            # commit below, once the chunk is verified.
            self.metrics = None
            fout, self.flight_out = getattr(self, "flight_out",
                                            None), None
            try:
                st, tr = self.run(budget, state=st)
            except IntegrityViolation as e:
                rollback({"chunk": ci, "kind": "guard",
                          "detail": str(e)})
                continue
            finally:
                self.metrics = metrics
                self.flight_out = fout
            pstats, ptele = self.last_run_stats, self.last_run_telemetry
            pflight = self.last_run_flight
            dp = None   # post-chunk digest, reused at commit when the
            #           # shadow compare already paid for it
            if mode == "shadow" and due:
                checks += 1
                try:
                    twin = self._shadow_rerun(budget, pre)
                    ds, dp = (self._state_digests(twin),
                              self._state_digests(st))
                except IntegrityViolation as e:
                    rollback({"chunk": ci, "kind": "shadow_guard",
                              "detail": str(e)})
                    continue
                from .digest import first_digest_mismatch
                hit = first_digest_mismatch(ds, dp)
                if hit is not None:
                    bad, shadow_h, primary_h = hit
                    rollback({
                        "chunk": ci, "kind": "shadow",
                        "world": bad if batch is not None else None,
                        "primary": primary_h, "shadow": shadow_h})
                    continue
            # commit: the chunk is verified — advance the snapshot
            # (and only now flush its telemetry to the metrics
            # stream, exactly the lines run() would have flushed)
            chunk_stats.append(pstats)
            frame_chunks.append(ptele)
            flight_chunks.append(pflight)
            if metrics is not None and ptele is not None:
                metrics.superstep_chunk(self.metrics_label, ptele)
            if fout is not None and pflight is not None:
                # drain the VERIFIED chunk's events only — a rolled-
                # back chunk's events never reach the log
                if isinstance(pflight, list):
                    for b, lg in enumerate(pflight):
                        fout.write(lg, world=b)
                else:
                    fout.write(pflight)
            if batch is not None:
                for b in range(nworld):
                    rows[b].extend(tr[b].row(i)
                                   for i in range(len(tr[b])))
            else:
                rows[0].extend(tr.row(i) for i in range(len(tr)))
            if digest_on:
                vdig = dp if dp is not None \
                    else self._state_digests(st)
                chain = [chain_state_digest(chain[b], vdig[b])
                         for b in range(nworld)]
            snap = (st, [len(r) for r in rows])
            consecutive = 0
            if metrics is not None and self.verify != "off":
                # one line per chunk a check actually ran on — the
                # guard plane and the digest entry check both run
                # every chunk (only the shadow sampling is cadenced),
                # so gating this on `due` would undercount verified
                # epochs for a metrics consumer
                metrics.emit("integrity", label=self.metrics_label,
                             mode=mode, chunk=ci, event="verified")
            ci += 1

        # a zero-chunk run too (already quiesced, or budget 0): it
        # must not leave a PREVIOUS run's stats behind for the digest
        # fields below to graft onto — that record would be a chimera
        # of old wall/superstep numbers and this run's digests
        self._stats_merge(chunk_stats)
        if self.telemetry != "off":
            from ..obs.telemetry import concat_frames
            self.last_run_telemetry = concat_frames(frame_chunks)
        if getattr(self, "record", "off") != "off":
            from ..obs.flight import concat_flight
            self.last_run_flight = concat_flight(flight_chunks)
        self.last_run_integrity = {
            "mode": mode, "chunks": ci, "checks": checks,
            "rollbacks": rollbacks, "violations": violations,
            "state_digest": ([int(d) for d in vdig]
                             if digest_on else None),
            "digest_chain": list(chain) if digest_on else None,
        }
        if digest_on and self.last_run_stats is not None:
            # the rolling digest chains through last_run_stats — the
            # uniform place run-level facts live (obs/, RunStatsMixin)
            self.last_run_stats["state_digest"] = [int(d)
                                                   for d in vdig]
            self.last_run_stats["digest_chain"] = list(chain)
        if batch is not None:
            return st, [SuperstepTrace.from_rows(r) for r in rows]
        return st, SuperstepTrace.from_rows(rows[0])


def _get(x):
    import jax
    return jax.device_get(x)
