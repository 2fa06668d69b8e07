"""The controller-driven chunked driver (dispatch/; docs/dispatch.md).

``run_controlled`` is ``run_stream``'s adaptive sibling: the fleet (or
solo run) executes one jitted chunk at a time, and **between** chunks
the bound :class:`~timewarp_tpu.dispatch.DispatchController` reads the
chunk's telemetry (``engine.last_run_telemetry``) and picks the next
chunk's dispatch knobs — window width and rung pin as *traced scalars*
(``DynDispatch``, common.py: new values re-invoke the same executable;
nothing retraces), chunk length through the pow2-padded scan cache
(a revisited length is a cache hit; ``last_run_stats``'s per-chunk
compile attribution proves it).

Laws (tests/test_zzzdispatch.py):

- **replay law** — re-running with ``mode="replay"`` over the emitted
  decision trace is bit-identical on states, traces, digests, and
  checkpoints (solo, batched, under faults);
- **per-chunk static equivalence** — each chunk is bit-identical to a
  static engine constructed with that chunk's window, run for that
  chunk's budget from the same state (degradation-free runs; under a
  degradation window the device clamp varies the effective window
  *within* a chunk, which no single static construction can express —
  there the replay law and the ``short_delay == 0`` evidence carry
  the guarantee).

The mixin serves every chunk-capable engine; an engine whose window is
a compile-time constant (EdgeEngine: classic supersteps) keeps
``_dyn_ok = False`` and adapts chunk length only, with the pinned
knob values recorded in the trace.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .common import DynDispatch

__all__ = ["ControlledRunMixin"]


class ControlledRunMixin:
    """``controller=`` wiring + the adaptive chunked driver (module
    docstring). Host state only — an engine without a controller is
    byte-identical to the pre-dispatch engine (``_dyn`` stays None, so
    every traced driver lowers its original program)."""

    #: the bound DispatchController (None = static dispatch)
    controller = None
    #: the traced DynDispatch operand while a chunk traces (None =
    #: static values — the compile-time constants the engine was
    #: constructed with)
    _dyn = None
    #: whether this engine threads dynamic window/rung scalars
    #: (JaxEngine and its subclasses); False = the controller adapts
    #: chunk length only (EdgeEngine)
    _dyn_ok = False
    #: the emitted decision list of the last run_controlled call
    last_run_decisions = None

    def _bind_controller(self, controller) -> None:
        """Engine-construction half of the wiring: validate the
        controller against this engine's observability mode. The
        engine binding is *loud*: an auto controller without
        telemetry would silently decide from nothing every chunk."""
        if controller is None:
            return
        if not hasattr(controller, "decide") \
                or not hasattr(controller, "begin"):
            raise ValueError(
                f"controller must be a dispatch.DispatchController "
                f"(or duck-type decide/begin), got {controller!r}")
        if getattr(controller, "mode", "auto") == "auto" \
                and self.telemetry == "off":
            raise ValueError(
                "an auto dispatch controller consumes "
                "last_run_telemetry between chunks; build the engine "
                "with telemetry='counters' (or 'full') — replay mode "
                "alone runs with telemetry off (docs/dispatch.md)")
        self.controller = controller

    def dyn_values(self, decision) -> Optional[DynDispatch]:
        """The traced knob operand for one decision — None when this
        engine's knobs are compile-time constants (chunk-length-only
        adaptation)."""
        if not self._dyn_ok:
            return None
        return DynDispatch(window=jnp.int64(decision.window_us),
                           rung_pin=jnp.int32(decision.rung_pin))

    def _controlled_progress(self, state, budgets, start):
        """(steps_done, remaining, active) — ``fleet_progress``'s law
        generalized to solo states (0-d arrays reduce identically)."""
        steps_done = (np.asarray(jax.device_get(state.steps), np.int64)
                      - np.asarray(start, np.int64))
        remaining = np.maximum(np.asarray(budgets, np.int64)
                               - steps_done, 0)
        active = (np.asarray(jax.device_get(self.world_active(state)))
                  & (remaining > 0))
        return steps_done, remaining, active

    def run_controlled(self, budgets, state=None):
        """Run to quiescence/budget under the bound controller,
        deciding the dispatch knobs chunk by chunk. Accepts the same
        budget forms as :meth:`run` (int; batched engines also a
        per-world vector). Returns ``(final_state, trace)`` —
        batched engines a per-world trace list — exactly like
        :meth:`run`; the decision trace lands on
        ``last_run_decisions`` (and streams to an attached metrics
        registry as ``decision`` lines)."""
        from ...trace.events import SuperstepTrace
        ctrl = self.controller
        if ctrl is None:
            raise ValueError(
                "run_controlled needs a dispatch controller; build "
                "the engine with controller=DispatchController(...) "
                "(docs/dispatch.md) — static runs use run()/run_quiet")
        ctrl.begin(self)
        batch = getattr(self, "batch", None)
        if batch is not None:
            budgets = np.broadcast_to(
                np.asarray(budgets, np.int64), (batch.B,)).copy()
        else:
            budgets = int(budgets)
        if np.min(budgets) < 0:
            raise ValueError("step budgets must be >= 0")
        st = state if state is not None else self.init_state()
        start = np.asarray(jax.device_get(st.steps), np.int64)
        rows = [[] for _ in range(batch.B)] if batch is not None \
            else []
        chunk_stats = []
        frame_chunks = []
        flight_chunks = []
        self.last_run_telemetry = None
        ci = 0
        while True:
            _, remaining, active = self._controlled_progress(
                st, budgets, start)
            if not np.any(active):
                break
            t_now = int(np.min(np.asarray(
                jax.device_get(st.time), np.int64)))
            dec, fresh = ctrl.decide(ci, self.last_run_telemetry,
                                     t_now)
            if self._dyn_ok and dec.window_us > self.window:
                from ...dispatch.trace import DispatchTraceError
                raise DispatchTraceError(
                    f"chunk {ci} decision requests window "
                    f"{dec.window_us} µs beyond the engine bound "
                    f"{self.window} µs")
            if fresh and self.metrics is not None:
                self.metrics.emit("decision", label=self.metrics_label,
                                  chunk=dec.chunk,
                                  window_us=dec.window_us,
                                  rung_pin=dec.rung_pin,
                                  chunk_len=dec.chunk_len)
            dyn = self.dyn_values(dec)
            kw = {} if dyn is None else {"_dyn": dyn}
            if batch is not None:
                vec = np.where(active,
                               np.minimum(remaining, dec.chunk_len), 0)
                st, traces = self.run(vec, state=st, **kw)
                for b in range(batch.B):
                    rows[b].extend(traces[b].row(i)
                                   for i in range(len(traces[b])))
            else:
                step_n = int(min(int(remaining), dec.chunk_len))
                st, tr = self.run(step_n, state=st, **kw)
                rows.extend(tr.row(i) for i in range(len(tr)))
            chunk_stats.append(self.last_run_stats)
            frame_chunks.append(self.last_run_telemetry)
            flight_chunks.append(self.last_run_flight)
            ci += 1
        if chunk_stats:
            self._stats_merge(chunk_stats)
        if self.telemetry != "off":
            # post-run consumers (the CLI's --metrics-out/--trace-out
            # exporters) must see the WHOLE run's telemetry, not the
            # final chunk's — the controller consumed the per-chunk
            # views already
            from ...obs.telemetry import concat_frames
            self.last_run_telemetry = concat_frames(frame_chunks)
        if getattr(self, "record", "off") != "off":
            # same whole-run contract for the flight log (indices are
            # run-global already — each chunk drained as it committed)
            from ...obs.flight import concat_flight
            self.last_run_flight = concat_flight(flight_chunks)
        self.last_run_decisions = ctrl.decisions
        if batch is not None:
            return st, [SuperstepTrace.from_rows(r) for r in rows]
        return st, SuperstepTrace.from_rows(rows)
