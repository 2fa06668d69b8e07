"""Opt-in observability: on-device telemetry, metrics, Perfetto traces.

The emulator at production scale is a black box in flight unless it
reports on itself — SCALE-Sim TPU (PAPERS.md) reports utilization per
workload so packing decisions are measurable, and Revati frames the
emulator as a *serving* system, which demands runtime observability.
This package is that sensor layer, under one hard contract:

**Zero overhead when off, bit-exact when on.** Every engine takes
``telemetry="off"|"counters"|"full"``. ``"off"`` (the default) lowers
to the exact pre-telemetry jaxpr — not "cheap", *absent* — and every
mode produces bit-identical digests, traces, and checkpoints, because
telemetry planes are *derived only from values the superstep already
computes* and ride as extra scan outputs that feed nothing back
(tests/test_zztelemetry.py pins both halves of the law).

Layers:

- :mod:`~timewarp_tpu.obs.telemetry` — the on-device per-superstep
  counter row (:class:`TelemetryRow`) and its host-side decode
  (:class:`TelemetryFrames`): active senders, selected routing rung,
  mailbox fill high-water, per-world quiescence slack, route/fault
  drop deltas.
- :mod:`~timewarp_tpu.obs.metrics` — :class:`MetricsRegistry`, a
  schema-validated JSONL metrics stream aggregating chunk-flushed
  telemetry, spans, and run summaries (``python -m
  timewarp_tpu.obs.metrics validate FILE`` is the CI gate).
- :mod:`~timewarp_tpu.obs.perfetto` — :class:`TraceBuilder`, a
  Chrome-trace/Perfetto exporter: wall-clock spans (sweep attempts,
  retries, checkpoints, journal fsyncs, jit compiles) on one process
  track, virtual-time superstep counters on another. Open the file at
  https://ui.perfetto.dev.
- :mod:`~timewarp_tpu.obs.profiler` — where host and chip time go:
  ``profile_session`` opens a ``jax.profiler`` session, ``span`` is
  the one host-span primitive (the drivers' ``tw.<driver>``,
  ``tw.dispatch``, ``tw.wait``, ``tw.guard``; ``tw.sweep.bucket``),
  beside the superstep's ``jax.named_scope`` stages on the device
  (``interp/jax_engine/common.py`` ``STAGES``).
- :mod:`~timewarp_tpu.obs.flight` — the causal flight recorder:
  ``record="off"|"deliveries"|"full"`` on every scan-driver engine
  threads a bounded per-superstep event plane (delivered messages;
  full adds sends and fault actions) through the traced scan, under
  the same zero-overhead/bit-exactness contract, drained into a
  schema'd JSONL event log.
- :mod:`~timewarp_tpu.obs.query` — causal queries over a recorded
  log: reconstruct a delivery's full chain (send → fault windows →
  delivery) and draw it as Perfetto flow arrows. CLI: ``timewarp-tpu
  explain``.
- :mod:`~timewarp_tpu.obs.bisect` — divergence bisection: binary-
  search two runs' per-chunk digest chains to the first diverging
  chunk, re-run it recorded, and report the first diverging
  superstep, field, and event delta in one pinned line. CLI:
  ``timewarp-tpu bisect``.
- :mod:`~timewarp_tpu.obs.ledger` — the persistent cross-run
  measurement ledger: git-sha-stamped, ``config_key``-joined ingest
  of bench lines, sweep journals, and metrics streams into one
  append-only index + per-run artifact dirs. CLI: ``timewarp-tpu
  ledger add|import|list|show|compare|anomalies``; ``bench.py
  --ledger DIR`` auto-appends every bench line.
- :mod:`~timewarp_tpu.obs.regress` — noise-aware cross-run
  regression gates (median-of-reps with min/max spread bands,
  per-metric relative-change gates) and single-run anomaly
  detectors (rollback storms, rung thrash, bucket_util collapse,
  quiescence stragglers), each finding one pinned line.
- :mod:`~timewarp_tpu.obs.watch` — the live, read-only sweep tail
  behind ``timewarp-tpu sweep watch``: torn-tail-tolerant
  incremental readers over the journal + metrics streams, folded
  through the SAME :class:`~timewarp_tpu.sweep.journal.JournalState`
  fold as ``sweep status`` (the two surfaces agree by construction).

docs/observability.md is the user-facing guide ("Fleet
observability" covers the cross-run plane).
"""

from .bisect import (DivergenceReport, bisect_engines, chain_bisect,
                     first_trail_divergence)
from .flight import (RECORD_MODES, FlightLog, FlightRecorderMixin,
                     FlightWriter, RecordRow, concat_flight,
                     decode_flight, load_flight_jsonl, validate_record)
from .ledger import (LEDGER_SCHEMA, LedgerError, RunLedger,
                     derive_config_key, resolve_git_sha)
from .metrics import (METRICS_SCHEMA, MetricsRegistry, validate_line,
                      validate_metrics_file)
from .perfetto import TraceBuilder
from .profiler import profile_session, span
from .query import (add_flight_flows, chain_lines, explain_delivery,
                    find_deliveries)
from .regress import (Anomaly, CompareReport, Delta, compare_runs,
                      compare_selections, detect_anomalies,
                      detect_target_anomalies)
from .telemetry import (TELEMETRY_MODES, TelemetryFrames, TelemetryRow,
                        decode_frames, summarize_frames, validate_mode)
from .watch import SweepWatch, TailReader

__all__ = [
    "TELEMETRY_MODES", "TelemetryRow", "TelemetryFrames",
    "decode_frames", "summarize_frames", "validate_mode",
    "METRICS_SCHEMA", "MetricsRegistry", "validate_line",
    "validate_metrics_file",
    "TraceBuilder", "profile_session", "span",
    "RECORD_MODES", "RecordRow", "FlightLog", "FlightWriter",
    "FlightRecorderMixin", "validate_record", "decode_flight",
    "concat_flight", "load_flight_jsonl",
    "explain_delivery", "find_deliveries", "chain_lines",
    "add_flight_flows",
    "DivergenceReport", "bisect_engines", "chain_bisect",
    "first_trail_divergence",
    "LEDGER_SCHEMA", "LedgerError", "RunLedger", "derive_config_key",
    "resolve_git_sha",
    "Delta", "Anomaly", "CompareReport", "compare_runs",
    "compare_selections", "detect_anomalies",
    "detect_target_anomalies",
    "SweepWatch", "TailReader",
]
