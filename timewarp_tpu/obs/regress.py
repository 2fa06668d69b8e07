"""Cross-run regression gates and single-run anomaly detectors.

Two consumers of the run ledger (obs/ledger.py):

**compare** — ``timewarp-tpu ledger compare A B`` joins two
selections (run ids, batches, or config_key substrings) per
``config_key`` and gates each shared measurement with a *noise-aware*
relative-change check:

- rates (``value``, the median-of-``--reps`` msg/s) fail when the
  candidate drops more than ``rate_gate`` below the baseline **and**
  the two runs' min/max spread bands (when ``--reps`` recorded them)
  do not overlap — an overlap means run-to-run swing could explain
  the delta, which is reported as a note, never a failure;
- wall seconds (``seconds``, the smoke per-config timing) fail when
  the candidate exceeds ``1 + wall_gate`` times the baseline — the
  default 0.75 is loose enough for CI runner jitter and strict
  enough that a 2x slowdown always trips.

Byte-identical re-ingest of the same run compares with zero delta
and exits 0 — determinism is the contract the CI gate stands on.
Every failure is ONE pinned line (the TraceMismatch convention):
metric, configs, values, relative change, gate, run ids, git shas.

**anomalies** — detectors over a single run's telemetry/journal,
each reporting one pinned line:

- *rollback storm*: speculation rollbacks swamping committed
  decisions (the misspeculation ledger turned red), or repeated
  integrity violations (an SDC-prone host);
- *rung thrash*: the dispatch controller flip-flopping its rung pin
  on most consecutive decisions — the policy is oscillating, not
  adapting;
- *bucket_util collapse*: a bucket whose ``budget_efficiency`` or
  ``worlds_active_mean`` fell under the floor — the pack is
  mis-bucketed (docs/sweeps.md);
- *quiescence straggler*: a world still burning supersteps long
  after the fleet median quiesced — re-pack or split it.

Everything here is host-side and read-only: journals and metrics are
opened for reading only, so the bit-exact laws and the journal
compare surfaces are untouched by construction.
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

__all__ = ["Delta", "Anomaly", "CompareReport", "compare_runs",
           "compare_selections", "detect_anomalies",
           "detect_target_anomalies"]


# -- cross-run comparison -------------------------------------------------

#: metric field -> (better direction, default gate attr)
_METRICS = {"value": ("higher", "rate_gate"),
            "seconds": ("lower", "wall_gate"),
            # serving admission throughput (bench.py serve_gossip):
            # present only on serve lines; _compare_one skips metrics
            # missing on either side, so every other config is inert
            "admit_per_s": ("higher", "rate_gate"),
            # packing rollups (sweep/journal.py util_rollup,
            # docs/sweeps.md "Predictive packing"): present only on
            # sweep_hetero / serve_gossip bench lines — efficiency
            # regresses DOWN, pad waste regresses UP
            "budget_efficiency": ("higher", "rate_gate"),
            "pad_waste_frac": ("lower", "rate_gate")}


@dataclass
class Delta:
    """One gated measurement comparison between two ledger runs."""
    config_key: str
    metric: str                 # "value" | "seconds"
    a_run: str
    b_run: str
    a: float
    b: float
    #: signed relative change b vs a; None when the baseline is 0
    #: and the candidate is not (the ratio is undefined — a 0-second
    #: baseline with a nonzero candidate still GATES, see below)
    rel: Optional[float]
    gate: float
    regression: bool
    #: bands overlapped (noise could explain the delta) — never fails
    within_spread: bool = False
    a_git: str = "unknown"
    b_git: str = "unknown"

    def line(self) -> str:
        arrow = f"{self.a:g} -> {self.b:g}"
        pct = ("baseline 0, ratio undefined" if self.rel is None
               else f"{self.rel:+.1%}")
        if self.regression:
            why = ("any nonzero increase gates" if self.rel is None
                   else f"beyond the {self.gate:.0%} gate")
            return (f"REGRESSION {self.config_key} {self.metric}: "
                    f"{arrow} ({pct} — {why}) "
                    f"[{self.a_run} vs {self.b_run}, git {self.a_git} "
                    f"vs {self.b_git}]")
        note = (" within measured spread" if self.within_spread
                else "")
        return (f"ok {self.config_key} {self.metric}: {arrow} "
                f"({pct}{note}) [{self.a_run} vs {self.b_run}]")

    def to_json(self) -> dict:
        return {"config_key": self.config_key, "metric": self.metric,
                "a_run": self.a_run, "b_run": self.b_run,
                "a": self.a, "b": self.b,
                "rel": None if self.rel is None else round(self.rel,
                                                           6),
                "gate": self.gate, "regression": self.regression,
                "within_spread": self.within_spread}


@dataclass
class CompareReport:
    deltas: List[Delta] = field(default_factory=list)
    #: config_keys present on only one side (reported, never fatal —
    #: a grown config inventory is not a regression)
    unmatched_a: List[str] = field(default_factory=list)
    unmatched_b: List[str] = field(default_factory=list)

    @property
    def regressions(self) -> List[Delta]:
        return [d for d in self.deltas if d.regression]

    def lines(self) -> List[str]:
        out = [d.line() for d in self.deltas]
        for key in self.unmatched_a:
            out.append(f"note {key}: only in the baseline selection")
        for key in self.unmatched_b:
            out.append(f"note {key}: only in the candidate selection")
        n = len(self.regressions)
        out.append(f"({len(self.deltas)} compared, {n} regressions)")
        return out

    def to_json(self) -> dict:
        return {"deltas": [d.to_json() for d in self.deltas],
                "unmatched_a": self.unmatched_a,
                "unmatched_b": self.unmatched_b,
                "regressions": len(self.regressions),
                "ok": not self.regressions}


def _band(rec: Dict[str, Any]) -> Optional[Tuple[float, float]]:
    """The run's measured min/max spread (``--reps`` recorded it), or
    a point band at the value."""
    if "min" in rec and "max" in rec:
        return float(rec["min"]), float(rec["max"])
    if "value" in rec:
        v = float(rec["value"])
        return v, v
    return None


def _compare_one(a: Dict[str, Any], b: Dict[str, Any],
                 rate_gate: float, wall_gate: float) -> List[Delta]:
    out: List[Delta] = []
    gates = {"rate_gate": rate_gate, "wall_gate": wall_gate}
    for metric, (direction, gate_name) in _METRICS.items():
        va, vb = a.get(metric), b.get(metric)
        if not isinstance(va, (int, float)) \
                or not isinstance(vb, (int, float)) \
                or isinstance(va, bool) or isinstance(vb, bool):
            continue
        va, vb = float(va), float(vb)
        gate = gates[gate_name]
        if va > 0:
            rel = vb / va - 1.0
            worse = (rel < -gate) if direction == "higher" \
                else (rel > gate)
        elif vb == va:
            rel, worse = 0.0, False
        else:
            # 0 baseline, nonzero candidate: the ratio is undefined —
            # a lower-is-better metric (wall seconds) gates on ANY
            # increase (0 -> 10 s must never print "+0.0% ok"); a
            # higher-is-better metric's 0 baseline means the BASELINE
            # was broken, and a nonzero candidate only improves it
            rel, worse = None, direction == "lower"
        within = False
        if worse and metric == "value":
            # noise-awareness: overlapping spread bands mean the
            # measured variance could explain the delta — note it,
            # never fail on it
            ba, bb = _band(a), _band(b)
            if ba and bb and ba[0] <= bb[1] and bb[0] <= ba[1]:
                within, worse = True, False
        out.append(Delta(
            config_key=a.get("config_key", "?"), metric=metric,
            a_run=a.get("run_id", "?"), b_run=b.get("run_id", "?"),
            a=va, b=vb, rel=rel, gate=gate, regression=worse,
            within_spread=within,
            a_git=a.get("git_sha", "unknown"),
            b_git=b.get("git_sha", "unknown")))
    return out


def compare_runs(a_runs: List[dict], b_runs: List[dict], *,
                 rate_gate: float = 0.30,
                 wall_gate: float = 0.75) -> CompareReport:
    """Join two run selections per ``config_key`` (latest run of a
    key wins within each side — re-ingests supersede) and gate every
    shared measurement. Non-bench records (sweep/metrics ingests)
    carry no comparable rate and are skipped."""
    def keyed(runs: List[dict]) -> Dict[str, dict]:
        out: Dict[str, dict] = {}
        for r in runs:                  # index order = oldest first
            if r.get("kind") == "bench":
                out[r["config_key"]] = r
        return out

    ka, kb = keyed(a_runs), keyed(b_runs)
    rep = CompareReport(
        unmatched_a=sorted(set(ka) - set(kb)),
        unmatched_b=sorted(set(kb) - set(ka)))
    for key in sorted(set(ka) & set(kb)):
        rep.deltas.extend(_compare_one(ka[key], kb[key],
                                       rate_gate, wall_gate))
    return rep


def compare_selections(ledger, a: str, b: str, *,
                       rate_gate: float = 0.30,
                       wall_gate: float = 0.75) -> CompareReport:
    """Resolve two CLI selectors and compare. A selector is a run_id
    (``r0007``), a batch label (``b0002`` / ``BENCH_r03``), or a
    config_key substring (the latest matching run wins)."""
    return compare_runs(_select(ledger, a, "A"),
                        _select(ledger, b, "B"),
                        rate_gate=rate_gate, wall_gate=wall_gate)


def _select(ledger, sel: str, who: str) -> List[dict]:
    index = ledger.index()
    hit = [r for r in index if r.get("run_id") == sel]
    if hit:
        return hit
    hit = [r for r in index if r.get("batch") == sel]
    if hit:
        return hit
    hit = [r for r in index if sel in (r.get("config_key") or "")]
    if hit:
        return hit[-1:]     # latest run of the key
    from .ledger import LedgerError
    raise LedgerError(
        f"selector {who}={sel!r} matches no run_id, batch, or "
        f"config_key in this ledger (batches: {ledger.batches()})")


# -- single-run anomaly detectors -----------------------------------------

@dataclass
class Anomaly:
    """One detector firing — rendered as one pinned line, the
    TraceMismatch convention (never an array dump)."""
    kind: str
    subject: str            # bucket / world / stream the line names
    detail: str
    severity: str = "warn"

    def line(self) -> str:
        return f"ANOMALY {self.kind} [{self.subject}]: {self.detail}"

    def to_json(self) -> dict:
        return {"kind": self.kind, "subject": self.subject,
                "detail": self.detail, "severity": self.severity}


#: detector thresholds — overridable per call, defaults chosen so a
#: healthy smoke sweep (tests, CI) never fires
THRESHOLDS = dict(
    rollback_rate=0.5,      # spec rollbacks / (rollbacks + decisions)
    rollback_min=3,         # ... but never on fewer events than this
    integrity_min=3,        # detected corruptions before "storm"
    thrash_frac=0.5,        # rung changes / consecutive pairs
    thrash_min_decisions=8,
    util_floor=0.25,        # budget_efficiency / worlds_active_mean
    straggler_factor=4.0,   # supersteps vs fleet median
    straggler_min_worlds=4,
)


def detect_anomalies(scan=None, metrics_path: Optional[str] = None,
                     **overrides) -> List[Anomaly]:
    """Run every detector over a journal scan (a ``JournalState``)
    and/or a metrics JSONL stream. Read-only; returns pinned-line
    findings, empty when healthy."""
    th = dict(THRESHOLDS)
    unknown = set(overrides) - set(th)
    if unknown:
        raise ValueError(
            f"unknown anomaly thresholds {sorted(unknown)}; known: "
            f"{sorted(th)}")
    th.update(overrides)
    out: List[Anomaly] = []
    if scan is not None:
        out += _journal_anomalies(scan, th)
    if metrics_path is not None:
        out += _metrics_anomalies(metrics_path, th)
    return out


def _journal_anomalies(scan, th) -> List[Anomaly]:
    out: List[Anomaly] = []
    # rollback storm — speculation: rollbacks vs committed decisions
    rb = len(scan.spec_rollbacks)
    decs = sum(len(v) for v in scan.decisions.values())
    if rb >= th["rollback_min"]:
        rate = rb / (rb + decs) if (rb + decs) else 1.0
        if rate > th["rollback_rate"]:
            out.append(Anomaly(
                "rollback-storm", "speculation",
                f"{rb} causality rollbacks vs {decs} committed "
                f"decisions (rate {rate:.2f} > "
                f"{th['rollback_rate']:.2f}) — the window policy is "
                "betting past the link's real support "
                "(docs/speculation.md)"))
    # rollback storm — integrity: repeated detected corruptions
    iv = len(scan.integrity)
    if iv >= th["integrity_min"]:
        out.append(Anomaly(
            "rollback-storm", "integrity",
            f"{iv} detected-and-rolled-back state corruptions in one "
            f"run (>= {th['integrity_min']}) — an SDC-prone host "
            "(docs/integrity.md)", severity="error"))
    # rung thrash — per bucket, consecutive decision flip-flops
    for bucket, dl in sorted(scan.decisions.items()):
        if len(dl) < th["thrash_min_decisions"]:
            continue
        pairs = list(zip(dl, dl[1:]))
        changes = sum(1 for a, b in pairs
                      if a.get("rung_pin") != b.get("rung_pin"))
        frac = changes / len(pairs)
        if frac > th["thrash_frac"]:
            out.append(Anomaly(
                "rung-thrash", f"bucket {bucket}",
                f"rung pin changed on {changes}/{len(pairs)} "
                f"consecutive decisions (frac {frac:.2f} > "
                f"{th['thrash_frac']:.2f}) — the controller is "
                "oscillating, not adapting (docs/dispatch.md)"))
    # bucket_util collapse
    for bucket, u in sorted(scan.util.items()):
        for sig in ("budget_efficiency", "worlds_active_mean"):
            v = u.get(sig)
            if isinstance(v, (int, float)) and v < th["util_floor"]:
                out.append(Anomaly(
                    "bucket-util-collapse", f"bucket {bucket}",
                    f"{sig} {v:.3f} < floor {th['util_floor']:.2f} — "
                    "the pack is mis-bucketed: split skewed budgets "
                    "or re-pack early-quiescing worlds "
                    "(docs/sweeps.md)"))
    # quiescence stragglers — per-world supersteps vs the fleet median
    totals = {rid: int(res.get("supersteps", 0))
              for rid, res in scan.done.items()}
    if len(totals) >= th["straggler_min_worlds"]:
        import statistics
        med = statistics.median(totals.values())
        if med > 0:
            for rid, s in sorted(totals.items()):
                if s > th["straggler_factor"] * med:
                    out.append(Anomaly(
                        "quiescence-straggler", f"world {rid}",
                        f"{s} supersteps vs fleet median {med:g} "
                        f"(> {th['straggler_factor']:g}x) — this "
                        "world kept the bucket's scan alive long "
                        "after its siblings quiesced; re-pack it"))
    return out


def _metrics_anomalies(path: str, th) -> List[Anomaly]:
    """Detectors over a metrics JSONL stream alone (no journal): the
    speculation/integrity rollups and the decision sequence."""
    spec = {"committed": 0, "rollback": 0}
    integ = 0
    decisions: List[dict] = []
    with open(path) as f:
        lines = f.read().splitlines()
    for i, raw in enumerate(lines):
        if not raw.strip():
            continue
        try:
            rec = json.loads(raw)
        except json.JSONDecodeError as e:
            if i == len(lines) - 1:
                continue    # a torn FINAL line: a live writer caught
                            # mid-append — the journal crash model
            from .ledger import LedgerError
            raise LedgerError(
                f"{path} line {i + 1} is corrupt mid-file ({e}); "
                "refusing to under-count anomalies over damaged "
                "telemetry — a crash can only tear the last "
                "line") from None
        k = rec.get("kind")
        if k == "speculation" and rec.get("outcome") in spec:
            spec[rec["outcome"]] += 1
        elif k == "integrity" and rec.get("event") == "rollback":
            integ += 1
        elif k == "decision":
            decisions.append(rec)
    out: List[Anomaly] = []
    rb, ok = spec["rollback"], spec["committed"]
    if rb >= th["rollback_min"]:
        rate = rb / (rb + ok) if (rb + ok) else 1.0
        if rate > th["rollback_rate"]:
            out.append(Anomaly(
                "rollback-storm", os.path.basename(path),
                f"{rb} speculation rollbacks vs {ok} commits (rate "
                f"{rate:.2f} > {th['rollback_rate']:.2f}) "
                "(docs/speculation.md)"))
    if integ >= th["integrity_min"]:
        out.append(Anomaly(
            "rollback-storm", os.path.basename(path),
            f"{integ} integrity rollbacks (>= "
            f"{th['integrity_min']}) — an SDC-prone host",
            severity="error"))
    if len(decisions) >= th["thrash_min_decisions"]:
        pairs = list(zip(decisions, decisions[1:]))
        changes = sum(1 for a, b in pairs
                      if a.get("rung_pin") != b.get("rung_pin"))
        frac = changes / len(pairs)
        if frac > th["thrash_frac"]:
            out.append(Anomaly(
                "rung-thrash", os.path.basename(path),
                f"rung pin changed on {changes}/{len(pairs)} "
                f"consecutive decisions (frac {frac:.2f} > "
                f"{th['thrash_frac']:.2f}) (docs/dispatch.md)"))
    return out


def detect_target_anomalies(target: str, **overrides) -> List[Anomaly]:
    """CLI entry: ``target`` is a sweep journal dir (its metrics
    stream, when present, is read too) or a metrics JSONL file."""
    if os.path.isdir(target):
        from ..sweep.journal import SweepJournal
        j = SweepJournal(target)
        if not j.exists():
            from .ledger import LedgerError
            raise LedgerError(
                f"{target!r} holds no sweep journal (no "
                "journal.jsonl) and is not a metrics file")
        mpath = os.path.join(target, "metrics.jsonl")
        return detect_anomalies(
            scan=j.scan(),
            metrics_path=mpath if os.path.exists(mpath) else None,
            **overrides)
    return detect_anomalies(metrics_path=target, **overrides)
