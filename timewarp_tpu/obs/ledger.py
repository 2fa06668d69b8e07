"""Persistent cross-run ledger: the fleet's measurement memory.

Every run already emits rich artifacts — BENCH_SCHEMA JSON lines,
schema-validated metrics JSONL, sweep journals — but each one is an
island: nothing reads *across* runs, so the bench trajectory lives in
hand-curated ``BENCH_r*.json`` files and a regression is invisible
until a human diffs two of them. Revati (PAPERS.md) frames the
emulator itself as a production serving system; SCALE-Sim TPU makes
utilization reporting the first-class objective — both presuppose a
durable measurement ledger. This module is that ledger, and the
standing home for the chip-round measurement debt the ROADMAP carries.

Layout — one directory, append-only::

    <ledger>/
      index.jsonl        # one line per ingested run (flushed+fsync'd)
      runs/<run_id>/
        record.json      # the full record incl. the raw source line

Every record carries a stable ``config_key`` (bench config name +
requested shape + platform — BENCH_SCHEMA v2 lines stamp their own;
v1 archives get a deterministic derivation, below) and the producing
``git_sha``, so cross-run joins are unambiguous. ``run_id`` is a
monotone ``rNNNN``; each ingest session shares a ``batch`` label
(``bNNNN``, or a caller-chosen name such as the seed artifacts'
``BENCH_r01``), which is what :mod:`~timewarp_tpu.obs.regress`
compares batch-against-batch.

Crash model: ``record.json`` is written atomically *before* the index
line is appended (the index append is the commitment point, same
discipline as the sweep journal); a torn final index line is dropped
on read — the run it described simply is not in the ledger.

CLI (``timewarp-tpu ledger``)::

    ledger add     --ledger DIR SOURCE...   # bench JSONL / metrics /
                                            # sweep journal dir
    ledger import  --ledger DIR FILE...     # BENCH_r0*.json artifacts
    ledger list    --ledger DIR [--config SUBSTR] [--json]
    ledger show    --ledger DIR RUN_ID
    ledger compare --ledger DIR A B [...]   # obs/regress.py
    ledger anomalies [--ledger DIR] TARGET  # obs/regress.py

``bench.py --ledger DIR`` auto-appends every emitted bench line (one
batch per bench invocation), so running the bench *is* recording it.
"""

from __future__ import annotations

import json
import os
import re
import subprocess
from typing import Any, Dict, List, Optional

__all__ = ["LEDGER_SCHEMA", "LedgerError", "RunLedger",
           "derive_config_key", "resolve_git_sha", "ledger_main"]

#: index/record line schema — bumped when the record contract changes
LEDGER_SCHEMA = 1

#: index fields kept out of runs/<id>/record.json duplication: the
#: index line is the record minus the raw source line (kept slim so
#: `ledger list` scans stay cheap at thousands of runs)
_INDEX_DROP = ("line",)


class LedgerError(ValueError):
    """Bad ingest input or a self-contradictory ledger — never
    silently reconciled (the sweep-journal convention)."""


def resolve_git_sha(cwd: Optional[str] = None) -> str:
    """The producing commit, for cross-run provenance: ``TW_GIT_SHA``
    when set (hermetic CI), else ``git rev-parse``, else ``unknown``
    — a ledger outside a checkout still ingests, honestly marked."""
    env = os.environ.get("TW_GIT_SHA")
    if env:
        return env
    try:
        out = subprocess.run(
            ["git", "rev-parse", "--short=12", "HEAD"],
            cwd=cwd or os.path.dirname(os.path.abspath(__file__)),
            capture_output=True, text=True, timeout=10)
    except (OSError, subprocess.TimeoutExpired):
        return "unknown"
    sha = out.stdout.strip()
    return sha if out.returncode == 0 and sha else "unknown"


def _slug(text: str) -> str:
    return re.sub(r"-+", "-",
                  re.sub(r"[^a-z0-9]+", "-", text.lower())).strip("-")


def derive_config_key(line: Dict[str, Any]) -> str:
    """The stable join key for one bench line. BENCH_SCHEMA >= 2
    lines stamp their own ``config_key`` (bench.py names the config +
    requested shape + platform); v1 archive lines (the r01–r05
    artifacts) get a deterministic derivation — the metric text minus
    its boilerplate unit phrase, slugged, plus the platform — so the
    historical trajectory joins under keys that cannot collide with
    differently-shaped runs."""
    key = line.get("config_key")
    if isinstance(key, str) and key:
        return key
    metric = line.get("metric") or line.get("config")
    if not isinstance(metric, str) or not metric:
        raise LedgerError(
            "bench line carries neither config_key nor metric/config "
            f"— not a bench line: {json.dumps(line)[:120]}")
    for noise in ("delivered-messages/sec/chip",
                  "delivered-messages/sec", "aggregate"):
        metric = metric.replace(noise, " ")
    return f"{_slug(metric)}|{line.get('platform', 'unknown')}"


class RunLedger:
    """Append-only run ledger over one directory (module docstring)."""

    def __init__(self, root: str) -> None:
        self.root = root
        self.index_path = os.path.join(root, "index.jsonl")
        self.runs_dir = os.path.join(root, "runs")
        #: highest run number seen (in-memory after the first scan,
        #: so multi-line ingest stays O(lines), not O(lines^2))
        self._max_run: Optional[int] = None

    # -- reading -----------------------------------------------------------

    def index(self) -> List[dict]:
        """Every index line, oldest first. A torn *final* line (crash
        mid-append) is dropped; earlier damage is corruption and
        fails loudly — the sweep journal's crash model."""
        if not os.path.exists(self.index_path):
            return []
        with open(self.index_path) as f:
            lines = f.read().splitlines()
        out: List[dict] = []
        for i, line in enumerate(lines):
            if not line.strip():
                continue
            try:
                out.append(json.loads(line))
            except json.JSONDecodeError as e:
                if i == len(lines) - 1:
                    continue    # torn final append: the run is not in
                raise LedgerError(
                    f"ledger index {self.index_path!r} line {i + 1} "
                    f"is corrupt mid-file ({e}); a crash can only "
                    "tear the last line — this index has been "
                    "damaged externally") from None
        return out

    def runs(self, *, config_key: Optional[str] = None,
             batch: Optional[str] = None) -> List[dict]:
        """Index lines filtered by exact batch and/or config_key
        substring (keys embed shape + platform, so substring is the
        ergonomic selector)."""
        out = self.index()
        if batch is not None:
            out = [r for r in out if r.get("batch") == batch]
        if config_key is not None:
            out = [r for r in out
                   if config_key in (r.get("config_key") or "")]
        return out

    def get(self, run_id: str) -> dict:
        """The full record (raw source line included)."""
        path = os.path.join(self.runs_dir, run_id, "record.json")
        if not os.path.exists(path):
            known = [r["run_id"] for r in self.index()]
            raise LedgerError(
                f"ledger has no run {run_id!r} (known: "
                f"{known[-8:] if known else 'none — empty ledger'})")
        with open(path) as f:
            return json.load(f)

    def batches(self) -> List[str]:
        """Distinct batch labels, in first-seen order."""
        seen: List[str] = []
        for r in self.index():
            b = r.get("batch")
            if b and b not in seen:
                seen.append(b)
        return seen

    # -- writing -----------------------------------------------------------

    def new_batch(self) -> str:
        """The next free ``bNNNN`` label — one per ingest session
        (``bench.py --ledger`` takes one for its whole invocation).
        Two ingests racing the same ledger can still pick the same
        label (batches are selection labels, not identities — run
        ids never collide, see ``_commit``); pass an explicit
        ``--batch`` when parallel writers must stay separable."""
        mx = 0
        for b in self.batches():
            m = re.fullmatch(r"b(\d+)", b)
            if m:
                mx = max(mx, int(m.group(1)))
        return f"b{mx + 1:04d}"

    def _next_run_id(self) -> str:
        """The next free ``rNNNN``: max over the index AND over the
        ``runs/`` dir names — a crash between record write and index
        append leaves an orphan dir (the documented model: that run
        is not in the ledger), which must never be re-claimed."""
        if self._max_run is None:
            mx = 0
            for r in self.index():
                m = re.fullmatch(r"r(\d+)", r.get("run_id", ""))
                if m:
                    mx = max(mx, int(m.group(1)))
            if os.path.isdir(self.runs_dir):
                for name in os.listdir(self.runs_dir):
                    m = re.fullmatch(r"r(\d+)", name)
                    if m:
                        mx = max(mx, int(m.group(1)))
            self._max_run = mx
        self._max_run += 1
        return f"r{self._max_run:04d}"

    def _commit(self, rec: Dict[str, Any]) -> str:
        """Durably add one record: claim the run dir (mkdir is the
        atomic claim — a concurrent writer racing the same id loses
        the mkdir and takes the next number, so two ingests into one
        shared ledger can never clobber each other's records), then
        the atomic record.json, then the fsync'd index append (the
        commitment point)."""
        from ..utils.checkpoint import atomic_write
        while True:
            run_dir = os.path.join(self.runs_dir, rec["run_id"])
            try:
                os.makedirs(run_dir)
                break
            except FileExistsError:
                # another writer (or a crash orphan created since our
                # scan) holds this id — rescan and take the next
                self._max_run = None
                rec["run_id"] = self._next_run_id()

        def write(f):
            json.dump(rec, f, indent=1, sort_keys=True)
            f.write("\n")
        atomic_write(os.path.join(run_dir, "record.json"), write,
                     mode="w")
        slim = {k: v for k, v in rec.items() if k not in _INDEX_DROP}
        with open(self.index_path, "a") as f:
            f.write(json.dumps(slim, sort_keys=True) + "\n")
            f.flush()
            os.fsync(f.fileno())
        return rec["run_id"]

    def add_bench_line(self, line: Dict[str, Any], *,
                       batch: Optional[str] = None,
                       source: Optional[str] = None) -> str:
        """Ingest one BENCH_SCHEMA JSON line (v1 archives welcome —
        ``derive_config_key`` gives them a deterministic join key).
        Returns the new run_id."""
        if not isinstance(line, dict):
            raise LedgerError(
                f"bench line must be a JSON object, got "
                f"{type(line).__name__}")
        key = derive_config_key(line)
        os.makedirs(self.runs_dir, exist_ok=True)
        rec: Dict[str, Any] = {
            "ledger_schema": LEDGER_SCHEMA,
            "run_id": self._next_run_id(),
            "batch": batch or self.new_batch(),
            "kind": "bench",
            "config_key": key,
            "config": line.get("config"),
            "git_sha": line.get("git_sha", "unknown"),
            "bench_schema": line.get("schema"),
            "platform": line.get("platform"),
            "device_kind": line.get("device_kind"),
            "jax_version": line.get("jax_version"),
            "metric": line.get("metric"),
            "unit": line.get("unit"),
            "smoke": bool(line.get("smoke", False)),
            "source": source,
            "line": line,
        }
        # the comparable measurements ride the index line itself:
        # the rate (median-of-reps, with min/max bands when --reps
        # ran), the smoke wall seconds, and the serving layer's
        # admission throughput (bench.py serve_gossip — gateable now
        # that its causal explanation, the engine_builds/compiles
        # counters, rides the same line)
        # budget_efficiency / pad_waste_frac are the packing rollups
        # (sweep/journal.py util_rollup) the predictive-packing gate
        # compares (docs/sweeps.md "Predictive packing")
        for f in ("value", "min", "max", "reps", "seconds",
                  "admit_per_s", "budget_efficiency",
                  "pad_waste_frac"):
            if isinstance(line.get(f), (int, float)) \
                    and not isinstance(line.get(f), bool):
                rec[f] = line[f]
        return self._commit(rec)

    def add_search(self, journal_dir: str, *,
                   batch: Optional[str] = None) -> str:
        """Ingest a chaos-search campaign journal (timewarp_tpu/
        search/, docs/search.md) as the ``search`` kind: campaign
        identity (base config, objective, knobs, seed), per-
        generation progress, fork savings, and — when found — the
        counterexample and its minimized repro string, so found
        violations are queryable history."""
        from ..sweep.journal import SweepJournal
        j = SweepJournal(journal_dir)
        if not j.exists():
            raise LedgerError(
                f"{journal_dir!r} holds no campaign journal "
                "(no journal.jsonl)")
        recs = j.records()
        meta = next((r for r in recs
                     if r.get("ev") == "search_campaign"), None)
        if meta is None:
            raise LedgerError(
                f"{journal_dir!r} holds no search_campaign record — "
                "not a chaos-search journal (sweep journals ingest "
                "as the 'sweep' kind)")
        gens = [r for r in recs if r.get("ev") == "search_gen"]
        done = next((r for r in recs
                     if r.get("ev") == "search_done"), None)
        minimized = next((r for r in recs
                          if r.get("ev") == "search_minimized"), None)
        ce = next((r for r in recs
                   if r.get("ev") == "search_counterexample"), None)
        forks = [r for r in recs if r.get("ev") == "search_fork"]
        base = meta.get("base", {})
        os.makedirs(self.runs_dir, exist_ok=True)
        rec = {
            "ledger_schema": LEDGER_SCHEMA,
            "run_id": self._next_run_id(),
            "batch": batch or self.new_batch(),
            "kind": "search",
            "config_key": (f"search|{base.get('scenario', '?')}"
                           f"|{_slug(str(meta.get('objective')))}"
                           f"|s{meta.get('seed')}"),
            "git_sha": resolve_git_sha(journal_dir),
            "source": os.path.abspath(journal_dir),
            "search": {
                "objective": meta.get("objective"),
                "base": base,
                "population": meta.get("population"),
                "generations_planned": meta.get("generations"),
                "generations_run": len(gens),
                "seed": meta.get("seed"),
                "found": bool(done and done.get("found")),
                "evaluations": (done or {}).get("evaluations"),
                "counterexample": (ce or {}).get("faults"),
                "minimized": (minimized or {}).get("faults"),
                "fork": (done or {}).get("fork"),
                "forks": len(forks),
            },
        }
        return self._commit(rec)

    def add_serve(self, journal_dir: str, *,
                  batch: Optional[str] = None) -> str:
        """Ingest a service journal dir (serve/, docs/serving.md) as
        the ``serve`` kind: admission/steal/lease-reclaim/repack
        rollups, the per-host lease table, and the shared event-counts
        block — so a serving fleet's history is queryable next to
        bench and sweep runs."""
        from ..sweep.journal import SweepJournal, status_fields
        j = SweepJournal(journal_dir)
        if not j.exists():
            raise LedgerError(
                f"{journal_dir!r} holds no service journal "
                "(no journal*.jsonl)")
        scan = j.scan()
        if not (scan.hosts or scan.admits or scan.serve_buckets):
            raise LedgerError(
                f"{journal_dir!r} holds no serve_open/admit/lease "
                "records — not a service journal (sweep journals "
                "ingest as the 'sweep' kind)")
        open_rec = next((e for e in scan.events
                         if e.get("ev") == "serve_open"), None)
        host0 = (open_rec or {}).get("host") \
            or (sorted(scan.hosts) or ["?"])[0]
        os.makedirs(self.runs_dir, exist_ok=True)
        fields = status_fields(scan, len(scan.admits))
        # (features, budget, supersteps) training rows for the
        # packing forecaster (pack/predict.py) — assembled at ingest
        # so `pack fit` reads the index alone, never the journals
        from ..sweep.spec import RunConfig, SweepConfigError
        cfgs = []
        for a in scan.admits.values():
            try:
                cfgs.append(RunConfig.from_json(dict(a["config"]), 0))
            except (SweepConfigError, KeyError, TypeError):
                continue
        from ..pack.predict import training_rows
        pack_stats = training_rows(cfgs, scan.done)
        rec = {
            "ledger_schema": LEDGER_SCHEMA,
            "run_id": self._next_run_id(),
            "batch": batch or self.new_batch(),
            "kind": "serve",
            # stable across re-ingest of the same dir: the frontend
            # host + its journaled open ts anchor the identity
            "config_key": (f"serve|{host0}|"
                           f"{int((open_rec or {}).get('ts', 0))}"),
            "git_sha": resolve_git_sha(journal_dir),
            "source": os.path.abspath(journal_dir),
            "serve": {
                **fields.get("serve", {}),
                "completed": len(scan.done),
                "failed": sorted(scan.failed),
                "hosts": fields.get("hosts", {}),
                "events": scan.event_counts(),
                "utilization": scan.util,
                "pack_stats": pack_stats,
            },
        }
        return self._commit(rec)

    def add_sweep(self, journal_dir: str, *,
                  batch: Optional[str] = None) -> str:
        """Ingest a finished (or killed) sweep journal: worlds done/
        failed, retries, the event-counts block (identical to ``sweep
        status --json``'s ``events`` by construction), and the
        per-bucket utilization records."""
        from ..sweep.journal import SweepJournal, status_fields
        j = SweepJournal(journal_dir)
        if not j.exists():
            raise LedgerError(
                f"{journal_dir!r} holds no sweep journal "
                "(no journal.jsonl)")
        scan = j.scan()
        total = None
        pack_stats = []
        if os.path.exists(j.pack_path):
            with open(j.pack_path) as f:
                total = len(json.load(f))
            # forecaster training rows (pack/predict.py), assembled
            # at ingest so `pack fit` reads the index alone
            from ..pack.predict import training_rows
            from ..sweep.spec import SweepPack
            try:
                pack_stats = training_rows(
                    SweepPack.load(j.pack_path).configs, scan.done)
            except Exception:  # noqa: BLE001 — archival best-effort
                pack_stats = []
        os.makedirs(self.runs_dir, exist_ok=True)
        sha = scan.pack_sha or "unpacked"
        sweep_fields = status_fields(scan, total)
        sweep_fields["pack_stats"] = pack_stats
        rec = {
            "ledger_schema": LEDGER_SCHEMA,
            "run_id": self._next_run_id(),
            "batch": batch or self.new_batch(),
            "kind": "sweep",
            "config_key": f"sweep|{sha[:12]}",
            "git_sha": resolve_git_sha(journal_dir),
            "source": os.path.abspath(journal_dir),
            "sweep": sweep_fields,
        }
        return self._commit(rec)

    def add_metrics(self, path: str, *,
                    batch: Optional[str] = None) -> str:
        """Ingest a metrics JSONL stream (validated first — a stream
        the CI gate would reject must not enter the ledger): per-kind
        line counts plus the decision/speculation/integrity rollups
        the anomaly detectors read."""
        from .metrics import validate_metrics_file
        validate_metrics_file(path)     # raises, naming file + line
        kinds: Dict[str, int] = {}
        spec = {"committed": 0, "rollback": 0}
        integ = {"verified": 0, "rollback": 0}
        supersteps = 0
        run_label = None
        with open(path) as f:
            for raw in f:
                if not raw.strip():
                    continue
                rec = json.loads(raw)
                k = rec["kind"]
                kinds[k] = kinds.get(k, 0) + 1
                run_label = run_label or rec.get("run")
                if k == "supersteps":
                    supersteps += int(rec.get("supersteps", 0))
                elif k == "speculation" \
                        and rec.get("outcome") in spec:
                    spec[rec["outcome"]] += 1
                elif k == "integrity" and rec.get("event") in integ:
                    integ[rec["event"]] += 1
        os.makedirs(self.runs_dir, exist_ok=True)
        rec = {
            "ledger_schema": LEDGER_SCHEMA,
            "run_id": self._next_run_id(),
            "batch": batch or self.new_batch(),
            "kind": "metrics",
            "config_key": f"metrics|{run_label or _slug(os.path.basename(path))}",
            "git_sha": resolve_git_sha(os.path.dirname(path) or "."),
            "source": os.path.abspath(path),
            "metrics": {"kinds": kinds, "supersteps": supersteps,
                        "speculation": spec, "integrity": integ},
        }
        return self._commit(rec)

    def add_source(self, path: str, *,
                   batch: Optional[str] = None) -> List[str]:
        """Auto-detecting ingest of one source: a sweep journal dir,
        a metrics JSONL stream, a bench-artifact wrapper
        (``BENCH_r0N.json``: ``{"parsed": <line>, ...}``), or a file
        of bench JSON lines. Returns the new run_ids."""
        if os.path.isdir(path):
            # a journal dir is a sweep unless a FIRST record says it
            # is a chaos-search campaign (search/, docs/search.md) or
            # a service journal (serve/, docs/serving.md — the
            # frontend's per-host file opens with serve_open) —
            # sniffed from first lines only, so a large finished
            # journal is not fully parsed twice
            import glob as _glob
            firsts = []
            jp = os.path.join(path, "journal.jsonl")
            paths = ([jp] if os.path.exists(jp) else []) + sorted(
                p for p in _glob.glob(
                    os.path.join(path, "journal-*.jsonl"))
                if p != jp)
            for p in paths:
                with open(p) as f:
                    for line in f:
                        if line.strip():
                            try:
                                firsts.append(json.loads(line))
                            except json.JSONDecodeError:
                                pass
                            break
            evs = {f.get("ev") for f in firsts
                   if isinstance(f, dict)}
            if "search_campaign" in evs:
                return [self.add_search(path, batch=batch)]
            if "serve_open" in evs:
                return [self.add_serve(path, batch=batch)]
            return [self.add_sweep(path, batch=batch)]
        with open(path) as f:
            text = f.read()
        lines = [ln for ln in text.splitlines() if ln.strip()]
        if not lines:
            raise LedgerError(
                f"{path!r} is empty — the producing run wrote "
                "nothing (the empty-stream refusal, obs/metrics.py)")
        try:
            first = json.loads(lines[0])
        except json.JSONDecodeError:
            # a pretty-printed artifact is ONE object across lines
            first = json.loads(text)
            lines = [text]
        if isinstance(first, dict) and "parsed" in first:
            # the historical bench-artifact wrapper: the measured
            # line lives under "parsed", the round number under "n"
            batch = batch or _artifact_batch(path, first)
            return [self.add_bench_line(first["parsed"], batch=batch,
                                        source=os.path.abspath(path))]
        if isinstance(first, dict) and "kind" in first \
                and "schema" in first:
            return [self.add_metrics(path, batch=batch)]
        batch = batch or self.new_batch()
        out = []
        for ln in lines:
            out.append(self.add_bench_line(
                json.loads(ln) if isinstance(ln, str) else ln,
                batch=batch, source=os.path.abspath(path)))
        return out


def _artifact_batch(path: str, wrapper: Dict[str, Any]) -> str:
    """Batch label for a historical wrapper artifact: the file stem
    (``BENCH_r01``) — the trajectory `ledger list` should read as
    r01..r05 — falling back to the wrapper's round number."""
    stem = os.path.splitext(os.path.basename(path))[0]
    if stem:
        return stem
    return f"round{wrapper.get('n', '?')}"


# -- CLI ------------------------------------------------------------------

def _fmt_run(r: Dict[str, Any]) -> str:
    val = ""
    if "value" in r:
        val = f"  {r['value']:g} {r.get('unit') or ''}".rstrip()
        if "min" in r and "max" in r:
            val += f" [{r['min']:g}..{r['max']:g}]"
    elif "seconds" in r:
        val = f"  {r['seconds']:g} s"
    elif r.get("kind") == "sweep":
        sw = r.get("sweep", {})
        val = (f"  worlds {sw.get('completed')}/{sw.get('worlds')} "
               f"events {sw.get('events')}")
    elif r.get("kind") == "search":
        se = r.get("search", {})
        val = (f"  FOUND {se.get('minimized')!r}"
               if se.get("found") else "  no counterexample") + \
            f" gens {se.get('generations_run')}"
    elif r.get("kind") == "serve":
        sv = r.get("serve", {})
        val = (f"  admitted {sv.get('admitted')} completed "
               f"{sv.get('completed')} steals {sv.get('steals')} "
               f"repacks {sv.get('repacks')} hosts "
               f"{sorted(sv.get('hosts', {}))}")
    smoke = " smoke" if r.get("smoke") else ""
    return (f"{r['run_id']}  {r.get('batch', '?'):>10}  "
            f"{r.get('kind', '?'):7s}{smoke}  "
            f"git {r.get('git_sha', 'unknown')}  "
            f"{r.get('config_key', '?')}{val}")


def _add(argv, prog="ledger add", seed=False) -> int:
    import argparse
    p = argparse.ArgumentParser(
        prog=f"timewarp-tpu {prog}",
        description=("Seed the ledger from historical bench "
                     "artifacts (BENCH_r0*.json)" if seed else
                     "Ingest runs: bench JSONL, metrics JSONL, or "
                     "sweep journal dirs."))
    p.add_argument("--ledger", required=True,
                   help="ledger directory (created on first add)")
    p.add_argument("sources", nargs="+",
                   help="bench line file | metrics.jsonl | sweep "
                        "journal dir | chaos-search campaign journal "
                        "dir" + (" | BENCH_r0N.json artifact"
                                 if seed else ""))
    p.add_argument("--batch", default=None,
                   help="batch label (default: one fresh bNNNN per "
                        "invocation; artifact wrappers default to "
                        "their file stem)")
    args = p.parse_args(argv)
    led = RunLedger(args.ledger)
    # one shared batch per invocation for non-wrapper sources (so
    # `ledger compare bNNNN bMMMM` compares ingest-against-ingest);
    # wrapper artifacts pick their own file-stem batch (BENCH_r01...)
    batch = args.batch
    added: List[str] = []
    for src in args.sources:
        if _is_wrapper(src):
            added += led.add_source(src, batch=args.batch)
        else:
            if batch is None:
                batch = led.new_batch()
            added += led.add_source(src, batch=batch)
    by_id = {r["run_id"]: r for r in led.index()}
    for rid in added:
        print(_fmt_run(by_id[rid]))
    return 0


def _is_wrapper(path: str) -> bool:
    if os.path.isdir(path):
        return False
    try:
        with open(path) as f:
            return "parsed" in json.load(f)
    except (json.JSONDecodeError, OSError):
        return False


def _list(argv) -> int:
    import argparse
    p = argparse.ArgumentParser(
        prog="timewarp-tpu ledger list",
        description="One line per ingested run, oldest first.")
    p.add_argument("--ledger", required=True)
    p.add_argument("--config", default=None,
                   help="config_key substring filter")
    p.add_argument("--batch", default=None, help="exact batch filter")
    p.add_argument("--json", action="store_true")
    args = p.parse_args(argv)
    runs = RunLedger(args.ledger).runs(config_key=args.config,
                                       batch=args.batch)
    if args.json:
        print(json.dumps({"runs": runs, "count": len(runs)}))
        return 0
    for r in runs:
        print(_fmt_run(r))
    print(f"({len(runs)} runs)")
    return 0


def _show(argv) -> int:
    import argparse
    p = argparse.ArgumentParser(
        prog="timewarp-tpu ledger show",
        description="The full record of one run (raw line included).")
    p.add_argument("--ledger", required=True)
    p.add_argument("run_id")
    args = p.parse_args(argv)
    print(json.dumps(RunLedger(args.ledger).get(args.run_id),
                     indent=1, sort_keys=True))
    return 0


def _compare(argv) -> int:
    import argparse
    p = argparse.ArgumentParser(
        prog="timewarp-tpu ledger compare",
        description="Noise-aware cross-run regression gate "
                    "(obs/regress.py): exit 1 on any gated "
                    "regression, one pinned line each.")
    p.add_argument("--ledger", required=True)
    p.add_argument("a", help="baseline: run_id | batch | config_key "
                             "substring (latest run wins)")
    p.add_argument("b", help="candidate: same selector forms")
    p.add_argument("--rate-gate", type=float, default=0.30,
                   help="relative rate drop that fails (default "
                        "0.30: run-to-run swing is reported as a "
                        "note where the spread bands overlap)")
    p.add_argument("--wall-gate", type=float, default=0.75,
                   help="relative wall-time increase that fails "
                        "(default 0.75: a 2x slowdown always trips)")
    p.add_argument("--json", action="store_true")
    args = p.parse_args(argv)
    from .regress import compare_selections
    led = RunLedger(args.ledger)
    report = compare_selections(led, args.a, args.b,
                                rate_gate=args.rate_gate,
                                wall_gate=args.wall_gate)
    if args.json:
        print(json.dumps(report.to_json()))
    else:
        for line in report.lines():
            print(line)
    return 1 if report.regressions else 0


def _anomalies(argv) -> int:
    import argparse
    p = argparse.ArgumentParser(
        prog="timewarp-tpu ledger anomalies",
        description="Single-run anomaly detectors (obs/regress.py): "
                    "rollback storms, rung thrash, bucket_util "
                    "collapse, quiescence stragglers — one pinned "
                    "line each; exit 1 when any fire.")
    p.add_argument("target",
                   help="a ledger run_id (with --ledger), a sweep "
                        "journal dir, or a metrics.jsonl file")
    p.add_argument("--ledger", default=None)
    p.add_argument("--json", action="store_true")
    args = p.parse_args(argv)
    from .regress import detect_target_anomalies
    target = args.target
    if args.ledger is not None and not os.path.exists(target):
        rec = RunLedger(args.ledger).get(target)
        if rec.get("kind") == "bench":
            raise SystemExit(
                f"ledger run {args.target!r} is a bench line — it "
                "carries no telemetry/journal to detect over; point "
                "at a sweep journal dir or metrics.jsonl (or a "
                "sweep/metrics ledger run)")
        target = rec.get("source")
        if not target or not os.path.exists(target):
            raise SystemExit(
                f"ledger run {args.target!r} names source "
                f"{target!r}, which does not exist here — run "
                "anomalies where the artifact lives, or pass its "
                "path directly")
    findings = detect_target_anomalies(target)
    if args.json:
        print(json.dumps({"anomalies": [f.to_json() for f in findings],
                          "count": len(findings)}))
    else:
        for f in findings:
            print(f.line())
        print(f"({len(findings)} anomalies)")
    return 1 if findings else 0


def ledger_main(argv) -> int:
    cmds = {"add": lambda rest: _add(rest),
            "import": lambda rest: _add(rest, prog="ledger import",
                                        seed=True),
            "list": _list, "show": _show,
            "compare": _compare, "anomalies": _anomalies}
    if not argv or argv[0] not in cmds:
        raise SystemExit(
            "usage: timewarp-tpu ledger "
            "add|import|list|show|compare|anomalies ... "
            "(docs/observability.md 'Fleet observability')")
    try:
        return cmds[argv[0]](argv[1:])
    except (LedgerError, OSError, json.JSONDecodeError) as e:
        # the CLI convention everywhere else (test_zgrammar): exit 1
        # with the actionable message, never a raw traceback
        raise SystemExit(str(e)) from None
