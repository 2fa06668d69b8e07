"""Benchmark driver: delivered-messages/sec/chip across the baseline
workloads (BASELINE.json configs; targets in BASELINE.md).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline",
"schema", "platform", "device_kind", "jax_version", "calib"}.
``vs_baseline`` is value / 1e8 (the north-star target; the reference
itself publishes no numbers — BASELINE.md); ``calib`` is a
frozen-kernel session fingerprint (see ``_calibrate``) so cross-run
artifacts separate session variance from code changes; the environment
fields (``_env_fields``, schema-versioned) name the device every line
ran on. The measured path refuses anything but a TPU
(``_require_chip``); ``--smoke`` runs the gates anywhere and its
rates are discarded.
Since BENCH_SCHEMA=2 every line also carries ``config``,
``config_key`` (the stable cross-run join key: config + requested
shape + platform), and ``git_sha``; ``--ledger DIR`` auto-appends
every emitted line to the persistent run ledger
(timewarp_tpu/obs/ledger.py — `timewarp-tpu ledger compare` is the
cross-run regression gate over it).

Configs (select with TW_BENCH_CONFIG, default ``token_ring_dense``):

- ``token_ring_dense`` — the headline: dense token ring on the fused
  Pallas engine (one kernel per superstep, fused_ring.py), verified
  in-bench bit-for-bit against the XLA edge engine; the reference's
  north-star scenario at 1M nodes.
- ``token_ring_dense_xla`` — the same ring on the XLA edge engine
  (the pre-fusion baseline).
- ``token_ring_observer`` — the reference's *actual* token-ring shape
  (observer hub, dynamic destinations) on the general engine.
- ``gossip_100k`` — push-rumor broadcast, 100k nodes, lognormal
  latency quantized to a 1 ms grid (net/delays.py ``Quantize``:
  time-bucketed batching) on the general engine.
- ``praos_1m`` — Ouroboros-Praos slot-leader consensus at 1M stake
  nodes, general engine, quantized lognormal links.
- ``gossip_100k_b8`` / ``praos_1m_b4`` — the sparse workloads as
  multi-world FLEETS (engine.py ``batch=BatchSpec``, round 7): 8
  seed-swept gossip worlds / 4 link-swept praos worlds through one
  batched engine, reporting AGGREGATE delivered-msg/s/chip. Gated
  in-bench by the batch exactness law (world-b slice ≡ solo run,
  bit-for-bit) before the measured run counts.
- ``sweep_hetero`` — the fault-tolerant sweep service (sweep/,
  docs/sweeps.md) on a heterogeneous pack with one injected transient
  failure: aggregate delivered-msg/s THROUGH the service (journal +
  checkpoints included), gated by the sweep survival law (every
  streamed result ≡ its solo run, bit-for-bit).
- ``serve_gossip`` — emulation as a service (serve/,
  docs/serving.md): heterogeneous gossip configs admitted into
  open-bucket reserved slots (half mid-bucket) under a work-stealing
  curator, reporting served configs/sec and p50/p95
  submit→world_done latency, gated by the extended survival law
  (every streamed record ≡ its solo run, bit-for-bit).

Env knobs: TW_BENCH_CONFIG, TW_BENCH_NODES (config-default), and
TW_BENCH_STEPS (supersteps in the measured window). ``--reps K``
repeats the measured run K times and reports the median rate with
min/max in the JSON line — whole-run rates swing from run to run, so
batched-vs-solo comparisons need it.

``python bench.py --smoke`` is the CI fast path: every config at tiny
N with all in-bench exactness gates on (fused ring AND the batch
exactness law), one JSON line per config — a kernel or
world-axis regression fails CI before a full bench round ever runs.
"""

import json
import os
import sys
import time

from timewarp_tpu.utils import jaxconfig

import jax


#: measured-window repetitions (set by --reps): the engine, its jit
#: compiles, and the in-bench exactness gates are paid ONCE per
#: config; only the measured window repeats. Virtual-time emulation
#: is deterministic, so `delivered` is identical across reps — only
#: wall-clock varies, which is exactly the run-to-run variance
#: --reps exists to average out.
_REPS = 1
#: min/max rates of the last _measure (populated when _REPS > 1)
_SPREAD = {}
#: set by --smoke: measured numbers are meaningless at smoke scale,
#: so wall-clock gates (the verify and record overhead budgets, the
#: controller's and speculation's wall gains) report instead of
#: asserting there
_SMOKE = False

#: BENCH_*.json line schema version: bumped when the line's field
#: contract changes. v1 added the environment fields below — the
#: carried-forward CPU-vs-chip parity debt (ROADMAP) was invisible in
#: the artifacts themselves until the line said where it ran. v2 adds
#: ``config``, ``config_key`` (config name + requested shape +
#: platform — the stable cross-run join key), and ``git_sha`` (the
#: producing commit), so the run ledger (timewarp_tpu/obs/ledger.py)
#: joins trajectories unambiguously; v1 archives remain ingestable
#: (the ledger derives their key deterministically).
BENCH_SCHEMA = 2

#: resolved once per process (the sha cannot change mid-bench)
_GIT_SHA = None


def _git_sha():
    global _GIT_SHA
    if _GIT_SHA is None:
        from timewarp_tpu.obs.ledger import resolve_git_sha
        _GIT_SHA = resolve_git_sha(
            os.path.dirname(os.path.abspath(__file__)))
    return _GIT_SHA


def _config_key(cfg, n, steps):
    """The stable cross-run join key (BENCH_SCHEMA v2): config name +
    the REQUESTED shape (0/None = the config's default — itself a
    stable identity) + platform. Rates at different shapes or
    platforms are not comparable, so the key must separate them."""
    return (f"{cfg}|n{n or 'dflt'}|s{steps or 'dflt'}"
            f"|{jax.default_backend()}")


def _env_fields():
    """Environment provenance on every JSON line: cross-round
    trajectories are only interpretable when each
    line names the platform/device/jax/commit that produced it."""
    dev = jax.devices()[0]
    return {"schema": BENCH_SCHEMA,
            "platform": jax.default_backend(),
            "device_kind": dev.device_kind,
            "jax_version": jax.__version__,
            "git_sha": _git_sha()}


#: (RunLedger, batch_id) when --ledger DIR was passed: every emitted
#: bench line auto-appends to the cross-run ledger (obs/ledger.py) —
#: running the bench IS recording it
_LEDGER = None


def _emit(line):
    """Print one bench JSON line AND (with --ledger) append it to the
    run ledger under this invocation's shared batch label."""
    print(json.dumps(line), flush=True)
    if _LEDGER is not None:
        _LEDGER[0].add_bench_line(line, batch=_LEDGER[1],
                                  source="bench.py")


def _require_chip(what):
    """The measured path times a TPU or nothing: a rate from XLA:CPU
    or the Pallas interpreter is not speed and is never printed as
    one. ``--smoke`` (gates only, rates discarded) runs anywhere."""
    if _SMOKE:
        return
    platform = jax.devices()[0].platform
    if platform != "tpu":
        raise SystemExit(
            f"bench: {what} measures the chip and JAX found "
            f"{platform!r} — refusing to time it (run through the "
            "chip tool, or `python bench.py --smoke` for the "
            "exactness gates alone)")


def _measure(engine, steps, warm_steps=2):
    import numpy as np
    _require_chip("_measure")
    st = engine.init_state()
    st = jax.block_until_ready(st)

    def total(s):  # batched states carry per-world [B] counters
        return int(np.asarray(jax.device_get(s.delivered)).sum())

    # Warmup: compile the while_loop driver (minutes for a large
    # general-engine ladder, CHANGES.md PR 21; cached thereafter)
    warm = engine.run_quiet(warm_steps, st)
    base = total(warm)  # force completion via host readback
    dts = []
    for _ in range(_REPS):
        t0 = time.perf_counter()
        fin = engine.run_quiet(steps, warm)
        delivered = total(fin) - base  # forces readback
        dts.append(time.perf_counter() - t0)
    import statistics
    dt = statistics.median(dts)
    _SPREAD.clear()
    if len(dts) > 1:
        _SPREAD.update(min=delivered / max(dts),
                       max=delivered / min(dts))
    return delivered, dt, fin


def _dense_ring(n):
    from timewarp_tpu.models.token_ring import token_ring
    from timewarp_tpu.net.delays import FixedDelay
    sc = token_ring(
        n, n_tokens=n, think_us=0, bootstrap_us=1_000,
        end_us=(1 << 50), with_observer=False, mailbox_cap=4)
    return sc, FixedDelay(500)


def _assert_ring_states_equal(rs, es, who):
    """The dense ring's exactness gate: ``es`` (an ``EdgeState``, or a
    fused state converted to one) equals ``EdgeEngine``'s ``rs`` field
    by field, bit for bit."""
    import numpy as np
    for f in ("wake", "q_rel", "q_pay", "delivered", "overflow",
              "steps", "time"):
        assert np.array_equal(
            np.asarray(jax.device_get(getattr(rs, f))),
            np.asarray(jax.device_get(getattr(es, f)))), \
            f"{who} diverged from EdgeEngine on {f}"
    for leaf in ("cnt", "val", "send_at"):
        assert np.array_equal(
            np.asarray(jax.device_get(rs.states[leaf])),
            np.asarray(jax.device_get(es.states[leaf]))), \
            f"{who} diverged from EdgeEngine on state.{leaf}"


def bench_token_ring_dense(n, steps):
    """Dense ring, think_us=0, on the fused Pallas engine
    (interp/jax_engine/fused_ring.py): one kernel per superstep, each
    state byte touched once. In-bench verification: 12 supersteps on
    the general EdgeEngine must reproduce the fused state
    BIT-FOR-BIT before the measured run counts (the fused engine's
    exactness law, tests/test_fused_ring.py)."""
    from timewarp_tpu.interp.jax_engine.edge_engine import EdgeEngine
    from timewarp_tpu.interp.jax_engine.fused_ring import FusedRingEngine

    n = n or 1 << 20
    sc, link = _dense_ring(n)
    if n % 8192 != 0:
        # the fused kernel's pipeline block shape needs n % 8192 == 0
        # (fused_ring.py); smaller smoke shapes run the XLA engine
        return bench_token_ring_dense_xla(n, steps)
    engine = FusedRingEngine(sc, link, cap=2, interpret=_SMOKE)
    ref = EdgeEngine(sc, link, cap=2)
    _assert_ring_states_equal(
        ref.run_quiet(12), engine.to_edge_state(engine.run_quiet(12)),
        "fused engine")
    delivered, dt, fin = _measure(engine, steps or 8192)
    assert int(fin.overflow) == 0, "measured run left the parity regime"
    return (f"token-ring dense (fused pallas superstep) "
            f"delivered-messages/sec/chip @{n} nodes", delivered / dt)


def bench_token_ring_dense_xla(n, steps):
    """The same dense ring on the general XLA edge engine — the
    pre-fusion baseline, kept measurable."""
    from timewarp_tpu.interp.jax_engine.edge_engine import EdgeEngine

    n = n or 1 << 20
    sc, link = _dense_ring(n)
    engine = EdgeEngine(sc, link, cap=2)
    delivered, dt, fin = _measure(engine, steps or 2048)
    # in-bench proof the measured run is in the parity regime: per-edge
    # capacity legitimately diverges from the oracle under overflow
    # (edge_engine.py warns), so the headline number must come from a
    # run with none — mirroring bench_gossip_100k's quiescence asserts
    for counter in ("overflow", "misrouted", "unrouted", "bad_delay"):
        v = int(getattr(fin, counter))
        assert v == 0, f"measured run left the parity regime: {counter}={v}"
    return (f"token-ring dense (xla edge engine) "
            f"delivered-messages/sec/chip @{n} nodes", delivered / dt)


def bench_token_ring_observer(n, steps):
    """The reference example's real shape (examples/token-ring/Main.hs:
    104-208): every token hop also notifies an observer hub —
    dynamic destinations, general engine. Dense-token regime with
    think quantized so rings fire co-temporally. The hub's inbox is
    the scenario's 8 slots: of the n notes an instant it keeps 8 and
    the engine counts the rest in ``overflow`` (the benchmark's cell
    ``ring_64k.observer`` gates every job on that count)."""
    from timewarp_tpu.interp.jax_engine.engine import JaxEngine
    from timewarp_tpu.models.token_ring import token_ring
    from timewarp_tpu.net.delays import FixedDelay

    n = n or (1 << 16)  # ring nodes; +1 observer
    sc = token_ring(
        n, n_tokens=n, think_us=1_000, bootstrap_us=1_000,
        end_us=(1 << 50), with_observer=True,
        mailbox_cap=8)
    engine = JaxEngine(sc, FixedDelay(500))
    steps = steps or 512
    # one whole ring cycle to warm up (timers, tokens, the hub), so the
    # measured run starts where a cycle does
    delivered, dt, fin = _measure(engine, steps, warm_steps=3)
    # in-bench proof of what the run drops: all n notes of a cycle
    # reach the hub at one instant, its inbox has 8 slots, and the
    # engine keeps the first 8 in arrival order and counts the rest.
    # A cycle's notes are sent on its second superstep, so the warm-up
    # and the measured run hold (steps + 4) // 3 such supersteps
    cycles = (steps + 4) // 3
    assert int(fin.overflow) == cycles * (n - 8), (
        f"the hub dropped {int(fin.overflow)} notes, not the "
        f"{cycles} x {n - 8} a bounded hub of 8 slots drops")
    for counter in ("bad_dst", "bad_delay", "short_delay", "route_drop"):
        v = int(getattr(fin, counter))
        assert v == 0, f"measured run lost messages elsewhere: {counter}={v}"
    return (f"token-ring observer (general engine; bounded hub, notes "
            f"past 8 an instant dropped and counted) "
            f"delivered-messages/sec/chip @{n} nodes", delivered / dt)


def _gossip_wave(n):
    """The gossip-wave workload: burst relays (all fanout peers in one
    firing — how a real node pushes over parallel connections) + an
    8 ms propagation floor licensing an 8-instant superstep window —
    the time-bucketed batching answer to the sparse broadcast ramp
    (JaxEngine.window)."""
    from timewarp_tpu.models.gossip import gossip, gossip_links
    from timewarp_tpu.net.delays import Quantize
    sc = gossip(n, fanout=8, think_us=2_000, burst=True,
                end_us=5_000_000, mailbox_cap=16)
    link = Quantize(gossip_links(median_us=20_000, sigma=0.6,
                                 floor_us=8_000), 1_000)
    return sc, link


def _assert_wave_done(engine, fin, n):
    """Genuine quiescence, not a window or deadline artifact: no
    events pending, the parity-regime counters are 0, and the
    epidemic covered the network up to the push-only miss floor (a
    node is missed with prob ~e^-fanout = e^-8 ≈ 3e-4; demanding
    literal 100% would assert against probability theory). Batched
    states are checked per WORLD — a truncated world must not hide
    behind the fleet aggregate."""
    import numpy as np
    from timewarp_tpu.core.scenario import NEVER
    # batched: per-world next-event times (vmap — _next_event mixes
    # the world-local epoch into the result); ALL worlds must quiesce
    nxt = jax.vmap(engine._next_event)(fin) \
        if getattr(engine, "batch", None) is not None \
        else engine._next_event(fin)
    assert int(np.asarray(jax.device_get(nxt)).min()) >= NEVER, \
        "broadcast did not quiesce inside the step budget"
    assert int(np.asarray(jax.device_get(fin.short_delay)).sum()) == 0, \
        "windowed run left the exact regime"
    assert int(np.asarray(jax.device_get(fin.route_drop)).sum()) == 0, \
        "routing dropped messages"
    hops = np.asarray(jax.device_get(fin.states["hop"]))
    for b, h in enumerate(hops.reshape(-1, hops.shape[-1])):
        missed = int((h < 0).sum())
        assert missed <= max(n // 500, 8), \
            f"wave truncated: {missed} nodes never infected (world {b})"


def _assert_batched_exact(batched, solo_factory, gate_steps=12):
    """The batch exactness law, in-bench (ISSUE 3 acceptance): for the
    first and last world, slicing the world out of a ``gate_steps``
    batched run must reproduce the solo engine's state BIT-FOR-BIT
    before any measured run counts (tests/test_world_batch.py is the
    CPU-side law; this runs it on the bench hardware)."""
    from timewarp_tpu.interp.jax_engine.batched import world_slice
    from timewarp_tpu.trace.events import assert_states_equal
    bs = batched.run_quiet(gate_steps)
    for b in (0, batched.batch.B - 1):
        ss = solo_factory(b).run_quiet(gate_steps)
        assert_states_equal(ss, world_slice(bs, b),
                            f"in-bench batch exactness gate, world {b}")


def bench_gossip_100k(n, steps):
    """One full broadcast wave, measured start to quiescence (the
    while_loop exits when the epidemic dies, so a large step budget
    costs nothing): whole-run average msg/s, ramp-up included."""
    from timewarp_tpu.interp.jax_engine.engine import JaxEngine

    n = n or 100_000
    sc, link = _gossip_wave(n)
    # window="auto" derives the widest exact window from the link's
    # declared 8 ms floor; adaptive sender-compacted routing sizes
    # the insertion stage per superstep on-device — no hand-measured
    # capacity constants
    engine = JaxEngine(sc, link, window="auto")
    delivered, dt, fin = _measure(engine, steps or (1 << 20))
    _assert_wave_done(engine, fin, n)
    return (f"gossip broadcast wave to quiescence (lognormal links) "
            f"delivered-messages/sec/chip @{n} nodes", delivered / dt)


def bench_gossip_100k_b8(n, steps):
    """The gossip wave as a FLEET: 8 seed-swept worlds through one
    batched engine (engine.py ``batch=BatchSpec`` — the world axis).
    Measured on a v5e at 2^17 (benchmark cell gossip_100k.fleet8;
    PERF.md, Findings PR 28): the AGGREGATE rate is 6.7e6 delivered
    msg/s, 0.95 of the solo wave's 7.1e6; each of the 94 iterations
    steps all 8 worlds at one shared rung of the routing ladder
    (12.9 ms, 1.61 ms a world against the solo superstep's 1.48 ms).
    Until PR 28 the ladder was pinned to its widest rung under vmap
    and the rate was 307 000 (ROADMAP U1).
    Gated in-bench by the batch exactness law before the measured run."""
    from timewarp_tpu.interp.jax_engine.engine import (BatchSpec,
                                                       JaxEngine)

    n = n or 100_000
    B = 8
    sc, link = _gossip_wave(n)
    spec = BatchSpec(seeds=tuple(range(B)))
    engine = JaxEngine(sc, link, window="auto", batch=spec)
    # solo twins use the batched engine's RESOLVED window ("auto"
    # resolves against the min over world links) — the law compares
    # like with like
    _assert_batched_exact(engine, lambda b: JaxEngine(
        sc, spec.world_link(link, b), seed=spec.seeds[b],
        window=engine.window))
    delivered, dt, fin = _measure(engine, steps or (1 << 20))
    _assert_wave_done(engine, fin, n)
    stats = engine.last_run_stats or {}
    assert int(stats.get("compiles", 0)) == 0, (
        f"a MEASURED rep recompiled the warmed executable: {stats} — "
        "per-world identity rides as traced operands precisely so "
        "the fleet executable compiles once (batched.WorldIdentity)")
    return (f"gossip broadcast wave fleet (batched x{B}) aggregate "
            f"delivered-messages/sec/chip @{n} nodes", delivered / dt,
            {"engine_builds": 1,
             "compiles": int(stats.get("compiles", 0))})


def bench_gossip_100k_chaos(n, steps):
    """Monte-Carlo chaos study: 8 gossip worlds, 8 DISTINCT fault
    schedules (reset crashes + a mid-run partition + a degradation
    window per world — faults/), one batched engine. Steady-state
    mongering (not the one-shot wave) so re-infection after heals is
    guaranteed and convergence is a meaningful property. Gated
    in-bench by the chaos-fleet exactness law (world-b slice ≡ solo
    run with that world's schedule, bit-for-bit) AND a robustness
    property check (deliveries continue after every world's faults
    clear; every world converges to full infection) before the
    measured run counts. Reports aggregate delivered-msg/s/chip plus
    per-world route_drop / fault_dropped in the JSON line (the
    never-silent contract on the world axis).
    Measured on a v5e at 2^17 (benchmark cell gossip_100k_chaos.fleet8;
    PERF.md, Findings PR 53), with ``end_us`` 160 ms and with 40
    mailbox slots for the 8 below: 4.74e6 delivered msg/s aggregate,
    22.3 s a job of 282 iterations at 78.3 ms of device time each,
    27.0 ms of it under the ``fault`` scopes; the same fleet without
    ``faults=`` 8.15 s and 164 iterations at 49.7 ms. **The cap**: the
    8 slots of this config lose messages silently (the gates below do
    not read ``overflow``); the plain reference holds up to 32
    messages pending to one node under the 3.75-fold window, so the
    cell runs ``mailbox_cap`` 40."""
    import numpy as np
    from timewarp_tpu.core.scenario import NEVER
    from timewarp_tpu.faults import (FaultFleet, FaultSchedule,
                                     LinkWindow, NodeCrash, Partition,
                                     eventually_delivered)
    from timewarp_tpu.interp.jax_engine.engine import (BatchSpec,
                                                       JaxEngine)
    from timewarp_tpu.models.gossip import gossip
    from timewarp_tpu.net.delays import Quantize, UniformDelay

    n = n or 100_000
    B = 8
    sc = gossip(n, fanout=1, think_us=1_000, gossip_interval=1_000,
                end_us=300_000, steady=True, mailbox_cap=8)
    link = Quantize(UniformDelay(500, 4_500), 1_000)
    half = n // 2
    heal_us = 0
    scheds = []
    for b in range(B):
        part_end = 70_000 + 2_000 * b
        crash_up = 60_000 + 5_000 * b
        # the LAST fault to clear in this world: the second crash
        # window runs to crash_up + 10 ms
        heal_us = max(heal_us, part_end, crash_up + 10_000)
        scheds.append(FaultSchedule((
            NodeCrash((7 * b + 3) % n, 20_000, crash_up,
                      reset_state=True),
            NodeCrash((11 * b + half + 5) % n, 30_000,
                      crash_up + 10_000),
            Partition((tuple(range(half)), tuple(range(half, n))),
                      25_000, part_end),
            LinkWindow(None, None, 80_000, 120_000,
                       scale=2.0 + 0.25 * b),
        )))
    fleet = FaultFleet(tuple(scheds))
    spec = BatchSpec(seeds=tuple(range(B)))
    engine = JaxEngine(sc, link, window="auto", batch=spec,
                       faults=fleet)
    # gate 1: the chaos-fleet exactness law on the bench hardware
    _assert_batched_exact(engine, lambda b: JaxEngine(
        sc, link, seed=spec.seeds[b], window=engine.window,
        faults=fleet.world_schedule(b)))
    # gate 2: robustness properties on a traced confirmation run —
    # traffic must still flow after every world's faults clear
    _, traces = engine.run(192)
    for b, tr in enumerate(traces):
        assert eventually_delivered(tr, heal_us), \
            f"world {b}: no deliveries after its faults healed"
    delivered, dt, fin = _measure(engine, steps or (1 << 20))
    # quiescence + parity-regime counters + convergence, per world
    nxt = jax.vmap(engine._next_event)(fin)
    assert int(np.asarray(jax.device_get(nxt)).min()) >= NEVER, \
        "chaos fleet did not quiesce inside the step budget"
    assert int(np.asarray(jax.device_get(fin.short_delay)).sum()) == 0, \
        "windowed run left the exact regime"
    route_drop = np.asarray(jax.device_get(fin.route_drop))
    fault_dropped = np.asarray(jax.device_get(fin.fault_dropped))
    assert int(route_drop.sum()) == 0, "routing dropped messages"
    hops = np.asarray(jax.device_get(fin.states["hop"]))
    for b in range(B):
        assert int(fault_dropped[b]) > 0, \
            f"world {b}: chaos schedule never bit (fault_dropped=0)"
        missed = int((hops[b] < 0).sum())
        assert missed <= max(n // 500, 8), \
            f"world {b} did not converge: {missed} nodes uninfected"
    extra = {"route_drop": route_drop.tolist(),
             "fault_dropped": fault_dropped.tolist()}
    return (f"gossip steady-state chaos fleet (batched x{B}, per-world "
            f"fault schedules) aggregate delivered-messages/sec/chip "
            f"@{n} nodes", delivered / dt, extra)


def bench_sweep_hetero(n, steps):
    """The fault-tolerant sweep service (sweep/, docs/sweeps.md) on a
    heterogeneous pack: token-ring seed+link sweeps (one world
    faulted, budgets differing) plus windowed burst-gossip worlds,
    shape-bucketed onto batched engines and run under the supervision
    loop with ONE injected transient failure (the retry path is
    exercised every time, not just in tests). Gated by the sweep
    survival law before the number counts: every streamed per-world
    result record — chained trace digest + never-silent counters —
    must be bit-identical to the solo run of that config. Runs the
    SAME pack twice — ``--pack first-fit`` and ``--pack predicted``
    (timewarp_tpu/pack/, docs/sweeps.md "Predictive packing") — and
    gates the packed leg in-bench: strictly better
    ``budget_efficiency``, no worse ``pad_waste_frac``, identical
    engine-build count, survival law on both legs, and one journaled
    ``pack_decision`` per bucket (first-fit journals none). Reports
    the packed leg's aggregate delivered-msg/s through the service
    (journal + atomic checkpoints included — service throughput, not
    bare engine throughput) with both legs' packing rollups on the
    line."""
    import shutil
    import tempfile

    from timewarp_tpu.sweep import SweepPack, SweepService, solo_result

    n = n or 4096
    steps = steps or 2000
    # the half-budget world's budget is the largest pow2 <= steps/2:
    # a pow2 budget drains on exact scan rungs, so the packing gate
    # below measures PACKING (which worlds share a bucket), not the
    # pow2 rung residue of an arbitrary odd budget
    half = max(8, 1 << (max(1, steps // 2).bit_length() - 1))
    ring = {"nodes": n, "n_tokens": max(4, n // 64), "think_us": 2000,
            "end_us": 1 << 40, "mailbox_cap": 8}
    gossip = {"nodes": n, "fanout": 4, "burst": True,
              "end_us": 400_000, "mailbox_cap": 16, "think_us": 700}
    pack = SweepPack.from_json([
        {"id": "ring-s0", "scenario": "token-ring", "params": ring,
         "link": "uniform:1000:5000", "seed": 0, "budget": steps},
        {"id": "ring-s1", "scenario": "token-ring", "params": ring,
         "link": "uniform:2000:7000", "seed": 1, "budget": half},
        {"id": "ring-chaos", "scenario": "token-ring", "params": ring,
         "link": "uniform:1000:5000", "seed": 2, "budget": steps,
         "faults": "crash:3:5ms:40ms:reset; partition:0-1|2-3:10ms:30ms"},
        {"id": "gos-s0", "scenario": "gossip", "params": gossip,
         "link": "quantize:1000:uniform:3000:9000", "seed": 3,
         "window": "auto", "budget": steps},
        {"id": "gos-s1", "scenario": "gossip", "params": gossip,
         "link": "quantize:1000:uniform:4000:8000", "seed": 4,
         "window": "auto", "budget": steps},
    ])
    from timewarp_tpu.sweep.journal import SweepJournal, util_rollup

    def leg(pack_mode):
        d = tempfile.mkdtemp(prefix="tw_sweep_bench_")
        try:
            t0 = time.perf_counter()
            # max_bucket=2 makes the packing decision REAL at this
            # pack's scale: the three token-ring worlds (budgets
            # steps, steps/2, steps in pack order) cannot share one
            # bucket, so first-fit pairs a half-budget world with a
            # full-budget one while predicted re-sorts the group
            # best-fit-decreasing and pairs like with like
            # pow2 chunk for the same reason as the pow2 half budget
            chunk = max(64, 1 << (max(1, steps // 8).bit_length() - 1))
            svc = SweepService(pack, d, chunk=chunk,
                               lint="off", inject="fail:2",
                               max_bucket=2, pack_mode=pack_mode)
            report = svc.run()
            dt = time.perf_counter() - t0
            assert report.ok, f"sweep failed: {report.to_json()}"
            assert report.retries >= 1, \
                "the injected transient failure never exercised " \
                "the retry path"
            # the survival law, world by world, on BOTH legs: packing
            # is pure throughput — streamed results must be
            # bit-identical to solo regardless of bucketing (the gate
            # deliberately costs a second pass)
            for rid, res in report.done.items():
                want = solo_result(pack.by_id(rid), lint="off")
                assert want == res, (
                    f"sweep survival law violated for {rid} "
                    f"({pack_mode}):\n"
                    f"  solo:     {want}\n  streamed: {res}")
            scan = SweepJournal(d).scan()
            roll = util_rollup(scan.util)
            builds = sum(int(u.get("engine_builds", 0))
                         for u in scan.util.values())
            return {"report": report, "dt": dt, "roll": roll,
                    "builds": builds,
                    "decisions": len(scan.pack_decisions),
                    "delivered": sum(r["delivered"]
                                     for r in report.done.values())}
        finally:
            shutil.rmtree(d, ignore_errors=True)

    ff = leg("first-fit")
    pr = leg("predicted")
    # the in-bench packing gate (docs/sweeps.md "Predictive
    # packing"): on the same pack, the packed leg must strictly win
    # budget efficiency, never lose pad waste, and build exactly as
    # many engines — packing changes WHERE worlds run, never what
    # they compute or how often anything compiles
    assert pr["roll"]["budget_efficiency"] \
            > ff["roll"]["budget_efficiency"], (
        f"predicted packing did not beat first-fit: "
        f"budget_efficiency {pr['roll']} vs {ff['roll']}")
    assert pr["roll"]["pad_waste_frac"] \
            <= ff["roll"]["pad_waste_frac"] + 1e-9, (
        f"predicted packing grew pad waste: {pr['roll']} "
        f"vs {ff['roll']}")
    assert pr["builds"] == ff["builds"], (
        f"packing changed engine build count: {pr['builds']} "
        f"predicted vs {ff['builds']} first-fit")
    assert ff["decisions"] == 0, \
        "first-fit journaled pack_decision records (the first-fit " \
        "plan is a pure function of the pack — nothing to journal)"
    assert pr["decisions"] == pr["report"].buckets, (
        f"predicted leg journaled {pr['decisions']} pack_decision "
        f"records for {pr['report'].buckets} buckets — the plan "
        "must be journaled one record per bucket before any starts")
    extra = {"worlds": pr["report"].total,
             "buckets": pr["report"].buckets,
             "retries": pr["report"].retries,
             "splits": pr["report"].splits,
             # the packing rollups (sweep/journal.py util_rollup) —
             # promoted to the ledger index so `ledger compare`
             # rate-gates packing regressions across rounds
             "budget_efficiency": pr["roll"]["budget_efficiency"],
             "pad_waste_frac": pr["roll"]["pad_waste_frac"],
             "first_fit_budget_efficiency":
                 ff["roll"]["budget_efficiency"],
             "first_fit_pad_waste_frac":
                 ff["roll"]["pad_waste_frac"],
             "pack_decisions": pr["decisions"]}
    return (f"heterogeneous sweep service (retry + stream + survival "
            f"law + predictive packing gate) aggregate "
            f"delivered-messages/sec @{n} nodes",
            pr["delivered"] / pr["dt"], extra)


def _bursty_gossip(n):
    """Density-varying workload for the dispatch-controller bench
    (dispatch/, docs/dispatch.md): burst-wave gossip with a long think
    incubation — quiet phases between fan-out storm generations — over
    an 8 ms-floor link, plus a mid-run degradation window that
    undercuts the floor to 2 ms. The scenario where no single static
    window can win: a static engine must validate against the
    schedule-wide degraded floor (2 ms) for the WHOLE run, while the
    controller runs the 8 ms bound and the per-superstep device clamp
    (faults/apply.window_floor) narrows exactly the supersteps the
    degradation window overlaps."""
    from timewarp_tpu.faults import FaultSchedule, LinkWindow
    from timewarp_tpu.models.gossip import gossip, gossip_links
    from timewarp_tpu.net.delays import Quantize
    sc = gossip(n, fanout=8, think_us=40_000, burst=True,
                end_us=5_000_000, mailbox_cap=16)
    link = Quantize(gossip_links(median_us=20_000, sigma=0.6,
                                 floor_us=8_000), 1_000)
    faults = FaultSchedule((LinkWindow(None, None, 100_000, 200_000,
                                       scale=0.25),))
    return sc, link, faults


def bench_gossip_100k_auto(n, steps):
    """The bursty gossip wave under the online dispatch controller
    (run_controlled: telemetry-driven window/rung/chunk adaptation,
    zero retrace). Gated in-bench by the REPLAY LAW — a second engine
    re-executing the emitted decision trace must reproduce the
    digests bit-for-bit — and by a deterministic structural win:
    fewer supersteps than the best static window (which the
    degradation window forces down to the schedule-wide floor).
    Reports ``controller_gain_frac`` vs the best single static
    config; the wall-clock half is asserted > 0 on full rounds only
    (smoke-scale CPU noise dwarfs it — the superstep win asserts
    everywhere)."""
    import numpy as np
    from timewarp_tpu.dispatch import DecisionTrace, DispatchController
    from timewarp_tpu.interp.jax_engine.engine import JaxEngine
    from timewarp_tpu.sweep.spec import DIGEST_ZERO, chain_digest
    from timewarp_tpu.trace.events import assert_states_equal

    n = n or 100_000
    steps = steps or (1 << 14)
    sc, link, faults = _bursty_gossip(n)
    eng = JaxEngine(sc, link, window="auto", faults=faults,
                    telemetry="counters", lint="off",
                    controller=DispatchController(chunk=16,
                                                  chunk_max=64))
    eng.run_controlled(steps)  # warmup: compiles + the decision trace
    decs = eng.last_run_decisions
    t0 = time.perf_counter()
    fin, tr = eng.run_controlled(steps)  # decisions replayed from made
    wall_auto = time.perf_counter() - t0
    delivered = int(np.asarray(jax.device_get(fin.delivered)).sum())
    # gate 1: the replay law — a fresh engine re-executing the
    # decision trace must match digests bit-for-bit
    rep = JaxEngine(sc, link, window="auto", faults=faults, lint="off",
                    controller=DispatchController(
                        mode="replay", replay=DecisionTrace.of(decs)))
    rfin, rtr = rep.run_controlled(steps)
    assert chain_digest(DIGEST_ZERO, tr) == chain_digest(DIGEST_ZERO,
                                                         rtr), \
        "controller run's digests diverge from its decision-trace " \
        "replay (the replay law)"
    assert_states_equal(fin, rfin, "controller replay law (bench)")
    _assert_wave_done(eng, fin, n)
    # best static config: the widest legal static window (the
    # schedule-wide degraded floor — construction refuses anything
    # wider under this schedule) and the classic window=1 engine.
    # Each gets its BEST driver — run_quiet's while_loop exits at
    # quiescence with no trace/telemetry work compiled in — so the
    # controller's chunked traced driver competes against the
    # strongest static baseline, not a strawman
    best_rate, best_name, static_steps = 0.0, "", None
    for name, w in (("static-auto", "auto"), ("window-1", 1)):
        st_eng = JaxEngine(sc, link, window=w, faults=faults,
                           lint="off")
        st_eng.run_quiet(steps)  # warmup compile
        t0 = time.perf_counter()
        sfin = st_eng.run_quiet(steps)
        dt = time.perf_counter() - t0
        sdel = int(np.asarray(jax.device_get(sfin.delivered)).sum())
        assert sdel == delivered, \
            f"static {name} delivered {sdel} != controller {delivered}"
        if sdel / dt > best_rate:
            best_rate, best_name = sdel / dt, name
        if name == "static-auto":
            static_steps = int(np.asarray(
                jax.device_get(sfin.steps)).max())
    # gate 2: deterministic structural win — the controller's wide
    # windows outside the degradation slice coalesce more instants
    assert len(tr) < static_steps, \
        f"controller ran {len(tr)} supersteps vs static-auto's " \
        f"{static_steps} — the window adaptation never bit"
    gain = delivered / wall_auto / best_rate - 1.0
    if not _SMOKE:
        assert gain > 0, \
            f"controller_gain_frac={gain:.4f} <= 0 vs {best_name}"
    extra = {"controller_gain_frac": round(gain, 4),
             "best_static": best_name,
             "supersteps_auto": len(tr),
             "supersteps_static": static_steps,
             "decisions": len(decs),
             "decision_windows": sorted({d.window_us for d in decs})}
    return (f"bursty gossip wave under the dispatch controller "
            f"(auto window/rung/chunk) delivered-messages/sec/chip "
            f"@{n} nodes", delivered / wall_auto, extra)


def bench_gossip_100k_spec(n, steps):
    """Optimistic time-warp execution on a long-tail link
    (speculate/, docs/speculation.md): bursty gossip over
    ``quantize:500:pareto:4000:1.2`` — Pareto delays supported on
    [4 ms, ∞) with a heavy upper tail, DECLARED floor the 500 µs
    quantize grid. The provable window serializes supersteps at
    500 µs while no sample ever lands below 4 ms; ``speculate="auto"``
    ladders the window into that gap, rolling back when a probe
    overshoots the distribution's real support. Gated in-bench by the
    SPECULATION EQUIVALENCE LAW (canonical surface — granularity-
    invariant trace aggregates + final-state sha — bit-identical to
    the conservative run, speculate/equiv.py) and by the
    deterministic structural win (strictly fewer supersteps).
    Reports ``speculation_gain_frac`` (supersteps saved) with the
    honest misspeculation ledger — rollback count and rate — on the
    BENCH_SCHEMA line; the wall-clock half is asserted > 0 on full
    rounds only (smoke-scale CPU noise dwarfs it, the
    gossip_100k_auto precedent)."""
    import numpy as np
    from timewarp_tpu.interp.jax_engine.engine import JaxEngine
    from timewarp_tpu.models.gossip import gossip
    from timewarp_tpu.net.delays import ParetoDelay, Quantize
    from timewarp_tpu.speculate import (assert_spec_equiv,
                                        canonical_rows)

    n = n or 100_000
    steps = steps or (1 << 14)
    sc = gossip(n, fanout=8, think_us=40_000, burst=True,
                end_us=5_000_000, mailbox_cap=16)
    link = Quantize(ParetoDelay(4_000, 1.2), 500)

    spec = JaxEngine(sc, link, window="auto", lint="off",
                     speculate="auto")
    spec.run_speculative(steps, chunk=64)   # warmup: compiles
    t0 = time.perf_counter()
    sfin, strc = spec.run_speculative(steps, chunk=64)
    wall_spec = time.perf_counter() - t0
    si = spec.last_run_speculation
    delivered = int(np.asarray(jax.device_get(sfin.delivered)).sum())
    _assert_wave_done(spec, sfin, n)

    # the conservative twin: same config, the widest PROVABLE static
    # window ("auto" = the declared floor). Traced run for the
    # equivalence gate + superstep count; run_quiet for the timing
    # baseline (its best driver — no strawman)
    cons = JaxEngine(sc, link, window="auto", lint="off")
    cfin, ctrc = cons.run(steps)
    _assert_wave_done(cons, cfin, n)
    assert int(np.asarray(jax.device_get(cfin.overflow)).sum()) == 0, \
        "overflow > 0: outside the windowed-exactness regime"
    # gate 1: the speculation equivalence law, bit-for-bit
    assert_spec_equiv(canonical_rows(cfin, ctrc),
                      canonical_rows(sfin, strc),
                      "gossip_100k_spec in-bench gate")
    cons.run_quiet(steps)                   # warmup the quiet driver
    t0 = time.perf_counter()
    cons.run_quiet(steps)
    wall_cons = time.perf_counter() - t0
    # gate 2: deterministic structural win — wide committed windows
    # coalesce instants the conservative floor serializes
    assert len(strc) < len(ctrc), \
        f"speculation ran {len(strc)} supersteps vs the " \
        f"conservative {len(ctrc)} — the window never widened"
    gain = 1.0 - len(strc) / len(ctrc)
    wall_gain = wall_cons / wall_spec - 1.0
    if not _SMOKE:
        assert wall_gain > 0, \
            f"speculation wall gain {wall_gain:.4f} <= 0"
    chunks = int(si["chunks"])
    rb = int(si["rollbacks"])
    extra = {"speculation_gain_frac": round(gain, 4),
             "wall_gain_frac": round(wall_gain, 4),
             "rollbacks": rb,
             "rollback_rate": round(rb / max(chunks + rb, 1), 4),
             "supersteps_spec": len(strc),
             "supersteps_conservative": len(ctrc),
             "windows": si["windows"],
             "floor_us": si["floor_us"]}
    return (f"bursty gossip on a heavy-tail pareto link under "
            f"optimistic time-warp execution (speculative windows + "
            f"causality rollback) delivered-messages/sec/chip "
            f"@{n} nodes", delivered / wall_spec, extra)


def bench_sweep_hetero_auto(n, steps):
    """The heterogeneous sweep with the windowed gossip worlds under
    ``controller: auto`` (sweep/: per-bucket decisions journaled
    before each chunk). Gated by the controller form of the sweep
    survival law: every streamed result must be bit-identical to the
    solo run REPLAYING the bucket's journaled decision chain — plus
    the plain law for the controller-off worlds."""
    import shutil
    import tempfile

    from timewarp_tpu.sweep import SweepPack, SweepService, solo_result

    n = n or 4096
    steps = steps or 2000
    ring = {"nodes": n, "n_tokens": max(4, n // 64), "think_us": 2000,
            "end_us": 1 << 40, "mailbox_cap": 8}
    gossip = {"nodes": n, "fanout": 4, "burst": True,
              "end_us": 400_000, "mailbox_cap": 16, "think_us": 700}
    pack = SweepPack.from_json([
        {"id": "ring-s0", "scenario": "token-ring", "params": ring,
         "link": "uniform:1000:5000", "seed": 0, "budget": steps},
        {"id": "gos-a0", "scenario": "gossip", "params": gossip,
         "link": "quantize:1000:uniform:3000:9000", "seed": 3,
         "window": "auto", "budget": steps, "controller": "auto"},
        {"id": "gos-a1", "scenario": "gossip", "params": gossip,
         "link": "quantize:1000:uniform:3000:9000", "seed": 4,
         "window": "auto", "budget": max(steps // 2, 8),
         "controller": "auto"},
        {"id": "gos-a2", "scenario": "gossip", "params": gossip,
         "link": "quantize:1000:uniform:4000:8000", "seed": 5,
         "window": "auto", "budget": steps, "controller": "auto"},
    ])
    d = tempfile.mkdtemp(prefix="tw_sweep_auto_")
    try:
        t0 = time.perf_counter()
        svc = SweepService(pack, d, chunk=max(16, steps // 16),
                           lint="off", inject="fail:2")
        report = svc.run()
        dt = time.perf_counter() - t0
        assert report.ok, f"sweep failed: {report.to_json()}"
        assert report.retries >= 1, \
            "the injected transient failure never exercised the retry"
        scan = svc.journal.scan()
        n_dec = sum(len(v) for v in scan.decisions.values())
        assert n_dec > 0, "controller bucket journaled no decisions"
        for rid, res in report.done.items():
            cfg = pack.by_id(rid)
            decs = svc.decisions_for_world(rid, scan) \
                if cfg.controller == "auto" else None
            want = solo_result(cfg, lint="off", decisions=decs)
            assert want == res, (
                f"controller sweep survival law violated for {rid}:\n"
                f"  solo:     {want}\n  streamed: {res}")
        delivered = sum(r["delivered"] for r in report.done.values())
        extra = {"worlds": report.total,
                 "controller_worlds": sum(
                     1 for c in pack.configs if c.controller == "auto"),
                 "decisions_journaled": n_dec,
                 "retries": report.retries}
    finally:
        shutil.rmtree(d, ignore_errors=True)
    return (f"heterogeneous sweep service with per-bucket dispatch "
            f"controller (decisions journaled + replay-verified) "
            f"aggregate delivered-messages/sec @{n} nodes",
            delivered / dt, extra)


def bench_search_gossip(n, steps):
    """Adversarial chaos search (timewarp_tpu/search/, docs/
    search.md): a seeded ChaosSearch campaign over fault-schedule
    space on burst gossip — generations of candidate schedules
    evaluated as shape-shared batched fleets, counterfactual forking
    (suffix continuations from a digest-verified mid-run snapshot),
    delta-minimization, and the repro artifact. Three gates before
    the number counts: the campaign must FIND a property violation
    (eventually-delivered — the rumor can be starved), the minimized
    repro must re-fail the property on a from-scratch solo
    evaluation (the replayability gate), and at least one fork must
    have saved real supersteps (``fork_saving_frac > 0``). Reports
    world evaluations/sec through the whole campaign (compiles,
    forks, minimization, and journaling included — this is search
    throughput, not bare engine throughput)."""
    import shutil
    import tempfile

    from timewarp_tpu.search import ChaosSearch
    from timewarp_tpu.search.objectives import rejudge_repro
    from timewarp_tpu.sweep.spec import RunConfig

    n = n or 64
    steps = steps or 300
    params = {"nodes": n, "fanout": 2, "end_us": 120_000,
              "burst": True, "think_us": 5000, "mailbox_cap": 16}
    base = RunConfig(run_id="search-base", family="gossip",
                     params=tuple(sorted(params.items())),
                     link="uniform:1000:5000", seed=0, window="auto",
                     budget=steps)
    d = tempfile.mkdtemp(prefix="tw_search_bench_")
    try:
        t0 = time.perf_counter()
        campaign = ChaosSearch(base=base,
                               objective="eventually-delivered",
                               population=8, generations=6, seed=2,
                               fork_k=2, journal_dir=d)
        result = campaign.run()
        dt = time.perf_counter() - t0
        assert result.found, (
            f"the seeded campaign failed to rediscover a violating "
            f"schedule: {result.to_json()}")
        assert result.fork["saving_frac"] > 0, (
            "counterfactual forking never saved a superstep: "
            f"{result.fork}")
        # the replayability gate: the emitted repro re-fails the
        # property on a fresh solo evaluation (the one shared
        # artifact-replay helper — search/objectives.rejudge_repro)
        rec = result.repro
        obj, violated, _ = rejudge_repro(rec)
        assert violated, (
            f"minimized repro {rec['faults']!r} does not re-fail "
            f"{obj.name}")
        evals = (result.evaluations + result.fork["fork_worlds"]
                 + result.fork["confirmations"] + 1)
        extra = {"evaluations": evals,
                 "generations": len(result.generations),
                 "found": True,
                 "fork_saving_frac": result.fork["saving_frac"],
                 "forks": result.fork["forks"],
                 "minimized": result.minimized,
                 "minimized_events": rec["events"]}
    finally:
        shutil.rmtree(d, ignore_errors=True)
    return (f"adversarial chaos search (campaign + counterfactual "
            f"fork + minimize + repro re-fail gate) world "
            f"evaluations/sec @{n} nodes", evals / dt, extra)


def bench_praos_1m_b4(n, steps):
    """Praos as a 4-world fleet sweeping BOTH seed and link model per
    world (lognormal median 18/20/22/24 ms — a Monte-Carlo link study
    in one engine, via BatchSpec.link_params), exactness-gated like
    the gossip fleet; aggregate delivered-msg/s/chip. The benchmark's
    cell ``praos_1m.fleet4`` is this row (PR 55), one slot a job from a
    seeded genesis to the quiescence of the slowest world, with the
    plain reference of every world's own seed and link beside it."""
    import numpy as np
    from timewarp_tpu.interp.jax_engine.engine import (BatchSpec,
                                                       JaxEngine)

    n = n or 1 << 20
    B = 4
    sc, link = _praos_consensus(n)
    spec = BatchSpec(
        seeds=tuple(range(B)),
        link_params={"inner.median_us": [18_000, 20_000,
                                         22_000, 24_000]})
    engine = JaxEngine(sc, link, window="auto", batch=spec)
    _assert_batched_exact(engine, lambda b: JaxEngine(
        sc, spec.world_link(link, b), seed=spec.seeds[b],
        window=engine.window))
    delivered, dt, fin = _measure(engine, steps or 256, warm_steps=16)
    assert int(np.asarray(jax.device_get(fin.overflow)).sum()) == 0, \
        "a mailbox overflowed: tips lost"
    assert int(np.asarray(jax.device_get(fin.short_delay)).sum()) == 0, \
        "windowed run left the exact regime"
    assert int(np.asarray(jax.device_get(fin.route_drop)).sum()) == 0, \
        "adaptive routing dropped messages"
    return (f"praos slot-leader consensus fleet (batched x{B}, link "
            f"sweep) aggregate delivered-messages/sec/chip "
            f"@{n} stake nodes", delivered / dt)


def bench_gossip_steady_1m(n, steps):
    """Rumor-mongering steady state: every infected node relays to one
    pseudo-random peer per 1 ms round — the dense dynamic-destination
    regime of the general engine (1M messages per superstep at 1M
    nodes, every one through the eager all-destination routing path:
    ``window`` 1 and one outbox slot, so no ladder).

    ``mailbox_cap`` 24, not the 8 this row had until PR 31: a push is in
    flight 1-5 rounds and a node receives one a round on average, so
    some three are in flight to a node at once and 8 slots dropped
    0.4 % of the messages, silently (PERF.md, Findings PR 31). The
    benchmark's cell ``gossip_steady_1m.rounds`` is this row with the
    plain reference beside it."""
    from timewarp_tpu.interp.jax_engine.engine import JaxEngine
    from timewarp_tpu.models.gossip import gossip
    from timewarp_tpu.net.delays import Quantize, UniformDelay

    n = n or 1 << 20
    sc = gossip(n, fanout=1, think_us=1_000, gossip_interval=1_000,
                end_us=(1 << 50), steady=True, mailbox_cap=24)
    link = Quantize(UniformDelay(500, 4_500), 1_000)
    engine = JaxEngine(sc, link)
    # warm through the infection ramp-up so the measured window is the
    # steady state: at 2^20 every node holds the rumor after superstep
    # 57-61 (PERF.md, Findings PR 31), so 64 left three supersteps
    delivered, dt, fin = _measure(engine, steps or 256, warm_steps=128)
    assert int(fin.overflow) == 0, "a mailbox overflowed: messages lost"
    assert int(fin.short_delay) == 0, "a flight shorter than the window"
    assert int(fin.route_drop) == 0, "routing dropped messages"
    return (f"gossip steady-state (rumor mongering) "
            f"delivered-messages/sec/chip @{n} nodes", delivered / dt)


def _praos_consensus(n):
    """The praos workload: burst diffusion (a fresh tip floods all
    fanout peers in one firing) + 8 ms propagation floor + 8 ms
    window — adoption instants spread by lognormal delays batch 8
    grid instants per superstep (exact — engine.py JaxEngine.window).
    The 150 ms delay cap bounds the straggler tail (a 60 s praos
    relay is not a network, it is an outage).

    ``mailbox_cap`` 24, not the 16 this row had until PR 33: at 2^20
    some 20 tips are in flight to one node at the height of a slot's
    flood, and 16 slots dropped the rest, silently (PERF.md, Findings
    PR 33). The benchmark's cell ``praos_1m.slots`` is this row, one
    slot a job (two took 2.82 s on the chip: PERF.md, Findings PR 33),
    with the plain reference beside it."""
    from timewarp_tpu.models.praos import praos
    from timewarp_tpu.net.delays import LogNormalDelay, Quantize
    sc = praos(n, slot_us=1_000_000, n_slots=1 << 30,
               leader_prob=4.0 / n, fanout=8, burst=True,
               mailbox_cap=24)
    link = Quantize(LogNormalDelay(20_000, 0.6, cap_us=150_000,
                                   floor_us=8_000), 1_000)
    return sc, link


def bench_praos_1m(n, steps):
    from timewarp_tpu.interp.jax_engine.engine import JaxEngine

    n = n or 1 << 20
    sc, link = _praos_consensus(n)
    # window="auto" (link's 8 ms floor) + adaptive routing: no
    # hand-measured capacity constants
    engine = JaxEngine(sc, link, window="auto")
    delivered, dt, fin = _measure(engine, steps or 256, warm_steps=16)
    assert int(fin.overflow) == 0, "a mailbox overflowed: tips lost"
    assert int(fin.short_delay) == 0, "windowed run left the exact regime"
    # invariant, not a tuning-knob guard (see bench_gossip_100k)
    assert int(fin.route_drop) == 0, "adaptive routing dropped messages"
    return (f"praos slot-leader consensus "
            f"delivered-messages/sec/chip @{n} stake nodes",
            delivered / dt)


def _verify_detection_gate(make_engine, budget=64, chunk=8):
    """The detection law, in-bench (integrity/, ISSUE 10 acceptance):
    one seeded flip injected between chunks of a digest-mode run must
    be DETECTED (>= 1 rollback) and the recovered run bit-identical —
    states, traces, digest chain — to a clean run. Runs before any
    measured number counts, like every other in-bench gate."""
    from timewarp_tpu.integrity import FlipInjector
    from timewarp_tpu.trace.events import (assert_states_equal,
                                           assert_traces_equal)
    clean = make_engine("digest")
    fc, tc = clean.run_verified(budget, chunk=chunk)
    injected = make_engine("digest")
    inj = FlipInjector("flip:7:2")
    fi, ti = injected.run_verified(budget, chunk=chunk, inject=inj)
    assert inj.fired, "flip never fired (fewer than 2 chunks ran)"
    assert injected.last_run_integrity["rollbacks"] >= 1, \
        "injected flip went UNDETECTED (the detection law is broken)"
    assert_traces_equal(tc, ti, "clean", "recovered")
    assert_states_equal(fc, fi, "in-bench detection-law gate")
    assert clean.last_run_stats["digest_chain"] \
        == injected.last_run_stats["digest_chain"], \
        "recovered digest chain diverged from the clean run's"


def bench_gossip_100k_verify(n, steps):
    """Self-verifying execution (integrity/, docs/integrity.md): the
    gossip wave through the verified chunked driver under every
    verify mode, reporting ``verify_overhead_frac`` per mode vs the
    same driver with verify off. Gated in-bench by the detection law
    (one injected flip -> detected + bit-exact recovery) and by the
    digest-mode overhead budget: <= 10% strict on a chip-attached
    round; on CPU/smoke a ratio of two wall-clock medians is the
    host's noise (it failed a 2x bound beside five busy test workers:
    ROADMAP D27), so there the measured fractions ride the JSON line
    and nothing of the clock is asserted."""
    import statistics

    from timewarp_tpu.interp.jax_engine.engine import JaxEngine

    n = n or 100_000
    sc, link = _gossip_wave(n)

    def make(mode):
        return JaxEngine(sc, link, window="auto", lint="off",
                         verify=mode)

    _verify_detection_gate(make)
    budget = steps or (1 << 20)
    chunk = 256

    def med(mode, reps=2):
        eng = make(mode)
        eng.run_verified(budget, chunk=chunk)   # warm the compiles
        walls = []
        for _ in range(reps):
            t0 = time.perf_counter()
            fin, _tr = eng.run_verified(budget, chunk=chunk)
            walls.append(time.perf_counter() - t0)
        return statistics.median(walls), fin, eng

    w_off, fin, eng_off = med("off")
    _assert_wave_done(eng_off, fin, n)
    import numpy as np
    delivered = int(np.asarray(jax.device_get(fin.delivered)).sum())
    overheads = {}
    for mode in ("guard", "digest", "shadow"):
        w_mode, fin_m, eng_m = med(mode)
        assert eng_m.last_run_integrity["rollbacks"] == 0, \
            f"verify={mode} false positive on a clean run"
        overheads[mode] = round(w_mode / w_off - 1.0, 4)
    if not _SMOKE:
        assert overheads["digest"] <= 0.10, (
            f"verify='digest' costs {overheads['digest']:.1%} — over "
            "the 10% budget (integrity/ overhead contract)")
    return (f"gossip broadcast wave to quiescence (verified chunked "
            f"driver, verify=off) delivered-messages/sec/chip "
            f"@{n} nodes", delivered / w_off,
            {"verify_overhead_frac": overheads})


def bench_gossip_100k_record(n, steps):
    """Causal flight recorder (obs/flight.py, docs/observability.md):
    the gossip wave through the traced chunked driver under every
    record mode, reporting ``record_overhead_frac`` per mode vs the
    same driver with record off. Gated in-bench by the record
    exactness law (off ≡ deliveries ≡ full, bit-for-bit on states
    AND trace rows, before any measured number counts) and by the
    deliveries-mode overhead budget: <= 10% on a chip-attached round
    — the slim deliveries row is one cumsum + searchsorted compaction
    per superstep (obs/flight.py ``record_deliveries``). On the smoke
    path — like ``gossip_100k_verify`` — the ratio of two host-clock
    medians rides the JSON line and is not asserted (ROADMAP D27).
    Full mode
    (sends + fault captures across the routing switch) rides the
    JSON line honestly, ungated. Event/drop counts are reported too:
    a nonzero ``dropped`` means the wave peak outran ``record_cap``
    (counted, never silent — obs/flight.py)."""
    import statistics

    import numpy as np

    from timewarp_tpu.interp.jax_engine.engine import JaxEngine
    from timewarp_tpu.trace.events import (assert_states_equal,
                                           assert_traces_equal)

    n = n or 100_000
    sc, link = _gossip_wave(n)
    cap = 4096

    def make(mode):
        return JaxEngine(sc, link, window="auto", lint="off",
                         record=mode, record_cap=cap)

    # the exactness gate: every mode is the same emulation
    off = make("off")
    f_off, tr_off = off.run(24)
    for mode in ("deliveries", "full"):
        eng = make(mode)
        f, tr = eng.run(24)
        assert_traces_equal(tr_off, tr, "record-off",
                            f"record-{mode}")
        assert_states_equal(f_off, f, f"record={mode} exactness gate")

    budget = steps or (1 << 20)
    chunk = 256

    def drive(eng):
        # the chunked traced drive a recorded run actually uses (the
        # whole-budget scan would materialize a [budget, cap] event
        # plane; chunking bounds it at [chunk, cap], drained per
        # chunk like run_stream/run_verified do)
        st = eng.init_state()
        done = events = dropped = 0
        while done < budget:
            step = int(min(chunk, budget - done))
            st, tr = eng.run(step, state=st)
            done += len(tr)
            log = eng.last_run_flight
            if log is not None:
                events += len(log)
                dropped += log.dropped
            if len(tr) < step:      # quiesced inside the chunk
                break
        return st, events, dropped

    def med(mode, reps=3):
        eng = make(mode)
        drive(eng)                  # warm the compiles
        walls, out = [], None
        for _ in range(reps):
            t0 = time.perf_counter()
            out = drive(eng)
            walls.append(time.perf_counter() - t0)
        return statistics.median(walls), out, eng

    w_off, (fin, _, _), eng_off = med("off")
    _assert_wave_done(eng_off, fin, n)
    delivered = int(np.asarray(jax.device_get(fin.delivered)).sum())
    overheads, counts = {}, {}
    for mode in ("deliveries", "full"):
        w_m, (_f, events, dropped), _e = med(mode)
        overheads[mode] = round(w_m / w_off - 1.0, 4)
        counts[mode] = {"events": events, "dropped": dropped}
    if not _SMOKE:
        assert overheads["deliveries"] <= 0.10, (
            f"record='deliveries' costs {overheads['deliveries']:.1%} "
            "on the traced chunked driver — over the 10% budget "
            "(obs/flight.py overhead contract)")
    return (f"gossip broadcast wave to quiescence (traced chunked "
            f"driver, record=off) delivered-messages/sec/chip "
            f"@{n} nodes", delivered / w_off,
            {"record_overhead_frac": overheads,
             "record_events": counts, "record_cap": cap})


def bench_serve_gossip(n, steps):
    """Emulation as a service (serve/, docs/serving.md): a
    work-stealing curator thread plus an in-process admission book —
    the serving layer WITHOUT the TCP hop, so the number isolates the
    machinery (admission journaling, lease renewal, open-bucket
    engine rebuilds, checkpoints, result streaming) from loopback
    latency; the CI serve-smoke job measures the wire path. Eight
    gossip configs (heterogeneous seeds + budgets, one faulted) are
    submitted against ONE 8-slot open bucket — half up front, half
    mid-bucket while the first chunks run, so admission-into-reserved-
    slots is exercised every round AGAINST A WARM EXECUTABLE: the
    zero-recompile law (identity as traced operands, serve/worker.py)
    is gated in-bench — the journaled ``bucket_util`` must report
    ``engine_builds == 1`` across every mid-bucket admission, and
    both counters ride the JSON line so the ledger can gate
    ``admit_per_s`` against its causal explanation. Reports
    end-to-end served configs/sec (first admit -> last world_done,
    journal ts) plus admission throughput and p50/p95
    submit->world_done latency on the BENCH_SCHEMA=2 line. Gated by
    the extended survival law before the number counts: every
    streamed record's result must be bit-identical to the solo run
    of its config. Runs TWO legs — ``--pack first-fit`` then ``--pack
    predicted`` with a forecaster fitted in-bench from the first
    leg's own results (training_rows -> fit_rows, pack/predict.py) —
    and gates the predicted leg: one journaled ``pack_decision`` per
    admission BEFORE its admit record naming the bucket the admit
    landed in, engine builds unchanged, survival law on both legs.
    Both legs' ``budget_efficiency``/``pad_waste_frac`` rollups ride
    the line for `ledger compare` (the strict packed-vs-first-fit
    win is gated where the plan is deterministic —
    ``bench_sweep_hetero``)."""
    import shutil
    import tempfile
    import threading

    from timewarp_tpu.serve.curator import ServeCurator
    from timewarp_tpu.serve.frontend import ServeFrontend
    from timewarp_tpu.sweep import SweepJournal
    from timewarp_tpu.sweep.spec import RunConfig, solo_result

    n = n or 4096
    steps = steps or 2000
    gossip = {"nodes": n, "fanout": 4, "burst": True,
              "end_us": 400_000, "mailbox_cap": 16, "think_us": 700}
    cfgs = []
    for i in range(8):
        d = {"id": f"w{i}", "scenario": "gossip", "params": gossip,
             "link": "quantize:1000:uniform:3000:9000", "seed": i,
             "budget": steps if i % 2 == 0 else max(steps // 2, 8)}
        if i == 3:
            d["faults"] = "crash:1:5ms:40ms:reset"
        cfgs.append(d)
    from timewarp_tpu.sweep.journal import util_rollup

    def leg(pack_mode, artifact=None):
        root = tempfile.mkdtemp(prefix="tw_serve_bench_")
        try:
            journal = SweepJournal(root, host="bench")
            front = ServeFrontend(journal, "bench", ("127.0.0.1", 0),
                                  slots=8, pack_mode=pack_mode,
                                  pack_artifact=artifact)
            cur = ServeCurator(root, "bench",
                               chunk=max(32, steps // 8),
                               lint="off", lease_ttl_s=60.0,
                               poll_s=0.02, journal=journal,
                               pack_mode=pack_mode,
                               pack_artifact=artifact)
            t0 = time.perf_counter()
            for d in cfgs[:4]:
                front.admit(d)
            admit_half = time.perf_counter()
            worker = threading.Thread(target=cur.run, daemon=True)
            worker.start()
            # mid-bucket admission: the curator is already running
            # the first chunks when these land in the reserved slots
            for d in cfgs[4:]:
                front.admit(d)
            admit_done = time.perf_counter()
            journal.append({"ev": "serve_drain", "host": "bench"})
            worker.join(timeout=600)
            assert not worker.is_alive(), "serve curator never drained"
            dt = time.perf_counter() - t0
            scan = SweepJournal(root).scan()
            assert sorted(scan.done) == sorted(d["id"] for d in cfgs), \
                f"unserved worlds: {sorted(scan.done)}"
            # the extended survival law, world by world, on BOTH legs
            # (the gate deliberately costs a second pass —
            # docs/serving.md): placement policy changes WHERE a world
            # runs, never what it streams
            for d in cfgs:
                cfg = RunConfig.from_json(d, 0)
                want = solo_result(cfg, lint="off")
                got = scan.done[d["id"]]
                assert want == got, (
                    f"serve survival law violated for {d['id']} "
                    f"({pack_mode}):\n"
                    f"  solo:     {want}\n  streamed: {got}")
            # submit->world_done latency per world from the journal's
            # own ts stamps (admit append -> world_done append, one
            # clock)
            t_admit, t_done = {}, {}
            for e in scan.events:
                if e.get("ev") == "admit" \
                        and e["run_id"] not in t_admit:
                    t_admit[e["run_id"]] = float(e["ts"])
                elif e.get("ev") == "world_done":
                    t_done[e["result"]["run_id"]] = float(e["ts"])
            lats = sorted(t_done[r] - t_admit[r] for r in t_done)
            p50 = lats[len(lats) // 2]
            p95 = lats[min(len(lats) - 1, int(len(lats) * 0.95))]
            delivered = sum(r["delivered"]
                            for r in scan.done.values())
            # the zero-recompile serving gate, pinned on BOTH legs: 4
            # of the 8 configs landed mid-bucket (one faulted,
            # fault-pad-compatible with the warmup build), yet each
            # bucket's executable compiled ONCE — admission is an
            # operand write, never a rebuild, whichever bucket the
            # placement policy picked
            builds = {b: u.get("engine_builds")
                      for b, u in scan.util.items()}
            assert builds and all(v == 1 for v in builds.values()), (
                f"mid-bucket admission rebuilt an engine ("
                f"{pack_mode}): {builds} — the zero-recompile "
                "serving law (serve/worker.py rebind_identity)")
            compiles = sum(int(u.get("compiles", 0))
                           for u in scan.util.values())
            return {
                "dt": dt, "scan": scan,
                "roll": util_rollup(scan.util),
                "admit_per_s": round(
                    len(cfgs) / max(1e-9, (admit_half - t0)
                                    + (admit_done - admit_half)), 2),
                "p50": p50, "p95": p95,
                "builds": sum(builds.values()),
                "compiles": compiles, "delivered": delivered,
            }
        finally:
            shutil.rmtree(root, ignore_errors=True)

    ff = leg("first-fit")
    # fit the superstep forecaster from the first leg's own journal —
    # the full training loop (training_rows -> fit_rows) exercised
    # in-bench, exactly what `ledger add` + `pack fit` assemble
    from timewarp_tpu.pack import fit_rows, training_rows
    rows = training_rows(
        [RunConfig.from_json(d, 0) for d in cfgs], ff["scan"].done)
    assert len(rows) == len(cfgs), \
        f"training_rows dropped worlds: {len(rows)}/{len(cfgs)}"
    art = fit_rows(rows)
    pr = leg("predicted", artifact=art)
    # the predictive-placement gate: every admission journaled ONE
    # pack_decision BEFORE its admit record (decision-before-effect),
    # naming the bucket the admit then landed in; first-fit journals
    # nothing (its placement is a pure function of admission order)
    assert not ff["scan"].pack_decisions, \
        "first-fit leg journaled pack_decision records"
    places = {d["run_id"]: d for d in pr["scan"].pack_decisions
              if d.get("kind") == "place"}
    assert sorted(places) == sorted(x["id"] for x in cfgs), (
        f"predicted leg journaled placements for {sorted(places)}, "
        f"admitted {sorted(x['id'] for x in cfgs)}")
    for rid, a in pr["scan"].admits.items():
        if "repacked_from" in a:
            continue
        assert places[rid]["bucket"] == a["bucket"], (
            f"pack_decision for {rid} named bucket "
            f"{places[rid]['bucket']} but the admit landed in "
            f"{a['bucket']} — the journaled decision must BE the "
            "placement")
    # packing rollups on both legs: with one 8-slot bucket the two
    # policies pack identically, so the packed leg must not LOSE
    # anything — the strict packed-vs-first-fit win is gated where
    # the plan is deterministic (bench_sweep_hetero); here the gate
    # pins that predicted placement + its journaling perturb nothing
    assert pr["builds"] == ff["builds"], (
        f"placement policy changed engine build count: "
        f"{pr['builds']} predicted vs {ff['builds']} first-fit")
    extra = {
        "worlds": len(cfgs),
        "admit_per_s": pr["admit_per_s"],
        "submit_p50_s": round(pr["p50"], 4),
        "submit_p95_s": round(pr["p95"], 4),
        "buckets": len(pr["scan"].serve_buckets),
        "engine_builds": pr["builds"],
        "compiles": pr["compiles"],
        "delivered_per_s": round(pr["delivered"] / pr["dt"], 2),
        # the packing rollups (sweep/journal.py util_rollup) —
        # promoted to the ledger index so `ledger compare` rate-gates
        # packing regressions across rounds
        "budget_efficiency": pr["roll"]["budget_efficiency"],
        "pad_waste_frac": pr["roll"]["pad_waste_frac"],
        "first_fit_budget_efficiency":
            ff["roll"]["budget_efficiency"],
        "first_fit_pad_waste_frac": ff["roll"]["pad_waste_frac"],
        "pack_decisions": len(pr["scan"].pack_decisions),
        "predictor_sha": art["sha"][:12],
    }
    return (f"emulation service (admission + open buckets + stream + "
            f"survival law + predictive placement) served "
            f"configs/sec @{n} nodes", len(cfgs) / pr["dt"], extra)


def bench_lint_sweep(n, steps):
    """Fleet-scale static verification (analysis/, docs/sweeps.md +
    docs/serving.md "Pre-flight verification"): time the three pass
    families a fleet pays BEFORE any engine builds — the scenario
    sanitizer sweep over every shipped model (the same sweep as this
    bench's own pre-run gate), the plan lint over every example pack
    (bucket/width/window prediction, fault-pad rebuild detection,
    fault-aware capacity proofs), and the jaxpr determinism sweep
    over every shipped engine x observability mode (TW7xx scans plus
    the TW705 off-mode neutrality proofs). Gated in-bench both ways:
    the shipped models, the clean example packs, and the jaxpr sweep
    must lint ZERO errors, and the doomed example pack must FAIL —
    the refusal corpus staying refused is as much a contract as the
    clean corpus staying clean. Reports verified subjects+configs/sec
    with per-surface second splits on the BENCH_SCHEMA=2 line: the
    honest price of refuse-before-run at sweep-prepare/admission
    time."""
    import glob as globlib

    from timewarp_tpu.analysis import lint_pack_path
    from timewarp_tpu.cli import jaxpr_sweep, lint_sweep

    n = n or 64
    here = os.path.dirname(os.path.abspath(__file__))
    packs = sorted(globlib.glob(
        os.path.join(here, "examples", "packs", "*.json")))
    assert packs, "examples/packs/*.json missing"
    t0 = time.perf_counter()
    subjects, rep = lint_sweep(nodes=n)
    assert rep.ok, f"shipped models failed lint:\n{rep.render()}"
    t1 = time.perf_counter()
    configs = 0
    for path in packs:
        n_entries, prep = lint_pack_path(path)
        configs += n_entries
        if os.path.basename(path).startswith("doomed"):
            assert not prep.ok, (
                f"{path}: the doomed refusal corpus linted GREEN — "
                "the refuse-before-run gate has gone blind")
        else:
            assert prep.ok, (
                f"{path}: shipped example pack failed the plan "
                f"lint:\n{prep.render()}")
    t2 = time.perf_counter()
    # abstract tracing: the driver's primitive inventory does not
    # change with fleet width, so the jaxpr sweep stays at 8 nodes
    jx_subjects, jx_rep = jaxpr_sweep(nodes=8)
    assert jx_rep.ok, (
        f"jaxpr determinism sweep failed:\n{jx_rep.render()}")
    assert any(f.code == "TW705" for f in jx_rep.infos), \
        "no TW705 neutrality proofs in the jaxpr sweep"
    t3 = time.perf_counter()
    total = subjects + configs + jx_subjects
    extra = {
        "lint_subjects": subjects,
        "pack_files": len(packs),
        "pack_configs": configs,
        "jaxpr_subjects": jx_subjects,
        "sanitizer_s": round(t1 - t0, 2),
        "plan_s": round(t2 - t1, 2),
        "jaxpr_s": round(t3 - t2, 2),
    }
    return (f"static pre-flight verification (sanitizer + plan lint "
            f"+ jaxpr determinism sweep, refusal corpus gated) "
            f"verified subjects/sec @{n} nodes",
            total / (t3 - t0), extra)


CONFIGS = {
    "token_ring_dense": bench_token_ring_dense,
    "token_ring_dense_xla": bench_token_ring_dense_xla,
    "token_ring_observer": bench_token_ring_observer,
    "gossip_100k": bench_gossip_100k,
    "gossip_100k_b8": bench_gossip_100k_b8,
    "gossip_100k_chaos": bench_gossip_100k_chaos,
    "gossip_100k_auto": bench_gossip_100k_auto,
    "gossip_100k_spec": bench_gossip_100k_spec,
    "gossip_100k_verify": bench_gossip_100k_verify,
    "gossip_100k_record": bench_gossip_100k_record,
    "gossip_steady_1m": bench_gossip_steady_1m,
    "praos_1m": bench_praos_1m,
    "praos_1m_b4": bench_praos_1m_b4,
    "sweep_hetero": bench_sweep_hetero,
    "sweep_hetero_auto": bench_sweep_hetero_auto,
    "search_gossip": bench_search_gossip,
    "serve_gossip": bench_serve_gossip,
    "lint_sweep": bench_lint_sweep,
}

#: --smoke shapes: every config tiny enough for a CPU CI runner, all
#: in-bench exactness gates live (the fused ring's 8192-node floor
#: pins that row's size)
SMOKE = {
    "token_ring_dense": (8192, 16),
    "token_ring_dense_xla": (4096, 32),
    "token_ring_observer": (1024, 32),
    "gossip_100k": (2048, 1 << 14),
    "gossip_100k_b8": (1024, 1 << 14),
    "gossip_100k_chaos": (1024, 1 << 14),
    "gossip_100k_auto": (1024, 1 << 14),
    "gossip_100k_spec": (1024, 1 << 14),
    "gossip_100k_verify": (1024, 1 << 14),
    "gossip_100k_record": (1024, 1 << 14),
    "gossip_steady_1m": (4096, 16),
    "praos_1m": (2048, 24),
    "praos_1m_b4": (1024, 24),
    "sweep_hetero": (256, 96),
    "sweep_hetero_auto": (256, 96),
    "search_gossip": (64, 300),
    "serve_gossip": (256, 96),
    "lint_sweep": (64, 1),
}


def _calibrate():
    """Session-condition fingerprint: a frozen XLA kernel (64 rounds of
    ``lax.sort`` over 2^20 int32 — the op profile that dominates the
    general engine) whose code must NEVER change across rounds.
    Comparing the ``calib`` field across runs separates session
    variance from actual framework changes."""
    import jax.numpy as jnp
    from jax import lax

    @jax.jit
    def kern(x):
        def body(i, x):
            return lax.sort(x * jnp.int32(1103515245) + i)
        return lax.fori_loop(jnp.int32(0), jnp.int32(64), body, x)

    x = jnp.arange(1 << 20, dtype=jnp.int32)
    int(kern(x)[0])  # compile; the readback is the sync
    t0 = time.perf_counter()
    int(kern(x)[0])
    dt = time.perf_counter() - t0
    return {"kernel": "sort_1m_int32_x64", "seconds": round(dt, 4)}


def _lint_gate() -> None:
    """Scenario sanitizer sweep (timewarp_tpu.analysis) over every
    shipped model and program twin before any config runs: a bench
    number for a contract-violating scenario is a number about
    nothing. Same sweep as CI's `lint` job — but silent on success,
    so the bench contract (one JSON line per config/run on stdout)
    holds."""
    from timewarp_tpu.cli import lint_sweep
    _, report = lint_sweep()
    if not report.ok:
        sys.stderr.write(report.render() + "\n")
        raise SystemExit(
            "bench: error-severity lint findings in shipped models "
            "(run `timewarp-tpu lint` for the report)")


def smoke() -> None:
    """CI fast path: every config at its SMOKE shape, exactness gates
    on, one JSON line each. Throughput numbers at smoke scale are
    meaningless and marked so — the value of this mode is that a
    kernel-vs-engine divergence or a broken parity-regime invariant
    raises before a full bench round ever runs. TW_BENCH_CONFIG (a
    comma-separated subset) restricts the sweep — the regression-gate
    CI job runs a cheap two-config smoke twice into a ledger rather
    than paying for the full sweep twice."""
    _lint_gate()
    env = _env_fields()
    cfgs = SMOKE
    only = os.environ.get("TW_BENCH_CONFIG")
    if only:
        names = [s.strip() for s in only.split(",") if s.strip()]
        unknown = sorted(set(names) - set(SMOKE))
        if unknown:
            raise SystemExit(
                f"TW_BENCH_CONFIG names unknown configs {unknown}; "
                f"choose from {sorted(SMOKE)}")
        cfgs = {k: SMOKE[k] for k in names}
    for cfg, (n, steps) in cfgs.items():
        t0 = time.perf_counter()
        metric, _rate, extra = _run_config(cfg, n, steps)
        _emit({
            "config": cfg, "config_key": _config_key(cfg, n, steps),
            "metric": metric, "smoke": True,
            "ok": True, "seconds": round(time.perf_counter() - t0, 1),
            **env, **extra,
        })


def _run_config(cfg, n, steps):
    """Run one config; normalize its return to (metric, rate, extra).
    ``extra`` is a dict of additional JSON-line fields (the chaos
    config reports per-world route_drop / fault_dropped — the
    never-silent contract on the world axis)."""
    res = CONFIGS[cfg](n, steps)
    metric, rate = res[0], res[1]
    extra = res[2] if len(res) > 2 else {}
    return metric, rate, extra


def _parse_ledger() -> None:
    """--ledger DIR: auto-append every emitted line to the cross-run
    ledger (obs/ledger.py) under one fresh batch label per
    invocation, so `timewarp-tpu ledger compare` can gate this run
    against any earlier one."""
    if "--ledger" not in sys.argv:
        return
    try:
        d = sys.argv[sys.argv.index("--ledger") + 1]
    except IndexError:
        raise SystemExit("--ledger takes a ledger directory")
    if d.startswith("--"):
        raise SystemExit(f"--ledger takes a ledger directory, "
                         f"got {d!r}")
    from timewarp_tpu.obs.ledger import RunLedger
    global _LEDGER
    led = RunLedger(d)
    _LEDGER = (led, led.new_batch())


def main() -> None:
    jaxconfig.enable_compile_cache()
    _parse_ledger()
    if "--smoke" in sys.argv:
        if "--reps" in sys.argv:
            # never-silent knob convention: smoke's value is its gates,
            # not its (meaningless-at-smoke-scale) rates — a dropped
            # rep count must not masquerade as a median-of-K number
            raise SystemExit("--reps applies to measured runs only; "
                             "--smoke rates are not measurements")
        global _SMOKE
        _SMOKE = True
        smoke()
        return
    _require_chip("the measured path")
    _lint_gate()
    reps = 1
    if "--reps" in sys.argv:
        # median-of-K measurement: whole-run rates swing from run to
        # run, so a single rep cannot honestly rank batched vs solo
        # — report the median with the spread
        try:
            reps = int(sys.argv[sys.argv.index("--reps") + 1])
        except (IndexError, ValueError):
            raise SystemExit("--reps takes an integer rep count K")
        if reps < 1:
            raise SystemExit(f"--reps must be >= 1, got {reps}")
    cfg = os.environ.get("TW_BENCH_CONFIG", "token_ring_dense")
    n = int(os.environ.get("TW_BENCH_NODES", 0)) or None
    steps = int(os.environ.get("TW_BENCH_STEPS", 0)) or None
    global _REPS
    _REPS = reps  # _measure repeats the window; gates/compiles run once
    metric, rate, extra = _run_config(cfg, n, steps)
    out = {
        "config": cfg,
        "config_key": _config_key(cfg, n, steps),
        "metric": metric,
        "value": round(rate, 1),  # the median-of-K rate (K = --reps)
        "unit": "msg/s",
        "vs_baseline": round(rate / 1e8, 4),
        **_env_fields(),
        **extra,
    }
    if reps > 1:
        out["reps"] = reps
        out["min"] = round(_SPREAD["min"], 1)
        out["max"] = round(_SPREAD["max"], 1)
    out["calib"] = _calibrate()
    _emit(out)


if __name__ == "__main__":
    main()
