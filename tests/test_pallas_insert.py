"""The insert= knob's exactness law (pallas_insert.py, round 12):
every insertion strategy — ``"xla"`` flat scatters (default),
``"xla2d"`` 2D scatter form (the promoted ``TW_FLAT_SCATTER`` escape
hatch), and the Pallas fire-compaction + in-tile insertion kernels
(``"interpret"`` on this CPU test platform; ``"pallas"`` on a chip) —
produces bit-identical ``EngineState``, traces, and digests on the
same configuration, *including under faults, telemetry, and the world
axis*. ``JaxEngine`` is itself pinned to the host oracle
(tests/test_parity.py), so the chain pallas ≡ xla ≡ oracle covers the
kernels; the real-chip compile runs the same gates in bench
(bench.py ``gossip_100k_insert`` / ``praos_1m_insert`` and --smoke).
"""

import os

import numpy as np
import pytest

import jax

from timewarp_tpu.interp.jax_engine.engine import BatchSpec, JaxEngine
from timewarp_tpu.interp.jax_engine.pallas_insert import INSERT_MODES
from timewarp_tpu.faults import (FaultFleet, FaultSchedule, NodeCrash,
                                 Partition)
from timewarp_tpu.models.gossip import gossip, gossip_links
from timewarp_tpu.models.praos import praos
from timewarp_tpu.models.token_ring import token_ring
from timewarp_tpu.net.delays import (LogNormalDelay, Quantize,
                                     UniformDelay, WithDrop)
from timewarp_tpu.trace.events import (assert_states_equal,
                                       assert_traces_equal)

N = 1024  # the kernels' 1024-lane mailbox-plane floor


def _gossip(mailbox_cap=8):
    sc = gossip(N, fanout=8, think_us=2_000, burst=True,
                end_us=1_000_000, mailbox_cap=mailbox_cap)
    link = Quantize(gossip_links(median_us=20_000, sigma=0.6,
                                 floor_us=8_000), 1_000)
    return sc, link


def _cmp(tag, make, modes, horizons, trace_steps=12):
    """Run one engine per insert mode; states must match at every
    horizon and traces (digests included) over ``trace_steps``
    (``trace_steps=0`` skips the traced-driver compile — for legs
    whose digest law is already pinned by the gossip/praos/faulted
    acceptance tests)."""
    engines = [make(insert=m) for m in modes]
    states = [e.init_state() for e in engines]
    for k in horizons:
        states = [e.run_quiet(k, s) for e, s in zip(engines, states)]
        for m, s in zip(modes[1:], states[1:]):
            assert_states_equal(states[0], s, f"{tag} {m} +{k}")
    if trace_steps:
        traces = [e.run(trace_steps)[1] for e in engines]
        for m, tr in zip(modes[1:], traces[1:]):
            assert_traces_equal(traces[0], tr, f"{tag}-{modes[0]}",
                                f"{tag}-{m}")
    return states[0]


def test_insert_variants_equal_seeded_gossip():
    """ALL insert variants on one seeded gossip run (the promoted
    TW_FLAT_SCATTER satellite's result-equivalence pin): flat scatters
    ≡ 2D scatters ≡ the Pallas kernels, state + trace, through
    ramp-up and peak."""
    sc, link = _gossip()
    rs = _cmp("gossip", lambda **kw: JaxEngine(sc, link, window="auto",
                                               seed=7, **kw),
              ("xla", "xla2d", "interpret"), (2, 12), trace_steps=8)
    assert int(rs.delivered) > N // 2  # the wave actually spread


def test_insert_pallas_equals_xla_praos():
    """The praos bench shape: needs_key leadership draws, payload
    width 2, slot timers + diffusion bursts under an 8 ms window —
    the profiled hotspot the kernels exist for."""
    sc = praos(N, slot_us=100_000, n_slots=30, leader_prob=4.0 / N,
               fanout=8, burst=True, mailbox_cap=8)
    link = Quantize(LogNormalDelay(20_000, 0.6, cap_us=150_000,
                                   floor_us=8_000), 1_000)
    rs = _cmp("praos", lambda **kw: JaxEngine(sc, link, window="auto",
                                              **kw),
              ("xla", "interpret"), (2, 10), trace_steps=8)
    assert int(rs.delivered) > 0


def test_insert_ordered_inbox_append_mode():
    """Ordered inboxes run the kernel's append-after-kept mode (the
    contract-#2 slot-order law): the observer token ring (max_out=2,
    classic supersteps) through the fire-compacted adaptive path."""
    sc = token_ring(N - 1, n_tokens=16, think_us=1_000,
                    with_observer=True, mailbox_cap=8)
    assert not sc.commutative_inbox and sc.max_out == 2
    _cmp("ring", lambda **kw: JaxEngine(sc, UniformDelay(1_000, 5_000),
                                        **kw),
         ("xla", "interpret"), (2, 30), trace_steps=10)


@pytest.mark.slow
def test_insert_eager_and_lazy_paths():
    """The non-adaptive call sites: a droppy link (eager routing) and
    a route_cap (lazy routing) both dispatch _insert_sorted into the
    insertion kernel — bit-identical to the XLA scatters."""
    sc = gossip(N, fanout=4, think_us=700, burst=True,
                end_us=300_000, mailbox_cap=8)
    _cmp("drop-eager", lambda **kw: JaxEngine(
        sc, WithDrop(UniformDelay(2_000, 9_000), 0.1), **kw),
        ("xla", "interpret"), (1, 12), trace_steps=0)
    _cmp("lazy-cap", lambda **kw: JaxEngine(
        sc, UniformDelay(2_000, 9_000), route_cap=2048, **kw),
        ("xla", "interpret"), (1, 12), trace_steps=0)


def test_insert_overflow_bit_exact():
    """A mailbox too small for the burst fan-in: the in-kernel
    hole-vs-count overflow accounting must match _insert_sorted's
    bit-for-bit (counted, never silent)."""
    sc = gossip(N, fanout=8, think_us=2_000, burst=True,
                end_us=1_000_000, mailbox_cap=2)
    link = Quantize(UniformDelay(8_000, 30_000), 1_000)
    rs = _cmp("overflow", lambda **kw: JaxEngine(sc, link,
                                                 window="auto", **kw),
              ("xla", "interpret"), (1, 4, 20), trace_steps=0)
    assert int(rs.overflow) > 0  # the regime actually overflowed


def test_insert_faulted_batched_world_axis():
    """The acceptance law's hardest leg: a 2-world fleet with
    per-world fault schedules (reset crashes + partitions) through the
    fire-compacted kernels — every mask point (cuts before compaction,
    down-window drops after sampling) stays in XLA around the kernels,
    so chaos states, per-world traces, and fault_dropped counters are
    bit-identical to insert='xla'. The kernels vmap over the world
    axis (the batch exactness law chains world b to its solo run)."""
    B, half = 2, N // 2
    fleet = FaultFleet(tuple(
        FaultSchedule((
            NodeCrash((7 * b + 3) % N, 20_000, 60_000 + 5_000 * b,
                      reset_state=True),
            Partition((tuple(range(half)), tuple(range(half, N))),
                      25_000, 70_000 + 2_000 * b),
        )) for b in range(B)))
    spec = BatchSpec(seeds=(0, 1))
    sc = gossip(N, fanout=1, think_us=1_000, gossip_interval=1_000,
                end_us=200_000, steady=True, mailbox_cap=8)
    link = Quantize(UniformDelay(500, 4_500), 1_000)
    ref = JaxEngine(sc, link, window="auto", batch=spec, faults=fleet)
    pal = JaxEngine(sc, link, window="auto", batch=spec, faults=fleet,
                    insert="interpret")
    rs, ps = ref.init_state(), pal.init_state()
    for k in (1, 5, 40):
        rs = ref.run_quiet(k, rs)
        ps = pal.run_quiet(k, ps)
        assert_states_equal(rs, ps, f"faulted-batched +{k}")
    _, trs = ref.run(25)
    _, tps = pal.run(25)
    for b in range(B):
        assert_traces_equal(trs[b], tps[b], f"w{b}-xla", f"w{b}-pallas")
    fd = np.asarray(jax.device_get(rs.fault_dropped))
    assert (fd > 0).all(), "chaos schedule never bit"


def test_insert_telemetry_exact_and_rung():
    """Telemetry on the pallas path: counters-mode digests are
    bit-identical to an off-mode xla run (the zero-perturbation law
    crosses the insert knob), and the recorded rung is the stage's
    static sender-denominated batch width."""
    sc, link = _gossip()
    off = JaxEngine(sc, link, window="auto")
    tel = JaxEngine(sc, link, window="auto", insert="interpret",
                    telemetry="counters")
    _, tr = off.run(16)
    _, tp = tel.run(16)
    assert_traces_equal(tr, tp, "xla-off", "pallas-counters")
    fr = tel.last_run_telemetry
    assert len(fr) > 0
    assert set(fr.data["rung"].tolist()) == {tel._pallas_stage.A}
    assert tel._pallas_stage.A == N  # default insert_cap = n * max_out


def test_insert_cap_drops_are_counted():
    """An insert_cap smaller than the burst's fired width drops the
    excess into route_drop — counted, never silent (the same contract
    as route_cap / fused max_batch); at the default cap the counter
    is 0 by construction (every other test here)."""
    sc, link = _gossip()
    capped = JaxEngine(sc, link, window="auto", insert="interpret",
                       insert_cap=64)
    cs = capped.run_quiet(40)
    assert int(cs.route_drop) > 0


def test_insert_knob_resolution_and_env():
    """The documented TW_INSERT hatch (and the legacy TW_FLAT_SCATTER
    alias it promotes, docs/engines.md "Measured on a v5e"), the off-TPU auto-fallback, and
    the never-silent scope guards."""
    sc, link = _gossip()
    for var in ("TW_INSERT", "TW_FLAT_SCATTER"):
        os.environ.pop(var, None)
    try:
        e = JaxEngine(sc, link, window="auto")
        assert (e.insert, e.insert_resolved) == ("xla", "xla")
        os.environ["TW_INSERT"] = "xla2d"
        e = JaxEngine(sc, link, window="auto")
        assert e.insert_resolved == "xla2d"
        del os.environ["TW_INSERT"]
        os.environ["TW_FLAT_SCATTER"] = "0"   # legacy: 0 = 2D form
        e = JaxEngine(sc, link, window="auto")
        assert e.insert_resolved == "xla2d"
        os.environ["TW_FLAT_SCATTER"] = "1"   # legacy: 1 = flat
        e = JaxEngine(sc, link, window="auto")
        assert e.insert_resolved == "xla"
    finally:
        for var in ("TW_INSERT", "TW_FLAT_SCATTER"):
            os.environ.pop(var, None)
    # "pallas" with no TPU: refused — the compiled kernels need the
    # chip, and no other strategy is ever picked from the backend's
    # name (the interpreter is an explicit request)
    assert jax.default_backend() != "tpu"
    with pytest.raises(RuntimeError, match="needs a TPU"):
        JaxEngine(sc, link, window="auto", insert="pallas")
    os.environ["TW_INSERT"] = "pallas"
    try:
        with pytest.raises(RuntimeError, match="needs a TPU"):
            JaxEngine(sc, link, window="auto")
    finally:
        del os.environ["TW_INSERT"]
    # unknown mode
    with pytest.raises(ValueError, match="insert must be one of"):
        JaxEngine(sc, link, window="auto", insert="mosaic")
    assert set(INSERT_MODES) == {"xla", "xla2d", "pallas", "interpret"}
    # kernel scope: non-1024-multiple node count refused loudly for
    # an EXPLICIT request…
    small = gossip(100, fanout=4, burst=True, end_us=100_000)
    with pytest.raises(ValueError, match="multiple"):
        JaxEngine(small, UniformDelay(2_000, 9_000), window=2_000,
                  insert="interpret")
    # …but an ENV-selected mode must stay behavior-neutral: out of
    # kernel scope -> xla fallback, loudly recorded, never a crash
    # (a stale TW_INSERT cannot hard-fail a sweep bucket)
    os.environ["TW_INSERT"] = "interpret"
    try:
        e = JaxEngine(small, UniformDelay(2_000, 9_000), window=2_000)
        assert e.insert_resolved == "xla"
        assert "kernel scope" in e.insert_fallback
        # the unused cap rides the recorded scope-fallback reason
        e = JaxEngine(small, UniformDelay(2_000, 9_000), window=2_000,
                      insert_cap=64)
        assert e.insert_resolved == "xla"
        assert "insert_cap" in e.insert_fallback
    finally:
        del os.environ["TW_INSERT"]
    # insert_cap without a REQUESTED pallas mode is a refused no-op,
    # and with insert="pallas" and no TPU the refusal is the chip's
    with pytest.raises(ValueError, match="insert_cap"):
        JaxEngine(sc, link, window="auto", insert_cap=64)
    with pytest.raises(RuntimeError, match="needs a TPU"):
        JaxEngine(sc, link, window="auto", insert="pallas",
                  insert_cap=64)
    # env hatch must NOT leak into engines that replace the insertion
    # stage themselves (fused/sharded subclasses resolve "xla")
    os.environ["TW_INSERT"] = "interpret"
    try:
        from timewarp_tpu.interp.jax_engine.fused_sparse import \
            FusedSparseEngine
        sc16, link16 = _gossip(mailbox_cap=16)
        f = FusedSparseEngine(sc16, link16, window="auto",
                              interpret=True)
        assert f.insert_resolved == "xla"
        assert f._pallas_stage is None
    finally:
        del os.environ["TW_INSERT"]


@pytest.mark.slow
def test_insert_checkpoint_interchange(tmp_path):
    """EngineState is strategy-independent: a checkpoint saved from an
    xla run resumes under the pallas engine bit-for-bit (and back)."""
    from timewarp_tpu.utils.checkpoint import load_state, save_state
    sc, link = _gossip()
    ref = JaxEngine(sc, link, window="auto")
    pal = JaxEngine(sc, link, window="auto", insert="interpret")
    mid = ref.run_quiet(8)
    path = str(tmp_path / "mid.npz")
    save_state(path, mid, meta={"scenario": sc.name})
    loaded, _ = load_state(path, pal.init_state(),
                           expect_meta={"scenario": sc.name})
    assert_states_equal(ref.run_quiet(15, mid),
                        pal.run_quiet(15, loaded), "resume-under-pallas")
