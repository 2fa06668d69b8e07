"""The fused-sparse Pallas engine's exactness law: state AND trace
equality against :class:`JaxEngine` at every checkpoint, on the
gossip and praos bench shapes (ISSUE r6 acceptance). `JaxEngine` is
itself pinned to the host oracle (tests/test_parity.py), so the chain
fused-sparse ≡ general ≡ oracle covers the new kernel.

On this CPU test platform the kernel runs under the pallas
interpreter (same DMA/loop semantics, no Mosaic); the real-chip
compile and the same equality check run in the bench
(bench.py gossip_100k_fused / praos_1m_fused and --smoke).
"""

import numpy as np
import pytest

from timewarp_tpu.interp.jax_engine.engine import JaxEngine
from timewarp_tpu.interp.jax_engine.fused_sparse import FusedSparseEngine
from timewarp_tpu.models.gossip import gossip, gossip_links
from timewarp_tpu.models.praos import praos
from timewarp_tpu.models.token_ring import token_ring
from timewarp_tpu.net.delays import (FnDelay, LogNormalDelay, Quantize,
                                     SeededHashUniform, UniformDelay,
                                     WithDrop)
from timewarp_tpu.trace.events import (assert_states_equal,
                                       assert_traces_equal)

N = 1024  # minimum fused block width (1024-lane mailbox planes)


def _gossip():
    sc = gossip(N, fanout=8, think_us=2_000, burst=True,
                end_us=2_000_000, mailbox_cap=16)
    link = Quantize(gossip_links(median_us=20_000, sigma=0.6,
                                 floor_us=8_000), 1_000)
    return sc, link


def _praos():
    sc = praos(N, slot_us=100_000, n_slots=40, leader_prob=4.0 / N,
               fanout=8, burst=True, mailbox_cap=16)
    link = Quantize(LogNormalDelay(20_000, 0.6, cap_us=150_000,
                                   floor_us=8_000), 1_000)
    return sc, link


_assert_state_equal = assert_states_equal


def _check(sc, link, horizons, tag, **kw):
    ref = JaxEngine(sc, link, **kw)
    fus = FusedSparseEngine(sc, link, **kw, interpret=True)
    rs, fs = ref.init_state(), fus.init_state()
    for k in horizons:
        rs = ref.run_quiet(k, rs)
        fs = fus.run_quiet(k, fs)
        _assert_state_equal(rs, fs, f"{tag} +{k}")
    _, tr = ref.run(30)
    _, tf = fus.run(30)
    assert_traces_equal(tr, tf, f"general-{tag}", f"fused-{tag}")
    return rs


def test_fused_equals_general_gossip_wave():
    """The gossip bench shape (burst fanout 8, quantized lognormal,
    window='auto'), through ramp-up, peak, and quiescence — the float
    link model exercises the in-kernel Box-Muller path."""
    sc, link = _gossip()
    rs = _check(sc, link, (1, 2, 5, 20, 60), "gossip", window="auto")
    assert int(rs.delivered) > N  # the wave actually spread


def test_fused_equals_general_praos():
    """The praos bench shape: needs_key leadership draws, payload
    width 2, slot timers + diffusion bursts under an 8 ms window."""
    sc, link = _praos()
    rs = _check(sc, link, (1, 3, 15, 50), "praos", window="auto")
    assert int(rs.delivered) > N


def test_fused_integer_links_and_multiblock():
    """8192 nodes = a multi-block DMA pipeline (G > 1, 8-row blocks),
    with the reference's seeded (dst, t)-hash link — the integer model
    family the parity gate stands on."""
    sc = gossip(8192, fanout=4, think_us=700, burst=True,
                end_us=400_000, mailbox_cap=8)
    _check(sc, SeededHashUniform(3_000, 9_000, 7), (1, 4, 40),
           "gossip-8k", window=3_000)


def test_fused_classic_window_wide_outbox():
    """window=1 with max_out > 1 (wide outbox, classic supersteps) —
    the other regime the adaptive path serves."""
    sc = gossip(N, fanout=4, think_us=700, burst=True,
                end_us=300_000, mailbox_cap=8)
    _check(sc, UniformDelay(2_000, 9_000), (1, 5, 40), "w1", window=1)


def test_fused_overflow_bit_exact():
    """A mailbox too small for the burst fan-in: the overflow counter
    and the surviving mailbox state must still match bit-for-bit
    (overflow = the kernel's cnt - holes accounting)."""
    sc = gossip(N, fanout=8, think_us=2_000, burst=True,
                end_us=1_000_000, mailbox_cap=2)
    link = Quantize(UniformDelay(8_000, 30_000), 1_000)
    rs = _check(sc, link, (1, 4, 30), "overflow", window="auto")
    assert int(rs.overflow) > 0  # the regime actually overflowed


def test_fused_event_ring_matches_general():
    """The device event ring (record_events) is inherited unchanged —
    record-level equality with the general engine."""
    sc = gossip(N, fanout=4, think_us=700, burst=True,
                end_us=300_000, mailbox_cap=8)
    link = Quantize(UniformDelay(3_000, 9_000), 1_000)
    ref = JaxEngine(sc, link, window=3_000, record_events=4096)
    fus = FusedSparseEngine(sc, link, window=3_000, record_events=4096,
                            interpret=True)
    rstate = ref.run_quiet(40)
    fstate = fus.run_quiet(40)
    rev, rdrop = ref.events(rstate)
    fev, fdrop = fus.events(fstate)
    assert rev == fev
    assert rdrop == fdrop


def test_fused_checkpoint_interchange(tmp_path):
    """EngineState is shared bit-for-bit, so a checkpoint saved from
    either engine resumes under the other exactly (utils/checkpoint.py
    — the cross-engine interchange the fused_ring engine needs a
    to_edge_state conversion for; here it is the identity)."""
    from timewarp_tpu.utils.checkpoint import load_state, save_state
    sc, link = _gossip()
    ref = JaxEngine(sc, link, window="auto")
    fus = FusedSparseEngine(sc, link, window="auto", interpret=True)
    mid = ref.run_quiet(10)
    path = str(tmp_path / "mid.npz")
    save_state(path, mid, meta={"scenario": sc.name})
    loaded, _ = load_state(path, fus.init_state(),
                           expect_meta={"scenario": sc.name})
    fs = fus.run_quiet(25, loaded)
    rs = ref.run_quiet(25, mid)
    _assert_state_equal(rs, fs, "resume-under-fused")
    # and the reverse hand-off
    back, _ = load_state(path, ref.init_state())
    _assert_state_equal(fus.run_quiet(7, loaded),
                        ref.run_quiet(7, back), "resume-under-general")


def test_fused_batch_cap_drops_are_counted():
    """A max_batch smaller than the superstep's traffic drops the
    excess into route_drop — counted, never silent (the same contract
    as route_cap); with max_batch >= n*max_out the counter is 0 by
    construction (every other test here)."""
    sc = gossip(N, fanout=8, think_us=2_000, burst=True,
                end_us=1_000_000, mailbox_cap=16)
    link = Quantize(UniformDelay(8_000, 30_000), 1_000)
    fus = FusedSparseEngine(sc, link, window="auto", max_batch=128,
                            interpret=True)
    fs = fus.run_quiet(40)
    ref = JaxEngine(sc, link, window="auto")
    rs = ref.run_quiet(40)
    assert int(fs.route_drop) > 0
    assert int(fs.delivered) + int(fs.route_drop) + int(fs.overflow) \
        <= int(rs.delivered) + int(rs.overflow) + int(rs.route_drop) \
        + int(fs.route_drop)


def test_fused_sharded_leg():
    """The multi-chip windowed path: ShardedFusedSparseEngine's trace
    and final state equal the 1-device general engine's on the virtual
    8-device mesh (the fused insertion runs per shard after the
    all_to_all exchange)."""
    import jax
    from timewarp_tpu.interp.jax_engine.sharded import (
        ShardedFusedSparseEngine, make_mesh)
    n = 8192
    sc = gossip(n, fanout=4, think_us=3_000, burst=True,
                end_us=400_000, mailbox_cap=8)
    link = Quantize(UniformDelay(3_000, 9_000), 1_000)
    ref = JaxEngine(sc, link, window=3_000)
    fus = ShardedFusedSparseEngine(sc, link, make_mesh(8),
                                   window=3_000, interpret=True)
    _, tr = ref.run(60)
    _, tf = fus.run(60)
    assert_traces_equal(tr, tf, "general-1dev", "sharded-fused-8dev")
    rs = ref.run_quiet(60)
    fs = jax.tree.map(jax.device_get, fus.run_quiet(60))
    _assert_state_equal(rs, fs, "sharded-fused")


def test_fused_scope_guards():
    """Every unsupported regime is refused loudly at construction."""
    sc, link = _gossip()
    # non-1024-multiple node count
    small = gossip(100, fanout=4, burst=True, end_us=100_000)
    with pytest.raises(ValueError, match="multiple"):
        FusedSparseEngine(small, UniformDelay(2_000, 9_000),
                          window=2_000, interpret=True)
    # droppy link
    with pytest.raises(ValueError, match="drop-free"):
        FusedSparseEngine(sc, WithDrop(UniformDelay(2_000, 9_000), .1),
                          window="auto", interpret=True)
    # non-commutative inbox (ordered token ring with observer)
    ring = token_ring(N - 1, n_tokens=8, think_us=1_000,
                      with_observer=True)
    with pytest.raises(ValueError, match="multiple|commutative"):
        FusedSparseEngine(ring, UniformDelay(2_000, 9_000),
                          window=2_000, interpret=True)
    # un-lowerable link model (drop-free, so it reaches the registry)
    class _NoDropFn(FnDelay):
        @property
        def can_drop(self):
            return False

    fn = _NoDropFn(lambda s, d, t, k: (t * 0 + 5_000, t < 0))
    with pytest.raises(ValueError, match="cannot lower"):
        FusedSparseEngine(sc, fn, window=1, interpret=True)
    # classic narrow regime (nothing to batch)
    steady = gossip(N, fanout=1, steady=True, end_us=100_000)
    with pytest.raises(ValueError, match="windowed"):
        FusedSparseEngine(steady, UniformDelay(2_000, 9_000), window=1,
                          interpret=True)


def test_fused_sparse_refuses_without_a_tpu():
    """Compiled kernel by default; the Pallas interpreter only on an
    explicit request — with no TPU and no request both fused-sparse
    engines raise (no strategy is picked from the backend's name)."""
    import jax
    assert jax.default_backend() != "tpu"
    sc, link = _gossip()
    with pytest.raises(RuntimeError, match="needs a TPU"):
        FusedSparseEngine(sc, link, window="auto")
    from timewarp_tpu.interp.jax_engine.sharded import (
        ShardedFusedSparseEngine, make_mesh)
    sc8 = gossip(8192, fanout=4, burst=True, end_us=100_000)
    with pytest.raises(RuntimeError, match="needs a TPU"):
        ShardedFusedSparseEngine(sc8, UniformDelay(2_000, 9_000),
                                 make_mesh(8), window=2_000)
