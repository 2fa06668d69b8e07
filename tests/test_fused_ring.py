"""The fused-Pallas dense-ring engine's exactness law: its state,
converted back to the general engine's layout, equals
:class:`EdgeEngine`'s state **bit-for-bit at every checkpoint** —
including queue payloads, stale slots, counters, and virtual time.
EdgeEngine is itself pinned to the host oracle and the hand-rolled
protocol trace (tests/test_cross_world.py), so the chain
fused ≡ edge ≡ oracle ≡ closed-form covers the new kernel.

On this CPU test platform the kernel runs under the pallas
interpreter (same DMA/loop semantics, no Mosaic); the real-chip
compile and the same equality check run in the bench
(bench.py token_ring_dense) and were verified on hardware in round 5
(docs/engines.md "Measured on a v5e": 6.5e9 msg/s, state-equal at 2^20).
"""

import numpy as np
import pytest

from timewarp_tpu.interp.jax_engine.edge_engine import EdgeEngine
from timewarp_tpu.interp.jax_engine.fused_ring import FusedRingEngine
from timewarp_tpu.models.token_ring import token_ring
from timewarp_tpu.net.delays import FixedDelay, UniformDelay

N = 8192  # the kernel's minimum width (block pipeline shape)


def _assert_state_equal(rs, es, tag):
    for name in ("wake", "q_rel", "q_step", "q_pay", "delivered",
                 "overflow", "steps", "time"):
        assert np.array_equal(np.asarray(getattr(rs, name)),
                              np.asarray(getattr(es, name))), \
            f"{name} diverged ({tag})"
    for leaf in ("cnt", "val", "send_at"):
        assert np.array_equal(np.asarray(rs.states[leaf]),
                              np.asarray(es.states[leaf])), \
            f"state.{leaf} diverged ({tag})"


def test_fused_equals_edge_bit_for_bit():
    """Dense regime (every node holds a token, zero think): checked
    at several horizons including past the end_us deadline, where the
    ring quiesces."""
    sc = token_ring(N, n_tokens=N, think_us=0, bootstrap_us=1_000,
                    end_us=60_000, with_observer=False, mailbox_cap=4)
    link = FixedDelay(500)
    ref = EdgeEngine(sc, link, cap=2)
    fus = FusedRingEngine(sc, link, cap=2, interpret=True)
    rs, fs = ref.init_state(), fus.init_state()
    for k in (1, 2, 7, 40, 130):
        rs = ref.run_quiet(k, rs)
        fs = fus.run_quiet(k, fs)
        _assert_state_equal(rs, fus.to_edge_state(fs), f"+{k}")
    assert int(rs.delivered) > 0


def test_fused_equals_edge_sparse_tokens_and_think():
    """Sparse regime: few tokens, nonzero think time — partial
    firings, armed timers (send_at/wake divergence candidates)."""
    sc = token_ring(N, n_tokens=5, think_us=1_700, bootstrap_us=900,
                    end_us=80_000, with_observer=False, mailbox_cap=4)
    link = FixedDelay(700)
    ref = EdgeEngine(sc, link, cap=2)
    fus = FusedRingEngine(sc, link, cap=2, interpret=True)
    rs, fs = ref.init_state(), fus.init_state()
    for k in (3, 10, 60):
        rs = ref.run_quiet(k, rs)
        fs = fus.run_quiet(k, fs)
        _assert_state_equal(rs, fus.to_edge_state(fs), f"sparse +{k}")


def test_fused_scope_guards():
    sc = token_ring(N, n_tokens=N, think_us=0, bootstrap_us=1_000,
                    end_us=60_000, with_observer=False, mailbox_cap=4)
    with pytest.raises(ValueError, match="FixedDelay"):
        FusedRingEngine(sc, UniformDelay(1, 5), cap=2, interpret=True)
    with pytest.raises(ValueError, match="cap=2"):
        FusedRingEngine(sc, FixedDelay(500), cap=3, interpret=True)
    small = token_ring(64, n_tokens=64, think_us=0, bootstrap_us=1_000,
                       end_us=60_000, with_observer=False,
                       mailbox_cap=4)
    with pytest.raises(ValueError, match="multiple"):
        FusedRingEngine(small, FixedDelay(500), cap=2, interpret=True)
    obs = token_ring(N, n_tokens=N, think_us=0, bootstrap_us=1_000,
                     end_us=60_000, with_observer=True, mailbox_cap=8)
    # the observer adds node N+1, so this trips the block-shape guard
    # before the lean-dense one — either way it is rejected
    with pytest.raises(ValueError, match="multiple|lean dense"):
        FusedRingEngine(obs, FixedDelay(500), cap=2, interpret=True)


def test_fused_ring_refuses_without_a_tpu():
    """The default is the kernel compiled by Mosaic: with no TPU and
    no explicit interpreter request the constructor raises — never a
    quiet fall to the interpreter on the backend's name."""
    import jax
    assert jax.default_backend() != "tpu"
    sc = token_ring(N, n_tokens=N, think_us=0, bootstrap_us=1_000,
                    end_us=60_000, with_observer=False, mailbox_cap=4)
    with pytest.raises(RuntimeError, match="needs a TPU"):
        FusedRingEngine(sc, FixedDelay(500), cap=2)
    assert FusedRingEngine(sc, FixedDelay(500), cap=2,
                           interpret=True).interpret
