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

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from timewarp_tpu.core.scenario import NEVER
from timewarp_tpu.interp.jax_engine.common import I32MAX
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


# -- the carried next event ------------------------------------------------

def _eqns(jaxpr):
    """Every equation of a jaxpr, nested ones too (a ``jit`` inside a
    body), but for a kernel's own."""
    for e in jaxpr.eqns:
        yield e
        if e.primitive.name == "pallas_call":
            continue
        for v in e.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


def _size(v):
    return int(np.prod(getattr(v, "aval", v).shape, dtype=np.int64))


def test_the_drivers_while_scans_nothing():
    """Inside the quiet driver's ``while`` nothing reads the state but
    the kernel and the one-element reads of the ring wrap: the
    condition is scalars, and the next superstep's time is the minimum
    the kernel reported, not a reduction over planes."""
    sc = token_ring(N, n_tokens=N, think_us=0, bootstrap_us=1_000,
                    end_us=60_000, with_observer=False, mailbox_cap=4)
    fus = FusedRingEngine(sc, FixedDelay(500), cap=2, interpret=True)
    jaxpr = jax.make_jaxpr(lambda s, k: fus._run_while(s, k))(
        fus.init_state(), jnp.int64(4)).jaxpr
    loop, = [e for e in _eqns(jaxpr) if e.primitive.name == "while"]
    for e in _eqns(loop.params["cond_jaxpr"].jaxpr):
        assert all(_size(v) < N for v in e.invars), e
    body = list(_eqns(loop.params["body_jaxpr"].jaxpr))
    assert sum(e.primitive.name == "pallas_call" for e in body) == 1
    for e in body:
        if e.primitive.name == "pallas_call":
            continue
        if any(_size(v) >= N for v in e.invars):
            # a read of one element, never a pass over a plane
            assert all(_size(v) == 1 for v in e.outvars), e
        assert not e.primitive.name.startswith(("reduce_", "arg")) \
            or _size(e.invars[0]) < N, e
    # the one scan of a run (three planes) is therefore before the loop
    assert sum(e.primitive.name == "reduce_min" and _size(e.invars[0]) >= N
               for e in _eqns(jaxpr)) == 3


_CARRIED = {
    "dense": (dict(n_tokens=N, think_us=0, bootstrap_us=1_000,
                   end_us=60_000), 500, 40),
    "sparse_think": (dict(n_tokens=5, think_us=1_700, bootstrap_us=900,
                          end_us=80_000), 700, 60),
    # end_us falls in the run: the tokens stop, the ring drains
    "to_quiescence": (dict(n_tokens=N, think_us=0, bootstrap_us=1_000,
                           end_us=4_000), 500, 40),
}


@pytest.mark.parametrize("regime", sorted(_CARRIED))
def test_the_kernels_minimum_is_the_successors_next_event(regime):
    """What the kernel folds from the planes as it writes them equals
    the scan of the successor state, superstep by superstep; at
    quiescence it is the sentinel and the loop stops where
    ``EdgeEngine``'s does."""
    kw, delay, budget = _CARRIED[regime]
    sc = token_ring(N, with_observer=False, mailbox_cap=4, **kw)
    link = FixedDelay(delay)
    fus = FusedRingEngine(sc, link, cap=2, interpret=True)
    step = jax.jit(fus._step)
    fs = fus.init_state()
    t = fus._earliest(fs.planes)
    steps = 0
    while int(t) < I32MAX and steps < budget:
        fs, t = step(fs, t)
        steps += 1
        assert int(fus._next_event(fs)) == (
            NEVER if int(t) >= I32MAX else int(fs.base) + int(t)), steps
    quiesced = regime == "to_quiescence"
    assert (int(t) >= I32MAX) == quiesced
    assert (steps < budget) == quiesced
    # the driver, carrying that minimum, stops on the same superstep
    ref = EdgeEngine(sc, link, cap=2)
    rs = ref.run_quiet(budget)
    ds = fus.run_quiet(budget)
    assert int(ds.steps) == int(rs.steps) == steps
    _assert_state_equal(rs, fus.to_edge_state(ds), regime)
    _assert_state_equal(rs, fus.to_edge_state(fs), regime + " stepped")
