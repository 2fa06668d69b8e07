"""Batched multi-world execution (engine.py ``batch=BatchSpec``).

The law under test is the batch exactness law (batched.py): slicing
world b out of ANY batched run — traced or quiet, local or sharded,
seed-swept or link-swept — is bit-identical to the solo run with that
world's seed and link. Plus the driver-side guarantees that make the
law hold (per-world quiescence and step-budget masking) and the
pow2-padded ``_run_scan`` compile-reuse contract. The ladder's shared
rung and the fleet sharded over a mesh are
tests/test_world_batch_sharded.py.
"""

import numpy as np
import pytest

from timewarp_tpu.interp.jax_engine.batched import (BatchSpec, rebind_link,
                                                    world_slice)
from timewarp_tpu.interp.jax_engine.engine import JaxEngine, _scan_pad
from timewarp_tpu.models.gossip import gossip
from timewarp_tpu.models.token_ring import token_ring, token_ring_links
from timewarp_tpu.net.delays import Quantize, UniformDelay
from timewarp_tpu.trace.events import assert_states_equal, assert_traces_equal


def _ring(n=48):
    sc = token_ring(n, n_tokens=8, think_us=2_000, bootstrap_us=1000,
                    end_us=200_000, with_observer=True, mailbox_cap=16)
    return sc, token_ring_links(n)


def _burst_gossip(n=64):
    sc = gossip(n, fanout=4, think_us=700, burst=True, end_us=400_000,
                mailbox_cap=16)
    return sc, Quantize(UniformDelay(3_000, 9_000), 1_000)




def test_batched_run_slices_equal_solo():
    sc, link = _ring()
    spec = BatchSpec(seeds=(0, 1, 5))
    eng = JaxEngine(sc, link, batch=spec)
    final, traces = eng.run(120)
    assert isinstance(traces, list) and len(traces) == 3
    for b, s in enumerate(spec.seeds):
        solo_final, solo_trace = JaxEngine(sc, link, seed=s).run(120)
        assert_traces_equal(solo_trace, traces[b], "solo", f"world{b}")
        assert_states_equal(solo_final, world_slice(final, b),
                            f"world {b}")


def test_batched_worlds_actually_differ():
    """Per-world digests are per-world: different seeds must produce
    different event streams (a fleet of clones would ace the
    exactness law while testing nothing)."""
    sc, link = _ring()
    eng = JaxEngine(sc, link, batch=BatchSpec(seeds=(0, 1)))
    _, traces = eng.run(80)
    assert not np.array_equal(traces[0].recv_hash, traces[1].recv_hash)


def test_batched_link_sweep_windowed_slices_equal_solo():
    """Seed AND link-model sweep under a multi-instant window: each
    world's solo twin uses BatchSpec.world_link (the host-level
    per-world link) and the batched engine's resolved window."""
    sc, link = _burst_gossip()
    spec = BatchSpec(seeds=(3, 4, 9, 11),
                     link_params={"inner.lo": [3000, 4000, 3000, 5000],
                                  "inner.hi": [9000, 9000, 12000, 8000]})
    eng = JaxEngine(sc, link, window=3_000, batch=spec)
    final, traces = eng.run(200)
    for b in range(spec.B):
        solo = JaxEngine(sc, spec.world_link(link, b),
                         seed=spec.seeds[b], window=3_000)
        solo_final, solo_trace = solo.run(200)
        assert_traces_equal(solo_trace, traces[b], "solo", f"world{b}")
        assert_states_equal(solo_final, world_slice(final, b),
                            f"world {b}")


def test_batched_run_quiet_budget_and_quiescence_masking():
    """run_quiet: a world must stop at ITS OWN budget/quiescence
    point even while sibling worlds keep stepping — frozen worlds
    slice out bit-identical to solo runs with the same budget."""
    sc, link = _ring()
    spec = BatchSpec(seeds=(0, 2, 7))
    eng = JaxEngine(sc, link, batch=spec)
    for budget in (70, 1000):   # mid-run freeze and full quiescence
        fin = eng.run_quiet(budget)
        for b, s in enumerate(spec.seeds):
            solo = JaxEngine(sc, link, seed=s).run_quiet(budget)
            assert_states_equal(solo, world_slice(fin, b),
                                f"budget={budget} world {b}")


def test_batched_resume_across_worlds():
    """Mid-run state handoff: run(120) then run(180, state=...) must
    equal run(300) per world (the driver's own resume contract, now
    with the world axis)."""
    sc, link = _ring()
    eng = JaxEngine(sc, link, batch=BatchSpec(seeds=(1, 6)))
    _, full = eng.run(300)
    mid, first = eng.run(120)
    _, rest = eng.run(180, state=mid)
    for b in range(2):
        assert np.array_equal(
            np.concatenate([first[b].times, rest[b].times]),
            full[b].times)
        assert np.array_equal(
            np.concatenate([first[b].recv_hash, rest[b].recv_hash]),
            full[b].recv_hash)


def test_batched_window_auto_resolves_fleet_floor():
    """window="auto" under a link sweep must use the MIN over every
    world's declared floor — the widest window exact fleet-wide."""
    sc, link = _burst_gossip()
    spec = BatchSpec(seeds=(0, 1),
                     link_params={"inner.lo": [3000, 5000],
                                  "inner.hi": [9000, 9000]})
    eng = JaxEngine(sc, link, window="auto", batch=spec)
    assert eng.window == 3000


def test_batchspec_validation_errors():
    with pytest.raises(ValueError, match="at least one world"):
        BatchSpec(seeds=())
    with pytest.raises(ValueError, match="one value per world"):
        BatchSpec(seeds=(0, 1), link_params={"lo": [1, 2, 3]})
    with pytest.raises(ValueError, match="needs batch= or seeds="):
        BatchSpec.of()
    with pytest.raises(ValueError, match="disagrees"):
        BatchSpec.of(3, [0, 1])
    assert BatchSpec.of(3, base_seed=5).seeds == (5, 6, 7)
    assert BatchSpec.of(None, range(2, 5)).seeds == (2, 3, 4)


def test_rebind_link_unknown_path_names_fields():
    link = Quantize(UniformDelay(1_000, 2_000), 500)
    with pytest.raises(ValueError, match="sweepable fields"):
        rebind_link(link, {"nope": 1})
    with pytest.raises(ValueError, match="sweepable fields"):
        rebind_link(link, {"inner.nope": 1})
    swept = rebind_link(link, {"inner.lo": 1500, "quantum_us": 250})
    assert swept == Quantize(UniformDelay(1_500, 2_000), 250)


def test_batched_engine_guards():
    sc, link = _ring(16)
    with pytest.raises(ValueError, match="BatchSpec"):
        JaxEngine(sc, link, batch=3)  # a bare int is not a fleet
    with pytest.raises(ValueError, match="solo-run debug ring"):
        JaxEngine(sc, link, batch=BatchSpec(seeds=(0, 1)),
                  record_events=64)
    # windowed validation uses the fleet floor: a world whose link
    # can undercut the window must be rejected at construction
    gsc, glink = _burst_gossip(16)
    with pytest.raises(ValueError, match="min over the batch worlds"):
        JaxEngine(gsc, glink, window=3_000, batch=BatchSpec(
            seeds=(0, 1),
            link_params={"inner.lo": [3000, 1000],
                         "inner.hi": [9000, 9000]}))




def test_scan_pad_buckets():
    assert [_scan_pad(m) for m in (0, 1, 2, 3, 4, 5, 8, 9, 1000)] == \
        [0, 1, 2, 4, 4, 8, 8, 16, 1024]


def test_run_scan_compile_reuse_within_pow2_bucket():
    """The satellite contract: repeated budgets in one pow2 bucket
    reuse ONE _run_scan executable (the scan length is the only
    static compile input); a new bucket costs exactly one more."""
    sc, link = _ring(16)
    eng = JaxEngine(sc, link)
    eng.run(5)  # prime the 8-bucket
    before = JaxEngine._run_scan._cache_size()
    for budget in (5, 6, 7, 8):
        eng.run(budget)
    assert JaxEngine._run_scan._cache_size() == before
    eng.run(9)  # 16-bucket: one fresh compile
    assert JaxEngine._run_scan._cache_size() == before + 1
    # and the padded/masked tail must not change results
    _, t7 = eng.run(7)
    _, t8 = eng.run(8)
    assert len(t7) == 7 and len(t8) == 8
    assert np.array_equal(t7.recv_hash, t8.recv_hash[:7])
