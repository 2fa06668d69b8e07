"""The token ring over a mesh of four as a deployment (ISSUE 37):
``ShardedEdgeEngine`` streamed in jobs on one sharded state equals the
benchmark's plain reference and the one-device ``EdgeEngine``; every
job is one program, one dispatch and one readback on a state that
stays four slices on four devices; the call's record counts the shards
and the messages delivered across a shard boundary; the edge engine's
superstep names its stages, with every ``ppermute`` under
``tw.route/exchange``, and the names are names and nothing else: the
one-device drivers lower to the text they had.

(Named test_zz* to sort after the whole existing suite.)
"""

import hashlib
import os
import re
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from timewarp_tpu.interp.jax_engine.common import STAGES
from timewarp_tpu.interp.jax_engine.edge_engine import EdgeEngine
from timewarp_tpu.interp.jax_engine.fused_ring import FusedRingEngine
from timewarp_tpu.interp.jax_engine.sharded import ShardedEdgeEngine
from timewarp_tpu.models.token_ring import token_ring
from timewarp_tpu.net.delays import FixedDelay
from timewarp_tpu.obs import profiler
from timewarp_tpu.parallel.mesh import make_mesh
from timewarp_tpu.trace.events import assert_traces_equal

BENCHMARK = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCHMARK not in sys.path:
    sys.path.insert(0, BENCHMARK)

import run  # noqa: E402
from builders import sharded_ring  # noqa: E402
from reference import ring_ref  # noqa: E402

N, SHARDS, JOBS, PER_JOB = 1 << 10, 4, 8, 16
BOOTSTRAP_US, LINK_US = 1000, 500


def _ring(n=N, n_tokens=None, think_us=0, end_us=2**50, mailbox_cap=4):
    sc = token_ring(n, n_tokens=n if n_tokens is None else n_tokens,
                    think_us=think_us, bootstrap_us=BOOTSTRAP_US,
                    end_us=end_us, with_observer=False,
                    mailbox_cap=mailbox_cap)
    return sc, FixedDelay(LINK_US)


def _seeded(eng, val0):
    st = eng.init_state()
    val = jax.device_put(val0, st.states["val"].sharding)
    return st._replace(states={**st.states, "val": val})


def _cell():
    """The benchmark's builder at this file's size: its ``_facts`` and
    ``_placement`` are what the cell's gates and comparison run."""
    traffic, config = run.load_cell("ring_1m_x4.dense")
    config["params"]["n_nodes"] = N
    traffic["supersteps_per_job"] = PER_JOB
    return sharded_ring.Cell(config, traffic)


@pytest.fixture(scope="module")
def streamed():
    """Eight jobs of sixteen supersteps on one sharded state, and what
    each left behind: the call's stats, where the state lives, the
    state's facts."""
    cell = _cell()
    eng = cell.engine
    val0 = np.random.default_rng(37).integers(0, 1 << 20, N, dtype=np.int32)
    st = _seeded(eng, val0)
    jobs = []
    for _ in range(JOBS):
        st = eng.run_quiet(PER_JOB, st)
        jobs.append({"stats": dict(eng.last_run_stats),
                     "record": profiler.calls()[-1],
                     "placement": cell._placement(st),
                     "shards": [(s.data.shape, s.index[0].start or 0,
                                 s.device) for s in
                                st.wake.addressable_shards],
                     "facts": cell._facts(st), "state": st})
    return {"cell": cell, "val0": val0, "jobs": jobs}


# -- the streamed run against the reference and the one-device engine -------

@pytest.mark.parametrize("job, many", [(0, False), (JOBS - 1, False),
                                       (JOBS - 1, True)],
                         ids=["first_job", "last_job",
                              "last_job_closed_form"])
def test_streamed_jobs_equal_the_plain_reference(streamed, job, many):
    facts = streamed["jobs"][job]["facts"]
    steps = (job + 1) * PER_JOB
    want = ring_ref.expect(streamed["val0"], steps,
                           bootstrap_us=BOOTSTRAP_US,
                           link_delay_us=LINK_US, many=many)
    assert facts["steps"] == steps
    for field, w in want.items():
        assert np.array_equal(
            np.broadcast_to(np.asarray(w), np.shape(facts[field])),
            np.asarray(facts[field])), field


def test_streamed_jobs_equal_the_one_device_engine(streamed):
    ref = EdgeEngine(*_ring(), cap=2)
    want = ref.run_quiet(JOBS * PER_JOB, _seeded(ref, streamed["val0"]))
    got = streamed["jobs"][-1]["state"]
    assert "shards" not in ref.last_run_stats
    assert "boundary_msgs" not in ref.last_run_stats
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(want),
                            jax.tree.leaves(got)):
        assert a.dtype == b.dtype and np.array_equal(a, b), path


@pytest.mark.parametrize("job", range(JOBS))
def test_every_job_is_one_program_on_four_slices(streamed, job):
    made = streamed["jobs"][job]
    stats = made["stats"]
    assert stats["supersteps"] == PER_JOB
    assert (stats["dispatches"], stats["readbacks"]) == (1, 1)
    # the first call compiles the driver, no later one does: the state
    # comes back placed as it went in
    assert stats["compiles"] == (1 if job == 0 else 0)
    assert made["placement"] == []
    assert {s[0] for s in made["shards"]} == {(N // SHARDS,)}
    assert sorted(s[1] for s in made["shards"]) == [
        d * N // SHARDS for d in range(SHARDS)]
    assert len({s[2] for s in made["shards"]}) == SHARDS
    # the run's first superstep delivers nothing; every later one
    # delivers one message across each of the four boundaries
    delivering = PER_JOB - (1 if job == 0 else 0)
    assert stats["shards"] == SHARDS
    assert stats["boundary_msgs"] == SHARDS * delivering
    assert made["record"]["counts"] == stats
    assert made["record"]["engine"] == "ShardedEdgeEngine"


@pytest.mark.parametrize("shards, crossing", [(1, 0), (2, 2), (8, 8)])
def test_the_boundary_count_follows_the_mesh(shards, crossing):
    eng = ShardedEdgeEngine(*_ring(256), make_mesh(shards), cap=2)
    st = eng.run_quiet(5)
    assert eng.last_run_stats["shards"] == shards
    assert eng.last_run_stats["boundary_msgs"] == crossing * 4
    assert int(st.delivered) == 256 * 4


def test_the_scan_driver_counts_and_traces_like_its_twin():
    sc, link = _ring(256)
    eng = ShardedEdgeEngine(sc, link, make_mesh(SHARDS), cap=2)
    ref = EdgeEngine(sc, link, cap=2)
    # a budget inside its pow2 pad: the masked tail counts nothing
    _, got = eng.run(13)
    _, want = ref.run(13)
    assert_traces_equal(want, got, "edge", "sharded-edge")
    assert eng.last_run_stats["shards"] == SHARDS
    assert eng.last_run_stats["boundary_msgs"] == SHARDS * 12
    merged = eng._stats_merge([eng.last_run_stats, eng.last_run_stats])
    assert (merged["shards"], merged["boundary_msgs"]) == (
        SHARDS, 2 * SHARDS * 12)
    assert "shards" not in ref._stats_merge([ref.last_run_stats])


def test_a_sparse_ring_crosses_where_its_tokens_are():
    # one token, think 0: it passes a boundary every 64th superstep
    eng = ShardedEdgeEngine(*_ring(256, n_tokens=1), make_mesh(SHARDS),
                            cap=2)
    eng.run_quiet(1 + 200)
    assert eng.last_run_stats["boundary_msgs"] == 200 // 64


# -- the names, and that they are nothing else -------------------------------

def _sharded_text():
    eng = ShardedEdgeEngine(*_ring(), make_mesh(SHARDS), cap=2)
    return type(eng)._run_while.lower(
        eng, eng.init_state(), PER_JOB).as_text(debug_info=True)


def test_every_ppermute_is_under_the_exchange_scope():
    text = _sharded_text()
    names = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    hops = re.findall(
        r'"stablehlo\.collective_permute".*loc\((#loc\d+)\)$', text, re.M)
    # the validity plane and the payload plane, one ppermute each
    assert len(hops) == 2
    for ref in hops:
        assert "/tw.route/exchange/" in names[ref], names[ref]
    scopes = {part for name in names.values() for part in name.split("/")
              if part.startswith("tw.")}
    assert scopes == set(STAGES)
    # the gathers that agree on the next event (PR 38): the one scan
    # before the loop, and each superstep's product for its successor;
    # the loop's condition reads the carried scalar and gathers nothing
    gathers = [names[ref] for ref in re.findall(
        r'"stablehlo\.all_gather".*loc\((#loc\d+)\)$', text, re.M)]
    assert sorted(g.partition("/all_gather")[0].removeprefix(
        "jit(_run_while)/") for g in gathers) == [
        "tw.next_event", "while/body/tw.next_event"]


#: sha256 of the one-device drivers' lowering (``as_text()``: no names,
#: no locations) as the parent of PR 37 lowers them, before the edge
#: engine's superstep named its stages: the dense ring at 2^10 nodes
#: through ``EdgeEngine``'s quiet and scan drivers, a ring of four
#: tokens and a think time through the quiet driver, and the fused
#: ring's driver on the state ``from_edge_state`` makes (the
#: benchmark's ``ring_1m.dense`` path, the kernel interpreted). A PR
#: that changes what these drivers compute changes the constants, and
#: says so. PR 38 did, by design, for the two quiet drivers of
#: ``EdgeEngine``: their ``while`` carries the state's horizon, its
#: condition reduces nothing and its body selects nothing by ``live``
#: (tests/test_loop_edge.py). The scan driver and the fused ring
#: compute what they computed, and keep the parent of PR 37's text.
_PARENT_LOWERING = {
    "edge_quiet":
        "e09176fa41081b9b51070d5ddf2b6e23855a255197aa9057f15bc35a226e4724",
    "edge_scan":
        "7a36360ebdb6f7719211c5792db6f74153591b59108197aea27ccf44026fecaf",
    "edge_sparse_quiet":
        "86712ac6c97e14cee54b3c58b51df98056614ee02a1e6388f27871f23802c1a8",
    "fused_quiet":
        "0592e883ecca20c9cd7970bcc1b75d27093f76fbe26dea01e6fe0700ee74d11d",
}


def _edge_quiet():
    eng = EdgeEngine(*_ring(), cap=2)
    return EdgeEngine._run_while.lower(eng, eng.init_state(), 16)


def _edge_scan():
    eng = EdgeEngine(*_ring(), cap=2)
    return EdgeEngine._run_scan.lower(eng, eng.init_state(), 16,
                                      jnp.asarray(16, jnp.int64))


def _edge_sparse_quiet():
    eng = EdgeEngine(*_ring(16, n_tokens=4, think_us=2000, end_us=120_000,
                            mailbox_cap=8), cap=2)
    return EdgeEngine._run_while.lower(eng, eng.init_state(), 16)


def _fused_quiet():
    sc, link = _ring(8192)
    eng = FusedRingEngine(sc, link, cap=2, interpret=True)
    fs = eng.from_edge_state(EdgeEngine(sc, link, cap=2).init_state())
    return type(eng)._run_while.lower(eng, fs, 16)


@pytest.mark.parametrize("key, lower", [
    ("edge_quiet", _edge_quiet), ("edge_scan", _edge_scan),
    ("edge_sparse_quiet", _edge_sparse_quiet),
    ("fused_quiet", _fused_quiet)])
def test_the_one_device_drivers_lower_to_the_parents_text(key, lower):
    text = lower().as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == _PARENT_LOWERING[key]


def test_the_one_device_edge_engine_names_its_stages_too():
    text = _edge_quiet().as_text(debug_info=True)
    names = set(re.findall(r'loc\("(jit\(_run_while\)[^"]*)"', text))
    scopes = {part for name in names for part in name.split("/")
              if part.startswith("tw.")}
    assert scopes == set(STAGES)
    assert any("/tw.route/exchange/" in name for name in names)


# -- the harness and fewer chips than the cell asks for ------------------------

def test_the_harness_refuses_the_cell_on_fewer_than_four_chips(
        monkeypatch, capsys):
    class Chip:
        platform, device_kind = "tpu", "TPU v5 lite"
    kept = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    monkeypatch.setattr(jax, "devices", lambda *a: [Chip(), Chip()])
    try:
        with pytest.raises(run.Refused, match="asks for 4 chips, JAX "
                                              "found 2"):
            run.prepare("ring_1m_x4.dense")
        assert run.run_cell("ring_1m_x4.dense", 7, 0.1, False) == 2
    finally:
        for k, v in kept.items():
            jax.config.update(k, v)
    assert "asks for 4 chips" in capsys.readouterr().err
