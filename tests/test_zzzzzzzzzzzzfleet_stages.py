"""The seed-sweep fleet as a deployment (ISSUE 27): a fleet's lowered
quiet driver carries every stage name the solo one does, written
``vmap(tw.<stage>)``, and the benchmark's reader takes the wrapper off;
``last_run_stats`` counts per world and the loop's iterations;
``rebind_identity`` permutes the worlds and compiles nothing; a fleet's
job launches three programs, and expanding a seed launches none.

(Named test_zz* to sort after the whole existing suite.)
"""

import glob
import os
import re
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from timewarp_tpu.core import rng
from timewarp_tpu.core.scenario import NEVER
from timewarp_tpu.interp.jax_engine.batched import BatchSpec, world_slice
from timewarp_tpu.interp.jax_engine.common import STAGES
from timewarp_tpu.interp.jax_engine.engine import JaxEngine
from timewarp_tpu.models.gossip import gossip
from timewarp_tpu.net.delays import Quantize, UniformDelay
from timewarp_tpu.trace.events import assert_states_equal

BENCHMARK = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCHMARK not in sys.path:
    sys.path.insert(0, BENCHMARK)

import fleet_reduce  # noqa: E402
import span_reduce  # noqa: E402

SEEDS = (0, 1, 2, 3)
BUDGET = 1 << 12


def _gossip(n=64):
    sc = gossip(n, fanout=3, burst=True, end_us=150_000, mailbox_cap=16)
    return sc, Quantize(UniformDelay(3000, 9000), 1000)


def _engine(**kw):
    return JaxEngine(*_gossip(), window="auto", lint="off",
                     **kw)


@pytest.fixture(scope="module")
def fleet():
    return _engine(batch=BatchSpec(seeds=SEEDS))


@pytest.fixture(scope="module")
def solo_steps():
    """``steps`` of the solo run to quiescence with each seed."""
    out = {}
    for seed in SEEDS:
        fin = _engine(seed=seed).run_quiet(BUDGET)
        out[seed] = int(fin.steps)
    return out


def _op_names(eng) -> set:
    text = type(eng)._run_while.lower(
        eng, eng.init_state(), jnp.int64(8),
        eng._identity()).as_text(debug_info=True)
    return set(re.findall(r'loc\("(jit\(_run_while\)[^"]*)"', text))


# -- (a) the names, and the reader's unwrapping ---------------------------

def test_a_fleet_carries_every_stage_the_solo_driver_does(fleet):
    solo = {span_reduce.stage_of(n) for n in _op_names(_engine())}
    assert set(STAGES) <= solo
    names = _op_names(fleet)
    # under vmap JAX writes the scope inside the transformation's name,
    # and span_reduce reads such a component as JAX's own
    assert {span_reduce.stage_of(n) for n in names} == {span_reduce.UNSCOPED}
    for stage in solo - {span_reduce.UNSCOPED}:
        assert any(f"/vmap({stage})/" in n for n in names), stage
    # with the wrapper off a fleet reads stage for stage like the solo
    unwrapped = {fleet_reduce.unwrap(n) for n in names}
    assert {span_reduce.stage_of(n) for n in unwrapped} == solo
    # the next event is found once before the loop and at the end of
    # each iteration, never in the condition (ISSUE 34)
    assert any(n.startswith("jit(_run_while)/tw.next_event")
               for n in unwrapped)
    assert any("/body/tw.next_event" in n for n in unwrapped)
    assert not [n for n in unwrapped
                if "/cond/" in n and "tw.next_event" in n]
    nested = {span_reduce.stage_of(n, 2) for n in unwrapped}
    assert {"tw.route/insert", "tw.route/sample"} <= nested


@pytest.mark.parametrize("op_name, stage, nested", [
    ("jit(_run_while)/while/body/vmap(tw.route)/insert/jit(sort)/sort",
     "tw.route", "tw.route/insert"),
    ("jit(_run_while)/while/cond/vmap(tw.next_event)/reduce_min",
     "tw.next_event", "tw.next_event"),
    ("jit(f)/vmap(vmap(tw.fire))/cond/branch_1_fun/add", "tw.fire",
     "tw.fire"),
    # a solo engine's path is left as it is
    ("jit(f)/while/body/tw.route/cond/branch_3_fun/insert/sort",
     "tw.route", "tw.route/insert"),
    # JAX's own wrapped names stay JAX's own
    ("jit(f)/while/body/vmap(jit(_where))/select_n", "unscoped",
     "unscoped"),
    ("jit(f)/while/body/vmap()/mul", "unscoped", "unscoped"),
    ("", "unscoped", "unscoped"),
])
def test_unwrap_takes_the_transformation_off_a_stage_name(op_name, stage,
                                                          nested):
    name = fleet_reduce.unwrap(op_name)
    assert span_reduce.stage_of(name) == stage
    assert span_reduce.stage_of(name, 2) == nested
    if "vmap(tw." not in op_name:
        assert name == op_name


# -- (b) the counters --------------------------------------------------------

def test_fleet_stats_count_each_world_and_the_loops_iterations(
        fleet, solo_steps):
    fleet.rebind_identity(BatchSpec(seeds=SEEDS))
    fin = fleet.run_quiet(BUDGET)
    st = fleet.last_run_stats
    assert st["world_supersteps"] == [solo_steps[s] for s in SEEDS]
    assert st["world_supersteps"] == np.asarray(fin.steps).tolist()
    assert st["fleet_iterations"] == max(st["world_supersteps"])
    assert st["supersteps"] == sum(st["world_supersteps"])
    # the worlds do differ, or the largest would say nothing
    assert len(set(st["world_supersteps"])) > 1
    assert (st["dispatches"], st["readbacks"]) == (1, 1)


def test_a_fleets_budget_bounds_the_iterations(fleet):
    fleet.run_quiet(np.asarray([3, 9, 5, 7]))
    st = fleet.last_run_stats
    assert st["world_supersteps"] == [3, 9, 5, 7]
    assert st["fleet_iterations"] == 9


def test_a_solo_engine_has_no_world_counts():
    eng = _engine()
    eng.run_quiet(5)
    assert "world_supersteps" not in eng.last_run_stats
    assert "fleet_iterations" not in eng.last_run_stats


# -- (c) a permutation of the worlds --------------------------------------

@pytest.mark.parametrize("order", [(3, 2, 1, 0), (2, 0, 3, 1)])
def test_rebinding_a_permutation_permutes_the_worlds(fleet, order):
    assert fleet.rebind_identity(BatchSpec(seeds=SEEDS))
    st0 = fleet.init_state()
    base = fleet.run_quiet(BUDGET, st0)
    assert fleet.rebind_identity(BatchSpec(seeds=order))
    fin = fleet.run_quiet(BUDGET, st0)
    assert fleet.last_run_stats["compiles"] == 0
    for slot, seed in enumerate(order):
        assert_states_equal(world_slice(fin, slot),
                            world_slice(base, SEEDS.index(seed)))
    quiet = jax.vmap(fleet._next_event)(fin) >= NEVER
    assert bool(quiet.all())


# -- (d) programs a job ------------------------------------------------------

def _programs(tmp_path, fn) -> int:
    """Programs the CPU executed while ``fn`` ran, from a profile."""
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(f"{tmp_path}/plugins/profile/*/*.xplane.pb")
    return sum(e.name == "PjRtCpuExecutable::Execute"
               for plane in ProfileData.from_file(path).planes
               for line in plane.lines for e in line.events)


def test_a_fleets_job_launches_three_programs(fleet, tmp_path):
    st0 = jax.block_until_ready(fleet.init_state())
    counters = jax.jit(lambda fin: (fin.delivered, fin.steps))

    def job():
        fin = fleet.run_quiet(BUDGET, st0)
        return jax.device_get(counters(fin) + (fin.states["hop"],))
    job()                                   # compiles
    # the budget's scalar, the driver, the caller's counters
    counts = [_programs(tmp_path / str(i), job) for i in range(2)]
    assert counts == [3, 3]
    # and a fresh state, which the job above does not make, costs a
    # hundred more: it is made once
    assert _programs(tmp_path / "init", lambda: jax.block_until_ready(
        fleet.init_state())) > 20


def test_expanding_a_seed_launches_no_program(fleet, tmp_path):
    assert _programs(tmp_path / "words", lambda: [
        rng.seed_words(s) for s in range(8)]) == 0
    # a fleet's new identity is two small arrays of seed words
    assert _programs(tmp_path / "rebind", lambda: fleet.rebind_identity(
        BatchSpec(seeds=(1, 0, 3, 2)))) <= 4


@pytest.mark.parametrize("seed", [0, 1, 7, 2**31, 2**32 + 5, 2**63 - 1,
                                  -1, 3_000_000_019])
def test_seed_words_is_the_threefry_block_in_integers(seed):
    s0 = np.uint32(seed & 0xFFFFFFFF)
    s1 = np.uint32((seed >> 32) & 0xFFFFFFFF)
    a, b = rng.threefry2x32(s0, s1 ^ np.uint32(rng._GOLD), np.uint32(0),
                            np.uint32(1))
    assert rng.seed_words(seed) == (int(a), int(b))
