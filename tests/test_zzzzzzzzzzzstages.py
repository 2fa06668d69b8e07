"""The program's own names (ISSUE 24): every superstep stage of
``common.STAGES`` is a ``jax.named_scope`` of the drivers' lowered
programs, in each routing regime; the fused ring's kernel has its
name; every driver call is a ``tw.<driver>`` span with ``tw.dispatch``
and ``tw.wait`` inside it, carrying ``run`` and ``cause``; and
``last_run_stats`` counts what the call launched and read back.

(Named test_zz* to sort after the whole existing suite.)
"""

import re

import pytest

import jax
import jax.numpy as jnp

from timewarp_tpu.interp.jax_engine.common import STAGES, Stages
from timewarp_tpu.interp.jax_engine.edge_engine import EdgeEngine
from timewarp_tpu.interp.jax_engine.engine import JaxEngine
from timewarp_tpu.interp.jax_engine.fused_ring import FusedRingEngine
from timewarp_tpu.models.gossip import gossip
from timewarp_tpu.models.token_ring import token_ring
from timewarp_tpu.net.delays import (FixedDelay, Quantize, UniformDelay,
                                     WithDrop)
from timewarp_tpu.obs.profiler import profile_session, span


def _gossip(n=64):
    sc = gossip(n, fanout=3, burst=True, end_us=150_000, mailbox_cap=16)
    return sc, Quantize(UniformDelay(3000, 9000), 1000)


def _ring(n=16):
    sc = token_ring(n, n_tokens=4, think_us=2000, bootstrap_us=1000,
                    end_us=120_000, with_observer=False, mailbox_cap=8)
    return sc, FixedDelay(500)


def _op_names(eng, *args) -> set:
    """Every ``op_name`` path of the engine's lowered quiet driver."""
    text = type(eng)._run_while.lower(eng, *args).as_text(debug_info=True)
    return set(re.findall(r'loc\("(jit\(_run_while\)[^"]*)"', text))


def _scopes(names) -> set:
    """The ``tw.`` scope paths among ``op_name`` paths:
    ``jit(f)/while/body/tw.route/insert/sort`` gives ``tw.route`` and
    ``tw.route/insert``."""
    out = set()
    for name in names:
        parts = name.split("/")
        for i, p in enumerate(parts):
            if p.startswith("tw."):
                for j in range(i + 1, len(parts)):
                    out.add("/".join(parts[i:j]))
                break
    return out


# the two routing regimes of JaxEngine._superstep: what marks each (a
# link that can drop is the eager regime's, as tests/insertion_laws.py
# ``SITE`` has it) and the nested scope that only it has
REGIMES = {
    "adaptive": (lambda link: link, "tw.route/sample"),
    "dense": (lambda link: WithDrop(link, 0.1), "tw.route/sort"),
}


@pytest.mark.parametrize("regime", sorted(REGIMES))
def test_every_stage_is_a_scope_of_the_general_driver(regime):
    sc, link = _gossip(64)
    relink, its_own = REGIMES[regime]
    eng = JaxEngine(sc, relink(link), lint="off", window="auto")
    assert eng._adaptive_regime() == (regime != "dense")
    names = _op_names(eng, eng.init_state(), jnp.int64(8), eng._identity())
    scopes = _scopes(names)
    assert set(STAGES) <= scopes, sorted(set(STAGES) - scopes)
    # the one scan for the next event is before the loop; inside it
    # each superstep ends on its successor's (`_superstep_carried`),
    # and the condition reads what the loop carries (ISSUE 34)
    assert any(n.startswith("jit(_run_while)/tw.next_event") for n in names)
    assert any("/body/tw.next_event" in n for n in names)
    assert not [n for n in names if "/cond/" in n and "tw.next_event" in n]
    # the parts that have a function of their own are nested scopes
    assert {"tw.route/insert", its_own} <= scopes
    the_others, = {mark for _, mark in REGIMES.values()} - {its_own}
    assert the_others not in scopes
    # a stage is never opened inside another
    assert not [s for s in scopes if s.count("tw.") > 1]


def test_the_ring_names_its_stages_and_its_kernel():
    sc = token_ring(8192, n_tokens=8192, think_us=0, bootstrap_us=1000,
                    end_us=1 << 40, with_observer=False, mailbox_cap=4)
    eng = FusedRingEngine(sc, FixedDelay(500), cap=2, interpret=True)
    names = _op_names(eng, eng.init_state(), jnp.int64(4))
    scopes = _scopes(names)
    assert {"tw.next_event", "tw.ring_kernel", "tw.finish"} <= scopes
    assert any("tw.ring_kernel/tw_ring_superstep" in s for s in scopes)
    # the one scan for the next event is before the loop; inside it the
    # condition reads the minimum the kernel reported (fused_ring._step)
    assert any(n.startswith("jit(_run_while)/tw.next_event") for n in names)
    assert not [n for n in names if "/cond/" in n and "tw.next_event" in n]


def test_stages_walks_scopes_without_nesting_them():
    def f(x):
        with Stages() as stage:
            stage("tw.fire")
            y = x + 1
            stage("tw.route")
            return y * 2
    text = jax.jit(f).lower(1.0).as_text(debug_info=True)
    assert "jit(f)/tw.fire/add" in text and "jit(f)/tw.route/mul" in text
    assert "tw.fire/tw.route" not in text
    # and it leaves nothing open behind it
    assert "tw." not in jax.jit(lambda x: x - 1).lower(1.0).as_text(
        debug_info=True)


def _engines():
    yield "general", JaxEngine(*_gossip(), window="auto", lint="off")
    yield "edge", EdgeEngine(*_ring(), lint="off")
    sc = token_ring(8192, n_tokens=8192, think_us=0, bootstrap_us=1000,
                    end_us=1 << 40, with_observer=False, mailbox_cap=4)
    yield "ring", FusedRingEngine(sc, FixedDelay(500), cap=2,
                                  interpret=True)


@pytest.mark.parametrize("which", ["general", "edge", "ring"])
def test_run_quiet_launches_once_and_reads_back_once(which):
    eng = dict(_engines())[which]
    eng.run_quiet(5)
    st = eng.last_run_stats
    assert (st["dispatches"], st["readbacks"]) == (1, 1), st
    assert st["supersteps"] == 5 and st["wall_seconds"] > 0


def test_a_guard_that_runs_is_one_more_readback():
    eng = JaxEngine(*_gossip(), window="auto", lint="off", verify="guard")
    eng.run_quiet(5)
    assert eng.last_run_stats["readbacks"] == 2
    assert eng.last_run_stats["dispatches"] == 1


def test_chunked_drivers_sum_the_counts():
    eng = JaxEngine(*_gossip(), window="auto", lint="off")
    merged = eng._stats_merge([
        {"supersteps": 3, "wall_seconds": .1, "compiles": 1,
         "dispatches": 1, "readbacks": 1},
        {"supersteps": 2, "wall_seconds": .1, "compiles": 0,
         "dispatches": 1, "readbacks": 2}])
    assert (merged["dispatches"], merged["readbacks"]) == (2, 3)
    assert eng._stats_merge([])["dispatches"] == 0
    # and what routing and a fleet's worlds counted (ISSUE 35, ROADMAP
    # D3), elementwise; a key that a chunk lacks is left out
    assert "rung_lanes" not in merged
    fleet = [{"supersteps": 5, "wall_seconds": .1, "compiles": 0,
              "dispatches": 1, "readbacks": 1, "rung_lanes": 3072,
              "sender_lanes": 1500, "rung_steps": [3, 0],
              "fleet_iterations": 3, "world_supersteps": [3, 2]},
             {"supersteps": 4, "wall_seconds": .1, "compiles": 0,
              "dispatches": 1, "readbacks": 1, "rung_lanes": 4096,
              "sender_lanes": 2500, "rung_steps": [0, 2],
              "fleet_iterations": 2, "world_supersteps": [2, 2]}]
    merged = eng._stats_merge(fleet)
    assert (merged["rung_lanes"], merged["sender_lanes"]) == (7168, 4000)
    assert merged["rung_steps"] == [3, 2]
    assert (merged["fleet_iterations"], merged["world_supersteps"]) == \
        (5, [5, 4])
    assert "rung_steps" not in eng._stats_merge(
        fleet + [{"supersteps": 1, "wall_seconds": .1, "compiles": 0}])


def _host_events(logdir):
    import glob
    from jax.profiler import ProfileData
    path, = glob.glob(f"{logdir}/plugins/profile/*/*.xplane.pb")
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                out += [(e.start_ns, e.start_ns + e.duration_ns, e.name,
                         dict(e.stats)) for e in line.events
                        if e.name.startswith("tw.")]
    return sorted(out, key=lambda e: e[:3])


def test_driver_spans_carry_run_and_cause(tmp_path):
    eng = JaxEngine(*_gossip(), window="auto", lint="off")
    eng.run_quiet(3)                      # compile outside the session
    eng.run(3)
    key = "jax_compilation_cache_include_metadata_in_key"
    was = getattr(jax.config, key)
    with profile_session(str(tmp_path)):
        assert getattr(jax.config, key) is True
        st = eng.run_quiet(3)
        with span("tw.sweep.bucket", bucket=7):
            eng.run(3, state=st)
    assert getattr(jax.config, key) == was
    evs = _host_events(tmp_path)
    by_name = {}
    for e in evs:
        by_name.setdefault(e[2], []).append(e)
    quiet, = by_name["tw.run_quiet"]
    run_, = by_name["tw.run"]
    assert "cause" not in quiet[3]
    assert run_[3]["cause"] == "tw.sweep.bucket"
    assert by_name["tw.sweep.bucket"][0][3]["bucket"] == 7
    assert run_[3]["run"] == quiet[3]["run"] + 1
    for outer in (quiet, run_):
        inner = [e for e in evs if e[2] in ("tw.dispatch", "tw.wait")
                 and e[3]["run"] == outer[3]["run"]]
        assert [e[2] for e in inner] == ["tw.dispatch", "tw.wait"]
        for e in inner:
            assert e[3]["cause"] == outer[2]
            assert outer[0] <= e[0] and e[1] <= outer[1]
    assert "tw.guard" not in by_name      # no guard ran
