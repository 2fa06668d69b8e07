"""The chaos fleet as a deployment (ISSUE 53): eight worlds of steady
push gossip, each under its own schedule of crashes, a partition and a
degraded link, on one batched engine with ``faults=`` on. Through the
benchmark's builder at toy size every world equals the benchmark's
plain reference fact for fact, whichever slot it sits in, and the solo
engine under ``fleet.world_schedule(b)``; at 64 nodes the reference,
the oracle and the engine agree three ways, the losses by cause
included; the three controls fail; the call's record counts what the
schedule did by cause and merges over streamed calls; an engine without
``faults`` has neither the counts nor a ``fault`` scope; and the CLI's
``--faults`` once a world builds the fleet the library call builds.

(Named test_zz* to sort after the whole existing suite.)
"""

import json
import os
import sys

import numpy as np
import pytest

import jax

from timewarp_tpu.cli import main
from timewarp_tpu.faults import FaultFleet, parse_faults
from timewarp_tpu.interp.jax_engine.batched import BatchSpec
from timewarp_tpu.interp.jax_engine.engine import JaxEngine
from timewarp_tpu.interp.ref.superstep import SuperstepOracle
from timewarp_tpu.models.gossip import gossip
from timewarp_tpu.net.delays import Quantize, UniformDelay
from timewarp_tpu.trace.events import assert_states_equal
from timewarp_tpu.utils.checkpoint import load_state

BENCHMARK = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCHMARK not in sys.path:
    sys.path.insert(0, BENCHMARK)

from builders import gossip_chaos  # noqa: E402
from reference import gossip_chaos_ref  # noqa: E402

N, WORLDS = 512, 8
CAUSES = ("cut", "down", "purged")
COUNTS = ("fault_cut", "fault_down", "fault_purged", "fault_degraded",
          "fault_restarts", "fault_table_lanes", "fault_gather_lanes")


def _load(kind, name):
    with open(os.path.join(BENCHMARK, kind, name + ".json")) as f:
        return json.load(f)


def _config(n, **cuts):
    traffic = _load("workloads", "gossip_100k_chaos.fleet8")
    config = _load("configs", traffic["config"])
    config["params"].update(
        n_nodes=n, faults=gossip_chaos.schedules(n, WORLDS), **cuts)
    return config, traffic


@pytest.fixture(scope="module")
def cell():
    return gossip_chaos.Cell(*_config(N))


@pytest.fixture(scope="module")
def wants(cell):
    """The plain reference's run of every world, once."""
    return gossip_chaos_ref.Fleet(cell.p).runs()


@pytest.fixture(scope="module")
def fleet_run(cell):
    """One job of the fleet at seed 7, and the record of its call."""
    assert not cell.set_up(7)["failed"]
    job = cell.job(1)
    return job, dict(cell.engine.last_run_stats), cell.fleets[-1]


# -- the program against the plain reference ----------------------------------

@pytest.mark.parametrize("seed", [7, 5_300_000_017])
def test_every_world_equals_the_reference_in_every_slot(cell, wants, seed):
    assert not cell.set_up(seed)["failed"]
    jobs = [cell.job(1), cell.job(2)]
    assert not any(j["failed"] for j in jobs), jobs
    assert sorted(cell.order) == list(range(WORLDS))
    assert not cell.engine.last_run_stats["compiles"]
    rows = cell.compare(gossip_chaos_ref, wants=wants)
    *exact, (name, largest, cap) = rows
    assert len(exact) == 10 and [v for _, v, _ in exact] == [0] * 10, rows
    assert all(limit == 0 for _, _, limit in exact)
    assert name.startswith("reference.largest") and largest + 4 <= cap == 40
    # the same work in every job of every seed
    assert jobs[0]["msgs"] == jobs[1]["msgs"] == sum(
        w["delivered"] for w in wants.values())
    assert jobs[0]["supersteps"] == max(w["steps"] for w in wants.values())


def test_two_seeds_draw_two_orders(cell):
    orders = set()
    for seed in (7, 5_300_000_017):
        cell.set_up(seed)
        orders.add(cell.order)
    assert len(orders) == 2


def test_the_schedule_bites_by_two_causes_and_purges_nothing(wants):
    for seed, w in wants.items():
        assert w["cut"] > 0 and w["down"] > 0 and w["restarts"] == 1, seed
        # a message due before t_down fires its node when it is due,
        # and one due inside the window never enters the mailbox
        assert w["purged"] == 0
        assert (w["hop"] < 0).sum() <= max(N // 500, 8)


def test_a_job_outside_the_guarantees_fails_its_gates():
    # the source's eight slots, and a schedule with no crash in it
    config, traffic = _config(N, mailbox_cap=8)
    config["params"]["faults"] = [
        f"partition:0-{N // 2 - 1}|{N // 2}-{N - 1}:25ms:70ms"] * WORLDS
    failed = gossip_chaos.Cell(config, traffic).set_up(3)["failed"]
    assert "overflow=" in failed and "no message down" in failed


# -- the controls -------------------------------------------------------------

def test_the_three_controls_fail(cell, wants):
    cell.set_up(11)
    cell.job(1)
    assert all(v <= lim for _, v, lim in cell.compare(
        gossip_chaos_ref, wants=wants))
    rows = {name: v for name, v, _ in cell.control(
        gossip_chaos_ref, wants=wants)}
    for control in ("low_word", "swapped_schedules", "no_faults"):
        moved = {k: v for k, v in rows.items()
                 if k.startswith(control + ".fleets") and v}
        assert moved, (control, rows)
    # exchanged schedules move the two worlds they name, and no other
    assert rows["swapped_schedules.fleets_1x8.cut.worlds_that_differ"] == 2
    assert rows["no_faults.fleets_1x8.cut.worlds_that_differ"] == WORLDS
    # the engine is the cell's own again afterwards
    assert not cell.job(2)["failed"]


# -- every world against its solo run; reference, oracle and engine -----------

@pytest.fixture(scope="module")
def small():
    """At 64 nodes: the scenario, the link, the eight schedules, the
    fleet's final state, and a solo engine a world, made once."""
    p = _config(64)[0]["params"]
    sc, link = gossip_chaos.scenario_and_link(p)
    fleet = FaultFleet(tuple(map(parse_faults, p["faults"])))
    eng = JaxEngine(sc, link, window="auto", faults=fleet,
                    batch=BatchSpec(seeds=tuple(range(WORLDS))))
    fin = jax.device_get(eng.run_quiet(1 << 20))
    solos = {}

    def solo(b):
        if b not in solos:
            solos[b] = JaxEngine(sc, link, window="auto", seed=b,
                                 faults=fleet.world_schedule(b))
        return solos[b]
    return p, sc, link, fin, solo


@pytest.mark.parametrize("b", [1, 5, 7])
def test_world_equals_the_solo_engine_under_its_schedule(small, b):
    # the chaos-fleet exactness law (tests/test_zfault_parity.py holds
    # it in general) on this deployment's schedules: an on-grid scale
    # apart, a reboot inside the degraded window, the slowest link
    *_, fin, solo = small
    assert_states_equal(jax.tree.map(lambda x: x[b], fin),
                        jax.device_get(solo(b).run_quiet(1 << 20)))


@pytest.mark.parametrize("b", [1, 5])
def test_reference_oracle_and_engine_agree_at_64_nodes(small, b):
    p, sc, link, _, solo = small
    want = gossip_chaos_ref.World(p, b, p["faults"][b]).run()
    oracle = SuperstepOracle(sc, link, seed=b, window="auto",
                             faults=parse_faults(p["faults"][b]))
    trace = oracle.run()
    eng = solo(b)
    fin = jax.device_get(eng.run_quiet(1 << 20))
    stats = eng.last_run_stats
    for cause in CAUSES + ("degraded", "restarts"):
        assert want[cause] == oracle.fault_counts[cause] \
            == stats["fault_" + cause], cause
    assert sum(want[c] for c in CAUSES) == oracle.fault_dropped_total \
        == int(fin.fault_dropped) > 0
    assert want["steps"] == len(trace) == int(fin.steps)
    assert want["time"] == oracle.time == int(fin.time)
    assert want["delivered"] == int(fin.delivered)
    for field in ("hop", "lcg"):
        np.testing.assert_array_equal(want[field], fin.states[field])
        np.testing.assert_array_equal(want[field], oracle.states[field])
    assert oracle.overflow_total == 0 == int(fin.overflow)


# -- the record ---------------------------------------------------------------

def test_the_record_counts_the_losses_by_cause_a_world(fleet_run, cell):
    job, stats, (nodes, worlds) = fleet_run
    assert set(COUNTS) <= set(stats)
    for name in COUNTS[:5]:
        per_world = stats["world_" + name]
        assert len(per_world) == WORLDS and sum(per_world) == stats[name]
    assert stats["fault_cut"] + stats["fault_down"] \
        + stats["fault_purged"] == job["fault_dropped"] > 0
    assert stats["world_fault_restarts"] == [1] * WORLDS
    assert 0 < stats["fault_degraded"] <= job["msgs"] + job["fault_dropped"]
    # from the shapes: two crash rows (one may reboot), one partition
    # row, one link row, one message a node, every rung the top one.
    # On the node lanes the crash rows twice and the link row's source
    # bit, on the outbox lanes the partition row and the link row's
    # verdict, in the rung the crash rows (until PR 54, the same sum
    # as 2 * 2 + 2 + 2 + 1: the partition row at both ends of a lane,
    # the link row in the rung)
    iters = stats["fleet_iterations"]
    assert stats["rung_lanes"] == iters * N
    assert stats["fault_table_lanes"] == iters * N * (
        (2 * 2 + 1) + (1 + 1) + 2)
    assert job["fault_table_lanes"] == stats["fault_table_lanes"]
    # and a table is read through an index once a message: the
    # destination's packed word (until PR 54 four times: 2 * 1 + 2 * 1,
    # a partition row and a link row at both ends of a lane)
    assert stats["fault_gather_lanes"] == iters * N * 1
    from timewarp_tpu.obs import profiler
    assert any(set(COUNTS) <= set(c["counts"]) for c in profiler.calls())


def test_link_rows_alone_take_the_word_in_the_rung(small):
    # no partition row, so no look-up before the ladder's compaction
    # to ride on (engine.py `_fault_reads`: not early): the senders'
    # bits ride their in-window offsets through the rung's gather and
    # the destinations' packed word is looked up on the rung's lanes.
    # Same integers as the oracle, which keeps `degrade`'s look-ups
    p, sc, link, *_ = small
    sched = parse_faults(
        "crash:5:20ms:50ms; degrade:0-31:16-63:10ms:120ms:2.0; "
        "degrade:all:40-63:60ms:100ms:1.5:300")
    oracle = SuperstepOracle(sc, link, seed=3, window="auto", faults=sched)
    trace = oracle.run()
    eng = JaxEngine(sc, link, window="auto", seed=3, faults=sched)
    assert eng._adaptive_regime() and eng._fault_reads() == (2, False)
    fin = jax.device_get(eng.run_quiet(1 << 20))
    stats = eng.last_run_stats
    assert len(trace) == int(fin.steps) and oracle.time == int(fin.time)
    for field in ("hop", "lcg"):
        np.testing.assert_array_equal(oracle.states[field],
                                      fin.states[field])
    assert oracle.fault_counts["degraded"] == stats["fault_degraded"] > 0
    assert oracle.fault_counts["down"] == stats["fault_down"]
    # one crash row and two link rows' source bits on the node lanes,
    # the crash row and both link rows on the rung's lanes; one look-up
    # a rung lane (until PR 54: 2 x 2)
    iters, rung = stats["supersteps"], stats["rung_lanes"]
    assert stats["fault_table_lanes"] == iters * 64 * (1 + 2) + rung * 3
    assert stats["fault_gather_lanes"] == rung * 1


def test_the_counts_merge_over_streamed_calls(small):
    eng = small[-1](5)
    whole = eng.run_quiet(1 << 20)
    total = dict(eng.last_run_stats)
    st, chunks = None, []
    while st is None or chunks[-1]["supersteps"]:
        st = eng.run_quiet(48, st)
        chunks.append(eng.last_run_stats)
    assert len(chunks) > 4
    assert_states_equal(jax.device_get(whole), jax.device_get(st))
    merged = eng._stats_merge(chunks)
    assert {k: merged[k] for k in COUNTS} == {k: total[k] for k in COUNTS}
    assert merged["fault_cut"] + merged["fault_down"] \
        + merged["fault_purged"] == int(st.fault_dropped) > 0
    # and a run_summary line carries them
    from timewarp_tpu.obs.metrics import METRICS_SCHEMA, validate_line
    validate_line({"schema": METRICS_SCHEMA, "kind": "run_summary",
                   "label": "chaos",
                   **{k: merged[k] for k in (
                       "supersteps", "wall_seconds", "compiles")},
                   **{k: merged[k] for k in COUNTS}})


# -- an engine without faults -------------------------------------------------

def _lowered(eng) -> str:
    return type(eng)._run_while.lower(
        eng, eng.init_state(), eng._coerce_budget(8)[0],
        eng._identity()).as_text(debug_info=True)


@pytest.mark.parametrize("batch", [None, BatchSpec(seeds=(0, 1))])
def test_no_faults_no_counts_no_scope(batch):
    config, _ = _config(64)
    p = config["params"]
    sc, link = gossip_chaos.scenario_and_link(p)
    plain = JaxEngine(sc, link, window="auto", batch=batch)
    if batch is None:
        plain.run_quiet(16)
        assert not [k for k in plain.last_run_stats if "fault" in k]
    assert "/fault" not in _lowered(plain)
    text = _lowered(JaxEngine(sc, link, window="auto", batch=batch,
                              faults=parse_faults(p["faults"][0])))
    stage = "vmap(tw.%s)/fault" if batch else "tw.%s/fault"
    for scope in (stage % "next_event", stage % "fire",
                  (stage % "route") + "/", "/sample/fault/"):
        assert scope in text, scope


# -- the ladder's top rung reads the outbox in place (PR 56) -----------------

def _gathers(eng):
    """Every ``gather`` of the quiet driver as it is traced for
    lowering: ``(branch, scopes)``, ``branch`` the index of the routing
    switch's branch it lies in (None outside it, and where the ladder
    has one rung and no switch), ``scopes`` its name stack."""
    traced = type(eng)._run_while.trace(
        eng, eng.init_state(), eng._coerce_budget(8)[0], eng._identity())

    def inner(eqn):
        for v in eqn.params.values():
            for x in v if isinstance(v, (tuple, list)) else (v,):
                x = getattr(x, "jaxpr", x)
                if hasattr(x, "eqns"):
                    yield x

    def walk(jaxpr, branch, outer):
        for eqn in jaxpr.eqns:
            # an inner program's name stacks start at its call
            scopes = f"{outer}/{eqn.source_info.name_stack}"
            if eqn.primitive.name == "gather":
                yield branch, scopes
            rungs = eqn.primitive.name == "cond" and branch is None \
                and scopes.rstrip("/").endswith(
                    "vmap(tw.route)" if eng.batch else "tw.route")
            for i, sub in enumerate(inner(eqn)):
                yield from walk(sub, i if rungs else branch, scopes)
    return list(walk(traced.jaxpr.jaxpr, None, ""))


def _routing(gathers, branch):
    """The scopes of a branch's gathers that are the ladder's own:
    under ``tw.route``, outside the insertion and the fault masks."""
    return [s for b, s in gathers if b == branch and "tw.route" in s
            and "insert" not in s and "fault" not in s]


def test_the_top_rung_gathers_nothing_and_the_rungs_below_what_they_did():
    # the chaos fleet at 4 096 nodes: rungs of 1 024, 2 048 and 4 096
    config, _ = _config(4096)
    p = config["params"]
    sc, link = gossip_chaos.scenario_and_link(p)
    eng = JaxEngine(sc, link, window="auto",
                    faults=FaultFleet(tuple(map(parse_faults, p["faults"]))),
                    batch=BatchSpec(seeds=tuple(range(WORLDS))))
    rungs = eng._sender_rungs(4096)
    assert rungs == [1024, 2048, 4096] and eng._fault_reads() == (1, True)
    gathers = _gathers(eng)
    assert {b for b, _ in gathers} == {None, 0, 1, 2}
    # one outbox slot, one payload word: a rung under the top gathers
    # the sender's offset, its destination and its payload word, as it
    # did; the top rung reads all three where they lie
    assert [len(_routing(gathers, b)) for b in range(3)] == [3, 3, 0]
    # the insertion's gathers (a fleet's: the destination's hole words)
    # are every rung's alike, the top one's too (ROADMAP B1 (e))
    inserts = [sum(b == i and "insert" in s for b, s in gathers)
               for i in range(3)]
    assert inserts[0] == inserts[1] == inserts[2] > 0
    # and the masks still look one table up, in front of the switch
    assert [b for b, s in gathers if "fault" in s] == [None]
    text = _lowered(eng)
    assert "vmap(tw.route)/cond/branch_2_fun/inplace" in text
    assert "branch_1_fun/inplace" not in text


def test_a_solo_engine_of_512_nodes_routes_without_a_gather():
    # one rung, and it is the top one: no switch, no sender gather
    config, _ = _config(512)
    sc, link = gossip_chaos.scenario_and_link(config["params"])
    eng = JaxEngine(sc, link, window="auto")
    assert eng._adaptive_regime() and eng._sender_rungs(512) == [512]
    # (a solo commutative inbox stages its arrivals and fills its
    # holes on the node lanes: no gather in its insertion either)
    assert not [s for _, s in _gathers(eng) if "tw.route" in s]
    assert "tw.route/inplace" in _lowered(eng)


def test_the_record_names_the_iterations_routed_in_place():
    # steady mongering at 2 048 nodes climbs the ladder's two rungs
    config, _ = _config(2048)
    sc, link = gossip_chaos.scenario_and_link(config["params"])
    eng = JaxEngine(sc, link, window="auto")
    eng.run_quiet(1 << 20)
    stats = eng.last_run_stats
    low, top = stats["rung_steps"]
    assert low > 0 and top > 0 and stats["inplace_rung_steps"] == top
    # summed over a chunked run's calls, and a run_summary line carries it
    merged = eng._stats_merge([stats, stats])
    assert merged["inplace_rung_steps"] == 2 * top
    from timewarp_tpu.obs.metrics import METRICS_SCHEMA, validate_line
    validate_line({"schema": METRICS_SCHEMA, "kind": "run_summary",
                   "label": "ladder",
                   **{k: merged[k] for k in (
                       "supersteps", "wall_seconds", "compiles",
                       "inplace_rung_steps")}})


# -- the CLI ------------------------------------------------------------------

_CLI = ["gossip", "--nodes", "64", "--steady", "--fanout", "1", "--batch",
        "4", "--window", "auto", "--mailbox-cap", "40", "--end-us", "160000",
        "--link", "quantize:1000:uniform:500:4500", "--steps", "1024"]


def _cli_faults(specs):
    return [x for s in specs for x in ("--faults", s)]


def test_cli_faults_once_a_world_builds_the_library_s_fleet(tmp_path,
                                                            capsys):
    specs = gossip_chaos.schedules(64, 4)
    ck = tmp_path / "fleet.npz"
    assert main(_CLI + _cli_faults(specs) + ["--save", str(ck)]) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    sc = gossip(64, fanout=1, steady=True, end_us=160_000, mailbox_cap=40)
    link = Quantize(UniformDelay(500, 4500), 1000)
    eng = JaxEngine(sc, link, window="auto",
                    batch=BatchSpec(seeds=(0, 1, 2, 3)),
                    faults=FaultFleet(tuple(map(parse_faults, specs))))
    fin, _ = eng.run(1024)
    assert out["fault_dropped"] == np.asarray(fin.fault_dropped).tolist()
    assert len(set(out["fault_dropped"])) > 1      # four schedules
    got, meta = load_state(str(ck), eng.init_state())
    assert_states_equal(jax.device_get(got), jax.device_get(fin))
    assert meta["faults"] == specs


def test_cli_faults_once_still_replicates():
    from types import SimpleNamespace
    from timewarp_tpu.cli import build_faults
    specs = gossip_chaos.schedules(64, 4)
    args = SimpleNamespace(faults=specs[2], batch=4, seeds=None, seed=0)
    assert build_faults(args) == parse_faults(specs[2])
    args.faults = specs
    assert build_faults(args) == FaultFleet(tuple(map(parse_faults, specs)))
    args.faults = None
    assert build_faults(args) is None


@pytest.mark.parametrize("given, worlds", [(3, ["--batch", "4"]),
                                           (2, ["--batch", "4"]),
                                           (2, [])])
def test_cli_refuses_a_count_that_is_neither(given, worlds):
    specs = gossip_chaos.schedules(64, 4)[:given]
    argv = [a for a in _CLI if a not in ("--batch", "4")] + worlds
    with pytest.raises(SystemExit) as e:
        main(argv + _cli_faults(specs))
    said = str(e.value)
    assert f"given {given} times" in said
    assert f"{4 if worlds else 1} world" in said
