"""The praos fleet as a deployment (ISSUE 55): four worlds of Praos on
four link medians, world b the pair (seed b, median b), on one batched
engine with ``BatchSpec.link_params``. Through the benchmark's builder
at 2 048 and 8 192 nodes every world equals the benchmark's plain
reference of its own seed and its own link, whichever slot it sits in,
at the end of a job and stopped mid-flood; the three controls fail as
stated (the bfloat16 reference in every world, the exchanged medians in
exactly two, ``link_params=None`` in exactly three); the call's record
counts each world's own senders (``world_sender_lanes``) beside the
busiest's; the CLI's ``--link`` once a world builds the configuration's
engine and each malformed use exits with its message; the grammar's
optional floor and cap parse; and a solo engine carries nothing new.

(Named test_zz* to sort after the whole existing suite.)
"""

import hashlib

import numpy as np
import pytest

from timewarp_tpu import cli
from timewarp_tpu.interp.jax_engine.batched import BatchSpec
from timewarp_tpu.interp.jax_engine.engine import JaxEngine
from timewarp_tpu.net.delays import (LogNormalDelay, ParetoDelay, Quantize,
                                     WithDrop)
from timewarp_tpu.net.links import LINK_GRAMMAR, parse_link
from timewarp_tpu.sweep.spec import fleet_link_params

from praos_laws import _load, _lowered   # puts benchmark/ on the path

from builders import praos_fleet, praos_slots  # noqa: E402
from reference import praos_fleet_ref, praos_ref  # noqa: E402

WORLDS = 4
MEDIANS = [18_000, 20_000, 22_000, 24_000]
#: half the reference's least superstep count over the four worlds
#: (44, 47, 50, 50 at 2 048 nodes; 50, 54, 57, 58 at 8 192)
MID = {2048: 22, 8192: 25}


def _config(n):
    traffic = _load("workloads", "praos_1m.fleet4")
    config = _load("configs", traffic["config"])
    config["params"]["n_nodes"] = n
    traffic["mid_supersteps"] = MID[n]
    return config, traffic


@pytest.fixture(scope="module")
def cells():
    made = {}
    return lambda n: made.get(n) or made.setdefault(
        n, praos_fleet.Cell(*_config(n)))


# -- the program against the plain reference ----------------------------------

@pytest.mark.parametrize("n, seed", [
    (2048, 7), (2048, 5_500_000_017), (8192, 11), (8192, 2**31 + 55)])
def test_every_world_equals_its_reference_at_the_end_and_mid_flood(
        cells, n, seed):
    c = cells(n)
    assert not c.set_up(seed)["failed"]
    jobs = [c.job(1), c.job(2)]
    assert not any(j["failed"] for j in jobs), jobs
    assert sorted(c.order) == list(range(WORLDS))
    assert not c.engine.last_run_stats["compiles"]
    rows = c.compare(praos_fleet_ref)
    exact, held = rows[:-WORLDS], rows[-WORLDS:]
    assert len(exact) == 18 and [v for _, v, _ in exact] == [0] * 18, rows
    assert all(limit == 0 for _, _, limit in exact)
    assert sum(name.startswith(f"mid_{MID[n]}.") for name, _, _ in exact) == 9
    for name, largest, cap in held:
        assert name.startswith("reference.world_") and largest + 4 <= cap == 24
    # two genesis draws a world, the same work in every job of every seed
    (h0, _, worlds), (h1, _, _) = c.runs
    assert len(h0) == WORLDS and (h0 != h1).all()
    ends, mids = c._reference(praos_fleet_ref)
    assert jobs[0]["msgs"] == jobs[1]["msgs"] == sum(
        w["delivered"] for w in ends.values())
    assert jobs[0]["supersteps"] == max(w["supersteps"] for w in ends.values())
    assert [w["supersteps"] for w in worlds] == [
        ends[s]["supersteps"] for s in c.order]
    # the state was stopped mid-flood in every world, and a slower link
    # is a longer flood
    assert all(w["supersteps"] == MID[n] < ends[s]["supersteps"]
               for s, w in mids.items())
    steps = [ends[s]["supersteps"] for s in range(WORLDS)]
    assert steps == sorted(steps) and steps[0] < steps[-1]


def test_two_seeds_draw_two_orders_and_the_pairs_move_together(cells):
    c, orders = cells(2048), set()
    for seed in (7, 5_500_000_017):
        c.set_up(seed)
        orders.add(c.order)
        np.testing.assert_array_equal(
            c.engine.batch.link_params["inner.median_us"],
            [MEDIANS[s] for s in c.order])
        assert c.engine.batch.seeds == c.order
    assert len(orders) == 2


def test_the_restated_recursion_is_chains_own_at_the_end():
    p = {**_config(2048)[0]["params"], "n_slots": 1}
    chain = praos_ref.Chain(praos_fleet_ref.world_params(p, 3, 24_000))
    want, got = chain.run(5), praos_fleet_ref.run(chain, 5)
    assert set(want) < set(got)
    for key, value in want.items():
        np.testing.assert_array_equal(got[key], value)
    assert got["in_flight_count"].sum() == 0
    assert (got["in_flight_earliest"] == -1).all()
    mid = praos_fleet_ref.run(chain, 5, 22)
    assert mid["supersteps"] == 22 and mid["in_flight_count"].sum() > 2048
    assert ((mid["in_flight_earliest"] > mid["time"])
            == (mid["in_flight_count"] > 0)).all()


# -- the controls -------------------------------------------------------------

def test_the_three_controls_fail_each_as_stated(cells):
    c = cells(2048)
    c.set_up(11)
    c.job(1)
    assert all(v <= lim for _, v, lim in c.compare(praos_fleet_ref))
    rows = {name: v for name, v, _ in c.control(praos_fleet_ref)}
    assert {name.partition(".")[0] for name in rows} == {
        "low_precision", "swapped_medians", "no_link_params"}
    mid = f"mid_{MID[2048]}"
    # bfloat16 moves arrivals by whole quanta: mid-flood, every world
    assert rows[f"low_precision.{mid}.worlds_that_differ"] == WORLDS
    assert rows["low_precision.jobs_1x4.supersteps.worlds_that_differ"] \
        + rows["low_precision.jobs_1x4.time.worlds_that_differ"] >= 1
    # what a node ends with does not move: it floods once whenever the
    # tip comes, which is why the comparison stops a state mid-flood
    assert rows["low_precision.jobs_1x4.lcg.nodes_that_differ"] == 0
    # the exchange moves the two worlds it names, and no other
    assert rows["swapped_medians.jobs_1x4.worlds_that_differ"] == 2
    assert rows[f"swapped_medians.{mid}.worlds_that_differ"] == 2
    assert rows[f"swapped_medians.{mid}.in_flight_count.nodes_that_differ"] \
        > 2048 // 4
    assert rows["swapped_medians.jobs_1x4.slot.worlds_misplaced"] == 0
    # all on the engine's own 20 000: world 1's, and no other's
    assert rows["no_link_params.jobs_1x4.worlds_that_differ"] == 3
    assert rows[f"no_link_params.{mid}.worlds_that_differ"] == 3
    # the engine is the cell's own again afterwards
    assert not c.job(2)["failed"]


# -- the record: each world's own senders -------------------------------------

def test_the_record_counts_each_worlds_own_senders(cells):
    c = cells(2048)
    c.set_up(7)
    job = c.job(1)
    stats = c.engine.last_run_stats
    own = stats["world_sender_lanes"]
    assert job["world_sender_lanes"] == own and len(own) == WORLDS
    # a world's own are at most the busiest's, iteration by iteration
    assert all(0 < x <= stats["sender_lanes"] for x in own)
    assert sum(own) < stats["sender_lanes"] * WORLDS
    assert stats["sender_lanes"] <= stats["rung_lanes"]
    # a world's own senders are the plain reference's of that world:
    # the nodes that pushed, summed over its supersteps
    ends, _ = c._reference(praos_fleet_ref)
    assert own == [ends[seed]["senders"] for seed in c.order]
    solo = praos_slots.engine_of(
        {**_load("configs", "praos_1m")["params"], "n_nodes": 2048}, 1)
    _, counts = solo._counted(solo.init_state())
    assert counts.world_sender_lanes is None
    from timewarp_tpu.obs import profiler
    assert any("world_sender_lanes" in call["counts"]
               for call in profiler.calls())


def test_equal_worlds_fill_the_rung_alike_and_the_counts_merge(cells):
    # the control's engine: no link_params; here on four equal seeds
    c = cells(2048)
    c.order = c.seeds
    eng, _ = c._plain()
    assert eng.batch.link_params is None
    assert eng.rebind_identity(BatchSpec(seeds=(1, 1, 1, 1)))
    whole = eng.run_quiet(1 << 20)
    total = dict(eng.last_run_stats)
    assert total["world_sender_lanes"] == [total["sender_lanes"]] * 4
    # streamed in calls of 16 iterations: a world that is out of
    # budget rides the others' iterations and counts nothing of its own
    st, chunks = None, []
    while st is None or chunks[-1]["supersteps"]:
        st = eng.run_quiet(16, st)
        chunks.append(eng.last_run_stats)
    assert int(st.steps[0]) == int(whole.steps[0]) and len(chunks) > 2
    merged = eng._stats_merge(chunks)
    assert merged["world_sender_lanes"] == total["world_sender_lanes"]
    # and a world's own budget stops its count where it stops the world
    eng.run_quiet(np.asarray([4, 64, 64, 64]))
    own = eng.last_run_stats["world_sender_lanes"]
    assert own[0] < own[1] == own[2] == own[3] == total["sender_lanes"]


# -- the CLI: --link once a world ---------------------------------------------

def _line(n, links):
    return ["praos", "--burst", "--nodes", str(n), "--slots", "1",
            "--leader-prob", repr(4.0 / n), "--batch", "4",
            "--mailbox-cap", "24", "--window", "auto"] + [
        x for link in links for x in ("--link", link)]


def _links(medians=MEDIANS, tail=":0.6:8000:150000"):
    return [f"quantize:1000:lognormal:{m}{tail}" for m in medians]


class _Built(Exception):
    pass


def _engine_of(argv, monkeypatch):
    """The engine ``main(argv)`` builds, and nothing run on it."""
    real = cli.build_engine

    def capture(*a, **kw):
        raise _Built(real(*a, **kw))
    monkeypatch.setattr(cli, "build_engine", capture)
    with pytest.raises(_Built) as e:
        cli.main(argv)
    return e.value.args[0]


def test_the_configurations_line_builds_the_cells_engine(cells, monkeypatch):
    config, _ = _config(2048)
    # the deployment names the line, at the configuration's own size
    line = " ".join(_line(1 << 20, _links()))
    assert repr(4.0 / (1 << 20)) == "3.814697265625e-06"
    assert line in config["deployment"].replace("python -m timewarp_tpu ", "")
    eng = _engine_of(_line(2048, _links()), monkeypatch)
    cell = cells(2048)
    assert eng.batch.seeds == (0, 1, 2, 3)
    assert list(eng.batch.link_params) == ["inner.median_us"]
    np.testing.assert_array_equal(
        eng.batch.link_params["inner.median_us"], MEDIANS)
    assert eng.window == cell.engine.window == 8000
    for b in range(WORLDS):
        assert eng.batch.world_link(eng.link, b) == Quantize(LogNormalDelay(
            MEDIANS[b], 0.6, cap_us=150_000, floor_us=8_000), 1_000)
    assert _lowered(eng) == _lowered(cell.engine)


def test_one_link_keeps_its_meaning_and_its_executable(monkeypatch):
    one = _engine_of(_line(2048, _links()[1:2]), monkeypatch)
    assert one.batch.link_params is None
    monkeypatch.undo()
    # given four times alike: nothing differs, nothing is swept
    same = _engine_of(_line(2048, _links([20_000] * 4)), monkeypatch)
    assert same.batch.link_params is None and same.link == one.link
    sc = one.scenario
    lib = JaxEngine(sc, Quantize(LogNormalDelay(
        20_000, 0.6, cap_us=150_000, floor_us=8_000), 1_000),
        window="auto", batch=BatchSpec(seeds=(0, 1, 2, 3)))
    assert _lowered(one) == _lowered(lib)


@pytest.mark.parametrize("links, worlds, said", [
    (_links()[:3], ["--batch", "4"], "given 3 times and the run has 4 worlds"),
    (_links()[:2], [], "given 2 times and the run has 1 world:"),
    (_links()[:3] + ["quantize:1000:uniform:500:4500"], ["--batch", "4"],
     "world 3's link differs from world 0's in more than its sweepable"),
    (_links()[:3] + ["drop:0.1:" + _links()[3]], ["--batch", "4"],
     "world 3's link differs"),
])
def test_cli_refuses_a_malformed_link_study(links, worlds, said):
    argv = [a for a in _line(2048, links) if a not in ("--batch", "4")]
    with pytest.raises(SystemExit) as e:
        cli.main(argv + worlds)
    assert said in str(e.value), str(e.value)


def test_cli_refuses_link_once_a_world_on_an_engine_without_worlds():
    argv = _line(2048, _links()[:2]) + ["--engine", "edge"]
    argv[argv.index("--batch") + 1] = "2"
    with pytest.raises(SystemExit) as e:
        cli.main(argv)
    assert "--link once a world needs a world axis" in str(e.value)


def test_the_sweeps_buckets_take_the_same_path():
    links = [parse_link(s) for s in _links()]
    swept = fleet_link_params(links)
    assert sorted(swept) == ["inner.cap_us", "inner.floor_us",
                             "inner.median_us", "inner.sigma", "quantum_us"]
    assert all(len(v) == WORLDS for v in swept.values())
    only = fleet_link_params(links, that_differ=True)
    assert list(only) == ["inner.median_us"]
    np.testing.assert_array_equal(only["inner.median_us"], MEDIANS)
    assert fleet_link_params(links[:1] * 4, that_differ=True) is None
    with pytest.raises(ValueError, match="world 1's link differs"):
        fleet_link_params([links[0], parse_link("fixed:5")])


# -- the grammar: an optional floor and cap -----------------------------------

def test_the_grammars_optional_floor_and_cap():
    assert parse_link("lognormal:20000:0.6") == LogNormalDelay(20000, 0.6)
    assert parse_link("lognormal:20000:0.6").floor_us == 1
    assert parse_link("lognormal:20000:0.6:8000") == LogNormalDelay(
        20000, 0.6, floor_us=8000)
    assert parse_link("quantize:1000:lognormal:20000:0.6:8000:150000") \
        == Quantize(LogNormalDelay(20000, 0.6, cap_us=150_000,
                                   floor_us=8_000), 1000)
    assert parse_link("pareto:4000:1.5") == ParetoDelay(4000, 1.5)
    assert parse_link("drop:0.1:pareto:4000:1.5:2000:90000") == WithDrop(
        ParetoDelay(4000, 1.5, cap_us=90_000, floor_us=2_000), 0.1)
    # the floor is what --window auto takes
    assert parse_link("lognormal:20000:0.6:8000").min_delay_us == 8000
    assert "lognormal:MEDIAN:SIGMA[:FLOOR[:CAP]]" in LINK_GRAMMAR
    assert "pareto:XM:ALPHA[:FLOOR[:CAP]]" in LINK_GRAMMAR


@pytest.mark.parametrize("bad, said", [
    ("lognormal:20000:0.6:8000:150000:7", "optionally FLOOR and CAP"),
    ("lognormal:20000:0.6:x", "invalid literal"),
    ("lognormal:20000:0.6:0", "FLOOR must be >= 1"),
    ("pareto:4000:1.5:9000:8000", "CAP 8000 is under FLOOR 9000"),
    ("pareto:4000", "optionally FLOOR and CAP"),
])
def test_a_malformed_floor_or_cap_names_the_grammar(bad, said):
    with pytest.raises(SystemExit) as e:
        parse_link(bad)
    assert said in str(e.value) and LINK_GRAMMAR in str(e.value)


# -- a solo engine carries nothing new ----------------------------------------

#: tests/test_praos_lowering.py's constant: the quiet driver of
#: ``praos_1m.slots``' engine at 2^11 nodes, two slots, as PR 56 left it
#: (the top rung reads the outbox in place; 2655443c024a… from PR 48 on)
_SLOTS_LOWERING = \
    "756f016e125d243e511de9d42383762c71472edfb5c5bd4404590cf127330543"


def test_a_solo_engine_lowers_to_the_text_it_had():
    solo = praos_slots.engine_of(
        {**_load("configs", "praos_1m")["params"], "n_nodes": 1 << 11}, 2)
    text = _lowered(solo)
    assert hashlib.sha256(text.encode()).hexdigest() == _SLOTS_LOWERING
    st, counts = solo._counted(solo.init_state())
    assert counts.world_sender_lanes is None
    # and the fleet's carry holds the one count more, a world a row
    config, _ = _config(2048)
    sc, link = praos_fleet.scenario_and_link(config["params"], 1)
    fleet = JaxEngine(sc, link, window="auto",
                      batch=BatchSpec(seeds=(0, 1), link_params={
                          "inner.median_us": MEDIANS[:2]}))
    _, counts = fleet._counted(fleet.init_state())
    assert counts.world_sender_lanes.shape == (2,)


def test_every_operation_of_a_worlds_link_draw_is_under_the_sample_scope():
    # the rebind_link tracers enter inside `sample`: every operation
    # whose operand is the world's median carries the scope's name
    config, _ = _config(2048)
    sc, link = praos_fleet.scenario_and_link(config["params"], 1)
    fleet = JaxEngine(sc, link, window="auto",
                      batch=BatchSpec(seeds=(0, 1), link_params={
                          "inner.median_us": MEDIANS[:2]}))
    jaxpr = type(fleet)._run_while.trace(
        fleet, fleet.init_state(), fleet._coerce_budget(8)[0],
        fleet._identity()).jaxpr
    found = []

    def walk(jx, medians, path):
        """Equations that read one of ``medians`` (vars of ``jx``), or
        a value computed from one by converts and broadcasts alone."""
        live = set(medians)
        for eqn in jx.eqns:
            hit = [i for i, v in enumerate(eqn.invars)
                   if not hasattr(v, "val") and v in live]
            if not hit:
                continue
            scope = path + "/" + str(eqn.source_info.name_stack)
            subs = [s for s in (eqn.params.get("jaxpr"),
                                eqn.params.get("body_jaxpr"),
                                *eqn.params.get("branches", ()))
                    if s is not None]
            if subs:
                for sub in subs:
                    inner = getattr(sub, "jaxpr", sub)
                    offset = len(eqn.invars) - len(inner.invars)
                    walk(inner, [inner.invars[i - offset] for i in hit
                                 if i >= offset], scope)
            elif eqn.primitive.name in ("convert_element_type",
                                        "broadcast_in_dim", "squeeze",
                                        "reshape"):
                live.update(eqn.outvars)
            else:
                found.append((eqn.primitive.name, scope))
    # the identity's link-parameter vector is the driver's last operand
    walk(jaxpr.jaxpr, [jaxpr.jaxpr.invars[-1]], "")
    assert found, "the walk lost the median"
    assert {p for p, _ in found} == {"mul"}, found
    assert all("sample" in scope for _, scope in found), found
