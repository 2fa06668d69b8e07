"""The token ring with its observer hub as a deployment (ISSUE 39): the
general engine's default contract (the ordered inbox with sender ids,
two outbox slots on the routing ladder) against the benchmark's plain
reference, entry for entry, at ring sizes that are no multiple of 128
lanes; a hub of bounded inbox whose drops are counted exactly; the two
scopes of the ordered inbox's sorts (``tw.deliver/sort``,
``tw.rebase/compact``) and the counter ``fan_in_peak``, carried by an
engine whose inbox is ordered and by no other. Since PR 43 the ranked
insertion of a solo engine cuts its scatters to the prefix that can
land: streamed jobs equal the one-scatter engine's leaf for leaf, and
``scatter_lanes`` reads the cycle's three widths.

(Named test_zz* to sort after the whole existing suite.)
"""

import json
import os
import re
import sys

import numpy as np
import pytest

import jax.numpy as jnp

from timewarp_tpu.interp.jax_engine import engine as engine_module
from timewarp_tpu.interp.jax_engine.batched import BatchSpec
from timewarp_tpu.interp.jax_engine.engine import JaxEngine
from timewarp_tpu.interp.ref.superstep import SuperstepOracle
from timewarp_tpu.models.token_ring import TOKEN, token_ring
from timewarp_tpu.net.delays import FixedDelay, WithDrop
from timewarp_tpu.obs.metrics import MetricsRegistry, validate_line
from timewarp_tpu.trace.events import assert_states_equal

BENCHMARK = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCHMARK not in sys.path:
    sys.path.insert(0, BENCHMARK)

import fleet_reduce  # noqa: E402
import hub_costs  # noqa: E402
import span_reduce  # noqa: E402
from builders import gossip_wave, observer_ring  # noqa: E402
from reference import observer_ring_ref  # noqa: E402

SIZES = (1000, 4096, 8191)           # ring nodes; + 1: the hub
FIELDS = ("cnt", "val", "send_at", "wake", "mailbox_due", "mailbox_src",
          "mailbox_word", "mailbox_kind", "hub_prev", "hub_errs",
          "delivered", "overflow", "steps", "time")
ORDER_SCOPES = {"tw.deliver/sort", "tw.rebase/compact"}


def _load(kind, name):
    with open(os.path.join(BENCHMARK, kind, name + ".json")) as f:
        return json.load(f)


def _cell(n):
    traffic = _load("workloads", "ring_64k.observer")
    config = _load("configs", traffic["config"])
    config["params"].update(n_ring=n, n_nodes=n + 1, n_tokens=n)
    return observer_ring.Cell(config, traffic)


@pytest.fixture(scope="module")
def cells():
    made = {}
    return lambda n: made.get(n) or made.setdefault(n, _cell(n))


def _mismatches(got, want, fields=FIELDS):
    return {f: int(np.sum(np.asarray(got[f]) != np.asarray(want[f])))
            for f in fields}


# -- the program against the plain reference ----------------------------------

@pytest.mark.parametrize("steps", [1, 2, 3, 96])
@pytest.mark.parametrize("n", SIZES)
def test_the_engine_equals_the_reference_entry_for_entry(cells, n, steps):
    c = cells(n)
    c.set_up(3_000_000_019 + n)          # compiles; its job is not used
    start = c.engine.init_state()
    val = start.states["val"].at[:n].set(jnp.asarray(c.val0))
    st = c.engine.run_quiet(
        steps, start._replace(states={**start.states, "val": val}))
    want = observer_ring_ref.ObserverRing(c.p, c.val0).run_to(steps)
    assert want["steps"] == steps
    assert _mismatches(c._facts(st), want) == dict.fromkeys(FIELDS, 0)
    # two supersteps in: the hub's slots hold the notes of senders 0-7
    if steps == 2:
        assert want["mailbox_src"][:, n].tolist() == list(range(8))
        assert (want["mailbox_due"][:, :n] == -1).all()
    if steps >= 2:
        assert c.engine.last_run_stats["fan_in_peak"] \
            == want["fan_in_peak"] == n


@pytest.mark.parametrize("n", SIZES)
def test_a_cycle_delivers_n_plus_8_and_counts_n_minus_8_dropped(cells, n):
    c = cells(n)
    first = c.set_up(7)
    jobs = [first, c.job(1), c.job(2)]
    assert [j["failed"] for j in jobs] == ["", "", ""]
    for j in jobs:
        assert j["supersteps"] == 96 and j["msgs"] == 32 * (n + 8)
        assert j["fan_in_peak"] == n
    assert int(c.state.overflow) == 3 * 32 * (n - 8)
    for lost in ("bad_dst", "bad_delay", "short_delay", "route_drop"):
        assert int(getattr(c.state, lost)) == 0
    stats = c.engine.last_run_stats
    assert (stats["dispatches"], stats["readbacks"]) == (1, 1)
    # two supersteps in three at the least rung that holds the ring's
    # n senders (the top one, of n + 1, only where no other does), the
    # third (the hub alone, no sender) at the ladder's lowest
    rungs = c.engine._sender_rungs(n + 1)
    by_rung = [0] * len(rungs)
    by_rung[0] += 32
    by_rung[min(i for i, r in enumerate(rungs) if r >= n)] += 64
    assert stats["rung_steps"] == by_rung
    assert all(v == 0 for _, v, _ in c.compare(observer_ring_ref))


@pytest.mark.parametrize("n", SIZES[:2])
def test_three_jobs_of_96_equal_one_run_of_288(cells, n):
    c = cells(n)
    c.set_up(5)
    start = c.state
    for i in range(3):
        assert not c.job(i + 1)["failed"]
    assert_states_equal(c.engine.run_quiet(288, start), c.state,
                        "one run of 288 against three jobs of 96")


def _cycle_lanes(eng, n, cut):
    """What a ring cycle's three insertions hand to each scatter: the
    tokens' (every sender uses one slot of two: half the rung's lanes
    hold the prefix), the notes' (the hub keeps 8: an eighth) and the
    hub's own superstep (no arrival at all, at the lowest rung), each
    at full width where ``cut`` says the rung has no widths."""
    rungs = eng._sender_rungs(n + 1)
    wide, low = 2 * min(r for r in rungs if r >= n), 2 * rungs[0]
    return sum(-(-L // d) if cut(L) else L
               for L, d in ((wide, 2), (wide, 8), (low, 8)))


@pytest.mark.parametrize("n", SIZES[:2])
def test_streamed_jobs_with_the_scatters_cut_equal_the_one_scatter_engines(
        cells, n, monkeypatch):
    """With ``_PREFIX_SCATTER_LANES`` patched down every rung scatters
    a prefix: three streamed jobs of 96 supersteps, from the state the
    unpatched engine starts on, end on its states leaf for leaf, and
    ``scatter_lanes`` reads the cycle's three widths 32 times a job."""
    c = cells(n)
    c.set_up(11)
    start, want, plain = c.state, [], []
    for i in range(3):
        assert not c.job(i + 1)["failed"]
        want.append(c.state)
        plain.append(c.engine.last_run_stats["scatter_lanes"])
    assert plain == [32 * _cycle_lanes(c.engine, n, lambda L: False)] * 3
    monkeypatch.setattr(engine_module, "_PREFIX_SCATTER_LANES", 64)
    eng = observer_ring.engine_of(c.p)
    st = start
    for ref in want:
        st = eng.run_quiet(96, st)
        assert eng.last_run_stats["scatter_lanes"] \
            == 32 * _cycle_lanes(eng, n, lambda L: True)
        assert eng.last_run_stats["fan_in_peak"] == n
        assert_states_equal(st, ref, "the scatters cut against one scatter")
    assert int(st.overflow) - int(start.overflow) == 3 * 32 * (n - 8)


@pytest.mark.parametrize("site", ["rung", "eager"])
def test_full_ring_mailboxes_take_their_arrivals_width_and_count_every_drop(
        cells, site, monkeypatch):
    """Every ring node's 8 slots kept by hand (tokens due 10 ms on):
    the timers' tokens, one a node, find no room. The ranks alone pick
    the width, so the cut engine takes that of the arrivals (half the
    rung: where only the lanes that fit counted, PR 43 took an
    eighth) and counts all ``n``; a job streamed from there, through
    the kept tokens' delivery, ends on the one-scatter engine's state
    leaf for leaf, on a rung of the ladder and on the eager path."""
    n = SIZES[0]
    c = cells(n)
    c.set_up(17)
    sc = c.engine.scenario

    def engine():
        if site == "rung":
            return observer_ring.engine_of(c.p)
        return JaxEngine(sc, WithDrop(FixedDelay(c.p["link"]["delay_us"]),
                                      0.1), window=c.p["window"])
    K, ring = sc.mailbox_cap, jnp.arange(n + 1) < n
    slot = jnp.arange(K, dtype=jnp.int32)[:, None]
    st = c.state
    full = st._replace(
        mb_rel=jnp.where(ring, jnp.int32(10_000), st.mb_rel),
        mb_src=jnp.where(ring, (jnp.arange(n + 1, dtype=jnp.int32) - 1) % n,
                         st.mb_src),
        mb_payload=jnp.where(
            ring, jnp.stack([jnp.broadcast_to(100 * slot, (K, n + 1)),
                             jnp.full((K, n + 1), TOKEN, jnp.int32)], axis=1),
            st.mb_payload))
    plain = engine()
    assert plain._adaptive_regime() == (site == "rung")
    first, job = plain.run_quiet(1, full), plain.run_quiet(96, full)
    dropped = int(first.overflow) - int(st.overflow)
    # every token, but those the eager site's link lost on the way
    assert dropped == n if site == "rung" else n * 4 // 5 < dropped < n
    monkeypatch.setattr(engine_module, "_PREFIX_SCATTER_LANES", 64)
    eng = engine()
    L = 2 * (min(r for r in eng._sender_rungs(n + 1) if r >= n)
             if site == "rung" else n + 1)
    assert len(eng._scatter_widths(L)) == 4
    assert_states_equal(eng.run_quiet(1, full), first,
                        "full mailboxes, the timers' tokens")
    # the arrivals end at lane n (fewer where the link dropped some):
    # over a quarter of the lanes, so half of them are taken
    assert eng.last_run_stats["scatter_lanes"] == -(-L // 2)
    assert_states_equal(eng.run_quiet(96, full), job,
                        "full mailboxes, a job of 96")
    assert eng.last_run_stats["fan_in_peak"] \
        == plain.last_run_stats["fan_in_peak"] > K
    assert int(job.overflow) - int(st.overflow) > dropped
    assert int(job.delivered) - int(st.delivered) > K * n


def test_the_widest_ring_here_cuts_its_scatters_as_it_is_built(cells):
    """8191 ring nodes take the rung of 8192 senders, 16 384 lanes:
    ``_PREFIX_SCATTER_LANES`` as the program has it, so the jobs the
    tests above hold to the reference ran the ladder of widths there
    and on no other rung."""
    n = SIZES[2]
    c = cells(n)
    cuts = [len(c.engine._scatter_widths(2 * r)) > 1
            for r in c.engine._sender_rungs(n + 1)]
    assert cuts == [False, False, False, True]
    assert not c.set_up(13)["failed"]
    assert c.engine.last_run_stats["scatter_lanes"] == 32 * _cycle_lanes(
        c.engine, n, lambda L: L >= engine_module._PREFIX_SCATTER_LANES) \
        == 32 * (8192 + 2048 + 2048)


def test_a_job_that_loses_a_count_fails_its_gate(cells, monkeypatch):
    c = cells(1000)
    c.set_up(5)
    monkeypatch.setattr(c, "dropped", c.dropped - 1)
    assert "the hub dropped and counted 31744 notes" in c.job(1)["failed"]
    monkeypatch.setattr(c, "n", 999)
    assert "fan_in_peak 1000, due 999" in c.job(2)["failed"]


# -- the controls ----------------------------------------------------------------

def test_both_controls_fail_the_comparison(cells):
    n = 1000
    c = cells(n)
    c.set_up(4_100_000_007)
    assert not c.job(1)["failed"] and not c.job(2)["failed"]
    assert all(v == 0 for _, v, _ in c.compare(observer_ring_ref))
    rows = {name: v for name, v, _ in c.control(observer_ring_ref)}
    assert {r.partition(".")[0] for r in rows} == {"int16_values",
                                                   "hub_descending"}
    for tag in ("first_job", "window_end", "tokens_in_flight", "hub_inbox"):
        # seeded values reach 2^20: every one wraps in int16
        assert rows[f"int16_values.{tag}.val.mismatches"] == n
        assert rows[f"int16_values.{tag}.steps.mismatches"] == 0
        for f in ("hub_prev", "hub_errs"):
            assert rows[f"hub_descending.{tag}.{f}.mismatches"] == 1
        # the ring itself does not see the hub's order
        assert rows[f"hub_descending.{tag}.val.mismatches"] == 0
    assert rows["int16_values.tokens_in_flight.mailbox_word.mismatches"] == n
    # the hub kept senders n-1 .. n-8 instead of 0 .. 7
    assert rows["hub_descending.hub_inbox.mailbox_src.mismatches"] == 8
    assert rows["hub_descending.window_end.mailbox_src.mismatches"] == 0


def test_a_control_that_passes_is_returned_alone(cells, monkeypatch):
    c = cells(1000)
    c.set_up(5)
    c.job(1)
    # a "control" that is the configuration's own precision passes,
    # and must not hide behind the other
    monkeypatch.setitem(c.control_of, "value_dtype", "int32")
    rows = c.control(observer_ring_ref)
    assert all(name.startswith(("first_job.", "window_end.",
                                "tokens_in_flight.", "hub_inbox."))
               for name, _, _ in rows)
    assert all(v <= limit for _, v, limit in rows)


# -- the reference against the host oracle ------------------------------------------

@pytest.mark.parametrize("steps", [2, 3, 11, 24])
def test_the_reference_equals_the_host_oracle_at_64_plus_1(steps):
    n = 64
    p = {**_load("configs", "token_ring_64k_observer")["params"],
         "n_ring": n, "n_nodes": n + 1, "n_tokens": n}
    val0 = np.random.default_rng(steps).integers(0, 1 << 20, n,
                                                 dtype=np.int32)
    sc = token_ring(n, n_tokens=n, think_us=p["think_us"],
                    bootstrap_us=p["bootstrap_us"], end_us=p["end_us"],
                    with_observer=True, mailbox_cap=p["mailbox_cap"])
    oracle = SuperstepOracle(sc, FixedDelay(p["link"]["delay_us"]),
                             lint="off")
    oracle.states["val"][:n] = val0
    trace = oracle.run(steps)
    want = observer_ring_ref.ObserverRing(p, val0).run_to(steps)
    assert len(trace) == want["steps"] == steps
    assert oracle.time == want["time"]
    assert oracle.overflow_total == want["overflow"]
    assert trace.total_delivered() == want["delivered"]
    for f in ("cnt", "val"):
        assert (oracle.states[f] == want[f]).all(), f
    assert int(oracle.states["prev"][n]) == want["hub_prev"]
    assert int(oracle.states["errs"][n]) == want["hub_errs"]
    never = 1 << 61
    assert [-1 if w > never else w for w in oracle.wake] \
        == want["wake"].tolist()
    # every mailbox, slot by slot in arrival order
    for i, box in enumerate(oracle.mailbox):
        held = [(int(t), int(src), int(pay[0]), int(pay[1]))
                for t, src, pay in box]
        k = len(held)
        assert (want["mailbox_due"][k:, i] == -1).all()
        assert held == list(zip(*(want[f][:k, i].tolist() for f in (
            "mailbox_due", "mailbox_src", "mailbox_word",
            "mailbox_kind")))), i


def test_the_reference_says_what_it_cannot_run():
    p = _load("configs", "token_ring_64k_observer")["params"]
    small = {**p, "n_ring": 16, "n_nodes": 17, "n_tokens": 16}
    val0 = np.zeros(16, np.int32)
    ring = observer_ring_ref.ObserverRing(small, val0)
    ring.run_to(6)
    with pytest.raises(ValueError, match="only runs forwards"):
        ring.run_to(5)
    with pytest.raises(ValueError, match="n_nodes = n_ring"):
        observer_ring_ref.ObserverRing({**small, "n_nodes": 16}, val0)
    with pytest.raises(ValueError, match="every ring node holds"):
        observer_ring_ref.ObserverRing({**small, "n_tokens": 1}, val0)
    # 16 notes an instant and 8 slots: two cycles drop 8 each
    assert ring.facts()["overflow"] == 16 and ring.fan_in_peak == 16


# -- the scopes, and who carries the counter -------------------------------------------

def _wave_engine(n, **kw):
    p = _load("configs", "gossip_100k")["params"]
    sc, link = gossip_wave.scenario_and_link({**p, "n_nodes": n})
    return JaxEngine(sc, link, window="auto", insert="xla", lint="off",
                     **kw)


def _ring_engine(n, **kw):
    sc = token_ring(n, n_tokens=n, think_us=1000, bootstrap_us=1000,
                    end_us=1 << 50, with_observer=True, mailbox_cap=8)
    return JaxEngine(sc, FixedDelay(500), lint="off", **kw)


def _nested_scopes(eng) -> set:
    text = type(eng)._run_while.lower(
        eng, eng.init_state(), eng._coerce_budget(8)[0],
        eng._identity()).as_text(debug_info=True)
    names = re.findall(r'loc\("(jit\(_run_while\)[^"]*)"', text)
    return {span_reduce.stage_of(fleet_reduce.unwrap(n), 2) for n in names}


@pytest.mark.parametrize("make, ordered", [
    (lambda: _ring_engine(1000), True),
    (lambda: _ring_engine(256, batch=BatchSpec(seeds=(0, 1))), True),
    (lambda: _wave_engine(1024, seed=0), False),
    (lambda: _wave_engine(1024, batch=BatchSpec(seeds=(0, 1))), False)],
    ids=["ordered", "ordered-fleet", "commutative", "commutative-fleet"])
def test_the_order_scopes_are_in_an_ordered_engines_text_alone(make, ordered):
    nested = _nested_scopes(make())
    assert "tw.route/insert" in nested
    assert (ORDER_SCOPES <= nested) if ordered \
        else not (ORDER_SCOPES & nested)


@pytest.mark.parametrize("kw", [
    {"seed": 0}, {"batch": BatchSpec(seeds=(0, 1))}], ids=["solo", "fleet"])
def test_a_commutative_inbox_carries_no_fan_in_peak(kw):
    eng = _wave_engine(1024, **kw)
    assert not eng._ranks_fan_in()
    eng.run_quiet(6)
    assert "fan_in_peak" not in eng.last_run_stats
    assert eng._counted(eng.init_state())[1].fan_in_peak is None
    # nor the lanes of scatters it does not cut (PR 43)
    assert not eng._cuts_scatters()
    assert eng._scatter_widths(1 << 20) == (1 << 20,)
    assert "scatter_lanes" not in eng.last_run_stats
    assert eng._counted(eng.init_state())[1].scatter_lanes is None


def test_scatter_lanes_reach_the_summary_line_and_merge_as_a_sum():
    eng = _ring_engine(256)
    assert eng._cuts_scatters()
    eng.run_quiet(3)
    stats = eng.last_run_stats
    # under the constant: one scatter at the one rung's 2 x 257 lanes
    assert stats["scatter_lanes"] == 3 * 2 * 257
    eng.run(3)                           # the scan driver counts it too
    assert eng.last_run_stats["scatter_lanes"] == 3 * 2 * 257
    reg = MetricsRegistry()
    reg.run_summary("observer", stats)
    line, = reg.lines
    assert line["scatter_lanes"] == stats["scatter_lanes"]
    validate_line(line)
    assert eng._stats_merge([stats, stats])["scatter_lanes"] \
        == 2 * stats["scatter_lanes"]


def test_an_ordered_fleet_reads_its_worlds_largest_fan_in():
    n = 256
    eng = _ring_engine(n, batch=BatchSpec(seeds=(0, 1)))
    eng.run_quiet(3)
    assert eng.last_run_stats["fan_in_peak"] == n
    eng.run_quiet(1)                     # the timers alone: one a node
    assert eng.last_run_stats["fan_in_peak"] == 1
    eng.run(4)                           # the scan driver counts it too
    assert eng.last_run_stats["fan_in_peak"] == n
    # a fleet ranks and keeps the one scatter: no width to count
    assert not eng._cuts_scatters()
    assert eng._scatter_widths(1 << 20) == (1 << 20,)
    assert "scatter_lanes" not in eng.last_run_stats
    assert eng._counted(eng.init_state())[1].scatter_lanes is None


def test_the_counter_reaches_the_summary_line_and_merges_as_a_maximum():
    eng = _ring_engine(256)
    eng.run_quiet(3)
    stats = eng.last_run_stats
    assert stats["fan_in_peak"] == 256
    reg = MetricsRegistry()
    reg.run_summary("observer", stats)
    line, = reg.lines
    assert line["fan_in_peak"] == 256
    validate_line(line)
    with pytest.raises(ValueError, match="fan_in_peak"):
        validate_line({**line, "fan_in_peak": 1.5})
    merged = eng._stats_merge([{**stats, "fan_in_peak": 3},
                               {**stats, "fan_in_peak": 7}])
    assert merged["fan_in_peak"] == 7
    assert "fan_in_peak" not in eng._stats_merge(
        [stats, {k: v for k, v in stats.items() if k != "fan_in_peak"}])


# -- the committed cell --------------------------------------------------------------

def test_the_committed_cell_is_bench_pys_row_with_nothing_cut():
    traffic = _load("workloads", "ring_64k.observer")
    config = _load("configs", traffic["config"])
    p = config["params"]
    assert (p["n_ring"], p["n_nodes"], p["n_tokens"]) == (65536, 65537, 65536)
    assert (p["think_us"], p["bootstrap_us"], p["mailbox_cap"]) == (
        1000, 1000, 8)
    assert p["link"] == {"model": "fixed", "delay_us": 500}
    assert p["window"] == 1 and p["with_observer"] and config["reduced"] == []
    assert traffic["chips"] == 1 and traffic["supersteps_per_job"] == 96
    assert "bounded_hub" in config["guarantees"]
    with open(os.path.join(os.path.dirname(BENCHMARK),
                           "BENCHMARK.json")) as f:
        bench = json.load(f)
    mine = [m for m in bench["per_layer"]
            if m.get("workloads") == ["ring_64k.observer"]]
    assert [m["name"] for m in mine] == [
        "hub_superstep_us", "hub_order_us", "hub_route_us", "hub_insert_us",
        "hub_fire_us", "hub_fan_in_peak", "hub_superstep_roofline",
        "hub_scatter_lane_share"]
    assert {m["moves"] for m in mine} == {"msgs_per_s"}
    # found by name: a later PR appends behind them (PR 46 did)
    entry, = [w for w in bench["workloads"]
              if w["name"] == "ring_64k.observer"]
    assert (entry["config"], entry["chips"]) == (config["name"], 1)
    listed, = [c for c in bench["configs"] if c["name"] == config["name"]]
    assert listed["reduced"] == []


def test_the_bytes_of_a_superstep_that_touches_its_state_once():
    # per node: 32 bytes of leaves and 8 slots of four int32 words, read
    # and written; a message that takes a slot writes its four words
    assert hub_costs.hub_superstep_bytes(1, 8, 2, 0) == 2 * (32 + 128)
    assert hub_costs.hub_superstep_bytes(1, 8, 2, 3) == 2 * (32 + 128) + 48
    assert hub_costs.hub_superstep_bytes(65537, 8, 2, 65544 / 3) \
        == 21_321_408
