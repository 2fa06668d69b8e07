"""One world laid over a mesh of four by nodes as a deployment
(ISSUE 49): steady mongering on ``ShardedEngine`` through the
benchmark's builder equals ``JaxEngine`` leaf for leaf and the plain
reference fact for fact, at the capacity that cannot overflow and at
the rule's, streamed in calls that are one program, one dispatch and
one readback each on a state that stays four slices on four devices;
the call's record counts what the exchange handed over
(``shards``, ``remote_msgs``, ``bucket_fill_peak``, ``bucket_cap``,
``exchange_lanes``) as the reference counts the same rounds, call by
call and merged; a capacity one under the largest bucket loses exactly
the reference's excess and still reads the uncut peak; and the CLI's
``--bucket-cap`` builds that engine and no other engine takes it.

(Named test_zz* to sort after the whole existing suite.)
"""

import json
import os
import sys

import numpy as np
import pytest

import jax

from timewarp_tpu.cli import main
from timewarp_tpu.interp.jax_engine.sharded import ShardedEngine
from timewarp_tpu.obs import profiler
from timewarp_tpu.obs.metrics import MetricsRegistry, validate_line
from timewarp_tpu.parallel.mesh import make_mesh
from timewarp_tpu.trace.events import assert_states_equal

BENCHMARK = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCHMARK not in sys.path:
    sys.path.insert(0, BENCHMARK)

import run  # noqa: E402
from builders import gossip_steady, gossip_steady_x4  # noqa: E402
from reference import gossip_steady_x4_ref  # noqa: E402

N, SHARDS = 1024, 4
LOCAL = N // SHARDS
#: a stream in calls: the ramp and past it, three single supersteps
#: (a call's counts are then one round's), and on
CALLS = (48, 1, 1, 1, 13)
ENDS = tuple(np.cumsum(CALLS))
COUNTERS = ("shards", "remote_msgs", "bucket_fill_peak", "bucket_cap",
            "exchange_lanes")


def _toy(bucket_cap):
    """The committed cell's files at this file's size."""
    traffic, config = run.load_cell("gossip_steady_1m_x4.rounds")
    config["params"].update(n_nodes=N, bucket_cap=bucket_cap)
    return config, traffic


@pytest.fixture(scope="module")
def reference():
    """The plain reference from node 0, run to the stream's end."""
    ref = gossip_steady_x4_ref.Mongering(_toy(LOCAL)[0]["params"], 0)
    ref.run_to(ENDS[-1])
    return ref


@pytest.fixture(scope="module")
def capacity(reference):
    """The capacities by name: the default (a device's whole outbox
    width), the configuration's rule at this size (the next multiple of
    8 at least 1/16 over the reference's largest bucket), and one under
    that largest bucket."""
    largest = reference.largest_bucket(0, ENDS[-1])
    return {"default": LOCAL, "rule": -(-largest * 17 // 16 // 8) * 8,
            "under": largest - 1}


@pytest.fixture(scope="module")
def solo():
    """``JaxEngine``'s state after the same supersteps."""
    eng = gossip_steady.engine_of(_toy(LOCAL)[0]["params"])
    return eng.run_quiet(int(ENDS[-1]))


@pytest.fixture(scope="module")
def streams(capacity):
    """One engine a capacity (the builder's), streamed in ``CALLS``:
    the cell, the state after each call, each call's stats and what
    the placement gate said of each state."""
    out = {}
    for name, cap in capacity.items():
        cell = gossip_steady_x4.Cell(*_toy(cap))
        st, states, stats, gates = cell.engine.init_state(), [], [], []
        for k in CALLS:
            st = cell.engine.run_quiet(k, st)
            states.append(st)
            stats.append(dict(cell.engine.last_run_stats))
            gates.append(cell._placement(st))
        out[name] = (cell, states, stats, gates)
    return out


# -- (a) the same world, wherever it lives -----------------------------------

@pytest.mark.parametrize("name", ["default", "rule"])
def test_the_sharded_world_is_the_one_device_worlds(streams, solo, name):
    _, states, _, _ = streams[name]
    assert_states_equal(solo, states[-1])
    assert int(states[-1].overflow) == 0


@pytest.mark.parametrize("name", ["default", "rule"])
@pytest.mark.parametrize("call", [0, 2, len(CALLS) - 1])
def test_the_sharded_world_is_the_plain_references(streams, name, call):
    cell, states, _, _ = streams[name]
    # a reference of its own: one runs forwards only
    ref = gossip_steady_x4_ref.Mongering(cell.p, 0)
    want = ref.run_to(int(ENDS[call]))
    rows = cell._rows("state", cell._facts(states[call]), want)
    assert len(rows) == 10 and [r for r in rows if r[1]] == []
    assert want["largest_in_flight"] <= cell.p["mailbox_cap"]


@pytest.mark.parametrize("name", ["default", "rule", "under"])
def test_a_stream_is_one_program_on_a_state_that_stays_put(streams, name):
    cell, states, stats, gates = streams[name]
    assert [s["compiles"] for s in stats] == [1] + [0] * (len(CALLS) - 1)
    assert [(s["dispatches"], s["readbacks"]) for s in stats] \
        == [(1, 1)] * len(CALLS)
    assert [s["supersteps"] for s in stats] == list(CALLS)
    assert gates == [[]] * len(CALLS)
    fresh = cell.engine.init_state()
    for st in states:
        for a, b in zip(jax.tree.leaves(fresh), jax.tree.leaves(st)):
            assert a.sharding == b.sharding
    shards = states[-1].mb_rel.addressable_shards
    assert {s.data.shape for s in shards} == {(24, LOCAL)}
    assert len({s.device for s in shards}) == SHARDS


# -- (b) what the exchange handed over, against the reference -----------------

@pytest.mark.parametrize("name", ["default", "rule", "under"])
def test_the_exchange_counts_what_the_reference_counts(
        streams, capacity, reference, name):
    _, _, stats, _ = streams[name]
    cap = capacity[name]
    for first, last, s in zip((0,) + ENDS, ENDS, stats):
        assert {k: s[k] for k in COUNTERS} == {
            "shards": SHARDS,
            "remote_msgs": reference.remote_pushes(first, last),
            "bucket_fill_peak": reference.largest_bucket(first, last),
            "bucket_cap": cap, "exchange_lanes": SHARDS * cap}
    # a single superstep's call is one round's buckets
    one = reference.buckets(ENDS[1], ENDS[2])[0]
    assert stats[2]["remote_msgs"] == one.sum() - np.trace(one) \
        and stats[2]["bucket_fill_peak"] == one.max()
    # three pushes in four leave their shard once every node pushes
    assert abs(stats[-1]["remote_msgs"] / (CALLS[-1] * N) - 0.75) < 0.02


def test_the_counts_merge_over_streamed_calls(streams, reference):
    cell, _, stats, _ = streams["rule"]
    merged = cell.engine._stats_merge(stats)
    assert merged["remote_msgs"] == reference.remote_pushes(0, ENDS[-1]) \
        == sum(s["remote_msgs"] for s in stats)
    assert merged["bucket_fill_peak"] \
        == reference.largest_bucket(0, ENDS[-1]) \
        == max(s["bucket_fill_peak"] for s in stats)
    assert {k: merged[k] for k in ("shards", "bucket_cap",
                                   "exchange_lanes")} \
        == {k: stats[0][k] for k in ("shards", "bucket_cap",
                                     "exchange_lanes")}
    assert merged["supersteps"] == ENDS[-1] and merged["compiles"] == 1


def test_the_record_and_the_metrics_line_hold_the_counts(streams):
    cell, states, _, _ = streams["rule"]
    cell.engine.run_quiet(1, states[-1])
    stats = cell.engine.last_run_stats
    record = profiler.calls()[-1]
    assert record["counts"] == stats and record["engine"] == "ShardedEngine"
    reg = MetricsRegistry()
    reg.run_summary("steady/sharded", stats)
    line = reg.lines[-1]
    validate_line(line)
    assert {k: line[k] for k in COUNTERS} == {k: stats[k] for k in COUNTERS}
    with pytest.raises(ValueError, match="'remote_msgs' must be int"):
        validate_line({**line, "remote_msgs": 1.5})


def test_no_other_engine_counts_an_exchange():
    eng = gossip_steady.engine_of(_toy(LOCAL)[0]["params"])
    counts = jax.eval_shape(eng._counted, eng.init_state())[1]
    assert counts.remote_msgs is None and counts.bucket_fill_peak is None


# -- (c) a capacity that is short ---------------------------------------------

def test_a_short_capacity_loses_the_excess_and_reads_the_uncut_peak(
        streams, capacity, reference):
    _, states, stats, _ = streams["under"]
    cap = capacity["under"]
    excess = np.maximum(reference.buckets(0, ENDS[-1]) - cap, 0)
    # every node holds the rumor by then: a lost push moves no later one
    assert excess[:reference.saturation_step()].sum() == 0
    assert int(states[-1].overflow) == excess.sum() > 0
    assert max(s["bucket_fill_peak"] for s in stats) == cap + 1
    by_call = [excess[a:b].sum() for a, b in zip((0,) + ENDS, ENDS)]
    overflow = [int(st.overflow) for st in states]
    assert list(np.diff([0] + overflow)) == by_call


def test_the_constructor_caps_the_capacity_at_the_outbox_width():
    j = gossip_steady.engine_of(_toy(LOCAL)[0]["params"])
    for asked, got in ((None, LOCAL), (10 * LOCAL, LOCAL), (72, 72)):
        eng = ShardedEngine(j.scenario, j.link, make_mesh(SHARDS),
                            bucket_cap=asked)
        assert eng.bucket_cap == got


# -- (d) the CLI -----------------------------------------------------------------

_CLI = ["gossip", "--nodes", "256", "--steady", "--mailbox-cap", "24",
        "--link", "quantize:1000:uniform:500:4500", "--steps", "40",
        "--end-us", str(2**50)]


def test_the_cli_builds_the_engine_with_its_bucket_cap(capsys, tmp_path):
    out = tmp_path / "metrics.jsonl"
    assert main(_CLI + ["--engine", "sharded", "--devices", "4",
                        "--bucket-cap", "56", "--telemetry", "counters",
                        "--metrics-out", str(out)]) == 0
    sharded = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert main(_CLI + ["--engine", "general"]) == 0
    solo = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert sharded["delivered"] == solo["delivered"] > 0
    assert sharded["overflow"] == 0
    summary, = [line for line in map(json.loads, out.read_text().splitlines())
                if line["kind"] == "run_summary"]
    assert (summary["shards"], summary["bucket_cap"],
            summary["exchange_lanes"]) == (4, 56, 224)
    assert 0 < summary["bucket_fill_peak"] <= 56
    assert 0 < summary["remote_msgs"] < sharded["delivered"] + 256 * 5


@pytest.mark.parametrize("engine", ["oracle", "general", "edge",
                                    "sharded-edge", "sharded-batched"])
def test_no_other_engine_takes_the_flag(engine):
    extra = ["--batch", "4"] if engine == "sharded-batched" else []
    with pytest.raises(SystemExit, match="--bucket-cap applies to the "
                                         "node-sharded general engine"):
        main(["gossip", "--nodes", "64", "--engine", engine,
              "--bucket-cap", "8"] + extra)
