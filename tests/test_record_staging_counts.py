"""One record a driver call (tests/test_zzzzzzzzzzzzzzzrecord.py), the
counts of the staging: ``dense_stage_steps``, ``wide_tail_steps`` and,
since PR 44, ``dense_lanes``, ``tail_lanes`` and ``net_rows`` say which
form of ``_stage_by_rank`` ran, by the scan and the quiet driver alike;
and the sharded engines' counts follow their local twins'."""

import pytest

from record_laws import FLEET, N, _steady
from timewarp_tpu.interp.jax_engine.engine import JaxEngine
from timewarp_tpu.models.gossip import gossip
from timewarp_tpu.net.delays import Quantize, UniformDelay
from timewarp_tpu.obs import profiler


@pytest.mark.parametrize("which", ["ladder", "eager", "burst", "fleet"])
def test_the_staging_counts_say_which_form_ran(which):
    """``dense_stage_steps``: the supersteps whose arrivals were staged
    in the dense form (``engine.py`` ``_stage_by_rank``: the call's
    lanes at least ``_DENSE_STAGE_RATIO`` of its nodes),
    ``wide_tail_steps``: of those, the ones
    whose ranks past 0 were over half the lanes. Carried and read like
    the rungs' counts, by the scan and the quiet driver alike. Since
    PR 44 the same carry counts ``dense_lanes``, ``tail_lanes`` and
    ``net_rows`` where insertion stages by rank: at these widths
    (under ``_PREFIX_SCATTER_LANES`` lanes) every dense superstep
    sends one row through the network and scatters its tail at half
    its lanes, or at all of them (tests/test_stage_tail_law.py holds
    the form over that lane count)."""
    if which == "burst":
        # 64 nodes, fanout 16 in one firing, delays inside one window:
        # the third generation sends some 750 messages on 1024 lanes,
        # and at most 64 of a superstep's arrivals are a node's first
        sc = gossip(64, fanout=16, think_us=2_000, burst=True,
                    end_us=150_000, mailbox_cap=32)
        link = Quantize(UniformDelay(8_000, 9_000), 1_000)
        kw, steps = {"window": "auto"}, 12
    else:
        sc, link = _steady(8192 if which == "ladder" else N)
        kw = {"ladder": {"window": "auto"}, "eager": {},
              "fleet": {"window": "auto", "batch": FLEET}}[which]
        steps = 40
    eng = JaxEngine(sc, link, lint="off", **kw)
    eng.run_quiet(steps)
    st = dict(eng.last_run_stats)
    dense, wide = st["dense_stage_steps"], st["wide_tail_steps"]
    if which == "ladder":
        # one slot a node: the rung of 1024 senders is 1024 lanes for
        # 8192 nodes and keeps the scatters, the top rung is dense
        rungs = eng._sender_rungs(8192)
        assert rungs == [1024, 2048, 4096, 8192]
        by_form = [eng._stages_dense(a) for a in rungs]
        assert not by_form[0] and by_form[-1] and min(st["rung_steps"]) > 0
        assert (dense, wide) == (sum(
            k for k, d in zip(st["rung_steps"], by_form) if d), 0)
        lanes = sum(k * a for k, a, d in zip(st["rung_steps"], rungs,
                                             by_form) if d)
    elif which == "eager":
        assert (dense, wide) == (steps, 0)
        lanes = steps * N
    elif which == "burst":
        assert dense == st["supersteps"] and 0 < wide < dense
        lanes = dense * 1024
    else:
        assert not eng._stages_by_rank() and (dense, wide) == (0, 0)
        assert not {"dense_lanes", "tail_lanes", "net_rows"} & set(st)
    if which != "fleet":
        assert (st["dense_lanes"], st["net_rows"]) == (lanes, dense)
        if which == "burst":
            assert st["tail_lanes"] == (dense + wide) * 512
        else:
            assert st["tail_lanes"] == lanes // 2
        assert profiler.calls()[-1]["counts"]["tail_lanes"] \
            == st["tail_lanes"]
    assert profiler.calls()[-1]["counts"]["dense_stage_steps"] == dense
    eng.run(steps)
    assert (eng.last_run_stats["dense_stage_steps"],
            eng.last_run_stats["wide_tail_steps"]) == (dense, wide)
    for key in ("dense_lanes", "tail_lanes", "net_rows"):
        assert eng.last_run_stats.get(key) == st.get(key), key


def test_sharded_engines_follow_their_local_twins():
    from timewarp_tpu.interp.jax_engine.sharded import (
        ShardedBatchedEngine, ShardedEngine)
    from timewarp_tpu.parallel.mesh import make_mesh
    sc, link = _steady()
    local = JaxEngine(sc, link, window="auto", batch=FLEET)
    local.run_quiet(40)
    fleet = ShardedBatchedEngine(sc, link, make_mesh(2, axis="worlds"),
                                 window="auto", batch=FLEET)
    fleet.run_quiet(40)
    # a device a world: each takes its own world's rung, and the call
    # reports the device whose rungs sum widest
    assert fleet.last_run_stats["rung_lanes"] <= \
        local.last_run_stats["rung_lanes"]
    assert sum(fleet.last_run_stats["rung_steps"]) == 40
    fleet.run(40)
    assert sum(fleet.last_run_stats["rung_steps"]) == 40
    nodes = ShardedEngine(sc, link, make_mesh(2, axis="nodes"))
    for drive in (nodes.run_quiet, nodes.run):
        drive(12)
        st = nodes.last_run_stats
        assert (st["rung_lanes"], st["rung_steps"]) == (12 * N, [12])
        # a device stages what it was handed, on its own nodes: 2048
        # lanes for 1024, the dense form
        assert (st["dense_stage_steps"], st["wide_tail_steps"]) == (12, 0)
        assert (st["dense_lanes"], st["tail_lanes"], st["net_rows"]) \
            == (12 * 2048, 12 * 1024, 12)
