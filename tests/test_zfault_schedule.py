"""Fault-schedule construction, grammar, lint rules, and properties
(faults/schedule.py, analysis/fault_lint.py, faults/properties.py).

(Named to sort after test_world_batch.py — tier-1 truncation rule.)
"""

import pytest

from timewarp_tpu.analysis import LintError, lint_fault_schedule
from timewarp_tpu.faults import (ClockSkew, FaultFleet, FaultSchedule,
                                 LinkWindow, NodeCrash, Partition,
                                 TraceRow, converged,
                                 eventually_delivered, parse_faults)
from timewarp_tpu.models.token_ring import token_ring
from timewarp_tpu.trace.events import SuperstepTrace


# -- event / schedule validation ------------------------------------------

def test_event_validation_errors():
    with pytest.raises(ValueError, match="node id >= 0"):
        NodeCrash(-1, 0, 10)
    with pytest.raises(ValueError, match="int µs"):
        NodeCrash(0, 1.5, 10)
    with pytest.raises(ValueError, match="at least two groups"):
        Partition(((0, 1),), 0, 10)
    with pytest.raises(ValueError, match="two partition groups"):
        Partition(((0, 1), (1, 2)), 0, 10)
    with pytest.raises(ValueError, match="group 1 is empty"):
        Partition(((0, 1), ()), 0, 10)
    with pytest.raises(ValueError, match="scale"):
        LinkWindow(None, None, 0, 10, scale=0.0)
    with pytest.raises(ValueError, match="extra_us"):
        LinkWindow(None, None, 0, 10, extra_us=-5)
    with pytest.raises(ValueError, match="NodeCrash / Partition"):
        FaultSchedule(("crash",))


def test_tables_shapes_and_fleet_padding():
    s0 = FaultSchedule((NodeCrash(1, 10, 20),
                        Partition(((0, 1), (2, 3)), 5, 15)))
    s1 = FaultSchedule((NodeCrash(2, 30, 40, reset_state=True),
                        NodeCrash(3, 50, 60),
                        LinkWindow((0,), (1,), 5, 9, scale=2.0)))
    fleet = FaultFleet((s0, s1))
    ft = fleet.tables(4)
    assert ft.crash_node.shape == (2, 2)       # [B, Cmax]
    assert ft.part_group.shape == (2, 1, 4)
    assert ft.link_src.shape == (2, 1, 4)
    assert fleet.n_restarts == 2
    # world_schedule returns the PADDED shape; padding rows are inert
    w0 = fleet.world_schedule(0)
    t0 = w0.tables(4)
    assert t0.crash_node.shape == (2,)
    assert int(t0.crash_up[1]) == int(t0.crash_down[1]) == 0
    with pytest.raises(ValueError, match="cannot shrink"):
        s1.padded(1, 0, 0)
    with pytest.raises(ValueError, match="at least one world"):
        FaultFleet(())


def test_skews_sum_and_min_delay_floor():
    s = FaultSchedule((ClockSkew(1, 100), ClockSkew(1, 50),
                       LinkWindow(None, None, 0, 10, scale=0.25)))
    assert int(s.tables(4).skew[1]) == 150
    assert s.has_skew
    # a shrink window lowers the windowed-exactness floor: 4000 * 1/4
    assert s.min_delay_floor(4_000) == 1_000
    assert FaultSchedule(()).min_delay_floor(4_000) == 4_000
    # overlapping shrink windows COMPOUND (degrade applies rows in
    # order): the floor is the greedy fold, 4000 -> 2000 -> 1000
    s2 = FaultSchedule((LinkWindow(None, None, 0, 10, scale=0.5),
                        LinkWindow(None, None, 5, 15, scale=0.5)))
    assert s2.min_delay_floor(4_000) == 1_000
    # a grow window never raises the floor above the link's own
    s3 = FaultSchedule((LinkWindow(None, None, 0, 10, scale=3.0),))
    assert s3.min_delay_floor(4_000) == 4_000


# -- the --faults grammar --------------------------------------------------

def test_parse_faults_grammar():
    sched = parse_faults(
        "crash:3:5s:9s:reset; partition:0-3|4-7:2s:4s; "
        "degrade:0-1+5:all:1s:2s:4.0:10ms; skew:2:250")
    assert len(sched.crashes) == 1 and sched.crashes[0].reset_state
    assert sched.crashes[0].t_down == 5_000_000
    assert sched.partitions[0].groups == ((0, 1, 2, 3), (4, 5, 6, 7))
    lw = sched.link_windows[0]
    assert lw.src == (0, 1, 5) and lw.dst is None
    assert lw.extra_us == 10_000 and lw.scale == 4.0
    assert sched.skews[0].offset_us == 250


@pytest.mark.parametrize("bad", [
    "crash:3:5s",                      # missing UP
    "crash:3:5s:9s:maybe",             # bad reset token
    "partition:0-3:2s:4s",             # one group
    "degrade:all:all:1s:2s",           # missing scale
    "skew:2",                          # missing offset
    "explode:1:2:3",                   # unknown kind
    "crash:3:5x:9s",                   # bad time
    "",                                # empty
])
def test_parse_faults_rejects_with_grammar(bad):
    with pytest.raises(SystemExit, match="grammar|FAULT"):
        parse_faults(bad)


# -- TW5xx lint rules ------------------------------------------------------

def _sc(n=8):
    return token_ring(n, with_observer=False)


def test_tw501_node_out_of_range():
    rep = lint_fault_schedule(
        FaultSchedule((NodeCrash(99, 0, 10),)), _sc())
    assert "TW501" in rep.codes() and not rep.ok


def test_tw502_overlapping_or_touching_crash_windows():
    rep = lint_fault_schedule(
        FaultSchedule((NodeCrash(1, 0, 50), NodeCrash(1, 40, 80))),
        _sc())
    assert "TW502" in rep.codes() and not rep.ok
    # ADJACENT windows are flagged too: single-pass deferral lands an
    # event exactly on the second window's t_down — it would fire
    # inside it (faults/apply.py)
    rep2 = lint_fault_schedule(
        FaultSchedule((NodeCrash(1, 0, 50), NodeCrash(1, 50, 80))),
        _sc())
    assert "TW502" in rep2.codes()
    # windows separated by a gap are fine
    rep3 = lint_fault_schedule(
        FaultSchedule((NodeCrash(1, 0, 50), NodeCrash(1, 51, 80))),
        _sc())
    assert "TW502" not in rep3.codes()


def test_tw503_empty_window():
    rep = lint_fault_schedule(
        FaultSchedule((Partition(((0, 1), (2, 3)), 40, 40),)), _sc())
    assert "TW503" in rep.codes() and not rep.ok


def test_tw504_reset_without_init_batched():
    sc = _sc()
    sc.init_batched = None  # force the host-loop-template path
    rep = lint_fault_schedule(
        FaultSchedule((NodeCrash(1, 0, 10, reset_state=True),)), sc)
    assert "TW504" in rep.codes() and rep.ok  # warning, not error


def test_lint_sweep_carries_fault_schedule():
    """``timewarp-tpu lint --faults`` runs the TW5xx rules against
    every swept scenario (the sweep surface of the fault lints)."""
    from timewarp_tpu.cli import lint_sweep
    bad = FaultSchedule((NodeCrash(99, 0, 10),))
    _, rep = lint_sweep(["ping-pong"], probe=False, faults=bad)
    assert "TW501" in rep.codes() and not rep.ok
    _, clean = lint_sweep(["ping-pong"], probe=False,
                          faults=FaultSchedule((NodeCrash(1, 0, 10),)))
    assert "TW501" not in clean.codes()


def test_engine_lint_error_mode_refuses():
    from timewarp_tpu.interp.jax_engine.engine import JaxEngine
    from timewarp_tpu.net.delays import FixedDelay
    sc = _sc()
    bad = FaultSchedule((NodeCrash(99, 0, 10),))
    with pytest.raises(LintError, match="TW501"):
        JaxEngine(sc, FixedDelay(500), faults=bad, lint="error")
    # warn mode constructs (the fault is inert — TW501 says so)
    JaxEngine(sc, FixedDelay(500), faults=bad, lint="warn")


# -- engine guards ---------------------------------------------------------

def test_engine_fault_guards():
    from timewarp_tpu.interp.jax_engine.engine import (BatchSpec,
                                                       JaxEngine)
    from timewarp_tpu.net.delays import FixedDelay
    sc = _sc()
    link = FixedDelay(500)
    sched = FaultSchedule((NodeCrash(1, 0, 10),))
    with pytest.raises(ValueError, match="FaultSchedule"):
        JaxEngine(sc, link, faults="crash:1:0:10")
    with pytest.raises(ValueError, match="batch=BatchSpec"):
        JaxEngine(sc, link, faults=FaultFleet((sched,)))
    with pytest.raises(ValueError, match="world schedules"):
        JaxEngine(sc, link, batch=BatchSpec(seeds=(0, 1, 2)),
                  faults=FaultFleet((sched, sched)))
    # a shrink-degradation window lowers the exact-window floor
    shrink = FaultSchedule((
        LinkWindow(None, None, 0, 10_000, scale=0.1),))
    from timewarp_tpu.net.delays import Quantize, UniformDelay
    wlink = Quantize(UniformDelay(3_000, 9_000), 1_000)
    with pytest.raises(ValueError, match="min_delay_us"):
        JaxEngine(sc, wlink, window=3_000, faults=shrink)
    # auto resolves to the DEGRADED floor: 3000 µs * 1/10 = 300 µs
    assert JaxEngine(sc, wlink, window="auto",
                     faults=shrink).window == 300


# -- properties ------------------------------------------------------------

def _trace(rows):
    return SuperstepTrace.from_rows(rows)


def test_properties_eventually_delivered_and_converged():
    rows = [(t, 1, 0, r, 0, 0, 0, 0)
            for t, r in ((10, 1), (20, 0), (30, 2), (40, 0))]
    tr = _trace(rows)
    assert eventually_delivered(tr, 25)          # t=30 delivers
    assert not eventually_delivered(tr, 35)      # nothing after
    assert converged(tr, lambda r: r.recv_count >= 1) is False
    assert converged(tr, lambda r: r.recv_count <= 2)
    assert converged(tr, lambda r: r.recv_count == 0)  # from row 3 on
    assert not converged(_trace([]), lambda r: True)
    assert isinstance(TraceRow(*tr.row(0)).t, int)
