"""The mailbox-insertion law, shapes the matrix does not have
(tests/test_insert_oracle_adaptive.py; tests/insertion_laws.py has the
view and the comparisons).

Praos (``needs_key``, payload width 2, a lognormal link), the
socket-state hub (1023 clients into one mailbox), a two-world faulted
fleet (world b's slice against the solo oracle under
``fleet.world_schedule(b)``), and the three refusals that are left of
the ``insert=`` selection. The ladder's rungs and the checkpoint handed
from solo runs to a fleet are tests/test_insert_rungs.py.
"""

import functools
import os

import pytest

import jax

from insertion_laws import (INBOX, _burst, hold_to_oracle, oracle_catches_up,
                            pair)
from timewarp_tpu.faults import FaultFleet, FaultSchedule, NodeCrash, Partition
from timewarp_tpu.interp.jax_engine.batched import BatchSpec, world_slice
from timewarp_tpu.interp.jax_engine.engine import JaxEngine
from timewarp_tpu.interp.ref.superstep import SuperstepOracle
from timewarp_tpu.models.gossip import gossip
from timewarp_tpu.models.praos import praos
from timewarp_tpu.models.socket_state import socket_state
from timewarp_tpu.net.delays import LogNormalDelay, Quantize, UniformDelay


@pytest.mark.parametrize("n", [1024, 1000], ids="n{}".format)
def test_praos_needs_key_payload2_equals_oracle(n):
    """Leadership draws from the firing key, payload width 2, slot
    timers and diffusion bursts under the lognormal link's 8 ms
    window: at 2 supersteps and at 12."""
    sc = praos(n, slot_us=100_000, n_slots=30, leader_prob=4.0 / n,
               fanout=8, burst=True, mailbox_cap=8)
    assert sc.needs_key and sc.payload_width == 2
    link = Quantize(LogNormalDelay(20_000, 0.6, cap_us=150_000,
                                   floor_us=8_000), 1_000)
    eng, orc = pair(sc, link, window="auto")
    st = hold_to_oracle(f"praos-n{n}", eng, orc, (2, 10))
    assert int(st.delivered) > n


def test_socket_state_hub_fan_in_equals_oracle():
    """1023 clients into the server's one mailbox: ranks far past the
    mailbox's depth at one destination. Every scheduled ping is
    delivered or counted in ``overflow``, as the oracle has it."""
    sc = socket_state(n_clients=1023, seed=1, send_interval_us=20_000,
                      server_life_us=2_000_000, mailbox_cap=64)
    link = Quantize(UniformDelay(3_000, 9_000), 1_000)
    eng, orc = pair(sc, link, window=3_000)
    st = hold_to_oracle("socket-hub", eng, orc, (32, 32))
    assert int(st.overflow) > 1023 - 64




_FLEET_SEEDS = (0, 1)


@functools.lru_cache(maxsize=None)
def _faulted_fleet():
    """One run of the fleet for both of its cases: the states at the
    two horizons and the per-world traces between them."""
    n, half = 1024, 512
    fleet = FaultFleet(tuple(
        FaultSchedule((
            NodeCrash((7 * b + 3) % n, 20_000, 60_000 + 5_000 * b,
                      reset_state=True),
            Partition((tuple(range(half)), tuple(range(half, n))),
                      25_000, 70_000 + 2_000 * b),
        )) for b in range(len(_FLEET_SEEDS))))
    sc = gossip(n, fanout=1, think_us=1_000, gossip_interval=1_000,
                end_us=200_000, steady=True, mailbox_cap=8)
    link = Quantize(UniformDelay(500, 4_500), 1_000)
    eng = JaxEngine(sc, link, window="auto", lint="off", faults=fleet,
                    batch=BatchSpec(seeds=_FLEET_SEEDS))
    st, runs = eng.init_state(), []
    for k in (8, 32):
        st, trs = eng.run(k, st)
        runs.append((k, st, trs))
    return sc, link, fleet, eng.window, runs


@pytest.mark.parametrize("b", range(len(_FLEET_SEEDS)), ids="world{}".format)
def test_faulted_fleet_world_equals_solo_oracle(b):
    """World b of a fleet under per-world crashes (with state loss)
    and partitions, sliced out of the batched state, against the solo
    oracle with that world's seed under ``fleet.world_schedule(b)``:
    the insertion runs under ``vmap`` with every fault mask around
    it."""
    sc, link, fleet, window, runs = _faulted_fleet()
    orc = SuperstepOracle(sc, link, seed=_FLEET_SEEDS[b], lint="off",
                          window=window, faults=fleet.world_schedule(b))
    for k, st, trs in runs:
        oracle_catches_up(f"world{b}", orc, k, world_slice(st, b), trs[b])
    assert orc.fault_dropped_total > 0, "the schedule never bit"


def _small():
    return _burst(64, 8), INBOX["commutative"][1]


def _driver_jaxpr(eng) -> str:
    return str(jax.make_jaxpr(lambda s: eng._step_all(s, True))(
        eng.init_state()))


@pytest.mark.parametrize("mode", ["pallas", "interpret", "xla2d"])
def test_removed_insert_modes_are_refused_in_one_line(mode):
    with pytest.raises(ValueError, match="removed in PR 29") as ei:
        JaxEngine(*_small(), insert=mode)
    assert "\n" not in str(ei.value) and "193bc01" in str(ei.value)


def test_insert_xla_is_the_default_engine():
    """``insert="xla"`` is accepted because the benchmark's builders
    pass it (ROADMAP D2'): the same attributes, the same program."""
    sc, link = _small()
    a = JaxEngine(sc, link, window="auto", lint="off")
    b = JaxEngine(sc, link, window="auto", lint="off", insert="xla")
    assert sorted(vars(a)) == sorted(vars(b))
    assert not [k for k in vars(b) if "insert" in k]
    assert _driver_jaxpr(a) == _driver_jaxpr(b)


@pytest.mark.parametrize("var,value", [("TW_INSERT", "interpret"),
                                       ("TW_INSERT", "xla2d"),
                                       ("TW_FLAT_SCATTER", "0")])
def test_the_environment_selects_nothing(monkeypatch, var, value):
    sc, link = _small()
    for v in ("TW_INSERT", "TW_FLAT_SCATTER"):
        monkeypatch.delenv(v, raising=False)
    base = _driver_jaxpr(JaxEngine(sc, link, window="auto", lint="off"))
    monkeypatch.setenv(var, value)
    assert os.environ[var] == value
    assert _driver_jaxpr(JaxEngine(sc, link, window="auto",
                                   lint="off")) == base


@pytest.mark.parametrize("argv", [
    ["--engine", "fused-sparse"], ["--engine", "sharded-fused"],
    ["--insert", "xla"], ["--insert-cap", "64"], ["--max-batch", "64"]],
    ids=lambda a: a[0].lstrip("-") + "-" + a[1])
def test_cli_refuses_what_went(argv, capsys):
    from timewarp_tpu.cli import main
    with pytest.raises(SystemExit) as ei:
        main(["gossip", "--nodes", "64", "--steps", "4", *argv])
    assert ei.value.code == 2           # argparse's usage error
    err = capsys.readouterr().err
    assert "invalid choice" in err or "unrecognized arguments" in err
