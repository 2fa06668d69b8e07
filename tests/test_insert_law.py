"""The mailbox-insertion law: ``JaxEngine`` against ``SuperstepOracle``.

``_insert_sorted`` (engine.py) is the one insertion form, reached from
three call sites of the superstep's routing stage. Every case here runs
the engine and the host oracle side by side and compares the *state* at
two horizons (every node's scenario state and wake time, the clock, the
mailbox contents message by message, the never-silent counters) and the
trace over both. The state comparison is what the trace laws elsewhere
do not make: a message in the wrong slot, or a payload word scattered
to a neighbour, shows in the mailbox one superstep before it shows in
a digest.

The matrix is the one the removed kernel tests walked against the XLA
form (PR 29; they are at 193bc01), walked against the oracle instead:

- call site: ``adaptive`` (windowed, drop-free link: the ladder's
  tail), ``eager`` (a ``WithDrop`` link), ``lazy`` (``route_cap`` above
  the load);
- inbox: commutative (the gossip burst: the r-th message takes the
  destination's r-th hole; and the same at two words of holes, below)
  and ordered (the observer token ring,
  ``max_out`` 2: append after the kept messages);
- mailbox: fits, and too small for the fan-in (``overflow`` > 0 is
  asserted, and the surviving messages must still be the oracle's);
- n: 1024, and 1000 (a width that is no multiple of a lane or a tile).

Since PR 30 a commutative inbox's holes are ``ceil(K/32)`` uint32
words a node and the r-th hole is a bit select (ops/numeric.py;
tests/test_free_bits.py is the primitive's own law). So the matrix
has a third inbox, a wave of fanout 40 into mailboxes of two words,
fitting (64 slots, 52 used) and not (40); and one run is replayed
with the sorted free-slot table of the parent in the primitive's
place, the two mailboxes compared *slot for slot*, which no observer
could tell apart if they differed.

Then praos (``needs_key``, payload width 2, a lognormal link), the
socket-state hub (1023 clients into one mailbox), a two-world faulted
fleet (world b's slice against the solo oracle under
``fleet.world_schedule(b)``), the ladder's first, a middle and its top
rung (read back from telemetry's ``rung`` column), a checkpoint handed
from a solo run to a fleet, and the three refusals that are left of
the ``insert=`` selection.

Left out as covered: small-n trace parity of the token ring, ping-pong
and invalid destinations (test_parity.py); windowed against classic
semantics, ``route_cap`` over the load and the sharded forms
(test_windowed.py); mixed fault schedules on the solo engines and the
fleet-slice-against-solo-*engine* law (test_zfault_parity.py,
test_world_batch.py, whose reference is another engine configuration,
never the oracle at these widths).
"""

import functools
import os
from typing import Any, NamedTuple

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from test_free_bits import free_rows_by_sort
from timewarp_tpu.core.scenario import NEVER
from timewarp_tpu.faults import (FaultFleet, FaultSchedule, NodeCrash,
                                 Partition)
from timewarp_tpu.interp.jax_engine.batched import BatchSpec, world_slice
from timewarp_tpu.interp.jax_engine.common import I32MAX
from timewarp_tpu.interp.jax_engine.engine import JaxEngine
from timewarp_tpu.interp.ref.superstep import SuperstepOracle
from timewarp_tpu.models.gossip import gossip
from timewarp_tpu.models.praos import praos
from timewarp_tpu.models.socket_state import socket_state
from timewarp_tpu.models.token_ring import token_ring
from timewarp_tpu.net.delays import (LogNormalDelay, Quantize,
                                     UniformDelay, WithDrop)
from timewarp_tpu.trace.events import (assert_states_equal,
                                       assert_traces_equal)


# ---------------------------------------------------------------------------
# one view of a state, from either side
# ---------------------------------------------------------------------------

class View(NamedTuple):
    """What both executors must agree on after the same supersteps.
    The mailbox is ``[n, K]``, a node's pending messages first: in
    arrival order for an ordered inbox (the engine keeps it in slot
    order, the oracle in list order), sorted by (time, src, payload)
    for a commutative one (slot order is unobservable there)."""
    states: Any
    wake: Any
    time: Any
    mb_time: Any
    mb_src: Any
    mb_pay: Any
    overflow: Any
    bad_dst: Any
    short_delay: Any
    fault_dropped: Any


def _mailbox(sc, t, src, pay):
    """Canonical ``[n, K]`` mailbox from per-slot arrays (``t`` is
    NEVER in an empty slot)."""
    n, K = t.shape
    empty = t >= NEVER
    src = np.where(empty | (not sc.inbox_src), 0, src)
    pay = np.where(empty[:, :, None], 0, pay)
    node = np.repeat(np.arange(n), K)
    if sc.commutative_inbox:
        keys = tuple(pay[:, :, p].ravel() for p in
                     reversed(range(pay.shape[2]))) \
            + (src.ravel(), t.ravel(), node)
    else:
        keys = (np.tile(np.arange(K), n), empty.ravel(), node)
    order = np.lexsort(keys)
    return (t.ravel()[order].reshape(n, K),
            src.ravel()[order].reshape(n, K),
            pay.reshape(n * K, -1)[order].reshape(n, K, -1))


def engine_view(sc, st) -> View:
    st = jax.device_get(st)
    rel = np.asarray(st.mb_rel).T                       # [n, K]
    t = np.where(rel == I32MAX, NEVER,
                 int(st.time) + rel.astype(np.int64))
    mb = _mailbox(sc, t, np.asarray(st.mb_src).T,
                  np.asarray(st.mb_payload).transpose(2, 0, 1))
    return View({k: np.asarray(v) for k, v in st.states.items()},
                np.asarray(st.wake), int(st.time), *mb,
                int(st.overflow), int(st.bad_dst), int(st.short_delay),
                int(st.fault_dropped))


def oracle_view(o: SuperstepOracle) -> View:
    sc = o.scenario
    n, K, P = sc.n_nodes, sc.mailbox_cap, sc.payload_width
    t = np.full((n, K), NEVER, np.int64)
    src = np.zeros((n, K), np.int32)
    pay = np.zeros((n, K, P), np.int32)
    for i, box in enumerate(o.mailbox):
        assert len(box) <= K
        for j, (dt, s, p) in enumerate(box):
            t[i, j], src[i, j], pay[i, j] = dt, s, p
    return View({k: np.asarray(v) for k, v in o.states.items()},
                np.asarray(o.wake, np.int64), int(o.time),
                *_mailbox(sc, t, src, pay),
                o.overflow_total, o.bad_dst_total, o.short_delay_total,
                o.fault_dropped_total)


def oracle_catches_up(tag, orc, k, st, etr):
    """Step the oracle the ``k`` supersteps the engine just ran (to
    the solo state ``st``, over the trace ``etr``) and hold both to
    it. Returns the oracle's trace."""
    otr = orc.run(k)
    assert_states_equal(oracle_view(orc), engine_view(orc.scenario, st),
                        f"{tag} +{k}")
    assert_traces_equal(otr, etr, f"oracle-{tag}+{k}", f"engine-{tag}+{k}")
    return otr


def hold_to_oracle(tag, eng, orc, horizons):
    """Engine and oracle over ``horizons`` (supersteps, each from the
    last): views equal at every one, the traces equal over each.
    Returns the engine's last state."""
    st, delivered = eng.init_state(), 0
    for k in horizons:
        st, etr = eng.run(k, st)
        delivered += oracle_catches_up(tag, orc, k, st,
                                       etr).total_delivered()
    assert delivered == int(st.delivered)
    assert int(st.route_drop) == 0 and int(st.bad_delay) == 0
    return st


def pair(sc, link, *, seed=0, **kw):
    """The engine and its oracle, on one window."""
    eng = JaxEngine(sc, link, seed=seed, lint="off", **kw)
    return eng, SuperstepOracle(sc, link, seed=seed, lint="off",
                                window=eng.window)


# ---------------------------------------------------------------------------
# the matrix: call site x inbox x mailbox x n
# ---------------------------------------------------------------------------

def _burst(n, K):
    return gossip(n, fanout=8, think_us=2_000, burst=True,
                  end_us=1_000_000, mailbox_cap=K)


def _wide_burst(n, K):
    """Fanout 40: up to 52 messages pending at one node of 1024, so a
    mailbox's holes past row 31 (its second uint32 word of free
    slots, PR 30) are taken."""
    return gossip(n, fanout=40, think_us=2_000, burst=True,
                  end_us=1_000_000, mailbox_cap=K)


def _observer_ring(n, K):
    sc = token_ring(n - 1, n_tokens=64, think_us=1_000,
                    bootstrap_us=1_000, with_observer=True,
                    mailbox_cap=K)
    assert not sc.commutative_inbox and sc.max_out == 2
    return sc


_WAVE_LINK = Quantize(UniformDelay(8_000, 30_000), 1_000)

#: inbox -> (scenario of n nodes and K slots, its drop-free link, the
#: mailbox that fits, the one that does not)
INBOX = {
    "commutative": (_burst, _WAVE_LINK, 24, 2),
    "commutative-two-words": (_wide_burst, _WAVE_LINK, 64, 40),
    "ordered": (_observer_ring, UniformDelay(1_000, 5_000), 96, 2),
}

#: call site -> (the link the engine gets, its keywords, whether
#: ``_route_adaptive`` is the routing tail)
SITE = {
    "adaptive": (lambda link: link, lambda sc: {}, True),
    "eager": (lambda link: WithDrop(link, 0.1), lambda sc: {}, False),
    "lazy": (lambda link: link,
             lambda sc: {"route_cap": sc.n_nodes * sc.max_out}, False),
}


@pytest.mark.parametrize("n", [1024, 1000], ids="n{}".format)
@pytest.mark.parametrize("mailbox", ["fits", "overflows"])
@pytest.mark.parametrize("inbox", sorted(INBOX))
@pytest.mark.parametrize("site", sorted(SITE))
def test_insertion_equals_oracle(site, inbox, mailbox, n):
    make, link, fits, small = INBOX[inbox]
    sc = make(n, fits if mailbox == "fits" else small)
    assert sc.commutative_inbox == inbox.startswith("commutative")
    relink, kw, adaptive = SITE[site]
    eng, orc = pair(sc, relink(link), window="auto", **kw(sc))
    assert eng.window > 1 and eng._adaptive_regime() == adaptive
    st = hold_to_oracle(f"{site}-{inbox}-{mailbox}-n{n}", eng, orc, (8, 8))
    assert int(st.delivered) > 64       # the load is there
    assert (int(st.overflow) > 0) == (mailbox == "overflows")
    if inbox == "commutative-two-words":
        assert -(-sc.mailbox_cap // 32) == 2
        if mailbox == "fits":
            # the second word was needed: some node holds a message
            # past row 31 while an earlier row of it is a hole again
            used = np.asarray(st.mb_rel) != I32MAX
            assert (used[32:].any(axis=0) & ~used[:32].all(axis=0)).any()


# ---------------------------------------------------------------------------
# the slot itself: a run replayed with the parent's sorted table
# ---------------------------------------------------------------------------

def _table_in_place_of_the_words(monkeypatch):
    """The parent's pair in the primitive's place, through the seam
    ``_insert_sorted`` has (``nth_set_bit([w[dst] for w in holes], rank,
    K)``): ``holes`` is the sorted ``[K, N]`` table of free rows, its
    "words" are the table's rows, and the select is the table's entry
    ``[rank, dst]``, K past the last row. Returns what counts the
    lookups traced."""
    from timewarp_tpu.interp.jax_engine import engine as engine_mod
    traced = []

    def lookup(rows, rank, none):
        K = len(rows)
        assert none == K
        traced.append(K)
        lane = jnp.arange(rank.shape[0])
        return jnp.where(
            rank < K, jnp.stack(rows)[jnp.clip(rank, 0, K - 1), lane], K)

    monkeypatch.setattr(engine_mod, "free_bits",
                        lambda keep: free_rows_by_sort(keep, jnp))
    monkeypatch.setattr(engine_mod, "nth_set_bit", lookup)
    return traced


@pytest.mark.parametrize("K,make", [(24, _burst), (40, _wide_burst)],
                         ids=["one-word", "two-words"])
def test_every_slot_is_the_one_the_sorted_table_gave(K, make, monkeypatch):
    """Bit-equal, slot for slot: the raw mailbox arrays (holes' stale
    words included) and every other leaf of the state, at two
    horizons, with overflow in the two-word case."""
    sc, link = make(1024, K), _WAVE_LINK
    eng = JaxEngine(sc, link, window="auto", lint="off")
    states = []
    st = eng.init_state()
    for k in (10, 6):
        st, _ = eng.run(k, st)
        states.append(jax.device_get(st))
    traced = _table_in_place_of_the_words(monkeypatch)
    ref = JaxEngine(sc, link, window="auto", lint="off")
    st = ref.init_state()
    for want in states:
        st, _ = ref.run(int(want.steps) - int(st.steps), st)
        got = jax.device_get(st)
        for name, a, b in zip(got._fields, got, want):
            for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
                assert np.array_equal(x, y), (name, int(want.steps))
    assert traced and set(traced) == {K}, "the table was never read"
    assert (np.asarray(want.mb_rel) != I32MAX).sum() > 1024
    assert (int(want.overflow) > 0) == (K == 40)


# ---------------------------------------------------------------------------
# shapes the matrix does not have
# ---------------------------------------------------------------------------

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


# -- the two-world faulted fleet ------------------------------------------

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


# -- the ladder's rungs ----------------------------------------------------

@functools.lru_cache(maxsize=None)
def _ladder():
    """A synchronized wave at 4096 nodes (fanout 8, delays inside one
    window): generations of 1, 8, 64, 470 senders, then 2127, then
    1336. The engine with telemetry on, and the rung it took at every
    superstep."""
    sc = gossip(4096, fanout=8, think_us=2_000, burst=True,
                end_us=1_000_000, mailbox_cap=24)
    link = Quantize(UniformDelay(8_000, 9_000), 1_000)
    eng = JaxEngine(sc, link, window="auto", telemetry="counters",
                    lint="off")
    eng.run(16)
    fr = eng.last_run_telemetry
    return (sc, link, eng, fr.data["rung"].tolist(),
            fr.data["active_senders"].tolist())


@pytest.mark.parametrize("which", ["first", "middle", "top"])
def test_every_rung_equals_oracle(which):
    """``_route_adaptive`` runs ``_insert_sorted`` at the rung's
    width, so each width is its own compiled insertion. For the
    ladder's first, a middle and its top rung: the busiest superstep
    that took it (telemetry's ``rung`` column says which did), and the
    state right after that superstep against the oracle's."""
    sc, link, eng, rungs, senders = _ladder()
    ladder = eng._sender_rungs(sc.n_nodes)
    assert ladder == [1024, 2048, 4096]
    want = ladder[{"first": 0, "middle": 1, "top": -1}[which]]
    took = [i for i, r in enumerate(rungs) if r == want]
    assert took, f"no superstep took rung {want}: {rungs}"
    k = max(took, key=lambda i: senders[i])
    below = ladder[ladder.index(want) - 1] if want != ladder[0] else 0
    assert below < senders[k] <= want
    orc = SuperstepOracle(sc, link, lint="off", window=eng.window)
    st = hold_to_oracle(f"rung-{want}", eng, orc, (k + 1,))
    assert eng.last_run_telemetry.data["rung"].tolist()[k] == want
    assert int(st.overflow) == 0


# -- a checkpoint, from solo runs to a fleet -------------------------------

def test_checkpoint_from_solo_runs_resumes_in_a_fleet(tmp_path):
    """``EngineState`` is the same pytree solo and batched but for the
    world axis: two solo runs' checkpoints, stacked, are a fleet's
    state, and the fleet resumes each world to where the oracle of
    that world's seed gets in one run."""
    from timewarp_tpu.utils.checkpoint import load_state, save_state
    seeds = (3, 5)
    sc = _burst(1024, 24)
    link = INBOX["commutative"][1]
    loaded, oracles = [], []
    for s in seeds:
        eng, orc = pair(sc, link, seed=s, window="auto")
        mid = hold_to_oracle(f"solo-{s}", eng, orc, (8,))
        path = str(tmp_path / f"seed{s}.npz")
        save_state(path, mid, meta={"scenario": sc.name})
        got, _ = load_state(path, eng.init_state(),
                            expect_meta={"scenario": sc.name})
        loaded.append(jax.device_get(got))
        oracles.append(orc)
    fleet = JaxEngine(sc, link, window="auto", lint="off",
                      batch=BatchSpec(seeds=seeds))
    st = jax.tree.map(lambda *xs: np.stack(xs), *loaded)
    st, trs = fleet.run(8, st)
    for b, orc in enumerate(oracles):
        oracle_catches_up(f"resumed-seed{seeds[b]}", orc, 8,
                          world_slice(st, b), trs[b])


# ---------------------------------------------------------------------------
# what is left of the selection: refusals
# ---------------------------------------------------------------------------

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
