"""The mailbox-insertion law, the ladder's rungs and a checkpoint
(tests/insertion_laws.py has the view and the comparisons): the
ladder's first, a middle and its top rung (read back from telemetry's
``rung`` column), each its own compiled insertion, against the oracle;
and a checkpoint handed from solo runs to a fleet."""

import functools

import numpy as np
import pytest

import jax

from insertion_laws import (INBOX, _burst, hold_to_oracle, oracle_catches_up,
                            pair)
from timewarp_tpu.interp.jax_engine.batched import BatchSpec, world_slice
from timewarp_tpu.interp.jax_engine.engine import JaxEngine
from timewarp_tpu.interp.ref.superstep import SuperstepOracle
from timewarp_tpu.models.gossip import gossip
from timewarp_tpu.net.delays import Quantize, UniformDelay


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
