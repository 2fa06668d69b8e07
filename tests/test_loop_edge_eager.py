"""The edge of the quiet loop, the law's exactness (tests/test_loop_edge.py
has what the law is, tests/loop_edge_laws.py its body): the eager
routing path's cases, a file of their own because every case compiles
an engine's five programs. And which engine takes that path at all:
the regime follows what the engine sees, constructors only."""

import pytest

from loop_edge_laws import UNI, _gossip, carried_horizon_law, law_cases
from timewarp_tpu.interp.jax_engine.engine import JaxEngine
from timewarp_tpu.interp.jax_engine.sharded import ShardedEngine, make_mesh
from timewarp_tpu.net.delays import WithDrop


@law_cases("eager")
def test_the_carried_horizon_is_the_states_and_the_drivers_agree(
        name, faulted):
    carried_horizon_law(name, faulted)


@pytest.mark.parametrize("mesh", [False, True], ids=["one-device", "mesh8"])
@pytest.mark.parametrize("burst", [False, True], ids=["out1", "out3"])
@pytest.mark.parametrize("window", [1, "auto"], ids="w{}".format)
@pytest.mark.parametrize("drops", [False, True], ids=["drop-free", "drops"])
def test_the_regime_follows_the_link_the_window_the_outbox_and_the_mesh(
        drops, window, burst, mesh):
    """``_adaptive_regime()`` against the rule written out: the ladder
    where the link cannot drop, the nodes are one device's and there
    is something to compact (a window of several instants or an outbox
    of several slots); the eager path everywhere else. No keyword picks
    it. Nothing is compiled."""
    sc = _gossip(burst)
    link = WithDrop(UNI, 0.1) if drops else UNI
    assert (sc.max_out, link.can_drop) == (3 if burst else 1, drops)
    if mesh:
        eng = ShardedEngine(sc, link, make_mesh(8), window=window,
                            lint="off")
    else:
        eng = JaxEngine(sc, link, window=window, lint="off")
    # "auto" is the link's floor, several instants wide, whatever
    # drops on the way
    assert UNI.min_delay_us > 1
    assert eng.window == (1 if window == 1 else UNI.min_delay_us)
    assert eng._adaptive_regime() == (
        not drops and not mesh and (eng.window > 1 or sc.max_out > 1))
