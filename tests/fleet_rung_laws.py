"""What tests/test_zzzzzzzzzzzzzfleet_rung.py and
tests/test_fleet_rung_sharded.py share. No test lives here."""

from timewarp_tpu.analysis.jaxpr_lint import _all_jaxprs
from timewarp_tpu.interp.jax_engine.engine import JaxEngine
from timewarp_tpu.models.gossip import gossip
from timewarp_tpu.net.delays import Quantize, UniformDelay


N = 2048
RUNGS = JaxEngine._sender_rungs(N)
#: per-world link bounds: world 1's links are four times slower, so its
#: ramp is still under the first rung when world 0's has filled the top
SLOW = {"inner.lo": [500, 4_000], "inner.hi": [4_500, 16_000]}


def _steady(n=N, end_us=60_000):
    """Steady gossip: the active set doubles a round, so a run crosses
    the ladder's rungs on its ramp."""
    sc = gossip(n, fanout=1, think_us=1_000, gossip_interval=1_000,
                end_us=end_us, steady=True, mailbox_cap=8)
    return sc, Quantize(UniformDelay(500, 4_500), 1_000)


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs in its parameters
    (loop bodies, branches, ``shard_map`` and ``pjit`` bodies)."""
    return [e for jx in _all_jaxprs(jaxpr.jaxpr) for e in jx.eqns]


def _named_axes(eqn) -> set:
    """The axis names an equation reduces or exchanges over."""
    names = set()
    for key in ("axes", "axis_name"):
        v = eqn.params.get(key, ())
        names |= {a for a in (v if isinstance(v, (tuple, list)) else (v,))
                  if isinstance(a, str)}
    return names


def _ladder_conds(jaxpr, rungs):
    return [e for e in _eqns(jaxpr) if e.primitive.name == "cond"
            and len(e.params["branches"]) == len(rungs)]


def _shared_rung(frames):
    """The ``rung`` column of a fleet's telemetry, by iteration. A
    world steps from the loop's first iteration until it is quiet or
    out of budget, so row ``i`` of its frames is iteration ``i``, and
    every world that stepped in an iteration recorded the same."""
    cols = sorted((fr.data["rung"].tolist() for fr in frames), key=len)
    for col in cols:
        assert col == cols[-1][:len(col)]
    return cols[-1]
