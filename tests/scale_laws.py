"""What tests/test_scale_models.py and tests/test_scale_praos.py share:
the three-way trace comparison. No test lives here."""

from timewarp_tpu.interp.jax_engine.engine import JaxEngine
from timewarp_tpu.interp.jax_engine.sharded import ShardedEngine, make_mesh
from timewarp_tpu.interp.ref.superstep import SuperstepOracle
from timewarp_tpu.trace.events import assert_traces_equal


def three_way(sc, link, steps):
    ot = SuperstepOracle(sc, link).run(10 * steps)
    lst, lt = JaxEngine(sc, link).run(steps)
    sst, st = ShardedEngine(sc, link, make_mesh(8)).run(steps)
    assert_traces_equal(ot, lt, "oracle", "local", limit=len(lt))
    assert_traces_equal(ot, st, "oracle", "sharded", limit=len(st))
    return lst, lt
