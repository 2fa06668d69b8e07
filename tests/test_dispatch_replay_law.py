"""Online adaptive dispatch, the replay law on a solo run
(tests/test_zzzdispatch.py has the laws' statements): one
controller-driven run (``_auto_run``, compiled once for the three
tests) re-executed from its decision trace is bit-identical on states,
traces and checkpoints, and every chunk of it is a static engine's run
at that chunk's window."""

import functools

from dispatch_laws import BUDGET, _auto_engine, _replay_engine, _wave
from timewarp_tpu.dispatch import DecisionTrace
from timewarp_tpu.interp.jax_engine.engine import JaxEngine
from timewarp_tpu.trace.events import assert_states_equal, assert_traces_equal


@functools.lru_cache(maxsize=None)
def _auto_run():
    """The controller-driven solo run that the three tests replay:
    compiled and made once."""
    sc, link = _wave()
    eng = _auto_engine(sc, link)
    final, trace = eng.run_controlled(BUDGET)
    return sc, link, eng, final, trace, eng.last_run_decisions


def test_replay_law_solo_bit_identical(tmp_path):
    sc, link, _, final, trace, decs = _auto_run()
    assert len(decs) >= 2, "run too short to exercise adaptation"
    # trace file round-trip: what --decisions-out writes is what
    # --controller replay: loads
    path = str(tmp_path / "trace.jsonl")
    DecisionTrace.of(decs).save(path)
    rep = _replay_engine(sc, link, DecisionTrace.load(path).decisions)
    final2, trace2 = rep.run_controlled(BUDGET)
    assert_traces_equal(trace, trace2, "auto", "replay")
    assert_states_equal(final, final2, "replay law (solo)")
    assert [d.chunk for d in rep.last_run_decisions] == \
        [d.chunk for d in decs]


def test_replay_law_checkpoint_identical(tmp_path):
    """Checkpoints written mid-run by the two sides are bit-equal:
    drive both engines chunk-by-chunk over the same decisions and
    compare the state pytree after every chunk."""
    sc, link, eng, _, _, decs = _auto_run()
    rep = _replay_engine(sc, link, decs)
    rep.controller.begin(rep)
    st_a, st_b = eng.init_state(), rep.init_state()
    for d in decs:
        dyn = eng.dyn_values(d)
        st_a, _ = eng.run(d.chunk_len, state=st_a, _dyn=dyn)
        st_b, _ = rep.run(d.chunk_len, state=st_b,
                          _dyn=rep.dyn_values(d))
        assert_states_equal(st_a, st_b,
                            f"checkpoint after chunk {d.chunk}")


def test_per_chunk_equals_static_run(tmp_path):
    """Each chunk of a (degradation-free) controlled run ≡ a STATIC
    engine constructed with that chunk's window, run for the same
    budget from the same state."""
    sc, link, _, _, _, decs = _auto_run()
    ctl = _replay_engine(sc, link, decs)
    ctl.controller.begin(ctl)
    st_c = ctl.init_state()
    st_s = None
    for d in decs:
        static = JaxEngine(sc, link, window=d.window_us, lint="off")
        if st_s is None:
            st_s = static.init_state()
        st_c, tr_c = ctl.run(d.chunk_len, state=st_c,
                             _dyn=ctl.dyn_values(d))
        st_s, tr_s = static.run(d.chunk_len, state=st_s)
        assert_traces_equal(tr_s, tr_c, "static", "chunk")
        assert_states_equal(st_s, st_c,
                            f"chunk {d.chunk} ≡ static "
                            f"window={d.window_us}")
