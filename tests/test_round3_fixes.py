"""Round-3 regression suite for that round's review findings:

- deadlock detection in the pure emulator (quiescence must not mask a
  parked-forever thread — ≙ GHC's BlockedIndefinitelyOnMVar, which the
  reference inherits from the RTS);
- fork handoff + pre-start throw_to parity between interpreters;
- AwaitIO cleanup under outer cancellation (user ``finally`` must run);
- the edge engine's dst-consistency counter (never-silent contract).
"""

import asyncio

import numpy as np
import pytest

from timewarp_tpu.core.effects import (AwaitIO, Fork, Park, Wait,
                                       kill_thread)
from timewarp_tpu.core.errors import DeadlockError
from timewarp_tpu.interp.aio.timed import run_real_time
from timewarp_tpu.interp.ref.des import run_emulation
from timewarp_tpu.manage.sync import MVar


# -- deadlock detection --------------------------------------------------

def test_deadlock_main_parked_raises():
    def main():
        yield Park()

    with pytest.raises(DeadlockError):
        run_emulation(main)


def test_deadlock_on_empty_mvar_take():
    mv = MVar()

    def main():
        return (yield from mv.take())

    with pytest.raises(DeadlockError):
        run_emulation(main)


def test_deadlock_catchable_and_finally_runs():
    log = []

    def main():
        try:
            yield Park()
        except DeadlockError:
            log.append("caught")
        finally:
            log.append("finally")
        return "done"

    assert run_emulation(main) == "done"
    assert log == ["caught", "finally"]


def test_deadlocked_daemon_not_fatal_but_cleaned_up():
    """Main finishing with a parked daemon left over: the run succeeds,
    and the daemon's finally block still runs (DeadlockError delivered,
    death logged — never silently dropped)."""
    log = []

    def worker():
        try:
            yield Park()
        finally:
            log.append("cleanup")

    def main():
        yield Fork(worker)
        yield Wait(10)
        return 42

    assert run_emulation(main) == 42
    assert log == ["cleanup"]


def test_quiescence_without_parked_threads_is_clean():
    def main():
        yield Wait(100)
        return "fine"

    assert run_emulation(main) == "fine"


# -- fork handoff / throw_to parity --------------------------------------

def _fork_kill_scenario(log):
    def child():
        log.append("ran")
        yield Wait(50_000)
        log.append("after-wait")

    def main():
        tid = yield Fork(child)
        yield from kill_thread(tid)
        yield Wait(100_000)
        return "ok"

    return main


def test_fork_then_kill_parity_emulation():
    log = []
    assert run_emulation(_fork_kill_scenario(log)) == "ok"
    # child reached its first suspension before the parent resumed
    # (forkIO handoff), then died there — never past the wait
    assert log == ["ran"]


def test_fork_then_kill_parity_realtime():
    log = []
    assert run_real_time(_fork_kill_scenario(log)) == "ok"
    assert log == ["ran"]


# -- AwaitIO cancellation cleanup ----------------------------------------

def test_awaitio_scenario_exit_runs_finally():
    """Scenario exit cancels survivors; a thread blocked in AwaitIO must
    run its finally blocks (the round-1 leak: inner future leaked and
    the program never closed)."""
    log = []

    def worker():
        try:
            yield AwaitIO(asyncio.sleep(5))
        finally:
            log.append("cleanup")

    def main():
        yield Fork(worker)
        yield Wait(20_000)  # 20 ms real
        return "ok"

    assert run_real_time(main) == "ok"
    assert log == ["cleanup"]


def test_awaitio_throw_to_cancels_inner():
    """throw_to at a thread in AwaitIO cancels the awaitable and raises
    at the yield point (the AwaitIO cancellation contract)."""
    log = []

    async def slow():
        try:
            await asyncio.sleep(5)
        except asyncio.CancelledError:
            log.append("inner-cancelled")
            raise

    def worker():
        try:
            yield AwaitIO(slow())
        except RuntimeError as e:
            log.append(str(e))

    def main():
        tid = yield Fork(worker)
        yield Wait(10_000)
        from timewarp_tpu.core.effects import ThrowTo
        yield ThrowTo(tid, RuntimeError("stop"))
        yield Wait(30_000)
        return "ok"

    assert run_real_time(main) == "ok"
    assert log == ["inner-cancelled", "stop"]


# -- edge-engine dst consistency -----------------------------------------

def test_misrouted_send_counted():
    """A step emitting a dst that disagrees with its static_dst
    declaration is counted (routing goes by the declared table)."""
    import jax.numpy as jnp

    from timewarp_tpu.core.scenario import NEVER, Outbox, Scenario
    from timewarp_tpu.interp.jax_engine.edge_engine import EdgeEngine
    from timewarp_tpu.net.delays import FixedDelay

    n = 4
    ring = ((np.arange(n, dtype=np.int32) + 1) % n).reshape(n, 1)

    def step(state, inbox, now, i, key):
        alive = now < 10_000
        out = Outbox(valid=jnp.asarray([alive]),
                     dst=jnp.int32(0)[None],   # always 0: wrong for i>=1
                     payload=jnp.zeros((1, 2), jnp.int32))
        wake = jnp.where(alive, now + 1_000, jnp.int64(NEVER))
        return state, out, wake

    def init(i):
        import jax.numpy as jnp
        return {"x": jnp.int32(0)}, 0

    sc = Scenario(name="liar", n_nodes=n, step=step, init=init,
                  payload_width=2, max_out=1, mailbox_cap=4,
                  static_dst=ring, commutative_inbox=True)
    eng = EdgeEngine(sc, FixedDelay(100), cap=2)
    st, _ = eng.run(30)
    # nodes 0..2 declare succ 1..3 (!= 0) but emit 0 — counted every
    # firing; node 3's declared dst *is* 0, so it is consistent
    assert int(st.misrouted) > 0


def test_deadlock_catch_and_repark_terminates():
    """A thread that catches DeadlockError and parks again must not
    livelock the run loop: delivery is at most once per thread."""
    def main():
        while True:
            try:
                yield Park()
            except DeadlockError:
                pass

    # terminates (thread left parked after its one delivery; main never
    # returns, so the run yields None)
    assert run_emulation(main) is None
