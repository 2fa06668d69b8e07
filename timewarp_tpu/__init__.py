"""timewarp_tpu — a TPU-native framework for writing distributed-system
scenarios once and running them under interchangeable interpreters.

Capability parity target: `input-output-hk/time-warp` (see SURVEY.md).
The three interpreters:

- :mod:`timewarp_tpu.interp.ref` — pure deterministic discrete-event
  emulation on the host (the oracle; ≙ ``TimedT``).
- :mod:`timewarp_tpu.interp.jax_engine` — the batched XLA engine:
  per-node step functions ``vmap``-ed over the node axis, virtual time
  driven by ``lax.scan``, message delivery as sharded collectives over
  the TPU mesh. This is what the reference never had: emulation that
  *scales*.
- :mod:`timewarp_tpu.interp.aio` — real wall-clock mode over asyncio
  TCP (≙ ``TimedIO`` + ``Transfer``).

All interpreters agree on observable event traces (bit-for-bit at small
node counts — the framework's core law, tested in tests/test_parity*).
"""
_PACKAGE_START_NS = __import__("time").perf_counter_ns()  # obs/profiler.py

from .core import effects, errors, time
from .core.effects import (Fork, ForkSlave, GetLogName, GetTime, MyTid,
                           SetLogName, ThrowTo, Wait, fork, fork_,
                           fork_slave, invoke, kill_thread, modify_log_name,
                           my_thread_id, repeat_forever, schedule,
                           sleep_forever, start_timer, timeout,
                           virtual_time, wait, work)
from .core.errors import (AlreadyListening, MailboxOverflow, NetworkError,
                          PeerClosedConnection, ThreadKilled, TimedError,
                          TimeoutExpired, TimeWarpError, TransferError)
from .core.time import (FOREVER, Microsecond, after, at, for_, hour, mcs,
                        minute, ms, now, sec, till)
from .interp.aio.timed import AioThreadId, RealTime, run_real_time
from .interp.ref.des import PureEmulation, PureThreadId, run_emulation
from .manage.jobs import Force, InterruptType, JobCurator, Plain, WithTimeout
from .manage.sync import CLOSED, Channel, Flag, MVar

__version__ = "0.1.0"
