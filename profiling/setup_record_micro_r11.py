"""What the program's record of its set-up costs on the host (round 11;
``timewarp_tpu/obs/profiler.py``, docs/observability.md "Before the
first call, and what a call compiled").

No device work: the listener of JAX's monitoring events is fed the
events by hand, a live span and a driver call's record are opened and
closed around nothing. Microseconds each, the median, the least and
the most of seven loops of 20 000:

- one noted event: JAX's start scalar and its duration event, as a
  trace, a lowering or a backend compile fires them;
- an outer event with a nested one inside, which is not noted;
- a live span (``profiler.phase``, what ``tw.engine.init`` costs);
- a driver call's record: ``profiler.call``, its ``tw.dispatch`` and
  ``tw.wait``, and ``compile_account`` (PR 35's three spans and a
  record, and what PR 51 put beside them).

    python profiling/setup_record_micro_r11.py
"""

import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

from timewarp_tpu.obs import profiler  # noqa: E402

profiler.listen()
E = "/jax/core/compile/jaxpr_trace_duration"


def per(fn, n=20000):
    out = []
    for _ in range(7):
        t0 = time.perf_counter()
        for _ in range(n):
            fn()
        out.append((time.perf_counter() - t0) / n * 1e6)
    return statistics.median(out), min(out), max(out)


def event():
    jax.monitoring.record_scalar(E, 0.0, fun_name="f")
    jax.monitoring.record_event_duration_secs(E, 1e-6, fun_name="f")


def nested():
    jax.monitoring.record_scalar(E, 0.0, fun_name="f")
    jax.monitoring.record_scalar(E, 0.0, fun_name="g")
    jax.monitoring.record_event_duration_secs(E, 1e-6, fun_name="g")
    jax.monitoring.record_event_duration_secs(E, 1e-6, fun_name="f")


def live():
    with profiler.phase("tw.micro", engine="E", n_nodes=1):
        pass


def call():
    with profiler.call("tw.micro.call") as rec:
        with profiler.span("tw.dispatch", run=rec["run"]):
            pass
        with profiler.span("tw.wait", run=rec["run"]):
            pass
        profiler.compile_account()


print("us median/min/max: one noted event (start + duration)", per(event))
print("us: an outer and a nested event (the nested not noted)", per(nested))
print("us: a live span", per(live))
print("us: a driver call's record (call, two spans, compile_account)",
      per(call))
