"""Plain reference of the dense token ring, written from the scenario's
definition (input-output-hk/time-warp ``examples/token-ring/Main.hs``,
the lean ring without the observer): numpy, no engine, nothing of the
program imported.

Every one of the ``n`` ring nodes holds one token at the start and
forwards ``value + 1`` to its successor after a think time of 0, over a
link of fixed latency. So all nodes fire together, once per link
latency:

- superstep 1 (at ``bootstrap_us``): every node sends; nothing is
  delivered yet;
- every later superstep (``link_delay_us`` after the last): node ``i``
  receives ``val[i-1] + 1``, keeps the larger of that and its own, and
  sends again. ``n`` messages are delivered.

After ``k`` supersteps: ``delivered = n * (k - 1)``, the clock stands
at ``bootstrap_us + link_delay_us * (k - 1)``, no node holds a token or
a timer (each token is in flight to the successor, one per edge), and
nothing overflowed.
"""

import numpy as np

def val_after(val0: np.ndarray, supersteps: int, dtype=np.int32) -> np.ndarray:
    """``val`` after ``supersteps`` supersteps, by the recursion itself:
    one ``max(val, roll(val, 1) + 1)`` per delivering superstep. Linear
    in ``supersteps``; what the first jobs of a run are held to.
    ``dtype`` is the token values' integer type (int32 as configured;
    the control computes in a narrower one)."""
    v = val0.astype(dtype)
    one = dtype(1)
    for _ in range(max(0, supersteps - 1)):
        v = np.maximum(v, np.roll(v, 1) + one)
    return v


def val_after_many(val0: np.ndarray, supersteps: int, dtype=np.int32
                   ) -> np.ndarray:
    """The same ``val`` for a number of supersteps too large to iterate
    (a whole window is 10^5 of them). Unrolled, the recursion is a
    running maximum over the ``d`` predecessors, ``val[i] = max over
    j = 0..d of (val0[i - j] + j)`` with ``d = supersteps - 1``, and a
    maximum over a range is built from two overlapping ranges of a
    power-of-two length (``tests/test_rehearsal.py`` holds this to
    ``val_after``). Indices wrap round the ring, as the ring does."""
    d = max(0, supersteps - 1)
    n = val0.shape[0]
    best = val0.astype(dtype)       # max over j in [0, span)
    span = 1
    while span * 2 <= d + 1:
        best = np.maximum(best, np.roll(best, span % n) + dtype(span))
        span *= 2
    rest = d + 1 - span             # cover [0, d] by [0, span) and [rest, d]
    if rest:
        best = np.maximum(best, np.roll(best, rest % n) + dtype(rest))
    return best


def expect(val0: np.ndarray, supersteps: int, *, bootstrap_us: int,
           link_delay_us: int, many: bool = False, dtype=np.int32) -> dict:
    """Every field a run of ``supersteps`` supersteps from ``val0`` must
    show, as plain numpy values keyed by what they are."""
    n = val0.shape[0]
    k = int(supersteps)
    val = (val_after_many if many else val_after)(val0, k, dtype)
    return {
        "val": val,
        # one token in flight on each edge: what node i is about to get
        "in_flight": np.roll(val, 1) + dtype(1),
        "in_flight_due_us": bootstrap_us + link_delay_us * k,
        "tokens_held": np.zeros(n, np.int32),
        "timers_armed": 0,
        "delivered": n * (k - 1),
        "overflow": 0,
        "steps": k,
        "time": bootstrap_us + link_delay_us * (k - 1),
    }
