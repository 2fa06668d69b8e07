"""Event-trace containers and the parity checker.

The TPU analogue of the reference's measure-event stream (bench
Commons.hs:80-83, 121-126) and the acceptance oracle for the framework's
core law: every interpreter must produce the same trace (SURVEY.md §4.1,
§6 north star: "bit-for-bit event-trace parity vs the pure emulator").

A trace is one fixed-width record per *superstep*:

  (time, fired_count, fired_hash, recv_count, recv_hash,
   sent_count, sent_hash, overflow_count)

Hashes are order-independent digests of the full per-event detail
(trace/hashing.py), so equality here pins down the set of fired nodes,
every delivered message (with source, deliver time, payload word), and
every routed message (with sampled deliver time) at each instant.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional

import numpy as np

__all__ = ["SuperstepTrace", "TraceMismatch", "assert_states_equal",
           "assert_traces_equal"]

_FIELDS = ("times", "fired_count", "fired_hash", "recv_count", "recv_hash",
           "sent_count", "sent_hash", "overflow")


@dataclass
class SuperstepTrace:
    """Columnar trace; one row per superstep that actually fired."""
    times: np.ndarray        # int64[S]
    fired_count: np.ndarray  # int32[S]
    fired_hash: np.ndarray   # uint32[S]
    recv_count: np.ndarray   # int32[S]
    recv_hash: np.ndarray    # uint32[S]
    sent_count: np.ndarray   # int32[S]
    sent_hash: np.ndarray    # uint32[S]
    overflow: np.ndarray     # int32[S]

    def __len__(self) -> int:
        return len(self.times)

    @staticmethod
    def from_rows(rows: List[tuple]) -> "SuperstepTrace":
        cols = list(zip(*rows)) if rows else [[] for _ in _FIELDS]
        dts = (np.int64, np.int32, np.uint32, np.int32, np.uint32,
               np.int32, np.uint32, np.int32)
        return SuperstepTrace(*(np.asarray(c, dtype=d)
                                for c, d in zip(cols, dts)))

    def total_delivered(self) -> int:
        return int(self.recv_count.sum())

    def row(self, i: int) -> tuple:
        return tuple(int(getattr(self, f)[i]) for f in _FIELDS)


class TraceMismatch(AssertionError):
    """Raised by the parity checker with the first diverging superstep."""


def assert_traces_equal(a: SuperstepTrace, b: SuperstepTrace,
                        a_name: str = "oracle", b_name: str = "engine",
                        limit: Optional[int] = None) -> None:
    """Bit-for-bit comparison, reporting the first divergence precisely."""
    n = min(len(a), len(b)) if limit is None else min(len(a), len(b), limit)
    for i in range(n):
        ra, rb = a.row(i), b.row(i)
        if ra != rb:
            labels = _FIELDS
            diffs = ", ".join(f"{f}: {x} != {y}"
                              for f, x, y in zip(labels, ra, rb) if x != y)
            raise TraceMismatch(
                f"superstep {i} (t={ra[0]} vs {rb[0]}): {a_name} != {b_name}"
                f" — {diffs}")
    if limit is None and len(a) != len(b):
        raise TraceMismatch(
            f"trace lengths differ: {a_name}={len(a)} {b_name}={len(b)}"
            f" (first {n} supersteps agree)")


def assert_states_equal(a, b, tag: str = "") -> None:
    """Bit-for-bit EngineState (or any NamedTuple-of-arrays pytree
    whose ``states`` field is a dict of arrays) comparison — the
    exactness law between two engines that share a state layout
    (sharded against one-device, batched slice against solo, the
    in-bench gates). One copy, so every caller asserts the same law."""
    import jax
    suffix = f" ({tag})" if tag else ""
    for name in a._fields:
        x, y = getattr(a, name), getattr(b, name)
        if name == "states":
            for leaf in x:
                if not np.array_equal(
                        np.asarray(jax.device_get(x[leaf])),
                        np.asarray(jax.device_get(y[leaf]))):
                    raise TraceMismatch(
                        f"state.{leaf} diverged{suffix}")
        elif not np.array_equal(np.asarray(jax.device_get(x)),
                                np.asarray(jax.device_get(y))):
            raise TraceMismatch(f"{name} diverged{suffix}")
