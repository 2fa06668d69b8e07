"""What an ordered inbox's ranked insertion pays for the lanes that
cannot land, and from how many lanes on a ladder of static widths
around its scatters pays for itself (round 7; the threshold
``engine.py`` ``_PREFIX_SCATTER_LANES``).

The mailbox of the observer ring's cell: ``[8, 65 537]`` planes of
deliver time and sender and ``[8, 2, 65 537]`` of payload, carried by a
``fori_loop`` as the quiet driver's ``while`` carries them. One
iteration is one insertion of ``L`` destination-sorted lanes (``L``
from 2^11 to 2^17), in the two forms:

- ``one``: the four flat scatters (deliver time, sender, two payload
  words) at ``L`` lanes, the lanes that do not fit at the out-of-range
  index, ``mode="drop"``;
- ``switch``: one scalar, the lane after the last that fits, and the
  same four scatters inside a ``lax.switch`` over the static widths
  ``L/8``, ``L/4``, ``L/2``, ``L``, each on slices from lane 0;

on three kinds of lanes, the cell's and the worst case:

- ``notes``: half the lanes valid, all to one hub of 8 slots: 8 fit
  (the switch takes ``L/8``);
- ``tokens``: half the lanes valid, one to a node: all of them fit
  (``L/2``);
- ``full``: every lane valid and fitting, two to a node (``L``: the
  switch is pure cost).

Imports nothing of the engine. ``python
profiling/prefix_scatter_micro_r07.py`` prints one JSON line a piece;
on a TPU it writes them to ``chiprun_out/prefix_scatter_micro_r07.jsonl``
too.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from timewarp_tpu.utils import jaxconfig  # noqa: F401

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

N, K, P = 65_537, 8, 2
REPS = 16
ROWS = []


def lanes_of(kind, L):
    """``(sd, pos)`` of ``L`` lanes sorted by destination, the invalid
    ones (row ``N``) last: the destination and the slot each lane
    asks for (``pos >= K`` does not fit)."""
    lane = np.arange(L)
    if kind == "notes":
        sd = np.where(lane < L // 2, N - 1, N)
        pos = np.where(lane < L // 2, lane, 0)
    elif kind == "tokens":
        sd = np.where(lane < L // 2, lane, N)
        pos = np.zeros(L)
    else:
        sd, pos = lane // 2, lane % 2
    return sd.astype(np.int32), pos.astype(np.int32)


def scatters(planes, sd, fits, col, fields, w):
    """The ranked insertion's four scatters over the first ``w``
    lanes, as ``_insert_sorted`` writes them."""
    rel, src, pay = planes
    sd, fits, col = sd[:w], fits[:w], col[:w]
    drel, srcs, pay0, pay1 = (x[:w] for x in fields)
    flat = jnp.where(fits, col * jnp.int32(N) + sd, jnp.int32(K * N))
    rel = rel.reshape(-1).at[flat].set(drel, mode="drop").reshape(K, N)
    src = src.reshape(-1).at[flat].set(srcs, mode="drop").reshape(K, N)
    pay = pay.reshape(-1)
    for p, words in enumerate((pay0, pay1)):
        flat_p = jnp.where(
            fits, (col * jnp.int32(P) + p) * jnp.int32(N) + sd,
            jnp.int32(K * P * N))
        pay = pay.at[flat_p].set(words, mode="drop")
    return rel, src, pay.reshape(K, P, N)


def insertion(form, L):
    widths = tuple(-(-L // d) for d in (8, 4, 2, 1))

    def insert(i, planes, sd, pos, fields):
        # the slot moves with the iteration, so nothing is hoisted
        fits = (sd < N) & (pos < K)
        col = (jnp.clip(pos, 0, K - 1) + i) % jnp.int32(K)
        fields = tuple(x + i for x in fields)
        if form == "one":
            return scatters(planes, sd, fits, col, fields, L)
        hi = jnp.max(jnp.where(
            fits, jnp.arange(1, L + 1, dtype=jnp.int32), 0))
        idx = jnp.sum(hi > jnp.asarray(widths, jnp.int32))
        return lax.switch(idx, [
            (lambda w: lambda: scatters(planes, sd, fits, col, fields,
                                        w))(w) for w in widths])
    return insert


def timed(form, kind, L):
    rng = np.random.default_rng(L)
    sd, pos = lanes_of(kind, L)
    fields = tuple(jnp.asarray(rng.integers(0, 10**6, L, dtype=np.int32))
                   for _ in range(4))
    planes = (jnp.full((K, N), 2**31 - 1, jnp.int32),
              jnp.zeros((K, N), jnp.int32),
              jnp.zeros((K, P, N), jnp.int32))
    insert = insertion(form, L)

    @jax.jit
    def reps(planes, sd, pos, fields):
        return lax.fori_loop(
            jnp.int32(0), jnp.int32(REPS),
            lambda i, pl: insert(i, pl, sd, pos, fields), planes)
    args = (planes, jnp.asarray(sd), jnp.asarray(pos), fields)
    t0 = time.perf_counter()
    out = reps(*args)
    int(out[0][0, 0])
    first = time.perf_counter() - t0
    best = None
    for _ in range(3):
        t0 = time.perf_counter()
        int(reps(*args)[0][0, 0])
        dt = (time.perf_counter() - t0) / REPS
        best = dt if best is None else min(best, dt)
    landed = int((np.asarray(out[0]) != 2**31 - 1).sum())
    row = {"form": form, "lanes_kind": kind, "log2_L": int(np.log2(L)),
           "us": round(best * 1e6, 1), "first_call_s": round(first, 2),
           "slots_filled": landed}
    ROWS.append(row)
    print(json.dumps(row), flush=True)
    return out


def main():
    dev = jax.devices()[0]
    print(json.dumps({"device": dev.device_kind,
                      "platform": dev.platform}), flush=True)
    top = int(sys.argv[1]) if len(sys.argv) > 1 else 17
    for log2 in range(11, top + 1):
        for kind in ("notes", "tokens", "full"):
            one = timed("one", kind, 1 << log2)
            cut = timed("switch", kind, 1 << log2)
            for a, b in zip(jax.tree.leaves(one), jax.tree.leaves(cut)):
                assert np.array_equal(a, b), (kind, log2)
    if dev.platform == "tpu":
        os.makedirs("chiprun_out", exist_ok=True)
        with open("chiprun_out/prefix_scatter_micro_r07.jsonl", "w") as f:
            for row in ROWS:
                f.write(json.dumps(row) + "\n")


if __name__ == "__main__":
    main()
