"""What staging a commutative inbox's arrivals costs on this chip, a
piece at a time (round 6; docs/engines.md per-op table, the threshold
``engine.py`` ``_DENSE_STAGE_RATIO``).

Every piece runs inside a ``fori_loop`` whose inputs move with the
iteration, with a readback sync (``access_micro_r05.py``'s way):

- the lane-axis expansion network (``ops.numeric.expand_lanes``) over
  n lanes, displacement and two fields;
- a scatter of L updates into a fresh ``[24 n]`` buffer, as the
  compiler takes it (it sorts the indices first) and with the indices
  sorted by the program and declared so;
- the sorts beside them;
- the two whole forms of staging one superstep's arrivals by rank, at
  L = n, n/2, n/4, n/8 lanes of uniform destinations and two or three
  fields: a scatter a field (the form under the threshold), and one
  sort by staged index, rank 0 by the network, the tail scattered at
  half width and declared sorted (the form at and over it).

Imports ``ops/`` only. ``python profiling/stage_micro_r06.py [log2 n]``
prints one JSON line a piece; on a TPU it writes them to
``chiprun_out/stage_micro_r06_n<log2 n>.jsonl`` too.
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

from timewarp_tpu.ops.numeric import I32MAX, expand_lanes, group_rank

K = 24
REPS = 16
ROWS = []


def loop(name, fn, *args, **facts):
    """``fn(x, i, *args)`` REPS times on the carry ``x`` (one int32
    word: every piece folds a word of its result into it, so nothing
    is dead and nothing is hoisted), timed on its second call."""
    def rep(x, *rest):
        return lax.fori_loop(jnp.int32(0), jnp.int32(REPS),
                             lambda i, x: fn(x, i, *rest), x)
    f = jax.jit(rep)
    t0 = time.perf_counter()
    int(f(jnp.int32(0), *args))
    first = time.perf_counter() - t0
    best = None
    for _ in range(3):
        t0 = time.perf_counter()
        int(f(jnp.int32(0), *args))
        dt = (time.perf_counter() - t0) / REPS
        best = dt if best is None else min(best, dt)
    row = {"op": name, "us": round(best * 1e6, 1),
           "first_call_s": round(first, 2), **facts}
    ROWS.append(row)
    print(json.dumps(row), flush=True)


def word(x, i, *arrays):
    """The carry after a piece: one word of each result, at a place
    that moves with the iteration."""
    for a in arrays:
        x = x ^ a[(i * 7919) % a.shape[0]].astype(jnp.int32)
    return x


def arrivals(rng, n, L):
    """One superstep's L arrivals at uniform destinations, sorted by
    destination as the routing sort leaves them, with their ranks."""
    sd = np.sort(rng.integers(0, n, L)).astype(np.int32)
    rank = np.asarray(group_rank(jnp.asarray(sd)))
    return jnp.asarray(sd), jnp.asarray(rank)


def moved(sd, rank, i, n):
    """The staged index of every lane with the destinations turned by
    ``i`` (a bijection: ranks and uniqueness stay, nothing repeats
    from one iteration to the next)."""
    fits = rank < K
    L = sd.shape[0]
    return jnp.where(fits, rank * jnp.int32(n) + (sd + i) % jnp.int32(n),
                     jnp.int32(K * n) + jnp.arange(L, dtype=jnp.int32))


def stage_scatter(flat, fields, nothing, n):
    """A scatter a field, the indices as they come."""
    return [jnp.full((K * n,), e, x.dtype).at[flat].set(x, mode="drop")
            for x, e in zip(fields, nothing)]


def stage_dense(flat, fields, nothing, n):
    """One sort by staged index, rank 0 by the network, the tail at
    half width declared sorted (``engine.py`` ``_stage_by_rank``)."""
    L = flat.shape[0]
    flat, *fields = lax.sort((flat,) + tuple(fields), num_keys=1)
    c0 = jnp.sum(flat < n, dtype=jnp.int32)
    tail = jnp.sum(flat < K * n, dtype=jnp.int32) - c0
    m = min(L, n)

    def head(x, fill):
        x = x[:m]
        return x if m == n else jnp.concatenate(
            [x, jnp.full((n - m,), fill, x.dtype)])
    row0 = expand_lanes(head(flat, 0), c0, [head(x, 0) for x in fields],
                        nothing)

    def scatter(width):
        def go():
            at = lax.dynamic_slice_in_dim(flat, c0, width)
            return [jnp.full((K * n,), e, x.dtype).at[at].set(
                lax.dynamic_slice_in_dim(x, c0, width), mode="drop",
                indices_are_sorted=True, unique_indices=True)
                for x, e in zip(fields, nothing)]
        return go
    bufs = lax.cond(tail <= L // 2, scatter(L // 2), scatter(L))
    return [lax.dynamic_update_slice_in_dim(b, r, 0, 0)
            for b, r in zip(bufs, row0)]


def main():
    lg = int(sys.argv[1]) if len(sys.argv) > 1 else 20
    n = 1 << lg
    rng = np.random.default_rng(6)
    print(json.dumps({"device": jax.devices()[0].device_kind,
                      "platform": jax.devices()[0].platform, "n": n}))

    # the network alone: a 63 % prefix (one arrival a node on average,
    # 1 - 1/e of the nodes get one), displacement and two fields
    for m in sorted({max(lg - 3, 1), lg}):
        nn = 1 << m
        t = np.unique(rng.integers(0, nn, nn)).astype(np.int32)
        target = np.zeros(nn, np.int32)
        target[:t.size] = t
        a = jnp.asarray(rng.integers(0, 1 << 30, nn).astype(np.int32))
        loop(f"expand_lanes n=2^{m}, two fields",
             lambda x, i, tg, a: word(x, i, *expand_lanes(
                 tg, jnp.int32(t.size) - (i & 7), [a ^ i, a + i],
                 [I32MAX, 0])),
             jnp.asarray(target), a, lanes=nn, prefix=int(t.size))

    sd, rank = arrivals(rng, n, n)
    vals = jnp.asarray(rng.integers(0, 1 << 30, n).astype(np.int32))

    # sorts at 2^lg
    loop("sort L=n, key + two values",
         lambda x, i, sd, rank, v: word(x, i, *lax.sort(
             (moved(sd, rank, i, n), v ^ i, v + i), num_keys=1)),
         sd, rank, vals)
    loop("sort L=n, key + one value",
         lambda x, i, sd, rank, v: word(x, i, *lax.sort(
             (moved(sd, rank, i, n), v ^ i), num_keys=1)),
         sd, rank, vals)

    # one scatter into a fresh [24 n] buffer, by width
    for sh in range(4):
        L = n >> sh
        sdl, rankl = arrivals(rng, n, L)
        v = vals[:L]
        loop(f"scatter L=n/{1 << sh} into fresh [24 n], as it comes",
             lambda x, i, sd, rank, v: word(x, i, *stage_scatter(
                 moved(sd, rank, i, n), [v ^ i], [I32MAX], n)),
             sdl, rankl, v, lanes=L)
        order = jnp.sort(moved(sdl, rankl, jnp.int32(0), n))
        loop(f"scatter L=n/{1 << sh} into fresh [24 n], sorted and "
             "declared",
             lambda x, i, at, v: word(
                 x, i, jnp.full((K * n,), I32MAX, jnp.int32).at[
                     at + i].set(v ^ i, mode="drop",
                                 indices_are_sorted=True,
                                 unique_indices=True)),
             order, v, lanes=L)

    # the two whole forms, by L / n and by fields
    for nf in (2, 3):
        nothing = [I32MAX] + [0] * (nf - 1)
        for sh in range(4):
            L = n >> sh
            sdl, rankl = arrivals(rng, n, L)
            v = vals[:L]
            for name, form in (("a scatter a field", stage_scatter),
                               ("sorted once, rank 0 expanded",
                                stage_dense)):
                loop(f"stage L=n/{1 << sh}, {nf} fields: {name}",
                     lambda x, i, sd, rank, v, form=form: word(
                         x, i, *form(moved(sd, rank, i, n),
                                     [v ^ i] + [v + i + f
                                                for f in range(nf - 1)],
                                     nothing, n)),
                     sdl, rankl, v, lanes=L, fields=nf)

    if jax.devices()[0].platform == "tpu":
        os.makedirs("chiprun_out", exist_ok=True)
        with open(f"chiprun_out/stage_micro_r06_n{lg}.jsonl", "w") as f:
            for row in ROWS:
                f.write(json.dumps(row) + "\n")


if __name__ == "__main__":
    main()
