"""What the dense staging's tail costs on this chip by how it is
placed (round 8; docs/engines.md "Staging by rank: two forms", the
constants ``engine.py`` ``_NET_ROW_RATIO`` and the ladder of tail
widths in ``_dense_plan``).

One call is what ``_stage_dense`` does *after* its one sort: the lanes
come sorted by staged index ``rank * n + d`` (the lanes that do not
fit past ``K * n``), and every form places them in fresh ``[K n]``
buffers, one a field. The sort is the same in every form and is left
out; every piece runs inside a ``fori_loop`` whose inputs move with
the iteration, with a readback sync (``stage_micro_r06.py``'s way).

- ``parent``: PR 36's form: rank 0 through the network over n lanes,
  every later rank scattered at ``L/2`` or ``L`` (a ``lax.cond``);
- ``rows R, widths``: the ranks under R through ONE network over
  ``R * n`` lanes (they are a compacted prefix ascending in staged
  index, so rows 0 … R-1 are its monotone expansion), the rest
  scattered at the smallest of the static widths that holds them (a
  ``lax.switch``); R = 1, 2, 3, widths ``L/8, L/4, L/2, L`` (R = 3
  also ``L/32, L/8, L/2, L``);
- ``windows``: R = 2 as ISSUE 44 wrote it: a network a row over n
  lanes, row 1's window cut at lane ``c0`` from the lanes padded by
  n (the comparison that chose the one network);

on uniform destinations (a node's arrivals Poisson(L / n)) at L / n
of 2, 1, 1/2, 1/4 with two and three fields, and on a burst whose
tail is wide (L = n lanes, eight to a node). Besides, the pieces: the
network over n, 2n and 3n lanes, and one declared-sorted scatter into
a fresh ``[K n]`` buffer at L/32 … L/4 lanes.

Imports ``ops/`` only. ``python profiling/stage_tail_micro_r08.py
[log2 n]`` prints one JSON line a piece; on a TPU it writes them to
``chiprun_out/stage_tail_micro_r08_n<log2 n>.jsonl`` too.
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


def staged(dst, n, L):
    """``L`` lanes, ``dst`` the valid ones' destinations: the staged
    indices as ``_stage_dense``'s sort leaves them (host side)."""
    sd = np.concatenate([np.sort(dst), np.full(L - len(dst), n)]
                        ).astype(np.int32)
    rank = np.asarray(group_rank(jnp.asarray(sd)))
    fits = (sd < n) & (rank < K)
    flat = np.where(fits, rank.astype(np.int64) * n + sd,
                    K * n + np.arange(L))
    return jnp.asarray(np.sort(flat).astype(np.int32))


def moved(flat, v, i, nf, n):
    """The call's operands at iteration ``i``: the lanes that do not
    fit move (the order and every index that lands stay), the fields'
    words change."""
    flat = flat + jnp.where(flat >= K * n, i, 0)
    return flat, [v ^ i] + [v + i + f for f in range(nf - 1)]


def head(x, m):
    L = x.shape[0]
    return x[:m] if L >= m else jnp.concatenate(
        [x, jnp.zeros((m - L,), x.dtype)])


def scatters(flat, fields, nothing, n, start, widths, took):
    """The tail from lane ``start`` on, at ``widths[took]`` lanes."""
    def at_width(width):
        def go():
            at = lax.dynamic_slice_in_dim(flat, start, width)
            return [jnp.full((K * n,), e, x.dtype).at[at].set(
                lax.dynamic_slice_in_dim(x, start, width), mode="drop",
                indices_are_sorted=True, unique_indices=True)
                for x, e in zip(fields, nothing)]
        return go
    if len(widths) == 2:
        return lax.cond(took > 0, at_width(widths[1]), at_width(widths[0]))
    return lax.switch(took, [at_width(w) for w in widths])


def form_rows(R, divisors):
    """Rows 0 … R-1 by one network over ``R * n`` lanes, the rest at
    the smallest of ``L / divisors`` that holds it."""
    def form(flat, fields, nothing, n):
        L = flat.shape[0]
        widths = tuple(-(-L // d) for d in divisors)
        c = jnp.sum(flat < R * n, dtype=jnp.int32)
        tail = jnp.sum(flat < K * n, dtype=jnp.int32) - c
        took = jnp.sum(tail > jnp.asarray(widths[:-1], jnp.int32),
                       dtype=jnp.int32)
        rows = expand_lanes(head(flat, R * n), c,
                            [head(x, R * n) for x in fields], nothing)
        bufs = scatters(flat, fields, nothing, n, c, widths, took)
        return [lax.dynamic_update_slice_in_dim(b, r, 0, 0)
                for b, r in zip(bufs, rows)]
    return form


def form_parent(flat, fields, nothing, n):
    """PR 36's: row 0 by the network, the rest at ``L/2`` or ``L``."""
    L = flat.shape[0]
    c0 = jnp.sum(flat < n, dtype=jnp.int32)
    wide = jnp.sum(flat < K * n, dtype=jnp.int32) - c0 > L // 2
    row0 = expand_lanes(head(flat, n), c0, [head(x, n) for x in fields],
                        nothing)
    bufs = scatters(flat, fields, nothing, n, c0, (L // 2, L),
                    wide.astype(jnp.int32))
    return [lax.dynamic_update_slice_in_dim(b, r, 0, 0)
            for b, r in zip(bufs, row0)]


def form_windows(flat, fields, nothing, n):
    """R = 2, a network a row: row 1's window of n lanes starts at
    lane ``c0`` of the lanes padded by n (no clamp)."""
    L = flat.shape[0]
    widths = tuple(-(-L // d) for d in (8, 4, 2, 1))
    c0 = jnp.sum(flat < n, dtype=jnp.int32)
    c1 = jnp.sum(flat < 2 * n, dtype=jnp.int32) - c0
    tail = jnp.sum(flat < K * n, dtype=jnp.int32) - c0 - c1
    took = jnp.sum(tail > jnp.asarray(widths[:-1], jnp.int32),
                   dtype=jnp.int32)
    row0 = expand_lanes(head(flat, n), c0, [head(x, n) for x in fields],
                        nothing)

    def window(x):
        return lax.dynamic_slice_in_dim(head(x, L + n), c0, n)
    row1 = expand_lanes(window(flat) - jnp.int32(n), c1,
                        [window(x) for x in fields], nothing)
    bufs = scatters(flat, fields, nothing, n, c0 + c1, widths, took)
    return [lax.dynamic_update_slice_in_dim(
        b, jnp.concatenate([r0, r1]), 0, 0)
        for b, r0, r1 in zip(bufs, row0, row1)]


FORMS = (("parent", form_parent, 0),
         ("rows 1, L/8 L/4 L/2 L", form_rows(1, (8, 4, 2, 1)), 0),
         ("rows 2, L/8 L/4 L/2 L", form_rows(2, (8, 4, 2, 1)), 0),
         ("rows 3, L/8 L/4 L/2 L", form_rows(3, (8, 4, 2, 1)), 1),
         ("rows 3, L/32 L/8 L/2 L", form_rows(3, (32, 8, 2, 1)), 1))


def main():
    lg = int(sys.argv[1]) if len(sys.argv) > 1 else 20
    n = 1 << lg
    rng = np.random.default_rng(8)
    print(json.dumps({"device": jax.devices()[0].device_kind,
                      "platform": jax.devices()[0].platform, "n": n}))
    vals = jnp.asarray(rng.integers(0, 1 << 30, 2 * n).astype(np.int32))

    def whole(tag, flat, nf, forms, **facts):
        L = flat.shape[0]
        v = vals[:L]
        nothing = [I32MAX] + [0] * (nf - 1)
        f = np.asarray(flat)
        facts = dict(lanes=L, fields=nf, rank0=int((f < n).sum()),
                     rank1=int(((f >= n) & (f < 2 * n)).sum()),
                     rank2=int(((f >= 2 * n) & (f < 3 * n)).sum()),
                     fitting=int((f < K * n).sum()), **facts)
        for name, form in forms:
            loop(f"{tag}, {nf} fields: {name}",
                 lambda x, i, flat, v, form=form: word(
                     x, i, *form(*moved(flat, v, i, nf, n), nothing, n)),
                 flat, v, **facts)

    # the whole forms on uniform destinations, by L / n and fields
    for num, den in ((2, 1), (1, 1), (1, 2), (1, 4)):
        L = n * num // den
        flat = staged(rng.integers(0, n, L), n, L)
        for nf in (2, 3):
            whole(f"uniform L={num}n/{den}", flat, nf,
                  [(name, form) for name, form, least in FORMS
                   if L >= least * n])
    # R = 2 with a network a row, where the one network was chosen
    flat = staged(rng.integers(0, n, n), n, n)
    whole("uniform L=1n/1", flat, 2, [("windows 2, L/8 L/4 L/2 L",
                                       form_windows)])
    # a burst whose tail is wide: eight arrivals a node at n/8 nodes
    flat = staged(np.repeat(rng.permutation(n)[:n // 8], 8), n, n)
    for nf in (2, 3):
        whole("burst L=n, 8 a node", flat, nf,
              [(name, form) for name, form, _ in FORMS])

    # the pieces: the network by its lanes, one scatter by its width
    for rows in (1, 2, 3):
        m = rows * n
        t = np.unique(rng.integers(0, m, m)).astype(np.int32)
        target = np.zeros(m, np.int32)
        target[:t.size] = t
        a = jnp.asarray(rng.integers(0, 1 << 30, m).astype(np.int32))
        loop(f"expand_lanes over {rows}n lanes, two fields",
             lambda x, i, tg, a: word(x, i, *expand_lanes(
                 tg, jnp.int32(t.size) - (i & 7), [a ^ i, a + i],
                 [I32MAX, 0])),
             jnp.asarray(target), a, lanes=m, prefix=int(t.size))
    flat = staged(rng.integers(0, n, n), n, n)
    for d in (32, 16, 8, 4):
        w = n // d
        loop(f"scatter of n/{d} lanes into fresh [24 n], sorted and "
             "declared",
             lambda x, i, flat, v, w=w: word(
                 x, i, jnp.full((K * n,), I32MAX, jnp.int32).at[
                     lax.dynamic_slice_in_dim(flat, i, w)].set(
                         lax.dynamic_slice_in_dim(v, i, w) ^ i,
                         mode="drop", indices_are_sorted=True,
                         unique_indices=True)),
             flat, vals[:n], lanes=w)

    if jax.devices()[0].platform == "tpu":
        os.makedirs("chiprun_out", exist_ok=True)
        with open(f"chiprun_out/stage_tail_micro_r08_n{lg}.jsonl",
                  "w") as f:
            for row in ROWS:
                f.write(json.dumps(row) + "\n")


if __name__ == "__main__":
    main()
