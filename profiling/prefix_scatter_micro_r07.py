"""What an ordered inbox's ranked insertion pays for the lanes that
cannot land, and from how many lanes on a ladder of static widths
around its scatters pays for itself (round 7; the threshold
``engine.py`` ``_PREFIX_SCATTER_LANES``).

The mailbox of the observer ring's cell: ``[8, 65 537]`` planes of
deliver time and sender and ``[8, 2, 65 537]`` of payload, carried by a
``fori_loop`` as the quiet driver's ``while`` carries them. One
iteration is one insertion of ``L`` destination-sorted lanes (``L``
from 2^11 to 2^17). The scatters alone, the slot of every lane given:

- ``one``: the four flat scatters (deliver time, sender, two payload
  words) at ``L`` lanes, the lanes that do not fit at the out-of-range
  index, ``mode="drop"``;
- ``switch``: one scalar, the lane after the last that fits, and the
  same four scatters inside a ``lax.switch`` over the static widths
  ``L/8``, ``L/4``, ``L/2``, ``L``, each on slices from lane 0;

and from 2^14 lanes on (PR 52) the pieces and the whole insertion, the
slot computed from the destinations' kept counts as the engine does:

- ``gather``: ``counts[clip(sd)] + rank`` alone, on the first ``w``
  lanes, ``w`` each of the four widths;
- ``scatters``: the four scatters alone on the first ``w`` lanes, no
  switch;
- ``ranked-fits``: PR 43's insertion: the gather on all ``L`` lanes,
  the width from the last lane that fits, the scatters in the switch;
- ``ranked-ranks``: PR 52's: the width from the last valid lane of
  rank under 8, the gather and the scatters in the switch on its
  ``w`` lanes, ``overflow`` as the valid lanes less those that landed;

on these kinds of lanes, the cell's and the worst cases:

- ``notes``: half the lanes valid, all to one hub of 8 slots: 8 fit
  (the switch takes ``L/8``);
- ``tokens``: half the lanes valid, one to a node: all of them fit
  (``L/2``);
- ``full``: every lane valid and fitting, two to a node (``L``: the
  switch is pure cost);
- ``spread-full`` (the ranked forms only): the tokens' lanes on
  mailboxes that are all full: nothing fits, and the ranks say
  nothing of it (``ranked-fits`` takes ``L/8``, ``ranked-ranks``
  ``L/2``: the one case where the newer form is the wider).

Imports nothing of the engine. ``python
profiling/prefix_scatter_micro_r07.py [top [word]]`` prints one JSON
line a piece (sizes up to ``2^top``, 17; with ``word``, the forms
whose name holds it); on a TPU it writes them to
``chiprun_out/prefix_scatter_micro_r07.jsonl`` too.
"""

import json
import os
import sys
import time
from functools import partial

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
    """``(sd, rank, counts)`` of ``L`` lanes sorted by destination,
    the invalid ones (row ``N``) last: each lane's destination and its
    rank among that destination's arrivals, and the messages every
    node keeps (a lane asks for slot ``counts[sd] + rank``, and fits
    under ``K``)."""
    lane = np.arange(L)
    counts = np.zeros(N)
    if kind == "notes":
        sd = np.where(lane < L // 2, N - 1, N)
        rank = np.where(lane < L // 2, lane, 0)
    elif kind in ("tokens", "spread-full"):
        sd = np.where(lane < L // 2, lane, N)
        rank = np.zeros(L)
        if kind == "spread-full":
            counts[:] = K
    else:
        sd, rank = lane // 2, lane % 2
    return tuple(x.astype(np.int32) for x in (sd, rank, counts))


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


def ranked(form, L):
    """The whole insertion from ``(sd, rank, counts)``, the width from
    the lanes that fit (PR 43) or from the ranks alone (PR 52)."""
    widths = tuple(-(-L // d) for d in (8, 4, 2, 1))
    lane = jnp.arange(1, L + 1, dtype=jnp.int32)
    steps = jnp.asarray(widths, jnp.int32)

    def slots(counts, sd, rank, w):
        pos = counts[jnp.clip(sd[:w], 0, N - 1)] + rank[:w]
        return (sd[:w] < N) & (pos < K), jnp.clip(pos, 0, K - 1)

    def insert(i, carry, sd, rank, counts, fields):
        planes, over = carry
        # the kept counts move with the iteration (a full mailbox
        # stays full), so the gather is not hoisted
        counts = counts + (i & 1)
        fields = tuple(x + i for x in fields)
        ok = sd < N
        if form == "ranked-fits":
            fits, col = slots(counts, sd, rank, L)
            hi = jnp.max(jnp.where(fits, lane, 0))
            rel, src, pay = lax.switch(jnp.sum(hi > steps), [
                partial(scatters, planes, sd, fits, col, fields, w)
                for w in widths])
            landed = jnp.sum(fits, dtype=jnp.int32)
        else:
            hi = jnp.max(jnp.where(ok & (rank < K), lane, 0))

            def branch(w):
                fits, col = slots(counts, sd, rank, w)
                return scatters(planes, sd, fits, col, fields, w) + (
                    jnp.sum(fits, dtype=jnp.int32),)
            rel, src, pay, landed = lax.switch(
                jnp.sum(hi > steps), [partial(branch, w) for w in widths])
        return (rel, src, pay), over + jnp.sum(ok, dtype=jnp.int32) - landed
    return insert


def best_of(reps, args, first_word):
    """Compile and run ``reps`` once, then the best of three calls:
    ``(out, us a repetition, the first call's seconds)``."""
    t0 = time.perf_counter()
    out = reps(*args)
    int(first_word(out))
    first = time.perf_counter() - t0
    best = None
    for _ in range(3):
        t0 = time.perf_counter()
        int(first_word(reps(*args)))
        dt = (time.perf_counter() - t0) / REPS
        best = dt if best is None else min(best, dt)
    return out, best * 1e6, first


def row_of(out, us, first, **row):
    row.update(us=round(us, 1), first_call_s=round(first, 2))
    if out is not None:
        row["slots_filled"] = int((np.asarray(out[0]) != 2**31 - 1).sum())
    ROWS.append(row)
    print(json.dumps(row), flush=True)


def timed(form, kind, L, w=None):
    """One row: ``form`` on ``kind``'s lanes (``w``: the static width
    of a piece timed alone). Returns the planes it ends on."""
    rng = np.random.default_rng(L)
    sd, rank, counts = (jnp.asarray(x) for x in lanes_of(kind, L))
    fields = tuple(jnp.asarray(rng.integers(0, 10**6, L, dtype=np.int32))
                   for _ in range(4))
    planes = (jnp.full((K, N), 2**31 - 1, jnp.int32),
              jnp.zeros((K, N), jnp.int32),
              jnp.zeros((K, P, N), jnp.int32))
    name = {"form": form, "lanes_kind": kind, "log2_L": int(np.log2(L))}
    if form == "gather":
        @jax.jit
        def reps(sd, rank, counts):
            # the carry is the gathered slots themselves: written
            # every iteration, from counts that move with it
            return lax.fori_loop(
                jnp.int32(0), jnp.int32(REPS),
                lambda i, _: (counts + (i & 1))[
                    jnp.clip(sd[:w], 0, N - 1)] + rank[:w],
                jnp.zeros(w, jnp.int32))
        _, us, first = best_of(reps, (sd, rank, counts), lambda o: o[0])
        row_of(None, us, first, **name, width=w)
        return None
    if form.startswith("ranked"):
        insert = ranked(form, L)

        @jax.jit
        def reps(planes, sd, rank, counts, fields):
            return lax.fori_loop(
                jnp.int32(0), jnp.int32(REPS),
                lambda i, c: insert(i, c, sd, rank, counts, fields),
                (planes, jnp.int32(0)))
        out, us, first = best_of(reps, (planes, sd, rank, counts, fields),
                                 lambda o: o[1])
        row_of(out[0], us, first, **name, overflow=int(out[1]))
        return out
    pos = counts[jnp.clip(sd, 0, N - 1)] + rank
    if form == "scatters":
        def insert(i, planes, sd, pos, fields):
            fits = (sd < N) & (pos < K)
            col = (jnp.clip(pos, 0, K - 1) + i) % jnp.int32(K)
            return scatters(planes, sd, fits, col,
                            tuple(x + i for x in fields), w)
        name["width"] = w
    else:
        insert = insertion(form, L)

    @jax.jit
    def reps(planes, sd, pos, fields):
        return lax.fori_loop(
            jnp.int32(0), jnp.int32(REPS),
            lambda i, pl: insert(i, pl, sd, pos, fields), planes)
    out, us, first = best_of(reps, (planes, sd, pos, fields),
                             lambda o: o[0][0, 0])
    row_of(out, us, first, **name)
    return out


def main():
    dev = jax.devices()[0]
    print(json.dumps({"device": dev.device_kind,
                      "platform": dev.platform}), flush=True)
    top = int(sys.argv[1]) if len(sys.argv) > 1 else 17
    word = sys.argv[2] if len(sys.argv) > 2 else ""

    def wanted(*forms):
        return any(word in form for form in forms)

    def same(one, cut, *what):
        for a, b in zip(jax.tree.leaves(one), jax.tree.leaves(cut)):
            assert np.array_equal(a, b), what
    for log2 in range(11, top + 1):
        L = 1 << log2
        if wanted("one", "switch"):
            for kind in ("notes", "tokens", "full"):
                same(timed("one", kind, L), timed("switch", kind, L),
                     kind, log2)
        if log2 < 14:
            continue
        for w in (-(-L // d) for d in (8, 4, 2, 1)):
            for piece in ("gather", "scatters"):
                if wanted(piece):
                    timed(piece, "full", L, w)
        if wanted("ranked-fits", "ranked-ranks"):
            for kind in ("notes", "tokens", "full", "spread-full"):
                same(timed("ranked-fits", kind, L),
                     timed("ranked-ranks", kind, L), kind, log2)
    if dev.platform == "tpu":
        os.makedirs("chiprun_out", exist_ok=True)
        with open("chiprun_out/prefix_scatter_micro_r07.jsonl", "w") as f:
            for row in ROWS:
                f.write(json.dumps(row) + "\n")


if __name__ == "__main__":
    main()
