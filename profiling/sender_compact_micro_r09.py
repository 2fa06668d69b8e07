"""What the ladder's sender compaction costs on this chip by how it is
done (round 9; docs/engines.md "The sender compaction, by its form",
``engine.py`` ``_route_adaptive``'s scope ``tw.route/senders``).

One call is what ``_route_adaptive`` does before its rungs: from the
mask of the nodes that send, the live node ids ascending in front and
``n`` behind them, at the full node width. Every form gives the same
array, word for word (checked here on the device before it is timed):

- ``sort``: the parent's ``lax.sort(where(mask, ids, n))``, one
  operand;
- ``cumsum + network``: ``ops.numeric.compress_lanes``' network on
  ``lax.cumsum``'s prefix count;
- ``rows + network``: the same network on a two-level prefix: the sums
  of rows of 128 lanes, a prefix over the rows, a seven-step prefix
  inside a row;
- ``mxu + network``: ``compress_lanes`` as it ships: the two-level
  prefix with the seven steps done by one int8 matrix product with a
  strict triangle of ones (int32 accumulation: exact;
  ``ops.numeric._live_below``);

at ``[2^17]`` (the wave), ``[8, 2^17]`` under ``vmap`` (the fleet),
``[65 537]`` (the observer ring) and ``[2^20]`` (praos), with one lane
in 128 live (the smallest rung's share) and with half. Besides, the
pieces: each prefix alone, and the network alone with the ids as its
one field, with two fields, and with no field (the ids read back from
the displacement, ``lane + displacement``), and **the floor**: the
loop with the mask made and a word of it folded and nothing between,
which every other row carries too (the call's launch and readback over
its 16 iterations, the mask, the fold).

Every piece runs inside a ``fori_loop`` whose mask moves with the
iteration, with a readback sync (``stage_micro_r06.py``'s way); a row
is the median of five timed calls.

Imports ``ops/`` only. ``python profiling/sender_compact_micro_r09.py
[word]`` prints one JSON line a piece (with ``word``: the pieces whose
name holds it); on a TPU it writes them to
``chiprun_out/sender_compact_micro_r09[_word].jsonl`` too.
"""

import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from timewarp_tpu.utils import jaxconfig  # noqa: F401

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from timewarp_tpu.ops import numeric
from timewarp_tpu.ops.numeric import compress_lanes

REPS = 16
ROWS = []
ROW = numeric._ROW
#: the pieces to run: those whose name holds this
ONLY = sys.argv[1] if len(sys.argv) > 1 else ""


def loop(name, fn, *args, **facts):
    """``fn(x, i, *args)`` REPS times on the carry ``x`` (one int32
    word: every piece folds a word of its result into it, so nothing
    is dead and nothing is hoisted): the median of five timed calls
    after the one that compiles."""
    if ONLY not in name:
        return

    def rep(x, *rest):
        return lax.fori_loop(jnp.int32(0), jnp.int32(REPS),
                             lambda i, x: fn(x, i, *rest), x)
    f = jax.jit(rep)
    t0 = time.perf_counter()
    int(f(jnp.int32(0), *args))
    first = time.perf_counter() - t0
    times = []
    for _ in range(5):
        t0 = time.perf_counter()
        int(f(jnp.int32(0), *args))
        times.append((time.perf_counter() - t0) / REPS)
    row = {"op": name, "us": round(statistics.median(times) * 1e6, 1),
           "us_min": round(min(times) * 1e6, 1),
           "first_call_s": round(first, 2), **facts}
    ROWS.append(row)
    print(json.dumps(row), flush=True)


def word(x, i, *arrays):
    """The carry after a piece: one word of each result, at a place
    that moves with the iteration."""
    for a in arrays:
        flat = a.reshape(-1)
        x = x ^ flat[(i * 7919) % flat.shape[0]].astype(jnp.int32)
    return x


def _rows(mask, dtype):
    """``mask``'s lanes as rows of 128, zeros past the last lane."""
    n = mask.shape[-1]
    r = -(-n // ROW)
    x = mask.astype(dtype)
    if r * ROW != n:
        x = jnp.concatenate(
            [x, jnp.zeros(x.shape[:-1] + (r * ROW - n,), dtype)], axis=-1)
    return x.reshape(x.shape[:-1] + (r, ROW))


def _from_rows(inrow, x):
    """The exclusive prefix over all lanes from the one inside each
    row (``inrow``) and the rows' lanes (``x``, int32)."""
    row = inrow[..., -1] + x[..., -1]
    below = inrow + (jnp.cumsum(row, axis=-1) - row)[..., None]
    return below.reshape(below.shape[:-2] + (-1,))


def below_cumsum(mask):
    live = mask.astype(jnp.int32)
    return jnp.cumsum(live, axis=-1) - live


def below_rows(mask):
    """Two levels, the row's own prefix by seven shifted adds."""
    x = _rows(mask, jnp.int32)
    inc = x
    s = 1
    while s < ROW:
        inc = inc + jnp.concatenate(
            [jnp.zeros(inc.shape[:-1] + (s,), inc.dtype), inc[..., :-s]],
            axis=-1)
        s *= 2
    return _from_rows(inc - x, x)[..., :mask.shape[-1]]


#: two levels, the row's own prefix by one product with the strict
#: triangle: what ``ops/numeric.py`` ships
below_mxu = numeric._live_below


PREFIXES = (("cumsum", below_cumsum), ("rows", below_rows),
            ("mxu", below_mxu))


def by_sort(mask, ids, n):
    return lax.sort(jnp.where(mask, ids, jnp.int32(n)))


def displacement(below, mask):
    """What ``compress_lanes`` hands its network, on the prefix
    ``below``."""
    lane = jnp.arange(mask.shape[-1], dtype=jnp.int32)
    return jnp.where(mask, lane - below(mask), numeric._NO_LANE)


def by_network(below):
    if below is below_mxu:      # the shipped call itself, jit and all
        return lambda mask, ids, n: compress_lanes(
            mask, [ids], [jnp.int32(n)])[0]
    return lambda mask, ids, n: numeric._compress(
        displacement(below, mask), [ids], [jnp.int32(n)])[0]


def ids_by_displacement(mask, n):
    """The network with no field: the displacement alone travels, and
    a lane that was reached reads its id back as ``lane + disp``."""
    lane = jnp.arange(n, dtype=jnp.int32)
    disp = displacement(below_mxu, mask)
    for i in range((n - 1).bit_length()):
        s = 1 << i
        above = jnp.concatenate(
            [disp[s:], jnp.full((s,), numeric._NO_LANE, disp.dtype)])
        comes = (above & jnp.int32(s)) != 0
        stays = (disp & jnp.int32(s)) == 0
        disp = jnp.where(comes, above,
                         jnp.where(stays, disp, numeric._NO_LANE))
    return jnp.where(disp == numeric._NO_LANE, jnp.int32(n), lane + disp)


def moved(h, i, live_of_128):
    """The mask at iteration ``i``: ``live_of_128`` lanes in 128 live,
    which ones moves with ``i``."""
    return ((h ^ (i * jnp.int32(40503))) & jnp.int32(127)) < live_of_128


def main():
    rng = np.random.default_rng(9)
    dev = jax.devices()[0]
    print(json.dumps({"device": dev.device_kind,
                      "platform": dev.platform}))
    for batch, n in ((None, 1 << 17), (8, 1 << 17), (None, 65537),
                     (None, 1 << 20)):
        shape = (n,) if batch is None else (batch, n)
        tag = "[" + ", ".join(str(d) for d in shape) + "]"
        h = jnp.asarray(rng.integers(0, 1 << 30, shape).astype(np.int32))
        ids = jnp.arange(n, dtype=jnp.int32)
        forms = [("sort", by_sort)] + [
            (f"{name} + network", by_network(below))
            for name, below in PREFIXES]

        def call(form):
            one = lambda m: form(m, ids, n)
            return one if batch is None else jax.vmap(one)
        # every form against the sort, on the device, before any timing
        for live in (0, 1, 64, 127, 128):
            m = moved(h, jnp.int32(3), live)
            want = np.asarray(jax.jit(call(by_sort))(m))
            for name, form in forms[1:]:
                got = np.asarray(jax.jit(call(form))(m))
                assert np.array_equal(got, want), (tag, name, live)
        for live in (1, 64):
            for name, form in forms:
                loop(f"{tag} {name}",
                     lambda x, i, h, f=call(form), live=live: word(
                         x, i, f(moved(h, i, live))),
                     h, shape=list(shape), live_of_128=live)
        # the pieces
        for name, below in PREFIXES:
            loop(f"{tag} prefix alone: {name}",
                 lambda x, i, h, below=below: word(
                     x, i, below(moved(h, i, 1))),
                 h, shape=list(shape), live_of_128=1)
        loop(f"{tag} the floor: the mask and the fold alone",
             lambda x, i, h: word(x, i, moved(h, i, 1)),
             h, shape=list(shape), live_of_128=1)
        if batch is None:
            m0 = moved(h, jnp.int32(3), 64)
            assert np.array_equal(
                np.asarray(jax.jit(lambda m: ids_by_displacement(m, n))(m0)),
                np.asarray(jax.jit(call(by_sort))(m0))), tag
            loop(f"{tag} mxu + network, no field (ids from the "
                 "displacement)",
                 lambda x, i, h: word(
                     x, i, ids_by_displacement(moved(h, i, 1), n)),
                 h, shape=list(shape), live_of_128=1)
            loop(f"{tag} mxu + network, two fields",
                 lambda x, i, h: word(x, i, *compress_lanes(
                     moved(h, i, 1), [ids, h ^ i], [jnp.int32(n), 0])),
                 h, shape=list(shape), live_of_128=1)

    if dev.platform == "tpu":
        os.makedirs("chiprun_out", exist_ok=True)
        name = "sender_compact_micro_r09" + (
            "_" + "".join(c for c in ONLY if c.isalnum()) if ONLY else "")
        with open(f"chiprun_out/{name}.jsonl", "w") as f:
            for row in ROWS:
                f.write(json.dumps(row) + "\n")


if __name__ == "__main__":
    main()
