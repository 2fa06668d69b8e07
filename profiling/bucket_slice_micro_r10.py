"""What the sharded exchange's bucketing costs on this chip by how the
``[shards, bucket_cap]`` send buffers are filled (round 10;
docs/engines.md "The node-sharded exchange and its capacity",
``sharded.py`` ``ShardedEngine._exchange``'s scope
``tw.route/exchange/bucket``).

One call is what a device does before its ``all_to_all``s: the stable
sort of its ``L`` outbox lanes by destination shard with four int32
planes riding along, then the five buffers (the occupancy as int8 and
the four planes), no collective. Every form gives the same buffers,
word for word (checked here against a numpy bucketing before it is
timed):

- ``scatter``: the parent's ``group_rank`` and
  ``zeros((D, B)).at[brow, bcol].set(x, mode="drop")`` a plane;
- ``slices``: a bucket is the contiguous run of the sorted plane from
  ``start[d]``, so ``D`` unrolled ``dynamic_slice``s of the plane
  padded by ``B`` lanes, masked by the run's length;
- ``vmapped``: as it ships: the same with one ``vmap`` of
  ``dynamic_slice`` over ``start`` (a gather of ``D`` slices, which
  the chip's compiler runs as a loop of ``D`` slices a plane);
- ``stacked``: the planes stacked ``[4, L]`` first, one two-axis
  ``dynamic_slice`` a shard;

and **the floor**, ``sort``: the loop with the sort alone, which every
other row carries too. At ``(L, D, B)`` = (2^18, 4, 73 728) (the cell
``gossip_steady_1m_x4.rounds``: every lane valid, destinations
uniform) and (2^18, 4, 2^18) (the default capacity).

Every piece runs inside a ``fori_loop`` whose destinations move with
the iteration, behind an ``optimization_barrier`` so that the buffers
are written whole, with a readback sync (``stage_micro_r06.py``'s
way); a row is the median of five timed calls.

Imports ``ops/`` only. ``python profiling/bucket_slice_micro_r10.py
[word]`` prints one JSON line a piece (with ``word``: the pieces whose
name holds it); on a TPU it writes them to
``chiprun_out/bucket_slice_micro_r10[_word].jsonl`` too. The chip's
compiler takes 35-60 s a program here (the five-operand sort), so the
whole is some 14 minutes: ``chiprun --timeout 1200``.
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

from timewarp_tpu.ops.numeric import group_rank

REPS = 16
ROWS = []
#: the pieces to run: those whose name holds this
ONLY = sys.argv[1] if len(sys.argv) > 1 else ""


def by_shard(ok, dst, planes, L, D):
    dshard = jnp.where(ok, dst // jnp.int32(L), jnp.int32(D))
    return dshard, lax.sort((dshard,) + planes, dimension=0, num_keys=1)


def sort(ok, dst, planes, L, D, B):
    return list(by_shard(ok, dst, planes, L, D)[1])


def scatter(ok, dst, planes, L, D, B):
    sk, *ops = by_shard(ok, dst, planes, L, D)[1]
    rank = group_rank(sk)
    brow = jnp.where((sk < D) & (rank < B), sk, D)
    bcol = jnp.clip(rank, 0, B - 1)
    return [jnp.zeros((D, B), jnp.int8).at[brow, bcol].set(
        jnp.int8(1), mode="drop")] + [
        jnp.zeros((D, B), x.dtype).at[brow, bcol].set(x, mode="drop")
        for x in ops]


def runs(dshard, D, B):
    count = jnp.sum(dshard == jnp.arange(D, dtype=jnp.int32)[:, None],
                    axis=1, dtype=jnp.int32)
    live = jnp.arange(B, dtype=jnp.int32) < jnp.minimum(count, B)[:, None]
    return jnp.cumsum(count, dtype=jnp.int32) - count, live


def slices(ok, dst, planes, L, D, B):
    dshard, ops = by_shard(ok, dst, planes, L, D)
    start, live = runs(dshard, D, B)
    return [live.astype(jnp.int8)] + [
        jnp.where(live, jnp.stack([
            lax.dynamic_slice(jnp.pad(x, (0, B)), (start[d],), (B,))
            for d in range(D)]), 0) for x in ops[1:]]


def vmapped(ok, dst, planes, L, D, B):
    dshard, ops = by_shard(ok, dst, planes, L, D)
    start, live = runs(dshard, D, B)
    return [live.astype(jnp.int8)] + [
        jnp.where(live, jax.vmap(lambda s, x=jnp.pad(x, (0, B)):
                                 lax.dynamic_slice(x, (s,), (B,)))(start), 0)
        for x in ops[1:]]


def stacked(ok, dst, planes, L, D, B):
    dshard, ops = by_shard(ok, dst, planes, L, D)
    start, live = runs(dshard, D, B)
    x = jnp.pad(jnp.stack(ops[1:]), ((0, 0), (0, B)))
    rows = jnp.where(live, jnp.stack([
        lax.dynamic_slice(x, (jnp.int32(0), start[d]), (x.shape[0], B))
        for d in range(D)], axis=1), 0)
    return [live.astype(jnp.int8)] + list(rows)


def plain(ok, dst, planes, L, D, B):
    """The buffers in numpy: the valid lanes in a stable order by
    shard, the first ``B`` of a shard's in its row."""
    shard = np.where(ok, dst // L, D)
    order = np.argsort(shard, kind="stable")
    sk = shard[order]
    first = np.searchsorted(sk, np.arange(D + 1))
    rank = np.arange(L) - first[sk]
    fits = (sk < D) & (rank < B)
    bufs = np.zeros((1 + len(planes), D, B), np.int32)
    bufs[0, sk[fits], rank[fits]] = 1
    for k, x in enumerate(planes):
        bufs[k + 1, sk[fits], rank[fits]] = x[order][fits]
    return bufs


def loop(name, fn, ok, dst, planes, **shape):
    """``fn`` REPS times, the destinations turned by the iteration and
    a word of every buffer folded into the carry behind a barrier (so
    nothing is dead, hoisted or fused away): the median of five timed
    calls after the one that compiles."""
    n = shape["L"] * shape["D"]

    def rep(x, ok, dst, planes):
        def body(i, x):
            out = lax.optimization_barrier(
                fn(ok, (dst + i * jnp.int32(7919)) % jnp.int32(n),
                   planes, **shape))
            for b in out:
                x = x ^ b.reshape(-1)[0].astype(jnp.int32)
            return x
        return lax.fori_loop(jnp.int32(0), jnp.int32(REPS), body, x)
    f = jax.jit(rep)
    t0 = time.perf_counter()
    int(f(jnp.int32(0), ok, dst, planes))
    compile_s = time.perf_counter() - t0
    times = []
    for _ in range(5):
        t0 = time.perf_counter()
        int(f(jnp.int32(0), ok, dst, planes))
        times.append(time.perf_counter() - t0)
    row = dict(piece=name, **shape,
               us_a_call=round(statistics.median(times) / REPS * 1e6, 1),
               compile_s=round(compile_s, 1),
               platform=jax.devices()[0].platform)
    ROWS.append(row)
    print(json.dumps(row), flush=True)


def main():
    rng = np.random.default_rng(10)
    for L, D, B in ((1 << 18, 4, 73728), (1 << 18, 4, 1 << 18)):
        if jax.devices()[0].platform == "cpu":
            L, B = L >> 6, B >> 6
        ok = jnp.ones((L,), bool)
        dst = jnp.asarray(rng.integers(0, L * D, L), jnp.int32)
        planes = tuple(jnp.asarray(rng.integers(0, 1 << 30, L), jnp.int32)
                       for _ in range(4))
        shape = dict(L=L, D=D, B=B)
        want = plain(*map(np.asarray, (ok, dst)),
                     [np.asarray(x) for x in planes], **shape)
        for fn in (sort, scatter, slices, vmapped, stacked):
            if ONLY not in fn.__name__:
                continue
            if fn is not sort:
                got = jax.jit(fn, static_argnames=("L", "D", "B"))(
                    ok, dst, planes, **shape)
                assert np.array_equal(want, np.stack(got)), fn
            loop(fn.__name__, fn, ok, dst, planes, **shape)
    if jax.devices()[0].platform == "tpu":
        os.makedirs("chiprun_out", exist_ok=True)
        with open("chiprun_out/bucket_slice_micro_r10"
                  + (f"_{ONLY}" if ONLY else "") + ".jsonl", "w") as f:
            for row in ROWS:
                f.write(json.dumps(row) + "\n")


if __name__ == "__main__":
    main()
