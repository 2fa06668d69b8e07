"""What the fault masks' table look-ups cost on this chip, by where
each table is read (round 12; docs/faults.md "Where each table is
read", ``faults/apply.py``, ``engine.py`` ``_route_adaptive``).

The shape is the chaos fleet's (``gossip_100k_chaos.fleet8``): eight
worlds of 2^17 nodes under ``vmap``, one partition row, one link row,
one message a node, every lane a sender, so 2^20 lanes a call. In that
cell's trace a gather of 2^20 lanes is 6.30 ms [ledger, PR 53] and the
parent's masks made four. The pieces, each a loop of 16 calls with one
readback (``bucket_slice_micro_r10.py``'s way):

- ``floor``: the loop alone (the destinations turned by the iteration,
  a word folded into the carry), which every other row carries too;
- ``parent_cut``: ``cut_mask`` at both ends of every outbox lane (two
  look-ups: ``part_group[:, src]``, ``part_group[:, dst]``);
- ``parent_degrade``: ``degrade`` on the same lanes (two more:
  ``link_src[i][src]``, ``link_dst[i][dst]``, and the int64
  ``num // den``);
- ``parent_masks``: both, the parent's four look-ups;
- ``source_in_place``: the sender's side read on the node lanes
  (``src_link_bits``, its own packed word's group): no look-up;
- ``packed_lookup``: the ONE look-up (``dst_words``), the cut from its
  low bits (``cut_mask_at``) and the link verdicts from its high bits
  (``link_aff_bits``) packed above the destination id;
- ``take_plain`` and ``take_packed``: a rung's ``take(pdst, sidc)`` of
  the plain destinations, and of the packed word with the unpacking
  and ``degrade_bits`` behind it (the gather is the same lanes and the
  same word: the difference is the unpacking and the arithmetic);
- ``new_masks``: ``packed_lookup`` and ``take_packed`` together, what
  an iteration pays since PR 54 (less ``take_plain``, which the rung
  paid before too).

Before a piece is timed its result is held to the parent's, lane for
lane. Imports ``faults/`` only. ``python
profiling/fault_gather_micro_r12.py [word]`` prints one JSON line a
piece (with ``word``: the pieces whose name holds it); on a TPU it
writes them to ``chiprun_out/fault_gather_micro_r12[_word].jsonl``
too. Some four minutes on the chip (the compiler takes 5-20 s a
piece).
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

from timewarp_tpu.faults import (FaultFleet, FaultSchedule, LinkWindow,
                                 Partition)
from timewarp_tpu.faults.apply import (cut_mask, cut_mask_at, degrade,
                                       degrade_bits, dst_words,
                                       link_aff_bits, own_lanes,
                                       src_link_bits)
from timewarp_tpu.faults.schedule import dst_word_layout

REPS = 16
ROWS = []
#: the pieces to run: those whose name holds this
ONLY = sys.argv[1] if len(sys.argv) > 1 else ""


def tables(n, worlds):
    """A fleet's stacked tables: a world's partition cuts the nodes in
    two at its own place and its link row slows its own stretch of
    sources to every destination but a stretch, inside windows that
    hold half of the instants drawn below."""
    scheds = []
    for b in range(worlds):
        cut = n // 2 + b * (n // 64)
        lo = b * (n // 16)
        scheds.append(FaultSchedule((
            Partition((tuple(range(cut)), tuple(range(cut, n))), 50, 150),
            LinkWindow(tuple(range(lo, lo + n // 2)),
                       tuple(range(n // 8, n)), 100, 200, scale=2.5,
                       extra_us=500))))
    return jax.tree.map(jnp.asarray, FaultFleet(tuple(scheds)).tables(n))


def pack(ft, node_ids, pdst, now, rows):
    """engine.py's form before the compaction: ``(cut, pdst with the
    verdicts above the id)``."""
    n = ft.dst_word.shape[-1]
    at_dst = dst_words(ft, pdst)
    cut = (pdst >= 0) & cut_mask_at(ft, node_ids, at_dst, now)
    aff = link_aff_bits(ft, src_link_bits(ft, node_ids, now, rows),
                        at_dst[0], rows)
    return cut, jnp.where((pdst >= 0) & ~cut,
                          pdst | (aff << (n - 1).bit_length()), -1)


def unpack(ft, packed, delay, sidc, rows):
    """and after it: ``(destinations, delays)`` of the rung's lanes."""
    dbits = (ft.dst_word.shape[-1] - 1).bit_length()
    dst_a = jnp.take(packed, sidc, axis=1)
    aff = jnp.where(dst_a >= 0, dst_a >> dbits, 0)
    return (jnp.where(dst_a >= 0, dst_a & ((1 << dbits) - 1), -1),
            degrade_bits(ft, jnp.take(delay, sidc, axis=1), aff, rows))


def pieces(rows):
    def lanes(node_ids, pdst, now):
        return (jnp.broadcast_to(node_ids[None, :], pdst.shape),
                jnp.broadcast_to(now[None, :], pdst.shape))

    def floor(ft, node_ids, pdst, now, delay, sidc):
        return [pdst]

    def parent_cut(ft, node_ids, pdst, now, delay, sidc):
        src, t = lanes(node_ids, pdst, now)
        return [(pdst >= 0) & cut_mask(ft, src, pdst, t)]

    def parent_degrade(ft, node_ids, pdst, now, delay, sidc):
        src, t = lanes(node_ids, pdst, now)
        return [degrade(ft, delay, src, pdst, t)]

    def parent_masks(ft, *a):
        return parent_cut(ft, *a) + parent_degrade(ft, *a)

    def source_in_place(ft, node_ids, pdst, now, delay, sidc):
        return [src_link_bits(ft, node_ids, now, rows),
                own_lanes(ft.dst_word, node_ids)[0]]

    def packed_lookup(ft, node_ids, pdst, now, delay, sidc):
        return list(pack(ft, node_ids, pdst, now, rows))

    def take_plain(ft, node_ids, pdst, now, delay, sidc):
        return [jnp.take(pdst, sidc, axis=1),
                jnp.take(delay, sidc, axis=1)]

    def take_packed(ft, node_ids, pdst, now, delay, sidc):
        return list(unpack(ft, pdst, delay, sidc, rows))

    def new_masks(ft, node_ids, pdst, now, delay, sidc):
        cut, packed = pack(ft, node_ids, pdst, now, rows)
        return [cut, *unpack(ft, packed, delay, sidc, rows)]

    return (floor, parent_cut, parent_degrade, parent_masks,
            source_in_place, packed_lookup, take_plain, take_packed,
            new_masks)


def check(ft, args, rows):
    """The new form against the parent's, lane for lane, a world."""
    def world(ft, node_ids, pdst, now, delay, sidc):
        src = jnp.broadcast_to(node_ids[None, :], pdst.shape)
        t = jnp.broadcast_to(now[None, :], pdst.shape)
        want_cut = (pdst >= 0) & cut_mask(ft, src, pdst, t)
        want_delay = degrade(ft, delay, src, pdst, t)
        cut, packed = pack(ft, node_ids, pdst, now, rows)
        dst_l, slowed = unpack(ft, packed, delay, sidc, rows)
        live = dst_l >= 0
        return (jnp.all(cut == want_cut)
                & jnp.all(dst_l == jnp.take(
                    jnp.where(want_cut, -1, pdst), sidc, axis=1))
                & jnp.all(jnp.where(live, slowed, 0) == jnp.where(
                    live, jnp.take(want_delay, sidc, axis=1), 0)),
                jnp.sum(want_cut), jnp.sum(live & (slowed != jnp.take(
                    delay, sidc, axis=1))))
    same, cuts, slowed = jax.jit(jax.vmap(world))(ft, *args)
    assert bool(jnp.all(same)) and int(cuts.min()) > 0 \
        and int(slowed.min()) > 0, (same, cuts, slowed)


def loop(name, fn, ft, args, **shape):
    """``fn`` under ``vmap`` REPS times, the destinations turned by the
    iteration and a word of every result folded into the carry behind
    a barrier: the median of five timed calls after the compiling
    one."""
    n = shape["n"]

    def rep(x, ft, node_ids, pdst, now, delay, sidc):
        def body(i, x):
            turned = jnp.where(pdst >= 0,
                               (pdst + i * jnp.int32(7919)) % jnp.int32(n),
                               pdst)
            out = lax.optimization_barrier(jax.vmap(fn)(
                ft, node_ids, turned, now, delay,
                (sidc + i * jnp.int32(104729)) % jnp.int32(n)))
            for b in out:
                x = x ^ b.reshape(-1)[0].astype(jnp.int32)
            return x
        return lax.fori_loop(jnp.int32(0), jnp.int32(REPS), body, x)
    f = jax.jit(rep)
    t0 = time.perf_counter()
    int(f(jnp.int32(0), ft, *args))
    compile_s = time.perf_counter() - t0
    times = []
    for _ in range(5):
        t0 = time.perf_counter()
        int(f(jnp.int32(0), ft, *args))
        times.append(time.perf_counter() - t0)
    row = dict(piece=name, **shape,
               us_a_call=round(statistics.median(times) / REPS * 1e6, 1),
               compile_s=round(compile_s, 1),
               platform=jax.devices()[0].platform)
    ROWS.append(row)
    print(json.dumps(row), flush=True)


def main():
    worlds, n, M = 8, 1 << 17, 1
    if jax.devices()[0].platform == "cpu":
        n >>= 6
    rng = np.random.default_rng(12)
    ft = tables(n, worlds)
    rows = dst_word_layout(n, ft.link_start.shape[-1])[1]
    pdst = rng.integers(0, n, size=(worlds, M, n)).astype(np.int32)
    pdst[rng.random(pdst.shape) < 1 / 16] = -1
    args = tuple(map(jnp.asarray, (
        np.tile(np.arange(n, dtype=np.int32), (worlds, 1)), pdst,
        rng.integers(0, 250, size=(worlds, n)).astype(np.int64),
        rng.integers(1_000, 5_000, size=pdst.shape).astype(np.int64),
        np.sort(rng.integers(0, n, size=(worlds, n)).astype(np.int32)))))
    check(ft, args, rows)
    for fn in pieces(rows):
        if ONLY in fn.__name__:
            loop(fn.__name__, fn, ft, args, worlds=worlds, n=n, M=M)
    if jax.devices()[0].platform == "tpu":
        os.makedirs("chiprun_out", exist_ok=True)
        with open("chiprun_out/fault_gather_micro_r12"
                  + (f"_{ONLY}" if ONLY else "") + ".jsonl", "w") as f:
            for row in ROWS:
                f.write(json.dumps(row) + "\n")


if __name__ == "__main__":
    main()
