"""The fault masks read where each table lies (PR 54): on random
tables the node-lane forms of faults/apply.py (``own_lanes``,
``src_link_bits``, ``dst_words``, ``cut_mask_at``, ``link_aff_bits``,
``degrade_bits``) equal the specification (``cut_mask``, ``degrade``,
which the oracle and the edge engine call) lane for lane, in the two
shapes engine.py gives them: the packed word looked up on the outbox
lanes with the verdicts riding the destination id through a compaction
(a partition row, or the eager path), and the word looked up after the
compaction with the sender's bits riding its offset (link rows alone
on the ladder). Pure jax.numpy on a few hundred lanes: no engine."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from timewarp_tpu.faults.apply import (cut_mask, cut_mask_at, degrade,
                                       degrade_bits, dst_words,
                                       link_aff_bits, own_lanes,
                                       src_link_bits)
from timewarp_tpu.faults.schedule import (FaultTables, dst_word_layout,
                                          pack_dst_word)

N = 96
SPARE = 31 - (N - 1).bit_length()          # 24 link rows fit a word


def _tables(rng, n, Pn, L, sparse_groups=False):
    """Random tables of the given shapes: groups with absent nodes
    (-1) and, with ``sparse_groups``, ids far over ``n`` (what an
    out-of-range member leaves: the word packs ranks); windows of
    which some are inert, some hold the instants drawn below (the
    first row of a kind always does)."""
    ids = np.array([0, 1, 2, 5 * n, 7 * n + 3]) if sparse_groups \
        else np.arange(4)
    part_group = rng.choice(np.concatenate([[-1], ids]),
                            size=(Pn, n)).astype(np.int32)
    start = rng.integers(0, 60, size=Pn + L).astype(np.int64)
    end = start + rng.integers(-5, 60, size=Pn + L)
    for first in ([0] if Pn else []) + ([Pn] if L else []):
        start[first], end[first] = 20, 90      # one live row a kind
    link_src = rng.random((L, n)) < 0.6
    link_dst = rng.random((L, n)) < 0.6
    return FaultTables(
        np.zeros(0, np.int32), np.zeros(0, np.int64),
        np.zeros(0, np.int64), np.zeros(0, bool),
        part_group, start[:Pn], end[:Pn],
        link_src, link_dst, start[Pn:], end[Pn:],
        rng.integers(1, 9, size=L).astype(np.int64),
        rng.integers(1, 5, size=L).astype(np.int64),
        rng.integers(0, 7, size=L).astype(np.int64),
        np.zeros(n, np.int64), pack_dst_word(part_group, link_dst))


def _lanes(rng, n, M, lo=0, width=None):
    """An outbox of the node lanes ``lo .. lo + width``: destinations
    over all ``n`` nodes with invalid lanes (-1), each node's own send
    instant (in and out of the windows), sampled delays."""
    width = n if width is None else width
    pdst = rng.integers(0, n, size=(M, width)).astype(np.int32)
    pdst[rng.random((M, width)) < 0.25] = -1
    now = rng.integers(0, 110, size=width).astype(np.int64)
    delay = rng.integers(1, 5_000, size=(M, width)).astype(np.int64)
    return (jnp.arange(lo, lo + width, dtype=jnp.int32),
            jnp.asarray(pdst), jnp.asarray(now), jnp.asarray(delay))


def _spec(ft, node_ids, pdst, now, delay):
    """The specification on the outbox lanes: (cut, delay after the
    link rows), as engine.py called it until PR 54."""
    src = jnp.broadcast_to(node_ids[None, :], pdst.shape)
    t = jnp.broadcast_to(now[None, :], pdst.shape)
    return ((pdst >= 0) & cut_mask(ft, src, pdst, t),
            degrade(ft, delay, src, pdst, t))


def _compact(rng, pdst, width):
    """A rung's gather: a prefix of live senders, ascending, then the
    sentinel senders (engine.py ``gather``: index 0, nothing valid)."""
    live = np.flatnonzero(np.asarray(jnp.any(pdst >= 0, axis=0)))
    live = live[np.sort(rng.choice(len(live), size=min(len(live),
                                                       width - 3),
                                   replace=False))]
    real = np.arange(width) < len(live)
    sidc = np.where(real, np.resize(live, width), 0)
    return jnp.asarray(sidc), jnp.asarray(real)


def _early(ft, node_ids, pdst, now, delay, rows, sidc, real):
    """engine.py's form where the word is looked up on the outbox
    lanes: returns (cut on the outbox lanes, the compacted lanes'
    delays, their destinations as the rung unpacks them)."""
    n = ft.dst_word.shape[-1]
    dbits = (n - 1).bit_length()
    at_dst = dst_words(ft, pdst)
    cut = (pdst >= 0) & cut_mask_at(ft, node_ids, at_dst, now)
    packed = pdst
    if rows:
        aff = link_aff_bits(ft, src_link_bits(ft, node_ids, now, rows),
                            at_dst[0], rows)
        packed = jnp.where(pdst >= 0, pdst | (aff << dbits), -1)
    dst_a = jnp.take(packed, sidc, axis=1)
    ok = (dst_a >= 0) & real[None, :]
    aff_a = jnp.where(dst_a >= 0, dst_a >> dbits, 0) if rows else None
    dst_l = jnp.where(dst_a >= 0, dst_a & ((1 << dbits) - 1), -1) \
        if rows else dst_a
    src_l = jnp.broadcast_to(node_ids[sidc][None, :], dst_l.shape)
    t_l = jnp.broadcast_to(now[sidc][None, :], dst_l.shape)
    slowed = degrade_bits(ft, jnp.take(delay, sidc, axis=1), aff_a,
                          rows, src_l, dst_l, t_l)
    return cut, slowed, dst_l, ok


def _late(ft, node_ids, pdst, now, delay, rows, sidc, real, W=64):
    """engine.py's form with link rows alone on the ladder: the
    sender's bits ride its in-window offset (under ``W``) through the
    rung's gather, the word is looked up on the rung's lanes."""
    wbits = (W - 1).bit_length()
    rows = min(rows, 31 - wbits)
    woff_n = (now % W).astype(jnp.int32)
    word = woff_n | (src_link_bits(ft, node_ids, now, rows) << wbits) \
        if rows else woff_n
    woff_a = word[sidc]
    dst_l = jnp.take(pdst, sidc, axis=1)
    ok = (dst_l >= 0) & real[None, :]
    aff_a = link_aff_bits(ft, woff_a >> wbits, dst_words(ft, dst_l)[0],
                          rows) if rows else None
    np.testing.assert_array_equal(woff_a & ((1 << wbits) - 1),
                                  woff_n[sidc])
    src_l = jnp.broadcast_to(node_ids[sidc][None, :], dst_l.shape)
    t_l = jnp.broadcast_to(now[sidc][None, :], dst_l.shape)
    slowed = degrade_bits(ft, jnp.take(delay, sidc, axis=1), aff_a,
                          rows, src_l, dst_l, t_l)
    return jnp.zeros(pdst.shape, bool), slowed, dst_l, ok


def _check(ft, lanes, rows, sidc, real):
    node_ids, pdst, now, delay = lanes
    form = _early if ft.part_group.shape[0] else _late
    cut, slowed, dst_l, ok = form(ft, *lanes, rows, sidc, real)
    want_cut, want_delay = _spec(ft, *lanes)
    np.testing.assert_array_equal(cut, want_cut)
    # the rung's lanes: every valid one carries its destination and
    # the specification's delay; a sentinel sender's are not valid
    np.testing.assert_array_equal(
        jnp.where(ok, dst_l, -1),
        jnp.where(real[None, :], jnp.take(pdst, sidc, axis=1), -1))
    np.testing.assert_array_equal(
        jnp.where(ok, slowed, 0),
        jnp.where(ok, jnp.take(want_delay, sidc, axis=1), 0))
    return int(jnp.sum(cut)), int(jnp.sum(
        ok & (slowed != jnp.take(delay, sidc, axis=1))))


@pytest.mark.parametrize("M", [1, 3])
@pytest.mark.parametrize("L", [0, 1, 2, SPARE + 1])
@pytest.mark.parametrize("Pn", [0, 1, 3])
def test_node_lane_forms_equal_the_specification(Pn, L, M):
    rng = np.random.default_rng(1000 * Pn + 10 * L + M)
    ft = jax.tree.map(jnp.asarray, _tables(rng, N, Pn, L, Pn == 3))
    rows = dst_word_layout(N, L)[1]
    assert rows == min(L, SPARE) and ft.dst_word.shape == (
        max(Pn, min(L, 1)), N)
    lanes = _lanes(rng, N, M)
    cuts, slowed = _check(ft, lanes, rows, *_compact(rng, lanes[1], 64))
    # the draws exercise what they are meant to
    assert (cuts > 0) == (Pn > 0) and (slowed > 0) == (L > 0)


def test_a_devices_share_of_the_node_lanes_reads_its_slice():
    # where the lanes are a contiguous share of the nodes (an offset
    # iota), a table's own side is that slice of it, no gather
    rng = np.random.default_rng(7)
    ft = jax.tree.map(jnp.asarray, _tables(rng, N, 2, 2))
    lanes = _lanes(rng, N, 3, lo=32, width=32)
    assert own_lanes(ft.part_group, lanes[0]).shape == (2, 32)
    cuts, slowed = _check(ft, lanes, 2, *_compact(rng, lanes[1], 24))
    assert cuts > 0 and slowed > 0


def test_the_eager_lanes_are_the_node_lanes_m_times_over():
    # engine.py's eager path: the same forms on flat lanes
    rng = np.random.default_rng(11)
    M = 3
    ft = jax.tree.map(jnp.asarray, _tables(rng, N, 2, 3))
    node_ids, pdst, now, delay = _lanes(rng, N, M)
    src_f, dst_f = jnp.tile(node_ids, M), pdst.reshape(-1)
    tmsg = jnp.tile(now, M)
    at_dst = dst_words(ft, dst_f)
    cut = (dst_f >= 0) & cut_mask_at(ft, node_ids, at_dst, tmsg)
    aff = link_aff_bits(ft, src_link_bits(ft, node_ids, now, 3),
                        at_dst[0], 3)
    np.testing.assert_array_equal(
        cut, (dst_f >= 0) & cut_mask(ft, src_f, dst_f, tmsg))
    np.testing.assert_array_equal(
        degrade_bits(ft, delay.reshape(-1), aff, 3),
        degrade(ft, delay.reshape(-1), src_f, dst_f, tmsg))


def test_under_vmap_with_a_leading_world_axis():
    rng = np.random.default_rng(3)
    B, M = 3, 3
    worlds = [_tables(rng, N, 1, 2) for _ in range(B)]
    ftv = FaultTables(*(jnp.asarray(np.stack(x)) for x in zip(*worlds)))
    lanes = [_lanes(rng, N, M) for _ in range(B)]
    stacked = tuple(jnp.stack(x) for x in zip(*lanes))
    sidc, real = _compact(rng, jnp.min(stacked[1], axis=0), 48)

    def world(ft, *ln):
        return _early(ft, *ln, 2, sidc, real)
    got = jax.vmap(world)(ftv, *stacked)
    for b in range(B):
        ft = jax.tree.map(jnp.asarray, worlds[b])
        want = _early(ft, *lanes[b], 2, sidc, real)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g[b], w)
    _check(ft, lanes[-1], 2, sidc, real)
