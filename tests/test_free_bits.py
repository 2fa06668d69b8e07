"""The law of the hole words: ``free_bits`` + ``nth_set_bit``
(ops/numeric.py) against the sorted free-slot table they replaced,
and ``fill_holes`` (PR 32: the same decision taken a node at a time,
on the node's own lanes) against a loop over every node's slots.

Until PR 30 ``tw.rebase`` built, for a commutative inbox, the
ascending list of every node's free rows with one ``[K, N]`` sort
(``lax.sort(where(keep, K, slots), dimension=0)``), and
``_insert_sorted`` read ``free_rows[rank, dst]`` from it. That table
lives on here as the plain reference (``free_rows_by_sort``): the
words and the bit select must give its entry for every column and
every rank, the ranks past the free count (→ K) included. The bit
select has no caller in the program since PR 32; it is the reference
tests/test_insert_slot_law.py holds the program's slots to.

``expand_lanes`` (PR 36) is ``fill_holes``' expand run along the lane
axis: a compacted ascending prefix spread over the lanes it names. Its
plain reference is the scatter it replaces. ``compress_lanes`` (PR 48)
is its mirror, the compress network run forwards: the lanes a mask
names put in front, in their order. Its plain reference is the sort it
replaces in ``_route_adaptive``, ``lax.sort(where(mask, ids, n))``.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from timewarp_tpu.ops.numeric import (I32MAX, compress_lanes, expand_lanes,
                                      fill_holes, free_bits, nth_set_bit)

from free_bits_laws import (N, fill_eager, fill_holes_law, fill_jitted,
                            keep_mask)

#: one word, its edges (31, 32, 33), two words, four (127), five (130)
KS = (1, 8, 24, 31, 32, 33, 64, 127, 130)


def free_rows_by_sort(keep, xp=np):
    """The table the parent built: ``[K, N]``, entry ``[r, i]`` the row
    of node ``i``'s ``r``-th free slot, K where it has ``r`` or fewer
    (``keep`` is ``[K, N]`` bool; ``xp=jnp`` for a traced one)."""
    K = keep.shape[0]
    slots = xp.arange(K, dtype=xp.int32)[:, None]
    return xp.sort(xp.where(keep, K, slots), axis=0)


@functools.cache
def _bit_select(K):
    """One jitted select a K, whatever the fill."""
    @jax.jit
    def f(keep, rank, dst):
        words = free_bits(keep)
        return nth_set_bit([w[dst] for w in words], rank, K)
    return f


def rows_by_bit_select(keep, rank, dst):
    """What ``_insert_sorted`` computes: the words of ``keep``, one 1D
    gather a word at ``dst``, the bit select at ``rank``."""
    return np.asarray(_bit_select(keep.shape[0])(keep, rank, dst))


@pytest.mark.parametrize("fill", ["all_free", "none_free", "random"])
@pytest.mark.parametrize("K", KS, ids="K{}".format)
def test_bit_select_equals_the_sorted_table(K, fill):
    keep = keep_mask(fill, K, seed=1000 + K)
    table = free_rows_by_sort(keep)
    words = np.asarray(free_bits(jnp.asarray(keep)))
    assert words.shape == (-(-K // 32), N) and words.dtype == np.uint32
    # the words are the mask, bit for bit, and nothing past row K
    bits = (words[:, None, :] >> np.arange(32, dtype=np.uint32)[None, :, None]) & 1
    assert np.array_equal(bits.reshape(-1, N)[:K].astype(bool), ~keep)
    assert not bits.reshape(-1, N)[K:].any()
    # every (rank, column), ranks 0 … K + 2
    ranks = np.arange(K + 3, dtype=np.int32)
    rank = np.repeat(ranks, N)
    dst = np.tile(np.arange(N, dtype=np.int32), K + 3)
    got = rows_by_bit_select(keep, rank, dst).reshape(K + 3, N)
    want = np.concatenate([table, np.full((3, N), K)], axis=0)
    assert got.dtype == np.int32
    assert np.array_equal(got, want), (
        f"K={K} {fill}: {np.argwhere(got != want)[:5].tolist()}")
    if fill == "none_free":
        assert (got == K).all()
    if fill == "all_free":
        assert np.array_equal(got[:K], np.broadcast_to(ranks[:K, None], (K, N)))


def test_ranks_far_past_the_word_give_none():
    """A hub's fan-in: ranks in the thousands at one destination."""
    keep = keep_mask("random", 24, seed=7)
    rank = np.array([24, 31, 32, 33, 1000, 2**20, 2**31 - 1], np.int32)
    dst = np.zeros_like(rank)
    assert (rows_by_bit_select(keep, rank, dst) == 24).all()


# ---------------------------------------------------------------------------
# fill_holes: staged rows 0, 1, 2, … into each node's holes, in order
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fill", ["all_free", "none_free", "random"])
@pytest.mark.parametrize("K", KS, ids="K{}".format)
def test_fill_holes_equals_the_loop(K, fill):
    """Past the word's edges one operation at a time: as one program
    the network of K rows takes XLA:CPU minutes to compile (K 127:
    140 s and more) and under a second to run. The jitted program at
    two, four and five words is tests/test_free_bits_jitted.py's."""
    fill_holes_law(fill_jitted if K <= 33 else fill_eager, K, fill)


def test_fill_holes_lowers_without_an_index():
    """Elementwise on the node's lanes: no gather, no scatter, no
    sort, whatever the word count."""
    for K in (24, 40):
        keep = jax.ShapeDtypeStruct((K, N), bool)
        plane = jax.ShapeDtypeStruct((K, N), np.int32)
        text = jax.jit(lambda keep, a, b: fill_holes(
            free_bits(keep), [list(a)], [list(b)], I32MAX)).lower(
                keep, plane, plane).as_text()
        for op in ("gather", "scatter", "sort", "dynamic_slice"):
            assert f"stablehlo.{op}" not in text, (K, op)
        assert "stablehlo.select" in text


# -- the lane-axis expansion -------------------------------------------------

def _targets(fill, n, seed):
    """The ascending targets of a compacted prefix, by case."""
    rng = np.random.default_rng(seed)
    if fill == "empty":
        return np.zeros(0, np.int32)
    if fill == "full":
        return np.arange(n, dtype=np.int32)
    if fill == "one-node":
        # every arrival at one node: one lane of rank 0
        return np.array([rng.integers(0, n)], np.int32)
    if fill == "last-lanes":
        return np.arange(n - n // 3, n, dtype=np.int32)
    if fill == "first-lanes":
        return np.arange(n // 3, dtype=np.int32)
    # one arrival a node on average: 1 - 1/e of the lanes named
    return np.unique(rng.integers(0, n, n)).astype(np.int32)


#: each field's own "nothing", and one program a width, whatever the fill
_NOTHING = (I32MAX, 0, -7)
_expand_jitted = jax.jit(lambda tg, c, f: expand_lanes(tg, c, f, _NOTHING))


@pytest.mark.parametrize("n", [64, 100, 257, 1024, 4096], ids="n{}".format)
@pytest.mark.parametrize("fill", ["empty", "full", "one-node", "last-lanes",
                                  "first-lanes", "random"])
def test_expand_lanes_equals_a_scatter(fill, n):
    """Lane ``j`` of the prefix lands at ``target[j]`` on every field,
    each field's own "nothing" everywhere else; what the arrays hold
    past the prefix is never read."""
    t = _targets(fill, n, seed=n)
    rng = np.random.default_rng(5000 + n)
    target = rng.integers(0, n, n).astype(np.int32)     # junk past count
    target[:t.size] = t
    fields = [rng.integers(-2**31, 2**31, n).astype(np.int32)
              for _ in range(3)]
    got = _expand_jitted(target, np.int32(t.size), fields)
    for g, x, e in zip(got, fields, _NOTHING):
        want = np.full(n, e, np.int32)
        want[t] = x[:t.size]
        assert g.dtype == np.int32 and np.array_equal(g, want), (
            f"{fill} n={n}: {np.argwhere(np.asarray(g) != want)[:5].tolist()}")


def test_expand_lanes_lowers_without_an_index():
    """``bit_length(n - 1)`` stages of shifts and selects: no gather,
    no scatter, no sort."""
    n = 1000
    lanes = jax.ShapeDtypeStruct((n,), np.int32)
    text = jax.jit(lambda tg, c, a, b: expand_lanes(
        tg, c, [a, b], (I32MAX, 0))).lower(
            lanes, jax.ShapeDtypeStruct((), np.int32), lanes,
            lanes).as_text()
    for op in ("gather", "scatter", "sort", "dynamic_slice",
               "dynamic_update_slice"):
        assert f"stablehlo.{op}" not in text, op
    assert "stablehlo.select" in text


# -- the lane-axis compression -------------------------------------------------

#: one lane, the network's first stages, a row of 128 less one, a rung
#: and one over it, two rungs and one, the observer ring's 2^16 + 1
COMPRESS_NS = (1, 2, 3, 127, 1024, 1025, 2049, 65537)
SHARES = ("none", "one-lane", "1%", "30%", "97%", "all")


def _live(share, shape, seed):
    """The mask of the live lanes, by case."""
    rng = np.random.default_rng(seed)
    if share == "none":
        return np.zeros(shape, bool)
    if share == "all":
        return np.ones(shape, bool)
    if share == "one-lane":
        m = np.zeros(shape, bool)
        m[..., rng.integers(0, shape[-1])] = True
        return m
    return rng.random(shape) < float(share[:-1]) / 100


@functools.cache
def _compress_jitted(n):
    """One program a width, whatever the share: the node ids and a
    field of words, each with its own "nothing"."""
    ids = jnp.arange(n, dtype=jnp.int32)
    return jax.jit(lambda m, v: compress_lanes(m, [ids, v], [n, -7]))


def _sender_sort(mask):
    """What ``_route_adaptive`` ran until PR 48, on the last axis."""
    n = mask.shape[-1]
    return np.asarray(jax.lax.sort(jnp.where(
        mask, jnp.arange(n, dtype=jnp.int32), jnp.int32(n))))


@pytest.mark.parametrize("n", COMPRESS_NS, ids="n{}".format)
@pytest.mark.parametrize("share", SHARES)
def test_compress_lanes_equals_the_sort(share, n):
    """The live ids ascending, then n: the sort's output word for
    word; and a second field rides with them, its own "nothing"
    behind the live count."""
    mask = _live(share, (n,), seed=n)
    v = np.random.default_rng(7000 + n).integers(
        -2**31, 2**31, n).astype(np.int32)
    ids, words = _compress_jitted(n)(mask, v)
    want = _sender_sort(mask)
    assert ids.dtype == np.int32 and words.dtype == np.int32
    assert np.array_equal(ids, want), (
        f"{share} n={n}: {np.argwhere(np.asarray(ids) != want)[:5].tolist()}")
    k = int(mask.sum())
    assert np.array_equal(words[:k], v[mask]) and (words[k:] == -7).all()


@pytest.mark.parametrize("n", COMPRESS_NS, ids="n{}".format)
def test_compress_lanes_under_vmap_over_eight_rows(n):
    """A fleet's worlds: every row its own mask, ``vmap`` of the call
    and the call on the batch itself alike."""
    mask = np.stack([_live(share, (n,), seed=100 * n + r)
                     for r, share in enumerate(SHARES + ("30%", "1%"))])
    ids = jnp.arange(n, dtype=jnp.int32)
    want = _sender_sort(mask)
    by_vmap = jax.jit(jax.vmap(
        lambda m: compress_lanes(m, [ids], [n])[0]))(mask)
    whole = jax.jit(lambda m: compress_lanes(
        m, [jnp.broadcast_to(ids, m.shape)], [n])[0])(mask)
    assert np.array_equal(by_vmap, want) and np.array_equal(whole, want)


@pytest.mark.parametrize("n", COMPRESS_NS, ids="n{}".format)
@pytest.mark.parametrize("share", ["one-lane", "30%", "all"])
def test_expand_lanes_undoes_compress_lanes(share, n):
    """The compacted prefix names its own targets: expanded over them
    every live lane holds its word again, every other "nothing"."""
    mask = _live(share, (n,), seed=n + 1)
    v = np.random.default_rng(9000 + n).integers(
        -2**31, 2**31, n).astype(np.int32)
    ids, words = _compress_jitted(n)(mask, v)
    back, = jax.jit(lambda t, c, x: expand_lanes(t, c, [x], [I32MAX]))(
        ids, np.int32(mask.sum()), words)
    assert np.array_equal(back, np.where(mask, v, I32MAX))


def test_compress_lanes_lowers_without_an_index():
    """One prefix sum and ``bit_length(n - 1)`` stages of shifts and
    selects: no gather, no scatter, no sort."""
    n = 1000
    text = jax.jit(lambda m, a, b: compress_lanes(
        m, [a, b], (n, 0))).lower(
            jax.ShapeDtypeStruct((n,), bool),
            jax.ShapeDtypeStruct((n,), np.int32),
            jax.ShapeDtypeStruct((n,), np.int32)).as_text()
    for op in ("gather", "scatter", "sort", "dynamic_slice",
               "dynamic_update_slice"):
        assert f"stablehlo.{op}" not in text, op
    assert "stablehlo.select" in text
