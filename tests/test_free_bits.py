"""The law of the hole words: ``free_bits`` + ``nth_set_bit``
(ops/numeric.py) against the sorted free-slot table they replaced.

Until PR 30 ``tw.rebase`` built, for a commutative inbox, the
ascending list of every node's free rows with one ``[K, N]`` sort
(``lax.sort(where(keep, K, slots), dimension=0)``), and
``_insert_sorted`` read ``free_rows[rank, dst]`` from it. That table
lives on here as the plain reference (``free_rows_by_sort``, also what
tests/test_insert_law.py replays a run against): the words and the bit
select must give its entry for every column and every rank, the ranks
past the free count (→ K) included.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from timewarp_tpu.ops.numeric import free_bits, nth_set_bit

#: one word, its edges (31, 32, 33), two words, four (127), five (130)
KS = (1, 8, 24, 31, 32, 33, 64, 127, 130)
N = 257         # no multiple of a lane


def free_rows_by_sort(keep, xp=np):
    """The table the parent built: ``[K, N]``, entry ``[r, i]`` the row
    of node ``i``'s ``r``-th free slot, K where it has ``r`` or fewer
    (``keep`` is ``[K, N]`` bool; ``xp=jnp`` for a traced one)."""
    K = keep.shape[0]
    slots = xp.arange(K, dtype=xp.int32)[:, None]
    return xp.sort(xp.where(keep, K, slots), axis=0)


def rows_by_bit_select(keep, rank, dst):
    """What ``_insert_sorted`` computes: the words of ``keep``, one 1D
    gather a word at ``dst``, the bit select at ``rank``."""
    K = keep.shape[0]

    @jax.jit
    def f(keep, rank, dst):
        words = free_bits(keep)
        return nth_set_bit([w[dst] for w in words], rank, K)
    return np.asarray(f(keep, rank, dst))


def _keep(fill, K, seed):
    if fill == "all_free":
        return np.zeros((K, N), bool)
    if fill == "none_free":
        return np.ones((K, N), bool)
    rng = np.random.default_rng(seed)
    # every density, column by column: empty-ish to full-ish mailboxes
    return rng.random((K, N)) < rng.random((1, N))


@pytest.mark.parametrize("fill", ["all_free", "none_free", "random"])
@pytest.mark.parametrize("K", KS, ids="K{}".format)
def test_bit_select_equals_the_sorted_table(K, fill):
    keep = _keep(fill, K, seed=1000 + K)
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
    keep = _keep("random", 24, seed=7)
    rank = np.array([24, 31, 32, 33, 1000, 2**20, 2**31 - 1], np.int32)
    dst = np.zeros_like(rank)
    assert (rows_by_bit_select(keep, rank, dst) == 24).all()
