"""What the laws of ``fill_holes`` share (tests/test_free_bits.py op by
op past the word's edges, tests/test_free_bits_jitted.py as one
program): no test lives here."""

import numpy as np

import jax
import jax.numpy as jnp

from timewarp_tpu.ops.numeric import I32MAX, fill_holes, free_bits

N = 257         # no multiple of a lane


def keep_mask(fill, K, seed):
    if fill == "all_free":
        return np.zeros((K, N), bool)
    if fill == "none_free":
        return np.ones((K, N), bool)
    rng = np.random.default_rng(seed)
    # every density, column by column: empty-ish to full-ish mailboxes
    return rng.random((K, N)) < rng.random((1, N))


def fill_by_loop(keep, staged, old, nothing):
    """Node by node, slot by slot: the ``h``-th hole takes row ``h``
    of every staged plane if the key plane staged something there."""
    K, n = keep.shape
    out = [o.copy() for o in old]
    for i in range(n):
        h = 0
        for k in range(K):
            if keep[k, i]:
                continue
            if staged[0][h, i] != nothing:
                for o, s in zip(out, staged):
                    o[k, i] = s[h, i]
            h += 1
    return out


def fill_eager(keep, staged, old):
    """``fill_holes`` one operation at a time."""
    return [jnp.stack(rows) for rows in fill_holes(
        free_bits(jnp.asarray(keep)), staged, old, I32MAX)]


#: one program a K, whatever the fill
fill_jitted = jax.jit(fill_eager)


def fill_holes_law(run, K, fill):
    """``run`` (``fill_eager`` or ``fill_jitted``) against the loop:
    three planes (the key and two riders) over columns that stage
    0 … K rows each: fewer than the holes (the holes past the staged
    count keep what they held, on every plane), as many, and more (the
    rows past the last hole go nowhere)."""
    keep = keep_mask(fill, K, seed=2000 + K)
    rng = np.random.default_rng(3000 + K)
    count = rng.integers(0, K + 1, N)
    count[:3] = (0, K, K // 2)
    key = rng.integers(0, 10**6, (K, N)).astype(np.int32)
    key[np.arange(K)[:, None] >= count[None, :]] = I32MAX
    i32 = lambda: rng.integers(-2**31, 2**31, (K, N)).astype(np.int32)
    staged, old = [key, i32(), i32()], [i32(), i32(), i32()]
    got = run(keep, staged, old)
    want = fill_by_loop(keep, staged, old, I32MAX)
    for p, (g, w) in enumerate(zip(got, want)):
        assert g.dtype == np.int32 and g.shape == (K, N)
        assert np.array_equal(g, w), (
            f"K={K} {fill} plane {p}: {np.argwhere(g != w)[:5].tolist()}")
    moved = np.minimum(count, (~keep).sum(axis=0)).sum()
    assert sum((g != o).sum() for g, o in zip(got, old)) <= 3 * moved
    if fill == "none_free":
        assert all(np.array_equal(g, o) for g, o in zip(got, old))
    if fill == "all_free":
        # no occupied slot below any hole: nothing moves a row
        assert np.array_equal(got[0], np.where(key != I32MAX, key, old[0]))
