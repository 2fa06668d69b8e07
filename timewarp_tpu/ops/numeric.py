"""Engine-generic integer primitives shared by every batched engine.

These are the "hot ops" of the TPU build in their XLA-native form —
profiled and shaped for the VPU (docs/engines.md "Measured on a v5e"):
pure elementwise/scan/sort building blocks, no gathers or scatters.
SURVEY.md §2 records the design stance: XLA-compiled JAX *is* this
framework's native layer; Pallas would only enter if a fused op beat
the compiler, and at 10x the performance target none currently does.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["I32MAX", "group_rank", "free_bits", "nth_set_bit", "u32sum",
           "tlo", "thi"]

I32MAX = np.int32(2**31 - 1)


def group_rank(sorted_keys: jax.Array) -> jax.Array:
    """Rank of each element within its run of equal keys (keys must be
    sorted ascending): ``iota - cummax(run-start indices)``.

    Replaces ``searchsorted(keys, keys, 'left')`` in the routing path —
    on TPU searchsorted lowers to ~log2(S) chained gather rounds
    (~1 ms each at 131k elements, docs/engines.md "Measured on a v5e")
    while the cummax scan is elementwise-cheap. Uses the ``lax.cummax``
    primitive: the hand-rolled ``associative_scan(maximum, …)`` tree it
    replaces wedged the TPU compile service for minutes-to-forever at
    S ≥ ~4M (slice/concat-heavy recursive lowering), while the
    primitive compiles in seconds and runs ~0.1 s at 16M."""
    S = sorted_keys.shape[0]
    iota = jnp.arange(S, dtype=jnp.int32)
    boundary = jnp.concatenate([
        jnp.ones((1,), bool), sorted_keys[1:] != sorted_keys[:-1]])
    first = jax.lax.cummax(jnp.where(boundary, iota, 0))
    return iota - first


def free_bits(keep: jax.Array) -> jax.Array:
    """A ``[K, N]`` occupancy mask as ``[ceil(K/32), N]`` uint32 words
    of its *holes*: bit ``k % 32`` of word ``k // 32`` is set iff row
    ``k`` is free (``~keep[k]``). Elementwise plus a reduce along K
    (the bits of a word are distinct, so the sum is their OR); it
    fuses with whatever computed ``keep``. The rows past K in the last
    word read as occupied."""
    K = keep.shape[0]
    bit = jnp.uint32(1) << (jnp.arange(K, dtype=jnp.uint32) % 32)
    holes = jnp.where(keep, jnp.uint32(0),
                      bit.reshape((K,) + (1,) * (keep.ndim - 1)))
    return jnp.stack([jnp.sum(holes[k:k + 32], axis=0, dtype=jnp.uint32)
                      for k in range(0, K, 32)])


def nth_set_bit(words, rank: jax.Array, none: int) -> jax.Array:
    """Position of the ``rank``-th set bit (0-based, ascending from bit
    0 of ``words[0]``; word ``j`` holds positions ``32 j … 32 j + 31``)
    of each lane's bit set, ``none`` where the set holds ``rank`` bits
    or fewer. ``words`` is a sequence of uint32 arrays of ``rank``'s
    shape. A rank over a mask needs no sort: the word is found by
    running popcounts, the bit by five popcount halvings — elementwise
    throughout (tests/test_free_bits.py holds it to the sorted table it
    replaced)."""
    rank = rank.astype(jnp.int32)
    word = jnp.zeros_like(words[0])
    pos = jnp.full(rank.shape, none, jnp.int32)
    found = jnp.zeros(rank.shape, bool)
    for j, w in enumerate(words):
        c = jax.lax.population_count(w).astype(jnp.int32)
        here = ~found & (rank < c)
        word = jnp.where(here, w, word)
        pos = jnp.where(here, jnp.int32(32 * j), pos)
        found = found | here
        rank = jnp.where(found, rank, rank - c)
    # binary search inside the word: is the bit in the low half of the
    # window? If not, drop that half and the bits it held
    for width in (16, 8, 4, 2, 1):
        c = jax.lax.population_count(
            word & jnp.uint32((1 << width) - 1)).astype(jnp.int32)
        up = found & (rank >= c)
        word = jnp.where(up, word >> jnp.uint32(width), word)
        rank = jnp.where(up, rank - c, rank)
        pos = jnp.where(up, pos + jnp.int32(width), pos)
    return pos


def u32sum(x: jax.Array) -> jax.Array:
    """Wrapping uint32 sum — the order-independent digest reduction
    (commutative, so cross-device ``psum`` is exact)."""
    return jnp.sum(x.astype(jnp.uint32), dtype=jnp.uint32)


def tlo(t: jax.Array) -> jax.Array:
    """Low 32 bits of an int64 µs timestamp (digest word)."""
    return (t & jnp.int64(0xFFFFFFFF)).astype(jnp.uint32)


def thi(t: jax.Array) -> jax.Array:
    """High 32 bits of an int64 µs timestamp (digest word)."""
    return ((t >> jnp.int64(32)) & jnp.int64(0xFFFFFFFF)).astype(jnp.uint32)
