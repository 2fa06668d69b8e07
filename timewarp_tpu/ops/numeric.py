"""Engine-generic integer primitives shared by every batched engine.

These are the "hot ops" of the TPU build in their XLA-native form —
profiled and shaped for the VPU (docs/engines.md "Measured on a v5e"):
pure elementwise/scan/sort building blocks, no gathers or scatters
(and one small product on the matrix unit: ``compress_lanes``' prefix
count).
SURVEY.md §2 records the design stance: XLA-compiled JAX *is* this
framework's native layer; Pallas would only enter if a fused op beat
the compiler, and at 10x the performance target none currently does.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["I32MAX", "group_rank", "free_bits", "nth_set_bit",
           "fill_holes", "expand_lanes", "compress_lanes", "u32sum", "tlo",
           "thi"]

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
    replaced). The engine decides the same a node at a time since
    PR 32 (:func:`fill_holes`); this form, a message at a time, is
    what tests/test_insert_law.py holds that one's slots to."""
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


def _shift_up(words, s: int):
    """The bit set ``words`` (a list of uint32 lanes, word ``j`` holds
    positions ``32 j … 32 j + 31``) with every position raised by the
    static ``s``; what passes the last word is lost."""
    q, r = divmod(s, 32)
    out = []
    for j in range(len(words)):
        v = jnp.zeros_like(words[0])
        if j - q >= 0:
            v = words[j - q] << jnp.uint32(r)
        if r and j - q - 1 >= 0:
            v = v | (words[j - q - 1] >> jnp.uint32(32 - r))
        out.append(v)
    return out


def _shift_down(words, s: int):
    """:func:`_shift_up`'s inverse: every position lowered by ``s``."""
    q, r = divmod(s, 32)
    nw = len(words)
    out = []
    for j in range(nw):
        v = jnp.zeros_like(words[0])
        if j + q < nw:
            v = words[j + q] >> jnp.uint32(r)
        if r and j + q + 1 < nw:
            v = v | (words[j + q + 1] << jnp.uint32(32 - r))
        out.append(v)
    return out


def _bit(words, k: int) -> jax.Array:
    """Whether position ``k`` of the bit set ``words`` is set."""
    return ((words[k // 32] >> jnp.uint32(k % 32)) & jnp.uint32(1)) != 0


def fill_holes(words, staged, old, nothing):
    """Move each lane's staged rows 0, 1, 2, … into its holes in
    ascending order. ``staged`` and ``old`` are sequences of planes, a
    plane a sequence of K rows (arrays of the words' shape). For every
    plane ``p``, row ``k`` of the result is ``staged[p][h]`` where row
    ``k`` is a hole (bit ``k % 32`` of ``words[k // 32]``, as
    ``free_bits`` writes them), ``h`` holes lie below it and row ``h``
    was staged (``staged[0][h] != nothing``: plane 0 is the key);
    ``old[p][k]`` everywhere else. Returns the planes as lists of rows.

    This is what ``nth_set_bit`` decides a message at a time, decided
    a node at a time and with no index: the *expand* network of
    Hacker's Delight 7-5 with rows in the place of bits. The move
    masks come from the hole words by parallel-suffix steps on uint32
    lanes (``ceil(K/32)`` words as one long bit set); stage ``j`` then
    raises by ``2^j`` the rows its mask names, the largest ``j``
    first, so a row travels the distance "occupied slots below my
    hole" one binary digit at a time and no two rows ever meet:
    ``bit_length(K - 1)`` selects a slot, not K. A row is an array of
    its own, so raising one is naming another: nothing moves along an
    array's axis (tests/test_free_bits.py holds it to a loop)."""
    K = len(staged[0])
    words = list(words)
    # the masks, least shift first (compress order): `mk` marks the
    # positions with an odd count of occupied slots below, of those
    # still to be halved
    m = words
    mk = _shift_up([~w for w in words], 1)
    moves = []
    for i in range((K - 1).bit_length()):
        mp = mk
        s = 1
        while s < K:
            mp = [a ^ b for a, b in zip(mp, _shift_up(mp, s))]
            s *= 2
        mv = [a & b for a, b in zip(mp, m)]
        moves.append(mv)
        m = [(a ^ b) | c for a, b, c in
             zip(m, mv, _shift_down(mv, 1 << i))]
        mk = [a & ~b for a, b in zip(mk, mp)]
    planes = [list(rows) for rows in staged]
    for i in reversed(range(len(moves))):
        s = 1 << i
        # no mask names a row under its own shift: rows < s keep
        up = [_bit(moves[i], k) for k in range(s, K)]
        planes = [rows[:s] + [jnp.where(u, rows[k - s], rows[k])
                              for k, u in zip(range(s, K), up)]
                  for rows in planes]
    got = [_bit(words, k) & (planes[0][k] != nothing) for k in range(K)]
    return [[jnp.where(g, x, o) for g, x, o in zip(got, rows, olds)]
            for rows, olds in zip(planes, old)]


#: :func:`expand_lanes`' mark of an empty lane: the sign bit alone, so
#: no stage's shift bit is set in it and an empty lane never moves
_NO_LANE = np.int32(-2**31)


def expand_lanes(target, count, fields, nothing):
    """Spread a compacted, ascending prefix over the lanes it names:
    lane ``j < count`` of every field goes to lane ``target[j]`` of
    the result, a dense array of ``n = target.shape[0]`` lanes a
    field, ``nothing[f]`` wherever no lane went. ``target`` must
    ascend strictly over the prefix, inside ``[0, n)`` (so
    ``target[j] >= j``); what it holds past ``count`` is not read.
    ``fields`` is a sequence of ``[n]`` arrays, ``nothing`` their fill
    values. Returns the fields as a list.

    :func:`fill_holes`' expand (Hacker's Delight 7-5) along the lane
    axis: a monotone expansion needs no index. Every lane carries its
    remaining displacement ``target[j] - j``; stage ``i`` raises by
    ``2^i`` the lanes whose displacement has bit ``i`` set, the
    largest ``i`` first, so after the stages down to ``i`` a lane
    stands at ``target - (displacement mod 2^i)``: these are the
    states of the compress network run backwards, in which no two
    lanes ever meet. A stage is one shift of each array by a static
    distance and two selects: ``bit_length(n - 1)`` elementwise
    passes, no gather, no scatter, no sort (tests/test_free_bits.py
    holds it to a scatter)."""
    n = target.shape[0]
    lane = jnp.arange(n, dtype=jnp.int32)
    disp = jnp.where(lane < count, target.astype(jnp.int32) - lane,
                     _NO_LANE)
    fields = list(fields)

    def raised(x, s, fill):
        return jnp.concatenate(
            [jnp.full((s,), fill, x.dtype), x[:n - s]])
    for i in reversed(range((n - 1).bit_length())):
        s = 1 << i
        below = raised(disp, s, _NO_LANE)
        comes = (below & jnp.int32(s)) != 0
        stays = (disp & jnp.int32(s)) == 0
        fields = [jnp.where(comes, raised(x, s, 0), x) for x in fields]
        disp = jnp.where(comes, below, jnp.where(stays, disp, _NO_LANE))
    return [jnp.where(disp == _NO_LANE, jnp.asarray(e, x.dtype), x)
            for x, e in zip(fields, nothing)]


#: :func:`_live_below`'s row: the lanes of one vector register
_ROW = 128


def _live_below(mask):
    """Each lane's count of set lanes below it along the last axis
    (int32, the exclusive prefix sum of ``mask``), in two levels:
    inside a row of 128 lanes one product with the strict triangle of
    ones (int8 operands, int32 sums: exact, and the matrix unit's
    work where seven shifted adds would be the vector unit's), and
    over the rows a prefix of their sums, ``n / 128`` entries. On a
    v5e 35 us at 2^20 lanes where ``lax.cumsum``'s ``reduce-window``
    takes 216 (and 20-35 s of the compiler's time for 0.5), the seven
    adds 56 (profiling/sender_compact_micro_r09.py less its floor,
    PR 48; docs/engines.md "The sender compaction, by its form")."""
    n = mask.shape[-1]
    rows = -(-n // _ROW)
    x = mask.astype(jnp.int8)
    if rows * _ROW != n:
        x = jnp.concatenate(
            [x, jnp.zeros(x.shape[:-1] + (rows * _ROW - n,), x.dtype)],
            axis=-1)
    x = x.reshape(x.shape[:-1] + (rows, _ROW))
    k = jnp.arange(_ROW, dtype=jnp.int32)
    in_row = jnp.matmul(x, (k[:, None] < k[None, :]).astype(jnp.int8),
                        preferred_element_type=jnp.int32)
    last = partial(jax.lax.index_in_dim, index=_ROW - 1, axis=-1,
                   keepdims=False)
    row = last(in_row) + last(x)
    below = in_row + (jnp.cumsum(row, axis=-1) - row)[..., None]
    return below.reshape(below.shape[:-2] + (rows * _ROW,))[..., :n]


def _compress(disp, fields, nothing):
    """:func:`compress_lanes`' network on the lanes' displacements
    ``disp`` (int32 ``[..., n]``: how far down each live lane goes,
    ``_NO_LANE`` on a dead one)."""
    n = disp.shape[-1]
    fields = list(fields)

    def lowered(x, s, fill):
        return jnp.concatenate(
            [x[..., s:], jnp.full(x.shape[:-1] + (s,), fill, x.dtype)],
            axis=-1)
    for i in range((n - 1).bit_length()):
        s = 1 << i
        above = lowered(disp, s, _NO_LANE)
        comes = (above & jnp.int32(s)) != 0
        stays = (disp & jnp.int32(s)) == 0
        fields = [jnp.where(comes, lowered(x, s, 0), x) for x in fields]
        disp = jnp.where(comes, above, jnp.where(stays, disp, _NO_LANE))
    return [jnp.where(disp == _NO_LANE, jnp.asarray(e, x.dtype), x)
            for x, e in zip(fields, nothing)]


@jax.jit
def compress_lanes(mask, fields, nothing):
    """Put the lanes ``mask`` names in front, in their order: lane
    ``j`` of every field of the result is the field's ``j``-th live
    lane, ``nothing[f]`` from the live count on. ``mask`` is bool
    ``[..., n]``, ``fields`` a sequence of arrays of its shape,
    ``nothing`` their fill values; the lanes run along the last axis
    and every leading axis is a batch. Returns the fields as a list.
    ``compress_lanes(m, [ids], [n])[0]`` is
    ``lax.sort(where(m, ids, n))`` for ascending ``ids < n``, word for
    word.

    :func:`expand_lanes`' mirror, the *compress* network of Hacker's
    Delight 7-5 along the lane axis: every live lane carries its
    displacement ``lane - (live lanes below it)``, the dead lanes
    below it, and stage ``i`` lowers by ``2^i`` the lanes whose
    displacement has bit ``i`` set, the smallest ``i`` first (the
    order in which :func:`fill_holes` builds its masks), so after the
    stages up to ``i`` a lane stands at ``lane - (displacement mod
    2^(i+1))``: two live lanes ``a < b`` part by at least
    ``1 + (d_b - d_a)`` and ``d mod 2^k`` grows no faster than ``d``,
    so no two lanes ever meet. A stage is one shift of each array by
    a static distance and two selects: one prefix count
    (:func:`_live_below`) and ``bit_length(n - 1)`` elementwise
    passes, no gather, no scatter, no sort (tests/test_free_bits.py
    holds it to the sort).

    Jitted, so that a program's tracing finds it traced: a ladder
    driver built under ``vmap`` and ``shard_map`` spends more of
    XLA:CPU's time tracing these stages than compiling them (tier-1's
    clock, PR 48); the compiler inlines the call, the chip's program
    is the same."""
    n = mask.shape[-1]
    lane = jnp.arange(n, dtype=jnp.int32)
    return _compress(jnp.where(mask, lane - _live_below(mask), _NO_LANE),
                     fields, nothing)


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
