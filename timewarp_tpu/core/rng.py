"""Counter-based RNG: elementwise threefry2x32 over uint32 words.

The reference threads one sequential ``StdGen`` through the emulated
network (seeded ``mkStdGen 0``, examples/token-ring/Main.hs:60, 82-85);
the TPU build keys every draw by *what it is for* — ``(node, time)``
for a firing, ``(src, dst, time, slot)`` for a link sample — so any
interpreter, batched or sequential, sharded or not, derives
bit-identical streams (SURVEY.md §5.2).

Round-2 note: round 1 used ``jax.random.fold_in`` chains, which
materialize a ``[batch, 2]`` key array per draw — on TPU that minor
dim of 2 pads to 128 lanes and the chain becomes multi-ms per
superstep. This module is the redesign: Threefry-2x32 written as pure
elementwise uint32 ops that broadcast in whatever layout the caller
already has ([N], [E, N], [S] …), never materializing key structures.
Integer-only ⇒ bit-exact across CPU/TPU.
"""

from __future__ import annotations

from typing import Tuple

from ..utils import jaxconfig  # noqa: F401  (int64 time words need x64)

import jax.numpy as jnp

__all__ = [
    "threefry2x32", "seed_words", "fire_bits", "msg_bits", "split_bits",
    "uniform_int", "bernoulli", "normal_f32",
]

_PARITY = 0x1BD11BDA  # threefry key-schedule parity constant
_GOLD = 0x9E3779B9    # golden ratio — domain separation for seeding

# Domain tags: distinct streams for fires vs link samples vs user splits.
_FIRE_TAG = 0xF14EF14E
_MSG_TAG = 0x4D534721

_ROT_A = (13, 15, 26, 6)
_ROT_B = (17, 29, 16, 24)


def _rotl(x, r: int):
    r = jnp.uint32(r)
    return (x << r) | (x >> (jnp.uint32(32) - r))


def threefry2x32(k0, k1, c0, c1) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Standard 20-round Threefry-2x32 block cipher: key (k0,k1),
    counter (c0,c1) -> two uint32 words. All args broadcast; pure
    elementwise integer ops (VPU-friendly in any layout)."""
    k0 = jnp.asarray(k0).astype(jnp.uint32)
    k1 = jnp.asarray(k1).astype(jnp.uint32)
    x0 = jnp.asarray(c0).astype(jnp.uint32) + k0
    x1 = jnp.asarray(c1).astype(jnp.uint32) + k1
    ks = (k0, k1, k0 ^ k1 ^ jnp.uint32(_PARITY))
    for g in range(5):
        rots = _ROT_A if g % 2 == 0 else _ROT_B
        for r in rots:
            x0 = x0 + x1
            x1 = _rotl(x1, r) ^ x0
        x0 = x0 + ks[(g + 1) % 3]
        x1 = x1 + ks[(g + 2) % 3] + jnp.uint32(g + 1)
    return x0, x1


def seed_words(seed: int) -> Tuple[int, int]:
    """Host-side: expand a Python int seed into two uint32 words:
    ``threefry2x32(seed_lo, seed_hi ^ _GOLD, 0, 1)`` in Python integers.
    Not through the ``jnp`` block above: called eagerly, each add, shift
    and xor of it is a device program, some 190 launches a seed and
    1500 for a fleet of eight in ``rebind_identity`` (PERF.md, PR 27)."""
    m = 0xFFFFFFFF
    k0, k1 = seed & m, ((seed >> 32) & m) ^ _GOLD
    x0, x1 = k0, (1 + k1) & m
    ks = (k0, k1, k0 ^ k1 ^ _PARITY)
    for g in range(5):
        for r in (_ROT_A if g % 2 == 0 else _ROT_B):
            x0 = (x0 + x1) & m
            x1 = (((x1 << r) | (x1 >> (32 - r))) & m) ^ x0
        x0 = (x0 + ks[(g + 1) % 3]) & m
        x1 = (x1 + ks[(g + 2) % 3] + g + 1) & m
    return x0, x1


def _t_words(t):
    t = jnp.asarray(t, jnp.int64)
    lo = (t & jnp.int64(0xFFFFFFFF)).astype(jnp.uint32)
    hi = ((t >> jnp.int64(32)) & jnp.int64(0xFFFFFFFF)).astype(jnp.uint32)
    return lo, hi


def fire_bits(s0, s1, node, t) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Entropy for one node's firing at virtual time ``t``.

    ≙ the per-event randomness of the reference's threaded StdGen, made
    order-independent. Broadcasting: ``node`` may be [N] while ``t`` is
    scalar.
    """
    tlo, thi = _t_words(t)
    a0, a1 = threefry2x32(jnp.uint32(s0) ^ jnp.uint32(_FIRE_TAG),
                          jnp.uint32(s1), node, tlo)
    return threefry2x32(a0, a1, thi, jnp.uint32(0))


def msg_bits(s0, s1, src, dst, t, slot) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Entropy for the link sample of one message ``src -> dst`` emitted
    at time ``t`` from outbox slot ``slot`` (≙ the seeded ``Delays``
    draw, examples/token-ring/Main.hs:73-77)."""
    tlo, thi = _t_words(t)
    a0, a1 = threefry2x32(jnp.uint32(s0) ^ jnp.uint32(_MSG_TAG),
                          jnp.uint32(s1), src, dst)
    b0, b1 = threefry2x32(a0, a1, tlo, thi)
    return threefry2x32(b0, b1, slot, jnp.uint32(0))


def split_bits(b0, b1, tag: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Derive an independent substream from an entropy pair (≙
    ``jax.random.split``); ``tag`` must be a static int."""
    return threefry2x32(b0, b1, jnp.uint32(tag), jnp.uint32(1))


def uniform_int(bits, lo: int, hi: int):
    """Uniform integer in [lo, hi] from one uint32 word (modulo scheme:
    deterministic and identical everywhere; the ≤2^-32-scale modulo
    bias is irrelevant for link-delay sampling)."""
    span = jnp.uint32(hi - lo + 1)
    return jnp.asarray(lo, jnp.int64) + (bits % span).astype(jnp.int64)


def bernoulli(bits, p: float):
    """True with (static) probability ``p`` from one uint32 word —
    integer threshold compare, bit-exact on every backend."""
    if p <= 0.0:
        return jnp.zeros(jnp.shape(bits), bool)
    thr = int(p * 4294967296.0)
    if thr >= 1 << 32:
        return jnp.ones(jnp.shape(bits), bool)
    return bits < jnp.uint32(thr)


def normal_f32(b0, b1):
    """Standard normal via Box-Muller from two uint32 words (float32).

    Transcendentals differ across backends, and by more than an ulp
    (a v5e's float32 ``log`` is up to 3.7e-4 relative from the host
    CPU's, docs/engines.md "The parity regime") — integer models stay
    bit-exact everywhere; float models are exact within one backend
    only (the documented LogNormalDelay caveat, net/delays.py).
    """
    # 24-bit mantissa uniforms in (0, 1). The draw goes uint32 ->
    # int32 -> float32: Mosaic has no uint32 -> float32 cast
    # (docs/pallas_kernels.md), so a kernel that draws delays can
    # call this as it stands; a value below 2^24 is exact either way,
    # so the bits are the same on every path
    def u24(b):
        return (b >> jnp.uint32(8)).astype(jnp.int32).astype(jnp.float32)
    u1 = u24(b0) * jnp.float32(2 ** -24) + jnp.float32(2 ** -25)
    u2 = u24(b1) * jnp.float32(2 ** -24)
    r = jnp.sqrt(jnp.float32(-2.0) * jnp.log(u1))
    return r * jnp.cos(jnp.float32(2.0 * 3.141592653589793) * u2)
