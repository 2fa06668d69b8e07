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

__all__ = ["I32MAX", "group_rank", "u32sum", "tlo", "thi"]

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
