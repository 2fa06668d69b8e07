"""Plain reference of steady rumor mongering laid over shards by nodes:
``gossip_steady_ref.Mongering`` (imported, not edited; nothing of the
program is imported and nothing the program made is taken) with the
deployment's one addition, written from its definition.

The deployment. The ``n`` nodes are divided over ``devices`` shards in
the order of their ids: node ``i`` lives on shard ``i // (n /
devices)``. A push from ``src`` to ``dst`` goes from ``src``'s shard to
``dst``'s; it is remote where the two differ. A round's bucket
``(s, d)`` is the number of that round's pushes from shard ``s`` to
shard ``d``: what a device would have to hold for one destination
device before anything is exchanged.

Who pushes in a round, and to whom, follows from the state before the
round as the scenario's definition has it (``gossip_steady_ref``'s
docstring): a node that held the rumor before the round and whose next
push is due advances its generator and draws the peer ``(i + 1 + |x|
mod (n - 1)) mod n``. A node that hears the rumor in this very round
first pushes ``think_us`` later, so it is not among them. The buckets
ride along the base class's ``history`` as ``devices ** 2`` further
columns a round; integer arithmetic, exact on every backend.
"""

import numpy as np

from reference import gossip_steady_ref
from reference.gossip_ref import LCG_A, LCG_C

_BASE_COLUMNS = 3             # ``gossip_steady_ref``'s own, a round


class Mongering(gossip_steady_ref.Mongering):
    """``gossip_steady_ref.Mongering`` that also keeps, round by round,
    how many pushes went from each shard to each shard."""

    def __init__(self, params: dict, origin: int, word_bits: int = 32):
        self.shards = int(params["devices"])
        n = int(params["n_nodes"])
        if n % self.shards:
            raise ValueError(f"{n} nodes do not divide over "
                             f"{self.shards} shards")
        self.n_local = n // self.shards
        super().__init__(params, origin, word_bits)
        self.history = np.zeros((0, _BASE_COLUMNS + self.shards ** 2),
                                np.int64)

    def _round(self, state, x):
        import jax.numpy as jnp
        hop, lcg, nxt = state[:3]
        r, i32, shards = x[0], jnp.int32, self.shards
        ids = jnp.arange(self.n, dtype=i32)
        push = (hop >= 0) & (nxt <= r)
        x1 = lcg * i32(LCG_A) + i32(LCG_C)
        dst = (ids + i32(1) + jnp.abs(x1) % i32(self.n - 1)) % i32(self.n)
        bucket = jnp.where(push, ids // i32(self.n_local) * i32(shards)
                           + dst // i32(self.n_local), i32(shards ** 2))
        pushes = jnp.zeros(shards ** 2, i32).at[bucket].add(
            i32(1), mode="drop")
        state, seen = super()._round(state, x)
        return state, jnp.concatenate([seen, pushes])

    def buckets(self, first: int, last: int) -> np.ndarray:
        """``[rounds, shards, shards]``: the pushes of rounds ``first``
        to ``last - 1`` of the run (0 is the origin's first push) by
        source and destination shard."""
        if last > self.steps:
            raise ValueError("the reference has not run that far")
        return self.history[first:last, _BASE_COLUMNS:].reshape(
            -1, self.shards, self.shards)

    def remote_pushes(self, first: int, last: int) -> int:
        """Pushes of those rounds whose destination's shard is not the
        sender's."""
        b = self.buckets(first, last)
        return int(b.sum() - np.trace(b, axis1=1, axis2=2).sum())

    def largest_bucket(self, first: int, last: int) -> int:
        """The most pushes from one shard to one shard (its own
        counted) in one of those rounds."""
        return int(self.buckets(first, last).max(initial=0))
