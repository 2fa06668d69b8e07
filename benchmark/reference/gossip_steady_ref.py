"""Plain reference of steady rumor mongering, written from the
scenario's definition: round-paced, with no mailbox, no slots, no sort
and no cap. Nothing of the program is imported and nothing the program
made is taken; the Threefry block, the seed's expansion and the
generator's constants are ``gossip_ref``'s.

The scenario. Time runs in rounds of the link's quantum. Every node
keeps a linear congruential generator seeded from its id (state ``(i *
2654435761) mod (2^31 - 1) + 1``, advanced ``x * A + C`` in wrapping
int32). The origin holds the rumor at hop 0 and first pushes at
``bootstrap_us``; a node that hears the rumor for the first time
adopts the least hop count among the pushes that reach it in that
round, and first pushes ``think_us`` later. From then on it pushes
once every ``gossip_interval_us``, for ever: it advances its generator,
draws the peer ``(i + 1 + |x| mod (n - 1)) mod n`` and sends it its own
hop count plus one. A push from ``src`` to ``dst`` made at time ``t``
(outbox slot 0) is in flight ``lo + word mod (hi - lo + 1)``
microseconds, at least 1, rounded up to the quantum; ``word`` is the
first word of three chained Threefry-2x32 blocks keyed by ``(seed,
src, dst, t, slot)``.

State: per node ``hop`` (-1 before the rumor came), the generator and
the round of the next push; the pushes in flight as a ring of due
rounds holding, per ``(due round, destination)``, how many are in
flight and the least hop among them. A round delivers the bucket that
is due, lets every node whose time has come push, and adds each push
to the bucket of its due round. A run also keeps, round by round, the
deliveries, the nodes infected, and the largest number of pushes in
flight to one node: what a mailbox would have to hold.

All of it is integer arithmetic and exact on every backend, so it is
plain ``jax.numpy`` (``.at[].add``, ``.at[].min``, one ``lax.scan`` over
the rounds) and runs where the program runs: in numpy the Threefry
blocks alone take a third of a second a round at 2^20 nodes. Time goes
in as two 32-bit words made on the host, so nothing needs 64-bit mode.
``word_bits=16`` cuts the link's word to its low 16 bits, the precision
below its 32: the control of the comparison.
"""

from functools import partial

import numpy as np

from reference import gossip_ref
from reference.gossip_ref import LCG_A, LCG_C, seed_words, threefry2x32

NO_HOP = 2**31 - 1            # the least hop of an empty bucket
_NO_PUSH = 2**31 - 1          # the next push of a node without the rumor


class Mongering:
    """One configuration's steady mongering from one origin; ``run_to``
    advances it and returns the facts a comparison needs."""

    def __init__(self, params: dict, origin: int, word_bits: int = 32):
        import jax
        import jax.numpy as jnp
        link = params["link"]
        self.n = n = int(params["n_nodes"])
        self.round_us = q = int(link["quantum_us"])
        timers = {k: int(params[k]) for k in (
            "think_us", "gossip_interval_us", "bootstrap_us")}
        if int(params["fanout"]) != 1 or not params["steady"] \
                or link["model"] != "uniform" \
                or any(v % q or v < q for v in timers.values()):
            raise ValueError("steady mongering is round-paced: one peer a "
                             "push, a uniform link, timers on the "
                             "quantum's grid")
        self.think = timers["think_us"] // q
        self.interval = timers["gossip_interval_us"] // q
        self.first_round = timers["bootstrap_us"] // q
        self.lo, self.hi = int(link["lo_us"]), int(link["hi_us"])
        # the longest flight in rounds: the ring holds that many buckets
        self.ring = -(-max(self.hi, 1) // q)
        self.word_mask = (1 << word_bits) - 1
        s0, s1 = seed_words(int(params["engine_seed"]))
        self.key = (s0 ^ gossip_ref._MSG_TAG, s1)

        ids = np.arange(n, dtype=np.int64)
        hop = np.full(n, -1, np.int32)
        nxt = np.full(n, _NO_PUSH, np.int32)
        hop[origin], nxt[origin] = 0, self.first_round
        self.state = (
            jnp.asarray(hop),
            jnp.asarray(((ids * 2654435761) % (2**31 - 1) + 1)
                        .astype(np.int32)),
            jnp.asarray(nxt),
            jnp.zeros((self.ring, n), jnp.int32),
            jnp.full((self.ring, n), NO_HOP, jnp.int32))
        # one program a number of rounds (jit keys on the shapes)
        self._scan = jax.jit(partial(jax.lax.scan, self._round))
        self.steps = 0
        self.delivered = 0
        #: round by round: deliveries, nodes infected after the round,
        #: the most pushes in flight to one node after the round
        self.history = np.zeros((0, 3), np.int64)

    # -- one round --------------------------------------------------------

    def _round(self, state, x):
        import jax.numpy as jnp
        hop, lcg, nxt, count, least = state
        r, t_lo, t_hi = x
        n, ring = self.n, self.ring
        i32, u32 = jnp.int32, jnp.uint32
        ids = jnp.arange(n, dtype=i32)

        # deliver the bucket that is due: a node without the rumor
        # adopts the least hop and arms its first push
        slot = r % ring
        due_count, due_least = count[slot], least[slot]
        new = (hop < 0) & (due_count > 0)
        hop = jnp.where(new, due_least, hop)
        nxt = jnp.where(new, r + i32(self.think), nxt)
        count = count.at[slot].set(0)
        least = least.at[slot].set(NO_HOP)

        # every node whose time has come pushes to one peer
        push = (hop >= 0) & (nxt <= r)
        x1 = lcg * i32(LCG_A) + i32(LCG_C)
        dst = (ids + i32(1) + jnp.abs(x1) % i32(n - 1)) % i32(n)
        lcg = jnp.where(push, x1, lcg)
        nxt = jnp.where(push, r + i32(self.interval), nxt)

        a0, a1 = threefry2x32(u32(self.key[0]), u32(self.key[1]),
                              ids.astype(u32), dst.astype(u32))
        b0, b1 = threefry2x32(a0, a1, t_lo, t_hi)
        word, _ = threefry2x32(b0, b1, u32(0), u32(0))
        word = word & u32(self.word_mask)
        flight = i32(self.lo) + (word % u32(self.hi - self.lo + 1)).astype(i32)
        rounds = (jnp.maximum(flight, 1) + i32(self.round_us - 1)) \
            // i32(self.round_us)
        flat = jnp.where(push, (r + rounds) % ring * i32(n) + dst,
                         i32(ring * n))
        count = count.reshape(-1).at[flat].add(
            i32(1), mode="drop").reshape(ring, n)
        least = least.reshape(-1).at[flat].min(
            hop + i32(1), mode="drop").reshape(ring, n)
        seen = jnp.stack([due_count.sum(), (hop >= 0).sum(dtype=i32),
                          count.sum(axis=0).max()])
        return (hop, lcg, nxt, count, least), seen

    # -- a run, and the facts at its end ------------------------------------

    def run_to(self, steps: int) -> dict:
        """Advance to ``steps`` rounds run in all (a round is what the
        program calls a superstep: from the origin's first push on,
        some node pushes in every round) and return the facts then."""
        import jax
        if steps < self.steps:
            raise ValueError("the reference runs forwards only")
        if steps > self.steps:
            r = np.arange(self.steps, steps, dtype=np.int64) \
                + self.first_round
            t = r * self.round_us
            xs = (r.astype(np.int32), (t & 0xFFFFFFFF).astype(np.uint32),
                  (t >> 32).astype(np.uint32))
            self.state, seen = self._scan(self.state, xs)
            seen = np.asarray(jax.device_get(seen), np.int64)
            self.history = np.concatenate([self.history, seen])
            self.delivered += int(seen[:, 0].sum())
            self.steps = steps
        return self.facts()

    def facts(self) -> dict:
        import jax.numpy as jnp
        hop, lcg, nxt, count, least = self.state
        last = self.first_round + self.steps - 1
        # bucket j is due j + 1 rounds after the last one run
        order = (last + 1 + np.arange(self.ring)) % self.ring
        return {
            "hop": hop, "lcg": lcg,
            # the round of the next push; -1 where none is armed
            "next_round": jnp.where(nxt == _NO_PUSH, -1, nxt),
            "in_flight_count": count[order],
            "in_flight_least_hop": least[order],
            "delivered": self.delivered, "steps": self.steps,
            "time": last * self.round_us,
            "largest_in_flight": int(self.history[:, 2].max(initial=0)),
        }

    def saturation_step(self):
        """The first round after which every node holds the rumor;
        ``None`` while some do not."""
        full = np.flatnonzero(self.history[:, 1] == self.n)
        return int(full[0]) + 1 if len(full) else None

