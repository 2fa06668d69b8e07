"""Plain reference of Praos slot-leader consensus with burst diffusion,
written from the scenario's definition: a discrete-event simulation in
numpy, nothing of the program imported and nothing the program made
taken. From ``gossip_ref`` it takes the Threefry block, the expansion
of a seed into key words and the generator's constants: the rest is
its own.

The scenario. Time is cut into slots of ``slot_us``. Every node keeps
the length of the longest chain it knows (``best``, the genesis length
at first), the number of slots it has seen (``slot``) and a linear
congruential generator seeded from its id (``lcg``). At every slot
boundary ``k * slot_us`` (k = 1 .. ``n_slots``) every node draws its
private leadership word, the first of two Threefry-2x32 words keyed by
``(seed, node, instant)``, and leads the slot if the word is below the
threshold ``leaders_per_slot / n * 2^32``: a leader extends its chain
by one block. A message carries a chain length; a node that hears a
length above its own adopts it (of several that arrive at one instant,
the longest), before it draws for a slot that starts at that instant. A
node whose tip is fresh, adopted or minted, pushes it at that instant
to ``fanout`` peers drawn from its generator (a peer drawn twice gets
one push); a node with nothing fresh leaves its generator where it was.
A push from ``src`` to ``dst`` made at ``t`` from outbox slot ``j``
takes the lognormal latency of ``gossip_ref`` (keyed by ``(seed, src,
dst, t, j)``, float32, clipped to ``[floor, cap]``, rounded to whole
microseconds and up to the link's quantum).

A superstep of width ``window`` (the link's least latency, which is
what makes it exact: nothing sent inside a window lands inside it)
starts at the earliest pending instant ``t``, and every node with an
instant in ``[t, t + window)`` handles its earliest one, and that one
only; its later ones wait for a later superstep. ``Chain.run`` is that
recursion, and counts its supersteps as it goes.

What a run to quiescence returns: every node's ``best``, ``slot`` and
``lcg``, the messages delivered, the supersteps and the time of the
last, the blocks minted in each slot, and the largest number of
messages that were ever in flight to one node at the end of a
superstep, which is what a mailbox has to hold.

The integer arithmetic is exact everywhere and runs in numpy. The
float32 arithmetic of the latency is exact within one backend only, so
``latencies`` is this file's own jax.numpy expression and runs on the
backend the program runs on (``gossip_ref.latencies`` reads a node's
peers from a table; here they move with the generator, so they come as
an argument). ``precision="bfloat16"`` computes the lognormal in
bfloat16: the control of the comparison.
"""

import numpy as np

from reference.gossip_ref import LCG_A, LCG_C, seed_words, threefry2x32

_FIRE_TAG = 0xF14EF14E        # the stream of firing entropy
_MSG_TAG = 0x4D534721         # the stream of link samples
_NEVER = 1 << 56               # no time reaches it


def latencies(link, seed, precision="float32"):
    """``f(src[B], dst[B, fanout], t_lo[B], t_hi[B]) -> int32[B, fanout]``,
    computed on the device: the latency in microseconds of the push
    from ``src[b]`` to ``dst[b, j]`` made at time ``t[b]`` from slot
    ``j``."""
    import jax
    import jax.numpy as jnp
    s0, s1 = seed_words(seed)
    f32, u32 = jnp.float32, jnp.uint32
    median, sigma = float(link["median_us"]), float(link["sigma"])
    floor, cap = float(link["floor_us"]), float(link["cap_us"])
    quantum = int(link["quantum_us"])

    @jax.jit
    def f(src, dst, t_lo, t_hi):
        slot = jnp.arange(dst.shape[1], dtype=u32)[None, :]
        a0, a1 = threefry2x32(u32(s0 ^ _MSG_TAG), u32(s1),
                              src.astype(u32)[:, None], dst.astype(u32))
        b0, b1 = threefry2x32(a0, a1, t_lo[:, None], t_hi[:, None])
        w0, w1 = threefry2x32(b0, b1, slot, u32(0))

        def u24(w):                  # the top 24 bits, as a float
            return (w >> u32(8)).astype(jnp.int32).astype(f32)
        u1 = u24(w0) * f32(2 ** -24) + f32(2 ** -25)
        u2 = u24(w1) * f32(2 ** -24)
        z = jnp.sqrt(f32(-2.0) * jnp.log(u1)) \
            * jnp.cos(f32(2.0 * 3.141592653589793) * u2)
        if precision == "float32":
            d = jnp.asarray(median, f32) * jnp.exp(f32(sigma) * z)
        else:
            low = jnp.dtype(precision)
            d = (jnp.asarray(median, low) * jnp.exp(
                jnp.asarray(sigma, low) * z.astype(low))).astype(f32)
        d = jnp.round(jnp.clip(d, f32(floor), f32(cap))).astype(jnp.int32)
        d = jnp.maximum(d, 1)
        return (d + (quantum - 1)) // quantum * quantum
    return f


def _words(t):
    """The low and the high 32 bits of int64 times."""
    return ((t & 0xFFFFFFFF).astype(np.uint32),
            (t >> 32).astype(np.uint32))


class Chain:
    """One configuration's slots, stake and link, built once; ``run``
    takes a world from genesis to quiescence."""

    def __init__(self, params: dict, precision="float32"):
        self.n, self.fanout = int(params["n_nodes"]), int(params["fanout"])
        self.slot_us = int(params["slot_us"])
        self.n_slots = int(params["n_slots"])
        # equal stake: one threshold for all, a whole number of 2^-32
        self.threshold = min(int(float(params["leaders_per_slot"]) / self.n
                                 * 4294967296.0), 2**32 - 1)
        link = params["link"]
        q = int(link["quantum_us"])
        self.window = max(-(-int(link["floor_us"]) // q) * q, q)
        self.seed = int(params["engine_seed"])
        self._latencies = latencies(link, self.seed, precision)

    def _leads(self, node, at):
        """Whether each ``node`` draws under the threshold at the
        instant ``at``: the first word of the firing entropy."""
        s0, s1 = seed_words(self.seed)
        lo, hi = _words(at)
        key = threefry2x32(np.uint32(s0 ^ _FIRE_TAG), np.uint32(s1),
                           node.astype(np.uint32), lo)
        word, _ = threefry2x32(*key, hi, np.uint32(0))
        return word < np.uint32(self.threshold)

    def _peers(self, x, node):
        """``fanout`` chained draws of the generators ``x`` of
        ``node``: the generators after them, the peers ``[B, fanout]``
        and which draws count (the first of each peer). Wrapping int32;
        no node draws itself."""
        n, i32 = self.n, node.astype(np.int32)
        dst = np.empty((len(node), self.fanout), np.int32)
        with np.errstate(over="ignore"):
            for j in range(self.fanout):
                x = x * np.int32(LCG_A) + np.int32(LCG_C)
                dst[:, j] = (i32 + np.int32(1)
                             + np.abs(x) % np.int32(n - 1)) % np.int32(n)
        distinct = np.ones(dst.shape, bool)
        for a in range(1, self.fanout):
            for b in range(a):
                distinct[:, a] &= dst[:, a] != dst[:, b]
        return x, dst, distinct

    def _latency(self, src, dst, at):
        """Latencies ``[len(src), fanout]`` of the pushes of ``src`` to
        ``dst`` at ``at``; padded to a power of two so that the device
        compiles a few shapes and not one for every batch."""
        b = len(src)
        pad = max(1024, 1 << (b - 1).bit_length())
        s = np.zeros(pad, np.int32)
        s[:b] = src
        d = np.zeros((pad, self.fanout), np.int32)
        d[:b] = dst
        t = np.zeros(pad, np.int64)
        t[:b] = at
        return np.asarray(self._latencies(s, d, *_words(t)))[:b].astype(
            np.int64)

    def run(self, genesis: int = 0) -> dict:
        """A world whose every node starts on a chain of length
        ``genesis``, run until nothing is pending."""
        n = self.n
        ids = np.arange(n, dtype=np.int64)
        best = np.full(n, genesis, np.int32)
        lcg = ((ids * 2654435761) % (2**31 - 1) + 1).astype(np.int32)
        slot = np.zeros(n, np.int32)
        # messages in flight: to whom, when they land, the length told
        to = np.empty(0, np.int64)
        land = np.empty(0, np.int64)
        told = np.empty(0, np.int32)
        in_flight = np.zeros(n, np.int64)
        minted = [0] * self.n_slots
        delivered = steps = last = largest = 0
        while True:
            # every node's next slot boundary, and the earliest instant
            timer = np.where(slot < self.n_slots,
                             (slot.astype(np.int64) + 1) * self.slot_us,
                             _NEVER)
            t = min(int(timer.min()), int(land.min()) if len(land)
                    else _NEVER)
            if t == _NEVER:
                break
            steps, last = steps + 1, t
            # a node's earliest instant inside the window, if it has one
            near = np.flatnonzero(land < t + self.window)
            now = np.where(timer < t + self.window, timer, _NEVER)
            np.minimum.at(now, to[near], land[near])
            # what lands on a node at that instant is heard there
            heard = near[land[near] == now[to[near]]]
            longest = np.full(n, -1, np.int64)
            np.maximum.at(longest, to[heard], told[heard])
            delivered += len(heard)
            np.subtract.at(in_flight, to[heard], 1)
            rest = np.ones(len(to), bool)
            rest[heard] = False
            to, land, told = to[rest], land[rest], told[rest]

            turn = np.flatnonzero(now < _NEVER)
            at = now[turn]
            adopt = longest[turn] > best[turn]
            tip = np.where(adopt, longest[turn], best[turn]).astype(np.int32)
            due = timer[turn] == at
            leader = due & self._leads(turn, at)
            for k in slot[turn][leader]:
                minted[k] += 1
            best[turn] = tip + leader
            slot[turn] += due

            fresh = adopt | leader
            src, at = turn[fresh], at[fresh]
            lcg[src], dst, distinct = self._peers(lcg[src], src)
            arrive = at[:, None] + self._latency(src, dst, at)
            to = np.concatenate([to, dst[distinct].astype(np.int64)])
            land = np.concatenate([land, arrive[distinct]])
            told = np.concatenate([
                told, np.broadcast_to(best[src][:, None],
                                      dst.shape)[distinct]])
            np.add.at(in_flight, dst[distinct], 1)
            largest = max(largest, int(in_flight.max()))
        return {"best": best, "slot": slot, "lcg": lcg,
                "delivered": delivered, "supersteps": steps, "time": last,
                "minted": minted, "largest_in_flight": largest}
