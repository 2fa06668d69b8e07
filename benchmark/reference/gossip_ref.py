"""Plain reference of the push-rumor broadcast, written from the
scenario's definition: a discrete-event simulation in numpy, nothing of
the program imported and nothing the program made taken.

The scenario. Every node keeps a linear congruential generator seeded
from its id. The origin pushes the rumor at ``bootstrap_us``; every
other node, ``think_us`` after it first hears the rumor, pushes it once
to ``fanout`` peers drawn from that generator (a peer drawn twice gets
one push). A push from ``src`` to ``dst`` made at time ``t`` from
outbox slot ``j`` takes a lognormal latency drawn from a counter-based
generator keyed by ``(seed, src, dst, t, j)``: Threefry-2x32 words, a
Box-Muller normal ``z`` in float32, ``median * exp(sigma * z)`` clipped
to ``[floor, cap]``, rounded to whole microseconds and then up to the
link's quantum. A node adopts the least hop count among the rumors that
reach it at the instant it first hears one.

What a wave run to quiescence leaves behind, and what ``Graph.wave``
returns: every node's hop count (-1 where the rumor never came), the
messages delivered, the number of supersteps and the time of the last.
A superstep of width ``window`` (the link's least latency, which is
what makes it exact) starts at the earliest pending instant ``t`` and
lets every node with an instant in ``[t, t + window)`` handle its
earliest one.

The integer arithmetic (peers, Threefry) is exact everywhere and runs
in numpy. The float32 arithmetic of the latency is exact within one
backend only (a TPU's ``log`` is not the host's), so ``latencies`` is
this file's own jax.numpy expression of the definition above and runs
on the backend the program runs on; it takes time as two 32-bit words
and needs no 64-bit mode. ``precision="bfloat16"`` computes the
lognormal in bfloat16: the control of the comparison.
"""

import numpy as np

LCG_A = 1103515245
LCG_C = 12345

_PARITY = 0x1BD11BDA          # Threefry's key-schedule constant
_GOLD = 0x9E3779B9            # seeding: domain separation
_MSG_TAG = 0x4D534721         # the stream of link samples
_ROTATIONS = ((13, 15, 26, 6), (17, 29, 16, 24))
_HOP_BITS = 6                 # hop counts stay far below 64
_NEVER = 1 << 56               # no time reaches it


def peers(n: int, fanout: int):
    """``(dst[n, fanout], distinct[n, fanout])``: the peers every node
    pushes to and which of the draws count (first occurrence). The
    generator runs in wrapping int32: state ``(i * 2654435761) mod
    (2^31 - 1) + 1``, advanced ``x * A + C`` per draw, peer ``(i + 1 +
    |x| mod (n - 1)) mod n`` so that no node draws itself."""
    ids = np.arange(n, dtype=np.int64)
    x = ((ids * 2654435761) % (2**31 - 1) + 1).astype(np.int32)
    i32 = ids.astype(np.int32)
    dst = np.empty((n, fanout), np.int32)
    with np.errstate(over="ignore"):
        for j in range(fanout):
            x = x * np.int32(LCG_A) + np.int32(LCG_C)
            dst[:, j] = (i32 + np.int32(1)
                         + np.abs(x) % np.int32(n - 1)) % np.int32(n)
    distinct = np.ones((n, fanout), bool)
    for a in range(1, fanout):
        for b in range(a):
            distinct[:, a] &= dst[:, a] != dst[:, b]
    return dst, distinct


def threefry2x32(k0, k1, c0, c1):
    """The standard 20-round Threefry-2x32 block: key ``(k0, k1)``,
    counter ``(c0, c1)``, two words out. Wrapping uint32 arithmetic on
    numpy or jax.numpy arrays alike."""
    x0, x1 = c0 + k0, c1 + k1
    ks = (k0, k1, k0 ^ k1 ^ _PARITY)
    for g in range(5):
        for r in _ROTATIONS[g % 2]:
            x0 = x0 + x1
            x1 = ((x1 << r) | (x1 >> (32 - r))) ^ x0
        x0 = x0 + ks[(g + 1) % 3]
        x1 = x1 + ks[(g + 2) % 3] + (g + 1)
    return x0, x1


def seed_words(seed: int):
    """The two key words a run's integer seed expands to."""
    def word(x):
        return np.array([x & 0xFFFFFFFF], np.uint32)
    a, b = threefry2x32(word(seed), word((seed >> 32) ^ _GOLD),
                        word(0), word(1))
    return int(a[0]), int(b[0])


def latencies(link, seed, peers_of, precision="float32"):
    """``f(src[B], t_lo[B], t_hi[B]) -> int32[B, fanout]``, computed on
    the device: the latency in microseconds of the push from ``src[b]``
    to its peer ``peers_of[src[b], j]`` made at time ``t[b]`` from slot
    ``j``."""
    import jax
    import jax.numpy as jnp
    s0, s1 = seed_words(seed)
    f32, u32 = jnp.float32, jnp.uint32
    median, sigma = float(link["median_us"]), float(link["sigma"])
    floor, cap = float(link["floor_us"]), float(link["cap_us"])
    quantum = int(link["quantum_us"])

    def f(peers_of, src, t_lo, t_hi):
        dst = peers_of[src]
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
    # the table goes in as an argument: as a constant it would be
    # copied into the executable of every batch size
    f, peers_of = jax.jit(f), jax.device_put(peers_of)
    return lambda src, t_lo, t_hi: f(peers_of, src, t_lo, t_hi)


class Graph:
    """One configuration's push graph and link, built once; ``wave``
    runs a broadcast from one origin."""

    def __init__(self, params: dict, precision="float32"):
        self.n, self.fanout = int(params["n_nodes"]), int(params["fanout"])
        self.think = int(params["think_us"])
        self.end = int(params["end_us"])
        self.bootstrap = int(params["bootstrap_us"])
        link = params["link"]
        q = int(link["quantum_us"])
        # the link's least latency: what a superstep's window is, and
        # how far ahead of the earliest pending push nothing can land
        self.window = max(-(-int(link["floor_us"]) // q) * q, q)
        self.dst, self.distinct = peers(self.n, self.fanout)
        self._latencies = latencies(link, int(params["engine_seed"]),
                                    self.dst, precision)

    def _latency(self, src, t):
        """Latencies of the pushes of nodes ``src`` at times ``t``,
        ``[len(src), fanout]``; padded to a power of two so that the
        device compiles a few shapes and not one for every batch."""
        b = len(src)
        pad = max(1024, 1 << (b - 1).bit_length())
        s = np.zeros(pad, np.int32)
        s[:b] = src
        tt = np.zeros(pad, np.int64)
        tt[:b] = t
        out = self._latencies(s, (tt & 0xFFFFFFFF).astype(np.uint32),
                              (tt >> 32).astype(np.uint32))
        return np.asarray(out)[:b].astype(np.int64)

    def wave(self, origin: int) -> dict:
        """A wave from ``origin`` run to quiescence."""
        n = self.n
        # (arrival << bits | hop) of the rumor a node hears first, the
        # least hop among those that arrive together
        first = np.full(n, _NEVER << _HOP_BITS, np.int64)
        due = np.full(n, _NEVER, np.int64)    # when a node will push
        pushed = np.zeros(n, bool)
        hop = np.full(n, -1, np.int32)
        hop[origin], due[origin] = 0, self.bootstrap
        nodes, times = [], []                 # every push and arrival
        while True:
            t = due.min()
            if t == _NEVER:
                break
            # nothing sent from t on lands before t + window, so every
            # push due before then is final
            batch = np.flatnonzero(due < t + self.window)
            at = due[batch]
            due[batch] = _NEVER
            pushed[batch] = True
            nodes.append(batch)
            times.append(at)
            alive = at < self.end
            batch, at = batch[alive], at[alive]
            # a peer drawn twice gets one push: the second never lands
            arrive = np.where(self.distinct[batch],
                              at[:, None] + self._latency(batch, at),
                              _NEVER).ravel()
            to = self.dst[batch].ravel()
            rumor = np.repeat(hop[batch] + 1, self.fanout)
            nodes.append(to)
            times.append(arrive)
            np.minimum.at(first, to, (arrive << _HOP_BITS) | rumor)
            new = to[~pushed[to]]
            heard = first[new] >> _HOP_BITS
            hop[new] = first[new] & ((1 << _HOP_BITS) - 1)
            due[new] = np.where(heard < self.end, heard + self.think,
                                _NEVER)
        nodes, times = np.concatenate(nodes), np.concatenate(times)
        landed = times < _NEVER
        steps, last = self._supersteps(nodes[landed], times[landed])
        return {"hop": hop, "delivered": int(landed.sum()) - int(pushed.sum()),
                "supersteps": steps, "time": last}

    def _supersteps(self, node, at):
        """How many supersteps handle the instants ``(node, at)``
        (arrivals and pushes; one instant where several coincide), and
        when the last one starts."""
        t0 = int(at.min())
        # after its own instants every node gets one that never comes,
        # beyond any window that starts at a real one
        bits = (int(at.max()) - t0 + self.window).bit_length()
        never = (1 << bits) - 1
        key = np.unique(np.concatenate([
            (node.astype(np.int64) << bits) | (at - t0),
            (np.arange(self.n) << bits) | never]))
        at = key & never                      # node by node, in order
        count = np.bincount(key >> bits)
        head = np.cumsum(count) - count       # each node's next instant
        cur = at[head]
        steps = last = 0
        while True:
            t = cur.min()
            if t == never:
                return steps, t0 + last
            steps, last = steps + 1, int(t)
            turn = np.flatnonzero(cur < t + self.window)
            head[turn] += 1
            cur[turn] = at[head[turn]]
