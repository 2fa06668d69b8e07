"""Plain reference of steady rumor mongering under a fault schedule,
written from the scenario's definition (``gossip_steady_ref``'s
docstring) and from ``docs/faults.md`` "Mask semantics (normative)".
Nothing of the program is imported and nothing the program made is
taken; the Threefry block, the seed's expansion and the generator's
constants are ``gossip_ref``'s, as the steady reference takes them.

The scenario. Every node keeps a linear congruential generator seeded
from its id (state ``(i * 2654435761) mod (2^31 - 1) + 1``, advanced
``x * A + C`` in wrapping int32), a hop count (-1 before the rumor
came), whether it mongers, and the time of its next push. Node 0 holds
the rumor at hop 0 and first pushes at ``bootstrap_us``. A node has an
*instant* whenever its timer comes due or a message reaches it, and
handles it so: it takes every message due by now; without the rumor it
adopts the least hop among them and, if ``now < end_us``, starts to
monger with its first push ``think_us`` later; a mongering node whose
push time has come (and ``now < end_us``) advances its generator, draws
the peer ``(i + 1 + |x| mod (n - 1)) mod n``, sends it its hop count
plus one and sets its next push ``gossip_interval_us`` later. Its timer
is its next push time while it mongers and ``now < end_us``, else none.
A push from ``src`` to ``dst`` made at ``t`` (outbox slot 0) is in
flight ``lo + word mod (hi - lo + 1)`` microseconds rounded up to the
link's quantum; ``word`` is the first word of three chained
Threefry-2x32 blocks keyed by ``(seed, src, dst, t, slot)``.

The faults, in the documented order. *Partition*: a push made while a
partition is live (``start <= t < end``) from one of its groups to
another is lost at the send instant and counted (``cut``). *Link
window*: a push made inside a window has its quantized flight ``d``
replaced by ``d * num // den + extra`` (``scale`` as the exact rational
``num/den``), rows in declaration order; then the flight is at least
1 us. *Crash*: a push whose deliver time lies in ``[t_down, t_up)`` of
its destination is lost at routing and counted (``down``); a node's
next event (its timer, or its earliest message) that falls inside its
own window slides to ``t_up``; a ``reset`` row, until consumed, gives
its node an event at exactly ``t_up``, at which the node's state goes
back to the scenario's first state before the instant is handled, and
what the node still held from before ``t_down`` is lost and counted
(``purged``).

Time. Not round-paced: a degraded flight of 2.25, 2.75, ... times a
multiple of the quantum lands between rounds, and a node that hears
the rumor there pushes between rounds ever after. So messages are kept
by their exact deliver time (one bucket an instant: destinations and
hops), with no mailbox, no slots, no sort and no cap. The run advances
as the program's windowed superstep is defined (``gossip_ref``'s
docstring): a superstep of width ``window`` (the link's least flight,
which no link window of scale >= 1 lowers) starts at the earliest
pending instant ``t`` and lets every node with an instant in ``[t, t +
window)`` handle its earliest one, at its own time; nothing sent from
``t`` on lands before ``t + window``, so the events are those of the
run that handles one instant at a time, and ``steps`` and the time of
the last superstep are the program's. After every superstep the
largest number of messages pending to one node is taken: what a mailbox
under this window has to hold.

All of it is integer arithmetic, exact on every backend: numpy, but
for the link's word, which is this file's own ``jax.numpy`` expression
of the three blocks and runs where the program runs (time goes in as
two 32-bit words: nothing needs 64-bit mode). ``word_bits=16`` cuts the
word to its low 16 bits, the precision below its 32: the control of
the comparison.
"""

from concurrent.futures import ThreadPoolExecutor
from fractions import Fraction

import numpy as np

from reference import gossip_ref
from reference.gossip_ref import LCG_A, LCG_C, seed_words, threefry2x32

NEVER = 1 << 62               # no time reaches it
NO_HOP = 2**31 - 1            # the least hop of an empty inbox
_THREADS = 4                  # numpy and the device's words let go of the lock
_UNITS = (("us", 1), ("ms", 1_000), ("s", 1_000_000))


# -- the --faults grammar, as docs/faults.md gives it -------------------

def _time(text: str) -> int:
    for suffix, mult in _UNITS:
        if text.endswith(suffix):
            return int(Fraction(text[:-len(suffix)]) * mult)
    return int(text)


def _nodes(text: str, n: int) -> np.ndarray:
    """A node set (``all``, or ``+``-joined ids and ranges) as a mask."""
    mask = np.zeros(n, bool)
    if text == "all":
        mask[:] = True
        return mask
    for part in text.split("+"):
        lo, _, hi = part.partition("-")
        mask[int(lo):int(hi or lo) + 1] = True
    return mask


def parse_schedule(text: str, n: int) -> dict:
    """``{"crash": [(node, down, up, reset)], "partition": [(group of
    every node or -1, start, end)], "degrade": [(src mask, dst mask,
    start, end, num, den, extra)]}`` of one world's ``--faults``
    string. A clock skew has no part in this deployment and is
    refused."""
    out = {"crash": [], "partition": [], "degrade": []}
    for event in filter(None, (e.strip() for e in text.split(";"))):
        kind, *f = event.split(":")
        if kind == "crash":
            out[kind].append((int(f[0]), _time(f[1]), _time(f[2]),
                              f[3:] == ["reset"]))
        elif kind == "partition":
            group = np.full(n, -1, np.int32)
            for g, members in enumerate(f[0].split("|")):
                group[_nodes(members, n)] = g
            out[kind].append((group, _time(f[1]), _time(f[2])))
        elif kind == "degrade":
            scale = Fraction(f[4])
            out[kind].append((_nodes(f[0], n), _nodes(f[1], n),
                              _time(f[2]), _time(f[3]), scale.numerator,
                              scale.denominator,
                              _time(f[5]) if len(f) > 5 else 0))
        else:
            raise ValueError(f"no {kind!r} in this deployment's schedules")
    return out


# -- the link's word, on the device ---------------------------------------

def flights(link, seed: int, word_bits: int):
    """``f(src, dst, t) -> int64[len(src)]``: the quantized flight in
    microseconds of the push ``src[b] -> dst[b]`` made at ``t[b]`` from
    slot 0, before any fault touches it. Padded to a power of two so
    that the device compiles a few shapes."""
    import jax
    import jax.numpy as jnp
    s0, s1 = seed_words(seed)
    k0, k1 = s0 ^ gossip_ref._MSG_TAG, s1
    lo, hi, q = (int(link[k]) for k in ("lo_us", "hi_us", "quantum_us"))
    if link["model"] != "uniform":
        raise ValueError("the chaos fleet's link is uniform, quantized")
    u32 = jnp.uint32

    @jax.jit
    def f(src, dst, t_lo, t_hi):
        a0, a1 = threefry2x32(u32(k0), u32(k1), src.astype(u32),
                              dst.astype(u32))
        b0, b1 = threefry2x32(a0, a1, t_lo, t_hi)
        word, _ = threefry2x32(b0, b1, u32(0), u32(0))
        word = word & u32((1 << word_bits) - 1)
        d = jnp.int32(lo) + (word % u32(hi - lo + 1)).astype(jnp.int32)
        return (d + jnp.int32(q - 1)) // jnp.int32(q) * jnp.int32(q)

    def flight(src, dst, t):
        b = len(src)
        pad = max(1024, 1 << (b - 1).bit_length())
        s, d = np.zeros(pad, np.int32), np.zeros(pad, np.int32)
        tt = np.zeros(pad, np.int64)
        s[:b], d[:b], tt[:b] = src, dst, t
        out = f(s, d, (tt & 0xFFFFFFFF).astype(np.uint32),
                (tt >> 32).astype(np.uint32))
        return np.asarray(out)[:b].astype(np.int64)
    return flight


# -- one world -----------------------------------------------------------

class World:
    """One world of the fleet: the configuration's scenario and link
    under engine seed ``seed`` and the schedule ``faults`` (a string of
    the ``--faults`` grammar). ``run`` takes it from its first state
    to quiescence and returns the facts a comparison needs."""

    def __init__(self, params: dict, seed: int, faults: str,
                 word_bits: int = 32):
        self.n = n = int(params["n_nodes"])
        link = params["link"]
        timers = {k: int(params[k]) for k in (
            "think_us", "gossip_interval_us", "bootstrap_us", "end_us")}
        if int(params["fanout"]) != 1 or not params["steady"] \
                or int(params["origin"]):
            raise ValueError("steady mongering from node 0: one peer a push")
        self.think, self.interval = timers["think_us"], \
            timers["gossip_interval_us"]
        self.bootstrap, self.end = timers["bootstrap_us"], timers["end_us"]
        self.sched = parse_schedule(faults, n)
        # the least flight any push can have: the link's, which a
        # window of scale >= 1 and extra >= 0 does not lower
        q = int(link["quantum_us"])
        self.window = max(-(-int(link["lo_us"]) // q) * q, q)
        if any(num < den for *_, num, den, _ in self.sched["degrade"]):
            raise ValueError("a link window that shortens flights narrows "
                             "the superstep's window: not this deployment")
        self._flight = flights(link, seed, word_bits)

    def _first_state(self, nodes):
        """The scenario's first state of ``nodes``: ``(hop, lcg,
        mongers, next push)``."""
        nodes = np.asarray(nodes, np.int64)
        origin = nodes == 0
        return (np.where(origin, 0, -1).astype(np.int32),
                ((nodes * 2654435761) % (2**31 - 1) + 1).astype(np.int32),
                origin.copy(),
                np.where(origin, self.bootstrap, NEVER))

    def run(self) -> dict:
        n, W = self.n, self.window
        crashes = self.sched["crash"]
        crashing = np.zeros(n, bool)
        crashing[[c for c, *_ in crashes]] = True
        hop, lcg, mongers, nxt = self._first_state(np.arange(n))
        timer = nxt.copy()
        #: deliver time -> [destinations, hops] of the pushes in flight
        due: dict = {}
        pending = np.zeros(n, np.int64)        # in flight, by destination
        #: the deliver times of what each crash row's node has in
        #: flight: its earliest message is part of the event that slides
        held = {c: [] for c, *_ in crashes}
        consumed = [False] * len(crashes)
        count = dict.fromkeys(("delivered", "cut", "down", "purged",
                               "degraded", "restarts", "steps"), 0)
        last = largest = 0
        while True:
            # each node's next event: its timer, or its earliest
            # message. A crash row's node's: slid out of its window,
            # and its reboot while that is to come
            ahead = timer.copy()
            for r, (c, down, up, reset) in enumerate(crashes):
                x = min(int(timer[c]), min(held[c], default=NEVER))
                if down <= x < up:
                    x = up
                if reset and not consumed[r]:
                    x = min(x, up)
                ahead[c] = x
            keys = sorted(due)
            t = min(int(ahead.min()), next(
                (k for k in keys if not crashing[due[k][0]].all()), NEVER))
            if t >= NEVER:
                break
            # every node with an instant in [t, t + W) handles its
            # earliest one, at its own time
            keys = [k for k in keys if k < t + W]
            now = np.where(ahead < t + W, ahead, NEVER)
            for k in keys:
                to = due[k][0]
                to = to[~crashing[to]]
                now[to] = np.minimum(now[to], k)
            fire = now < NEVER
            count["steps"] += 1
            last = t

            # reboots: the first state again, and the loss of what the
            # node held from before it went down
            for r, (c, down, up, reset) in enumerate(crashes):
                if reset and not consumed[r] and fire[c] and now[c] == up:
                    consumed[r] = True
                    count["restarts"] += 1
                    hop[c], lcg[c], mongers[c], nxt[c] = \
                        (x[0] for x in self._first_state([c]))
                    lost = [d for d in held[c] if d < down]
                    count["purged"] += len(lost)
                    pending[c] -= len(lost)
                    held[c] = [d for d in held[c] if d >= down]
                    for d in set(lost):     # in `keys`: d < t_down < t
                        to, hops = due[d]
                        due[d] = [to[to != c], hops[to != c]]

            # deliveries: what is due by a node's own instant
            least = np.full(n, NO_HOP, np.int32)
            for k in keys:
                to, hops = due.pop(k)
                got = fire[to] & (now[to] >= k)
                np.minimum.at(least, to[got], hops[got])
                np.subtract.at(pending, to[got], 1)
                count["delivered"] += int(got.sum())
                if not got.all():
                    due[k] = [to[~got], hops[~got]]
            for c in held:
                if fire[c]:
                    held[c] = [d for d in held[c] if d > now[c]]

            # the instant, handled
            ids = np.flatnonzero(fire)
            at = now[ids]
            alive = at < self.end
            new = (hop[ids] < 0) & (least[ids] < NO_HOP)
            hop[ids] = np.where(new, least[ids], hop[ids])
            start = new & alive
            mongers[ids] |= start
            nxt[ids] = np.where(start, at + self.think, nxt[ids])
            push = mongers[ids] & (nxt[ids] <= at) & alive
            src, at_push = ids[push], at[push]
            with np.errstate(over="ignore"):
                x1 = lcg[src] * np.int32(LCG_A) + np.int32(LCG_C)
                to = ((src.astype(np.int32) + np.int32(1)
                       + np.abs(x1) % np.int32(n - 1)) % np.int32(n))
            lcg[src] = x1
            nxt[src] = at_push + self.interval
            timer[ids] = np.where(mongers[ids] & alive, nxt[ids], NEVER)
            self._send(src, to, hop[src] + 1, at_push, due, pending, held,
                       count)
            largest = max(largest, int(pending.max()))
        return {"hop": hop, "lcg": lcg,
                # the next push time the state holds; -1 where none
                "next": np.where(nxt >= NEVER, -1, nxt),
                **count, "time": last, "largest_in_flight": largest}

    def _send(self, src, to, hops, at, due, pending, held, count):
        """The pushes ``src -> to`` made at ``at``: cut, slowed,
        dropped at a down node, or put in flight."""
        ok = np.ones(len(src), bool)
        for group, start, end in self.sched["partition"]:
            gs, gd = group[src], group[to]
            ok &= ~((start <= at) & (at < end) & (gs != gd)
                    & (gs >= 0) & (gd >= 0))
        count["cut"] += int((~ok).sum())
        src, to, hops, at = src[ok], to[ok], hops[ok], at[ok]
        plain = self._flight(src, to, at)
        d = plain
        for s_mask, d_mask, start, end, num, den, extra in \
                self.sched["degrade"]:
            hit = (start <= at) & (at < end) & s_mask[src] & d_mask[to]
            d = np.where(hit, d * num // den + extra, d)
        count["degraded"] += int((d != plain).sum())
        lands = at + np.maximum(d, 1)
        ok = np.ones(len(src), bool)
        for c, down, up, _ in self.sched["crash"]:
            ok &= ~((to == c) & (down <= lands) & (lands < up))
        count["down"] += int((~ok).sum())
        to, hops, lands = to[ok], hops[ok], lands[ok]
        np.add.at(pending, to, 1)
        order = np.argsort(lands, kind="stable")
        to, hops, lands = to[order], hops[order], lands[order]
        cuts = np.flatnonzero(np.diff(lands)) + 1
        for k, a, b in zip(lands[np.r_[0, cuts]] if len(lands) else [],
                           np.split(to, cuts), np.split(hops, cuts)):
            k = int(k)
            if k in due:
                a = np.concatenate([due[k][0], a])
                b = np.concatenate([due[k][1], b])
            due[k] = [a, b]
        for c in held:
            held[c] += [int(x) for x in lands[to == c]]


class Fleet:
    """One configuration's worlds: world ``b`` has engine seed
    ``world_seeds[b]`` and the schedule ``faults[b]``."""

    def __init__(self, params: dict, word_bits: int = 32):
        self.worlds = {
            int(seed): World(params, int(seed), faults, word_bits)
            for seed, faults in zip(params["world_seeds"],
                                    params["faults"])}

    def runs(self) -> dict:
        """``{engine seed: World.run()}``: every world run to
        quiescence, each on its own."""
        with ThreadPoolExecutor(_THREADS) as pool:
            done = pool.map(lambda w: w.run(), self.worlds.values())
            return dict(zip(self.worlds, done))
