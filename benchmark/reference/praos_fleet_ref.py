"""Plain reference of the praos fleet: four worlds of Praos slot-leader
consensus, world b on engine seed b and on a lognormal link of its own
median, each run for itself, event by event, by ``praos_ref.Chain`` (the
solo cell's reference, imported and not edited) built from that world's
seed and that world's link, the median a Python constant of that world.
Nothing of the program is imported and nothing the program made taken.

``Chain.run`` runs to quiescence only. A cell whose point is four links
has to see each world's link at the nodes, and no per-node row of a
final state depends on a latency (every leader of a slot mints the same
length, a node adopts the first tip it hears and floods once: what a
latency moves is when, not what). So :func:`run` restates ``Chain.run``'s
recursion, once, so that it can also stop after ``stop_after``
supersteps and return what is pending then: which node holds the tip by
then depends on every latency drawn so far, and so does what is in
flight to whom and due when. It calls the chain's own ``_leads``,
``_peers`` and ``_latency`` and owns only the loop; a test holds it to
``Chain.run`` at the end.

A world's superstep is its own (``praos_ref``'s module docstring: the
earliest pending instant and the link's floor as the window), whatever
its neighbours in the fleet do: a fleet's iteration steps every world
that is still running once, so "after k iterations" is "after k
supersteps of each world" wherever k is under every world's count.
"""

import numpy as np

from reference import praos_ref
from reference.praos_ref import _NEVER


def world_params(params: dict, seed: int, median_us: int) -> dict:
    """The solo configuration of the world (``seed``, ``median_us``):
    the fleet's parameters with that engine seed and that median."""
    return {**params, "engine_seed": int(seed),
            "link": {**params["link"], "median_us": int(median_us)}}


def run(chain, genesis: int = 0, stop_after=None) -> dict:
    """``chain``'s world from a chain of length ``genesis`` at every
    node, through ``stop_after`` supersteps (None: until nothing is
    pending). What ``Chain.run`` returns and, beside it, what is
    pending at the stop, as two tables a node: ``in_flight_count``
    (messages on their way to it) and ``in_flight_earliest`` (the due
    time of the first of them, -1 where there is none); and
    ``senders``, the nodes that pushed, summed over the supersteps
    (what a routing stage has to carry: the program counts the same
    as a world's own ``world_sender_lanes``)."""
    n = chain.n
    ids = np.arange(n, dtype=np.int64)
    best = np.full(n, genesis, np.int32)
    lcg = ((ids * 2654435761) % (2**31 - 1) + 1).astype(np.int32)
    slot = np.zeros(n, np.int32)
    to = np.empty(0, np.int64)
    land = np.empty(0, np.int64)
    told = np.empty(0, np.int32)
    in_flight = np.zeros(n, np.int64)
    minted = [0] * chain.n_slots
    delivered = steps = last = largest = senders = 0
    while stop_after is None or steps < stop_after:
        timer = np.where(slot < chain.n_slots,
                         (slot.astype(np.int64) + 1) * chain.slot_us, _NEVER)
        t = min(int(timer.min()), int(land.min()) if len(land) else _NEVER)
        if t == _NEVER:
            break
        steps, last = steps + 1, t
        # each node's earliest instant inside the window, if it has one
        near = np.flatnonzero(land < t + chain.window)
        now = np.where(timer < t + chain.window, timer, _NEVER)
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
        leader = due & chain._leads(turn, at)
        for k in slot[turn][leader]:
            minted[k] += 1
        best[turn] = tip + leader
        slot[turn] += due

        fresh = adopt | leader
        src, at = turn[fresh], at[fresh]
        senders += len(src)
        lcg[src], dst, distinct = chain._peers(lcg[src], src)
        arrive = at[:, None] + chain._latency(src, dst, at)
        to = np.concatenate([to, dst[distinct].astype(np.int64)])
        land = np.concatenate([land, arrive[distinct]])
        told = np.concatenate([
            told, np.broadcast_to(best[src][:, None], dst.shape)[distinct]])
        np.add.at(in_flight, dst[distinct], 1)
        largest = max(largest, int(in_flight.max()))
    earliest = np.full(n, _NEVER, np.int64)
    np.minimum.at(earliest, to, land)
    return {"best": best, "slot": slot, "lcg": lcg,
            "delivered": delivered, "supersteps": steps, "time": last,
            "minted": minted, "largest_in_flight": largest,
            "senders": senders,
            "in_flight_count": in_flight.astype(np.int32),
            "in_flight_earliest": np.where(earliest < _NEVER, earliest, -1)}


class Fleet:
    """The configuration's worlds by seed, each a ``praos_ref.Chain`` of
    its own seed and median. ``medians`` stands in for the
    configuration's (a control: the worlds on other links than their
    own); ``precision`` is ``Chain``'s (a control: the lognormal in the
    precision below float32)."""

    def __init__(self, params: dict, n_slots: int, precision="float32",
                 medians=None):
        seeds = [int(s) for s in params["world_seeds"]]
        if medians is None:
            (path, medians), = params["link_params"].items()
            if path != "inner.median_us":
                raise SystemExit("reference: the fleet sweeps its links' "
                                 "median and nothing else")
        if len(medians) != len(seeds):
            raise SystemExit("reference: one median a world")
        self.medians = dict(zip(seeds, (int(m) for m in medians)))
        self.chains = {
            seed: praos_ref.Chain(
                {**world_params(params, seed, median), "n_slots": n_slots},
                precision)
            for seed, median in self.medians.items()}

    def runs(self, stop_after=None) -> dict:
        """``{seed: run of that world}`` from genesis 0, to quiescence
        or through ``stop_after`` supersteps of each."""
        return {seed: run(chain, 0, stop_after)
                for seed, chain in self.chains.items()}
