"""Gossip broadcast — BASELINE.json config 4 ("gossip broadcast, 100k
nodes, lognormal latency model").

A push-rumor epidemic: node 0 originates a rumor; every node, on first
hearing it, relays it to ``fanout`` pseudo-random peers, one send per
``gossip_interval`` after a ``think_us`` incubation. The scenario the
reference *could* have written against its `Delays`-style emulated
network (examples/token-ring/Main.hs:73-77 is the same shape: a seeded
per-link latency draw on every message) but never shipped.

Destinations are dynamic — drawn from an in-state LCG per send — so
this runs on the general engine (`interp/jax_engine/engine.py`), and
sharded on the all_to_all :class:`ShardedEngine`. The inbox reduces
commutatively (min over hop counts), so no contract-#2 sort is
compiled in.

Payload layout: ``[hop]`` — the relay depth at which the rumor
travels; receivers adopt the minimum incoming hop (width 1: one
fewer mailbox scatter per superstep in the engines).
"""

from __future__ import annotations

from ..utils import jaxconfig  # noqa: F401

import jax.numpy as jnp

from ..core.scenario import NEVER, Inbox, Outbox, Scenario
from ..core.time import Microsecond, ms, sec
from ..net.delays import LinkModel, LogNormalDelay
from ..obs.profiler import phased
from .peers import distinct_mask, lcg_peers

__all__ = ["gossip", "gossip_links"]


@phased("tw.scenario", model="gossip")
def gossip(n: int, *,
           fanout: int = 8,
           think_us: Microsecond = ms(5),
           gossip_interval: Microsecond = ms(2),
           bootstrap_us: Microsecond = ms(1),
           end_us: Microsecond = sec(60),
           steady: bool = False,
           burst: bool = False,
           mailbox_cap: int = 16) -> Scenario:
    """Build the gossip scenario. Node 0 starts infected; the run
    quiesces when every node has relayed its ``fanout`` sends (or the
    ``end_us`` deadline passes).

    ``steady=True`` is the *rumor-mongering / anti-entropy* variant:
    an infected node keeps relaying to one random peer every
    ``gossip_interval`` until the deadline (not fanout-bounded) — the
    classic epidemic steady state, and the dense general-engine
    regime (every infected node fires co-temporally each round).

    ``burst=True`` (wave mode only) relays to all ``fanout`` peers in
    ONE firing after the incubation — how a real node pushes over its
    parallel peer connections, and the form windowed supersteps can
    batch (a per-node one-send-per-interval chain is sequential by
    construction). ``gossip_interval`` is unused then."""
    if n < 2:
        raise ValueError(f"gossip needs n >= 2 nodes, got {n} "
                         "(peer draw divides by n - 1)")
    if burst and steady:
        raise ValueError("burst applies to the broadcast wave only; "
                         "steady mode is round-paced by definition")

    def step_burst(state, inbox: Inbox, now, i, key):
        hop, lcg = state["hop"], state["lcg"]
        left, nxt = state["left"], state["next"]

        hin = jnp.min(jnp.where(inbox.valid, inbox.payload[:, 0],
                                jnp.int32(2**31 - 1)))
        got_new = (hop < 0) & (hin < 2**31 - 1)
        hop1 = jnp.where(got_new, hin, hop)
        alive = now < jnp.int64(end_us)
        left1 = jnp.where(got_new & alive, jnp.int32(1), left)
        nxt1 = jnp.where(got_new & alive, now + jnp.int64(think_us), nxt)

        # one firing floods all fanout peers: chained LCG draws.
        # Duplicate draws are masked — a real node pushes a rumor at
        # most once per peer connection, and distinctness is also what
        # keeps the net-stack twin µs-identical (same-socket
        # co-temporal chunks serialize +1 µs under TCP FIFO —
        # models/gossip_net.py)
        due = (left1 > 0) & (nxt1 <= now) & alive
        lc, dsts = lcg_peers(lcg, i, n, fanout)
        lcg1 = jnp.where(due, lc, lcg)
        out = Outbox(
            valid=due & distinct_mask(dsts),
            dst=jnp.stack(dsts),
            payload=jnp.broadcast_to((hop1 + 1).reshape(1, 1),
                                     (fanout, 1)))
        left2 = jnp.where(due, jnp.int32(0), left1)
        nxt2 = jnp.where(due, jnp.int64(NEVER), nxt1)
        wake = jnp.where((left2 > 0) & alive, nxt2, jnp.int64(NEVER))
        return {"hop": hop1, "lcg": lcg1, "left": left2,
                "next": nxt2}, out, wake

    def step(state, inbox: Inbox, now, i, key):
        hop, lcg = state["hop"], state["lcg"]
        left, nxt = state["left"], state["next"]

        # adopt the minimum incoming relay depth (commutative)
        hin = jnp.min(jnp.where(inbox.valid, inbox.payload[:, 0],
                                jnp.int32(2**31 - 1)))
        got_new = (hop < 0) & (hin < 2**31 - 1)
        hop1 = jnp.where(got_new, hin, hop)
        alive = now < jnp.int64(end_us)
        # first infection: arm the relay burst after the incubation
        left1 = jnp.where(got_new & alive, jnp.int32(fanout), left)
        nxt1 = jnp.where(got_new & alive, now + jnp.int64(think_us), nxt)

        # one relay send per firing of the relay timer (dst is only
        # observable when due — outbox validity gates it)
        due = (left1 > 0) & (nxt1 <= now) & alive
        lc, (dst,) = lcg_peers(lcg, i, n, 1)
        lcg1 = jnp.where(due, lc, lcg)
        out = Outbox(
            valid=due[None],
            dst=dst[None],
            payload=(hop1 + 1).reshape(1, 1))
        if steady:
            left2 = left1                     # mongering never exhausts
            nxt2 = jnp.where(due, now + jnp.int64(gossip_interval), nxt1)
        else:
            left2 = left1 - due.astype(jnp.int32)
            nxt2 = jnp.where(due,
                             jnp.where(left2 > 0,
                                       now + jnp.int64(gossip_interval),
                                       jnp.int64(NEVER)),
                             nxt1)
        wake = jnp.where((left2 > 0) & alive, nxt2, jnp.int64(NEVER))
        return {"hop": hop1, "lcg": lcg1, "left": left2,
                "next": nxt2}, out, wake

    def init(i: int):
        seeded = i == 0
        return {
            "hop": jnp.int32(0 if seeded else -1),
            "lcg": jnp.int32((i * 2654435761) % (2**31 - 1) + 1),
            "left": jnp.int32(fanout if seeded else 0),
            "next": jnp.int64(bootstrap_us if seeded else NEVER),
        }, bootstrap_us if seeded else NEVER

    def init_batched(nn: int):
        ids = jnp.arange(nn, dtype=jnp.int32)
        seeded = ids == 0
        wake = jnp.where(seeded, jnp.int64(bootstrap_us),
                         jnp.int64(NEVER))
        states = {
            "hop": jnp.where(seeded, 0, -1).astype(jnp.int32),
            "lcg": ((ids.astype(jnp.int64) * 2654435761)
                    % (2**31 - 1) + 1).astype(jnp.int32),
            "left": jnp.where(seeded, fanout, 0).astype(jnp.int32),
            "next": wake,
        }
        return states, wake

    return Scenario(
        name=f"gossip-{n}",
        n_nodes=n,
        step=step_burst if burst else step,
        init=init,
        init_batched=init_batched,
        payload_width=1,
        max_out=fanout if burst else 1,
        mailbox_cap=mailbox_cap,
        commutative_inbox=True,
        # the adopt is a pure min-reduction over payloads: sender
        # identity is never read, so engines skip the mb_src scatter
        inbox_src=False,
        meta={"fanout": fanout, "end_us": end_us, "burst": burst},
    )


def gossip_links(*, median_us: int = ms(50), sigma: float = 0.6,
                 cap_us: int = sec(10), floor_us: int = 1) -> LinkModel:
    """The baseline config's lognormal latency model (net/delays.py).
    ``floor_us`` adds the propagation-delay floor that licenses
    windowed supersteps (LogNormalDelay.min_delay_us)."""
    return LogNormalDelay(median_us, sigma, cap_us, floor_us)
