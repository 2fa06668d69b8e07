"""Socket-state — the *batched* twin of BASELINE config 3 (the
reference's per-socket user-state example,
`/root/reference/examples/socket-state/Main.hs:63-106`).

The net-stack world (models/socket_state_net.py) runs the protocol
over the full transport: a server counts requests per connection via
per-socket user state; each client sends ``Ping cid`` once per
interval, continuing with probability 2/3 per round (the seeded
``ruskaRuletka`` draw, Main.hs:105-106), then closes; the listener
stops at a deadline. This module is the same protocol as a
state-machine scenario the batched engines (and the host oracle) can
execute — closing the one baseline config that had no batched twin
and no parity-artifact presence.

World mapping (and its honest limits):

- node 0 ≙ the server; node ``cid`` (1..C) ≙ client ``cid``. One
  client keeps one connection, so the reference's *per-socket*
  counters are per-client counters — the server state carries
  ``cnt[C]``.
- the roulette is drawn host-side at build time with the net twin's
  exact RNG (``random.Random((seed << 8) | cid)``), so both worlds
  schedule the same number of sends per client by construction; what
  the cross-world leg then *checks* is the delivery/counting machinery
  — every ping that arrives before the listener deadline is counted,
  on the right counter, in both worlds
  (tests/test_cross_world_socket_state.py).
- the twin abstracts the established-connection steady state; the net
  world's timeline additionally contains transport session setup, so
  the cross-world law here is value-stream equality (final counters +
  send counts), not the µs-for-µs timeline law the gossip/ping-pong
  twins support.

The listener deadline maps to a ``now < server_life_us`` counting
gate (≙ ``invoke (after 10 sec) stop``, Main.hs:78): late deliveries
still fire the server node, they are just no longer counted — exactly
a stopped listener.
"""

from __future__ import annotations

import random as _random

from ..utils import jaxconfig  # noqa: F401

import jax.numpy as jnp

from ..core.scenario import NEVER, Inbox, Outbox, Scenario
from ..core.time import Microsecond

__all__ = ["socket_state", "roulette_sends"]


def roulette_sends(n_clients: int, seed: int):
    """Per-client send counts from the net twin's exact seeded
    roulette (``while rng.randrange(3) > 0`` —
    models/socket_state_net.py client(), ≙ ``whileM ruskaRuletka``)."""
    sends = []
    for cid in range(1, n_clients + 1):
        rng = _random.Random((seed << 8) | cid)
        k = 0
        while rng.randrange(3) > 0:
            k += 1
        sends.append(k)
    return sends


def socket_state(n_clients: int = 3, *,
                 send_interval_us: Microsecond = 50_000,
                 server_life_us: Microsecond = 600_000,
                 seed: int = 0,
                 mailbox_cap: int = 8) -> Scenario:
    """Build the batched socket-state scenario (module docstring).
    ``seed`` keys the roulette exactly as the net twin's ``seed``."""
    if n_clients < 1:
        raise ValueError("socket_state needs at least one client")
    n = n_clients + 1
    C = n_clients
    sends = roulette_sends(n_clients, seed)

    def step(state, inbox: Inbox, now, i, key):
        cnt, left, nxt = state["cnt"], state["left"], state["next"]
        is_server = i == 0
        listening = now < jnp.int64(server_life_us)

        # count each delivered ping on its client's counter (≙
        # counterTic on the socket's user state, Main.hs:91-93).
        # Invalid slots are masked to the out-of-range index C, which
        # mode="drop" discards — jnp scatters WRAP negative indices
        # even under mode="drop", so payload-0 slots must not be left
        # to index -1. The reduction is a per-counter sum:
        # commutative, slot-order free.
        cids = jnp.where(inbox.valid, inbox.payload[:, 0] - 1, C)
        inc = jnp.zeros((C,), jnp.int32).at[cids].add(
            inbox.valid.astype(jnp.int32), mode="drop")
        cnt1 = jnp.where(is_server & listening, cnt + inc, cnt)

        # one ping per interval while the roulette allows (the draw
        # count is in-state; the schedule is the net twin's
        # Wait(interval)-then-send loop)
        due = (left > 0) & (nxt <= now) & ~is_server
        out = Outbox(
            valid=due[None],
            dst=jnp.zeros((1,), jnp.int32),
            payload=i.astype(jnp.int32).reshape(1, 1))
        left1 = left - due.astype(jnp.int32)
        nxt1 = jnp.where(due, nxt + jnp.int64(send_interval_us), nxt)
        wake = jnp.where(left1 > 0, nxt1, jnp.int64(NEVER))
        return {"cnt": cnt1, "left": left1, "next": nxt1}, out, wake

    def init(i: int):
        left = 0 if i == 0 else sends[i - 1]
        first = send_interval_us if left > 0 else NEVER
        return {
            "cnt": jnp.zeros((C,), jnp.int32),
            "left": jnp.int32(left),
            "next": jnp.int64(first),
        }, first

    def init_batched(nn: int):
        ids = jnp.arange(nn, dtype=jnp.int32)
        left = jnp.asarray([0] + sends, jnp.int32)
        first = jnp.where(left > 0, jnp.int64(send_interval_us),
                          jnp.int64(NEVER))
        states = {
            "cnt": jnp.zeros((nn, C), jnp.int32),
            "left": left,
            "next": first,
        }
        del ids
        return states, first

    return Scenario(
        name=f"socket-state-{n}",
        n_nodes=n,
        step=step,
        init=init,
        init_batched=init_batched,
        payload_width=1,
        max_out=1,
        mailbox_cap=mailbox_cap,
        commutative_inbox=True,
        # the counter key travels in the payload; sender identity is
        # never read (inbox.src elided stack-wide)
        inbox_src=False,
        meta={"sends": sends, "send_interval_us": send_interval_us,
              "server_life_us": server_life_us},
    )
