"""Token-ring scenario — the framework's north-star workload.

Behavioral spec: `/root/reference/examples/token-ring/Main.hs` — N nodes
in a ring pass an incrementing token (:143-154); on receipt a node
notifies an observer (``noteToken``, 0-latency link) and, after a think
time (3 s), forwards ``v+1`` to its successor (:137-141); the observer
checks values arrive monotonically (:197-208); everything stops at a
deadline (20 s killThread, :125-127). Link latency for non-observer
messages is uniform 1–5 ms from a seeded RNG (:48-49, 73-77).

The continuation-per-node of the reference becomes an explicit state
machine (SURVEY.md §7): ``(cnt, val, send_at)`` per ring node and
``(prev, errs)`` on the observer, advanced by a pure jittable step.

Generalizations over the reference (used by bench configs):

- ``n_tokens`` initial tokens (reference: 1). With ``n_tokens == n_ring``
  every node forwards a token every superstep — the dense ring exchange
  that maps onto the TPU as a pure neighbor shift.
- a node holding several tokens forwards them one per think-interval
  (a bounded queue, like the reference's serialized worker thread).

Without the observer the scenario is *static-topology* (every node only
ever sends to its fixed successor) and *inbox-commutative* (the step
reduces over received tokens with max/sum), so it declares
``static_dst``/``commutative_inbox`` and runs on the sort/scatter-free
edge engine (interp/jax_engine/edge_engine.py). With the observer the
hub node has in-degree N, so it stays on the general engine.
"""

from __future__ import annotations

from typing import Tuple

from ..utils import jaxconfig  # noqa: F401

import jax
import jax.numpy as jnp
import numpy as np

from ..core.scenario import NEVER, Inbox, Outbox, Scenario
from ..core.time import Microsecond, ms, sec
from ..net.delays import FnDelay, LinkModel, UniformDelay
from ..obs.profiler import phased

__all__ = ["token_ring", "token_ring_links", "TOKEN", "NOTE"]

TOKEN, NOTE = 0, 1


@phased("tw.scenario", model="token_ring")
def token_ring(n_ring: int, *,
               n_tokens: int = 1,
               think_us: Microsecond = sec(3),
               bootstrap_us: Microsecond = sec(1),
               end_us: Microsecond = sec(20),
               with_observer: bool = True,
               mailbox_cap: int = 8) -> Scenario:
    """Build the token-ring scenario.

    Node ids ``0..n_ring-1`` form the ring; id ``n_ring`` is the
    observer (when enabled). Payload layout: ``[value, kind]``.
    """
    if n_tokens > n_ring:
        raise ValueError(f"n_tokens={n_tokens} exceeds n_ring={n_ring}")
    n_nodes = n_ring + (1 if with_observer else 0)
    obs_id = n_ring

    def step(state, inbox: Inbox, now, i, key):
        cnt, val, send_at = state["cnt"], state["val"], state["send_at"]
        kind = inbox.payload[:, 1]
        vin = inbox.payload[:, 0]
        tok_in = inbox.valid & (kind == TOKEN)

        # --- ring-node half (Main.hs:137-154) ---
        got = tok_in.any()
        k_in = jnp.sum(tok_in, dtype=jnp.int32)
        cnt1 = cnt + k_in
        vmax = jnp.max(jnp.where(tok_in, vin, jnp.int32(-2**31)))
        val1 = jnp.maximum(val, jnp.where(got, vmax, val))
        # arm the forward timer on first arrival (wait $ for 3 sec)
        send_at1 = jnp.where(got & (send_at >= NEVER),
                             now + jnp.int64(think_us), send_at)
        alive = now < jnp.int64(end_us)  # ≙ the 20 s killThread
        due = (send_at1 <= now) & (cnt1 > 0) & alive
        succ = ((i + 1) % jnp.int32(n_ring)).astype(jnp.int32)
        cnt2 = jnp.where(alive, cnt1 - due.astype(jnp.int32), 0)
        send_at2 = jnp.where(
            due, jnp.where(cnt2 > 0, now + jnp.int64(think_us),
                           jnp.int64(NEVER)),
            jnp.where(alive, send_at1, jnp.int64(NEVER)))

        if not with_observer:
            # lean static-topology form: one outbox slot, no observer
            # bookkeeping — the dense-ring regime of the bench
            out = Outbox(valid=due[None], dst=succ[None],
                         payload=jnp.stack([val1 + 1,
                                            jnp.int32(TOKEN)])[None])
            new_state = {"cnt": cnt2, "val": val1, "send_at": send_at2}
            return new_state, out, send_at2

        prev, errs = state["prev"], state["errs"]
        note_in = inbox.valid & (kind == NOTE)
        is_obs = i == obs_id
        W = inbox.valid.shape[0]  # inbox width is engine-dependent

        # --- observer half (Main.hs:197-208): monotone check in
        # inbox order ---
        def obs_scan(carry, j):
            p, e = carry
            v = vin[j]
            ok = note_in[j]
            e = e + jnp.where(ok & (v != p + 1), 1, 0).astype(jnp.int32)
            p = jnp.where(ok, v, p)
            return (p, e), None

        (prev1, errs1), _ = jax.lax.scan(
            obs_scan, (prev, errs), jnp.arange(W))

        # --- outbox: slot 0 = token to successor, slot 1 = note ---
        send_tok = due & ~is_obs
        send_note = got & ~is_obs & alive
        valid = jnp.stack([send_tok, send_note])
        dst = jnp.stack([succ, jnp.int32(obs_id)])
        payload = jnp.stack([
            jnp.stack([val1 + 1, jnp.int32(TOKEN)]),
            jnp.stack([vmax, jnp.int32(NOTE)]),
        ])
        out = Outbox(valid=valid, dst=dst, payload=payload)

        new_state = {
            "cnt": jnp.where(is_obs, cnt, cnt2),
            "val": jnp.where(is_obs, val, val1),
            "send_at": jnp.where(is_obs, jnp.int64(NEVER), send_at2),
            "prev": jnp.where(is_obs, prev1, prev),
            "errs": jnp.where(is_obs, errs1, errs),
        }
        wake = jnp.where(is_obs, jnp.int64(NEVER), send_at2)
        return new_state, out, wake

    def init(i: int) -> Tuple[dict, Microsecond]:
        is_ring = i < n_ring
        holds = is_ring and i < n_tokens
        send_at = bootstrap_us if holds else NEVER
        state = {
            "cnt": jnp.int32(1 if holds else 0),
            "val": jnp.int32(0),
            "send_at": jnp.int64(send_at),
        }
        if with_observer:
            state["prev"] = jnp.int32(0)
            state["errs"] = jnp.int32(0)
        return state, send_at if holds else NEVER

    def init_batched(n: int):
        ids = jnp.arange(n, dtype=jnp.int32)
        holds = (ids < n_ring) & (ids < n_tokens)
        send_at = jnp.where(holds, jnp.int64(bootstrap_us),
                            jnp.int64(NEVER))
        states = {
            "cnt": holds.astype(jnp.int32),
            "val": jnp.zeros(n, jnp.int32),
            "send_at": send_at,
        }
        if with_observer:
            states["prev"] = jnp.zeros(n, jnp.int32)
            states["errs"] = jnp.zeros(n, jnp.int32)
        return states, send_at

    if with_observer:
        static_dst = None
    else:
        static_dst = ((np.arange(n_ring, dtype=np.int32) + 1)
                      % n_ring).reshape(n_ring, 1)

    return Scenario(
        name=f"token-ring-{n_ring}",
        n_nodes=n_nodes,
        step=step,
        init=init,
        init_batched=init_batched,
        payload_width=2,
        max_out=2 if with_observer else 1,
        mailbox_cap=mailbox_cap,
        static_dst=static_dst,
        commutative_inbox=not with_observer,
        meta={"n_ring": n_ring, "obs_id": obs_id if with_observer else None,
              "think_us": think_us, "end_us": end_us},
    )


def token_ring_links(n_ring: int, *, lo_us: int = ms(1), hi_us: int = ms(5),
                     with_observer: bool = True) -> LinkModel:
    """The reference's ``Delays``: observer-bound messages connect in 0
    (clamped to the 1 µs floor), everything else uniform 1–5 ms
    (examples/token-ring/Main.hs:48-49, 73-77)."""
    if not with_observer:
        return UniformDelay(lo_us, hi_us)
    obs_id = n_ring
    uni = UniformDelay(lo_us, hi_us)

    def fn(src, dst, t, key):
        d, drop = uni.sample(src, dst, t, key)
        return jnp.where(dst == obs_id, jnp.int64(0), d), drop

    return FnDelay(fn)
