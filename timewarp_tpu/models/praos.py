"""Ouroboros-Praos slot-leader consensus — BASELINE.json config 5
("Ouroboros-Praos slot-leader consensus, 1M stake nodes").

The abstract shape of Praos (the protocol the reference library was
built to serve at IOHK): time is divided into fixed slots; in every
slot each stake node independently wins slot leadership with
probability ``f`` from a private VRF draw; a leader extends its
current best chain by one block and diffuses the new tip; nodes adopt
the longest tip they hear and relay it onward. Chain growth and fork
resolution emerge from message latency vs slot length.

TPU mapping: leadership is the per-(node, slot-instant) counter-based
entropy the engines already derive (``fire_bits``; the scenario
declares ``needs_key``) — an integer threshold compare, bit-exact on
every backend. Tips diffuse to ``fanout`` pseudo-random peers per
adoption (dynamic destinations → general engine; sharded all_to_all).
The inbox reduces commutatively (max over tip length).

Payload layout: ``[chain_len, relayer]`` — slot 1 carries the id of
the node that *relayed* this tip (re-stamped at every hop), not the
block's original minter.
"""

from __future__ import annotations

from ..utils import jaxconfig  # noqa: F401

import jax.numpy as jnp

from ..core.scenario import NEVER, Inbox, Outbox, Scenario
from ..core.time import Microsecond, ms, sec
from ..obs.profiler import phased
from .peers import distinct_mask, lcg_peers

__all__ = ["praos"]


@phased("tw.scenario", model="praos")
def praos(n: int, *,
          slot_us: Microsecond = sec(1),
          n_slots: int = 20,
          leader_prob: float = 0.05,
          stake=None,
          fanout: int = 8,
          relay_interval: Microsecond = ms(2),
          burst: bool = False,
          mailbox_cap: int = 16) -> Scenario:
    """Build the Praos scenario. Quiesces after ``n_slots`` slots once
    the last relay bursts drain. ``leader_prob`` is the per-slot
    per-node leadership probability at stake weight 1 (the aggregate
    block rate is ``sum(stake) * leader_prob`` per slot — keep it ≲ a
    few for realistic fork behavior at scale). ``stake`` (optional
    int array [n]) weights each node's leadership linearly — the
    "stake nodes" of the baseline config; None = equal stake 1.

    ``burst=True`` pushes a fresh tip to all ``fanout`` peers in ONE
    firing (outbox width ``fanout``; ``relay_interval`` unused) — how
    a real node floods its peer set over parallel TCP connections, and
    the form that lets windowed supersteps batch diffusion (a paced
    one-send-per-interval chain is a per-node *sequential* dependency
    no batched executor can collapse). ``burst=False`` keeps the paced
    bandwidth-limited model."""
    import numpy as _np

    if n < 2:
        raise ValueError(f"praos needs n >= 2 nodes, got {n} "
                         "(peer draw divides by n - 1)")

    if stake is None:
        thr_arr = _np.full(
            n, min(int(leader_prob * 4294967296.0), 2**32 - 1),
            _np.uint32)
    else:
        stake = _np.asarray(stake)
        if stake.shape != (n,) or (stake < 0).any():
            raise ValueError("stake must be a non-negative int array [n]")
        thr_arr = _np.minimum(
            stake.astype(_np.float64) * leader_prob * 4294967296.0,
            2**32 - 1).astype(_np.uint32)
    # the threshold rides IN THE STATE, not as a closed-over [n] table:
    # a vmapped `table[i]` lowers to an N-wide gather, and even
    # iota-indexed gathers cost ~9 ns/element on this chip (~9 ms at
    # 1M nodes per superstep — docs/engines.md per-op cost table); a state leaf
    # is a pure elementwise read

    def step_burst(state, inbox: Inbox, now, i, key):
        best, lcg = state["best"], state["lcg"]
        slot, nslot = state["slot"], state["nslot"]

        # adopt the longest incoming tip (commutative max)
        tin = jnp.max(jnp.where(inbox.valid, inbox.payload[:, 0],
                                jnp.int32(-1)))
        adopt = tin > best
        best1 = jnp.where(adopt, tin, best)

        # slot boundary: private stake-weighted leadership draw
        due_slot = (slot < jnp.int32(n_slots)) & (nslot <= now)
        b0, _ = key
        leader = due_slot & (b0 < state["thr"])
        best2 = best1 + leader.astype(jnp.int32)
        slot1 = slot + due_slot.astype(jnp.int32)
        nslot1 = jnp.where(due_slot, nslot + jnp.int64(slot_us), nslot)

        # a fresh tip (adopted or minted) floods all peers at once:
        # `fanout` chained LCG draws, committed only when fresh
        fresh = adopt | leader
        lc, dsts = lcg_peers(lcg, i, n, fanout)
        lcg1 = jnp.where(fresh, lc, lcg)
        pay = jnp.stack([best2, i])
        # duplicate peer draws are masked (one push per peer
        # connection per tip — peers.distinct_mask)
        out = Outbox(
            valid=fresh & distinct_mask(dsts),
            dst=jnp.stack(dsts),
            payload=jnp.broadcast_to(pay, (fanout, 2)))

        wake = jnp.where(slot1 < jnp.int32(n_slots), nslot1,
                         jnp.int64(NEVER))
        return {"best": best2, "lcg": lcg1, "slot": slot1,
                "nslot": nslot1, "thr": state["thr"]}, out, wake

    def step(state, inbox: Inbox, now, i, key):
        best, lcg = state["best"], state["lcg"]
        left, nrelay = state["left"], state["nrelay"]
        slot, nslot = state["slot"], state["nslot"]

        # adopt the longest incoming tip (commutative max)
        tin = jnp.max(jnp.where(inbox.valid, inbox.payload[:, 0],
                                jnp.int32(-1)))
        adopt = tin > best
        best1 = jnp.where(adopt, tin, best)

        # slot boundary: private stake-weighted leadership draw from
        # the firing entropy (≙ the VRF threshold check)
        due_slot = (slot < jnp.int32(n_slots)) & (nslot <= now)
        b0, _ = key
        leader = due_slot & (b0 < state["thr"])
        best2 = best1 + leader.astype(jnp.int32)
        slot1 = slot + due_slot.astype(jnp.int32)
        nslot1 = jnp.where(due_slot, nslot + jnp.int64(slot_us), nslot)

        # a new tip (adopted or minted) re-arms the relay burst
        fresh = adopt | leader
        left1 = jnp.where(fresh, jnp.int32(fanout), left)
        nrelay1 = jnp.where(fresh, now + jnp.int64(relay_interval), nrelay)

        # one relay send per firing of the relay timer (dst observable
        # only when due_relay — outbox validity gates it)
        due_relay = (left1 > 0) & (nrelay1 <= now)
        lc, (dst,) = lcg_peers(lcg, i, n, 1)
        lcg1 = jnp.where(due_relay, lc, lcg)
        out = Outbox(
            valid=due_relay[None],
            dst=dst[None],
            payload=jnp.stack([best2, i])[None])
        left2 = left1 - due_relay.astype(jnp.int32)
        nrelay2 = jnp.where(due_relay,
                            now + jnp.int64(relay_interval), nrelay1)

        slot_wake = jnp.where(slot1 < jnp.int32(n_slots), nslot1,
                              jnp.int64(NEVER))
        relay_wake = jnp.where(left2 > 0, nrelay2, jnp.int64(NEVER))
        wake = jnp.minimum(slot_wake, relay_wake)
        return {"best": best2, "lcg": lcg1, "left": left2,
                "nrelay": nrelay2, "slot": slot1,
                "nslot": nslot1, "thr": state["thr"]}, out, wake

    def init(i: int):
        st = {
            "best": jnp.int32(0),
            "lcg": jnp.int32((i * 2654435761) % (2**31 - 1) + 1),
            "slot": jnp.int32(0),
            "nslot": jnp.int64(slot_us),
            "thr": jnp.uint32(thr_arr[i]),
        }
        if not burst:
            st["left"] = jnp.int32(0)
            st["nrelay"] = jnp.int64(NEVER)
        return st, slot_us

    def init_batched(nn: int):
        ids = jnp.arange(nn, dtype=jnp.int32)
        wake = jnp.full(nn, slot_us, jnp.int64)
        states = {
            "best": jnp.zeros(nn, jnp.int32),
            "lcg": ((ids.astype(jnp.int64) * 2654435761)
                    % (2**31 - 1) + 1).astype(jnp.int32),
            "slot": jnp.zeros(nn, jnp.int32),
            "nslot": jnp.full(nn, slot_us, jnp.int64),
            "thr": jnp.asarray(thr_arr),
        }
        if not burst:
            states["left"] = jnp.zeros(nn, jnp.int32)
            states["nrelay"] = jnp.full(nn, NEVER, jnp.int64)
        return states, wake

    return Scenario(
        name=f"praos-{n}",
        n_nodes=n,
        step=step_burst if burst else step,
        init=init,
        init_batched=init_batched,
        payload_width=2,
        max_out=fanout if burst else 1,
        mailbox_cap=mailbox_cap,
        needs_key=True,
        commutative_inbox=True,
        # the adopt is a pure max-reduction over tip lengths and the
        # relayer id travels in payload[:, 1] — inbox.src is never
        # read, so engines skip the mb_src scatter (docs/engines.md "Measured on a v5e")
        inbox_src=False,
        meta={"slot_us": slot_us, "n_slots": n_slots,
              "leader_prob": leader_prob, "fanout": fanout,
              "burst": burst},
    )
