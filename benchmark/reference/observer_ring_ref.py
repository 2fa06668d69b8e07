"""Plain reference of the token ring with its observer hub, written from
the scenario's definition (input-output-hk/time-warp v1.1.1.1
``examples/token-ring/Main.hs:104-208``) and the emulator's delivery
contract: numpy, superstep by superstep, no engine, nothing of the
program imported.

``n`` ring nodes and one hub (node ``n``). A ring node that receives a
token keeps the larger of its value and the token's, reports the token
to the hub (``noteToken``: a note carrying the token's value) and, a
think time after the first token it holds arrived, forwards ``value +
1`` to its successor. The hub checks every note against the one before
it, in the order they reach it (``v == prev + 1``, ``Main.hs:197-208``),
and counts the misses in ``errs``.

What the emulator adds, and what makes the hub a bounded one:

- every node has a mailbox of ``mailbox_cap`` slots. A message sent is
  appended to its destination's mailbox if a slot is free and is
  dropped and counted in ``overflow`` otherwise. Messages of one
  superstep are appended in arrival order: by sender id, then by the
  sender's outbox slot (0 the token, 1 the note);
- a superstep runs at the least pending time ``t`` (a timer or a
  message's due time). Every node with something pending at ``t``
  fires: it is handed the messages due, ordered by due time and then
  by the slot they stood in, and they leave its mailbox, the rest
  closing up in order;
- every link has the same fixed latency, the hub's too.

With every node holding a token a cycle is three supersteps: the timers
fire and ``n`` tokens are sent; the tokens arrive, and ``n`` notes are
sent to the hub at one instant, of which the first ``mailbox_cap`` by
sender id are kept and ``n - mailbox_cap`` are dropped and counted; the
hub fires alone on what it kept. So a cycle delivers ``n + mailbox_cap``
messages and counts ``n - mailbox_cap`` dropped notes, and no token is
ever lost.

"No timer" and "empty slot" are -1 here, whatever the program's are.
"""

import numpy as np

TOKEN, NOTE = 0, 1
NONE = -1                       # no timer armed, no message in the slot
_LEAST = {np.dtype(np.int32): -2**31, np.dtype(np.int16): -2**15}


class ObserverRing:
    """One stream from the seeded values ``val0`` (one a ring node),
    advanced on demand. ``dtype`` is the integer type of the token
    values (int32 as configured; a control computes in int16).
    ``hub_descending`` takes the hub's arrivals of a superstep in
    descending sender order instead (the other control: the order is
    the contract)."""

    def __init__(self, params, val0, dtype=np.int32, hub_descending=False):
        self.n = n = int(params["n_ring"])
        if int(params["n_nodes"]) != n + 1 or not params["with_observer"]:
            raise ValueError("the reference is of the ring with its hub: "
                             "n_nodes = n_ring + 1")
        if int(params["n_tokens"]) != n or val0.shape != (n,):
            raise ValueError("every ring node holds a token, and val0 "
                             "gives each its first value")
        self.N, self.K = n + 1, int(params["mailbox_cap"])
        self.think = int(params["think_us"])
        self.end = int(params["end_us"])
        self.delay = max(int(params["link"]["delay_us"]), 1)
        self.dtype = np.dtype(dtype)
        self.descending = bool(hub_descending)
        N, K = self.N, self.K
        ring = np.arange(N) < n
        # per node; the hub's row holds no token and no timer
        self.cnt = ring.astype(np.int32)
        self.val = np.zeros(N, self.dtype)
        self.val[:n] = val0.astype(self.dtype)
        self.send_at = np.where(ring, int(params["bootstrap_us"]),
                                NONE).astype(np.int64)
        self.wake = self.send_at.copy()
        self.prev, self.errs = 0, 0
        # the mailboxes, [slot, node], filled from slot 0 with no gap
        self.due = np.full((K, N), NONE, np.int64)
        self.src = np.zeros((K, N), np.int32)
        self.word = np.zeros((K, N), self.dtype)      # the token's value
        self.kind = np.zeros((K, N), np.int32)
        self.held = np.zeros(N, np.int64)
        self.delivered = self.overflow = self.steps = self.time = 0
        self.fan_in_peak = 0

    def _wrapped(self, x: int) -> int:
        """``x`` as the token values' integer type holds it."""
        least = _LEAST[self.dtype]
        return (x - least) % (-2 * least) + least

    # -- one superstep ------------------------------------------------------

    def _step(self):
        n, N, K = self.n, self.N, self.K
        full = self.due != NONE
        far = np.iinfo(np.int64).max
        pending = np.minimum(
            np.where(self.wake == NONE, far, self.wake),
            np.where(full, self.due, far).min(axis=0))
        t = int(pending.min())
        if t == far:
            return False
        fire = pending == t

        # what each firing node is handed: the messages due, by due
        # time and then by slot; the rest close up in slot order. Only
        # a node that holds mail has anything to hand out or close up
        mail = np.flatnonzero(full.any(axis=0))
        due, src, word, kind = (x[:, mail] for x in (
            self.due, self.src, self.word, self.kind))
        held = due != NONE
        handed = held & (due <= t) & fire[mail]
        order = np.argsort(np.where(handed, due, far), axis=0, kind="stable")
        got = np.zeros((K, N), bool)
        in_word = np.zeros((K, N), self.dtype)
        in_kind = np.zeros((K, N), np.int32)
        got[:, mail] = np.take_along_axis(handed, order, axis=0)
        in_word[:, mail] = np.take_along_axis(word, order, axis=0)
        in_kind[:, mail] = np.take_along_axis(kind, order, axis=0)
        stays = held & ~handed
        order = np.argsort(~stays, axis=0, kind="stable")
        kept = np.take_along_axis(stays, order, axis=0)
        for plane, x, empty in ((self.due, due, NONE), (self.src, src, 0),
                                (self.word, word, 0), (self.kind, kind, 0)):
            plane[:, mail] = np.where(
                kept, np.take_along_axis(x, order, axis=0), empty)
        self.held[mail] = kept.sum(axis=0)
        self.delivered += int(handed.sum())

        # the hub: every note against the one before it, in that order
        if fire[n]:
            for j in range(K):
                if got[j, n] and in_kind[j, n] == NOTE:
                    v = int(in_word[j, n])
                    self.errs += v != self._wrapped(self.prev + 1)
                    self.prev = v
            self.wake[n] = NONE

        # the ring nodes that fire
        r = fire.copy()
        r[n] = False
        token = got & (in_kind == TOKEN)
        arrived = token.any(axis=0) & r
        least = _LEAST[self.dtype]
        top = np.where(token, in_word, least).max(axis=0).astype(self.dtype)
        cnt = self.cnt + np.where(r, token.sum(axis=0), 0).astype(np.int32)
        val = np.where(arrived, np.maximum(self.val, top), self.val)
        send_at = np.where(arrived & (self.send_at == NONE),
                           t + self.think, self.send_at)
        alive = np.bool_(t < self.end)
        forward = r & (send_at != NONE) & (send_at <= t) & (cnt > 0) & alive
        cnt = np.where(r, np.where(alive, cnt - forward, 0), cnt)
        again = np.where(cnt > 0, t + self.think, NONE)
        send_at = np.where(forward, again,
                           np.where(r & ~alive, NONE, send_at))
        self.cnt, self.val, self.send_at = cnt.astype(np.int32), val, send_at
        # a timer never fires at the instant that set it
        self.wake = np.where(
            r, np.where(send_at == NONE, NONE, np.maximum(send_at, t + 1)),
            self.wake)

        # what they send, in arrival order: by sender, then the token
        # (outbox slot 0) before the note (slot 1)
        senders = np.arange(N)
        sends = np.stack([forward, arrived & alive], axis=1)       # [N, 2]
        dst = np.stack([(senders + 1) % n, np.full(N, n)], axis=1)
        word = np.stack([(val + self.dtype.type(1)).astype(self.dtype), top],
                        axis=1)
        kind = np.broadcast_to(np.array([TOKEN, NOTE], np.int32), (N, 2))
        src = np.broadcast_to(senders[:, None], (N, 2))
        m = sends.reshape(-1)
        dst, word, kind, src = (x.reshape(-1)[m]
                                for x in (dst, word, kind, src))
        if self.descending:
            # the control: the hub's arrivals of this superstep
            # turned round, everyone else's as they were
            to_hub = np.flatnonzero(dst == n)
            for x in (word, kind, src):
                x[to_hub] = x[to_hub[::-1]]
        by_dst = np.argsort(dst, kind="stable")
        dst, word, kind, src = (x[by_dst] for x in (dst, word, kind, src))
        first = np.searchsorted(dst, dst, side="left")
        rank = np.arange(dst.size) - first
        if dst.size:
            self.fan_in_peak = max(self.fan_in_peak, int(rank.max()) + 1)
        slot = self.held[dst] + rank
        fits = slot < K
        self.overflow += int((~fits).sum())
        s, d = slot[fits], dst[fits]
        self.due[s, d] = t + self.delay
        self.src[s, d] = src[fits]
        self.word[s, d] = word[fits]
        self.kind[s, d] = kind[fits]
        self.held = self.held + np.bincount(d, minlength=N)

        self.steps += 1
        self.time = t
        return True

    # -- what the comparison asks ---------------------------------------------

    def run_to(self, steps):
        """The facts after ``steps`` supersteps of this stream (it only
        runs forwards), each a copy."""
        if steps < self.steps:
            raise ValueError("the reference stream only runs forwards")
        while self.steps < steps and self._step():
            pass
        return self.facts()

    def facts(self):
        return {
            "cnt": self.cnt.copy(), "val": self.val.copy(),
            "send_at": self.send_at.copy(), "wake": self.wake.copy(),
            "hub_prev": self.prev, "hub_errs": self.errs,
            "mailbox_due": self.due.copy(), "mailbox_src": self.src.copy(),
            "mailbox_word": self.word.copy(),
            "mailbox_kind": self.kind.copy(),
            "delivered": self.delivered, "overflow": self.overflow,
            "steps": self.steps, "time": self.time,
            "fan_in_peak": self.fan_in_peak,
        }
