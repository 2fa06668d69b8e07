"""Pure jittable fault masks: ``(FaultTables, virtual time) -> masks``.

Every function here is elementwise/broadcast jax.numpy over the
fixed-shape tables of :mod:`timewarp_tpu.faults.schedule` — no host
control flow on traced values, so the same code runs inside the solo
superstep, under ``vmap`` for a :class:`~timewarp_tpu.faults.schedule.
FaultFleet` (tables carry a leading world axis), and under
``shard_map`` (masks are per-node elementwise; node ids are global).
Zero-row tables short-circuit at trace time (shapes are static), so an
engine built without a given fault kind compiles the exact pre-fault
program for that stage.

The one piece of *state* faults need is ``restart_done: bool[C]`` —
whether each crash row's injected restart firing has been consumed.
Everything else is a pure function of the schedule and the clock
(injecting restarts statelessly would re-fire a rebooted node whose
window start the epoch has not yet crossed — windowed supersteps run
per-node instants ahead of the epoch).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..core.scenario import NEVER
from .schedule import dst_word_layout

__all__ = [
    "defer_next", "restart_fire", "consume_restarts",
    "cut_mask", "down_mask", "degrade", "skewed_step",
    "window_floor",
    "own_lanes", "src_link_bits", "dst_words", "cut_mask_at",
    "link_aff_bits", "degrade_bits",
]


def _crash_active(ft):
    return ft.crash_up > ft.crash_down            # [C] (inert rows off)


def defer_next(ft, node_ids, node_next, restart_done):
    """Crash-adjusted next-event times: an event inside the node's
    down window slides to ``t_up`` (single pass — overlapping windows
    per node are a TW502 lint error), and every unconsumed
    ``reset_state`` row injects a restart firing at exactly ``t_up``
    (the reboot event the reset anchors to)."""
    if ft.crash_node.shape[0] == 0:
        return node_next
    m = (ft.crash_node[:, None] == node_ids[None, :]) \
        & _crash_active(ft)[:, None]                        # [C, N]
    x = node_next[None, :]
    inwin = m & (ft.crash_down[:, None] <= x) & (x < ft.crash_up[:, None])
    deferred = jnp.max(jnp.where(inwin, ft.crash_up[:, None], x),
                       axis=0)
    pend = m & ft.crash_reset[:, None] & ~restart_done[:, None]
    inject = jnp.min(jnp.where(pend, ft.crash_up[:, None],
                               jnp.int64(NEVER)), axis=0)
    return jnp.minimum(deferred, inject)


def restart_fire(ft, fire, now_vec, node_ids, restart_done):
    """The restart firings happening *this* superstep: a fired node
    whose instant equals an unconsumed reset row's ``t_up``. Returns
    ``(reset_now bool[N], purge_before int64[N])`` — reset the node's
    state before its step runs, and purge mailbox entries with deliver
    time < ``purge_before`` (memory the reboot lost; 0 = none)."""
    n = node_ids.shape[0]
    if ft.crash_node.shape[0] == 0:
        return (jnp.zeros((n,), bool), jnp.zeros((n,), jnp.int64))
    m = (ft.crash_node[:, None] == node_ids[None, :]) \
        & _crash_active(ft)[:, None]
    hit = m & ft.crash_reset[:, None] & ~restart_done[:, None] \
        & fire[None, :] & (now_vec[None, :] == ft.crash_up[:, None])
    reset_now = jnp.any(hit, axis=0)
    purge_before = jnp.max(
        jnp.where(hit, ft.crash_down[:, None], jnp.int64(0)), axis=0)
    return reset_now, purge_before


def consume_restarts(ft, fire, now_vec, node_ids, restart_done):
    """``restart_done`` after this superstep: a row is consumed when
    its node fires at exactly its ``t_up`` (the injected restart — or
    a coincident legitimate event; either way the reboot happened)."""
    if ft.crash_node.shape[0] == 0:
        return restart_done
    m = (ft.crash_node[:, None] == node_ids[None, :]) \
        & _crash_active(ft)[:, None]
    hit = m & ft.crash_reset[:, None] & fire[None, :] \
        & (now_vec[None, :] == ft.crash_up[:, None])
    return restart_done | jnp.any(hit, axis=1)


def _flat(*xs):
    """Broadcast operands to a common shape and flatten — the mask
    bodies below work on 1-D lanes, callers pass any (mutually
    broadcastable) rank: [S] message lanes, [M, N] outbox planes,
    scalar times against [N] node vectors."""
    bs = jnp.broadcast_arrays(*(jnp.asarray(x) for x in xs))
    return bs[0].shape, tuple(b.reshape(-1) for b in bs)


def cut_mask(ft, src, dst, t_send):
    """True where a message crosses a live partition cut: some
    partition row active at the *send instant* puts src and dst in
    different (non-absent) groups. ``src``/``dst`` are global node
    ids; out-of-range values must be pre-masked by the caller (indices
    are clipped here only for gather safety)."""
    shape, (src, dst, t) = _flat(src, dst, t_send)
    if ft.part_group.shape[0] == 0:
        return jnp.zeros(shape, bool)
    n = ft.part_group.shape[-1]
    gs = ft.part_group[:, jnp.clip(src, 0, n - 1)]         # [Pn, S]
    gd = ft.part_group[:, jnp.clip(dst, 0, n - 1)]
    act = (ft.part_start[:, None] <= t[None, :]) \
        & (t[None, :] < ft.part_end[:, None])
    cut = act & (gs != gd) & (gs >= 0) & (gd >= 0)
    return jnp.any(cut, axis=0).reshape(shape)


def down_mask(ft, node, t):
    """True where ``node`` is inside a crash window at time ``t`` —
    the routing stage drops messages whose *deliver* time lands in the
    destination's down window (the NIC is off)."""
    shape, (node, t) = _flat(node, t)
    if ft.crash_node.shape[0] == 0:
        return jnp.zeros(shape, bool)
    m = (ft.crash_node[:, None] == node[None, :]) \
        & _crash_active(ft)[:, None]
    win = (ft.crash_down[:, None] <= t[None, :]) \
        & (t[None, :] < ft.crash_up[:, None])
    return jnp.any(m & win, axis=0).reshape(shape)


def degrade(ft, delay, src, dst, t_send):
    """Apply every live link-degradation window to the sampled delays:
    ``delay' = (delay * num) // den + extra`` for affected messages.
    Rows compose in table order (a static Python loop — L is a shape).
    Integer arithmetic throughout: bit-exact on every backend."""
    L = ft.link_start.shape[0]
    if L == 0:
        return delay
    shape, (delay, src, dst, t) = _flat(delay, src, dst, t_send)
    n = ft.link_src.shape[-1]
    sc = jnp.clip(src, 0, n - 1)
    dc = jnp.clip(dst, 0, n - 1)
    for i in range(L):
        aff = (ft.link_start[i] <= t) & (t < ft.link_end[i]) \
            & ft.link_src[i][sc] & ft.link_dst[i][dc]
        delay = jnp.where(
            aff, (delay * ft.link_num[i]) // ft.link_den[i]
            + ft.link_add[i], delay)
    return delay.reshape(shape)


# -- the same masks, each table read where it lies ------------------------
#
# ``cut_mask`` and ``degrade`` above look every table up at both ends
# of every lane: they are the specification (the oracle and the edge
# engine call them; tests/test_fault_node_lanes_law.py holds the forms
# below to them lane for lane). Where a lane's source is the lane's own
# node (the outbox planes of engine.py), the source's side of a table
# is the table itself, and what a message needs of its destination is
# one packed word (schedule.py ``pack_dst_word``): one look-up a
# message, and the link rows' verdicts as bits that ride the message.

def own_lanes(table, node_ids):
    """The columns of a per-node ``table`` (``[..., n_global]``) that
    are the node lanes' own: the table as it lies where the lanes are
    all its nodes, and where they are a device's contiguous share
    (``node_ids`` an offset iota, comm.node_ids()), that share's
    slice. No gather either way."""
    n = node_ids.shape[0]
    if table.shape[-1] == n:
        return table
    return jax.lax.dynamic_slice_in_dim(table, node_ids[0], n, axis=-1)


def src_link_bits(ft, node_ids, t_node, rows: int):
    """int32[N]: bit ``i`` (``i < rows``) says that node lane ``n``,
    sending at its instant ``t_node[n]``, is a source of link row
    ``i`` inside that row's window. The whole time test of
    ``degrade`` is a fact of the sender, so it is made here, on the
    node lanes, once a superstep."""
    bits = jnp.zeros(node_ids.shape, jnp.int32)
    for i in range(rows):
        live = own_lanes(ft.link_src[i], node_ids) \
            & (ft.link_start[i] <= t_node) & (t_node < ft.link_end[i])
        bits = bits | (live.astype(jnp.int32) << i)
    return bits


def dst_words(ft, dst):
    """``ft.dst_word`` at the destinations: int32[R, *dst.shape], the
    one look-up through an index a message's masks need (out-of-range
    values are clipped for the gather's safety and must be masked by
    the caller, as for ``cut_mask``)."""
    R, n = ft.dst_word.shape
    if R == 0:   # a host constant: nothing enters the program
        return np.zeros((0,) + jnp.shape(dst), np.int32)
    return jnp.take(ft.dst_word, jnp.clip(dst, 0, n - 1), axis=-1)


def _group_bits(ft) -> int:
    """The width of a packed word's group field (``dst_word_layout``)."""
    return dst_word_layout(ft.dst_word.shape[-1], 0)[0]


def _group_field(ft, words):
    """The group fields of packed words: rank plus one, 0 = absent."""
    return words[:ft.part_group.shape[0]] \
        & jnp.int32((1 << _group_bits(ft)) - 1)


def cut_mask_at(ft, node_ids, at_dst, t_send):
    """``cut_mask`` on lanes that are the node lanes ``node_ids`` M
    times over (``[M, N]`` planes or ``M * N`` flat, source-minor: the
    outbox's layouts), from the destinations' packed words ``at_dst``
    (:func:`dst_words` of those lanes) and the send instants
    (broadcastable to the lanes). A source's group is its own word's,
    read in place (:func:`own_lanes`); a group's rank stands for the
    group: ranks are equal where groups are, and both ends read the
    same numbering."""
    lanes = at_dst.shape[1:]
    Pn, n = ft.part_group.shape[0], node_ids.shape[0]
    if Pn == 0:
        return jnp.zeros(lanes, bool)
    gs = _group_field(ft, own_lanes(ft.dst_word, node_ids))[:, None, :]
    gd = _group_field(ft, at_dst).reshape(Pn, -1, n)
    t = jnp.broadcast_to(t_send, lanes).reshape(-1, n)
    act = (ft.part_start[:, None, None] <= t) \
        & (t < ft.part_end[:, None, None])
    return jnp.any(act & (gs != gd) & (gs > 0) & (gd > 0),
                   axis=0).reshape(lanes)


def link_aff_bits(ft, src_bits, at_dst0, rows: int):
    """int32 per lane: bit ``i`` (``i < rows``) is ``degrade``'s
    ``aff`` of link row ``i``: the senders' :func:`src_link_bits`
    (``[n]``) against the ``link_dst`` bits of the destinations' words
    (row 0 of :func:`dst_words`, on lanes that are the senders M times
    over). Never negative (``rows`` is at most ``dst_word_layout``'s
    ``packed``, under 32), so it can ride the spare high bits of a
    valid destination id."""
    dst_bits = (at_dst0 >> _group_bits(ft)) & jnp.int32((1 << rows) - 1)
    return (dst_bits.reshape(-1, src_bits.shape[0])
            & src_bits).reshape(at_dst0.shape)


def degrade_bits(ft, delay, aff, rows: int, src=None, dst=None,
                 t_send=None):
    """``degrade`` with the first ``rows`` link rows' ``aff`` read
    from the lanes' own bits (:func:`link_aff_bits`) and the rows
    beyond them (a table of more rows than a word has room for:
    ``src``, ``dst``, ``t_send`` are theirs alone) looked up as
    ``degrade`` does; the same integer arithmetic in the same table
    order."""
    n = ft.link_src.shape[-1]
    for i in range(ft.link_start.shape[0]):
        if i < rows:
            hit = ((aff >> i) & 1) != 0
        else:
            hit = (ft.link_start[i] <= t_send) & (t_send < ft.link_end[i]) \
                & ft.link_src[i][jnp.clip(src, 0, n - 1)] \
                & ft.link_dst[i][jnp.clip(dst, 0, n - 1)]
        delay = jnp.where(
            hit, (delay * ft.link_num[i]) // ft.link_den[i]
            + ft.link_add[i], delay)
    return delay


def window_floor(ft, t, w_req, base_floor: int):
    """Effective exact superstep window at instant ``t`` for a
    *requested* width ``w_req`` (traced int64 scalar): the degraded
    delay floor over sends in ``[t, t + w_req)``, clamped to
    ``[1, w_req]``. The device-side half of the dynamic-window
    contract (engine.py): a degradation window that undercuts the
    link's declared floor mid-run narrows the superstep window for
    exactly the supersteps it overlaps, instead of forcing the whole
    run onto the conservative schedule-wide floor
    (``FaultSchedule.min_delay_floor``).

    ``base_floor`` is a *host int* lower bound on every world's
    undegraded delay (the engine's controller window bound) — a
    per-world traced floor would not lower under the link-param sweep
    (``min_delay_us`` of a rebound link may do host arithmetic).
    Same greedy fold as the host ``min_delay_floor`` (transforms are
    monotone, so ``x <- min(x, T_i(x))`` in declaration order realizes
    the minimum over every row subset), restricted to rows whose
    window overlaps ``[t, t + w_req)`` — restricting to the *requested*
    (not effective) span only admits extra rows, so the clamp is
    conservative-safe. Inert pad rows (``t_end <= t_start``) never
    match. Deterministic given ``(tables, t, w_req)``, which is what
    keeps controller replay bit-exact."""
    f = jnp.int64(base_floor)
    L = ft.link_start.shape[0]
    if L == 0:
        return jnp.clip(jnp.asarray(w_req, jnp.int64), jnp.int64(1), f)
    for i in range(L):
        live = (ft.link_end[i] > ft.link_start[i]) \
            & (ft.link_start[i] < t + w_req) & (ft.link_end[i] > t)
        fi = jnp.maximum(
            jnp.int64(1),
            (f * ft.link_num[i]) // ft.link_den[i] + ft.link_add[i])
        f = jnp.where(live, jnp.minimum(f, fi), f)
    return jnp.clip(jnp.asarray(w_req, jnp.int64), jnp.int64(1),
                    jnp.maximum(f, jnp.int64(1)))


def skewed_step(step, skew):
    """Wrap a scenario step so the node observes skewed time: ``now``
    and (valid) inbox deliver times shift by ``skew[node]``; the
    returned wake shifts back to true time (NEVER stays NEVER).
    Engine internals — entropy keys, digests, fault windows, the
    contract-#5 clamp — all stay on true time. The *same* wrapped
    function runs under the oracle's vmap and the engines', so skewed
    behavior cannot diverge between interpreters."""
    def wrapped(state, inbox, now, node_id, key):
        off = skew[node_id]
        ib = inbox._replace(
            time=jnp.where(inbox.valid, inbox.time + off, inbox.time))
        st, out, wake = step(state, ib, now + off, node_id, key)
        wake = jnp.where(wake >= NEVER, wake, wake - off)
        return st, out, wake
    return wrapped
