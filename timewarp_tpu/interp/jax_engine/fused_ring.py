"""Pallas fused superstep for the dense token ring — the whole
deliver → step → shift-route → insert → rebase pipeline as ONE kernel.

Why: the XLA edge-engine superstep (edge_engine.py) lowers to ~18
separate near-bandwidth kernels (profiler, round 5) — 0.57 ms at 2^20
nodes where the pure HBM floor for the ~44 MB working set is ~0.1 ms.
The fused kernel reads and writes every byte exactly once per
superstep. This is the kernel-level lever SURVEY.md §2 reserved for
the case where a fused op beats the compiler — the first place in the
tree where one does.

Shape imposed by the compiler it was written against (observed in
round 5, owed a re-probe — docs/pallas_kernels.md, ROADMAP D7): (a)
int64 does not lower — every time value is stored **int32 relative to
the epoch** (the epoch advances in int64 outside the kernel, so no
horizon is lost); (b) a ``grid=`` pallas_call crashed it — the kernel
is grid-free and pipelines over blocks itself with double-buffered
async DMA (the guide's canonical pattern). The whole engine state
lives in ONE stacked ``int32[10, N/1024, 1024]`` array so each block
moves as a single DMA in each direction; the ring-shift boundary
rides the block loop's carry, and the ring wrap (node N-1 → node 0)
is computed on one element outside the kernel and fed in as SMEM
scalars.

Beside the successor state the kernel writes three ``[8, 128]``
lane-partials (no reduction to a scalar lowers inside it): messages
delivered, mailbox overflows, and the **minimum of the successor's
event times** (the ``_QR0``, ``_QR1`` and ``_WAKE`` planes as written,
already relative to the new base). The quiet driver's ``while`` carries
that minimum as the next superstep's time, so it scans the state once,
before the loop, and its condition is two scalars: inside the loop
nothing reads the planes but the kernel and the one-element reads of
the ring wrap. int32 ``min`` is exact in any order, so the carried
time is the scan's, bit for bit.

Scope (validated in __init__): the dense-ring regime of the headline
bench — the token-ring scenario without observer (models/
token_ring.py lean form, ``commutative_inbox`` so no contract-#2 sort
is owed), single pure-shift edge, ``cap=2``, ``FixedDelay`` link.

Correctness is pinned by exact *state* equality against the general
:class:`~timewarp_tpu.interp.jax_engine.edge_engine.EdgeEngine` at
every superstep (tests/test_fused_ring.py converts the relative state
back to an ``EdgeState`` and compares bit-for-bit), which transitively
pins it to the host oracle and the hand-rolled protocol trace
(tests/test_cross_world.py).

≙ the hot loop this batches: the reference's event dispatch,
`/root/reference/src/Control/TimeWarp/Timed/TimedT.hs:234-286`.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

from ...utils.jaxconfig import require_tpu

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.scenario import NEVER, Scenario
from ...net.delays import FixedDelay
from .common import I32MAX as _I32MAX
from .common import RunStatsMixin
from .edge_engine import EdgeEngine, EdgeState

__all__ = ["FusedRingEngine", "FusedRingState"]

TOKEN = 0
_LANES = 1024
_ROWS = 8          # rows per pipelined block
# stacked state plane indices
_QR0, _QR1, _QV0, _QV1, _QK0, _QK1, _WAKE, _CNT, _VAL, _SEND = range(10)


class FusedRingState(NamedTuple):
    """The dense-ring state as ONE stacked int32 array (plane layout
    above; [10, N/1024, 1024]) plus host-side scalars. All times are
    µs relative to ``base``; I32MAX = NEVER / empty sentinel."""
    planes: jax.Array     # int32[10, NR, 1024]
    base: jax.Array       # int64[]
    delivered: jax.Array  # int64[]
    overflow: jax.Array   # int32[]
    steps: jax.Array      # int64[]


def _block_compute(blk, t, alive, think, drel, cv, cx):
    """One [10, R, L] block of the fused superstep (pure values).
    ``cv``/``cx`` carry the previous flat lane's outbox (the ring
    shift's block boundary). Returns the output block, the updated
    carry, the (delivered, overflow) partial sums and the partial
    minimum of the output block's event times (``_QR0``, ``_QR1``,
    ``_WAKE`` as written: the successor state's earliest event)."""
    MAXI = jnp.int32(_I32MAX)
    NEG = jnp.int32(-2**31)
    r0, r1 = blk[_QR0], blk[_QR1]
    w, c, v, s = blk[_WAKE], blk[_CNT], blk[_VAL], blk[_SEND]

    nn = jnp.minimum(w, jnp.minimum(r0, r1))
    fire = nn == t
    d0 = (r0 <= t) & fire
    d1 = (r1 <= t) & fire

    # the ring step (models/token_ring.py lean form): reductions are
    # slot-order free, so no inbox sort is owed (commutative_inbox)
    tok0 = d0 & (blk[_QK0] == TOKEN)
    tok1 = d1 & (blk[_QK1] == TOKEN)
    got = tok0 | tok1
    cnt1 = c + tok0.astype(jnp.int32) + tok1.astype(jnp.int32)
    vmax = jnp.maximum(jnp.where(tok0, blk[_QV0], NEG),
                       jnp.where(tok1, blk[_QV1], NEG))
    val1 = jnp.where(got, jnp.maximum(v, vmax), v)
    send1 = jnp.where(got & (s >= MAXI), t + think, s)
    due = (send1 <= t) & (cnt1 > 0) & alive & fire
    cnt2 = jnp.where(alive, cnt1 - due.astype(jnp.int32), jnp.int32(0))
    send2 = jnp.where(due, jnp.where(cnt2 > 0, t + think, MAXI),
                      jnp.where(alive, send1, MAXI))
    wake2 = jnp.where(send2 >= MAXI, MAXI,
                      jnp.maximum(send2, t + 1) - t)  # contract #5
    o_cnt = jnp.where(fire, cnt2, c)
    o_val = jnp.where(fire, val1, v)
    o_send = jnp.where(fire,
                       jnp.where(send2 >= MAXI, MAXI, send2 - t),
                       jnp.where(s >= MAXI, MAXI, s - t))
    o_wake = jnp.where(fire, wake2,
                       jnp.where(w >= MAXI, MAXI, w - t))

    # route by the ring shift: +1 flat lane, carry across blocks.
    # jnp.roll shifts within rows (and is the one lane-crossing op
    # that round's Mosaic compiled — lane-axis concat crashed it);
    # lane 0 is then patched to the PREVIOUS row's last lane via an
    # axis-0 concat + masked where. Static slices only.
    R = due.shape[0]
    ov = due.astype(jnp.int32)
    oval = val1 + 1
    rolled_v = jnp.roll(ov, 1, axis=1)
    rolled_x = jnp.roll(oval, 1, axis=1)
    # each row's LAST lane, read from lane 0 of the rolled array —
    # slicing lane L-1 directly crashed that round's Mosaic
    rows_last_v = rolled_v[:, 0:1]                    # [R, 1]
    rows_last_x = rolled_x[:, 0:1]
    pv = jnp.concatenate([jnp.full((1, 1), cv, jnp.int32),
                          rows_last_v[:R - 1]], axis=0)
    px = jnp.concatenate([jnp.full((1, 1), cx, jnp.int32),
                          rows_last_x[:R - 1]], axis=0)
    lane0 = jax.lax.broadcasted_iota(
        jnp.int32, (R, _LANES), 1) == jnp.int32(0)
    in_v = jnp.where(lane0, pv, rolled_v) > 0
    in_x = jnp.where(lane0, px, rolled_x)
    cv2 = rows_last_v[R - 1, 0]
    cx2 = rows_last_x[R - 1, 0]

    # keep + rebase, insert into the first free slot
    keep0 = (r0 < MAXI) & ~d0
    keep1 = (r1 < MAXI) & ~d1
    rel0 = jnp.where(keep0, r0 - t, MAXI)
    rel1 = jnp.where(keep1, r1 - t, MAXI)
    free0 = rel0 >= MAXI
    free1 = rel1 >= MAXI
    ins0 = in_v & free0
    ins1 = in_v & ~free0 & free1
    ovf = in_v & ~free0 & ~free1
    o_r0 = jnp.where(ins0, drel, rel0)
    o_r1 = jnp.where(ins1, drel, rel1)
    out = jnp.stack([
        o_r0, o_r1,
        jnp.where(ins0, in_x, blk[_QV0]),
        jnp.where(ins1, in_x, blk[_QV1]),
        jnp.where(ins0, jnp.int32(TOKEN), blk[_QK0]),
        jnp.where(ins1, jnp.int32(TOKEN), blk[_QK1]),
        o_wake, o_cnt, o_val, o_send,
    ])
    # no scalar reductions: neither jnp.sum (int64 accumulator) nor
    # lax.reduce lowers inside this kernel — fold [R, 1024] counts
    # into [R, 128] lane-partials with unrolled elementwise ops; the
    # host side of the jit does the final sum (or min). Whole-vreg
    # lane slices, not a reshape to [R, 8, 128]: that is a relayout,
    # and with three of them the kernel's compute passed its HBM
    # writes (64.0 us a superstep at 2^20 on a v5e against 56.8 by
    # slices; PERF.md, PR 25)
    def fold(x, op):
        acc = x[:, :128]
        for j in range(128, _LANES, 128):
            acc = op(acc, x[:, j:j + 128])
        return acc
    deliv = fold(d0.astype(jnp.int32) + d1.astype(jnp.int32), jnp.add)
    novf = fold(ovf.astype(jnp.int32), jnp.add)
    nxt = fold(jnp.minimum(o_wake, jnp.minimum(o_r0, o_r1)),
               jnp.minimum)
    return out, cv2, cx2, deliv, novf, nxt


def _superstep_kernel(scal, st_ref, out_ref, cnt_ref):
    """Grid-free driver: double-buffered DMA pipeline over blocks of
    the stacked state (docs/pallas_kernels.md). ``scal`` (SMEM):
    [t, alive, think, drel, wrap_valid, wrap_val]."""
    t = scal[0]
    alive = scal[1] > 0
    think = scal[2]
    drel = scal[3]
    NR = st_ref.shape[1]
    G = NR // _ROWS

    def body(in_buf0, in_buf1, out_buf0, out_buf1,
             in_sem0, in_sem1, out_sem0, out_sem1):
        RW = jnp.int32(_ROWS)
        # two SEPARATE buffers per direction: slicing the leading dim
        # of a (2, ...) scratch emits a 64-bit memref index Mosaic
        # rejects under x64 — even for static indices
        in_bufs = (in_buf0, in_buf1)
        out_bufs = (out_buf0, out_buf1)
        in_sems = (in_sem0, in_sem1)
        out_sems = (out_sem0, out_sem1)

        def in_dma(slot, b):
            # slot is always a static python int here (when_slot)
            return pltpu.make_async_copy(
                st_ref.at[:, pl.ds(b * RW, _ROWS), :],
                in_bufs[slot], in_sems[slot])

        def out_dma(slot, b):
            return pltpu.make_async_copy(
                out_bufs[slot],
                out_ref.at[:, pl.ds(b * RW, _ROWS), :],
                out_sems[slot])

        in_dma(0, 0).start()
        ONE = jnp.int32(1)
        TWO = jnp.int32(2)
        GG = jnp.int32(G)

        def when_slot(slot, fn):
            # dynamic buffer-slot indices emit 64-bit memref slices
            # that Mosaic rejects — unroll the two slots statically
            @pl.when(slot == jnp.int32(0))
            def _():
                fn(0)

            @pl.when(slot == ONE)
            def _():
                fn(1)

        def loop(carry):
            # slot toggles in the carry: any python-int binary op on a
            # traced value (%, *, -) recurses in dtype promotion
            # inside this pallas trace, so everything is explicit
            b, slot, cv, cx, deliv, novf, nxt = carry

            @pl.when(b + ONE < GG)
            def _():
                when_slot(slot, lambda sl: in_dma(1 - sl,
                                                  b + ONE).start())

            when_slot(slot, lambda sl: in_dma(sl, b).wait())
            blk = jnp.where(slot == ONE, in_buf1[:], in_buf0[:])
            out, cv2, cx2, d, o, m = _block_compute(
                blk, t, alive, think, drel, cv, cx)

            @pl.when(b >= TWO)
            def _():
                when_slot(slot, lambda sl: out_dma(sl,
                                                   b - TWO).wait())

            def put(sl):
                out_bufs[sl][:] = out
                out_dma(sl, b).start()
            when_slot(slot, put)
            return (b + ONE, ONE - slot, cv2, cx2, deliv + d,
                    novf + o, jnp.minimum(nxt, m))

        # the first flat lane's boundary is the ring wrap, computed
        # outside on node N-1 and passed through scal. An explicit
        # int32-counter while_loop: fori_loop's counter normalization
        # cannot lower here (int64) and recurses under x64
        carry = jax.lax.while_loop(
            lambda c: c[0] < GG, loop,
            (jnp.int32(0), jnp.int32(0), scal[4], scal[5],
             jnp.zeros((_ROWS, 128), jnp.int32),
             jnp.zeros((_ROWS, 128), jnp.int32),
             jnp.full((_ROWS, 128), _I32MAX, jnp.int32)))

        # drain the in-flight output DMAs (G is static: plain python
        # `if`, so a G==1 program never even traces a block -1 DMA)
        if G >= 2:
            out_dma(G % 2, jnp.int32(G - 2)).wait()
        out_dma((G - 1) % 2, jnp.int32(G - 1)).wait()
        cnt_ref[:] = jnp.stack(carry[4:])

    pl.run_scoped(
        body,
        in_buf0=pltpu.VMEM((10, _ROWS, _LANES), jnp.int32),
        in_buf1=pltpu.VMEM((10, _ROWS, _LANES), jnp.int32),
        out_buf0=pltpu.VMEM((10, _ROWS, _LANES), jnp.int32),
        out_buf1=pltpu.VMEM((10, _ROWS, _LANES), jnp.int32),
        in_sem0=pltpu.SemaphoreType.DMA(()),
        in_sem1=pltpu.SemaphoreType.DMA(()),
        out_sem0=pltpu.SemaphoreType.DMA(()),
        out_sem1=pltpu.SemaphoreType.DMA(()),
    )


class FusedRingEngine(RunStatsMixin):
    """Single-kernel dense-ring executor. Same ``run_quiet`` contract
    as :class:`EdgeEngine`; ``to_edge_state`` converts back for the
    exact-equality law. Carries the uniform ``last_run_stats`` and the
    ``telemetry`` knob — but per-superstep telemetry planes need a
    traced scan driver, and this engine runs only the fused
    while-loop, so any mode but "off" is refused loudly (run the XLA
    :class:`EdgeEngine` when you need the counters; it is bit-exact
    to this engine by the fused-ring law).

    The kernel is compiled by Mosaic and needs a TPU backend;
    ``interpret=True`` asks for the Pallas interpreter instead (the
    CPU test surface and ``bench.py --smoke`` — semantics only, never
    a timing). It is an explicit request, never a fallback: with no
    TPU and no such request the constructor raises."""

    def __init__(self, scenario: Scenario, link, *, cap: int = 2,
                 lint: str = "warn", telemetry: str = "off",
                 verify: str = "off", interpret: bool = False) -> None:
        self.interpret = bool(interpret)
        if not self.interpret:
            require_tpu(type(self).__name__)
        # static scenario sanitizer — same knob contract as EdgeEngine
        from ...analysis import check_scenario
        from ...integrity.checks import validate_verify
        from ...obs.telemetry import validate_mode
        self.telemetry = validate_mode(telemetry, type(self).__name__)
        if self.telemetry != "off":
            raise ValueError(
                "FusedRingEngine runs the whole superstep as one fused "
                "while-loop kernel — there is no traced scan to thread "
                "per-superstep telemetry planes through; run the XLA "
                "EdgeEngine (bit-exact to this engine) with "
                f"telemetry={self.telemetry!r} instead")
        if validate_verify(verify, type(self).__name__) != "off":
            # same refusal shape as telemetry: no scan driver to
            # thread the guard plane (or chunk) through — never a
            # silently-unverified run
            raise ValueError(
                "FusedRingEngine has no chunked scan driver to "
                "verify; run the XLA EdgeEngine (bit-exact to this "
                f"engine) with verify={verify!r} instead "
                "(docs/integrity.md)")
        self.last_run_telemetry = None
        self.lint = lint
        self.lint_report = check_scenario(scenario, lint,
                                          who=type(self).__name__)
        if not isinstance(link, FixedDelay):
            raise ValueError("FusedRingEngine supports FixedDelay "
                             "links (delay is a kernel scalar)")
        if cap != 2:
            raise ValueError("FusedRingEngine is specialized to "
                             "cap=2 (two unrolled queue slots)")
        n = scenario.n_nodes
        if n % (_ROWS * _LANES) != 0:
            raise ValueError(
                f"n_nodes must be a multiple of {_ROWS * _LANES} "
                "(pipeline block shape)")
        if scenario.max_out != 1 or scenario.payload_width != 2 \
                or not scenario.commutative_inbox:
            raise ValueError("FusedRingEngine runs the lean dense "
                             "token ring (models/token_ring.py "
                             "with_observer=False)")
        meta = scenario.meta or {}
        if "think_us" not in meta or "end_us" not in meta:
            # never-silent: a missing knob must not default — a wrong
            # think time produces a silently different protocol
            raise ValueError("scenario.meta must carry think_us and "
                             "end_us (models/token_ring.py does)")
        self.think = int(meta["think_us"])
        self.end_us = int(meta["end_us"])
        self.drel = max(1, int(link.delay))
        if 2 * self.think + self.drel >= _I32MAX:
            # t + think is int32 inside the kernel and relative t can
            # itself be ~think after a rebase
            raise ValueError("2*think_us + delay must fit int32")
        if self.drel >= _I32MAX - 1:
            raise ValueError("delay must fit int32")
        self.scenario = scenario
        self.link = link
        self.n = n
        self._edge = EdgeEngine(scenario, link, cap=2)

    # -- state conversion ------------------------------------------------

    def init_state(self) -> FusedRingState:
        return self.from_edge_state(self._edge.init_state())

    def _rel(self, x64, base):
        r = jnp.where(x64 >= NEVER, jnp.int64(_I32MAX), x64 - base)
        return jnp.minimum(r, jnp.int64(_I32MAX)).astype(
            jnp.int32).reshape(-1, _LANES)

    def from_edge_state(self, st: EdgeState) -> FusedRingState:
        base = st.time
        # never-silent: a finite time beyond base + 2^31-2 µs cannot be
        # represented relative-int32 — refuse rather than silently
        # clamping real events to the NEVER sentinel
        horizon = base + jnp.int64(_I32MAX - 1)
        for x in (st.wake, st.states["send_at"]):
            if bool(jnp.any((x < NEVER) & (x > horizon))):
                raise ValueError(
                    "a wake/send_at time exceeds the int32-relative "
                    "horizon (~35 min of virtual time past the "
                    "state's epoch); run the XLA EdgeEngine instead")
        shp = (-1, _LANES)
        planes = jnp.stack([
            st.q_rel[0, 0].reshape(shp), st.q_rel[0, 1].reshape(shp),
            st.q_pay[0, 0, 0].reshape(shp),
            st.q_pay[0, 1, 0].reshape(shp),
            st.q_pay[0, 0, 1].reshape(shp),
            st.q_pay[0, 1, 1].reshape(shp),
            self._rel(st.wake, base),
            st.states["cnt"].reshape(shp),
            st.states["val"].reshape(shp),
            self._rel(st.states["send_at"], base),
        ])
        return FusedRingState(planes=planes, base=base,
                              delivered=st.delivered,
                              overflow=st.overflow, steps=st.steps)

    def to_edge_state(self, fs: FusedRingState) -> EdgeState:
        """Back to the general engine's layout — the exact-equality
        law's comparison surface (also makes checkpoints
        interchangeable)."""
        n = self.n
        p = fs.planes

        def abs64(plane):
            r = plane.reshape(n).astype(jnp.int64)
            return jnp.where(r >= _I32MAX, jnp.int64(NEVER),
                             fs.base + r)

        q_rel = jnp.stack([p[_QR0].reshape(n),
                           p[_QR1].reshape(n)])[None]
        # commutative_inbox: q_step is elided to width 0
        q_step = jnp.zeros((1, 0, n), jnp.int32)
        q_pay = jnp.stack([
            jnp.stack([p[_QV0].reshape(n), p[_QK0].reshape(n)]),
            jnp.stack([p[_QV1].reshape(n), p[_QK1].reshape(n)]),
        ])[None]
        return EdgeState(
            states={"cnt": p[_CNT].reshape(n),
                    "val": p[_VAL].reshape(n),
                    "send_at": abs64(p[_SEND])},
            wake=abs64(p[_WAKE]),
            q_rel=q_rel, q_step=q_step, q_pay=q_pay,
            overflow=fs.overflow,
            unrouted=jnp.int32(0), misrouted=jnp.int32(0),
            bad_delay=jnp.int32(0),
            delivered=fs.delivered, steps=fs.steps, time=fs.base,
            fault_dropped=jnp.int32(0),
            restart_done=jnp.zeros((0,), bool),
        )

    # -- one superstep ---------------------------------------------------

    def _superstep(self, fs: FusedRingState) -> FusedRingState:
        return self._step(fs, self._earliest(fs.planes))[0]

    @staticmethod
    @jax.named_scope("tw.next_event")
    def _earliest(p) -> jax.Array:
        """The earliest pending event, int32 relative to the state's
        base, by a scan of the three planes that hold event times."""
        return jnp.minimum(jnp.minimum(p[_WAKE].min(), p[_QR0].min()),
                           p[_QR1].min())

    def _step(self, fs: FusedRingState, t):
        """One superstep at the relative time ``t`` of the state's
        earliest event. Returns the successor state and ITS earliest
        event (relative to its base), which the kernel folds from the
        planes as it writes them: the driver's loop carries it and
        scans nothing."""
        with jax.named_scope("tw.ring_kernel"):
            out, counts = self._kernel_call(fs, t)
        with jax.named_scope("tw.next_event"):
            nxt = counts[2].min()
        with jax.named_scope("tw.finish"):
            return FusedRingState(
                planes=out,
                base=fs.base + t.astype(jnp.int64),
                delivered=fs.delivered
                + counts[0].sum(dtype=jnp.int64),
                overflow=fs.overflow + counts[1].sum(dtype=jnp.int32),
                steps=fs.steps + 1,
            ), nxt

    def _kernel_call(self, fs: FusedRingState, t):
        """The kernel's scalar operands (the ring wrap: node N-1's
        outbox this superstep) and its call."""
        MAXI = jnp.int32(_I32MAX)
        p = fs.planes
        alive_now = (fs.base + t.astype(jnp.int64)) < self.end_us

        # ring wrap: node N-1's outbox this superstep (one element,
        # same algebra as the kernel)
        def last(i):
            return p[i, -1, -1]
        NEG = jnp.int32(-2**31)
        w_nn = jnp.minimum(last(_WAKE),
                           jnp.minimum(last(_QR0), last(_QR1)))
        w_fire = w_nn == t
        w_tok0 = (last(_QR0) <= t) & w_fire & (last(_QK0) == TOKEN)
        w_tok1 = (last(_QR1) <= t) & w_fire & (last(_QK1) == TOKEN)
        w_got = w_tok0 | w_tok1
        w_cnt1 = last(_CNT) + w_tok0.astype(jnp.int32) \
            + w_tok1.astype(jnp.int32)
        w_vmax = jnp.maximum(jnp.where(w_tok0, last(_QV0), NEG),
                             jnp.where(w_tok1, last(_QV1), NEG))
        w_val1 = jnp.where(w_got, jnp.maximum(last(_VAL), w_vmax),
                           last(_VAL))
        w_send1 = jnp.where(w_got & (last(_SEND) >= MAXI),
                            t + self.think, last(_SEND))
        w_due = (w_send1 <= t) & (w_cnt1 > 0) & alive_now & w_fire

        scal = jnp.stack([
            t, alive_now.astype(jnp.int32), jnp.int32(self.think),
            jnp.int32(self.drel),
            w_due.astype(jnp.int32), w_val1 + 1])

        return pl.pallas_call(
            _superstep_kernel, name="tw_ring_superstep",
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[pl.BlockSpec(memory_space=pl.ANY),
                       pl.BlockSpec(memory_space=pltpu.VMEM)],
            out_shape=[
                # the successor's home is HBM, said outright: left to
                # itself the compiler keeps the loop's whole carry in
                # on-chip memory once nothing else in the loop reads
                # it (84 of a v5e's 128 MiB at 2^20 nodes), and the
                # superstep is then no longer the HBM-bound pass that
                # the benchmark's byte count describes (PERF.md, PR 25)
                pltpu.HBM(p.shape, jnp.int32),
                jax.ShapeDtypeStruct((3, _ROWS, 128), jnp.int32)],
            interpret=self.interpret,
        )(scal, p)

    # -- driver ----------------------------------------------------------

    def _next_event(self, fs: FusedRingState) -> jax.Array:
        m = self._earliest(fs.planes)
        return jnp.where(m >= _I32MAX, jnp.int64(NEVER),
                         fs.base + m.astype(jnp.int64))

    @partial(jax.jit, static_argnums=(0,))
    def _run_while(self, fs: FusedRingState, max_steps
                   ) -> FusedRingState:
        start = fs.steps
        max_steps = jnp.asarray(max_steps, jnp.int64)

        def cond(c):
            st, t = c
            return (t < _I32MAX) & (st.steps - start < max_steps)

        # the one scan of a run: from here the kernel reports each
        # successor's earliest event, so the condition is two scalars
        return jax.lax.while_loop(
            cond, lambda c: self._step(*c),
            (fs, self._earliest(fs.planes)))[0]

    def run_quiet(self, max_steps: int, state=None) -> FusedRingState:
        with self._driver_call("run_quiet") as call:
            fs = state if state is not None else self.init_state()
            final = call.dispatch(self._run_while, fs, max_steps)
            call.wait(fs.steps, final.steps)
        return final
