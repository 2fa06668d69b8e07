"""Pallas fused routing superstep for the *sparse* general engine —
the gossip / praos path (round 6).

Round 5 proved the Pallas lever on the dense ring (fused_ring.py:
6.5e9 msg/s/chip) but every dynamic-destination config still runs the
XLA `JaxEngine` at 0.05-0.08x the north star, and the profiler says
where the fat is (docs/engines.md "Where the remaining praos fat is"): the
free-rows [K, N] short-axis sort, the (1 + P) flat mailbox scatters
with their tiled-layout relayout copies, and the per-stage HBM
round-trips between them. This module fuses the post-compaction
pipeline — delay sampling → destination bucketing → hole-ranked
mailbox insertion — into ONE grid-free, double-buffered Pallas kernel
that streams the [K, N] mailbox planes exactly once while the
sender-compacted message batch stays resident in VMEM:

- the **compaction insight is reused, not replaced**: active senders
  are still compacted by ONE single-operand N-sort and the batch is
  still ordered by ``(destination, window offset, sender-major rank)``
  in XLA (sorts are the one thing XLA does near-bandwidth;
  docs/engines.md per-op cost table) — but the sorted batch is then handed to
  the kernel ONCE and never re-materialized per stage;
- link delays are sampled **in-kernel** with the counter-based
  threefry of core/rng.py inlined as uint32 VPU ops (the same bits
  the XLA engine derives — entropy is keyed by (src, dst, send
  instant, slot), so execution venue cannot change the stream); int64
  never lowers on this chip's Mosaic (fused_ring.py), so send
  instants enter as two uint32 words and the in-window offset is
  added with an explicit carry;
- mailbox **holes are ranked in-VMEM per block** (an unrolled
  K-cumsum while the block is already resident), so the free-rows
  [K, N] sort is not owed at all (`JaxEngine._fused_holes`), and the
  r-th message to a destination meets its r-th hole by a per-slot
  gather from the resident batch — no [K, N] scatter, no relayout
  copy, every mailbox byte read and written exactly once;
- counters (``overflow`` / ``bad_delay`` / ``short_delay``)
  accumulate as lane partials (scalar reductions do not lower —
  fused_ring.py constraint inventory) and are summed outside; they
  land in the same never-silent ``EngineState`` fields.

The per-destination bucket boundaries (``start``/``cnt``) are two
S-sized scatters into [N] planes computed in XLA from the sorted
batch — S is the *compacted* batch width, so this is the sparse
regime's cheap side.

**State layout is `EngineState`, bit-for-bit.** The engine subclasses
:class:`JaxEngine` and overrides only the adaptive routing stage, so
drivers, trace digests, the device event ring, checkpoints
(utils/checkpoint.py — a `.npz` saved by either engine resumes under
the other), and the CLI/bench plumbing are inherited unchanged, and
the exactness law is *state + trace equality against JaxEngine at
every superstep* (tests/test_fused_sparse.py; chained to the host
oracle by tests/test_parity.py).

Capacity: the resident batch is VMEM-bounded, so the engine carries a
static ``max_batch`` (messages per superstep). Supersteps whose
active-sender count exceeds ``max_batch // max_out`` drop the excess
messages and count them in ``EngineState.route_drop`` — the same
loudly-accounted capacity contract as ``route_cap`` (a parity run
must keep the counter 0; the in-bench gate asserts it). Scope guards
(constructor, never silent): ``commutative_inbox`` scenarios (hole
insertion), drop-free links that lower to the in-kernel uint32/f32
registry (`_lower_link`), windowed or wide-outbox workloads, and
``n_nodes`` divisible by the 1024-lane block shape.

Hardware status: the exactness tests run the kernel under the Pallas
interpreter (``interpret=True``, an explicit request: identical
DMA/loop semantics); the default is the kernel compiled by Mosaic, and
with no TPU backend the constructor raises. The kernel is written
inside fused_ring.py's round-5 constraint inventory (grid-free,
int32-only, no scalar reductions, slot-unrolled DMA buffers), plus one
construct that inventory does not cover — the per-slot gather from the
resident batch — and **that gather does not lower under the installed
Mosaic (JAX 0.9.0)**, which lowers gathers only in take_along_axis
shape (docs/pallas_kernels.md; tests/test_chip_compile.py holds the
compile as ``xfail(strict=True)`` until ROADMAP S2). The in-kernel
delay draw does lower, after PR 21's cast repairs.

≙ the reference's event dispatch this batches:
`/root/reference/src/Control/TimeWarp/Timed/TimedT.hs:234-286`.
"""

from __future__ import annotations

from ...utils.jaxconfig import require_tpu

import jax
import jax.numpy as jnp

from ...core.rng import normal_f32, threefry2x32
from ...core.scenario import Scenario
from ...net.delays import (FixedDelay, LinkModel, LogNormalDelay,
                           Quantize, SeededHashUniform, UniformDelay)
from ...trace.hashing import SENT, mix32_jnp
from .common import thi as _thi, tlo as _tlo, u32sum as _u32sum
from .engine import JaxEngine
# the kernel machinery now lives in pallas_insert.py (the insert=
# knob's home, round 12) — these modules share ONE copy so the probed
# Mosaic constraint inventory and the VMEM budget cannot drift apart.
# Re-exported here because the sharded engines (and r6-era callers)
# import them from this module.
from .pallas_insert import (_LANES, _ROWS, _VMEM_BUDGET,  # noqa: F401
                            _build_kernel, _fold_lanes, _fold_rows8,
                            _fused_insert_call, _insertion_plan, _umax)

__all__ = ["FusedSparseEngine"]


# ----------------------------------------------------------------------
# link-model lowering: the kernel's uint32/float32 delay samplers
# ----------------------------------------------------------------------

def _lower_link(link: LinkModel):
    """Compile ``link.sample`` into kernel-lowerable ops: returns
    ``(needs_key, max_delay_us, fn)`` where ``fn(src, dst, tlo, thi,
    key) -> uint32 delay`` uses only uint32/int32/float32 arithmetic
    (int64 never lowers in-kernel — fused_ring.py) and reproduces the
    XLA sampler's values bit-for-bit for integer models (float models
    carry delays.py's documented transcendental-lowering caveat).
    Unsupported models raise — a model the kernel cannot express must
    fail construction loudly, not sample differently."""
    if isinstance(link, Quantize):
        nk, mx, inner = _lower_link(link.inner)
        q = int(link.quantum_us)
        if q < 1:
            raise ValueError("Quantize quantum_us must be >= 1")

        def fn(src, dst, tl, th, key):
            d = _umax(inner(src, dst, tl, th, key), jnp.uint32(1))
            qq = jnp.uint32(q)
            return ((d + qq - jnp.uint32(1)) // qq) * qq
        return nk, ((max(mx, 1) + q - 1) // q) * q, fn
    if isinstance(link, FixedDelay):
        d = int(link.delay)
        if not 0 <= d < 2**31:
            raise ValueError("FixedDelay delay must fit int32 for the "
                             "fused kernel's uint32 deliver arithmetic")

        def fn(src, dst, tl, th, key):
            return jnp.full(jnp.shape(dst), d, jnp.uint32)
        return False, d, fn
    if isinstance(link, UniformDelay):
        lo, hi = int(link.lo), int(link.hi)
        if not (0 <= lo <= hi < 2**31):
            raise ValueError("UniformDelay bounds must satisfy "
                             "0 <= lo <= hi < 2**31 for the fused kernel")

        def fn(src, dst, tl, th, key):
            b0, _ = key
            return jnp.uint32(lo) + b0 % jnp.uint32(hi - lo + 1)
        return True, hi, fn
    if isinstance(link, SeededHashUniform):
        lo, hi = int(link.lo_us), int(link.hi_us)
        if not (0 <= lo <= hi < 2**31):
            raise ValueError("SeededHashUniform bounds must satisfy "
                             "0 <= lo <= hi < 2**31 for the fused kernel")
        s0, s1 = link._s0, link._s1

        def fn(src, dst, tl, th, key):
            # the model's own (dst, t)-keyed self-contained draw —
            # same chain as SeededHashUniform.sample, uint32-only
            bits, _ = threefry2x32(
                jnp.uint32(s0) ^ dst.astype(jnp.uint32),
                jnp.uint32(s1), tl, th)
            return jnp.uint32(lo) + bits % jnp.uint32(hi - lo + 1)
        return False, hi, fn
    if isinstance(link, LogNormalDelay):
        med, sig = int(link.median_us), float(link.sigma)
        cap, floor = int(link.cap_us), int(link.floor_us)
        if not 0 <= cap < 2**31:
            raise ValueError("LogNormalDelay cap_us must fit int32 for "
                             "the fused kernel")

        def fn(src, dst, tl, th, key):
            b0, b1 = key
            z = normal_f32(b0, b1)
            d = jnp.float32(med) * jnp.exp(jnp.float32(sig) * z)
            d = jnp.clip(d, jnp.float32(floor), jnp.float32(cap))
            # float32 -> int32 -> uint32: Mosaic has no float ->
            # unsigned cast, and d is clipped to [floor, cap] with
            # cap < 2^31, so the value is the same
            return jnp.round(d).astype(jnp.int32).astype(jnp.uint32)
        return True, cap, fn
    raise ValueError(
        f"FusedSparseEngine cannot lower link model {link!r} into the "
        "kernel (supported: FixedDelay / UniformDelay / "
        "SeededHashUniform / LogNormalDelay, optionally Quantize-"
        "wrapped); run the XLA JaxEngine instead")


# ----------------------------------------------------------------------
# the engine
# ----------------------------------------------------------------------

class FusedSparseEngine(JaxEngine):
    """:class:`JaxEngine` with the adaptive routing stage replaced by
    the fused Pallas kernel (module docstring). Same state, drivers,
    trace, event ring, and checkpoint format — construction-time scope
    guards are the only API difference.

    ``max_batch`` bounds the VMEM-resident message batch per
    superstep; excess messages are dropped into
    ``EngineState.route_drop`` (never silent — the parity regime and
    the in-bench gate require the counter to stay 0). With
    ``max_batch >= n_nodes * max_out`` no superstep can ever drop."""

    def __init__(self, scenario: Scenario, link: LinkModel, *,
                 seed: int = 0, window=1, record_events: int = 0,
                 max_batch: int = 1 << 16,
                 lint: str = "warn", telemetry: str = "off",
                 controller=None, verify: str = "off",
                 record: str = "off", record_cap=None,
                 interpret: bool = False) -> None:
        # the kernel is compiled by Mosaic: the Pallas interpreter is
        # an explicit request (tests, bench.py --smoke), never a
        # fallback — no TPU and no request is a refusal
        self.interpret = bool(interpret)
        if not self.interpret:
            require_tpu(type(self).__name__)
        super().__init__(scenario, link, seed=seed, window=window,
                         route_cap=None, record_events=record_events,
                         lint=lint, telemetry=telemetry,
                         controller=controller, verify=verify,
                         record=record, record_cap=record_cap)
        # the fused kernel bakes the window into its uint32 deliver
        # arithmetic and in-kernel short-delay counter, so a dispatch
        # controller adapts CHUNK LENGTH only here — window/rung ride
        # the decision trace pinned (dispatch/, controlled.py)
        self._dyn_ok = False
        sc = scenario
        if link.can_drop:
            raise ValueError(
                "FusedSparseEngine requires a drop-free link (message "
                "validity must not depend on the sample — the lazy-"
                "sampling precondition, engine.py)")
        if not (self.window > 1 or sc.max_out > 1):
            raise ValueError(
                "FusedSparseEngine serves the windowed / wide-outbox "
                "sparse regime (window > 1 or max_out > 1); the "
                "classic regime routes nothing the kernel can batch")
        n = sc.n_nodes
        nk, mx, fn = _lower_link(link)
        if mx + self.window >= 2**32:
            raise ValueError("max link delay + window must fit the "
                             "kernel's uint32 deliver arithmetic")
        self._delay_fn, self._link_needs_key = fn, nk
        A = min(n, max(1, int(max_batch) // sc.max_out))
        self._A = A
        self._S, self._R, G = _insertion_plan(
            sc, n, A * sc.max_out, who="FusedSparseEngine")
        self._fused_holes = True
        self._kernel = _build_kernel(
            K=sc.mailbox_cap, P=sc.payload_width, R=self._R, G=G,
            SR=self._S // 128, n=n, M=sc.max_out, W=self.window,
            inbox_src=sc.inbox_src, mode="sample", needs_key=nk,
            s0=self.s0, s1=self.s1, delay_fn=fn)

    # -- the fused routing stage -----------------------------------------

    def _route_adaptive(self, out, out_valid, now_vec, t, mb_rel,
                        mb_src, mb_payload, free_rows, counts,
                        node_ids, with_trace):
        """Sender-compact in XLA (one N-sort — the compaction insight
        of the base engine, unchanged), sort the batch by
        (destination, window offset, sender-major rank), then hand it
        to the fused kernel ONCE: sampling, bucketing, and hole-ranked
        insertion all happen against the resident batch while the
        mailbox planes stream through VMEM (module docstring)."""
        sc = self.scenario
        K, M, P = sc.mailbox_cap, sc.max_out, sc.payload_width
        n = self.comm.n_local
        n_glob = self.comm.n_global
        W = self.window
        if self.telemetry != "off":
            # the fused engine's "rung" is its static VMEM batch slice
            self._t_rung = jnp.int32(self._A)

        dst32 = out.dst.astype(jnp.int32)                       # [M, N]
        dst_okf = (dst32 >= 0) & (dst32 < n_glob)
        bad_dst_step = jnp.sum(out_valid & ~dst_okf, dtype=jnp.int32)
        pdst = jnp.where(out_valid & dst_okf, dst32, -1)        # [M, N]
        sender_live = jnp.any(pdst >= 0, axis=0)                # [N]
        sid_sorted = jax.lax.sort(
            jnp.where(sender_live, node_ids, jnp.int32(n)))
        woff_n = (now_vec - t).astype(jnp.int32)                # [N]

        # static batch slice: active senders sort first, so slicing A
        # keeps every active sender while n_active <= A; the excess is
        # counted into route_drop below, never silent
        A = self._A
        sids = jax.lax.slice_in_dim(sid_sorted, 0, A)
        real = sids < n
        sidc = jnp.where(real, sids, 0)
        woff_a = woff_n[sidc]                                   # [A]
        dst_a = jnp.take(pdst, sidc, axis=1)                    # [M, A]
        pay_a = tuple(jnp.take(out.payload[:, p, :], sidc, axis=1)
                      for p in range(P))
        SA = A * M
        dst_f = dst_a.reshape(SA)
        ok = (dst_f >= 0) & jnp.broadcast_to(
            real[None, :], (M, A)).reshape(SA)
        smrank = (jnp.broadcast_to(sidc[None, :] * jnp.int32(M),
                                   (M, A))
                  + jnp.arange(M, dtype=jnp.int32)[:, None]
                  ).reshape(SA)
        total_msgs = jnp.sum(pdst >= 0, dtype=jnp.int32)
        kept = jnp.sum(ok, dtype=jnp.int32)
        route_drop_step = total_msgs - kept

        sort_dst = jnp.where(ok, dst_f, n)
        pay_f = tuple(p.reshape(SA) for p in pay_a)
        if W > 1:
            woff_f = jnp.broadcast_to(woff_a[None, :], (M, A)
                                      ).reshape(SA)
            ops = jax.lax.sort((sort_dst, woff_f, smrank) + pay_f,
                               dimension=0, num_keys=3)
            sd, woff_s, smrank_s = ops[0], ops[1], ops[2]
            pay_s = ops[3:]
        else:
            ops = jax.lax.sort((sort_dst, smrank) + pay_f,
                               dimension=0, num_keys=2)
            sd, smrank_s = ops[0], ops[1]
            woff_s = jnp.zeros_like(sd)
            pay_s = ops[2:]

        scal = jnp.stack([_tlo(t).astype(jnp.int32),
                          _thi(t).astype(jnp.int32),
                          jnp.int32(0), jnp.int32(0)])
        mrel, msrc, mpay, cnts = _fused_insert_call(
            self._kernel, self._S, n, K, P, sc.inbox_src, scal,
            sd, woff_s, smrank_s, pay_s, mb_rel, mb_src, mb_payload,
            interpret=self.interpret)
        overflow_step = jnp.sum(cnts[0], dtype=jnp.int32)
        bad_delay_step = jnp.sum(cnts[1], dtype=jnp.int32)
        short_step = jnp.sum(cnts[2], dtype=jnp.int32)

        sent_count = kept
        rec_full = with_trace and self.record == "full"
        sent_hash = jnp.uint32(0)
        if with_trace:
            # the SENT digest needs per-message flight times; re-derive
            # them in XLA from the same counters (bit-identical stream
            # — entropy is keyed by message identity, not venue). Only
            # the traced `run` driver compiles this; `run_quiet`
            # benchmarks never do. The flight recorder's send capture
            # (obs/flight.py) rides the same re-derivation.
            ok_s = sd < n
            src_s = smrank_s // jnp.int32(M)
            tmsg_s = t + woff_s.astype(jnp.int64)
            flight_s, _, _, _, _ = self._sample_nodrop(
                src_s, sd, tmsg_s, smrank_s % jnp.int32(M), woff_s,
                ok_s)
            dt_abs = tmsg_s + flight_s
            sent_mix = mix32_jnp(SENT, src_s, sd, _tlo(dt_abs),
                                 _thi(dt_abs), pay_s[0])
            sent_hash = _u32sum(jnp.where(ok_s, sent_mix, 0))
        ret = (mrel, msrc, mpay, overflow_step, bad_dst_step,
               bad_delay_step, short_step, route_drop_step,
               sent_count, sent_hash)
        if rec_full:
            ret += (self._rec_sends(ok_s, None, src_s, sd, tmsg_s,
                                    dt_abs),)
        return ret
