"""Pallas mailbox-insertion kernels for the general engine's sparse
path — fire-compaction + in-tile hole-ranked insertion (round 12).

docs/engines.md "Where the remaining praos fat is" (measured in round
5, not re-measured) names it precisely: ~15 ms/superstep at 2²⁰×8
dominated by the rung-width outbox gathers (~1.4 ms per
65k-lane rung access, 3 arrays), the sender-compaction N-sort
(1.0–1.6 ms), the free-rows short-axis sort, and the `[K, N]`
elementwise base — and records that fire-compaction via XLA gathers is
*pathological* on the mailbox side (minor-axis `[K, A]` column
gathers blew praos superstep 30 up to 301 ms). This module is the
structural exit: both halves become grid-free Pallas kernels that
stream their operands exactly once, so no minor-axis XLA gather (and
no N-wide sender sort) is owed at all.

Two kernels, one opt-in engine knob (``JaxEngine(insert=...)``):

- **fire-compaction** (:func:`_build_compact_kernel`): streams the raw
  ``[M, N]`` outbox planes through VMEM in double-buffered blocks and
  emits the *compact* fired batch ``(dst, woff, smrank, payload…)``
  directly — in-block exclusive ranks via log-step masked roll-adds
  (``jnp.roll`` is the one lane-crossing op the probed Mosaic
  inventory admits, fused_ring.py), a running write base carried
  through the sequential block loop, and capacity drops counted as
  lane partials into ``EngineState.route_drop`` (never silent). This
  replaces the sender-compaction sort + per-rung gathers of
  ``JaxEngine._route_adaptive``: the ordering sort still runs in XLA,
  but at *compacted* width (a 131k-element sort is < 0.1 ms on this
  chip — docs/engines.md per-op cost table), not at N.
- **insertion** (:func:`_build_kernel` — shared with fused_sparse.py,
  which this module is now the home of): the double-buffered, grid-free
  kernel that streams the ``[K, N]`` mailbox planes through VMEM once
  and merges the destination-sorted batch in-tile — hole-ranked rows
  for commutative inboxes (an unrolled K-cumsum while the block is
  resident, so the free-rows ``[K, N]`` sort is not owed), or
  append-after-kept rows for ordered inboxes (``counts`` rides as one
  extra input plane). Overflow is counted in-kernel, bit-identical to
  ``JaxEngine._insert_sorted``'s accounting.

**The exactness law extends unconditionally**: ``insert="pallas"``
(or ``"interpret"``) produces bit-identical ``EngineState``, traces,
and digests to ``insert="xla"`` — under faults (sampling, partition
cuts, and down-window drops stay in XLA around the kernels, so every
mask point is preserved), under telemetry, and under the world axis
(the kernels ``vmap``; tests/test_pallas_insert.py pins a faulted
batched config). ``JaxEngine`` is itself pinned to the host oracle
(tests/test_parity.py), so the chain pallas ≡ xla ≡ oracle covers the
kernels.

Knob resolution (:func:`resolve_insert`): ``insert=None`` reads the
``TW_INSERT`` env hatch (the promotion of round 5's
``TW_FLAT_SCATTER``, which is still honored as a legacy alias) and
defaults to ``"xla"``; ``"pallas"`` is the kernels compiled for the
TPU and raises where JAX found none (no strategy is ever picked from
the backend's name), while ``"interpret"`` asks for the Pallas
interpreter explicitly — the CPU test surface.
``"xla2d"`` selects the 2D ``[col, row]`` scatter form of the XLA
insertion stage (no flat-reshape relayout copy of the tiled mailbox —
the escape hatch round 5 kept for future hardware, docs/engines.md
"Measured on a v5e").

Hardware status: the exactness tests run the kernels under the Pallas
interpreter (``insert="interpret"``: identical DMA/loop semantics);
tests/test_chip_compile.py asks the TPU's compiler for both at 2^17
nodes. Both kernels are written inside the round-5 Mosaic
constraint inventory (grid-free, int32-only, no scalar reductions,
``pl.when``-unrolled DMA slots, roll-based lane crossings — the full
list is consolidated in docs/pallas_kernels.md), plus the two
constructs the inventory does not cover — the insertion kernel's
per-slot gather from the resident batch (carried over from
fused_sparse.py) and the compaction kernel's per-row scatter into the
VMEM-resident output. **Neither lowers under the installed Mosaic
(JAX 0.9.0)**: gathers lower only in take_along_axis shape and
scatter has no lowering rule (docs/pallas_kernels.md); both compile
tests are ``xfail(strict=True)`` until ROADMAP S2 re-expresses them.

≙ the reference's event dispatch this batches:
`/root/reference/src/Control/TimeWarp/Timed/TimedT.hs:234-286`.
"""

from __future__ import annotations

import os
from typing import Optional

from ...utils import jaxconfig  # noqa: F401

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.rng import _MSG_TAG, threefry2x32
from ...core.scenario import Scenario
from .common import I32MAX as _I32MAX
from .common import group_rank

__all__ = ["INSERT_MODES", "PallasInsertStage", "resolve_insert"]

_LANES = 1024
_ROWS = 8          # rows per pipelined mailbox block (when NR % 8 == 0)
#: VMEM budget the constructors guard against (resident batch + the
#: double-buffered block buffers), leaving headroom of a ~16 MB VMEM
#: for the compiler's own temporaries
_VMEM_BUDGET = 12 * 2**20

#: the engine knob's legal values: "xla" = flat-index 1D scatters (the
#: r5-measured default on this chip), "xla2d" = the 2D [col, row]
#: scatter form (no tiled-relayout copy — the TW_FLAT_SCATTER escape
#: hatch, promoted), "pallas" = the kernels compiled for the TPU (a
#: refusal where there is none), "interpret" = the kernels under the
#: Pallas interpreter on any backend (the test/CI surface)
INSERT_MODES = ("xla", "xla2d", "pallas", "interpret")
_ENV_KNOB = "TW_INSERT"
_LEGACY_ENV = "TW_FLAT_SCATTER"


def resolve_insert(requested: Optional[str], *, honor_env: bool,
                   who: str = "engine"):
    """Resolve the ``insert=`` knob to the strategy that was asked
    for: ``(mode, from_env)``. ``None`` reads the documented
    ``TW_INSERT`` env hatch (legacy ``TW_FLAT_SCATTER=1`` maps to
    ``"xla"``, ``=0`` to ``"xla2d"``) and defaults to ``"xla"``.
    Nothing here asks which backend is there: ``"pallas"`` is the
    compiled kernels, and the engine that builds them refuses where
    JAX found no TPU (``"interpret"`` asks for the Pallas interpreter
    explicitly). ``from_env`` marks env-sourced modes: an env hatch
    must stay behavior-neutral, so kernel-scope violations fall back
    (recorded) instead of crashing runs that worked before the var was
    exported — explicit constructor/CLI requests still refuse loudly.
    Engine subclasses that replace the insertion stage themselves pass
    ``honor_env=False`` so the hatch cannot leak into a path whose
    kernels it does not describe."""
    mode, from_env = requested, False
    if mode is None and honor_env:
        mode = os.environ.get(_ENV_KNOB)
        if mode is None:
            legacy = os.environ.get(_LEGACY_ENV)
            if legacy is not None:
                mode = "xla" if legacy not in ("0", "") else "xla2d"
        from_env = mode is not None
    if mode is None:
        mode = "xla"
    if mode not in INSERT_MODES:
        raise ValueError(
            f"{who}: insert must be one of {INSERT_MODES}, got "
            f"{mode!r} ('xla' = flat scatters, 'xla2d' = 2D scatter "
            "form, 'pallas' = the Pallas insertion kernels, "
            "'interpret' = the kernels under the Pallas interpreter)")
    return mode, from_env


# ----------------------------------------------------------------------
# kernel helpers: reductions as lane partials (no scalar reductions
# lower in-kernel — the probed constraint inventory, fused_ring.py /
# docs/pallas_kernels.md)
# ----------------------------------------------------------------------

def _umax(a, b):
    """Unsigned max/min as compare + select: Mosaic legalizes the
    unsigned compares but not ``arith.maxui`` / ``arith.minui``
    (compiled for a described v5e, tests/test_chip_compile.py)."""
    return jnp.where(a > b, a, b)


def _umin(a, b):
    return jnp.where(a < b, a, b)


def _fold_lanes(x):
    """[R, 1024] int32 -> [R, 128] partial sums (unrolled adds)."""
    R = x.shape[0]
    x = x.reshape(R, _LANES // 128, 128)
    acc = x[:, 0]
    for j in range(1, _LANES // 128):
        acc = acc + x[:, j]
    return acc


def _fold_rows8(x):
    """[rows, 128] int32 -> [8, 128] partial sums. rows must be a
    multiple of 8, or < 8 (zero-padded — axis-0 concat lowers, lane
    axis does not)."""
    rows = x.shape[0]
    if rows < 8:
        return jnp.concatenate(
            [x, jnp.zeros((8 - rows, 128), jnp.int32)], axis=0)
    acc = x[0:8]
    for i in range(1, rows // 8):
        acc = acc + x[8 * i:8 * i + 8]
    return acc


def _lane_excl_prefix(v, lane):
    """Exclusive per-row prefix sum of int32 ``v`` along the 1024-lane
    axis via log-step masked roll-adds — ``jnp.roll`` is the one
    lane-crossing op the probed Mosaic inventory admits (fused_ring.py;
    wrapped lanes are masked out with the lane iota)."""
    x = v
    s = 1
    while s < _LANES:
        # explicit int32 literals: under x64 a python int in a
        # jnp op is an int64 constant first, and Mosaic's lowering
        # of the int64 -> int32 convert recurses without end
        x = x + jnp.where(lane >= jnp.int32(s),
                          jnp.roll(x, s, axis=-1), jnp.int32(0))
        s *= 2
    return x - v


def _row_total(incl):
    """Per-row total of an inclusive lane prefix, [R, 1024] -> [R, 1]:
    the last lane read through ``roll`` + lane 0 (last-lane slices
    crashed the round-5 Mosaic; lane-0 reads of a rolled array
    are the fused_ring.py boundary idiom)."""
    return jnp.roll(incl, 1, axis=-1)[:, 0:1]


# ----------------------------------------------------------------------
# shared scope guards + static shape plan (the fused engines AND the
# insert= knob — one copy, so the kernels' constraint inventory and
# the VMEM budget cannot desynchronize between them)
# ----------------------------------------------------------------------

def _insertion_plan(sc: Scenario, n: int, S_raw: int, *, who: str,
                    what_n: str = "n_nodes",
                    require_commutative: bool = True):
    """Check ``sc`` against the fused insertion kernel's constraint
    inventory (K <= 128 unrolled hole/append cumsum, 1024-lane mailbox
    planes; ``require_commutative`` for the fused engines, whose
    sample-mode kernel has no append path), round the resident batch
    width up to 8-row tiling, and size the VMEM footprint against the
    budget. Returns ``(S, R, G)`` — batch width, rows per block, block
    count. Raises ``ValueError`` (never silently narrows scope)."""
    if require_commutative and not sc.commutative_inbox:
        raise ValueError(
            f"{who} requires a commutative_inbox scenario (insertion "
            "targets mailbox holes; an ordered inbox owes the "
            "contract-#2 compaction sort — run the XLA engine)")
    if sc.payload_width < 1:
        raise ValueError("payload_width must be >= 1")
    if sc.mailbox_cap > 128:
        raise ValueError("mailbox_cap must be <= 128 (the kernel "
                         "unrolls the hole-rank cumsum over K)")
    if n % _LANES:
        raise ValueError(
            f"{what_n} must be a multiple of {_LANES} (mailbox "
            "block lane shape)")
    NR = n // _LANES
    R = _ROWS if NR % _ROWS == 0 else 1
    S = -(-S_raw // 1024) * 1024            # SR must be 8-row tiled
    K, P = sc.mailbox_cap, sc.payload_width
    NP = 2 + K + K * P + (K if sc.inbox_src else 0)
    if not sc.commutative_inbox:
        NP += 1                             # the counts plane (append)
    NPO = K + K * P + (K if sc.inbox_src else 0)
    footprint = (3 + P) * S * 4 + 2 * (NP + NPO) * R * _LANES * 4
    if footprint > _VMEM_BUDGET:
        raise ValueError(
            f"fused-insertion VMEM footprint {footprint} B exceeds the "
            f"{_VMEM_BUDGET} B budget — lower the batch bound "
            "(max_batch / bucket_cap / insert_cap) or mailbox_cap")
    return S, R, NR // R


# ----------------------------------------------------------------------
# the insertion kernel (the home of fused_sparse.py's kernel builder;
# that module re-exports these names for its engines)
# ----------------------------------------------------------------------

def _build_kernel(*, K, P, R, G, SR, n, M, W, inbox_src, mode,
                  needs_key, s0, s1, delay_fn, ordered=False):
    """Build the grid-free fused insertion kernel for one static shape.

    Refs: ``scal`` SMEM int32[4] = [t_lo, t_hi, 0, 0]; ``msgs`` VMEM
    int32[3+P, SR, 128] — the resident sorted batch, planes
    (dst | woff | smrank | payload_0..P-1) in ``mode="sample"`` or
    (dst | drel | src | payload…) in ``mode="drel"`` (pre-sampled:
    the sharded insertion path and the ``insert="pallas"`` knob);
    ``st_ref`` ANY int32[NP, N/1024, 1024] — stacked (start | cnt |
    counts? | mb_rel[K] | mb_payload[K*P] | mb_src[K]?) planes, where
    the ``counts`` plane exists only for ``ordered=True`` (the
    append-after-kept target of ordered inboxes — drel mode only);
    outputs: the post-insertion mailbox planes (same layout minus the
    batch-boundary planes) and int32[3, 8, 128] lane-partial counters
    (overflow, bad_delay, short_delay)."""
    if ordered and mode != "drel":
        raise ValueError("ordered insertion is a drel-mode construct "
                         "(the fused engines' sample mode is hole-only)")
    KP = K * P
    OFS = 3 if ordered else 2
    NP = OFS + K + KP + (K if inbox_src else 0)
    NPO = K + KP + (K if inbox_src else 0)

    def kernel(scal, msgs_ref, st_ref, out_ref, cnt_ref):
        MAXI = jnp.int32(_I32MAX)
        m = msgs_ref[:]                                 # [3+P, SR, 128]
        dstp = m[0]
        valid = dstp < jnp.int32(n)
        zero_part = jnp.zeros((SR, 128), jnp.int32)
        if mode == "sample":
            woffp, smrank = m[1], m[2]
            srcp = smrank // jnp.int32(M)
            slot = smrank - srcp * jnp.int32(M)
            # send instant = t + woff as two uint32 words with an
            # explicit carry (int64 does not lower in-kernel)
            tl = scal[0].astype(jnp.uint32)
            th = scal[1].astype(jnp.uint32)
            woff_u = woffp.astype(jnp.uint32)
            lo = tl + woff_u
            carry = (lo < tl).astype(jnp.uint32)
            hi = th + carry
            key = None
            if needs_key:
                # msg_bits (core/rng.py) inlined: same chain, same bits
                a0, a1 = threefry2x32(
                    jnp.uint32(s0) ^ jnp.uint32(_MSG_TAG),
                    jnp.uint32(s1), srcp, dstp)
                b0, b1 = threefry2x32(a0, a1, lo, hi)
                key = threefry2x32(b0, b1, slot, jnp.uint32(0))
            delay = delay_fn(srcp, dstp, lo, hi, key)
            flight = _umax(delay, jnp.uint32(1))        # contract #4
            dsum = woff_u + flight
            badm = valid & (dsum > jnp.uint32(_I32MAX - 1))
            shortm = (valid & (flight < jnp.uint32(W))) if W > 1 \
                else jnp.zeros((SR, 128), bool)
            drelp = _umin(
                dsum, jnp.uint32(_I32MAX - 1)).astype(jnp.int32)
            bad8 = _fold_rows8(badm.astype(jnp.int32))
            short8 = _fold_rows8(shortm.astype(jnp.int32))
            srcp = srcp if inbox_src else None
        else:
            drelp, srcp = m[1], (m[2] if inbox_src else None)
            bad8 = short8 = _fold_rows8(zero_part)
        payps = [m[3 + p] for p in range(P)]

        def block_compute(blk):
            """Insert the resident batch into one [NP, R, L] mailbox
            block: meet the r-th message to each destination at its
            r-th hole (hole-ranked, commutative inboxes — an unrolled
            K-cumsum while the block is resident) or at row
            ``counts + r`` (append-after-kept, ordered inboxes) via a
            gather from the resident planes. Returns the output block
            and the per-node overflow partial."""
            start_b, cnt_b = blk[0], blk[1]
            rel = blk[OFS:OFS + K]
            pay = blk[OFS + K:OFS + K + KP]
            smb = blk[OFS + K + KP:] if inbox_src else None
            o_rel, o_pay, o_src = [], [None] * KP, []

            def take(want, j, k):
                jr = j // jnp.int32(128)
                jc = j - jr * jnp.int32(128)
                o_rel.append(jnp.where(want, drelp[jr, jc], rel[k]))
                for p in range(P):
                    o_pay[k * P + p] = jnp.where(
                        want, payps[p][jr, jc], pay[k * P + p])
                if inbox_src:
                    o_src.append(jnp.where(want, srcp[jr, jc], smb[k]))

            if ordered:
                # append mode: row k receives the (k - counts)-th new
                # message of its node — the kernel half of
                # _insert_sorted's `pos = counts + rank` law
                base_b = blk[2]
                for k in range(K):
                    j = jnp.int32(k) - base_b
                    want = (j >= 0) & (j < cnt_b)
                    take(want, jnp.where(want, start_b + j,
                                         jnp.int32(0)), k)
                ovf = jnp.maximum(
                    cnt_b - (jnp.int32(K) - base_b), jnp.int32(0))
            else:
                acc = jnp.zeros(rel[0].shape, jnp.int32)
                for k in range(K):
                    free_k = rel[k] >= MAXI
                    h_k = acc
                    acc = acc + free_k.astype(jnp.int32)
                    want = free_k & (h_k < cnt_b)
                    take(want, jnp.where(want, start_b + h_k,
                                         jnp.int32(0)), k)
                # messages beyond a destination's hole count are
                # dropped and counted — identical to _insert_sorted's
                # ok & ~fits
                ovf = jnp.maximum(cnt_b - acc, jnp.int32(0))
            out = jnp.stack(o_rel + o_pay + o_src)
            return out, _fold_lanes(ovf)

        def body(in_buf0, in_buf1, out_buf0, out_buf1,
                 in_sem0, in_sem1, out_sem0, out_sem1):
            RW = jnp.int32(R)
            in_bufs = (in_buf0, in_buf1)
            out_bufs = (out_buf0, out_buf1)
            in_sems = (in_sem0, in_sem1)
            out_sems = (out_sem0, out_sem1)

            def in_dma(slot, b):
                return pltpu.make_async_copy(
                    st_ref.at[:, pl.ds(b * RW, R), :],
                    in_bufs[slot], in_sems[slot])

            def out_dma(slot, b):
                return pltpu.make_async_copy(
                    out_bufs[slot],
                    out_ref.at[:, pl.ds(b * RW, R), :],
                    out_sems[slot])

            in_dma(0, 0).start()
            ONE = jnp.int32(1)
            TWO = jnp.int32(2)
            GG = jnp.int32(G)

            def when_slot(slot, fn):
                # dynamic buffer-slot indices emit 64-bit memref
                # slices Mosaic rejects — unroll the two slots
                @pl.when(slot == jnp.int32(0))
                def _():
                    fn(0)

                @pl.when(slot == ONE)
                def _():
                    fn(1)

            def loop(carry):
                b, slot, ovf = carry

                @pl.when(b + ONE < GG)
                def _():
                    when_slot(slot,
                              lambda sl: in_dma(1 - sl, b + ONE).start())

                when_slot(slot, lambda sl: in_dma(sl, b).wait())
                blk = jnp.where(slot == ONE, in_buf1[:], in_buf0[:])
                out, o = block_compute(blk)

                @pl.when(b >= TWO)
                def _():
                    when_slot(slot, lambda sl: out_dma(sl, b - TWO).wait())

                def put(sl):
                    out_bufs[sl][:] = out
                    out_dma(sl, b).start()
                when_slot(slot, put)
                return (b + ONE, ONE - slot, ovf + o)

            carry = jax.lax.while_loop(
                lambda c: c[0] < GG, loop,
                (jnp.int32(0), jnp.int32(0),
                 jnp.zeros((R, 128), jnp.int32)))

            if G >= 2:
                out_dma(G % 2, jnp.int32(G - 2)).wait()
            out_dma((G - 1) % 2, jnp.int32(G - 1)).wait()
            cnt_ref[:] = jnp.stack(
                [_fold_rows8(carry[2]), bad8, short8])

        pl.run_scoped(
            body,
            in_buf0=pltpu.VMEM((NP, R, _LANES), jnp.int32),
            in_buf1=pltpu.VMEM((NP, R, _LANES), jnp.int32),
            out_buf0=pltpu.VMEM((NPO, R, _LANES), jnp.int32),
            out_buf1=pltpu.VMEM((NPO, R, _LANES), jnp.int32),
            in_sem0=pltpu.SemaphoreType.DMA(()),
            in_sem1=pltpu.SemaphoreType.DMA(()),
            out_sem0=pltpu.SemaphoreType.DMA(()),
            out_sem1=pltpu.SemaphoreType.DMA(()),
        )

    return kernel


# ----------------------------------------------------------------------
# the insertion-kernel invocation shared by the fused engines, the
# sharded insertion path, and the insert= knob
# ----------------------------------------------------------------------

def _fused_insert_call(kernel, S, n, K, P, inbox_src, scal, sd, a1, a2,
                       pay_s, mb_rel, mb_src, mb_payload, *,
                       interpret, ordered=False, counts=None):
    """Stack the sorted batch + per-node bucket planes and run the
    fused kernel once. ``sd`` is the sorted destination row (sentinel
    ``n`` = invalid); ``(a1, a2)`` are the mode's second/third resident
    planes — (woff, smrank) for in-kernel sampling, (drel, src) for
    pre-sampled insertion. ``ordered=True`` threads the per-node kept
    ``counts`` as one extra input plane (the append-mode target);
    ``interpret`` runs the kernel under the Pallas interpreter (the
    caller's explicit request, never derived here). Returns the post-insertion
    mailbox arrays plus the [3, 8, 128] counter partials."""
    SA = sd.shape[0]
    L = _LANES
    NR = n // L

    # per-destination bucket boundaries: two S-sized scatters into [N]
    # planes (S = the compacted batch width — the sparse regime's
    # cheap side); the kernel meets rank r at hole r via start + r
    rank = group_rank(sd)
    validm = sd < n
    iota = jnp.arange(SA, dtype=jnp.int32)
    start = jnp.zeros(n, jnp.int32).at[
        jnp.where(validm & (rank == 0), sd, n)].set(iota, mode="drop")
    nxt = jnp.concatenate([sd[1:], jnp.full((1,), n, sd.dtype)])
    cnt = jnp.zeros(n, jnp.int32).at[
        jnp.where(validm & (sd != nxt), sd, n)].set(
            rank + 1, mode="drop")

    pad = S - SA

    def padded(x, fill):
        if not pad:
            return x
        return jnp.concatenate([x, jnp.full((pad,), fill, x.dtype)])

    SR = S // 128
    msgs = jnp.stack(
        [padded(sd, n).reshape(SR, 128),
         padded(a1, 0).reshape(SR, 128),
         padded(a2, 0).reshape(SR, 128)]
        + [padded(p, 0).reshape(SR, 128) for p in pay_s])
    st_planes = jnp.concatenate(
        [start.reshape(1, NR, L), cnt.reshape(1, NR, L)]
        + ([counts.reshape(1, NR, L)] if ordered else [])
        + [mb_rel.reshape(K, NR, L),
           mb_payload.reshape(K * P, NR, L)]
        + ([mb_src.reshape(K, NR, L)] if inbox_src else []),
        axis=0)

    NPO = K + K * P + (K if inbox_src else 0)
    out_planes, cnts = pl.pallas_call(
        kernel,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pltpu.VMEM),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[pl.BlockSpec(memory_space=pl.ANY),
                   pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_shape=[
            jax.ShapeDtypeStruct((NPO, NR, L), jnp.int32),
            jax.ShapeDtypeStruct((3, 8, 128), jnp.int32)],
        interpret=interpret,
    )(scal, msgs, st_planes)
    mrel = out_planes[:K].reshape(K, n)
    mpay = out_planes[K:K + K * P].reshape(K, P, n)
    msrc = out_planes[K + K * P:].reshape(K, n) if inbox_src \
        else mb_src
    return mrel, msrc, mpay, cnts


# ----------------------------------------------------------------------
# the fire-compaction kernel
# ----------------------------------------------------------------------

def _build_compact_kernel(*, M, P, RW, G, SR, n, W):
    """Build the grid-free fire-compaction kernel for one static
    shape: stream the raw outbox planes (woff? | dst[M] | pay[M*P],
    int32[NPI, N/1024, 1024], destination -1 = no message) through
    double-buffered VMEM blocks and emit the compact fired batch
    (dst | woff | smrank | payload…, int32[3+P, SR, 128], sentinel
    dst = n beyond the fired width) plus [8, 128] lane-partial
    capacity-drop counters. Ranks within a block are exclusive lane
    prefixes via log-step masked roll-adds; the running write base is
    a [1, 1] carry of the sequential block loop (scalar *reductions*
    do not lower — scalar carries do, fused_ring.py)."""
    NPI = (1 if W > 1 else 0) + M + M * P
    DOF = 1 if W > 1 else 0
    L = _LANES
    S = SR * 128

    def kernel(src_ref, msgs_ref, cnt_ref):
        lane = jax.lax.broadcasted_iota(jnp.int32, (RW, L), 1)

        def block_compute(b, blk, wbase, msgs, drops):
            woff_b = blk[0] if W > 1 else None
            for mm in range(M):
                d_m = blk[DOF + mm]                     # [RW, L]
                v_m = d_m >= 0
                vi = v_m.astype(jnp.int32)
                excl = _lane_excl_prefix(vi, lane)      # [RW, L]
                tot = _row_total(excl + vi)             # [RW, 1]
                for r in range(RW):
                    pos = wbase[0] + excl[r]            # [L]
                    okw = v_m[r] & (pos < jnp.int32(S))
                    tgt = jnp.where(okw, pos, jnp.int32(S))
                    jr = tgt // jnp.int32(128)
                    jc = tgt - jr * jnp.int32(128)
                    msgs = msgs.at[0, jr, jc].set(d_m[r], mode="drop")
                    if W > 1:
                        msgs = msgs.at[1, jr, jc].set(woff_b[r],
                                                      mode="drop")
                    node0 = (b * jnp.int32(RW) + jnp.int32(r)) \
                        * jnp.int32(L)
                    smr = (node0 + lane[r]) * jnp.int32(M) \
                        + jnp.int32(mm)
                    msgs = msgs.at[2, jr, jc].set(smr, mode="drop")
                    for p in range(P):
                        msgs = msgs.at[3 + p, jr, jc].set(
                            blk[DOF + M + mm * P + p][r], mode="drop")
                    drops = drops + (
                        v_m[r] & (pos >= jnp.int32(S))
                    ).astype(jnp.int32)[None, :]
                    wbase = wbase + tot[r:r + 1]
            return msgs, drops, wbase

        def body(in_buf0, in_buf1, in_sem0, in_sem1):
            RWI = jnp.int32(RW)
            in_bufs = (in_buf0, in_buf1)
            in_sems = (in_sem0, in_sem1)

            def in_dma(slot, b):
                return pltpu.make_async_copy(
                    src_ref.at[:, pl.ds(b * RWI, RW), :],
                    in_bufs[slot], in_sems[slot])

            in_dma(0, 0).start()
            ONE = jnp.int32(1)
            GG = jnp.int32(G)

            def when_slot(slot, fn):
                @pl.when(slot == jnp.int32(0))
                def _():
                    fn(0)

                @pl.when(slot == ONE)
                def _():
                    fn(1)

            # the output batch stays VMEM-resident across the whole
            # stream as a loop-carried value; sentinel dst = n marks
            # the unfired tail (axis-0 concat lowers)
            init_msgs = jnp.concatenate(
                [jnp.full((1, SR, 128), n, jnp.int32),
                 jnp.zeros((2 + P, SR, 128), jnp.int32)], axis=0)

            def loop(carry):
                b, slot, wbase, drops, msgs = carry

                @pl.when(b + ONE < GG)
                def _():
                    when_slot(slot,
                              lambda sl: in_dma(1 - sl, b + ONE).start())

                when_slot(slot, lambda sl: in_dma(sl, b).wait())
                blk = jnp.where(slot == ONE, in_buf1[:], in_buf0[:])
                msgs, drops, wbase = block_compute(
                    b, blk, wbase, msgs, drops)
                return (b + ONE, ONE - slot, wbase, drops, msgs)

            carry = jax.lax.while_loop(
                lambda c: c[0] < GG, loop,
                (jnp.int32(0), jnp.int32(0),
                 jnp.zeros((1, 1), jnp.int32),
                 jnp.zeros((1, L), jnp.int32), init_msgs))
            msgs_ref[:] = carry[4]
            cnt_ref[:] = _fold_rows8(_fold_lanes(carry[3]))

        pl.run_scoped(
            body,
            in_buf0=pltpu.VMEM((NPI, RW, L), jnp.int32),
            in_buf1=pltpu.VMEM((NPI, RW, L), jnp.int32),
            in_sem0=pltpu.SemaphoreType.DMA(()),
            in_sem1=pltpu.SemaphoreType.DMA(()),
        )

    return kernel


def _fire_compact_call(kernel, S, n, M, P, W, pdst, woff_n, payload,
                       interpret):
    """Stack the raw outbox planes and run the fire-compaction kernel
    once: ``pdst`` int32[M, N] (-1 = no message), ``woff_n`` int32[N]
    in-window send offsets, ``payload`` int32[M, P, N]. Returns the
    compact batch columns ``(dst, woff, smrank, pay_tuple)`` at static
    width S (sentinel dst = n beyond the fired width) plus the
    capacity-drop count."""
    L = _LANES
    NR = n // L
    planes = ([woff_n.reshape(1, NR, L)] if W > 1 else []) \
        + [pdst.reshape(M, NR, L),
           payload.reshape(M * P, NR, L)]
    src_planes = jnp.concatenate(planes, axis=0)
    SR = S // 128
    msgs, cnts = pl.pallas_call(
        kernel,
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[pl.BlockSpec(memory_space=pltpu.VMEM),
                   pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_shape=[
            jax.ShapeDtypeStruct((3 + P, SR, 128), jnp.int32),
            jax.ShapeDtypeStruct((8, 128), jnp.int32)],
        interpret=interpret,
    )(src_planes)
    dst_c = msgs[0].reshape(S)
    woff_c = msgs[1].reshape(S)
    smrank_c = msgs[2].reshape(S)
    pay_c = tuple(msgs[3 + p].reshape(S) for p in range(P))
    drop_step = jnp.sum(cnts, dtype=jnp.int32)
    return dst_c, woff_c, smrank_c, pay_c, drop_step


# ----------------------------------------------------------------------
# the engine-facing stage (JaxEngine insert="pallas"|"interpret")
# ----------------------------------------------------------------------

class PallasInsertStage:
    """The ``insert="pallas"`` knob's kernel bundle, owned by one
    :class:`~timewarp_tpu.interp.jax_engine.engine.JaxEngine`: the
    fire-compaction kernel (adaptive regimes — it replaces the
    sender-compaction sort + rung gathers of ``_route_adaptive``) and
    per-width drel-mode insertion kernels (every ``_insert_sorted``
    call site: the compacted adaptive batch, the eager S = N·max_out
    width, the lazy ``route_cap`` width). Construction validates the
    full kernel scope loudly (1024-lane node multiple, K <= 128, VMEM
    budget at the widths this engine's regime will actually run) —
    never a silent narrowing.

    ``insert_cap`` bounds the compacted adaptive batch in *messages*
    (like the fused engine's ``max_batch``); the default is
    ``n_nodes * max_out`` — no superstep can ever drop, so the
    exactness law holds unconditionally. A smaller cap drops the
    excess into ``EngineState.route_drop``, counted, never silent.
    The cap is rounded UP to the next 1024 multiple (the resident
    batch's lane tiling), so the effective floor is 1024 messages —
    caps below that behave identically (``self.S`` is the width that
    actually runs, and the VMEM budget is checked on it)."""

    def __init__(self, scenario: Scenario, n: int, *, window: int,
                 interpret: bool, adaptive: bool,
                 insert_cap: Optional[int],
                 route_cap: Optional[int]) -> None:
        sc = scenario
        self.sc, self.n = sc, n
        self.K, self.M, self.P = (sc.mailbox_cap, sc.max_out,
                                  sc.payload_width)
        self.W = int(window)
        self.interpret = bool(interpret)
        self.ordered = not sc.commutative_inbox
        self.adaptive = bool(adaptive)
        full = n * sc.max_out
        if insert_cap is not None:
            if int(insert_cap) < sc.max_out:
                raise ValueError(
                    f"insert_cap must be >= max_out={sc.max_out} "
                    "(one whole sender), got "f"{insert_cap}")
            if not adaptive:
                raise ValueError(
                    "insert_cap bounds the fire-compacted adaptive "
                    "batch; this engine's regime (route_cap / droppy "
                    "link / classic narrow outbox) never compacts — "
                    "drop the knob or use route_cap")
        cap = full if insert_cap is None else min(int(insert_cap), full)
        self._kernels = {}
        who = "insert='pallas'"
        if adaptive:
            self.S, _, _ = _insertion_plan(
                sc, n, cap, who=who, require_commutative=False)
            NR = n // _LANES
            RWc = _ROWS if NR % _ROWS == 0 else 1
            NPI = (1 if self.W > 1 else 0) \
                + sc.max_out * (1 + sc.payload_width)
            extra = 2 * NPI * RWc * _LANES * 4 \
                + (3 + sc.payload_width) * self.S * 4
            if extra > _VMEM_BUDGET:
                raise ValueError(
                    f"fire-compaction VMEM footprint {extra} B exceeds "
                    f"the {_VMEM_BUDGET} B budget — lower insert_cap "
                    "or max_out")
            self._compact_kernel = _build_compact_kernel(
                M=sc.max_out, P=sc.payload_width, RW=RWc,
                G=NR // RWc, SR=self.S // 128, n=n, W=self.W)
        else:
            # the eager width (route_cap slices it when set and
            # smaller — slice_cap in engine.py)
            width = full if route_cap is None \
                else min(int(route_cap), full)
            self.S, _, _ = _insertion_plan(
                sc, n, width, who=who, require_commutative=False)
            self._compact_kernel = None
        #: sender-denominated static width — what telemetry records as
        #: the pallas path's "rung" (the ladder analog of the fused
        #: engine's VMEM batch slice)
        self.A = self.S // sc.max_out
        self._insert_kernel_for(self.S)   # pre-build + budget-check

    def _insert_kernel_for(self, SA: int):
        """The drel-mode insertion kernel for a call-site batch width
        ``SA`` (cached per padded width — the eager, lazy, and
        compacted-adaptive call sites each see exactly one)."""
        S = -(-SA // 1024) * 1024
        hit = self._kernels.get(S)
        if hit is None:
            sc = self.sc
            _, R, G = _insertion_plan(
                sc, self.n, S, who="insert='pallas'",
                require_commutative=False)
            hit = _build_kernel(
                K=self.K, P=self.P, R=R, G=G, SR=S // 128, n=self.n,
                M=self.M, W=self.W, inbox_src=sc.inbox_src,
                mode="drel", needs_key=False, s0=0, s1=0,
                delay_fn=None, ordered=self.ordered)
            self._kernels[S] = hit
        return hit, S

    def insert(self, sd, drel_s, src_s, pay_s, mb_rel, mb_src,
               mb_payload, counts):
        """One destination-sorted batch through the insertion kernel —
        the pallas form of ``JaxEngine._insert_sorted`` (same
        arguments' semantics, same overflow accounting, bit-for-bit).
        ``counts`` is the ordered-inbox kept-rows plane (None for
        commutative scenarios — holes are ranked in-tile)."""
        kernel, S = self._insert_kernel_for(sd.shape[0])
        mrel, msrc, mpay, cnts = _fused_insert_call(
            kernel, S, self.n, self.K, self.P, self.sc.inbox_src,
            jnp.zeros(4, jnp.int32), sd, drel_s, src_s, pay_s,
            mb_rel, mb_src, mb_payload, ordered=self.ordered,
            counts=counts, interpret=self.interpret)
        return mrel, msrc, mpay, jnp.sum(cnts[0], dtype=jnp.int32)

    def compact(self, pdst, woff_n, payload):
        """The fire-compaction front end (adaptive regimes only):
        raw pre-masked outbox planes in, compact fired batch out."""
        return _fire_compact_call(
            self._compact_kernel, self.S, self.n, self.M, self.P,
            self.W, pdst, woff_n, payload, self.interpret)
