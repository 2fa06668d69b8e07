"""Link models: per-message latency and loss injection.

Revives the reference's removed fault-injection surface — ``Delays`` /
``ConnectionOutcome`` (examples/token-ring/Main.hs:73-77; the README's
promised "manually controlled network nastiness", README.md:13-15) — as
first-class, *batchable* models: a link model is a pure function from
``(src, dst, send_time, entropy)`` to ``(delay_µs, drop)``, written in
elementwise jax.numpy so the same code broadcasts over millions of
messages on TPU — in whatever layout the engine already holds them —
and evaluates per-message in the host oracle with identical bits.

Entropy is a pair of uint32 words from :mod:`timewarp_tpu.core.rng`
(counter-derived per message, never a materialized key array — see
docs/engines.md "Measured on a v5e" for why). Models that use no
randomness declare ``needs_key = False`` so engines skip deriving
entropy entirely.

All delays are int64 µs; the engine clamps in-flight time to ≥ 1 µs
(determinism contract #4, core/scenario.py).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Tuple

import jax
import jax.numpy as jnp

from ..core.rng import bernoulli, normal_f32, split_bits, uniform_int

__all__ = [
    "LinkModel", "FixedDelay", "UniformDelay", "LogNormalDelay",
    "ParetoDelay", "WithDrop", "FnDelay", "Quantize",
    "SeededHashUniform", "NEVER_CONNECTED",
]

#: Drop probability 1 — ≙ the old API's ``NeverConnected`` outcome.
NEVER_CONNECTED = 1.0


class LinkModel:
    """Base class. ``sample`` must be jittable (broadcasting jnp ops
    only). ``key`` is an ``(uint32, uint32)`` entropy pair (``None``
    when ``needs_key`` is False)."""

    #: whether ``sample`` consumes entropy; engines skip derivation if not
    needs_key: bool = True

    def sample(self, src, dst, t, key) -> Tuple[jax.Array, jax.Array]:
        """-> (delay int64 µs, drop bool)."""
        raise NotImplementedError

    @property
    def min_delay_us(self) -> int:
        """Static lower bound on every delay this model can sample
        (after the engine's ≥1 µs clamp, contract #4). Multi-instant
        windowed supersteps are exact only for window ≤ this bound —
        engines validate against it (interp/jax_engine/engine.py) and
        count dynamic violations in ``short_delay``, never silent.
        Conservative default: 1 µs (no windowing headroom)."""
        return 1

    @property
    def can_drop(self) -> bool:
        """Whether ``sample`` can ever return ``drop=True``. Drop-free
        models let the general engine compact the senders first and
        sample on the ladder's rung (sampling cost ∝ active senders,
        not outbox slots — engine.py ``_adaptive_regime``).
        Conservative default: True."""
        return True


@dataclass(frozen=True)
class FixedDelay(LinkModel):
    """Every message takes exactly ``delay`` µs (≙ ``ConnectedIn d``)."""
    delay: int
    needs_key = False

    def sample(self, src, dst, t, key):
        d = jnp.full(jnp.shape(dst), self.delay, jnp.int64)
        return d, jnp.zeros(jnp.shape(dst), bool)

    @property
    def min_delay_us(self) -> int:
        return max(int(self.delay), 1)

    @property
    def can_drop(self) -> bool:
        return False


@dataclass(frozen=True)
class UniformDelay(LinkModel):
    """Uniform integer delay in [lo, hi] µs — the token-ring example's
    1–5 ms uniform link (examples/token-ring/Main.hs:48-49, 73-77).
    Integer-only: bit-exact across CPU/TPU backends."""
    lo: int
    hi: int

    def sample(self, src, dst, t, key):
        b0, _ = key
        return uniform_int(b0, self.lo, self.hi), \
            jnp.zeros(jnp.shape(dst), bool)

    @property
    def min_delay_us(self) -> int:
        return max(int(self.lo), 1)

    @property
    def can_drop(self) -> bool:
        return False


@dataclass(frozen=True)
class LogNormalDelay(LinkModel):
    """Lognormal latency (the gossip-100k baseline config): delay =
    round(median * exp(sigma * N(0,1))), capped to [floor, cap] µs.

    ``floor_us`` models the propagation-delay floor every real network
    has (a packet can't beat the speed of light); it is also the bound
    that licenses multi-instant windowed supersteps (``min_delay_us``).

    Float32 internally; quantized to µs. Bit-parity is validated on CPU;
    across CPU/TPU a boundary-rounding µs divergence is possible in
    principle (transcendental lowering), which is why the parity *gate*
    configs use integer models.
    """
    median_us: int
    sigma: float
    cap_us: int = 60_000_000
    floor_us: int = 1

    def sample(self, src, dst, t, key):
        b0, b1 = key
        z = normal_f32(b0, b1)
        d = jnp.asarray(self.median_us, jnp.float32) * jnp.exp(
            jnp.float32(self.sigma) * z)
        d = jnp.clip(d, jnp.float32(self.floor_us), jnp.float32(self.cap_us))
        return jnp.asarray(jnp.round(d), jnp.int64), \
            jnp.zeros(jnp.shape(dst), bool)

    @property
    def min_delay_us(self) -> int:
        return max(int(self.floor_us), 1)

    @property
    def can_drop(self) -> bool:
        return False


@dataclass(frozen=True)
class ParetoDelay(LinkModel):
    """Pareto (heavy upper tail) latency — the long-tail link of the
    optimistic-execution win gate (``speculate=``, docs/speculation.md):
    delay = round(xm · U^(-1/alpha)) clamped to [floor, cap] µs, so
    samples are supported on [xm_us, cap_us] with the classic
    power-law tail P(delay > x) = (xm/x)^alpha.

    ``min_delay_us`` declares ``floor_us`` (default 1), **not** xm:
    the clamp floor is the only bound the model *promises*, and the
    gap between the provable floor and the practical minimum xm is
    deliberate — it is exactly the long-median/short-provable-floor
    regime where a conservative window serializes supersteps at
    ``floor_us`` while no sample ever lands below xm. Optimistic
    execution (``speculate=``) closes that gap at run time: the
    speculative window ladders up toward xm with zero violations and
    only rolls back when it probes past the distribution's real
    support. Declaring xm instead would be legal but would also
    license a *static* window=xm, making the config useless as a
    speculation benchmark — use an explicit ``floor_us=xm_us`` when a
    provable xm floor is what you want.

    Float32 internally (the ``U^(-1/alpha)`` power), quantized to µs —
    the same CPU-validated / cross-backend-caveat regime as
    :class:`LogNormalDelay`."""
    xm_us: int
    alpha: float
    cap_us: int = 60_000_000
    floor_us: int = 1

    def sample(self, src, dst, t, key):
        b0, _ = key
        # 24-bit mantissa uniform in (0, 1) — never 0, so the power
        # cannot overflow (the cap clamp below bounds it anyway).
        # Every field access is tracer-safe jnp arithmetic: the sweep
        # service vmaps these fields per world (sweep/spec.py
        # _SWEEPABLE), so they may arrive as batch tracers
        u = (b0 >> jnp.uint32(8)).astype(jnp.float32) \
            * jnp.float32(2 ** -24) + jnp.float32(2 ** -25)
        d = jnp.asarray(self.xm_us, jnp.float32) * jnp.exp(
            (jnp.float32(-1.0)
             / jnp.asarray(self.alpha, jnp.float32)) * jnp.log(u))
        d = jnp.clip(
            d,
            jnp.maximum(jnp.asarray(self.floor_us, jnp.float32),
                        jnp.float32(1.0)),
            jnp.asarray(self.cap_us, jnp.float32))
        return jnp.asarray(jnp.round(d), jnp.int64), \
            jnp.zeros(jnp.shape(dst), bool)

    @property
    def min_delay_us(self) -> int:
        return max(int(self.floor_us), 1)

    @property
    def can_drop(self) -> bool:
        return False


@dataclass(frozen=True)
class WithDrop(LinkModel):
    """Wrap a model with i.i.d. message loss — the "nastiness" knob
    (socket-state-with-drop baseline config). ``drop_prob=1`` ≙ the old
    ``NeverConnected`` outcome. The drop decision is an integer
    threshold compare — bit-exact everywhere."""
    inner: LinkModel
    drop_prob: float

    def sample(self, src, dst, t, key):
        b0, b1 = key
        drop = bernoulli(b0, self.drop_prob)
        inner_key = split_bits(b0, b1, 0x1A7E5EED)
        delay, inner_drop = self.inner.sample(src, dst, t, inner_key)
        return delay, drop | inner_drop

    @property
    def min_delay_us(self) -> int:
        return self.inner.min_delay_us


@dataclass(frozen=True)
class Quantize(LinkModel):
    """Round the inner model's delays *up* to a multiple of
    ``quantum_us`` — time-bucketed batching (SURVEY.md §7 hard part 4).

    The fire-all-at-min superstep delivers every message due at the
    same instant in one batch; free-running delays make every arrival
    its own instant, so at scale each superstep does O(N) work to
    deliver O(1) messages. Aligning arrivals on a grid (with scenario
    timers on the same grid) turns sparse event streams into dense
    co-temporal batches — the difference between ~10³ and ~10⁷+
    delivered-messages/sec at 100k+ nodes. Deterministic and
    order-preserving: quantization is monotone, so relative arrival
    order within a link never inverts.

    **Inner-sample clamp (round 5, changes sampled values):** the
    inner model's raw delay is clamped to ≥ 1 µs *before* rounding
    up, so an inner draw of 0 µs yields ``quantum_us`` — not 0 riding
    the engines' ≥ 1 µs flight clamp. This keeps the declared
    ``min_delay_us`` (≥ quantum) a true lower bound of the sampled
    values, which is what gates windowed-superstep validation. For
    any config/seed whose inner model can emit a raw 0 µs delay
    (e.g. ``UniformDelay(0, hi)``), delays sampled since round 5
    differ from earlier rounds, so digests and parity artifacts from
    before the clamp are not comparable for those configs (README
    "Compatibility notes")."""
    inner: LinkModel
    quantum_us: int

    @property
    def needs_key(self):  # type: ignore[override]
        return self.inner.needs_key

    def sample(self, src, dst, t, key):
        d, drop = self.inner.sample(src, dst, t, key)
        q = jnp.int64(self.quantum_us)
        # clamp BEFORE rounding up (class docstring: keeps
        # min_delay_us a true lower bound; changes digests for
        # inner models that can emit a raw 0)
        d = jnp.maximum(d, jnp.int64(1))
        return ((d + q - 1) // q) * q, drop

    @property
    def min_delay_us(self) -> int:
        q = int(self.quantum_us)
        m = max(self.inner.min_delay_us, 1)
        return ((m + q - 1) // q) * q

    @property
    def can_drop(self) -> bool:
        return self.inner.can_drop


@dataclass(frozen=True)
class SeededHashUniform(LinkModel):
    """Uniform ``[lo_us, hi_us]`` delay drawn by a *self-contained*
    threefry hash of ``(dst, t)`` — the reference's own ``Delays``
    contract, a seeded deterministic function of destination and time
    (`/root/reference/examples/token-ring/Main.hs:60, 73-77` draws
    uniform 1–5 ms from ``mkStdGen 0``).

    ``needs_key = False`` is the point: the draw ignores the
    transport's chunk/slot sequencing entirely, so the SAME model
    produces bit-identical delays in the generator-program world (the
    emulated byte fabric keyed by endpoint ids — ``EmulatedBackend``
    ``endpoint_ids``) and in the batched-scenario world (node
    indices) — the alignment the cross-world random-link parity law
    stands on (tests/test_cross_world.py)."""
    lo_us: int
    hi_us: int
    salt: int = 0
    needs_key = False

    def __post_init__(self):
        # expand the salt eagerly: seed_words reads back concrete ints,
        # which is illegal inside a jit trace
        from ..core.rng import seed_words
        s0, s1 = seed_words(self.salt)
        object.__setattr__(self, "_s0", s0)
        object.__setattr__(self, "_s1", s1)

    def sample(self, src, dst, t, key):
        from ..core.rng import threefry2x32, uniform_int
        s0, s1 = self._s0, self._s1
        t64 = jnp.asarray(t, jnp.int64)
        tlo = (t64 & jnp.int64(0xFFFFFFFF)).astype(jnp.uint32)
        thi = ((t64 >> jnp.int64(32))
               & jnp.int64(0xFFFFFFFF)).astype(jnp.uint32)
        d32 = jnp.asarray(dst).astype(jnp.uint32)
        bits, _ = threefry2x32(jnp.uint32(s0) ^ d32, jnp.uint32(s1),
                               tlo, thi)
        d = uniform_int(bits, self.lo_us, self.hi_us)
        return d, jnp.zeros(jnp.shape(d), bool)

    @property
    def min_delay_us(self) -> int:
        return int(self.lo_us)

    @property
    def can_drop(self) -> bool:
        return False


@dataclass(frozen=True)
class FnDelay(LinkModel):
    """Arbitrary per-link behavior from a user function
    ``fn(src, dst, t, key) -> (delay, drop)`` in broadcasting jnp ops —
    the full generality of the old ``Delays`` newtype (a function of
    destination and time, examples/token-ring/Main.hs:73-77)."""
    fn: Callable

    def sample(self, src, dst, t, key):
        delay, drop = self.fn(src, dst, t, key)
        return jnp.asarray(delay, jnp.int64), jnp.asarray(drop, bool)
