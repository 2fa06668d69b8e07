"""Multi-world batching: one compiled superstep, a fleet of worlds.

The production use of a cheap emulator is *fleets* of runs — seed
sweeps, link-model sweeps, Monte-Carlo fault studies (ROADMAP north
star; the replica-sweep workload of Revati-style time-warp emulation,
PAPERS.md). Per-superstep the general engine pays fixed N-width costs
(the sender compaction, rung gathers, the [K, N] mailbox base —
docs/engines.md "Measured on a v5e") that do not shrink with the instantaneous event count;
a leading **world axis B** amortizes one compile, one dispatch and one
readback over B independent worlds. Measured on a v5e (PERF.md,
Findings PR 28) eight gossip worlds of 2^17 nodes deliver 6.7e6 msg/s
together, 0.95 of one solo wave's rate: the batched sorts, gathers and
scatters cost each world what its solo superstep costs, no less. (Until
PR 28 the fleet ran every world at the routing ladder's top rung and
delivered 1/23 of that.)

:class:`BatchSpec` declares the fleet: per-world engine seeds, plus an
optional pytree of per-world link-model parameters (dotted attribute
paths into the link dataclass, e.g. ``{"lo": [...], "hi": [...]}`` for
a ``UniformDelay`` sweep or ``{"inner.median_us": [...]}`` through a
``Quantize`` wrapper). Worlds share one scenario (topology, shapes,
step function); everything else that distinguishes a run — the RNG
stream and the link model — varies per world.

The exactness law that makes the batch trustworthy and cheap to
verify: **slicing world b out of any batched run is bit-identical to
the solo run with that world's seed and link** (tests/test_world_batch.py;
the in-bench gates in bench.py; the batched column of
tools/parity_tpu.py). It holds by construction: ``vmap`` of the
integer superstep is the same arithmetic per world, per-world
quiescence and step budgets are masked exactly like the solo drivers
mask a finished run, and the adaptive routing ladder takes ONE rung
for all the worlds of a superstep, the smallest that holds the busiest
world's senders (rungs are result-identical by design, and any rung
that fits the largest count fits every world). The index has to be
shared: under ``vmap`` a ``lax.switch`` on a per-world index lowers to
select-over-all-branches, every rung in every world, while an index
reduced over the ``vmap``'s axis (``jax.lax.pmax``) is not batched and
the switch stays one conditional (engine.py ``_route_adaptive``). A
fleet whose worlds differ pays the busiest world's rung in all of them.

Sweepable parameters are the ones ``LinkModel.sample`` uses
*arithmetically* (delay bounds, medians, sigmas, quanta). Parameters
burned into static Python control flow — ``WithDrop.drop_prob``
(integer-threshold compare built at trace time) or
``SeededHashUniform.salt`` (expanded host-side) — cannot vary per
world and fail at trace time; sweep those by constructing one engine
per value instead.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Mapping, NamedTuple, Optional, Sequence, Tuple

import numpy as np

__all__ = ["BatchSpec", "WorldIdentity", "rebind_link", "world_slice"]


class WorldIdentity(NamedTuple):
    """The fleet's per-world *identity* as ONE traced-operand pytree:
    seed words, link-parameter vectors, and (optional) fault tables,
    all with a leading world axis B. The batched drivers thread this
    through ``jit`` as ordinary traced operands — never compile-time
    constants — so the compiled executable is a pure function of the
    bucket's *shape* (scenario params, link structure, window, pad
    dims, B), and swapping identity (a new admission's seed, link
    values, or same-shape fault schedule) re-invokes the SAME
    executable with new device arrays: zero recompiles
    (``JaxEngine.rebind_identity``; docs/serving.md)."""
    s0v: Any          # uint32[B] — per-world seed word 0
    s1v: Any          # uint32[B] — per-world seed word 1
    lpv: Any          # dict dotted-path -> [B] link-parameter vectors
    ftv: Any          # FaultTables with leading [B] axis, or None


def _split_params(params: Mapping[str, Any]):
    """Group dotted paths by head attribute: {"inner.lo": v} ->
    ({}, {"inner": {"lo": v}})."""
    direct, nested = {}, {}
    for path, v in params.items():
        head, dot, rest = path.partition(".")
        if dot:
            nested.setdefault(head, {})[rest] = v
        else:
            direct[head] = v
    return direct, nested


def rebind_link(link, params: Mapping[str, Any]):
    """A copy of ``link`` (a frozen dataclass, possibly nested) with
    the dotted-path ``params`` substituted. Values may be Python
    scalars (host-side validation links) or traced per-world scalars
    (inside the vmapped superstep). Unknown paths fail with the field
    inventory — a typo'd sweep must not silently sweep nothing."""
    direct, nested = _split_params(params)
    fields = {f.name for f in dataclasses.fields(link)}
    for attr in list(direct) + list(nested):
        if attr not in fields:
            raise ValueError(
                f"link {type(link).__name__} has no parameter "
                f"{attr!r}; sweepable fields: {sorted(fields)}")
    for attr, sub in nested.items():
        direct[attr] = rebind_link(getattr(link, attr), sub)
    return dataclasses.replace(link, **direct)


def world_slice(state, b: int):
    """World ``b``'s slice of a batched state pytree — the left-hand
    side of the batch exactness law (compare against the solo run's
    state with :func:`~timewarp_tpu.trace.events.assert_states_equal`)."""
    import jax
    return jax.tree.map(lambda x: x[b], state)


@dataclass(frozen=True)
class BatchSpec:
    """A fleet declaration for the world axis (module docstring).

    ``seeds`` — one engine seed per world (world count B = len(seeds);
    replaces the engine's ``seed`` argument). ``link_params`` — optional
    mapping of dotted link-model attribute paths to length-B vectors of
    per-world values (``None``: all worlds share the engine's link).
    """
    seeds: Tuple[int, ...]
    link_params: Optional[Mapping[str, Any]] = None

    def __post_init__(self) -> None:
        seeds = tuple(int(s) for s in self.seeds)
        if not seeds:
            raise ValueError("a batch needs at least one world "
                             "(BatchSpec.seeds is empty)")
        object.__setattr__(self, "seeds", seeds)
        if self.link_params is not None:
            lp = {}
            for path, v in dict(self.link_params).items():
                arr = np.asarray(v)
                if arr.ndim != 1 or arr.shape[0] != len(seeds):
                    raise ValueError(
                        f"link_params[{path!r}] must be one value per "
                        f"world, shape [{len(seeds)}]; got {arr.shape}")
                lp[path] = arr
            object.__setattr__(self, "link_params", lp)

    @property
    def B(self) -> int:
        return len(self.seeds)

    @classmethod
    def of(cls, batch: Optional[int] = None,
           seeds: Optional[Sequence[int]] = None, *,
           base_seed: int = 0,
           link_params: Optional[Mapping[str, Any]] = None
           ) -> "BatchSpec":
        """The CLI constructor: ``--batch B`` -> seeds
        ``base_seed .. base_seed+B-1``; ``--seeds a:b`` -> the explicit
        half-open range. Both given must agree on B."""
        if seeds is not None:
            seeds = tuple(int(s) for s in seeds)
            if batch is not None and batch != len(seeds):
                raise ValueError(
                    f"--batch {batch} disagrees with --seeds "
                    f"({len(seeds)} worlds)")
        elif batch is not None:
            seeds = tuple(base_seed + i for i in range(batch))
        else:
            raise ValueError("BatchSpec.of needs batch= or seeds=")
        return cls(seeds=seeds, link_params=link_params)

    # -- per-world views --------------------------------------------------

    def world_link(self, link, b: int):
        """World ``b``'s concrete (host-level) link model: the engine's
        link with this world's parameters substituted as Python
        scalars. This is the link a solo run must use to reproduce
        world b bit-for-bit, and the object whose ``min_delay_us``
        gates windowed execution for the whole batch (the batched
        engine validates its window against the min over worlds)."""
        if not self.link_params:
            return link
        return rebind_link(link, {
            path: v[b].item() for path, v in self.link_params.items()})
