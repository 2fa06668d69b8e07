"""Device-mesh communication layer — the collectives the sharded
engines ride (SURVEY.md §2.5/§5.8).

Simulated-node message passing maps onto XLA collectives over the
mesh's ICI — ``ppermute`` for fixed shift topologies (the token ring's
neighbor exchange), ``lax.all_to_all`` for dynamic destinations —
instead of the reference's TCP sockets
(`/root/reference/src/Control/TimeWarp/Rpc/Transfer.hs:473,577`).

:class:`MeshComm` substitutes mesh collectives behind the single-chip
:class:`~timewarp_tpu.interp.jax_engine.common.LocalComm` interface so
one superstep implementation serves both; :class:`ShardedDriver` is
the shared ``shard_map`` run harness (state placement with
``NamedSharding`` so XLA keeps every per-node array resident on its
owning device across the whole loop, plus the jitted scan/while
wrappers).
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple, Union

from ..utils import jaxconfig  # noqa: F401

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..interp.jax_engine.common import LocalComm, padded_scan


def _smap(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking off (the engines'
    collectives are hand-placed; the checker rejects the
    boundary-slice ppermute pattern)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


__all__ = ["AxisName", "Mesh", "MeshComm", "ShardedDriver", "axis_size",
           "make_mesh"]

#: a mesh axis: one name, or a tuple of names whose row-major product
#: the collectives flatten over (multi-slice meshes)
AxisName = Union[str, Tuple[str, ...]]


def make_mesh(n_devices: Optional[int] = None,
              axis: str = "nodes", *,
              shape: Optional[tuple] = None,
              axes: Optional[tuple] = None) -> Mesh:
    """A 1-D mesh over the first ``n_devices`` devices, or — with
    ``shape``/``axes`` — a multi-axis mesh, e.g.
    ``make_mesh(shape=(2, 4), axes=("dcn", "ici"))`` for a two-slice
    deployment. The engines accept the axis-name *tuple* wherever they
    take an axis: every collective (psum / all_gather / ppermute /
    all_to_all) runs over the flattened row-major product, so the same
    boundary-slice ring and destination-shard exchange span slices —
    lay the minor axis over ICI so the high-traffic neighbor hops stay
    intra-slice."""
    devs = jax.devices()
    if shape is not None:
        n = int(np.prod(shape))
        if axes is None or len(axes) != len(shape):
            raise ValueError("axes must name every mesh dimension")
        if len(devs) < n:
            raise ValueError(
                f"mesh shape {shape} needs {n} devices, have {len(devs)}")
        return Mesh(np.asarray(devs[:n]).reshape(shape), tuple(axes))
    if axes is not None:
        raise ValueError("axes= requires shape=")
    if n_devices is None:
        n_devices = len(devs)
    return Mesh(np.asarray(devs[:n_devices]), (axis,))


def axis_size(mesh: Mesh, axis: AxisName) -> int:
    """Total device count of ``axis`` (a name or a tuple of names)."""
    if isinstance(axis, tuple):
        return int(np.prod([mesh.shape[a] for a in axis]))
    return mesh.shape[axis]


class MeshComm(LocalComm):
    """Mesh collectives behind the LocalComm interface; valid only
    inside a ``shard_map`` body with ``axis`` bound."""

    def __init__(self, axis: AxisName, n_global: int,
                 n_shards: int) -> None:
        if n_global % n_shards:
            raise ValueError(
                f"n_nodes {n_global} not divisible by {n_shards} shards")
        self.axis = axis
        self.n_global = n_global
        self.n_shards = n_shards
        self.n_local = n_global // n_shards

    def node_ids(self) -> jax.Array:
        off = jax.lax.axis_index(self.axis).astype(jnp.int32) \
            * jnp.int32(self.n_local)
        return off + jnp.arange(self.n_local, dtype=jnp.int32)

    def all_min(self, x: jax.Array) -> jax.Array:
        # Not ``pmin``: the int64 min-all-reduce fails to lower on the
        # TPU compiler path ("Supported lowering only of Sum all
        # reduce"); gathering one scalar per device and reducing
        # locally lowers everywhere and costs D words on ICI.
        return jax.lax.all_gather(x, self.axis).min()

    def all_sum(self, x: jax.Array) -> jax.Array:
        return jax.lax.psum(x, self.axis)

    def all_max(self, x: jax.Array) -> jax.Array:
        # same gather-then-reduce shape as all_min (pmax shares pmin's
        # lowering caveat on the TPU compiler path)
        return jax.lax.all_gather(x, self.axis).max()

    def roll(self, x: jax.Array, s: int) -> jax.Array:
        """Global roll by ``s`` along the last (node) axis: local roll +
        boundary-slice ``ppermute`` to the next shard (and a whole-shard
        ``ppermute`` when ``s`` spans shards). One ICI neighbor hop for
        the ring's s=1."""
        s = s % self.n_global
        if s == 0:
            return x
        D, nl = self.n_shards, self.n_local
        whole, rem = divmod(s, nl)
        if whole:
            perm = [(i, (i + whole) % D) for i in range(D)]
            x = jax.lax.ppermute(x, self.axis, perm)
        if rem:
            tail = x[..., nl - rem:]
            perm = [(i, (i + 1) % D) for i in range(D)]
            recv = jax.lax.ppermute(tail, self.axis, perm)
            x = jnp.concatenate([recv, x[..., :nl - rem]], axis=-1)
        return x

    def local_rows(self, table: np.ndarray) -> jax.Array:
        off = jax.lax.axis_index(self.axis).astype(jnp.int32) \
            * jnp.int32(self.n_local)
        return jax.lax.dynamic_slice_in_dim(
            jnp.asarray(table), off, self.n_local, axis=-1)


class ShardedDriver:
    """Shared ``shard_map`` driver for the sharded engines. The
    concrete engine supplies ``_state_specs`` (its state's
    PartitionSpecs, built from :meth:`_leaf_spec`), ``_superstep``,
    the quiet driver's ``while`` on one device's shard
    (``_quiet_loop``, inherited from its local base class,
    ``JaxEngine`` or ``EdgeEngine``: both carry the state's horizon,
    reduced over the mesh where a superstep produces it, so no
    loop's condition holds a collective; the world-sharded fleet's
    reads its own device's worlds, each device its own trip count:
    ``last_run_stats`` ``device_iterations``) and what the loops carry
    beside the state (``_counted``, ``_step_counted``; the edge
    engine's quiet body: ``_step_carried``). ``_next_event``, a local
    base class's probe of a state at rest, is asked by no loop."""

    def _leaf_spec(self, x, last_axis: bool) -> P:
        """PartitionSpec for one state leaf: the node axis (leading or
        trailing per the engine's layout) sharded over the mesh axis,
        everything else replicated; scalars fully replicated."""
        ax = self.axis
        nd = getattr(x, "ndim", 0)
        if nd == 0:
            return P()
        if last_axis:
            return P(*([None] * (nd - 1) + [ax]))
        return P(ax, *([None] * (nd - 1)))

    def init_state(self):
        """The local engine's initial state, each leaf placed by its
        spec: where a driver returns it, so that a run streamed in
        calls (each on the state the last returned) is one compiled
        program from the first call on. A leaf with no entries (the
        edge engine's ``q_step`` under a commutative inbox) comes back
        from a driver replicated whatever its spec says, and a state
        that went in otherwise would compile the second call anew."""
        st = super().init_state()
        specs = self._state_specs(st)
        return jax.tree.map(
            lambda x, s: jax.device_put(
                x, NamedSharding(self.mesh, s if x.size else P())),
            st, specs)

    def _trace_spec(self) -> P:
        """PartitionSpec of one scan-trace leaf: replicated for the
        node-sharded engines (trace scalars are already psum'd mesh-
        wide); the world-sharded engine overrides (per-world rows live
        on the world's device)."""
        return P()

    def _carry_specs(self, st, specs):
        """PartitionSpecs of what a driver returns of its loop's
        carry: the state's and, beside it, what the engine counts
        there (``_counted``). The general engines' routing counts
        (``JaxEngine._counted``): a world to a row in the
        world-sharded fleet, replicated scalars in a node-sharded
        world (whose routing counts its full width, the same on every
        device; beside them its exchange's two counts are a row a
        shard: ``ShardedEngine`` overrides). The edge engine's
        boundary messages are a row a shard (``ShardedEdgeEngine``
        overrides)."""
        world = getattr(self, "worlds_local", None) is not None
        return specs, jax.tree.map(
            lambda x: P(self.axis, *[None] * (x.ndim - 1)) if world
            else P(), jax.eval_shape(self._counted, st)[1])

    @partial(jax.jit, static_argnums=(0, 2))
    def _run_scan(self, st, n_pad: int, max_steps, dyn=None,
                  ident=None):
        # pow2-padded scan length + masked tail, the shared
        # compile-reuse contract (jax_engine/common.py padded_scan).
        # `dyn` is the dispatch controller's traced knob operand
        # (jax_engine/controlled.py) — replicated scalars, bound onto
        # `self` inside the shard_map body exactly like the local
        # driver binds them, so one superstep implementation reads
        # them in both venues. `ident` is the world-sharded fleet's
        # per-world identity operand (jax_engine/batched.py
        # WorldIdentity) — replicated [B] arrays, bound the same way;
        # _step_all slices this device's worlds by mesh position.
        # Node-sharded engines pass None (an empty pytree: the
        # operand list is unchanged, so their jaxprs are too).
        specs = self._state_specs(st)
        # per-world budget vectors on the WORLD-sharded engine: the
        # replicated [B] budget must mask this device's local world
        # slice (the scan carry is [B/D, ...]) — slice it by mesh
        # position exactly like _step_all slices the world context.
        # Node-sharded engines never see a vector (batch is None).
        Bl = getattr(self, "worlds_local", None)
        ms_vec = getattr(max_steps, "ndim", 0) == 1

        def local_ms(ms):
            if not ms_vec or Bl is None:
                return ms
            off = jax.lax.axis_index(self.axis).astype(jnp.int32) \
                * jnp.int32(Bl)
            return jax.lax.dynamic_slice_in_dim(ms, off, Bl, 0)

        dyn_specs = jax.tree.map(lambda _: P(), dyn)
        ident_specs = jax.tree.map(lambda _: P(), ident)

        def body(s, ms, dy, idn):
            self._dyn = dy
            self._ident_in = idn
            try:
                # every sharded engine carries its counts beside the
                # state (``_counted``: the general engines' routing
                # counts, the edge engine's boundary messages)
                return padded_scan(self._step_counted,
                                   self._counted(s), n_pad,
                                   local_ms(ms))
            finally:
                self._dyn = None
                self._ident_in = None

        return _smap(body, self.mesh,
                     (specs, P(), dyn_specs, ident_specs),
                     (self._carry_specs(st, specs), self._trace_spec()))(
            st, max_steps, dyn, ident)

    @partial(jax.jit, static_argnums=(0,))
    def _run_while(self, st, max_steps, ident=None):
        specs = self._state_specs(st)
        max_steps = jnp.asarray(max_steps, jnp.int64)
        ident_specs = jax.tree.map(lambda _: P(), ident)

        def body_fn(s, ms, idn):
            self._ident_in = idn
            try:
                return self._quiet_loop(s, ms)
            finally:
                self._ident_in = None

        return _smap(body_fn, self.mesh, (specs, P(), ident_specs),
                     self._carry_specs(st, specs))(st, max_steps, ident)
