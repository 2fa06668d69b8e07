"""Builder of steady mongering laid over a mesh by nodes: the
node-sharded general engine (``ShardedEngine(sc, link, make_mesh(4),
window=1, seed=0, bucket_cap=...)``: what ``python -m timewarp_tpu gossip
--engine sharded --steady --devices 4 --bucket-cap N`` builds) runs one
world whose node axis is divided over the cell's chips, every message
bucketed by its destination's chip and handed over by ``all_to_all``,
streamed in jobs of a fixed number of supersteps, each ``run_quiet`` on
the sharded state the last returned and ended by one readback of its
counters.

The scenario, the link, the origin's swap, the ramp and its gate, a
job's supersteps and their gates, the facts, the comparison's twenty
rows and two of the controls are ``builders/gossip_steady.py``'s: this
``Cell`` is that one with another engine, another layout of the state
and further gates, as ``sharded_ring.Cell`` is ``fused_ring.Cell``'s.
What is new is where the state lives and what crosses the mesh: the
gates hold every job to the first, and the comparison holds what the
engine counted of the second (``last_run_stats`` ``remote_msgs``,
``bucket_fill_peak``) to the plain reference's count of the same
rounds. README_steady_x4.md has the page.
"""

import jax

import steady_x4_costs
import x4_reduce
from builders import gossip_steady
from timewarp_tpu.interp.jax_engine.sharded import ShardedEngine
from timewarp_tpu.parallel.mesh import make_mesh

#: what a job keeps of its call's ``last_run_stats`` for the comparison
#: and the readers; None from a program that does not count them
_COUNTED = ("remote_msgs", "bucket_fill_peak", "exchange_lanes")


def engine_of(p, mailbox_cap=None, bucket_cap=None):
    """The configuration's engine: ``gossip_steady.engine_of``'s
    scenario and link on a mesh of ``devices`` chips; ``mailbox_cap``
    and ``bucket_cap`` stand in for the configuration's own (the
    controls)."""
    solo = gossip_steady.engine_of(p, mailbox_cap)
    return ShardedEngine(
        solo.scenario, solo.link, make_mesh(int(p["devices"]), p["axis"]),
        axis=p["axis"], window=p["window"], seed=int(p["engine_seed"]),
        bucket_cap=int(p["bucket_cap"] if bucket_cap is None
                       else bucket_cap))


class Cell(gossip_steady.Cell):
    def __init__(self, config, traffic, *, interpret=False):
        p = config["params"]
        self.chips = int(traffic["chips"])
        if int(p["devices"]) != self.chips:
            raise SystemExit(f"benchmark: the configuration's {p['devices']} "
                             f"devices are not the cell's {self.chips} chips")
        if len(jax.devices()) < self.chips:
            raise SystemExit(f"benchmark: the cell's mesh needs "
                             f"{self.chips} devices, JAX found "
                             f"{len(jax.devices())}")
        # the reduction of a state to facts, the ramp, the jobs; its
        # one-chip engine is built and never run
        super().__init__(config, traffic, interpret=interpret)
        self.engine = engine_of(p)
        fresh = self.engine.init_state()
        counts = jax.eval_shape(self.engine._counted, fresh)[1]
        if getattr(counts, "remote_msgs", None) is None:
            raise SystemExit("benchmark: this program's ShardedEngine does "
                             "not count what its exchange hands over "
                             "(remote_msgs, bucket_fill_peak: PR 49), and "
                             "the cell's comparison holds it to them")
        #: where every leaf of a fresh state lives: a job's result has
        #: to live there too
        self._fresh = [(jax.tree_util.keystr(path), x.sharding, x.ndim)
                       for path, x in
                       jax.tree_util.tree_leaves_with_path(fresh)]

    def set_up(self, seed):
        self._window = []                # the window's jobs' counts
        return super().set_up(seed)

    # -- one job ----------------------------------------------------------

    def _placement(self, st):
        """What is wrong with where the job's result lives: its
        ``wake`` has to be one slice a chip, each ``n / chips`` wide,
        on distinct devices at distinct offsets
        (``sharded_ring.Cell._placement``), and every leaf has to come
        back laid out as the same leaf of the fresh state
        (``gossip_fleet_x4.Cell._placement``)."""
        shards = st.wake.addressable_shards
        shapes = {s.data.shape for s in shards}
        offsets = {s.index[0].start or 0 for s in shards}
        devices = {s.device for s in shards}
        why = []
        if (len(shards), shapes) != (self.chips,
                                     {(self.n // self.chips,)}) \
                or len(offsets) != self.chips or len(devices) != self.chips:
            why.append(f"wake lives as {len(shards)} shards of "
                       f"{sorted(shapes)} at {len(offsets)} offsets on "
                       f"{len(devices)} devices")
        moved = [name for (name, sharding, ndim), leaf in zip(
                     self._fresh, jax.tree.leaves(st))
                 if not sharding.is_equivalent_to(leaf.sharding, ndim)]
        if moved:
            why.append(f"leaves laid out otherwise than a fresh state's: "
                       f"{moved}")
        return why

    def job(self, i, why=()):
        res = super().job(i, why)
        stats = self.engine.last_run_stats      # still this job's call
        gates = self._placement(self.state)
        if (stats["dispatches"], stats["readbacks"]) != (1, 1):
            gates.append(f"{stats['dispatches']} dispatches, "
                         f"{stats['readbacks']} readbacks in the call")
        res["failed"] = "; ".join(filter(None, [res["failed"]] + gates))
        res.update({key: stats[key] for key in _COUNTED})
        if i:
            self._window.append(res)
        return res

    # -- what decides `correct` -------------------------------------------

    def compare(self, reference, stand_in=None):
        """``gossip_steady.Cell.compare``'s rows and, of the program
        itself, four more: what it counted as handed to another chip in
        the first job of the window and in the whole window against the
        plain reference's remote pushes of the same rounds, its fullest
        bucket of the window against the reference's largest of the
        same rounds (all exact, limit 0), and the reference's largest
        bucket of the whole run against the engine's ``bucket_cap``. A
        stand-in brings facts and no counts: the link's low word moves
        no push to another shard."""
        rows = super().compare(reference, stand_in)
        if stand_in is not None:
            return rows
        # every plane's names, where the steady cell's reads the first's
        self._op_names = x4_reduce.traced_op_names(self.workload, self.seed)
        ref = self._mongering            # run to the window's end by now
        end = int(self.state.steps)
        first = int(self.first.steps) - self.per_job
        remote = [j["remote_msgs"] for j in self._window]
        return rows + [
            ("first_job.remote_msgs.off_by", abs(
                remote[0] - ref.remote_pushes(first, first + self.per_job)),
             0),
            ("window.remote_msgs.off_by",
             abs(sum(remote) - ref.remote_pushes(first, end)), 0),
            ("window.bucket_fill_peak.off_by", abs(
                max(j["bucket_fill_peak"] for j in self._window)
                - ref.largest_bucket(first, end)), 0),
            ("reference.largest_bucket", ref.largest_bucket(0, end),
             self.engine.bucket_cap)]

    def control(self, reference):
        """``gossip_steady.Cell.control``'s two and a third that has to
        fail: the program built with the control's ``bucket_cap``, the
        mean of a bucket, which cuts about half the buckets of every
        superstep (``overflow`` counts what was cut; the reference
        loses nothing)."""
        rows = super().control(reference)
        if all(v <= limit for _, v, limit in rows):
            return rows                  # one that passed, alone
        small = self._stream_rows(reference, engine_of(
            self.p, bucket_cap=self.control_of["bucket_cap"]))
        if all(v <= limit for _, v, limit in small):
            print("the control small_bucket passed the comparison")
            return small
        return rows + [(f"small_bucket.{row}", v, limit)
                       for row, v, limit in small]

    def _small_mailbox(self, reference):
        return self._stream_rows(reference, engine_of(
            self.p, mailbox_cap=self.control_of["mailbox_cap"]))

    def _stream_rows(self, reference, engine):
        """``gossip_steady.Cell._small_mailbox`` on ``engine``: the
        comparison's rows after the first job of the window of a stream
        from the same origin, and what that engine counted as
        overflow."""
        st, _ = self._ramp(engine)
        for _ in range((int(self.first.steps) - self.ramp) // self.per_job):
            st = self._advance(engine, st)[0]
        return self._rows("first_job", self._facts(st),
                          self._want(reference, int(st.steps))) + [
            ("first_job.overflow", int(st.overflow), 0)]

    # -- counts for the per-layer readers ---------------------------------

    def facts(self):
        eng, sc = self.engine, self.engine.scenario
        return {"op_names": self._op_names,
                "bucket_cap": eng.bucket_cap,
                "payload_width": sc.payload_width,
                "superstep_bytes": steady_x4_costs.superstep_bytes(
                    self.n // self.chips, sc.mailbox_cap, sc.payload_width,
                    self.chips, eng.bucket_cap)}
