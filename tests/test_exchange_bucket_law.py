"""The sharded exchange's send buffers against a plain bucketing.

``ShardedEngine._exchange`` sorts a device's outbox lanes by
destination shard and cuts each plane's ``[shards, bucket_cap]`` buffer
out of the sorted plane as ``shards`` slices (a bucket is a contiguous
run after the sort), masked by the run's length. The law: what every
device hands the ``all_to_all``s, the occupancy, the overflow count
and the two counts beside the state equal a lane-by-lane numpy
bucketing (arrival order within a shard, the first ``bucket_cap`` fit,
zeros elsewhere) word for word, whatever the mesh, the capacity and
the payload's width; and no scatter is left under the exchange's
scope.
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from timewarp_tpu.core.scenario import NEVER, Inbox, Outbox, Scenario
from timewarp_tpu.interp.jax_engine.sharded import ShardedEngine
from timewarp_tpu.models.gossip import gossip
from timewarp_tpu.net.delays import FixedDelay
from timewarp_tpu.parallel.mesh import _smap, make_mesh

#: nodes a shard and outbox slots a node: 64 lanes a device
NL, M = 16, 4
L = NL * M


def _engine(D, words, bucket_cap):
    def step(state, inbox: Inbox, now, i, key):
        out = Outbox(valid=jnp.zeros((M,), bool),
                     dst=jnp.zeros((M,), jnp.int32),
                     payload=jnp.zeros((M, words), jnp.int32))
        return state, out, jnp.int64(NEVER)

    sc = Scenario(name="lanes", n_nodes=D * NL, step=step,
                  init=lambda i: ({"x": jnp.int32(0)}, NEVER),
                  payload_width=words, max_out=M, mailbox_cap=4,
                  commutative_inbox=True)
    return ShardedEngine(sc, FixedDelay(500), make_mesh(D),
                         bucket_cap=bucket_cap, lint="off")


def _lanes(D, words, case, rng):
    """Every device's ``L`` lanes: validity, global destination, and
    the ``4 + words`` other planes, words no two lanes share."""
    ok = rng.random((D, L)) < 0.8
    dst = rng.integers(0, D * NL, (D, L))
    if case == "one_shard":
        dst = dst % NL + NL * (D - 1)
    if case == "none_valid":
        ok[:] = False
    rest = rng.permutation(D * L * (4 + words)).reshape(
        4 + words, D, L) + 1
    # _exchange's order: drel, src, dst, smrank, woff, payload words
    planes = [rest[0], rest[1], dst, *rest[2:]]
    return ok, [p.astype(np.int32) for p in planes]


def _plain(ok, planes, D, B):
    """One device's buffers, lane by lane: a valid lane takes the next
    column of its destination shard's row while one is free."""
    bufs = np.zeros((1 + len(planes), D, B), np.int32)
    fill = np.zeros(D, np.int64)
    for lane in np.flatnonzero(ok):
        d = planes[2][lane] // NL
        if fill[d] < B:
            bufs[0, d, fill[d]] = 1
            bufs[1:, d, fill[d]] = [p[lane] for p in planes]
        fill[d] += 1
    return bufs, fill


def _exchanged(eng, ok, planes):
    """``_exchange`` on every device of the mesh: what each received,
    laid back as the senders' buffers ``[plane, sender, shard, B]``,
    and each device's overflow and two counts."""
    D, B = eng.comm.n_shards, eng.bucket_cap

    def device(ok, *planes):
        planes = [p[0] for p in planes]
        *got, pay, ovf = eng._exchange(
            ok[0], *planes[:5], tuple(planes[5:]))
        # the rows come back local: put the shard's offset back
        here = jax.lax.axis_index(eng.axis).astype(jnp.int32)
        got[3] = got[3] + here * jnp.int32(NL)
        return (jnp.stack([x.astype(jnp.int32) for x in (*got, *pay)])[None],
                jnp.stack([ovf, *eng._exchanged])[None])

    got, counts = jax.jit(_smap(
        device, eng.mesh, P(eng.axis), P(eng.axis)))(ok, *planes)
    # device d's lanes [s * B, (s + 1) * B) are sender s's row d
    got = np.asarray(got).reshape(D, -1, D, B).transpose(1, 2, 0, 3)
    return got, np.asarray(counts)


CASES = ("under", "at", "over", "default", "one_shard", "none_valid")


@pytest.mark.parametrize("words", (1, 2))
@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("D", (2, 4, 8))
def test_the_buffers_are_the_plain_bucketing(D, case, words):
    rng = np.random.default_rng(50 + D)
    ok, planes = _lanes(D, words, case, rng)
    fullest = max(_plain(ok[s], [p[s] for p in planes], D, L)[1].max()
                  for s in range(D))
    cap = {"under": max(fullest // 2, 1), "at": fullest,
           "over": fullest + 3, "one_shard": L // 2,
           "none_valid": 5}.get(case)
    eng = _engine(D, words, cap)
    B = eng.bucket_cap
    assert B == (L if cap is None else cap)
    got, counts = _exchanged(eng, jnp.asarray(ok),
                             [jnp.asarray(p) for p in planes])
    lost = 0
    for s in range(D):
        bufs, fill = _plain(ok[s], [p[s] for p in planes], D, B)
        assert np.array_equal(got[:, s], bufs), (s, case)
        lost += int(np.maximum(fill - B, 0).sum())
        assert counts[s, 1] == fill.sum() - fill[s]
        assert counts[s, 2] == fill.max(initial=0)
    assert (counts[:, 0] == lost).all()
    if case in ("under", "one_shard"):
        assert lost > 0
    elif case != "none_valid":
        assert lost == 0 and got[0].sum() == ok.sum()


def test_no_scatter_is_left_under_the_exchange():
    """The compiled quiet driver of a ``ShardedEngine`` on the virtual
    mesh: the operations named for ``tw.route/exchange`` hold the
    slices and no scatter (the general engine's insertion, under
    ``tw.route/insert``, keeps its own)."""
    sc = gossip(64, fanout=2, think_us=2_000, gossip_interval=1_000,
                end_us=300_000, mailbox_cap=8)
    eng = ShardedEngine(sc, FixedDelay(500), make_mesh(4), bucket_cap=24)
    text = type(eng)._run_while.lower(
        eng, eng.init_state(), jnp.int64(4), None).compile().as_text()
    named = re.findall(
        r"= .*? ([a-z][a-z0-9-]*)\(.*op_name=\"[^\"]*tw\.route/exchange",
        text)
    assert "dynamic-slice" in named and "all-to-all" in named
    assert "scatter" not in named
