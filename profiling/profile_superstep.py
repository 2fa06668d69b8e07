"""Per-piece timing of the engine superstep on the current backend.

Times the building blocks of the *round-1* `JaxEngine._superstep`
design in isolation at the bench shapes (pieces 3-6 measure the old
int64-lexsort/scatter path on purpose — they are the evidence behind
docs/engines.md "Measured on a v5e"), then the full current superstep.
Run on the chip through the chip tool (one process per chip); a CPU
run says nothing about speed.

Isolated per-dispatch numbers are enqueue times unless the result is
read back; trust only the in-scan FULL-superstep figures at the
bottom.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from timewarp_tpu.utils import jaxconfig  # noqa: F401

import jax
import jax.numpy as jnp
import numpy as np

from timewarp_tpu.core.rng import fire_bits, msg_bits
from timewarp_tpu.core.scenario import NEVER
from timewarp_tpu.interp.jax_engine.engine import JaxEngine
from timewarp_tpu.models.token_ring import token_ring
from timewarp_tpu.net.delays import FixedDelay

N = int(os.environ.get("TW_PROF_NODES", 65536))
K = 4
M = 2
P = 2
REPS = int(os.environ.get("TW_PROF_REPS", 20))


def bench(name, fn, *args):
    fn2 = jax.jit(fn)
    out = jax.block_until_ready(fn2(*args))  # compile
    t0 = time.perf_counter()
    for _ in range(REPS):
        out = fn2(*args)
    jax.block_until_ready(out)
    dt = (time.perf_counter() - t0) / REPS
    print(json.dumps({"piece": name, "ms": round(dt * 1e3, 3)}))
    return dt


def main():
    print(json.dumps({"platform": jax.devices()[0].platform, "N": N}))
    key = jax.random.PRNGKey(0)
    node_ids = jnp.arange(N, dtype=jnp.int32)
    t = jnp.int64(12345)
    mb_time = jnp.where(
        jax.random.bernoulli(key, 0.5, (N, K)),
        jnp.int64(12345), NEVER)
    mb_valid = mb_time < NEVER
    mb_src = jnp.zeros((N, K), jnp.int32)
    mb_payload = jnp.zeros((N, K, P), jnp.int32)
    slots = jnp.broadcast_to(jnp.arange(K, dtype=jnp.int32), (N, K))

    S = N * M
    src_f = jnp.repeat(node_ids, M)
    slot_f = jnp.tile(jnp.arange(M, dtype=jnp.int32), N)
    dst_f = (src_f + 1) % N
    v_f = jnp.ones((S,), bool)

    # 1. fire entropy derivation (elementwise threefry, core/rng.py)
    bench("fire_bits [N]",
          lambda s: fire_bits(1, s, node_ids, t)[0], jnp.uint32(2))

    # 2. msg entropy derivation (elementwise threefry x3)
    bench("msg_bits [N*M]",
          lambda s: msg_bits(1, s, src_f, dst_f, t, slot_f)[0],
          jnp.uint32(2))

    # 3. inbox lexsort (3 keys incl. int64, [N, K])
    deliver = mb_valid
    bench("inbox lexsort [N,K]",
          lambda d, mt: jnp.lexsort((slots, mt, ~d), axis=-1), deliver,
          mb_time)

    # 4. compaction lexsort (2 keys, [N, K])
    bench("compact lexsort [N,K]",
          lambda kp: jnp.lexsort((slots, ~kp), axis=-1), mb_valid)

    # 5. routing argsort + searchsorted over S
    def route(dst, ok):
        sort_dst = jnp.where(ok, dst, N)
        perm3 = jnp.argsort(sort_dst, stable=True)
        sd = sort_dst[perm3]
        rank = jnp.arange(S, dtype=jnp.int32) - jnp.searchsorted(
            sd, sd, side="left").astype(jnp.int32)
        return perm3, rank
    bench("route argsort+searchsorted [S]", route, dst_f, v_f)

    # 6. mailbox scatter (4x .at[row, col].set)
    row = dst_f
    col = jnp.zeros((S,), jnp.int32)
    def scatter(mt, ms_, mp, mv):
        mt = mt.at[row, col].set(t, mode="drop")
        ms_ = ms_.at[row, col].set(src_f, mode="drop")
        mp = mp.at[row, col].set(jnp.zeros((S, P), jnp.int32), mode="drop")
        mv = mv.at[row, col].set(True, mode="drop")
        return mt, ms_, mp, mv
    bench("mailbox scatter x4", scatter, mb_time, mb_src, mb_payload,
          mb_valid)

    # 7. trace digests
    from timewarp_tpu.trace.hashing import FIRED, mix32_jnp
    bench("digest mix32 [N,K]x2",
          lambda s: (mix32_jnp(FIRED, s, s, s, s).astype(jnp.uint32).sum(),
                     mix32_jnp(FIRED, s, s).astype(jnp.uint32).sum()),
          mb_src)

    # 8. full current superstep
    sc = token_ring(N, n_tokens=N, think_us=0, bootstrap_us=1_000,
                    end_us=(1 << 50), with_observer=False, mailbox_cap=K)
    engine = JaxEngine(sc, FixedDelay(500))
    st = jax.block_until_ready(engine.init_state())
    st = jax.block_until_ready(engine.run_quiet(2, st))  # mid-flight state

    step = jax.jit(lambda s: engine._superstep(s, False)[0])
    out = jax.block_until_ready(step(st))
    t0 = time.perf_counter()
    cur = st
    for _ in range(REPS):
        cur = step(cur)
    jax.block_until_ready(cur)
    dt = (time.perf_counter() - t0) / REPS
    print(json.dumps({"piece": "FULL superstep (jit, dispatched per step)",
                      "ms": round(dt * 1e3, 3)}))

    # 9. full superstep inside while_loop (no per-step dispatch)
    st2 = jax.block_until_ready(engine.run_quiet(2, st))
    t0 = time.perf_counter()
    fin = jax.block_until_ready(engine.run_quiet(REPS * 4, st2))
    dt = (time.perf_counter() - t0) / (REPS * 4)
    print(json.dumps({"piece": "FULL superstep (while_loop)",
                      "ms": round(dt * 1e3, 3),
                      "delivered": int(fin.delivered - st2.delivered)}))


if __name__ == "__main__":
    main()
