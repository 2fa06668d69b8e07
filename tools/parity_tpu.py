"""TPU-in-the-loop parity artifact: oracle-on-CPU vs engine-on-TPU.

Runs the host oracle with all jax computation pinned to the CPU
backend and the batched engines on the default accelerator (the TPU
when one is attached), compares the event traces bit-for-bit, and
writes ``PARITY_TPU.json`` with per-config digests. Integer-only link
models, so equality is exact across backends (core/rng.py, SURVEY.md
§5.2).

Configs: ping-pong (BASELINE config 1), token-ring 64 fixed-latency
(config 2, edge engine), token-ring 64 w/ observer + uniform links
(general engine), gossip-64 w/ drops, the round-4 execution modes:
burst-gossip and burst-praos under a multi-instant window (all
integer link models; the praos row ran under a generous ``route_cap``
until PR 57 took the knob away: the same trace), plus — round 6 —
socket-state (BASELINE config 3's batched twin, models/socket_state.py)
at the baseline shape and at the 1024-node windowed hub-fan-in shape.

Round 9 adds a **faulted column** on the gossip row: the same
config re-run under a mixed fault schedule (reset crash + partition +
degradation window, faults/) through both the oracle and the general
engine — trace AND ``fault_dropped`` counter bit-compared, so the
chaos subsystem's parity law is pinned on the artifact hardware.

Round 7 adds a **batched column**: the batch exactness law
(engine.py ``batch=BatchSpec``) on the artifact hardware — each
general-engine config runs a 3-world batched fleet (seeds 0/1/2) and
every world's trace is compared bit-for-bit against the solo run with
that seed (world 0 against the solo column itself). Engines without
the world axis record the refusal, never a silent absence.

Usage: ``python tools/parity_tpu.py`` (writes PARITY_TPU.json at the
repo root). Exits nonzero on any trace mismatch, and refuses to write
the artifact where JAX found no TPU. One process does all chip work.
``--self-check`` (CI mode) runs the same comparison anywhere and
writes nothing — on a CPU-only runner the engines and oracle share a
backend, so it degrades to an engine≡oracle gate.
"""

import hashlib
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from timewarp_tpu.utils import jaxconfig  # noqa: E402

import jax  # noqa: E402
import numpy as np  # noqa: E402


def trace_sha(tr) -> str:
    h = hashlib.sha256()
    for f in ("times", "fired_count", "fired_hash", "recv_count",
              "recv_hash", "sent_count", "sent_hash", "overflow"):
        h.update(np.ascontiguousarray(getattr(tr, f)).tobytes())
    return h.hexdigest()[:16]


def main() -> int:
    from timewarp_tpu.interp.jax_engine.edge_engine import EdgeEngine
    from timewarp_tpu.interp.jax_engine.engine import (BatchSpec,
                                                       JaxEngine)
    from timewarp_tpu.interp.ref.superstep import SuperstepOracle
    from timewarp_tpu.models.gossip import gossip
    from timewarp_tpu.models.ping_pong import ping_pong
    from timewarp_tpu.models.praos import praos
    from timewarp_tpu.models.socket_state import socket_state
    from timewarp_tpu.models.token_ring import token_ring, token_ring_links
    from timewarp_tpu.net.delays import (
        FixedDelay, Quantize, UniformDelay, WithDrop)
    from timewarp_tpu.trace.events import TraceMismatch, assert_traces_equal

    self_check = "--self-check" in sys.argv
    jaxconfig.enable_compile_cache()
    jaxconfig.keep_host_cpu()
    platform = jax.devices()[0].platform
    if platform != "tpu" and not self_check:
        # the artifact is named for the TPU: a CPU run is never
        # written under that name
        raise SystemExit(
            f"parity_tpu: JAX found {platform!r}, not a TPU — "
            "PARITY_TPU.json is written from a chip run only (use "
            "--self-check for the engine≡oracle gate on the CPU)")
    cpu = jax.devices("cpu")[0]

    wlink = Quantize(UniformDelay(3_000, 9_000), 1_000)  # min delay 3 ms
    configs = {
        "ping-pong": (
            ping_pong(rounds=50), UniformDelay(500, 2_000),
            JaxEngine, 400, {}),
        "token-ring-64-fixed": (
            token_ring(64, n_tokens=16, think_us=2_000, bootstrap_us=1000,
                       end_us=400_000, with_observer=False, mailbox_cap=6),
            FixedDelay(1_500), EdgeEngine, 600, {}),
        "token-ring-64-observer": (
            token_ring(64, n_tokens=8, think_us=3_000, bootstrap_us=1000,
                       end_us=300_000, with_observer=True, mailbox_cap=16),
            token_ring_links(64), JaxEngine, 600, {}),
        "gossip-64-drop": (
            gossip(64, fanout=6, think_us=3_000, gossip_interval=1_000,
                   end_us=5_000_000),
            WithDrop(UniformDelay(2_000, 30_000), 0.15), JaxEngine, 800, {}),
        # round-4 execution modes: multi-instant windows, burst
        # diffusion — the sparse-regime machinery, proven on the real
        # chip
        "gossip-64-burst-windowed": (
            gossip(64, fanout=4, think_us=700, burst=True,
                   end_us=400_000, mailbox_cap=16),
            wlink, JaxEngine, 600, {"window": 3_000}),
        "praos-48-burst-windowed": (
            praos(48, slot_us=20_000, n_slots=6, leader_prob=2.0 / 48,
                  fanout=4, burst=True, mailbox_cap=16),
            wlink, JaxEngine, 600, {"window": 3_000}),
        # round 6: BASELINE config 3's batched twin — the per-socket
        # user-state example, value-stream-tied to the net world in
        # tests/test_cross_world_socket_state.py; here it holds the
        # same bit-exact oracle ≡ engine law as every other config
        # (the deadline shape: the listener stop-gate actually bites)
        "socket-state-4": (
            socket_state(n_clients=3, seed=24, send_interval_us=50_000,
                         server_life_us=120_000),
            wlink, JaxEngine, 400, {}),
        # the 1024-node windowed hub-fan-in shape: the hard regime
        # for insertion's hole accounting — a 1023-way co-temporal
        # fan-in overflowing the hub mailbox
        "socket-state-1024-windowed": (
            socket_state(n_clients=1023, seed=1,
                         send_interval_us=20_000,
                         server_life_us=2_000_000, mailbox_cap=64),
            wlink, JaxEngine, 250, {"window": 3_000}),
        # the bench's wave family at artifact scale: burst gossip at
        # 1024 nodes under the 3 ms window
        "gossip-1024-burst-windowed": (
            gossip(1024, fanout=4, think_us=700, burst=True,
                   end_us=400_000, mailbox_cap=16),
            wlink, JaxEngine, 600, {"window": 3_000}),
    }

    out = {"engine_platform": platform, "oracle_platform": "cpu",
           "configs": {}, "ok": True}
    for name, (sc, link, eng_cls, steps, ekw) in configs.items():
        with jax.default_device(cpu):
            otrace = SuperstepOracle(
                sc, link, window=ekw.get("window", 1)).run(20 * steps)
        engine = eng_cls(sc, link, **ekw)
        _, etrace = engine.run(steps)
        entry = {
            "supersteps": len(etrace),
            "delivered": etrace.total_delivered(),
            "oracle_sha": trace_sha(otrace),
            "engine_sha": trace_sha(etrace),
        }
        try:
            # a shorter engine trace is only legitimate when the step
            # cap was actually hit; premature quiescence (fewer rows
            # than budgeted) must fail the length check, not be
            # prefix-compared away
            truncated = len(etrace) == steps and len(otrace) > steps
            entry["truncated_at_step_cap"] = truncated
            assert_traces_equal(otrace, etrace, "oracle-cpu",
                                f"engine-{platform}",
                                limit=steps if truncated else None)
            entry["equal"] = True
        except TraceMismatch as e:
            entry["equal"] = False
            entry["mismatch"] = str(e)
            out["ok"] = False

        # faulted column (round 9): the gossip row re-run under a
        # mixed crash+partition+degradation schedule — oracle ≡
        # engine bit-for-bit, chaos included (faults/)
        if name == "gossip-64-drop":
            from timewarp_tpu.faults import (FaultSchedule, LinkWindow,
                                             NodeCrash, Partition)
            fsched = FaultSchedule((
                NodeCrash(3, 200_000, 900_000, reset_state=True),
                NodeCrash(17, 100_000, 500_000),
                Partition((tuple(range(32)), tuple(range(32, 64))),
                          300_000, 1_200_000),
                LinkWindow(None, None, 1_500_000, 2_500_000,
                           scale=2.0, extra_us=1_000),
            ))
            with jax.default_device(cpu):
                fo = SuperstepOracle(sc, link, faults=fsched)
                fotrace = fo.run(20 * steps)
            feng = JaxEngine(sc, link, faults=fsched)
            fstate, fetrace = feng.run(steps)
            fent = {"supported": True,
                    "sha": trace_sha(fetrace),
                    "fault_dropped": int(fstate.fault_dropped)}
            try:
                assert_traces_equal(fotrace, fetrace, "oracle-cpu",
                                    f"faulted-engine-{platform}")
                assert fo.fault_dropped_total == \
                    int(fstate.fault_dropped), (
                        f"fault_dropped diverged: oracle "
                        f"{fo.fault_dropped_total} vs engine "
                        f"{int(fstate.fault_dropped)}")
                fent["equal"] = True
            except (TraceMismatch, AssertionError) as e:
                fent["equal"] = False
                fent["mismatch"] = str(e)
                out["ok"] = False
            entry["faulted"] = fent

        # batched multi-world column (round 7): the batch exactness
        # law on the artifact hardware — every world of a 3-world
        # fleet sliced against the solo run with that world's seed.
        # World 0 shares the solo column's seed=0, so its trace must
        # equal `etrace` itself.
        if eng_cls is JaxEngine:
            batched = JaxEngine(sc, link, batch=BatchSpec(
                seeds=(0, 1, 2)), **ekw)
            _, btr = batched.run(steps)
            bent = {"supported": True,
                    "sha": [trace_sha(t) for t in btr]}
            try:
                assert_traces_equal(etrace, btr[0],
                                    f"solo-{platform}",
                                    f"batched-w0-{platform}")
                for b in (1, 2):
                    _, strc = JaxEngine(sc, link, seed=b,
                                        **ekw).run(steps)
                    assert_traces_equal(strc, btr[b],
                                        f"solo-seed{b}-{platform}",
                                        f"batched-w{b}-{platform}")
                bent["equal"] = True
            except TraceMismatch as e:
                bent["equal"] = False
                bent["mismatch"] = str(e)
                out["ok"] = False
            entry["batched"] = bent
        else:
            entry["batched"] = {
                "supported": False,
                "reason": "engine has no world axis (batch=BatchSpec "
                          "is the general engine's lever)"}

        out["configs"][name] = entry
        bat = entry["batched"]
        bat_word = ("batched out of scope" if not bat["supported"]
                    else "batched "
                    + ("OK" if bat["equal"] else "MISMATCH"))
        flt = entry.get("faulted")
        flt_word = "" if flt is None else (
            ", faulted " + ("OK" if flt["equal"] else "MISMATCH"))
        print(f"{name}: {'OK' if entry['equal'] else 'MISMATCH'} "
              f"({entry['supersteps']} supersteps, "
              f"{entry['delivered']} delivered, "
              f"{bat_word}{flt_word})")

    if not self_check:
        root = os.path.dirname(os.path.dirname(os.path.abspath(
            __file__)))
        with open(os.path.join(root, "PARITY_TPU.json"), "w") as f:
            json.dump(out, f, indent=1)
    print(json.dumps({"parity_tpu_ok": out["ok"],
                      "engine_platform": platform}))
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
