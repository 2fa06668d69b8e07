"""chip_smoke.py — the standing proof that the emulator's main path
starts on the attached TPU. A smoke, not a benchmark: the seconds it
prints say that the path ran, never how fast the system is.

    python chip_smoke.py            # one chip (what the driver runs)
    python chip_smoke.py --chips 4  # the node-sharded engines on four

One process does all chip work (a chip belongs to one process). Exits
non-zero, and prints no result line, unless ``jax.devices()[0]`` is a
TPU; every failed check is an uncaught exception, so no phase can fail
and the run still end in 0.

One chip, three phases, one JSON line each (what ran, its counters,
compile and run seconds):

- ``ring``: ``FusedRingEngine`` on the dense token ring at 2^20 nodes,
  the compiled Mosaic kernel (a ``tpu_custom_call`` in the lowered
  driver, never the interpreter); 12 supersteps bit-equal to
  ``EdgeEngine`` field by field (the gate of bench.py
  ``bench_token_ring_dense``), then 4096 supersteps by ``run_quiet``
  with a readback.
- ``gossip_cli``: the gossip wave (fanout 8, burst, lognormal links
  quantized to 1 ms, ``--window auto``) at 2^17 nodes on ``--engine
  general`` through ``timewarp_tpu.cli.main`` — the path ``python -m
  timewarp_tpu gossip ...`` takes — run to quiescence, then bench.py's
  ``_assert_wave_done`` on the saved final state.
- ``law``: the core law on the chip — the gossip and the praos step
  at 1024 nodes on ``JaxEngine`` on the TPU against ``SuperstepOracle``,
  ``assert_traces_equal``: across backends (every oracle draw pinned
  to the host CPU) with the integer twin of the wave's link, and with
  the wave's own lognormal link within the backend (float links are
  exact within one backend only).

``--chips 4`` runs the sharded engines and their one-device twins and
no other phase: ``ShardedEdgeEngine`` on the ring (ppermute) against
``EdgeEngine`` at 2^20 nodes, ``ShardedEngine`` on the gossip wave
(all_to_all) against ``JaxEngine`` at 2^17, on a mesh of the four real
devices; counters and traces bit-equal, and the state's shards seen on
four distinct devices.

The last line of stdout is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

import argparse
import contextlib
import io
import json
import os
import sys
import tempfile
import time

RING_N = 1 << 20
GOSSIP_N = 1 << 17
LAW_N = 1024
#: the CLI wave quiesces in 367 supersteps at 2^17 (counted on XLA:CPU
#: — a count, the same on every backend); the traced driver scans the
#: pow2 pad of --steps whatever happens, so the budget stays tight
GOSSIP_STEPS = 512

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")
_compile_s = [0.0]


def _on_duration(event, secs, **_):
    if event in _COMPILE_EVENTS:
        _compile_s[0] += secs


@contextlib.contextmanager
def phase(name, **what):
    """Run one phase, then print its JSON line. ``compile_s`` is what
    JAX spent tracing, lowering and compiling (or fetching from the
    persistent cache) inside the phase; ``run_s`` is the rest of its
    wall time. No exception is caught here."""
    out = {"phase": name, "smoke": True, **what}
    c0, t0 = _compile_s[0], time.perf_counter()
    yield out
    wall = time.perf_counter() - t0
    comp = _compile_s[0] - c0
    out["compile_s"] = round(comp, 2)
    out["run_s"] = round(wall - comp, 2)
    print(json.dumps(out), flush=True)


def _np(x):
    import jax
    import numpy as np
    return np.asarray(jax.device_get(x))


# -- one chip ---------------------------------------------------------------

def _compiled_ring_engine(sc, link):
    """The fused ring engine with its kernel compiled by Mosaic (the
    constructor refuses where there is no TPU), its initial state, and
    the count of ``tpu_custom_call`` in its lowered driver."""
    from timewarp_tpu.interp.jax_engine.fused_ring import FusedRingEngine
    eng = FusedRingEngine(sc, link, cap=2)
    assert not eng.interpret
    st = eng.init_state()
    calls = type(eng)._run_while.lower(eng, st, 12).as_text().count(
        "tpu_custom_call")
    assert calls >= 1, "the fused ring's driver holds no Mosaic kernel"
    return eng, st, calls


def phase_ring():
    import bench
    from timewarp_tpu.interp.jax_engine.edge_engine import EdgeEngine
    with phase("ring", engine="FusedRingEngine", scenario="token_ring",
               nodes=RING_N) as out:
        sc, link = bench._dense_ring(RING_N)
        eng, st, out["tpu_custom_call"] = _compiled_ring_engine(sc, link)
        ref = EdgeEngine(sc, link, cap=2)
        bench._assert_ring_states_equal(
            ref.run_quiet(12), eng.to_edge_state(eng.run_quiet(12, st)),
            "FusedRingEngine")
        out["gate_supersteps"] = 12
        fin = eng.run_quiet(4096, st)
        out["supersteps"] = int(fin.steps)
        out["delivered"] = int(fin.delivered)
        out["overflow"] = int(fin.overflow)
        assert out["supersteps"] == 4096
        assert out["overflow"] == 0, "ring left the parity regime"
        assert out["delivered"] > 0


GOSSIP_LINK = "quantize:1000:lognormal:20000:0.6"


def phase_gossip_cli():
    import bench
    from timewarp_tpu import cli
    from timewarp_tpu.interp.jax_engine.engine import JaxEngine
    from timewarp_tpu.models.gossip import gossip
    from timewarp_tpu.net.links import parse_link
    from timewarp_tpu.utils.checkpoint import load_state
    argv = ["gossip", "--nodes", str(GOSSIP_N), "--engine", "general",
            "--fanout", "8", "--burst", "--mailbox-cap", "16",
            "--end-us", "5000000", "--link", GOSSIP_LINK,
            "--window", "auto", "--steps", str(GOSSIP_STEPS)]
    with phase("gossip_cli", engine="general", insert="xla",
               nodes=GOSSIP_N, argv=argv) as out, \
            tempfile.TemporaryDirectory() as tmp:
        ckpt = os.path.join(tmp, "wave.npz")
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            rc = cli.main(argv + ["--save", ckpt])
        assert rc == 0, f"cli.main returned {rc}"
        summary = json.loads(buf.getvalue().strip().splitlines()[-1])
        out["summary"] = summary
        # the checks of bench.py _assert_wave_done, on the state the
        # CLI saved; the engine built here is only the state's
        # template and the owner of _next_event (it runs nothing)
        sc = gossip(GOSSIP_N, fanout=8, end_us=5_000_000, burst=True,
                    mailbox_cap=16)
        engine = JaxEngine(sc, parse_link(GOSSIP_LINK), window="auto")
        fin, _ = load_state(ckpt, engine.init_state(),
                            expect_meta={"scenario": sc.name})
        bench._assert_wave_done(engine, fin, GOSSIP_N)
        out["window_us"] = int(engine.window)
        out["short_delay"] = int(fin.short_delay)
        out["route_drop"] = int(fin.route_drop)
        out["never_infected"] = int((_np(fin.states["hop"]) < 0).sum())
        assert summary["supersteps"] < GOSSIP_STEPS, \
            "the wave used its whole step budget"
        assert summary["supersteps"] == int(fin.steps)
        assert summary["delivered"] > GOSSIP_N
        assert summary["overflow"] == 0


def phase_law():
    """The core law at 1024 nodes, engine on the TPU against the host
    oracle. Twice for each scenario, because the wave's own link is a
    float model: its lognormal draws go through ``exp``/``log``/``cos``,
    which the TPU and the host CPU round differently (found by this
    script's first chip run, PERF.md PR 21; docs/engines.md "The
    parity regime" says float links are exact within one backend
    only). So: (a) across backends with the integer twin of the link
    (same 8 ms floor, same 1 ms grid), every oracle draw on the host
    CPU; (b) with the lognormal link itself, the oracle's draws on the
    TPU beside the engine's."""
    import jax
    import bench
    from timewarp_tpu.interp.jax_engine.engine import JaxEngine
    from timewarp_tpu.interp.ref.superstep import SuperstepOracle
    from timewarp_tpu.net.delays import Quantize, UniformDelay
    from timewarp_tpu.trace.events import assert_traces_equal
    int_link = Quantize(UniformDelay(8_000, 60_000), 1_000)
    for name, build, steps in (
            ("gossip", bench._gossip_wave, 256),
            ("praos", bench._praos_consensus, 128)):
        sc, float_link = build(LAW_N)
        for link, draws_on in ((int_link, jax.devices("cpu")[0]),
                               (float_link, jax.devices()[0])):
            with phase("law", scenario=name, nodes=LAW_N,
                       link=type(link.inner).__name__,
                       engine="JaxEngine on tpu vs SuperstepOracle",
                       oracle_draws_on=draws_on.platform) as out:
                eng = JaxEngine(sc, link, window="auto")
                _, etrace = eng.run(steps)
                with jax.default_device(draws_on):
                    otrace = SuperstepOracle(
                        sc, link, window=eng.window).run(steps)
                assert_traces_equal(otrace, etrace,
                                    f"oracle-{draws_on.platform}",
                                    "engine-tpu")
                out["supersteps"] = len(etrace)
                out["delivered"] = etrace.total_delivered()
                assert out["supersteps"] > 0 and out["delivered"] > 0


# -- four chips -------------------------------------------------------------

def _node_shard_devices(state, n, n_devices):
    """How many distinct devices hold distinct node slices of the
    state's per-node ``wake`` array, each ``n / n_devices`` wide (code
    that has never met a second chip may put everything on the first,
    or replicate)."""
    shards = state.wake.addressable_shards
    assert {s.data.shape for s in shards} == {(n // n_devices,)}, \
        f"wake shards are {[s.data.shape for s in shards]}"
    slices = {(s.index[0].start or 0) for s in shards}
    assert len(slices) == len(shards), "shards repeat a node slice"
    return len({s.device for s in shards})


def phase_sharded_ring(mesh):
    import bench
    from timewarp_tpu.interp.jax_engine.edge_engine import EdgeEngine
    from timewarp_tpu.interp.jax_engine.sharded import ShardedEdgeEngine
    with phase("sharded_ring", engine="ShardedEdgeEngine vs EdgeEngine",
               nodes=RING_N, devices=mesh.size) as out:
        sc, link = bench._dense_ring(RING_N)
        sh = ShardedEdgeEngine(sc, link, mesh, cap=2)
        ref = EdgeEngine(sc, link, cap=2)
        fs, rs = sh.run_quiet(256), ref.run_quiet(256)
        out["state_devices"] = _node_shard_devices(fs, RING_N, mesh.size)
        assert out["state_devices"] == mesh.size, \
            f"state lives on {out['state_devices']} devices"
        bench._assert_ring_states_equal(rs, fs, "ShardedEdgeEngine")
        out["supersteps"] = int(fs.steps)
        out["delivered"] = int(fs.delivered)
        assert out["delivered"] > 0 and int(fs.overflow) == 0
        # a short traced run: the digests ride every superstep
        _, st = sh.run(16)
        _, rt = ref.run(16)
        from timewarp_tpu.trace.events import assert_traces_equal
        assert_traces_equal(rt, st, "edge", "sharded-edge")
        out["trace_rows"] = len(st)


def phase_sharded_gossip(mesh):
    import bench
    from timewarp_tpu.interp.jax_engine.engine import JaxEngine
    from timewarp_tpu.interp.jax_engine.sharded import ShardedEngine
    from timewarp_tpu.trace.events import (assert_states_equal,
                                           assert_traces_equal)
    with phase("sharded_gossip", engine="ShardedEngine vs JaxEngine",
               nodes=GOSSIP_N, devices=mesh.size) as out:
        sc, link = bench._gossip_wave(GOSSIP_N)
        sh = ShardedEngine(sc, link, mesh, window="auto")
        ref = JaxEngine(sc, link, window=sh.window)
        fs, st = sh.run(128)
        fr, rt = ref.run(128)
        out["state_devices"] = _node_shard_devices(fs, GOSSIP_N,
                                                   mesh.size)
        assert out["state_devices"] == mesh.size, \
            f"state lives on {out['state_devices']} devices"
        assert_traces_equal(rt, st, "general", "sharded")
        assert_states_equal(fr, fs, "ShardedEngine vs JaxEngine")
        bench._assert_wave_done(sh, fs, GOSSIP_N)
        out["supersteps"] = len(st)
        out["delivered"] = st.total_delivered()
        out["route_drop"] = int(fs.route_drop)
        assert out["delivered"] > GOSSIP_N


# -- entry ------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: the node-sharded engines on a mesh of "
                         "four chips against their one-device twins, "
                         "and no other phase")
    args = ap.parse_args()

    from timewarp_tpu.utils import jaxconfig
    cache_dir = jaxconfig.enable_compile_cache()
    jaxconfig.keep_host_cpu()   # the oracle's draws run there (law)
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found {dev.platform!r}, not a TPU — "
              "nothing ran", file=sys.stderr)
        return 1
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(jax.devices())}",
              file=sys.stderr)
        return 1
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    print(json.dumps({
        "smoke": True, "note": "a smoke, not a benchmark",
        "chips": args.chips, "device_kind": dev.device_kind,
        "jax": jax.__version__, "compile_cache": cache_dir,
        "cache_entries_at_start":
            len(os.listdir(cache_dir)) if os.path.isdir(cache_dir)
            else 0}), flush=True)

    if args.chips == 4:
        from timewarp_tpu.parallel.mesh import make_mesh
        mesh = make_mesh(4)
        phase_sharded_ring(mesh)
        phase_sharded_gossip(mesh)
    else:
        phase_ring()
        phase_gossip_cli()
        phase_law()

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
