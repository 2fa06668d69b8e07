"""Ask the TPU's compiler, with no chip attached, whether the Pallas
kernels of the main path lower at the sizes the chip runs them at.

The TPU compiler is installed beside the CPU backend the suite runs
on, and compiles for a chip that is *described*, not attached
(``jax.experimental.topologies``). A compile that passes is not a chip
run and says nothing about results or times (``chip_smoke.py`` is the
chip run) — but a kernel Mosaic refuses is refused here, in seconds,
on every later PR. Interpret-mode tests cannot see that: both sparse
kernel paths passed every one of them for sixteen rounds while neither
lowered (ROADMAP S2).

Rules this file keeps (on-chip-measurement guide §2): the topology is
described only inside a module-scoped fixture of THIS file, which
skips where it cannot be described — never at import, never in
``conftest.py``, never ``autouse``, never in a child process (one
process holds libtpu until it exits); the persistent compilation cache
is off around the compiles (an entry written for a described chip
cannot be read back without one); the engines' "is there a TPU?"
refusal (``utils/jaxconfig.py:require_tpu``) is steered in the test,
not through an option of the program.

What does not lower yet is ``xfail(strict=True)`` with the compiler's
own failure, so the PR that repairs it (ROADMAP S2) must flip the
test.
"""

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

# the scenarios of the smoke and the benchmark, from their one home
from bench import _dense_ring, _gossip_wave

#: the smoke's and the benchmark's sizes (chip_smoke.py, bench.py)
RING_N = 1 << 20
GOSSIP_N = 1 << 17


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever libtpu raises
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def as_tpu(monkeypatch):
    """The engines refuse a compiled kernel where JAX's default
    backend is no TPU; here the *compiler* is the subject, so the test
    answers for the backend."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _sds(sharding, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_fused_ring_superstep_lowers_at_2p20(one_chip, as_tpu):
    """The dense ring's whole superstep is ONE Mosaic kernel at the
    headline size, reading and writing the ~42 MB state once."""
    from timewarp_tpu.interp.jax_engine.fused_ring import (
        FusedRingEngine, FusedRingState)
    n = RING_N
    eng = FusedRingEngine(*_dense_ring(n), cap=2)
    st = FusedRingState(
        planes=_sds(one_chip, (10, n // 1024, 1024)),
        base=_sds(one_chip, (), jnp.int64),
        delivered=_sds(one_chip, (), jnp.int64),
        overflow=_sds(one_chip, ()),
        steps=_sds(one_chip, (), jnp.int64))
    compiled = jax.jit(eng._superstep).lower(st).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    # the kernel carries its name into the compiled program, under the
    # stage scope a trace reduction finds it by
    kernel, = [ln for ln in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in ln]
    assert "%tw_ring_superstep" in kernel
    assert "tw.ring_kernel/tw_ring_superstep" in kernel
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes >= 10 * n * 4
    assert mem.temp_size_in_bytes < (1 << 20)   # nothing staged in HBM


def test_in_kernel_delay_draw_lowers(one_chip):
    """The fused-sparse kernel samples link delays in-kernel from
    threefry bits (fused_sparse.py:_lower_link). Mosaic has no
    uint32 <-> float32 cast; the draw goes through int32, which is
    exact below 2^24 (core/rng.py:normal_f32) and below cap_us < 2^31
    (the quantized lognormal's result)."""
    from jax.experimental import pallas as pl
    from timewarp_tpu.interp.jax_engine.fused_sparse import _lower_link
    _, link = _gossip_wave(1024)
    needs_key, _, delay_fn = _lower_link(link)
    assert needs_key

    def kernel(b0_ref, b1_ref, out_ref):
        b0, b1 = b0_ref[:], b1_ref[:]
        d = delay_fn(None, None, None, None, (b0, b1))
        out_ref[:] = d.astype(jnp.int32)

    f = pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct((8, 128), jnp.int32))
    bits = _sds(one_chip, (8, 128), jnp.uint32)
    compiled = jax.jit(f).lower(bits, bits).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_lane_prefix_lowers(one_chip):
    """The fire-compaction kernel's in-block ranks (log-step masked
    roll-adds). A python int literal in a jnp op is an int64 constant
    under x64, and Mosaic's int64 -> int32 convert recursed without
    end (the RecursionError of ROADMAP S2); the literals are int32."""
    from jax.experimental import pallas as pl
    from timewarp_tpu.interp.jax_engine.pallas_insert import (
        _lane_excl_prefix, _row_total)

    def kernel(v_ref, out_ref, tot_ref):
        v = v_ref[:]
        lane = jax.lax.broadcasted_iota(jnp.int32, v.shape, 1)
        excl = _lane_excl_prefix(v, lane)
        out_ref[:] = excl
        tot_ref[:] = _row_total(excl + v)

    f = pl.pallas_call(
        kernel,
        out_shape=[jax.ShapeDtypeStruct((8, 1024), jnp.int32),
                   jax.ShapeDtypeStruct((8, 1), jnp.int32)])
    compiled = jax.jit(f).lower(_sds(one_chip, (8, 1024))).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.xfail(
    strict=True, raises=AssertionError,
    reason="jax/_src/pallas/mosaic/lowering.py:_gather_lowering_rule: "
           "`assert indices_aval.shape == in_aval.shape + (1,)` — "
           "Mosaic (JAX 0.9.0) lowers only take_along_axis-shaped "
           "gathers (tpu.dynamic_gather); the kernel's per-slot "
           "gather of [R, 1024] indices from the resident [SR, 128] "
           "batch is not one (ROADMAP S2)")
def test_fused_sparse_insertion_kernel_lowers_at_2p17(one_chip, as_tpu):
    """FusedSparseEngine's sample-mode insertion kernel for the gossip
    wave at 2^17 nodes, max_batch = 1 << 18 (bench.py
    gossip_100k_fused)."""
    from timewarp_tpu.interp.jax_engine.fused_sparse import \
        FusedSparseEngine
    from timewarp_tpu.interp.jax_engine.pallas_insert import \
        _fused_insert_call
    n = GOSSIP_N
    sc, link = _gossip_wave(n)
    eng = FusedSparseEngine(sc, link, window="auto",
                            max_batch=1 << 18, lint="off")
    K, P, S = sc.mailbox_cap, sc.payload_width, eng._S

    def call(scal, sd, woff, smrank, pay, mb_rel, mb_src, mb_pay):
        return _fused_insert_call(
            eng._kernel, S, n, K, P, sc.inbox_src, scal, sd, woff,
            smrank, pay, mb_rel, mb_src, mb_pay, interpret=False)

    col = _sds(one_chip, (S,))
    jax.jit(call).lower(
        _sds(one_chip, (4,)), col, col, col, (col,) * P,
        _sds(one_chip, (K, n)), _sds(one_chip, (K, n)),
        _sds(one_chip, (K, P, n))).compile()


def _pallas_stage(n):
    from timewarp_tpu.interp.jax_engine.engine import JaxEngine
    sc, link = _gossip_wave(n)
    eng = JaxEngine(sc, link, window="auto", insert="pallas",
                    insert_cap=min(1 << 18, n * sc.max_out),
                    lint="off")
    return sc, eng._pallas_stage


@pytest.mark.xfail(
    strict=True, raises=RecursionError,
    reason="maximum recursion depth exceeded in "
           "jax/_src/pallas/mosaic/lowering.py:"
           "_convert_element_type_lowering_rule: the in-kernel "
           "`msgs.at[0, jr, jc].set(...)` normalizes its static index "
           "through an int64 -> int32 convert, which Mosaic's "
           "_convert_helper re-issues without end; behind it, Mosaic "
           "(JAX 0.9.0) has no lowering rule for scatter at all "
           "(ROADMAP S2)")
def test_pallas_fire_compaction_kernel_lowers_at_2p17(one_chip, as_tpu):
    """``insert="pallas"``: the fire-compaction kernel for the gossip
    wave at 2^17 nodes (bench.py gossip_100k_insert)."""
    n = GOSSIP_N
    sc, stage = _pallas_stage(n)
    M, P = sc.max_out, sc.payload_width
    jax.jit(stage.compact).lower(
        _sds(one_chip, (M, n)), _sds(one_chip, (n,)),
        _sds(one_chip, (M, P, n))).compile()


@pytest.mark.xfail(
    strict=True, raises=AssertionError,
    reason="jax/_src/pallas/mosaic/lowering.py:_gather_lowering_rule: "
           "`assert indices_aval.shape == in_aval.shape + (1,)` — the "
           "same per-slot gather as the fused-sparse kernel, in its "
           "pre-sampled (drel) mode (ROADMAP S2)")
def test_pallas_insertion_kernel_lowers_at_2p17(one_chip, as_tpu):
    """``insert="pallas"``: the drel-mode insertion kernel at the
    fire-compacted width, same scenario."""
    n = GOSSIP_N
    sc, stage = _pallas_stage(n)
    K, P = sc.mailbox_cap, sc.payload_width
    col = _sds(one_chip, (stage.S,))

    def call(sd, drel, src, pay, mb_rel, mb_src, mb_pay):
        return stage.insert(sd, drel, src, pay, mb_rel, mb_src,
                            mb_pay, None)

    jax.jit(call).lower(
        col, col, col, (col,) * P, _sds(one_chip, (K, n)),
        _sds(one_chip, (K, n)), _sds(one_chip, (K, P, n))).compile()
