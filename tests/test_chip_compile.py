"""Ask the TPU's compiler, with no chip attached, whether the Pallas
kernel of the main path lowers at the size the chip runs it at.

The TPU compiler is installed beside the CPU backend the suite runs
on, and compiles for a chip that is *described*, not attached
(``jax.experimental.topologies``). A compile that passes is not a chip
run and says nothing about results or times (``chip_smoke.py`` is the
chip run) — but a kernel Mosaic refuses is refused here, in seconds,
on every later PR. Interpret-mode tests cannot see that: two sparse
kernel paths passed every one of them for sixteen rounds while neither
lowered (removed in PR 29; docs/pallas_kernels.md keeps what Mosaic
refused). A new kernel's compile test belongs in this file.

Rules this file keeps (on-chip-measurement guide §2): the topology is
described only inside a module-scoped fixture of THIS file, which
skips where it cannot be described — never at import, never in
``conftest.py``, never ``autouse``, never in a child process (one
process holds libtpu until it exits); the persistent compilation cache
is off around the compiles (an entry written for a described chip
cannot be read back without one); the engines' "is there a TPU?"
refusal (``utils/jaxconfig.py:require_tpu``) is steered in the test,
not through an option of the program.
"""

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

# the scenario of the smoke and the benchmark, from its one home
from bench import _dense_ring

#: the smoke's and the benchmark's size (chip_smoke.py, bench.py)
RING_N = 1 << 20


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


def test_hole_select_compiles_for_v5e_without_a_sort(one_chip):
    """A commutative inbox's free slots at the benchmark's shape
    (``[24, 2^17]`` mailbox, ``free_bits``) and the bit select at the
    ladder's first rung's lanes and at two words (``nth_set_bit``):
    the chip's compiler takes the popcounts, and the program it makes
    holds no sort (the ``[K, N]`` sort of free rows they replaced in
    PR 30 was the general engine's largest single operation)."""
    from timewarp_tpu.ops.numeric import free_bits, nth_set_bit
    n, lanes = 1 << 17, 1024 * 8

    def slots(keep, rank, dst):
        words = free_bits(keep)
        return nth_set_bit([w[dst] for w in words], rank, keep.shape[0])

    for K in (24, 40):
        compiled = jax.jit(slots).lower(
            _sds(one_chip, (K, n), jnp.bool_), _sds(one_chip, (lanes,)),
            _sds(one_chip, (lanes,))).compile()
        text = compiled.as_text()
        assert "popcnt" in text
        assert " sort(" not in text
        assert compiled.memory_analysis().temp_size_in_bytes < K * n


def test_fill_holes_compiles_for_v5e_without_an_index(one_chip):
    """The node side of a commutative insertion (PR 32) at the
    benchmark's wave shape, one word of holes and two, two planes: the
    chip's compiler takes the row shifts inside a tile (1, 2 and 4
    rows of the ``(8, 128)`` layout), and the program it makes holds
    no gather, scatter or sort."""
    from timewarp_tpu.ops.numeric import I32MAX, fill_holes, free_bits
    n = 1 << 17

    def fill(keep, rel, pay, mb_rel, mb_pay):
        return fill_holes(free_bits(keep), [list(rel), list(pay)],
                          [list(mb_rel), list(mb_pay)], I32MAX)

    for K in (24, 40):
        plane = _sds(one_chip, (K, n))
        text = jax.jit(fill).lower(_sds(one_chip, (K, n), jnp.bool_),
                                   plane, plane, plane, plane
                                   ).compile().as_text()
        for op in (" gather(", " scatter(", " sort("):
            assert op not in text, (K, op)
