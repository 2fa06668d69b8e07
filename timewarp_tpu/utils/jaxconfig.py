"""Central JAX configuration: import this before touching jax anywhere.

Virtual time is int64 µs (SURVEY.md §7 hard-part #2: fixed-point time,
never float), which requires x64 mode. All engine code uses explicit
dtypes (int32/int64/float32/bfloat16) so enabling x64 never leaks
float64 into TPU compute paths.
"""

import os

import jax

jax.config.update("jax_enable_x64", True)

#: the checkout's own compile cache: fixed, derived from the package's
#: path (the directory is part of the cache key, so a path that moves
#: never hits), listed in .gitignore
_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on; returns where it
    lives. Called by the entry points (``cli.main``, ``bench.main``,
    ``chip_smoke.py``, ``tools/parity_tpu.py``), never at import, so a
    library user or a test writes nothing into the checkout. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and
    nothing is set in code; otherwise ``<checkout>/.jax_cache``. One
    general-engine driver is minutes of compile on the chip
    (CHANGES.md, PR 21), and a chip call starts with no compiled
    code."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", _CACHE_DIR)
    return _CACHE_DIR


def keep_host_cpu() -> None:
    """Keep the host CPU backend beside the accelerator, for the
    programs that pin the oracle's draws to it (``chip_smoke.py``,
    ``tools/parity_tpu.py``). Where ``JAX_PLATFORMS`` names the
    accelerator alone, the CPU is named behind it: the first platform
    stays the default, and a listed platform that cannot start is
    still an error. Call before the first device query."""
    plats = jax.config.jax_platforms
    if plats and "cpu" not in plats.split(","):
        jax.config.update("jax_platforms", plats + ",cpu")


def require_tpu(what: str) -> None:
    """Refuse to run ``what`` (a compiled Pallas kernel path) where
    JAX's default backend is not a TPU. The interpreter is an explicit
    request (``interpret=True``), never a fallback: a run that finds
    no chip must say so."""
    backend = jax.default_backend()
    if backend != "tpu":
        raise RuntimeError(
            f"{what} runs compiled Mosaic kernels and needs a TPU "
            f"backend; JAX found {backend!r}. Ask for the Pallas "
            "interpreter explicitly (interpret=True on "
            "FusedRingEngine) to run the kernel's semantics "
            "elsewhere — never as a timing")
