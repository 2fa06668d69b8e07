"""Test configuration.

Tests always run on a virtual 8-device CPU platform (multi-chip
sharding without TPU hardware, per the driver contract).

``JAX_PLATFORMS=cpu`` is set here before jax is imported, and again
through ``jax.config`` for a process that imported jax earlier. No
test asks the attached chip anything; the kernels' compile tests
(tests/test_chip_compile.py) ask the TPU *compiler* for a described
chip, inside a fixture of their own. The persistent compilation cache
is off, so the suite writes nothing into the checkout (the chip tool
copies the tree as it stands on disk).
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"
# in the environment too, so that a child process a test starts
# (tests/test_examples.py) keeps no cache either
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "0"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)
jax.config.update("jax_enable_compilation_cache", False)

try:
    from hypothesis import settings  # noqa: E402
except ImportError:     # property tests skip themselves (importorskip);
    pass                # the rest of the suite must still collect
else:
    settings.register_profile("ci", max_examples=25, deadline=None)
    settings.load_profile("ci")
