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

A worker of the suite keeps every program it has compiled mapped until
it exits, some 37 memory mappings a program: near the suite's end a
worker held 45 000-57 000 of the 65 530 a process may have
(``vm.max_map_count``), and one that passes the limit dies in
XLA:CPU's next compile of a segmentation fault, after which xdist's
``loadfile`` run hangs to its time limit (seen once in PR 34). So a
worker that holds 8 000 drops its compiled programs between two
test files (``_bound_the_mappings``: the peak is 37 600 with it).
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
import pytest  # noqa: E402

# the modules that hold what several test files share (no test lives
# in them, no ``test_`` leads their names): their asserts read like a
# test file's own
pytest.register_assert_rewrite(*(
    name[:-3] for name in sorted(os.listdir(os.path.dirname(__file__)))
    if name.endswith(".py") and not name.startswith(("test_", "conftest"))))

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


#: an eighth of Linux's default ``vm.max_map_count``: the file that
#: compiles most adds some 33 000 mappings on top of what its worker
#: starts it with
_MAPPINGS_KEPT = 8_000


@pytest.fixture(scope="module", autouse=True)
def _bound_the_mappings():
    """After a test file: where this process holds more than
    ``_MAPPINGS_KEPT`` memory mappings, drop every compiled program
    (``jax.clear_caches``: what a later file needs it compiles again,
    as it would in a fresh worker)."""
    yield
    try:
        with open("/proc/self/maps") as f:
            held = sum(1 for _ in f)
    except OSError:         # no procfs: nothing to read, nothing to do
        return
    if held > _MAPPINGS_KEPT:
        jax.clear_caches()
