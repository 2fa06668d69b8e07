"""The entry points' JAX configuration (utils/jaxconfig.py): where the
persistent compile cache goes, and the refusal the compiled kernel
paths share. The suite itself keeps the cache off (conftest.py), so
nothing here writes into the checkout."""

import os

import pytest

import jax

from timewarp_tpu.utils import jaxconfig

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_dir_restored():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_is_placed_from_outside(monkeypatch,
                                              cache_dir_restored):
    """Where JAX_COMPILATION_CACHE_DIR is set, the program sets
    nothing in code (JAX reads the variable itself)."""
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert jaxconfig.enable_compile_cache() == "/some/dir"
    assert jax.config.jax_compilation_cache_dir is None


def test_compile_cache_default_is_fixed_in_the_checkout(
        monkeypatch, cache_dir_restored):
    """Without the variable: <checkout>/.jax_cache, derived from the
    package's own path — the path is part of the cache's key, so it is
    never a temp name, a pid or a time — and git-ignored. The call
    creates nothing by itself."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(_REPO, ".jax_cache")
    assert jaxconfig.enable_compile_cache() == want
    assert jaxconfig.enable_compile_cache() == want     # every call
    assert jax.config.jax_compilation_cache_dir == want
    with open(os.path.join(_REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
    # the suite keeps the cache off, so no test leaves the directory
    assert not jax.config.jax_enable_compilation_cache


def test_require_tpu_names_the_explicit_interpreter_request():
    assert jax.default_backend() != "tpu"
    with pytest.raises(RuntimeError, match="interpret=True"):
        jaxconfig.require_tpu("SomeEngine")


def test_profile_session_fails_when_it_cannot_start(tmp_path):
    """A trace directory that was asked for and a session that cannot
    start is an error, not a warned no-op (`timewarp-tpu profile` must
    not exit 0 with no trace); no directory asked for is a no-op."""
    from timewarp_tpu.obs.profiler import profile_session
    with profile_session(None) as got:
        assert got is None
    with profile_session(str(tmp_path / "a")) as got:
        assert got == str(tmp_path / "a")
        # a second session cannot start while one is open
        with pytest.raises(Exception, match="(?i)already|one profile"):
            with profile_session(str(tmp_path / "b")):
                pass
