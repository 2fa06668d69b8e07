"""The examples/ scripts are user-facing entry points — keep them
runnable (emulated modes only: fast and deterministic)."""

import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_example(*args, timeout=120, env_extra=None):
    # the child runs on the CPU like the suite: a chip belongs to one
    # process, and no test asks it anything
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    out = subprocess.run(
        [sys.executable, *args], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout


def test_ping_pong_example():
    out = run_example("examples/ping_pong.py")
    assert "pong-got-ping" in out and "ping-got-pong" in out


def test_socket_state_example():
    out = run_example("examples/socket_state.py", "--drop", "0.03")
    assert "per-socket totals:" in out


def test_token_ring_example():
    out = run_example("examples/token_ring.py")
    assert "observer noted token value" in out and "errors: none" in out


def test_token_ring_engine_example():
    out = run_example("examples/token_ring.py", "--engine",
                      "--nodes", "8")
    assert "messages delivered" in out


def test_playground_example_all_scenarios():
    out = run_example("examples/playground.py")
    assert "generation 2 stopped; port re-binds cleanly" in out
    assert "content never parsed" in out                   # proxy
    assert "finally received b'patience pays'" in out      # slowpoke
    assert "yo-ho-ho" in out                               # yohoho reply
    assert "forked EpicRequest" in out                     # fork strategy


def test_playground_single_scenario_flag():
    out = run_example("examples/playground.py", "--scenario", "proxy")
    assert "via proxy" in out and "yo-ho-ho" not in out


def test_profiling_script_runs():
    out = run_example("profiling/profile_superstep.py", timeout=300,
                      env_extra={"TW_PROF_NODES": "512",
                                 "TW_PROF_REPS": "1"})
    assert '"FULL superstep (while_loop)"' in out


def test_cross_world_example():
    out = run_example("examples/cross_world.py", "--nodes", "12")
    assert "CROSS-WORLD LAW HOLDS" in out
