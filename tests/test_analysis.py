"""Scenario sanitizer (timewarp_tpu.analysis): every seeded defect
class is caught, every shipped model lints clean, and the engines'
construction-time ``lint=`` knob behaves (error raises / warn logs /
off skips)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from timewarp_tpu.analysis import (LintError, LintReport, lint_capacity,
                                   lint_module_programs, lint_scenario,
                                   lint_source, probe_commutative_inbox,
                                   worst_case_fan_in)
from timewarp_tpu.core.scenario import NEVER, Outbox, Scenario
from timewarp_tpu.models.gossip import gossip
from timewarp_tpu.models.ping_pong import ping_pong
from timewarp_tpu.models.praos import praos
from timewarp_tpu.models.socket_state import socket_state
from timewarp_tpu.models.token_ring import token_ring
from timewarp_tpu.net.delays import FixedDelay, UniformDelay


# ----------------------------------------------------------------------
# fixtures
# ----------------------------------------------------------------------

def _out(M=1, P=1):
    return Outbox(valid=jnp.zeros((M,), bool),
                  dst=jnp.zeros((M,), jnp.int32),
                  payload=jnp.zeros((M, P), jnp.int32))


def _mk(step, name="fixture", **kw):
    kw.setdefault("n_nodes", 4)
    kw.setdefault("payload_width", 1)
    kw.setdefault("max_out", 1)
    kw.setdefault("mailbox_cap", 4)
    kw.setdefault("init", lambda i: ({"x": jnp.int32(0)}, 0))
    return Scenario(name=name, step=step, **kw)


def _ok_step(state, inbox, now, i, key):
    return state, _out(), jnp.int64(NEVER)


# ----------------------------------------------------------------------
# jaxpr lints: each seeded defect class
# ----------------------------------------------------------------------

def test_catches_host_callback():
    def step(state, inbox, now, i, key):
        jax.debug.callback(lambda v: None, now)
        return state, _out(), jnp.int64(NEVER)
    rep = lint_scenario(_mk(step))
    assert "TW101" in [f.code for f in rep.errors]


def test_catches_int32_time_truncation():
    def step(state, inbox, now, i, key):
        d = (now // 2).astype(jnp.int32)        # time truncated...
        wake = d.astype(jnp.int64) + 5          # ...then widened back
        return state, _out(), wake
    rep = lint_scenario(_mk(step))
    assert "TW102" in [f.code for f in rep.errors]


def test_catches_inbox_time_truncation():
    def step(state, inbox, now, i, key):
        t0 = inbox.time.min().astype(jnp.int32)
        return state, _out(), t0.astype(jnp.int64) + 10
    rep = lint_scenario(_mk(step))
    assert "TW102" in [f.code for f in rep.errors]


def test_catches_float_time_promotion():
    def step(state, inbox, now, i, key):
        return state, _out(), (now * 1.5).astype(jnp.int64)
    rep = lint_scenario(_mk(step))
    assert "TW103" in [f.code for f in rep.errors]


def test_int64_time_arithmetic_is_clean():
    def step(state, inbox, now, i, key):
        due = now >= jnp.int64(5)               # bool kills the taint
        x = state["x"] + due.astype(jnp.int32)  # int32 from bool: fine
        return {"x": x}, _out(), now + jnp.int64(1000)
    rep = lint_scenario(_mk(step))
    assert not [f for f in rep.errors
                if f.code in ("TW102", "TW103")]


def test_catches_narrow_next_wake():
    def step(state, inbox, now, i, key):
        return state, _out(), jnp.int32(5)
    rep = lint_scenario(_mk(step))
    assert "TW104" in [f.code for f in rep.errors]


def test_catches_wrong_outbox_shape_and_dtype():
    def step(state, inbox, now, i, key):
        out = Outbox(valid=jnp.zeros((2,), bool),         # M=1 declared
                     dst=jnp.zeros((1,), jnp.int32),
                     payload=jnp.zeros((1,), jnp.int32))  # missing P dim
        return state, out, jnp.int64(NEVER)
    rep = lint_scenario(_mk(step))
    assert [f.code for f in rep.errors].count("TW105") == 2

    def step_f(state, inbox, now, i, key):
        out = Outbox(valid=jnp.zeros((1,), bool),
                     dst=jnp.zeros((1,), jnp.int32),
                     payload=jnp.zeros((1, 1), jnp.float32))
        return state, out, jnp.int64(NEVER)
    rep = lint_scenario(_mk(step_f))
    assert "TW105" in [f.code for f in rep.errors]


def test_catches_state_pytree_instability():
    def step(state, inbox, now, i, key):
        return {"x": state["x"].astype(jnp.int64)}, _out(), \
            jnp.int64(NEVER)
    rep = lint_scenario(_mk(step))
    assert "TW106" in [f.code for f in rep.errors]


def test_catches_false_needs_key_flag():
    def step(state, inbox, now, i, key):
        b0, _ = key
        x = state["x"] + (b0 > 0).astype(jnp.int32)
        return {"x": x}, _out(), jnp.int64(NEVER)
    rep = lint_scenario(_mk(step, needs_key=False))
    assert "TW107" in [f.code for f in rep.errors]
    # conservative converse: declared True, never consumed — perf warn
    rep = lint_scenario(_mk(_ok_step, needs_key=True))
    assert "TW108" in [f.code for f in rep.warnings]


def test_catches_false_inbox_src_flag():
    def step(state, inbox, now, i, key):
        x = state["x"] + inbox.src.max()        # max preserves int32
        return {"x": x}, _out(), jnp.int64(NEVER)
    rep = lint_scenario(_mk(step, inbox_src=False))
    assert "TW109" in [f.code for f in rep.errors]
    # conservative converse — perf warning
    rep = lint_scenario(_mk(_ok_step, inbox_src=True))
    assert "TW110" in [f.code for f in rep.warnings]


def test_untraceable_step_warns_not_crashes():
    def step(state, inbox, now, i, key):
        if int(now) > 0:        # host branching on a traced value
            return state, _out(), jnp.int64(NEVER)
        return state, _out(), jnp.int64(NEVER)
    rep = lint_scenario(_mk(step))
    assert "TW100" in [f.code for f in rep.warnings]
    assert rep.ok


# ----------------------------------------------------------------------
# capacity proofs
# ----------------------------------------------------------------------

def test_capacity_provable_overflow_is_error():
    sd = np.zeros((8, 1), np.int32)             # all 8 -> node 0
    sc = _mk(_ok_step, n_nodes=8, static_dst=sd, mailbox_cap=4)
    assert worst_case_fan_in(sc) == (8, 0)
    rep = lint_capacity(sc)
    assert "TW202" in [f.code for f in rep.errors]
    # raising the cap to the proven fan-in turns it into a proof
    rep = lint_capacity(_mk(_ok_step, n_nodes=8, static_dst=sd,
                            mailbox_cap=8))
    assert rep.ok and "TW204" in rep.codes()


def test_capacity_range_check():
    sd = np.full((4, 1), 9, np.int32)
    rep = lint_capacity(_mk(_ok_step, static_dst=sd))
    assert "TW201" in [f.code for f in rep.errors]
    sd2 = np.full((4, 1), -1, np.int32)         # -1 = unused is legal
    rep = lint_capacity(_mk(_ok_step, static_dst=sd2))
    assert rep.ok


def test_capacity_dynamic_bound_is_reported_not_error():
    rep = lint_capacity(_mk(_ok_step))
    assert rep.ok
    assert "TW203" in [f.code for f in rep.infos]


# ----------------------------------------------------------------------
# commutative-inbox probe
# ----------------------------------------------------------------------

def test_probe_catches_order_dependent_step():
    def step(state, inbox, now, i, key):
        return {"x": inbox.payload[0, 0]}, _out(), jnp.int64(NEVER)
    rep = probe_commutative_inbox(_mk(step, commutative_inbox=True))
    assert "TW401" in [f.code for f in rep.errors]


def test_probe_accepts_commutative_reduction():
    def step(state, inbox, now, i, key):
        x = jnp.max(jnp.where(inbox.valid, inbox.payload[:, 0],
                              jnp.int32(-1)))
        return {"x": x}, _out(), jnp.int64(NEVER)
    rep = probe_commutative_inbox(_mk(step, commutative_inbox=True))
    assert rep.ok and not rep.findings


def test_probe_skips_undeclared_scenarios():
    def step(state, inbox, now, i, key):
        return {"x": inbox.payload[0, 0]}, _out(), jnp.int64(NEVER)
    rep = probe_commutative_inbox(_mk(step, commutative_inbox=False))
    assert not rep.findings


# ----------------------------------------------------------------------
# effect-program AST linter
# ----------------------------------------------------------------------

def test_program_lint_missing_yield_from():
    rep = lint_source("""
def prog():
    wait(for_(sec(1)))
    yield GetTime()
""", name="p")
    assert [f.code for f in rep.errors] == ["TW301"]


def test_program_lint_yield_of_combinator():
    rep = lint_source("""
def prog():
    yield wait(5)
""", name="p")
    assert [f.code for f in rep.errors] == ["TW301"]


def test_program_lint_lambda_factory_is_exempt():
    rep = lint_source("""
def prog():
    yield Fork(lambda: wait(5))
    yield from schedule(after(10), lambda: invoke(5, body))
""", name="p")
    assert not rep.findings


def test_program_lint_await_io_in_pure_context():
    rep = lint_source("""
def prog():
    r = yield from await_io(sock.recv())
    yield AwaitIO(fut)
""", name="p")
    assert [f.code for f in rep.errors] == ["TW302", "TW302"]
    # real-IO context: legal
    rep = lint_source("""
def prog():
    r = yield from await_io(sock.recv())
""", name="p", pure=False)
    assert not rep.findings


def test_program_lint_swallowed_thread_killed():
    rep = lint_source("""
def prog():
    try:
        yield from body()
    except ThreadKilled:
        pass
""", name="p")
    assert [f.code for f in rep.errors] == ["TW303"]


def test_program_lint_broad_handler_warns_unless_preceded():
    rep = lint_source("""
def prog():
    try:
        yield from body()
    except Exception:
        log(1)
""", name="p")
    assert [f.code for f in rep.warnings] == ["TW304"]
    # the repeat_forever idiom (core/effects.py:331-334) is clean
    rep = lint_source("""
def prog():
    try:
        yield from body()
    except ThreadKilled:
        raise
    except BaseException as e:
        nxt = handler(e)
""", name="p")
    assert not rep.findings


def test_program_lint_source_suppression():
    rep = lint_source("""
def prog():
    wait(5)  # tw-lint: ignore[TW301]
    unpark(tid)  # tw-lint: ignore
""", name="p")
    assert not rep.findings


def test_shipped_program_twins_lint_clean():
    import timewarp_tpu.core.effects as effects
    import timewarp_tpu.models.gossip_net as gn
    import timewarp_tpu.models.ping_pong_net as ppn
    import timewarp_tpu.models.praos_net as prn
    import timewarp_tpu.models.socket_state_net as ssn
    import timewarp_tpu.models.token_ring_net as trn
    for mod in (effects, gn, ppn, prn, ssn, trn):
        rep = lint_module_programs(mod)
        assert not rep.findings, \
            f"{mod.__name__}: {[f.render() for f in rep.findings]}"


# ----------------------------------------------------------------------
# shipped models: zero error-severity findings (acceptance)
# ----------------------------------------------------------------------

@pytest.mark.parametrize("build", [
    lambda: token_ring(32),
    lambda: token_ring(32, with_observer=False),
    lambda: gossip(32),
    lambda: gossip(32, burst=True),
    lambda: gossip(32, steady=True),
    lambda: praos(32),
    lambda: praos(32, burst=True),
    lambda: ping_pong(),
    lambda: socket_state(4),
], ids=["ring-obs", "ring-lean", "gossip", "gossip-burst",
        "gossip-steady", "praos", "praos-burst", "ping-pong",
        "socket-state"])
def test_shipped_models_have_zero_error_findings(build):
    rep = lint_scenario(build(), probe=True)
    assert rep.ok, [f.render() for f in rep.errors]


def test_meta_lint_ignore_suppression():
    sc = _mk(_ok_step, inbox_src=True)          # would warn TW110
    assert "TW110" in lint_scenario(sc).codes()
    sc2 = _mk(_ok_step, inbox_src=True,
              meta={"lint_ignore": ["TW110", "TW203"]})
    rep = lint_scenario(sc2)
    assert "TW110" not in rep.codes() and "TW203" not in rep.codes()


# ----------------------------------------------------------------------
# scenario declaration validation (Scenario.__post_init__)
# ----------------------------------------------------------------------

@pytest.mark.parametrize("kw,needle", [
    ({"n_nodes": 0}, "n_nodes"),
    ({"mailbox_cap": 0}, "mailbox_cap"),
    ({"max_out": 0}, "max_out"),
    ({"payload_width": 0}, "payload_width"),
    ({"mailbox_cap": "8"}, "mailbox_cap"),
])
def test_scenario_post_init_rejects_bad_declarations(kw, needle):
    with pytest.raises(ValueError, match=needle):
        _mk(_ok_step, **kw)


def test_scenario_post_init_rejects_wrong_static_dst_shape():
    with pytest.raises(ValueError, match=r"static_dst shape"):
        _mk(_ok_step, n_nodes=4, max_out=2,
            static_dst=np.zeros((4, 1), np.int32))


# ----------------------------------------------------------------------
# engine-construction lint: every engine class
# ----------------------------------------------------------------------

def _bad_scenario():
    def step(state, inbox, now, i, key):
        return state, _out(), jnp.int32(0)      # TW104
    ring = np.array([[1], [2], [3], [0]], np.int32)
    return _mk(step, static_dst=ring, commutative_inbox=True)


def _engine_cases():
    from timewarp_tpu.interp.jax_engine.edge_engine import EdgeEngine
    from timewarp_tpu.interp.jax_engine.engine import JaxEngine
    from timewarp_tpu.interp.jax_engine.sharded import (
        ShardedEdgeEngine, ShardedEngine, make_mesh)
    from timewarp_tpu.interp.ref.superstep import SuperstepOracle
    link = UniformDelay(1000, 5000)
    fixed = FixedDelay(1000)
    lean = token_ring(16, with_observer=False)
    mesh = make_mesh(8)
    return [
        ("oracle", SuperstepOracle, (token_ring(16), link), {}),
        ("general", JaxEngine, (token_ring(16), link), {}),
        ("edge", EdgeEngine, (lean, link), {}),
        ("sharded", ShardedEngine, (lean, link, mesh), {}),
        ("sharded-edge", ShardedEdgeEngine, (lean, fixed, mesh), {}),
    ]


@pytest.mark.parametrize("case", _engine_cases(),
                         ids=lambda c: c[0])
def test_engine_construction_lint_knob(case):
    _, cls, args, kw = case
    # clean scenario: constructs even under the strict mode, report kept
    eng = cls(*args, lint="error", **kw)
    assert eng.lint_report is not None and eng.lint_report.ok
    # default is warn: report attached, no raise
    eng = cls(*args, **kw)
    assert eng.lint == "warn"
    assert eng.lint_report is not None
    # off: no check at all
    eng = cls(*args, lint="off", **kw)
    assert eng.lint_report is None
    with pytest.raises(ValueError, match="lint"):
        cls(*args, lint="loud", **kw)


def test_engine_construction_lint_error_raises_on_defect():
    from timewarp_tpu.interp.jax_engine.edge_engine import EdgeEngine
    from timewarp_tpu.interp.jax_engine.engine import JaxEngine
    from timewarp_tpu.interp.ref.superstep import SuperstepOracle
    bad = _bad_scenario()
    link = FixedDelay(1000)
    for cls in (JaxEngine, EdgeEngine, SuperstepOracle):
        with pytest.raises(LintError) as ei:
            cls(bad, link, lint="error")
        assert "TW104" in ei.value.report.codes()
        cls(bad, link, lint="off")              # off: constructs fine
        cls(bad, link)                          # warn: constructs fine


def test_fused_ring_engine_lint_knob():
    from timewarp_tpu.interp.jax_engine.fused_ring import \
        FusedRingEngine
    sc = token_ring(8192, with_observer=False)  # 8x1024 block floor
    eng = FusedRingEngine(sc, FixedDelay(1000), lint="error",
                          interpret=True)
    assert eng.lint_report is not None and eng.lint_report.ok
    eng = FusedRingEngine(sc, FixedDelay(1000), lint="off", interpret=True)
    assert eng.lint_report is None


def test_lint_report_rendering_ranks_errors_first():
    rep = lint_scenario(_bad_scenario())
    text = rep.render()
    assert text.splitlines()[0].startswith("[ERROR")
    j = rep.to_json()
    assert j["errors"] >= 1
    assert j["findings"][0]["severity"] == "error"


def test_catches_pass_through_flag_violations():
    """A key/src that flows straight into the returned state (no eqn
    consumes it) is still consumed — the engine would feed None/zeros."""
    def s_key(state, inbox, now, i, key):
        b0, _ = key
        return {"k": b0}, _out(), jnp.int64(NEVER)
    sc = _mk(s_key, needs_key=False,
             init=lambda i: ({"k": jnp.uint32(0)}, 0))
    assert "TW107" in [f.code for f in lint_scenario(sc).errors]

    def s_src(state, inbox, now, i, key):
        return {"s": inbox.src}, _out(), jnp.int64(NEVER)
    sc = _mk(s_src, inbox_src=False,
             init=lambda i: ({"s": jnp.zeros((4,), jnp.int32)}, 0))
    assert "TW109" in [f.code for f in lint_scenario(sc).errors]


def test_scenario_post_init_accepts_numpy_integers():
    sc = _mk(_ok_step, n_nodes=np.int64(4), mailbox_cap=np.int32(4))
    assert sc.n_nodes == 4
    with pytest.raises(ValueError, match="n_nodes"):
        _mk(_ok_step, n_nodes=True)     # bool is not a node count
