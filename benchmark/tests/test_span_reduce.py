"""``span_reduce.py`` on hand-made tuples and on a hand-made ``.xplane.pb``:
the clock's bracket, the owner of a gap, device time by stage, idle
time inside the loop, the three identities that tie the new numbers to
the old ones, and the readers over them."""

import pytest

import span_reduce as sr
import trace_reduce as tr
from layer_metrics import (device_idle_share, gap_in_client_ms,
                           gap_in_run_ms, loop_idle_us, programs_per_job,
                           stage_deliver_us, stage_finish_us, stage_fire_us,
                           stage_next_event_us, stage_rebase_us,
                           stage_route_us, stage_unscoped_share,
                           superstep_us, sync_gap_ms)


# -- the clock ---------------------------------------------------------------

def test_clock_bracket_closes():
    # the device's clock stands 1000 ns ahead of the host's. Programs
    # start 30 and 12 ns after their enqueue began, and the host ran
    # their completion callbacks 40 and 25 ns after they ended
    before = [(100, 1130), (500, 1512)]
    after = [(340, 1300), (725, 1700)]
    assert sr.clock_bracket(before, after) == (975, 1012)


def test_clock_bracket_raises_on_spans_that_contradict_causality():
    # a program that "ended" after the callbacks that follow its end,
    # by more than any offset the launches allow
    with pytest.raises(ValueError, match="causality"):
        sr.clock_bracket([(100, 1130)], [(340, 1500)])
    with pytest.raises(ValueError, match="not bracketed"):
        sr.clock_bracket([(100, 1130)], [])


def test_causal_pairs_join_on_run_id():
    programs = [(1130, 170, "jit_a(1)", 7), (1512, 188, "jit_a(1)", 8)]
    launches = [(100, 5, sr.LAUNCH_EVENT, 7), (340, 9, sr.DONE_EVENT, 7),
                (500, 5, sr.LAUNCH_EVENT, 8), (725, 9, sr.DONE_EVENT, 8),
                (900, 5, sr.LAUNCH_EVENT, 9)]        # not in the trace
    before, after = sr.causal_pairs(launches, programs)
    assert before == [(100, 1130), (500, 1512)]
    assert after == [(340, 1300), (725, 1700)]


# -- who owns a gap ------------------------------------------------------------

HOST = [(0, 1000, "bench_job", {}),
        (100, 700, "tw.run_quiet", {"run": 1}),
        (110, 90, "tw.dispatch", {"run": 1, "cause": "tw.run_quiet"}),
        (210, 500, "tw.wait", {"run": 1, "cause": "tw.run_quiet"})]


def test_a_gap_wholly_under_wait():
    assert sr.owner_of_gaps([(300, 100)], HOST, 0) == {"tw.wait": 100}
    # the same gap on a device clock 5000 ns ahead
    assert sr.owner_of_gaps([(5300, 100)], HOST, 5000) == {"tw.wait": 100}


def test_a_gap_split_between_dispatch_and_no_span():
    # 50..100 the caller's, 100..110 the driver's own, 110..150 dispatch
    assert sr.owner_of_gaps([(50, 100)], HOST, 0) == {
        sr.CLIENT: 50, "tw.run_quiet": 10, "tw.dispatch": 40}


def test_nested_spans_resolve_to_the_innermost():
    # 190..200 dispatch, 200..210 between the two, 210..230 wait: never
    # the enclosing driver span where a narrower one covers the instant
    assert sr.owner_of_gaps([(190, 40)], HOST, 0) == {
        "tw.dispatch": 10, "tw.run_quiet": 10, "tw.wait": 20}
    # after the driver returned: the caller's, though bench_job covers it
    assert sr.owner_of_gaps([(850, 100)], HOST, 0) == {sr.CLIENT: 100}


# -- device time by stage -------------------------------------------------------

def test_stage_of():
    assert sr.stage_of("jit(f)/while/body/tw.route/insert/sort:") == \
        "tw.route"
    assert sr.stage_of("jit(f)/while/cond/tw.next_event/reduce_min:") == \
        "tw.next_event"
    assert sr.stage_of("jit(f)/while/body/tw.route/insert/sort:", 2) == \
        "tw.route/insert"
    assert sr.stage_of("jit(f)/while/body/tw.route/gather:", 2) == "tw.route"
    # the ladder's branches and jnp's own jits are no scopes of ours
    assert sr.stage_of("jit(f)/while/body/tw.route/cond/branch_3_fun/insert/"
                       "jit(_where)/select_n:", 2) == "tw.route/insert"
    assert sr.stage_of("jit(f)/while/body/tw.fire/vmap(step)/add:", 2) == \
        "tw.fire"
    assert sr.stage_of("jit(f)/while:") == sr.UNSCOPED
    assert sr.stage_of("") == sr.UNSCOPED


def test_stage_ns_nested_scope_while_parent_and_residue():
    events = [
        (0, 100, "%while = while()"),             # spans its body
        (0, 30, "%sort = sort()"),
        (30, 20, "%fusion.1 = fusion()"),
        (50, 40, "%copy.29 = copy()"),
        (90, 10, "%fusion.2 = fusion()")]
    names = {"%while = while()": "jit(f)/while:",
             "%sort = sort()": "jit(f)/while/body/tw.route/insert/sort:",
             "%fusion.1 = fusion()": "jit(f)/while/body/tw.route/gather:",
             "%copy.29 = copy()": "jit(f)/while:",
             "%fusion.2 = fusion()": "jit(f)/while/body/tw.finish/add:"}
    ops = tr.leaves(events)
    assert "%while = while()" not in [n for _, _, n in ops]
    acc = sr.stage_ns(ops, [names[n] for _, _, n in ops])
    assert acc == {"tw.route": 50, sr.UNSCOPED: 40, "tw.finish": 10}
    assert sr.stage_ns(ops, [names[n] for _, _, n in ops], depth=2) == {
        "tw.route/insert": 30, "tw.route": 20, sr.UNSCOPED: 40,
        "tw.finish": 10}


# -- a whole hand-made trace ----------------------------------------------------

def _trace_and_spans():
    """Two jobs. Each runs a small program of the caller's (20 ns), then
    the main program (200 ns, two supersteps of 90 ns busy each: next
    event 10, route 60 of which 20 under insert, a copy of the
    compiler's 15, finish 5). The device's clock is 1000 ns ahead."""
    scope = {"%m = fusion()": "jit(run)/while/cond/tw.next_event/min:",
             "%g = fusion()": "jit(run)/while/body/tw.route/gather:",
             "%s = sort()": "jit(run)/while/body/tw.route/insert/sort:",
             "%c = copy()": "jit(run)/while:",
             "%f = fusion()": "jit(run)/while/body/tw.finish/add:",
             "%o = fusion()": "jit(origin)/scatter:"}
    ops, modules, programs, launches, host = [], [], [], [], []
    for j, base in enumerate((1100, 1500)):
        ops.append((base, 20, "%o = fusion()"))
        modules += [(base, 20, "jit_origin(2)"),
                    (base + 60, 200, "jit_run(1)")]
        programs += [(base, 20, "jit_origin(2)", 10 + 2 * j),
                     (base + 60, 200, "jit_run(1)", 11 + 2 * j)]
        for s in (base + 65, base + 165):
            ops += [(s, 10, "%m = fusion()"), (s + 10, 40, "%g = fusion()"),
                    (s + 50, 20, "%s = sort()"), (s + 70, 15, "%c = copy()"),
                    (s + 85, 5, "%f = fusion()")]
        h = base - 1000                     # the same instants, host clock
        launches += [(h - 8, 3, sr.LAUNCH_EVENT, 10 + 2 * j),
                     (h + 50, 3, sr.LAUNCH_EVENT, 11 + 2 * j),
                     (h + 26, 3, sr.DONE_EVENT, 10 + 2 * j),
                     (h + 266, 3, sr.DONE_EVENT, 11 + 2 * j)]
        host += [(h - 30, 400, tr.JOB_SPAN, {}),
                 (h + 30, 260, "tw.run_quiet", {"run": j + 1}),
                 (h + 32, 24, "tw.dispatch", {"run": j + 1}),
                 (h + 56, 232, "tw.wait", {"run": j + 1})]
    trace = tr.Trace(ops=[sorted(ops)], asyncs=[[]], modules=modules,
                     jobs=[e[:3] for e in host if e[2] == tr.JOB_SPAN])
    spans = sr.Spans(host=sorted(host, key=lambda e: e[:3]),
                     scopes=[[scope[n] for _, _, n in sorted(ops)]],
                     launches=sorted(launches), programs=sorted(programs))
    run = {"jobs": [{"supersteps": 2}, {"supersteps": 2}], "facts": {},
           "spans": spans}
    return trace, spans, run


def test_the_readers_on_a_hand_made_trace():
    t, spans, run = _trace_and_spans()
    assert sr.clock(spans) == (994, 1008)   # after the end, before the start
    assert stage_next_event_us.read(t, run) == pytest.approx(0.010)
    assert stage_route_us.read(t, run) == pytest.approx(0.060)
    assert stage_finish_us.read(t, run) == pytest.approx(0.005)
    for absent in (stage_deliver_us, stage_fire_us, stage_rebase_us):
        assert absent.read(t, run) is None  # no operation under the scope
    # the compiler's copy (15 a superstep) and the caller's program (10)
    assert stage_unscoped_share.read(t, run) == pytest.approx(
        100 * 25 / 100)
    assert loop_idle_us.read(t, run) == pytest.approx(0.010)
    assert programs_per_job.read(t, run) == 2.0
    # one gap between the two main programs: 1360..1500 idle, then the
    # caller's program, then 1520..1560 idle. At the bracket's middle
    # (1001) the driver's span ends at 1391 (its wait at 1389) and the
    # next begins at 1531 (dispatch 1533..1557, then its wait)
    assert sync_gap_ms.read(t, run) == pytest.approx(180e-6)
    assert gap_in_run_ms.read(t, run) == pytest.approx((31 + 29) * 1e-6)
    assert (gap_in_run_ms.read(t, run) + gap_in_client_ms.read(t, run)
            == pytest.approx(sync_gap_ms.read(t, run)))
    assert gap_in_client_ms.read(t, run) == pytest.approx(120e-6)
    assert sr.gap_owners_ms(t, run) == pytest.approx({
        "tw.wait": (29 + 3) * 1e-6, "tw.run_quiet": (2 + 2) * 1e-6,
        "tw.dispatch": 24e-6, sr.CLIENT: (109 + 11) * 1e-6})


def test_the_three_identities():
    t, spans, run = _trace_and_spans()
    steps = sr.supersteps(run)
    busy, window = tr.busy_and_window(t)
    # 1. the stages and the unscoped residue are the busy time
    assert sum(sr.stages(t, run).values()) == busy
    assert sum(sr.stages(t, run).values()) / steps / 1e3 == pytest.approx(
        superstep_us.read(t, run))
    # 2. the two owners of the gaps are the gaps
    assert gap_in_run_ms.read(t, run) + gap_in_client_ms.read(t, run) == \
        pytest.approx(sync_gap_ms.read(t, run))
    # 3. idle inside the loop and idle between programs are the idle
    #    time device_idle_share reads: the window's two edges (70 ns
    #    before the first main program, 110 after the last) make one
    #    more gap between jobs, here to the nanosecond
    idle = window * device_idle_share.read(t, run) / 100
    inside = loop_idle_us.read(t, run) * 1e3 * steps
    between = sync_gap_ms.read(t, run) * 1e6 * len(t.jobs)
    assert idle == pytest.approx(inside + between)


def test_a_program_without_names_reads_nothing():
    t, spans, run = _trace_and_spans()
    bare = run | {"spans": spans._replace(
        host=[e for e in spans.host if e[2] == tr.JOB_SPAN],
        scopes=[["jit(run)/while:"] * len(t.ops[0])])}
    for reader in (stage_next_event_us, stage_route_us, stage_finish_us,
                   stage_unscoped_share, gap_in_run_ms, gap_in_client_ms):
        assert reader.read(t, bare) is None
        assert reader.read(t, {**run, "spans": None}) is None
        assert reader.read(t, {k: v for k, v in run.items()
                               if k != "spans"}) is None
    # the two that need no name read the same on any program
    assert loop_idle_us.read(t, bare) == loop_idle_us.read(t, run)
    assert programs_per_job.read(t, bare) == 2.0
    assert sr.clock(bare["spans"]) == (994, 1008)


# -- reading the profiler's file ----------------------------------------------

def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _msg(*fields):
    """A protobuf message from ``(number, value)``: ints as varints,
    ``str``/``bytes`` length-delimited."""
    out = b""
    for num, v in fields:
        if isinstance(v, int):
            out += _varint(num << 3) + _varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += _varint(num << 3 | 2) + _varint(len(v)) + v
    return out


def _xspace():
    """One chip, one job: a ``while`` of two operations and a copy."""
    stat_meta = {1: "tf_op", 2: "run_id", 3: "run", 4: "cause",
                 5: "jit(run)/while/body/tw.fire/add:"}
    ops = {1: ("%w = while()", "jit(run)/while:"),
           2: ("%a = fusion()", 5),                  # a ref_value
           3: ("%c = copy()", None),                 # no op_name at all
           4: ("%s = sort()", "jit(run)/while/body/tw.route/insert/sort:")}

    def metadata(i, name, value=None):
        stat = [] if value is None else [(5, _msg(
            (1, 1), (7, value) if isinstance(value, int) else (5, value)))]
        return (4, _msg((1, i), (2, _msg((1, i), (2, name), *stat))))

    def event(meta, off_ps, dur_ps, *stats):
        return (4, _msg((1, meta), (2, off_ps), (3, dur_ps),
                        *[(4, s) for s in stats]))
    stats = [(5, _msg((1, i), (2, _msg((1, i), (2, n)))))
             for i, n in stat_meta.items()]
    device = _msg(
        (2, "/device:TPU:0"),
        (3, _msg((2, "XLA Modules"), (3, 5000), event(
            9, 0, 100_000, _msg((1, 2), (3, 77))))),
        (3, _msg((2, "XLA Ops"), (3, 5000),
                 event(1, 0, 100_000), event(2, 5_000, 30_000),
                 event(3, 40_000, 20_000), event(4, 60_000, 35_000))),
        *[metadata(i, n, v) for i, (n, v) in ops.items()],
        metadata(9, "jit_run(1)"), *stats)
    host = _msg(
        (2, "/host:CPU"),
        (3, _msg((2, "python"), (3, 4000),
                 event(20, 0, 300_000),
                 event(21, 10_000, 250_000, _msg((1, 3), (4, 1))),
                 event(22, 12_000, 20_000, _msg((1, 3), (4, 1)),
                       _msg((1, 4), (5, "tw.run_quiet"))))),
        (3, _msg((2, "runtime"), (3, 4000),
                 event(23, 20_000, 3_000, _msg((1, 2), (3, 77))),
                 event(24, 140_000, 3_000, _msg((1, 2), (3, 77))),
                 event(23, 900_000, 3_000, _msg((1, 2), (3, 78))))),
        metadata(20, tr.JOB_SPAN), metadata(21, "tw.run_quiet"),
        metadata(22, "tw.dispatch"), metadata(23, sr.LAUNCH_EVENT),
        metadata(24, sr.DONE_EVENT), *stats)
    return _msg((1, device), (1, host))


def test_load_reads_scopes_spans_and_the_clock(tmp_path):
    path = tmp_path / "toy.xplane.pb"
    path.write_bytes(_xspace())
    assert sr.op_names(str(path)) == {"/device:TPU:0": {
        "%w = while()": "jit(run)/while:",
        "%a = fusion()": "jit(run)/while/body/tw.fire/add:",
        "%s = sort()": "jit(run)/while/body/tw.route/insert/sort:"}}
    trace = tr.load(str(path))
    assert [n for _, _, n in trace.ops[0]] == [
        "%a = fusion()", "%c = copy()", "%s = sort()"]
    assert trace.modules == [(5000, 100, "jit_run(1)")]
    assert trace.jobs == [(4000, 300, tr.JOB_SPAN)]
    spans = sr.load(str(path), trace)
    assert spans.scopes == [[
        "jit(run)/while/body/tw.fire/add:", "",
        "jit(run)/while/body/tw.route/insert/sort:"]]
    assert sr.stage_ns(trace.ops[0], spans.scopes[0]) == {
        "tw.fire": 30, sr.UNSCOPED: 20, "tw.route": 35}
    assert [(s, d, n) for s, d, n, _ in spans.host] == [
        (4000, 300, tr.JOB_SPAN), (4010, 250, "tw.run_quiet"),
        (4012, 20, "tw.dispatch")]
    assert spans.host[2][3] == {"run": 1, "cause": "tw.run_quiet"}
    assert spans.programs == [(5000, 100, "jit_run(1)", 77)]
    assert spans.launches == [(4020, 3, sr.LAUNCH_EVENT, 77),
                              (4140, 3, sr.DONE_EVENT, 77),
                              (4900, 3, sr.LAUNCH_EVENT, 78)]
    # enqueued at 4020 and started at 5000; ended at 5100, done at 4140
    assert sr.clock(spans) == (960, 980)
    # without a Trace the host half loads alone (an XLA:CPU trace)
    assert sr.load(str(path)).scopes == []


def test_load_reads_the_drivers_spans_of_a_recorded_cpu_trace(tmp_path):
    """A real trace, recorded here on XLA:CPU: no device plane, so
    ``trace_reduce.load`` refuses it, and the host half still loads:
    one ``tw.run_quiet`` with its ``tw.dispatch`` and ``tw.wait``."""
    import jax
    from timewarp_tpu.interp.jax_engine.edge_engine import EdgeEngine
    from timewarp_tpu.models.token_ring import token_ring
    from timewarp_tpu.net.delays import FixedDelay
    sc = token_ring(64, n_tokens=8, think_us=0, bootstrap_us=1000,
                    end_us=1 << 40, with_observer=False, mailbox_cap=4)
    eng = EdgeEngine(sc, FixedDelay(500), cap=2)
    st = eng.run_quiet(4)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation(tr.JOB_SPAN):
            eng.run_quiet(4, st)
    finally:
        jax.profiler.stop_trace()
    path = tr.find_xplane(str(tmp_path))
    with pytest.raises(ValueError, match="XLA Ops"):
        tr.load(path)
    spans = sr.load(path)
    names = [n for _, _, n, _ in spans.host]
    assert names == [tr.JOB_SPAN, "tw.run_quiet", "tw.dispatch", "tw.wait"]
    job, outer, dispatch, wait = spans.host
    assert dispatch[3]["cause"] == wait[3]["cause"] == "tw.run_quiet"
    assert outer[3]["run"] == dispatch[3]["run"] == wait[3]["run"] == 2
    assert job[0] <= outer[0] and outer[0] + outer[1] <= job[0] + job[1]
    assert spans.scopes == [] and spans.programs == []
    # a gap the size of the job, owned as the spans say
    owners = sr.owner_of_gaps([(job[0], job[1])], spans.host, 0)
    assert sum(owners.values()) == job[1]
    assert owners["tw.wait"] == wait[1]
