"""Fuzz-style error-path coverage for the CLI spec grammars.

The never-silent contract extends to *parsing*: every malformed
``--link`` / ``--faults`` spec must die with a message naming the
grammar (cli.LINK_GRAMMAR / faults.schedule.FAULT_GRAMMAR), never
escape as a raw IndexError/ValueError traceback. These tests sweep a
corpus of malformed specs — every historical parse bug class plus
adversarial shapes (empty fields, wrong arity, non-numeric values,
nested-spec damage) — and assert the contract for each.
"""

import pytest

from timewarp_tpu.cli import LINK_GRAMMAR, parse_link
from timewarp_tpu.faults.schedule import FAULT_GRAMMAR, parse_faults

BAD_LINKS = [
    "",                          # empty spec
    ":",                         # empty kind
    "bogus:3",                   # unknown kind
    "fixed",                     # missing delay
    "fixed:",                    # empty delay
    "fixed:abc",                 # non-numeric delay
    "fixed:1:2",                 # excess params
    "uniform:1",                 # missing HI
    "uniform:a:b",               # non-numeric bounds
    "uniform:1:2:3",             # excess params
    "lognormal:5",               # missing SIGMA
    "lognormal:x:y",             # non-numeric
    "drop",                      # bare wrapper
    "drop:0.5",                  # wrapper without inner spec
    "drop:0.5:",                 # empty inner spec
    "drop:zz:fixed:5",           # non-numeric probability
    "drop:0.1:bogus:2",          # damaged inner spec
    "quantize",                  # bare wrapper
    "quantize:5:",               # empty inner spec
    "quantize:a:fixed:1",        # non-numeric grid
    "quantize:5:uniform:1",      # damaged inner arity
    "never:1",                   # never takes no params
    "pareto",                    # missing params
    "pareto:4000",               # missing ALPHA
    "pareto:a:1.5",              # non-numeric XM
    "pareto:4000:x",             # non-numeric ALPHA
    "pareto:0:1.5",              # XM must be >= 1
    "pareto:4000:0",             # ALPHA must be > 0
    "pareto:4000:-1.5",          # negative ALPHA
    "pareto:4000:1.5:9:10:11",   # excess params (FLOOR and CAP: PR 55)
    "quantize:500:pareto:4000",  # damaged inner pareto arity
]

BAD_FAULTS = [
    "",                          # empty spec
    ";;",                        # only separators
    "crash",                     # no fields
    "crash:1",                   # missing window
    "crash:1:2",                 # missing UP
    "crash:1:2:3:4",             # 5th field must be 'reset'
    "crash:1:2:3:resetX",        # damaged reset token
    "crash:x:2:3",               # non-numeric node
    "crash:-1:2:3",              # negative node
    "crash:1:2q:3",              # bad time suffix
    "partition:0|1",             # missing window
    "partition:0:1:2",           # one group cuts nothing
    "partition:all|1:0:5",       # 'all' group is not explicit
    "partition:0-|1:0:5",        # damaged range
    "partition:3-1|5:0:5",       # empty range
    "partition:0+0|1:0:5",       # node in two... (duplicate in group)
    "degrade:1:2:3",             # missing fields
    "degrade:all:all:0:5:x",     # non-numeric scale
    "degrade:all:all:0:5:-1",    # scale must be > 0
    "degrade:all:all:0:5:1.0:-3",  # negative extra
    "skew:1",                    # missing offset
    "skew:a:5",                  # non-numeric node
    "bogus:1:2",                 # unknown kind
    "crash:1:2:3,crash:2:3:4",   # comma is not the separator
]


@pytest.mark.parametrize("spec", BAD_LINKS)
def test_malformed_link_specs_name_the_grammar(spec):
    with pytest.raises(SystemExit) as ei:
        parse_link(spec)
    msg = str(ei.value)
    assert "grammar" in msg and LINK_GRAMMAR in msg, \
        f"{spec!r} died without naming the grammar: {msg}"


@pytest.mark.parametrize("spec", BAD_LINKS)
def test_malformed_link_specs_never_raw_traceback(spec):
    # the contract's other half: the ONLY exception species is the
    # grammar-named SystemExit — no IndexError/ValueError escapes
    try:
        parse_link(spec)
    except SystemExit:
        pass
    else:
        pytest.fail(f"{spec!r} parsed without error")


@pytest.mark.parametrize("spec", BAD_FAULTS)
def test_malformed_fault_specs_name_the_grammar(spec):
    with pytest.raises(SystemExit) as ei:
        parse_faults(spec)
    msg = str(ei.value)
    assert "grammar" in msg and FAULT_GRAMMAR in msg, \
        f"{spec!r} died without naming the grammar: {msg}"


@pytest.mark.parametrize("spec", BAD_FAULTS)
def test_malformed_fault_specs_never_raw_traceback(spec):
    try:
        parse_faults(spec)
    except SystemExit:
        pass
    else:
        pytest.fail(f"{spec!r} parsed without error")


def test_good_specs_still_parse():
    """The fuzz corpus must not have been 'fixed' by rejecting valid
    grammar: canonical good specs from the docs still parse."""
    from timewarp_tpu.net.delays import Quantize, WithDrop
    assert parse_link("fixed:500").delay == 500
    assert isinstance(parse_link("drop:0.25:quantize:1000:uniform:1000:5000"),
                      WithDrop)
    assert isinstance(parse_link("quantize:1000:lognormal:5000:0.5"),
                      Quantize)
    sched = parse_faults(
        "crash:3:5s:9s:reset; partition:0-3|4-7:2s:4s; "
        "degrade:all:all:1s:2s:4.0:10ms; skew:2:250")
    assert len(sched.events) == 4


# ---------------------------------------------------------------------------
# the --inject flip: grammar (integrity/, ISSUE 10 satellite)
# ---------------------------------------------------------------------------

BAD_INJECTS = [
    "flip",                      # no seed
    "flip:",                     # empty seed
    "flip:x",                    # non-numeric seed
    "flip:-1",                   # negative seed
    "flip:1:0",                  # chunk must be >= 1
    "flip:1:z",                  # non-numeric chunk
    "flip:1:2:",                 # empty plane
    "flip:1:2:mb_rel:extra",     # excess fields
    "flip:1.5",                  # float seed
]


@pytest.mark.parametrize("spec", BAD_INJECTS)
def test_malformed_flip_specs_name_the_grammar(spec):
    from timewarp_tpu.integrity.inject import INJECT_GRAMMAR
    from timewarp_tpu.sweep.service import InjectPlan
    from timewarp_tpu.sweep.spec import SweepConfigError
    with pytest.raises(SweepConfigError) as ei:
        InjectPlan(spec)
    msg = str(ei.value)
    assert "grammar" in msg and INJECT_GRAMMAR in msg, \
        f"{spec!r} died without naming INJECT_GRAMMAR: {msg}"


@pytest.mark.parametrize("spec", BAD_INJECTS)
def test_malformed_flip_specs_never_raw_traceback(spec):
    from timewarp_tpu.sweep.service import InjectPlan
    from timewarp_tpu.sweep.spec import SweepConfigError
    try:
        InjectPlan(spec)
    except SweepConfigError:
        pass
    else:
        pytest.fail(f"{spec!r} parsed without error")


def test_good_flip_specs_parse():
    from timewarp_tpu.integrity.inject import FlipSpec, parse_flip
    from timewarp_tpu.sweep.service import InjectPlan
    assert parse_flip("flip:3") == FlipSpec(seed=3, chunk=1,
                                            plane=None)
    assert parse_flip("flip:3:7") == FlipSpec(seed=3, chunk=7,
                                              plane=None)
    assert parse_flip("flip:3:7:mb_rel") == FlipSpec(
        seed=3, chunk=7, plane="mb_rel")
    plan = InjectPlan("fail:1;flip:5:2:mb_rel;die:9")
    assert plan.flip[2].seed == 5 and plan.flip[2].plane == "mb_rel"
    assert plan.fail == {1} and plan.die == {9}


# ---------------------------------------------------------------------------
# parse round-trip idempotence (ISSUE 10 satellite): parsing the same
# spec twice yields the SAME model — field-equal objects AND (for
# faults) bit-identical lowered tables. A parser with hidden state
# (mutating defaults, shared caches, entropy) would break the sweep
# bucketer's link_signature identity and the resume path's
# re-derivation of the same plan from the journaled pack.
# ---------------------------------------------------------------------------

GOOD_LINKS = [
    "fixed:500",
    "uniform:1000:5000",
    "lognormal:5000:0.5",
    "pareto:4000:1.5",
    "never",
    "drop:0.25:quantize:1000:uniform:1000:5000",
    "quantize:1000:lognormal:5000:0.5",
    "quantize:500:pareto:4000:1.2",
]


# ---------------------------------------------------------------------------
# the --speculate grammar (speculate/, ISSUE 12)
# ---------------------------------------------------------------------------

BAD_SPECULATES = [
    "",                          # empty spec
    "Auto",                      # case matters (a typo, not a mode)
    "on",                        # unknown mode
    "fixed",                     # bare fixed (no width)
    "fixed:",                    # empty width
    "fixed:abc",                 # non-numeric width
    "fixed:1",                   # W=1 is the classic engine
    "fixed:-500",                # negative width
    "auto:3",                    # auto takes no parameters
]


@pytest.mark.parametrize("spec", BAD_SPECULATES)
def test_malformed_speculate_specs_name_the_grammar(spec):
    from timewarp_tpu.speculate import (SPECULATE_GRAMMAR,
                                        parse_speculate)
    with pytest.raises(ValueError) as ei:
        parse_speculate(spec)
    msg = str(ei.value)
    assert "grammar" in msg and SPECULATE_GRAMMAR in msg, \
        f"{spec!r} died without naming SPECULATE_GRAMMAR: {msg}"


def test_good_speculate_specs_parse():
    from timewarp_tpu.speculate import parse_speculate
    assert parse_speculate(None) == ("off", None)
    assert parse_speculate("off") == ("off", None)
    assert parse_speculate("auto") == ("auto", None)
    assert parse_speculate("fixed:8000") == ("fixed", 8000)

GOOD_FAULTS = [
    "crash:3:5s:9s",
    "crash:3:5s:9s:reset",
    "partition:0-3|4-7:2s:4s",
    "degrade:all:all:1s:2s:4.0:10ms",
    "skew:2:250",
    "crash:1:2s:3s; partition:0-1|2-3:1s:2s; "
    "degrade:all:all:1s:2s:2.0; skew:0:100",
]


@pytest.mark.parametrize("spec", GOOD_LINKS)
def test_parse_link_round_trip_idempotent(spec):
    assert parse_link(spec) == parse_link(spec)


@pytest.mark.parametrize("spec", GOOD_FAULTS)
def test_parse_faults_round_trip_idempotent(spec):
    import numpy as np
    a, b = parse_faults(spec), parse_faults(spec)
    assert a == b
    ta, tb = a.tables(8), b.tables(8)
    assert all(np.array_equal(x, y) for x, y in zip(ta, tb))


# -- format_faults: the grammar round-trip serializer ----------------------
#
# The chaos search (timewarp_tpu/search/) emits every minimized
# counterexample as a paste-able --faults string, which needs a
# serializer whose re-parse is FIELD-EQUAL to the schedule it
# printed — pinned over the whole good-spec corpus plus adversarial
# shapes (non-contiguous node sets, descending ids, float scales).

FORMAT_FAULTS = GOOD_FAULTS + [
    "degrade:0+5:all:0:100:1.5",          # non-contiguous node set
    "degrade:7+2:3-5:10:20:2.5:7",        # descending ids + ranges
    "crash:0:0:1",                        # minimal window
    "partition:0|1-6+7:0:10",             # singleton group + join
    "skew:4:-250",                        # negative offset
]


@pytest.mark.parametrize("spec", FORMAT_FAULTS)
def test_format_faults_round_trips_field_equal(spec):
    import numpy as np

    from timewarp_tpu.faults.schedule import format_faults
    a = parse_faults(spec)
    out = format_faults(a)
    b = parse_faults(out)
    assert a.events == b.events, (spec, out)
    # and the lowered tables agree bit-for-bit
    ta, tb = a.tables(8), b.tables(8)
    assert all(np.array_equal(x, y) for x, y in zip(ta, tb))
    # idempotent: formatting the re-parse prints the same string
    assert format_faults(b) == out


def test_format_faults_numpy_scale_round_trips():
    """np.float64 IS a float subclass, so LinkWindow accepts it — and
    its repr ('np.float64(2.0)') must never leak into the grammar
    string (programmatic scales come from numpy vectors). The
    constructor normalizes to a plain float."""
    import numpy as np

    from timewarp_tpu.faults.schedule import (FaultSchedule,
                                              LinkWindow,
                                              format_faults)
    s = FaultSchedule((LinkWindow(None, None, 0, 100,
                                  scale=np.float64(2.0)),))
    out = format_faults(s)
    assert out == "degrade:all:all:0:100:2.0"
    assert parse_faults(out).events == s.events


def test_format_faults_refuses_empty_schedule():
    from timewarp_tpu.faults.schedule import (FaultSchedule,
                                              format_faults)
    with pytest.raises(ValueError, match="empty"):
        format_faults(FaultSchedule(()))


def test_format_faults_ignores_fleet_pad():
    """pad is a fleet-shape artifact with no grammar form: a padded
    schedule prints the same events, and the re-parse (pad zero) is
    result-identical by the inert-row law."""
    from timewarp_tpu.faults.schedule import format_faults
    a = parse_faults("crash:3:5s:9s")
    assert format_faults(a.padded(4, 2, 2)) == format_faults(a)


# ---------------------------------------------------------------------------
# the --hosts/--listen host-spec grammar (serve/, ISSUE 15 satellite)
# ---------------------------------------------------------------------------

BAD_HOSTS = [
    "",                          # empty spec
    " ",                         # whitespace spec
    ",",                         # only separator
    "a,",                        # trailing empty entry
    ",b",                        # leading empty entry
    "a,,b",                      # empty middle entry
    "a,a",                       # duplicate host name
    "a,b,a",                     # duplicate host name (non-adjacent)
    "bad name",                  # space in NAME
    "a@",                        # '@' without HOST:PORT
    "a@hostonly",                # missing port
    "a@:7000",                   # empty host
    "a@h:",                      # empty port
    "a@h:x",                     # non-integer port
    "a@h:0",                     # port below range
    "a@h:65536",                 # port above range
    "a@h:70:9",                  # host containing ':' (excess field)
    "a@@h:7000",                 # double '@'
    "café",                 # non-ASCII name
]

BAD_LISTENS = [
    "",                          # empty spec
    "host",                      # missing port
    ":7000",                     # empty host
    "h:",                        # empty port
    "h:x",                       # non-integer port
    "h:0",                       # port below range
    "h:65536",                   # port above range
    "h h:7000",                  # space in host (untrimmed)
    "a@h:7000",                  # '@' belongs to --hosts, not --listen
    "h,i:7000",                  # ',' in host
]


@pytest.mark.parametrize("spec", BAD_HOSTS)
def test_malformed_host_specs_name_the_grammar(spec):
    from timewarp_tpu.serve.hosts import HOST_GRAMMAR, parse_hosts
    with pytest.raises(SystemExit) as ei:
        parse_hosts(spec)
    msg = str(ei.value)
    assert "grammar" in msg and HOST_GRAMMAR in msg, \
        f"{spec!r} died without naming HOST_GRAMMAR: {msg}"


@pytest.mark.parametrize("spec", BAD_HOSTS)
def test_malformed_host_specs_never_raw_traceback(spec):
    from timewarp_tpu.serve.hosts import parse_hosts
    try:
        parse_hosts(spec)
    except SystemExit:
        pass
    else:
        pytest.fail(f"{spec!r} parsed without error")


@pytest.mark.parametrize("spec", BAD_LISTENS)
def test_malformed_listen_specs_name_the_grammar(spec):
    from timewarp_tpu.serve.hosts import HOST_GRAMMAR, parse_listen
    with pytest.raises(SystemExit) as ei:
        parse_listen(spec)
    msg = str(ei.value)
    assert "grammar" in msg and HOST_GRAMMAR in msg, \
        f"{spec!r} died without naming HOST_GRAMMAR: {msg}"


def test_good_host_specs_parse():
    from timewarp_tpu.serve.hosts import (HostSpec, parse_host,
                                          parse_hosts, parse_listen)
    assert parse_listen("127.0.0.1:7700") == ("127.0.0.1", 7700)
    assert parse_listen("my-box.local:1") == ("my-box.local", 1)
    assert parse_host("alpha") == HostSpec("alpha")
    assert parse_host("a@10.0.0.1:7700") == \
        HostSpec("a", ("10.0.0.1", 7700))
    fleet = parse_hosts("a@10.0.0.1:7700,b,c.2_x")
    assert [h.name for h in fleet] == ["a", "b", "c.2_x"]
    assert fleet[0].addr == ("10.0.0.1", 7700)
    assert fleet[1].addr is None


# -- pack entries (sweep/spec.py PACK_GRAMMAR) ----------------------------
#
# a malformed SweepPack/RunConfig JSON dies naming the offending field
# and quoting PACK_GRAMMAR — never a raw KeyError/TypeError from
# deeper in the machinery (the LINK_GRAMMAR/FAULT_GRAMMAR discipline)

BAD_PACKS = [
    "nope",                                    # entry not an object
    {"params": {"nodes": 8}},                  # missing scenario
    {"scenario": 42},                          # scenario not a string
    {"scenario": "warp-drive"},                # unknown family
    {"scenario": "gossip", "mailbox": 9},      # unknown key
    {"scenario": "gossip", "params": [8]},     # params not an object
    {"scenario": "gossip",
     "params": {"teleport": 1}},               # unknown builder param
    {"scenario": "gossip", "link": 123},       # link not a string spec
    {"scenario": "gossip", "seed": "0"},       # seed not an int
    {"scenario": "gossip", "seed": True},      # bool masquerading
    {"scenario": "gossip", "window": True},    # bool window (== 1!)
    {"scenario": "gossip", "window": 0},       # window below range
    {"scenario": "gossip", "window": "wide"},  # window not int/'auto'
    {"scenario": "gossip", "budget": 3.5},     # budget not an int
    {"scenario": "gossip", "budget": 0},       # budget below range
    {"scenario": "gossip", "faults": ["c"]},   # faults not a string
    {"scenario": "gossip", "controller": None},   # controller type
    {"scenario": "gossip", "controller": "maybe"},  # controller value
    {"scenario": "gossip", "speculate": 2000},    # speculate type
    {"scenario": "gossip", "speculate": "fixed"},  # missing :W
    {"scenario": "gossip", "speculate": "auto",
     "controller": "auto"},                    # two decision sources
]


@pytest.mark.parametrize("entry", BAD_PACKS,
                         ids=[str(i) for i in range(len(BAD_PACKS))])
def test_malformed_pack_entries_name_the_field(entry):
    from timewarp_tpu.sweep.spec import RunConfig, SweepConfigError
    with pytest.raises(SweepConfigError) as ei:
        RunConfig.from_json(entry, 0)
    msg = str(ei.value)
    assert "0" in msg or "'w0'" in msg, \
        f"{entry!r} died without naming the entry: {msg}"


@pytest.mark.parametrize("entry", BAD_PACKS,
                         ids=[str(i) for i in range(len(BAD_PACKS))])
def test_malformed_pack_entries_never_raw_traceback(entry):
    from timewarp_tpu.sweep.spec import RunConfig, SweepConfigError
    try:
        RunConfig.from_json(entry, 0)
    except SweepConfigError:
        pass                    # the loud, field-naming species
    else:
        pytest.fail(f"{entry!r} parsed without error")


def test_malformed_pack_shapes_die_loudly():
    from timewarp_tpu.sweep.spec import SweepConfigError, SweepPack
    for data in ("worlds", {"no_worlds": []}, 17):
        with pytest.raises(SweepConfigError):
            SweepPack.from_json(data)
    with pytest.raises(SweepConfigError) as ei:
        SweepPack.from_json([])            # empty pack
    assert "at least one" in str(ei.value)
    dup = [{"scenario": "gossip", "id": "w0"},
           {"scenario": "gossip", "id": "w0"}]
    with pytest.raises(SweepConfigError) as ei:
        SweepPack.from_json(dup)
    assert "duplicate" in str(ei.value)


def test_field_refusals_quote_pack_grammar():
    from timewarp_tpu.sweep.spec import (PACK_GRAMMAR, RunConfig,
                                         SweepConfigError)
    for entry in [{"scenario": "gossip", "params": [8]},
                  {"scenario": "gossip", "window": True},
                  {"scenario": "gossip", "link": 123},
                  {"params": {"nodes": 8}}]:
        with pytest.raises(SweepConfigError) as ei:
            RunConfig.from_json(entry, 0)
        assert PACK_GRAMMAR in str(ei.value), \
            f"{entry!r} died without quoting PACK_GRAMMAR"


def test_good_pack_entries_round_trip():
    from timewarp_tpu.sweep.spec import RunConfig, SweepPack
    entries = [
        {"scenario": "gossip", "params": {"nodes": 8}},
        {"scenario": "token-ring", "id": "ring",
         "params": {"nodes": 8, "with_observer": False},
         "link": "fixed:1000", "seed": 3, "window": "auto",
         "budget": 50},
        {"scenario": "praos", "faults": "crash:1:5s:9s:reset",
         "speculate": "fixed:16000"},
    ]
    pack = SweepPack.from_json(entries)
    again = SweepPack.from_json(pack.to_json())
    assert again == pack and again.sha() == pack.sha()
    # every to_json survives its own from_json field-for-field
    for i, c in enumerate(pack.configs):
        assert RunConfig.from_json(c.to_json(), i) == c


# ---------------------------------------------------------------------------
# the --pack knob: grammar (pack/allocate.py, predictive packing)
# ---------------------------------------------------------------------------

BAD_PACK_MODES = [
    "",                 # empty
    "best-fit",         # the algorithm, not the knob value
    "firstfit",         # missing dash
    "first fit",        # space, not dash
    "Predicted",        # case matters
    "predicted ",       # trailing whitespace
    "predict",          # truncated
    "bfd",              # insider shorthand
    "first-fit|predicted",  # the grammar string itself is not a value
]


@pytest.mark.parametrize("mode", BAD_PACK_MODES)
def test_malformed_pack_modes_name_the_grammar(mode):
    from timewarp_tpu.pack.allocate import (PACK_MODE_GRAMMAR,
                                            validate_pack_mode)
    from timewarp_tpu.sweep.spec import SweepConfigError
    with pytest.raises(SweepConfigError) as ei:
        validate_pack_mode(mode)
    msg = str(ei.value)
    assert "grammar" in msg and PACK_MODE_GRAMMAR in msg, \
        f"{mode!r} died without naming PACK_MODE_GRAMMAR: {msg}"


@pytest.mark.parametrize("mode", BAD_PACK_MODES)
def test_malformed_pack_modes_refused_everywhere(mode):
    # every surface that takes the knob refuses with the SAME loud
    # species: the planner, the sweep service, the serve frontend,
    # and the curator — never a silent fallback to first-fit
    from timewarp_tpu.sweep.bucket import plan_buckets
    from timewarp_tpu.sweep.spec import SweepConfigError
    with pytest.raises(SweepConfigError):
        plan_buckets([], pack_mode=mode)


def test_good_pack_modes_validate():
    from timewarp_tpu.pack.allocate import (PACK_MODES,
                                            validate_pack_mode)
    for mode in PACK_MODES:
        assert validate_pack_mode(mode) == mode


def test_pack_fit_refuses_absent_and_empty_ledgers(tmp_path):
    # `pack fit` on nothing must be ONE actionable line, never a
    # silent empty artifact (pack/cli.py)
    from timewarp_tpu.pack.cli import pack_main
    with pytest.raises(SystemExit) as ei:
        pack_main(["fit", "--ledger", str(tmp_path / "nope")])
    assert "index.jsonl" in str(ei.value) \
        and "ledger add" in str(ei.value)
    # a ledger that exists but holds no pack_stats rows is refused
    # just as loudly
    from timewarp_tpu.obs.ledger import RunLedger
    led = tmp_path / "led"
    RunLedger(str(led)).add_bench_line(
        {"config": "x", "config_key": "x|cpu", "value": 1.0,
         "schema": 2}, source="test")
    with pytest.raises(SystemExit) as ei:
        pack_main(["fit", "--ledger", str(led)])
    assert "pack_stats" in str(ei.value)


def test_pack_subcommand_usage_is_loud():
    from timewarp_tpu.pack.cli import pack_main
    with pytest.raises(SystemExit) as ei:
        pack_main(["frobnicate"])
    assert "usage" in str(ei.value)
