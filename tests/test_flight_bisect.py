"""The causal flight recorder's divergence bisection (obs/bisect.py;
tests/test_zzzzzflight.py has the record exactness law it stands on):
the chain's units, the pinned one-line diagnostic on an injected flip,
identical runs reporting none, the first diverging chunk by name, and
the CLI's ``bisect``."""

import json

import pytest

from flight_laws import N, _ring, _run_cli
from timewarp_tpu.interp.jax_engine.edge_engine import EdgeEngine
from timewarp_tpu.interp.jax_engine.engine import JaxEngine
from timewarp_tpu.models.gossip import gossip
from timewarp_tpu.net.delays import Quantize, UniformDelay


def test_chain_bisect_units():
    from timewarp_tpu.obs.bisect import chain_bisect
    assert chain_bisect(["a", "b", "c"], ["a", "b", "c"]) is None
    assert chain_bisect(["a", "b", "c"], ["a", "x", "y"]) == 1
    assert chain_bisect(["x"], ["y"]) == 0
    # a strict prefix diverges at its end (one side kept running)
    assert chain_bisect(["a", "b"], ["a", "b", "c"]) == 2
    assert chain_bisect([], []) is None


def test_bisect_pinned_diagnostic_on_injected_flip():
    """The pinned contract (tests/test_zzdiag.py's TraceMismatch
    style, extended): an injected flip: divergence is ONE line naming
    chunk, superstep, field, and the event delta — never arrays."""
    from timewarp_tpu.integrity import FlipInjector
    from timewarp_tpu.obs.bisect import bisect_engines
    sc = gossip(N, fanout=4, burst=True, end_us=400_000,
                mailbox_cap=16)
    link = Quantize(UniformDelay(3000, 9000), 1000)

    def make(record="off"):
        return JaxEngine(sc, link, seed=0, window="auto", lint="off",
                         record=record, record_cap=4096)

    rep = bisect_engines(make, make, 60, chunk=16,
                         names=("clean", "corrupt"),
                         inject_b=lambda: FlipInjector("flip:1:2:mb_rel"),
                         basis="state")
    assert rep is not None
    line = rep.line()
    assert "\n" not in line                       # ONE line
    assert "array" not in line and "[[" not in line
    assert f"chunk {rep.chunk} " in line
    assert rep.chunk == 1                         # deterministic
    assert rep.superstep is not None
    assert f"superstep {rep.superstep}" in line
    assert "clean != corrupt" in line
    assert rep.fields                             # the field clause
    assert rep.only_a + rep.only_b > 0            # the event delta
    assert rep.first_delta and rep.first_delta in line
    # re-running the bisection is bit-deterministic
    rep2 = bisect_engines(make, make, 60, chunk=16,
                          names=("clean", "corrupt"),
                          inject_b=lambda: FlipInjector("flip:1:2:mb_rel"),
                          basis="state")
    assert rep2.line() == line


def test_bisect_identical_runs_report_none():
    from timewarp_tpu.obs.bisect import bisect_engines
    sc, link = _ring()

    def mk_gen(record="off"):
        return JaxEngine(sc, link, seed=0, lint="off", record=record)

    def mk_edge(record="off"):
        return EdgeEngine(sc, link, seed=0, lint="off", record=record)

    # engine vs engine on the ring: bit-identical, trace basis
    assert bisect_engines(mk_gen, mk_edge, 30, chunk=8,
                          basis="trace") is None


def test_first_trail_divergence_names_the_chunk():
    from timewarp_tpu.obs.bisect import first_trail_divergence
    from timewarp_tpu.sweep.spec import DIGEST_ZERO, chain_digest
    sc, link = _ring()
    eng = JaxEngine(sc, link, seed=0, lint="off")
    _, tr = eng.run(24)
    assert len(tr) >= 16

    class _Slice:
        def __init__(self, t, a, b):
            self.t, self.a, self.b = t, a, b

        def __len__(self):
            return self.b - self.a

        def row(self, i):
            return self.t.row(self.a + i)

    trail, cur = [], DIGEST_ZERO
    for hi in (8, 16, len(tr)):
        cur = chain_digest(cur, _Slice(tr, trail[-1][0] if trail
                                       else 0, hi))
        trail.append([hi, cur])
    assert first_trail_divergence(trail, tr) is None
    bad = [list(e) for e in trail]
    bad[1][1] = "f" * 64
    d = first_trail_divergence(bad, tr)
    assert d["chunk"] == 1 and d["supersteps"] == [8, 16]
    assert d["streamed"] == "f" * 64 and d["solo"] == trail[1][1]


def test_cli_bisect_names_the_chunk(capsys):
    rc = _run_cli(["bisect", "gossip", "--nodes", "32", "--steps",
                   "60", "--chunk", "16", "--burst",
                   "--link", "quantize:1000:uniform:3000:9000",
                   "--window", "auto",
                   "--inject-flip", "flip:1:2:mb_rel", "--json"])
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip())
    d = out["divergence"]
    assert d["chunk"] == 1 and d["superstep"] is not None
    assert "clean != corrupt" in d["line"]


def test_cli_bisect_refuses_nothing_to_bisect():
    with pytest.raises(SystemExit, match="nothing to bisect"):
        _run_cli(["bisect", "gossip", "--nodes", "8"])
    # --engine-b + --inject-flip is ambiguous: the cross-engine trace
    # basis cannot see a payload-plane flip (a wrong all-clear)
    with pytest.raises(SystemExit, match="mutually exclusive"):
        _run_cli(["bisect", "gossip", "--nodes", "8", "--engine-b",
                  "edge", "--inject-flip", "flip:1:1"])
