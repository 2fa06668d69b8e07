"""The sorts of one general-engine superstep, counted in its lowered
text (XLA:CPU lowering of ``_step_all``, no chip, nothing compiled).

A sort is the superstep's dearest kind of operation on the chip
(docs/engines.md per-op table), so their number is part of the
engine's shape. Until PR 30 a commutative inbox paid four: the sender
compaction's N-sort, one routing sort a ladder rung (two rungs at 2048
nodes), and ``tw.rebase``'s ``[K, N]`` sort of every node's free rows.
The last is gone: a node's holes are bit words and the r-th hole a bit
select (ops/numeric.py, tests/test_free_bits.py). An ordered inbox
never had it, and keeps its five (two ``[K, N]`` ones: the inbox's
ordering and the mailbox's compaction).

Since PR 32 a solo engine's commutative ``tw.route/insert`` reads
nothing of the node side on the message lanes: its arrivals are staged
by rank in buffers of their own (one scatter a field) and every node
fills its holes from them elementwise (ops/numeric.py ``fill_holes``).
The parent's one gather a hole word under that scope is gone, on the
ladder and on the eager path; a fleet keeps it (``JaxEngine.
_stages_by_rank`` says why); the sorts are what they were.

Since PR 36 a solo engine's staging sorts once itself where its lanes
are at least its nodes (``_stages_dense``: here both rungs, 8192 and
16 384 lanes for 2048 nodes, and the eager path at full width): one
variadic sort by staged index under ``tw.route/insert`` a rung, in the
place of the sort the compiler put in front of every scatter (which no
lowered text shows), and the scatters after it declared sorted, two
branches of them (half the lanes, or all).

Since PR 48 the sender compaction is no sort: the active sender ids
come in front by a prefix count and a log N shift network
(ops/numeric.py ``compress_lanes``; tests/test_free_bits.py holds it
to the sort it replaced, tests/test_sender_rungs.py to its scope), so
every ladder superstep counts one sort fewer: the routing sort a rung
and, for an ordered inbox, the two along the mailbox's slots.
"""

import re

import pytest

import jax

from timewarp_tpu.interp.jax_engine.batched import BatchSpec
from timewarp_tpu.interp.jax_engine.engine import JaxEngine
from timewarp_tpu.models.gossip import gossip
from timewarp_tpu.models.token_ring import token_ring
from timewarp_tpu.net.delays import Quantize, UniformDelay

N, K = 2048, 24         # a ladder of two rungs: 1024, 2048


def _commutative():
    sc = gossip(N, fanout=8, think_us=2_000, burst=True,
                end_us=1_000_000, mailbox_cap=K)
    return sc, Quantize(UniformDelay(8_000, 30_000), 1_000)


def _ordered():
    sc = token_ring(N - 1, n_tokens=64, think_us=1_000,
                    bootstrap_us=1_000, with_observer=True, mailbox_cap=K)
    return sc, UniformDelay(1_000, 5_000)


#: inbox -> (scenario and link, ``stablehlo.sort`` operations in the
#: superstep at 28d821d, how many of them have gone since: the
#: free-rows sort of a commutative inbox in PR 30, the sender
#: compaction's N-sort of both in PR 48)
INBOX = {"commutative": (_commutative, 4, 2), "ordered": (_ordered, 5, 1)}


def _sort_operands(text: str):
    """The operand types of every ``stablehlo.sort`` of a lowered
    module, one tuple of ``tensor<…>`` strings a sort (the signature
    follows the comparator's region)."""
    sigs = re.findall(
        r'"stablehlo\.sort"\(.*?\}\) (?:\{[^}]*\} )?: \(([^)]*)\)', text,
        flags=re.S)
    assert len(sigs) == text.count('"stablehlo.sort"(')
    return [tuple(re.findall(r"tensor<[^>]*>", s)) for s in sigs]


@pytest.mark.parametrize("fleet", [False, True], ids=["solo", "batch"])
@pytest.mark.parametrize("inbox", sorted(INBOX))
def test_sorts_of_one_superstep(inbox, fleet):
    make, parent, gone = INBOX[inbox]
    sc, link = make()
    assert sc.commutative_inbox == (inbox == "commutative")
    eng = JaxEngine(sc, link, window="auto", lint="off",
                    batch=BatchSpec(seeds=(0, 1, 2)) if fleet else None)
    assert eng._adaptive_regime() and eng._sender_rungs(N) == [1024, 2048]
    text = jax.jit(lambda st: eng._step_all(st, False)).lower(
        eng.init_state()).as_text()
    sorts = _sort_operands(text)
    # a solo commutative inbox stages densely in both rungs: one sort
    # by staged index each (PR 36)
    dense = 0 if fleet or inbox == "ordered" else sum(
        eng._stages_dense(a * sc.max_out) for a in eng._sender_rungs(N))
    assert dense == (2 if inbox == "commutative" and not fleet else 0)
    assert len(sorts) == parent - gone + dense, sorts
    # a [K, N] operand (a fleet's: [B, K, N]) is a sort along the
    # mailbox's slots: none for a commutative inbox, the ordered
    # inbox's two as they were
    slot_axis = [s for s in sorts
                 if re.match(rf"tensor<(\d+x)?{K}x{N}x", s[0])]
    assert len(slot_axis) == (0 if inbox == "commutative" else 2), sorts
    # the holes are there in its place: popcounts, and only where a
    # commutative inbox is
    assert ("stablehlo.popcnt" in text) == (inbox == "commutative")


# ---------------------------------------------------------------------------
# tw.route/insert: scatters into staging planes, and no gather
# ---------------------------------------------------------------------------

def _scopes_of(text: str, op: str):
    """The ``jax.named_scope`` path of every ``stablehlo.<op>`` of a
    module lowered with ``debug_info=True``: the name of the ``loc``
    alias that ends the operation (a scatter's and a sort's follows
    its region, whose own lines carry locations too)."""
    names = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, flags=re.M))
    tail = r"[^\n]*\(\{\n.*?\n\s*\}\) [^\n]*?" \
        if op in ("scatter", "sort") else r"\b[^\n]*?"
    return [names.get(alias, "") for alias in re.findall(
        rf"stablehlo\.{op}\"?{tail}loc\((#loc\d+)\)$", text,
        flags=re.S | re.M)]


def _steady():
    """One slot and ``window`` 1: the eager routing path, no ladder
    (the steady cell's program)."""
    sc = gossip(N, fanout=1, think_us=1_000, gossip_interval=1_000,
                end_us=200_000, steady=True, mailbox_cap=K)
    return sc, Quantize(UniformDelay(1_000, 5_000), 1_000), {}


def _wave():
    return _commutative() + ({"window": "auto"},)


@pytest.mark.parametrize("fleet", [False, True], ids=["solo", "batch"])
@pytest.mark.parametrize("path", ["ladder", "eager"])
def test_a_solo_insert_gathers_nothing(path, fleet):
    sc, link, kw = {"ladder": _wave, "eager": _steady}[path]()
    eng = JaxEngine(sc, link, lint="off", **kw,
                    batch=BatchSpec(seeds=(0, 1, 2)) if fleet else None)
    assert eng._adaptive_regime() == (path == "ladder")
    text = jax.jit(lambda st: eng._step_all(st, False)).lower(
        eng.init_state()).as_text(debug_info=True)

    def under_insert(op):
        return [s for s in _scopes_of(text, op)
                if "tw.route" in s and "/insert/" in s]
    # one scatter a field (deliver time, one payload word; no
    # ``inbox_src``), in every rung of the ladder: a solo engine's
    # into staging buffers, with no gather beside them; a fleet's into
    # the mailbox, behind the parent's one gather a hole word
    rungs = len(eng._sender_rungs(N)) if path == "ladder" else 1
    assert eng._stages_by_rank() == (not fleet)
    # a solo engine's lanes are at least its nodes in every rung here
    # and on the eager path: the dense staging, whose scatters come
    # in two branches (half the lanes, or all) behind one sort a rung
    dense = 0 if fleet else rungs
    assert len(under_insert("scatter")) == \
        (rungs + dense) * (1 + sc.payload_width)
    assert len(under_insert("gather")) == (rungs if fleet else 0)
    assert len(under_insert("sort")) == dense
    assert len(under_insert("popcnt")) >= 1     # the holes are counted
    # the ladder's own gathers (the rung's senders) are still found
    # by the same reading, so "none" above is no blind spot
    if path == "ladder":
        assert any("tw.route" in s for s in _scopes_of(text, "gather"))
    # the routing sort a rung (no N-sort in front of them since
    # PR 48), the eager path's one
    assert len(_sort_operands(text)) == \
        (2 if path == "ladder" else 1) + dense
