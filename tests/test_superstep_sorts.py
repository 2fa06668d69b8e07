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
#: parent's superstep (28d821d), how many of them went in PR 30)
INBOX = {"commutative": (_commutative, 4, 1), "ordered": (_ordered, 5, 0)}


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
    assert len(sorts) == parent - gone, sorts
    # a [K, N] operand (a fleet's: [B, K, N]) is a sort along the
    # mailbox's slots: none for a commutative inbox, the ordered
    # inbox's two as they were
    slot_axis = [s for s in sorts
                 if re.match(rf"tensor<(\d+x)?{K}x{N}x", s[0])]
    assert len(slot_axis) == (0 if inbox == "commutative" else 2), sorts
    # the holes are there in its place: popcounts, and only where a
    # commutative inbox is
    assert ("stablehlo.popcnt" in text) == (inbox == "commutative")
