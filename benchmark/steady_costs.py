"""What a full-width superstep of the general engine has to move, from
its shapes alone: the numerator of ``steady_superstep_roofline``. Kept
with the benchmark, beside ``kernel_costs.py`` (which no later PR
edits), so that no PR that claims a gain can change it.

There is no kernel here: the superstep is XLA's, some hundred fusions,
sorts, gathers and scatters. The share says how far that is from the
one pass over the state that a fused full-width superstep would make.
"""

#: bytes a node of the per-node planes every superstep reads and
#: writes: ``hop``, ``lcg``, ``left`` (int32), ``next``, ``wake`` (int64)
NODE_BYTES = 3 * 4 + 2 * 8
#: a message in flight is int32 words in the mailbox's planes: its
#: deliver time (``mb_rel``) and one column of ``mb_payload`` a payload
#: word. ``mb_src`` is left out: a scenario that never reads the sender
#: (``inbox_src=False``) never has it written
WORD_BYTES = 4


def steady_superstep_bytes(n_nodes: int, mailbox_cap: int,
                           payload_width: int = 1) -> int:
    """HBM bytes one superstep of steady mongering cannot avoid when
    every node receives and every node sends: every per-node plane and
    every mailbox plane (``[mailbox_cap, n]`` deliver times and
    payloads) read once and written once, plus the words of ``n`` new
    messages written into their slots. 469 762 048 at 2^20 nodes and 24
    slots: 573.6 us at a v5e's 819 GB/s."""
    message = (1 + int(payload_width)) * WORD_BYTES
    return int(n_nodes) * (
        2 * (NODE_BYTES + int(mailbox_cap) * message) + message)
