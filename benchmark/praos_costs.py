"""What one pass over a Praos world's state has to move, from its
shapes alone: the numerator of ``praos_superstep_roofline``. Kept with
the benchmark, beside ``kernel_costs.py`` and ``steady_costs.py`` (which
no later PR edits), so that no PR that claims a gain can change it.

There is no kernel here: the superstep is XLA's, a conditional of
eleven routing rungs among some hundreds of fusions, sorts, gathers and
scatters, and most supersteps of a job touch a small part of the
nodes. The share prices what a superstep that touched the state once
would take; it says how far the program is from that, not how near a
kernel is to its roofline.
"""

#: bytes a node of the per-node planes: ``best``, ``lcg``, ``slot``,
#: ``thr`` (32 bits each), ``nslot``, ``wake`` (int64)
NODE_BYTES = 4 * 4 + 2 * 8
#: a message in flight is int32 words in the mailbox's planes: its
#: deliver time (``mb_rel``) and one column of ``mb_payload`` a payload
#: word. ``mb_src`` is left out: a scenario that never reads the sender
#: (``inbox_src=False``) never has it written
WORD_BYTES = 4


def praos_superstep_bytes(n_nodes: int, mailbox_cap: int,
                          payload_width: int,
                          messages_per_superstep: float) -> float:
    """HBM bytes of a superstep that reads every per-node plane and
    every mailbox plane (``[mailbox_cap, n]`` deliver times and
    payload words) once and writes them once, plus the words of the
    messages a superstep sends on average. 671 088 640 + 12 a message
    at 2^20 nodes, 24 slots and two payload words: 819.4 us at a v5e's
    819 GB/s."""
    message = (1 + int(payload_width)) * WORD_BYTES
    return (int(n_nodes) * 2 * (NODE_BYTES + int(mailbox_cap) * message)
            + float(messages_per_superstep) * message)
