"""What a full-width iteration of a faulted fleet has to move, from its
shapes alone: the numerator of ``chaos_superstep_roofline``. Kept with
the benchmark, beside ``steady_costs.py`` (whose bytes a node and a
word these are), so that no PR that claims a gain can change it.

There is no kernel here: the iteration is XLA's, the vmapped routing
ladder at its top rung with the fault masks on its lanes. The share
says how far that is from the one pass over the fleet's state that a
fused full-width iteration would make. The fault tables add nothing a
world a node-sized plane does not dwarf: a partition's group of every
node is one int32 plane a row, read at both ends of a message.
"""

from steady_costs import NODE_BYTES, WORD_BYTES

#: a partition row's group of every node, read once an iteration
GROUP_BYTES = 4


def chaos_superstep_bytes(n_nodes: int, worlds: int, mailbox_cap: int,
                          payload_width: int = 1,
                          partition_rows: int = 1) -> int:
    """HBM bytes one iteration of the fleet cannot avoid when every
    node of every world receives and sends: in each world every
    per-node plane and every mailbox plane (``[mailbox_cap, n]``
    deliver times and payloads) read once and written once, the words
    of ``n`` new messages written into their slots, and each partition
    row's groups read once. 742 391 808 at eight worlds of 2^17 nodes
    and 40 slots: 906.5 us at a v5e's 819 GB/s."""
    message = (1 + int(payload_width)) * WORD_BYTES
    return int(worlds) * int(n_nodes) * (
        2 * (NODE_BYTES + int(mailbox_cap) * message) + message
        + int(partition_rows) * GROUP_BYTES)
