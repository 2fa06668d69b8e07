"""What a kernel has to move or compute, from its shapes alone: the
numerators of the roofline shares. Kept with the benchmark so that no
PR that claims a gain can change them.
"""

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))

#: state planes of the fused ring superstep (two queue slots of
#: deliver time, value and kind; wake, cnt, val, send_at), int32 each
RING_PLANES = 10
RING_BYTES_PER_ENTRY = 4


def ring_superstep_bytes(n_nodes: int) -> int:
    """HBM bytes one fused ring superstep cannot avoid: every plane of
    every node read once and written once (2 x 10 x 4 bytes a node,
    83 886 080 at 2^20). The kernel does a few integer operations a
    byte, so its roofline is the HBM one."""
    return 2 * RING_PLANES * RING_BYTES_PER_ENTRY * int(n_nodes)


def device_peaks(device_kind: str) -> dict:
    """Published peaks of the device from ``peaks.json``. A device that
    is not in the table is an error, not a default."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise SystemExit(
            f"benchmark: no published peak for device_kind "
            f"{device_kind!r} in benchmark/peaks.json; add it with its "
            "source (a share of a guessed peak is not reported)")
    return table[device_kind]
