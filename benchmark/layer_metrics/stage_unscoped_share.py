"""Device: share of the leaf operations' device time that lies under no
``tw.`` scope, in percent: what the stage metrics do not account for
(operations the compiler put in itself, such as the copy that stages
the ring kernel's operand, carry no scope of the program's)."""

import span_reduce


def read(trace, run):
    acc = span_reduce.stages(trace, run)
    if acc is None:
        return None
    return 100.0 * acc.get(span_reduce.UNSCOPED, 0.0) / sum(acc.values())
