"""``span_run.py`` for a fleet's cell: the same run and the same line,
with the ``vmap(...)`` that JAX wraps a fleet's stage names in taken
off first (``fleet_reduce.unwrap``), so that device time by stage and
by nested scope reads as it does for a solo engine. ``supersteps`` is
the iterations of the fleet's loop, each of which steps every world.

    python benchmark/fleet_span_run.py --workload <cell> --seed <n> --seconds <s>
"""

import sys

import fleet_reduce
import span_reduce
import span_run

_load = span_reduce.load


def load(path, trace=None):
    spans = _load(path, trace)
    return spans._replace(scopes=[[fleet_reduce.unwrap(s) for s in chip]
                                  for chip in spans.scopes])


if __name__ == "__main__":
    span_reduce.load = load
    sys.exit(span_run.main())
