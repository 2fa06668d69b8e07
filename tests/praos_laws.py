"""What tests/test_zzzzzzzzzzzzzzpraos_slots.py and
tests/test_praos_lowering.py share: ``benchmark/`` on the path, its
praos files loaded, a cell at a test's size, and a driver's lowered
text. No test lives here."""

import json
import os
import re
import sys

BENCHMARK = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
# the benchmark's own modules, by the names its scripts use: whoever
# imports this module first may import them after
if BENCHMARK not in sys.path:
    sys.path.insert(0, BENCHMARK)

import fleet_reduce  # noqa: E402
import span_reduce  # noqa: E402
from builders import praos_slots  # noqa: E402


def _load(kind, name):
    with open(os.path.join(BENCHMARK, kind, name + ".json")) as f:
        return json.load(f)


def _cell(n, control_cap=8, mailbox_cap=24):
    traffic = _load("workloads", "praos_1m.slots")
    config = _load("configs", traffic["config"])
    config["params"].update(n_nodes=n, mailbox_cap=mailbox_cap)
    # the committed cell runs one slot a job (two take 2.8 s on the
    # chip); here two, so that a chain grows over one it already has
    traffic["slots_per_job"] = 2
    # 16 slots hold every tip at these sizes (15 and 16 in flight)
    config["control"]["mailbox_cap"] = control_cap
    return praos_slots.Cell(config, traffic)


def _lowered(eng, **kw):
    return type(eng)._run_while.lower(
        eng, eng.init_state(), eng._coerce_budget(8)[0],
        eng._identity()).as_text(**kw)


def _nested_scopes(eng) -> set:
    names = re.findall(r'loc\("(jit\(_run_while\)[^"]*)"',
                       _lowered(eng, debug_info=True))
    return {span_reduce.stage_of(fleet_reduce.unwrap(n), 2) for n in names}
