"""The control of each cell's comparison, on the chip at the cell's own
size: for every seed, a short window of the cell as ``run.py`` drives
it, the comparison on what it produced (must pass), and the same
comparison with the control in the program's place (must fail): the
plain reference computed in the precision below the one the
configuration states (``Cell.control`` of the cell's builder).

    python benchmark/control.py --workload <cell> --seconds <s> --seeds <n> <n> ...

One process for all seeds of a cell, so the program is built and
compiled once. Prints one JSON line per seed and exits 0 only if every
sound run passed and every control failed. The benchmark's own runs do
not run this; ``tests/test_control.py`` keeps it at a size a test run
can hold.
"""

import argparse
import importlib
import json
import sys
import time

import run


def one_seed(cell, config, traffic, watch, seed, seconds):
    run.set_up(cell, traffic, seed)
    jobs, _ = run.drive(cell, seconds, watch)
    failed = [j for j in jobs if j["failed"]]
    reference = importlib.import_module(f"reference.{config['reference']}")
    print(f"-- seed {seed}: the program")
    t0 = time.perf_counter()
    sound_rows = cell.compare(reference)
    print(f"reference and comparison: {time.perf_counter() - t0:.2f} s")
    sound = run.judge(sound_rows, failed)
    print(f"-- seed {seed}: the control in the program's place")
    control_rows = cell.control(reference)
    control = run.judge(control_rows, [])
    print(json.dumps({
        "seed": seed, "jobs": len(jobs), "failed": len(failed),
        "correct": sound, "control_correct": control,
        "sound": {name: int(v) for name, v, _ in sound_rows},
        "control": {name: int(v) for name, v, _ in control_rows}}))
    return sound and not control


def main(argv=None, *, on_chip=True, extra_dir=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    a = ap.parse_args(argv)
    try:
        cell, config, traffic, watch, _, _ = run.prepare(
            a.workload, on_chip=on_chip, extra_dir=extra_dir)
    except run.Refused as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    ok = [one_seed(cell, config, traffic, watch, abs(s), a.seconds)
          for s in a.seeds]
    return 0 if all(ok) else 1


if __name__ == "__main__":
    sys.exit(main())
