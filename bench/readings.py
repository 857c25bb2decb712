"""The readings a cell's correctness limits are set from.

    python3 bench/readings.py --workload <cell> --seed <n> --program 12 --control 3

One process sets the cell up as a run does, then prints one JSON line per
reading:

* ``program``: a question on a fresh seed, through the timed path,
  against the frozen reference on the CPU — the lower readings;
* ``control``: the reference computed in float32 in the program's place
  (on the accelerator) against the reference in float64 on the CPU — the
  upper readings.

The benchmark's own runs never run this.  Like ``run.py`` it needs a TPU.
"""
import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import harness  # noqa: E402
import surface_util  # noqa: E402
from metric_math import derive_seed  # noqa: E402


def main(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--program", type=int, default=12)
    ap.add_argument("--control", type=int, default=3)
    args = ap.parse_args(argv)
    cell = harness.load_cell(harness.ROOT, args.workload)
    harness.require_chip(cell.chips)
    sys.path.insert(0, str(harness.ROOT / "src"))
    harness.enable_cache(harness.ROOT)
    import repro.core  # noqa: F401
    surface = harness.load_module(
        HERE / "surfaces" / f"{cell.traffic['surface']}.py", "surface")
    state = surface.prepare(cell.config, cell.traffic)
    harness.ask(surface, state, args.seed, -1)
    print(json.dumps({"setup_s": time.perf_counter() - PROCESS_START}),
          flush=True)
    for i in range(args.program):
        q = harness.ask(surface, state, args.seed, i)
        t0 = time.perf_counter()
        with surface_util.on_cpu():
            gaps = surface.compare(surface.answer(q),
                                   surface.reference(state, q["seed"]))
        print(json.dumps({"kind": "program", "seed": q["seed"],
                          "question_s": q["wall_s"],
                          "reference_s": time.perf_counter() - t0, **gaps}),
              flush=True)
    for j in range(args.control):
        s = derive_seed(args.seed, 10_000 + j)
        t0 = time.perf_counter()
        gaps = surface_util.control(surface, state, s)
        print(json.dumps({"kind": "control", "seed": s,
                          "seconds": time.perf_counter() - t0, **gaps}),
              flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
