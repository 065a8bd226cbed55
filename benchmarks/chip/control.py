#!/usr/bin/env python3
"""The controls of the correctness comparison, at a cell's own size.

Each control puts the reference in the program's place with one guarantee
of the configuration broken, runs the rest of a run as `run.py` does, and
prints the checks; the comparison has to call every control wrong:

  values32   values kept in 32 bits (the store states u64 values)
  find_first FINDs answered before the plan's DELETEs (the contract orders
             INSERT -> DELETE -> FIND)

    python3 benchmarks/chip/control.py --workload <cell> --seconds <s> \
        --seeds <n> [<n> ...]

The benchmark's own runs never run it; `test_chipbench_harness.py` runs the
same controls at a tiny size.
"""
import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import bench_harness  # noqa: E402
import bench_reference as ref  # noqa: E402
from bench_system import ModelSystem  # noqa: E402

CONTROLS = {
    "values32": lambda: ref.DictModel(value_bits=32),
    "find_first": lambda: ref.DictModel(order=("insert", "find", "delete")),
}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    bench = bench_harness.load_benchmark()
    chips = next(w["chips"] for w in bench["workloads"]
                 if w["name"] == args.workload)
    for name, model in CONTROLS.items():
        for seed in args.seeds:
            out = bench_harness.run_cell(
                args.workload, seed, args.seconds, False,
                system=ModelSystem(model(), chips))
            print(json.dumps({"control": name, "workload": args.workload,
                              "seed": seed, "correct": out["correct"],
                              "plans": out["info"]["plans"],
                              "checks": {k: c["value"] for k, c in
                                         out["checks"].items()}}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
