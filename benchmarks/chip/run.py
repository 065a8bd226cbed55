#!/usr/bin/env python3
"""Chip benchmark of the store: one run of one cell of `BENCHMARK.json`.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine that holds the chips the cell
asks for. The last line of stdout is one JSON object: `correct`,
`attempted`, `failed`, `metrics` (the cell's end-to-end metrics, or with
`--trace 1` its per-layer metrics), `device` (platform, kind, count, peak
bytes of the fullest chip; with `--trace 1` also `busy_s` and `window_s`),
with `--trace 1` a `breakdown`, and last `checks`: each number the
correctness comparison compared, beside its limit. The same checks are the
last lines of stderr. A JAX that finds no TPU, or fewer chips than the cell
asks for, exits 2 with no result line.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

# the TPU runtime logs to a fixed path under /tmp unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")

sys.path.insert(0, str(Path(__file__).resolve().parent))

import bench_harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dump-trace", metavar="JSON",
                    help="with --trace 1, also write every plane and line "
                    "of the trace with its first events, to read by hand")
    args = ap.parse_args(argv)
    try:
        result = bench_harness.run_cell(args.workload, args.seed,
                                        args.seconds, bool(args.trace),
                                        t_process=T_PROCESS,
                                        dump_trace=args.dump_trace)
    except bench_harness.NoChip as e:
        print(f"chip benchmark: {e}; nothing was run", file=sys.stderr)
        return 2
    for k, c in result["checks"].items():
        print(f"check {k}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
