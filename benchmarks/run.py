"""Benchmark harness: one module per paper table (+ framework benches and
the execution-layer probe comparison).

Prints ``name,us_per_call,derived`` CSV; ``--out DIR`` additionally writes
machine-readable ``BENCH_<table>.json`` files for tables ported to the
shared `benchmarks.common.Recorder` harness.
"""
import argparse
import inspect
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> None:
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, ROOT)       # `python benchmarks/run.py` from anywhere
    import repro  # noqa: F401
    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None,
                    help="directory for BENCH_*.json artifacts")
    ap.add_argument("--only", nargs="*", default=None,
                    help="run only these table modules (by name)")
    args = ap.parse_args()

    print("name,us_per_call,derived")
    from benchmarks import (framework_bench, probe_modes, recovery,
                            serve_trace, table1_queues, table2_3_skiplist,
                            table4_det_vs_rand, table5_8_hashes, tiers_churn)
    mods = {m.__name__.rsplit(".", 1)[-1]: m
            for m in (table1_queues, table2_3_skiplist, table4_det_vs_rand,
                      table5_8_hashes, probe_modes, tiers_churn,
                      serve_trace, recovery, framework_bench)}
    unknown = set(args.only or ()) - set(mods)
    if unknown:
        ap.error(f"unknown table(s) {sorted(unknown)}; "
                 f"available: {sorted(mods)}")
    for name, mod in mods.items():
        if args.only and name not in args.only:
            continue
        if "out_dir" in inspect.signature(mod.run).parameters:
            mod.run(out_dir=args.out)
        else:
            mod.run()


if __name__ == '__main__':
    main()
