"""Device time per step of each of the program's `obs.*` phases, from a
`jax.profiler` trace.

`bench_trace.reduce` splits a chip's device time into probe, collectives
and the rest. This splits it by phase name: an op's self time counts for
every `obs.*` name on its scope path (so a nested phase also counts inside
the phase around it), and "unscoped" takes the ops under none. The window
and the step count are `reduce`'s: the harness's host spans, and its
`step` spans.

A phase the traced programs declare (an op of their HLO carries the name)
but the window never ran reads 0; one they do not declare (a program older
than the phase) reads nothing.

A traced run keeps its trace with `--dump-trace`; this reads it:

    python3 benchmarks/chip/run.py --workload skiplist-mixed --seed 1 \\
        --seconds 30 --trace 1 --dump-trace chiprun_out/sm
    python3 benchmarks/chip/bench_scopes.py chiprun_out/sm.xplane.pb

and prints, as JSON, each phase's ms per step on the slowest chip.
"""
from __future__ import annotations

import json
import os
import sys
import tempfile
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import bench_trace  # noqa: E402
from bench_harness import SPANS  # noqa: E402

UNSCOPED = "unscoped"


def obs_scopes(scope: str) -> set:
    """The `obs.*` phase names on a scope path."""
    return {p for p in scope.split("/") if p.startswith("obs.")}


def declared(xspace: bytes) -> list:
    """The `obs.*` names that the HLO kept in a trace declares, run or
    not."""
    return sorted({p for names in bench_trace.hlo_programs(xspace).values()
                   for op_name in names.values()
                   for p in obs_scopes(op_name)})


def scope_step_ns(ex: dict, spans=SPANS, scopes=()) -> tuple:
    """(steps, {chip: {phase: self ns per step}}) for an `extract` dict;
    `scopes` are the declared phases, each read 0 where no op ran."""
    host = [(n, s, s + d) for n, s, d in ex["host"] if n in spans]
    if not host:
        raise ValueError("the trace holds none of the harness's host spans")
    w0 = min(s for _, s, _ in host)
    w1 = max(e for _, _, e in host)
    steps = sum(1 for n, _, _ in host if n == "step")
    out = {}
    for chip, ops in sorted(ex["chips"].items()):
        _, own = bench_trace.self_times(ops, w0, w1)
        by_scope = dict.fromkeys([*scopes, UNSCOPED], 0.0)
        for i, t in own.items():
            for sc in obs_scopes(ops[i][3]) or (UNSCOPED,):
                by_scope[sc] = by_scope.get(sc, 0.0) + t
        out[chip] = ({k: v / steps for k, v in by_scope.items()}
                     if steps else {})
    return steps, out


def scope_ms(per_chip: dict, scope: str):
    """A phase's ms per step on the slowest chip; None where no chip knows
    the phase (or the window held no step)."""
    per = [c[scope] for c in per_chip.values() if scope in c]
    return max(per) * 1e-6 if per else None


def read(xplane_path) -> dict:
    """Phase -> ms per step on the slowest chip, from one `.xplane.pb`."""
    raw = Path(xplane_path).read_bytes()
    with tempfile.TemporaryDirectory() as d:
        os.symlink(os.path.abspath(xplane_path),
                   os.path.join(d, "t.xplane.pb"))
        ex = bench_trace.extract(d, SPANS)
    _, per_chip = scope_step_ns(ex, SPANS, declared(raw))
    names = sorted({k for c in per_chip.values() for k in c})
    return {k: scope_ms(per_chip, k) for k in names}


if __name__ == "__main__":
    if len(sys.argv) != 2:
        sys.exit("usage: bench_scopes.py <trace>.xplane.pb")
    print(json.dumps(read(sys.argv[1]), indent=1))
