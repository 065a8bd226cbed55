"""Export `repro.store.obs` trace spans as Chrome-trace / Perfetto JSON.

`obs.tracing()` records host wall-clock spans (`obs.span`: the engine
step, eager scans, the serving loop, recoveries) into a Tracer; device
phases (`obs.scope`) name HLO ops for `jax.profiler` traces and never
reach a Tracer, so the export holds real host time only.
`to_chrome_trace` converts one Tracer into the Chrome Trace Event format
(JSON object with a ``traceEvents`` list of complete "X" events), which
https://ui.perfetto.dev opens directly — see docs/observability.md for the
span taxonomy and a how-to.

Run as a CLI it produces a demo timeline from a single-device `StoreEngine`
over an observed tier stack (churn workload: inserts, deletes, finds), and
embeds the final metrics plane in the trace metadata so the counter totals
ride along with the timeline:

    python tools/trace_export.py --out trace.json
    python tools/trace_export.py --out trace.json \\
        --backend obs:tiered3/lru --steps 8 --lanes 64

CI runs exactly that and uploads ``trace.json`` as the ``perfetto-trace``
artifact, so every push has an openable timeline.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def to_chrome_trace(tracer, meta: dict | None = None) -> dict:
    """Chrome Trace Event JSON for one `obs.Tracer`.

    Every span becomes a complete event (``ph: "X"``) with microsecond
    ``ts``/``dur`` relative to the tracer's epoch, so timestamps start near
    zero and nested spans (a recovery > its replayed engine steps) stack
    in Perfetto's flame view. `meta` (e.g. the final metrics plane) lands in
    ``otherData``, the spec's free-form metadata slot."""
    events = [{
        "name": "process_name", "ph": "M", "pid": 0, "tid": 0,
        "args": {"name": "repro.store"},
    }]
    for s in tracer.spans:
        events.append({
            "name": s.name,
            "cat": "host",
            "ph": "X",
            "ts": (s.ts_ns - tracer.t0_ns) / 1e3,
            "dur": s.dur_ns / 1e3,
            "pid": 0,
            "tid": 0,
            "args": {k: (v if isinstance(v, (int, float, str, bool))
                         else str(v)) for k, v in s.args.items()},
        })
    payload = {"traceEvents": events, "displayTimeUnit": "ms"}
    if meta:
        payload["otherData"] = meta
    return payload


def record_demo_trace(backend: str = "obs:tiered3/lru", steps: int = 8,
                      lanes: int = 64, fault_step: int | None = None):
    """Run a small churn workload on a 1-device engine under `tracing()`;
    returns (tracer, metrics dict of plain ints). The spans are host
    spans: one "step" per batch (the dispatch's wall time, args carrying
    the step number and the engine's trace count). With
    `fault_step` set, the engine is wrapped in a `ResilientEngine` with a
    shard-drop at that step, so the timeline also shows a real "recover"
    span (snapshot + journal rebuild) mid-trace."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from repro.launch.mesh import make_mesh
    from repro.store import obs
    from repro.store import resilience as R
    from repro.store.engine import StoreEngine

    mesh = make_mesh((1,), ("d",), devices=jax.devices()[:1])
    eng = StoreEngine(mesh, ("d",), lanes=lanes, backend=backend)
    drive = eng
    if fault_step is not None:
        fplan = R.FaultPlan(0, [R.Fault("shard_drop", fault_step, shard=0)])
        drive = R.ResilientEngine(eng, snapshot_every=2, fault_plan=fplan)
    state = jax.device_put(eng.init(max(4 * lanes, 64), hot_bucket=4,
                                    hot_frac=8), eng.sharding)
    rng = np.random.default_rng(0)
    with obs.tracing() as tracer:
        for _ in range(steps):
            ops = jnp.asarray(rng.integers(0, 3, lanes).astype(np.int32))
            keys = jnp.asarray(
                rng.integers(1, 4 * lanes, lanes).astype(np.uint64))
            vals = jnp.asarray(
                rng.integers(1, 1 << 20, lanes).astype(np.uint64))
            state, _, _, _ = drive.step(state, ops, keys, vals)
    if fault_step is not None:
        metrics = {k: int(v) for k, v in drive.metrics(state).items()}
    else:
        metrics = {k: int(v[0]) for k, v in eng.metrics(state).items()}
    return tracer, metrics


def main(argv: list[str]) -> int:
    ap = argparse.ArgumentParser(
        description="export a demo store timeline as Perfetto JSON")
    ap.add_argument("--out", default="trace.json",
                    help="output path (default trace.json)")
    ap.add_argument("--backend", default="obs:tiered3/lru",
                    help="obs:-prefixed registry string (default "
                         "obs:tiered3/lru)")
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--lanes", type=int, default=64)
    ap.add_argument("--fault-step", type=int, default=None,
                    help="inject a shard drop at this step (wraps the "
                         "engine in a ResilientEngine) so the timeline "
                         "includes a 'recover' span")
    args = ap.parse_args(argv[1:])
    if not args.backend.startswith("obs:"):
        ap.error("--backend must be obs:-prefixed (the demo embeds the "
                 "metrics plane in the trace metadata)")

    sys.path.insert(0, os.path.join(ROOT, "src"))
    tracer, metrics = record_demo_trace(backend=args.backend,
                                        steps=args.steps, lanes=args.lanes,
                                        fault_step=args.fault_step)
    payload = to_chrome_trace(tracer, meta={"backend": args.backend,
                                            "metrics": metrics})
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"wrote {args.out} ({len(tracer.spans)} spans; open at "
          f"https://ui.perfetto.dev)")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
