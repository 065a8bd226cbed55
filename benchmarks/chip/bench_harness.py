"""One run of one cell: set-up, the measured window, the check, the metrics.

`run_cell` is what `run.py` calls. Tests call it too, on the CPU at tiny
sizes, with the chip check off (`devices=`), a size override (`sizes=`),
a bounded window (`max_plans=`) or another system in the program's place
(`system=`).

The loop is closed: every client has one op outstanding, a plan holds one
op per client, and a plan is issued when the previous plan's results are on
the host. An op's latency runs from its issue to its result on the host, so
it counts the transfer in, the step and the fetch; the next plan is drawn
while the device runs the step, since what a plan holds depends only on the
seed. Nothing compiles in the window: the load and warm-up plans run the
same compiled step on the same shapes first.
"""
from __future__ import annotations

import importlib.util
import json
import os
import shutil
import sys
import time
from contextlib import nullcontext
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))

import bench_reference as ref  # noqa: E402
import bench_traffic as traffic  # noqa: E402
from bench_system import EngineSystem, cache_dir, import_program  # noqa: E402,E501

TRACE_SECONDS = 4.0          # the traced stretch at the start of the window
TRACE_DIR = ROOT / ".bench_trace"
TAIL = 0.95
# the host's own spans in the window, put on the profiler's clock
SPANS = ("plan_build", "device_put", "step", "result_fetch")


class NoChip(RuntimeError):
    """JAX finds no accelerator, or fewer chips than the cell asks for."""


def load_benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def find_cell(bench: dict, name: str):
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"cells: {sorted(cells)}")
    cell = cells[name]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cfg = json.loads((ROOT / cfg_entry["file"]).read_text())
    return cell, cfg, traffic.load_mix(cell["traffic"])


def metric_reader(name: str):
    """`metrics/<name>.py`'s `read(ctx)`: the per-layer metric's reducer."""
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"chipbench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def chip_devices(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX sees no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs[:chips]


def weighted_quantile(values, weights, q: float) -> float:
    """Nearest-rank quantile of `values` where value i stands for
    `weights[i]` ops (all ops of a plan share its latency)."""
    order = np.argsort(values, kind="stable")
    cum = np.cumsum(np.asarray(weights, np.int64)[order])
    i = int(np.searchsorted(cum, q * cum[-1], side="left"))
    return float(np.asarray(values)[order][i])


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t_process: float | None = None, devices=None, sizes=None,
             system=None, max_plans: int | None = None,
             dump_trace: str | None = None) -> dict:
    t_process = time.perf_counter() if t_process is None else t_process
    bench = load_benchmark()
    cell, cfg, mix = find_cell(bench, name)
    cfg = {**cfg, **(sizes or {})}
    chips = cell["chips"]
    phases = {}
    mark = lambda what: phases.setdefault(  # noqa: E731
        what, time.perf_counter() - t_process)
    import jax
    if devices is None:
        devices = chip_devices(chips)
        # after the chip check, so that a run with no chip writes nothing,
        # and before the program's import, whose first compile fixes the
        # cache directory (to $JAX_COMPILATION_CACHE_DIR where that is set)
        jax.config.update("jax_compilation_cache_dir", cache_dir())
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    mark("devices")
    import_program()
    mark("imports")
    dev0 = devices[0]

    lanes = cfg["lanes_per_plan"] * chips
    gen = traffic.Generator(mix, seed, lanes,
                            cfg["records_per_shard"] * chips)
    mark("traffic")
    if system is None:
        system = EngineSystem(cfg, devices[:chips])
    mark("engine")
    plans, results = [], []

    def run_plan(p):
        out = system.step(system.put(*p))
        res, ok, dropped = system.fetch(out)
        plans.append(p)
        results.append((ok, res))
        return ok, dropped

    set_up_dropped = 0
    for p in gen.load_plans():
        set_up_dropped += run_plan(p)[1]
        mark("first_plan")
    mark("load")
    for _ in range(mix["warmup_plans"]):
        set_up_dropped += run_plan(gen.next_plan())[1]
    n_setup = len(plans)
    counters0 = system.counters()
    mark("warmup")

    if trace:
        from jax.profiler import TraceAnnotation
        span = TraceAnnotation
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # the harness's spans, not every call
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    else:
        span = lambda _name: nullcontext()  # noqa: E731
    tracing = trace
    traced_plans = 0

    lat, dropped_w, refused_w = [], 0, 0
    load0, cpu0 = os.getloadavg()[0], time.process_time()
    t_start = time.perf_counter()
    setup_s = t_start - t_process
    issue = t_start
    pending = gen.next_plan()
    host = []      # per plan: seconds in put, step dispatch, build, fetch
    while True:
        t0 = time.perf_counter()
        with span("device_put"):
            args = system.put(*pending)
        t1 = time.perf_counter()
        with span("step"):
            out = system.step(args)
        t2 = time.perf_counter()
        with span("plan_build"):
            nxt = gen.next_plan()
        t3 = time.perf_counter()
        with span("result_fetch"):
            res, ok, dropped = system.fetch(out)
        done = time.perf_counter()
        host.append((t1 - t0, t2 - t1, t3 - t2, done - t3))
        del out, args
        lat.append(done - issue)
        plans.append(pending)
        results.append((ok, res))
        dropped_w += dropped
        refused_w += int(np.count_nonzero(
            (pending[0] == traffic.OP_INSERT) & ~ok))
        if tracing:
            traced_plans += 1
            if done - t_start >= min(TRACE_SECONDS, seconds):
                jax.profiler.stop_trace()
                tracing = False
        if done - t_start >= seconds or len(lat) == max_plans:
            break
        issue, pending = done, nxt
    t_end = done
    # the host around the window: a run whose plans all ran slow on the
    # host can be told from a busy neighbour by these
    host_load = {"cpus": len(os.sched_getaffinity(0)),
                 "loadavg_1m": [load0, os.getloadavg()[0]],
                 "process_cpu_s": time.process_time() - cpu0}
    if tracing:
        jax.profiler.stop_trace()

    counters1 = system.counters()
    mem = system.memory()
    shard_sizes = system.shard_sizes()
    system.release()

    # -- the check: every lane of every plan, load and warm-up included ----
    model = ref.DictModel()
    wrong, bad_plans = ref.lanes_wrong(model, plans, results)
    want_sizes = model.shard_sizes(len(shard_sizes))
    checks = {
        "lanes_wrong": (wrong, 0),
        "dropped_lanes": (set_up_dropped + dropped_w, 0),
        "shard_size_gap": (int(np.abs(shard_sizes - want_sizes).sum()), 0),
    }
    if counters1 is not None:
        checks["counter_gap"] = (sum(abs(counters1[k] - model.counts[k])
                                     for k in ref.COUNTERS), 0)
    correct = all(v <= lim for v, lim in checks.values())

    n_plans = len(lat)
    attempted = n_plans * lanes
    failed = dropped_w + refused_w
    live = len(model.d)
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem["peak_bytes"]}
    out = {"correct": bool(correct), "attempted": attempted,
           "failed": failed}
    info = {"plans": n_plans, "setup_plans": n_setup, "bad_plans": bad_plans,
            "live_records": live, "shard_sizes": shard_sizes.tolist(),
            "window_s": t_end - t_start, "traced_plans": traced_plans,
            "bytes_in_use": mem["bytes_in_use"], "setup": phases,
            "host": host_load}
    if not trace:
        out["metrics"] = {
            "ops_per_s": {"value": (attempted - failed) / (t_end - t_start),
                          "unit": "ops/s"},
            "op_latency_p95_ms": {
                "value": 1e3 * weighted_quantile(lat, [lanes] * n_plans,
                                                 TAIL),
                "unit": "ms"},
            "device_bytes_per_record": {"value": mem["array_bytes"] / live,
                                        "unit": "B/record"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }
        info["latency_p50_ms"] = 1e3 * float(np.median(lat))
        # a stall: plans far slower than the median, and the slowest
        info["latency_max_ms"] = 1e3 * float(np.max(lat))
        info["plans_over_1.5x_p50"] = int(np.count_nonzero(
            np.asarray(lat) > 1.5 * np.median(lat)))
        info["host_p50_ms"] = dict(zip(
            ("put", "dispatch", "build", "fetch"),
            (1e3 * np.median(np.asarray(host), axis=0)).tolist()))
    else:
        import bench_trace
        if dump_trace:
            bench_trace.dump_raw(TRACE_DIR, dump_trace)
        reduced = bench_trace.reduce(bench_trace.extract(TRACE_DIR, SPANS),
                                     SPANS)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        traced = plans[n_setup:n_setup + traced_plans]
        ctx = {
            "cell": cell, "config": cfg, "trace": reduced,
            "find_lanes_per_step": float(np.mean(
                [np.count_nonzero(p[0] == traffic.OP_FIND) for p in traced])),
            "peaks": bench_trace.peaks(dev0.device_kind),
            "counters": (None if counters1 is None else
                         {k: counters1[k] - counters0[k] for k in counters1}),
        }
        metrics = {}
        for m in bench["per_layer"]:
            if name not in m.get("workloads", [name]):
                continue
            v = metric_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        out["metrics"] = metrics
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        out["breakdown"] = reduced["breakdown"]
        info["trace_steps"] = reduced["steps"]
    out["device"] = device
    out["info"] = info
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in checks.items()}
    return out
