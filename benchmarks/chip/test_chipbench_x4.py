"""The 4-chip cell `skiplist-x4-mixed` on the CPU, at a tiny size.

One child process with 4 CPU devices runs the cell through the harness and
`StoreEngine` over a 4-device mesh, and reads the all-to-all ops of the
compiled step: the comparison passes, every shard holds keys, and the
exchange's ops carry `obs.exchange` where the bucketing's do not. In this
process, a reference that keeps each chip's ops on that chip (the exchange
left out) fails the comparison. The two readers of the exchange are checked
on hand-made numbers.
"""
import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))

import bench_harness as H  # noqa: E402
import bench_reference as ref  # noqa: E402
from bench_system import ModelSystem  # noqa: E402

CELL = "skiplist-x4-mixed"
CHIPS = 4
TINY = dict(capacity_per_shard=1024, lanes_per_plan=64, records_per_shard=512)
SEED = 2 ** 31 + 12345
PLANS = 12

CHILD = f"""
import json, re, sys
sys.path.insert(0, {str(HERE)!r})
import jax
import numpy as np
import bench_harness as H
from bench_system import EngineSystem

devs = jax.devices()[:{CHIPS}]
_, cfg, _ = H.find_cell(H.load_benchmark(), {CELL!r})
cfg = {{**cfg, **{TINY!r}}}
system = EngineSystem(cfg, devs)
out = H.run_cell({CELL!r}, {SEED}, 60.0, False, devices=devs, sizes={TINY!r},
                 system=system, max_plans={PLANS})

eng = system.engine
n = cfg["lanes_per_plan"] * {CHIPS}
state = jax.device_put(eng.init(cfg["capacity_per_shard"]), eng.sharding)
args = [jax.device_put(np.zeros(n, t), eng.sharding)
        for t in (np.int32, np.uint64, np.uint64)]
hlo = eng._jit_step.lower(state, *args).compile().as_text()
ops = {{}}
for line in hlo.splitlines():
    m = re.match(r"\\s*\\S+ = .*? (all-to-all|sort)\\(", line)
    if m:
        name = re.search(r'op_name="([^"]*)"', line)
        ops.setdefault(m.group(1), []).append(name.group(1) if name else "")
out["op_names"] = ops
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def child():
    flags = os.environ.get("XLA_FLAGS", "")
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": f"{flags} --xla_force_host_platform_device_count="
                        f"{CHIPS}".strip()}
    p = subprocess.run([sys.executable, "-c", CHILD], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def checks(out):
    return {k: c["value"] for k, c in out["checks"].items()}


def test_x4_cell_through_engine_is_correct(child):
    assert child["correct"], child["checks"]
    c = checks(child)
    assert c["lanes_wrong"] == 0
    assert c["dropped_lanes"] == 0 and c["shard_size_gap"] == 0
    sizes = child["info"]["shard_sizes"]
    assert len(sizes) == CHIPS and min(sizes) > 0
    assert child["attempted"] == PLANS * CHIPS * TINY["lanes_per_plan"]
    assert child["failed"] == 0
    assert child["info"]["live_records"] == CHIPS * TINY["records_per_shard"]


def test_exchange_scope_holds_the_all_to_all_only(child):
    ops = child["op_names"]
    assert ops["all-to-all"], "the 4-device step holds no all-to-all"
    assert all("obs.exchange" in n for n in ops["all-to-all"]), ops
    bucket_sorts = [n for n in ops["sort"] if "obs.route" in n.split("/")]
    assert bucket_sorts, ops["sort"]
    assert not any("obs.exchange" in n for n in bucket_sorts)


class PerChipModel:
    """A reference with the exchange left out: each chip's lanes go to that
    chip's own `DictModel`, so a chip sees only the keys its own clients
    wrote."""

    def __init__(self, chips: int, lanes: int):
        self.models = [ref.DictModel() for _ in range(chips)]
        self.lanes = lanes

    def apply(self, ops, keys, vals):
        parts = [m.apply(*(a[i * self.lanes:(i + 1) * self.lanes]
                           for a in (ops, keys, vals)))
                 for i, m in enumerate(self.models)]
        return (np.concatenate([ok for ok, _ in parts]),
                np.concatenate([res for _, res in parts]))

    def shard_sizes(self, n_shards: int) -> np.ndarray:
        return np.array([len(m.d) for m in self.models])


def test_control_without_exchange_fails():
    import jax
    model = PerChipModel(CHIPS, TINY["lanes_per_plan"])
    out = H.run_cell(CELL, SEED, 60.0, False, devices=jax.devices()[:1],
                     sizes=TINY, system=ModelSystem(model, CHIPS),
                     max_plans=PLANS)
    assert not out["correct"]
    c = checks(out)
    assert c["lanes_wrong"] > 0 and c["shard_size_gap"] > 0


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        f"x4_{name}", HERE / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _ctx(collective_ns):
    bench = H.load_benchmark()
    cell, cfg, _ = H.find_cell(bench, CELL)
    per = {f"/device:TPU:{i}": {"probe": 1.0, "collective": ns, "other": 1.0}
           for i, ns in enumerate(collective_ns)}
    return {"cell": cell, "config": cfg,
            "trace": {"per_chip_step_ns": per},
            # 90% of the 16384 lanes of a plan
            "find_lanes_per_step": 0.9 * CHIPS * cfg["lanes_per_plan"]}


def test_collective_reader_takes_slowest_chip():
    read = _reader("collective_device_ms").read
    assert read(_ctx([1e6, 2.5e6, 0.5e6, 2e6])) == pytest.approx(2.5)
    assert read(_ctx([0.0] * CHIPS)) is None


def test_exchange_least_bytes_and_peak():
    m = _reader("exchange_roofline")
    # per chip 4096 lanes, 3686.4 FINDs and 409.6 writes, 3/4 owned away:
    # out 3072 x 9 B, back 2764.8 x 9 B + 307.2 x 1 B
    assert m.least_bytes(_ctx([1e6] * CHIPS)) == pytest.approx(52838.4)
    assert m.ici_bytes_per_s("TPU v5 lite") == 200e9
    with pytest.raises(KeyError):
        m.ici_bytes_per_s("TPU v99")
