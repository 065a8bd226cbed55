"""The per-phase split of device time (`bench_scopes.py`), without a chip:
on a hand-made trace with nested phases whose answers are worked out below,
on two traces recorded on a TPU v5e (`testdata/trace_v5e_skiplist.json.gz`,
from a program that named no write pass, and
`testdata/trace_v5e_skiplist_phases.json.gz`: `bench_trace.extract` of the
whole traced stretch, 4 steps, of a 1-s `skiplist-mixed` run at 2^19, plus
the `obs.*` names its HLO declares under `scopes`), and on a hand-made
`.xplane.pb`."""
import gzip
import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import bench_harness  # noqa: E402
import bench_scopes  # noqa: E402
import bench_trace  # noqa: E402
from test_chipbench_trace import XSPACE, _hlo_proto  # noqa: E402

# nested phases; each op's time counts for every `obs.*` name on its path
P = "jit(counted)"
SCOPED = {
    "host": [["step", 0, 100], ["step", 100, 100]],
    "scopes": ["obs.demote", "obs.find", "obs.insert", "obs.merge",
               "obs.range_delete", "obs.rebuild", "obs.reclaim",
               "obs.tombstone"],
    "chips": {
        "/device:TPU:0": [
            ["fusion.1", 10, 20, f"{P}/obs.insert/obs.demote/obs.merge/sort"],
            ["fusion.2", 40, 10, f"{P}/obs.demote/obs.rebuild/add"],
            ["conditional.3", 60, 50, f"{P}/obs.range_delete"],   # holds 4, 5
            ["fusion.4", 65, 20, f"{P}/obs.range_delete/obs.reclaim/scatter"],
            ["fusion.5", 90, 15,
             f"{P}/obs.range_delete/obs.reclaim/obs.rebuild/add"],
            ["fusion.6", 120, 30, f"{P}/gather"],
            ["copy-done.7", 160, 5, ""],
            ["fusion.8", 170, 10, f"{P}/obs.find/gather"]],
        "/device:TPU:1": [
            ["fusion.1", 10, 30, f"{P}/obs.demote/obs.merge/sort"]]},
}


def _split(ex, spans=("step",)):
    return bench_scopes.scope_step_ns(ex, spans, ex.get("scopes", ()))


def test_scope_times_on_hand_made_trace():
    steps, per_chip = _split(SCOPED)
    assert steps == 2
    # per step, over 2 steps: the conditional's self time is 50 - 20 - 15
    assert per_chip["/device:TPU:0"] == {
        "obs.insert": 10, "obs.demote": 15, "obs.merge": 10,
        "obs.rebuild": 12.5, "obs.range_delete": 25, "obs.reclaim": 17.5,
        "obs.find": 5, "obs.tombstone": 0, "unscoped": 17.5}
    chip1 = per_chip["/device:TPU:1"]
    assert chip1["obs.merge"] == 15 and chip1["obs.rebuild"] == 0


def test_slowest_chip_and_missing_phases():
    _, per_chip = _split(SCOPED)
    ms = lambda s: bench_scopes.scope_ms(per_chip, s)  # noqa: E731
    # ms per step on the slowest chip; the merge's is chip 1's
    assert ms("obs.merge") == pytest.approx(15e-6)
    assert ms("obs.rebuild") == pytest.approx(12.5e-6)
    assert ms("obs.range_delete") == pytest.approx(25e-6)
    assert ms("obs.reclaim") == pytest.approx(17.5e-6)
    assert ms(bench_scopes.UNSCOPED) == pytest.approx(17.5e-6)
    # declared but never run: 0; not declared (an older program): nothing
    assert ms("obs.tombstone") == 0.0
    older = {k: v for k, v in SCOPED.items() if k != "scopes"}
    _, older_chips = _split(older)
    assert bench_scopes.scope_ms(older_chips, "obs.tombstone") is None
    assert bench_scopes.scope_ms(older_chips, "obs.merge") == \
        pytest.approx(15e-6)
    no_steps = {**SCOPED, "host": [["device_put", 0, 10]]}
    steps, empty = _split(no_steps, ("device_put", "step"))
    assert steps == 0 and bench_scopes.scope_ms(empty, "obs.merge") is None


def _recorded(name):
    with gzip.open(HERE / "testdata" / name) as f:
        return json.load(f)


def test_recorded_trace_of_a_program_without_write_phases():
    ex = _recorded("trace_v5e_skiplist.json.gz")
    steps, per_chip = bench_scopes.scope_step_ns(ex)
    (scopes,) = per_chip.values()
    assert steps == 3
    assert "obs.merge" not in scopes
    # the write path ran under no phase name: most of the step's time
    other = bench_trace.reduce(ex)["per_chip_step_ns"]
    (chip,) = other.values()
    assert scopes["unscoped"] > 0.9 * chip["other"]
    assert scopes["unscoped"] > scopes["obs.route"] > 0


# what the 1-s run that recorded it reported (TPU v5 lite, seed 3100000311)
PHASES_RUN = {"obs.merge": 193.76317874999998,
              "obs.rebuild": 7.52280925,
              "obs.range_delete": 41.336391,
              "unscoped": 0.29783575,
              "nonprobe_device_ms": 250.02670625,
              "probe_device_ms": 8.98033925,
              "device_idle_share": 1.936208469271783}


@pytest.mark.parametrize("metric", sorted(PHASES_RUN))
def test_recorded_v5e_trace_with_phases(metric):
    ex = _recorded("trace_v5e_skiplist_phases.json.gz")
    if metric in ("obs.merge", "obs.rebuild", "obs.range_delete",
                  "unscoped"):
        steps, per_chip = _split(ex, bench_harness.SPANS)
        assert steps == 4
        got = bench_scopes.scope_ms(per_chip, metric)
    else:   # the accepted readers, on the same trace
        trace = bench_trace.reduce(ex, bench_harness.SPANS)
        got = bench_harness.metric_reader(metric)({"trace": trace})
    assert got == PHASES_RUN[metric]


def test_recorded_phases_add_up():
    # the write passes, routing and what no phase names make up the
    # non-probe time, less the one fusion XLA gave both a tombstone and a
    # merge path (it counts for each)
    ex = _recorded("trace_v5e_skiplist_phases.json.gz")
    steps, per_chip = _split(ex, bench_harness.SPANS)
    (chip, scopes), = per_chip.items()
    other = bench_trace.reduce(ex)["per_chip_step_ns"][chip]["other"]
    both = {"obs.tombstone", "obs.merge"}
    shared = sum(d for _, _, d, sc in ex["chips"][chip]
                 if both <= bench_scopes.obs_scopes(sc)) / steps
    named = sum(scopes[k] for k in ("obs.merge", "obs.rebuild",
                                    "obs.range_delete", "obs.tombstone",
                                    "obs.route", "unscoped"))
    assert named - shared == pytest.approx(other, rel=1e-3)
    assert scopes["obs.reclaim"] == 0           # declared, never ran
    assert scopes["unscoped"] < 0.05 * other


def test_read_an_xplane(tmp_path):
    from jax.profiler import ProfileData
    hlo = _hlo_proto({"fusion.1": "jit(counted)/obs.merge/sort",
                      "scatter.2": "jit(counted)/scatter",
                      "add.3": "jit(counted)/obs.reclaim/add"})
    text = XSPACE % "".join("\\%03o" % b for b in hlo)
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    assert bench_scopes.declared(path.read_bytes()) == ["obs.merge",
                                                        "obs.reclaim"]
    # one step; add.3 is declared but never ran
    assert bench_scopes.read(path) == pytest.approx(
        {"obs.merge": 20e-6, "obs.reclaim": 0.0, "unscoped": 30e-6})
