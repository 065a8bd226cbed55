"""The trace reduction, without a chip: on a hand-made trace whose answers
are worked out below, on a short trace recorded on a TPU v5e
(`testdata/trace_v5e_skiplist.json.gz`: `bench_trace.extract` of a
`skiplist-mixed` trace, cut to its first 3 steps, times from its start),
and `extract` itself on a hand-made `.xplane.pb`."""
import gzip
import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import bench_trace  # noqa: E402

STEP = "jit(wrapped)/jit(main)/obs.step"
HAND = {
    "host": [["device_put", 0, 10], ["step", 10, 2], ["plan_build", 12, 8],
             ["result_fetch", 20, 80], ["device_put", 100, 10],
             ["step", 110, 2], ["plan_build", 112, 8],
             ["result_fetch", 120, 80], ["unrelated", 0, 500]],
    "chips": {"/device:TPU:0": [
        ["fusion.1", 15, 20, f"{STEP}/obs.find/gather"],
        ["while.2", 40, 35, f"{STEP}/scatter"],      # holds the next two
        ["fusion.4", 45, 10, f"{STEP}/obs.insert/sort"],
        ["all-to-all.3", 60, 10, f"{STEP}/obs.route"],
        ["fusion.1", 130, 20, f"{STEP}/obs.find/gather"],
        ["fusion.2", 160, 30, f"{STEP}/scatter"],
        ["fusion.9", 300, 5, f"{STEP}/scatter"],
    ]},
}


def test_hand_made_trace():
    r = bench_trace.reduce(HAND)
    ns = 1e-9
    assert r["steps"] == 2
    assert r["window_s"] == pytest.approx(200 * ns)
    # union of [15,35] [40,75] [130,150] [160,190]; fusion.9 is outside
    assert r["busy_s"] == pytest.approx(105 * ns)
    chip = r["per_chip_step_ns"]["/device:TPU:0"]
    assert chip["probe"] == 20 and chip["collective"] == 5
    # while.2's self time (35 - 10 - 10) + fusion.4 + fusion.2, per step
    assert chip["other"] == (15 + 10 + 30) / 2
    gaps = dict(r["breakdown"]["idle_gaps"])
    # [0,15] lies mostly under device_put; the other gaps under a fetch
    assert gaps == pytest.approx({"device_put": 15 * ns,
                                  "result_fetch": 80 * ns})
    ops = dict(r["breakdown"]["device_ops"])
    assert ops["obs.find:fusion"] == pytest.approx(40 * ns)
    assert ops["obs.route:all-to-all"] == pytest.approx(10 * ns)


def test_metric_readers_on_hand_made_trace():
    sys.path.insert(0, str(HERE / "metrics"))
    import device_idle_share
    import probe_device_ms
    import probe_roofline
    ctx = {"trace": bench_trace.reduce(HAND), "find_lanes_per_step": 1000,
           "peaks": bench_trace.peaks("TPU v5 lite")}
    assert device_idle_share.read(ctx) == pytest.approx(47.5)
    assert probe_device_ms.read(ctx) == pytest.approx(20e-6)
    least = 1000 * 32 / 819e9
    assert probe_roofline.read(ctx) == pytest.approx(100 * least / 20e-9)


def test_nested_ops_count_once():
    ops = [["while.1", 0, 100, "a/obs.find"],       # spans its body
           ["fusion.2", 10, 30, "a/obs.find/body"],
           ["fusion.3", 50, 40, "a/scatter"],
           ["fusion.4", 120, 10, "a/scatter"]]
    ivs, own = bench_trace.self_times(ops, 0, 200)
    assert own == {0: 30, 1: 30, 2: 40, 3: 10}
    assert bench_trace._union(ivs) == sum(own.values())


@pytest.mark.parametrize("name", ["all-to-all.3", "all_to_all.12",
                                  "all-to-all-start.1", "all_to_all_done.4",
                                  "all-reduce.2"])
def test_collectives_are_classified(name):
    assert bench_trace.classify(name, "a/obs.route") == "collective"
    assert bench_trace.classify("fusion.3", "a/obs.route") == "other"


def test_no_host_span_is_an_error():
    with pytest.raises(ValueError):
        bench_trace.reduce({"host": [], "chips": HAND["chips"]})


def test_recorded_v5e_trace():
    with gzip.open(HERE / "testdata" / "trace_v5e_skiplist.json.gz") as f:
        ex = json.load(f)
    r = bench_trace.reduce(ex)
    assert r["steps"] == 3
    assert 0 < r["busy_s"] < r["window_s"]
    (chip,) = r["per_chip_step_ns"].values()
    assert chip["probe"] > 0 and chip["other"] > 0
    assert chip["collective"] == 0          # one chip: nothing to exchange
    # self times of nested ops (a while's body) add up to the busy time
    device_total = chip["probe"] + chip["other"]
    assert device_total * r["steps"] == pytest.approx(chip["busy_ns"],
                                                      rel=1e-9)
    assert 5.9e6 < chip["probe"] < 6.1e6      # ns per step, as recorded
    assert 45e6 < chip["other"] < 46e6
    assert len(r["breakdown"]["device_ops"]) == 10


def _ld(field: int, payload: bytes) -> bytes:
    """A length-delimited protobuf field."""
    n, out = len(payload), bytearray([field << 3 | 2])
    while True:
        out.append(n & 0x7F | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out) + payload


def _hlo_proto(op_names: dict) -> bytes:
    """An HloProto whose one computation holds these instructions, each
    with its `op_name` metadata."""
    comp = b"".join(_ld(2, _ld(1, n.encode()) + _ld(7, _ld(2, o.encode())))
                    for n, o in op_names.items())
    return _ld(1, _ld(3, comp))


XSPACE = """
planes { name: "/host:metadata"
  event_metadata { key: 1 value { id: 1 name: "jit_step(7)"
    stats { metadata_id: 1 bytes_value: "%s" } } }
  stat_metadata { key: 1 value { id: 1 name: "hlo_proto" } } }
planes { name: "/device:TPU:0"
  lines { name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000 } }
  lines { name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 2 offset_ps: 10000 duration_ps: 20000 }
    events { metadata_id: 3 offset_ps: 40000 duration_ps: 30000 } }
  lines { name: "Scalar Unit" timestamp_ns: 1000
    events { metadata_id: 2 offset_ps: 0 duration_ps: 1000 } }
  event_metadata { key: 1 value { id: 1 name: "jit_step(7)" } }
  event_metadata { key: 2 value { id: 2 name: "%%fusion.1 = u64[8] fusion()" } }
  event_metadata { key: 3 value { id: 3 name: "%%scatter.2 = u64[8] scatter()" } } }
planes { name: "/host:CPU"
  lines { name: "python3" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 5000 }
    events { metadata_id: 2 offset_ps: 5000 duration_ps: 95000 }
    events { metadata_id: 3 offset_ps: 0 duration_ps: 100000 } }
  event_metadata { key: 1 value { id: 1 name: "device_put" } }
  event_metadata { key: 2 value { id: 2 name: "step" } }
  event_metadata { key: 3 value { id: 3 name: "unrelated" } } }
"""


def test_extract_reads_an_xplane(tmp_path):
    from jax.profiler import ProfileData
    hlo = _hlo_proto({"fusion.1": f"{STEP}/obs.find/gather",
                      "scatter.2": f"{STEP}/scatter"})
    text = XSPACE % "".join("\\%03o" % b for b in hlo)
    (tmp_path / "t.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(text))
    ex = bench_trace.extract(tmp_path, ("device_put", "step"))
    assert ex["host"] == [["device_put", 1000, 5], ["step", 1005, 95]]
    assert ex["chips"] == {"/device:TPU:0": [
        ["fusion.1", 1010, 20, f"{STEP}/obs.find/gather"],
        ["scatter.2", 1040, 30, f"{STEP}/scatter"]]}
    r = bench_trace.reduce(ex, ("device_put", "step"))
    assert r["per_chip_step_ns"]["/device:TPU:0"]["probe"] == 20
