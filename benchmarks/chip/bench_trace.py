"""From a `jax.profiler` trace to the numbers the per-layer metrics read.

`extract` reads the `.xplane.pb` that `jax.profiler` wrote, through
`jax.profiler.ProfileData`, into a plain dict: per chip the device's ops
(HLO name, start, duration, and the `named_scope` path the program gave
them, joined from the HLO kept in the trace), and the harness's own host
spans. `reduce` works on that dict alone, so a small recorded trace
(`testdata/`) checks it without a chip:

  window    from the start of the first host span to the end of the last
  busy      the union of the device's op intervals inside the window
  probe     device time of ops under a `find` scope (`obs.span("find")`
            opens `jax.named_scope("obs.find")`)
  collective device time of the collectives (all-to-all and kin)
  other     the rest of the device time: the write path and bookkeeping

Per-step times are per chip, over the steps (host `step` spans) in the
window; readers take the slowest chip.
"""
from __future__ import annotations

import bisect
import glob
import json
import os
import re
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"
PROBE_SCOPE = "obs.find"
# HLO names of collectives ("all-to-all.3"; the TPU trace writes
# "all_to_all.3")
COLLECTIVE = re.compile(r"(all[-_]to[-_]all|all[-_]reduce|all[-_]gather|"
                        r"reduce[-_]scatter|collective[-_]permute|send|recv)")
DEVICE_OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def peaks(kind: str) -> dict:
    """The peak table's row for a `device_kind`; an unknown kind is an
    error, never a default."""
    table = json.loads(PEAKS.read_text())["kinds"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in the peak table "
                       f"{PEAKS.name}: {sorted(table)}")
    return table[kind]


def _wire(buf: bytes):
    """(field number, value) of each field of a protobuf message; a
    length-delimited value stays bytes."""
    from google.protobuf.internal.decoder import _DecodeVarint
    i, n = 0, len(buf)
    while i < n:
        key, i = _DecodeVarint(buf, i)
        kind = key & 7
        if kind == 0:
            v, i = _DecodeVarint(buf, i)
        elif kind == 2:
            size, i = _DecodeVarint(buf, i)
            v, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            v, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {kind} in a trace")
        yield key >> 3, v


def hlo_op_names(hlo_proto: bytes) -> dict:
    """Instruction name -> `op_name` metadata (the `named_scope` path) of
    an `HloProto` (hlo.proto: HloProto.hlo_module = 1; module.computations
    = 3; computation.instructions = 2; instruction.name = 1, .metadata =
    7; OpMetadata.op_name = 2)."""
    out = {}
    for f, module in _wire(hlo_proto):
        if f != 1:
            continue
        for g, comp in _wire(module):
            if g != 3:
                continue
            for h, ins in _wire(comp):
                if h != 2:
                    continue
                name, op = None, ""
                for k, v in _wire(ins):
                    if k == 1:
                        name = v.decode()
                    elif k == 7:
                        op = next((w.decode() for j, w in _wire(v) if j == 2),
                                  "")
                out[name] = op
    return out


def xplane_file(trace_dir) -> str:
    found = sorted(glob.glob(os.path.join(str(trace_dir), "**",
                                          "*.xplane.pb"), recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


HLO_NAME = re.compile(r"^%?([^\s=]+)")


def hlo_programs(xspace: bytes) -> dict:
    """Program name -> {instruction name -> `op_name`} from the HLO protos
    that the trace keeps in its `/host:metadata` plane, which
    `jax.profiler.ProfileData` does not expose (xplane.proto: XSpace.planes
    = 1; XPlane.name = 2, .event_metadata = 4, a map whose entries hold an
    XEventMetadata at 2; XEventMetadata.name = 2, .stats = 5;
    XStat.bytes_value = 6)."""
    programs = {}
    for f, plane in _wire(xspace):
        if f != 1:
            continue
        fields = list(_wire(plane))
        if next((v for k, v in fields if k == 2), b"") != b"/host:metadata":
            continue
        for k, entry in fields:
            if k != 4:
                continue
            meta = next((v for j, v in _wire(entry) if j == 2), b"")
            name, protos = "", []
            for j, v in _wire(meta):
                if j == 2:
                    name = v.decode()
                elif j == 5:
                    protos += [w for i, w in _wire(v) if i == 6]
            for proto in protos:
                programs[name] = hlo_op_names(proto)
    return programs


def extract(trace_dir, host_spans=None) -> dict:
    """Device ops of every chip and the named host spans, from the newest
    trace under `trace_dir`, read by `jax.profiler.ProfileData`. An op's
    scope is the `op_name` that the HLO of its program (kept in the
    trace's metadata plane) gives it."""
    from jax.profiler import ProfileData
    raw = Path(xplane_file(trace_dir)).read_bytes()
    programs = hlo_programs(raw)
    any_program = {}
    for names in programs.values():
        any_program.update(names)
    chips, host = {}, []
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        device = plane.name.startswith("/device:")
        if not (device or plane.name.startswith("/host:")):
            continue
        for line in plane.lines:
            if device and line.name not in (DEVICE_OPS_LINE, MODULES_LINE):
                continue
            evs = [(e.name, e.start_ns, e.duration_ns) for e in line.events]
            if not device:
                host += [list(e) for e in evs
                         if host_spans is None or e[0] in host_spans]
            elif line.name == MODULES_LINE:
                chips.setdefault(plane.name, {})["modules"] = evs
            else:
                chips.setdefault(plane.name, {})["ops"] = evs
    out = {}
    for chip, lines in chips.items():
        modules = sorted(lines.get("modules", []), key=lambda m: m[1])
        starts = [m[1] for m in modules]
        ops = []
        for name, s, d in lines.get("ops", []):
            op = HLO_NAME.match(name).group(1)
            k = bisect.bisect_right(starts, s) - 1
            names = (programs.get(modules[k][0], any_program) if k >= 0
                     else any_program)
            ops.append([op, s, d, names.get(op, any_program.get(op, ""))])
        if ops:
            out[chip] = ops
    if not out:
        raise ValueError("the trace holds no device op line "
                         f"({DEVICE_OPS_LINE!r}): no device ran an op")
    return {"chips": out, "host": host}


def dump_raw(trace_dir, out_path) -> None:
    """Keep the trace file itself beside `out_path`, to read it by hand."""
    import shutil
    shutil.copy(xplane_file(trace_dir), str(out_path) + ".xplane.pb")


def _union(intervals) -> float:
    total, end = 0.0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def classify(name: str, scope: str) -> str:
    if COLLECTIVE.match(name):
        return "collective"
    if PROBE_SCOPE in scope.split("/"):
        return "probe"
    return "other"


def op_label(name: str, scope: str) -> str:
    """A label stable across compiles: the innermost `obs.*` scope and the
    op's name without its numeric suffix."""
    obs = [p for p in scope.split("/") if p.startswith("obs.")]
    base = re.sub(r"[.\d]+$", "", name)
    return f"{obs[-1]}:{base}" if obs else base


def _host_label(spans, s: float, e: float) -> str:
    best, cover = "no host span", 0.0
    for name, hs, he in spans:
        c = min(e, he) - max(s, hs)
        if c > cover:
            best, cover = name, c
    return best


def self_times(ops, w0: float, w1: float):
    """Each op's interval clipped to the window [w0, w1], and its self
    time: its duration less that of the ops nested in it (a `while` or
    `conditional` event spans the ops of its body on the same line), so
    that self times add up to the busy time."""
    ivs = sorted((max(s, w0), min(s + d, w1), i)
                 for i, (_, s, d, _) in enumerate(ops)
                 if min(s + d, w1) > max(s, w0))
    own = {i: e - s for s, e, i in ivs}
    stack = []
    for s, e, i in sorted(ivs, key=lambda t: (t[0], -t[1])):
        while stack and stack[-1][1] <= s:
            stack.pop()
        if stack:
            own[stack[-1][2]] -= e - s
        stack.append((s, e, i))
    return [(s, e) for s, e, _ in ivs], own


def reduce(ex: dict, spans=("plan_build", "device_put", "step",
                            "result_fetch")) -> dict:
    host = [(n, s, s + d) for n, s, d in ex["host"] if n in spans]
    if not host:
        raise ValueError("the trace holds none of the harness's host spans")
    w0 = min(s for _, s, _ in host)
    w1 = max(e for _, _, e in host)
    steps = sum(1 for n, _, _ in host if n == "step")
    per_chip, busy_all, labels, gaps = {}, [], {}, {}
    for chip, ops in sorted(ex["chips"].items()):
        ivs, own = self_times(ops, w0, w1)
        cat = {"probe": 0.0, "collective": 0.0, "other": 0.0}
        for i, t in own.items():
            name, _, _, scope = ops[i]
            cat[classify(name, scope)] += t
            lab = op_label(name, scope)
            labels[lab] = labels.get(lab, 0.0) + t
        busy = _union(ivs)
        busy_all.append(busy)
        per_chip[chip] = {k: v / steps for k, v in cat.items()}
        per_chip[chip]["busy_ns"] = busy
        end = w0
        for s, e in ivs:
            if s > end:
                lab = _host_label(host, end, s)
                gaps[lab] = gaps.get(lab, 0.0) + (s - end)
            end = max(end, e)
        if w1 > end:
            lab = _host_label(host, end, w1)
            gaps[lab] = gaps.get(lab, 0.0) + (w1 - end)
    n = len(per_chip)
    top = lambda d: [[k, v / n * 1e-9] for k, v in  # noqa: E731
                     sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    return {"window_s": (w1 - w0) * 1e-9,
            "busy_s": sum(busy_all) / n * 1e-9,
            "steps": steps,
            "per_chip_step_ns": per_chip,
            "breakdown": {"device_ops": top(labels), "idle_gaps": top(gaps)}}
