"""Reduce a JAX profiler trace of a benchmark window to what the per-layer
metrics read.

The trace is the ``.xplane.pb`` file that ``jax.profiler`` writes; it is read
with ``jax.profiler.ProfileData`` and nothing else. Each TPU chip is a plane
named ``/device:TPU:<n>`` whose ``XLA Ops`` line holds one event per device
operation, named by the instruction's HLO text (``%fusion.150 = (f32[],
f32[3,3,3,32]) fusion(...), kind=kOutput, calls=%fused_computation.355``); no
event carries a category, so which ops are convolutions is read from the
compiled module (``conv_ops``). The host planes hold the benchmark's own spans
(``jax.profiler.TraceAnnotation``), on the same clock.

The window runs from the first ``bench.batch_source`` span to the start of the
span that follows the window's last step. Within it, per chip:

- busy: the union of the intervals in which an operation ran;
- per-operation time, summed over the op's events;
- collective time: operations whose opcode is a collective, and the part of
  their union during which no other operation ran (exposed);
- idle gaps: the window less the busy union, each labelled by the benchmark
  span the host was in for most of the gap (``bench.batch_source``,
  ``bench.step_call``) or else ``driver loop``: the driver's read-back of the
  step's metrics and its bookkeeping.

Chip numbers are averaged over the chips.

This module is named ``devtrace`` and not ``trace``, which would hide the
standard library's module of that name.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import gzip
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
SOURCE_SPAN, STEP_SPAN = "bench.batch_source", "bench.step_call"
HOST_SPANS = (SOURCE_SPAN, STEP_SPAN)
OTHER_HOST = "driver loop"
COLLECTIVE = re.compile(
    r"(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all|"
    r"collective-broadcast|send|recv)(-start|-done)?$"
)
INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
CALLS = re.compile(r"calls=%?([\w.\-]+)")
TARGET = re.compile(r'custom_call_target="([^"]+)"')
COMPUTATION = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")


def is_collective(opcode: str) -> bool:
    return COLLECTIVE.match(opcode) is not None


def parse_instr(text: str) -> tuple[str, str, str, str]:
    """``(name, result shape, opcode, rest)`` of one HLO instruction's text,
    as the trace names a device op and as the compiled module lists it."""
    m = INSTR.match(text)
    if not m:
        return text, "", "", ""
    name, rhs = m.groups()
    end, depth = 0, 0
    for end, ch in enumerate(rhs):
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        elif ch == " " and depth == 0:
            break
    shape, rest = rhs[:end], rhs[end:].lstrip()
    return name, shape, rest.split("(", 1)[0].strip(), rest


def conv_ops(hlo_text: str) -> set[str]:
    """Names of the instructions of a compiled module that carry conv MACs: the
    convolutions, the fusions whose computation holds one, and the Pallas
    kernels' custom calls (target ``tpu_custom_call``)."""
    holds, calls, instrs = set(), collections.defaultdict(set), []
    comp = None
    for line in hlo_text.splitlines():
        m = COMPUTATION.match(line)
        if m and " = " not in line:
            comp = m.group(1)
            continue
        name, _, opcode, rest = parse_instr(line)
        if not opcode:
            continue
        instrs.append((name, opcode, rest))
        c = CALLS.search(rest)
        if c:
            calls[comp].add(c.group(1))
        if opcode == "convolution":
            holds.add(comp)
    grown = True
    while grown:
        grown = False
        for comp_name, callees in calls.items():
            if comp_name not in holds and callees & holds:
                holds.add(comp_name)
                grown = True
    out = set()
    for name, opcode, rest in instrs:
        c, t = CALLS.search(rest), TARGET.search(rest)
        if (opcode == "convolution" or (opcode == "fusion" and c and c.group(1) in holds)
                or (opcode == "custom-call" and t and t.group(1) == "tpu_custom_call")):
            out.add(name)
    return out


@dataclasses.dataclass
class Op:
    name: str
    start: float        # ns
    end: float
    opcode: str
    shape: str = ""


@dataclasses.dataclass
class Summary:
    chips: int
    window: tuple[float, float]        # ns, on the trace's clock
    busy_s: float                      # per chip, averaged
    window_s: float
    op_s: dict                         # name -> seconds per chip, averaged
    op_kind: dict                      # name -> "opcode shape"
    collective_s: float                # per chip, averaged
    collective_exposed_s: float
    gaps: list                         # (label, seconds), every gap of every chip

    def seconds_of(self, names) -> float:
        return sum(s for name, s in self.op_s.items() if name in names)

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:top]
        label = lambda n: f"{n} {self.op_kind[n]}"[:200]
        gaps = sorted(self.gaps, key=lambda g: -g[1])[:top]
        return {"device_ops": [[label(n), s] for n, s in ops],
                "idle_gaps": [[lab, s] for lab, s in gaps]}


def union(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def length(intervals) -> float:
    return sum(b - a for a, b in intervals)


def intersect(xs, ys) -> list[tuple[float, float]]:
    """Intersection of two sorted, disjoint interval lists."""
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if a < b:
            out.append((a, b))
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def complement(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    out, cur = [], lo
    for a, b in intervals:
        if a > cur:
            out.append((cur, min(a, hi)))
        cur = max(cur, b)
    if cur < hi:
        out.append((cur, hi))
    return [(a, b) for a, b in out if b > a]


def clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def find_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(f"{len(files)} xplane files under {trace_dir}")
    return files[0]


def read(path: str):
    """Device ops per chip and the benchmark's host spans, from one file
    (``.xplane.pb``, or the same gzipped)."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    devices: dict[int, list[Op]] = {}
    spans: dict[str, list] = collections.defaultdict(list)
    # every step repeats the same instructions: parse each name once
    parsed: dict[str, tuple] = {}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    if e.name not in parsed:
                        parsed[e.name] = parse_instr(e.name)
                    name, shape, opcode, _ = parsed[e.name]
                    ops.append(Op(name, e.start_ns, e.start_ns + e.duration_ns, opcode, shape))
            devices[int(m.group(1))] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in HOST_SPANS:
                        spans[e.name].append((e.start_ns, e.start_ns + e.duration_ns))
    return devices, {k: sorted(v) for k, v in spans.items()}


def reduce_dir(trace_dir: str, chips: int, steps: int) -> Summary:
    return reduce(*read(find_xplane(trace_dir)), chips=chips, steps=steps)


def reduce(devices: dict, spans: dict, *, chips: int, steps: int) -> Summary:
    """The window of ``steps`` steps and what each of the first ``chips``
    chips did in it."""
    sources = spans.get(SOURCE_SPAN, [])
    if len(sources) < steps + 1:
        raise ValueError(f"{len(sources)} batch-source spans for a window of {steps} steps")
    lo, hi = sources[0][0], sources[steps][0]
    ids = sorted(devices)[:chips]
    if len(ids) < chips or not any(devices[i] for i in ids):
        raise ValueError(f"trace holds device ops for {len(ids)} of {chips} chips")
    host = HostSpans(spans)
    op_s: dict[str, float] = collections.defaultdict(float)
    kind: dict[str, str] = {}
    busy = coll = exposed = 0.0
    gaps = []
    for i in ids:
        ops = [o for o in devices[i] if o.end > lo and o.start < hi]
        for o in ops:
            a, b = max(o.start, lo), min(o.end, hi)
            op_s[o.name] += (b - a) / chips
            kind[o.name] = f"{o.opcode} {o.shape}"
        busy_u = union(clip([(o.start, o.end) for o in ops], lo, hi))
        coll_u = union(clip([(o.start, o.end) for o in ops if is_collective(o.opcode)], lo, hi))
        comp_u = union(clip([(o.start, o.end) for o in ops if not is_collective(o.opcode)], lo, hi))
        busy += length(busy_u)
        coll += length(coll_u)
        exposed += length(coll_u) - length(intersect(coll_u, comp_u))
        for a, b in complement(busy_u, lo, hi):
            gaps.append((host.label(a, b), (b - a) * 1e-9))
    ns = 1e-9 / chips
    return Summary(
        chips=chips, window=(lo, hi), busy_s=busy * ns, window_s=(hi - lo) * 1e-9,
        op_s={k: v * 1e-9 for k, v in op_s.items()}, op_kind=kind,
        collective_s=coll * ns, collective_exposed_s=exposed * ns, gaps=gaps,
    )


class HostSpans:
    """The benchmark's host spans, sorted by start, to label idle gaps."""

    def __init__(self, spans: dict):
        self.spans = sorted((a, b, name) for name, iv in spans.items() for a, b in iv)
        self.starts = [a for a, _, _ in self.spans]
        self.longest = max((b - a for a, b, _ in self.spans), default=0)

    def label(self, a: float, b: float) -> str:
        """The span the host was in for at least half of ``[a, b]``, else
        ``OTHER_HOST``. Only spans that start within the longest span's
        length before ``a`` can overlap the gap."""
        best, label = 0.0, OTHER_HOST
        lo = bisect.bisect_left(self.starts, a - self.longest)
        hi = bisect.bisect_left(self.starts, b)
        for s, e, name in self.spans[lo:hi]:
            ov = min(b, e) - max(a, s)
            if ov > best:
                best, label = ov, name
        return label if 2 * best >= b - a else OTHER_HOST
