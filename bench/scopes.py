"""Read the program's own spans and named scopes from a traced run.

The program names its work in two ways (``src/repro/obs.py``), and this module
reads both from what a traced run already holds:

- Scopes. Each compiled instruction's ``metadata={op_name=...}`` in
  ``result["hlo"]`` carries the ``jax.named_scope``s it was traced under:
  ``jit(train_step)/.../transpose(jvp(layer01))/pool/select_and_scatter``. An
  instruction is classified as ``(layer, scope, direction)``: the ``layerNN``
  component, the innermost known scope (``conv``, ``bn``, ``pool``, ``halo``,
  ``reshard``, ``loss``, ``grad_sum``, ``optimizer``; else ``unscoped``), and
  ``bwd`` where a component is a ``transpose(...)``, else ``fwd``. An
  instruction without an ``op_name`` takes the root of the computation it
  calls (a fusion), else its first operand's (a copy XLA inserted).
  ``devtrace.Summary.op_s`` gives each instruction's device time.
- Host spans. The driver's ``driver.*`` spans and ``arch.place_batch`` are
  read from the run's ``.xplane.pb``, with each chip's busy intervals on the
  same clock; the file is parsed once per run. A chip's idle time in the
  window is split by whether the host was inside ``driver.wait`` (it waits
  on work it has already issued, such as an input copy) or not (the host
  holds the chip back), by intersecting sorted interval lists.

A program without these spans and scopes reads as nothing: each function
returns None, and so does each metric built on it.
"""
from __future__ import annotations

import collections
import functools
import re

import devtrace

SCOPES = ("conv", "bn", "pool", "halo", "reshard", "loss", "grad_sum", "optimizer")
UNSCOPED = "unscoped"
LAYER = re.compile(r"^layer\d\d$")
WRAPPED = re.compile(r"^([\w.\-]+)\((.*)\)$")
OP_NAME = re.compile(r'op_name="([^"]*)"')
OPERAND = re.compile(r"%([\w.\-]+)")
WAIT = "driver.wait"
HOST_SPANS = ("driver.make_batch", "driver.dispatch", WAIT, "driver.metrics",
              "driver.checkpoint", "driver.restore", "driver.replan", "arch.place_batch")


def classify(op_name: str) -> tuple[str | None, str, str]:
    """``(layer, scope, "fwd" | "bwd")`` of one ``op_name``; its last
    component is the primitive and is not read."""
    layer, scope, direction = None, UNSCOPED, "fwd"
    for part in op_name.split("/")[:-1]:
        m = WRAPPED.match(part)
        while m:
            if m.group(1) == "transpose":
                direction = "bwd"
            part = m.group(2)
            m = WRAPPED.match(part)
        if LAYER.match(part):
            layer = part
        elif part in SCOPES:
            scope = part
    return layer, scope, direction


def instruction_scopes(hlo_text: str) -> dict[str, tuple[str | None, str, str]]:
    """Each instruction of a compiled module, by name, classified."""
    own, calls, first, roots, names = {}, {}, {}, {}, []
    comp = None
    for line in hlo_text.splitlines():
        m = devtrace.COMPUTATION.match(line)
        if m and " = " not in line:
            comp = m.group(1)
            continue
        name, _, opcode, rest = devtrace.parse_instr(line)
        if not opcode:
            continue
        names.append(name)
        if line.lstrip().startswith("ROOT"):
            roots[comp] = name
        n = OP_NAME.search(rest)
        if n:
            own[name] = n.group(1)
        c = devtrace.CALLS.search(rest)
        if c:
            calls[name] = c.group(1)
        args = rest.split("(", 1)[1] if "(" in rest else ""
        o = OPERAND.search(args)
        if o:
            first[name] = o.group(1)

    resolved: dict[str, str | None] = {}

    def op_name_of(name: str) -> str | None:
        if name in resolved:
            return resolved[name]
        resolved[name] = None                       # guards a cycle
        found = own.get(name)
        if found is None and calls.get(name) in roots:
            found = op_name_of(roots[calls[name]])
        if found is None and name in first:
            found = op_name_of(first[name])
        resolved[name] = found
        return found

    return {n: classify(op_name_of(n) or "") for n in names}


def seconds_by_scope(run) -> dict[tuple, float] | None:
    """Device seconds a chip spent in the window, averaged over chips, for
    each ``(layer, scope, direction)``; None where no instruction carries a
    known scope."""
    hlo = run.result.get("hlo")
    if not hlo:
        return None
    classes = _classes(hlo)
    if not any(s != UNSCOPED for _, s, _ in classes.values()):
        return None
    out: dict[tuple, float] = collections.defaultdict(float)
    for name, s in run.trace.op_s.items():
        out[classes.get(name, (None, UNSCOPED, "fwd"))] += s
    return dict(out)


@functools.lru_cache(maxsize=2)
def _classes(hlo: str):
    return instruction_scopes(hlo)


def scope_ms(run, scope: str) -> float | None:
    """Device ms per window step under ``scope``, forward and backward,
    averaged over chips; None where the program names no such scope."""
    by = seconds_by_scope(run)
    if by is None:
        return None
    s = [v for (_, sc, _), v in by.items() if sc == scope]
    if not s:
        return None
    return 1e3 * sum(s) / run.result["window"]["steps"]


@functools.lru_cache(maxsize=2)
def read_trace(path: str):
    """Each chip's busy intervals (the union of its ``XLA Ops`` events) and
    the program's host spans, by name, from one ``.xplane.pb``."""
    data = _load(path)
    busy, spans = {}, collections.defaultdict(list)
    wanted = set(HOST_SPANS)
    for plane in data.planes:
        m = devtrace.DEVICE_PLANE.match(plane.name)
        if m:
            iv = [(e.start_ns, e.start_ns + e.duration_ns)
                  for line in plane.lines if line.name == devtrace.OPS_LINE for e in line.events]
            busy[int(m.group(1))] = devtrace.union(iv)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in wanted:
                        spans[e.name].append((e.start_ns, e.start_ns + e.duration_ns))
    return busy, {k: devtrace.union(v) for k, v in spans.items()}


def _load(path: str):
    """The profile in ``path`` (``.xplane.pb``, or the same gzipped)."""
    import gzip

    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def idle_split(busy: dict, spans: dict, window: tuple, chips: int) -> dict | None:
    """Per chip, averaged: the window's seconds idle while the host was in
    ``driver.wait`` (``wait``), idle otherwise (``host``), and idle while the
    host was in each span (``in``; spans nest, so these overlap). None
    without ``driver.wait`` spans in the window."""
    lo, hi = window
    wait = devtrace.clip(spans.get(WAIT, []), lo, hi)
    if not wait:
        return None
    ids = sorted(busy)[:chips]
    idle_wait = idle_all = 0.0
    within = collections.defaultdict(float)
    for i in ids:
        idle = devtrace.complement(devtrace.clip(busy[i], lo, hi), lo, hi)
        idle_all += devtrace.length(idle)
        idle_wait += devtrace.length(devtrace.intersect(idle, wait))
        for name, iv in spans.items():
            within[name] += devtrace.length(devtrace.intersect(idle, devtrace.clip(iv, lo, hi)))
    ns = 1e-9 / len(ids)
    return {"wait": idle_wait * ns, "host": (idle_all - idle_wait) * ns,
            "in": {k: v * ns for k, v in within.items()}}


def run_idle_split(run) -> dict | None:
    """``idle_split`` of a traced run, over the window ``devtrace`` read."""
    busy, spans = read_trace(devtrace.find_xplane(run.ctx.trace_dir))
    return idle_split(busy, spans, run.trace.window, len(run.ctx.devices))
