"""Device ms per window step of the compiled instructions under the program's
``shortcut`` named scope (``core.spatial.add_shortcut``): each residual
unit's add and the crop of its skip, forward and backward. Averaged over
chips. XLA fuses an add into the pass beside it; a fusion counts here when
its root, whose ``op_name`` the fusion takes, is the add (PERF.md §3).

``scopes.SCOPES`` does not name ``shortcut``, so this reader resolves each
instruction's ``op_name`` itself, by ``scopes.instruction_scopes``' rules
(its own, else its called computation's root's, else its first operand's),
and classifies it with ``shortcut`` known beside ``scopes.SCOPES``. A
program without the scope reads as nothing (None)."""

import devtrace
import scopes

SHORTCUT = "shortcut"
KNOWN = scopes.SCOPES + (SHORTCUT,)


def innermost_scope(op_name: str) -> str | None:
    """The innermost component of ``op_name`` that ``KNOWN`` names, read as
    ``scopes.classify`` reads them (the last component is the primitive)."""
    found = None
    for part in op_name.split("/")[:-1]:
        m = scopes.WRAPPED.match(part)
        while m:
            part = m.group(2)
            m = scopes.WRAPPED.match(part)
        if part in KNOWN:
            found = part
    return found


def op_names(hlo: str) -> dict[str, str | None]:
    """Each instruction's ``op_name``, resolved as ``scopes.instruction_scopes``
    resolves it."""
    own, calls, first, roots, names = {}, {}, {}, {}, []
    comp = None
    for line in hlo.splitlines():
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
        if n := scopes.OP_NAME.search(rest):
            own[name] = n.group(1)
        if c := devtrace.CALLS.search(rest):
            calls[name] = c.group(1)
        if o := scopes.OPERAND.search(rest.split("(", 1)[1] if "(" in rest else ""):
            first[name] = o.group(1)

    resolved: dict[str, str | None] = {}

    def of(name: str) -> str | None:
        if name not in resolved:
            resolved[name] = None                   # guards a cycle
            found = own.get(name)
            if found is None and calls.get(name) in roots:
                found = of(roots[calls[name]])
            if found is None and name in first:
                found = of(first[name])
            resolved[name] = found
        return resolved[name]

    return {n: of(n) for n in names}


def shortcut_instructions(hlo: str) -> set[str]:
    """Names of the instructions whose innermost known scope is ``shortcut``."""
    return {name for name, op in op_names(hlo).items() if op and innermost_scope(op) == SHORTCUT}


def read(run):
    hlo = run.result.get("hlo")
    if not hlo:
        return None
    names = shortcut_instructions(hlo)
    if not names:
        return None
    return 1e3 * run.trace.seconds_of(names) / run.result["window"]["steps"]
