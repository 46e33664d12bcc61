"""Reading compiled HLO text: which instructions run inside a while loop."""
import re

_HEADER = re.compile(r"^(?:ENTRY )?%([\w.\-]+) .*\{$")
_CALLEE = re.compile(
    r"(?:body|condition|calls|to_apply|true_computation|false_computation)"
    r"=%([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")


def _computations(hlo: str) -> dict[str, list[str]]:
    comps, name = {}, None
    for line in hlo.splitlines():
        m = _HEADER.match(line)
        if m:
            name = m.group(1)
            comps[name] = []
        elif line.startswith("}"):
            name = None
        elif name is not None and line.strip():
            comps[name].append(line.strip())
    return comps


def _callees(line: str) -> list[str]:
    names = _CALLEE.findall(line)
    for group in _BRANCHES.findall(line):
        names += [n.strip().lstrip("%") for n in group.split(",")]
    return names


def while_body_instructions(hlo: str) -> list[str]:
    """Every instruction of every while body, and of what those bodies call."""
    comps = _computations(hlo)
    todo = [b for lines in comps.values() for line in lines
            if " while(" in line for b in re.findall(r"body=%([\w.\-]+)",
                                                     line)]
    seen: set[str] = set()
    while todo:
        name = todo.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        for line in comps[name]:
            todo += _callees(line)
    return [line for name in sorted(seen) for line in comps[name]]


def opcode(line: str) -> tuple[str, str]:
    """(result shape, opcode) of one HLO instruction line."""
    m = re.match(r"(?:ROOT )?%[\w.\-]+ = (.+?) ([\w\-]+)\(", line)
    return (m.group(1), m.group(2)) if m else ("", "")


def ops_of_shape(lines: list[str], opcodes: tuple[str, ...],
                 dims: tuple[tuple[int, ...], ...]) -> list[str]:
    """The instructions among ``lines`` with one of ``opcodes`` whose result
    (or a tuple's first element) has one of the dimension lists ``dims``."""
    ends = tuple("[" + ",".join(map(str, d)) + "]" for d in dims)
    return [line for line in lines if opcode(line)[1] in opcodes
            and opcode(line)[0].split("{")[0].endswith(ends)]
