"""Device time by the model's own scopes: the compiled program's op -> scope
map, read on demand.

The device trace names an op ``fusion.25``; the compiled program knows where
in the model that instruction came from.  Every op JAX lowers carries its
name stack as ``metadata={op_name="jit(train_step)/transpose(jvp(
TransformerLM))/block_3/.../attn/query/dot_general"}``: flax names a
module's ops, ``jax.named_scope`` names the regions between modules (each
named in place; ``tests/test_op_scopes.py`` collects them from the
sources), ``transpose(`` marks the backward pass and
``rematted_computation`` a forward that runs again inside it.  The metadata
survives compilation, fusion and ``serialize`` -> ``deserialize_and_load``,
so a running trainer can be asked what its own program's ops are
(:func:`watch`, :func:`read`: the pattern of ``telemetry/router_load.py``).

:func:`scope_map` reads one compiled program's HLO text into ``{instruction
name: (part, phase, kind, also)}``; :func:`attribute` joins it to a trace's
per-op self times.  Both run when somebody asks (a benchmark's reader after
its window, ``utils/profiling.py`` at a window's close, the CLI below) and
never on the train path.

The rules (docs/designs/telemetry.md, "Op scopes"):

- **part**: the name stack without what JAX put there (``jit(...)``,
  ``jvp``, ``transpose``, ``vmap``, ``shard_map``, ``checkpoint``,
  ``rematted_computation``, ``while/body``, ``cond/branch_N_fun``, the
  lowered primitive at its end), without flax's method scopes
  (``block_7._attention``), without the anonymous root module, and with
  counters folded (``block_7`` -> ``block``, ``mtp_1_block`` ->
  ``mtp/block``): ``block/attn/rope``, ``lm_head``, ``optimizer``.
- **phase**: ``optimizer`` by scope; ``recompute`` where
  ``rematted_computation`` is in the stack; ``backward`` where a
  ``transpose(`` is; else ``forward``.
- **kind**: ``kernel`` (a compiled kernel's custom-call), ``matmul`` (a ``dot`` or
  ``convolution``, alone or inside a fusion), ``collective`` (by opcode),
  ``other``.
- **the anchor of a fusion**: the ``dot`` / ``convolution`` / custom-call
  inside it with the largest output, else its root; the fusion is the
  anchor's part and phase, and ``also`` lists the other top-level parts
  fused into it.  Time is never split inside an op.  An instruction XLA
  made without metadata (a layout copy) is its operand's, else
  unattributed.

    python -m elasticdl_tpu.telemetry.op_scopes <window dir> [--depth N]

prints a profile window's device time by part x phase x kind
(``utils/profiling.py`` writes ``op_scopes.json`` beside the window's
``.xplane.pb``).
"""

from __future__ import annotations

import functools
import glob
import json
import os
import re
import sys
import weakref
from typing import NamedTuple

# an op directly under the root module: the map's own name for that part
# (every other name of a part is a flax module's or a ``jax.named_scope``'s,
# named where it is used)
ROOT = "model"
OP_SCOPES_FILE = "op_scopes.json"
# the scope a model puts around a loop that runs its stack several times
# (models/long_seq_transformer.py): the loop's own ops (the carry's copies,
# the stacked exits) are this part's; an op of a part inside the loop is that
# part's, as if the loop were not there
LOOP = "loop"
UNATTRIBUTED = "unattributed"

# perf/trace_reduce.py::COLLECTIVE, copied: the program imports nothing of
# the benchmark
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast",
)
# what JAX's transforms and control flow leave in a name stack
_STRUCTURE = frozenset(
    {
        "checkpoint", "remat", "remat2", "rematted_computation", "while",
        "body", "cond", "closed_call", "core_call", "custom_jvp_call",
        "custom_vjp_call", "custom_vjp_call_jaxpr", "shard_map", "pjit",
        "named_call", "custom_lin",
    }
)
_REMATTED = "rematted_computation"
_TRANSFORM = re.compile(r"^(\w+)\((.*)\)$")
_BRANCH = re.compile(r"^branch_\d+(_fun)?$")
_IDENTIFIER = re.compile(r"^[A-Za-z_]\w*$")
_COUNTER = re.compile(r"(?:_\d+)+(?:_|$)")
_ANONYMOUS = re.compile(r"_\d+$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_ARRAY = re.compile(r"\b([a-z]+\d*[a-z0-9]*)\[([\d,]*)\]")
_CALLED = re.compile(
    r"\b(calls|to_apply|body|condition|true_computation|false_computation)"
    r"=%?([\w.\-]+)"
)
_BRANCHES = re.compile(r"(?:branch|called)_computations=\{([^}]*)\}")
_OPERAND = re.compile(r"%([\w.\-]+)")
_COMMENT = re.compile(r"/\*.*?\*/")
# never on a device's op line, or there for no time of their own
_NO_TIME = frozenset(
    {"parameter", "constant", "tuple", "get-tuple-element", "bitcast"}
)
# a custom-call that is a compiled kernel (Mosaic's on a TPU); XLA's own
# (``ConcatBitcast``, ``AllocateBuffer``) are bookkeeping
_KERNEL_TARGET = re.compile(r'custom_call_target="[^"]*(tpu_custom_call|mosaic|triton)')
_KERNEL = "kernel-call"
_ANCHORS = frozenset({"dot", "convolution", _KERNEL})
_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2}


# ---- the name stack -----------------------------------------------------------


@functools.lru_cache(maxsize=1 << 16)
def canonical(op_name: str) -> tuple[str | None, str]:
    """``(part, phase)`` of one ``op_name``; the part is None where the
    stack names nothing (an op of the step outside every scope)."""
    elements = op_name.split("/")
    if elements[0] in elements[1:]:
        # a function several layers call, compiled once: XLA strings the
        # callers' stacks together, and the first is as good as any
        elements = elements[: elements.index(elements[0], 1)]
    elements = elements[:-1]  # the last is the lowered primitive
    transposed = rematted = False
    kept: list[str] = []
    rooted = False
    for element in elements:
        called = False
        while True:
            match = _TRANSFORM.match(element)
            if match is None:
                break
            transform, element = match.groups()
            transposed = transposed or transform == "transpose"
            if transform in ("jit", "pjit"):
                called = True  # a function's own jit: names no region
                break
        if called or not element:
            continue
        if element == _REMATTED:
            rematted = True
        if (
            element in _STRUCTURE
            or _BRANCH.match(element)
            # flax's ``block_7._attention``, an einsum's ``nkd,nk->nd``
            or not _IDENTIFIER.match(element)
        ):
            continue
        if (
            not kept and element[0].isupper()
            and not _ANONYMOUS.search(element)
        ):
            # flax names an anonymous root by its class and every
            # anonymous child ``Class_N`` (a recomputed layer's stack
            # names the root twice)
            rooted = True
            continue
        for piece in _COUNTER.split(element):
            # (a scope entered again inside itself counts once)
            if piece and piece != (kept[-1] if kept else None):
                kept.append(piece)
    if len(kept) > 1 and kept[0] == LOOP:
        del kept[0]
    if "optimizer" in kept:
        phase = "optimizer"
    elif rematted:
        phase = "recompute"
    elif transposed:
        phase = "backward"
    else:
        phase = "forward"
    if not kept:
        return (ROOT if rooted else None), phase
    return "/".join(kept), phase


def top_level(part: str) -> str:
    return part.split("/", 1)[0]


# ---- the compiled program's text ------------------------------------------------


class _Instruction(NamedTuple):
    name: str
    opcode: str
    op_name: str | None
    size: int
    root: bool
    called: list
    operands: list


def _shape_end(text: str) -> int:
    """Where the result's shape ends: a tuple's closing parenthesis, else
    the first space (an array's shape and layout hold none)."""
    if not text.startswith("("):
        end = text.find(" ")
        return len(text) if end < 0 else end
    depth = 0
    for at, char in enumerate(text):
        if char == "(":
            depth += 1
        elif char == ")":
            depth -= 1
            if depth == 0:
                return at + 1
    return len(text)


def _shape_bytes(shape: str) -> int:
    total = 0
    for dtype, dims in _ARRAY.findall(shape):
        elements = 1
        for dim in dims.split(","):
            if dim:
                elements *= int(dim)
        total += elements * _BYTES.get(dtype, 4 if "64" not in dtype else 8)
    return total


def _parse_instruction(line: str) -> _Instruction | None:
    line = line.strip()
    root = line.startswith("ROOT ")
    if root:
        line = line[5:]
    name, equals, rest = line.partition(" = ")
    if not equals:
        return None
    name = name.lstrip("%")
    end = _shape_end(rest)
    shape, rest = rest[:end], rest[end:].lstrip()
    opcode, paren, rest = rest.partition("(")
    if not paren or not opcode or " " in opcode:
        return None
    # a kernel's body rides in backend_config as a megabyte of base64
    config = rest.find("backend_config=")
    attributes = rest if config < 0 else rest[:config]
    if opcode == "custom-call" and _KERNEL_TARGET.search(attributes):
        opcode = _KERNEL
    if "/*" in attributes:  # a long tuple's ``/*index=5*/``
        attributes = _COMMENT.sub("", attributes)
    found = _OP_NAME.search(attributes)
    if found is None and config >= 0:
        at = rest.find("metadata={", config)
        if at >= 0:
            found = _OP_NAME.search(rest, at, at + 4096)
    called = [m.group(2) for m in _CALLED.finditer(attributes)]
    branches = _BRANCHES.search(attributes)
    if branches is not None:
        called += [b.strip().lstrip("%") for b in branches.group(1).split(",")]
    # (what follows the first ``=`` names attributes and computations)
    operands = _OPERAND.findall(attributes.split("=", 1)[0])
    return _Instruction(
        name, opcode,
        # (a parameter's is its path in the arguments, and an instruction
        # XLA made by rewriting another has the bare ``gather``: no stack)
        found.group(1)
        if found and opcode not in _NO_TIME and "/" in found.group(1)
        else None,
        _shape_bytes(shape), root, called, operands,
    )


def _computations(text: str) -> dict[str, list[_Instruction]]:
    computations: dict[str, list[_Instruction]] = {}
    current = None
    for line in text.splitlines():
        if not line:
            continue
        if line[0] not in " }":
            if line.rstrip().endswith("{"):
                head = line.split("(", 1)[0].split()
                if head and head[0] != "HloModule":
                    current = computations.setdefault(
                        head[-1].lstrip("%"), []
                    )
            continue
        if line[0] == "}":
            current = None
        elif current is not None:
            instruction = _parse_instruction(line)
            if instruction is not None:
                current.append(instruction)
    return computations


def _kind_of(opcode: str, inner=()) -> str:
    if COLLECTIVE.search(opcode) or any(
        COLLECTIVE.search(i.opcode) for i in inner
    ):
        return "collective"
    if opcode == _KERNEL or any(i.opcode == _KERNEL for i in inner):
        return "kernel"
    if opcode in ("dot", "convolution") or any(
        i.opcode in ("dot", "convolution") for i in inner
    ):
        return "matmul"
    return "other"


_CONTROL = ("while", "conditional", "call", "async-start")


def _scope_of_text(text: str) -> dict:
    computations = _computations(text)
    fused, applied, caller = set(), set(), {}
    for name, instructions in computations.items():
        for instruction in instructions:
            if instruction.opcode == "fusion":
                fused.update(instruction.called)
            elif instruction.opcode in _CONTROL:
                for called in instruction.called:
                    caller[called] = (name, instruction)
            else:
                applied.update(instruction.called)
    by_name = {
        name: {i.name: i for i in instructions}
        for name, instructions in computations.items()
    }
    first_user: dict[str, _Instruction] = {}
    for instructions in computations.values():
        for instruction in instructions:
            for operand in instruction.operands:
                first_user.setdefault(operand, instruction)
    sources: dict[str, tuple] = {}

    def source_of(computation: str, instruction: _Instruction) -> tuple:
        """``(part, phase)`` from the instruction's anchor, else from the
        value it copies, else from what reads it, else from the loop or
        branch it runs in."""
        if instruction.name in sources:
            return sources[instruction.name]
        sources[instruction.name] = (None, "forward")  # (a cycle ends here)
        named = [i for i in inner_of(instruction) if i.op_name]
        anchors = [i for i in named if i.opcode in _ANCHORS]
        if anchors:
            found = max(anchors, key=lambda i: i.size)
        elif named:
            # the root, or the named instruction nearest to it
            found = next((i for i in named if i.root), named[-1])
        else:
            found, hops = instruction, 0
            while found is not None and not found.op_name and hops < 4:
                # XLA's own copy of a value: the value's
                found = by_name[computation].get(
                    found.operands[0] if found.operands else None
                )
                hops += 1
        user = first_user.get(instruction.name)
        if found is not None and found.op_name:
            scope = canonical(found.op_name)
        elif instruction.op_name:
            scope = canonical(instruction.op_name)
        elif user is not None and user.name in by_name[computation]:
            scope = source_of(computation, user)
        elif computation in caller:
            scope = source_of(*caller[computation])
        else:
            scope = (None, "forward")
        sources[instruction.name] = scope
        return scope

    def inner_of(instruction: _Instruction) -> list:
        if instruction.opcode not in ("fusion", "async-start"):
            return []
        return [
            i
            for called in instruction.called
            for i in computations.get(called, ())
        ]

    scopes: dict[str, tuple] = {}
    for name, instructions in computations.items():
        if name in fused or name in applied:
            continue
        for instruction in instructions:
            if instruction.opcode in _NO_TIME:
                continue
            part, phase = source_of(name, instruction)
            inner = inner_of(instruction)
            also = ()
            if part is not None and inner:
                others = {
                    top_level(p)
                    for p in (
                        canonical(i.op_name)[0] for i in inner if i.op_name
                    )
                    if p is not None
                }
                also = tuple(sorted(others - {top_level(part)}))
            scopes[instruction.name] = (
                part, phase, _kind_of(instruction.opcode, inner), also
            )
    return scopes


_maps: dict[int, tuple] = {}


def scope_map(compiled) -> dict:
    """``{instruction name: (part, phase, kind, also)}`` of one compiled
    program (a ``jax.stages.Compiled``, loaded from the program store or
    built here), from its HLO text.  Built once a program and kept."""
    kept = _maps.get(id(compiled))
    if kept is None or kept[0] is not compiled:
        kept = _maps[id(compiled)] = (compiled, _scope_of_text(compiled.as_text()))
    return kept[1]


# ---- the trainer's programs, read on demand -----------------------------------------

_watched = None


def watch(trainer):
    """Remember (weakly) the trainer whose train programs :func:`read`
    maps."""
    global _watched
    _watched = weakref.ref(trainer)


def read() -> list[dict] | None:
    """The maps of the train programs the watched trainer has run; None
    without a trainer or before its first step."""
    trainer = _watched() if _watched is not None else None
    if trainer is None:
        return None
    programs = trainer.train_programs()
    if not programs:
        return None
    for gone in set(_maps) - {id(p) for p in programs}:
        del _maps[gone]
    return [scope_map(program) for program in programs]


# ---- the join -------------------------------------------------------------------


def attribute(op_self_s: dict, maps: list[dict]) -> dict:
    """Per-op self times joined to ``maps``: ``{"scopes": {(part, phase,
    kind): seconds}, "unattributed": seconds, "fused_across": seconds,
    "unattributed_ops": [[name, seconds], ...]}``.  An op no map holds,
    one held without a part, and one on which two programs disagree are
    unattributed; ``fused_across`` is the time of the ops whose ``also``
    is not empty."""
    scopes: dict[tuple, float] = {}
    unattributed = fused_across = 0.0
    missing = []
    for name, seconds in op_self_s.items():
        found = {m[name] for m in maps if name in m}
        if len(found) != 1 or next(iter(found))[0] is None:
            unattributed += seconds
            missing.append([name, seconds])
            continue
        part, phase, kind, also = next(iter(found))
        scopes[(part, phase, kind)] = scopes.get((part, phase, kind), 0.0) + seconds
        if also:
            fused_across += seconds
    missing.sort(key=lambda item: -item[1])
    return {
        "scopes": scopes,
        UNATTRIBUTED: unattributed,
        "fused_across": fused_across,
        "unattributed_ops": missing[:10],
    }


def at_depth(part: str, depth: int) -> str:
    return "/".join(part.split("/")[:depth]) if depth else part


def table(attributed: dict, busy_s: float, depth: int = 3, steps: int = 0) -> str:
    """Device time by part x phase x kind, largest first, as text."""
    rows: dict[tuple, float] = {}
    for (part, phase, kind), seconds in attributed["scopes"].items():
        key = (at_depth(part, depth), phase, kind)
        rows[key] = rows.get(key, 0.0) + seconds
    if attributed[UNATTRIBUTED]:
        rows[(UNATTRIBUTED, "", "")] = attributed[UNATTRIBUTED]
    scale = 1e3 / steps if steps else 1e3
    unit = "ms/step" if steps else "ms"
    total = sum(rows.values())
    width = max([len(part) for part, _, _ in rows] + [4])
    lines = [f"{'part':<{width}}  {'phase':<9}  {'kind':<10}  {unit:>10}  {'%':>6}"]
    for (part, phase, kind), seconds in sorted(
        rows.items(), key=lambda item: -item[1]
    ):
        if not seconds:
            continue
        lines.append(
            f"{part:<{width}}  {phase:<9}  {kind:<10}  {seconds * scale:>10.3f}"
            f"  {100.0 * seconds / busy_s if busy_s else 0.0:>6.2f}"
        )
    lines.append(
        f"{'total':<{width}}  {'':<9}  {'':<10}  {total * scale:>10.3f}"
        f"  {100.0 * total / busy_s if busy_s else 0.0:>6.2f}"
    )
    lines.append(
        f"busy {busy_s * scale:.3f} {unit}; fused across top-level parts "
        f"{attributed['fused_across'] * scale:.3f}"
    )
    return "\n".join(lines)


# ---- a profile window ---------------------------------------------------------------


def dump(path: str) -> bool:
    """Write the watched trainer's maps to ``path``; False with nothing to
    write."""
    maps = read()
    if not maps:
        return False
    with open(path, "w") as f:
        json.dump({"programs": maps}, f, separators=(",", ":"))
    return True


def load(path: str) -> list[dict]:
    with open(path) as f:
        kept = json.load(f)
    return [
        {
            name: (part, phase, kind, tuple(also))
            for name, (part, phase, kind, also) in program.items()
        }
        for program in kept["programs"]
    ]


_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_OP_LINE = "XLA Ops"


def window_self_times(xplane: str) -> tuple[dict, float]:
    """``({op name: self seconds}, busy seconds)`` of a trace's device op
    lines, averaged over the devices: an op that encloses others (a
    ``while``) keeps only the time its body's ops do not take."""
    from jax.profiler import ProfileData

    op_self: dict[str, int] = {}
    busy = planes = 0
    for plane in ProfileData.from_file(xplane).planes:
        if not _DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name != _OP_LINE:
                continue
            planes += 1
            events = sorted(
                (
                    int(e.start_ns), -int(e.duration_ns),
                    e.name.partition(" = ")[0].lstrip("%"),
                )
                for e in line.events
            )
            stack: list[list] = []  # [name, end, self]
            covered_to = 0

            def close(until):
                while stack and stack[-1][1] <= until:
                    name, _, own = stack.pop()
                    op_self[name] = op_self.get(name, 0) + own

            for start, negative, name in events:
                end = start - negative
                close(start)
                if stack:
                    stack[-1][2] -= min(end, stack[-1][1]) - start
                stack.append([name, end, end - start])
                if end > covered_to:
                    busy += end - max(start, covered_to)
                    covered_to = end
            close(float("inf"))
    planes = max(1, planes)
    return (
        {name: ns / planes / 1e9 for name, ns in op_self.items()},
        busy / planes / 1e9,
    )


def main(argv=None) -> int:
    import argparse

    parser = argparse.ArgumentParser(
        description="device time of a profile window by part x phase x kind"
    )
    parser.add_argument("window", help="a --profile_dir, or a directory under it")
    parser.add_argument("--depth", type=int, default=3, help="0: whole parts")
    parser.add_argument("--steps", type=int, default=0, help="print ms a step")
    args = parser.parse_args(argv)
    traces = sorted(
        glob.glob(os.path.join(args.window, "**", "*.xplane.pb"), recursive=True)
    )
    if not traces:
        sys.stderr.write(f"no .xplane.pb under {args.window}\n")
        return 1
    scopes = os.path.join(os.path.dirname(traces[-1]), OP_SCOPES_FILE)
    if not os.path.exists(scopes):
        sys.stderr.write(f"no {OP_SCOPES_FILE} beside {traces[-1]}\n")
        return 1
    op_self, busy_s = window_self_times(traces[-1])
    sys.stdout.write(
        table(attribute(op_self, load(scopes)), busy_s, args.depth, args.steps)
        + "\n"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
