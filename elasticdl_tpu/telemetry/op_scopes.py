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

The same text says what the step holds where it holds most:
:func:`live_bytes` walks a scheduled program in schedule order and gives the
bytes alive at its peak by owner (an argument's place in the train state,
any other buffer's part), phase and role, held to XLA's own peak
(docs/designs/telemetry.md, "Bytes at the peak").  ``... <window dir>
--memory`` prints the window's ``step_memory.json``
(``telemetry/memory.py::read_step_memory``).
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
_INDEX = re.compile(r"\bindex=(\d+)")
# ``output_to_operand_aliasing={{0}: (2, {}), {1}: (0, {1})}``
_IN_PLACE = re.compile(r"\{([\d, ]*)\}: \((\d+), \{([\d, ]*)\}\)")
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
    # what the live-byte reading needs besides: the result's shape as
    # written (layout, tiles and memory space), a parameter's number or a
    # ``get-tuple-element``'s index, a parameter's path in the arguments
    # (``state.params['block_0']['attn']...``), and the operands a
    # custom-call writes its outputs in place of
    shape: str = ""
    index: int | None = None
    path: str | None = None
    in_place: tuple = ()


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
    index = path = None
    if opcode == "parameter":
        index = int(rest.split(")", 1)[0])
        path = found.group(1).replace("\\", "") if found else None
    elif opcode == "get-tuple-element":
        index = int(_INDEX.search(attributes).group(1))
    in_place = ()
    if "output_to_operand_aliasing" in attributes:
        in_place = tuple(
            (
                tuple(int(i) for i in out.split(",") if i.strip()),
                int(operand),
                tuple(int(i) for i in inside.split(",") if i.strip()),
            )
            for out, operand, inside in _IN_PLACE.findall(attributes)
        )
    return _Instruction(
        name, opcode,
        # (a parameter's is its path in the arguments, and an instruction
        # XLA made by rewriting another has the bare ``gather``: no stack)
        found.group(1)
        if found and opcode not in _NO_TIME and "/" in found.group(1)
        else None,
        _shape_bytes(shape), root, called, operands, shape, index, path,
        in_place,
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
    return _scopes(_computations(text))


def _scopes(computations: dict) -> dict:
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


# id(program) -> [program, its scope map, its live bytes]; the last two
# None until somebody asks
_maps: dict[int, list] = {}


def _kept(compiled) -> list:
    kept = _maps.get(id(compiled))
    if kept is None or kept[0] is not compiled:
        kept = _maps[id(compiled)] = [compiled, None, None]
    return kept


def scope_map(compiled) -> dict:
    """``{instruction name: (part, phase, kind, also)}`` of one compiled
    program (a ``jax.stages.Compiled``, loaded from the program store or
    built here), from its HLO text.  Built once a program and kept."""
    kept = _kept(compiled)
    if kept[1] is None:
        kept[1] = _scope_of_text(compiled.as_text())
    return kept[1]


# ---- the live bytes of a scheduled program ------------------------------------------
#
# XLA hands out no buffer list for a TPU program (its serialized buffer
# assignment is empty there), so the reading is made from the scheduled
# text: a computation's instructions are in the order they run, a buffer
# lives from the instruction that defines it to the last that reads it
# (through any ``tuple`` / ``get-tuple-element`` / ``bitcast`` of it), and the
# step's peak is where the live bytes are largest.  Held to XLA's own totals
# (:func:`live_bytes`): the walk knows nothing of fragmentation nor of a
# kernel's scratch, guesses which element-wise results XLA writes over
# their operands, and says how far off it is by its ratio.  The rules are
# docs/designs/telemetry.md, "Bytes at the peak".

ARGUMENT, RESIDUAL, TEMPORARY, OUTPUT = (
    "argument", "residual", "temporary", "output"
)
STEP_MEMORY_FILE = "step_memory.json"
# a buffer the forward made is a residual where its last reader is of these
_AFTER_FORWARD = frozenset({"backward", "recompute", "optimizer"})
# reading over XLA's peak: outside these the split is not reported
RATIO_RANGE = (0.9, 1.1)
_LARGEST = 20
_LEAF = re.compile(r"([a-z]+\d*[a-z0-9]*)\[([\d,]*)\](?:\{([^{}]*)\})?")
_TILE = re.compile(r"T\(([\d,]+)\)")
_SPACE = re.compile(r"S\((\d+)\)")
_ENTRY = re.compile(r"^ENTRY %?([\w.\-]+)", re.M)
# the module's ``input_output_alias``: ``{0}: (0, {}, may-alias)`` is output
# leaf 0 written in place of parameter 0
_DONATED = re.compile(r"\{(\d+)\}: \((\d+), \{\}")
# the result is a view of the first operand, or is written in place of it
_OF_FIRST_OPERAND = frozenset(
    {"bitcast", "opt-barrier", "while", "dynamic-update-slice", "scatter"}
)
_WRITES_IN_PLACE = frozenset({"dynamic-update-slice", "scatter"})
_RUNS_COMPUTATIONS = ("while", "conditional", "call")
# XLA's element-wise opcodes: a result may take the place of an operand
_ELEMENTWISE = frozenset(
    """abs add and atan2 bitcast-convert cbrt ceil clamp clz compare complex
    convert copy cosine divide erf exponential exponential-minus-one floor
    imag is-finite log log-plus-one logistic maximum minimum multiply negate
    not or popcnt power real reduce-precision remainder round-nearest-afz
    round-nearest-even rsqrt select shift-left shift-right-arithmetic
    shift-right-logical sign sine sqrt stochastic-convert subtract tan tanh
    xor""".split()
)


def _leaf_bytes(dtype: str, dims: str, layout: str | None) -> int:
    """Bytes of one array in the device's main memory: its dimensions padded
    to the layout's first tile, and 0 for an array the layout keeps in
    another memory space (``S(1)``: the compiler's own prefetch into on-chip
    memory)."""
    sizes = [int(d) for d in dims.split(",") if d]
    elements = 1
    if layout:
        space = _SPACE.search(layout)
        if space is not None and space.group(1) != "0":
            return 0
        tile = _TILE.search(layout)
        if tile is not None:
            order = [int(d) for d in layout.split(":")[0].split(",") if d.strip()]
            edges = [int(t) for t in tile.group(1).split(",")]
            for minor, edge in enumerate(reversed(edges)):
                if minor < len(order):
                    sizes[order[minor]] = -(-sizes[order[minor]] // edge) * edge
                else:
                    elements *= edge
    for size in sizes:
        elements *= size
    return elements * _BYTES.get(dtype, 4 if "64" not in dtype else 8)


def _shape_tree(shape: str):
    """A result's shape as ``(bytes, shape as written)`` an array, a tuple
    as a list."""
    stack: list[list] = [[]]
    at = 0
    while at < len(shape):
        char = shape[at]
        if char == "(":
            stack.append([])
        elif char == ")" and len(stack) > 1:
            done = stack.pop()
            stack[-1].append(done)
        else:
            leaf = _LEAF.match(shape, at)
            if leaf is not None:
                stack[-1].append((_leaf_bytes(*leaf.groups()), leaf.group(0)))
                at = leaf.end()
                continue
        at += 1
    return stack[0][0] if stack[0] else (0, "")


def _leaves(value, found=None) -> list:
    """The buffers of a value, a tuple's in order; None where an operand is
    no instruction of the computation."""
    found = [] if found is None else found
    if isinstance(value, list):
        for element in value:
            _leaves(element, found)
    else:
        found.append(value)
    return found


class _Peak(NamedTuple):
    bytes: int
    at: _Instruction | None
    live: list  # the computation's own buffers alive there
    inner: object  # the _Peak of the computation ``at`` runs, or None


class _LiveWalk:
    """The buffers of one scheduled module and where each lives."""

    def __init__(self, computations: dict):
        self.computations = computations
        self.sizes: list[int] = []
        self.shapes: list[str] = []
        self.made_by: list[_Instruction] = []
        # a buffer written in place of another: a loop body's new carry, a
        # donated argument's output
        self.merged: dict[int, int] = {}
        # a computation's root value, as its last walk left it
        self.results: dict[str, object] = {}
        self._shareable: dict[str, list] = {}
        # the last instruction to read a buffer (bookkeeping apart)
        self.reader: dict[int, _Instruction] = {}

    def fresh(self, tree, instruction: _Instruction):
        if isinstance(tree, list):
            return [self.fresh(element, instruction) for element in tree]
        self.sizes.append(tree[0])
        self.shapes.append(tree[1])
        self.made_by.append(instruction)
        return len(self.sizes) - 1

    def resolve(self, buffer: int) -> int:
        while buffer in self.merged:
            buffer = self.merged[buffer]
        return buffer

    def _fusion_writes_into(self, instruction: _Instruction) -> dict:
        """``{output leaf: operand number}`` of a fusion whose root updates
        a slice of one of its parameters in place."""
        fused = {
            i.name: i for i in self.computations.get(instruction.called[0], ())
        }
        root = next((i for i in fused.values() if i.root), None)
        if root is None:
            return {}
        heads = root.operands if root.opcode == "tuple" else [root.name]
        found = {}
        for leaf, name in enumerate(heads):
            at, wrote = fused.get(name), False
            while (
                at is not None and at.operands
                and (at.opcode == "bitcast" or at.opcode in _WRITES_IN_PLACE)
            ):
                wrote = wrote or at.opcode in _WRITES_IN_PLACE
                at = fused.get(at.operands[0])
            if wrote and at is not None and at.opcode == "parameter":
                found[leaf] = at.index
        return found

    def _value(self, instruction: _Instruction, operands: list, handed):
        """The buffers of an instruction's result: an operand's where the
        result is a view of it or is written in place of it, else new."""
        opcode = instruction.opcode
        first = operands[0] if operands else None
        if opcode == "parameter":
            if instruction.index < len(handed):
                return handed[instruction.index]
        elif opcode == "tuple":
            return operands
        elif opcode == "get-tuple-element":
            if isinstance(first, list) and instruction.index < len(first):
                return first[instruction.index]
        elif opcode in _OF_FIRST_OPERAND:
            if first is not None:
                return first
        elif opcode.endswith("-done") and first is not None:
            if opcode == "all-reduce-done" or not isinstance(first, list):
                return first
            # ``copy-start`` gives (copy, source, context), every other
            # start ((operands), result, context)
            return first[0 if opcode == "copy-done" else min(1, len(first) - 1)]
        tree = _shape_tree(instruction.shape)
        if opcode == "constant":  # in the program's code, not in a buffer
            return self.fresh(_emptied(tree), instruction)
        if opcode.endswith("-start") and isinstance(tree, list) and len(tree) > 1:
            if opcode == "copy-start":
                value = self.fresh(tree, instruction)
                value[1] = first
                return value
            if opcode != "all-reduce-start" and len(_leaves(tree[0])) == len(
                _leaves(operands)
            ):
                return [operands, *self.fresh(tree[1:], instruction)]
        value = self.fresh(tree, instruction)
        in_place = list(instruction.in_place)
        if opcode == "fusion" and instruction.called:
            in_place += [
                ((leaf,) if isinstance(value, list) else (), operand, ())
                for leaf, operand in self._fusion_writes_into(instruction).items()
            ]
        for out, operand, inside in in_place:
            source = operands[operand] if operand < len(operands) else None
            for step in inside:
                source = (
                    source[step]
                    if isinstance(source, list) and step < len(source)
                    else None
                )
            if source is None:
                continue
            if not out:
                value = source
            elif isinstance(value, list) and len(out) == 1 and out[0] < len(value):
                value[out[0]] = source
        return value

    def walk(self, name: str, handed: list, in_place_of=None) -> _Peak:
        """One computation in schedule order: the peak of the buffers it
        makes itself.  ``handed`` is its parameters' buffers, the caller's;
        ``in_place_of`` the buffers its root is written into: a loop's
        carry, the entry's donated arguments (a leaf None where there is
        none)."""
        instructions = self.computations[name]
        end = len(instructions)
        values: dict[str, object] = {}
        born: dict[int, int] = {}
        last: dict[int, int] = {}
        inner: dict[int, _Peak] = {}
        for at, instruction in enumerate(instructions):
            operands = [values.get(o) for o in instruction.operands]
            used = operands
            if instruction.opcode == "get-tuple-element" and operands:
                tuple_ = operands[0]
                if isinstance(tuple_, list) and instruction.index < len(tuple_):
                    used = tuple_[instruction.index]
            for buffer in _leaves(used):
                if buffer is not None:
                    last[buffer] = at
                    if instruction.opcode not in _NO_TIME:
                        self.reader[buffer] = instruction
            before = len(self.sizes)
            value = self._value(instruction, operands, handed)
            values[instruction.name] = value
            # a branch's or a called computation's result is made inside it,
            # counted there until it ends, and the caller's from then on
            handed_over = instruction.opcode in ("conditional", "call")
            for buffer in _leaves(value):
                # (one made and then given up for an operand's is no buffer)
                if buffer is not None and buffer >= before:
                    born[buffer] = at + 1 if handed_over else at
            if instruction.opcode in _RUNS_COMPUTATIONS and instruction.called:
                inner[at] = self._called(instruction, operands, value)
            if instruction.root:
                self.results[name] = value
                mine = _leaves(value)
                for buffer in mine:
                    if buffer is not None:
                        last[buffer] = end
                theirs = _leaves(in_place_of) if in_place_of is not None else []
                if len(mine) == len(theirs):
                    for own, target in zip(mine, theirs):
                        if own is None or target is None:
                            continue
                        own, target = self.resolve(own), self.resolve(target)
                        if own != target and own in born:
                            self.merged[own] = target
        own = [b for b in born if b not in self.merged and self.sizes[b]]
        self._share(instructions, values, born, last, own)
        change = [0] * (end + 2)
        for buffer in own:
            last[buffer] = min(end, last.get(buffer, born[buffer]))
            change[born[buffer]] += self.sizes[buffer]
            change[last[buffer] + 1] -= self.sizes[buffer]
        running = peak = 0
        where = None
        for at in range(end):
            running += change[at]
            here = running + (inner[at].bytes if at in inner else 0)
            if here > peak:
                peak, where = here, at
        if where is None:
            return _Peak(0, None, [], None)
        return _Peak(
            peak, instructions[where],
            [b for b in own if born[b] <= where <= last[b]],
            inner.get(where),
        )

    def _share(self, instructions, values, born, last, own):
        """XLA writes an element-wise result over an operand of its shape
        that nothing reads afterwards: such an operand's life ends before
        the instruction, not at it."""
        candidates = set(own)
        for at, instruction in enumerate(instructions):
            if instruction.opcode == "fusion" and instruction.called:
                shareable = self._elementwise_parameters(instruction.called[0])
            elif instruction.opcode in _ELEMENTWISE:
                shareable = range(len(instruction.operands))
            else:
                continue
            results = [
                b for b in _leaves(values.get(instruction.name))
                if b in candidates and born[b] == at
            ]
            for number in shareable:
                if not results or number >= len(instruction.operands):
                    break
                operand = values.get(instruction.operands[number])
                if (
                    isinstance(operand, list) or operand not in candidates
                    or last[operand] != at or born[operand] >= at
                ):
                    continue
                for result in results:
                    if self.shapes[result] == self.shapes[operand]:
                        last[operand] = at - 1
                        # the same bytes, updated: their first maker's
                        self.made_by[result] = self.made_by[operand]
                        results.remove(result)
                        break

    def _elementwise_parameters(self, fused: str) -> list:
        """The parameters of a fused computation that every instruction
        between them and the root reads element by element."""
        kept = self._shareable.get(fused)
        if kept is None:
            instructions = self.computations.get(fused, ())
            users: dict[str, list] = {}
            for instruction in instructions:
                for operand in instruction.operands:
                    users.setdefault(operand, []).append(instruction)

            def elementwise(name, seen):
                for user in users.get(name, ()):
                    if user.name in seen:
                        continue
                    seen.add(user.name)
                    if user.opcode != "tuple" and user.opcode not in _ELEMENTWISE:
                        return False
                    if not elementwise(user.name, seen):
                        return False
                return True

            kept = self._shareable[fused] = sorted(
                i.index for i in instructions
                if i.opcode == "parameter" and elementwise(i.name, set())
            )
        return kept

    def _called(self, instruction: _Instruction, operands: list, value) -> _Peak:
        """The largest peak among the computations a ``while``, ``call`` or
        ``conditional`` runs: what they add to what is live across it."""
        if instruction.opcode == "while":
            # (written ``condition=..., body=...``; the body's root is the
            # new carry, in place of the old)
            runs = [
                (called, [operands[0]], value if at else None)
                for at, called in enumerate(instruction.called)
            ]
        elif instruction.opcode == "conditional":
            runs = [
                (called, operands[at + 1: at + 2], None)
                for at, called in enumerate(instruction.called)
            ]
        else:
            runs = [(called, operands, None) for called in instruction.called]
        peaks = [
            self.walk(called, handed, into)
            for called, handed, into in runs
            if called in self.computations
        ]
        return max(peaks, key=lambda p: p.bytes, default=_Peak(0, None, [], None))


def _emptied(tree):
    return [_emptied(t) for t in tree] if isinstance(tree, list) else (0, tree[1])


def _argument_owner(path: str | None) -> str:
    """An argument's owner by its path in the step's arguments: ``params``,
    ``opt_state``, a model buffer's collection (``router_stats``), ``step``;
    ``batch`` for what is not the state's."""
    if not path:
        return ARGUMENT
    if not path.startswith("state"):
        return "batch"
    field = re.match(r"state\.(\w+)(?:\['(\w+)'\])?", path)
    if field is None:
        return ARGUMENT
    if field.group(1) == "model_state" and field.group(2):
        return field.group(2)
    return field.group(1)


def _live_of_text(text: str, scopes: dict | None = None) -> dict | None:
    """The reading of one module's text (:func:`live_bytes` without XLA's
    figures; ``scopes`` its scope map where that is made already); None for
    a text that is not scheduled."""
    head = text.split("\n", 1)[0]
    entry = _ENTRY.search(text)
    if "is_scheduled=true" not in head or entry is None:
        return None
    computations = _computations(text)
    if scopes is None:
        scopes = _scopes(computations)
    walk = _LiveWalk(computations)
    instructions = computations[entry.group(1)]
    # the arguments first: the root's donated leaves are written into them
    parameters = {
        i.index: walk.fresh(_shape_tree(i.shape), i)
        for i in instructions if i.opcode == "parameter"
    }
    handed = [parameters.get(n) for n in range(max(parameters, default=-1) + 1)]
    root = next((i for i in instructions if i.root), None)
    outputs = len(_leaves(_shape_tree(root.shape))) if root is not None else 0
    donated = {int(out): int(p) for out, p in _DONATED.findall(
        head.partition("input_output_alias=")[2].partition("entry_computation")[0]
    )}
    written_over = set(donated.values())
    into = [
        handed[donated[leaf]]
        if leaf in donated and donated[leaf] < len(handed)
        and not isinstance(handed[donated[leaf]], list)
        else None
        for leaf in range(outputs)
    ]
    arguments = [b for b in range(len(walk.sizes)) if walk.sizes[b]]
    peak = walk.walk(entry.group(1), handed, into)
    chain, at = [], peak
    live = list(arguments)
    while at is not None and at.at is not None:
        chain.append(at.at)
        live += at.live
        at = at.inner
    # the peak's own place in the model: the innermost instruction's, as
    # far in as the map names one
    part, phase = None, "forward"
    for instruction in reversed(chain):
        if instruction.name in scopes:
            part, phase = scopes[instruction.name][:2]
            break
    outputs_of_entry = {
        walk.resolve(b) for b in _leaves(walk.results.get(entry.group(1)))
        if b is not None
    }
    rows: dict[tuple, int] = {}
    largest = []
    for buffer in live:
        made_by = walk.made_by[buffer]
        if made_by.opcode == "parameter":
            key = (_argument_owner(made_by.path), "", ARGUMENT)
        else:
            owner, made_in = scopes.get(made_by.name, (None, "forward"))[:2]
            reader = walk.reader.get(buffer)
            read_in = scopes.get(reader.name, (None, made_in))[1] if reader else made_in
            if buffer in outputs_of_entry:
                role = OUTPUT
            elif made_in == "forward" and read_in in _AFTER_FORWARD:
                role = RESIDUAL
            else:
                role = TEMPORARY
            key = (owner or UNATTRIBUTED, made_in, role)
        rows[key] = rows.get(key, 0) + walk.sizes[buffer]
        largest.append([made_by.name, *key, walk.sizes[buffer]])
    largest.sort(key=lambda row: -row[-1])
    return {
        "peak_bytes": peak.bytes + sum(walk.sizes[b] for b in arguments),
        "instruction": chain[-1].name if chain else None,
        "within": [i.name for i in chain[:-1]],
        "part": part or UNATTRIBUTED,
        "phase": phase,
        "live": sorted(
            ([*key, size] for key, size in rows.items()), key=lambda r: -r[-1]
        ),
        "largest": largest[:_LARGEST],
        # the state's leaves the step writes no output in place of: such a
        # leaf stands twice while the step runs
        "undonated": [
            [i.path, walk.sizes[parameters[i.index]]]
            for i in instructions
            if i.opcode == "parameter" and (i.path or "").startswith("state")
            and i.index not in written_over
            and not isinstance(parameters[i.index], list)
        ],
    }


XLA_SIZES = ("argument", "output", "alias", "temp", "generated_code")


def xla_sizes(compiled) -> dict | None:
    """XLA's own account of one compiled program, bytes a device:
    ``argument``, ``output``, ``alias``, ``temp``, ``generated_code``,
    ``peak`` (the arguments and the most the temporaries hold at once) and
    ``total`` (arguments + temporaries + outputs - aliased); None where the
    backend gives none."""
    try:
        analysis = compiled.memory_analysis()
    except Exception:  # noqa: BLE001 — a backend without the analysis
        analysis = None
    if analysis is None:
        return None
    sizes = {
        name: int(getattr(analysis, name + "_size_in_bytes", 0) or 0)
        for name in XLA_SIZES
    }
    sizes["peak"] = int(getattr(analysis, "peak_memory_in_bytes", 0) or 0)
    sizes["total"] = (
        sizes["argument"] + sizes["temp"] + sizes["output"] - sizes["alias"]
    )
    return sizes


def live_bytes(compiled) -> dict | None:
    """What one compiled program holds where it holds most, by the model's
    own scopes: ``peak_bytes``, the ``instruction`` there (``within`` the
    loops that run it) with its ``part`` and ``phase``, and ``live``, rows
    of ``[owner, phase, role, bytes]`` that add up to ``peak_bytes``
    (``largest``: the biggest single buffers, ``[instruction, owner, phase,
    role, bytes]``).  An argument's owner is its place in the train state
    (``params``, ``opt_state``, a model buffer's collection, ``batch``),
    any other buffer's the part of the instruction that made it; the roles
    are ``argument``, ``residual`` (made in the forward pass, alive at a
    peak after it), ``temporary`` and ``output``.

    Held to XLA's figures: ``xla`` is :func:`xla_sizes`, ``ratio`` the
    reading over XLA's peak (``held_to`` says ``total``, arguments +
    temporaries + outputs - aliased, where the backend's peak leaves the
    temporaries out, as the CPU's does), and outside
    :data:`RATIO_RANGE` ``live`` and ``largest`` are None: a split that
    does not add up to the program's bytes is not handed out.  None for a
    program whose text is not scheduled.  Made once a program and kept."""
    kept = _kept(compiled)
    if kept[2] is None:
        found = _live_of_text(compiled.as_text(), kept[1])
        if found is None:
            return None
        sizes = xla_sizes(compiled)
        found["xla"] = sizes
        found["ratio"] = found["held_to"] = None
        if sizes is not None:
            outside = sizes["argument"] + sizes["output"] - sizes["alias"]
            # XLA's peak where it counts the temporaries (a CPU program's
            # names the arguments and the outputs alone), else its total
            held_to = (
                "peak" if sizes["peak"] - outside >= sizes["temp"] / 2 else "total"
            )
            if sizes[held_to]:
                found["held_to"] = held_to
                found["ratio"] = found["peak_bytes"] / sizes[held_to]
        low, high = RATIO_RANGE
        if found["ratio"] is not None and not low <= found["ratio"] <= high:
            found["live"] = found["largest"] = None
        kept[2] = found
    return kept[2]


# ---- the trainer's programs, read on demand -----------------------------------------

_watched = None


def watch(trainer):
    """Remember (weakly) the trainer whose train programs :func:`read`
    maps."""
    global _watched
    _watched = weakref.ref(trainer)


def watched_programs() -> tuple:
    """``(trainer, its train programs)``; ``(None, [])`` without a watched
    trainer or before its first step."""
    trainer = _watched() if _watched is not None else None
    programs = trainer.train_programs() if trainer is not None else []
    if not programs:
        return None, []
    for gone in set(_maps) - {id(p) for p in programs}:
        del _maps[gone]
    return trainer, programs


def read() -> list[dict] | None:
    """The maps of the train programs the watched trainer has run; None
    without a trainer or before its first step."""
    programs = watched_programs()[1]
    return [scope_map(program) for program in programs] or None


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


def memory_table(program: dict, depth: int = 3) -> str:
    """One program's bytes at its peak by owner x phase x role, largest
    first, as text (``program``: :func:`live_bytes`)."""
    xla = program.get("xla") or {}
    lines = []
    if program.get("live") is not None:
        rows: dict[tuple, int] = {}
        for owner, phase, role, size in program["live"]:
            key = (at_depth(owner, depth), phase, role)
            rows[key] = rows.get(key, 0) + size
        width = max([len(owner) for owner, _, _ in rows] + [5])
        lines.append(
            f"{'owner':<{width}}  {'phase':<9}  {'role':<9}  {'MB':>10}  {'%':>6}"
        )
        for (owner, phase, role), size in sorted(
            rows.items(), key=lambda item: -item[1]
        ):
            lines.append(
                f"{owner:<{width}}  {phase:<9}  {role:<9}  {size / 1e6:>10.1f}"
                f"  {100.0 * size / program['peak_bytes']:>6.2f}"
            )
        lines.append(
            f"{'total':<{width}}  {'':<9}  {'':<9}"
            f"  {program['peak_bytes'] / 1e6:>10.1f}  {100.0:>6.2f}"
        )
    if "peak_bytes" in program:
        within = "".join(f" in {name}" for name in reversed(program["within"]))
        ratio = program.get("ratio")
        lines.append(
            f"peak at {program['instruction']}{within}: {program['part']}, "
            f"{program['phase']}; {program['peak_bytes'] / 1e6:.1f} MB read, "
            + (
                f"{ratio:.4f} of XLA's {program['held_to']} "
                f"{xla[program['held_to']] / 1e6:.1f} MB"
                if ratio is not None else "XLA gives no figure"
            )
            + ("" if program.get("live") is not None else
               ": outside what the split is reported for")
        )
    if xla:
        lines.append("XLA: " + ", ".join(
            f"{name} {xla[name] / 1e6:.1f} MB" for name in (*XLA_SIZES, "peak")
        ))
    return "\n".join(lines)


def _print_memory(path: str, depth: int) -> int:
    with open(path) as f:
        reading = json.load(f)
    out = []
    for program in reading["programs"]:
        out.append(memory_table(program, depth))
    state = reading["state"]
    out.append(
        f"state on device {state['device']}: " + ", ".join(
            f"{name} {size / 1e6:.1f} MB"
            for name, size in state.items() if name != "device"
        )
        + f"; other arrays {reading['other_arrays'] / 1e6:.1f} MB"
    )
    if reading["undonated"]:
        out.append("not donated: " + ", ".join(
            f"{path} {size / 1e6:.1f} MB" for path, size in reading["undonated"]
        ))
    if reading["allocator"]:
        out.append("allocator: " + ", ".join(
            f"{name} {size / 1e6:.1f} MB"
            for name, size in reading["allocator"].items() if name != "id"
        ))
    sys.stdout.write("\n".join(out) + "\n")
    return 0


def main(argv=None) -> int:
    import argparse

    parser = argparse.ArgumentParser(
        description="device time of a profile window by part x phase x kind"
    )
    parser.add_argument("window", help="a --profile_dir, or a directory under it")
    parser.add_argument("--depth", type=int, default=3, help="0: whole parts")
    parser.add_argument("--steps", type=int, default=0, help="print ms a step")
    parser.add_argument(
        "--memory", action="store_true",
        help="the step's bytes at its peak by owner x phase x role instead",
    )
    args = parser.parse_args(argv)
    if args.memory:
        found = sorted(
            glob.glob(
                os.path.join(args.window, "**", STEP_MEMORY_FILE), recursive=True
            )
        )
        if not found:
            sys.stderr.write(f"no {STEP_MEMORY_FILE} under {args.window}\n")
            return 1
        return _print_memory(found[-1], args.depth)
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
