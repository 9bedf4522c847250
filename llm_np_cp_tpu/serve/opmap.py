"""What the operations of a compiled step are: HLO instruction name →
named scope, result shape, pool-shaped or not.

A device profile names an operation by its HLO instruction
(``%fusion.148``, ``%copy.89``): numbers that change with every edit of
the step.  ``jax.profiler.ProfileData`` does not show the ``op_name``
the scopes live in, so the PROGRAM says what its operations are.  The
text of the compiled module carries, for every instruction, the
``op_name`` it was traced under
(``jit(mixed_step)/while/body/closed_call/mlp/bsh,ho->bso/dot_general``):
``scope`` is the innermost path component that is one of the step's
``jax.named_scope`` names (models/transformer.STEP_SCOPES).  The
operations the compiler adds around the layer loop to move the KV pool
(layout copies, the loop's dynamic-slice / dynamic-update-slice of a
layer's slab) carry no scope; they are told by their result, which has
the shape of the pool (as the step's arguments have it, or flat over
layer and block as the unified step's scan carries it) or of one
layer's slab of it.

The map goes into ``otherData["op_map"]`` of the trace dump once, after
warm-up (``ServeEngine.device_op_map``), merged over the step's buckets
and keyed the way a profile names an operation (``merge``):
``benchmark/layers`` and ``tools/summarize_trace.py`` look operations up
in it and derive nothing.  This module is text processing only and
imports no JAX.

The same text says whether an execution RE-LAYS OUT a weight
(``weight_relayouts``): an entry parameter keeps the layout its caller
gave it, so where the program wants another the compiler copies the whole
weight, every execution (seven 100 MB ``%copy bf16[1,4096,12288]`` a tick
of one cell: PERF.md section 6, PR 54).  The engine puts its weights where
the step reads them when it is built (``ServeEngine.step_weight_formats``);
the count of what is left is published per program beside the map
(``otherData["weight_relayout_bytes"]``) and as a ``/metrics`` gauge.
"""

from __future__ import annotations

import re
from typing import Iterable

# opcodes that never run as an operation of their own
_SILENT = frozenset((
    "parameter", "get-tuple-element", "tuple", "bitcast", "constant",
))
_LAYOUT = re.compile(r"\{[^{}]*\}")
_COMPUTATION = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(
    r"^\s+(?:ROOT\s+)?%?([\w.\-]+) = (.*?)\s([\w\-]+)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
# computations an instruction runs as a program of its own (a fusion's
# or a reducer's body is part of the instruction that calls it)
_CALLED = re.compile(
    r"\b(?:body|condition|true_computation|false_computation)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"\bbranch_computations=\{([^}]*)\}")
_TO_APPLY = re.compile(r"\bto_apply=%?([\w.\-]+)")
_ARRAY = re.compile(r"\b[a-z]+\d*\[[\d,]*\]")
SHAPE_LIMIT = 200
_OPERANDS = re.compile(r"%([\w.\-]+)")
_DTYPE_BITS = re.compile(r"[a-z]+(\d+)")
# a weight smaller than this is not worth a line: a norm's scale is
# re-read with the activations it scales, whatever its layout
RELAYOUT_MIN_ELEMENTS = 1 << 20

_HLO_DTYPES = {
    "bfloat16": "bf16", "float16": "f16", "float32": "f32",
    "float64": "f64", "int8": "s8", "int16": "s16", "int32": "s32",
    "int64": "s64", "uint8": "u8", "uint16": "u16", "uint32": "u32",
    "uint64": "u64", "bool": "pred",
}


def hlo_shape(dtype_name: str, shape: Iterable[int]) -> str:
    """``("bfloat16", (28, 1026, 64, 2, 128))`` as HLO prints it."""
    return (f"{_HLO_DTYPES.get(dtype_name, dtype_name)}"
            f"[{','.join(str(int(d)) for d in shape)}]")


def pool_shapes(arrays: Iterable[tuple[str, tuple[int, ...]]]) -> dict:
    """``{hlo shape: "pool" | "slab"}`` for the pool's arrays, each given
    as (dtype name, per-device shape): the whole array as the program's
    arguments have it (``[L, NB, ...]``) and as the unified step's layer
    loop carries it (flat over layer and block, ``[L*NB, ...]``), and one
    layer's slab of it with and without the leading 1."""
    arrays = [(name, tuple(shape)) for name, shape in arrays]
    out: dict[str, str] = {}
    for dtype_name, shape in arrays:
        out[hlo_shape(dtype_name, shape)] = "pool"
        out[hlo_shape(dtype_name, (shape[0] * shape[1],) + shape[2:])] = "pool"
    # after every "pool": a one-layer pool's slab IS its flat shape
    for dtype_name, shape in arrays:
        out.setdefault(hlo_shape(dtype_name, shape[1:]), "slab")
        out.setdefault(hlo_shape(dtype_name, (1,) + shape[1:]), "slab")
    return out


def _scope_of(op_name: str, scopes: frozenset[str]) -> str:
    for part in reversed(op_name.split("/")):
        if part in scopes:
            return part
    return ""


def _pool_kind(shape: str, pool: dict[str, str]) -> str:
    """"pool" / "slab" when EVERY array of the result is pool-shaped (a
    loop's whole carry is not), the largest kind among them."""
    arrays = _ARRAY.findall(shape)
    kinds = {pool.get(a) for a in arrays}
    if not arrays or None in kinds:
        return ""
    return "pool" if "pool" in kinds else "slab"


def op_map_from_hlo(text: str, scopes: Iterable[str],
                    pool: dict[str, str],
                    named: Iterable[tuple[str, str]] = ()) -> dict[str, list]:
    """``{instruction name: [scope, result shape, pool kind]}`` for the
    instructions of one compiled module that run as operations: those of
    the entry computation and of the loop bodies, conditions and branches
    reachable from it.  ``named``: ``(instruction-name prefix, scope)``
    for operations the compiler REWRITES and names itself, dropping the
    ``op_name`` they were traced under (a TPU turns ``lax.ragged_dot``
    into the custom calls ``%ragged-dot-none`` / ``%ragged-dot-metadata``
    with ``op_name="ragged-dot-none"``): such an operation is told by its
    own name, where its ``op_name`` names no scope.  Since PR 42 the
    expert layers of a traced TPU run reach ``lax.ragged_dot`` only
    through ``ops/moe.expert_row_tile``'s way out — the grouped matmul's
    probe refused by Mosaic, or expert matrices that are not whole lanes
    wide; a Pallas call keeps the ``op_name`` it was traced under and
    needs no such rescue."""
    scopes = frozenset(scopes)
    named = tuple(named)
    computations: dict[str, list[tuple]] = {}
    calls: dict[str, set[str]] = {}
    entry = current = None
    for line in text.splitlines():
        if not line.startswith((" ", "\t")):
            m = _COMPUTATION.match(line)
            current = m.group(2) if m else None
            if m:
                computations[current] = []
                calls[current] = set()
                if m.group(1):
                    entry = current
            continue
        if current is None:
            continue
        m = _INSTRUCTION.match(_LAYOUT.sub("", line))
        if not m:
            continue
        name, shape, opcode = m.groups()
        called = set(_CALLED.findall(line))
        for group in _BRANCHES.findall(line):
            called.update(c.strip().lstrip("%") for c in group.split(","))
        if opcode == "call":
            called.update(_TO_APPLY.findall(line))
        calls[current] |= called
        if opcode in _SILENT:
            continue
        op = _OP_NAME.search(line)
        scope = _scope_of(op.group(1), scopes) if op else ""
        if not scope:
            scope = next((sc for prefix, sc in named
                          if name.startswith(prefix)), "")
        computations[current].append((name, scope, shape[:SHAPE_LIMIT]))
    out: dict[str, list] = {}
    todo, seen = [entry] if entry else [], set()
    while todo:
        comp = todo.pop()
        if comp in seen or comp not in computations:
            continue
        seen.add(comp)
        todo.extend(calls[comp])
        for name, scope, shape in computations[comp]:
            out[name] = [scope, shape, _pool_kind(shape, pool)]
    return out


def _elements(array: str) -> int:
    n = 1
    for d in array[array.index("[") + 1:-1].split(","):
        n *= int(d) if d else 1
    return n


def _array_bytes(array: str) -> int:
    bits = _DTYPE_BITS.match(array)
    return _elements(array) * (int(bits.group(1)) if bits else 8) // 8


def weight_relayouts(text: str, argument: str = "params",
                     min_elements: int = RELAYOUT_MIN_ELEMENTS) -> list[tuple]:
    """The operations of one compiled module that write a WEIGHT out
    again in another layout, every execution: ``[(instruction name,
    result shape, bytes written, the parameter's label)]``.

    Such an operation stands in the ENTRY computation, is a ``copy``, a
    ``transpose`` or a loop fusion, reads a parameter traced as (part of)
    the jitted function's ``argument`` (``op_name="params['layers'][0]
    ['q_proj']"``) - directly, or through what only renames it: a
    ``bitcast``, or the ``copy-start`` / ``copy-done`` pair that
    prefetches it into another memory space - and its result has as many
    elements as that parameter (at least ``min_elements``).  So it is
    told by its OPERAND: a wide program's activations are as large, and
    the pool, the recurrent state and the packed operand are other
    arguments.  A fusion NESTED in another computation (the
    ``%bitcast_fusion`` inside a matmul's fusion reads the same weight)
    is part of the operation that calls it and moves nothing of its
    own."""
    entry = text.find("\nENTRY ")
    if entry < 0:
        return []
    weights: dict[str, tuple[str, int]] = {}  # name -> (label, elements)
    out = []
    for line in text[entry + 1:].splitlines()[1:]:
        if not line.startswith((" ", "\t")):
            break  # the entry computation's closing brace
        bare = _LAYOUT.sub("", line)
        m = _INSTRUCTION.match(bare)
        if not m:
            continue
        name, shape, opcode = m.groups()
        if opcode == "parameter":
            op = _OP_NAME.search(line)
            arrays = _ARRAY.findall(shape)
            if op and len(arrays) == 1 and re.match(
                    rf"{re.escape(argument)}\b", op.group(1)):
                weights[name] = (op.group(1).replace("\\'", "'"),
                                 _elements(arrays[0]))
            continue
        operands = _OPERANDS.findall(
            bare[m.end():].split(")", 1)[0])
        read = [weights[o] for o in operands if o in weights]
        if not read:
            continue
        if opcode in ("bitcast", "copy-start", "copy-done"):
            weights[name] = read[0]
            continue
        if opcode not in ("copy", "transpose") and not (
                opcode == "fusion" and "kind=kLoop" in line):
            continue
        arrays = _ARRAY.findall(shape)
        if len(arrays) != 1:
            continue
        for label, elements in read:
            if elements >= min_elements and _elements(arrays[0]) == elements:
                out.append((name, arrays[0], _array_bytes(arrays[0]), label))
                break
    return out


def trace_key(name: str, shape: str) -> str:
    """The key a profile's operation is looked up under: instruction
    name and result shape, cut the way ``benchmark/devtrace.short_name``
    cuts them (``%copy.89 bf16[28,1026,64,2,128]``).  Buckets of one
    step reuse instruction names; the shape tells most of them apart."""
    return f"%{name} {shape if len(shape) <= 60 else shape[:57] + '...'}"


def merge(bucket_maps: Iterable[dict[str, list]]) -> dict[str, list | None]:
    """The map as the dump carries it, over all buckets of a step:
    ``{trace key: [scope, pool kind]}``; a key that two buckets give
    different answers for maps to None (ambiguous: not attributed).  A
    reader looks a profile's operation up and does nothing else."""
    table: dict[str, list | None] = {}
    for ops in bucket_maps:
        for name, (scope, shape, kind) in ops.items():
            key, val = trace_key(name, shape), [scope, kind]
            if key in table and table[key] != val:
                table[key] = None
            else:
                table.setdefault(key, val)
    return table
