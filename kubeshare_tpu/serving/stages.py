"""Device time by stage: which instruction of a step program belongs to
which stage of a dispatch.

The step programs name their stages with ``jax.named_scope`` where the work
is written (``serving/paged.py``, ``models/transformer.py``, ``ops/moe.py``).
The optimized HLO of a compiled program keeps, on every instruction, the
``op_name`` it was traced under, scope path included
(``jit(kubeshare_mixed_step)/experts/while/body/closed_call/dot_general``),
and a device trace's ``XLA Ops`` events carry the instruction's name.  So
instruction name -> stage is a table the program can offer, and whoever reads
a trace books each operation's device time by it.  ``STAGE_OF_SCOPE`` is the
one vocabulary: a scope that is not in it is no stage, and an instruction
under none of them is ``unscoped`` — the cure for a large ``unscoped`` share
is a ``named_scope`` where the work is written, never a rule in a reader.

``ServingEngine.warmup()`` registers every program it warms here — a name
(``<kind>/<width>``: ``mixed/256``, ``diffusion/0``), the jitted function and
its arguments' shapes — and names the program on each
``kubeshare.engine.launch`` span.  Nothing is lowered or compiled until a
trace's reader asks for a table (``stage_table``): that lowers the program
from the shapes and takes the executable's text, which comes from the
persistent compilation cache where the warm-up left it there.  The registry
is process-wide, like the span ring, and holds no device array: a table can
be built after the engine is gone.  Engines of one process that warm a
program under one name are taken to run the same program (replicas of a
fleet do); the last registration stands.

The compile cache's key leaves instruction metadata out, so an executable
that another build of this program left in the cache carries THAT build's
scopes: after a change of the scopes the tables are that much out of date
until the cache has compiled the program anew.  The ``unscoped`` share says
how far a table can be trusted.
"""

from __future__ import annotations

import re
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax

__all__ = ["STAGES", "STAGE_OF_SCOPE", "UNSCOPED", "instruction_stages",
           "program_name", "register", "stage_of", "stage_table"]

UNSCOPED = "unscoped"
# scope -> stage.  `mla` holds the latent attention's projections; the finer
# scopes inside it (kv_write, kv_view) win, being innermost
STAGE_OF_SCOPE = {
    "attention": "attention", "kv_view": "attention", "qk_norm": "attention",
    "mla": "attention",
    "kv_write": "kv_write",
    "mlp": "ffn", "ffn": "ffn", "dense_ffn": "ffn", "shared_expert": "ffn",
    "experts": "experts", "router": "experts",
    "lm_head": "head", "sample": "head", "denoise_pick": "head",
    # a 'retention' block's own: the state's query, the unfolded rows'
    # weights, a fold, the log gate, the feature map (its q/k norms stay
    # `attention`'s, its tail's row writes `kv_write`'s, its SwiGLU `ffn`'s)
    "retention_state": "retention", "retention_tail": "retention",
    "retention_fold": "retention", "gate": "retention", "phi": "retention",
    # the gated short convolution of a model whose layers name it
    # (ops/short_conv.py): its projections, gates and filter, and the
    # state's read, shift and write (its attention layers keep
    # `attention` / `qk_norm` / `kv_write`, its feed-forwards `dense_ffn` /
    # `router` / `experts`)
    "short_conv": "conv", "conv_state": "conv",
}
STAGES = tuple(dict.fromkeys(STAGE_OF_SCOPE.values())) + (UNSCOPED,)

# `%fusion.12 = bf16[128,768]{1,0} fusion(%a, %b), ..., metadata={op_name="..."}`
# (the % went from newer printers; ROOT marks a computation's result):
# ROOT, the name, the result's shape, the opcode
_INSTRUCTION = re.compile(
    r"^\s+(ROOT\s+)?%?([\w.\-]+) = (\([^=]*?\)|\S+) ([\w\-]+)\(")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+) \(.*\{\s*$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLED = re.compile(r"\b(body|condition|calls|to_apply)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"\bbranch_computations=\{([^}]*)\}")
_TUPLE_INDEX = re.compile(r"\bindex=(\d+)")
_COMMENT = re.compile(r"/\*.*?\*/")
TRACED = "jit("  # what every op_name of traced work starts with


def stage_of(op_name: str) -> str:
    """The stage of the innermost recognised scope of ``op_name``."""
    for scope in reversed(op_name.split("/")):
        stage = STAGE_OF_SCOPE.get(scope)
        if stage is not None:
            return stage
    return UNSCOPED


def _operands(line: str, start: int) -> List[str]:
    """The names in the operand list that opens at ``line[start - 1]`` of
    an instruction's line without its comments."""
    depth, end = 1, start
    while end < len(line) and depth:
        depth += {"(": 1, ")": -1}.get(line[end], 0)
        end += 1
    names = []
    for piece in line[start:end - 1].split(","):
        words = piece.split()
        if words and "[" not in words[-1]:  # a shape's dimensions also split
            names.append(words[-1].lstrip("%"))
    return names


def instruction_stages(hlo_text: str) -> Dict[str, str]:
    """{instruction name: stage} of a compiled program's text
    (``compiled.as_text()``), every computation's instructions alike: a
    fusion takes its own ``op_name`` (its root's), a ``while`` its own, the
    instructions of its body theirs.

    What the COMPILER made carries no scope path — no ``op_name`` at all,
    or an argument's name: the prefetch of a weight's slice into fast
    memory and its concatenation, a copy to another layout, the loop of
    row updates a scatter was expanded into.  Such an instruction takes
    the stage of the first instruction that uses it (through a loop's
    carry into the next iteration, where it is made for that) and, where
    nothing in its computation does, of the instruction that calls its
    computation (the expanded scatter's ``while``)."""
    own: Dict[str, Optional[str]] = {}  # None: the compiler's
    users: Dict[str, List[str]] = {}
    operands: Dict[str, List[str]] = {}
    computation: Dict[str, str] = {}  # of an instruction
    caller: Dict[str, str] = {}  # of a computation: who calls it
    bodies: Dict[str, List[str]] = {}  # a tuple -> the loop bodies it enters
    parameters: Dict[str, List[str]] = {}  # of a computation
    reads: Dict[Tuple[str, int], List[str]] = {}  # (tuple, index) -> gte
    current = ""
    for line in hlo_text.splitlines():
        line = _COMMENT.sub("", line)  # /*index=5*/ among tuples' parts
        found = _INSTRUCTION.match(line)
        if found is None:
            header = _COMPUTATION.match(line)
            if header is not None:
                current = header.group(1)
            continue
        root, name, _, opcode = found.groups()
        op_name = _OP_NAME.search(line)
        own[name] = (stage_of(op_name.group(1))
                     if op_name and op_name.group(1).startswith(TRACED)
                     else None)
        computation[name] = current
        operands[name] = _operands(line, found.end())
        for operand in operands[name]:
            users.setdefault(operand, []).append(name)
        if opcode == "parameter":
            parameters.setdefault(current, []).append(name)
        elif opcode == "get-tuple-element":
            index = _TUPLE_INDEX.search(line[found.end():])
            reads.setdefault((operands[name][0], int(index.group(1))),
                             []).append(name)
        elif opcode == "tuple" and root:
            # a body's result is the next iteration's parameter
            bodies.setdefault(name, []).append(current)
        called = _CALLED.findall(line)
        for _, target in called:
            caller[target] = name
        for group in _BRANCHES.findall(line):
            for target in group.split(","):
                caller[target.strip().lstrip("%")] = name
        if opcode == "while":  # its operand tuple enters its body
            bodies.setdefault(operands[name][0], []).append(
                dict(called)["body"])

    def uses(name: str) -> List[str]:
        """Who uses ``name``; a tuple that enters a loop (its operand, or
        its body's result) hands it to the body's reads of its parameter."""
        out = []
        for user in users.get(name, ()):
            if user not in bodies or own[user] is not None:
                out.append(user)
                continue
            for index, operand in enumerate(operands[user]):
                if operand == name:
                    for body in bodies[user]:
                        for parameter in parameters.get(body, ()):
                            out += reads.get((parameter, index), ())
        return out

    def through_uses(name: str, visiting: frozenset) -> Optional[str]:
        """The stage of the first use of ``name`` that has one."""
        stage = own.get(name, UNSCOPED)
        if stage is not None or name in visiting:
            return stage  # None: a value a loop only carries came back
        for user in uses(name):
            stage = through_uses(user, visiting | {name})
            if stage is not None:
                return stage
        return None

    def resolve(name: str) -> str:
        stage = through_uses(name, frozenset())
        if stage is None:
            calling = caller.get(computation[name])
            stage = UNSCOPED if calling is None else resolve(calling)
        own[name] = stage
        return stage

    return {name: resolve(name) for name in own}


def program_name(kind: str, *widths: int) -> str:
    """``<kind>/<width>``: the widths that tell one compiled shape of a
    step program from another (the chunk's, a verify span's, a loop's
    depth), 0 where the kind has one shape."""
    return f"{kind}/{'x'.join(str(w) for w in widths) or 0}"


@dataclass
class _Program:
    fn: Any  # the jitted step program
    avals: Tuple  # its arguments as jax.ShapeDtypeStruct
    table: Optional[Dict[str, str]] = None


_programs: Dict[str, _Program] = {}  # one store or one look-up at a time


def _aval(x) -> jax.ShapeDtypeStruct:
    sharding = getattr(x, "sharding", None)
    if sharding is not None and len(sharding.device_set) == 1:
        sharding = None  # one device: the program is placed as it was called
    return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)


def register(name: str, fn, args: Tuple) -> None:
    """Keep what a table of the program ``fn(*args)`` can be built from."""
    _programs[name] = _Program(fn, jax.tree.map(_aval, args))


def stage_table(name: str) -> Optional[Dict[str, str]]:
    """{instruction name: stage} of the registered program ``name``, built
    on the first call (one lowering and one compile, from the persistent
    cache where the program is there) and kept; None for a name nothing
    registered.  For a trace's reader, after the window: never on the
    serving path."""
    program = _programs.get(name)
    if program is None:
        return None
    if program.table is None:
        compiled = program.fn.lower(*program.avals).compile()
        program.table = instruction_stages(compiled.as_text())
    return program.table
