"""The main path's programs compile for the real chip — without the chip.

The TPU compiler is installed wherever libtpu is, and compiles for a chip
that is described and not attached (``jax.experimental.topologies``).  The
cells' step programs are also read by the program's own table of stages
(``serving/stages.py``: ``_stages_hold``).  Each case below compiles one program of ``chip_smoke.py``'s main path at the
flagship width for one v5e device and checks what only the real compiler
can say: the Pallas kernel survived lowering (``tpu_custom_call`` — a
silent demotion to the XLA reference fails here), and the program fits the
chip's memory.  Nothing runs, so none of this is a chip result.

Skipped where the topology cannot be described (no libtpu).  The persistent
compilation cache is off around the cases: a compile for a described device
is written to it but cannot be read back without the chip.
"""

import os
import sys

import jax
import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from kubeshare_tpu.models.transformer import (  # noqa: E402
    TransformerConfig, transformer_init)
from kubeshare_tpu.ops.attention import (  # noqa: E402
    _flash_attention, _flash_forward, default_blocks)
from kubeshare_tpu.serving import paged, stages  # noqa: E402
from kubeshare_tpu.serving.packed_args import PackedProgram  # noqa: E402
from kubeshare_tpu.serving.paged import (  # noqa: E402
    KEY_BLOCK, paged_decode_loop, paged_decode_span, paged_decode_step,
    paged_diffusion_pass, paged_mixed_diffusion_step, paged_mixed_step,
    paged_prefill_step)

V5E_HBM_BYTES = 16 << 30


@pytest.fixture(scope="module")
def one_chip():
    """A described (not attached) v5e device, compile cache off."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # describing a topology takes libtpu's one-process lockfile; xdist
    # workers (and any other test that loads libtpu) would abort each
    # other.  Nothing here opens a chip, so the lock guards nothing.
    os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu / no compiler on this host
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_was_on)
    compilation_cache.reset_cache()


def _lower(fn, args, sharding, donate_argnums=()):
    shaped = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        args)
    return jax.jit(fn, donate_argnums=donate_argnums).lower(*shaped)


def _compile(fn, args, sharding, donate_argnums=()):
    return _lower(fn, args, sharding, donate_argnums).compile()


# (cell, kind, kernel mode) -> the compiled step program: a cell's mixed
# program compiles in 20-35 s, and two tests read it
_STEP_PROGRAMS = {}


def _compile_step(fn, args, sharding, donate_argnums=(1, 2), key=None):
    """``fn`` compiled as the engine compiles a step program since PR 45
    (``engine._step_program``: a ``PackedProgram``): called with the
    arguments' shapes in ``fn``'s own order, it takes every host argument —
    every array after the pool that is not donated — as ONE ``uint32``
    buffer and slices it apart inside.  Kept under ``key`` where one is
    given."""
    key = key and (*key, paged._kernel_mode())
    if key not in _STEP_PROGRAMS:
        shaped = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=sharding), args)
        compiled = PackedProgram("kubeshare_step", fn,
                                 donate_argnums).lower(*shaped).compile()
        if key is None:
            return compiled
        _STEP_PROGRAMS[key] = compiled
    return _STEP_PROGRAMS[key]


def _flash_args(b, h, h_kv, s, d):
    q = jax.ShapeDtypeStruct((b, h, s, d), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((b, h_kv, s, d), jnp.bfloat16)
    return q, kv, kv


FLASH_SHAPES = chip_smoke.FULL.kernel_shapes  # MHA and GQA, s=2048, d=128


@pytest.mark.parametrize("shape", FLASH_SHAPES, ids=str)
def test_flash_forward_compiles_as_kernel(one_chip, shape):
    block_q, block_k = default_blocks(shape[3])
    compiled = _compile(
        lambda q, k, v: _flash_forward(q, k, v, True, block_q, False,
                                       block_k=block_k),
        _flash_args(*shape), one_chip)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("shape", FLASH_SHAPES, ids=str)
def test_flash_backward_compiles_as_kernel(one_chip, shape):
    block_q, block_k = default_blocks(shape[3])

    def loss(q, k, v):
        out = _flash_attention(q, k, v, True, block_q, False, None, block_k)
        return out.astype(jnp.float32).sum()

    compiled = _compile(jax.grad(loss, argnums=(0, 1, 2)),
                        _flash_args(*shape), one_chip)
    # forward + dkv + dq kernels
    assert compiled.as_text().count("tpu_custom_call") >= 3


def _serving_shapes():
    """The serving model and pool of chip_smoke.FULL, as shapes only."""
    config = TransformerConfig(dtype=jnp.bfloat16, **chip_smoke.FULL.model)
    # weights in the model's dtype, as chipbench/weights.py serves them:
    # over float32 masters a loop program hoists a bf16 copy of every
    # weight into its temporaries (ROADMAP 1.11), which would hide what
    # the pool costs there
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, config.dtype),
        jax.eval_shape(
            lambda: transformer_init(jax.random.PRNGKey(0), config)))
    e = chip_smoke.FULL.engine
    pool = jax.ShapeDtypeStruct(
        (config.n_layers, e["num_blocks"], config.kv_heads, e["block_size"],
         config.head_dim), config.dtype)
    lanes, width = e["num_slots"], e["max_request_len"] // e["block_size"]
    return config, params, pool, lanes, width, e["prefill_chunk"]


def _i32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32)


def _decode_step_case():
    config, params, pool, s, t, _ = _serving_shapes()
    fn = lambda w, pk, pv, tables, lengths, active, tokens: \
        paged_decode_step(w, config, pk, pv, tables, lengths, active, tokens)
    return fn, (params, pool, pool, _i32(s, t), _i32(s),
                jax.ShapeDtypeStruct((s,), bool), _i32(s))


def _prefill_case():
    config, params, pool, _, t, chunk = _serving_shapes()
    fn = lambda w, pk, pv, tables, starts, active, tokens, last: \
        paged_prefill_step(w, config, pk, pv, tables, starts, active,
                           tokens, last)
    return fn, (params, pool, pool, _i32(1, t), _i32(1),
                jax.ShapeDtypeStruct((1,), bool), _i32(1, chunk), _i32(1))


def _greedy_pick(logits, temps, keys):
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def _decode_loop_case():
    config, params, pool, s, t, _ = _serving_shapes()
    span, k_units = 4, 4
    fn = lambda w, pk, pv, tables, lengths, active, tokens, temps, keys, \
        budgets: paged_decode_loop(
            w, config, _greedy_pick, span, k_units, None, pk, pv, tables,
            lengths, active, tokens, temps, keys, budgets)
    return fn, (params, pool, pool, _i32(s, t), _i32(s),
                jax.ShapeDtypeStruct((s,), bool), _i32(s),
                jax.ShapeDtypeStruct((s,), jnp.float32),
                jax.ShapeDtypeStruct((s, span * k_units, 2), jnp.uint32),
                _i32(s))


def _mixed_step_case():
    config, params, pool, s, t, chunk = _serving_shapes()
    span = 4
    fn = lambda w, pk, pv, *rest: paged_mixed_step(
        w, config, _greedy_pick, span, None, pk, pv, *rest)
    return fn, (params, pool, pool,
                # the one filling lane: table, start, chunk, last row,
                # temperature, key
                _i32(1, t), _i32(1), _i32(1, chunk), _i32(1),
                jax.ShapeDtypeStruct((1,), jnp.float32),
                jax.ShapeDtypeStruct((1, 2), jnp.uint32),
                # the decode lanes: tables, lengths, active, tokens,
                # temperatures, keys, budgets
                _i32(s, t), _i32(s), jax.ShapeDtypeStruct((s,), bool),
                _i32(s), jax.ShapeDtypeStruct((s,), jnp.float32),
                jax.ShapeDtypeStruct((s, span, 2), jnp.uint32), _i32(s))


@pytest.mark.parametrize("case", [_decode_step_case, _prefill_case,
                                  _decode_loop_case, _mixed_step_case],
                         ids=["paged_decode_step", "paged_prefill_step",
                              "paged_decode_loop", "paged_mixed_step"])
def test_serving_program_compiles_and_fits(one_chip, case):
    """Compiled as the engine compiles it (``engine._step_program``: the
    pool halves donated), the program fits the chip, and its temporaries
    hold no second pool: the K/V rows are scattered into the donated
    buffers.  A step that restacks the pool or cuts layer slabs out of it
    needs 1.1-1.3 x BOTH halves of temporaries (PR 25)."""
    fn, args = case()
    memory = _compile_step(fn, args, one_chip).memory_analysis()
    resident = (memory.argument_size_in_bytes + memory.temp_size_in_bytes
                + memory.output_size_in_bytes)
    assert resident < V5E_HBM_BYTES, memory
    pool_half = args[1].size * args[1].dtype.itemsize
    assert memory.temp_size_in_bytes < pool_half // 2, memory


def _cell_case(name, kind):
    """The decode-span or mixed program of ``chipbench/configs/<name>.json``
    at its published widths, with its pool, as shapes only, compiled as
    the engine compiles them (a routed block returns its routing counts)."""
    import json

    from kubeshare_tpu.serving.kv_blocks import kv_row_layout

    with open(os.path.join(REPO, "chipbench", "configs",
                           name + ".json")) as f:
        config_file = json.load(f)
    tc = dict(config_file["transformer_config"])
    tc["dtype"] = jnp.dtype(tc["dtype"])
    config = TransformerConfig(**tc)
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, config.dtype),
        jax.eval_shape(
            lambda: transformer_init(jax.random.PRNGKey(0), config)))
    e = config_file["engine"]
    layout = kv_row_layout(config)
    num_blocks = e["pool_bytes"] // (
        layout.values_per_row() * 2 * e["block_size"]) + 1
    pool_k, pool_v = (
        jax.ShapeDtypeStruct(shape[:1] + (num_blocks,) + shape[1:],
                             config.dtype)
        for shape in layout.block_shapes(e["block_size"]))
    s, t = e["num_slots"], e["max_request_len"] // e["block_size"]
    span = 4
    routing = {"routing": True} if config.routed else {}
    if kind in ("diffusion", "mixed_diffusion"):
        # one pass over every lane's block, alone or beside a chunk
        rows = jax.ShapeDtypeStruct((s, config.diffusion_block), bool)
        lanes = (_i32(s, t), _i32(s), jax.ShapeDtypeStruct((s,), bool),
                 _i32(s, config.diffusion_block), rows, rows, _i32(s))
        if kind == "diffusion":
            fn = lambda w, pk, pv, *rest: paged_diffusion_pass(
                w, config, pk, pv, *rest, **routing)
            return config, fn, (params, pool_k, pool_v, *lanes)
        fn = lambda w, pk, pv, *rest: paged_mixed_diffusion_step(
            w, config, pk, pv, *rest, **routing)
        return config, fn, (
            params, pool_k, pool_v, _i32(1, t), _i32(1),
            _i32(1, e["prefill_chunk"]), _i32(1), *lanes)
    lanes = (_i32(s, t), _i32(s), jax.ShapeDtypeStruct((s,), bool), _i32(s),
             jax.ShapeDtypeStruct((s,), jnp.float32),
             jax.ShapeDtypeStruct((s, span, 2), jnp.uint32), _i32(s))
    if kind == "decode":
        fn = lambda w, pk, pv, *rest: paged_decode_span(
            w, config, _greedy_pick, span, None, pk, pv, *rest, **routing)
        return config, fn, (params, pool_k, pool_v, *lanes)
    fn = lambda w, pk, pv, *rest: paged_mixed_step(
        w, config, _greedy_pick, span, None, pk, pv, *rest, **routing)
    return config, fn, (
        params, pool_k, pool_v, _i32(1, t), _i32(1),
        _i32(1, e["prefill_chunk"]), _i32(1),
        jax.ShapeDtypeStruct((1,), jnp.float32),
        jax.ShapeDtypeStruct((1, 2), jnp.uint32), *lanes)


def _compiled_in_place(fn, args, sharding, resident_limit, key=None):
    """``fn`` compiled with its pool donated: it fits, the pool is
    aliased (written in place) and no pool-shaped array is copied.
    Returns (memory analysis, program text)."""
    import re

    compiled = _compile_step(fn, args, sharding, key=key)
    memory = compiled.memory_analysis()
    resident = (memory.argument_size_in_bytes + memory.temp_size_in_bytes
                + memory.output_size_in_bytes - memory.alias_size_in_bytes)
    assert resident < resident_limit, memory
    pool_bytes = sum(a.size * a.dtype.itemsize for a in args[1:3])
    assert memory.alias_size_in_bytes >= pool_bytes, memory
    text = compiled.as_text()
    pool_shapes = {",".join(map(str, a.shape)) for a in args[1:3]} \
        | {",".join(map(str, a.shape[:2] + a.shape[3:])) for a in args[1:3]}
    for shape in pool_shapes:
        assert not re.search(rf"bf16\[{shape}\][^ ]* copy\(", text), shape
    return memory, text


_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s32": 4,
                "u32": 4, "f32": 4}
# what takes no device time of its own: what holds others, what names data
_NO_WORK = {"parameter", "constant", "get-tuple-element", "tuple", "bitcast",
            "while", "conditional", "call"}


def _stages_hold(config, args, text):
    """The program's own table of stages (``serving/stages.py``) over the
    compiled text: every paged kernel call is the attention's, every
    instruction that reads an array with the experts' axis is the experts',
    and of the result bytes of the instructions that run as operations of
    their own (no fused computation's inside, nothing that only holds or
    names data, and no view of the donated pool, which a write returns
    whole) less than a tenth is under no stage."""
    import math
    import re

    table = stages.instruction_stages(text)
    fused = set(re.findall(r"\bcalls=%?([\w.\-]+)", text))
    # an axis only the pool's views have (a pool by kind: one a kind)
    blocks = {str(a.shape[1]) for a in jax.tree.leaves(args[1])}
    stacked = {f"{config.held_experts},{a},{b}"
               for a, b in ((config.d_model, config.expert_d_ff),
                            (config.expert_d_ff, config.d_model))} \
        if config.routed else set()
    shapes, current, by_stage, kernels = {}, None, {}, 0
    for line in text.splitlines():
        line = re.sub(r"/\*.*?\*/", "", line)
        found = stages._INSTRUCTION.match(line)
        if found is None:
            header = stages._COMPUTATION.match(line)
            current = header.group(1) if header else current
            continue
        _, name, shape, opcode = found.groups()
        shapes[name] = shape
        if 'custom_call_target="tpu_custom_call"' in line:
            kernels += 1
            assert table[name] == ("experts" if name.startswith(EXPERTS_KERNEL)
                                   else "attention"), line[:200]
        reads = {dims for operand in stages._operands(line, found.end())
                 for dims in re.findall(r"\[([0-9,]+)\]",
                                        shapes.get(operand, ""))}
        if reads & stacked and opcode not in _NO_WORK \
                and current not in fused:
            assert table[name] == "experts", line[:200]
        if current in fused or opcode in _NO_WORK:
            continue
        size = sum(
            _DTYPE_BYTES.get(dtype, 4) * math.prod(map(int, dims.split(",")))
            for dtype, dims in re.findall(r"\b([a-z]+[0-9]*)\[([0-9,]+)\]",
                                          shape)
            if not blocks & set(dims.split(",")))
        by_stage[table[name]] = by_stage.get(table[name], 0) + size
    assert kernels > 0 and set(by_stage) <= set(stages.STAGES)
    assert by_stage.get("unscoped", 0) < 0.1 * sum(by_stage.values()), \
        by_stage
    return by_stage


# temporaries of the same programs on the key-block loop over a staged
# slab (the parent of PR 30, compiled the same way).  The mixed programs'
# are those of the fused first step (PR 44): 297,772,032 and 854,657,024 B
# as first compiled, against 243,377,664 and 757,508,608 B back to back.
# The span's scan has the compiler lay every layer's `wq` and `wo` out anew
# before it (8.4 MB each at 1 B: 201 MB), and those copies, made after the
# chunk's pass when the chunk had one of its own, now stand through the
# first step, which reads them too; the rows' own arrays are 272 or 288
# rows where they were 256.  No pool-shaped array is copied.
LOOP_TEMPORARIES = {
    ("starcoderbase-1b", "decode"): 230_034_432,
    ("starcoderbase-1b", "mixed"): 300 << 20,
    ("starcoder2-3b", "decode"): 719_114_240,
    ("starcoder2-3b", "mixed"): 860 << 20,
}


def _kernel_calls_in_entry(text):
    """(in the entry computation, in every other) Pallas kernel calls of a
    compiled program: a mixed program's fused first step is written out in
    the entry computation, its span's remaining steps are a loop's body."""
    entry = text[text.index("\nENTRY "):]
    entry = entry[:entry.index("\n}")]
    call = 'custom_call_target="tpu_custom_call"'
    return entry.count(call), text.count(call) - entry.count(call)


@pytest.mark.parametrize("kind", ["decode", "mixed"])
@pytest.mark.parametrize("name", ["starcoderbase-1b", "starcoder2-3b"])
def test_dense_cell_program_attends_by_key_block(one_chip, monkeypatch, name,
                                                 kind):
    """The dense cells' decode-span and mixed programs at their published
    widths, built as on the chip (the backend is the one thing a
    described device cannot tell ``_attend_view``): they fit with the
    pool written in place; the decode lanes attend through the paged
    kernel, one call a layer, so no layer's slab is staged, no key block
    is gathered or scored for every lane, and nothing is as long as the
    view (``max_request_len``, 4096 rows); the chunk's one lane still
    attends a key block at a time; and the temporaries are no more than
    the loop's.  The mixed program's first step carries the chunk's rows
    and the lanes' first rows through one layer loop (PR 44): its lanes
    attend through the kernel there too, one call a layer beside the
    chunk's key-block loop, and the span's other steps are the scan's one
    call a layer."""
    import re

    monkeypatch.setattr(paged, "_kernel_mode", lambda: "compiled")
    config, fn, args = _cell_case(name, kind)
    memory, text = _compiled_in_place(fn, args, one_chip, V5E_HBM_BYTES,
                                      key=(name, kind))
    lanes, table_width = args[-7].shape
    _, blocks, h_kv, block_size, d = args[1].shape
    assert table_width * block_size == 4096
    assert not re.search(r"(f32|bf16)\[[0-9,]*\b4096\b[0-9,]*\]", text)
    assert _kernel_calls_in_entry(text) == (
        config.n_layers * (kind == "mixed"), config.n_layers)
    assert not re.search(rf"bf16\[{blocks},{h_kv},{block_size},{d}\]", text)
    assert not re.search(rf"f32\[{lanes},[0-9,]*,{KEY_BLOCK}\]", text)
    chunk_scores = re.search(rf"f32\[[0-9,]*\b256,{KEY_BLOCK}\]", text)
    assert bool(chunk_scores) == (kind == "mixed")
    assert memory.temp_size_in_bytes <= LOOP_TEMPORARIES[name, kind], memory
    by_stage = _stages_hold(config, args, text)
    assert {"attention", "ffn", "kv_write", "head"} <= set(by_stage)


EXPERTS_KERNEL = "grouped_experts"  # ops/moe.py: the experts' tiles


def _kernel_calls(text):
    """(the attention's, the experts') Pallas kernel calls of a compiled
    program, told apart by the kernels' names."""
    import re

    names = re.findall(
        r"^\s+(?:ROOT\s+)?%?([\w.\-]+) = [^\n]*"
        r'custom_call_target="tpu_custom_call"', text, re.M)
    experts = sum(name.startswith(EXPERTS_KERNEL) for name in names)
    return len(names) - experts, experts


def _experts_through_the_grouped_kernel(config, text, calls):
    """Each expert layer's tiles are ONE kernel call a pass over the
    layers (a mixed program's text has two: its first step's, over the
    chunk's rows and the lanes' together, and the scan body's),
    ``calls`` in all, and the tile loop is gone: no float32 accumulator
    of every row is carried (``f32[rows + 1, d]`` under the experts' scope:
    a fused first step's head reads 32 lanes' rows and the chunk's one),
    no tile of rows is gathered a trip."""
    import re

    assert _kernel_calls(text)[1] == calls
    carried = re.compile(rf"f32\[(33|129|513|545),{config.d_model}\]")
    assert not [line[:160] for line in text.splitlines()
                if carried.search(line) and "/experts/" in line]


# the routed cells' expert layers: (rows, d, expert width, experts held,
# router outputs, top_k) -> (tile, width block)
EXPERT_LAYERS = {
    "sdar-pass": ((128, 2048, 768, 128, 128, 8), (16, 768)),
    "sdar-chunk": ((512, 2048, 768, 128, 128, 8), (64, 768)),
    "joyai-step": ((32, 2048, 768, 256, 256, 8), (16, 768)),
    "joyai-chunk": ((512, 2048, 768, 256, 256, 8), (32, 768)),
    "lcf-step": ((32, 6144, 2048, 16, 768, 12), (16, 512)),
    "lcf-chunk": ((512, 6144, 2048, 16, 768, 12), (16, 512)),
    # a third expert shape: 18.9 MB an expert, whole and twice in 37.7 MB
    "lfm2-step": ((32, 2048, 1536, 64, 64, 4), (16, 1536)),
    "lfm2-chunk": ((512, 2048, 1536, 64, 64, 4), (64, 1536)),
}


@pytest.mark.parametrize("case", sorted(EXPERT_LAYERS))
def test_grouped_experts_compile_as_kernel(one_chip, case):
    """One expert layer of each routed cell at its published widths, over
    a decode step's (or a pass's) rows and over a chunk's: the tiles are
    one Pallas kernel the chip's compiler takes — an expert's three
    matrices whole and twice in fast memory (18.9 MB) or, at
    ``longcat-flash-chat``'s 75 MB an expert, in 512-column blocks
    (37.7 MB), beside the rows and the result resident for the whole grid
    — no tile loop is left, and the temporaries are the rows' and the
    grouping's alone."""
    from kubeshare_tpu.ops.moe import (expert_tile_rows, expert_width_block,
                                       routed_experts_apply)

    (n, d, f, held, outputs, top_k), (tile, width) = EXPERT_LAYERS[case]
    bf16 = jnp.bfloat16
    moe = {"router": jnp.zeros((d, outputs), bf16),
           "w_gate": jax.ShapeDtypeStruct((held, d, f), bf16),
           "w_up": jax.ShapeDtypeStruct((held, d, f), bf16),
           "w_down": jax.ShapeDtypeStruct((held, f, d), bf16)}
    assert expert_tile_rows(n, top_k, outputs) == tile
    assert expert_width_block(moe) == width
    moe["router"] = jax.ShapeDtypeStruct((d, outputs), bf16)
    compiled = _compile(
        lambda moe, y, live: routed_experts_apply(
            moe, y, n_routed=outputs - (256 if outputs == 768 else 0),
            top_k=top_k, scale=1.0, live=live, kernel_mode="compiled"),
        (moe, jax.ShapeDtypeStruct((n, d), bf16),
         jax.ShapeDtypeStruct((n,), bool)), one_chip)
    text = compiled.as_text()
    assert _kernel_calls(text) == (0, 1)
    assert " while(" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 16 << 20


def _attends_through_the_latent_kernel(config, text, kind):
    """The decode step's attention sub-layers each run the paged kernel
    (the span is a loop of one step, the chunk attends by key block), and
    no key block of every lane's table entries is gathered: 32 lanes x 32
    entries of one 16-row page, latent or rotary.  A mixed program's fused
    first step (PR 44), written out before the loop, runs the kernel for
    its lanes' rows too, a call a sub-layer beside the chunk's key-block
    loop; the kernels left are the experts'."""
    import re

    first, rest = _kernel_calls_in_entry(text)
    experts = _kernel_calls(text)[1]
    assert first + rest - experts == config.attn_sublayers * (
        2 if kind == "mixed" else 1)
    assert first == (config.attn_sublayers + config.expert_layers) * (
        kind == "mixed")
    # the chunk's 512 queries' scores over a key block: still the loop
    chunk_scores = re.search(
        rf"f32\[1,{config.n_heads},512,{KEY_BLOCK}\]", text)
    assert bool(chunk_scores) == (kind == "mixed")
    assert not re.search(r"bf16\[1024,16,(512|128)\]", text)


def _rows_written_whole(args, text):
    """No row-at-a-time loop is left in ``kv_write`` (PR 42).  A scatter
    whose window is narrower than the array's row — the 64 values of one
    rotary key in the 128-wide packed row — is expanded into a ``while``
    of one ``dynamic-update-slice`` of the V array an update (4.5-5.0 us a
    row on a v5e; a whole row 0.09).  The program's table of stages gives
    such a ``while`` the stage of the write it stands for, as it would a
    loop written under the scope; the decode span's scan, whose body holds
    every stage of a step, and the chunk's key-block loops have another.
    So: no ``while`` is ``kv_write``'s, and no ``dynamic-update-slice``
    makes an array of the V array's shape."""
    import re

    table = stages.instruction_stages(text)
    whiles = [found.group(2) for found in map(
        stages._INSTRUCTION.match,
        stages._COMMENT.sub("", text).splitlines())
        if found and found.group(4) == "while"]
    assert whiles  # the span's scan at least: the text was read
    assert not [name for name in whiles if table[name] == "kv_write"]
    v_shape = ",".join(map(str, args[2].shape))
    assert not re.search(rf"bf16\[{v_shape}\][^ ]* dynamic-update-slice\(",
                         text)


LCF_DECODE, LCF_MIXED = 157_534_208, 364_600_832


@pytest.mark.parametrize("kind,temporaries", [("decode", LCF_DECODE),
                                              ("mixed", LCF_MIXED)])
def test_latent_block_program_compiles_and_fits(one_chip, monkeypatch, kind,
                                                temporaries):
    """One expert-parallel rank at the published widths, built as on the
    chip, fits it with its pool; the pool is written in place (no copy of
    a pool-shaped array); the decode lanes attend through the paged
    latent kernel, one call a sub-layer, and no key block of all 32 lanes
    is gathered; and the expert layer's work follows the routing: nothing
    in the program has a row of every lane or chunk row for each of the
    16 held experts (``rows x 16`` expert rows a layer is what a
    capacity-pinned dispatch would multiply); and the packed rotary rows
    are written whole, no loop of row updates left in ``kv_write``
    (``_rows_written_whole``).  Its temporaries were
    158,880,768 / 362,003,456 B until the expert layer took the rows'
    liveness (the masks of the dead rows and the three counts more a
    step: 388,608 / 612,864 B), then 159,269,376 / 362,616,320 B on the
    key-block loop; with the kernel (PR 33) the decode lanes' gathered
    key blocks and their scores go: 159,269,376 -> 158,672,384 (decode
    span), 362,616,320 -> 355,068,416 (mixed); with the experts' tiles
    in the grouped kernel (PR 39: one call a layer and pass, the matrices
    in 512-column blocks) the loop's accumulator and gathered tiles go:
    157,574,656 / 350,972,416; with the packed rotary rows written whole
    (PR 42) the 16 row-at-a-time loops of the write and what they carried
    go: 157,404,160 / 348,711,936; with the chunk riding the span's first
    pass (PR 44: ONE layer loop over the chunk's 512 rows and the lanes' 32,
    the experts' tiles of both one call a layer) the first step's arrays
    are 544 rows where the chunk's were 512: 364,149,760 the mixed
    program; with the host arguments one buffer sliced apart inside
    (PR 45) 157,534,208 / 364,600,832: 0.13 / 0.45 MB of slices."""
    monkeypatch.setattr(paged, "_kernel_mode", lambda: "compiled")
    config, fn, args = _cell_case("longcat-flash-chat", kind)
    memory, text = _compiled_in_place(fn, args, one_chip, 15 * 10 ** 9,
                                      key=("longcat-flash-chat", kind))
    assert memory.temp_size_in_bytes == temporaries, memory
    _no_row_of_every_expert(config, args, text)
    _attends_through_the_latent_kernel(config, text, kind)
    _experts_through_the_grouped_kernel(
        config, text, config.expert_layers * (2 if kind == "mixed" else 1))
    _rows_written_whole(args, text)
    by_stage = _stages_hold(config, args, text)
    assert {"attention", "ffn", "experts", "kv_write", "head"} \
        <= set(by_stage)


def _no_row_of_every_expert(config, args, text):
    import re

    held, d, f = config.held_experts, config.d_model, config.expert_d_ff
    rows = "|".join(str(a.shape[0] * a.shape[-1]) for a in (args[3], args[5])
                    if len(a.shape) == 2) + "|32|512|544"
    assert not re.search(rf"\[{held},({rows}),({d}|{f})\]", text)


@pytest.mark.parametrize("kind", ["decode", "mixed"])
def test_single_latent_layers_program_compiles_and_fits(one_chip, monkeypatch,
                                                        kind):
    """The first pipeline stage of ``joyai-llm-flash`` at the published
    widths — five single latent layers, the last four with all 256 routed
    experts and the shared one, the whole vocabulary — built as on the
    chip, fits it under 15 GB with its pool in place (an odd count of
    64-wide rotary rows, packed two to a row); its decode lanes attend
    through the paged latent kernel, one call a layer; and nothing in the
    program has ``rows x 256`` expert rows a layer: the only arrays with
    the experts' axis are the experts' own matrices.  The packed rotary
    rows are written whole (PR 42): no loop of row updates is left in
    ``kv_write`` (``_rows_written_whole``)."""
    import re

    monkeypatch.setattr(paged, "_kernel_mode", lambda: "compiled")
    config, fn, args = _cell_case("joyai-llm-flash", kind)
    assert (config.attn_sublayers, config.expert_layers) == (5, 4)
    assert args[1].shape[0] == 5 and args[2].shape[0] == 3
    memory, text = _compiled_in_place(fn, args, one_chip, 15 * 10 ** 9,
                                      key=("joyai-llm-flash", kind))
    assert memory.temp_size_in_bytes < 128 << 20, memory
    _no_row_of_every_expert(config, args, text)
    _attends_through_the_latent_kernel(config, text, kind)
    _experts_through_the_grouped_kernel(
        config, text, config.expert_layers * (2 if kind == "mixed" else 1))
    with_experts = set(re.findall(r"(?:bf16|f32)\[256,[0-9,]+\]", text))
    assert with_experts <= {"bf16[256,2048,768]", "bf16[256,768,2048]"}, \
        with_experts
    _rows_written_whole(args, text)
    by_stage = _stages_hold(config, args, text)
    assert {"attention", "ffn", "experts", "kv_write", "head"} \
        <= set(by_stage)


DIFFUSION_TEMPORARIES = {"diffusion": 32 << 20, "mixed_diffusion": 64 << 20}
# a key block of all 32 lanes x 32 table entries, 4 KV heads x 16 rows x 128
DIFFUSION_KEY_BLOCK = r"bf16\[1024,4,16,128\]"


def _diffusion_case(one_chip, kind):
    """The pass (alone, or beside a 512-token chunk) of
    ``sdar-30b-a3b-chat``'s first pipeline stage, compiled with its 3 GiB
    pool in place: (config, program text)."""
    import re

    config, fn, args = _cell_case("sdar-30b-a3b-chat", kind)
    assert (config.n_layers, config.expert_layers, config.head_dim) \
        == (6, 6, 128)
    assert args[1].shape == args[2].shape == (6, 16385, 4, 16, 128)
    memory, text = _compiled_in_place(fn, args, one_chip, 15 * 10 ** 9,
                                      key=("sdar-30b-a3b-chat", kind))
    assert memory.temp_size_in_bytes < DIFFUSION_TEMPORARIES[kind], memory
    with_experts = set(re.findall(r"(?:bf16|f32)\[128,[0-9,]+\]", text))
    assert {"bf16[128,2048,768]", "bf16[128,768,2048]"} <= with_experts
    assert not re.search(r"\[128,(128|512|640),(2048|768)\]", text)
    assert not re.search(r"f32\[[0-9,]*\b8192\b[0-9,]*\]", text)
    if 'custom_call_target="tpu_custom_call"' in text:  # built as on the chip
        by_stage = _stages_hold(config, args, text)
        assert {"attention", "experts", "kv_write", "head"} <= set(by_stage)
    return config, text


@pytest.mark.parametrize("kind", list(DIFFUSION_TEMPORARIES))
def test_diffusion_program_compiles_and_fits(one_chip, monkeypatch, kind):
    """The first pipeline stage of ``sdar-30b-a3b-chat`` at the published
    widths — six layers of GQA 32 to 4 at head width 128, all 128 routed
    experts of each, the whole vocabulary — built as on the chip: one pass
    over 32 lanes' blocks of 4 rows, alone and beside a 512-token chunk,
    fits under 15 GB with its 3 GiB pool written in place.  The lanes' 4
    rows are one aligned block with one reach and attend through the paged
    kernel, one call a layer (a query group of 4 rows x 8 heads a KV
    head), so no key block of all 32 lanes is gathered
    (``bf16[1024,4,16,128]``); the chunk's one lane, rows of 128 blocks,
    still attends a key block at a time.  Nothing in the program has
    ``rows x 128`` expert rows a layer, and the only array as wide as the
    vocabulary beside the head's matrices is the pass's own float32
    logits (78 MB) and what the pick makes of them.  Temporaries read
    12,980,736 B (the pass alone) and 26,047,488 B (mixed) on the
    key-block loop (PR 35) and 11,934,720 B / 25,079,808 B
    through the kernel (PR 37), with the pass ahead of the chunk; chunk
    first, the compiler copied the pool four times (4,862,290,944 B)."""
    import re

    monkeypatch.setattr(paged, "_kernel_mode", lambda: "compiled")
    config, text = _diffusion_case(one_chip, kind)
    assert _kernel_calls(text)[0] == config.n_layers
    # the chunk beside a pass leaves K/V alone (no head reads it): its
    # last layer's experts feed nothing and are not in the program
    _experts_through_the_grouped_kernel(
        config, text, {"diffusion": 6, "mixed_diffusion": 6 + 5}[kind])
    assert not re.search(DIFFUSION_KEY_BLOCK, text)
    chunk_scores = re.search(rf"f32\[[0-9,]*\b512,{KEY_BLOCK}\]", text)
    assert bool(chunk_scores) == (kind == "mixed_diffusion")


def test_diffusion_program_off_the_chip_gathers_key_blocks(one_chip):
    """What the assertions above tell apart: the same pass built for no
    TPU (``_kernel_mode()`` is None here) runs the key-block loop — no
    kernel, and the key blocks of all 32 lanes gathered."""
    import re

    _, text = _diffusion_case(one_chip, "diffusion")
    assert 'custom_call_target="tpu_custom_call"' not in text
    assert re.search(DIFFUSION_KEY_BLOCK, text)


@pytest.mark.parametrize("name", ["longcat-flash-chat", "joyai-llm-flash"])
def test_latent_program_off_the_chip_gathers_key_blocks(one_chip, name):
    """What the two assertions above tell apart: the same decode span
    built for no TPU (``_kernel_mode()`` is None here) runs the key-block
    loop — no kernel, and the key blocks of all 32 lanes gathered."""
    import re

    config, fn, args = _cell_case(name, "decode")
    _, text = _compiled_in_place(fn, args, one_chip, 15 * 10 ** 9)
    assert 'custom_call_target="tpu_custom_call"' not in text
    assert re.search(r"bf16\[1024,16,512\]", text)
    assert re.search(r"bf16\[1024,16,128\]", text)


# ---------------------------------------------------------------------------
# the 'retention' block: a recurrent state a lane beside the paged tail
# ---------------------------------------------------------------------------

# temporaries of `brumby-14b-base`'s two programs as first accepted (PR 41):
# 552,131,072 B the span alone, 918,589,952 B beside a 512-row chunk; the
# chunk on the span's first pass (PR 48): 666,886,144 B
RETENTION_TEMPORARIES = {"decode": 700 << 20, "mixed": 800 << 20}


def _retention_case(kind):
    """The decode span or the mixed program of ``brumby-14b-base``'s first
    pipeline stage at its published widths, as shapes only, compiled as the
    engine compiles them: the pool's three arrays and the states (an array a
    layer) donated, the lanes' fold points and the chunk's slot after them."""
    import json

    from kubeshare_tpu.serving.kv_blocks import (init_paged_pool,
                                                 init_retention_states)

    with open(os.path.join(REPO, "chipbench", "configs",
                           "brumby-14b-base.json")) as f:
        config_file = json.load(f)
    tc = dict(config_file["transformer_config"])
    tc["dtype"] = jnp.dtype(tc["dtype"])
    config = TransformerConfig(**tc)
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, config.dtype),
        jax.eval_shape(
            lambda: transformer_init(jax.random.PRNGKey(0), config)))
    e = config_file["engine"]
    s, t = e["num_slots"], e["max_request_len"] // e["block_size"]
    num_blocks = e["pool_bytes"] // (20640 * e["block_size"]) + 1
    pool_k, pool_v, gate = jax.eval_shape(
        lambda: init_paged_pool(config, num_blocks,
                                e["block_size"]).arrays())
    recurrent = paged.Recurrent(gate, jax.eval_shape(
        lambda: init_retention_states(config, s)))
    span = 4
    lanes = (_i32(s, t), _i32(s), jax.ShapeDtypeStruct((s,), bool), _i32(s),
             jax.ShapeDtypeStruct((s,), jnp.float32),
             jax.ShapeDtypeStruct((s, span, 2), jnp.uint32), _i32(s))
    if kind == "decode":
        fn = lambda w, pk, pv, rec, folded, *rest: paged_decode_span(
            w, config, _greedy_pick, span, None, pk, pv, *rest,
            recurrent=rec, folded=folded)
        return config, fn, (params, pool_k, pool_v, recurrent, _i32(s),
                            *lanes)
    fn = lambda w, pk, pv, rec, p_folded, p_slot, d_folded, *rest: \
        paged_mixed_step(w, config, _greedy_pick, span, None, pk, pv, *rest,
                         recurrent=rec, p_folded=p_folded, p_slot=p_slot,
                         d_folded=d_folded)
    return config, fn, (
        params, pool_k, pool_v, recurrent, _i32(1), _i32(1), _i32(s),
        _i32(1, t), _i32(1), _i32(1, e["prefill_chunk"]), _i32(1),
        jax.ShapeDtypeStruct((1,), jnp.float32),
        jax.ShapeDtypeStruct((1, 2), jnp.uint32), *lanes)


@pytest.mark.parametrize("kind", list(RETENTION_TEMPORARIES))
def test_retention_program_compiles_and_fits(one_chip, kind):
    """The first pipeline stage of ``brumby-14b-base`` at the published
    widths — five power-retention layers of GQA 40 to 8 at head width 128,
    a SwiGLU of 17408, the whole vocabulary — with 32 lanes' states (5.79 GB,
    an array a layer) beside a 1 GiB pool of unfolded rows: the decode span
    alone and beside a 512-row chunk fit under 14.4 GB (``gpu_mem`` 0.9 of
    the chip), everything donated is written in place, and NO state is
    copied.  What the first forms of these programs did, each found here
    before the chip saw it (PR 41): a state closed over by the scan and
    folded after it was copied whole (5.4 GB: over the chip); a state array
    of 129 rows came back in another layout than it went in (a copy each
    way, each dispatch); one stacked array of all layers was windowed a
    layer a step (1.16 GB a layer a step); the folds of five layers held
    five float32 ``phi`` of a key block at once (680 MB); ``[d, 40, 128]``
    projections were laid out anew every dispatch (72 MB a layer)."""
    import re

    config, fn, args = _retention_case(kind)
    assert args[1].shape == args[2].shape == (5, 3252, 8, 16, 128)
    gate, states = args[3]
    assert gate.shape == (5, 8, 3252 * 16) and gate.dtype == jnp.float32
    assert len(states) == 5 and states[0].shape == (32, 8, 136, 8320)
    compiled = _compile_step(fn, args, one_chip, (1, 2, 3),
                             key=("brumby-14b-base", kind))
    memory = compiled.memory_analysis()
    resident = (memory.argument_size_in_bytes + memory.temp_size_in_bytes
                + memory.output_size_in_bytes - memory.alias_size_in_bytes)
    assert resident < 14.4e9, memory
    assert memory.temp_size_in_bytes < RETENTION_TEMPORARIES[kind], memory
    donated = sum(a.size * a.dtype.itemsize
                  for a in jax.tree.leaves(args[1:4]))
    assert donated > 6.8e9 and memory.alias_size_in_bytes >= donated, memory
    text = compiled.as_text()
    for shape in ("32,8,136,8320", "5,3252,8,16,128"):
        assert not re.search(rf"\[{shape}\][^ ]* copy\(", text), shape
    # the states come back as they went in: row-major, whole tiles
    results = text.split("entry_computation_layout=", 1)[1].split(
        ")->(")[1].split("\n")[0]
    assert results.count("f32[32,8,136,8320]{3,2,1,0:T(8,128)}") == 5
    table = stages.instruction_stages(text)
    assert "retention" in set(table.values())
    # a decode step's state query reads each state where it lies: one
    # multiply-and-sum over phi's columns a layer a step, no staged slice
    assert not re.search(r"f32\[32,8,5,136,8320\][^ ]* fusion\(", text)


# ---------------------------------------------------------------------------
# layers that name their operator: short convolutions' states by slot beside
# a pool of the attention layers' rows, two 64-wide heads a row
# ---------------------------------------------------------------------------

# temporaries of `lfm2-24b-a2b`'s two programs as first compiled (PR 43):
# 31,684,608 B the span alone, 73,308,160 B beside a 512-row chunk; the
# chunk on the span's first pass (PR 48): 88,963,584 B
CONV_TEMPORARIES = {"decode": 48 << 20, "mixed": 112 << 20}


def _conv_case(kind):
    """The decode span or the mixed program of ``lfm2-24b-a2b``'s first
    pipeline stage at its published widths, as shapes only, compiled as the
    engine compiles them: the pool's two arrays and the convolutions' states
    (an array a convolution layer) donated, the chunk's slot after them."""
    import json

    from kubeshare_tpu.serving.kv_blocks import (init_conv_states,
                                                 init_paged_pool)

    with open(os.path.join(REPO, "chipbench", "configs",
                           "lfm2-24b-a2b.json")) as f:
        config_file = json.load(f)
    tc = dict(config_file["transformer_config"])
    tc["dtype"] = jnp.dtype(tc["dtype"])
    config = TransformerConfig(**tc)
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, config.dtype),
        jax.eval_shape(
            lambda: transformer_init(jax.random.PRNGKey(0), config)))
    e = config_file["engine"]
    s, t = e["num_slots"], e["max_request_len"] // e["block_size"]
    num_blocks = e["pool_bytes"] // (4096 * e["block_size"]) + 1
    pool_k, pool_v = jax.eval_shape(
        lambda: init_paged_pool(config, num_blocks,
                                e["block_size"]).arrays())
    recurrent = paged.Recurrent(None, jax.eval_shape(
        lambda: init_conv_states(config, s)))
    span = 4
    lanes = (_i32(s, t), _i32(s), jax.ShapeDtypeStruct((s,), bool), _i32(s),
             jax.ShapeDtypeStruct((s,), jnp.float32),
             jax.ShapeDtypeStruct((s, span, 2), jnp.uint32), _i32(s))
    if kind == "decode":
        fn = lambda w, pk, pv, rec, folded, *rest: paged_decode_span(
            w, config, _greedy_pick, span, None, pk, pv, *rest, routing=True,
            recurrent=rec, folded=folded)
        return config, fn, (params, pool_k, pool_v, recurrent, _i32(s),
                            *lanes)
    fn = lambda w, pk, pv, rec, p_folded, p_slot, d_folded, *rest: \
        paged_mixed_step(w, config, _greedy_pick, span, None, pk, pv, *rest,
                         routing=True, recurrent=rec, p_folded=p_folded,
                         p_slot=p_slot, d_folded=d_folded)
    return config, fn, (
        params, pool_k, pool_v, recurrent, _i32(1), _i32(1), _i32(s),
        _i32(1, t), _i32(1), _i32(1, e["prefill_chunk"]), _i32(1),
        jax.ShapeDtypeStruct((1,), jnp.float32),
        jax.ShapeDtypeStruct((1, 2), jnp.uint32), *lanes)


@pytest.mark.parametrize("kind", list(CONV_TEMPORARIES))
def test_conv_hybrid_program_compiles_and_fits(one_chip, monkeypatch, kind):
    """The first pipeline stage of ``lfm2-24b-a2b`` at the published widths
    — six gated short convolutions and two GQA layers of 32 to 8 at head
    width 64, two dense SwiGLUs of 11776 and six layers of 64 experts, the
    whole vocabulary — built as on the chip with 32 lanes' convolution
    states (an array a layer, 256 KB each) beside a 1 GiB pool of the TWO
    attention layers' rows: 9.4 GB resident (8.32 GB of weights, the pool)
    and 32 / 89 MB of temporaries.  The pool's minor dimension is 128 (two
    64-wide heads side by side), everything donated is written in place
    and NO program copies or re-lays the pool; the decode lanes' one query
    row attends through the paged kernel (one call an attention layer: the
    query laid into its head's half of a row of zeros), the experts' tiles
    through the grouped kernel (one call an expert layer a pass); the rows
    are written whole (no loop of row updates in ``kv_write``); and the
    program's own table of stages has ``conv`` beside the others.  The
    mixed program is its fused first step and the scan's body: the lanes'
    kernel and the experts' are called in each, and the first step's experts
    see the chunk's 512 rows and the lanes' 32 as ONE grouping."""
    import re

    monkeypatch.setattr(paged, "_kernel_mode", lambda: "compiled")
    config, fn, args = _conv_case(kind)
    assert (config.conv_layers, config.attn_sublayers,
            config.expert_layers) == (6, 2, 6)
    assert args[1].shape == args[2].shape == (2, 16385, 4, 16, 128)
    gate, states = args[3]
    assert gate is None and len(states) == 6
    assert all(s.shape == (32, 2, 2048) and s.dtype == jnp.bfloat16
               for s in states)
    compiled = _compile_step(fn, args, one_chip, (1, 2, 3),
                             key=("lfm2-24b-a2b", kind))
    memory = compiled.memory_analysis()
    resident = (memory.argument_size_in_bytes + memory.temp_size_in_bytes
                + memory.output_size_in_bytes - memory.alias_size_in_bytes)
    assert 9.3e9 < resident < 9.6e9, memory
    assert memory.temp_size_in_bytes < CONV_TEMPORARIES[kind], memory
    donated = sum(a.size * a.dtype.itemsize
                  for a in jax.tree.leaves(args[1:4]))
    assert donated == 2 * 16385 * 16 * 2048 + 6 * 32 * 2 * 2048 * 2
    assert memory.alias_size_in_bytes >= donated, memory
    text = compiled.as_text()
    for shape in ("2,16385,4,16,128", "16385,4,16,128", "2,16385,16,128"):
        assert not re.search(rf"bf16\[{shape}\][^ ]* copy\(", text), shape
    passes = 2 if kind == "mixed" else 1
    assert _kernel_calls(text) == (config.attn_sublayers * passes,
                                   config.expert_layers * passes)
    grouped = set(re.findall(r"f32\[(\d+),2048\][^\n]*custom_call_target="
                             r'"tpu_custom_call"', text))
    assert grouped == ({"544", "32"} if kind == "mixed" else {"32"})
    # no key block of every lane's table entries is gathered for the lanes
    assert not re.search(r"bf16\[1024,4,16,128\]", text)
    _rows_written_whole(args, text)
    with_experts = set(re.findall(r"(?:bf16|f32)\[64,[0-9,]+\]", text))
    assert {"bf16[64,2048,1536]", "bf16[64,1536,2048]"} <= with_experts
    by_stage = _stages_hold(config, args, text)
    assert {"conv", "attention", "ffn", "experts", "kv_write", "head"} \
        <= set(by_stage)


# temporaries of `smallthinker-21ba3b-instruct`'s two programs as first
# compiled (PR 47): 147,692,032 B the span alone, 305,668,096 B beside a
# 512-row chunk
KINDS_TEMPORARIES = {"decode": 192 << 20, "mixed": 400 << 20}


def _kinds_case(kind):
    """The decode span or the mixed program of
    ``smallthinker-21ba3b-instruct``'s first pipeline stage at its published
    widths, as shapes only, compiled as the engine compiles them: the pool
    BY LAYER KIND (an array a kind, K and V) donated, a lane's tables side by
    side."""
    import json

    from kubeshare_tpu.serving.kv_blocks import (init_paged_pool,
                                                 kind_blocks, kv_row_layout)

    with open(os.path.join(REPO, "chipbench", "configs",
                           "smallthinker-21ba3b-instruct.json")) as f:
        config_file = json.load(f)
    tc = dict(config_file["transformer_config"])
    tc["dtype"] = jnp.dtype(tc["dtype"])
    config = TransformerConfig(**tc)
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, config.dtype),
        jax.eval_shape(
            lambda: transformer_init(jax.random.PRNGKey(0), config)))
    e = config_file["engine"]
    s, t = e["num_slots"], e["max_request_len"] // e["block_size"]
    num_blocks = e["pool_bytes"] // (16384 * e["block_size"]) + 1
    kinds = kind_blocks(kv_row_layout(config), num_blocks, e["block_size"],
                        e["max_request_len"], e["prefill_chunk"],
                        config.attention_window)
    pool_k, pool_v = jax.eval_shape(
        lambda: (lambda pool: (pool.k, pool.v))(init_paged_pool(
            config, num_blocks, e["block_size"], kinds=kinds)))
    span = 4
    lanes = (_i32(s, 2 * t), _i32(s), jax.ShapeDtypeStruct((s,), bool),
             _i32(s), jax.ShapeDtypeStruct((s,), jnp.float32),
             jax.ShapeDtypeStruct((s, span, 2), jnp.uint32), _i32(s))
    if kind == "decode":
        fn = lambda w, pk, pv, *rest: paged_decode_span(
            w, config, _greedy_pick, span, None, pk, pv, *rest, routing=True)
        return config, fn, (params, pool_k, pool_v, *lanes)
    fn = lambda w, pk, pv, *rest: paged_mixed_step(
        w, config, _greedy_pick, span, None, pk, pv, *rest, routing=True)
    return config, fn, (
        params, pool_k, pool_v, _i32(1, 2 * t), _i32(1),
        _i32(1, e["prefill_chunk"]), _i32(1),
        jax.ShapeDtypeStruct((1,), jnp.float32),
        jax.ShapeDtypeStruct((1, 2), jnp.uint32), *lanes)


@pytest.mark.parametrize("kind", list(KINDS_TEMPORARIES))
def test_cache_by_kind_program_compiles_and_fits(one_chip, monkeypatch, kind):
    """The first pipeline stage of ``smallthinker-21ba3b-instruct`` at the
    published widths — two full layers that rotate nothing and six under a
    4,096-row window, GQA 28 to 4 at head width 128 (a query group of 7,
    padded to a tile inside the kernel's call), 64 ReLU-gated experts top 6
    behind a router on the layer's input, the whole vocabulary — built as on
    the chip over a 4 GiB pool BY LAYER KIND: 35,492 blocks of the 2 full
    layers' rows and 10,016 of the 6 window layers', the bytes of 16,385
    blocks of every layer's row.  12.4 / 12.5 GB resident (7.93 GB of
    weights, the pool); everything donated is written in place and no
    program copies or re-lays either kind's pool; the decode lanes' one
    query row attends through the paged kernel in BOTH kinds (one call an
    attention layer a pass), the experts' tiles through the grouped kernel;
    no key block of every lane's table entries is gathered; and the
    program's own table of stages leaves no more unscoped than the other
    routed cells' (the two kinds and the early router add no stage)."""
    import re

    monkeypatch.setattr(paged, "_kernel_mode", lambda: "compiled")
    config, fn, args = _kinds_case(kind)
    assert (config.attn_sublayers, config.window_layers,
            config.expert_layers) == (8, 6, 8)
    assert [a.shape for a in args[1]] == [a.shape for a in args[2]] == [
        (2, 35492, 4, 16, 128), (6, 10016, 4, 16, 128)]
    compiled = _compile_step(fn, args, one_chip,
                             key=("smallthinker-21ba3b-instruct", kind))
    memory = compiled.memory_analysis()
    resident = (memory.argument_size_in_bytes + memory.temp_size_in_bytes
                + memory.output_size_in_bytes - memory.alias_size_in_bytes)
    assert 12.2e9 < resident < 12.7e9 < 0.9 * V5E_HBM_BYTES, memory
    assert memory.temp_size_in_bytes < KINDS_TEMPORARIES[kind], memory
    donated = sum(a.size * a.dtype.itemsize
                  for a in jax.tree.leaves(args[1:3]))
    assert donated == 16385 * 16 * 16384 == (1 << 32) + 16 * 16384
    assert memory.alias_size_in_bytes >= donated, memory
    text = compiled.as_text()
    for shape in ("2,35492,4,16,128", "6,10016,4,16,128", "35492,4,16,128",
                  "10016,4,16,128"):
        assert not re.search(rf"bf16\[{shape}\][^ ]* copy\(", text), shape
    passes = 2 if kind == "mixed" else 1
    assert _kernel_calls(text) == (config.attn_sublayers * passes,
                                   config.expert_layers * passes)
    # no key block of every lane's table entries is gathered for the lanes
    assert not re.search(r"bf16\[1024,4,16,128\]", text)
    with_experts = set(re.findall(r"(?:bf16|f32)\[64,[0-9,]+\]", text))
    assert {"bf16[64,2560,768]", "bf16[64,768,2560]"} <= with_experts
    by_stage = _stages_hold(config, args, text)
    assert {"attention", "experts", "kv_write", "head"} <= set(by_stage)
    assert not {"conv", "ffn", "retention"} & set(by_stage)


# ---------------------------------------------------------------------------
# one packed host buffer a dispatch: the programs are the parent's but for
# their entry parameters
# ---------------------------------------------------------------------------

# cell -> (kind, the PARENT's temporaries, its (attention, experts) kernel
# calls): PR 44's programs, a bare ``jax.jit`` taking 7-16 small arguments,
# by this file's chipless compile (PR 45 compiled both sides once); the two
# cells with a state by slot as PR 48's fused first step left them (their
# parents': 918,589,952 and (0, 0); 73,308,160 and (2, 12))
PARENTS = {
    "starcoderbase-1b": ("mixed", 297_772_032, (48, 0)),
    "starcoder2-3b": ("mixed", 854_657_024, (60, 0)),
    "longcat-flash-chat": ("mixed", 364_149_760, (16, 8)),
    "joyai-llm-flash": ("mixed", 63_936_000, (10, 8)),
    "brumby-14b-base": ("mixed", 666_886_144, (0, 0)),
    "lfm2-24b-a2b": ("mixed", 88_963_584, (4, 12)),
    "sdar-30b-a3b-chat": ("diffusion", 10_612_224, (6, 6)),
}


@pytest.mark.parametrize("name", sorted(PARENTS))
def test_packed_program_is_the_parents_but_for_its_arguments(
        one_chip, monkeypatch, name):
    """The six configurations' mixed programs and ``sdar``'s pass as the
    engine compiles them since PR 45 — every host argument of a dispatch in
    ONE ``uint32`` buffer, sliced apart by static offsets inside: the
    program is handed the weights, what is donated (the pool; a model's
    states by slot) and that buffer, nothing else; the compiler's plan did
    not change with the entry parameters — nothing the size of the pool or
    of a state is copied, the Pallas kernels are called as often as in the
    parent's program, and the temporaries are within 1 MB of the
    parent's."""
    import re

    monkeypatch.setattr(paged, "_kernel_mode", lambda: "compiled")
    kind, temporaries, kernel_calls = PARENTS[name]
    if name in ("brumby-14b-base", "lfm2-24b-a2b"):
        case = _retention_case if name == "brumby-14b-base" else _conv_case
        _, fn, args = case(kind)
        donated = (1, 2, 3)
    else:
        _, fn, args = _cell_case(name, kind)
        donated = (1, 2)
    compiled = _compile_step(fn, args, one_chip, donated, key=(name, kind))
    memory, text = compiled.memory_analysis(), compiled.as_text()
    # the entry parameters: the weights, the donated arrays, one buffer
    words = sum(a.size for a in args[max(donated) + 1:])
    layout = text.split("entry_computation_layout={(", 1)[1].split(")->")[0]
    assert layout.count(f"u32[{words}]") == 1
    handed = len(re.findall(r"\b(?:pred|bf16|f32|s32|u32)\[", layout))
    assert handed == len(jax.tree.leaves(args[:max(donated) + 1])) + 1
    # what is donated is written in place, and none of it is copied
    kept = jax.tree.leaves(args[1:max(donated) + 1])
    assert memory.alias_size_in_bytes >= sum(
        a.size * a.dtype.itemsize for a in kept), memory
    # (the retention block's gate array, 8.3 MB a row of the pool's, is
    # laid out anew on the way in and out, in the parent's program too)
    for shape in {",".join(map(str, a.shape)) for a in kept
                  if a.size * a.dtype.itemsize > 64 << 20}:
        assert not re.search(rf"\[{shape}\][^ ]* copy\(", text), shape
    assert _kernel_calls(text) == kernel_calls
    assert abs(memory.temp_size_in_bytes - temporaries) < 1 << 20, memory


# ---------------------------------------------------------------------------
# who keeps the chunk a pass of its own: the diffusion entry alone, whose
# program is the parent's; a state by slot rides the first pass (PR 48)
# ---------------------------------------------------------------------------

# sha256 (16 hex) of the lowered mixed program at the cell's size, a Pallas
# kernel's serialised body left out (it holds the checkout's paths and the
# calling function's name), as PR 43 lowers it and every PR since (the whole
# texts compared once, checkout against checkout).  A PR that means to
# change this program pins its own.
UNFUSED_MIXED = {"sdar-30b-a3b-chat": "6c71ecda30d19467"}
# the cells whose mixed program was the back-to-back composition until PR 48
FUSED_STATEFUL = {"brumby-14b-base": "bf16[544,17408]",
                  "lfm2-24b-a2b": "bf16[544,11776]"}


def _lowered(fn, args, sharding, donate_argnums):
    import re

    text = _lower(fn, args, sharding, donate_argnums).as_text()
    return re.sub(r'\\22body\\22: \\22[^\\]*\\22', "BODY", text)


@pytest.mark.parametrize("name", sorted({**UNFUSED_MIXED, **FUSED_STATEFUL}))
def test_unfused_mixed_programs_are_the_parents(one_chip, monkeypatch, name):
    """The diffusion entry (``paged_mixed_diffusion_step``) is another
    program than ``paged_mixed_step``, untouched: lowered to the text it had
    before the fused step existed.  The two cells with a state by slot ran
    the back-to-back composition until PR 48; their chunk now rides the
    span's first pass: the program is no longer the composition's, its
    feed-forward runs over the chunk's 512 rows and the lanes' 32 side by
    side, it compiles for the described v5e and fits under 14.4 GB
    (``gpu_mem`` 0.9 of the chip), everything donated is written in place,
    and nothing the size of the pool or of a state is copied."""
    import hashlib
    import re

    monkeypatch.setattr(paged, "_kernel_mode", lambda: "compiled")
    if name in UNFUSED_MIXED:
        _, fn, args = _cell_case(name, "mixed_diffusion")
        text = _lowered(fn, args, one_chip, (1, 2))
        assert hashlib.sha256(text.encode()).hexdigest()[:16] \
            == UNFUSED_MIXED[name]
        return
    case = _retention_case if name == "brumby-14b-base" else _conv_case
    _, fn, args = case("mixed")
    fused = _lowered(fn, args, one_chip, (1, 2, 3))
    compiled = _compile_step(fn, args, one_chip, (1, 2, 3),
                             key=(name, "mixed"))
    # the case builders call this module's name for it
    monkeypatch.setitem(globals(), "paged_mixed_step",
                        paged.paged_mixed_back_to_back)
    _, fn, args = case("mixed")
    assert _lowered(fn, args, one_chip, (1, 2, 3)) != fused
    memory, text = compiled.memory_analysis(), compiled.as_text()
    resident = (memory.argument_size_in_bytes + memory.temp_size_in_bytes
                + memory.output_size_in_bytes - memory.alias_size_in_bytes)
    print(f"{name} mixed, fused: resident {resident:,} B, temporaries "
          f"{memory.temp_size_in_bytes:,} B")
    assert resident < 14.4e9, memory
    kept = jax.tree.leaves(args[1:4])
    assert memory.alias_size_in_bytes >= sum(
        a.size * a.dtype.itemsize for a in kept), memory
    for shape in {",".join(map(str, a.shape)) for a in kept
                  if a.size * a.dtype.itemsize > 64 << 20}:
        assert not re.search(rf"\[{shape}\][^ ]* copy\(", text), shape
    # the feed-forward runs over both groups' rows side by side
    assert FUSED_STATEFUL[name] in text
