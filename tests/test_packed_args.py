"""A dispatch's host arguments cross to the device as ONE buffer
(``serving/packed_args.py``; every step program of ``serving/engine.py`` is a
``PackedProgram``): the layout round-trips bit for bit, the engines of the
tiny twins serve the tokens their parent served (``packed_args_streams.json``,
recorded on the parent commit's tree: ``python3 tests/test_packed_args.py``
prints it), a launch span says what its call carried, nothing compiles after
``warmup()``, and a program's table of stages builds from what was registered.
"""

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path[:0] = [REPO, HERE]

from kubeshare_tpu.models.transformer import (  # noqa: E402
    TransformerConfig, transformer_init)
from kubeshare_tpu.serving import (  # noqa: E402
    EngineConfig, Request, ServingEngine, stages)
from kubeshare_tpu.utils import profiling  # noqa: E402

STREAMS = os.path.join(HERE, "packed_args_streams.json")


# ---------------------------------------------------------------------------
# the twins, and what each is asked to serve
# ---------------------------------------------------------------------------

def _twin(name):
    with open(os.path.join(REPO, "chipbench", "tests", "configs",
                           f"{name}.json")) as f:
        tc = {**json.load(f)["transformer_config"], "dtype": "float32"}
    return tc, TransformerConfig(**{**tc, "dtype": jnp.float32})


def _dense():
    config = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, n_kv_heads=2, n_layers=2,
        d_ff=64, max_seq_len=128, positional="rope", dtype=jnp.float32,
        attention="reference")
    return (config, transformer_init(jax.random.PRNGKey(0), config),
            dict(num_slots=4, block_size=8, num_blocks=65,
                 max_request_len=128, prefill_chunk=16), 64)


def _conv():
    from chipbench import lfm2_24b_a2b_weights as weights

    tc, config = _twin("tiny_lfm2")
    return (config, weights.make_weights(11, tc),
            # (chunks of 8: the registry of stage tables is one name a
            # program a process, and `mixed/16` is the dense engine's here)
            dict(num_slots=3, block_size=8, num_blocks=1 + 3 * 16,
                 max_request_len=128, prefill_chunk=8), config.vocab_size)


def _sdar():
    from chipbench import sdar_30b_a3b_chat_weights as weights

    tc, config = _twin("tiny_sdar")
    return (config, weights.make_weights(11, tc),
            dict(num_slots=4, block_size=16, num_blocks=41,
                 max_request_len=128, prefill_chunk=16), config.mask_token)


def _speculative():
    from serving_helpers import _cyclic_params, _small_config

    config = _small_config()
    return (config, _cyclic_params(config),
            dict(num_slots=3, block_size=4, num_blocks=41,
                 max_request_len=48, prefill_chunk=8, speculative=True,
                 draft_len=4), 64)


# engine -> (its model and geometry, the requests it is sent)
ENGINES = {"dense": (_dense, 50), "conv": (_conv, 8), "sdar": (_sdar, 8),
           "speculative": (_speculative, 6)}
# one case a kind of step program: the engine that launches it
KINDS = [("dense", "prefill"), ("dense", "decode"), ("dense", "mixed"),
         ("conv", "mixed"), ("sdar", "diffusion"),
         ("sdar", "mixed_diffusion"), ("speculative", "verify")]


def _serve(name):
    """The engine ``name`` warmed, then its requests served: (tokens by
    request, the launch spans' attributes, compile counts after the
    warm-up and after the last request, the engine)."""
    build, requests = ENGINES[name]
    config, params, geometry, ids = build()
    engine = ServingEngine(params, config, EngineConfig(**geometry))
    engine.warmup()
    warm = engine.compile_counts()
    rng = np.random.default_rng(45)
    room = geometry["max_request_len"]
    since = time.monotonic()
    for i in range(requests):
        if name == "speculative":  # a prompt the drafter can match
            prompt = np.tile(rng.integers(0, ids, 4), 6)[:18 + i]
        else:
            prompt = rng.integers(0, ids, int(rng.integers(3, room // 2)))
        # (diffusion passes commit the argmax: greedy requests only)
        sampled = i % 3 == 0 and name in ("dense", "conv")
        engine.submit(Request(
            f"r{i}", prompt.astype(np.int32),
            int(rng.integers(2, min(24, room - prompt.size))),
            temperature=0.8 if sampled else 0.0,
            rng=jax.random.PRNGKey(i) if sampled else None))
    results = engine.run()
    launches = [record[4] for record in profiling.spans(
        since=since, name="kubeshare.engine.launch")]
    tokens = {rid: [int(t) for t in results[rid].tokens] for rid in results}
    return tokens, launches, warm, engine.compile_counts(), engine


_served = {}


@pytest.fixture
def served():
    def get(name):
        if name not in _served:
            _served[name] = _serve(name)
        return _served[name]
    return get


@pytest.fixture(scope="module", autouse=True)
def executables_let_go():
    """Four engines' warm-ups stay loaded in a tier-1 worker otherwise
    (``tests/test_short_conv.py`` says what that cost once)."""
    yield
    _served.clear()
    jax.clear_caches()


# ---------------------------------------------------------------------------
# the layout
# ---------------------------------------------------------------------------

S, SPAN, B = 5, 4, 4
_rng = np.random.default_rng(7)
ARRAYS = {
    "int32_tables": _rng.integers(-2 ** 31, 2 ** 31 - 1, (S, 9),
                                  dtype=np.int64).astype(np.int32),
    "uint32_keys": _rng.integers(0, 2 ** 32, (S, SPAN, 2),
                                 dtype=np.uint64).astype(np.uint32),
    # a negative zero, a denormal, an infinity and a NaN with a payload
    "float32_temps": np.array([-0.0, 1e-42, 0.7, -3.0e38, np.inf],
                              np.float32),
    "float32_nan": np.array([0x7fc00123, 0xff800000, 1],
                            np.uint32).view(np.float32),
    "bool_masks": _rng.integers(0, 2, (S, B)).astype(bool),
    "int32_scalar": np.asarray(-7, np.int32),
    "bool_empty": np.zeros((0, B), bool),
}


@pytest.mark.parametrize("name", sorted(ARRAYS))
def test_layout_round_trips_bit_for_bit(name):
    """Every dtype and shape a dispatch carries, alone between two
    neighbours: through ``pack`` and a jitted ``unpack`` it comes back with
    its shape, its dtype and its bits."""
    from kubeshare_tpu.serving import packed_args

    before, after = ARRAYS["int32_tables"], ARRAYS["bool_masks"]
    args = (before, ARRAYS[name], after)
    layout = packed_args.layout_of(args)
    assert None not in layout
    packed = packed_args.pack(layout, args)
    assert packed.dtype == np.uint32 \
        and packed.size == sum(a.size for a in args)
    out = jax.jit(lambda p: packed_args.unpack(layout, p, iter(())))(packed)
    for got, sent in zip(out, args):
        got = np.asarray(got)
        assert got.shape == sent.shape and got.dtype == sent.dtype
        assert got.tobytes() == sent.tobytes()


def test_a_buffer_is_never_written_again():
    """The backend may read (on the CPU: alias) a call's buffer after the
    call returns, so each call packs into a buffer of its own; and a view
    that is not contiguous (a table's row) is packed by its values."""
    from kubeshare_tpu.serving import packed_args

    table = np.arange(24, dtype=np.int32).reshape(4, 6)
    args = (table[:, 2], table.T)
    layout = packed_args.layout_of(args)
    first, second = (packed_args.pack(layout, args) for _ in range(2))
    assert not np.shares_memory(first, second)
    assert not any(np.shares_memory(first, a) for a in args)
    np.testing.assert_array_equal(
        first.view(np.int32),
        np.concatenate([table[:, 2], table.T.reshape(-1)]))


def test_what_cannot_ride_is_passed_as_it_is():
    """A device array, a pytree, a donated argument and a host array of
    another dtype are passed on, in their places; the donated one is the
    program's to keep; ``carried`` hears of the buffer and of the host
    array that could not ride."""
    from kubeshare_tpu.serving.packed_args import PackedProgram

    heard = []

    def fn(w, pk, pv, a, state, b, wide, c):
        return (w + a.sum() + b.sum() + c.sum() + wide.sum(), pk, pv,
                {"s": state["s"] + 1})

    step = PackedProgram("kubeshare_test_step", fn, (1, 2, 4),
                         lambda *carry: heard.append(carry))
    w, pk, pv = jnp.float32(1), jnp.zeros((2,)), jnp.ones((2,))
    state = {"s": jnp.arange(3.0)}
    a, c = np.arange(4, dtype=np.int32), np.ones((2, 2), np.float32)
    b = jnp.arange(3, dtype=jnp.int32)  # already on the device
    wide = np.arange(3, dtype=np.int8)  # no word an element: passed on
    out, _, _, kept = step(w, pk, pv, a, state, b, wide, c)
    assert float(out) == 1 + 6 + 3 + 4 + 3
    np.testing.assert_array_equal(kept["s"], [1.0, 2.0, 3.0])
    assert state["s"].is_deleted() and pk.is_deleted()
    assert not b.is_deleted()
    assert heard == [(2, (4 + 4) * 4 + 3)]
    assert step._cache_size() == 1
    # the same signature again: the kept layout, no second program
    state = {"s": jnp.arange(3.0)}
    step(w, jnp.zeros((2,)), jnp.ones((2,)), a, state, b, wide, c)
    assert step._cache_size() == 1 and len(step._kept) == 1


# ---------------------------------------------------------------------------
# the engines
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name,kind", KINDS,
                         ids=[f"{n}-{k}" for n, k in KINDS])
def test_served_tokens_are_the_parents(served, name, kind):
    """One case a kind of step program: the engine launched it, every such
    launch carried one host array, and every request's tokens are those
    the parent commit's engine served (its programs took 7-16 numpy
    arguments each)."""
    tokens, launches, _, _, _ = served(name)
    of_kind = [attrs for attrs in launches if attrs["kind"] == kind]
    assert of_kind, sorted({attrs["kind"] for attrs in launches})
    assert {attrs["host_args"] for attrs in of_kind} == {1}
    assert all(attrs["host_bytes"] > 0 and attrs["host_bytes"] % 4 == 0
               for attrs in of_kind)
    with open(STREAMS) as f:
        recorded = json.load(f)[name]
    assert tokens == recorded


def test_launch_span_says_what_the_call_carried(served):
    """``host_args`` is 1 on every planned launch and ``host_bytes`` the
    buffer's: a decode span's seven arrays are ``s x (table + 4 + 2 x
    span) + s`` words, the bools widened; a copy-on-write carries none;
    the engine's counters and the metrics plane hold their sums."""
    _, launches, _, _, engine = served("dense")
    ec = engine.engine_config
    planned = [a for a in launches if a["kind"] not in ("copy", "upload")]
    assert {a["host_args"] for a in planned} == {1}
    decode_words = ec.num_slots * (engine._table_width + 5
                                   + 2 * ec.decode_span)
    assert {a["host_bytes"] for a in planned if a["kind"] == "decode"} \
        == {4 * decode_words}
    assert all(a["host_args"] == 0 for a in launches if a["kind"] == "copy")
    assert engine.host_arg_transfers == len(planned)
    assert engine.host_arg_bytes == sum(a["host_bytes"] for a in planned)
    samples = {family.name: family.samples[0].value
               for family in engine.collect_metrics()
               if family.name.startswith("kubeshare_serving_host_arg")}
    assert samples == {
        "kubeshare_serving_host_args_total": engine.host_arg_transfers,
        "kubeshare_serving_host_arg_bytes_total": engine.host_arg_bytes}


@pytest.mark.parametrize("name", sorted(ENGINES))
def test_nothing_compiles_after_the_warm_up(served, name):
    """Warm-up and serving share the packed path, so each program keeps one
    signature: ``compile_counts()`` is what it was at the end of
    ``warmup()`` after every request (50 of them on the dense engine)."""
    tokens, _, warm, after, _ = served(name)
    assert len(tokens) == ENGINES[name][1]
    assert after == warm
    assert sum(warm.values()) > 0


def test_stage_table_builds_from_the_registered_callable(served):
    """``warmup()`` registers the packed callable with its arguments in the
    OUTER convention; the table is of the program that ran — lowering it
    again adds nothing to the callable's cache, and its module keeps the
    name the trace's readers look for."""
    _, launches, warm, _, engine = served("dense")
    program = next(a["program"] for a in launches if a["kind"] == "mixed")
    before = engine._mixed_step._cache_size()
    table = stages.stage_table(program)
    assert {"attention", "ffn", "kv_write", "head"} <= set(table.values())
    assert engine._mixed_step._cache_size() == before
    registered = stages._programs[program]
    assert registered.fn is engine._mixed_step
    lowered = registered.fn.lower(*registered.avals)
    assert "jit_kubeshare_mixed_step" in lowered.as_text()[:400]
    # one buffer where there were 13 arguments: params' leaves, the pool's
    # two arrays and the buffer are all the program is handed
    leaves = len(jax.tree.leaves(engine.params))
    assert len(jax.tree.leaves(lowered.args_info)) == leaves + 3


if __name__ == "__main__":
    # the parent's record: run on the parent commit's tree
    print(json.dumps({name: _serve(name)[0] for name in ENGINES}))
