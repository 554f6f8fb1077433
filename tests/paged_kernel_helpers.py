"""What the two files of the paged kernels' tests share
(``tests/test_paged_kernel.py``: the dense block's; ``tests/
test_paged_latent_kernel.py``: the latent blocks'): the page and the two
table entries a test marks, lanes at given lengths, a pool's shape, and an
engine with its streams and what its launch spans say it attended
through."""

import threading

import jax
import jax.numpy as jnp
import numpy as np

from kubeshare_tpu.serving import EngineConfig, Request, ServingEngine
from kubeshare_tpu.utils import profiling

PAGE = 16
IDLE = 0
POISON = -1
# what a routed block's engine counts: the kernel's engine routes as the
# key-block loop's does
ROUTING = ("moe_assignments", "moe_experts_touched", "moe_passes",
           "moe_tiles", "moe_tile_rows")


def _lanes(tables, lengths):
    """Tables and positions (the last row each lane sees: its reach) of
    lanes holding ``lengths`` rows each; an ``IDLE`` lane's table row is
    the scratch block, as the engine marshals it."""
    lengths = np.asarray(lengths)
    tables = np.where(lengths[:, None] > 0, tables, 0)
    return jnp.asarray(tables), jnp.asarray(np.maximum(lengths - 1, 0),
                                            jnp.int32)


def pool(*shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype)


def _engine(params, config):
    return ServingEngine(params, config, EngineConfig(
        num_slots=3, block_size=8, num_blocks=25, max_request_len=48,
        prefill_chunk=8))


def _streams(engine, vocab=64):
    rng = np.random.default_rng(31)
    for rid, prompt, new in (("long", 29, 9), ("s0", 5, 8), ("s1", 13, 4),
                             ("long2", 21, 6)):
        engine.submit(Request(rid, rng.integers(0, vocab, prompt), new))
    return {rid: r.tokens for rid, r in engine.run().items()}


def _launches(since):
    """The attributes of this thread's launch spans since ``since``."""
    me = threading.current_thread().name
    return [r[4] for r in profiling.spans(
        since=since, name="kubeshare.engine.launch") if r[3] == me]


def _attended(since, lanes=True):
    """What the dispatches whose lanes decode (or, ``lanes`` False, that
    carry a chunk alone) attended through."""
    return {a["attend"] for a in _launches(since) if bool(a["lanes"]) == lanes}
