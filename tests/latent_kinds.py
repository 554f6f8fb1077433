"""What the tests of the two latent blocks share: each kind's tiny twin
(``chipbench/tests/configs``), its plain reference and seeded weights (the
benchmark's own modules), and the paged step programs driven by hand.

- ``latent_shortcut`` (``tiny_longcat``): d 64, 4 heads, ranks 32 / 16, nope
  16 / rope 8 / v 16, 2 double layers, 16 routed + 8 zero experts, top 4, 4
  held by rank 0.
- ``latent_moe`` (``tiny_joyai``): the same attention without the rank
  factors, 3 single layers of which the first dense, 16 routed experts all
  held, top 4 by sigmoid score + a seeded choice bias, renormalised, 1 shared
  expert.
"""

import importlib
import json
import os
import sys
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from kubeshare_tpu.models.transformer import TransformerConfig  # noqa: E402
from kubeshare_tpu.serving import paged  # noqa: E402
from kubeshare_tpu.serving.kv_blocks import init_paged_pool  # noqa: E402

BLOCK = 4  # rows a pool block
ROWS = 64  # a lane's table covers this many


def _kind(twin: str, modules: str) -> SimpleNamespace:
    with open(os.path.join(REPO, "chipbench", "tests", "configs",
                           f"{twin}.json")) as f:
        tc = json.load(f)["transformer_config"]
    return SimpleNamespace(
        tc=tc,
        reference=importlib.import_module(f"chipbench.{modules}_reference"),
        weights=importlib.import_module(f"chipbench.{modules}_weights"))


KINDS = {"latent_shortcut": _kind("tiny_longcat", "longcat_flash"),
         "latent_moe": _kind("tiny_joyai", "joyai_llm_flash")}


def jitted_steps():
    return (jax.jit(paged.paged_prefill_step, static_argnums=(1,),
                    static_argnames=("routing",)),
            jax.jit(paged.paged_decode_step, static_argnums=(1,),
                    static_argnames=("routing",)))


STEPS = jitted_steps()


def config_of(kind: str, dtype, **changes) -> TransformerConfig:
    return TransformerConfig(**{**KINDS[kind].tc, "dtype": jnp.dtype(dtype),
                                **changes})


def params_of(kind: str, seed: int, dtype, **changes):
    """The benchmark's seeded weights (bf16 values), in ``dtype``."""
    k = KINDS[kind]
    return jax.tree.map(lambda a: a.astype(dtype),
                        k.weights.make_weights(seed, {**k.tc, **changes}))


def lane_tables(lanes: int):
    per = ROWS // BLOCK
    return jnp.asarray(1 + np.arange(lanes * per).reshape(lanes, per),
                       jnp.int32)


def served_logits(params, config, tokens, prompt_len, chunk=8, lanes=3,
                  lane=1, steps=None):
    """Logits [len(tokens) - prompt_len + 1, vocab] at the rows from the
    prompt's last on, as the step programs give them: the prompt prefilled
    in chunks of ``chunk`` into lane ``lane`` of a paged latent pool, then
    one decode step a token, the other lanes inactive."""
    paged_prefill_step, paged_decode_step = steps or STEPS
    pool = init_paged_pool(config, 1 + lanes * ROWS // BLOCK, BLOCK)
    pk, pv = pool.k, pool.v
    tables = lane_tables(lanes)
    table = tables[lane][None]
    rows = []
    for start in range(0, prompt_len, chunk):
        piece = np.zeros((1, chunk), np.int32)
        real = tokens[start:min(start + chunk, prompt_len)]
        piece[0, :len(real)] = real
        logits, pk, pv = paged_prefill_step(
            params, config, pk, pv, table, jnp.asarray([start]),
            jnp.ones((1,), bool), jnp.asarray(piece),
            jnp.asarray([len(real) - 1]))
    rows.append(np.asarray(logits[0]))
    active = np.zeros((lanes,), bool)
    active[lane] = True
    for i in range(prompt_len, len(tokens)):
        lengths = np.zeros((lanes,), np.int32)
        lengths[lane] = i
        toks = np.zeros((lanes,), np.int32)
        toks[lane] = tokens[i]
        logits, pk, pv = paged_decode_step(
            params, config, pk, pv, tables, jnp.asarray(lengths),
            jnp.asarray(active), jnp.asarray(toks))
        rows.append(np.asarray(logits[lane]))
    return np.stack(rows)
