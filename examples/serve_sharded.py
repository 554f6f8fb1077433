"""Tensor-parallel serving walkthrough: one engine, a 4-way ``tp``
mesh, and a head-sharded paged KV pool — under a tokend guard.

The sharded-serving subsystem (`serving/sharded.py`) from the outside:

  - ``EngineConfig.mesh_spec`` stands up a :class:`ShardedServingContext`
    inside the engine: Megatron-split params (column-parallel
    wq/wk/wv/w_in, row-parallel wo/w_out, sharded lm_head), a paged KV
    pool ``NamedSharding``-split on the KV-head axis so each device
    owns its GQA head group, and ``shard_map`` twins of every paged
    dispatch — collectives INSIDE the one compiled program per plan
    kind, so the engine's zero-recompile property survives the mesh;
  - ``long_context_threshold`` routes full prefill chunks through the
    Ulysses re-shard (heads-sharded -> sequence-sharded and back), the
    long-context layout, while decode stays head-local;
  - streams are BIT-EXACT with the single-device engine by
    construction (no collective ever carries a partial sum) — this
    example re-runs the same traffic through a plain engine and
    asserts every stream identical token for token;
  - the whole engine is gated through a tokend cell like any other
    dispatch path (``ExecutionGuard``), so a sharded serving pod is
    still a fractional tenant.

Run (no TPU needed; a forced 4-device CPU mesh, the runtime is real):

    JAX_PLATFORMS=cpu python -m examples.serve_sharded

On four chips the sharded engine runs in `chip_smoke.py --chips 4`; it
has no cell in `BENCHMARK.json` yet (`ROADMAP.md` 2.1), so nothing about
it is measured.
"""

from __future__ import annotations

import os
import socket
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

# the tp=4 serving mesh needs four devices; on a CPU host XLA must be
# told before the backend first initializes (i.e. before import jax)
if "host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4")

import jax

from kubeshare_tpu.utils.compile_cache import configure_compile_cache

configure_compile_cache()

import jax.numpy as jnp
import numpy as np

TP = 4


def main() -> None:
    from kubeshare_tpu.isolation import ExecutionGuard, TokenClient
    from kubeshare_tpu.models.transformer import (
        TransformerConfig, transformer_init)
    from kubeshare_tpu.parallel.mesh import MeshSpec
    from kubeshare_tpu.runtime import find_binary
    from kubeshare_tpu.serving import (EngineConfig, Request, ServingEngine,
                                       plan_sharding,
                                       serving_sharding_rules)
    from kubeshare_tpu.utils.atomicfile import write_atomic
    from kubeshare_tpu.utils.promtext import encode_families

    if len(jax.devices()) < TP:
        raise SystemExit(
            f"need {TP} devices for the tp={TP} mesh, have "
            f"{len(jax.devices())} — run under "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={TP}")

    tokend = find_binary("tpushare-tokend")
    if tokend is None:
        subprocess.run(["make", "-C", os.path.join(
            os.path.dirname(__file__), "..", "native")], check=True,
            capture_output=True)
        tokend = find_binary("tpushare-tokend")

    print("=== 1. model + sharding plan ===")
    config = TransformerConfig(
        d_model=256, n_layers=4, n_heads=8, n_kv_heads=4, d_ff=1024,
        vocab_size=8000, max_seq_len=256, dtype=jnp.float32,
        positional="rope", attention="reference")
    params = transformer_init(jax.random.PRNGKey(0), config)
    decision = plan_sharding(config, TP)
    print(f"tp={TP}: attention {'HEAD-SHARDED' if decision.attn_sharded else 'replicated'} "
          f"({config.n_kv_heads} KV heads -> "
          f"{config.n_kv_heads // TP if decision.attn_sharded else config.n_kv_heads} "
          f"per device), mlp "
          f"{'column/row-split' if decision.mlp_sharded else 'replicated'}, "
          f"lm_head {'sharded' if decision.lm_head_sharded else 'replicated'}")
    for needle, spec in sorted(serving_sharding_rules(decision).items()):
        print(f"  rule: ...{needle!r:24s} -> {spec}")

    print("=== 2. runtime: one tokend cell gating the sharded engine ===")
    workdir = tempfile.mkdtemp(prefix="serve-sharded-")
    uuid = "demo-chip-0"
    write_atomic(os.path.join(workdir, uuid),
                 "1\ndemo/sharded-cell 1.0 1.0 0\n")
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    proc = subprocess.Popen(
        [tokend, "-p", workdir, "-f", uuid, "-P", str(port),
         "-q", "50", "-m", "16", "-w", "1000"],
        stderr=subprocess.DEVNULL)
    deadline = time.time() + 10
    while True:
        try:
            socket.create_connection(("127.0.0.1", port), timeout=1).close()
            break
        except OSError:
            if time.time() >= deadline:
                proc.kill()
                raise RuntimeError(
                    f"tpushare-tokend did not start listening on {port}")
            time.sleep(0.05)

    try:
        client = TokenClient("127.0.0.1", port, "demo/sharded-cell")
        engine = ServingEngine(params, config, EngineConfig(
            num_slots=4, block_size=16, num_blocks=49,
            max_request_len=192, prefill_chunk=32, decode_span=4,
            mesh_spec=MeshSpec(dp=1, tp=TP, sp=1),
            long_context_threshold=32),
            guard=ExecutionGuard(client=client, from_env=False))

        print("=== 3. compile every shape once under the mesh ===")
        engine.warmup()
        warm_counts = engine.compile_counts()
        print(f"warmed programs: "
              f"{ {k: v for k, v in sorted(warm_counts.items())} } — "
              f"each is ONE shard_map dispatch, collectives inside")

        print("=== 4. traffic: ingest prompts + streamers, greedy and "
              "sampled ===")
        rng = np.random.default_rng(7)
        specs = []
        for i in range(3):   # multi-chunk ingest prompts: their full
            specs.append(dict(  # 32-token chunks route through Ulysses
                rid=f"ingest{i}",
                prompt=rng.integers(0, config.vocab_size,
                                    int(rng.integers(80, 129))),
                max_new_tokens=int(rng.integers(6, 13))))
        for i in range(5):   # short-prompt long-decode streamers
            specs.append(dict(
                rid=f"stream{i}",
                prompt=rng.integers(0, config.vocab_size,
                                    int(rng.integers(10, 25))),
                max_new_tokens=int(rng.integers(24, 41))))
        specs.append(dict(  # a sampled stream: its PRNG key schedule
            rid="sampled",  # must survive the mesh bit-exactly
            prompt=rng.integers(0, config.vocab_size, 18),
            max_new_tokens=24, temperature=0.8,
            rng=jax.random.PRNGKey(42)))

        start = time.monotonic()
        for spec in specs:
            engine.submit(Request(**spec))
        results = engine.run()
        elapsed = time.monotonic() - start
        total = 0
        for spec in specs:
            r = results[spec["rid"]]
            total += len(r.tokens)
            print(f"{spec['rid']:8s}: prompt {r.prompt_len:3d} -> "
                  f"{len(r.tokens):2d} tokens, "
                  f"ttft {1e3 * r.ttft:6.1f} ms, "
                  f"done +{1e3 * (r.finished_at - r.submitted_at):6.1f} ms")
        recompiles = sum(engine.compile_counts().values()) - sum(
            warm_counts.values())
        print(f"aggregate: {total} tokens in {elapsed:.2f} s "
              f"({total / elapsed:.0f} tok/s); recompiles after warmup: "
              f"{recompiles}")
        if recompiles:
            raise RuntimeError(
                f"{recompiles} recompilations after warmup — "
                f"static-shape leak in a sharded step")

        print("=== 5. per-device block occupancy ===")
        in_use = engine.allocator.blocks_in_use
        cached = engine.allocator.cached_idle_blocks
        for shard in engine.pool.k.addressable_shards:
            n_layers, blocks, local_heads, block_size, head_dim = \
                shard.data.shape
            per_block = (2 * n_layers * local_heads * block_size
                         * head_dim * engine.pool.k.dtype.itemsize)
            print(f"  {str(shard.device):16s}: {local_heads} KV "
                  f"head(s) of every block; {in_use} in use + "
                  f"{cached} cached of {blocks - 1} "
                  f"({in_use * per_block >> 10} KiB in use, "
                  f"{per_block} B/block locally — "
                  f"1/{TP} of the single-device row)")

        print("=== 6. collective-bytes estimate (the scrape surface) ===")
        for kind, nbytes in sorted(engine.collective_bytes.items()):
            print(f"  {kind:14s}: {nbytes >> 10:8d} KiB fleet-total")
        text = encode_families(engine.collect_metrics())
        for line in text.splitlines():
            if line.startswith("kubeshare_serving_collective_bytes_total"):
                print(f"  scrape: {line}")

        print("=== 7. the mesh changes nothing: single-device replay ===")
        mono = ServingEngine(params, config, EngineConfig(
            num_slots=4, block_size=16, num_blocks=49,
            max_request_len=192, prefill_chunk=32, decode_span=4))
        mono.warmup()
        for spec in specs:
            mono.submit(Request(**spec))
        mono_results = mono.run()
        diverged = [spec["rid"] for spec in specs
                    if list(results[spec["rid"]].tokens)
                    != list(mono_results[spec["rid"]].tokens)]
        if diverged:
            raise RuntimeError(
                f"streams diverged vs the single-device engine: {diverged}")
        print(f"all {len(specs)} streams bit-identical to the "
              f"single-device engine (greedy AND sampled — no collective "
              f"carries a partial sum)")

        import json

        stat = json.loads(TokenClient("127.0.0.1", port, "probe").stat())
        p = stat["pods"]["demo/sharded-cell"]
        print(f"tokend accounting [demo/sharded-cell]: "
              f"grants={p['grants']} charged={p['charged_total_ms']:.0f} ms")
        print("sharded demo complete")
    finally:
        proc.kill()
        proc.wait()


if __name__ == "__main__":
    main()
