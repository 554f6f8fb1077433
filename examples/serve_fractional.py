"""Fractional serving walkthrough: the continuous-batching engine on a
token-gated shared chip.

The serving twin of demo_e2e's training story (the reference shared GPUs
only for training pods — serving on a fraction of a chip is a capability
this framework adds):

  - a GQA Transformer (the KV cache, decode's dominant HBM cost, shrinks
    by the query-head group factor)
  - a block-paged KV cache (`serving/kv_blocks.py`): HBM reserved per
    request actually admitted, not `max_seq_len` per slot
  - the continuous-batching engine (`serving/engine.py`): mixed-length
    requests queue through a static slot pool — admitted mid-flight into
    freed slots, chunked prefill FUSED into the decode dispatch
    (stall-free mixed batching: in-flight streams never wait behind a
    prompt, and the fused chunk is bounded by `mixed_prefill_budget`),
    retired on max-tokens with their blocks recycled — zero
    recompilation after warmup; self-drafting speculative decoding on
    (`speculative=True`): prompt-lookup drafts verified in one batched
    dispatch, streams bit-exact with speculation off by construction
  - every XLA dispatch gated through the native token runtime exactly as
    a 0.5-chip pod's would be: tpushare-tokend (real C++ binary) grants
    budgeted time-quota tokens, the ExecutionGuard charges measured step
    time back (the engine charges EVERY prefill chunk and decode span)

Run (no TPU needed; the chip is CPU here, the runtime is real):

    JAX_PLATFORMS=cpu python -m examples.serve_fractional

What this path costs on the chip, alone and beside a co-tenant, is
measured by `python3 -m chipbench.run` (`scb-1b.gen.rate`,
`scb-1b.gen.shared`; numbers in `PERF.md`); this run prints counts.
"""

from __future__ import annotations

import os
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax

from kubeshare_tpu.utils.compile_cache import configure_compile_cache

configure_compile_cache()

import jax.numpy as jnp
import numpy as np


def main() -> None:
    from kubeshare_tpu.isolation import ExecutionGuard, TokenClient
    from kubeshare_tpu.models.transformer import (
        TransformerConfig, transformer_init)
    from kubeshare_tpu.runtime import find_binary
    from kubeshare_tpu.serving import (QOS_OPPORTUNISTIC, EngineConfig,
                                       Request, ServingEngine,
                                       TenantRegistry, TenantSpec)
    from kubeshare_tpu.utils.atomicfile import write_atomic

    tokend = find_binary("tpushare-tokend")
    if tokend is None:
        subprocess.run(["make", "-C", os.path.join(
            os.path.dirname(__file__), "..", "native")], check=True,
            capture_output=True)
        tokend = find_binary("tpushare-tokend")

    print("=== 1. model: GQA flagship (8 query heads over 2 KV heads) ===")
    config = TransformerConfig(
        d_model=256, n_layers=4, n_heads=8, n_kv_heads=2, d_ff=1024,
        vocab_size=8000, max_seq_len=256, dtype=jnp.float32,
        positional="rope", attention="reference")
    params = transformer_init(jax.random.PRNGKey(0), config)
    engine_config = EngineConfig(
        num_slots=4, block_size=16, num_blocks=33,  # 32 blocks = 512 rows
        max_request_len=192, prefill_chunk=32, decode_span=4,
        # stall-free mixed batching (the default, spelled out): a prod
        # admission's prefill chunks ride the decode dispatch — capped
        # at 16 fused prefill tokens per step, the bound on the extra
        # latency any in-flight stream pays per admission
        mixed=True, mixed_prefill_budget=16,
        # KV cache tiering: prefixes evicted from the 32-block pool
        # demote into a 1 MB host-RAM tier (~31 serialized blocks)
        # instead of being destroyed, and promote back on a trie hit —
        # the QoS-aware policy protects prod-charged host bytes from
        # batch pressure
        host_tier_bytes=1 << 20, tier_policy="qos",
        # self-drafting speculative decoding: each lane's prompt-lookup
        # drafter proposes up to draft_len tokens, one width-W verify
        # dispatch scores every lane, and exact-match acceptance keeps
        # all streams bit-identical to speculation off
        speculative=True, draft_len=4,
        # device-resident multi-step loop: on pure-decode steps, ONE
        # compiled launch runs up to 4 scheduler iterations of the
        # decode span on device (sampling, stop detection and the
        # emitted-token ring included) — the host planner fires per
        # launch, not per span, and streams stay bit-exact with K=1
        steps_per_launch=4)
    dense_bytes = (2 * config.n_layers * engine_config.num_slots
                   * config.kv_heads * config.max_seq_len
                   * config.head_dim * 4)
    paged_bytes = ((engine_config.num_blocks - 1)
                   * 2 * config.n_layers * config.kv_heads
                   * engine_config.block_size * config.head_dim * 4)
    print(f"KV pool: {paged_bytes / 1e6:.1f} MB in "
          f"{engine_config.num_blocks - 1} blocks (dense caches for "
          f"{engine_config.num_slots} slots would pin "
          f"{dense_bytes / 1e6:.1f} MB)")

    print("=== 2. runtime: tokend with a 0.5-share serving pod ===")
    workdir = tempfile.mkdtemp(prefix="serve-demo-")
    uuid = "demo-chip-0"
    write_atomic(os.path.join(workdir, uuid), "1\ndemo/serve-pod 1.0 0.5 0\n")
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    proc = subprocess.Popen(
        [tokend, "-p", workdir, "-f", uuid, "-P", str(port),
         "-q", "50", "-m", "5", "-w", "1000"],
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
        client = TokenClient("127.0.0.1", port, "demo/serve-pod")
        guard = ExecutionGuard(client=client, from_env=False)
        # two tenants INSIDE the pod: the paper's Guarantee/Opportunistic
        # split applied to the serving plane — "prod" is guaranteed,
        # "batch" is opportunistic with a KV-HBM quota of 3/4 of the
        # pool (loose enough to soak every slot, so prod must preempt)
        # and is the preemption victim when prod can't admit
        tenants = TenantRegistry([
            TenantSpec("prod"),
            TenantSpec("batch", qos_class=QOS_OPPORTUNISTIC,
                       kv_block_quota=3 * (engine_config.num_blocks - 1) // 4),
        ])
        engine = ServingEngine(params, config, engine_config, guard=guard,
                               tenants=tenants)

        print("=== 3. compile once, serve any mix (zero recompiles) ===")
        # warm the jit caches OUTSIDE the gated window, like the
        # training pods warm their step
        engine.warmup()
        warm_counts = engine.compile_counts()
        print(f"compiled steps: {warm_counts}")

        print("=== 4. requests: an opportunistic flood, then prod "
              "traffic preempting through it ===")
        # half the prod prompts open with one shared 24-token prefix
        # (the system-prompt traffic shape) so the radix prefix cache
        # has something to hit once early sharers retire.  The batch
        # flood is submitted FIRST and holds every slot with long
        # decodes — prod admissions preempt it (the victims' blocks go
        # into the prefix cache, so their resumes are nearly free).
        rng = np.random.default_rng(0)
        shared_prefix = rng.integers(0, config.vocab_size, 24)
        requests = []
        for i in range(6):  # the flood: long decodes, all slots
            prompt = rng.integers(0, config.vocab_size,
                                  int(rng.integers(12, 49)))
            requests.append(Request(f"batch{i}", prompt,
                                    int(rng.integers(48, 97)),
                                    tenant="batch"))
            engine.submit(requests[-1])
        # let the flood actually OCCUPY the slots (live-traffic shape:
        # prod arrives while batch decodes) — prod must then preempt
        for _ in range(24):
            engine.step()
        for i in range(8):
            prompt_len = int(rng.integers(12, 97))
            max_new = int(rng.integers(8, 49))
            prompt = rng.integers(0, config.vocab_size, prompt_len)
            if i % 2:
                prompt = np.concatenate([shared_prefix, prompt[24:]]) \
                    if prompt_len > 24 else prompt
            requests.append(Request(f"prod{i}", prompt, max_new,
                                    tenant="prod"))
            engine.submit(requests[-1])
        start = time.monotonic()
        results = engine.run()
        elapsed = time.monotonic() - start
        total = 0
        for req in requests:
            r = results[req.rid]
            total += len(r.tokens)
            print(f"{req.rid:7s} [{req.tenant:5s}]: prompt "
                  f"{r.prompt_len:3d} -> {len(r.tokens):2d} tokens, "
                  f"ttft {1e3 * r.ttft:6.1f} ms, "
                  f"done +{1e3 * (r.finished_at - r.submitted_at):6.1f} ms")
        print(f"qos: preemptions by tenant {engine.preemptions}; "
              f"tokens by tenant {engine.tenant_tokens}; "
              f"batch quota occupancy "
              f"{engine.allocator.tenant_usage('batch')}/"
              f"{tenants.get('batch').kv_block_quota} blocks")
        end_counts = engine.compile_counts()
        recompiles = sum(end_counts.values()) - sum(warm_counts.values())
        print(f"aggregate: {total} tokens in {elapsed:.2f} s "
              f"({total / elapsed:.0f} tok/s); "
              f"peak blocks {engine.peak_blocks_in_use}/"
              f"{engine.allocator.num_blocks - 1}; "
              f"recompiles after warmup: {recompiles} "
              f"({end_counts} vs {warm_counts})")
        print(f"prefix cache: {engine.prefix_hit_requests} hit requests, "
              f"{engine.prefix_hit_tokens} prompt tokens skipped, "
              f"{engine.cow_copies} CoW copies, "
              f"{engine.allocator.cached_idle_blocks} blocks cached idle")
        print(f"mixed batching: {engine.mixed_steps} fused dispatches "
              f"(prefill chunks that rode a decode span instead of "
              f"stalling it), {engine.prefill_chunks - engine.mixed_steps}"
              f" standalone chunks, "
              f"{engine.decode_steps - engine.mixed_steps} standalone "
              f"spans")
        drafted = sum(engine.spec_drafted.values())
        accepted = sum(engine.spec_accepted.values())
        print(f"speculative decoding: {engine.verify_steps} verify "
              f"dispatches ({engine.mixed_verify_steps} fused with "
              f"prefill), {drafted} tokens drafted, {accepted} accepted "
              f"({100 * accepted / max(1, drafted):.0f}% — random-weight "
              f"traffic drafts poorly; repetitive traffic is the win), "
              f"by tenant drafted={dict(engine.spec_drafted)} "
              f"accepted={dict(engine.spec_accepted)}")
        print(f"kv tier ({engine_config.tier_policy} policy, "
              f"{engine_config.host_tier_bytes >> 10} KiB host budget): "
              f"{engine.tier_demoted_blocks} blocks demoted host-side, "
              f"{engine.tier_promoted_blocks} promoted back, "
              f"{engine.tier_dropped_blocks} dropped, "
              f"{engine.tier_hit_requests} host-hit requests "
              f"({engine.tier_hit_tokens} tokens recovered), "
              f"{len(engine.host_tier)} entries / "
              f"{engine.host_tier.used_bytes >> 10} KiB resident; "
              f"evictions by reason {engine.evictions_by_reason}")
        if recompiles:
            raise RuntimeError(
                f"{recompiles} recompilations after warmup — static-shape "
                f"leak in the serving steps")

        import json

        stat = json.loads(TokenClient("127.0.0.1", port, "probe").stat())
        pod = stat["pods"]["demo/serve-pod"]
        print(f"tokend accounting: grants={pod['grants']} "
              f"charged={pod['charged_total_ms']:.0f} ms "
              f"(share limit 1.0, request 0.5) — every prefill chunk and "
              f"decode span charged through the guard")
        print("serve demo complete")
    finally:
        proc.kill()
        proc.wait()


if __name__ == "__main__":
    main()
