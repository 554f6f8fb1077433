"""Disaggregated serving walkthrough: prefill and decode pools as two
token-gated fractional cells, KV chains migrating between them.

The serving-side endgame of the fractional-cell idea (and of
serve_fractional's single-engine story): long prompts never contend
with decode lanes for dispatch slots or HBM bandwidth because they run
in a DIFFERENT pool —

  - a :class:`PrefillPool` and :class:`DecodePool`
    (`serving/disagg.py`): two engine instances with independent block
    allocators and warmup sets, each compiled only for its phase's
    shapes;
  - a :class:`KVMigrator`: when a prompt finishes prefill, its slot's
    block chain is packed through the versioned tier wire format and
    unpacked into freshly reserved decode-pool blocks (guard-only
    sync — the device copy-in overlaps the decode pool's pipelined
    dispatch); migrated bytes flow through a ``ledger_hook`` into the
    token runtime's fractional-HBM ledger, like any
    ``Buffer_CopyToDevice``;
  - a :class:`DisaggRouter`: submit/step/run shaped like the engine's,
    preserving BIT-EXACT streams across the handoff (greedy and
    sampled — this example re-runs the same traffic through a
    monolithic engine at the same total KV budget and asserts every
    stream identical token for token);
  - each pool gated through its OWN tokend pod (prefill cell + decode
    cell, 0.5 share each) — the two-fractional-cells deployment shape.
    Topology is pluggable: ``DisaggTopology("virtual_multislice")``
    instead places the pools on separate slices of a
    ``dryrun_multichip``-style mesh (the dp-over-DCN shape).

Run (no TPU needed; the chip is CPU here, the runtime is real):

    JAX_PLATFORMS=cpu python -m examples.serve_disagg

The split pools have no cell in `BENCHMARK.json` yet: nothing about
them is measured on the chip (`PERF.md` section 7).
"""

from __future__ import annotations

import os
import socket
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
    from kubeshare_tpu.serving import (DisaggRouter, EngineConfig, Request,
                                       ServingEngine)
    from kubeshare_tpu.utils.atomicfile import write_atomic

    tokend = find_binary("tpushare-tokend")
    if tokend is None:
        subprocess.run(["make", "-C", os.path.join(
            os.path.dirname(__file__), "..", "native")], check=True,
            capture_output=True)
        tokend = find_binary("tpushare-tokend")

    print("=== 1. model + split-pool geometry ===")
    config = TransformerConfig(
        d_model=256, n_layers=4, n_heads=8, n_kv_heads=2, d_ff=1024,
        vocab_size=8000, max_seq_len=256, dtype=jnp.float32,
        positional="rope", attention="reference")
    params = transformer_init(jax.random.PRNGKey(0), config)
    # one KV-HBM budget, split: 48 allocatable blocks total = 16
    # prefill + 32 decode (decode holds prompt AND generated rows for
    # every live stream; prefill only prompt covers in flight)
    prefill_ec = EngineConfig(
        num_slots=2, block_size=16, num_blocks=17,
        max_request_len=192, prefill_chunk=32)
    decode_ec = EngineConfig(
        num_slots=4, block_size=16, num_blocks=33,
        max_request_len=192, prefill_chunk=32, decode_span=4)
    print(f"prefill pool: {prefill_ec.num_slots} slots, "
          f"{prefill_ec.num_blocks - 1} blocks; decode pool: "
          f"{decode_ec.num_slots} slots, {decode_ec.num_blocks - 1} "
          f"blocks (same {prefill_ec.num_blocks - 1 + decode_ec.num_blocks - 1}"
          f"-block total a monolithic engine would get)")

    print("=== 2. runtime: one tokend, two fractional cells ===")
    workdir = tempfile.mkdtemp(prefix="serve-disagg-")
    uuid = "demo-chip-0"
    write_atomic(os.path.join(workdir, uuid),
                 "2\ndemo/prefill-cell 1.0 0.5 0\n"
                 "demo/decode-cell 1.0 0.5 0\n")
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
        p_client = TokenClient("127.0.0.1", port, "demo/prefill-cell")
        d_client = TokenClient("127.0.0.1", port, "demo/decode-cell")
        ledger = {"migrate": 0, "demote": 0, "promote": 0}

        def ledger_hook(nbytes: int, kind: str) -> None:
            # migration/tier traffic charged against the decode cell's
            # fractional-HBM ledger like any Buffer_CopyToDevice, then
            # credited back once the transient staging copy dies
            ok, _, _ = d_client.request_memory(nbytes)
            if not ok:
                raise RuntimeError(f"ledger refused {nbytes}B {kind}")
            d_client.request_memory(-nbytes)
            ledger[kind] += nbytes

        router = DisaggRouter(
            params, config, prefill_ec, decode_ec,
            guard=ExecutionGuard(client=p_client, from_env=False),
            decode_guard=ExecutionGuard(client=d_client, from_env=False),
            shared_tier_bytes=1 << 20,    # the cross-pool cache bus
            ledger_hook=ledger_hook)

        print("=== 3. compile each pool once (zero recompiles) ===")
        router.warmup()
        warm_counts = router.compile_counts()
        p_warm = sorted(k for k in warm_counts if k.startswith("prefill."))
        d_warm = sorted(k for k in warm_counts if k.startswith("decode."))
        print(f"prefill-pool programs: {len(p_warm)}; decode-pool "
              f"programs: {len(d_warm)} (each pool warms ONLY its "
              f"phase's shapes)")

        print("=== 4. traffic: ingest prompts + streamers, greedy and "
              "sampled ===")
        rng = np.random.default_rng(7)
        specs = []
        for i in range(3):   # multi-chunk ingest prompts, few tokens out
            specs.append(dict(
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
            rid="sampled",  # must survive the migration bit-exactly
            prompt=rng.integers(0, config.vocab_size, 18),
            max_new_tokens=24, temperature=0.8,
            rng=jax.random.PRNGKey(42)))

        start = time.monotonic()
        for spec in specs:
            router.submit(Request(**spec))
        results = router.run()
        elapsed = time.monotonic() - start
        total = 0
        for spec in specs:
            r = results[spec["rid"]]
            total += len(r.tokens)
            print(f"{spec['rid']:8s}: prompt {r.prompt_len:3d} -> "
                  f"{len(r.tokens):2d} tokens, "
                  f"ttft {1e3 * r.ttft:6.1f} ms, "
                  f"done +{1e3 * (r.finished_at - r.submitted_at):6.1f} ms")
        end_counts = router.compile_counts()
        recompiles = sum(end_counts.values()) - sum(warm_counts.values())
        mig = router.migrator
        print(f"aggregate: {total} tokens in {elapsed:.2f} s "
              f"({total / elapsed:.0f} tok/s); recompiles after warmup: "
              f"{recompiles}")
        print(f"migration: {mig.delivered}/{mig.migrations} chains "
              f"delivered, {mig.migrated_bytes >> 10} KiB over the wire "
              f"format; ledger saw migrate={ledger['migrate'] >> 10} KiB "
              f"demote={ledger['demote'] >> 10} KiB "
              f"promote={ledger['promote'] >> 10} KiB")
        print(f"phase split: {router.prefill.prefill_chunks} prefill "
              f"chunks ({router.prefill.decode_steps} decode steps — "
              f"must be 0) vs {router.decode.decode_steps} decode spans "
              f"({router.decode.prefill_chunks} prefill chunks — must "
              f"be 0)")
        if recompiles:
            raise RuntimeError(
                f"{recompiles} recompilations after warmup — "
                f"static-shape leak in a pool's steps")
        if mig.delivered != len(specs):
            raise RuntimeError(
                f"{mig.delivered} chains delivered for {len(specs)} "
                f"requests — some handoff never completed")

        print("=== 5. the handoff changes nothing: monolithic replay ===")
        mono = ServingEngine(params, config, EngineConfig(
            num_slots=decode_ec.num_slots, block_size=16,
            num_blocks=prefill_ec.num_blocks + decode_ec.num_blocks - 1,
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
                f"streams diverged vs the monolithic engine: {diverged}")
        print(f"all {len(specs)} streams bit-identical to the monolithic "
              f"engine (greedy AND sampled — key schedules survived the "
              f"migration)")

        import json

        stat = json.loads(TokenClient("127.0.0.1", port, "probe").stat())
        for pod in ("demo/prefill-cell", "demo/decode-cell"):
            p = stat["pods"][pod]
            print(f"tokend accounting [{pod}]: grants={p['grants']} "
                  f"charged={p['charged_total_ms']:.0f} ms, "
                  f"mem_used={p['mem_used']} (staging copies credited "
                  f"back)")
        print("disagg demo complete")
    finally:
        proc.kill()
        proc.wait()


if __name__ == "__main__":
    main()
