"""Native token-runtime tests: real tpushare-tokend / tpushare-pmgr binaries
over TCP, the Python + ctypes clients, the supervisor, and share enforcement."""

import os
import socket
import subprocess
import threading
import time

import pytest

from kubeshare_tpu.isolation import ExecutionGuard, NativeTokenClient, TokenClient
from kubeshare_tpu.isolation.guard import apply_hbm_cap
from kubeshare_tpu.runtime import ChipSupervisor, find_binary
from kubeshare_tpu.utils.atomicfile import write_atomic

from native_helpers import free_port, free_ports, wait_listening

TOKEND = find_binary("tpushare-tokend")
PMGR = find_binary("tpushare-pmgr")

pytestmark = pytest.mark.skipif(
    TOKEND is None or PMGR is None, reason="native binaries not built"
)


def _start_tokend(tmp_path, exclusive=False, config=None):
    config_dir = tmp_path / "config"
    config_dir.mkdir(exist_ok=True)
    uuid = "chip-0"
    write_atomic(
        str(config_dir / uuid),
        config or "2\nns/pod-a 1.0 0.5 1000000\nns/pod-b 1.0 0.3 500000\n",
    )
    port = free_port()
    cmd = [TOKEND, "-p", str(config_dir), "-f", uuid, "-P", str(port),
           "-q", "50", "-m", "5", "-w", "1000"]
    if exclusive:
        cmd.append("-x")
    proc = subprocess.Popen(cmd, stderr=subprocess.DEVNULL)
    wait_listening(port)
    return proc, {"port": port, "config_dir": config_dir, "uuid": uuid}


@pytest.fixture
def tokend(tmp_path):
    """Concurrent-mode (default) tokend, two pods at 0.5/0.3."""
    proc, info = _start_tokend(tmp_path)
    yield info
    proc.kill()
    proc.wait()


@pytest.fixture
def tokend_exclusive(tmp_path):
    """Exclusive-mode (-x, Gemini-parity) tokend."""
    proc, info = _start_tokend(tmp_path, exclusive=True)
    yield info
    proc.kill()
    proc.wait()


class TestTokend:
    def test_acquire_release(self, tokend):
        client = TokenClient("127.0.0.1", tokend["port"], "ns/pod-a")
        quota = client.acquire()
        assert quota > 0
        client.release(5.0)
        assert '"ns/pod-a"' in client.stat()
        client.close()

    def test_exclusive_token(self, tokend_exclusive):
        a = TokenClient("127.0.0.1", tokend_exclusive["port"], "ns/pod-a")
        b = TokenClient("127.0.0.1", tokend_exclusive["port"], "ns/pod-b")
        a.acquire()
        granted = []

        def try_b():
            b.acquire()
            granted.append(time.monotonic())
            b.release(1.0)

        t = threading.Thread(target=try_b)
        t.start()
        time.sleep(0.2)
        assert not granted  # b blocked while a holds the token
        a.release(1.0)
        t.join(timeout=5)
        assert granted
        a.close(); b.close()

    def test_multi_grant_disconnect_abandons_all(self, tokend):
        """ADVICE r1: one connection acquiring several tokens (or tokens
        for two pod names) then dying must abandon every grant — a stale
        holders_ entry would wedge exclusive-mode grants forever."""
        import json

        s = socket.create_connection(("127.0.0.1", tokend["port"]))
        for req in (b"REQ ns/pod-a 1.0\n", b"REQ ns/pod-a 1.0\n",
                    b"REQ ns/pod-b 1.0\n"):
            s.sendall(req)
            reply = b""
            while not reply.endswith(b"\n"):
                reply += s.recv(1)
            assert reply.startswith(b"TOK ")
        probe = TokenClient("127.0.0.1", tokend["port"], "x")
        assert json.loads(probe.stat())["holders"] == 3  # a(x2) + b
        s.close()  # die holding three grants
        deadline = time.time() + 5
        holders = None
        while time.time() < deadline:
            holders = json.loads(probe.stat())["holders"]
            if holders == 0:
                break
            time.sleep(0.05)
        probe.close()
        assert holders == 0

    def test_blocking_acquire_grants_immediately_when_free(self, tokend):
        # raw REQB against a free chip answers TOK without parking
        s = socket.create_connection(("127.0.0.1", tokend["port"]))
        s.sendall(b"REQB ns/pod-a 1.0 2000\n")
        reply = b""
        while not reply.endswith(b"\n"):
            reply += s.recv(1)
        assert reply.startswith(b"TOK ")
        s.close()

    def test_blocking_acquire_parks_until_timeout(self, tokend_exclusive):
        """REQB with a busy chip parks server-side and answers WAIT only
        after the requested timeout — the long-poll contract (the client
        then simply re-issues; no 5 ms poll storm)."""
        a = TokenClient("127.0.0.1", tokend_exclusive["port"], "ns/pod-a")
        a.acquire()
        try:
            s = socket.create_connection(
                ("127.0.0.1", tokend_exclusive["port"]))
            start = time.monotonic()
            s.sendall(b"REQB ns/pod-b 1.0 400\n")
            reply = b""
            while not reply.endswith(b"\n"):
                reply += s.recv(1)
            elapsed = time.monotonic() - start
            assert reply.startswith(b"WAIT ")
            assert elapsed >= 0.3, f"REQB returned early ({elapsed:.3f}s)"
            s.close()
        finally:
            a.release(1.0)
            a.close()

    def test_blocking_acquire_wakes_on_release(self, tokend_exclusive):
        """The release must WAKE a parked REQB immediately (event-driven
        handoff), not at a poll tick: measured end-to-end latency from
        release to grant stays far under the 2 s park window."""
        a = TokenClient("127.0.0.1", tokend_exclusive["port"], "ns/pod-a")
        b = TokenClient("127.0.0.1", tokend_exclusive["port"], "ns/pod-b")
        a.acquire()
        granted_at = []

        def wait_b():
            b.acquire()
            granted_at.append(time.monotonic())
            b.release(1.0)

        t = threading.Thread(target=wait_b)
        t.start()
        time.sleep(0.3)  # b is parked server-side by now
        released_at = time.monotonic()
        a.release(1.0)
        t.join(timeout=5)
        assert granted_at, "parked REQB never granted after release"
        assert granted_at[0] - released_at < 0.2, (
            f"handoff took {granted_at[0] - released_at:.3f}s — not "
            f"event-driven")
        a.close(); b.close()

    def test_client_falls_back_to_req_on_old_daemon(self):
        """A TokenClient against a daemon that answers ERR for REQB must
        degrade to REQ polling transparently."""
        server = socket.socket()
        server.bind(("127.0.0.1", 0))
        server.listen(1)
        port = server.getsockname()[1]
        replies = []

        def serve():
            conn, _ = server.accept()
            f = conn.makefile("rw", newline="\n")
            for line in f:
                replies.append(line.strip())
                if line.startswith("REQB"):
                    f.write("ERR unknown command\n")
                elif line.startswith("REQ"):
                    f.write("TOK 100\n")
                f.flush()

        t = threading.Thread(target=serve, daemon=True)
        t.start()
        client = TokenClient("127.0.0.1", port, "ns/pod-a")
        assert client.acquire() == 100.0
        assert any(r.startswith("REQB") for r in replies)
        assert any(r.startswith("REQ ") for r in replies)
        # the fallback is sticky: the next acquire goes straight to REQ
        assert client.acquire() == 100.0
        assert sum(1 for r in replies if r.startswith("REQB")) == 1
        client.close()
        server.close()

    def test_exclusive_reqb_contention_progresses(self, tmp_path):
        """Lost-wakeup stress for the REQB park/notify path: several
        clients fighting over an exclusive chip must all keep making
        progress — a missed notify would strand a parked waiter until
        its 2s window expires (visible as a collapsed grant count)."""
        proc, info = _start_tokend(
            tmp_path,
            config=("4\nns/p0 1.0 0.25 0\nns/p1 1.0 0.25 0\n"
                    "ns/p2 1.0 0.25 0\nns/p3 1.0 0.25 0\n"),
            exclusive=True)
        try:
            counts = {}
            lock = threading.Lock()

            def worker(pod):
                client = TokenClient("127.0.0.1", info["port"], pod)
                done = 0
                stop = time.monotonic() + 2.0
                while time.monotonic() < stop:
                    client.acquire()
                    client.release(0.5)
                    done += 1
                with lock:
                    counts[pod] = done
                client.close()

            threads = [threading.Thread(target=worker, args=(f"ns/p{i}",))
                       for i in range(4)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=15)
            assert all(not t.is_alive() for t in threads), counts
            # every worker finished NORMALLY (a crashed worker never
            # writes its count — an empty/partial dict must fail, not
            # pass vacuously) and made real progress (a stranded waiter
            # would show single-digit counts from repeated park expiries)
            assert sorted(counts) == [f"ns/p{i}" for i in range(4)], counts
            assert all(c >= 50 for c in counts.values()), counts
        finally:
            proc.kill()
            proc.wait()

    def test_client_honors_hint_from_poll_shaped_server(self):
        """A WAIT answered well before the park window (old daemon or the
        -G gang gate, which degrades REQB to poll-shaped) must make the
        client sleep the retry hint — NOT re-issue REQB in a tight loop
        (code-review r5: busy-spin burned the serial host core)."""
        server = socket.socket()
        server.bind(("127.0.0.1", 0))
        server.listen(1)
        port = server.getsockname()[1]
        seen = []

        def serve():
            conn, _ = server.accept()
            f = conn.makefile("rw", newline="\n")
            for line in f:
                seen.append((time.monotonic(), line.strip()))
                if len(seen) >= 4:
                    f.write("TOK 100\n")
                else:
                    f.write("WAIT 50\n")  # immediate, poll-shaped
                f.flush()

        t = threading.Thread(target=serve, daemon=True)
        t.start()
        client = TokenClient("127.0.0.1", port, "ns/pod-a")
        assert client.acquire() == 100.0
        # 3 WAITs at a 50ms hint: the acquire must have taken >= ~150ms
        # (a busy-spin finishes in ~1ms and sends hundreds of requests)
        assert len(seen) == 4
        assert seen[-1][0] - seen[0][0] >= 0.12
        client.close()
        server.close()

    def test_concurrent_holders(self, tokend):
        # default mode: both pods may hold tokens simultaneously
        a = TokenClient("127.0.0.1", tokend["port"], "ns/pod-a")
        b = TokenClient("127.0.0.1", tokend["port"], "ns/pod-b")
        assert a.acquire() > 0
        assert b.acquire() > 0  # does not block
        import json

        stat = json.loads(a.stat())
        assert stat["mode"] == "concurrent" and stat["holders"] == 2
        a.release(1.0); b.release(1.0)
        a.close(); b.close()

    def test_limit_cap_throttles(self, tmp_path):
        # pod capped at limit 0.2 of a 1000ms window; charging 100ms per
        # token must throttle grant rate to ~2 per window
        proc, info = _start_tokend(tmp_path, config="1\nns/greedy 0.2 0.1 0\n")
        try:
            client = TokenClient("127.0.0.1", info["port"], "ns/greedy")
            grants = 0
            start = time.monotonic()
            while time.monotonic() - start < 1.5:
                client.acquire()
                client.release(100.0)  # claims 100ms device time per token
                grants += 1
            client.close()
            # uncapped this loop does hundreds of grants; the 0.2 limit
            # allows roughly 0.2*1000ms/100ms = 2 per window plus decay slack
            assert grants <= 8, grants
        finally:
            proc.kill()
            proc.wait()

    def test_memory_cap(self, tokend):
        client = TokenClient("127.0.0.1", tokend["port"], "ns/pod-b")
        ok, used, cap = client.request_memory(400000)
        assert ok and used == 400000 and cap == 500000
        ok, used, cap = client.request_memory(200000)
        assert not ok and used == 400000  # 600000 > cap
        ok, _, _ = client.request_memory(-400000)
        assert ok
        client.close()

    def test_dropped_holder_recovers(self, tokend):
        a = TokenClient("127.0.0.1", tokend["port"], "ns/pod-a")
        a.acquire()
        a.close()  # dies holding the token
        b = TokenClient("127.0.0.1", tokend["port"], "ns/pod-b")
        quota = b.acquire()  # must not deadlock
        assert quota > 0
        b.release(1.0)
        b.close()

    def test_config_reload(self, tokend):
        # new pod appears in config; tokend picks it up via inotify
        write_atomic(
            str(tokend["config_dir"] / tokend["uuid"]),
            "1\nns/pod-c 0.5 0.2 12345\n",
        )
        time.sleep(1.0)
        client = TokenClient("127.0.0.1", tokend["port"], "ns/pod-c")
        client.acquire()
        client.release(1.0)
        stat = client.stat()
        assert '"ns/pod-c"' in stat and '"mem_cap":12345' in stat
        client.close()

    def test_share_enforcement(self, tokend):
        """A greedy pod and a modest pod contend; grants must respect the
        guarantee ordering (pod-a request 0.5 vs pod-b 0.3)."""
        counts = {"ns/pod-a": 0, "ns/pod-b": 0}
        stop = time.monotonic() + 2.0

        def worker(pod):
            client = TokenClient("127.0.0.1", tokend["port"], pod)
            while time.monotonic() < stop:
                client.acquire()
                time.sleep(0.01)  # simulate 10ms of chip work
                client.release(10.0)
                counts[pod] += 1
            client.close()

        threads = [threading.Thread(target=worker, args=(p,)) for p in counts]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        total = sum(counts.values())
        assert total > 50  # token churn is cheap
        # both made progress; a's guaranteed share is larger
        assert counts["ns/pod-a"] > 0 and counts["ns/pod-b"] > 0
        share_a = counts["ns/pod-a"] / total
        assert share_a >= 0.45  # got at least ~its request share


class TestPmgr:
    def test_identity_stamping(self, tokend):
        pmgr_port = free_port()
        env = dict(
            os.environ,
            SCHEDULER_IP="127.0.0.1",
            SCHEDULER_PORT=str(tokend["port"]),
            POD_MANAGER_IP="127.0.0.1",
            POD_MANAGER_PORT=str(pmgr_port),
            POD_NAME="ns/pod-a",
        )
        proc = subprocess.Popen([PMGR], env=env, stderr=subprocess.DEVNULL)
        try:
            wait_listening(pmgr_port)
            # client lies about its pod name; pmgr stamps the real one
            client = TokenClient("127.0.0.1", pmgr_port, "ns/pod-b")
            client.acquire()
            client.release(2.0)
            stat = client.stat()
            assert '"ns/pod-a":{' in stat
            # pod-a accounted the grant, pod-b didn't
            import json

            pods = json.loads(stat)["pods"]
            assert pods["ns/pod-a"]["grants"] == 1
            assert pods.get("ns/pod-b", {}).get("grants", 0) == 0
            client.close()
        finally:
            proc.kill()
            proc.wait()


class TestNativeClient:
    def test_ctypes_client(self, tokend):
        client = NativeTokenClient("127.0.0.1", tokend["port"], "ns/pod-a")
        quota = client.acquire(1.0)
        assert quota > 0
        client.release(2.0)
        ok, _, _ = client.request_memory(1000)
        assert ok
        client.close()


class TestSupervisor:
    def test_end_to_end(self, tmp_path):
        """configd-style files -> supervisor -> tokend + pmgr -> client."""
        config_dir = tmp_path / "config"
        port_dir = tmp_path / "ports"
        config_dir.mkdir(); port_dir.mkdir()
        uuid = "chip-0"
        tokend_port = free_port()
        pmgr_port = free_port()
        write_atomic(str(config_dir / uuid), "1\nns/p1 1.0 0.5 1000\n")
        write_atomic(str(port_dir / uuid), f"1\nns/p1 {pmgr_port}\n")
        with ChipSupervisor(
            uuid,
            config_dir=str(config_dir),
            port_dir=str(port_dir),
            tokend_port=tokend_port,
            poll_interval=0.1,
        ) as supervisor:
            wait_listening(tokend_port)
            wait_listening(pmgr_port)
            client = TokenClient("127.0.0.1", pmgr_port, "ignored")
            assert client.acquire() > 0
            client.release(1.0)
            client.close()
            # pod removed -> pmgr reaped
            write_atomic(str(port_dir / uuid), "0\n")
            deadline = time.time() + 5
            while supervisor.pod_managers and time.time() < deadline:
                time.sleep(0.1)
            assert not supervisor.pod_managers


class TestGuard:
    def test_guard_gates_and_measures(self, tokend):
        client = TokenClient("127.0.0.1", tokend["port"], "ns/pod-a")
        guard = ExecutionGuard(client=client, from_env=False)
        calls = []

        @guard
        def step(x):
            calls.append(x)
            time.sleep(0.005)
            return x * 2

        assert step(21) == 42
        assert guard.tokens_acquired == 1
        assert guard.total_gated_ms >= 5.0
        client.close()

    def test_guard_passthrough_without_broker(self):
        guard = ExecutionGuard(client=None, from_env=False)
        assert not guard.gated

        @guard
        def step(x):
            return x + 1

        assert step(1) == 2

    def test_apply_hbm_cap(self):
        env = {"TPUSHARE_MEM_FRACTION": "0.5000"}
        assert apply_hbm_cap(env) == 0.5
        assert env["XLA_PYTHON_CLIENT_MEM_FRACTION"] == "0.5000"
        assert env["XLA_PYTHON_CLIENT_PREALLOCATE"] == "false"
        assert apply_hbm_cap({}) is None
        assert apply_hbm_cap({"TPUSHARE_MEM_FRACTION": "2.0"}) is None


class TestServingLedgerWiring:
    """The serving plane's transfer-byte hook -> tokend MEM verb: every
    KV byte the disaggregated engine stages host-side (tier demotes,
    promotions, prefill->decode chain migrations) can be charged through
    ``TokenClient.request_memory`` — the same fractional-HBM ledger the
    LD_PRELOAD shim debits for ``PJRT_Buffer_CopyToDevice``, so a pod's
    cache-tier traffic is accounted like any other device copy."""

    def test_disagg_ledger_hook_charges_and_credits_broker(self, tokend):
        import json

        import jax
        import jax.numpy as jnp
        import numpy as np

        from kubeshare_tpu.models.transformer import (TransformerConfig,
                                                      transformer_init)
        from kubeshare_tpu.serving import DisaggRouter, EngineConfig, Request

        client = TokenClient("127.0.0.1", tokend["port"], "ns/pod-a")
        moved = []

        def hook(nbytes, kind):
            # charge the staging copy, credit it once landed — the
            # transient CopyToDevice shape; a persistent-cache policy
            # would keep the charge until the tier entry dies
            ok, used, cap = client.request_memory(nbytes)
            assert ok, (kind, nbytes, used, cap)
            ok, _, _ = client.request_memory(-nbytes)
            assert ok
            moved.append((kind, nbytes))

        config = TransformerConfig(
            vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
            max_seq_len=64, dtype=jnp.float32, attention="reference")
        params = transformer_init(jax.random.PRNGKey(0), config)
        router = DisaggRouter(
            params, config,
            EngineConfig(num_slots=2, block_size=4, num_blocks=17,
                         max_request_len=48, prefill_chunk=8, mixed=False),
            EngineConfig(num_slots=2, block_size=4, num_blocks=13,
                         max_request_len=48, prefill_chunk=8, mixed=False),
            shared_tier_bytes=1 << 20, ledger_hook=hook)
        router.warmup()
        rng = np.random.default_rng(3)
        for i in range(4):
            router.submit(Request(
                f"r{i}", rng.integers(0, 64, 12).astype(np.int32), 6))
        router.run()
        kinds = {k for k, _ in moved}
        assert "migrate" in kinds and "demote" in kinds
        assert sum(n for k, n in moved if k == "migrate") \
            == router.migrator.migrated_bytes
        # every charge was credited: the broker ledger is back to zero
        stat = json.loads(client.stat())["pods"]["ns/pod-a"]
        assert stat["mem_used"] == 0
        client.close()


class TestInterposer:
    """LD_PRELOAD path: a driver dlopens a fake PJRT plugin the way JAX
    loads libtpu; libtpushim must gate every Execute through the tokend."""

    def _paths(self):
        base = os.path.join(os.path.dirname(__file__), "..", "native", "build")
        shim = os.path.abspath(os.path.join(base, "libtpushim.so.1"))
        plugin = os.path.abspath(os.path.join(base, "fake_pjrt_plugin.so"))
        driver = os.path.abspath(os.path.join(base, "interposer_driver"))
        if not all(os.path.exists(p) for p in (shim, plugin, driver)):
            pytest.skip("interposer fixtures not built (make -C native test-fixtures)")
        return shim, plugin, driver

    def _run_driver(self, tokend, driver_args, extra_env=None, pod="ns/pod-a"):
        """Start a pmgr for `pod`, run the driver under LD_PRELOAD, return
        (CompletedProcess, stat_dict)."""
        import json

        shim, plugin, driver = self._paths()
        pmgr_port = free_port()
        pmgr_env = dict(
            os.environ,
            SCHEDULER_IP="127.0.0.1",
            SCHEDULER_PORT=str(tokend["port"]),
            POD_MANAGER_IP="127.0.0.1",
            POD_MANAGER_PORT=str(pmgr_port),
            POD_NAME=pod,
        )
        pmgr = subprocess.Popen([PMGR], env=pmgr_env, stderr=subprocess.DEVNULL)
        try:
            wait_listening(pmgr_port)
            env = dict(
                os.environ,
                LD_PRELOAD=shim,
                POD_MANAGER_IP="127.0.0.1",
                POD_MANAGER_PORT=str(pmgr_port),
                POD_NAME=pod,
            )
            env.update(extra_env or {})
            out = subprocess.run(
                [driver, plugin] + driver_args, env=env, capture_output=True,
                text=True, timeout=60,
            )
            client = TokenClient("127.0.0.1", tokend["port"], "x")
            stat = json.loads(client.stat())
            client.close()
            return out, stat
        finally:
            pmgr.kill()
            pmgr.wait()

    def test_preload_gates_execute(self, tokend):
        out, stat = self._run_driver(tokend, ["7"])
        assert out.returncode == 0, out.stderr
        assert "executed 7 real_calls 7 buffers 1" in out.stdout
        # every execute acquired a token: grants visible in tokend
        pods = stat["pods"]
        assert pods["ns/pod-a"]["grants"] == 7
        # HBM accounting: 4096-byte upload charged then credited on
        # destroy -> net zero but the path executed
        assert pods["ns/pod-a"]["mem_used"] == 0

    def test_hard_hbm_denial(self, tokend):
        """An over-cap upload must come back as a fabricated
        RESOURCE_EXHAUSTED (code 8) PJRT error and never reach the plugin
        (VERDICT r1 #2: Gemini rejects over-cap allocs; matching semantics)."""
        out, stat = self._run_driver(
            tokend, ["0", "--upload-bytes", "2000000"]  # cap is 1000000
        )
        assert out.returncode == 0, out.stderr
        assert "upload_denied code=8" in out.stdout
        assert "HBM cap exceeded" in out.stdout
        # the real plugin never saw the allocation
        assert "buffers 0" in out.stdout
        assert stat["pods"]["ns/pod-a"]["mem_used"] == 0

    def test_soft_mode_logs_and_allows(self, tokend):
        out, stat = self._run_driver(
            tokend, ["0", "--upload-bytes", "2000000"],
            extra_env={"TPUSHARE_MEM_ENFORCE": "soft"},
        )
        assert out.returncode == 0, out.stderr
        assert "upload_ok" in out.stdout
        assert "buffers 1" in out.stdout
        # denied charge is not recorded (and thus never mis-credited)
        assert stat["pods"]["ns/pod-a"]["mem_used"] == 0

    def test_within_cap_charge_persists_until_destroy(self, tokend):
        out, stat = self._run_driver(
            tokend, ["0", "--upload-bytes", "500000", "--keep-buffer"]
        )
        assert out.returncode == 0, out.stderr
        assert "upload_ok" in out.stdout
        assert stat["pods"]["ns/pod-a"]["mem_used"] == 500000

    def test_async_transfer_over_cap_denied(self, tokend):
        """VERDICT r4 #2: the async host-to-device path
        (CreateBuffersForAsyncHostToDevice) must be metered like an
        upload — an over-cap create comes back RESOURCE_EXHAUSTED without
        reaching the plugin."""
        out, stat = self._run_driver(
            tokend, ["0", "--async-upload", "2000000"]  # cap is 1000000
        )
        assert out.returncode == 0, out.stderr
        assert "async_create_denied code=8" in out.stdout
        assert stat["pods"]["ns/pod-a"]["mem_used"] == 0

    def test_async_transfer_credited_on_destroy(self, tokend):
        """A completed async transfer cycle (create at cap -> retrieve ->
        manager destroy -> buffer destroy) must credit the broker in
        full: the subsequent plain upload AT the cap succeeds only if the
        ledger returned to zero."""
        out, stat = self._run_driver(
            tokend, ["0", "--async-upload", "1000000",
                     "--upload-bytes", "1000000"]
        )
        assert out.returncode == 0, out.stderr
        assert "async_create_ok" in out.stdout
        assert "async_retrieve_ok" in out.stdout
        assert "tm_destroyed" in out.stdout
        assert "async_buffer_destroyed" in out.stdout
        assert "upload_ok" in out.stdout
        assert stat["pods"]["ns/pod-a"]["mem_used"] == 0

    def test_async_transfer_unretrieved_credited_by_manager_destroy(
            self, tokend):
        """Buffers never retrieved die with the transfer manager; its
        destroy must credit their share."""
        out, stat = self._run_driver(
            tokend, ["0", "--async-upload", "1000000", "--async-no-retrieve",
                     "--upload-bytes", "1000000"]
        )
        assert out.returncode == 0, out.stderr
        assert "async_create_ok" in out.stdout
        assert "async_retrieve_ok" not in out.stdout
        assert "tm_destroyed" in out.stdout
        assert "upload_ok" in out.stdout
        assert stat["pods"]["ns/pod-a"]["mem_used"] == 0

    def test_dma_map_metered(self, tokend):
        """PJRT_Client_DmaMap makes a host region device-visible; it is
        charged like an upload (cap-every-alloc posture) and credited on
        DmaUnmap."""
        out, stat = self._run_driver(
            tokend, ["0", "--dma-map", "2000000"]  # cap is 1000000
        )
        assert out.returncode == 0, out.stderr
        assert "dma_map_denied code=8" in out.stdout
        assert stat["pods"]["ns/pod-a"]["mem_used"] == 0
        out, stat = self._run_driver(
            tokend, ["0", "--dma-map", "1000000",
                     "--upload-bytes", "1000000"]
        )
        assert out.returncode == 0, out.stderr
        assert "dma_map_ok" in out.stdout
        assert "dma_unmapped" in out.stdout
        assert "upload_ok" in out.stdout
        assert stat["pods"]["ns/pod-a"]["mem_used"] == 0

    def test_copy_to_device_over_cap_denied(self, tokend):
        """VERDICT r5 #3: PJRT_Buffer_CopyToDevice allocates a same-size
        target buffer — an over-cap copy must come back RESOURCE_EXHAUSTED
        without reaching the plugin.  FAKE_OUTPUT_BYTES sizes the fake's
        OnDeviceSizeInBytes, i.e. the charge the shim computes for the
        copy (cap is 1000000; 600000 source + 600000 copy > cap)."""
        out, stat = self._run_driver(
            tokend, ["0", "--upload-bytes", "600000", "--keep-buffer",
                     "--copy"],
            extra_env={"FAKE_OUTPUT_BYTES": "600000"},
        )
        assert out.returncode == 0, out.stderr
        assert "upload_ok" in out.stdout
        assert "copy_denied code=8" in out.stdout
        # only the upload's charge stands; the denied copy never ran
        assert stat["pods"]["ns/pod-a"]["mem_used"] == 600000

    def test_copy_to_device_charged_and_credited(self, tokend):
        """A within-cap copy is charged at the source's size and its
        destroy credits exactly that: the ledger returns to the kept
        upload's charge alone."""
        out, stat = self._run_driver(
            tokend, ["0", "--upload-bytes", "400000", "--keep-buffer",
                     "--copy"],
            extra_env={"FAKE_OUTPUT_BYTES": "400000"},
        )
        assert out.returncode == 0, out.stderr
        assert "copy_ok" in out.stdout
        assert "copy_destroyed" in out.stdout
        assert stat["pods"]["ns/pod-a"]["mem_used"] == 400000

    def test_copy_charge_persists_until_destroy(self, tokend):
        out, stat = self._run_driver(
            tokend, ["0", "--upload-bytes", "400000", "--keep-buffer",
                     "--copy", "--keep-copy"],
            extra_env={"FAKE_OUTPUT_BYTES": "400000"},
        )
        assert out.returncode == 0, out.stderr
        assert "copy_ok" in out.stdout
        assert "copy_destroyed" not in out.stdout
        assert stat["pods"]["ns/pod-a"]["mem_used"] == 800000

    def test_view_of_device_buffer_is_zero_size(self, tokend):
        """VERDICT r5 #3: CreateViewOfDeviceBuffer wraps memory someone
        else allocated — the view is accounted explicitly as aliased /
        zero-size: creating it charges nothing and destroying it credits
        nothing (the kept upload's charge must survive both)."""
        out, stat = self._run_driver(
            tokend, ["0", "--upload-bytes", "500000", "--keep-buffer",
                     "--view"],
        )
        assert out.returncode == 0, out.stderr
        assert "view_ok" in out.stdout
        assert "view_destroyed" in out.stdout
        assert stat["pods"]["ns/pod-a"]["mem_used"] == 500000

    def test_completion_time_charging(self, tokend):
        """Async dispatch: the fake device acks Execute instantly but is
        busy 50ms per program.  Charged time must track the device span
        (~3x50ms), not the dispatch wall time (~0ms) (VERDICT r1 #3)."""
        out, stat = self._run_driver(
            tokend, ["3", "--sleep-ms", "600"],
            extra_env={"FAKE_DEVICE_MS": "50"},
        )
        assert out.returncode == 0, out.stderr
        pod = stat["pods"]["ns/pod-a"]
        assert pod["grants"] == 3
        # dispatch-time charging would total well under 10ms here
        assert pod["charged_total_ms"] >= 100, stat

    def test_caller_owned_completion_events(self, tokend):
        """When the runtime's caller requests device_complete_events
        itself, the shim must piggyback (second OnReady callback) without
        stealing or destroying the caller's events."""
        out, stat = self._run_driver(
            tokend, ["3", "--events", "--sleep-ms", "400"],
            extra_env={"FAKE_DEVICE_MS": "30"},
        )
        assert out.returncode == 0, out.stderr
        assert "events_ready 3" in out.stdout
        pod = stat["pods"]["ns/pod-a"]
        assert pod["grants"] == 3
        assert pod["charged_total_ms"] >= 60, stat

    def test_preload_ungated_without_env(self, tokend):
        shim, plugin, driver = self._paths()
        env = {k: v for k, v in os.environ.items() if k != "POD_MANAGER_PORT"}
        env["LD_PRELOAD"] = shim
        out = subprocess.run(
            [driver, plugin, "3"], env=env, capture_output=True, text=True,
            timeout=60,
        )
        assert out.returncode == 0, out.stderr
        assert "executed 3 real_calls 3 buffers 1" in out.stdout

    def test_executable_outputs_charged(self, tokend):
        """Execute's output buffers allocate HBM without any upload hook:
        the shim must charge them on first sighting (VERDICT r2 #1)."""
        out, stat = self._run_driver(
            tokend, ["1", "--outputs", "2"],
            extra_env={"FAKE_OUTPUT_BYTES": "300000"},
        )
        assert out.returncode == 0, out.stderr
        assert "outputs_collected 2" in out.stdout
        # both outputs held at exit -> 2 x 300000 still charged
        assert stat["pods"]["ns/pod-a"]["mem_used"] == 600000

    def test_outputs_over_cap_deny_until_destroy(self, tokend):
        """Outputs pushing past the cap flip the pod into an over-cap state:
        the next execute AND the next upload are denied (RESOURCE_EXHAUSTED)
        until output destroys clear the overflow (VERDICT r2 #1 'done'
        criterion: a compiled program's outputs push past the cap and the
        next upload/execute is denied)."""
        out, stat = self._run_driver(
            tokend, ["3", "--outputs", "1"],
            extra_env={"FAKE_OUTPUT_BYTES": "600000"},  # cap 1000000
        )
        assert out.returncode == 0, out.stderr
        # execute 0: output charged (600000 <= cap)
        # execute 1: runs, but its output is DENIED -> overflow
        # execute 2: denied outright - the pod is over cap
        assert "execute_denied i=2 code=8" in out.stdout
        assert "real_calls 2" in out.stdout
        # the upload after the executes is denied too
        assert "upload_denied code=8" in out.stdout
        assert "buffers 0" in out.stdout
        # broker ledger holds only the granted charge, never over cap
        assert stat["pods"]["ns/pod-a"]["mem_used"] == 600000

    def test_output_destroy_recovers_over_cap(self, tokend):
        """Destroying the over-cap outputs clears the overflow: the upload
        that follows goes through and the ledger returns to zero."""
        out, stat = self._run_driver(
            tokend,
            ["2", "--outputs", "1", "--destroy-outputs"],
            extra_env={"FAKE_OUTPUT_BYTES": "600000"},
        )
        assert out.returncode == 0, out.stderr
        assert "outputs_destroyed 2" in out.stdout
        assert "upload_ok" in out.stdout
        assert stat["pods"]["ns/pod-a"]["mem_used"] == 0

    def test_soft_mode_outputs_account_but_allow(self, tokend):
        """Soft mode: over-cap outputs are logged + tracked, nothing is
        denied — the operator-observability mode keeps working."""
        out, stat = self._run_driver(
            tokend, ["3", "--outputs", "1"],
            extra_env={"FAKE_OUTPUT_BYTES": "600000",
                       "TPUSHARE_MEM_ENFORCE": "soft"},
        )
        assert out.returncode == 0, out.stderr
        assert "execute_denied" not in out.stdout
        assert "real_calls 3" in out.stdout
        assert "upload_ok" in out.stdout

    def test_client_create_injects_allocator_cap(self, tokend):
        """PJRT_Client_Create must receive memory_fraction/preallocate
        create options so client-init preallocation obeys the pod's cap
        (SURVEY §7.4's TPU-specific hard part)."""
        out, _ = self._run_driver(
            tokend, ["0", "--create-client"],
            extra_env={"TPUSHARE_MEM_FRACTION": "0.5"},
        )
        assert out.returncode == 0, out.stderr
        assert "client_ok options=memory_fraction=0.5000;preallocate=false;" \
            in out.stdout

    def test_client_create_fail_open_on_rejected_options(self, tokend):
        """A plugin that rejects unknown create options must still get a
        working client: the shim retries without the injected options."""
        out, _ = self._run_driver(
            tokend, ["0", "--create-client"],
            extra_env={"TPUSHARE_MEM_FRACTION": "0.5",
                       "FAKE_REJECT_CREATE_OPTIONS": "1"},
        )
        assert out.returncode == 0, out.stderr
        # retry succeeded; the recorded options from the final (bare) call
        # are empty, and the plugin saw exactly two creates
        assert "client_ok options= creates=2" in out.stdout
        assert "retrying without them" in out.stderr

    def test_client_create_error_propagated(self, tokend):
        """A create failure that is NOT option rejection (RESOURCE_EXHAUSTED
        here) must reach the caller unchanged with no bare retry — a blind
        retry would destroy the original error and hand a partially
        initialized plugin a second create (ADVICE r3)."""
        out, _ = self._run_driver(
            tokend, ["0", "--create-client"],
            extra_env={"TPUSHARE_MEM_FRACTION": "0.5",
                       "FAKE_CREATE_FAIL_CODE": "8"},
        )
        assert out.returncode == 0, out.stderr
        assert "client_err code=8" in out.stdout
        assert "creates=1" in out.stdout  # no second (bare) create
        assert "retrying without them" not in out.stderr

    def test_client_destroy_settles_ledgers(self, tokend):
        """Client destroy releases every buffer the client owns without
        per-buffer destroys: the shim must clear the charged + overflow
        ledgers and credit the broker, or a pod that re-creates its client
        stays over-cap (denied) for the process lifetime (ADVICE r3)."""
        out, stat = self._run_driver(
            tokend,
            ["3", "--outputs", "1", "--destroy-client"],
            extra_env={"FAKE_OUTPUT_BYTES": "600000"},  # cap 1000000
        )
        assert out.returncode == 0, out.stderr
        # over-cap before the destroy: the first upload is denied
        assert "upload_denied code=8" in out.stdout
        # destroy clears the overflow and credits the broker: the retry
        # upload goes through and is itself settled on buffer destroy
        assert "client_destroyed destroys=1" in out.stdout
        assert "upload2_ok" in out.stdout
        assert stat["pods"]["ns/pod-a"]["mem_used"] == 0

    def test_preload_exports_allocator_env(self, tokend):
        """The shim's constructor translates TPUSHARE_MEM_FRACTION into the
        XLA allocator env before the runtime starts — a preload-only pod
        (no kubeshare_tpu import) still gets its client allocator capped."""
        shim, _, _ = self._paths()
        out = subprocess.run(
            ["/bin/sh", "-c", "echo frac=$XLA_PYTHON_CLIENT_MEM_FRACTION "
             "prealloc=$XLA_PYTHON_CLIENT_PREALLOCATE"],
            env=dict(os.environ, LD_PRELOAD=shim,
                     TPUSHARE_MEM_FRACTION="0.3500"),
            capture_output=True, text=True, timeout=30,
        )
        assert out.returncode == 0, out.stderr
        assert "frac=0.3500 prealloc=false" in out.stdout


class TestTsan:
    """Race detection for the token scheduler: hammer a TSAN build with
    concurrent clients; any data race aborts the process / prints a
    ThreadSanitizer report."""

    def test_tokend_tsan_concurrent(self, tmp_path):
        tsan_binary = find_binary("tpushare-tokend-tsan")
        if tsan_binary is None:
            pytest.skip("tsan build not present (make -C native tsan)")
        config_dir = tmp_path / "config"
        config_dir.mkdir()
        write_atomic(str(config_dir / "chip-0"),
                     "2\nns/a 1.0 0.5 100000\nns/b 1.0 0.3 100000\n")
        port = free_port()
        proc = subprocess.Popen(
            [tsan_binary, "-p", str(config_dir), "-f", "chip-0",
             "-P", str(port), "-q", "10", "-m", "2", "-w", "200"],
            stderr=subprocess.PIPE, text=True,
        )
        try:
            wait_listening(port)

            def hammer(pod):
                client = TokenClient("127.0.0.1", port, pod)
                stop = time.monotonic() + 2.0
                while time.monotonic() < stop:
                    client.acquire()
                    client.release(1.0)
                    client.request_memory(10)
                    client.request_memory(-10)
                client.close()

            threads = [threading.Thread(target=hammer, args=(p,))
                       for p in ("ns/a", "ns/b", "ns/a", "ns/b")]
            for t in threads:
                t.start()
            # concurrent config reloads while clients hammer
            for i in range(5):
                write_atomic(str(config_dir / "chip-0"),
                             f"2\nns/a 1.0 0.{4+i%3} 100000\nns/b 1.0 0.3 100000\n")
                time.sleep(0.3)
            for t in threads:
                t.join()
            assert proc.poll() is None, "tokend died under TSAN"
        finally:
            proc.kill()
            _, stderr = proc.communicate(timeout=10)
        assert "ThreadSanitizer" not in (stderr or ""), stderr


class TestIdleRelease:
    def test_idle_guard_returns_token(self, tokend_exclusive):
        """A guard holding a budgeted token but gone idle must release it so
        co-tenants are not starved (exclusive mode makes this observable)."""
        a = TokenClient("127.0.0.1", tokend_exclusive["port"], "ns/pod-a")
        guard = ExecutionGuard(client=a, from_env=False, idle_release_ms=100)
        guard.acquire()
        guard.charge(1.0)  # budget remains -> token still held
        # pod-b blocks while a holds; after idle release it proceeds
        b = TokenClient("127.0.0.1", tokend_exclusive["port"], "ns/pod-b")
        granted = []

        def try_b():
            b.acquire()
            granted.append(1)
            b.release(1.0)

        t = threading.Thread(target=try_b)
        t.start()
        time.sleep(0.05)
        assert not granted  # still held
        t.join(timeout=5)   # idle monitor releases within ~100ms
        assert granted
        a.close(); b.close()

    def test_no_release_while_step_in_flight(self, tokend_exclusive):
        """A long step (e.g. first-step compile) between acquire and charge
        must not be treated as idleness."""
        a = TokenClient("127.0.0.1", tokend_exclusive["port"], "ns/pod-a")
        guard = ExecutionGuard(client=a, from_env=False, idle_release_ms=80)
        guard.acquire()  # step begins; no charge yet
        time.sleep(0.4)  # "compiling"
        b = TokenClient("127.0.0.1", tokend_exclusive["port"], "ns/pod-b")
        granted = []
        t = threading.Thread(target=lambda: (b.acquire(), granted.append(1),
                                             b.release(1.0)))
        t.start()
        time.sleep(0.1)
        assert not granted  # still held through the in-flight step
        guard.charge(1.0)  # step ends; budget remains -> held but idle now
        t.join(timeout=5)  # idle monitor releases
        assert granted
        a.close(); b.close()


class TestSupervisorMetrics:
    def test_tokend_stat_as_prometheus(self, tmp_path):
        import urllib.request

        config_dir = tmp_path / "config"
        port_dir = tmp_path / "ports"
        config_dir.mkdir(); port_dir.mkdir()
        write_atomic(str(config_dir / "chip-0"), "1\nns/p 1.0 0.5 4096\n")
        write_atomic(str(port_dir / "chip-0"), "0\n")
        tokend_port = free_port()
        with ChipSupervisor("chip-0", config_dir=str(config_dir),
                            port_dir=str(port_dir), tokend_port=tokend_port,
                            poll_interval=0.2) as sup:
            wait_listening(tokend_port)
            client = TokenClient("127.0.0.1", tokend_port, "ns/p")
            client.acquire(); client.release(5.0)
            client.request_memory(1000)
            client.close()
            server = sup.serve_metrics(port=0)
            try:
                body = urllib.request.urlopen(
                    f"http://127.0.0.1:{server.port}/metrics", timeout=5
                ).read().decode()
                assert 'tpushare_pod_grants_total{chip="chip-0",pod="ns/p"} 1' in body
                assert 'tpushare_pod_mem_used_bytes{chip="chip-0",pod="ns/p"} 1000' in body
                assert "tpushare_pod_share" in body
            finally:
                server.stop()

    def test_config_reload_preserves_usage(self, tokend):
        # accumulate usage, then rewrite the config (same pod, new limits):
        # the decayed usage must survive the reload (no accounting reset)
        import json

        client = TokenClient("127.0.0.1", tokend["port"], "ns/pod-a")
        client.acquire()
        client.release(200.0)  # 200ms of a 1000ms window -> share ~0.2
        write_atomic(
            str(tokend["config_dir"] / tokend["uuid"]),
            "2\nns/pod-a 0.9 0.4 1000000\nns/pod-b 1.0 0.3 500000\n",
        )
        time.sleep(1.0)  # inotify reload + decay
        stat = json.loads(client.stat())
        pod_a = stat["pods"]["ns/pod-a"]
        assert pod_a["limit"] == 0.9  # new config applied
        assert pod_a["share"] > 0.05  # usage not reset (decayed from 0.2)
        client.close()


# ---------------------------------------------------------------------------
# Gang-aware coordination across sibling tokends (tokend -G; VERDICT r1 #9)
# ---------------------------------------------------------------------------

def _raw_cmd(port, line):
    with socket.create_connection(("127.0.0.1", port), timeout=5) as sock:
        f = sock.makefile("rw", newline="\n")
        f.write(line + "\n")
        f.flush()
        return f.readline().strip()


def _start_gang_pair(tmp_path, exclusive=False):
    """Two sibling tokends: gang/pod-x shared on both chips, ns/heavy only
    on chip-0.  Each is launched with -G pointing at the other."""
    config_dir = tmp_path / "config"
    config_dir.mkdir(exist_ok=True)
    write_atomic(str(config_dir / "chip-0"),
                 "2\ngang/pod-x 1.0 0.4 0\nns/heavy 1.0 0.5 0\n")
    write_atomic(str(config_dir / "chip-1"),
                 "1\ngang/pod-x 1.0 0.4 0\n")
    ports = free_ports(2)
    procs = []
    for i in range(2):
        cmd = [TOKEND, "-p", str(config_dir), "-f", f"chip-{i}",
               "-P", str(ports[i]), "-q", "50", "-m", "5", "-w", "1000",
               "-G", str(ports[1 - i])]
        if exclusive:
            cmd.append("-x")
        procs.append(subprocess.Popen(cmd, stderr=subprocess.DEVNULL))
    for port in ports:
        wait_listening(port)
    return procs, ports


@pytest.fixture
def gang_pair(tmp_path):
    procs, ports = _start_gang_pair(tmp_path)
    yield ports
    for proc in procs:
        proc.kill()
        proc.wait()


@pytest.fixture
def gang_pair_exclusive(tmp_path):
    procs, ports = _start_gang_pair(tmp_path, exclusive=True)
    yield procs, ports
    for proc in procs:
        if proc.poll() is None:
            proc.kill()
            proc.wait()


class TestGangTokend:
    def test_peer_ineligibility_blocks_grant(self, gang_pair):
        """A gang pod over its limit on chip-0 must WAIT on chip-1 too,
        even though chip-1 itself would grant — grants stay aligned."""
        port0, port1 = gang_pair
        c0 = TokenClient("127.0.0.1", port0, "gang/pod-x")
        c0.acquire()
        c0.release(2000.0)  # share 2.0 of a 1000ms window: over limit on chip-0
        reply = _raw_cmd(port1, "REQ gang/pod-x 0")
        assert reply.startswith("WAIT "), reply
        # decay restores eligibility on chip-0 -> chip-1 grants again
        deadline = time.time() + 5
        while time.time() < deadline:
            reply = _raw_cmd(port1, "REQ gang/pod-x 0")
            if reply.startswith("TOK "):
                break
            time.sleep(0.1)
        assert reply.startswith("TOK "), reply
        c0.close()

    def test_unshared_pod_not_constrained_by_peer(self, gang_pair):
        """ns/heavy exists only in chip-0's config; chip-1 answers the
        probe 'not mine' and chip-0 grants normally."""
        port0, _ = gang_pair
        reply = _raw_cmd(port0, "REQ ns/heavy 0")
        assert reply.startswith("TOK "), reply

    def test_elig_probe_does_not_create_state(self, gang_pair):
        import json

        port0, _ = gang_pair
        assert _raw_cmd(port0, "ELIG ns/never-seen").startswith("ELIG 1")
        stat = json.loads(_raw_cmd(port0, "STAT"))
        assert "ns/never-seen" not in stat["pods"]

    def test_holder_counts_as_eligible_exclusive(self, gang_pair_exclusive):
        """Sequential multi-chip acquisition in exclusive mode: the pod's
        own grant on chip-0 must not block its REQ on chip-1 (the probe
        reports a holder as eligible)."""
        _, (port0, port1) = gang_pair_exclusive
        c0 = TokenClient("127.0.0.1", port0, "gang/pod-x")
        c0.acquire()  # holds chip-0 exclusively
        reply = _raw_cmd(port1, "REQ gang/pod-x 0")
        assert reply.startswith("TOK "), reply
        c0.release(1.0)
        c0.close()

    def test_fail_open_when_peer_dies(self, gang_pair_exclusive):
        """A dead sibling must not stall the chip: queries fail open."""
        procs, (port0, port1) = gang_pair_exclusive
        procs[1].kill()
        procs[1].wait()
        reply = _raw_cmd(port0, "REQ gang/pod-x 0")
        assert reply.startswith("TOK "), reply

    def test_gang_grants_align_under_independent_clients(self, gang_pair):
        """VERDICT r1 #9 criterion: per-chip grants stay within one
        quantum.  Driven by *independent* per-chip clients (NOT the
        pairwise GangTokenClient, whose symmetry would make alignment
        tautological): chip-1's client free-runs while chip-0's is
        throttled over limit — without -G chip-1 would rack up dozens of
        unilateral grants; with the gate its charged time may not run more
        than one quantum ahead of chip-0's."""
        import json

        port0, port1 = gang_pair
        # drive pod-x over its limit on chip-0 (share 2.0 of window 1.0)
        c0 = TokenClient("127.0.0.1", port0, "gang/pod-x")
        c0.acquire()
        c0.release(2000.0)
        charged0 = json.loads(
            _raw_cmd(port0, "STAT"))["pods"]["gang/pod-x"]["charged_total_ms"]
        # an independent client hammers chip-1 for ~0.4 s (well inside the
        # ~0.7 s decay time chip-0 needs to become eligible again)
        c1 = TokenClient("127.0.0.1", port1, "gang/pod-x")
        granted_ms = 0.0
        deadline = time.monotonic() + 0.4
        while time.monotonic() < deadline:
            reply = _raw_cmd(port1, "REQ gang/pod-x 0")
            if reply.startswith("TOK "):
                granted_ms += 30.0
                c1.release(30.0)  # keep holder count balanced if granted
                pytest.fail(
                    f"chip-1 granted unilaterally while chip-0 over limit: {reply}"
                )
            time.sleep(0.02)
        charged1 = json.loads(
            _raw_cmd(port1, "STAT"))["pods"]["gang/pod-x"]["charged_total_ms"]
        # chip-1 never ran ahead: within one base quantum (50 ms) of chip-0's
        # progress is trivially satisfied by zero unilateral grants
        assert charged1 <= granted_ms + 50.0
        assert charged0 >= 2000.0  # chip-0's charge actually landed
        c0.close()
        c1.close()

    def test_gang_client_env_construction(self, gang_pair, monkeypatch):
        """connect_from_env builds a gang client from comma-separated
        POD_MANAGER_PORT, members sorted by (host, port)."""
        from kubeshare_tpu.isolation.client import (GangTokenClient,
                                                    connect_from_env)

        port0, port1 = gang_pair
        monkeypatch.setenv("POD_MANAGER_PORT", f"{max(port0, port1)},{min(port0, port1)}")
        monkeypatch.setenv("POD_NAME", "gang/pod-x")
        monkeypatch.setenv("POD_MANAGER_IP", "127.0.0.1")
        client = connect_from_env()
        assert isinstance(client, GangTokenClient)
        assert [c.port for c in client.clients] == sorted([port0, port1])
        quota = client.acquire()
        assert quota > 0
        client.release(1.0)
        client.close()

    def test_native_client_gang_ports(self, gang_pair):
        """The C client (the LD_PRELOAD shim's transport) accepts the
        comma-separated gang port form and gates on EVERY broker — an
        atoi() of the list would silently gate only the first chip,
        bypassing isolation on the rest."""
        import json

        port0, port1 = gang_pair
        client = NativeTokenClient(
            "127.0.0.1", f"{port1},{port0}", "gang/pod-x"
        )
        quota = client.acquire(1.0)
        assert quota > 0
        client.release(10.0)
        ok, _, _ = client.request_memory(1 << 20)
        assert ok
        client.request_memory(-(1 << 20))
        client.close()
        for port in (port0, port1):  # both brokers saw the grant + charge
            pod = json.loads(_raw_cmd(port, "STAT"))["pods"]["gang/pod-x"]
            assert pod["grants"] == 1
            assert pod["charged_total_ms"] >= 10.0

    def test_cancel_pops_newest_grant(self, tokend):
        """CAN (gang unwind) must cancel the just-granted token, not
        FIFO-retire the oldest: the oldest may be legitimately in flight,
        and its later RET must carry its own measured charge."""
        import json

        c = TokenClient("127.0.0.1", tokend["port"], "ns/pod-a")
        q1 = c.acquire()   # token 1: in flight
        c.acquire()        # token 2: to be rolled back
        c.cancel()         # pops token 2 with zero charge
        stat = json.loads(c.stat())["pods"]["ns/pod-a"]
        assert stat["grants"] == 2
        assert stat["charged_total_ms"] == 0.0  # nothing retired yet
        c.release(q1 * 0.5)  # token 1 retires with its real charge
        stat = json.loads(c.stat())["pods"]["ns/pod-a"]
        assert abs(stat["charged_total_ms"] - q1 * 0.5) < 1e-6
        # holder count dropped to zero: no Abandon charge on disconnect
        c.close()
        time.sleep(0.2)
        reply = _raw_cmd(tokend["port"], "STAT")
        assert json.loads(reply)["holders"] == 0

    def test_elig_reply_carries_known_field(self, gang_pair):
        """ELIG's third field distinguishes 'eligible because unshared'
        (known=0, cacheable by the peer gate) from 'eligible and shared'
        (known=1)."""
        port0, _ = gang_pair
        assert _raw_cmd(port0, "ELIG ns/never-seen").split() == \
            ["ELIG", "1", "0.000000", "0"]
        reply = _raw_cmd(port0, "ELIG gang/pod-x").split()
        assert reply[0] == "ELIG" and reply[3] == "1"


def _start_gang_quad(tmp_path):
    """Four sibling tokends (a 2x2-slice-shaped gang): gang/pod-x shared on
    all four chips, each tokend launched with -G naming the other three."""
    config_dir = tmp_path / "config"
    config_dir.mkdir(exist_ok=True)
    for i in range(4):
        # 64 MiB per-chip HBM cap for the pod (config column 4, bytes)
        write_atomic(str(config_dir / f"chip-{i}"),
                     f"1\ngang/pod-x 1.0 0.4 {64 << 20}\n")
    ports = [free_port() for _ in range(4)]
    procs = []
    for i in range(4):
        peers = ",".join(str(ports[j]) for j in range(4) if j != i)
        procs.append(subprocess.Popen(
            [TOKEND, "-p", str(config_dir), "-f", f"chip-{i}",
             "-P", str(ports[i]), "-q", "50", "-m", "5", "-w", "1000",
             "-G", peers],
            stderr=subprocess.DEVNULL))
    for port in ports:
        wait_listening(port)
    return procs, ports


@pytest.fixture
def gang_quad(tmp_path):
    procs, ports = _start_gang_quad(tmp_path)
    yield ports
    for proc in procs:
        proc.kill()
        proc.wait()


class TestGangQuad:
    """-G past the pairwise fixture (VERDICT r2 #9): four live sibling
    tokends must keep grants aligned, and the gang client's unwind
    semantics must hold at width 4."""

    def test_one_overloaded_chip_blocks_all_three_peers(self, gang_quad):
        ports = gang_quad
        c0 = TokenClient("127.0.0.1", ports[0], "gang/pod-x")
        c0.acquire()
        c0.release(2000.0)  # share 2.0 of a 1.0 window: over limit on chip-0
        for port in ports[1:]:
            reply = _raw_cmd(port, "REQ gang/pod-x 0")
            assert reply.startswith("WAIT "), (port, reply)
        # decay restores chip-0 -> every peer grants again
        deadline = time.time() + 5
        granted = set()
        while time.time() < deadline and len(granted) < 3:
            for port in ports[1:]:
                if port not in granted and _raw_cmd(
                        port, "REQ gang/pod-x 0").startswith("TOK "):
                    granted.add(port)
            time.sleep(0.1)
        assert len(granted) == 3
        c0.close()

    def test_quad_soak_no_unilateral_runahead(self, gang_quad):
        """Contention soak: chip-0 is pushed over limit while independent
        clients hammer chips 1-3 for the whole decay window — none may
        grant unilaterally, so no chip's charged time runs ahead."""
        import json

        ports = gang_quad
        c0 = TokenClient("127.0.0.1", ports[0], "gang/pod-x")
        c0.acquire()
        c0.release(2000.0)

        errors = []

        def hammer(port):
            deadline = time.monotonic() + 0.4
            while time.monotonic() < deadline:
                reply = _raw_cmd(port, "REQ gang/pod-x 0")
                if reply.startswith("TOK "):
                    errors.append((port, reply))
                    return
                time.sleep(0.01)

        threads = [threading.Thread(target=hammer, args=(p,))
                   for p in ports[1:]]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors, f"unilateral grants during overload: {errors}"
        for port in ports[1:]:
            charged = json.loads(_raw_cmd(port, "STAT"))[
                "pods"]["gang/pod-x"]["charged_total_ms"]
            assert charged == 0.0, (port, charged)
        c0.close()

    def test_gang_acquire_and_charge_spans_all_four(self, gang_quad):
        import json

        from kubeshare_tpu.isolation.client import GangTokenClient

        ports = gang_quad
        gang = GangTokenClient([
            TokenClient("127.0.0.1", p, "gang/pod-x") for p in ports
        ])
        quota = gang.acquire()
        assert quota > 0
        gang.release(25.0)
        for port in ports:
            pod = json.loads(_raw_cmd(port, "STAT"))["pods"]["gang/pod-x"]
            assert pod["grants"] == 1, (port, pod)
            assert pod["charged_total_ms"] >= 25.0
        gang.close()

    def test_mem_deny_on_last_chip_rolls_back_first_three(self, gang_quad):
        """HBM unwind at width 4: chip-3's ledger is pre-filled so the
        gang charge denies there — the three already-charged chips must be
        credited back, or the pod permanently loses headroom it never
        used."""
        ports = gang_quad
        mib = 1 << 20
        # fill chip-3 to 60 of the pod's 64 MiB per-chip cap
        reply = _raw_cmd(ports[3], f"MEM gang/pod-x {60 * mib}")
        assert reply.startswith("OK "), reply

        from kubeshare_tpu.isolation.client import GangTokenClient

        gang = GangTokenClient([
            TokenClient("127.0.0.1", p, "gang/pod-x") for p in ports
        ])
        ok, _, _ = gang.request_memory(8 * mib)  # fits on 0-2, not on 3
        assert not ok
        for port in ports[:3]:
            reply = _raw_cmd(port, "MEM gang/pod-x 0")
            used = int(reply.split()[1])
            assert used == 0, (port, reply)  # rolled back
        # chip-3 still holds only its pre-fill
        assert int(_raw_cmd(ports[3], "MEM gang/pod-x 0").split()[1]) \
            == 60 * mib
        gang.close()


class TestSupervisorGangWiring:
    def test_gang_peer_ports_reach_tokend_cmdline(self, tmp_path):
        sup = ChipSupervisor(
            chip_uuid="chip-0",
            config_dir=str(tmp_path / "config"),
            port_dir=str(tmp_path / "ports"),
            tokend_port=free_port(),
            gang_peer_ports=(49902, 49903),
            log_dir=str(tmp_path / "log"),
        )
        sup.start()
        try:
            with open(f"/proc/{sup.tokend.pid}/cmdline") as f:
                argv = f.read().split("\0")
            assert "-G" in argv
            assert argv[argv.index("-G") + 1] == "49902,49903"
        finally:
            sup.stop()
