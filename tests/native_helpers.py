"""Shared helpers for tests that drive the native token runtime over TCP."""

import socket

from kubeshare_tpu.utils.net import wait_listening as _wait_listening


def free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def free_ports(count):
    """``count`` free ports, all different: the sockets are held open
    together, so none is handed out twice (two :func:`free_port` calls in
    a row can read the same port)."""
    socks = [socket.socket() for _ in range(count)]
    try:
        for s in socks:
            s.bind(("127.0.0.1", 0))
        return [s.getsockname()[1] for s in socks]
    finally:
        for s in socks:
            s.close()


def wait_listening(port, timeout=10.0):
    _wait_listening(port, deadline_s=timeout)
