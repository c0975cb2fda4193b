"""The system under test as the benchmark's one child, and an HTTP client.

Copied from chip_smoke.py (PR 21). The server is started through its normal
entry point with the environment as it is: jax in the child picks whatever
device this machine has, and this process never imports jax.
"""

from __future__ import annotations

import http.client
import json
import os
import signal
import socket
import subprocess
import sys
import time

from .corpus import ROOT  # the checkout

class ServerFailure(Exception):
    """No server to talk to: the run ends at once, with no result."""


class Server:
    def __init__(self, storage: str, log_path: str, extra_args=(), env=None):
        self.storage, self.log_path = storage, log_path
        self.extra_args = list(extra_args)
        self.env = env  # None: inherit untouched
        self.proc: subprocess.Popen | None = None
        self.port = 0

    def start(self, timeout: float = 300.0) -> float:
        if not self.port:
            # a restart keeps the port: the server's instance id, and with
            # it the WAL directory it replays, derive from it
            with socket.socket() as s:
                s.bind(("127.0.0.1", 0))
                self.port = s.getsockname()[1]
        t0 = time.perf_counter()
        with open(self.log_path, "ab") as logf:
            self.proc = subprocess.Popen(
                [sys.executable, "-m", "tempo_tpu.services.app",
                 "--target=all", "--storage.path", self.storage,
                 "--http.port", str(self.port), *self.extra_args],
                cwd=ROOT, env=self.env, stdout=logf, stderr=subprocess.STDOUT)
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if self.proc.poll() is not None:
                raise ServerFailure(
                    f"server exited {self.proc.returncode} before /ready:\n"
                    + self.log_tail())
            try:
                conn = http.client.HTTPConnection("127.0.0.1", self.port,
                                                  timeout=2)
                conn.request("GET", "/ready")
                ok = conn.getresponse().status == 200
                conn.close()
                if ok:
                    return time.perf_counter() - t0
            except OSError:
                pass
            time.sleep(0.1)
        raise ServerFailure(f"server not ready after {timeout:.0f} s:\n"
                            + self.log_tail())

    def log_tail(self, n: int = 4000) -> str:
        try:
            with open(self.log_path, "rb") as f:
                f.seek(0, os.SEEK_END)
                f.seek(max(0, f.tell() - n))
                return f.read().decode("utf-8", "replace")
        except OSError:
            return ""

    def stop(self) -> int | None:
        """SIGTERM and wait: the server must drain and exit 0, or the chip
        is not released for the next process."""
        if self.proc is None or self.proc.poll() is not None:
            return self.proc.returncode if self.proc else None
        self.proc.send_signal(signal.SIGTERM)
        try:
            return self.proc.wait(timeout=120)
        except subprocess.TimeoutExpired:
            self.kill()
            return None

    def kill(self) -> None:
        if self.proc is not None and self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()


class Client:
    """One keep-alive connection. request() never raises for an HTTP or
    socket failure: it answers status 599 and the error text, so that a
    load generator records a failed request and goes on."""

    def __init__(self, port: int, timeout: float = 120.0):
        self.port, self.timeout = port, timeout
        self.conn = http.client.HTTPConnection("127.0.0.1", port,
                                               timeout=timeout)

    def _once(self, method, path, body, headers):
        self.conn.request(method, path, body=body, headers=headers)
        resp = self.conn.getresponse()
        return resp.status, resp.read()

    def request(self, method: str, path: str, body: bytes | None = None,
                headers: dict | None = None) -> tuple[int, bytes]:
        headers = headers or {}
        try:
            return self._once(method, path, body, headers)
        except TimeoutError as e:
            self.close()
            return 599, f"timeout: {e}".encode()
        except (OSError, http.client.HTTPException):
            # one reconnect: the server closes idle keep-alives
            self.close()
            self.conn = http.client.HTTPConnection("127.0.0.1", self.port,
                                                   timeout=self.timeout)
            try:
                return self._once(method, path, body, headers)
            except (OSError, http.client.HTTPException) as e:
                self.close()
                return 599, f"{type(e).__name__}: {e}".encode()

    def get_json(self, path: str, headers: dict | None = None):
        status, data = self.request("GET", path, headers=headers)
        if status != 200:
            return status, None
        try:
            return status, json.loads(data)
        except ValueError:
            return 598, None

    def close(self) -> None:
        try:
            self.conn.close()
        except OSError:
            pass
