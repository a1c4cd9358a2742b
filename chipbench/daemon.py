"""The system under test as a child process: `python -m dnn_tpu.node
--serve_lm` with the configuration's flags, its observability endpoint
read over HTTP. The child holds the chip; this process stays off JAX
while it lives.
"""

from __future__ import annotations

import json
import os
import re
import signal
import socket
import subprocess
import sys
import time
import urllib.request
from typing import Dict, Optional

__all__ = ["Daemon", "parse_prometheus", "free_port"]


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


_SERIES = re.compile(r"^([A-Za-z_:][\w:]*)(\{[^}]*\})?\s+(\S+)\s*$")


def parse_prometheus(text: str) -> Dict[str, float]:
    """{series (name with its label block): value} of a /metrics page."""
    out = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        m = _SERIES.match(line)
        if m:
            try:
                out[m.group(1) + (m.group(2) or "")] = float(m.group(3))
            except ValueError:
                pass
    return out


class Daemon:
    """Spawn, probe, scrape and stop one LM daemon."""

    def __init__(self, *, repo: str, workdir: str, model: str, dtype: str,
                 device_type: Optional[str], seed: int, serve_flags: dict,
                 env_extra: Optional[dict] = None):
        self.repo, self.workdir = repo, workdir
        self.port, self.mport = free_port(), free_port()
        self.addr = f"127.0.0.1:{self.port}"
        cfg = {"nodes": [{"id": "node1", "part_index": 0,
                          "address": self.addr}],
               "num_parts": 1, "model": model, "dtype": dtype,
               "runtime": "auto"}
        if device_type is not None:
            cfg["device_type"] = device_type
        self.config_path = os.path.join(workdir, "daemon_config.json")
        with open(self.config_path, "w") as f:
            json.dump(cfg, f)
        self.argv = ["--node_id", "node1", "--config", self.config_path,
                     "--serve_lm", "--seed", str(seed),
                     "--metrics_port", str(self.mport),
                     "--log_level", "WARNING"]
        for flag, value in serve_flags.items():
            self.argv += [f"--{flag}"] + ([] if value is True else [str(value)])
        self.log_path = os.path.join(workdir, "daemon.log")
        self.env = dict(os.environ, **(env_extra or {}))
        self.proc: Optional[subprocess.Popen] = None

    def spawn(self):
        with open(self.log_path, "w") as log:
            self.proc = subprocess.Popen(
                [sys.executable, "-m", "dnn_tpu.node"] + self.argv,
                stdout=log, stderr=subprocess.STDOUT, cwd=self.repo,
                env=self.env)

    def log_tail(self, n: int = 4000) -> str:
        try:
            with open(self.log_path, errors="replace") as f:
                return f.read()[-n:]
        except OSError:
            return ""

    def wait_ready(self, client, deadline_s: float):
        t_end = time.monotonic() + deadline_s
        while not client.health_check(timeout=2.0):
            if self.proc.poll() is not None:
                raise RuntimeError(
                    f"daemon exited rc={self.proc.returncode} before it "
                    f"was ready:\n{self.log_tail()}")
            if time.monotonic() > t_end:
                raise RuntimeError(f"daemon not ready after {deadline_s:.0f}s"
                                   f":\n{self.log_tail()}")
            time.sleep(0.25)

    def get(self, path: str, *, method: str = "GET", timeout: float = 30.0):
        req = urllib.request.Request(
            f"http://127.0.0.1:{self.mport}{path}", method=method)
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.read().decode()

    def metrics(self) -> Dict[str, float]:
        return parse_prometheus(self.get("/metrics"))

    def get_json(self, path: str, **kw):
        return json.loads(self.get(path, **kw))

    def stop(self, grace_s: float = 90.0) -> int:
        """SIGTERM (the daemon drains and exits 0), SIGKILL past the
        grace; always waits for the process to end."""
        if self.proc is None:
            return 0
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=grace_s)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(timeout=30)
        return self.proc.returncode
