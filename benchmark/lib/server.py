"""``serve-node`` as a child process that owns the chip, and the plain HTTP
helpers the harness talks to it with.  The child management, the prompt
maker and the log parsing are a copy of ``chip_smoke.py``'s (PERF.md, Open
questions).  Nothing here imports JAX: the parent must never hold the chip.
"""

import json
import os
import re
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request


class ServerFailed(RuntimeError):
    pass


def http(url, body=None, timeout=300):
    req = urllib.request.Request(
        url, data=None if body is None else json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.read().decode()


def free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def words(n_bytes, salt):
    """A deterministic ASCII prompt of exactly ``n_bytes`` bytes (the byte
    tokenizer of a checkpoint-less model makes that many tokens).  Two salts
    part within the first word, so no two prompts share a cache page."""
    out, x = [], (salt * 2654435761 + 12345) % 2**32
    size = 0
    while size <= n_bytes:      # the join drops one of the counted spaces
        x = (x * 1103515245 + 12345) % 2**31
        w = "".join(chr(97 + (x >> s) % 26) for s in (3, 8, 13, 18, 23))[
            : 2 + x % 4]
        out.append(w)
        size += len(w) + 1
    return " ".join(out)[:n_bytes]


def log_seconds(log, what):
    m = re.search(rf"{what} in ([0-9.]+)s", log)
    return float(m.group(1)) if m else None


def child_env(root, out_dir, rehearse):
    env = dict(os.environ)
    env["PYTHONPATH"] = root + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("TPU_LOG_DIR", "disabled")
    # the only way to trace the live server: /admin/profiler writes here
    env["HELIX_PROFILER_DIR"] = os.path.join(out_dir, "profiles")
    os.makedirs(env["HELIX_PROFILER_DIR"], exist_ok=True)
    if rehearse:
        env["JAX_PLATFORMS"] = "cpu"
    return env


class Server:
    """One ``python -m helix_tpu serve-node --profile <file>`` child."""

    def __init__(self, root, profile_path, out_dir, tag, rehearse=False):
        self.root, self.tag = root, tag
        self.port = free_port()
        self.url = f"http://127.0.0.1:{self.port}"
        self.log_path = os.path.join(out_dir, f"server_{tag}.log")
        self.log = open(self.log_path, "w")
        self.t0 = time.monotonic()
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "helix_tpu", "serve-node", "--profile",
             profile_path, "--host", "127.0.0.1", "--port", str(self.port)],
            cwd=root, env=child_env(root, out_dir, rehearse),
            stdout=self.log, stderr=subprocess.STDOUT)

    def log_text(self):
        with open(self.log_path, errors="replace") as f:
            return f.read()

    def die(self, msg):
        tail = self.log_text()[-3000:]
        self.kill()
        raise ServerFailed(
            f"server[{self.tag}]: {msg}\n--- server log tail ---\n{tail}")

    def kill(self):
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()
        self.log.close()

    def wait_running(self, deadline):
        """Poll ``/api/v1/state`` until the profile runs; returns the state."""
        while time.monotonic() < deadline:
            if self.proc.poll() is not None:
                self.die(f"exited {self.proc.returncode} before it served")
            try:
                state = json.loads(http(self.url + "/api/v1/state", timeout=5))
                status = state["profile"]["status"]
                if status == "running":
                    self.ready_s = time.monotonic() - self.t0
                    return state
                if status == "failed":
                    self.die(f"profile failed: {state['profile']['error']}")
            except (OSError, urllib.error.URLError, ValueError):
                pass
            time.sleep(1)
        self.die("not running before the time limit")

    def state(self):
        return json.loads(http(self.url + "/api/v1/state", timeout=30))

    def metrics_text(self):
        return http(self.url + "/metrics", timeout=30)

    def chat_once(self, model, text, max_tokens, **extra):
        """One non-streamed chat completion; the parsed response."""
        body = {"model": model, "max_tokens": max_tokens, "stream": False,
                "messages": [{"role": "user", "content": text}], **extra}
        return json.loads(http(self.url + "/v1/chat/completions", body))

    def stop(self, timeout=90):
        """SIGTERM, wait; returns the exit code (kills after ``timeout``,
        then returns None)."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=timeout)
            except subprocess.TimeoutExpired:
                self.kill()
                return None
        self.log.close()
        return self.proc.returncode


def device_of(state):
    """The device as the server child saw it, in the contract's keys."""
    acc = state["accelerators"]
    first = acc[0]
    return {"platform": first["vendor"], "kind": first["device_kind"],
            "count": len(acc), "arch": first.get("arch")}
