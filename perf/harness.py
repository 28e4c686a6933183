"""Processes, ports, plain HTTP and Prometheus text: what ``run.py`` and
``calibrate.py`` share. Never imports jax (the parent must not hold the
chip)."""

from __future__ import annotations

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

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class BenchError(RuntimeError):
    """The run cannot produce a result (not the same as ``correct: false``)."""


def log(msg: str, t0: float = time.monotonic()) -> None:
    print(f"[perf +{time.monotonic() - t0:7.1f}s] {msg}", flush=True)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Child:
    """A child process in its own session, logged to a file, stopped as a
    group."""

    def __init__(self, name: str, argv: list, env: dict, log_dir: str):
        os.makedirs(log_dir, exist_ok=True)
        self.name = name
        self.log_path = os.path.join(log_dir, f"{name}.log")
        self._log = open(self.log_path, "wb")
        self.proc = subprocess.Popen(
            argv, cwd=ROOT, env=env, stdout=self._log,
            stderr=subprocess.STDOUT, start_new_session=True,
        )

    def alive(self) -> bool:
        return self.proc.poll() is None

    def log_tail(self, n: int = 40) -> str:
        self._log.flush()
        with open(self.log_path, "rb") as f:
            return b"\n".join(f.read().splitlines()[-n:]).decode("utf-8", "replace")

    def signal(self, sig: int) -> None:
        if self.alive():
            os.kill(self.proc.pid, sig)

    def wait(self, timeout: float) -> int:
        return self.proc.wait(timeout=timeout)

    def stop(self) -> None:
        if self.alive():
            for sig, wait in ((signal.SIGTERM, 20), (signal.SIGKILL, 10)):
                try:
                    os.killpg(self.proc.pid, sig)
                except ProcessLookupError:
                    break
                try:
                    self.proc.wait(timeout=wait)
                    break
                except subprocess.TimeoutExpired:
                    continue
        if not self._log.closed:
            self._log.close()


def child_env(extra: dict = None) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env["PYTHONDONTWRITEBYTECODE"] = "1"
    env.update(extra or {})
    return env


def get_json(url: str, timeout: float = 30.0) -> dict:
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return json.loads(r.read() or b"{}")


def post_json(url: str, body: dict, timeout: float = 600.0) -> dict:
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return json.loads(r.read() or b"{}")
    except urllib.error.HTTPError as e:
        raise BenchError(f"POST {url} -> {e.code}: {e.read()[:400]!r}") from e


def wait_ready(url: str, child: Child, what: str, timeout: float) -> None:
    t_end = time.monotonic() + timeout
    last = "no answer yet"
    while time.monotonic() < t_end:
        if not child.alive():
            raise BenchError(
                f"{child.name} exited with code {child.proc.returncode} before "
                f"{what}; last log lines:\n{child.log_tail()}")
        try:
            with urllib.request.urlopen(url, timeout=5) as r:
                if r.status == 200:
                    return
        except urllib.error.HTTPError as e:
            last = f"HTTP {e.code}"
        except (urllib.error.URLError, OSError) as e:
            last = repr(e)
        time.sleep(0.25)
    raise BenchError(f"timed out waiting for {what} at {url} ({last}); last "
                     f"log lines of {child.name}:\n{child.log_tail()}")


_SAMPLE = re.compile(r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^}]*\})?\s+(\S+)")
_LABEL = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')


def parse_prom(text: str) -> dict:
    """Prometheus text -> {name: [(labels dict, value), ...]}."""
    out: dict = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        m = _SAMPLE.match(line)
        if not m:
            continue
        try:
            value = float(m.group(3))
        except ValueError:
            continue
        labels = dict(_LABEL.findall(m.group(2) or ""))
        out.setdefault(m.group(1), []).append((labels, value))
    return out


def scrape(base: str) -> dict:
    with urllib.request.urlopen(f"{base}/metrics", timeout=30) as r:
        return parse_prom(r.read().decode())


def counter_delta(before: dict, after: dict, name: str) -> float:
    return (sum(v for _, v in after.get(name, []))
            - sum(v for _, v in before.get(name, [])))


def steps_by_bucket(before: dict, after: dict) -> dict:
    """{"<kind>:<batch bucket>": [steps, mean host-timed ms]} between two scrapes."""
    name, out = "pst_engine_step_duration_seconds", {}
    prev_n = {tuple(sorted(l.items())): v for l, v in before.get(name + "_count", [])}
    prev_s = {tuple(sorted(l.items())): v for l, v in before.get(name + "_sum", [])}
    sums = {tuple(sorted(l.items())): v for l, v in after.get(name + "_sum", [])}
    for labels, v in after.get(name + "_count", []):
        key = tuple(sorted(labels.items()))
        n = v - prev_n.get(key, 0.0)
        if n > 0:
            out[f"{labels.get('kind')}:{labels.get('batch_bucket')}"] = [
                int(n), round((sums.get(key, 0.0) - prev_s.get(key, 0.0)) / n * 1e3, 3)]
    return out


def run_python_child(name: str, argv: list, env: dict, log_dir: str,
                     timeout: float) -> Child:
    """Run a python child to its end; raise with its log tail on failure."""
    child = Child(name, [sys.executable, *argv], env, log_dir)
    try:
        rc = child.wait(timeout)
    except subprocess.TimeoutExpired:
        rc = None
    tail = child.log_tail()
    child.stop()
    if rc is None:
        raise BenchError(f"{name} did not finish in {timeout:.0f}s:\n{tail}")
    if rc != 0:
        raise BenchError(f"{name} exited with code {rc}:\n{tail}")
    return child
