"""One master and one volume server as child processes, over plain HTTP.

Copied from ``chip_smoke.py``'s ``Cluster`` (PR 21), with the volume
server started under ``launch_volume.py`` so that the process that holds
the chip can be traced, and with a client of the standard library's
own: the benchmark takes nothing from the program but the servers.
"""

from __future__ import annotations

import http.client
import json
import os
import socket
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# the volume server also listens for framed TCP at http port + 20000,
# wrapping down past 65535 (utils/framing.py tcp_port_for)
TCP_PORT_OFFSET = 20000


class ClusterError(Exception):
    pass


def _tcp_port_for(p: int) -> int:
    return p + TCP_PORT_OFFSET if p + TCP_PORT_OFFSET <= 65535 \
        else p - TCP_PORT_OFFSET


def free_port() -> int:
    """A free port whose framed-TCP sibling is free too."""
    for _ in range(64):
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            p = s.getsockname()[1]
        try:
            with socket.socket() as t:
                t.bind(("127.0.0.1", _tcp_port_for(p)))
            return p
        except OSError:
            continue
    raise ClusterError("no free port pair")


def request(addr: str, method: str, path: str, body: bytes | None = None,
            timeout: float = 900.0) -> tuple[int, bytes]:
    host, port = addr.rsplit(":", 1)
    conn = http.client.HTTPConnection(host, int(port), timeout=timeout)
    try:
        headers = {"Content-Type": "application/json"} if body else {}
        conn.request(method, path, body=body, headers=headers)
        r = conn.getresponse()
        return r.status, r.read()
    finally:
        conn.close()


class Cluster:
    def __init__(self, data_dir: str, env: dict):
        self.data_dir = data_dir
        self.env = env
        self.procs: list[subprocess.Popen] = []
        self.logs: list[str] = []
        self.ctl = os.path.join(data_dir, "ctl")
        self.vdir = os.path.join(data_dir, "v")
        os.makedirs(self.ctl)
        os.makedirs(self.vdir, exist_ok=True)

    def start(self, spans: bool, fault: str | None) -> None:
        mport, vport = free_port(), free_port()
        self.master = f"127.0.0.1:{mport}"
        self.volume = f"127.0.0.1:{vport}"
        self._spawn("master", [os.path.join(ROOT, "weed.py"), "master",
                               "-port", str(mport),
                               "-mdir", os.path.join(self.data_dir, "m")])
        self._wait(self.master, "/dir/status")
        # -max 1: the server holds exactly the one volume
        argv = [os.path.join(HERE, "launch_volume.py"), "--ctl", self.ctl]
        if spans:
            argv.append("--spans")
        if fault:
            argv += ["--fault", fault]
        self._spawn("volume", argv + [
            "--", "-dir", self.vdir, "-port", str(vport), "-max", "1",
            "-mserver", self.master, "-ec.engine", "tpu"])
        self._wait(self.volume, "/status")

    def _spawn(self, name: str, argv: list[str]) -> None:
        log = os.path.join(self.data_dir, f"{name}.log")
        self.logs.append(log)
        with open(log, "wb") as f:
            self.procs.append(subprocess.Popen(
                [sys.executable] + argv, cwd=ROOT, env=self.env,
                stdout=f, stderr=subprocess.STDOUT))

    def _wait(self, addr: str, path: str, timeout: float = 300.0) -> None:
        end = time.monotonic() + timeout
        while time.monotonic() < end:
            for p in self.procs:
                if p.poll() is not None:
                    raise ClusterError(f"{os.path.basename(p.args[1])} "
                                       f"exited rc={p.returncode} at start")
            try:
                if request(addr, "GET", path, timeout=5.0)[0] == 200:
                    return
            except OSError:
                pass
            time.sleep(0.1)
        raise ClusterError(f"{addr}{path} not serving after {timeout}s")

    def post(self, path: str, body: dict) -> dict:
        status, raw = request(self.volume, "POST", path,
                              json.dumps(body).encode())
        if status != 200:
            raise ClusterError(f"POST {path} {body} -> {status}: "
                               f"{raw[:500]!r}")
        return json.loads(raw or b"{}")

    def get_json(self, path: str) -> dict:
        status, raw = request(self.volume, "GET", path, timeout=120.0)
        if status != 200:
            raise ClusterError(f"GET {path} -> {status}")
        return json.loads(raw)

    def status(self) -> dict:
        return self.get_json("/status")

    def spans(self) -> list[dict]:
        """Drain the program's span ring: its finished spans as Chrome
        trace events (ts, dur in microseconds)."""
        doc = self.get_json("/debug/traces?clear=1")
        return [e for e in doc.get("traceEvents", []) if e.get("ph") == "X"]

    # --- profiler control (launch_volume.py) --------------------------
    def _signal(self, name: str, ack: str, timeout: float) -> str:
        open(os.path.join(self.ctl, name), "w").close()
        path = os.path.join(self.ctl, ack)
        end = time.monotonic() + timeout
        while not os.path.exists(path):
            if time.monotonic() > end or self.procs[-1].poll() is not None:
                raise ClusterError(f"profiler did not answer {name!r}")
            time.sleep(0.005)
        with open(path) as f:
            return f.read()

    def start_trace(self) -> None:
        self._signal("start", "started", 120.0)

    def stop_trace(self) -> tuple[float, str]:
        """The traced window's seconds, and the profile directory."""
        window = float(self._signal("stop", "stopped", 300.0))
        return window, os.path.join(self.ctl, "profile")

    def stop(self) -> None:
        for p in reversed(self.procs):
            if p.poll() is None:
                p.terminate()
            try:
                p.wait(timeout=60)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()

    def log_tails(self, n: int = 2000) -> str:
        out = []
        for log in self.logs:
            try:
                with open(log, "rb") as f:
                    tail = f.read()[-n:].decode(errors="replace")
            except OSError:
                continue
            out.append(f"--- {os.path.basename(log)} (tail) ---\n{tail}")
        return "\n".join(out)
