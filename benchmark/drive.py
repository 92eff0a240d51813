"""The one traffic generator: it reads a mix's data file and drives it.

A mix (``benchmark/traffic/<name>.json``) names its operation and its
parameters; nothing of a mix lives in code.  Operations:

- ``ec_generate``: ``POST /admin/ec/generate`` of the sealed volume,
  back to back, with ``POST /admin/ec/delete`` of its shards between
  calls.  The window ends when the last encode started inside it
  returns.
- ``ec_rebuild``: the volume is encoded in set-up; then ``POST
  /admin/ec/delete`` of the ``lost_shards`` and ``POST
  /admin/ec/rebuild``, back to back.
- ``get``: the volume is encoded in set-up, served from its shards with
  the ``lost_shards`` gone, and read by ``clients`` closed-loop clients
  spread over ``processes`` processes, each GET of a uniformly random
  needle.

Each operation keeps what its answers said, and ``check`` compares them
with the plain reference after the window.
"""

from __future__ import annotations

import http.client
import multiprocessing
import os
import time

import numpy as np

import needles
from cluster import ClusterError
from reference import rs


def shard_path(vdir: str, vid: int, i: int) -> str:
    return os.path.join(vdir, f"{vid}.ec{i:02d}")


def pread_all(path: str, offsets, length: int) -> np.ndarray | None:
    """[len(offsets), length] bytes of `path`, or None when it is gone."""
    try:
        fd = os.open(path, os.O_RDONLY)
    except FileNotFoundError:
        return None
    try:
        out = np.zeros((len(offsets), length), dtype=np.uint8)
        for n, off in enumerate(offsets):
            b = os.pread(fd, length, int(off))
            out[n, :len(b)] = np.frombuffer(b, dtype=np.uint8)
        return out
    finally:
        os.close(fd)


class Check:
    """One number compared, beside its limit (exact comparisons: 0)."""

    def __init__(self, name: str, value: float, limit: float):
        self.name, self.value, self.limit = name, value, limit

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


class Mix:
    def __init__(self, traffic: dict, vol: needles.Volume,
                 dat: np.ndarray | None, cluster, seed: int):
        self.t, self.vol, self.dat = traffic, vol, dat
        self.cl, self.seed = cluster, seed
        self.vid = vol.vid
        self.op = traffic["op"]
        self.shard_size = rs.shard_size(vol.dat_size)
        self.lost = list(traffic.get("lost_shards", []))
        self.samples = []       # (offsets, [14 or lost, n, length]) per op
        self.attempted = self.failed = 0
        self.errors: list[str] = []
        self.result: dict = {}

    # --- set-up ---------------------------------------------------------
    def setup(self) -> None:
        self.cl.post("/admin/readonly", {"volume_id": self.vid,
                                         "readonly": True})
        if self.op == "ec_generate":
            return
        self._generate()
        if self.op == "get":
            # serve from the shards, the plain volume gone, then lose
            self.cl.post("/admin/ec/mount", {"volume_id": self.vid})
            self.cl.post("/admin/delete_volume", {"volume_id": self.vid})
        self.cl.post("/admin/ec/delete", {"volume_id": self.vid,
                                          "shard_ids": self.lost})

    def _generate(self) -> dict:
        return self.cl.post("/admin/ec/generate", {"volume_id": self.vid})

    def _drop_all(self) -> None:
        self.cl.post("/admin/ec/delete", {"volume_id": self.vid,
                                          "shard_ids": list(range(14))})

    def _rebuild(self) -> list[int]:
        got = self.cl.post("/admin/ec/rebuild", {"volume_id": self.vid})
        return sorted(got.get("rebuilt_shard_ids", []))

    def warmup(self) -> None:
        """One whole operation of the window's kind: it compiles every
        shape the window uses (an encode's dispatches, a rebuild's, a
        reconstruct's one padded width)."""
        if self.op == "ec_generate":
            self._generate()
            self._drop_all()
        elif self.op == "ec_rebuild":
            self._rebuild()
            self.cl.post("/admin/ec/delete", {"volume_id": self.vid,
                                              "shard_ids": self.lost})
        else:
            # every width a reconstruct can have: a needle wholly on a
            # lost shard, and each needle split over a block boundary
            # onto one (TpuEngine slices its output per width)
            on = self._on_lost()
            lost, split, keep = (np.flatnonzero(on == x).tolist()
                                 for x in (1, 2, 0))
            # their answers are not judged: the window's are
            for i in lost[:32] + split + keep[:32]:
                _get(self.cl.volume, self.vol.fid(i))

    def _on_lost(self) -> np.ndarray:
        """Per needle, 0: its record lies on no lost shard; 1: wholly
        on one; 2: split over a block boundary, a part on a lost one."""
        lo = self.vol.offsets
        hi = lo + np.array([needles.record_size(int(s))
                            for s in self.vol.sizes]) - 1
        a, b = (rs.shard_of(self.vol.dat_size, o) for o in (lo, hi))
        on = np.isin(a, self.lost) | np.isin(b, self.lost)
        return np.where(on, np.where(a == b, 1, 2), 0)

    # --- the window -----------------------------------------------------
    def window(self, seconds: float, on_start=None) -> None:
        if self.op == "get":
            self._read_window(seconds, on_start)
            return
        sample = self.t.get("sample_ranges", 16)
        length = self.t.get("sample_bytes", 65536)
        shards = list(range(14)) if self.op == "ec_generate" else self.lost
        if on_start:
            on_start()
        t0 = time.monotonic()
        n = 0
        while True:
            try:
                if self.op == "ec_generate":
                    self._generate()
                elif self._rebuild() != sorted(self.lost):
                    self.failed += 1
            except ClusterError:
                self.failed += 1
            n += 1
            # a seeded sample of every answer, read before it is deleted
            offs = needles.rng(self.seed, 10, n).integers(
                0, self.shard_size // length, sample) * length
            self.samples.append((offs, [pread_all(
                shard_path(self.cl.vdir, self.vid, s), offs, length)
                for s in shards]))
            if time.monotonic() - t0 >= seconds:
                break
            if self.op == "ec_generate":
                self._drop_all()
            else:
                self.cl.post("/admin/ec/delete", {"volume_id": self.vid,
                                                  "shard_ids": self.lost})
        wall = time.monotonic() - t0
        self.attempted = n
        if self.op == "ec_generate":
            self.result = {"encode_mbps": n * self.vol.dat_size / wall / 1e6}
        else:
            self.result = {"rebuild_s": wall / n}
        self.result["window_s"] = wall
        self.result["ops"] = n

    def _read_window(self, seconds: float, on_start) -> None:
        procs = self.t["processes"]
        clients = self.t["clients"]
        # fork: the clients share the volume's payload bytes, which they
        # compare every answer with, without a copy; this process holds
        # no thread at this point (the reference's pool runs after)
        ctx = multiprocessing.get_context("fork")
        ready = ctx.Barrier(procs + 1)
        go = ctx.Value("d", 0.0)
        pipes, workers = [], []
        for p in range(procs):
            mine = list(range(p, clients, procs))
            rx, tx = ctx.Pipe(duplex=False)
            w = ctx.Process(target=_read_worker, args=(
                self, mine, seconds, ready, go, tx), daemon=True)
            w.start()
            tx.close()
            pipes.append(rx)
            workers.append(w)
        ready.wait(timeout=120)
        if on_start:
            on_start()
        go.value = time.monotonic() + 0.05
        ready.wait(timeout=120)
        parts = []
        for rx in pipes:
            if not rx.poll(seconds + 120):
                raise RuntimeError("a load process sent no result")
            parts.append(rx.recv())
        for w in workers:
            w.join()
        end = max(p["end"] for p in parts)
        wall = end - go.value
        lat = np.concatenate([p["lat"] for p in parts])
        ok = np.concatenate([p["ok"] for p in parts])
        # a failed GET counts as over every limit: the whole window
        lat = np.where(ok, lat, wall)
        self.attempted, self.failed = len(lat), int((~ok).sum())
        self.errors = [e for p in parts for e in p["errors"]][:5]
        self.result = {
            "read_p99_ms": float(np.quantile(lat, 0.99)) * 1e3,
            "read_ops_s": float(ok.sum()) / wall,
            "read_p50_ms": float(np.quantile(lat, 0.5)) * 1e3,
            "window_s": wall, "ops": len(lat),
            "client_cpu_share": sum(p["cpu"] for p in parts) / wall / procs}

    # --- the check ------------------------------------------------------
    def check(self) -> list[Check]:
        """After the window, with the server stopped: every answer
        compared with the plain reference.  An encode or rebuild that
        failed, or rebuilt other shards than were lost, counts as all
        the bytes it should have made."""
        if self.op == "get":
            return [Check("wrong_or_failed_gets", self.failed, 0)]
        length = self.t.get("sample_bytes", 65536)
        want = list(range(14)) if self.op == "ec_generate" else self.lost
        wrong = self.failed * len(want) * self.shard_size
        for offs, got in self.samples:
            for n, off in enumerate(offs):
                ref = rs.shard_columns(self.dat, int(off), length)
                for s, g in zip(want, got):
                    wrong += length if g is None else \
                        int((g[n] != ref[s]).sum())
        wrong += self._final_shards(want)
        if self.op == "ec_generate":
            wrong += self._ecx_wrong()
        return [Check("wrong_bytes", wrong, 0)]

    def _final_shards(self, want: list[int], chunk: int = 32 << 20) -> int:
        """The last answer, which the window leaves on disk, in full."""
        wrong = 0
        files = {}
        for s in want:
            path = shard_path(self.cl.vdir, self.vid, s)
            if os.path.exists(path) \
                    and os.path.getsize(path) == self.shard_size:
                files[s] = open(path, "rb")
            else:
                wrong += self.shard_size
        try:
            for off in range(0, self.shard_size, chunk):
                n = min(chunk, self.shard_size - off)
                data = rs.data_columns(self.dat, off, n)
                parity = rs.encode(data) if any(s >= rs.K for s in files) \
                    else None
                for s, f in files.items():
                    ref = data if s < rs.K else parity
                    s %= rs.K
                    got = f.read(n)
                    if got != ref[s].tobytes():
                        wrong += int((np.frombuffer(got, np.uint8)
                                      != ref[s]).sum())
        finally:
            for f in files.values():
                f.close()
        return wrong

    def _ecx_wrong(self) -> int:
        path = os.path.join(self.cl.vdir, f"{self.vid}.ecx")
        ref = np.frombuffer(rs.sorted_index(self.vol.idx_bytes()), np.uint8)
        if not os.path.exists(path):
            return len(ref)
        got = np.fromfile(path, dtype=np.uint8)
        if len(got) != len(ref):
            return max(len(got), len(ref))
        return int((got != ref).sum())


def _get(addr: str, fid: str, conn=None) -> tuple[int, bytes]:
    own = conn is None
    if own:
        host, port = addr.rsplit(":", 1)
        conn = http.client.HTTPConnection(host, int(port), timeout=60)
    try:
        conn.request("GET", "/" + fid)
        r = conn.getresponse()
        return r.status, r.read()
    finally:
        if own:
            conn.close()


def _read_worker(mix: Mix, clients: list[int], seconds: float, ready, go,
                 tx) -> None:
    """One load process: its closed-loop clients on threads, each with
    one keep-alive connection."""
    import threading

    out: dict[int, dict] = {}
    host, port = mix.cl.volume.rsplit(":", 1)
    total = len(mix.vol)

    def client(c: int) -> None:  # thread-entry
        keys = needles.rng(mix.seed, 100, c).integers(0, total, 1 << 20)
        conn = http.client.HTTPConnection(host, int(port), timeout=60)
        lat, ok, errors = [], [], []
        t_end = go.value + seconds
        last = go.value
        n = 0
        while True:
            t = time.monotonic()
            if t >= t_end:
                break
            i = int(keys[n % len(keys)])
            n += 1
            try:
                status, body = _get(mix.cl.volume, mix.vol.fid(i), conn)
                good = status == 200 and body == mix.vol.body(i)
                if not good:
                    errors.append(f"GET {mix.vol.fid(i)}: {status}, "
                                  f"{len(body)} bytes")
            except (OSError, http.client.HTTPException) as e:
                good = False
                errors.append(f"GET {mix.vol.fid(i)}: {e!r}")
                conn.close()
                conn = http.client.HTTPConnection(host, int(port),
                                                  timeout=60)
            last = time.monotonic()
            lat.append(last - t)
            ok.append(good)
        conn.close()
        out[c] = {"lat": lat, "ok": ok, "errors": errors[:5], "end": last}

    ready.wait(timeout=120)
    ready.wait(timeout=120)
    cpu0 = time.process_time()
    threads = [threading.Thread(target=client, args=(c,)) for c in clients]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    parts = list(out.values())
    tx.send({"lat": np.array([x for p in parts for x in p["lat"]]),
             "ok": np.array([x for p in parts for x in p["ok"]], dtype=bool),
             "errors": [e for p in parts for e in p["errors"]][:5],
             "end": max(p["end"] for p in parts),
             "cpu": time.process_time() - cpu0})
    tx.close()
