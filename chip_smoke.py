#!/usr/bin/env python3
"""Drive the EC volume-server path once, end to end, on the chip.

Starts ``weed.py master`` and ``weed.py volume -ec.engine=tpu`` as
processes, fills one volume over HTTP (assign, then write) with needles
drawn from ``--seed``, reads a sample back, EC-encodes it through
``POST /admin/ec/generate`` and compares all 14 shards byte for byte
with a CPU reference (``ec/encoder.write_ec_files`` on the native
engine, computed here).  Then it serves the sample from the shards,
degraded with one data shard gone, and rebuilds 1 and then 4 missing
shards through ``POST /admin/ec/rebuild``.  Any engine fallback, worker
restart or encode retry fails the run.

This process never imports JAX: the volume server alone holds the chip,
and it reports the device it computed on.  The last line of stdout is
``{"ok": true, "device": {...}}`` only when every phase passed on a TPU.

``--chips 4`` runs only the multi-chip phase: one volume encoded with
the ``tpu`` and the ``mesh`` engine, which on several chips share one
plane (whole dispatches round-robin over per-device queues, the
one-chip word kernel on each chip, nothing exchanged between chips),
each compared with the CPU reference, with work on every device.  The
XLA shard_map over the chips is left only under ``MeshEngine``, the
codec behind ``-ec.engine=mesh`` reads.

Lines starting ``info:`` (timings, sizes) are informative only.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import socket
import subprocess
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
GiB = 1 << 30
MiB = 1 << 20
MIN_NEEDLE, MAX_NEEDLE = 1 << 10, 4 << 20  # weed benchmark default .. 4 MiB


class SmokeFailure(Exception):
    pass


def say(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, phase: str, detail: str) -> None:
    say(f"phase {phase}: {'PASS' if ok else 'FAIL'} ({detail})")
    if not ok:
        raise SmokeFailure(f"{phase}: {detail}")


def free_port() -> int:
    """A free port whose framed-TCP sibling (tcp_port_for) is free too."""
    from seaweedfs_tpu.utils.framing import tcp_port_for

    for _ in range(64):
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            p = s.getsockname()[1]
        try:
            with socket.socket() as t:
                t.bind(("127.0.0.1", tcp_port_for(p)))
            return p
        except OSError:
            continue
    raise SmokeFailure("no free port pair")


def needle_bytes(seed: int, i: int, size: int) -> bytes:
    import numpy as np

    return np.random.default_rng([seed, i]).bytes(size)


def needle_sizes(seed: int):
    """Seeded log-uniform sizes from 1 KiB to 4 MiB."""
    import numpy as np

    rng = np.random.default_rng(seed)
    lo, hi = np.log2(MIN_NEEDLE), np.log2(MAX_NEEDLE)
    while True:
        for v in np.exp2(rng.uniform(lo, hi, 1024)).astype(np.int64):
            yield int(v)


class Cluster:
    """One master and one volume server as child processes."""

    def __init__(self, data_dir: str):
        self.data_dir = data_dir
        self.procs: list[subprocess.Popen] = []
        self.logs: list[str] = []

    def start(self, engine: str) -> None:
        self.mport = free_port()
        self.vport = free_port()
        self.vdir = os.path.join(self.data_dir, "v")
        os.makedirs(self.vdir)
        self._spawn("master", ["master", "-port", str(self.mport),
                               "-mdir", os.path.join(self.data_dir, "m")])
        self._wait(f"127.0.0.1:{self.mport}/dir/status")
        # -max 1: the server holds exactly one volume, so every
        # assignment lands in it
        self._spawn("volume", ["volume", "-dir", self.vdir,
                               "-port", str(self.vport), "-max", "1",
                               "-mserver", f"127.0.0.1:{self.mport}",
                               "-ec.engine", engine])
        self._wait(f"127.0.0.1:{self.vport}/status")
        self.master = f"127.0.0.1:{self.mport}"
        self.volume = f"127.0.0.1:{self.vport}"

    def _spawn(self, name: str, argv: list[str]) -> None:
        log = os.path.join(self.data_dir, f"{name}.log")
        self.logs.append(log)
        with open(log, "wb") as f:
            self.procs.append(subprocess.Popen(
                [sys.executable, os.path.join(ROOT, "weed.py")] + argv,
                cwd=ROOT, stdout=f, stderr=subprocess.STDOUT))

    def _wait(self, url: str, timeout: float = 180.0) -> None:
        from seaweedfs_tpu.utils.httpd import http_bytes

        end = time.time() + timeout
        while time.time() < end:
            for p in self.procs:
                if p.poll() is not None:
                    raise SmokeFailure(
                        f"{p.args[2]} exited rc={p.returncode} at start")
            if http_bytes("GET", f"http://{url}", timeout=5.0)[0] == 200:
                return
            time.sleep(0.2)
        raise SmokeFailure(f"{url} not serving after {timeout}s")

    def post(self, path: str, body: dict) -> dict:
        from seaweedfs_tpu.utils.httpd import http_json

        return http_json("POST", f"http://{self.volume}{path}", body,
                         timeout=900.0)

    def status(self) -> dict:
        from seaweedfs_tpu.utils.httpd import http_json

        return http_json("GET", f"http://{self.volume}/status", timeout=60.0)

    def get(self, fid: str) -> bytes:
        from seaweedfs_tpu.utils.httpd import http_bytes

        status, body, _ = http_bytes("GET", f"http://{self.volume}/{fid}",
                                     timeout=120.0)
        if status != 200:
            raise SmokeFailure(f"GET {fid} -> {status}")
        return body

    def stop(self) -> None:
        for p in self.procs:
            if p.poll() is None:
                p.terminate()
        for p in self.procs:
            try:
                p.wait(timeout=20)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()

    def log_tails(self) -> str:
        out = []
        for log in self.logs:
            try:
                with open(log, "rb") as f:
                    tail = f.read()[-3000:].decode(errors="replace")
            except OSError:
                continue
            out.append(f"--- {os.path.basename(log)} (tail) ---\n{tail}")
        return "\n".join(out)


def fill(cl: Cluster, seed: int, target_bytes: int, min_needles: int,
         workers: int = 8) -> list[tuple[int, str, int]]:
    """Write needles until both targets are met: [(index, fid, size)]."""
    import concurrent.futures as cf
    import threading

    from seaweedfs_tpu.client.operation import WeedClient

    client = WeedClient(cl.master)
    sizes = needle_sizes(seed)
    lock = threading.Lock()
    state = {"i": 0, "bytes": 0}
    out: list[tuple[int, str, int]] = []

    def next_job():
        with lock:
            if state["bytes"] >= target_bytes and state["i"] >= min_needles:
                return None
            i, size = state["i"], next(sizes)
            state["i"] += 1
            state["bytes"] += size
            return i, size

    def worker():
        while True:
            job = next_job()
            if job is None:
                return
            i, size = job
            fid = client.upload(needle_bytes(seed, i, size))
            with lock:
                out.append((i, fid, size))

    # one write first: it grows the volume that all later writes share
    i, size = next_job()
    out.append((i, client.upload(needle_bytes(seed, i, size)), size))
    with cf.ThreadPoolExecutor(workers) as pool:
        for f in [pool.submit(worker) for _ in range(workers)]:
            f.result()
    out.sort()
    return out


def read_sample(cl: Cluster, seed: int, sample) -> int:
    bad = [fid for i, fid, size in sample
           if cl.get(fid) != needle_bytes(seed, i, size)]
    if bad:
        raise SmokeFailure(f"{len(bad)} needles read back wrong: {bad[:5]}")
    return len(sample)


def files_equal(a: str, b: str, chunk: int = 64 * MiB) -> bool:
    if os.path.getsize(a) != os.path.getsize(b):
        return False
    with open(a, "rb") as fa, open(b, "rb") as fb:
        while True:
            x = fa.read(chunk)
            if x != fb.read(chunk):
                return False
            if not x:
                return True


def cpu_reference(dat: str, ref_dir: str) -> str:
    """The 14 reference shards of `dat` from the native CPU codec."""
    from seaweedfs_tpu import native
    from seaweedfs_tpu.ec import encoder
    from seaweedfs_tpu.ec.codec import NativeEngine, ReedSolomon

    base = os.path.join(ref_dir, "ref")
    os.link(dat, base + ".dat")
    eng = NativeEngine()
    lib = native.load()
    say(f"info: cpu reference engine {eng.name} "
        f"(gfni={lib.gf_has_gfni()}, avx2={lib.gf_has_avx2()})")
    t0 = time.perf_counter()
    encoder.write_ec_files(base, ReedSolomon(10, 4, engine=eng))
    say(f"info: cpu reference encode wall {time.perf_counter() - t0:.3f} s")
    return base


def compare_shards(server_base: str, ref_base: str, phase: str) -> None:
    from seaweedfs_tpu.ec.layout import to_ext

    diff = [i for i in range(14) if not files_equal(
        server_base + to_ext(i), ref_base + to_ext(i))]
    size = os.path.getsize(server_base + to_ext(0))
    check(not diff, phase, f"14 shards of {size} bytes vs cpu reference; "
          f"differing: {diff}")


def drop_ref(ref_base: str) -> None:
    from seaweedfs_tpu.ec.layout import to_ext

    for ext in [to_ext(i) for i in range(14)] + [".dat", ".eci"]:
        if os.path.exists(ref_base + ext):
            os.remove(ref_base + ext)


def check_clean(resp: dict, op: str) -> None:
    """An EC admin response must show no fallback, restart or retry."""
    pipe = resp.get("pipeline", {})
    enc = resp.get("encoder", {})
    clean = (pipe.get("engine_fallbacks") == 0
             and pipe.get("worker_restarts") == 0
             and enc.get("fallbacks", 0) == 0
             and enc.get("retries", 0) == 0)
    check(clean, f"{op}-clean", f"pipeline {pipe}, encoder fallbacks "
          f"{enc.get('fallbacks')} retries {enc.get('retries')}")


def ec_op(cl: Cluster, path: str, body: dict, op: str) -> dict:
    t0 = time.perf_counter()
    resp = cl.post(path, body)
    wall = time.perf_counter() - t0
    enc = resp.get("encoder", {})
    say(f"info: {op} wall {wall:.3f} s (server encoder wall "
        f"{enc.get('wall_s')}, dispatches {enc.get('dispatches')}, "
        f"bytes_in {enc.get('bytes_in')})")
    check_clean(resp, op)
    return resp


def volume_of(needles) -> int:
    vids = {int(fid.split(",")[0]) for _, fid, _ in needles}
    check(len(vids) == 1, "one-volume", f"needles landed in volumes "
          f"{sorted(vids)}")
    return vids.pop()


def do_fill(cl: Cluster, args, target: int, min_needles: int):
    t0 = time.perf_counter()
    needles = fill(cl, args.seed, target, min_needles)
    wall = time.perf_counter() - t0
    total = sum(s for _, _, s in needles)
    vid = volume_of(needles)
    dat = os.path.join(cl.vdir, f"{vid}.dat")
    dat_size = os.path.getsize(dat)
    say(f"info: fill wrote {len(needles)} needles, {total} payload bytes, "
        f".dat {dat_size} bytes in {wall:.3f} s")
    check(dat_size >= target and len(needles) >= min_needles, "fill",
          f"volume {vid}: {dat_size} bytes >= {target}, "
          f"{len(needles)} needles >= {min_needles}")
    return needles, vid, dat


def sample_of(needles, seed: int, n: int):
    import numpy as np

    rng = np.random.default_rng(seed + 1)
    idx = set(rng.choice(len(needles), min(n, len(needles)),
                         replace=False).tolist())
    idx.add(max(range(len(needles)), key=lambda j: needles[j][2]))
    return [needles[j] for j in sorted(idx)]


def single_chip(cl: Cluster, args, data_dir: str, target: int,
                min_needles: int) -> None:
    from seaweedfs_tpu.ec.layout import to_ext

    needles, vid, dat = do_fill(cl, args, target, min_needles)
    base = dat[:-len(".dat")]
    sample = sample_of(needles, args.seed, args.sample)
    check(True, "read", f"{read_sample(cl, args.seed, sample)} sampled "
          f"needles read back intact")

    cl.post("/admin/readonly", {"volume_id": vid, "readonly": True})
    resp = ec_op(cl, "/admin/ec/generate", {"volume_id": vid}, "ec-generate")
    say(f"info: device {json.dumps(resp.get('device'))}")
    ref_dir = os.path.join(data_dir, "ref")
    os.makedirs(ref_dir)
    ref = cpu_reference(dat, ref_dir)
    compare_shards(base, ref, "encode-shards")
    drop_ref(ref)

    # serve from the shards: mount them, drop the plain volume
    cl.post("/admin/ec/mount", {"volume_id": vid})
    cl.post("/admin/delete_volume", {"volume_id": vid})
    check(True, "ec-read", f"{read_sample(cl, args.seed, sample)} sampled "
          f"needles served intact from the EC shards")

    aside = os.path.join(data_dir, "aside")
    os.makedirs(aside)

    def set_aside(shards):
        cl.post("/admin/ec/unmount", {"volume_id": vid})
        for s in shards:
            os.rename(base + to_ext(s), os.path.join(aside, to_ext(s)))
        cl.post("/admin/ec/mount", {"volume_id": vid})

    def rebuild(shards, phase):
        cl.post("/admin/ec/unmount", {"volume_id": vid})
        resp = ec_op(cl, "/admin/ec/rebuild", {"volume_id": vid}, phase)
        got = sorted(resp.get("rebuilt_shard_ids", []))
        same = [s for s in shards if files_equal(
            base + to_ext(s), os.path.join(aside, to_ext(s)))]
        check(got == sorted(shards) and len(same) == len(shards), phase,
              f"rebuilt {got}, byte-identical {same}")
        for s in shards:
            os.remove(os.path.join(aside, to_ext(s)))
        cl.post("/admin/ec/mount", {"volume_id": vid})

    before = cl.status()["EcDevice"]["codec_matmuls"]
    set_aside([3])
    t0 = time.perf_counter()
    n = read_sample(cl, args.seed, sample)
    matmuls = cl.status()["EcDevice"]["codec_matmuls"] - before
    say(f"info: degraded read of {n} needles {time.perf_counter() - t0:.3f}"
        f" s, {matmuls} device reconstructs")
    check(matmuls > 0, "degraded-read", f"{n} sampled needles intact with "
          f"shard 3 gone; {matmuls} reconstructs on the device engine")
    rebuild([3], "rebuild-1")
    set_aside([0, 7, 10, 13])
    rebuild([0, 7, 10, 13], "rebuild-4")
    check(True, "ec-read-after-rebuild", f"{read_sample(cl, args.seed, sample)}"
          f" sampled needles intact")


def four_chips(cl: Cluster, args, data_dir: str, target: int) -> None:
    from seaweedfs_tpu.ec.layout import to_ext

    needles, vid, dat = do_fill(cl, args, target, 0)
    base = dat[:-len(".dat")]
    cl.post("/admin/readonly", {"volume_id": vid, "readonly": True})
    ref_dir = os.path.join(data_dir, "ref")
    os.makedirs(ref_dir)
    ref = cpu_reference(dat, ref_dir)
    for engine in ("tpu", "mesh"):
        resp = ec_op(cl, "/admin/ec/generate",
                     {"volume_id": vid, "engine": engine},
                     f"ec-generate-{engine}")
        compare_shards(base, ref, f"encode-shards-{engine}")
        per = resp["encoder"].get("per_device", {})
        counts = [per.get(str(i), {}).get("dispatches", 0)
                  for i in range(4)]
        check(resp["encoder"].get("devices") == 4 and min(counts) > 0,
              f"{engine}-all-devices", f"dispatches per device {counts}")
        cl.post("/admin/ec/delete",
                {"volume_id": vid, "shard_ids": list(range(14))})
        check(not os.path.exists(base + to_ext(0)), f"drop-{engine}",
              "shards removed for the next engine")
    drop_ref(ref)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--chips", type=int, default=1, choices=[1, 4])
    p.add_argument("--size-mb", type=int, default=1024,
                   help="least volume size; below 1024 is a cut")
    p.add_argument("--needles", type=int, default=10000,
                   help="least needle count (one chip; scaled with a cut)")
    p.add_argument("--sample", type=int, default=200,
                   help="needles read back in each read phase")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)
    t_start = time.time()

    # native libraries from the committed sources, never a stale .so
    subprocess.run(["make", "-B", "-s", "-j4", "-C",
                    os.path.join(ROOT, "seaweedfs_tpu", "native")],
                   check=True, stdout=subprocess.DEVNULL)
    say("info: lines starting 'info:' are informative only")
    say("info: native libraries rebuilt from the committed .cpp files")

    target = args.size_mb * MiB
    # --chips 4 fills by size alone; a smaller one-chip volume scales
    # the needle count with it
    min_needles = 0 if args.chips == 4 else max(
        1, args.needles * min(args.size_mb, 1024) // 1024)
    data_dir = tempfile.mkdtemp(prefix="chip_smoke-")
    free = shutil.disk_usage(data_dir).free
    mean = (MAX_NEEDLE - MIN_NEEDLE) / math.log(MAX_NEEDLE / MIN_NEEDLE)
    expected = max(target, min_needles * mean)
    # .dat + 14 server shards + 14 reference shards, with room to spare
    if 4 * expected > free:
        f = free / (4 * expected)
        target, min_needles = int(target * f), int(min_needles * f)
        say(f"CUT: {free} bytes free in {data_dir} hold a volume of about "
            f"{int(expected * f)} bytes, not {int(expected)}")
    if target < GiB or (args.chips == 1 and min_needles < 10000):
        say(f"CUT: volume target {target} bytes (1 GiB uncut), least "
            f"needle count {min_needles} (10000 uncut)")
    say(f"info: data dir {data_dir}, {free} bytes free")

    cl = Cluster(data_dir)
    device = None
    try:
        cl.start("tpu")
        if args.chips == 4:
            four_chips(cl, args, data_dir, target)
        else:
            single_chip(cl, args, data_dir, target, min_needles)
        st = cl.status()
        pipe, dev = st["EcPipeline"], st["EcDevice"]
        check(pipe["engine_fallbacks"] == 0 and pipe["worker_restarts"] == 0,
              "server-clean", f"server totals {pipe}")
        say(f"info: compile cache: {dev['compiles']} compiles, "
            f"{dev['cache_hits']} cache hits of {dev['compile_requests']} "
            f"requests (dir {dev['compile_cache_dir'] or 'off'})")
        device = {"platform": dev["platform"], "kind": dev["kind"],
                  "count": dev["count"]}
        say(f"info: device {json.dumps(device)}; total wall "
            f"{time.time() - t_start:.3f} s")
        check("jax" not in sys.modules, "parent-no-jax",
              "this process never imported jax")
    except Exception as e:
        say(f"chip_smoke: FAILED: {type(e).__name__}: {e}")
        say(cl.log_tails())
        return 1
    finally:
        cl.stop()
        shutil.rmtree(data_dir, ignore_errors=True)
    if device["platform"] != "tpu" or device["count"] != args.chips:
        say(f"chip_smoke: FAILED: every phase passed, but on {device} "
            f"and not on {args.chips} TPU chip(s)")
        return 1
    say(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
