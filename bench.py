#!/usr/bin/env python
"""Headline benchmark: RS(10,4) ec.encode throughput per chip.

Prints ONE JSON line and ALWAYS exits 0:
  value       = sustained TPU encode throughput with data resident in HBM
                (MB of volume data encoded per second; the chip-side number a
                production pipeline with overlapped IO converges to)
  vs_baseline = value / CPU-SIMD engine throughput on this host (the
                equivalent of the reference's klauspost/reedsolomon AVX2
                path — SeaweedFS publishes no EC numbers, so the CPU engine
                measured on the same host is the baseline;
                ref: weed/storage/erasure_coding/ec_encoder.go:120)

Robustness contract (the round-1 artifact was rc=1 because jax.devices()
hung/crashed when no TPU was reachable):
  - the PARENT process never imports jax;
  - backend init is probed in a subprocess with a bounded timeout, retried
    once;
  - the measurement itself runs in a subprocess with a bounded timeout and
    checkpoints partial results to a scratch file after every section, so a
    mid-bench hang still surfaces the completed sections;
  - on TPU failure it falls back to CPU-backend jax, and failing that to a
    pure-numpy measurement — the JSON line is emitted no matter what, with
    an "error" detail explaining any degradation.

Methodology: the TPU kernel is timed as one jitted fori_loop of N
data-dependent encodes (each iteration XOR-perturbs the input and the
parity folds into a scalar), so per-dispatch launch latency and lazy
dispatch cannot distort the figure; differencing two loop lengths cancels
the fixed launch+readback cost.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import time

PROBE_TIMEOUT_S = 240       # first TPU compile can take ~40s
BENCH_TIMEOUT_S = 1500
CPU_BENCH_TIMEOUT_S = 900

# Per-section wall-clock caps (seconds).  BENCH_r05 died at the GLOBAL
# 1500s because e2e_stream ran 460s and the cluster sections behind it
# starved into the parent's SIGKILL — rc=-9 and an "error" instead of a
# JSON with whatever had completed.  Each section now runs under its own
# deadline; a section that would bust the remaining child budget is
# skipped upfront and recorded as {"skipped": "section_timeout"}.
SECTION_CAPS = {
    "cpu_baseline": 180, "inhbm": 300, "alt_geometries": 180,
    "multi_decode": 240, "batched_needles": 120, "rebuild": 180,
    "transfer": 90, "e2e_stream": 600, "e2e_rebuild": 300,
    "e2e_decode_8gb": 420, "roofline": 90, "cluster": 360,
    "cluster_traced": 300, "alerts": 420, "coordinator": 420,
    "cluster_native": 360, "cluster_scaled": 420, "parity": 120,
    "integrity": 120, "scenarios": 300, "capacity": 420,
    "heat": 420, "pipeline_health": 15, "multichip_encode": 420,
    "master_failover": 180, "resource_ledger": 420, "autoscale": 420,
}
SECTION_CAP_DEFAULT = 300
SECTION_MIN_S = 15          # least useful remaining budget to even start

# bumped whenever the emitted JSON's keys change shape incompatibly;
# tools/bench_diff.py refuses to compare documents across versions
# instead of misreporting a schema change as a perf regression
BENCH_SCHEMA_VERSION = 2


def _join_bounded(th, cap: float, remaining, grace: float = 8.0) -> bool:
    """Join `th` for at most `cap` seconds, waking each second to check
    the shared child budget — True when the thread finished, False when
    it was abandoned (cap hit, or the budget within `grace` seconds).
    A single th.join(cap) could sleep straight through the CHILD budget
    when the cap was carved from a nearly-spent budget — the parent then
    SIGKILLs mid-join and the JSON (with every completed section) is
    lost.  Waking each second lets an overrun be abandoned ~grace
    seconds before the budget line, early enough to checkpoint and
    print BENCH_CHILD_RESULT."""
    t0 = time.perf_counter()
    while th.is_alive():
        elapsed = time.perf_counter() - t0
        if elapsed >= cap or remaining() <= grace:
            break
        th.join(min(1.0, cap - elapsed))
    return not th.is_alive()


def _git_revision() -> str:
    """Short git revision of the tree this bench ran from (stamped into
    the JSON so bench_diff can name what it compared); empty when git
    is unavailable."""
    try:
        p = subprocess.run(
            ["git", "-C", os.path.dirname(os.path.abspath(__file__)),
             "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10)
        return p.stdout.strip() if p.returncode == 0 else ""
    except Exception:
        return ""


# --------------------------------------------------------------------------
# shared e2e helpers (module-level so the trace smoke test can import them;
# all heavy imports stay inside the functions — the parent process must
# remain stdlib-only at import time)
# --------------------------------------------------------------------------

def _write_big_random(path: str, size_mb: int) -> None:
    """size_mb of data from one tiled 256MB random chunk: rng byte
    generation runs ~70 MB/s on this class of box and would dominate
    the section; GF timing is data-independent and every stripe
    still differs (offsets shift per row)."""
    import numpy as np

    rng = np.random.default_rng(0xBE)
    chunk = rng.integers(0, 256, min(size_mb, 256) << 20,
                         dtype=np.uint8).tobytes()
    with open(path, "wb") as f:
        left = size_mb << 20
        while left > 0:
            n = min(left, len(chunk))
            f.write(chunk[:n])
            left -= n


def _span_summary(tracer, max_dispatches: int = 48) -> dict:
    """Per-dispatch stage breakdown from the tracer's pipeline.*/worker.*
    spans — the attributable timeline behind overlap_efficiency."""
    stage_totals: dict = {}
    per: dict = {}
    n_spans = 0
    for sp in tracer.snapshot():
        parts = sp.name.split(".", 1)
        if parts[0] not in ("pipeline", "worker") or len(parts) != 2:
            continue
        if parts[1] in ("encode_file", "rebuild_files"):
            continue  # root spans measure the wall, not a stage
        # worker.* spans keep their namespace: they run CONCURRENTLY with
        # the pipeline stages, so folding them into the same 'compute'
        # bucket would let per-dispatch sums exceed wall_s and misread
        # overlapped compute as a serial stage
        stage = parts[1] if parts[0] == "pipeline" else sp.name
        n_spans += 1
        dur = sp.t1 - sp.t0
        stage_totals[stage] = stage_totals.get(stage, 0.0) + dur
        d = sp.attrs.get("dispatch")
        if d is not None:
            row = per.setdefault(int(d), {})
            row[stage] = row.get(stage, 0.0) + dur
    dispatches = sorted(per)
    out = {
        "stage_totals_s": {k: round(v, 4)
                           for k, v in sorted(stage_totals.items())},
        "span_count": n_spans,
        "dispatches": len(dispatches),
        "per_dispatch_s": [
            {"d": d, **{k: round(v, 5) for k, v in sorted(per[d].items())}}
            for d in dispatches[:max_dispatches]],
    }
    if len(dispatches) > max_dispatches:
        out["per_dispatch_truncated"] = len(dispatches) - max_dispatches
    return out


def _attribution(tracer, stats: dict) -> dict:
    """Critical-path attribution for the rep just traced: per-stage
    seconds, the critical-path stage, and the clean-vs-degraded verdict
    (driven by this call's retry/fallback/restart deltas), computed by
    observability/analysis.py from the same span ring."""
    from seaweedfs_tpu.observability.analysis import (analyze,
                                                      attribution_summary)

    counters = {k: stats.get(k, 0)
                for k in ("retries", "fallbacks", "worker_restarts")}
    return attribution_summary(analyze(tracer, counters=counters))


def _e2e_one(base_dir, size_mb, reps=2, tracer=None, **enc_kw):
    """One e2e streaming-encode measurement -> (mbps, pipe, chrome_doc).
    With a tracer, the ring is cleared per rep and the BEST rep's span
    summary (pipe["spans"]) + attribution report (pipe["attribution"])
    + Chrome trace document are returned."""
    from seaweedfs_tpu.ec.streaming import StreamingEncoder

    with tempfile.TemporaryDirectory(dir=base_dir) as td:
        dat = os.path.join(td, "1.dat")
        _write_big_random(dat, size_mb)
        raw_len = size_mb << 20
        enc = StreamingEncoder(10, 4, tracer=tracer, **enc_kw)
        enc.encode_file(dat, os.path.join(td, "1"))  # warm compile+pages
        best_dt, stats, spans, chrome = float("inf"), None, None, None
        attribution = None
        for _ in range(reps):
            if tracer is not None:
                tracer.clear()
            t0 = time.perf_counter()
            enc.encode_file(dat, os.path.join(td, "1"))
            dt = time.perf_counter() - t0
            if dt < best_dt:
                best_dt, stats = dt, dict(enc.stats)
                if tracer is not None:
                    spans = _span_summary(tracer)
                    chrome = tracer.to_chrome()
                    attribution = _attribution(tracer, stats)
        mbps = round(raw_len / best_dt / 1e6, 1)
        pipe = {k: round(v, 3) if isinstance(v, float) else v
                for k, v in stats.items()}
        if spans is not None:
            pipe["spans"] = spans
        if attribution is not None:
            pipe["attribution"] = attribution
            # fraction of the wall the host was NOT blocked on the
            # device, from the same spans
            pipe["overlap_efficiency"] = attribution.get(
                "overlap_efficiency")
        return mbps, pipe, chrome


def trace_smoke(trace_out=None, size_mb=2, base_dir=None):
    """Tiny CPU-only traced encode — the --trace-out path in miniature,
    exercised by a fast `not slow` test.  Returns (mbps, pipe) with
    pipe["spans"] populated; writes the Chrome trace JSON to trace_out
    when given."""
    from seaweedfs_tpu.observability import Tracer

    tracer = Tracer(capacity=1 << 14)
    mbps, pipe, chrome = _e2e_one(base_dir, size_mb, reps=1, tracer=tracer,
                                  engine="host", zero_copy=False,
                                  overlap="none", dispatch_mb=1)
    if trace_out:
        with open(trace_out, "w") as f:
            json.dump(chrome, f)
    return mbps, pipe


# --------------------------------------------------------------------------
# child: the actual measurements (runs with jax importable, any backend)
# --------------------------------------------------------------------------

def _child(scratch_path: str, platform: str = "") -> None:
    import numpy as np

    if platform == "cpu":
        # pin through jax.config as well as the env var: the config
        # write holds even where something set jax_platforms earlier
        import jax as _jax

        _jax.config.update("jax_platforms", "cpu")

    detail: dict = {}

    def _dump_detail() -> str:
        # an abandoned (timed-out) section thread may still be mutating
        # detail: retry the serialize instead of dying on "dict changed
        # size during iteration" — valid JSON always beats a stack trace
        for _ in range(5):
            try:
                return json.dumps(detail)
            except RuntimeError:
                time.sleep(0.01)
        return json.dumps({k: v for k, v in list(detail.items())
                           if isinstance(v, (str, int, float, bool))
                           or v is None})

    def checkpoint():
        with open(scratch_path, "w") as f:
            f.write(_dump_detail())

    # the parent hands the child slightly less than its own subprocess
    # timeout; sections spend from this shared budget so a long early
    # section can no longer starve the rest into the parent's SIGKILL
    t_child0 = time.perf_counter()
    budget = float(os.environ.get("BENCH_CHILD_BUDGET_S", "0") or 0)

    def remaining() -> float:
        if not budget:
            return float("inf")
        return budget - (time.perf_counter() - t_child0)

    def section(name, fn):
        import threading as _threading

        cap = SECTION_CAPS.get(name, SECTION_CAP_DEFAULT)
        left = remaining()
        if left < SECTION_MIN_S:
            # would bust the global budget: record the skip, keep the
            # JSON (and every completed section) intact
            detail.setdefault("sections_skipped", {})[name] = \
                "section_timeout"
            checkpoint()
            return
        cap = min(cap, max(left - 10.0, SECTION_MIN_S))
        errs: list[str] = []

        def runner():
            try:
                fn()
            except Exception as e:  # record and continue: partial > nothing
                errs.append(f"{type(e).__name__}: {e}"[:500])

        t0 = time.perf_counter()
        th = _threading.Thread(target=runner, daemon=True,
                               name=f"bench-{name}")
        th.start()
        if not _join_bounded(th, cap, remaining):
            # the runaway thread cannot be killed — it is abandoned
            # (daemon) and later sections run beside it; the parent's
            # subprocess timeout stays the backstop
            detail[f"error_{name}"] = \
                f"section timeout after {int(time.perf_counter() - t0)}s (budget)"
            detail.setdefault("sections_skipped", {})[name] = \
                "section_timeout"
        elif errs:
            detail[f"error_{name}"] = errs[0]
        detail.setdefault("section_s", {})[name] = round(
            time.perf_counter() - t0, 1)
        checkpoint()

    import jax
    import jax.numpy as jnp

    from seaweedfs_tpu.ec.codec import CpuEngine, ReedSolomon, best_cpu_engine
    from seaweedfs_tpu.ec.gf256 import parity_rows
    from seaweedfs_tpu.ops.gf_matmul import (
        TpuEngine,
        expand_matrix_bitplanes,
        gf_matmul_pallas,
        gf_matmul_xla,
    )

    rng = np.random.default_rng(0xBE)
    detail["device"] = str(jax.devices()[0])
    detail["backend"] = jax.default_backend()
    on_tpu = detail["backend"] not in ("cpu", "gpu")
    checkpoint()

    # --- CPU baselines ----------------------------------------------------
    def time_cpu(engine, data, reps=3, d=10, p=4):
        rs = ReedSolomon(d, p, engine=engine)
        rs.encode(data[:d, :1024])  # warm tables
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            rs.encode(data[:d])
            best = min(best, time.perf_counter() - t0)
        return data[:d].nbytes / best / 1e6

    cpu_data = rng.integers(0, 256, (12, 1 << 24), dtype=np.uint8)  # 192MB

    def meas_cpu():
        simd = best_cpu_engine()
        detail["cpu_engine"] = simd.name
        detail["cpu_simd_mbps"] = round(time_cpu(simd, cpu_data), 1)
        detail["cpu_numpy_mbps"] = round(time_cpu(CpuEngine(), cpu_data, reps=1), 1)

    section("cpu_baseline", meas_cpu)

    # --- BASELINE.json tracked config: alt geometries RS(6,3) / RS(12,4) --
    def meas_alt_geometries():
        simd = best_cpu_engine()
        detail["cpu_simd_rs63_mbps"] = round(
            time_cpu(simd, cpu_data, d=6, p=3), 1)
        detail["cpu_simd_rs124_mbps"] = round(
            time_cpu(simd, cpu_data, d=12, p=4), 1)
        if on_tpu:
            for d, p, key in ((6, 3, "tpu_inhbm_rs63_mbps"),
                              (12, 4, "tpu_inhbm_rs124_mbps")):
                planes = jnp.asarray(
                    expand_matrix_bitplanes(parity_rows(d, p)))
                detail[key] = round(run_loop(
                    gf_matmul_pallas, 1 << 24, n_lo=4, n_hi=12,
                    planes=planes, d=d), 1)

    # --- BASELINE.json tracked config: worst-case multi-erasure decode ----
    def meas_multi_decode():
        """Recover 4 erased shards (2 data + 2 parity: exercises the
        decode-matrix inverse, not just a parity recompute) from the 10
        survivors of an RS(10,4) stripe.

        Decode and encode run the SAME GFNI kernel (R=4, K=10), so they
        must clock the same — BENCH_r04's 0.37x split came from memory
        placement, not compute: encode timed against one contiguous
        just-touched block while decode read 14 arrays allocated much
        earlier (remote/cold pages on a NUMA host).  Both sides now time
        against the same first-touched contiguous buffer, and the
        same-memory encode rate is reported alongside for an
        apples-to-apples ratio."""
        simd = best_cpu_engine()
        rs = ReedSolomon(10, 4, engine=simd)
        shard_b = 1 << 24  # 16MB/shard -> 160MB volume
        src = np.ascontiguousarray(cpu_data[:10, :shard_b])
        parity = rs.encode(src)
        full = [src[i] for i in range(10)] + [parity[i] for i in range(4)]
        survivor_ids = [i for i in range(14) if i not in (2, 7, 10, 13)]

        def measure():
            # ONE contiguous survivor buffer, first-touched here by the
            # bench thread right before timing — identical memory
            # discipline to the encode measurement
            surv = np.empty((10, shard_b), dtype=np.uint8)
            for row, i in enumerate(survivor_ids):
                np.copyto(surv[row], full[i])
            enc_best = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                rs.encode(surv)
                enc_best = min(enc_best, time.perf_counter() - t0)
            dec_best = float("inf")
            for _ in range(3):
                trial: list = [None] * 14
                for row, i in enumerate(survivor_ids):
                    trial[i] = surv[row]
                t0 = time.perf_counter()
                rs.reconstruct(trial)
                dec_best = min(dec_best, time.perf_counter() - t0)
            assert all(np.array_equal(trial[i], full[i])
                       for i in (2, 7, 10, 13))
            return enc_best, dec_best

        enc_best, dec_best = measure()
        if dec_best > enc_best / 0.85:
            # one guarded re-measure before reporting a kernel split that
            # the kernel can't produce (same template both directions)
            e2, d2 = measure()
            enc_best, dec_best = min(enc_best, e2), min(dec_best, d2)
        detail["multi_decode_4erasure_mbps"] = round(
            10 * shard_b / dec_best / 1e6, 1)
        detail["multi_decode_same_mem_encode_mbps"] = round(
            10 * shard_b / enc_best / 1e6, 1)
        detail["multi_decode_vs_encode"] = round(enc_best / dec_best, 3) \
            if dec_best else 0.0
        detail["multi_decode_8gb_est_s"] = round(
            dec_best * (8 << 30) / (10 * shard_b), 2)

    # --- BASELINE.json tracked config: batched small-needle encode --------
    def meas_batched_needles():
        """2M x 4KB objects scaled to this box: encode a volume of 4KB
        needles in 64-needle batches (64 x 4KB = 256KB per dispatch,
        matching the reference's 256KB IO buffers) and report needles/s;
        the contiguous whole-volume rate is the ceiling for contrast."""
        simd = best_cpu_engine()
        rs = ReedSolomon(10, 4, engine=simd)
        needle_b, batch = 4096, 64
        n_needles = (64 << 20) // needle_b  # 64MB volume -> 16k needles
        vol = np.ascontiguousarray(
            cpu_data[:10, : n_needles * needle_b // 10])
        per_dispatch = batch * needle_b // 10
        rs.encode(vol[:, :per_dispatch])  # warm
        t0 = time.perf_counter()
        for off in range(0, vol.shape[1], per_dispatch):
            rs.encode(np.ascontiguousarray(vol[:, off:off + per_dispatch]))
        dt = time.perf_counter() - t0
        detail["batched_needle_4kb_per_s"] = round(n_needles / dt, 1)
        detail["batched_needle_mbps"] = round(vol.nbytes / dt / 1e6, 1)
        detail["batched_needle_2m_est_s"] = round(
            dt * 2_000_000 / n_needles, 1)

    # invoked after the in-HBM section: the TPU branch of
    # meas_alt_geometries reuses run_loop, defined there

    # --- in-HBM sustained kernel loop ------------------------------------
    a_planes = jnp.asarray(expand_matrix_bitplanes(parity_rows(10, 4)))

    def make_loop(encode, n):
        @jax.jit
        def bench_loop(a, d):
            def body(i, acc):
                di = d ^ i.astype(jnp.uint8)
                p = encode(a, di)
                return acc + p.astype(jnp.uint32).sum()

            return jax.lax.fori_loop(0, n, body, jnp.uint32(0))

        return bench_loop

    def run_loop(encode, b, n_lo=10, n_hi=40, planes=None, d=10):
        planes = a_planes if planes is None else planes
        data = jax.device_put(rng.integers(0, 256, (d, b), dtype=np.uint8))
        data.block_until_ready()
        times = {}
        for n in (n_lo, n_hi):
            loop = make_loop(encode, n)
            jax.device_get(loop(planes, data))  # compile + warm
            best = float("inf")
            for _ in range(2):  # min-of-2: absorb scheduler noise
                t0 = time.perf_counter()
                jax.device_get(loop(planes, data))
                best = min(best, time.perf_counter() - t0)
            times[n] = best
        per_iter = (times[n_hi] - times[n_lo]) / (n_hi - n_lo)
        if per_iter <= 0:
            # noise swamped the differencing (seen on the CPU backend):
            # fall back to the raw long-loop rate, which still includes
            # the fixed launch cost and so only understates throughput
            per_iter = times[n_hi] / n_hi
        return data.nbytes / per_iter / 1e6

    # smaller resident set + fewer iters on CPU backend: the interpreter /
    # XLA:CPU path is a correctness fallback, not a perf surface
    hbm_b = (1 << 26) if on_tpu else (1 << 22)
    xla_b = (1 << 23) if on_tpu else (1 << 22)
    loop_counts = dict(n_lo=10, n_hi=40) if on_tpu else dict(n_lo=2, n_hi=6)

    def meas_hbm():
        # key names state what ran: tpu_* only when the TPU backend ran it
        if on_tpu:
            detail["tpu_inhbm_pallas_mbps"] = round(
                run_loop(gf_matmul_pallas, hbm_b, **loop_counts), 1)
            detail["tpu_inhbm_xla_mbps"] = round(
                run_loop(gf_matmul_xla, xla_b, **loop_counts), 1)
        else:
            detail["cpu_backend_xla_mbps"] = round(
                run_loop(gf_matmul_xla, xla_b, **loop_counts), 1)

    section("inhbm", meas_hbm)
    section("alt_geometries", meas_alt_geometries)
    section("multi_decode", meas_multi_decode)
    section("batched_needles", meas_batched_needles)

    # --- single-shard rebuild latency, 1GB volume -------------------------
    # shards are 100MB; decoding the missing one is a [8,80]x[80,100M]
    # bit-plane matmul over the 10 survivors
    def meas_rebuild():
        if not on_tpu:
            return
        shard_b = 100 * (1 << 20)
        dec_planes = jnp.asarray(expand_matrix_bitplanes(parity_rows(10, 1)))
        dec_mbps = run_loop(gf_matmul_pallas, shard_b, n_lo=4, n_hi=12,
                            planes=dec_planes)
        detail["rebuild_1gb_inhbm_ms"] = round(
            10 * shard_b / (dec_mbps * 1e6) * 1e3, 2)

    section("rebuild", meas_rebuild)

    # --- host<->device link bandwidth (bounds the e2e number) -------------
    def meas_transfer():
        if not on_tpu:
            return
        up = rng.integers(0, 256, (10, 8 << 20), dtype=np.uint8)  # 80MB
        a = jax.device_put(up)
        a.block_until_ready()
        t0 = time.perf_counter()
        a = jax.device_put(up)
        a.block_until_ready()
        detail["h2d_mbps"] = round(up.nbytes / (time.perf_counter() - t0) / 1e6, 1)
        # D2H measured through the same u32 packing the pipeline fetches
        # with. jax.Array caches the fetched value on first host conversion,
        # so warm-up and the timed fetch must use DISTINCT device arrays.
        w_warm, w_timed = (
            jnp.asarray(rng.integers(0, 2**32, (4, 2 << 20), dtype=np.uint32))
            for _ in range(2))
        w_timed.block_until_ready()
        np.asarray(w_warm)
        t0 = time.perf_counter()
        got = np.asarray(w_timed)
        detail["d2h_mbps"] = round(got.nbytes / (time.perf_counter() - t0) / 1e6, 1)

    section("transfer", meas_transfer)

    # --- e2e streaming file encode (overlapped pipeline) ------------------
    # run on BOTH a tmpfs and the default scratch disk: the delta
    # separates pipeline cost from storage-medium cost (round-2 verdict:
    # "nothing separates disk-bound from pipeline-overhead-bound").
    # _e2e_one / _write_big_random are module-level (shared with the
    # trace smoke path).

    def _tmpfs_free_mb() -> int:
        import shutil as _sh

        if not os.path.isdir("/dev/shm"):
            return 0
        return _sh.disk_usage("/dev/shm").free >> 20

    _alloc_rate: list = []

    def _tmpfs_alloc_mbps() -> float:
        """Fresh-page allocation rate on tmpfs (512MB probe, cached).
        Ballooned VMs grow their resident pool lazily — first-touch of
        multi-GB files can run at ~150-250 MB/s on a host that serves
        warm pages at 2-3 GB/s.  Flagship-size sections consult this so
        a slow-balloon box reports an estimate instead of timing the
        hypervisor."""
        if _alloc_rate:
            return _alloc_rate[0]
        if not os.path.isdir("/dev/shm"):
            _alloc_rate.append(0.0)
            return 0.0
        buf = bytes(1 << 20)
        path = "/dev/shm/.bench_alloc_probe"
        fd = os.open(path, os.O_CREAT | os.O_WRONLY | os.O_TRUNC)
        t0 = time.perf_counter()
        for off in range(0, 512 << 20, 1 << 20):
            os.pwrite(fd, buf, off)
        rate = 512 / (time.perf_counter() - t0)
        os.close(fd)
        os.unlink(path)
        _alloc_rate.append(round(rate, 1))
        detail["tmpfs_alloc_mbps"] = _alloc_rate[0]
        return _alloc_rate[0]

    def _io_floor(base_dir, size_mb, reps=3):
        """Zero-compute replay of the encode's exact data movement: mmap
        the input, pwrite the 10 data shards from the mapping and the 4
        parity-sized shards from a reused hot buffer.  This is the work
        ANY RS(10,4) encoder must do before computing a single parity
        byte — an independent floor, not derived from the pipeline's own
        counters (BENCH_r04's floor was, which let a faster write phase
        LOWER the reported ratio)."""
        import mmap as mmap_mod

        size_b = size_mb << 20
        shard = (size_b + 9) // 10
        hot = bytes(1 << 20)
        best = float("inf")
        with tempfile.TemporaryDirectory(dir=base_dir) as td:
            dat = os.path.join(td, "f.dat")
            _write_big_random(dat, size_mb)
            # files persist across reps (no O_TRUNC): the e2e pipeline is
            # timed warm over existing shard files, so the floor must be
            # too — both regimes overwrite live page-cache pages
            fds_all = [os.open(os.path.join(td, f"s{i}"), os.O_CREAT | os.O_WRONLY)
                       for i in range(14)]
            for _ in range(reps):
                t0 = time.perf_counter()
                with open(dat, "rb") as f, \
                        mmap_mod.mmap(f.fileno(), 0,
                                      access=mmap_mod.ACCESS_READ) as m:
                    mv = memoryview(m)
                    ch = 1 << 20
                    for i in range(10):
                        base = i * shard
                        for off in range(0, shard, ch):
                            n = min(ch, shard - off)
                            os.pwrite(fds_all[i], mv[base + off:base + off + n],
                                      off)
                    for j in range(4):
                        for off in range(0, shard, ch):
                            os.pwrite(fds_all[10 + j],
                                      hot[:min(ch, shard - off)], off)
                    mv.release()
                best = min(best, time.perf_counter() - t0)
            for fd in fds_all:
                os.close(fd)
        return best

    def meas_e2e():
        # the e2e section runs under a span tracer: the per-dispatch
        # stage breakdown (pipe["spans"]) rides the bench JSON so the
        # overlap-efficiency number comes with an attributable timeline,
        # and --trace-out persists the Chrome trace document
        from seaweedfs_tpu.observability import Tracer

        e2e_tracer = Tracer(capacity=1 << 16)
        trace_out = os.environ.get("BENCH_TRACE_OUT")
        chrome_doc = None
        t_sec0 = time.perf_counter()

        def _sec_left() -> float:
            """Budget left for THIS section: its own cap minus elapsed,
            clipped by the child's remaining global budget — the
            per-size legs consult it so an over-budget 512MB leg skips
            the 1GB leg instead of blowing the section cap."""
            cap = SECTION_CAPS.get("e2e_stream", SECTION_CAP_DEFAULT)
            return min(cap - (time.perf_counter() - t_sec0), remaining())

        def _stamp_link(pipe, mbps):
            """First-class link keys INSIDE every e2e_pipeline_* block:
            the e2e rate ceiling when only parity (r/k of bytes_in)
            crosses back over the measured d2h link, and this pipe's
            efficiency against it — comparable run-over-run without the
            side calculation ROADMAP had to quote.  The ONE place this
            ratio lives: the top-level e2e_link_* keys reuse the disk
            pipe's stamped values."""
            from seaweedfs_tpu.ec.layout import (DATA_SHARDS_COUNT,
                                                 PARITY_SHARDS_COUNT)

            d2h = detail.get("d2h_mbps")
            if not d2h:
                return
            ceiling = d2h * DATA_SHARDS_COUNT / PARITY_SHARDS_COUNT
            pipe["link_ceiling_mbps"] = round(ceiling, 1)
            pipe["e2e_link_efficiency"] = round(mbps / ceiling, 3)

        size_mb = 512 if on_tpu else 256
        shm = "/dev/shm" if os.path.isdir("/dev/shm") else None
        if shm:
            t_leg0 = time.perf_counter()
            mbps, pipe, chrome_doc = _e2e_one(shm, size_mb,
                                              tracer=e2e_tracer)
            t_leg = time.perf_counter() - t_leg0
            pipe["size_mb"] = size_mb
            _stamp_link(pipe, mbps)
            detail["e2e_file_encode_tmpfs_mbps"] = mbps
            detail["e2e_pipeline_tmpfs"] = pipe
            # pipeline efficiency vs the pure kernel number: > ~0.25 on a
            # 1-core host means the serial fill+compute+write sum is the
            # floor, not python overhead
            kern = detail.get("cpu_simd_mbps")
            if kern and not on_tpu:
                detail["e2e_tmpfs_vs_kernel"] = round(mbps / kern, 3)
            # independent single-core I/O floor (see _io_floor).  On one
            # core the kernel time is ADDITIVE on top (nothing to overlap
            # with), so floor+kernel is the honest wall minimum —
            # e2e_vs_floor_plus_kernel near 1.0 means the pipeline adds
            # ~nothing beyond irreducible I/O + compute
            floor_s = _io_floor(shm, size_mb)
            floor_mbps = round(size_mb * (1 << 20) / floor_s / 1e6, 1)
            detail["e2e_write_floor_mbps"] = floor_mbps
            detail["e2e_vs_write_floor"] = round(mbps / floor_mbps, 3)
            if kern:
                kern_s = size_mb * (1 << 20) / (kern * 1e6)
                fpk = round(size_mb * (1 << 20) / (floor_s + kern_s) / 1e6, 1)
                detail["e2e_floor_plus_kernel_mbps"] = fpk
                detail["e2e_vs_floor_plus_kernel"] = round(mbps / fpk, 3)
            # BASELINE tracked config: the REAL 1GB encode when the box
            # has tmpfs room (1GB .dat + 1.4GB shards, one timed rep).
            # The leg is gated on the SECTION budget: a 512MB leg that
            # already ate the cap records a skip marker instead of
            # letting the 1GB run bust it (BENCH_r05's 460s e2e_stream)
            if size_mb < 1024 and _tmpfs_free_mb() > 4096 \
                    and _tmpfs_alloc_mbps() > 400:
                # 2x the bytes, warm + 1 rep vs warm + 2 reps, plus the
                # 1GB rng file write: ~1.5x the 512MB leg + slack
                est_1g = 1.5 * t_leg + 30.0
                if est_1g > _sec_left() - 10.0:
                    detail.setdefault("sections_skipped", {})[
                        "e2e_stream_1gb"] = "section_timeout"
                else:
                    mbps_1g, pipe_1g, _ = _e2e_one(shm, 1024, reps=1,
                                                   tracer=e2e_tracer)
                    pipe_1g["size_mb"] = 1024
                    _stamp_link(pipe_1g, mbps_1g)
                    detail["e2e_file_encode_1gb_mbps"] = mbps_1g
                    detail["e2e_pipeline_1gb"] = pipe_1g
            if not on_tpu:
                # the overlap-worker claim, MEASURED (round-3 verdict):
                # staged pipeline with no worker vs with the process
                # worker over shared memory (ec/overlap.py) — same
                # mechanism a multicore host would use via threads.  On
                # 1 core the processes timeslice, so ~1.0x is the honest
                # expectation; >1.1x only appears with a second core.
                from seaweedfs_tpu.ec.streaming import default_drain_pool

                ov_mb = min(size_mb, 128)
                off_mbps, _, _ = _e2e_one(shm, ov_mb, reps=1,
                                          zero_copy=False, overlap="none")
                on_mbps, on_pipe, _ = _e2e_one(shm, ov_mb, reps=1,
                                               overlap="process")
                detail["overlap_worker"] = {
                    "pipeline_off_mbps": off_mbps,
                    "pipeline_process_mbps": on_mbps,
                    "speedup": round(on_mbps / off_mbps, 3),
                    "cores": os.cpu_count() or 1,
                    # drainer fetch-pool sizing: derived from
                    # os.cpu_count() (bounded), not a hard-coded 1 —
                    # the worker-backed run reports the pool it
                    # actually drained with
                    "drain_pool": on_pipe.get("drain_pool",
                                              default_drain_pool()),
                }
        disk_mb = size_mb if on_tpu else 32
        # when there is no tmpfs the disk run is the traced one
        mbps, pipe, disk_chrome = _e2e_one(
            None, disk_mb, tracer=None if shm else e2e_tracer)
        chrome_doc = chrome_doc or disk_chrome
        pipe["size_mb"] = disk_mb
        _stamp_link(pipe, mbps)
        detail["e2e_file_encode_mbps"] = mbps
        detail["e2e_pipeline_disk"] = pipe
        detail["e2e_file_size_mb"] = disk_mb
        if trace_out and chrome_doc is not None:
            with open(trace_out, "w") as f:
                json.dump(chrome_doc, f)
            detail["trace_out"] = trace_out
        # Where the host link is slow the e2e rate is bound by pulling
        # parity (r/k of the data) back over it; report the ceiling so the
        # pipeline's efficiency is separable from the link it ran over.
        # On a co-located host (PCIe, tens of GB/s D2H) the same pipeline
        # converges to the in-HBM rate.  Same math as every per-pipe
        # stamp: reuse the disk pipe's values (_stamp_link is the one
        # owner of the r/k ratio).
        if on_tpu and "link_ceiling_mbps" in pipe:
            detail["e2e_link_ceiling_mbps"] = pipe["link_ceiling_mbps"]
            detail["e2e_link_efficiency"] = pipe["e2e_link_efficiency"]

    def meas_e2e_profiled():
        # --profile-out: a wall-clock sampling profile of the e2e
        # section, in collapsed-stack (flamegraph.pl) format — separates
        # python overhead in the drain loop from device/kernel time.
        # try/finally: the file is written (and the 200 Hz sampler
        # stopped) even when the section dies mid-measurement
        profile_out = os.environ.get("BENCH_PROFILE_OUT")
        if not profile_out:
            return meas_e2e()
        from seaweedfs_tpu.observability import SamplingProfiler

        profiler = SamplingProfiler(hz=200).start()
        try:
            meas_e2e()
        finally:
            profiler.stop()
            with open(profile_out, "w") as f:
                f.write(profiler.collapsed())
            detail["profile_out"] = profile_out
            detail["profile_samples"] = profiler.samples

    section("e2e_stream", meas_e2e_profiled)

    # --- multichip: per-device dispatch queues across the mesh ------------
    def meas_multichip():
        """Aggregate mesh-engine throughput: whole dispatches round-robin
        across per-device queues, each queue draining through its own
        AsyncDrainer lane (ec/streaming._encode_file_mesh).  Measured at
        1/2/4/8 devices so the scaling curve (and where it flattens) is
        visible; the widest width that ran carries aggregate_mbps, the
        overlap/link-efficiency verdict and the per-device drain_profile
        attribution."""
        import jax as _jax

        from seaweedfs_tpu.ec.layout import (DATA_SHARDS_COUNT,
                                             PARITY_SHARDS_COUNT)
        from seaweedfs_tpu.ec.streaming import StreamingEncoder
        from seaweedfs_tpu.observability import Tracer

        ndev = len(_jax.devices())
        widths = [n for n in (1, 2, 4, 8) if n <= ndev]
        if not widths:
            return
        t_sec0 = time.perf_counter()

        def _sec_left() -> float:
            cap = SECTION_CAPS.get("multichip_encode", SECTION_CAP_DEFAULT)
            return min(cap - (time.perf_counter() - t_sec0), remaining())

        size_mb = 512 if on_tpu else 96
        shm = "/dev/shm" if os.path.isdir("/dev/shm") else None
        mc: dict = {"devices_available": ndev, "size_mb": size_mb,
                    "per_width": {}}
        detail["multichip_encode"] = mc
        mc_tracer = Tracer(capacity=1 << 16)
        with tempfile.TemporaryDirectory(dir=shm) as td:
            dat = os.path.join(td, "1.dat")
            _write_big_random(dat, size_mb)
            raw_len = size_mb << 20
            base_mbps = None
            for n in widths:
                # a leg is warm + one timed rep; don't start one the
                # section budget can't finish
                if _sec_left() < 45.0:
                    detail.setdefault("sections_skipped", {})[
                        f"multichip_encode_{n}dev"] = "section_timeout"
                    continue
                enc = StreamingEncoder(10, 4, engine="mesh",
                                       devices=str(n), tracer=mc_tracer)
                out = os.path.join(td, f"m{n}")
                enc.encode_file(dat, out)          # warm compile + pages
                mc_tracer.clear()
                t0 = time.perf_counter()
                enc.encode_file(dat, out)
                dt = time.perf_counter() - t0
                stats = dict(enc.stats)
                mbps = round(raw_len / dt / 1e6, 1)
                attribution = _attribution(mc_tracer, stats)
                overlap = attribution.get("overlap_efficiency")
                entry = {"encode_mbps": mbps,
                         "dispatches": stats.get("dispatches"),
                         "overlap_efficiency": overlap}
                if base_mbps is None:
                    base_mbps = mbps
                else:
                    entry["scaling_vs_1dev"] = round(mbps / base_mbps, 3)
                mc["per_width"][str(n)] = entry
                # the widest width that actually ran carries the headline
                # keys bench_diff floors
                mc["devices"] = n
                mc["aggregate_mbps"] = mbps
                mc["overlap_efficiency"] = overlap
                d2h = detail.get("d2h_mbps")
                if d2h:
                    # same ceiling as _stamp_link: only parity (r/k of
                    # bytes_in) crosses back over the measured d2h link
                    ceiling = d2h * DATA_SHARDS_COUNT / PARITY_SHARDS_COUNT
                    mc["link_ceiling_mbps"] = round(ceiling, 1)
                    mc["e2e_link_efficiency"] = round(mbps / ceiling, 3)
                mc["attribution"] = attribution
                mc["per_device"] = stats.get("per_device")

    section("multichip_encode", meas_multichip)

    # --- e2e rebuild latency (streaming, from files) ----------------------
    def meas_e2e_rebuild():
        from seaweedfs_tpu.ec.streaming import StreamingEncoder

        # the BASELINE tracked config is a REAL 1GB volume (1.4GB of
        # shards + the .dat = ~2.5GB of tmpfs); measure it whenever the
        # box has room and keep the scaled 256MB run as the cross-check
        shm = "/dev/shm" if os.path.isdir("/dev/shm") else None
        vol_mb = 1024 if (on_tpu or (_tmpfs_free_mb() > 4096
                                     and _tmpfs_alloc_mbps() > 400)) else 256
        with tempfile.TemporaryDirectory(dir=shm) as td:
            dat = os.path.join(td, "1.dat")
            _write_big_random(dat, vol_mb)
            enc = StreamingEncoder(10, 4)
            enc.encode_file(dat, os.path.join(td, "1"))
            shard0 = os.path.join(td, "1.ec00")
            os.remove(shard0)
            enc.rebuild_files(os.path.join(td, "1"))  # warm
            os.remove(shard0)
            t0 = time.perf_counter()
            enc.rebuild_files(os.path.join(td, "1"))
            dt = time.perf_counter() - t0
        detail["e2e_rebuild_volume_mb"] = vol_mb
        detail["e2e_rebuild_ms"] = round(dt * 1e3, 1)
        if vol_mb == 1024:
            detail["e2e_rebuild_1gb_ms"] = round(dt * 1e3, 1)
        else:
            detail["e2e_rebuild_1gb_est_ms"] = round(
                dt * 1e3 * 1024 / vol_mb, 1)

    section("e2e_rebuild", meas_e2e_rebuild)

    # --- BASELINE tracked config: 4-erasure decode on an 8GB volume ------
    def meas_e2e_decode_8gb():
        """The flagship decode size, measured for REAL when tmpfs has
        ~12GB to spare (8GB .dat is deleted before the timed rebuild;
        peak is ~11.2GB of shards): erase 2 data + 2 parity shards of an
        8GB RS(10,4) volume and reconstruct all four in one fused pass."""
        from seaweedfs_tpu.ec.layout import to_ext
        from seaweedfs_tpu.ec.streaming import StreamingEncoder

        # the 2GB CPU-fallback shape peaks at ~7GB of tmpfs (probe +
        # .dat + shards); the 8GB flagship needs ~24GB but only runs
        # on_tpu (gated below), so don't let its requirement block the
        # 2GB real measurement
        if _tmpfs_free_mb() < 8 << 10 or _tmpfs_alloc_mbps() < 300:
            # the microbench multi_decode_8gb_est_s stays the estimate;
            # a slow-balloon box would time the hypervisor's page
            # allocator, not the decode (see _tmpfs_alloc_mbps)
            detail["multi_decode_file_skipped"] = (
                f"tmpfs {_tmpfs_free_mb()}MB free, "
                f"alloc {_tmpfs_alloc_mbps()} MB/s")
            return
        with tempfile.TemporaryDirectory(dir="/dev/shm") as td:
            # the full 8GB config needs ~20GB of pool; a ballooned VM
            # grows its resident set lazily, so the 512MB probe can pass
            # while multi-GB growth still crawls at the hypervisor's
            # page-supply rate.  Probe AT SIZE with 2GB of throwaway
            # growth (it doubles as warm-up): a genuinely fast box runs
            # the flagship 8GB; a slow-balloon box measures the same
            # file-level decode at 2GB for real and keeps the microbench
            # 8GB estimate.
            probe = os.path.join(td, "grow")
            t0 = time.perf_counter()
            _write_big_random(probe, 2 << 10)
            grow_mbps = (2 << 10) / (time.perf_counter() - t0)
            os.unlink(probe)
            detail["multi_decode_file_pool_mbps"] = round(grow_mbps, 1)
            # ballooned-VM CPU boxes pass a 2GB probe and still crawl at
            # 20GB (the fast window is a few GB) — the full 8GB config
            # only runs on real-TPU hosts; CPU fallbacks measure the
            # same file-level decode at 2GB for real
            vol_mb = (8 << 10) if (on_tpu and grow_mbps > 1500
                                   and _tmpfs_free_mb() > 24 << 10) \
                else (2 << 10)
            if grow_mbps < 300:
                detail["multi_decode_file_skipped"] = (
                    f"pool growth {grow_mbps:.0f} MB/s")
                return
            dat = os.path.join(td, "1.dat")
            _write_big_random(dat, vol_mb)
            enc = StreamingEncoder(10, 4)
            enc.encode_file(dat, os.path.join(td, "1"))
            os.remove(dat)  # make room: decode reads shards only
            for i in (2, 7, 10, 13):
                os.remove(os.path.join(td, "1" + to_ext(i)))
            t0 = time.perf_counter()
            rebuilt = enc.rebuild_files(os.path.join(td, "1"))
            dt = time.perf_counter() - t0
            assert sorted(rebuilt) == [2, 7, 10, 13]
        key = "multi_decode_8gb" if vol_mb == 8 << 10 else "multi_decode_2gb"
        detail[key + "_s"] = round(dt, 2)
        detail[key + "_mbps"] = round(vol_mb * (1 << 20) / dt / 1e6, 1)

    section("e2e_decode_8gb", meas_e2e_decode_8gb)

    # --- roofline: achieved vs memory-bandwidth ceiling -------------------
    # RS(10,4) encode is memory-bound: the kernel must move at least
    # (k+r)/k bytes per data byte (read k rows, write r rows).  The
    # MFU-analog for this op is achieved_bytes_moved / peak_memory_BW.
    TPU_HBM_GBPS = {  # public per-chip HBM bandwidth numbers
        "v2": 700, "v3": 900, "v4": 1228, "v5e": 819, "v5p": 2765,
        "v6e": 1640, "v6p": 7400,
    }

    def _host_mem_gbps():
        # big-array copy bandwidth (counting read+write traffic) as the
        # host roofline denominator
        a = rng.integers(0, 256, 1 << 28, dtype=np.uint8)  # 256MB
        b_ = np.empty_like(a)
        np.copyto(b_, a)  # warm
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            np.copyto(b_, a)
            best = min(best, time.perf_counter() - t0)
        return 2 * a.nbytes / best / 1e9

    def meas_roofline():
        move_ratio = (10 + 4) / 10  # bytes moved per data byte
        roof = {}
        if on_tpu:
            kind = str(jax.devices()[0].device_kind).lower()
            peak = next((v for k, v in TPU_HBM_GBPS.items() if k in kind),
                        None)
            roof["device_kind"] = kind
            roof["peak_hbm_gbps"] = peak
            ach = detail.get("tpu_inhbm_pallas_mbps") \
                or detail.get("tpu_inhbm_xla_mbps")
            if ach and peak:
                roof["achieved_moved_gbps"] = round(
                    ach * move_ratio / 1e3, 1)
                roof["hbm_fraction"] = round(
                    ach * move_ratio / 1e3 / peak, 3)
        else:
            peak = round(_host_mem_gbps(), 1)
            roof["host_copy_gbps"] = peak
            ach = detail.get("cpu_simd_mbps")
            if ach and peak:
                roof["achieved_moved_gbps"] = round(
                    ach * move_ratio / 1e3, 1)
                roof["mem_bw_fraction"] = round(
                    ach * move_ratio / 1e3 / peak, 3)
        detail["roofline"] = roof

    section("roofline", meas_roofline)

    # --- cluster write/read req/s (weed benchmark analog) ------------------
    import contextlib
    import re as _re
    import socket as _socket
    import tempfile as _tempfile

    repo_dir = os.path.dirname(os.path.abspath(__file__))
    weed_py = os.path.join(repo_dir, "weed.py")
    # server procs must never probe the TPU; prepend (not overwrite)
    # PYTHONPATH — TPU VMs often supply deps through it
    cluster_env = dict(os.environ, JAX_PLATFORMS="cpu")
    cluster_env["PYTHONPATH"] = repo_dir + (
        os.pathsep + cluster_env["PYTHONPATH"]
        if cluster_env.get("PYTHONPATH") else "")

    def _free_port():
        s = _socket.socket()
        s.bind(("127.0.0.1", 0))
        p = s.getsockname()[1]
        s.close()
        return p

    @contextlib.contextmanager
    def spawn_cluster(n_vols, extra_vol_args=(), trace_sample=None,
                      extra_master_args=(), reqlog_sample=None):
        """Master + n_vols volume servers as separate processes; yields
        (master_port, scratch_root) once an assign succeeds.
        trace_sample enables distributed tracing in every server process
        at that head-sampling rate (the -trace.sample global flag);
        reqlog_sample likewise enables the workload flight recorder
        (-reqlog.sample) in every server."""
        import urllib.request

        root = _tempfile.mkdtemp()
        mport = _free_port()
        globals_ = (["-trace.sample", str(trace_sample)]
                    if trace_sample is not None else [])
        if reqlog_sample is not None:
            globals_ += ["-reqlog.sample", str(reqlog_sample)]
        procs = [subprocess.Popen(
            [sys.executable, weed_py, *globals_, "master",
             "-port", str(mport), *extra_master_args],
            env=cluster_env, stdout=subprocess.DEVNULL,
            stderr=subprocess.DEVNULL)]
        try:
            for i in range(n_vols):
                procs.append(subprocess.Popen(
                    [sys.executable, weed_py, *globals_, "volume",
                     "-dir", os.path.join(root, f"v{i}"),
                     "-port", str(_free_port()),
                     "-mserver", f"127.0.0.1:{mport}", "-max", "16",
                     *extra_vol_args],
                    env=cluster_env, stdout=subprocess.DEVNULL,
                    stderr=subprocess.DEVNULL))
            deadline = time.time() + 30
            while time.time() < deadline:
                try:
                    with urllib.request.urlopen(
                            f"http://127.0.0.1:{mport}/dir/assign",
                            timeout=2) as r:
                        if b'"fid"' in r.read():
                            break
                except OSError:
                    time.sleep(0.2)
            else:
                raise RuntimeError("cluster did not become ready")
            yield mport, root
        finally:
            for p in procs:
                p.terminate()
            for p in procs:
                try:
                    p.wait(timeout=5)
                except subprocess.TimeoutExpired:
                    p.kill()

    def run_bench(mport, n, use_tcp):
        argv = [sys.executable, weed_py, "benchmark",
                "-master", f"127.0.0.1:{mport}",
                "-n", str(n), "-c", "16", "-size", "1024"]
        if use_tcp:
            argv.append("-useTcp")
        p = subprocess.run(argv, env=cluster_env, capture_output=True,
                           text=True, timeout=300)
        rates = {}
        for phase in ("write", "read"):
            mo = _re.search(rf"{phase}: .* = (\d+) req/s", p.stdout)
            if mo:
                rates[phase] = float(mo.group(1))
        if p.returncode != 0 or len(rates) != 2:
            # a dead server / failed client must surface as an
            # error_cluster marker, not a fake 0.0 measurement
            tail = (p.stderr or p.stdout).strip().splitlines()
            raise RuntimeError(
                f"benchmark rc={p.returncode}: "
                f"{tail[-1][:200] if tail else 'no output'}")
        return rates

    def meas_cluster():
        """Cluster microbench with REAL process separation: master and
        volume server run as their own processes and the load generator
        (`weed.py benchmark`, command/benchmark.go analog) as a third, so
        no GIL is shared between client and servers — the shape of the
        reference's README numbers (15.7k w/s, 47k r/s, 1KB files, c=16).
        On a 1-core host this measures the same as in-process; on the
        many-core TPU host it measures actual server capacity."""
        with spawn_cluster(1) as (mport, _root):
            http_rates = run_bench(mport, 4000, use_tcp=False)
            detail["cluster_write_rps"] = http_rates.get("write", 0.0)
            detail["cluster_read_rps"] = http_rates.get("read", 0.0)
            tcp_rates = run_bench(mport, 4000, use_tcp=True)
            detail["cluster_tcp_write_rps"] = tcp_rates.get("write", 0.0)
            detail["cluster_tcp_read_rps"] = tcp_rates.get("read", 0.0)

    section("cluster", meas_cluster)

    # --- distributed tracing: sampling cost + one stitched trace ----------
    def meas_cluster_traced():
        """Same single-server shape with distributed tracing ON at 1%
        head sampling (PR 6): (a) HTTP read rps against the untraced
        cluster section — the acceptance bar is < 3% regression — and
        (b) one force-sampled cross-server write whose stitched trace is
        fetched back from the master's collector and attributed
        (bounding hop, network-vs-server split), embedded as proof the
        pipeline works end to end in real multi-process clusters."""
        import urllib.request

        with spawn_cluster(1, trace_sample="0.01") as (mport, _root):
            rates = run_bench(mport, 4000, use_tcp=False)
            detail["cluster_traced_write_rps"] = rates.get("write", 0.0)
            detail["cluster_traced_read_rps"] = rates.get("read", 0.0)
            base = detail.get("cluster_read_rps") or 0.0
            if base:
                detail["trace_sampling_read_overhead_pct"] = round(
                    100.0 * (1.0 - rates.get("read", 0.0) / base), 2)

            # one forced-sample distributed write: master /submit fans
            # out assign + volume upload, so the stitched trace crosses
            # processes; poll the collector for it (shippers flush on a
            # short interval)
            req = urllib.request.Request(
                f"http://127.0.0.1:{mport}/submit",
                data=b"trace-me" * 128, method="POST",
                headers={"X-Force-Trace": "1",
                         "Content-Type": "application/octet-stream"})
            with urllib.request.urlopen(req, timeout=30) as r:
                trace_id = r.headers.get("X-Trace-Id", "")
            block = {"trace_id": trace_id}
            deadline = time.time() + 8
            while time.time() < deadline:
                try:
                    with urllib.request.urlopen(
                            f"http://127.0.0.1:{mport}/cluster/traces/"
                            f"{trace_id}", timeout=5) as r:
                        doc = json.loads(r.read())
                except OSError:
                    doc = None
                if doc and any(
                        s["name"].startswith("http.volume.")
                        for s in doc.get("spans", [])):
                    an = doc["analysis"]
                    block.update({
                        "span_count": doc["span_count"],
                        "servers": doc["servers"],
                        "wall_s": an["wall_s"],
                        "network_s": an["network_s"],
                        "server_s": an["server_s"],
                        "bounding_hop": an["bounding_hop"],
                        "degraded": an["degraded"],
                        "summary": an["summary"],
                    })
                    break
                time.sleep(0.2)
            else:
                block["error"] = "stitched trace never reached collector"
            detail["cluster_trace"] = block

    section("cluster_traced", meas_cluster_traced)

    # --- alerting engine: evaluator overhead + forced e2e drill ------------
    def _alerts_drill():
        """In-process forced drill (the PR-9 acceptance chain): inject
        ec.shard.corrupt -> scrub detects -> counter rises -> rule
        fires autonomously -> event journaled with the scrub's trace id
        -> flight-recorder bundle captured.  Returns what each link of
        the chain produced so the bench JSON PROVES the pipeline, not
        just that code exists."""
        import tempfile as _tf

        from seaweedfs_tpu.master.server import MasterServer
        from seaweedfs_tpu.observability import (disable_tracing,
                                                 enable_tracing)
        from seaweedfs_tpu.storage.needle import Needle
        from seaweedfs_tpu.storage.volume import Volume
        from seaweedfs_tpu.utils import faultinject as fi
        from seaweedfs_tpu.utils.httpd import http_json
        from seaweedfs_tpu.volume_server.server import VolumeServer

        out = {"alert_fired": False, "event_trace": "", "bundle_id": "",
               "bundle_has_trace": False, "bundle_has_metrics": False}
        root = _tf.mkdtemp()
        v = Volume(root, "", 1)
        data = rng.integers(0, 256, 500, dtype=np.uint8).tobytes()
        for i in range(1, 60):
            v.write_needle(Needle(cookie=i, id=i, data=data))
        v.close()
        enable_tracing()
        master = MasterServer(port=_free_port(), pulse_seconds=0.4,
                              metrics_aggregation_seconds=0.25).start()
        master.aggregator.min_interval = 0.0
        master.alert_engine.min_interval = 0.0
        vs = VolumeServer([root], master.url, port=_free_port(),
                          pulse_seconds=0.4).start()
        try:
            deadline = time.time() + 5
            while time.time() < deadline and not master.topo.all_nodes():
                time.sleep(0.05)
            vs.store.ec_generate(1)
            vs.store.ec_mount(1)
            deadline = time.time() + 5
            while time.time() < deadline and \
                    not master.alert_engine.evaluations:
                time.sleep(0.05)
            fi.enable("ec.shard.corrupt",
                      params={"shard": 11, "offset": 4096, "bit": 0},
                      max_hits=1)
            http_json("POST", f"http://{vs.url}/ec/scrub/start",
                      {"rate_mb_s": 0})
            deadline = time.time() + 30
            while time.time() < deadline:
                alerts = {a["name"]: a for a in
                          master.alert_engine.to_dict()["alerts"]}
                a = alerts.get("corrupt_shards_increase") or {}
                if a.get("state") == "firing":
                    out["alert_fired"] = True
                    bundles = [b for b in a.get("bundles", [])
                               if b.get("id")]
                    if bundles and out["event_trace"]:
                        out["bundle_id"] = bundles[0]["id"]
                        bdoc = http_json(
                            "GET", f"http://{bundles[0]['server']}"
                            f"/debug/flightrecorder/{bundles[0]['id']}")
                        out["bundle_has_trace"] = bool(
                            bdoc.get("trace", {}).get("spans"))
                        out["bundle_has_metrics"] = \
                            "SeaweedFS" in bdoc.get("metrics", "")
                        break
                if not out["event_trace"]:
                    evs = http_json(
                        "GET", f"http://{master.url}/cluster/events"
                               "?type=shard_corrupt&limit=5")
                    if evs["events"]:
                        out["event_trace"] = \
                            evs["events"][-1].get("trace", "")
                time.sleep(0.2)
        finally:
            fi.clear()
            vs.stop()
            master.stop()
            disable_tracing()
        return out

    def meas_alerts():
        """Read rps with the alert evaluator LIVE on the master
        (-metricsAggregationSeconds 1: scrape + rule evaluation every
        second while the bench hammers reads) — acceptance: < 1%
        overhead, because evaluation runs on the master's aggregation
        loop and the volume-server hot path pays nothing.  The
        evaluator-OFF baseline is measured back-to-back in THIS section
        (a fresh spawn each, seconds apart) — comparing against the
        cluster section minutes earlier would put the acceptance figure
        below run-to-run spawn/cache noise.  Plus the forced
        end-to-end drill."""
        import urllib.request

        with spawn_cluster(1) as (mport, _root):
            base_rates = run_bench(mport, 4000, use_tcp=False)
        block = {"baseline_read_rps": base_rates.get("read", 0.0)}
        with spawn_cluster(
                1, extra_master_args=("-metricsAggregationSeconds",
                                      "1")) as (mport, _root):
            rates = run_bench(mport, 4000, use_tcp=False)
            block.update({"write_rps": rates.get("write", 0.0),
                          "read_rps": rates.get("read", 0.0)})
            base = block["baseline_read_rps"]
            if base:
                block["eval_read_overhead_pct"] = round(
                    100.0 * (1.0 - rates.get("read", 0.0) / base), 2)
            # the evaluator really ran during the load: rules present
            # and evaluations advancing
            try:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{mport}/cluster/alerts",
                        timeout=5) as r:
                    doc = json.loads(r.read())
                block["rules"] = len(doc.get("rules", []))
                block["evaluations"] = doc.get("evaluations", 0)
                block["firing"] = doc.get("firing", 0)
            except OSError:
                block["error_alerts_endpoint"] = "unreachable"
        block["drill"] = _alerts_drill()
        detail["alerts"] = block

    section("alerts", meas_alerts)

    # --- rebuild/rebalance coordinator: MTTR + convergence + idle cost -----
    def _coordinator_drill(size_mb=64):
        """The acceptance chain with a clock on it: inject
        ec.shard.corrupt on a 64MB EC volume spread over three racks ->
        the scrubber quarantines (locally unrepairable) -> the alert
        fires -> the ENABLED coordinator repairs cross-server with no
        manual intervention.  mttr_s = injection to the registry
        showing 14 clean shards again.  Then a fresh server joins a
        fourth rack and the continuous rebalance pass runs to
        convergence (rebalance_moves, skew before/after)."""
        import tempfile as _tf

        from seaweedfs_tpu.master.server import MasterServer
        from seaweedfs_tpu.observability import (disable_tracing,
                                                 enable_tracing)
        from seaweedfs_tpu.storage.needle import Needle
        from seaweedfs_tpu.storage.volume import Volume
        from seaweedfs_tpu.utils import faultinject as fi
        from seaweedfs_tpu.utils.httpd import http_json
        from seaweedfs_tpu.volume_server.server import VolumeServer

        out = {"alert_fired": False, "mttr_s": None,
               "rebalance_moves": None}
        roots = [_tf.mkdtemp() for _ in range(4)]
        v = Volume(roots[0], "", 1)
        chunk = rng.integers(0, 256, 1 << 20, dtype=np.uint8).tobytes()
        for i in range(1, size_mb + 1):
            v.write_needle(Needle(cookie=i, id=i, data=chunk))
        v.close()
        enable_tracing()
        master = MasterServer(port=_free_port(), pulse_seconds=0.3,
                              metrics_aggregation_seconds=0.25,
                              coordinator_seconds=0.3).start()
        master.aggregator.min_interval = 0.0
        master.alert_engine.min_interval = 0.0
        master.coordinator.pause("setup")
        master.coordinator.move_rate = 100.0
        servers = [VolumeServer([roots[i]], master.url,
                                port=_free_port(), rack=f"r{i}",
                                data_center="dc1",
                                pulse_seconds=0.3).start()
                   for i in range(3)]

        def registry():
            with master.topo.lock:
                locs = master.topo.ec_shard_locations.get(1, {})
                return {sid: [n.url for n in ns]
                        for sid, ns in locs.items() if ns}

        def wait_for(cond, timeout):
            deadline = time.time() + timeout
            while time.time() < deadline:
                if cond():
                    return True
                time.sleep(0.1)
            return False

        try:
            wait_for(lambda: len(master.topo.all_nodes()) == 3, 10)
            servers[0].store.ec_generate(1)
            servers[0].store.ec_mount(1)
            # spread 14 shards over the three racks
            layout = {1: [5, 6, 7, 8, 9], 2: [10, 11, 12, 13]}
            for i, sids in layout.items():
                http_json("POST",
                          f"http://{servers[i].url}/admin/ec/copy",
                          {"volume_id": 1, "shard_ids": sids,
                           "source_data_node": servers[0].url},
                          timeout=600)
                http_json("POST",
                          f"http://{servers[i].url}/admin/ec/mount",
                          {"volume_id": 1})
            http_json("POST",
                      f"http://{servers[0].url}/admin/ec/delete",
                      {"volume_id": 1,
                       "shard_ids": [s for ss in layout.values()
                                     for s in ss]})
            http_json("POST",
                      f"http://{servers[0].url}/admin/ec/mount",
                      {"volume_id": 1})
            http_json("POST",
                      f"http://{servers[0].url}/admin/delete_volume",
                      {"volume_id": 1})
            for vs in servers:
                vs.heartbeat_now()
            wait_for(lambda: len(registry()) == 14, 10)
            wait_for(lambda: master.alert_engine.evaluations > 0, 10)
            master.coordinator.resume()

            # inject: shard 7 rots on rack r1 — the clock starts HERE
            fi.enable("ec.shard.corrupt",
                      params={"shard": 7, "offset": 4096, "bit": 0},
                      max_hits=1)
            t0 = time.perf_counter()
            http_json("POST",
                      f"http://{servers[1].url}/ec/scrub/start",
                      {"rate_mb_s": 0, "interval_s": 0})
            # detection first: the quarantined shard leaves the
            # registry (a full registry BEFORE detection must not read
            # as already-healed)
            detected = wait_for(lambda: 7 not in registry(), 60)
            fi.clear()
            healed = detected and wait_for(
                lambda: set(registry()) == set(range(14)), 120)
            if healed:
                out["mttr_s"] = round(time.perf_counter() - t0, 2)
            else:
                out["error"] = ("corruption never detected"
                                if not detected
                                else "repair never converged")
            firing = {a["name"] for a in
                      master.alert_engine.to_dict()["alerts"]
                      if a["state"] == "firing"}
            out["alert_fired"] = bool(
                firing & {"corrupt_shards_increase",
                          "scrub_unrepairable",
                          "ec_under_replicated_increase"})
            # the repair_done event rides the shipper's flush cadence
            wait_for(lambda: master.event_journal.query(
                type_="repair_done", limit=5), 10)
            done = master.event_journal.query(type_="repair_done",
                                              limit=5)
            if done:
                out["repair_alert"] = done[-1]["details"].get(
                    "alert", "")
                out["repair_trace"] = done[-1].get("trace", "")

            # rebalance convergence: a fresh server joins rack r3
            def skew():
                counts = {}
                for sid, urls in registry().items():
                    for u in urls:
                        counts[u] = counts.get(u, 0) + 1
                for vs in servers:
                    counts.setdefault(vs.url, 0)
                return max(counts.values()) - min(counts.values())

            out["rebalance_skew_before"] = skew()
            moves0 = master.coordinator.status()["moves"]
            servers.append(VolumeServer(
                [roots[3]], master.url, port=_free_port(), rack="r3",
                data_center="dc1", pulse_seconds=0.3).start())
            wait_for(lambda:
                     master.coordinator.status()["moves"] > moves0, 30)

            def settled():
                a = master.coordinator.status()["moves"]
                time.sleep(1.0)
                return a == master.coordinator.status()["moves"]

            wait_for(settled, 60)
            out["rebalance_moves"] = \
                master.coordinator.status()["moves"] - moves0
            out["rebalance_skew_after"] = skew()
            out["repairs"] = master.coordinator.status()["repairs"]
        finally:
            fi.clear()
            for vs in servers:
                vs.stop()
            master.stop()
            disable_tracing()
        return out

    def meas_coordinator():
        """Idle-cost acceptance first: read rps with the coordinator +
        evaluator BOTH live on the master vs a back-to-back plain
        baseline (< 1% overhead — the coordinator plans on the master's
        cadence; the volume-server hot path pays nothing).  Then the
        in-process MTTR + rebalance drill."""
        with spawn_cluster(1) as (mport, _root):
            base_rates = run_bench(mport, 4000, use_tcp=False)
        block = {"baseline_read_rps": base_rates.get("read", 0.0)}
        with spawn_cluster(
                1, extra_master_args=(
                    "-metricsAggregationSeconds", "1",
                    "-coordinatorSeconds", "1")) as (mport, _root):
            rates = run_bench(mport, 4000, use_tcp=False)
            block.update({"read_rps": rates.get("read", 0.0),
                          "write_rps": rates.get("write", 0.0)})
            base = block["baseline_read_rps"]
            if base:
                block["idle_overhead_pct"] = round(
                    100.0 * (1.0 - rates.get("read", 0.0) / base), 2)
            import urllib.request

            try:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{mport}/cluster/coordinator",
                        timeout=5) as r:
                    doc = json.loads(r.read())
                block["cycles"] = doc.get("cycles", 0)
                block["enabled"] = doc.get("enabled", False)
            except OSError:
                block["error_coordinator_endpoint"] = "unreachable"
        drill = _coordinator_drill()
        block["mttr_s"] = drill.pop("mttr_s", None)
        block["rebalance_moves"] = drill.pop("rebalance_moves", None)
        block["drill"] = drill
        detail["coordinator"] = block

    section("coordinator", meas_coordinator)

    # --- native C++ data plane (GIL-free needle IO) -------------------------
    def meas_cluster_native():
        """Same single-server shape, with the volume server's needle IO
        served by the C++ data plane (native/dataplane.cpp) — the
        rebuild's production fast path for the reference's -useTcp
        experiment."""
        from seaweedfs_tpu.volume_server.dataplane import load_dataplane

        if load_dataplane() is None:
            detail["cluster_native_skipped"] = "no C++ toolchain"
            return
        with spawn_cluster(1, ("-dataplane", "native")) as (mport, _root):
            rates = run_bench(mport, 4000, use_tcp=True)
            detail["cluster_native_tcp_write_rps"] = rates.get("write", 0.0)
            detail["cluster_native_tcp_read_rps"] = rates.get("read", 0.0)

    section("cluster_native", meas_cluster_native)

    # --- production-shaped scenario suite (seaweedfs_tpu/scenarios) --------
    def meas_scenarios():
        """The failure-under-load proof (ROADMAP item 4): three
        declarative scenarios — Zipfian hot-set read storm, mixed-size
        write+churn+vacuum, and a rack-loss-shaped failure-under-load
        drill — run against in-process clusters with the deadline
        plane, admission control, retry budgets, and the alert engine
        ALL live.  Each result embeds per-route RED stats, per-phase
        p99s, shed/deadline/retry counters, the fault + alert
        timelines, one stitched trace, and a verdicted checks list;
        the failure scenario's checks ARE the acceptance criteria
        (healthy-fraction rps >= 60% of baseline under the fault,
        accepted p99 < 5x healthy, zero deadline overruns > 250ms,
        burn-rate alert fired during the fault and resolved after)."""
        from seaweedfs_tpu.scenarios import default_scenarios, run_scenario

        block: dict = {}
        for spec in default_scenarios():
            try:
                block[spec.name] = run_scenario(spec)
            except Exception as e:  # one broken scenario must not
                block[spec.name] = {  # hide the others' verdicts
                    "error": f"{type(e).__name__}: {e}"[:300],
                    "verdict": "error"}
        block["degraded"] = any(
            s.get("verdict") != "pass" for s in block.values()
            if isinstance(s, dict))
        detail["scenarios"] = block

    section("scenarios", meas_scenarios)

    # --- master HA: leader-failover drill (scenarios/failover.py) ----------
    def meas_master_failover():
        """The control-plane HA proof (master/consensus.py raft log):
        a 3-master quorum under a write storm loses its leader mid EC
        repair.  The drill measures election time, /dir/assign
        recovery latency on the new leader, pre-kill journaled-event
        loss across the failover (the raft contract demands exactly
        zero), and how long the new leader takes to re-plan the
        orphaned repair with its original alert/trace cause
        attribution.  bench_diff floors journal_loss_count at zero and
        watches the two latencies."""
        from seaweedfs_tpu.scenarios import master_failover, run_failover

        try:
            res = run_failover(master_failover())
        except Exception as e:
            detail["master_failover"] = {
                "error": f"{type(e).__name__}: {e}"[:300],
                "verdict": "error"}
            return
        detail["master_failover"] = {
            "election_time_s": res.get("election_time_s"),
            "assign_after_kill_s": res.get("assign_after_kill_s"),
            "journal_loss_count": res.get("journal_loss_count"),
            "pre_kill_events": res.get("pre_kill_events"),
            "repair_replan_s": res.get("repair_replan_s"),
            "repair_attribution": res.get("repair_attribution"),
            "total_ops": res.get("total_ops"),
            "checks": res.get("checks"),
            "verdict": res.get("verdict"),
        }

    section("master_failover", meas_master_failover)

    # --- workload recorder overhead + SLO capacity probe -------------------
    def meas_capacity():
        """The workload flight-deck numbers (ISSUE 14 acceptance):
        (a) recorder overhead — read rps with -reqlog.sample 1.0
        (every request recorded: the worst case) against a
        recorder-OFF baseline spawned back-to-back in THIS section
        (the PR-9 alerts-section methodology: a minutes-old baseline
        sits below spawn noise) — acceptance < 1%; (b) proof the
        recording pipeline ran end to end (records reached the
        master's /cluster/workload and spec_from_recording fits them);
        (c) the SLO capacity probe: binary-searched max sustainable
        rps for http_read / native_read / http_write under p99 < 5ms
        and error ratio < 0.1%, with knee point and bounding-resource
        attribution from a forced stitched trace — the dataplane
        refactor's acceptance baseline."""
        import urllib.request

        from seaweedfs_tpu.scenarios.capacity import (CapacitySLO,
                                                      probe_cluster)
        from seaweedfs_tpu.scenarios.replay import (recording_profile,
                                                    spec_from_recording)

        block: dict = {}
        with spawn_cluster(1) as (mport, _root):
            base_rates = run_bench(mport, 4000, use_tcp=False)
        block["baseline_read_rps"] = base_rates.get("read", 0.0)
        with spawn_cluster(1, reqlog_sample="1.0") as (mport, _root):
            rates = run_bench(mport, 4000, use_tcp=False)
            block["reqlog_read_rps"] = rates.get("read", 0.0)
            base = block["baseline_read_rps"]
            if base:
                block["reqlog_read_overhead_pct"] = round(
                    100.0 * (1.0 - rates.get("read", 0.0) / base), 2)
            # the recording really flowed: shippers land on the master
            deadline = time.time() + 8
            rec = None
            while time.time() < deadline:
                try:
                    with urllib.request.urlopen(
                            f"http://127.0.0.1:{mport}"
                            "/cluster/workload/export", timeout=5) as r:
                        rec = json.loads(r.read())
                except OSError:
                    rec = None
                if rec and rec.get("summary", {}).get("records", 0) > 100:
                    break
                time.sleep(0.3)
            if rec and rec.get("records"):
                prof = recording_profile(rec)
                spec = spec_from_recording(rec, name="bench_replay")
                block["recording"] = {
                    "records": rec["summary"]["records"],
                    "read_fraction": prof["read_fraction"],
                    "zipf_s": prof["zipf_s"],
                    "sizes": [list(s) for s in prof["sizes"]],
                    "observed_rps": prof["observed_rps"],
                    "fitted_target_rps": spec.target_rps,
                }
            else:
                block["error_recording"] = \
                    "no records reached /cluster/workload"
        # the probe cluster runs with tracing on (tiny rate: the
        # forced-sample attribution trace needs a live collector) and
        # the recorder at a production-shaped 10% sample
        with spawn_cluster(1, trace_sample="0.001",
                           reqlog_sample="0.1") as (mport, _root):
            cap = probe_cluster(
                f"127.0.0.1:{mport}",
                routes=("http_read", "native_read", "http_write"),
                slo=CapacitySLO(max_p99_ms=5.0, max_error_ratio=0.001),
                start_rps=200.0, max_rps=60000.0, step_s=1.5,
                preload=64, write_size=1024)
            for route, res in cap["routes"].items():
                res.pop("samples", None)  # the curve is bulky; keep
                # the answer + knee (BASELINE tracks capacity_rps)
            block["slo"] = cap["slo"]
            block.update(cap["routes"])
            # needle-cache effectiveness under the probe's Zipf-shaped
            # read mix (volume /status NeedleCache block; bench_diff
            # watches capacity.needle_cache_hit_ratio)
            try:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{mport}/dir/status",
                        timeout=5) as r:
                    topo = json.loads(r.read())
                vs_url = topo["Topology"]["DataCenters"][0]["Racks"][0][
                    "DataNodes"][0]["Url"]
                with urllib.request.urlopen(
                        f"http://{vs_url}/status", timeout=5) as r:
                    st = json.loads(r.read())
                nc = st.get("NeedleCache") or {}
                block["needle_cache_hit_ratio"] = nc.get("hit_ratio",
                                                         0.0)
                block["needle_cache"] = {
                    k: nc.get(k) for k in ("hits", "misses",
                                           "admissions", "evictions",
                                           "bytes")}
                dp = st.get("Dataplane") or {}
                block["dataplane"] = dp
            except Exception as e:
                block["needle_cache_error"] = f"{type(e).__name__}: {e}"
        detail["capacity"] = block

    section("capacity", meas_capacity)

    # --- heat-telemetry plane: accounting cost + flash-crowd proof ---------
    def meas_heat():
        """Heat-plane acceptance (ISSUE 16): (a) accounting overhead —
        read rps with heat accounting ON (the default) against an
        accounting-off (-heat.off) baseline spawned back-to-back in
        THIS section — acceptance < 1%; (b) proof the snapshot
        pipeline flowed end to end (per-volume heat + a live Zipf fit
        reached the master's /cluster/heat); (c) space-saving sketch
        head recall vs exact counts on a seeded Zipf stream —
        bench_diff floors heat.sketch_head_recall at 0.9; (d) the
        flash-crowd drill: mid-run the Zipf head jumps to a cold
        volume and the heat_shift/flash_crowd alert must fire within
        5s naming the newly hot volume, carrying an exemplar trace."""
        import random as _random
        import urllib.request
        from collections import Counter as _Counter

        from seaweedfs_tpu.observability.heat import SpaceSavingSketch
        from seaweedfs_tpu.scenarios import (ZipfSampler, flash_crowd,
                                             run_scenario)

        block: dict = {}
        with spawn_cluster(1, ("-heat.off",)) as (mport, _root):
            base = run_bench(mport, 4000, use_tcp=False)
        block["baseline_read_rps"] = base.get("read", 0.0)
        with spawn_cluster(1) as (mport, _root):
            rates = run_bench(mport, 4000, use_tcp=False)
            block["heat_read_rps"] = rates.get("read", 0.0)
            if block["baseline_read_rps"]:
                block["accounting_overhead_pct"] = round(
                    100.0 * (1.0 - rates.get("read", 0.0)
                             / block["baseline_read_rps"]), 2)
            # the snapshots really flowed: shippers land on the master
            doc = None
            deadline = time.time() + 8
            while time.time() < deadline:
                try:
                    with urllib.request.urlopen(
                            f"http://127.0.0.1:{mport}"
                            "/cluster/heat?top=4", timeout=5) as r:
                        doc = json.loads(r.read())
                except OSError:
                    doc = None
                if doc and doc.get("volumes"):
                    break
                time.sleep(0.3)
            if doc and doc.get("volumes"):
                block["cluster_heat"] = {
                    "ingested": doc.get("ingested", 0),
                    "volumes": len(doc.get("volumes") or []),
                    "hottest": (doc["volumes"][0] or {}).get("volume"),
                    "zipf_s": (doc.get("zipf") or {}).get("s", 0.0),
                    "server_imbalance": (doc.get("imbalance")
                                         or {}).get("server", 0.0),
                }
            else:
                block["error_cluster_heat"] = \
                    "no heat snapshots reached /cluster/heat"
        # sketch head recall: a 512-entry sketch over a 20k-key Zipf
        # stream must still name >= 90% of the exact top-50
        rng = _random.Random(0x4EA7)
        z = ZipfSampler(20000, 1.2)
        sk = SpaceSavingSketch(capacity=512, half_life=3600.0)
        exact: _Counter = _Counter()
        for i in range(120000):
            key = z.sample(rng)
            exact[key] += 1
            sk.touch(str(key), now=i * 1e-5)
        now = 120000 * 1e-5
        top = {row["key"] for row in sk.top(now, k=50)}
        head = [str(k) for k, _ in exact.most_common(50)]
        block["sketch_head_recall"] = round(
            sum(1 for k in head if k in top) / len(head), 3)
        # the flash-crowd drill (scenarios/spec.flash_crowd): the
        # drill's own checks carry the acceptance verdict
        res = run_scenario(flash_crowd())
        heat = res.get("heat") or {}
        block["flash_crowd"] = {
            "verdict": res.get("verdict"),
            "checks": res.get("checks"),
            "shift_t": heat.get("shift_t"),
            "alerts_fired": heat.get("alerts_fired"),
            "alert_latency_s": heat.get("alert_latency_s"),
            "named_volume": heat.get("named_volume"),
            "exemplar_trace": heat.get("exemplar_trace"),
            "cluster": heat.get("cluster"),
        }
        detail["heat"] = block

    section("heat", meas_heat)

    # --- resource-ledger plane: accounting + profiler cost -----------------
    def meas_resource_ledger():
        """Resource-ledger acceptance (ISSUE 19): (a) accounting
        overhead — read rps with the per-request ledger AND the
        always-on windowed profiler (the defaults) against an
        accounting-off (-ledger.off) baseline spawned back-to-back in
        THIS section — acceptance < 1% (bench_diff floors
        resource_ledger.ledger_overhead_pct at 1.0); (b) proof the
        snapshot pipeline flowed end to end: per-route CPU/queue-wait
        rates, loop-lag stats and profiler windows reached the
        master's /cluster/ledger, with http_read attributed; (c) the
        serving loop stayed healthy under the bench load — bench_diff
        floors resource_ledger.loop_lag_p99_ms at 5ms."""
        import urllib.request

        block: dict = {}
        with spawn_cluster(1, ("-ledger.off",)) as (mport, _root):
            base = run_bench(mport, 4000, use_tcp=False)
        block["baseline_read_rps"] = base.get("read", 0.0)
        with spawn_cluster(1) as (mport, _root):
            rates = run_bench(mport, 4000, use_tcp=False)
            block["ledger_read_rps"] = rates.get("read", 0.0)
            if block["baseline_read_rps"]:
                block["ledger_overhead_pct"] = round(
                    100.0 * (1.0 - rates.get("read", 0.0)
                             / block["baseline_read_rps"]), 2)
            # the snapshots really flowed: every server's ledger (and
            # its loop stats + profiler windows) lands on the master
            doc = None
            deadline = time.time() + 8
            while time.time() < deadline:
                try:
                    with urllib.request.urlopen(
                            f"http://127.0.0.1:{mport}"
                            "/cluster/ledger?top=8", timeout=5) as r:
                        doc = json.loads(r.read())
                except OSError:
                    doc = None
                if doc and doc.get("routes"):
                    break
                time.sleep(0.3)
            if doc and doc.get("routes"):
                routes = {row["route"]: row for row in doc["routes"]}
                rr = routes.get("http_read") or {}
                block["cluster_ledger"] = {
                    "peers": len(doc.get("peers") or {}),
                    "routes": sorted(routes),
                    "top_route": doc["routes"][0]["route"],
                    "http_read_cpu_share": rr.get("cpu_share", 0.0),
                    "http_read_cpu_rate": rr.get("cpu_rate", 0.0),
                    "http_read_queue_wait_rate":
                        rr.get("queue_wait_rate", 0.0),
                    "total_cpu_rate":
                        (doc.get("totals") or {}).get("cpu_rate", 0.0),
                    "profiled_servers":
                        len(doc.get("profiles") or {}),
                }
                block["loop_lag_p99_ms"] = max(
                    (s.get("loop_lag_p99_ms", 0.0)
                     for s in doc.get("servers") or []), default=0.0)
                block["loop_stalls"] = sum(
                    s.get("stalls", 0)
                    for s in doc.get("servers") or [])
            else:
                block["error_cluster_ledger"] = \
                    "no ledger snapshots reached /cluster/ledger"
        detail["resource_ledger"] = block

    section("resource_ledger", meas_resource_ledger)

    # --- heat autoscaler: closed-loop grow + cold tiering ------------------
    def meas_autoscale():
        """Heat-autoscaler acceptance (ISSUE 20): (a) the closed-loop
        flash-crowd drill (scenarios/spec.flash_crowd_autoscale) with
        the autoscaler ON against the SAME drill with it OFF —
        recovery-time-to-SLO (bench_diff floors
        autoscale.recovery_to_slo_s), post-shift hot-set serving-rate
        uplift (floors autoscale.hot_rps_uplift_pct at >= 0), grow
        attribution and the <=1-cycle thrash guard, all from the
        drill's machine-checked verdict; (b) idle overhead — read rps
        with the leader loop ticking at -autoscaleSeconds 1 against a
        loop-off baseline spawned back-to-back, acceptance < 1%
        (floors autoscale.idle_overhead_pct); (c) cold tiering at the
        storage layer: median tiered READ-THROUGH latency and the
        wall-clock to RECALL a 64MB volume from the remote backend
        (stamps autoscale.tier_recall_s)."""
        import dataclasses as _dc
        import tempfile as _tf

        from seaweedfs_tpu.scenarios import (flash_crowd_autoscale,
                                             run_scenario)

        block: dict = {}
        on_spec = flash_crowd_autoscale()
        res_on = run_scenario(on_spec)
        off_exp = {k: v for k, v in on_spec.expectations.items()
                   if not k.startswith("autoscale_")}
        res_off = run_scenario(_dc.replace(
            on_spec, name="flash_crowd_autoscale_off",
            autoscale=False, expectations=off_exp))
        auto = res_on.get("autoscale") or {}
        on_rps = (res_on.get("heat") or {}).get(
            "post_shift_read_rps", 0.0)
        off_rps = (res_off.get("heat") or {}).get(
            "post_shift_read_rps", 0.0)
        block["flash_crowd_on"] = {
            "verdict": res_on.get("verdict"),
            "checks": res_on.get("checks"),
            "first_grow_after_shift_s":
                auto.get("first_grow_after_shift_s"),
            "grow_events": auto.get("grow_events"),
            "attributed": auto.get("attributed"),
            "max_cycles_per_volume": auto.get("max_cycles_per_volume"),
            "post_shift_read_rps": on_rps,
        }
        block["flash_crowd_off"] = {
            "verdict": res_off.get("verdict"),
            "post_shift_read_rps": off_rps,
        }
        if auto.get("slo_recovery_s") is not None:
            block["recovery_to_slo_s"] = auto["slo_recovery_s"]
        if off_rps:
            block["hot_rps_uplift_pct"] = round(
                100.0 * (on_rps / off_rps - 1.0), 1)
        # idle overhead: the leader loop must cost nothing while the
        # cluster is quiet (no heat above grow_share, nothing tiered)
        with spawn_cluster(1) as (mport, _root):
            base = run_bench(mport, 4000, use_tcp=False)
        block["baseline_read_rps"] = base.get("read", 0.0)
        with spawn_cluster(1, extra_master_args=(
                "-autoscaleSeconds", "1.0")) as (mport, _root):
            rates = run_bench(mport, 4000, use_tcp=False)
            block["autoscale_read_rps"] = rates.get("read", 0.0)
        if block["baseline_read_rps"]:
            block["idle_overhead_pct"] = round(
                100.0 * (1.0 - block["autoscale_read_rps"]
                         / block["baseline_read_rps"]), 2)
        # cold tiering, storage level: 64MB volume -> dir backend,
        # read THROUGH the tier, then recall it back wholesale
        from seaweedfs_tpu.storage.backend import configure_backends
        from seaweedfs_tpu.storage.needle import Needle
        from seaweedfs_tpu.storage.volume import Volume

        troot = _tf.mkdtemp()
        remote = os.path.join(troot, "remote")
        os.makedirs(remote)
        configure_backends({"bench": {"type": "dir", "root": remote}})
        v = Volume(troot, "", 9)
        payload = os.urandom(4 << 20)
        for i in range(16):  # 64MB across 16 needles
            v.write_needle(Needle(id=i + 1, cookie=0xB0, data=payload),
                           check_cookie=False)
        v.tier_upload_begin("bench")
        v.tier_commit()
        lats = []
        for i in range(8):
            t0 = time.perf_counter()
            got = v.read_needle(1 + (i % 16), cookie=0xB0).data
            lats.append(time.perf_counter() - t0)
            if len(got) != len(payload):
                raise RuntimeError("tiered read-through truncated")
        lats.sort()
        block["tiered_read_ms"] = round(1e3 * lats[len(lats) // 2], 2)
        t0 = time.perf_counter()
        v.tier_download()
        block["tier_recall_s"] = round(time.perf_counter() - t0, 3)
        v.close()
        detail["autoscale"] = block

    section("autoscale", meas_autoscale)

    # --- scaled cluster: N volume servers, M client procs ------------------
    def meas_cluster_scaled():
        """Horizontal capacity on a many-core host: several volume-server
        processes behind one master, loaded by several client processes
        whose phase-aligned rates sum (each runs `weed benchmark -phase`).
        Skipped below 6 cores — there the processes just fight for the
        same cycles and the plain cluster numbers are the honest ones."""
        cores = os.cpu_count() or 1
        if cores < 6:
            detail["cluster_scaled_skipped"] = f"{cores} cores"
            return
        n_vols = max(2, min(6, cores // 4))
        n_clients = max(2, min(6, cores // 4))
        per_client = 4000
        from seaweedfs_tpu.volume_server.dataplane import load_dataplane

        native = load_dataplane() is not None
        extra = ("-dataplane", "native") if native else ()

        with spawn_cluster(n_vols, extra) as (mport, root):
            def phase_rate(phase, use_tcp):
                """Run n_clients aligned single-phase benchmarks; their
                rates sum (all started together, same op count each)."""
                cps = []
                try:
                    for ci in range(n_clients):
                        argv = [sys.executable, weed_py, "benchmark",
                                "-master", f"127.0.0.1:{mport}",
                                "-n", str(per_client), "-c", "8",
                                "-size", "1024", "-phase", phase,
                                "-fidsFile",
                                os.path.join(root, f"fids{use_tcp}{ci}")]
                        if use_tcp:
                            argv.append("-useTcp")
                        cps.append(subprocess.Popen(
                            argv, env=cluster_env, stdout=subprocess.PIPE,
                            stderr=subprocess.DEVNULL, text=True))
                    total = 0.0
                    for p in cps:
                        out, _ = p.communicate(timeout=300)
                        mo = _re.search(rf"{phase}: .* = (\d+) req/s", out)
                        if p.returncode != 0 or not mo:
                            raise RuntimeError(
                                f"scaled client rc={p.returncode}")
                        total += float(mo.group(1))
                    return round(total, 1)
                finally:
                    # a failed/hung client must not leave its siblings
                    # spinning against servers we are about to kill
                    for p in cps:
                        if p.poll() is None:
                            p.kill()
                            p.wait(timeout=5)

            detail["cluster_scaled_config"] = (
                f"{n_vols} volume servers, {n_clients} clients, "
                f"{cores} cores, "
                f"{'native' if native else 'python'} data plane")
            detail["cluster_scaled_tcp_write_rps"] = phase_rate(
                "write", use_tcp=True)
            detail["cluster_scaled_tcp_read_rps"] = phase_rate(
                "read", use_tcp=True)
            detail["cluster_scaled_write_rps"] = phase_rate(
                "write", use_tcp=False)
            detail["cluster_scaled_read_rps"] = phase_rate(
                "read", use_tcp=False)

    section("cluster_scaled", meas_cluster_scaled)

    # --- parity check ------------------------------------------------------
    def meas_parity():
        sample = rng.integers(0, 256, (10, 1 << 20), dtype=np.uint8)
        want = ReedSolomon(10, 4, engine=best_cpu_engine()).encode(sample)
        got_xla = ReedSolomon(10, 4, engine=TpuEngine(mode="xla")).encode(sample)
        got_pal = ReedSolomon(10, 4, engine=TpuEngine(mode="pallas")).encode(sample)
        detail["parity_match_cpu_xla_pallas"] = bool(
            np.array_equal(want, got_xla) and np.array_equal(want, got_pal))

    section("parity", meas_parity)

    # --- integrity: sidecar overhead + scrub throughput --------------------
    def meas_integrity():
        import tempfile as _tempfile

        from seaweedfs_tpu.ec.integrity import EciSidecar, verify_shard_file
        from seaweedfs_tpu.ec.layout import to_ext as _to_ext
        from seaweedfs_tpu.ec.streaming import StreamingEncoder

        size_mb = 96
        with _tempfile.TemporaryDirectory() as td:
            dat = os.path.join(td, "1.dat")
            _write_big_random(dat, size_mb)
            base = os.path.join(td, "1")
            # verify overhead on the encode path: same encoder, sidecar
            # crc accumulation on vs off.  One untimed warm-up first so
            # both timed runs see the same hot page cache / initialized
            # codec — without it the second run's cache warmth would
            # systematically understate the overhead
            StreamingEncoder(10, 4, engine="host",
                             sidecar=False).encode_file(dat, base)
            enc_off = StreamingEncoder(10, 4, engine="host", sidecar=False)
            t0 = time.perf_counter()
            enc_off.encode_file(dat, base)
            t_without = time.perf_counter() - t0
            enc_on = StreamingEncoder(10, 4, engine="host")
            t0 = time.perf_counter()
            enc_on.encode_file(dat, base)
            t_with = time.perf_counter() - t0
            # scrub throughput: one pass over all 14 shards against the
            # sidecar — the scrubber's block-verify hot loop, unpaced
            sc = EciSidecar.load(base)
            nbytes = 0
            t0 = time.perf_counter()
            for i in range(14):
                if verify_shard_file(sc, base + _to_ext(i), i):
                    detail["error_integrity_verify"] = \
                        f"shard {i} failed crc on a clean encode"
                nbytes += os.path.getsize(base + _to_ext(i))
            scrub_s = time.perf_counter() - t0
            detail["integrity"] = {
                "volume_mb": size_mb,
                "scrub_gbps": round(nbytes / max(scrub_s, 1e-9) / 1e9, 3),
                "encode_with_sidecar_s": round(t_with, 3),
                "encode_without_sidecar_s": round(t_without, 3),
                "sidecar_overhead_pct": round(
                    100.0 * max(t_with - t_without, 0.0)
                    / max(t_without, 1e-9), 1),
                "sidecar_s": round(enc_on.stats.get("sidecar_s", 0.0), 3),
            }

    section("integrity", meas_integrity)

    def meas_pipeline_health():
        # self-healing pipeline counters for the WHOLE bench run: nonzero
        # means some measurement above survived worker restarts or ran
        # (partly) on the CPU fallback — its throughput number reflects a
        # DEGRADED run and must not be read as the clean-path capability
        # (per-run deltas also ride each e2e pipe dict as
        # retries/fallbacks/worker_restarts)
        from seaweedfs_tpu.stats import (ec_integrity_metrics,
                                         ec_pipeline_metrics)

        totals = ec_pipeline_metrics().totals()
        integrity = ec_integrity_metrics().totals()
        detail["pipeline_health"] = {
            "worker_restarts": totals["worker_restarts"],
            "engine_fallbacks": totals["engine_fallbacks"],
            # nonzero corrupt_shards/scrub_repairs: some measurement ran
            # against shards that rotted and were demoted or repaired
            # mid-bench — the run is NOT clean even if it completed
            "corrupt_shards": integrity["corrupt_shards"],
            "scrub_repairs": integrity["scrub_repairs"],
        }
        detail["scrub_health"] = integrity

    section("pipeline_health", meas_pipeline_health)

    checkpoint()
    print("BENCH_CHILD_RESULT " + _dump_detail(), flush=True)


# --------------------------------------------------------------------------
# parent: orchestration; NEVER imports jax
# --------------------------------------------------------------------------

def _run_sub(argv, timeout, env=None):
    try:
        p = subprocess.run(argv, capture_output=True, text=True,
                           timeout=timeout, env=env)
        return p.returncode, p.stdout, p.stderr
    except subprocess.TimeoutExpired as e:
        # TimeoutExpired carries captured output as bytes even under text=True
        out = e.stdout or ""
        if isinstance(out, bytes):
            out = out.decode(errors="replace")
        return -9, out, f"timeout after {timeout}s"
    except Exception as e:  # pragma: no cover - os-level failure
        return -1, "", str(e)


def _probe_backend(timeout=PROBE_TIMEOUT_S):
    """Bounded subprocess probe of jax backend init; returns backend name or
    None. Retries once (a failed backend init can be transient)."""
    code = ("import jax; d = jax.devices(); "
            "print('PROBE_OK', jax.default_backend(), len(d))")
    for attempt in range(2):
        rc, out, err = _run_sub([sys.executable, "-c", code], timeout)
        for line in out.splitlines():
            if line.startswith("PROBE_OK"):
                _, backend, n = line.split()
                return backend, int(n), attempt
    return None, 0, 2


def _run_child(timeout, platform=""):
    """Run the measurement child; returns (detail dict or None, error)."""
    with tempfile.NamedTemporaryFile(mode="r", suffix=".json",
                                     delete=False) as scratch:
        scratch_path = scratch.name
    try:
        argv = [sys.executable, os.path.abspath(__file__), "--child",
                scratch_path]
        if platform:
            argv.append(platform)
        # the child gets a slightly smaller budget than the subprocess
        # timeout so IT decides what to skip and still prints its JSON,
        # instead of dying rc=-9 mid-section
        env = dict(os.environ,
                   BENCH_CHILD_BUDGET_S=str(max(timeout - 60, 60)))
        rc, out, err = _run_sub(argv, timeout, env=env)
        for line in out.splitlines():
            if line.startswith("BENCH_CHILD_RESULT "):
                return json.loads(line[len("BENCH_CHILD_RESULT "):]), None
        # died or hung mid-run: salvage the checkpointed partial sections
        partial = None
        try:
            with open(scratch_path) as f:
                txt = f.read()
            if txt.strip():
                partial = json.loads(txt)
        except Exception:
            pass
        tail = (err or out or "").strip().splitlines()
        return partial, f"child rc={rc}: {tail[-1][:300] if tail else 'no output'}"
    finally:
        try:
            os.unlink(scratch_path)
        except OSError:
            pass


def _numpy_last_resort():
    """Pure-numpy measurement if even CPU-backend jax is broken."""
    import numpy as np

    from seaweedfs_tpu.ec.codec import ReedSolomon, best_cpu_engine

    rng = np.random.default_rng(0xBE)
    data = rng.integers(0, 256, (10, 1 << 23), dtype=np.uint8)
    simd = best_cpu_engine()
    rs = ReedSolomon(10, 4, engine=simd)
    rs.encode(data[:, :1024])
    t0 = time.perf_counter()
    rs.encode(data)
    dt = time.perf_counter() - t0
    return {"cpu_engine": simd.name,
            "cpu_simd_mbps": round(data.nbytes / dt / 1e6, 1)}


def main() -> None:
    detail: dict = {}
    errors: list[str] = []

    backend, ndev, attempts = _probe_backend()
    detail["probe"] = {"backend": backend, "devices": ndev,
                       "attempts": attempts + 1}
    if backend is None:
        errors.append("TPU backend probe timed out/failed on both attempts; "
                      "falling back to CPU")

    result_detail = None
    if backend is not None:
        result_detail, err = _run_child(BENCH_TIMEOUT_S)
        if err:
            errors.append(f"bench({backend}): {err}")

    if result_detail is None or "cpu_simd_mbps" not in result_detail:
        # TPU probe failed or the bench died before the baseline: CPU fallback
        cpu_detail, err = _run_child(CPU_BENCH_TIMEOUT_S, platform="cpu")
        if err:
            errors.append(f"bench(cpu-fallback): {err}")
        if cpu_detail is not None:
            # TPU-child keys win the merge: a TPU run whose CPU-baseline
            # section failed must still be reported as a TPU result
            merged = dict(cpu_detail)
            if result_detail:
                merged["fallback_backend"] = cpu_detail.get("backend")
                merged.update(result_detail)
            result_detail = merged

    if result_detail is None:
        try:
            result_detail = _numpy_last_resort()
            errors.append("jax unusable on every backend; pure-numpy baseline only")
        except Exception as e:  # pragma: no cover
            result_detail = {}
            errors.append(f"numpy fallback failed: {type(e).__name__}: {e}")

    detail.update(result_detail)
    # provenance stamp: bench_diff refuses cross-schema comparisons and
    # names the revisions it compared instead of misreporting
    detail["schema_version"] = BENCH_SCHEMA_VERSION
    detail["git_revision"] = _git_revision()
    if errors:
        detail["error"] = "; ".join(errors)[:1000]

    cpu = detail.get("cpu_simd_mbps") or detail.get("cpu_numpy_mbps") or 0.0
    tpu = detail.get("tpu_inhbm_pallas_mbps") or detail.get("tpu_inhbm_xla_mbps")
    on_tpu = detail.get("backend") not in (None, "cpu", "gpu")
    if on_tpu and tpu:
        value, unit = float(tpu), "MB/s"
        metric = "ec.encode MB/s/chip (RS(10,4), in-HBM sustained)"
    else:
        value, unit = float(cpu), "MB/s"
        metric = "ec.encode MB/s (RS(10,4), CPU fallback — TPU unavailable)"
    vs_baseline = round(value / cpu, 2) if cpu else 0.0

    print(json.dumps({
        "metric": metric,
        "value": round(value, 1),
        "unit": unit,
        "vs_baseline": vs_baseline,
        "detail": detail,
    }))


if __name__ == "__main__":
    # --trace-out PATH: persist the e2e section's Chrome trace-event JSON
    # (open in chrome://tracing or ui.perfetto.dev).  --profile-out PATH:
    # persist a collapsed-stack (flamegraph.pl) sampling profile of the
    # same section.  Both carried to the measurement child via the
    # environment so every fallback re-exec (TPU -> CPU) inherits them.
    for flag, env_key in (("--trace-out", "BENCH_TRACE_OUT"),
                          ("--profile-out", "BENCH_PROFILE_OUT")):
        if flag in sys.argv:
            i = sys.argv.index(flag)
            if i + 1 >= len(sys.argv):
                print(f"{flag} requires a path", file=sys.stderr)
                sys.exit(2)
            os.environ[env_key] = os.path.abspath(sys.argv[i + 1])
            del sys.argv[i:i + 2]
    if len(sys.argv) >= 3 and sys.argv[1] == "--child":
        _child(sys.argv[2], sys.argv[3] if len(sys.argv) > 3 else "")
    else:
        main()
