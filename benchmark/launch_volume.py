#!/usr/bin/env python3
"""Run ``weed.py volume`` in this process, with a profiler control.

The volume server is the process that holds the chip, so only it can
trace the chip.  This launcher runs ``weed.main`` in-process and adds
one daemon thread that watches a control directory:

- the file ``start`` makes it call ``jax.profiler.start_trace`` into
  ``<ctl>/profile`` (a perfetto JSON too) and answer with ``started``;
- the file ``stop`` makes it call ``stop_trace`` and answer with
  ``stopped``, which holds the traced window's length in seconds.

``--spans`` turns the program's span tracer on, sampling every request,
with a ring large enough for a whole window (the default ring holds
8,192 spans).

``--fault`` plants one fault under the timed path, for the benchmark's
tests and its control runs (PERF.md, "How correct is decided"); the
benchmark's own runs never pass it.

    launch_volume.py --ctl DIR [--spans] [--fault NAME] -- <volume args>
"""

from __future__ import annotations

import argparse
import os
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _write(path: str, text: str) -> None:
    with open(path + ".part", "w") as f:
        f.write(text)
    os.replace(path + ".part", path)


def profiler_control(ctl: str) -> None:  # thread-entry
    start, stop = os.path.join(ctl, "start"), os.path.join(ctl, "stop")
    t0 = None
    while True:
        if t0 is None and os.path.exists(start):
            import jax

            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # a busy server's every call
            jax.profiler.start_trace(os.path.join(ctl, "profile"),
                                     create_perfetto_trace=True,
                                     profiler_options=opts)
            t0 = time.perf_counter()
            _write(os.path.join(ctl, "started"), "")
        elif t0 is not None and os.path.exists(stop):
            import jax

            window = time.perf_counter() - t0
            jax.profiler.stop_trace()
            _write(os.path.join(ctl, "stopped"), repr(window))
            return
        time.sleep(0.01)


def _flip(path: str, offset: int) -> None:
    with open(path, "r+b") as f:
        f.seek(offset)
        b = f.read(1)
        f.seek(offset)
        f.write(bytes([b[0] ^ 0x5A]))


def _zero_back_half(path: str) -> None:
    size = os.path.getsize(path)
    with open(path, "r+b") as f:
        f.seek(size // 2)
        f.write(bytes(size - size // 2))


class _BrokenEngine:
    """A device engine whose every call fails (``fallback``): the
    program falls back to its CPU codec, which answers the same bytes,
    off the device path the run reports."""
    name = "planted"

    def matmul(self, *a, **kw):
        raise RuntimeError("planted: the device dispatch failed")


def plant(spec: str) -> None:
    """Break the timed path underneath, as a later PR might.  `spec` is
    <where>.<fault>: where is encode, rebuild or reconstruct."""
    import numpy as np

    from seaweedfs_tpu.ec.codec import ReedSolomon
    from seaweedfs_tpu.ec.streaming import StreamingEncoder

    encode_file, rebuild_files = (StreamingEncoder.encode_file,
                                  StreamingEncoder.rebuild_files)
    reconstruct = ReedSolomon.reconstruct
    where, fault = spec.split(".")

    def after_encode(self, dat_path, out_base, *a, **kw):
        if fault == "stale":  # the encode leaves the disk as it was
            return None
        out = encode_file(self, dat_path, out_base, *a, **kw)
        if fault == "alter":  # one parity byte wrong where it is made
            _flip(out_base + ".ec12", os.path.getsize(out_base + ".ec12") // 3)
        elif fault == "half":  # the back half of the columns left out
            for i in range(10, 14):
                _zero_back_half(out_base + ".ec%02d" % i)
        return out

    def after_rebuild(self, base, *a, **kw):
        if fault == "stale":
            return []
        out = rebuild_files(self, base, *a, **kw)
        for s in out:
            path = base + ".ec%02d" % s
            if fault == "alter":
                _flip(path, os.path.getsize(path) // 3)
            elif fault == "half":
                _zero_back_half(path)
        return out

    def after_reconstruct(self, shards, *a, **kw):
        if fault == "fallback":
            self.engine = _BrokenEngine()
        missing = [i for i, s in enumerate(shards) if s is None]
        reconstruct(self, shards, *a, **kw)
        for i in missing:
            if shards[i] is None:
                continue
            s = np.array(shards[i], dtype=np.uint8)
            if fault == "alter":
                s[len(s) // 2] ^= 0x5A
            elif fault == "half":
                s[len(s) // 2:] = 0
            shards[i] = s

    if (where, fault) == ("encode", "fallback"):
        StreamingEncoder._dispatch = _BrokenEngine.matmul
    elif where == "encode":
        StreamingEncoder.encode_file = after_encode
    elif where == "rebuild":
        StreamingEncoder.rebuild_files = after_rebuild
    else:
        ReedSolomon.reconstruct = after_reconstruct


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--ctl", required=True)
    p.add_argument("--spans", action="store_true")
    p.add_argument("--fault", help="<where>.<fault>: where is encode, "
                   "rebuild or reconstruct; fault is stale, half, alter "
                   "or fallback")
    p.add_argument("volume_args", nargs=argparse.REMAINDER)
    args = p.parse_args()
    argv = [a for a in args.volume_args if a != "--"]
    sys.path.insert(0, ROOT)
    import weed

    if args.fault:
        plant(args.fault)
    flags = []
    if args.spans:
        from seaweedfs_tpu.observability import enable_tracing

        enable_tracing(capacity=1 << 21)
        flags = ["-trace.sample", "1"]
    threading.Thread(target=profiler_control, args=(args.ctl,),
                     name="bench-profiler", daemon=True).start()
    weed.main(flags + ["volume"] + argv)


if __name__ == "__main__":
    main()
