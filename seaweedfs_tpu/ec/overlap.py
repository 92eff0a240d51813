"""Asynchronous drains for the streaming EC encode (ec/streaming.py).

The encode's producer thread fills and dispatches; it never blocks in a
device fetch.  AsyncDrainer runs each dispatch's fetch on a small thread
pool and hands the fetched parity to ONE writer thread through a bounded
FIFO queue, so D2H transfers overlap the producer's fill, dispatch and
data writes while the shard append order and the `.eci` crc stream stay
those of a serial drain.  DrainerGroup is one such drain lane per device
of a slice: the per-device dispatch-queue plane.  DataLanes takes the
data shards' pass-through writes off the producer the same way, each
shard on the one thread that owns it.
"""

from __future__ import annotations

import concurrent.futures
import queue as queue_mod
import threading
import time


class AsyncDrainer:
    """FIFO-preserving asynchronous drain for the streaming pipelines.

    The producer (the pipeline's critical thread) calls submit(meta) and
    moves straight on to filling/dispatching the next dispatch; the
    blocking work happens elsewhere:

      - fetch(meta) runs on a small thread pool.  pool_size=1 keeps a
        strict FIFO fetch order, while device-array handles (independent
        D2H copies) may use more threads to keep several transfers in
        flight on the wire.
      - write(meta, result) runs on ONE dedicated writer thread, fed in
        SUBMISSION order through a bounded queue, so shard append order
        and the `.eci` write-order crc stream stay byte-identical to
        the serial pipeline no matter how fetches complete.

    Error model: the first fetch/write exception is captured (later
    results are consumed and discarded, never written) and re-raised
    from finish() — or surfaced through .error for the producer to poll
    between dispatches — so the pipeline's existing retry-from-
    checkpoint machinery sees the failure exactly where the old inline
    drain would have raised it.  abort() is the abnormal-exit path: it
    discards queued work and joins the threads.
    """

    def __init__(self, fetch, write, pool_size: int = 1,
                 queue_depth: int = 8, name: str = "ec-drain"):
        self._fetch_fn = fetch
        self._write_fn = write
        self.pool_size = max(1, int(pool_size))
        self._pool = concurrent.futures.ThreadPoolExecutor(
            max_workers=self.pool_size, thread_name_prefix=f"{name}-fetch")
        # bounded hand-off: sized by the caller to its slot count, so a
        # put never blocks in practice but the queue cannot grow without
        # bound if the contract is violated
        self._wq: queue_mod.Queue = queue_mod.Queue(
            maxsize=max(2, int(queue_depth)))
        self._error: BaseException | None = None  # guarded-by: _lock
        # DELIBERATELY lock-free: a monotonic abort flag the fetch/write
        # paths re-read; the unwinding caller must never block on _lock
        self.aborting = False
        self._inflight = 0  # guarded-by: _lock
        self._lock = threading.Lock()
        self._finished = False  # guarded-by: _lock
        self._writer = threading.Thread(target=self._write_loop,
                                        daemon=True, name=f"{name}-writer")
        self._writer.start()

    @property
    def error(self):
        """First fetch/write exception, or None.  The producer polls
        this between dispatches to fail fast instead of filling slots
        for a drain that can no longer complete."""
        with self._lock:
            return self._error

    @property
    def inflight(self) -> int:
        """Dispatches submitted but not yet written (or discarded)."""
        with self._lock:
            return self._inflight

    def submit(self, meta) -> None:
        with self._lock:
            err = self._error
            if err is None:
                self._inflight += 1
        if err is not None:
            raise err
        fut = self._pool.submit(self._fetch_fn, meta)
        self._wq.put((meta, fut))

    def _write_loop(self) -> None:
        while True:
            item = self._wq.get()
            if item is None:
                return
            meta, fut = item
            try:
                result = fut.result()
                with self._lock:
                    err = self._error
                if not self.aborting and err is None:
                    self._write_fn(meta, result)
            except (KeyboardInterrupt, SystemExit):  # pragma: no cover
                raise
            except BaseException as e:
                with self._lock:
                    if self._error is None and not self.aborting:
                        self._error = e
            finally:
                with self._lock:
                    self._inflight -= 1

    def finish(self, timeout: float | None = None) -> None:
        """Wait until every submitted dispatch is fetched AND written,
        then re-raise the first captured error (if any)."""
        with self._lock:
            finished, self._finished = self._finished, True
        if not finished:
            self._wq.put(None)
        self._writer.join(timeout)
        if self._writer.is_alive():
            raise RuntimeError("async drain writer stalled")
        self._pool.shutdown(wait=True)
        with self._lock:
            err = self._error
        if err is not None:
            raise err

    def abort(self) -> None:
        """Abnormal-exit teardown: discard queued work, join threads.
        Never raises; the caller is already unwinding an exception."""
        self.aborting = True  # weedlint: disable=W502 lock-free abort flag: the unwinding caller must never block on _lock
        with self._lock:
            finished, self._finished = self._finished, True
        if not finished:
            try:
                self._wq.put(None, timeout=1.0)
            except queue_mod.Full:  # pragma: no cover - contract breach
                pass
        try:
            self._writer.join(timeout=30)
            self._pool.shutdown(wait=True)
        except Exception:  # pragma: no cover - best-effort teardown
            pass


class DrainerGroup:
    """One AsyncDrainer per device queue — the `-ec.engine=mesh`
    per-device drain lanes.  Lane i fetches device i's D2H transfers on
    its own thread and writes through its own writer, so a slow device
    (or a congested per-device link) back-pressures only its own
    dispatch queue instead of stalling the whole slice.

    FIFO is per-lane; cross-lane ordering is the CALLER's contract —
    the mesh encode plane pwrites parity at known shard offsets
    (order-free) and retires the crc sidecar + resume checkpoint
    through an ordered completion tracker keyed by dispatch index.

    The error/abort surface mirrors AsyncDrainer so the pipeline's
    retry-from-checkpoint machinery treats N lanes as one drain:
    `.error` is the first captured lane error, finish() joins every
    lane then re-raises it, abort() tears all lanes down, and the
    lock-free `aborting` flag fans out to every lane."""

    def __init__(self, lanes: int, fetch, write, queue_depth: int = 8,
                 name: str = "ec-mesh-drain"):
        self.drainers = [
            AsyncDrainer(fetch, write, pool_size=1,
                         queue_depth=queue_depth, name=f"{name}-{i}")
            for i in range(max(1, int(lanes)))]
        self.pool_size = len(self.drainers)

    @property
    def error(self):
        for d in self.drainers:
            err = d.error
            if err is not None:
                return err
        return None

    @property
    def inflight(self) -> int:
        return sum(d.inflight for d in self.drainers)

    @property
    def aborting(self) -> bool:
        return any(d.aborting for d in self.drainers)

    @aborting.setter
    def aborting(self, value: bool) -> None:
        for d in self.drainers:
            d.aborting = value  # lock-free flag fan-out, same contract as AsyncDrainer.abort

    def submit(self, lane: int, meta) -> None:
        self.drainers[lane].submit(meta)

    def finish(self, timeout: float | None = None) -> None:
        """Join every lane, then re-raise the FIRST lane error — one
        failing device fails the encode exactly where a single-lane
        drain would have."""
        first: BaseException | None = None
        for d in self.drainers:
            try:
                d.finish(timeout)
            except (KeyboardInterrupt, SystemExit):  # pragma: no cover
                raise
            except BaseException as e:
                if first is None:
                    first = e
        if first is not None:
            raise first

    def abort(self) -> None:
        for d in self.drainers:
            d.abort()


class DataLanes:
    """The data shards' pass-through writes, on shard-owned threads.

    Lane j of n owns the shards i with i % n == j, so each shard's bytes
    and its `.eci` crc state are touched by one thread, in file order.
    The producer submit()s each dispatch once, in dispatch order, and
    goes straight on; every lane runs write(j, meta) for it, in that
    order.  What must not run ahead of the data, a slot's release or
    the resume checkpoint, asks written(d) or wait(d): the first d
    dispatches are written in every lane.

    Errors as AsyncDrainer's: the first write exception is kept, later
    dispatches are discarded unwritten, and it is re-raised from
    submit(), wait() and finish().  abort() discards queued work and
    joins the threads."""

    def __init__(self, lanes: int, write, name: str = "ec-data"):
        self.n = max(1, int(lanes))
        self._write_fn = write
        self._cv = threading.Condition()
        self._done = [0] * self.n  # guarded-by: _cv
        self._error: BaseException | None = None  # guarded-by: _cv
        # lock-free monotonic flag, as AsyncDrainer.aborting
        self.aborting = False
        self._qs = [queue_mod.SimpleQueue() for _ in range(self.n)]
        self._threads = [
            threading.Thread(target=self._lane_loop, args=(j,),
                             daemon=True, name=f"{name}-{j}")
            for j in range(self.n)]
        for t in self._threads:
            t.start()

    @property
    def error(self):
        with self._cv:
            return self._error

    def submit(self, meta) -> None:
        err = self.error
        if err is not None:
            raise err
        for q in self._qs:
            q.put(meta)

    def _lane_loop(self, j: int) -> None:
        q = self._qs[j]
        while True:
            meta = q.get()
            if meta is None:
                return
            if self.aborting or self.error is not None:
                continue
            try:
                self._write_fn(j, meta)
            except (KeyboardInterrupt, SystemExit):  # pragma: no cover
                raise
            except BaseException as e:
                with self._cv:
                    if self._error is None:
                        self._error = e
                    self._cv.notify_all()
                continue
            finally:
                meta = None  # the source view goes with the write
            with self._cv:
                self._done[j] += 1
                self._cv.notify_all()

    def written(self, d: int) -> bool:
        """The first d dispatches are written in every lane."""
        with self._cv:
            return min(self._done) >= d

    def wait(self, d: int, timeout_s: float) -> None:
        """Block until written(d); raises a lane's error, or
        RuntimeError once aborted or after timeout_s."""
        deadline = time.monotonic() + timeout_s
        with self._cv:
            while min(self._done) < d:
                if self._error is not None:
                    raise self._error
                if self.aborting:
                    raise RuntimeError("data lanes aborted")
                left = deadline - time.monotonic()
                if left <= 0:
                    raise RuntimeError("data lanes stalled")
                self._cv.wait(min(left, 0.2))

    def finish(self) -> None:
        """Write every submitted dispatch, join the lanes, then
        re-raise the first lane error (if any)."""
        for q in self._qs:
            q.put(None)
        for t in self._threads:
            t.join()
        err = self.error
        if err is not None:
            raise err

    def abort(self) -> None:
        """Abnormal-exit teardown: discard queued work, wake waiters,
        join the lanes.  Never raises."""
        self.aborting = True  # weedlint: disable=W502 lock-free abort flag, as AsyncDrainer.abort
        with self._cv:
            self._cv.notify_all()
        for q in self._qs:
            q.put(None)
        for t in self._threads:
            t.join(timeout=30)
