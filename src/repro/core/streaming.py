"""Bounded-memory streaming encode core: chunk windows, per-window plan
reuse, async write-behind.

Every write surface used to hold its own whole-array loop (`ShardStore.
write` flattened the full tensor host-side, checkpoint ``save_tree`` looped
leaf chunks inline, `ContainerWriter` kept its probe policy private).  This
module is the one shared engine they all ride now:

* :func:`iter_fixed_chunks` re-chunks an *iterable* of arbitrary-size array
  pieces into the container's fixed chunk geometry while holding at most
  one chunk plus one piece in memory — the spill-free ingestion primitive.
* :class:`WindowPlanner` is the selection policy as an object: probe once
  on the first sizeable chunk (exactly the historical writer policy), then
  group the stream into fixed-size **windows** (``REPRO_STREAM_WINDOW_BYTES``)
  and, at each window boundary, compare a PR 8
  :class:`~repro.core.plans.StreamFingerprint` of the stream-now against
  the fingerprint the current pick was selected on — re-selecting only on
  drift (``REPRO_PLAN_DRIFT``), reusing the plan otherwise.  The policy is
  a deterministic function of the chunk sequence, so the streamed and
  one-shot paths produce **byte-identical** containers for equal chunk
  geometry (tests/test_streaming.py pins this bitwise).
* :func:`stream_chunks` is the async write-behind pump: chunks encode on
  the caller's thread while serialized records drain to the file on a
  single background thread through a bounded queue
  (``REPRO_STREAM_QUEUE_DEPTH``) — encode overlaps I/O, memory stays
  O(queue-depth · record), and record order (hence container bytes) is
  exactly the submission order.

Knobs (read at call time; docs/knobs.md):

* ``REPRO_STREAM_WINDOW_BYTES`` — window size for the drift-refresh cadence
  (default 4 MiB).
* ``REPRO_STREAM_QUEUE_DEPTH`` — write-behind queue depth in records
  (default 2; memory bound of the pump).
"""
from __future__ import annotations

import os
import queue
import threading

import numpy as np

from .. import obs
from . import pipeline, plans, transforms as T

DEFAULT_WINDOW_BYTES = 4 << 20
DEFAULT_QUEUE_DEPTH = 2

# selection probe geometry (moved here from container/io.py, which
# re-exports them): arrays at or below the threshold run full auto per
# chunk; larger streams probe once on a strided sample per window policy
PROBE_ELEMS = 8192
PROBE_THRESHOLD = 16384


def stream_window_bytes() -> int:
    """Chunk-window size in bytes (``REPRO_STREAM_WINDOW_BYTES`` override)."""
    v = os.environ.get("REPRO_STREAM_WINDOW_BYTES", "").strip()
    return int(v) if v else DEFAULT_WINDOW_BYTES


def stream_queue_depth() -> int:
    """Write-behind queue depth (``REPRO_STREAM_QUEUE_DEPTH`` override)."""
    v = os.environ.get("REPRO_STREAM_QUEUE_DEPTH", "").strip()
    return max(1, int(v)) if v else DEFAULT_QUEUE_DEPTH


# ---------------------------------------------------------------------------
# fixed-geometry re-chunking
# ---------------------------------------------------------------------------

def iter_fixed_chunks(pieces, chunk_elems: int, dtype=None):
    """Re-chunk an iterable of array pieces into flat chunks of exactly
    ``chunk_elems`` elements (the last chunk may be shorter).

    Pieces may be any array-likes (a generator of them streams): each is
    flattened and sliced by **view** where possible — only a chunk that
    straddles piece boundaries is assembled by copy, so peak memory is
    O(chunk + piece), never O(stream).  ``dtype`` (when given) is enforced,
    not cast: a mismatched piece raises ``ValueError`` loudly instead of
    silently converting values on a path that promises bitwise storage.
    """
    if chunk_elems < 1:
        raise ValueError(f"chunk_elems must be >= 1, got {chunk_elems}")
    want = np.dtype(dtype) if dtype is not None else None
    buf: list[np.ndarray] = []
    have = 0
    for piece in pieces:
        a = np.asarray(piece).reshape(-1)
        if want is not None and a.dtype != want:
            raise ValueError(
                f"stream piece dtype {a.dtype} does not match the declared "
                f"stream dtype {want} (pieces are stored bitwise, not cast)"
            )
        n = a.shape[0]
        pos = 0
        if have:
            take = min(chunk_elems - have, n)
            buf.append(a[:take])
            have += take
            pos = take
            if have == chunk_elems:
                yield np.concatenate(buf)
                buf, have = [], 0
        while n - pos >= chunk_elems:
            yield a[pos : pos + chunk_elems]
            pos += chunk_elems
        if pos < n:
            buf.append(a[pos:])
            have = n - pos
    if have:
        yield buf[0] if len(buf) == 1 else np.concatenate(buf)


# ---------------------------------------------------------------------------
# per-window plan reuse with fingerprint-drift refresh
# ---------------------------------------------------------------------------

class WindowPlanner:
    """The writer's selection policy as a first-class object.

    One planner serves one container stream.  Policy, in order:

    * an explicit ``plan`` (:class:`~repro.core.plans.EncodePlan`) encodes
      every chunk phase-2-only through ``pipeline.encode_with_plan``;
    * an explicit ``method`` applies it per chunk (identity fallback);
    * ``method="auto"``: chunks at or below ``probe_threshold`` elements run
      full auto individually; the first larger chunk is probed once
      (``select_method(use_cache=True)`` on a strided sample) and its pick
      — plus a :class:`~repro.core.plans.StreamFingerprint` of that sample
      — becomes the window plan.  Every ``window_bytes`` of subsequent
      stream, the boundary chunk is fingerprinted and compared:
      ``drift > REPRO_PLAN_DRIFT`` re-selects (a *drift refresh*), anything
      else reuses the pick selection-free.

    The decision sequence depends only on the chunk sequence (sizes and
    values), so two writers fed the same chunks emit identical records —
    the streamed-equals-one-shot byte-identity contract.

    ``stats`` counters: ``probes`` (cold selections), ``windows`` (boundary
    checks), ``reused_windows``, ``drift_refreshes``.
    """

    def __init__(self, spec, backend: str | None = None, method: str = "auto",
                 params: dict | None = None, candidates=None, plan=None,
                 probe_elems: int = PROBE_ELEMS,
                 probe_threshold: int = PROBE_THRESHOLD,
                 fallback_identity: bool = True,
                 window_bytes: int | None = None):
        self._spec = spec
        self._backend = backend
        self._method = method
        self._params = params
        self._candidates = (candidates if candidates is not None
                            else pipeline.DEFAULT_CANDIDATES)
        self._plan = plan
        self._probe_elems = probe_elems
        self._probe_threshold = probe_threshold
        self._fallback_identity = fallback_identity
        self.window_bytes = (window_bytes if window_bytes is not None
                             else stream_window_bytes())
        self.picked: tuple[str, dict | None] | None = None
        self._fp: plans.StreamFingerprint | None = None
        self._window_fill = 0
        self.stats = {"probes": 0, "windows": 0, "reused_windows": 0,
                      "drift_refreshes": 0}

    @obs.span("select.probe")
    def _select(self, chunk, stat: str, sample=None,
                fp: plans.StreamFingerprint | None = None) -> None:
        if sample is None:
            sample = pipeline._strided(chunk, self._probe_elems)
        try:
            self.picked = pipeline.select_method(
                sample, candidates=self._candidates, spec=self._spec,
                backend=self._backend, use_cache=True,
            )
            self._fp = fp if fp is not None else (
                plans.StreamFingerprint.from_array(np.asarray(sample))
            )
            self.stats[stat] += 1
        except T.TransformError:
            # no feasible candidate for this sample: full auto per chunk
            self.picked = ("auto", None)
            self._fp = None

    def _window_check(self, chunk, nbytes: int) -> None:
        """Advance the window accounting; at a boundary, fingerprint the
        boundary chunk and drift-refresh or reuse."""
        self._window_fill += nbytes
        if self._window_fill < self.window_bytes:
            return
        self._window_fill = 0
        if self._fp is None or int(chunk.size) <= self._probe_threshold:
            # fingerprint-less pick (probe failed) or a tail chunk too
            # small to sample representatively: keep the current pick
            return
        self.stats["windows"] += 1
        sample = pipeline._strided(chunk, self._probe_elems)
        fp = plans.StreamFingerprint.from_array(np.asarray(sample))
        if self._fp.drift(fp) > plans.plan_drift_threshold():
            self._select(chunk, "drift_refreshes", sample=sample, fp=fp)
        else:
            self.stats["reused_windows"] += 1

    def encode(self, chunk) -> pipeline.Encoded:
        """Encode one chunk under the window policy (always round-trips:
        a chunk the picked transform rejects falls back to identity)."""
        if self._plan is not None and self._method == "auto":
            # pre-built plan: pure phase-2 encode — no probe, no phase-1
            # dispatches; a chunk the winner rejects walks the plan's own
            # ranked fallbacks and terminally lands on identity (verified)
            return pipeline.encode_with_plan(chunk, self._plan)
        name, prm = self._method, self._params
        if name == "auto":
            size = int(chunk.size)
            if self.picked is None:
                if size > self._probe_threshold:
                    self._select(chunk, "probes")
                    self._window_fill = size * chunk.dtype.itemsize
            else:
                self._window_check(chunk, size * chunk.dtype.itemsize)
            name, prm = self.picked or ("auto", None)
        try:
            if name == "auto":
                return pipeline.encode(
                    chunk, method="auto", candidates=self._candidates,
                    spec=self._spec, backend=self._backend,
                )
            return pipeline.apply_transform(chunk, name, prm, spec=self._spec,
                                            backend=self._backend)
        except T.TransformError:
            if not self._fallback_identity:
                raise
            # picked transform rejected this chunk's data: lossless fallback
            return pipeline.apply_transform(chunk, "identity", spec=self._spec,
                                            backend=self._backend)


# ---------------------------------------------------------------------------
# async write-behind pump
# ---------------------------------------------------------------------------

_DONE = object()


def stream_chunks(writer, chunks, queue_depth: int | None = None) -> int:
    """Pump an iterator of chunks through ``writer`` with write-behind.

    Chunks encode+serialize on the calling thread (``writer.encode_record``,
    the CPU half) while finished records drain to the destination on one
    background thread (``writer._write_record``, the I/O half) through a
    bounded queue — encode overlaps file I/O, and the queue bound keeps
    in-flight memory at O(depth · record) however long the stream is.

    Records are written in exactly the order chunks were submitted (single
    FIFO consumer), so the resulting container is byte-identical to calling
    ``writer.append`` per chunk.  The first failure on either side is
    re-raised here, in the caller; returns the number of chunks written.
    """
    depth = queue_depth if queue_depth is not None else stream_queue_depth()
    q: queue.Queue = queue.Queue(maxsize=max(1, depth))
    failure: list[BaseException] = []

    def drain() -> None:
        while True:
            item = q.get()
            if item is _DONE:
                return
            if failure:
                continue  # discard: keep unblocking the producer
            try:
                with obs.span("stream.write_record"):
                    writer._write_record(*item)
            except BaseException as e:  # noqa: BLE001 - re-raised in caller
                failure.append(e)

    t = threading.Thread(target=drain, name="rfpc-write-behind", daemon=True)
    t.start()
    n = 0
    try:
        for chunk in chunks:
            with obs.span("stream.encode_record"):
                rec = writer.encode_record(chunk)
            if failure:
                break
            with obs.span("stream.queue_wait"):
                q.put(rec)
            n += 1
    finally:
        with obs.span("stream.queue_wait"):
            q.put(_DONE)
            t.join()
    if failure:
        raise failure[0]
    return n
