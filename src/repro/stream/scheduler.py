"""Micro-batch coalescing scheduler: fixed shapes over a request stream.

The streaming service receives *variable-size* row requests but XLA
executables want *fixed* shapes — recompiling per request size would
stall the latency path exactly like the serving engine's problem with
ragged decode batches. ``serve/engine.py`` solves it with fixed slot
counts; here the same continuous-batching discipline is applied to
preprocessing:

  * requests are coalesced FIFO into **micro-batches**;
  * each micro-batch is padded to the smallest of a small set of
    **bucket capacities** (default {1Ki, 4Ki, 16Ki} rows) so every step
    runs one of ``len(buckets)`` pre-known shapes — after one warmup per
    bucket, no step ever compiles again (pinned by jit cache-miss
    counting in tests/test_stream_service.py);
  * each bucket owns a :class:`~repro.core.pipeline.FrozenVocabTransform`
    (loop ② with the offline-finalized vocabulary) sized to its capacity.
    Every bucket executes the *same*
    :class:`~repro.core.plan_compiler.CompiledPlan` — the one named by
    ``config.plan`` (default: the Criteo chain) — so the online service
    serves exactly the program the offline engines ran, crossed features
    and custom dense recipes included;
  * results are **routed back per request** by row span: concatenated
    request rows decode to contiguous output rows (the decoder assigns
    row *k* to the *k*-th newline), so the route step is a slice.

Both input formats are supported, matching ``PipelineConfig``:
``"utf8"`` requests carry row-framed encoded bytes (paper Config I/II);
``"binary"`` requests carry pre-decoded ``{label, dense, sparse}``
columns (Config III).
"""

from __future__ import annotations

import dataclasses
import itertools
import threading
import time

import jax
import numpy as np

from repro import obs
from repro.core import pipeline as pipeline_lib
from repro.core import schema as schema_lib
from repro.core import vocab as vocab_lib

DEFAULT_BUCKET_ROWS = (1024, 4096, 16384)

# Process-wide ids: requests and batches of every service are numbered
# in creation order (next() on a count is atomic under the GIL).
_request_ids = itertools.count()
_batch_ids = itertools.count()


class StreamRequest:
    """One in-flight preprocessing request — also the caller's handle.

    ``payload`` is either a uint8 array of whole encoded rows (utf8) or a
    ``{label, dense, sparse}`` dict of per-row arrays (binary). The
    service fills the timing fields; :meth:`result` blocks until the
    request's rows come back from the device (or the service failed).

    ``id`` is unique and increasing in creation order. ``submit_ns`` and
    ``taken_ns`` are ``time.perf_counter_ns`` stamps (the tracer's
    clock): accepted by ``submit``, and taken out of the ingress or the
    carry into a batch; ``batch_id`` names that batch.
    """

    def __init__(self, payload, n_rows: int, n_bytes: int):
        self.id = next(_request_ids)
        self.payload = payload
        self.n_rows = n_rows
        self.n_bytes = n_bytes
        self.done_t: float | None = None
        self.submit_ns: int | None = None
        self.taken_ns: int | None = None
        self.batch_id: int | None = None
        self._done = threading.Event()
        self._result: dict | None = None
        self._error: BaseException | None = None

    def result(self, timeout: float | None = None) -> dict:
        """Blocking fetch: ``{label, dense, sparse}`` numpy arrays with
        exactly ``n_rows`` rows (padding already stripped)."""
        if not self._done.wait(timeout):
            raise TimeoutError("stream request not completed in time")
        if self._error is not None:
            raise self._error
        return self._result

    @property
    def done(self) -> bool:
        return self._done.is_set()

    @property
    def submit_t(self) -> float | None:
        """``submit_ns`` in seconds, on ``time.perf_counter``'s clock."""
        return None if self.submit_ns is None else self.submit_ns * 1e-9

    @property
    def latency_s(self) -> float | None:
        if self.submit_t is None or self.done_t is None:
            return None
        return self.done_t - self.submit_t

    # -- service side ------------------------------------------------- #
    def _finish(self, result: dict) -> None:
        self._result = result
        if self.done_t is None:
            self.done_t = time.perf_counter()
        self._done.set()

    def _fail(self, err: BaseException) -> None:
        self._error = err
        if self.done_t is None:
            self.done_t = time.perf_counter()
        self._done.set()


class CompositeRequest:
    """Caller handle for an oversized request served as several sub-chunks.

    The scheduler's buckets cap one micro-batch at ``max_rows`` /
    ``max_bytes``; a larger request is **split** at submission into
    bucket-sized sub-requests (:meth:`MicroBatchScheduler.split`) whose
    row spans reassemble, in order, to the original request — so bulk
    callers get the same ``result()`` surface instead of a rejection.
    Sub-requests flow through the ordinary FIFO path (they are coalesced
    and padded like any other request), and each records its own
    latency/throughput metrics.
    """

    def __init__(self, parts: list[StreamRequest]):
        if not parts:
            raise ValueError("composite request needs at least one part")
        self.parts = parts
        self.n_rows = sum(p.n_rows for p in parts)
        self.n_bytes = sum(p.n_bytes for p in parts)

    def result(self, timeout: float | None = None) -> dict:
        """Blocking fetch: the per-part results concatenated back into
        one ``{label, dense, sparse}`` table of exactly ``n_rows`` rows
        (sub-chunk order == original row order). ``timeout`` bounds the
        *total* wait across parts."""
        deadline = None if timeout is None else time.monotonic() + timeout
        outs = []
        for p in self.parts:
            left = None if deadline is None else max(0.0, deadline - time.monotonic())
            outs.append(p.result(left))
        return {
            k: np.concatenate([o[k] for o in outs])
            for k in ("label", "dense", "sparse")
        }

    @property
    def done(self) -> bool:
        return all(p.done for p in self.parts)

    @property
    def submit_t(self) -> float | None:
        return self.parts[0].submit_t

    @property
    def done_t(self) -> float | None:
        ts = [p.done_t for p in self.parts]
        return None if any(t is None for t in ts) else max(ts)

    @property
    def latency_s(self) -> float | None:
        if self.submit_t is None or self.done_t is None:
            return None
        return self.done_t - self.submit_t


def make_request(payload, config: pipeline_lib.PipelineConfig) -> StreamRequest:
    """Validate + wrap a raw payload for ``config.input_format``."""
    schema = config.schema
    if config.input_format == "utf8":
        buf = np.asarray(payload, dtype=np.uint8)
        if buf.ndim != 1 or buf.size == 0:
            raise ValueError("utf8 payload must be a non-empty 1-D byte array")
        if buf[-1] != schema_lib.NEWLINE:
            raise ValueError("utf8 payload must hold whole rows (end with \\n)")
        n_rows = int((buf == schema_lib.NEWLINE).sum())
        return StreamRequest(buf, n_rows=n_rows, n_bytes=int(buf.size))
    cols = {k: np.asarray(payload[k], dtype=np.int32) for k in ("label", "dense", "sparse")}
    if cols["label"].ndim != 1:
        raise ValueError(f"binary label must be 1-D, got shape {cols['label'].shape}")
    n_rows = cols["label"].shape[0]
    if n_rows == 0:
        raise ValueError("binary payload must hold at least one row")
    if cols["dense"].shape != (n_rows, schema.n_dense) or cols["sparse"].shape != (
        n_rows,
        schema.n_sparse,
    ):
        raise ValueError(
            f"binary payload shapes {cols['dense'].shape}/{cols['sparse'].shape} "
            f"do not match schema (n_dense={schema.n_dense}, n_sparse={schema.n_sparse})"
        )
    return StreamRequest(cols, n_rows=n_rows, n_bytes=0)


@dataclasses.dataclass
class Bucket:
    """One fixed capacity: rows, utf8 byte capacity, compiled transform."""

    rows: int
    chunk_bytes: int
    transform: pipeline_lib.FrozenVocabTransform


@dataclasses.dataclass
class MicroBatch:
    """A packed step: the padded chunk plus per-request output row spans.

    ``id`` is unique and increasing in assembly order. The ``*_ns``
    fields are ``time.perf_counter_ns`` stamps of the batch's life: its
    requests taken (set by the service), assembled, the dispatch call
    returned, outputs ready on the device, and outputs copied and sliced
    per request (``route``)."""

    bucket: Bucket
    requests: list[StreamRequest]
    spans: list[tuple[int, int]]
    chunk: object  # uint8 [chunk_bytes] (utf8) or {label,dense,sparse,valid} dict
    id: int = dataclasses.field(default_factory=lambda: next(_batch_ids))
    taken_ns: int | None = None
    assembled_ns: int | None = None
    dispatched_ns: int | None = None
    ready_ns: int | None = None
    routed_ns: int | None = None

    @property
    def n_rows(self) -> int:
        return self.spans[-1][1] if self.spans else 0

    @property
    def request_bytes(self) -> int:
        return sum(r.n_bytes for r in self.requests)

    @property
    def bucket_bytes(self) -> int:
        """The utf8 chunk's byte capacity; 0 for a binary chunk, whose
        requests carry 0 bytes too."""
        return self.bucket.chunk_bytes if isinstance(self.chunk, np.ndarray) else 0


class MicroBatchScheduler:
    """Packs requests into bucketed fixed-shape chunks and routes results.

    Pure packing + dispatch — no threads. The service loop drives it:
    its ``_gather`` coalesces queued requests FIFO using :meth:`fits`,
    then ``assemble`` builds the padded chunk, ``dispatch`` launches the
    (async) device transform, and ``route`` blocks on the result and
    slices it back per request.

    Args:
      config: the shared :class:`~repro.core.pipeline.PipelineConfig`
        (``max_rows_per_chunk``/``chunk_bytes`` are overridden per bucket).
      vocabulary: the frozen offline-built vocabulary.
      bucket_rows: ascending row capacities. A request larger than the
        biggest bucket is not rejected: the service **splits** it at
        submission into bucket-sized sub-chunks (:meth:`split`) whose
        results reassemble per row span behind one
        :class:`CompositeRequest` handle.
      bytes_per_row: utf8 byte budget per bucket row. The default —
        ``schema.max_row_bytes`` — guarantees any row-fitting batch also
        byte-fits; smaller values trade buffer memory for the chance that
        the byte axis, not the row axis, picks the bucket.
      registry: the :class:`repro.obs.Registry` the packing metrics land
        in (exact valid/bucket row and request/bucket byte totals, whose
        ratios are the row and byte fill; the recompile counter). The
        service passes its own; standalone schedulers get a private one.
    """

    def __init__(
        self,
        config: pipeline_lib.PipelineConfig,
        vocabulary: vocab_lib.Vocabulary,
        bucket_rows: tuple[int, ...] = DEFAULT_BUCKET_ROWS,
        bytes_per_row: int | None = None,
        registry: obs.Registry | None = None,
    ):
        if not bucket_rows:
            raise ValueError("need at least one bucket capacity")
        self.config = config
        self.schema = config.schema
        self.plan = config.resolved_plan()
        self.registry = registry if registry is not None else obs.Registry()
        self._c_batches = self.registry.counter(
            "stream.batches_total", "dispatched micro-batches"
        )
        self._c_valid_rows = self.registry.counter(
            "stream.valid_rows_total", "request rows packed into buckets"
        )
        self._c_bucket_rows = self.registry.counter(
            "stream.bucket_rows_total", "bucket row capacity dispatched"
        )
        self._c_request_bytes = self.registry.counter(
            "stream.request_bytes_total", "utf8 request bytes packed into buckets"
        )
        self._c_bucket_bytes = self.registry.counter(
            "stream.bucket_bytes_total", "utf8 bucket byte capacity dispatched"
        )
        # Steady-state shape discipline, as a first-class signal: any
        # executable compiled past warmup increments this (the
        # no-recompile guarantee asserts it stays flat —
        # tests/test_stream_service.py).
        self._c_recompiles = self.registry.counter(
            "stream.recompiles_total", "executables compiled at dispatch"
        )
        self.bytes_per_row = (
            int(bytes_per_row) if bytes_per_row else config.schema.max_row_bytes
        )
        self.buckets: list[Bucket] = []
        for rows in sorted(set(int(r) for r in bucket_rows)):
            bucket_cfg = dataclasses.replace(
                config,
                max_rows_per_chunk=rows,
                chunk_bytes=rows * self.bytes_per_row,
            )
            self.buckets.append(
                Bucket(
                    rows=rows,
                    chunk_bytes=rows * self.bytes_per_row,
                    transform=pipeline_lib.FrozenVocabTransform(
                        vocabulary, config=bucket_cfg
                    ),
                )
            )

    # -- capacity queries --------------------------------------------- #
    @property
    def max_rows(self) -> int:
        return self.buckets[-1].rows

    @property
    def max_bytes(self) -> int:
        return self.buckets[-1].chunk_bytes

    def admits(self, req: StreamRequest) -> bool:
        """Whether the request fits the largest bucket at all."""
        if req.n_rows > self.max_rows:
            return False
        return self.config.input_format != "utf8" or req.n_bytes <= self.max_bytes

    def split(self, req: StreamRequest) -> list[StreamRequest]:
        """Split an oversized request into admitted, bucket-sized parts.

        Sub-chunks cut at whole-row boundaries, each within the largest
        bucket on both the row and (utf8) byte axes; concatenating the
        parts' rows in order reproduces the original request exactly. An
        already-admitted request passes through as ``[req]``. Raises
        :class:`ValueError` only when a *single row* exceeds the largest
        bucket's byte capacity (no split can help there).
        """
        if self.admits(req):
            return [req]
        parts: list[StreamRequest] = []
        if self.config.input_format == "utf8":
            buf = np.asarray(req.payload)
            # exclusive end byte of every encoded row (incl. its newline)
            ends = np.flatnonzero(buf == schema_lib.NEWLINE) + 1
            row0, byte0 = 0, 0
            while row0 < ends.size:
                hi = min(row0 + self.max_rows, ends.size)
                # the byte axis may bind first: longest whole-row prefix
                hi = min(
                    hi,
                    int(np.searchsorted(ends, byte0 + self.max_bytes, side="right")),
                )
                if hi <= row0:
                    raise ValueError(
                        f"row {row0} of the request is {int(ends[row0] - byte0)} "
                        f"bytes — larger than the biggest bucket "
                        f"({self.max_bytes} bytes); no row-aligned split exists"
                    )
                part = buf[byte0 : int(ends[hi - 1])]
                parts.append(
                    StreamRequest(part, n_rows=hi - row0, n_bytes=int(part.size))
                )
                row0, byte0 = hi, int(ends[hi - 1])
        else:
            cols = req.payload
            for lo in range(0, req.n_rows, self.max_rows):
                hi = min(lo + self.max_rows, req.n_rows)
                parts.append(
                    StreamRequest(
                        {k: v[lo:hi] for k, v in cols.items()},
                        n_rows=hi - lo,
                        n_bytes=0,
                    )
                )
        return parts

    def fits(self, rows: int, nbytes: int, req: StreamRequest) -> bool:
        """Whether ``req`` still fits a batch already holding rows/bytes."""
        if rows + req.n_rows > self.max_rows:
            return False
        return (
            self.config.input_format != "utf8"
            or nbytes + req.n_bytes <= self.max_bytes
        )

    def select_bucket(self, rows: int, nbytes: int) -> Bucket:
        """Smallest bucket covering the batch on both axes."""
        for b in self.buckets:
            if rows <= b.rows and (
                self.config.input_format != "utf8" or nbytes <= b.chunk_bytes
            ):
                return b
        raise ValueError(
            f"batch of {rows} rows / {nbytes} bytes exceeds the largest bucket "
            f"({self.max_rows} rows / {self.max_bytes} bytes)"
        )

    # -- packing ------------------------------------------------------- #
    def assemble(self, requests: list[StreamRequest]) -> MicroBatch:
        """Pack coalesced requests into one fixed-shape padded chunk."""
        spans, row = [], 0
        for r in requests:
            spans.append((row, row + r.n_rows))
            row += r.n_rows
        nbytes = sum(r.n_bytes for r in requests)
        bucket = self.select_bucket(row, nbytes)
        self._c_batches.add(1)
        self._c_valid_rows.add(row)
        self._c_bucket_rows.add(bucket.rows)

        if self.config.input_format == "utf8":
            chunk = np.zeros(bucket.chunk_bytes, dtype=np.uint8)
            cursor = 0
            for r in requests:
                chunk[cursor : cursor + r.n_bytes] = r.payload
                cursor += r.n_bytes
        else:
            cap = bucket.rows
            label = np.zeros(cap, np.int32)
            dense = np.zeros((cap, self.schema.n_dense), np.int32)
            sparse = np.zeros((cap, self.schema.n_sparse), np.int32)
            cursor = 0
            for r in requests:
                n = r.n_rows
                label[cursor : cursor + n] = r.payload["label"]
                dense[cursor : cursor + n] = r.payload["dense"]
                sparse[cursor : cursor + n] = r.payload["sparse"]
                cursor += n
            chunk = {
                "label": label,
                "dense": dense,
                "sparse": sparse,
                "valid": np.arange(cap) < row,
            }
        batch = MicroBatch(bucket=bucket, requests=requests, spans=spans, chunk=chunk)
        for r in requests:
            r.batch_id = batch.id
        self._c_request_bytes.add(nbytes)
        self._c_bucket_bytes.add(batch.bucket_bytes)
        batch.assembled_ns = time.perf_counter_ns()
        return batch

    # -- execution ----------------------------------------------------- #
    def dispatch(self, batch: MicroBatch) -> schema_lib.ProcessedBatch:
        """Launch the bucket's compiled transform. JAX dispatch is async:
        the call returns immediately with device futures, which is what
        lets the service assemble batch *i+1* while *i* transforms.

        Any executable compiled *by this call* (jit cache growth across
        the dispatch) increments ``stream.recompiles_total`` — warmup
        shows ``len(buckets)`` compiles, steady state must show zero.
        """
        before = batch.bucket.transform.compile_cache_size()
        out = batch.bucket.transform(batch.chunk)
        grew = batch.bucket.transform.compile_cache_size() - before
        if grew > 0:
            self._c_recompiles.add(grew)
        batch.dispatched_ns = time.perf_counter_ns()
        return out

    def route(self, batch: MicroBatch, out: schema_lib.ProcessedBatch) -> list[dict]:
        """Block on the device result, stamp ``ready_ns``; copy it to the
        host and slice it per request (batch order), stamp ``routed_ns``.
        The two phases are the ``stream/ready`` and ``stream/route``
        spans. The caller finishes the requests — the service records
        latency *before* unblocking waiters, so a metrics reset right
        after ``result()`` returns can never lose the record."""
        with obs.span("stream/ready", cat="stream", batch=batch.id):
            jax.block_until_ready(out)
        batch.ready_ns = time.perf_counter_ns()
        with obs.span("stream/route", cat="stream", batch=batch.id):
            label = np.asarray(out.label)
            dense = np.asarray(out.dense)
            sparse = np.asarray(out.sparse)
            results = [
                {"label": label[lo:hi], "dense": dense[lo:hi], "sparse": sparse[lo:hi]}
                for (lo, hi) in batch.spans
            ]
        batch.routed_ns = time.perf_counter_ns()
        return results

    # -- vocab + compile bookkeeping ----------------------------------- #
    @property
    def compiled(self):
        """The :class:`~repro.core.plan_compiler.CompiledPlan` the buckets
        execute — one program, instantiated per bucket shape."""
        return self.buckets[0].transform.compiled

    def swap_vocabulary(self, vocabulary: vocab_lib.Vocabulary) -> None:
        """Swap the frozen vocabulary on every bucket (between steps)."""
        for b in self.buckets:
            b.transform.swap_vocabulary(vocabulary)

    def compile_cache_size(self) -> int:
        """Total compiled executables across buckets — the shape
        discipline means this saturates at warmup and never grows."""
        return sum(b.transform.compile_cache_size() for b in self.buckets)
