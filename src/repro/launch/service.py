"""Async serving front-end: request queue, admission control, cross-tenant
micro-batching, background maintenance, SLO instrumentation (DESIGN.md §12).

The engines (``FGFTServeEngine``, ``RaggedFGFTServeEngine``) are library
objects: one caller, one fused dispatch at a time.  A production front door
sees the opposite shape — many independent tenants, each asking for a few
signal rows on ONE graph, arriving asynchronously.  ``AsyncFGFTService``
bridges the two:

  * ``submit(graph_id, signal, tier=...)`` enqueues one request and
    returns a future.  Admission control is a BOUNDED queue: past
    ``max_queue`` pending requests the submit fails fast with a typed
    ``ShedError`` (the caller can retry/degrade) instead of letting the
    queue grow without bound.
  * a dispatcher thread COALESCES queued requests that share a dispatch
    group — same size bucket, same quality tier (or the filter bank) —
    and answers them all in one dispatch: same-graph requests stack
    along the row axis of that graph's zero-padded block, and each
    graph's block is walked alone by a row-selecting program, launched
    back to back (placed engines, and buckets whose whole block is
    small, walk one whole-bucket block instead).
    Row counts are quantized (``quantize_rows``) so steady-state
    dispatches reuse a handful of compiled programs.
  * the dispatcher only launches; a completion thread waits for each
    dispatch, pulls its answers and replies, in launch order, while the
    dispatcher builds and launches the next: at most ``PIPELINE_DEPTH``
    dispatches are launched and unreplied.
  * ``maintain()`` (drift scoring, refresh/extend/refit, versioned hot
    swap — DESIGN.md §11) runs on a background maintainer thread, never
    on the serving path.  The hot path takes no lock around jitted calls:
    each launch reads the engine's immutable ``_LiveVersion`` once
    (``step_versioned``), so every response is served by exactly one
    consistent version and carries that version number.
  * every stage is instrumented with an INJECTABLE clock: per-tier
    latency recorders (queue wait / service / total, exact nearest-rank
    p50/p99), queue depth + peak, batch occupancy, shed counts and
    version-swap counts, surfaced through ``stats()`` and persisted with
    ``save()`` next to the engine checkpoint.

Unit tests drive the whole pipeline deterministically: ``auto_start=False``
plus ``drain_once()`` runs the dispatcher inline on the caller's thread,
and a fake clock makes every latency figure exact (tests/test_service.py).

CPU smoke:
  python -m repro.launch.serve --serve-async --graphs 4 \
      --graph-n 32 --load-requests 64 --load-workers 4
"""
from __future__ import annotations

import itertools
import json
import math
import os
import pathlib
import queue
import threading
import time
import weakref
from collections import deque
from concurrent.futures import Future
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from repro import obs
from repro.obs.metrics import (bucket_counts, geometric_edges,
                               merge_histograms)  # noqa: F401 — merge_histograms re-exported; histogram() below builds on the same ladder

BANK = "__bank__"          # pseudo-tier routing a request to the filter bank

#: Largest whole-bucket block, in elements (graphs x padded rows x
#: width), that a bucket of several graphs still walks in one launch
#: rather than one launch a served graph.  Every stage of the walk pays
#: a fixed cost whatever its block, about what streaming 2**19 f32
#: elements through the stage costs (TPU v5e, n = 4096: 14 us fixed and
#: 14 us more for each 128 rows).  At or below this size the graphs a
#: per-graph layout skips save less than that fixed cost, and each
#: launch beyond the first pays it again.
WHOLE_BLOCK_ELEMENTS = 1 << 19

#: Dispatches launched and not yet replied: one on the device, one whose
#: answers the completion thread pulls and replies.  A deeper pipeline
#: buys nothing while the host stages, not the device, set the pace.
PIPELINE_DEPTH = 2

# span lanes (``Tracer`` tids) of the pipeline's slots: two dispatches
# whose spans overlap in time record under different lanes, so a reader
# pairs each dispatch with its stages by lane and time containment
_SPAN_LANES = itertools.count(1)

# -- serving-path telemetry (DESIGN.md §15): every live service records
# into the process-wide registry; per-service isolation comes from the
# `service` label ------------------------------------------------------
_OBS_SUBMITTED = obs.counter("service_requests_total",
                             "requests admitted to the bounded queue",
                             ("service", "tier"))
_OBS_SHED = obs.counter("service_shed_total",
                        "requests rejected by admission control",
                        ("service",))
_OBS_DISPATCHES = obs.counter("service_dispatches_total",
                              "coalesced fused dispatches",
                              ("service", "tier"))
_OBS_SIGNAL_ELEMENTS = obs.counter(
    "service_signal_elements_total",
    "signal elements (rows x graph size) the dispatches carried",
    ("service", "tier"))
_OBS_BLOCK_ELEMENTS = obs.counter(
    "service_block_elements_total",
    "block elements (padded rows x bucket width, summed over the graph "
    "blocks) the dispatches walked", ("service", "tier"))
_OBS_GRAPH_BLOCKS = obs.counter(
    "service_graph_blocks_total",
    "graph blocks (one graph's padded rows) the dispatches walked",
    ("service", "tier"))
_OBS_DENSE_BLOCKS = obs.counter(
    "service_dense_blocks_total",
    "graph blocks served by the dense lowering (matmuls against dense "
    "legs, no stage walked)", ("service", "tier"))
_OBS_OVERLAPPED = obs.counter(
    "service_overlapped_dispatches_total",
    "dispatches launched while an earlier one was still unreplied",
    ("service", "tier"))
_OBS_QUEUE_DEPTH = obs.gauge("service_queue_depth",
                             "queue depth sampled at the last dispatch",
                             ("service",))
_OBS_STAGE_S = obs.histogram(
    "service_stage_seconds",
    "per-request stage latency on the obs geometric ladder",
    ("service", "tier", "stage"))

# every live service registers here so a test harness (tests/conftest.py's
# thread-leak guard) can force-stop leaked services instead of hanging the
# interpreter at exit on their non-daemon threads
_LIVE_SERVICES: "weakref.WeakSet" = weakref.WeakSet()


def shutdown_all_services(timeout: float = 5.0) -> int:
    """Best-effort close() of every still-open service; returns how many
    were closed.  An escape hatch for test harnesses — production code
    closes its own services (context manager)."""
    closed = 0
    for svc in list(_LIVE_SERVICES):
        if svc._threads:
            try:
                svc.close(timeout=timeout)
                closed += 1
            except RuntimeError:
                pass
    return closed


class ServiceClosed(RuntimeError):
    """submit() after close(): the service no longer accepts work."""


class ShedError(RuntimeError):
    """Typed admission-control rejection: the bounded request queue is
    full, so this request was shed instead of queued (the caller sees the
    overload immediately and can retry, back off, or drop to a cheaper
    tier).  Carries the observed depth and the configured bound."""

    def __init__(self, queue_depth: int, max_queue: int, graph_id: int):
        super().__init__(
            f"request for graph {graph_id} shed: queue depth "
            f"{queue_depth} >= max_queue {max_queue}")
        self.queue_depth = queue_depth
        self.max_queue = max_queue
        self.graph_id = graph_id


def quantize_rows(rows: int, quantum: int = 8) -> int:
    """Smallest power-of-two multiple of ``quantum`` >= rows.

    Coalesced blocks pad their row axis to a quantized count so the
    steady state cycles through O(log max_rows) compiled programs instead
    of one per distinct occupancy (the fig12 compile-count gate)."""
    if rows < 1:
        raise ValueError(f"rows must be >= 1, got {rows}")
    if quantum < 1:
        raise ValueError(f"quantum must be >= 1, got {quantum}")
    q = quantum
    while q < rows:
        q *= 2
    return q


class LatencyRecorder:
    """Deterministic latency/size statistics keyed by string.

    Retains up to ``max_samples`` most-recent samples per key (plus exact
    running count/total/max over ALL samples) and computes NEAREST-RANK
    percentiles over the retained window — pure arithmetic over recorded
    durations, no clock of its own, so a fake clock upstream makes every
    figure exact (tests/test_service.py asserts the math with zero
    wall-clock sensitivity)."""

    def __init__(self, max_samples: int = 8192):
        if max_samples < 1:
            raise ValueError(f"max_samples must be >= 1, got {max_samples}")
        self.max_samples = int(max_samples)
        self._lock = threading.Lock()
        self._samples: Dict[str, deque] = {}
        self._count: Dict[str, int] = {}
        self._total: Dict[str, float] = {}
        self._max: Dict[str, float] = {}

    def record(self, key: str, seconds: float):
        s = float(seconds)
        if not math.isfinite(s) or s < 0.0:
            raise ValueError(f"latency sample must be finite and >= 0, "
                             f"got {seconds!r}")
        with self._lock:
            dq = self._samples.get(key)
            if dq is None:
                dq = self._samples[key] = deque(maxlen=self.max_samples)
            dq.append(s)
            self._count[key] = self._count.get(key, 0) + 1
            self._total[key] = self._total.get(key, 0.0) + s
            self._max[key] = max(self._max.get(key, 0.0), s)

    def keys(self) -> List[str]:
        with self._lock:
            return sorted(self._samples)

    def count(self, key: str) -> int:
        with self._lock:
            return self._count.get(key, 0)

    def percentile(self, key: str, q: float) -> float:
        """Nearest-rank percentile (q in [0, 100]) over the retained
        samples: the smallest sample s.t. >= q% of samples are <= it."""
        if not 0.0 <= q <= 100.0:
            raise ValueError(f"q must be in [0, 100], got {q}")
        with self._lock:
            xs = sorted(self._samples.get(key, ()))
        if not xs:
            raise KeyError(f"no samples recorded under {key!r}")
        rank = max(int(math.ceil(q / 100.0 * len(xs))), 1)
        return xs[rank - 1]

    def histogram(self, key: str, origin: float = 1e-4,
                  base: float = 2.0,
                  bucket_count: int = 26) -> List[dict]:
        """Geometric-bucket histogram of the retained samples:
        ``[{"le_s": bound, "count": k}, ...]`` over the obs bounded
        geometric ladder (``obs.metrics.geometric_edges``): a 0-bucket,
        ``bucket_count`` edges origin·base^i, and a final +inf bucket.
        The edge list is a function of the PARAMETERS only — length
        ``bucket_count + 2`` no matter what was recorded — so
        histograms from different runs/processes merge by position
        (``merge_histograms``).  The pre-obs version grew the ladder to
        the max retained sample, which silently broke exactly that
        merge."""
        with self._lock:
            xs = list(self._samples.get(key, ()))
        edges = geometric_edges(origin, base, bucket_count)
        counts = bucket_counts(edges, xs)
        return [{"le_s": le, "count": c} for le, c in zip(edges, counts)]

    def summary(self) -> Dict[str, dict]:
        """{key: {count, mean_s, p50_s, p99_s, max_s}} over every key."""
        out = {}
        for key in self.keys():
            with self._lock:
                count = self._count[key]
                total = self._total[key]
                mx = self._max[key]
            out[key] = {"count": count, "mean_s": total / count,
                        "p50_s": self.percentile(key, 50.0),
                        "p99_s": self.percentile(key, 99.0),
                        "max_s": mx}
        return out


@dataclass
class ServeResult:
    """One answered request: the filtered block plus its provenance.
    ``version`` is the engine serving version that produced ``y`` — read
    ONCE together with the tables/spectra that served the dispatch, so it
    can never describe a different version than the payload."""

    y: np.ndarray
    graph_id: int
    tier: str
    version: int
    queue_s: float
    service_s: float
    total_s: float
    batch_size: int
    #: the obs trace id stamped at submit and threaded queue ->
    #: coalesce -> dispatch -> reply: ``default_tracer().spans(
    #: trace_id=r.trace_id)`` returns exactly this request's
    #: queue/batch/execute/request spans, and their durations telescope
    #: to ``total_s`` exactly under a fake clock (DESIGN.md §15)
    trace_id: int = 0


@dataclass
class _Request:
    graph_id: int
    signal: np.ndarray            # (r, n_i) float32, n_i = true graph size
    tier: str                     # resolved tier name, or BANK
    group: Tuple[Any, str]        # (bucket key, tier): the coalescing key
    future: Future = field(default_factory=Future)
    t_submit: float = 0.0
    trace_id: int = 0


@dataclass(frozen=True)
class _Layout:
    """One dispatch's graph blocks: request -> (block, row offset), each
    block's batch row (None on an unbatched engine) and padded row
    count, whether the blocks stack into one whole-bucket (b, r_pad, n)
    launch, and the signal vs walked elements (the bank's filter count
    multiplies both, so it is left out), and the engine's lowering
    ("dense" or "walk")."""

    offsets: List[Tuple[int, int]]
    rows: List[Optional[int]]
    r_pads: List[int]
    whole: bool
    n: int
    signal_elements: int
    block_elements: int
    lowering: str

    @property
    def b(self) -> int:
        """Graph blocks walked."""
        return len(self.r_pads)

    @property
    def r_pad(self) -> int:
        return max(self.r_pads)


@dataclass
class _Dispatch:
    """One coalesced dispatch between its two stages: ``_launch`` lays
    it out, builds, puts and launches it, and ``_complete`` waits for
    it, pulls and replies.  ``tid`` is the span lane its ``serve.*``
    spans record under, ``ts`` its start on the tracer's clock,
    ``in_flight`` the dispatches unreplied at its launch (None inline),
    ``out`` each launch's (device answer, serving version), and
    ``mirror`` its open ``serve.dispatch`` profiler annotation."""

    batch: List[_Request]
    lay: _Layout
    label: str
    t_collect: float
    t0: float
    tid: int
    in_flight: Optional[int]
    args: Optional[dict]
    ts: float = 0.0
    out: list = field(default_factory=list)
    mirror: Any = None

    def open_mirror(self, tracer):
        """Enter the ``serve.dispatch`` annotation on this thread, unless
        it is open already (the inline path runs both stages under
        one)."""
        if (self.mirror is None and tracer.enabled
                and tracer.annotation is not None):
            self.mirror = tracer.annotation("serve.dispatch",
                                            **(self.args or {}))
            self.mirror.__enter__()

    def close_mirror(self):
        if self.mirror is not None:
            self.mirror.__exit__(None, None, None)
            self.mirror = None


@dataclass(frozen=True)
class _Route:
    """Where one graph's requests dispatch: which engine, which batch row,
    its bucket key (None for a uniform fleet) and true size."""

    engine: Any
    bucket: Any
    row: int
    size: int
    batched: bool


def _build_routes(engine) -> List[_Route]:
    """Per-graph dispatch routes for a uniform engine or a ragged router
    (deferred import: serve.py is the module that defines the engines)."""
    from repro.launch.serve import RaggedFGFTServeEngine
    if isinstance(engine, RaggedFGFTServeEngine):
        routes = []
        for gid, w in enumerate(engine.widths):
            routes.append(_Route(engine=engine.engines[w], bucket=w,
                                 row=engine.bucket_of[w].index(gid),
                                 size=engine.sizes[gid], batched=True))
        return routes
    basis = engine.basis
    if basis.batched:
        b = int(np.atleast_1d(np.asarray(basis.spectrum)).shape[0])
        sizes = (np.full(b, basis.n) if basis.sizes is None
                 else np.atleast_1d(np.asarray(basis.sizes)))
        return [_Route(engine=engine, bucket=None, row=gid,
                       size=int(sizes[gid]), batched=True)
                for gid in range(b)]
    size = basis.n if basis.sizes is None else int(np.asarray(basis.sizes))
    return [_Route(engine=engine, bucket=None, row=0, size=size,
                   batched=False)]


class AsyncFGFTService:
    """Queue -> coalesce -> fused dispatch -> versioned swap (DESIGN.md
    §12) over an ``FGFTServeEngine`` or ``RaggedFGFTServeEngine``.

    ``h``: optional spectral response applied on tier dispatches (same
    contract as ``engine.step``).  ``maintain_interval``: seconds between
    background maintenance ticks for dynamic engines (``None`` = only on
    ``request_maintain()``/``maintain_now()``).  ``clock``: injectable
    monotonic clock for all SLO timestamps.  ``auto_start=False`` skips
    the threads; tests then pump the queue inline with ``drain_once()``."""

    def __init__(self, engine, *, h: Optional[Callable] = None,
                 max_queue: int = 128, max_batch: int = 8,
                 row_quantum: int = 8,
                 maintain_interval: Optional[float] = None,
                 clock: Callable[[], float] = time.monotonic,
                 latency_window: int = 8192, auto_start: bool = True,
                 name: str = "fgft-svc"):
        if max_queue < 1:
            raise ValueError(f"max_queue must be >= 1, got {max_queue}")
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        self.engine = engine
        self.max_queue = int(max_queue)
        self.max_batch = int(max_batch)
        self.row_quantum = int(row_quantum)
        self.maintain_interval = maintain_interval
        self.name = name
        self._h = h
        self._clock = clock
        self._routes = _build_routes(engine)
        self.latency = LatencyRecorder(max_samples=latency_window)
        # hot-path obs handles: label children resolved ONCE here —
        # per-request label kwargs would cost more than the recording
        # itself (the fig15 traced-vs-untraced QPS gate)
        self._obs_shed = _OBS_SHED.labels(service=self.name)
        self._obs_depth = _OBS_QUEUE_DEPTH.labels(service=self.name)
        self._obs_submitted: Dict[str, Any] = {}
        self._obs_dispatch: Dict[str, Any] = {}
        self._obs_stage: Dict[str, dict] = {}
        # one lock guards the queue and every counter; it is NEVER held
        # across an engine dispatch (jitted calls run lock-free — the
        # engine's atomic _LiveVersion read is the only synchronization
        # the hot path needs)
        self._cond = threading.Condition()
        self._queue: deque = deque()
        self._closed = False
        self._submitted = 0
        self._served = 0
        self._shed = 0
        self._errors = 0
        self._depth_peak = 0
        self._dispatches = 0
        self._coalesced = 0
        self._signal_elements = 0
        self._block_elements = 0
        self._graph_blocks = 0
        self._dense_blocks = 0
        self._overlapped = 0
        self._occ_max = 0
        self._maintain_ticks = 0
        self._maintain_errors = 0
        self._swaps = 0
        self._last_action: Any = None
        self._last_maint_error: Optional[BaseException] = None
        self._m_wake = threading.Event()
        self._m_done = threading.Condition()
        # the dispatch pipeline: a free slot is its span lane; the
        # dispatcher hands launched dispatches to the completion thread
        self._lanes: queue.Queue = queue.Queue()
        for _ in range(PIPELINE_DEPTH):
            self._lanes.put(next(_SPAN_LANES))
        self._handoff: queue.Queue = queue.Queue(maxsize=PIPELINE_DEPTH)
        self._unreplied = 0
        self._threads: List[threading.Thread] = []
        _LIVE_SERVICES.add(self)
        if auto_start:
            self.start()

    # -- lifecycle ---------------------------------------------------------

    def start(self):
        """Spawn the dispatcher and completion (and, for dynamic engines,
        the maintainer) threads; idempotent."""
        if self._threads:
            return
        if self._closed:
            raise ServiceClosed("service already closed")
        for target, role in ((self._dispatch_loop, "dispatch"),
                             (self._complete_loop, "complete")):
            self._threads.append(threading.Thread(
                target=target, name=f"{self.name}-{role}"))
        if getattr(self.engine, "dynamic", False):
            maint = threading.Thread(target=self._maintain_loop,
                                     name=f"{self.name}-maintain")
            self._threads.append(maint)
        for t in self._threads:
            t.start()

    def close(self, timeout: Optional[float] = 30.0):
        """Stop accepting work, drain the queue, join every thread.  The
        dispatcher launches all already-queued requests before exiting,
        and the completion thread answers every launched dispatch before
        it exits, so no accepted future is left unresolved."""
        with self._cond:
            self._closed = True
            self._cond.notify_all()
        self._m_wake.set()
        for t in self._threads:
            t.join(timeout)
        leaked = [t.name for t in self._threads if t.is_alive()]
        if leaked:
            raise RuntimeError(f"service threads failed to stop: {leaked}")
        self._threads = []

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    # -- submission (admission control) ------------------------------------

    def submit(self, graph_id: int, signal, tier: Optional[str] = None,
               bank: bool = False) -> Future:
        """Enqueue one request for ``graph_id``: ``signal`` is (r, n_i)
        (or (n_i,), promoted to one row).  ``tier`` picks a quality tier
        (default: the engine's best); ``bank=True`` routes through the
        fused filter bank instead.  Returns a future resolving to a
        ``ServeResult``; raises ``ShedError`` when the bounded queue is
        full and ``ServiceClosed`` after ``close()``."""
        if bank and tier is not None:
            raise ValueError("a request is either tiered or bank, not both")
        try:
            route = self._routes[graph_id] if graph_id >= 0 else None
        except IndexError:
            route = None
        if route is None:
            raise ValueError(f"graph_id {graph_id} not in fleet of "
                             f"{len(self._routes)}")
        x = np.asarray(signal, np.float32)
        if x.ndim == 1:
            x = x[None]
        if x.ndim != 2 or x.shape[1] != route.size:
            raise ValueError(f"signal for graph {graph_id} must be "
                             f"(r, {route.size}), got {x.shape}")
        if bank:
            if route.engine._live.bank is None:
                raise ValueError("engine was built without filter "
                                 "responses; bank requests unavailable")
            tier = BANK
        elif tier is None:
            tier = route.engine.default_tier
        elif tier not in route.engine._live.tiers:
            raise ValueError(f"unknown tier {tier!r}; engine serves "
                             f"{sorted(route.engine._live.tiers)}")
        req = _Request(graph_id=graph_id, signal=x, tier=tier,
                       group=(route.bucket, tier),
                       trace_id=obs.new_trace_id())
        req.t_submit = self._clock()
        with self._cond:
            if self._closed:
                raise ServiceClosed("service is closed")
            depth = len(self._queue)
            if depth >= self.max_queue:
                self._shed += 1
                self._obs_shed.inc()
                raise ShedError(depth, self.max_queue, graph_id)
            self._queue.append(req)
            self._submitted += 1
            self._depth_peak = max(self._depth_peak, depth + 1)
            self._cond.notify()
        label = "bank" if tier == BANK else tier
        child = self._obs_submitted.get(label)
        if child is None:
            child = self._obs_submitted[label] = _OBS_SUBMITTED.labels(
                service=self.name, tier=label)
        child.inc()
        return req.future

    # -- coalescing dispatcher ---------------------------------------------

    def _collect_locked(self):
        """Pop the head request plus up to max_batch-1 queued requests
        sharing its dispatch group (same bucket, same tier), preserving
        FIFO order within the group and leaving the rest queued."""
        head = self._queue.popleft()
        batch = [head]
        if len(batch) < self.max_batch:
            keep = deque()
            while self._queue and len(batch) < self.max_batch:
                req = self._queue.popleft()
                (batch if req.group == head.group else keep).append(req)
            keep.extend(self._queue)
            self._queue = keep
        return batch

    def drain_once(self) -> int:
        """Serve at most one coalesced batch inline on the CALLER's
        thread; returns the number of requests answered (0 if the queue
        was empty).  This is the dispatcher's unit of work, exposed so
        tests (and the fig12 synchronous baseline) can pump the queue
        deterministically without threads: both stages run serially,
        under one ``serve.dispatch`` span on the caller's thread."""
        with self._cond:
            if not self._queue:
                return 0
            batch = self._collect_locked()
        d = self._launch(batch, self._clock(), threading.get_ident(), None)
        if d is not None:
            self._complete(d)
        return len(batch)

    def _dispatch_loop(self):
        """Collect and launch: take a free pipeline slot (its span lane)
        before collecting, so the dispatcher blocks while
        ``PIPELINE_DEPTH`` dispatches are unreplied, then hand the
        launched dispatch to the completion thread."""
        try:
            while True:
                lane = self._lanes.get()
                with self._cond:
                    while not self._queue and not self._closed:
                        self._cond.wait()
                    if not self._queue:
                        return                  # closed and drained
                    batch = self._collect_locked()
                    in_flight = self._unreplied
                    self._unreplied += 1
                d = self._launch(batch, self._clock(), lane, in_flight)
                if d is None:                   # failed at launch
                    self._release(lane)
                    continue
                d.close_mirror()
                self._handoff.put(d)
        finally:
            self._handoff.put(None)             # the completion thread's stop

    def _complete_loop(self):
        """Wait, pull and reply each launched dispatch in launch order,
        then free its slot."""
        while True:
            d = self._handoff.get()
            if d is None:
                return
            try:
                self._complete(d)
            finally:
                self._release(d.tid)

    def _release(self, lane: int):
        with self._cond:
            self._unreplied -= 1
        self._lanes.put(lane)

    def _stage_children(self, label: str) -> dict:
        """Per-(tier, stage) bound histogram children, resolved once per
        tier label (benign if two threads race the first resolution —
        both children share one series key)."""
        cached = self._obs_stage.get(label)
        if cached is None:
            cached = self._obs_stage[label] = {
                stage: _OBS_STAGE_S.labels(service=self.name, tier=label,
                                           stage=stage)
                for stage in ("queue", "batch", "execute", "total")}
        return cached

    def _layout(self, batch: List[_Request]) -> "_Layout":
        """Where each request of ``batch`` lands and how much of the
        walked blocks is signal: same-graph requests stack along the row
        axis of their graph's block.

        An engine that serves row steps walks only the batch's graphs,
        each block's rows quantized alone, unless its bucket's whole
        block at the batch's quantized rows is at most
        ``WHOLE_BLOCK_ELEMENTS``: then one launch walks the whole
        bucket.  The choice depends only on the bucket and the quantized
        rows, and a per-graph block never walks fewer rows than the
        smallest count that chooses per-graph blocks, so each (bucket,
        tier, rows) has one program, the one a single request of those
        rows compiles.  A placed engine always walks the whole bucket,
        every block at the largest quantized row count (its batch axis
        is split over devices, and a row index must not cross them)."""
        route0 = self._routes[batch[0].graph_id]
        eng = route0.engine
        n = eng.basis.n
        q = self.row_quantum
        offsets = []                            # request -> (batch row, off)
        used: Dict[int, int] = {}               # batch row -> rows filled
        signal = 0
        for req in batch:
            row = self._routes[req.graph_id].row
            off = used.get(row, 0)
            offsets.append((row, off))
            used[row] = off + req.signal.shape[0]
            signal += req.signal.size           # rows x the graph's n
        b = int(np.shape(eng.basis.spectrum)[0]) if route0.batched else 1
        r_all = quantize_rows(max(used.values()), q)
        whole = route0.batched and (
            not eng.row_steps
            or 1 < b and b * r_all * n <= WHOLE_BLOCK_ELEMENTS)
        if whole:
            rows = list(range(b))
            r_pads = [r_all] * b
        else:
            # the fewest rows whose whole block exceeds the limit
            floor = (quantize_rows(WHOLE_BLOCK_ELEMENTS // (b * n) + 1, q)
                     if b > 1 else q)
            rows = list(used)                   # first-come block order
            block = {row: k for k, row in enumerate(rows)}
            offsets = [(block[row], off) for row, off in offsets]
            r_pads = [max(quantize_rows(used[row], q), floor)
                      for row in rows]
            if not route0.batched:
                rows = [None]
        return _Layout(offsets=offsets, rows=rows, r_pads=r_pads,
                       whole=whole, n=n, signal_elements=signal,
                       block_elements=sum(r_pads) * n,
                       lowering=eng.lowering)

    def _launch(self, batch: List[_Request], t_collect: float, tid: int,
                in_flight: Optional[int]) -> Optional[_Dispatch]:
        """The launch stage of one coalesced dispatch: lay it out, build
        and put every graph block, launch them back to back and queue
        every answer's copy to the host behind them.  Returns the
        dispatch pending, or None when it failed (its futures then hold
        the exception).  Its spans (DESIGN.md §15) record under lane
        ``tid``; ``in_flight`` is None on the inline path."""
        import jax
        t0 = self._clock()
        tracer = obs.default_tracer()
        try:
            lay = self._layout(batch)
        except Exception as exc:  # noqa: BLE001 — fail the batch, not the service
            self._fail(batch, exc)
            return None
        eng = self._routes[batch[0].graph_id].engine
        tier = batch[0].tier
        label = "bank" if tier == BANK else tier
        args = None
        if tracer.enabled:
            stages = eng.walk_stages(None if tier == BANK else tier)
            args = {"tier": label, "w": lay.n, "b": lay.b,
                    "r_pad": lay.r_pad, "requests": len(batch),
                    "rows": sum(req.signal.shape[0] for req in batch),
                    "signal_elements": lay.signal_elements,
                    "block_elements": lay.block_elements,
                    "walk_stages": lay.b * stages,
                    "lowering": lay.lowering}
            if in_flight is not None:
                args["in_flight"] = in_flight
        d = _Dispatch(batch=batch, lay=lay, label=label,
                      t_collect=t_collect, t0=t0, tid=tid,
                      in_flight=in_flight, args=args)
        d.open_mirror(tracer)
        if tracer.enabled:
            d.ts = tracer.now()

        def launch(x, row):
            # row None: the engine's own step over the block it is handed
            kw = {} if row is None else {"row": row}
            if tier == BANK:
                return eng.step_bank_versioned(x, **kw)
            return eng.step_versioned(x, self._h, tier=tier, **kw)

        try:
            with tracer.span("serve.build", cat="serve", tid=tid):
                if lay.whole:
                    host = [np.zeros((lay.b, lay.r_pad, lay.n), np.float32)]
                    views = list(host[0])
                    rows = [None]
                else:
                    host = views = [np.zeros((r, lay.n), np.float32)
                                    for r in lay.r_pads]
                    rows = lay.rows
                for req, (k, off) in zip(batch, lay.offsets):
                    r, size = req.signal.shape
                    views[k][off:off + r, :size] = req.signal
            with tracer.span("serve.put", cat="serve", tid=tid):
                xs = jax.device_put(host)
            with tracer.span("serve.launch", cat="serve", tid=tid):
                d.out = [launch(x, row) for x, row in zip(xs, rows)]
                # queue the answers' copies to the host behind the steps,
                # as a bare pull would, so that waiting for the device
                # adds no round trip
                for y, _ in d.out:
                    y.copy_to_host_async()
        except Exception as exc:  # noqa: BLE001 — fail the batch, not the service
            self._fail(batch, exc)
            self._finish(d)
            return None
        return d

    def _complete(self, d: _Dispatch):
        """The completion stage: wait for the device, pull every answer
        and reply.  Rows are independent under every kernel in the
        stack (they broadcast over the leading axes), so the coalesced
        answer matches the per-request loop — bitwise for the G family
        (tests/test_service.py)."""
        import jax
        tracer = obs.default_tracer()
        d.open_mirror(tracer)
        try:
            ys = [y for y, _ in d.out]
            try:
                with tracer.span("serve.device", cat="serve", tid=d.tid):
                    jax.block_until_ready(ys)
                with tracer.span("serve.pull", cat="serve", tid=d.tid):
                    ys = [np.asarray(y) for y in ys]
            except Exception as exc:  # noqa: BLE001 — fail the batch, not the service
                self._fail(d.batch, exc)
                return
            versions = [v for _, v in d.out]
            if d.lay.whole:
                ys, versions = list(ys[0]), versions * d.lay.b
            with tracer.span("serve.reply", cat="serve", tid=d.tid):
                self._reply(d, ys, versions)
        finally:
            self._finish(d)

    def _finish(self, d: _Dispatch):
        """Record the dispatch's ``serve.dispatch`` span around its
        stages and close its profiler annotation."""
        tracer = obs.default_tracer()
        if d.args is not None:
            tracer.add_span("serve.dispatch", d.ts, tracer.now(),
                            cat="serve", tid=d.tid, args=d.args)
        d.close_mirror()

    def _fail(self, batch: List[_Request], exc: Exception):
        with self._cond:
            self._errors += len(batch)
        for req in batch:
            req.future.set_exception(exc)

    def _reply(self, d: _Dispatch, ys, versions):
        """Crop each request's answer out of its graph block's host
        answer in ``ys``, do the dispatch's bookkeeping and resolve its
        futures with the version that block was served by."""
        batch, lay, label = d.batch, d.lay, d.label
        t_collect, t0 = d.t_collect, d.t0
        overlapped = bool(d.in_flight)
        results = []
        for req, (k, off) in zip(batch, lay.offsets):
            r, size = req.signal.shape
            results.append(ys[k][..., off:off + r, :size])
        t1 = self._clock()
        with self._cond:
            self._dispatches += 1
            self._coalesced += len(batch)
            self._occ_max = max(self._occ_max, len(batch))
            self._served += len(batch)
            self._signal_elements += lay.signal_elements
            self._block_elements += lay.block_elements
            self._graph_blocks += lay.b
            dense = lay.b if lay.lowering == "dense" else 0
            self._dense_blocks += dense
            self._overlapped += overlapped
            depth_now = len(self._queue)
        tracer = obs.default_tracer()
        if obs.recording_enabled():
            # every registry touch here is per BATCH, not per request:
            # batch-wait and execute are batch-uniform (one locked
            # count += len(batch)), and the per-request queue/total
            # samples go through one locked bucketing pass each — the
            # per-request lock round trips were the measurable cost the
            # fig15 QPS gate caught
            dchild = self._obs_dispatch.get(label)
            if dchild is None:
                dchild = self._obs_dispatch[label] = (
                    _OBS_DISPATCHES.labels(service=self.name, tier=label),
                    _OBS_SIGNAL_ELEMENTS.labels(service=self.name,
                                                tier=label),
                    _OBS_BLOCK_ELEMENTS.labels(service=self.name,
                                               tier=label),
                    _OBS_GRAPH_BLOCKS.labels(service=self.name,
                                             tier=label),
                    _OBS_DENSE_BLOCKS.labels(service=self.name,
                                             tier=label),
                    _OBS_OVERLAPPED.labels(service=self.name, tier=label))
            (dispatches, signal, block, blocks, dense_blocks,
             overlaps) = dchild
            dispatches.inc()
            signal.inc(lay.signal_elements)
            block.inc(lay.block_elements)
            blocks.inc(lay.b)
            if dense:
                dense_blocks.inc(dense)
            if overlapped:
                overlaps.inc()
            self._obs_depth.set(depth_now)
            stage_obs = self._stage_children(label)
            stage_obs["batch"].observe_many(t0 - t_collect, len(batch))
            stage_obs["execute"].observe_many(t1 - t0, len(batch))
            stage_obs["queue"].observe_seq(
                [t_collect - req.t_submit for req in batch])
            stage_obs["total"].observe_seq(
                [t1 - req.t_submit for req in batch])
        tid = threading.get_ident()
        for req, (k, _), yr in zip(batch, lay.offsets, results):
            version = versions[k]
            queue_s = t0 - req.t_submit
            self.latency.record(f"{label}/queue", queue_s)
            self.latency.record(f"{label}/service", t1 - t0)
            self.latency.record(f"{label}/total", t1 - req.t_submit)
            if tracer.enabled:
                # the four spans share their endpoints (t_submit <=
                # t_collect <= t0 <= t1, all read from THIS service's
                # injectable clock), so queue + batch + execute
                # telescopes to the request span exactly — integer
                # fake-clock times make the float sums exact, which
                # fig15 gates with ==.  Only the parent request span
                # carries args; the sub-spans are linked by trace_id.
                tr = req.trace_id
                tracer.add_spans((
                    ("request/queue", req.t_submit, t_collect,
                     "serve", tr, tid, None),
                    ("request/batch", t_collect, t0,
                     "serve", tr, tid, None),
                    ("request/execute", t0, t1,
                     "serve", tr, tid, None),
                    ("request", req.t_submit, t1, "serve", tr, tid,
                     {"graph": req.graph_id, "tier": label,
                      "version": version, "batch_size": len(batch)})))
            req.future.set_result(ServeResult(
                y=yr, graph_id=req.graph_id, tier=label, version=version,
                queue_s=queue_s, service_s=t1 - t0,
                total_s=t1 - req.t_submit, batch_size=len(batch),
                trace_id=req.trace_id))

    # -- background maintenance (dynamic engines; DESIGN.md §11) -----------

    def request_maintain(self):
        """Wake the maintainer for an immediate off-hot-path tick."""
        self._m_wake.set()

    def maintain_now(self, timeout: Optional[float] = 30.0) -> dict:
        """Trigger one maintenance tick and wait for it to complete;
        returns the engine's maintain() result.  With no maintainer
        thread running the tick executes inline on the caller's thread
        (still off the dispatcher's serving path)."""
        if not getattr(self.engine, "dynamic", False):
            raise ValueError("engine was built without dynamic=True")
        if not any(t.name.endswith("-maintain") and t.is_alive()
                   for t in self._threads):
            return self._maintain_tick()
        with self._m_done:
            errors0 = self._maintain_errors
            target = self._maintain_ticks + self._maintain_errors + 1
            self._m_wake.set()
            ok = self._m_done.wait_for(
                lambda: self._maintain_ticks + self._maintain_errors
                >= target, timeout)
        if not ok:
            raise TimeoutError("maintenance tick did not complete")
        if self._maintain_errors > errors0:
            raise RuntimeError("maintenance tick failed") \
                from self._last_maint_error
        return self._last_action

    def _swap_version(self) -> int:
        eng = self.engine
        if hasattr(eng, "engines"):             # ragged router
            return sum(e._live.version for e in eng.engines.values())
        return eng._live.version

    def _maintain_tick(self) -> dict:
        before = self._swap_version()
        try:
            if (getattr(self.engine, "placement", None) is not None
                    and hasattr(self.engine, "engines")):
                # placed router: tick ONLY dirty buckets, so refit work
                # lands exclusively on the devices owning them while the
                # rest of the mesh keeps serving (DESIGN.md §14)
                res = self.engine.maintain(dirty_only=True)
            else:
                res = self.engine.maintain()
        except Exception as exc:  # noqa: BLE001 — a failed refit must not kill serving
            with self._cond:
                self._maintain_errors += 1
                self._last_maint_error = exc
            with self._m_done:
                self._m_done.notify_all()
            raise
        after = self._swap_version()
        with self._cond:
            self._maintain_ticks += 1
            self._swaps += after - before
            self._last_action = res
        with self._m_done:
            self._m_done.notify_all()
        return res

    def _maintain_loop(self):
        while True:
            self._m_wake.wait(self.maintain_interval)
            if self._closed:
                return
            self._m_wake.clear()
            try:
                self._maintain_tick()
            except Exception:  # noqa: BLE001 — keep ticking; stats record it
                pass

    # -- SLO surface -------------------------------------------------------

    def reset_stats(self):
        """Zero every SLO counter and latency window (drivers call this
        after warmup so compile time doesn't pollute the steady-state
        figures; queue depth/peak restart from the current depth)."""
        with self._cond:
            self._submitted = self._served = self._shed = 0
            self._errors = 0
            self._depth_peak = len(self._queue)
            self._dispatches = self._coalesced = self._occ_max = 0
            self._signal_elements = self._block_elements = 0
            self._graph_blocks = self._dense_blocks = 0
            self._overlapped = 0
            self._maintain_ticks = self._maintain_errors = 0
            self._swaps = 0
        self.latency = LatencyRecorder(max_samples=self.latency.max_samples)

    def stats(self) -> dict:
        """One consistent snapshot of the SLO surface: counters, queue
        and batching gauges, maintenance/swap counts, per-tier latency
        summaries (exact nearest-rank p50/p99 over the retained window)."""
        with self._cond:
            snap = {
                "submitted": self._submitted,
                "served": self._served,
                "shed": self._shed,
                "errors": self._errors,
                "queue": {"depth": len(self._queue),
                          "peak": self._depth_peak,
                          "max": self.max_queue},
                "dispatches": self._dispatches,
                # block fill: signal_elements / block_elements is the
                # share of the walked (r_pad, w) graph blocks that is
                # signal; graph_blocks counts the blocks walked, and
                # dense_blocks those of them served by the dense lowering
                "signal_elements": self._signal_elements,
                "block_elements": self._block_elements,
                "graph_blocks": self._graph_blocks,
                "dense_blocks": self._dense_blocks,
                # dispatches answered that were launched while an
                # earlier one was still unreplied: the pipeline engaged
                "overlapped_dispatches": self._overlapped,
                "batch": {
                    "cap": self.max_batch,
                    "occupancy_mean": (self._coalesced / self._dispatches
                                       if self._dispatches else 0.0),
                    "occupancy_max": self._occ_max,
                },
                "maintain": {
                    "enabled": bool(getattr(self.engine, "dynamic",
                                            False)),
                    "ticks": self._maintain_ticks,
                    "errors": self._maintain_errors,
                    "swaps": self._swaps,
                },
            }
            fp = getattr(self.engine, "placement", None)
            if fp is not None:
                snap["placement"] = (
                    fp.manifest() if hasattr(fp, "manifest")
                    else {"device_ids": list(fp.device_ids),
                          "batch": int(fp.batch)})
        snap["latency"] = self.latency.summary()
        # the obs layer rides along: counters/gauges/histograms of the
        # process-wide registry (DESIGN.md §15), persisted with the slo
        # payload by save() below so a checkpoint carries the full
        # telemetry of the run that wrote it
        snap["obs"] = obs.default_registry().collect()
        return snap

    def save(self, directory, step: int = 0):
        """Persist the engine checkpoint WITH the service's SLO counters:
        uniform engines carry them as checkpoint metadata (``slo`` key),
        ragged routers get an atomic ``slo.json`` next to router.json.
        Either way ``load_slo_stats`` reads them back."""
        stats = self.stats()
        if hasattr(self.engine, "engines"):     # ragged router
            directory = pathlib.Path(self.engine.save(directory, step))
            tmp = directory / "slo.json.tmp"
            tmp.write_text(json.dumps(stats, indent=1))
            os.replace(tmp, directory / "slo.json")
            return directory
        return self.engine.save(directory, step,
                                extra_metadata={"slo": stats})


def load_slo_stats(directory, step: Optional[int] = None) -> Optional[dict]:
    """SLO stats persisted by ``AsyncFGFTService.save`` (either storage
    shape), or None when the checkpoint predates the service layer."""
    directory = pathlib.Path(directory)
    slo_json = directory / "slo.json"
    if slo_json.exists():
        return json.loads(slo_json.read_text())
    from repro.checkpoint import latest_step, read_metadata
    if step is None:
        step = latest_step(directory)
        if step is None:
            raise FileNotFoundError(f"no committed checkpoint in "
                                    f"{directory}")
    return read_metadata(directory, step).get("slo")


# ---------------------------------------------------------------------------
# Load generators (shared by the CLI driver and benchmarks/fig12_serving.py)
# ---------------------------------------------------------------------------


def closed_loop_load(service: AsyncFGFTService, requests: List[tuple],
                     workers: int = 4) -> List[ServeResult]:
    """CLOSED-loop load: ``workers`` threads round-robin the request list,
    each submitting its next request only after the previous answer
    arrived (think: that many always-on tenants).  Shed requests are
    retried by the same worker until accepted, so every request is
    eventually answered.  Returns results in request order."""
    results: List[Optional[ServeResult]] = [None] * len(requests)
    errors: List[BaseException] = []
    idx = iter(range(len(requests)))
    idx_lock = threading.Lock()

    def tenant():
        while True:
            with idx_lock:
                i = next(idx, None)
            if i is None:
                return
            gid, signal, tier, bank = requests[i]
            while True:
                try:
                    fut = service.submit(gid, signal, tier=tier, bank=bank)
                    break
                except ShedError:
                    time.sleep(0.0002)          # closed loop: retry
            try:
                results[i] = fut.result()
            except BaseException as exc:  # noqa: BLE001 — surface to caller
                errors.append(exc)
                return

    threads = [threading.Thread(target=tenant, name=f"tenant-{k}")
               for k in range(workers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]
    return results  # type: ignore[return-value]


def open_loop_load(service: AsyncFGFTService, requests: List[tuple],
                   qps: float) -> dict:
    """OPEN-loop load: arrivals are paced at ``qps`` regardless of how
    fast answers come back (think: independent internet tenants), so
    overload shows up as queue growth and shed requests instead of
    politely slowing the generator.  Returns
    {results, shed, offered_qps}."""
    if qps <= 0:
        raise ValueError(f"qps must be positive, got {qps}")
    period = 1.0 / qps
    futures = []
    shed = 0
    t_start = time.monotonic()
    for i, (gid, signal, tier, bank) in enumerate(requests):
        target = t_start + i * period
        delay = target - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        try:
            futures.append(service.submit(gid, signal, tier=tier,
                                          bank=bank))
        except ShedError:
            shed += 1
    results = [f.result() for f in futures]
    elapsed = max(time.monotonic() - t_start, 1e-9)
    return {"results": results, "shed": shed,
            "offered_qps": len(requests) / elapsed}
