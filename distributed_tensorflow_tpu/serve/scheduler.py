"""Lane-scheduled continuous batching: priority lanes + per-client
weighted fairness + typed admission control.

The scheduler is the single thread that owns the engine. Each ``step()``
is one serving iteration in the Orca sense:

  1. **shed** queued requests whose deadline passed while waiting,
  2. **admit** queued requests into free slots (prefill + first token —
     TTFT is measured here), releasing immediately if the first token
     already finishes the request,
  3. **decode** one engine round over every active slot,
  4. **complete** slots the round finished and free them — the very next
     ``step()`` refills those slots from the queue.

So a finished request's slot is recycled at TOKEN granularity, never
waiting for the rest of the batch: that is the whole continuous-batching
win over run-to-completion batching.

Admission order (PR 7, replacing pure FCFS): requests queue into one of
three **priority lanes** (0 = interactive, 1 = normal, 2 = batch) drained
by weighted interleave — under contention lane k gets ``lane_weights[k]``
admissions per cycle, so batch traffic cannot starve interactive traffic
and interactive bursts cannot starve batch forever. Within a lane,
**per-client deficit round-robin** (keyed on ``Request.client_id``,
optionally weighted) prevents one chatty client from monopolizing the
lane: each client's requests stay FIFO, but admissions rotate across
clients in proportion to their weight. A single anonymous client on one
lane degrades exactly to FCFS — the pre-PR-7 behavior and what the
existing order tests pin.

Load-shed is deterministic and TYPED — callers always get a
:class:`Completion` or a :class:`Rejection` with a machine-readable
``reason`` (``queue_full`` at submit, ``deadline`` at admission sweep,
``invalid`` for malformed params, ``shutting_down`` at stop). Nothing in
this module blocks indefinitely: ``submit`` either rejects synchronously
or enqueues, and ``PendingRequest.result(timeout)`` /
``stream_events(timeout)`` are the only waits.

Deadlines govern QUEUE WAIT only: a request admitted before its deadline
runs to completion (mid-flight eviction would waste the prefill it
already paid for — the expensive part; shedding is for work not yet
started). Fairness does not change what a deadline means — it changes
WHICH request is admitted next, and the shed sweep still measures every
queued request's own wait.

Streaming (``Request.stream=True``): the handle grows a per-request event
queue the scheduler feeds as tokens materialize — the first token at
admission, then one batch per engine round — ending with the terminal
outcome. ``serve/server.py`` turns those events into SSE; every terminal
path (completion, shed, stop) closes the stream, so a streaming consumer
can never hang either.

Draining (``begin_drain``): stop accepting (``/healthz`` flips 503) while
the loop keeps serving queued + in-flight work — the graceful half of
shutdown the fleet router relies on: a draining replica finishes what it
accepted and receives nothing new.

Variants (PR 12): with a :class:`deploy.variants.VariantTable` attached,
requests queue PER VARIANT (resolved at submit from an explicit
``Request.variant`` or the table's ``client_id`` hash-lane canary rule)
and each slot pins the variant it was admitted under for its lifetime —
the engine runs exactly one params tree per round, so the scheduler
switches the engine between variant buffers only at an EMPTY iteration
boundary (no active or prefilling slot left), a pure reference flip
(zero recompiles). ``variant_quantum`` bounds starvation: after that
many consecutive admissions for one variant while another has queued
work, admission pauses so the boundary arrives and the engine rotates.
Without a table every request lands in the single ``""`` queue and
behavior is exactly the pre-variant scheduler.

Spans (``obs/trace.py``, always on, off the flight recorder): each
``step()`` closes ``sched.step`` around ``sched.admit`` (boundary callbacks
through admission; ``sched.queue_wait`` is the interval each admitted
request waited), ``sched.metrics_sync``, the engine's ``engine.round``,
``sched.deliver`` and ``sched.complete``. The time from one
``engine.step`` returning to the next being entered is the
``serve_between_rounds_seconds`` histogram: what the host adds to a round.

Iteration-boundary callbacks (``at_boundary``): deploy's hot-swap needs
a moment on the driver thread when no jitted program is mid-flight to
canary and flip the live param reference. Callbacks run at the top of
``step()`` and in the background loop's idle branch — so a swap
submitted to an idle replica still applies promptly.
"""

from __future__ import annotations

import itertools
import queue as _queue
import threading
import time
from collections import deque
from dataclasses import dataclass, field

import numpy as np

from distributed_tensorflow_tpu.obs import trace as _trace
from distributed_tensorflow_tpu.serve.engine import SlotEngine
from distributed_tensorflow_tpu.serve.kv_pool import InsufficientPages

__all__ = [
    "Request",
    "Completion",
    "Rejection",
    "PendingRequest",
    "Scheduler",
    "NUM_LANES",
    "DEFAULT_LANE_WEIGHTS",
]

# Lane 0 = interactive, 1 = normal (default), 2 = batch/background.
NUM_LANES = 3
# Admissions per weighted-interleave cycle under full contention: 8:4:1.
DEFAULT_LANE_WEIGHTS = (8, 4, 1)


@dataclass(frozen=True)
class Request:
    """One generation request. ``deadline_s`` is a RELATIVE queue-wait
    budget from submit time (None = wait forever); see the module
    docstring for why it only sheds while queued. ``priority`` picks the
    lane (0 interactive … 2 batch), ``client_id`` the fairness key
    (empty = one shared anonymous client), ``stream`` requests
    per-token delivery through the handle."""

    prompt: tuple
    max_new_tokens: int = 16
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 0.0
    seed: int = 0
    eos_id: int | None = None
    deadline_s: float | None = None
    request_id: str = ""
    priority: int = 1
    client_id: str = ""
    stream: bool = False
    # Explicit variant pin (requires a VariantTable; unknown names get a
    # typed "invalid" rejection). Empty = resolve from client_id lanes.
    variant: str = ""


@dataclass(frozen=True)
class Completion:
    request_id: str
    tokens: tuple  # generated tokens only (prompt excluded), eos included
    ttft_s: float
    latency_s: float
    finish_reason: str  # "length" | "eos"
    # Attribution: which weight variant served this request and which
    # checkpoint step those weights came from (pinned at admission, so a
    # mid-flight hot swap of OTHER requests never relabels this one).
    variant: str = ""
    weight_version: int = 0


@dataclass(frozen=True)
class Rejection:
    request_id: str
    # "queue_full" | "deadline" | "invalid" | "shutting_down" |
    # "insufficient_pages" (decode tier cannot back a handoff import) |
    # "upstream_died" (decode peer lost after the handoff was accepted)
    reason: str
    detail: str = ""


@dataclass
class PendingRequest:
    """Submit-side handle: ``result(timeout)`` blocks until the scheduler
    posts a Completion or Rejection (never a hang under shed — every
    terminal path posts exactly once). For ``stream`` requests,
    ``stream_events(timeout)`` yields ``("tokens", [ints])`` batches as
    the engine produces them and always terminates with
    ``("done", outcome)`` — the same no-hang contract, per token."""

    request: Request
    submitted_at: float
    variant: str = ""  # resolved at submit; the queue it waits in
    _event: threading.Event = field(default_factory=threading.Event)
    _outcome: Completion | Rejection | None = None
    _stream_q: _queue.Queue | None = None

    def finish(self, outcome: Completion | Rejection) -> None:
        self._outcome = outcome
        if self._stream_q is not None:
            self._stream_q.put(("done", outcome))
        self._event.set()

    def push_tokens(self, tokens) -> None:
        """Feed freshly produced tokens to a streaming consumer (no-op
        for non-streaming handles)."""
        if self._stream_q is not None and tokens:
            self._stream_q.put(("tokens", [int(t) for t in tokens]))

    def done(self) -> bool:
        return self._event.is_set()

    def result(self, timeout: float | None = None) -> Completion | Rejection:
        if not self._event.wait(timeout):
            raise TimeoutError(
                f"request {self.request.request_id!r} not finished "
                f"within {timeout}s"
            )
        assert self._outcome is not None
        return self._outcome

    def stream_events(self, timeout: float | None = None):
        """Yield ``("tokens", [ints])`` then a final ``("done", outcome)``.
        ``timeout`` bounds the gap between consecutive events; exceeding
        it raises TimeoutError rather than hanging the consumer."""
        if self._stream_q is None:
            raise RuntimeError(
                "stream_events() on a non-streaming request "
                "(submit with Request(stream=True))"
            )
        while True:
            try:
                kind, payload = self._stream_q.get(timeout=timeout)
            except _queue.Empty:
                raise TimeoutError(
                    f"request {self.request.request_id!r}: no stream event "
                    f"within {timeout}s"
                ) from None
            yield kind, payload
            if kind == "done":
                return


class _FairQueue:
    """Priority lanes + per-client deficit round-robin (DRR).

    NOT thread-safe — the Scheduler's lock guards every call. Each lane
    holds per-client FIFO deques plus a service ring; ``pop`` first picks
    a lane by weighted interleave (credits refilled when the nonempty
    lanes run dry), then the lane's next client by DRR: a client's
    deficit grows by its weight each ring pass and each admission costs
    1, so admissions converge to weight-proportional shares while each
    client's own requests stay strictly FIFO."""

    def __init__(self, lane_weights=DEFAULT_LANE_WEIGHTS, client_weights=None):
        if len(lane_weights) != NUM_LANES or any(w < 1 for w in lane_weights):
            raise ValueError(
                f"lane_weights must be {NUM_LANES} integers >= 1, "
                f"got {lane_weights!r}"
            )
        self.lane_weights = tuple(int(w) for w in lane_weights)
        self.client_weights = dict(client_weights or {})
        if any(w <= 0 for w in self.client_weights.values()):
            raise ValueError(
                f"client weights must be > 0, got {self.client_weights!r}"
            )
        self._queues = [dict() for _ in range(NUM_LANES)]  # cid -> deque
        self._rings = [deque() for _ in range(NUM_LANES)]  # service order
        self._deficits = [dict() for _ in range(NUM_LANES)]
        self._credits = list(self.lane_weights)
        self._len = 0

    def __len__(self) -> int:
        return self._len

    def depths(self) -> tuple[int, ...]:
        return tuple(
            sum(len(q) for q in lane.values()) for lane in self._queues
        )

    def _weight(self, client_id: str) -> float:
        return float(self.client_weights.get(client_id, 1.0))

    def push(self, pending: PendingRequest) -> None:
        lane = pending.request.priority
        cid = pending.request.client_id
        qs = self._queues[lane]
        if cid not in qs:
            qs[cid] = deque()
            self._rings[lane].append(cid)
            self._deficits[lane][cid] = 0.0
        qs[cid].append(pending)
        self._len += 1

    def push_front(self, pending: PendingRequest) -> None:
        """Requeue at the HEAD of its client's deque and move the client
        to the front of the lane's service ring (with enough deficit to be
        served immediately). Used when admission popped a request the
        paged pool cannot back yet — the request keeps its place instead
        of paying the fairness rotation twice. The one-step ring bias this
        introduces is bounded: at most one requeue per admission attempt,
        and the request it favors is the one that was already chosen."""
        lane = pending.request.priority
        cid = pending.request.client_id
        qs = self._queues[lane]
        ring = self._rings[lane]
        defs = self._deficits[lane]
        if cid not in qs:
            qs[cid] = deque()
            defs[cid] = 0.0
            ring.appendleft(cid)
        else:
            ring.remove(cid)
            ring.appendleft(cid)
        defs[cid] = max(defs[cid], 1.0)
        qs[cid].appendleft(pending)
        self._len += 1

    def _drop_client(self, lane: int, cid: str) -> None:
        del self._queues[lane][cid]
        del self._deficits[lane][cid]
        self._rings[lane].remove(cid)

    def _pop_lane(self, lane: int) -> PendingRequest:
        ring = self._rings[lane]
        qs = self._queues[lane]
        defs = self._deficits[lane]
        while True:
            cid = ring[0]
            if defs[cid] >= 1.0:
                defs[cid] -= 1.0
                q = qs[cid]
                pending = q.popleft()
                if not q:
                    # A departing client forfeits its remaining deficit —
                    # rejoining starts fresh (no banking idle credit).
                    self._drop_client(lane, cid)
                elif defs[cid] < 1.0:
                    ring.rotate(-1)
                return pending
            defs[cid] += self._weight(cid)
            ring.rotate(-1)

    def pop(self) -> PendingRequest | None:
        if self._len == 0:
            return None
        nonempty = [i for i in range(NUM_LANES) if self._queues[i]]
        lane = next((i for i in nonempty if self._credits[i] > 0), None)
        if lane is None:
            self._credits = list(self.lane_weights)
            lane = nonempty[0]
        self._credits[lane] -= 1
        self._len -= 1
        return self._pop_lane(lane)

    def remove_if(self, pred) -> list[PendingRequest]:
        """Remove (and return) every queued request matching ``pred`` —
        the deadline shed sweep. Per-client FIFO order is preserved."""
        removed = []
        for lane in range(NUM_LANES):
            qs = self._queues[lane]
            for cid in list(qs):
                kept = deque()
                for pending in qs[cid]:
                    if pred(pending):
                        removed.append(pending)
                    else:
                        kept.append(pending)
                if kept:
                    qs[cid] = kept
                else:
                    self._drop_client(lane, cid)
        self._len -= len(removed)
        return removed

    def drain_all(self) -> list[PendingRequest]:
        return self.remove_if(lambda _: True)


class Scheduler:
    """Lane-scheduled continuous-batching scheduler over one
    :class:`SlotEngine`.

    ``submit()`` is thread-safe (the HTTP server calls it from handler
    threads); the engine is driven only from ``step()`` /
    ``run_until_idle()`` / the ``start()`` background loop — one driver at
    a time by contract.
    """

    def __init__(
        self,
        engine: SlotEngine,
        *,
        max_queue_depth: int = 64,
        metrics=None,
        clock=time.monotonic,
        lane_weights=DEFAULT_LANE_WEIGHTS,
        client_weights=None,
        variants=None,
        variant_quantum: int = 32,
        role: str = "mixed",
        handoff=None,
    ):
        if role not in ("prefill", "decode", "mixed"):
            raise ValueError(
                f"role must be prefill|decode|mixed, got {role!r}"
            )
        if max_queue_depth < 1:
            raise ValueError(
                f"max_queue_depth must be >= 1, got {max_queue_depth}"
            )
        if variant_quantum < 1:
            raise ValueError(
                f"variant_quantum must be >= 1, got {variant_quantum}"
            )
        self.engine = engine
        self.max_queue_depth = int(max_queue_depth)
        self.metrics = metrics
        self.clock = clock
        self.variants = variants  # deploy.variants.VariantTable | None
        self.variant_quantum = int(variant_quantum)
        self._lane_weights = lane_weights
        self._client_weights = client_weights
        # One _FairQueue per variant ("" = the single queue when no
        # table is attached — behavior identical to pre-variant builds).
        self._queues: dict[str, _FairQueue] = {
            "": _FairQueue(lane_weights, client_weights)
        }
        self._variant_served = 0  # consecutive admissions, current variant
        self._lock = threading.Lock()  # guards _queues and accept/drain state
        self._accepting = True
        self._draining = False
        self._drain_deadline: float | None = None
        self._inflight: dict[int, _InFlight] = {}
        # Disaggregated tiers (PR 13). role flows to /healthz -> probes ->
        # registry so the fleet router can steer fresh prompts at the
        # prefill tier; ``handoff`` is the prefill-side outbox (duck-typed:
        # available()/submit()). A parked slot lives in _parked with its
        # engine.active masked off — registers and pages intact — until
        # the decode peer ACCEPTS (release) or the push fails pre-accept
        # (reactivate: local-decode fallback, the request is never lost).
        self.role = role
        self.handoff = handoff
        self._parked: dict[int, _InFlight] = {}
        self._handoff_inbox: deque = deque()  # decode side: (bundle, pending)
        self._ids = itertools.count()
        self._boundary: deque = deque()  # thread-safe append/popleft
        # clock() when the last engine round returned with slots still
        # in flight (None otherwise): serve_between_rounds_seconds.
        self._round_returned_at: float | None = None
        self._thread: threading.Thread | None = None
        self._stop = threading.Event()

    # -- submit side (any thread) -----------------------------------------

    def submit(self, request: Request) -> PendingRequest:
        """Enqueue or reject NOW. The returned handle always terminates."""
        now = self.clock()
        pending = PendingRequest(request=request, submitted_at=now)
        if request.stream:
            pending._stream_q = _queue.Queue()
        if not request.request_id:
            request = Request(
                **{**request.__dict__, "request_id": f"r{next(self._ids)}"}
            )
            pending.request = request
        err = self._validate(request)
        if err is not None:
            pending.finish(Rejection(request.request_id, "invalid", err))
            self._count_shed()
            return pending
        variant = request.variant
        if self.variants is not None:
            if variant:
                if variant not in self.variants:
                    pending.finish(
                        Rejection(request.request_id, "invalid",
                                  f"unknown variant {variant!r}")
                    )
                    self._count_shed()
                    return pending
            else:
                variant = self.variants.resolve(request.client_id)
        elif variant:
            pending.finish(
                Rejection(request.request_id, "invalid",
                          f"variant {variant!r} requested but no variant "
                          f"table is configured")
            )
            self._count_shed()
            return pending
        pending.variant = variant
        with self._lock:
            if not self._accepting:
                pending.finish(
                    Rejection(request.request_id, "shutting_down",
                              "scheduler is draining" if self._draining
                              else "scheduler is stopping")
                )
                self._count_shed()
                return pending
            depth = sum(len(q) for q in self._queues.values())
            if depth >= self.max_queue_depth:
                pending.finish(
                    Rejection(
                        request.request_id, "queue_full",
                        f"queue depth {depth} >= {self.max_queue_depth}",
                    )
                )
                self._count_shed()
                return pending
            if variant not in self._queues:
                self._queues[variant] = _FairQueue(
                    self._lane_weights, self._client_weights
                )
            self._queues[variant].push(pending)
            depth += 1
            lane_depths = self._lane_depths_locked()
        if self.metrics is not None:
            self.metrics.record_queue_depth(depth)
            self.metrics.record_lane_depths(lane_depths)
        return pending

    def _lane_depths_locked(self) -> tuple[int, ...]:
        totals = [0] * NUM_LANES
        for q in self._queues.values():
            for lane, d in enumerate(q.depths()):
                totals[lane] += d
        return tuple(totals)

    def _validate(self, r: Request) -> str | None:
        e = self.engine
        p = len(r.prompt)
        if p < 1:
            return "empty prompt"
        cap = getattr(e, "max_prompt_len", e.prefill_len)
        if p > cap:
            # With chunked prefill on, the cap is max_len - 1 (prompts
            # beyond prefill_len chunk); with it off, prefill_len stays
            # the hard limit.
            return f"prompt length {p} > max prompt length {cap}"
        if r.max_new_tokens < 1:
            return f"max_new_tokens {r.max_new_tokens} < 1"
        if p + r.max_new_tokens > e.max_len:
            return (
                f"prompt {p} + {r.max_new_tokens} new > max_len {e.max_len}"
            )
        if r.deadline_s is not None and r.deadline_s < 0:
            return f"negative deadline_s {r.deadline_s}"
        if (isinstance(r.priority, bool) or not isinstance(r.priority, int)
                or not 0 <= r.priority < NUM_LANES):
            return f"priority {r.priority!r} outside [0, {NUM_LANES})"
        return None

    def _count_shed(self) -> None:
        if self.metrics is not None:
            self.metrics.record_shed()

    # -- engine-driver side (one thread) ----------------------------------

    # -- iteration-boundary callbacks (deploy hot-swap) --------------------

    def at_boundary(self, fn) -> None:
        """Run ``fn()`` on the driver thread at the next iteration
        boundary — after the previous engine round returned, before the
        next admission. Thread-safe; callbacks run once, in submission
        order, and exceptions propagate to the driver (a broken swap
        path is a bug, not traffic)."""
        self._boundary.append(fn)

    def _run_boundary(self) -> None:
        while True:
            try:
                fn = self._boundary.popleft()
            except IndexError:
                return
            fn()

    def step(self) -> int:
        """One serving iteration (boundary callbacks → shed → admit →
        decode → complete). Returns the number of requests completed
        this iteration."""
        with _trace.span("sched.step", flight=False) as step_span:
            with _trace.span("sched.admit", flight=False) as sp:
                self._run_boundary()
                now = self.clock()
                self._shed_expired(now)
                self._admit_handoffs(now)
                sp.note(admitted=self._admit(now))
            metrics = self.metrics
            if metrics is not None:
                with _trace.span("sched.metrics_sync", flight=False):
                    # Occupancy in the engine's native capacity unit: PAGE
                    # occupancy under the paged layout (what admission actually
                    # gates on), slot occupancy for the monolithic layout.
                    metrics.record_occupancy(self.engine.utilization)
                    metrics.sync_engine(self.engine)
            if (self.engine.active_count == 0
                    and getattr(self.engine, "prefilling_count", 0) == 0):
                self._round_returned_at = None
                step_span.note(completed=0)
                return 0
            t0 = self.clock()
            if metrics is not None and self._round_returned_at is not None:
                metrics.record_between_rounds(t0 - self._round_returned_at)
            toks, valid, done = self.engine.step()
            t1 = self.clock()
            with _trace.span("sched.deliver", flight=False) as sp:
                produced = 0
                round_toks: dict[int, list] = {}
                for k in range(toks.shape[0]):
                    for slot, fl in self._inflight.items():
                        if valid[k, slot]:
                            tok = int(toks[k, slot])
                            fl.tokens.append(tok)
                            round_toks.setdefault(slot, []).append(tok)
                            produced += 1
                for slot, new in round_toks.items():
                    fl = self._inflight[slot]
                    if fl.ttft_s is None:
                        # Chunked-prefill admission deferred the first token to
                        # this round — TTFT is request-observed first-token
                        # time.
                        fl.ttft_s = self.clock() - fl.pending.submitted_at
                        if metrics is not None:
                            metrics.record_ttft(fl.ttft_s)
                    fl.pending.push_tokens(new)
                if metrics is not None:
                    metrics.record_round(t1 - t0, produced)
                sp.note(produced=produced)
            with _trace.span("sched.complete", flight=False):
                completed = 0
                for slot in np.nonzero(done)[0]:
                    self._complete(int(slot))
                    completed += 1
                self._sweep_handoffs()
            # The between-rounds gap is host overhead only while work remains:
            # with every slot drained the next round waits for a request.
            self._round_returned_at = t1 if self._inflight else None
            step_span.note(completed=completed)
            return completed

    def _shed_expired(self, now: float) -> None:
        with self._lock:
            expired = []
            for q in self._queues.values():
                expired.extend(q.remove_if(
                    lambda p: (p.request.deadline_s is not None
                               and now - p.submitted_at
                               > p.request.deadline_s)
                ))
        for pending in expired:
            r = pending.request
            pending.finish(
                Rejection(
                    r.request_id, "deadline",
                    f"queued {now - pending.submitted_at:.3f}s > "
                    f"deadline {r.deadline_s}s",
                )
            )
            self._count_shed()

    def _current_variant(self) -> str:
        return self.engine.serving_variant if self.variants is not None else ""

    def _admit(self, now: float) -> int:
        """Admit queued requests into free slots; returns how many."""
        admitted = 0
        while True:
            with self._lock:
                if not any(len(q) for q in self._queues.values()):
                    return admitted
                cur = self._current_variant()
                curq = self._queues.get(cur)
                cur_depth = len(curq) if curq is not None else 0
                others = sorted(
                    v for v, q in self._queues.items()
                    if v != cur and len(q)
                )
                switch_to = None
                if others and (cur_depth == 0
                               or self._variant_served
                               >= self.variant_quantum):
                    if (self._inflight or getattr(
                            self.engine, "prefilling_count", 0)):
                        # The engine can only change variant buffers at
                        # an EMPTY boundary (slots pin their variant).
                        # Stop admitting so the current cohort drains;
                        # decode keeps running in step().
                        return admitted
                    # Rotate round-robin by name so two busy variants
                    # alternate rather than one always winning the tie.
                    switch_to = next(
                        (v for v in others if v > cur), others[0]
                    )
                elif cur_depth == 0:
                    return admitted
                if switch_to is None:
                    slot = self.engine.acquire_slot()
                    if slot is None:
                        return admitted
                    pending = self._queues[cur].pop()
            if switch_to is not None:
                # Empty iteration boundary: flip the engine onto the
                # next variant's staged buffer — a reference swap
                # between jitted rounds, no recompile — then resume
                # admitting from that variant's queue. A cross-structure
                # variant instead REBINDS self.engine to its sibling
                # engine (deploy/variants.set_engine): same boundary
                # rule, different engine object, so a treedef the base
                # engine would hard-reject serves behind the same
                # scheduler/lane/metrics surface.
                self.variants.activate(switch_to)
                self.engine = self.variants.engine_for(switch_to)
                self._variant_served = 0
                if self.metrics is not None:
                    self.metrics.bind_engine(self.engine)
                continue
            r = pending.request
            started_at = self.clock()
            try:
                first, finished = self.engine.start(
                    slot, r.prompt,
                    max_new_tokens=r.max_new_tokens,
                    temperature=r.temperature, top_k=r.top_k,
                    top_p=r.top_p, seed=r.seed, eos_id=r.eos_id,
                )
            except InsufficientPages:
                # Not an error: the paged pool is the real capacity gate
                # and it's full right now. Put the request back at the
                # head of its lane and stop admitting this round — pages
                # free as in-flight requests complete, and every request
                # holds all its pages up front, so progress is guaranteed.
                self.engine.release(slot)
                with self._lock:
                    self._queues[pending.variant].push_front(pending)
                return admitted
            except Exception as exc:  # _validate should prevent this
                self.engine.release(slot)
                pending.finish(Rejection(r.request_id, "invalid", str(exc)))
                self._count_shed()
                continue
            done_at = self.clock()
            self._variant_served += 1
            admitted += 1
            # The wait ends where engine.start is entered; one record per
            # ADMITTED request (a start the page pool refused is retried).
            _trace.interval("sched.queue_wait", pending.submitted_at,
                            started_at, lane=r.priority,
                            prompt_len=len(r.prompt))
            if self.metrics is not None:
                self.metrics.record_queue_wait(
                    started_at - pending.submitted_at)
            wv = int(getattr(self.engine, "weight_version", 0))
            if first is None:
                # Chunked prefill scheduled: the slot is PREFILLING and
                # the first token arrives from a later engine round (the
                # step() collection loop records TTFT then).
                self._inflight[slot] = _InFlight(pending, None, done_at,
                                                 None, pending.variant, wv)
                continue
            ttft = done_at - pending.submitted_at
            if self.metrics is not None:
                self.metrics.record_ttft(ttft)
            fl = _InFlight(pending, first, done_at, ttft, pending.variant,
                           wv)
            pending.push_tokens([int(first)])
            if finished:
                self.engine.release(slot)
                self._finish_completion(fl, done_at)
            else:
                self._inflight[slot] = fl

    def _complete(self, slot: int) -> None:
        fl = self._inflight.pop(slot)
        self.engine.release(slot)
        self._finish_completion(fl, self.clock())

    def _finish_completion(self, fl: _InFlight, now: float) -> None:
        r = fl.pending.request
        reason = (
            "eos"
            if r.eos_id is not None and fl.tokens
            and fl.tokens[-1] == r.eos_id
            else "length"
        )
        fl.pending.finish(
            Completion(
                request_id=r.request_id,
                tokens=tuple(fl.tokens),
                ttft_s=fl.ttft_s,
                latency_s=now - fl.pending.submitted_at,
                finish_reason=reason,
                variant=fl.variant,
                weight_version=fl.weight_version,
            )
        )
        if self.metrics is not None:
            self.metrics.record_completed(variant=fl.variant)

    # -- disaggregated tiers: prefill-side handoff (PR 13) -----------------

    def _sweep_handoffs(self) -> None:
        """End-of-iteration sweep on a prefill-role scheduler: every slot
        that has produced its first token (TTFT already measured locally)
        is exported and pushed to the decode tier. Slots still PREFILLING
        stay — chunked prefill finishes here first; slots whose earlier
        push fell back keep decoding locally (``handoff_banned``)."""
        if (self.role != "prefill" or self.handoff is None
                or not self.handoff.available()):
            return
        for slot in list(self._inflight):
            fl = self._inflight[slot]
            if fl.handoff_banned:
                continue
            if (self.engine.active[slot]
                    and not self.engine.prefilling[slot]):
                self._begin_handoff(slot, fl)

    def _begin_handoff(self, slot: int, fl: _InFlight) -> None:
        from distributed_tensorflow_tpu.serve.fleet.handoff import (
            LazyBundle,
            encode_bundle,
        )

        r = fl.pending.request
        history = [int(t) for t in r.prompt] + [int(t) for t in fl.tokens]
        t0 = time.monotonic()
        use_v2 = int(getattr(self.handoff, "wire_version", 1)) >= 2
        try:
            if use_v2:
                # v2: snapshot the page arrays (dispatch-only device
                # gathers) and let the outbox worker gather/encode each
                # chunk off-thread — the driver stalls only for the
                # snapshot, not the full host copy + serialize.
                bundle = self.engine.export_slot_meta(slot,
                                                      history=history)
            else:
                bundle = self.engine.export_slot(slot, history=history)
        except RuntimeError:
            return  # not exportable right now; keep decoding locally
        payload = (LazyBundle(bundle) if use_v2
                   else encode_bundle(bundle, request_id=r.request_id))
        # Park: decode stops (active masked off) but registers + pages
        # stay intact, and the pool still owns the slot — nothing can
        # re-acquire it until release() or a fallback reactivates it.
        self.engine.pause(slot)
        del self._inflight[slot]
        self._parked[slot] = fl
        if self.metrics is not None:
            self.metrics.record_handoff("export")
            self.metrics.record_handoff_stall(
                "export", time.monotonic() - t0)
        self.handoff.submit(payload, r.request_id,
                            _HandoffCallbacks(self, slot, fl))

    def _handoff_accepted(self, slot: int) -> None:
        """Driver thread (boundary): the decode peer imported the pages —
        the local copy is now redundant, free the slot."""
        fl = self._parked.pop(slot, None)
        if fl is None:
            return  # already fell back or stop() cleaned up
        self.engine.release(slot)
        if self.metrics is not None:
            self.metrics.record_handoff("accepted")

    def _handoff_fallback(self, slot: int, detail: str = "") -> None:
        """Driver thread (boundary): no peer accepted before any token
        streamed — reactivate the parked slot and decode locally. The
        request loses nothing (registers + pages never moved)."""
        fl = self._parked.pop(slot, None)
        if fl is None:
            return
        if fl.pending.done():  # stop() shed it while parked
            self.engine.release(slot)
            return
        fl.handoff_banned = True
        self.engine.resume(slot)
        self._inflight[slot] = fl
        if self.metrics is not None:
            self.metrics.record_handoff("fallback")

    def _handoff_done(self, fl: _InFlight, payload: dict) -> None:
        """Outbox worker thread: decode tier finished the request —
        assemble the end-to-end completion (local first token + relayed
        decode-tier tokens)."""
        if fl.pending.done():
            return
        r = fl.pending.request
        fl.pending.finish(
            Completion(
                request_id=r.request_id,
                tokens=tuple(fl.tokens),
                ttft_s=fl.ttft_s,
                latency_s=self.clock() - fl.pending.submitted_at,
                finish_reason=str(payload.get("finish_reason", "length")),
                variant=fl.variant,
                weight_version=fl.weight_version,
            )
        )
        if self.metrics is not None:
            self.metrics.record_handoff("done")
            self.metrics.record_completed(variant=fl.variant)

    def _handoff_abort(self, fl: _InFlight, detail: str) -> None:
        """Outbox worker thread: the decode peer died AFTER acceptance —
        its pages are gone and the local slot was already released, so
        the request ends with a typed error (the same
        never-retry-a-partial-stream stance as the fleet router)."""
        if fl.pending.done():
            return
        fl.pending.finish(
            Rejection(fl.pending.request.request_id, "upstream_died",
                      detail)
        )
        self._count_shed()
        if self.metrics is not None:
            self.metrics.record_handoff("failed")

    # -- disaggregated tiers: decode-side import (PR 13) -------------------

    def submit_handoff(self, bundle: dict) -> PendingRequest:
        """Decode-tier entry (any thread): queue a decoded handoff bundle
        for import at the next admission boundary. The returned handle is
        ALWAYS streaming — the prefill side relays its token/done events.
        Rejections are typed and retryable (``queue_full`` /
        ``insufficient_pages`` / ``shutting_down``) so the pushing side
        can try another peer or fall back to local decode."""
        now = self.clock()
        pending = self._handoff_pending(bundle, now)
        with self._lock:
            if not self._accepting:
                pending.finish(
                    Rejection(request.request_id, "shutting_down",
                              "scheduler is draining" if self._draining
                              else "scheduler is stopping")
                )
                self._count_shed()
                return pending
            depth = (sum(len(q) for q in self._queues.values())
                     + len(self._handoff_inbox))
            if depth >= self.max_queue_depth:
                pending.finish(
                    Rejection(request.request_id, "queue_full",
                              f"queue depth {depth} >= "
                              f"{self.max_queue_depth}")
                )
                self._count_shed()
                return pending
            self._handoff_inbox.append((bundle, pending))
        return pending

    def _handoff_pending(self, bundle: dict, now: float) -> PendingRequest:
        """Build the always-streaming PendingRequest a handoff bundle's
        registers describe (shared by the v1 inbox and v2 sessions)."""
        history = [int(t) for t in bundle.get("history") or []]
        made = int(bundle.get("made", 0))
        prompt = tuple(history[: max(1, len(history) - made)]) or (0,)
        request = Request(
            prompt=prompt,
            max_new_tokens=max(1, int(bundle.get("budget", 1)) - made),
            temperature=float(bundle.get("temperature", 0.0)),
            top_k=int(bundle.get("top_k", 0)),
            top_p=float(bundle.get("top_p", 0.0)),
            seed=int(bundle.get("seed", 0)),
            eos_id=(None if bundle.get("eos") is None
                    else int(bundle["eos"])),
            request_id=str(bundle.get("request_id")
                           or f"h{next(self._ids)}"),
            stream=True,
        )
        pending = PendingRequest(request=request, submitted_at=now)
        pending._stream_q = _queue.Queue()
        return pending

    def open_handoff_import(self, header: dict) -> "HandoffImportSession":
        """Decode-tier entry (HTTP handler thread) for a CHUNKED v2
        handoff: returns a session whose reserve/feed/commit/abort stage
        pages in behind the all-or-nothing contract — pages are alloc'd
        up front and scattered chunk-by-chunk as frames arrive, but the
        slot is acquired and bound only at commit, so any earlier
        failure leaves the decode tier exactly as it was."""
        return HandoffImportSession(self, header)

    def _admit_handoffs(self, now: float) -> None:
        """Driver thread: import queued handoff bundles into free slots.
        Imports happen BEFORE fresh admissions — a handed-off request
        already paid its prefill somewhere and must not starve behind
        new prompts. Failure is fail-fast and typed: the pushing prefill
        replica still holds the parked slot and handles retry/fallback."""
        while True:
            with self._lock:
                if not self._handoff_inbox:
                    return
                bundle, pending = self._handoff_inbox.popleft()
            if pending.done():  # stop() shed it while queued
                continue
            slot = self.engine.acquire_slot()
            if slot is None:
                self._reject_handoff(pending, "queue_full",
                                     "no free slot on decode tier")
                continue
            t0 = time.monotonic()
            try:
                self.engine.import_slot(slot, bundle)
            except InsufficientPages as exc:
                self.engine.release(slot)
                self._reject_handoff(pending, "insufficient_pages",
                                     str(exc))
                continue
            except Exception as exc:  # malformed / mismatched bundle
                self.engine.release(slot)
                self._reject_handoff(pending, "invalid", str(exc))
                continue
            wv = int(getattr(self.engine, "weight_version", 0))
            # ttft_s=0.0 (not None): the first token was already served
            # by the prefill tier — the chunked-TTFT branch in step()
            # must not re-measure it here.
            self._inflight[slot] = _InFlight(pending, None, now, 0.0,
                                             "", wv)
            if self.metrics is not None:
                self.metrics.record_handoff("import")
                # Monolithic import blocks this driver iteration for the
                # full scatter — the baseline the v2 staged path beats.
                self.metrics.record_handoff_stall(
                    "import", time.monotonic() - t0)

    def _reject_handoff(self, pending: PendingRequest, reason: str,
                        detail: str) -> None:
        pending.finish(
            Rejection(pending.request.request_id, reason, detail)
        )
        self._count_shed()
        if self.metrics is not None:
            self.metrics.record_handoff("import_rejected")

    def run_until_idle(self, max_steps: int | None = None) -> int:
        """Drive ``step()`` until queue and slots are empty; returns total
        completions. ``max_steps`` bounds runaway loops in tests."""
        total = 0
        steps = 0
        while True:
            self._run_boundary()
            with self._lock:
                queued = (sum(len(q) for q in self._queues.values())
                          + len(self._handoff_inbox))
            if queued == 0 and not self._inflight and not self._parked:
                return total
            total += self.step()
            if not self._inflight and self._parked:
                # Only parked handoffs remain: their outcome arrives from
                # the outbox worker via boundary ops — yield briefly.
                time.sleep(0.0005)
            steps += 1
            if max_steps is not None and steps >= max_steps:
                raise RuntimeError(
                    f"not idle after {max_steps} steps "
                    f"({queued} queued, {len(self._inflight)} in flight)"
                )

    # -- background loop (serve_lm) ---------------------------------------

    def start(self, poll_s: float = 0.001) -> None:
        """Run the serving loop on a daemon thread (the HTTP server's
        submit side stays on its own threads)."""
        if self._thread is not None:
            raise RuntimeError("scheduler already started")
        self._stop.clear()

        def loop():
            while not self._stop.is_set():
                # Boundary callbacks must drain even while idle — a hot
                # swap submitted to a quiet replica still has to apply.
                self._run_boundary()
                with self._lock:
                    idle = (not any(len(q) for q in self._queues.values())
                            and not self._handoff_inbox)
                if idle and not self._inflight:
                    # Parked handoff slots need no engine rounds — their
                    # boundary ops drain above each poll cycle.
                    self._stop.wait(poll_s)
                    continue
                self.step()

        self._thread = threading.Thread(
            target=loop, name="serve-scheduler", daemon=True
        )
        self._thread.start()

    def begin_drain(self, deadline_s: float | None = None) -> None:
        """Graceful-shutdown phase 1: refuse NEW submits (typed
        ``shutting_down``; ``/healthz`` flips 503 so the router stops
        dispatching here) while the loop keeps serving everything already
        accepted. ``deadline_s`` is advisory — it bounds the Retry-After
        the server advertises and what ``drain_remaining_s`` reports; the
        caller (``serve_lm``'s SIGTERM path) decides when to hard-stop."""
        with self._lock:
            self._accepting = False
            self._draining = True
            self._drain_deadline = (
                self.clock() + deadline_s if deadline_s is not None else None
            )

    def drain_remaining_s(self) -> float | None:
        """Seconds left before the announced drain deadline (None when not
        draining or no deadline was given; floors at 0.0)."""
        with self._lock:
            if not self._draining or self._drain_deadline is None:
                return None
            return max(0.0, self._drain_deadline - self.clock())

    @property
    def idle(self) -> bool:
        with self._lock:
            queued = (sum(len(q) for q in self._queues.values())
                      + len(self._handoff_inbox))
        return queued == 0 and not self._inflight and not self._parked

    def stop(self, timeout: float = 5.0) -> None:
        """Stop accepting, halt the loop, and shed anything unfinished
        (typed ``shutting_down``) so no caller is left hanging."""
        with self._lock:
            self._accepting = False
        if self._thread is not None:
            self._stop.set()
            self._thread.join(timeout)
            self._thread = None
        with self._lock:
            leftovers = []
            for q in self._queues.values():
                leftovers.extend(q.drain_all())
        leftovers.extend(fl.pending for fl in self._inflight.values())
        for slot in list(self._inflight):
            del self._inflight[slot]
            self.engine.release(slot)
        # Handoff state sheds the same way: parked slots free their
        # pages, queued imports answer typed rejections.
        leftovers.extend(fl.pending for fl in self._parked.values())
        for slot in list(self._parked):
            del self._parked[slot]
            self.engine.release(slot)
        with self._lock:
            inbox = list(self._handoff_inbox)
            self._handoff_inbox.clear()
        leftovers.extend(p for _, p in inbox)
        for pending in leftovers:
            if not pending.done():
                pending.finish(
                    Rejection(pending.request.request_id, "shutting_down",
                              "scheduler stopped before completion")
                )
                self._count_shed()

    @property
    def queue_depth(self) -> int:
        with self._lock:
            return sum(len(q) for q in self._queues.values())

    @property
    def lane_depths(self) -> tuple[int, ...]:
        with self._lock:
            return self._lane_depths_locked()

    def variant_depths(self) -> dict[str, int]:
        """Queued requests per variant (healthz/debug readout)."""
        with self._lock:
            return {v: len(q) for v, q in self._queues.items() if len(q)}

    @property
    def inflight_count(self) -> int:
        return len(self._inflight)

    @property
    def accepting(self) -> bool:
        """False once ``begin_drain()`` or ``stop()`` has begun — new
        submits get typed ``shutting_down`` rejections (what /healthz
        reports as 503)."""
        with self._lock:
            return self._accepting

    @property
    def draining(self) -> bool:
        with self._lock:
            return self._draining

    @property
    def loop_running(self) -> bool:
        """True while the ``start()`` background loop thread is alive. A
        never-started scheduler (externally driven via ``step()``) reports
        False without being unhealthy — healthz treats a DEAD started
        thread, not an absent one, as a liveness failure."""
        return self._thread is not None and self._thread.is_alive()


class _InFlight:
    """Host-side accumulation for a request occupying a slot."""

    __slots__ = ("pending", "tokens", "started_at", "ttft_s", "variant",
                 "weight_version", "handoff_banned")

    def __init__(self, pending, first_token, started_at, ttft_s,
                 variant="", weight_version=0):
        self.pending = pending
        # first_token/ttft_s are None while the slot is PREFILLING
        # (chunked prefill) — both arrive with the final chunk's round.
        self.tokens = [] if first_token is None else [int(first_token)]
        self.started_at = started_at
        self.ttft_s = ttft_s
        # Pinned at admission: the variant + checkpoint step the slot
        # was started under (attribution survives later hot swaps).
        self.variant = variant
        self.weight_version = int(weight_version)
        # Set after a failed handoff push: this request finishes on the
        # local replica (the sweep must not re-export it every round).
        self.handoff_banned = False


class _HandoffCallbacks:
    """Bridges :class:`~.fleet.handoff.HandoffOutbox` worker events back
    into the scheduler. Token/terminal events act on the PendingRequest
    directly (thread-safe by construction — the driver no longer touches
    a parked request); anything touching the engine trampolines onto the
    driver thread via ``at_boundary``."""

    __slots__ = ("sched", "slot", "fl")

    def __init__(self, sched: Scheduler, slot: int, fl: _InFlight):
        self.sched = sched
        self.slot = slot
        self.fl = fl

    def on_accepted(self, peer: str) -> None:
        self.sched.at_boundary(
            lambda: self.sched._handoff_accepted(self.slot))

    def on_tokens(self, tokens) -> None:
        if self.fl.pending.done():
            return
        toks = [int(t) for t in tokens]
        self.fl.tokens.extend(toks)
        self.fl.pending.push_tokens(toks)

    def on_done(self, payload: dict) -> None:
        self.sched._handoff_done(self.fl, payload)

    def on_failed(self, detail: str, accepted: bool) -> None:
        if accepted:
            self.sched._handoff_abort(self.fl, detail)
        else:
            self.sched.at_boundary(
                lambda: self.sched._handoff_fallback(self.slot, detail))


class HandoffImportError(RuntimeError):
    """Typed failure of a staged (v2) import. ``reason`` uses the
    scheduler's rejection vocabulary (``invalid`` / ``queue_full`` /
    ``insufficient_pages`` / ``shutting_down``) so the server maps it
    straight to an HTTP status and the sender to retry-vs-fallback."""

    def __init__(self, reason: str, detail: str = ""):
        super().__init__(detail or reason)
        self.reason = reason
        self.detail = detail


class HandoffImportSession:
    """Decode-side staged import of ONE chunk-streamed (DTFH2) handoff.

    Driven from an HTTP handler thread; every engine/pool mutation
    trampolines onto the driver thread via ``at_boundary`` (the pool's
    leaves are donated by the jitted step — only the driver may touch
    them between rounds):

    * ``reserve()`` — validate the header and ALLOCATE (not bind) the
      page ids. Blocks only the handler thread; failures are typed.
    * ``feed(start, stop, layer_rows)`` — enqueue one chunk's rows for
      scatter at the next iteration boundary and return immediately:
      the network transfer overlaps live decode rounds, and each
      scatter's driver time is recorded as receiver-side import stall.
    * ``commit()`` — after the CMIT frame: on the driver, acquire a
      slot, bind the staged pages, adopt registers, and return the
      always-streaming :class:`PendingRequest`. Typed failure frees the
      staged pages — the all-or-nothing contract holds because nothing
      was bound or activated before this point.
    * ``abort()`` — free the staged pages (idempotent; call on any
      handler-side error or disconnect before commit).
    """

    __slots__ = ("sched", "header", "n_pages", "pages", "committed",
                 "aborted", "_scatter_err")

    def __init__(self, sched: Scheduler, header: dict):
        self.sched = sched
        self.header = header
        try:
            self.n_pages = int(header["pages"]["n_pages"])
        except (KeyError, TypeError, ValueError) as exc:
            raise HandoffImportError(
                "invalid", f"malformed v2 header: {exc}") from exc
        self.pages: list[int] | None = None
        self.committed = False
        self.aborted = False
        self._scatter_err: list = [None]

    def _fail(self, reason: str, detail: str):
        if self.sched.metrics is not None:
            self.sched.metrics.record_handoff("import_rejected")
        raise HandoffImportError(reason, detail)

    def reserve(self, timeout_s: float = 30.0) -> None:
        sched = self.sched
        try:
            sched.engine.validate_handoff_header(self.header)
        except (ValueError, RuntimeError) as exc:
            self._fail("invalid", str(exc))
        pps = getattr(sched.engine.pool, "pages_per_slot", self.n_pages)
        if self.n_pages > pps:
            self._fail("invalid",
                       f"{self.n_pages} pages > pages_per_slot {pps}")
        with sched._lock:
            if not sched._accepting:
                self._fail("shutting_down",
                           "scheduler is draining" if sched._draining
                           else "scheduler is stopping")
        done = threading.Event()
        box: dict = {}

        def op():
            box["pages"] = sched.engine.pool.alloc_pages(self.n_pages)
            done.set()

        sched.at_boundary(op)
        if not done.wait(timeout_s):
            self._fail("queue_full",
                       "reserve timed out waiting for the driver")
        if box["pages"] is None:
            self._fail("insufficient_pages",
                       f"{self.n_pages} pages requested, "
                       f"{sched.engine.pool.pages_free} free")
        self.pages = box["pages"]

    def feed(self, start: int, stop: int, layer_rows) -> None:
        sched = self.sched
        pages = self.pages[start:stop]
        err = self._scatter_err

        def op():
            if err[0] is not None or self.aborted:
                return
            t0 = time.monotonic()
            try:
                sched.engine.pool.scatter_pages(pages, layer_rows)
            except Exception as exc:  # surfaces as typed reject at commit
                err[0] = exc
                return
            if sched.metrics is not None:
                sched.metrics.record_handoff_stall(
                    "import", time.monotonic() - t0)

        sched.at_boundary(op)

    def commit(self, timeout_s: float = 30.0) -> PendingRequest:
        sched = self.sched
        done = threading.Event()
        box: dict = {}

        def op():
            try:
                box["pending"] = self._commit_on_driver()
            except HandoffImportError as exc:
                box["err"] = exc
            except Exception as exc:
                box["err"] = HandoffImportError("invalid", str(exc))
            finally:
                done.set()

        sched.at_boundary(op)
        if not done.wait(timeout_s):
            self._fail("queue_full",
                       "commit timed out waiting for the driver")
        if "err" in box:
            self._fail(box["err"].reason, box["err"].detail)
        self.committed = True
        return box["pending"]

    def _commit_on_driver(self) -> PendingRequest:
        """Driver thread (boundary): the ordered-deque guarantee means
        every queued chunk scatter already ran when this executes. The
        whole block is timed as the ``commit`` stall side — it is the
        only decode-visible stall left after the last wire byte (the
        chunk scatters overlapped the transfer), which is what the
        handoff perf bench gates against v1's post-transfer import."""
        t0 = time.monotonic()
        sched = self.sched
        pages, self.pages = self.pages, None
        if self._scatter_err[0] is not None:
            sched.engine.pool.free_pages(pages)
            raise HandoffImportError(
                "invalid", f"chunk scatter failed: {self._scatter_err[0]}")
        with sched._lock:
            accepting, draining = sched._accepting, sched._draining
        if not accepting:
            sched.engine.pool.free_pages(pages)
            raise HandoffImportError(
                "shutting_down",
                "scheduler is draining" if draining
                else "scheduler is stopping")
        slot = sched.engine.acquire_slot()
        if slot is None:
            sched.engine.pool.free_pages(pages)
            raise HandoffImportError("queue_full",
                                     "no free slot on decode tier")
        try:
            sched.engine.validate_handoff_header(self.header)
        except Exception as exc:  # config changed mid-transfer (hot swap)
            sched.engine.pool.free_pages(pages)
            sched.engine.release(slot)
            raise HandoffImportError("invalid", str(exc)) from exc
        try:
            sched.engine.adopt_imported_slot(slot, self.header, pages)
        except Exception as exc:
            # bind landed (or raised before touching the slot's row) —
            # release() frees whatever got bound.
            sched.engine.release(slot)
            raise HandoffImportError("invalid", str(exc)) from exc
        now = sched.clock()
        pending = sched._handoff_pending(self.header, now)
        wv = int(getattr(sched.engine, "weight_version", 0))
        # ttft_s=0.0 (not None): first token already served by prefill.
        sched._inflight[slot] = _InFlight(pending, None, now, 0.0, "", wv)
        if sched.metrics is not None:
            sched.metrics.record_handoff("import")
            sched.metrics.record_handoff_stall(
                "commit", time.monotonic() - t0)
        return pending

    def abort(self) -> None:
        if self.committed or self.aborted:
            return
        self.aborted = True
        pages, self.pages = self.pages, None
        if pages:
            sched = self.sched
            sched.at_boundary(
                lambda: sched.engine.pool.free_pages(pages))
