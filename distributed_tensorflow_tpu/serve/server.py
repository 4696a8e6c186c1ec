"""Stdlib-only HTTP front end for the serving scheduler.

``http.server.ThreadingHTTPServer`` + JSON bodies — no web framework, the
same no-new-dependencies stance as the rest of the repo (the TB writer
speaks raw protobuf, the server speaks raw HTTP). Handler threads only
``submit()`` and wait on the returned handle; the engine stays owned by
the scheduler's single driver thread.

Endpoints:

* ``POST /generate`` — body ``{"prompt": [ints], "max_new_tokens": int,
  "temperature": float, "top_k": int, "top_p": float, "seed": int,
  "eos_id": int|null, "deadline_s": float|null, "priority": 0|1|2,
  "client_id": str, "stream": bool}`` (prompt may also be a string when
  the server was built with a codec). Responses map typed scheduler
  outcomes onto status codes — load-shed is an HTTP answer, never a hang:

  =====================  ====  =========================================
  outcome                code  body
  =====================  ====  =========================================
  Completion             200   request_id, tokens, text?, ttft_ms,
                               latency_ms, finish_reason
  Rejection queue_full   429   error="queue_full", detail, Retry-After
  Rejection deadline     503   error="deadline", detail, Retry-After
  Rejection shutting...  503   error="shutting_down", detail,
                               drain_deadline_s?, Retry-After
  Rejection invalid      400   error="invalid", detail
  result timeout         503   error="timeout", detail
  bad JSON / bad types   400   error="invalid", detail
  =====================  ====  =========================================

  Every 429/503 carries a ``Retry-After`` header (seconds) sized from
  what the server knows: queue pressure backs off briefly; a draining
  replica advertises its remaining drain window so clients (and the
  fleet router) stop knocking until it is actually gone.

  With ``"stream": true`` the accepted path switches to Server-Sent
  Events (``text/event-stream``): ``event: token`` frames carrying
  ``{"tokens": [ints]}`` as each engine round produces them, closed by
  one ``event: done`` frame with the same JSON a non-streaming 200
  would have returned (or the rejection object if the request was shed
  mid-queue). Synchronous rejections still answer plain JSON with the
  table's status codes — SSE begins only once tokens can flow. Frames
  are flushed per event and the response deliberately omits
  Content-Length (HTTP/1.0 close-delimited), so nothing between the
  engine and the client buffers the stream; TTFT is the wire arrival
  of the first token frame.

* ``GET /healthz`` — 200 ``{"ok": true, ...}`` while serving; **503**
  ``{"ok": false, ...}`` once the scheduler is shutting down (stopped
  accepting) or its started loop thread has died. The body always reports
  ``accepting``, ``loop_running``, slots, and queue depth so a probe's
  failure reason is one curl away. With an SLO monitor attached, the body
  also carries ``slo: "ok"|"degraded"`` — a sustained breach flips it to
  ``degraded`` but the status stays 200: an SLO-burning replica is slow,
  not dead, and killing it under load would make the breach worse. The
  router drains on ``degraded``; the orchestrator restarts on 503.
* ``GET /slo.json`` — 200 ``SloMonitor.status()`` (per-rule state, value,
  threshold, breach count), or ``{"enabled": false}`` when no monitor was
  attached.
* ``GET /metrics`` — 200 Prometheus text exposition
  (``text/plain; version=0.0.4``) rendered from the ``ServingMetrics``
  registry: TTFT / per-token histograms, queue depth, occupancy, and
  completed/shed/tokens counters.
* ``GET /metrics.json`` — 200 ``ServingMetrics.snapshot()`` JSON (the
  pre-Prometheus readout, kept for loadgen and humans).
* ``POST /handoff`` — decode-tier import of a serialized prefill-tier
  slot (binary bundle body; see ``serve/fleet/handoff.py``). Success is
  the streaming-/generate SSE shape — the first frame is the pushing
  side's commit signal; typed rejections (``insufficient_pages``,
  ``queue_full``, ``shutting_down``) stay plain JSON 429/503 so the
  pusher retries another peer or falls back to local decode.
* ``POST /admin/handoff_peers`` — ``{"urls": [...]}`` replaces the
  prefill replica's decode-peer list (the fleet supervisor pushes
  membership changes here).
* ``POST /admin/deploy`` — the fleet rollout controller's control
  surface (``serve/fleet/rollout.py``). One JSON body, two planes:
  ``{"watch_dir": ..., "step": N}`` makes THIS replica read the
  committed checkpoint step from disk and push it through its own
  ``WeightSwapper`` (stage → boundary canary → flip or rollback —
  raw params never ride the wire); ``{"canary_percent": P}``
  retargets the variant table's crc32 lane slice (the SLO ramp).
  Errors answer typed 400 ``{"error": "invalid"}``.
"""

from __future__ import annotations

import json
import struct
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

from distributed_tensorflow_tpu.obs import export as obs_export
from distributed_tensorflow_tpu.serve.scheduler import Completion, Request
from distributed_tensorflow_tpu.utils import faults

__all__ = ["make_server"]

_REJECTION_STATUS = {
    "queue_full": 429,
    "deadline": 503,
    "shutting_down": 503,
    "invalid": 400,
    # Disaggregated tiers: both are retryable-elsewhere conditions — the
    # pushing prefill replica tries another decode peer or decodes
    # locally (insufficient_pages), or surfaces the typed loss of a
    # decode peer mid-stream (upstream_died).
    "insufficient_pages": 503,
    "upstream_died": 503,
}


class _ChunkedReader:
    """Minimal HTTP/1.1 chunked-transfer decoder over the handler's
    ``rfile`` — the stdlib handler does not de-chunk request bodies, and
    the v2 handoff sender streams frames with ``Transfer-Encoding:
    chunked`` (total size unknown while encoding overlaps sending)."""

    def __init__(self, rfile):
        self.rfile = rfile
        self.remaining = 0  # data bytes left in the current HTTP chunk
        self.eof = False

    def _next_chunk(self) -> None:
        line = self.rfile.readline(1024)
        if line in (b"\r\n", b"\n"):  # CRLF terminating the previous chunk
            line = self.rfile.readline(1024)
        if not line:
            self.eof = True
            return
        try:
            size = int(line.strip().split(b";")[0], 16)
        except ValueError:
            self.eof = True
            return
        if size == 0:
            while True:  # trailers until the blank line
                t = self.rfile.readline(1024)
                if not t or t in (b"\r\n", b"\n"):
                    break
            self.eof = True
            return
        self.remaining = size

    def read(self, n: int) -> bytes:
        out = bytearray()
        while len(out) < n and not self.eof:
            if self.remaining == 0:
                self._next_chunk()
                continue
            take = min(n - len(out), self.remaining)
            data = self.rfile.read(take)
            if not data:
                self.eof = True
                break
            out += data
            self.remaining -= len(data)
            if self.remaining == 0:
                self.rfile.read(2)  # CRLF after the chunk data
        return bytes(out)


class _LengthReader:
    """Content-Length-bounded body reader with the same ``read`` shape."""

    def __init__(self, rfile, length: int):
        self.rfile = rfile
        self.remaining = max(0, int(length))

    def read(self, n: int) -> bytes:
        take = min(n, self.remaining)
        if take <= 0:
            return b""
        data = self.rfile.read(take)
        self.remaining -= len(data)
        return data


def _read_exact(reader, n: int) -> bytes:
    data = reader.read(n)
    if len(data) != n:
        raise ValueError(f"truncated handoff stream ({len(data)}/{n} bytes)")
    return data


def _parse_request(body: dict, codec, budget_s: float | None = None) -> Request:
    prompt = body.get("prompt")
    if isinstance(prompt, str):
        if codec is None:
            raise ValueError("string prompt needs a server-side codec")
        prompt = codec.encode(prompt)
    if not isinstance(prompt, (list, tuple)) or not prompt:
        raise ValueError("prompt must be a non-empty list of token ids")
    if not all(isinstance(t, int) and not isinstance(t, bool) for t in prompt):
        raise ValueError("prompt tokens must be ints")
    eos_id = body.get("eos_id")
    deadline = body.get("deadline_s")
    deadline = None if deadline is None else float(deadline)
    if budget_s is not None:
        # Propagated router budget (X-Budget-Ms): the remaining END-TO-END
        # time. The scheduler's deadline_s is a queue-wait budget, so the
        # min is conservative — a request the router can no longer finish
        # must not sit in the admission queue either.
        deadline = budget_s if deadline is None else min(deadline, budget_s)
    priority = body.get("priority", 1)
    if isinstance(priority, bool) or not isinstance(priority, int):
        raise ValueError(f"priority must be an int lane, got {priority!r}")
    return Request(
        prompt=tuple(prompt),
        max_new_tokens=int(body.get("max_new_tokens", 16)),
        temperature=float(body.get("temperature", 0.0)),
        top_k=int(body.get("top_k", 0)),
        top_p=float(body.get("top_p", 0.0)),
        seed=int(body.get("seed", 0)),
        eos_id=None if eos_id is None else int(eos_id),
        deadline_s=None if deadline is None else float(deadline),
        request_id=str(body.get("request_id", "")),
        priority=priority,
        client_id=str(body.get("client_id", "")),
        stream=bool(body.get("stream", False)),
        variant=str(body.get("variant", "")),
    )


def make_server(
    scheduler,
    host: str = "127.0.0.1",
    port: int = 8000,
    *,
    request_timeout_s: float = 60.0,
    codec=None,
    slo=None,
) -> ThreadingHTTPServer:
    """Build (not start) the HTTP server; caller runs ``serve_forever()``
    and owns scheduler start/stop. ``port=0`` binds an ephemeral port
    (tests read ``server.server_address``). ``slo`` is an optional
    ``obs.slo.SloMonitor``; the caller owns its ticker lifecycle."""

    class Handler(BaseHTTPRequestHandler):
        # Serving logs go through metrics, not per-request stderr lines.
        def log_message(self, fmt, *args):
            pass

        def _send(self, code: int, payload: dict, headers=None) -> None:
            data = json.dumps(payload).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(data)))
            for name, value in (headers or {}).items():
                self.send_header(name, value)
            self.end_headers()
            self.wfile.write(data)

        def _send_rejection(self, outcome) -> None:
            status = _REJECTION_STATUS.get(outcome.reason, 500)
            body = {
                "error": outcome.reason,
                "detail": outcome.detail,
                "request_id": outcome.request_id,
            }
            headers = {}
            if status in (429, 503):
                retry_after = 1
                if outcome.reason == "shutting_down":
                    remaining = None
                    drain_fn = getattr(scheduler, "drain_remaining_s", None)
                    if drain_fn is not None:
                        remaining = drain_fn()
                    if remaining is not None:
                        # Tell callers how long this replica keeps draining
                        # before it is gone for good.
                        body["drain_deadline_s"] = round(remaining, 3)
                        retry_after = max(1, int(remaining) + 1)
                    else:
                        # Stopping with no announced deadline: assume gone.
                        retry_after = 30
                headers["Retry-After"] = str(retry_after)
            self._send(status, body, headers)

        def _send_text(self, code: int, text: str) -> None:
            data = text.encode()
            self.send_response(code)
            self.send_header("Content-Type", "text/plain; version=0.0.4")
            self.send_header("Content-Length", str(len(data)))
            self.end_headers()
            self.wfile.write(data)

        def do_GET(self):
            if self.path == "/healthz":
                accepting = getattr(scheduler, "accepting", True)
                thread = getattr(scheduler, "_thread", None)
                # A never-started scheduler (driven externally via step())
                # is healthy; a STARTED loop whose thread died is not.
                loop_ok = thread is None or thread.is_alive()
                ok = bool(accepting and loop_ok)
                param_device = getattr(scheduler.engine, "param_device", None)
                body = {
                    "ok": ok,
                    "accepting": bool(accepting),
                    "loop_running": scheduler.loop_running,
                    "slots": scheduler.engine.slots,
                    "free_slots": scheduler.engine.free_slots,
                    "queue_depth": scheduler.queue_depth,
                    "draining": bool(getattr(scheduler, "draining", False)),
                    # Disaggregated-tier role (prefill|decode|mixed):
                    # probes carry it into the registry so the router
                    # steers fresh prompts at the prefill tier.
                    "role": str(getattr(scheduler, "role", "mixed")),
                    # Mesh topology: a tp-wide sharded replica is ONE
                    # replica spanning N devices, not N independent ones —
                    # the router must not multiply its capacity by tp.
                    "mesh": {
                        "tp": int(getattr(scheduler.engine, "tp", 1)),
                        "devices": int(
                            getattr(scheduler.engine, "mesh_device_count", 1)
                        ),
                        # Where the placed params live, as JAX names it —
                        # a benchmark refuses a server that landed on CPU.
                        "platform": getattr(param_device, "platform", ""),
                        "device_kind": getattr(
                            param_device, "device_kind", ""),
                    },
                    # Weight quantization mode ('native'/'int8'/'int4') —
                    # the router tells quantized variants apart by this.
                    "weight_dtype": str(
                        getattr(scheduler.engine, "weight_dtype", "native")
                    ),
                    # KV ACTIVATION format ('bf16'/'int8') — a prefill tier
                    # must only hand pages to a decode tier with the same
                    # format, so probes carry it into the registry.
                    "kv_dtype": str(
                        getattr(scheduler.engine, "kv_dtype", "bf16")
                    ),
                }
                # Page capacity is the real admission gate — routers
                # dispatching on free_slots alone would overfill an
                # oversubscribed pool.
                pool = scheduler.engine.pool
                body["pages_free"] = pool.pages_free
                body["pages_total"] = pool.pages_allocatable
                # Deploy state: which checkpoint step is live and which
                # variants this replica can serve — the fleet registry
                # reads this to route variant-pinned traffic.
                deploy = {
                    "weight_version": int(
                        getattr(scheduler.engine, "weight_version", 0)
                    ),
                    "serving_variant": str(
                        getattr(scheduler.engine, "serving_variant", "")
                    ),
                }
                variants = getattr(scheduler, "variants", None)
                if variants is not None:
                    deploy.update(variants.snapshot())
                # Last swap outcome: the rollout controller polls this to
                # tell "swap landed live" from "canary rolled it back".
                last = getattr(getattr(scheduler, "swapper", None),
                               "last", None)
                if last is not None:
                    deploy["last_swap"] = last.to_dict()
                body["deploy"] = deploy
                drain_fn = getattr(scheduler, "drain_remaining_s", None)
                remaining = drain_fn() if drain_fn is not None else None
                if remaining is not None:
                    body["drain_remaining_s"] = round(remaining, 3)
                if slo is not None:
                    # Degraded ≠ dead: still 200 (see module docstring).
                    body["slo"] = "degraded" if slo.degraded else "ok"
                self._send(200 if ok else 503, body)
            elif self.path == "/slo.json":
                if slo is None:
                    self._send(200, {"enabled": False})
                else:
                    status = slo.status()
                    status["enabled"] = True
                    self._send(200, status)
            elif self.path == "/metrics":
                if scheduler.metrics is None:
                    self._send_text(200, "")
                else:
                    self._send_text(
                        200,
                        obs_export.prometheus_text(scheduler.metrics.registry),
                    )
            elif self.path == "/metrics.json":
                snap = (scheduler.metrics.snapshot()
                        if scheduler.metrics is not None else {})
                self._send(200, snap)
            else:
                self._send(404, {"error": "not_found", "detail": self.path})

        def do_POST(self):
            if self.path == "/handoff":
                self._handle_handoff()
                return
            if self.path == "/admin/handoff_peers":
                self._handle_handoff_peers()
                return
            if self.path == "/admin/deploy":
                self._handle_admin_deploy()
                return
            if self.path != "/generate":
                self._send(404, {"error": "not_found", "detail": self.path})
                return
            # Chaos sites (DESIGN.md §22), armable via DTT_FAULT alone.
            stall = faults.delay_s("replica_stall")
            if stall:
                time.sleep(stall)
            if faults.fire("replica_hang"):
                # Hold the socket without answering — the stuck-socket
                # failure mode (process alive, healthz fine, request path
                # wedged). The caller's read timeout, not this server,
                # must turn it into a typed outcome. Handler threads are
                # daemons; the hold is bounded by the site's ms.
                time.sleep(faults.site_ms("replica_hang", 30_000.0) / 1e3)
                self.close_connection = True
                return
            if faults.fire("replica_5xx"):
                self._send(503, {"error": "injected_5xx",
                                 "detail": "DTT_FAULT replica_5xx"},
                           {"Retry-After": "1"})
                return
            budget_s = None
            raw_budget = self.headers.get("X-Budget-Ms")
            if raw_budget is not None:
                try:
                    budget_s = max(0.0, float(raw_budget) / 1000.0)
                except ValueError:
                    budget_s = None
            try:
                n = int(self.headers.get("Content-Length", 0))
                body = json.loads(self.rfile.read(n) or b"{}")
                if not isinstance(body, dict):
                    raise ValueError("body must be a JSON object")
                request = _parse_request(body, codec, budget_s=budget_s)
            except (ValueError, TypeError, json.JSONDecodeError) as exc:
                self._send(400, {"error": "invalid", "detail": str(exc)})
                return
            pending = scheduler.submit(request)
            if request.stream:
                self._stream_response(pending)
                return
            try:
                outcome = pending.result(timeout=request_timeout_s)
            except TimeoutError as exc:
                self._send(503, {"error": "timeout", "detail": str(exc)})
                return
            if isinstance(outcome, Completion):
                # Attribution headers: which variant/weights served this
                # request (the router relays them; loadgen splits its
                # report by them).
                self._send(200, self._completion_payload(outcome), {
                    "X-Variant": outcome.variant,
                    "X-Weight-Version": str(outcome.weight_version),
                })
            else:
                self._send_rejection(outcome)

        def _handle_handoff(self) -> None:
            """POST /handoff — decode-tier import of a prefill-tier slot.
            Body is a binary handoff bundle (DTFH1 monolithic or DTFH2
            chunk stream, sniffed by magic); the response is the same SSE
            shape as streaming /generate (the first frame doubles as the
            ACCEPT signal the pushing side commits on), with synchronous
            rejections answered as plain typed JSON so the pusher can
            retry another peer."""
            from distributed_tensorflow_tpu.serve.fleet.handoff import (
                decode_bundle,
                decode_bundle_v2,
            )

            if not hasattr(scheduler, "submit_handoff"):
                self._send(404, {"error": "not_found",
                                 "detail": "no handoff support"})
                return
            chunked = ("chunked"
                       in self.headers.get("Transfer-Encoding", "").lower())
            if chunked:
                reader = _ChunkedReader(self.rfile)
            else:
                reader = _LengthReader(
                    self.rfile, int(self.headers.get("Content-Length", 0)))
            try:
                magic = reader.read(5)
            except OSError:
                return  # sender died before the magic; nothing to answer
            if magic == b"DTFH2" and hasattr(scheduler,
                                             "open_handoff_import"):
                self._handle_handoff_v2(reader)
                return
            # v1 bundle — or a scheduler without the staged import path:
            # buffer the whole body and import monolithically.
            try:
                parts = [magic]
                while True:
                    block = reader.read(1 << 16)
                    if not block:
                        break
                    parts.append(block)
                data = b"".join(parts)
                bundle = (decode_bundle_v2(data) if magic == b"DTFH2"
                          else decode_bundle(data))
            except Exception as exc:  # noqa: BLE001 — malformed wire data
                self._send(400, {"error": "invalid", "detail": str(exc)})
                return
            pending = scheduler.submit_handoff(bundle)
            self._stream_response(pending)

        def _handle_handoff_v2(self, reader) -> None:
            """Streaming DTFH2 import: validate + reserve pages on the
            header, scatter each page-group chunk as it arrives (the
            transfer overlaps live decode rounds — scatters run at
            iteration boundaries), and claim a slot only at the commit
            frame. Any pre-commit failure aborts the staged pages and
            answers typed JSON AFTER draining the rest of the upload —
            answering mid-upload would surface as a broken pipe on the
            sender instead of the typed status. The all-or-nothing
            contract holds: SSE (and with it the pushing side's ACCEPT)
            begins only after commit."""
            from distributed_tensorflow_tpu.serve.fleet.handoff import (
                ChunkAssembler,
            )
            from distributed_tensorflow_tpu.serve.scheduler import (
                HandoffImportError,
            )

            session = None

            def drain_then(code: int, payload: dict, retry: bool) -> None:
                try:
                    while reader.read(1 << 16):
                        pass
                except OSError:
                    pass
                try:
                    self._send(code, payload,
                               {"Retry-After": "1"} if retry else None)
                except OSError:
                    pass  # sender already gone

            try:
                (head_len,) = struct.unpack("<I", _read_exact(reader, 4))
                header = json.loads(_read_exact(reader, head_len))
                asm = ChunkAssembler(header)
                session = scheduler.open_handoff_import(header)
                session.reserve()
                pending = None
                while pending is None:
                    tag = _read_exact(reader, 4)
                    if tag == b"CHNK":
                        plen, crc = struct.unpack(
                            "<II", _read_exact(reader, 8))
                        flags = _read_exact(reader, 1)[0]
                        payload = _read_exact(reader, plen)
                        start, stop, rows = asm.feed(payload, flags, crc)
                        session.feed(start, stop, rows)
                    elif tag == b"CMIT":
                        (total,) = struct.unpack(
                            "<I", _read_exact(reader, 4))
                        asm.finish(total)
                        pending = session.commit()
                    else:
                        raise ValueError(f"unknown frame tag {tag!r}")
            except HandoffImportError as exc:
                if session is not None:
                    session.abort()
                drain_then(_REJECTION_STATUS.get(exc.reason, 500),
                           {"error": exc.reason, "detail": exc.detail},
                           retry=exc.reason != "invalid")
                return
            except (ValueError, KeyError, TypeError) as exc:
                # HandoffCorrupt (a ValueError), truncation, bad header.
                if session is not None:
                    session.abort()
                drain_then(400, {"error": "invalid", "detail": str(exc)},
                           retry=False)
                return
            except OSError:
                if session is not None:
                    session.abort()
                return  # sender died mid-stream; nothing to answer
            self._stream_response(pending)

        def _handle_handoff_peers(self) -> None:
            """POST /admin/handoff_peers {"urls": [...]} — the fleet
            supervisor pushes the current decode-tier membership to
            prefill replicas as replicas come and go. Entries are bare
            URL strings or ``{"url": ..., "pages_free": ..., ...}``
            pressure dicts (registry probe data) feeding the outbox's
            pressure-aware peer score."""
            outbox = getattr(scheduler, "handoff", None)
            if outbox is None:
                self._send(400, {"error": "invalid",
                                 "detail": "replica has no handoff outbox "
                                           "(role is not prefill)"})
                return
            try:
                n = int(self.headers.get("Content-Length", 0))
                body = json.loads(self.rfile.read(n) or b"{}")
                urls = body["urls"]
                if not isinstance(urls, list) or not all(
                        isinstance(u, str)
                        or (isinstance(u, dict)
                            and isinstance(u.get("url"), str))
                        for u in urls):
                    raise ValueError(
                        "urls must be a list of strings or {'url': ...} "
                        "dicts")
            except (ValueError, TypeError, KeyError,
                    json.JSONDecodeError) as exc:
                self._send(400, {"error": "invalid", "detail": str(exc)})
                return
            outbox.set_peers(urls)
            self._send(200, {"ok": True, "peers": outbox.peers()})

        def _handle_admin_deploy(self) -> None:
            """POST /admin/deploy — push a committed checkpoint step
            and/or a canary-percent ramp update into this replica.

            The checkpoint plane re-reads the step from disk on the
            replica (``read_step`` + the watcher's params extraction —
            the same newest-readable-once machinery, pushed instead of
            polled) and submits it through the replica's own swapper so
            every fleet-pushed step still passes the boundary canary.
            ``DTT_FAULT=deploy_nan`` poisons the pushed candidate here
            exactly as it poisons a watched one. With ``wait_s`` the
            response reports the swap outcome inline; otherwise the
            caller polls ``/healthz``'s ``deploy.last_swap``."""
            from distributed_tensorflow_tpu.serve.deploy.watcher import (
                _extract_params,
                _poison_first_float_leaf,
            )
            from distributed_tensorflow_tpu.train.checkpoint import (
                read_step,
            )

            try:
                n = int(self.headers.get("Content-Length", 0))
                body = json.loads(self.rfile.read(n) or b"{}")
                if not isinstance(body, dict):
                    raise ValueError("body must be a JSON object")
            except (ValueError, TypeError, json.JSONDecodeError) as exc:
                self._send(400, {"error": "invalid", "detail": str(exc)})
                return
            out = {"ok": True}
            if "canary_percent" in body:
                variants = getattr(scheduler, "variants", None)
                if variants is None:
                    self._send(400, {"error": "invalid",
                                     "detail": "replica has no variant "
                                               "table"})
                    return
                try:
                    variants.set_canary(float(body["canary_percent"]),
                                        body.get("canary_variant"))
                except (ValueError, TypeError) as exc:
                    self._send(400, {"error": "invalid",
                                     "detail": str(exc)})
                    return
                out["canary_percent"] = variants.canary_percent
                out["canary_variant"] = variants.canary_variant
            if "step" in body:
                swapper = getattr(scheduler, "swapper", None)
                if swapper is None:
                    self._send(400, {"error": "invalid",
                                     "detail": "replica has no weight "
                                               "swapper"})
                    return
                try:
                    step = int(body["step"])
                    watch_dir = str(body["watch_dir"])
                    tree = read_step(watch_dir, step)
                    params = _extract_params(
                        tree, str(body.get("params_key", "auto")))
                except (OSError, KeyError, ValueError, TypeError) as exc:
                    self._send(400, {"error": "invalid",
                                     "detail": str(exc)})
                    return
                if faults.fire("deploy_nan"):
                    params = _poison_first_float_leaf(params)
                try:
                    swapper.submit(step, params,
                                   variant=body.get("variant") or None)
                except ValueError as exc:
                    self._send(400, {"error": "invalid",
                                     "detail": str(exc)})
                    return
                out["step"] = step
                wait_s = body.get("wait_s")
                if wait_s:
                    out["applied"] = bool(
                        swapper.wait_applied(timeout=float(wait_s)))
                    last = swapper.last
                    if last is not None:
                        out["swap"] = last.to_dict()
            self._send(200, out)

        def _completion_payload(self, outcome: Completion) -> dict:
            payload = {
                "request_id": outcome.request_id,
                "tokens": list(outcome.tokens),
                "ttft_ms": outcome.ttft_s * 1e3,
                "latency_ms": outcome.latency_s * 1e3,
                "finish_reason": outcome.finish_reason,
                "variant": outcome.variant,
                "weight_version": outcome.weight_version,
            }
            if codec is not None:
                payload["text"] = codec.decode(list(outcome.tokens))
            return payload

        def _write_event(self, event: str, obj: dict) -> None:
            frame = f"event: {event}\ndata: {json.dumps(obj)}\n\n".encode()
            self.wfile.write(frame)
            self.wfile.flush()  # per-event: nothing downstream may batch

        def _stream_response(self, pending) -> None:
            """SSE leg of /generate. The first event decides the shape:
            a synchronous rejection stays a plain JSON error response
            (clients branch on status, not on stream content); once a
            token exists we commit to 200 + event-stream and every
            terminal outcome — including a mid-queue shed — arrives as
            the final ``done`` frame."""
            events = pending.stream_events(timeout=request_timeout_s)
            try:
                kind, payload = next(events)
            except TimeoutError as exc:
                self._send(503, {"error": "timeout", "detail": str(exc)})
                return
            if kind == "done" and not isinstance(payload, Completion):
                self._send_rejection(payload)
                return
            self.send_response(200)
            self.send_header("Content-Type", "text/event-stream")
            self.send_header("Cache-Control", "no-cache")
            self.send_header("X-Accel-Buffering", "no")
            # Streams commit headers before completion: the variant was
            # pinned at submit, so it is already exact; the final `done`
            # frame carries the full attribution (incl. weight_version).
            self.send_header("X-Variant", getattr(pending, "variant", ""))
            # No Content-Length on purpose: HTTP/1.0 close-delimited body,
            # so proxies cannot wait for "the whole response".
            self.end_headers()
            try:
                while True:
                    if kind == "tokens":
                        if faults.fire("stream_cut"):
                            # Close without a done frame: the truncated
                            # stream is exactly what a mid-generation
                            # replica death looks like on the wire
                            # (``stream_cut:after=N`` lets N frames pass).
                            self.close_connection = True
                            return
                        self._write_event("token", {"tokens": payload})
                        kind, payload = next(events)
                        continue
                    if isinstance(payload, Completion):
                        self._write_event(
                            "done", self._completion_payload(payload))
                    else:
                        self._write_event("done", {
                            "error": payload.reason,
                            "detail": payload.detail,
                            "request_id": payload.request_id,
                        })
                    return
            except TimeoutError:
                # Stream went quiet past the deadline: surface in-band,
                # then close — the truncated stream is the error signal.
                try:
                    self._write_event("error", {"error": "timeout"})
                except OSError:
                    pass
            except (BrokenPipeError, ConnectionResetError):
                pass  # client left; the scheduler still finishes the slot

    return ThreadingHTTPServer((host, port), Handler)
