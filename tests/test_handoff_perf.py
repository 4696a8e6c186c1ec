"""Handoff fast path (ISSUE 17): the chunked DTFH2 wire format
round-trips byte-exactly to the v1 decode at every chunk-boundary shape
(f32 and int8, compressed and raw), corruption and truncation are caught
BEFORE any page is imported (typed 400, staged pages freed), v1
monolithic POSTs still decode, a real HTTP prefill→decode streamed
handoff is token-identical to local decode with export/import stall and
bytes-on-wire metrics recorded, the outbox steers pushes to the peer
with free pages (and bans a typed-400 peer for the rest of the push),
probed ``pages_free``/``pages_total`` flow registry→snapshot→gauge, and
the supervisor's tier balancing scales the hotter tier up and the cooler
tier down."""

import http.client
import json
import os
import struct
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.parse
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_tpu.models.transformer import (
    TransformerConfig,
    TransformerLM,
)
from distributed_tensorflow_tpu.obs.export import (
    parse_prometheus_text,
    prometheus_text,
)
from distributed_tensorflow_tpu.obs.registry import MetricsRegistry
from distributed_tensorflow_tpu.serve import ServingMetrics
from distributed_tensorflow_tpu.serve.engine import SlotEngine
from distributed_tensorflow_tpu.serve.fleet import (
    FleetSupervisor,
    ProbeResult,
    ReplicaRegistry,
)
from distributed_tensorflow_tpu.serve.fleet.handoff import (
    HandoffCorrupt,
    HandoffOutbox,
    _iter_sse,
    decode_bundle,
    decode_bundle_v2,
    encode_bundle,
    encode_bundle_v2,
)
from distributed_tensorflow_tpu.serve.scheduler import (
    Completion,
    Request,
    Scheduler,
)
from distributed_tensorflow_tpu.serve.server import make_server

pytestmark = [pytest.mark.serve, pytest.mark.paged, pytest.mark.elastic,
              pytest.mark.handoff_perf]

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CFG = TransformerConfig(
    vocab_size=64,
    d_model=32,
    num_heads=4,
    num_layers=2,
    d_ff=64,
    max_seq_len=64,
    compute_dtype=jnp.float32,
)
CFG_INT8 = TransformerConfig(
    vocab_size=64,
    d_model=32,
    num_heads=4,
    num_layers=2,
    d_ff=64,
    max_seq_len=64,
    compute_dtype=jnp.float32,
    kv_cache_dtype="int8",
)

_ENGINE_KW = dict(slots=2, max_len=64, prefill_len=16, page_size=8,
                  prefill_chunk_tokens=8)


@pytest.fixture(scope="module")
def params():
    return TransformerLM(CFG).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]


def _collect(engine, slot, toks):
    t, valid, done = engine.step()
    for k in range(t.shape[0]):
        if valid[k, slot]:
            toks.append(int(t[k, slot]))
    return bool(done[slot])


def _run_local(engine, prompt, kw):
    slot = engine.acquire_slot()
    toks = []
    first, finished = engine.start(slot, list(prompt), **kw)
    if first is not None:
        toks.append(first)
        if finished:
            engine.release(slot)
            return toks
    while engine.prefilling[slot] or engine.active[slot]:
        if _collect(engine, slot, toks):
            break
    engine.release(slot)
    return toks


def _materialize(bundle):
    """Copy page leaves to host so the bundle outlives its engine."""
    out = dict(bundle)
    pages = dict(out["pages"])
    pages["layers"] = [
        {name: np.array(arr) for name, arr in layer.items()}
        for layer in pages["layers"]
    ]
    out["pages"] = pages
    return out


def _scratch_zeroed(arr, length, ps):
    """Page leaf ``(pages, kv, ps[, dh])`` with the rows at or above the
    slot's ``length`` zeroed: v1 ships them as they lie, v2 as zeros (tail
    elision), and they are not all zero on a fresh pool: the decode round
    queued ahead of the host's reading has written the row at ``length``."""
    scratch = np.arange(arr.shape[0] * ps).reshape(-1, 1, ps) >= length
    return np.where(scratch.reshape(scratch.shape + (1,) * (arr.ndim - 3)),
                    np.zeros((), arr.dtype), arr)


@pytest.fixture(scope="module")
def bundles(params):
    """One multi-page exported slot per kv dtype (>= 3 pages so every
    chunk_pages in the round-trip matrix hits a ragged final chunk)."""
    out = {}
    prompt = list(range(1, 21))  # 20 tokens / page_size 8 -> 3 pages
    for name, cfg in (("f32", CFG), ("int8", CFG_INT8)):
        eng = SlotEngine(cfg, params, **_ENGINE_KW)
        slot = eng.acquire_slot()
        toks = []
        first, _ = eng.start(slot, list(prompt), max_new_tokens=6)
        if first is not None:
            toks.append(first)
        while eng.prefilling[slot]:
            _collect(eng, slot, toks)
        bundle = eng.export_slot(slot, history=prompt + toks)
        assert bundle["pages"]["n_pages"] >= 3
        out[name] = _materialize(bundle)
        eng.release(slot)
    return out


# -- wire format: round-trip, corruption, truncation, v1 compat ------------


@pytest.mark.parametrize("compress", [True, False], ids=["zlib", "raw"])
@pytest.mark.parametrize("chunk_pages", [1, 2, 3, 4, 7, 64])
def test_v2_round_trip_matches_v1_decode(bundles, chunk_pages, compress):
    """Every chunking of the page range — one page per chunk, ragged
    final chunk, everything in one chunk — reassembles to the exact
    bundle v1 decodes, for f32 and int8 leaves alike."""
    for name, bundle in bundles.items():
        ref = decode_bundle(encode_bundle(bundle, request_id="rt"))
        wire = encode_bundle_v2(bundle, request_id="rt",
                                chunk_pages=chunk_pages, compress=compress)
        assert wire[:5] == b"DTFH2"
        got = decode_bundle_v2(wire)
        for key in ("request_id", "length", "cur_tok", "made", "budget",
                    "eos", "top_k", "seed", "page_size"):
            assert got[key] == ref[key], (name, key)
        assert got["history"] == ref["history"]
        assert got["pages"]["n_pages"] == ref["pages"]["n_pages"]
        for ref_layer, got_layer in zip(ref["pages"]["layers"],
                                        got["pages"]["layers"]):
            assert set(ref_layer) == set(got_layer)
            for leaf, arr in ref_layer.items():
                assert got_layer[leaf].dtype == arr.dtype, (name, leaf)
                np.testing.assert_array_equal(
                    got_layer[leaf],
                    _scratch_zeroed(arr, ref["length"], ref["page_size"]))


def test_v2_compression_shrinks_the_wire(bundles):
    """The ISSUE gate at codec level: compressed v2 ships well under
    0.75x the v1 monolithic body for the int8-KV bundle (pages carry
    padded zero rows — zlib eats them); uncompressed v2 costs only the
    small per-chunk framing over v1."""
    for name, bundle in bundles.items():
        v1 = len(encode_bundle(bundle, request_id="sz"))
        packed = len(encode_bundle_v2(bundle, request_id="sz",
                                      chunk_pages=2, compress=True))
        raw = len(encode_bundle_v2(bundle, request_id="sz",
                                   chunk_pages=2, compress=False))
        assert packed < 0.75 * v1, (name, packed, v1)
        assert raw < v1 * 1.02, (name, raw, v1)


def _split_frames(wire):
    """Parse a v2 byte string into (header_dict, [(tag, offset, length)])
    where offset/length span the WHOLE frame including its tag."""
    assert wire[:5] == b"DTFH2"
    (hlen,) = struct.unpack_from("<I", wire, 5)
    header = json.loads(wire[9:9 + hlen])
    off = 9 + hlen
    frames = []
    while off < len(wire):
        tag = wire[off:off + 4]
        if tag == b"CHNK":
            (plen,) = struct.unpack_from("<I", wire, off + 4)
            frames.append((b"CHNK", off, 13 + plen))
            off += 13 + plen
        elif tag == b"CMIT":
            frames.append((b"CMIT", off, 8))
            off += 8
        else:
            raise AssertionError(f"unknown tag {tag!r} at {off}")
    return header, frames


def test_v2_crc_corruption_rejected_pre_import(bundles):
    wire = bytearray(encode_bundle_v2(bundles["f32"], request_id="crc",
                                      chunk_pages=1, compress=False))
    tag, off, length = next(f for f in _split_frames(bytes(wire))[1]
                            if f[0] == b"CHNK")
    wire[off + length - 1] ^= 0xFF  # last payload byte of chunk 0
    with pytest.raises(HandoffCorrupt, match="CRC"):
        decode_bundle_v2(bytes(wire))


def test_v2_truncated_stream_rejected(bundles):
    wire = encode_bundle_v2(bundles["f32"], request_id="tr",
                            chunk_pages=1, compress=False)
    _, frames = _split_frames(wire)
    tag, off, length = frames[1]  # cut after chunk 1 of >= 3
    with pytest.raises(HandoffCorrupt, match="without a commit"):
        decode_bundle_v2(wire[:off + length])


# -- decode server: streamed import over real HTTP -------------------------


@pytest.fixture(scope="module")
def decode_stack(params):
    engine = SlotEngine(CFG, params, **_ENGINE_KW)
    engine.warmup()
    metrics = ServingMetrics()
    sched = Scheduler(engine, max_queue_depth=8, metrics=metrics,
                      role="decode")
    server = make_server(sched, port=0, request_timeout_s=30.0)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    sched.start(poll_s=0.001)
    host, port = server.server_address
    try:
        yield f"http://{host}:{port}", sched, engine, metrics
    finally:
        server.shutdown()
        server.server_close()
        thread.join(timeout=5)
        sched.stop()


def _settled_pages_free(engine, timeout_s=10.0):
    """Wait for the decode pool to quiesce (no active/prefilling slots)
    and return its free-page count."""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if engine.active_count == 0 and engine.prefilling_count == 0:
            return engine.pool.pages_free
        time.sleep(0.01)
    return engine.pool.pages_free


def _sse_done(resp):
    for event, obj in _iter_sse(resp):
        if event in ("done", "error"):
            return event, obj
    return None, None


def _post_handoff(base, body, timeout=30):
    parsed = urllib.parse.urlsplit(base)
    conn = http.client.HTTPConnection(parsed.hostname, parsed.port,
                                      timeout=timeout)
    conn.request("POST", "/handoff", body=body,
                 headers={"Content-Type": "application/octet-stream"})
    return conn, conn.getresponse()


def test_v1_monolithic_post_still_streams(decode_stack, bundles):
    base, _, engine, metrics = decode_stack
    before = metrics.handoff_count("import")
    conn, resp = _post_handoff(
        base, encode_bundle(bundles["f32"], request_id="v1compat"))
    try:
        assert resp.status == 200
        assert resp.getheader("Content-Type", "").startswith(
            "text/event-stream")
        event, done = _sse_done(resp)
    finally:
        conn.close()
    assert event == "done" and done.get("finish_reason")
    assert metrics.handoff_count("import") == before + 1


def test_v2_post_streamed_import_completes(decode_stack, bundles):
    """A whole-buffer v2 POST (Content-Length path) is magic-sniffed
    into the streamed importer and decodes to completion."""
    base, _, engine, metrics = decode_stack
    before = metrics.handoff_count("import")
    wire = encode_bundle_v2(bundles["f32"], request_id="v2whole",
                            chunk_pages=1, compress=True)
    conn, resp = _post_handoff(base, wire)
    try:
        assert resp.status == 200
        event, done = _sse_done(resp)
    finally:
        conn.close()
    assert event == "done" and done.get("finish_reason")
    assert done["request_id"] == "v2whole"
    assert metrics.handoff_count("import") == before + 1


def test_v2_corrupt_chunk_typed_400_and_pages_restored(decode_stack,
                                                       bundles):
    base, _, engine, _ = decode_stack
    baseline = _settled_pages_free(engine)
    wire = bytearray(encode_bundle_v2(bundles["f32"], request_id="bad",
                                      chunk_pages=1, compress=False))
    tag, off, length = next(f for f in _split_frames(bytes(wire))[1]
                            if f[0] == b"CHNK")
    wire[off + length - 1] ^= 0xFF
    conn, resp = _post_handoff(base, bytes(wire))
    try:
        assert resp.status == 400
        body = json.loads(resp.read())
        assert "error" in json.dumps(body)
    finally:
        conn.close()
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline \
            and engine.pool.pages_free != baseline:
        time.sleep(0.01)
    assert engine.pool.pages_free == baseline, \
        "staged pages leaked after a corrupt chunk"


def test_v2_connection_cut_mid_stream_frees_staged_pages(decode_stack,
                                                         bundles):
    """Kill the socket after two of three chunks: the importer aborts,
    every staged page returns to the pool, and the NEXT handoff on the
    same server succeeds (no wedged slot, no leaked reservation)."""
    base, _, engine, _ = decode_stack
    baseline = _settled_pages_free(engine)
    wire = encode_bundle_v2(bundles["f32"], request_id="cut",
                            chunk_pages=1, compress=False)
    _, frames = _split_frames(wire)
    tag, off, length = frames[1]
    cut = off + length  # header + chunks 0..1 of >= 3, no commit
    parsed = urllib.parse.urlsplit(base)
    conn = http.client.HTTPConnection(parsed.hostname, parsed.port,
                                      timeout=10)
    conn.putrequest("POST", "/handoff")
    conn.putheader("Content-Type", "application/octet-stream")
    conn.putheader("Content-Length", str(len(wire)))
    conn.endheaders()
    conn.send(wire[:cut])
    time.sleep(0.3)  # let the importer reserve and scatter chunk 0
    conn.close()  # EOF mid-frame: truncated stream
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline \
            and engine.pool.pages_free != baseline:
        time.sleep(0.01)
    assert engine.pool.pages_free == baseline, \
        "staged pages leaked after a cut connection"
    # The tier still imports cleanly afterwards.
    conn, resp = _post_handoff(base, wire)
    try:
        assert resp.status == 200
        event, done = _sse_done(resp)
    finally:
        conn.close()
    assert event == "done" and done.get("finish_reason")


def test_streamed_handoff_http_token_parity_and_metrics(decode_stack,
                                                        params):
    """The full fast path over real HTTP: prefill scheduler + outbox
    stream DTFH2 chunks into the decode server; every request finishes
    token-identical to never-moved local decode, every export is
    accepted (zero fallbacks, zero failures), and the wire/overlap
    metrics — bytes by compression, chunk encode histogram, per-peer
    throughput EWMA, export/import stall — all record."""
    base, _, _, m_d = decode_stack
    eng_p = SlotEngine(CFG, params, **_ENGINE_KW)
    eng_p.warmup()
    rng = np.random.default_rng(17)
    reqs = [
        Request(prompt=tuple(rng.integers(1, 64, 6).tolist()),
                max_new_tokens=7),
        Request(prompt=tuple(rng.integers(1, 64, 10).tolist()),
                max_new_tokens=6),
        Request(prompt=tuple(rng.integers(1, 64, 9).tolist()),
                max_new_tokens=8, temperature=1.0, top_k=4, seed=13),
    ]
    refs = [_run_local(eng_p, r.prompt,
                       dict(max_new_tokens=r.max_new_tokens,
                            temperature=r.temperature, top_k=r.top_k,
                            seed=r.seed))
            for r in reqs]
    m_p = ServingMetrics()
    imports_before = m_d.handoff_count("import")
    import_stall_before = m_d.handoff_stall("import")["events"]
    outbox = HandoffOutbox([base], wire_version=2, chunk_pages=1,
                           metrics=m_p)
    sched_p = Scheduler(eng_p, max_queue_depth=8, metrics=m_p,
                        role="prefill", handoff=outbox)
    sched_p.start(poll_s=0.001)
    try:
        pendings = [sched_p.submit(r) for r in reqs]
        for pend, ref in zip(pendings, refs):
            outcome = pend.result(timeout=60)
            assert isinstance(outcome, Completion), outcome
            assert list(outcome.tokens) == ref
    finally:
        sched_p.stop()
        outbox.stop()
    exports = m_p.handoff_count("export")
    assert exports == len(reqs)
    assert m_p.handoff_count("accepted") == exports
    assert m_p.handoff_count("done") == exports
    assert m_p.handoff_count("fallback") == 0
    assert m_p.handoff_count("failed") == 0
    wire = m_p.handoff_bytes()
    assert wire["true"] + wire["false"] > 0
    snap = m_p.snapshot()
    assert snap["handoff_chunk_ms"]["count"] >= exports
    assert snap["handoff_throughput_bytes_per_s"].get(base, 0.0) > 0.0
    assert m_p.handoff_stall("export")["events"] >= exports
    assert m_d.handoff_count("import") == imports_before + exports
    assert m_d.handoff_stall("import")["events"] > import_stall_before


# -- outbox: pressure-aware steering + typed-400 ban -----------------------


def test_next_peers_prefers_free_pages_and_falls_back_to_rr():
    outbox = HandoffOutbox([], workers=1)
    try:
        full = {"url": "http://a:1", "pages_free": 0, "pages_total": 8,
                "occupancy": 1.0, "queue_depth": 3}
        free = {"url": "http://b:1", "pages_free": 8, "pages_total": 8,
                "occupancy": 0.0, "queue_depth": 0}
        outbox.set_peers([full, free])
        firsts = [outbox._next_peers()[0] for _ in range(10)]
        assert firsts.count("http://b:1") == 10  # >= 80% gate, trivially
        # Without pressure data the rotated round-robin order survives:
        # both peers take the lead across consecutive pushes.
        outbox.set_peers(["http://a:1", "http://b:1"])
        leads = {outbox._next_peers()[0] for _ in range(4)}
        assert leads == {"http://a:1", "http://b:1"}
    finally:
        outbox.stop()


def test_next_peers_throughput_ewma_breaks_pressure_ties():
    outbox = HandoffOutbox([], workers=1)
    try:
        same = dict(pages_free=4, pages_total=8, occupancy=0.5,
                    queue_depth=1)
        outbox.set_peers([dict(url="http://a:1", **same),
                          dict(url="http://b:1", **same)])
        outbox._record_throughput("http://b:1", 1 << 20, 0.5)
        outbox._record_throughput("http://a:1", 1 << 16, 0.5)
        assert all(outbox._next_peers()[0] == "http://b:1"
                   for _ in range(6))
    finally:
        outbox.stop()


class _StubPeer(BaseHTTPRequestHandler):
    """Decode-peer stand-in: drains the v1 body, then either refuses
    with a typed 400 or streams accept + done."""

    mode = "accept"
    hits: list = []

    def do_POST(self):  # noqa: N802 — BaseHTTPRequestHandler API
        body = self.rfile.read(int(self.headers.get("Content-Length", 0)))
        type(self).hits.append(len(body))
        if type(self).mode == "reject":
            out = json.dumps({"error": {
                "reason": "invalid", "detail": "stub refuses layout",
            }}).encode()
            self.send_response(400)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(out)))
            self.end_headers()
            self.wfile.write(out)
            return
        self.send_response(200)
        self.send_header("Content-Type", "text/event-stream")
        self.end_headers()
        done = json.dumps({"request_id": "stub", "tokens": [1, 2],
                           "finish_reason": "length"}).encode()
        self.wfile.write(b'event: token\ndata: {"tokens": [1, 2]}\n\n')
        self.wfile.write(b"event: done\ndata: " + done + b"\n\n")

    def log_message(self, *args):
        pass


def _stub_peer(mode):
    cls = type(f"_Stub_{mode}", (_StubPeer,), {"mode": mode, "hits": []})
    srv = ThreadingHTTPServer(("127.0.0.1", 0), cls)
    thread = threading.Thread(target=srv.serve_forever, daemon=True)
    thread.start()
    host, port = srv.server_address
    return srv, thread, cls, f"http://{host}:{port}"


class _Cb:
    def __init__(self):
        self.accepted = []
        self.tokens = []
        self.done = []
        self.failed = []
        self.terminal = threading.Event()

    def on_accepted(self, peer):
        self.accepted.append(peer)

    def on_tokens(self, toks):
        self.tokens.extend(toks)

    def on_done(self, payload):
        self.done.append(payload)
        self.terminal.set()

    def on_failed(self, detail, accepted):
        self.failed.append((detail, accepted))
        self.terminal.set()


def test_push_steers_to_free_peer_over_real_sockets():
    """ISSUE acceptance: one peer pinned near-full, one free — at least
    80% of pushes land on the free peer (here: all of them)."""
    srv_a, t_a, cls_a, url_a = _stub_peer("accept")
    srv_b, t_b, cls_b, url_b = _stub_peer("accept")
    outbox = HandoffOutbox(workers=1, backoff_s=0.01)
    try:
        outbox.set_peers([
            {"url": url_a, "pages_free": 0, "pages_total": 8,
             "occupancy": 1.0, "queue_depth": 4},  # pinned near-full
            {"url": url_b, "pages_free": 8, "pages_total": 8,
             "occupancy": 0.0, "queue_depth": 0},
        ])
        cbs = [_Cb() for _ in range(10)]
        for cb in cbs:
            outbox.submit(b"v1-opaque-bytes", "steer", cb)
        for cb in cbs:
            assert cb.terminal.wait(timeout=20)
            assert cb.done and not cb.failed
        total = len(cls_a.hits) + len(cls_b.hits)
        assert total == 10
        assert len(cls_b.hits) >= 8, (len(cls_a.hits), len(cls_b.hits))
    finally:
        outbox.stop()
        for srv, thr in ((srv_a, t_a), (srv_b, t_b)):
            srv.shutdown()
            srv.server_close()
            thr.join(timeout=5)


def test_typed_400_bans_peer_for_the_rest_of_the_push():
    """The preferred peer answers a typed 400: it must be tried exactly
    once this push — the retry goes straight to the other peer instead
    of burning attempts re-offering the refused layout."""
    srv_a, t_a, cls_a, url_a = _stub_peer("reject")
    srv_b, t_b, cls_b, url_b = _stub_peer("accept")
    outbox = HandoffOutbox(workers=1, backoff_s=0.01, max_attempts=3)
    try:
        outbox.set_peers([
            # Pressure makes the rejecting peer score FIRST.
            {"url": url_a, "pages_free": 8, "pages_total": 8,
             "occupancy": 0.0, "queue_depth": 0},
            {"url": url_b, "pages_free": 2, "pages_total": 8,
             "occupancy": 0.5, "queue_depth": 2},
        ])
        cb = _Cb()
        outbox.submit(b"v1-opaque-bytes", "ban", cb)
        assert cb.terminal.wait(timeout=20)
        assert cb.done and not cb.failed
        assert cb.accepted == [url_b]
        assert len(cls_a.hits) == 1, "banned peer was re-offered the push"
        assert len(cls_b.hits) == 1
    finally:
        outbox.stop()
        for srv, thr in ((srv_a, t_a), (srv_b, t_b)):
            srv.shutdown()
            srv.server_close()
            thr.join(timeout=5)


# -- registry: pages_free/pages_total flow ---------------------------------


def test_probe_pages_flow_into_snapshot_and_gauge():
    reg_m = MetricsRegistry()
    registry = ReplicaRegistry(
        ["http://x:1"],
        probe=lambda url: ProbeResult(ok=True, accepting=True, slots=2,
                                      role="decode", pages_free=5,
                                      pages_total=12),
        registry=reg_m, up_after=1)
    registry.probe_once()
    rep = next(iter(registry.snapshot()["replicas"].values()))
    assert rep["pages_free"] == 5 and rep["pages_total"] == 12
    samples = [s for s in parse_prometheus_text(prometheus_text(reg_m))
               if s["name"] == "fleet_replica_pages_free"]
    assert samples and samples[0]["value"] == 5.0


class _HealthzStub(BaseHTTPRequestHandler):
    body = {}

    def do_GET(self):  # noqa: N802 — BaseHTTPRequestHandler API
        if self.path != "/healthz":
            self.send_error(404)
            return
        out = json.dumps(type(self).body).encode()
        self.send_response(200)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(out)))
        self.end_headers()
        self.wfile.write(out)

    def log_message(self, *args):
        pass


def test_http_probe_reads_pages_from_healthz():
    from distributed_tensorflow_tpu.serve.fleet.registry import http_probe
    cls = type("_Hz", (_HealthzStub,), {"body": {
        "accepting": True, "slots": 2, "free_slots": 1, "queue_depth": 0,
        "role": "decode", "pages_free": 9, "pages_total": 16,
    }})
    srv = ThreadingHTTPServer(("127.0.0.1", 0), cls)
    thread = threading.Thread(target=srv.serve_forever, daemon=True)
    thread.start()
    host, port = srv.server_address
    try:
        result = http_probe(f"http://{host}:{port}")
    finally:
        srv.shutdown()
        srv.server_close()
        thread.join(timeout=5)
    assert result.ok and result.pages_free == 9 \
        and result.pages_total == 16


# -- supervisor: tier auto-balancing ---------------------------------------


def _balancing_supervisor(replicas, *, balance=True):
    registry = ReplicaRegistry(
        [], probe=lambda url: ProbeResult(ok=True),
        registry=MetricsRegistry(), up_after=1)
    registry.snapshot = lambda: {"replicas": replicas}
    return FleetSupervisor(
        registry, lambda role: None, balance_tiers=balance,
        role_for=lambda direction: "mixed")


def _rep(role, **kw):
    base = {"state": "up", "role": role, "inflight": 0, "queue_depth": 0,
            "occupancy": 0.0, "slots": 2, "pages_free": 0,
            "pages_total": 0}
    base.update(kw)
    return base


def test_balance_scales_the_hot_prefill_tier_up_cool_decode_down():
    sup = _balancing_supervisor({
        "p1": _rep("prefill", inflight=3, queue_depth=5, occupancy=1.0),
        "d1": _rep("decode", pages_free=60, pages_total=64),
    })
    assert sup._balance_role("up") == "prefill"
    assert sup._balance_role("down") == "decode"


def test_balance_scales_the_hot_decode_tier_up_cool_prefill_down():
    sup = _balancing_supervisor({
        "p1": _rep("prefill"),
        "d1": _rep("decode", pages_free=2, pages_total=64),
    })
    assert sup._balance_role("up") == "decode"
    assert sup._balance_role("down") == "prefill"


def test_balance_falls_back_when_a_tier_is_unmeasurable_or_off():
    # No up decode member: the injected role_for decides.
    sup = _balancing_supervisor({
        "p1": _rep("prefill", queue_depth=9),
        "d1": _rep("decode", pages_free=1, pages_total=64,
                   state="down"),
    })
    assert sup._balance_role("up") == "mixed"
    # Balancing disabled entirely: role_for decides even with data.
    sup = _balancing_supervisor({
        "p1": _rep("prefill", queue_depth=9),
        "d1": _rep("decode", pages_free=60, pages_total=64),
    }, balance=False)
    assert sup._balance_role("up") == "mixed"


def test_balance_non_paged_decode_uses_occupancy():
    sup = _balancing_supervisor({
        "p1": _rep("prefill"),
        "d1": _rep("decode", occupancy=0.95),  # pages_total == 0
    })
    assert sup._balance_role("up") == "decode"


# -- bench gate ------------------------------------------------------------


@pytest.mark.slow
def test_bench_fleet_handoff_perf_smoke_meets_gates():
    """Run the handoff fast-path bench in smoke shape and hold it to the
    same FLOORS/FRAC_CEILS bench_diff enforces: v2 wire bytes under the
    ceiling vs v1, import stall under the blocking-v1 ceiling, token
    parity 1.0, zero recompiles on either tier, zero silent fallbacks."""
    env = dict(os.environ)
    env.update(BENCH_SMOKE="1", JAX_PLATFORMS="cpu",
               JAX_ENABLE_COMPILATION_CACHE="false")
    env.pop("XLA_FLAGS", None)  # subprocesses don't need 8 virtual devices
    out = subprocess.run(
        [sys.executable, "-c",
         "import json, bench; "
         "print(json.dumps(bench.bench_fleet_handoff_perf()))"],
        cwd=_REPO, env=env, capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stderr[-4000:]
    rows = json.loads(out.stdout.strip().splitlines()[-1])
    import bench
    by_name = {r["metric"]: r for r in rows}
    for name, floor in bench.FLOORS.items():
        if name in by_name:
            assert by_name[name]["value"] >= floor, by_name[name]
    for name, ceil in bench.FRAC_CEILS.items():
        if name in by_name:
            assert by_name[name]["frac"] <= ceil, by_name[name]
    assert "fleet_handoff_perf_token_parity" in by_name
    assert "fleet_handoff_v2_bytes_frac" in by_name
