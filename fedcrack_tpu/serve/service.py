"""gRPC front door for the serving plane.

One bidi-streaming ``Predict`` RPC (``fedcrack.ServePlane``), hand-bound like
the control plane's ``FedControl`` (transport/service.py — no codegen
plugin). Requests stream in as LogChunk-style framed image chunks
(offset/last + optional CRC32C per chunk); on the final chunk the image is
assembled and routed:

- exact bucket shape -> the micro-batcher (dynamic batching, the hot path);
- smaller than a bucket -> zero-padded into the smallest holding bucket via
  the batcher, output cropped;
- larger than every bucket -> tiled sliding-window inference, pinned to one
  weights snapshot for the whole request (a multi-batch tiled request must
  not straddle a swap either).

Responses carry the thresholded uint8 mask plus the model version and
queue/total latency for client-side SLO accounting.
"""

from __future__ import annotations

import asyncio
import dataclasses
import logging
import threading
import time
from typing import Any, AsyncIterator

import grpc
import numpy as np

from fedcrack_tpu.analysis.sanitizers import make_lock
from fedcrack_tpu.transport import transport_pb2 as pb
from fedcrack_tpu.transport.service import channel_options

log = logging.getLogger("fedcrack.serve")

SERVE_SERVICE_NAME = "fedcrack.ServePlane"
PREDICT_METHOD = "Predict"
PREDICT_PATH = f"/{SERVE_SERVICE_NAME}/{PREDICT_METHOD}"
STREAM_METHOD = "StreamPredict"
STREAM_PATH = f"/{SERVE_SERVICE_NAME}/{STREAM_METHOD}"

OK = "OK"
REJECTED = "REJECTED"
# Admission-control shed (round 17, serve/router.py): the fleet refused the
# request BEFORE queueing it — the gRPC-status-code-shaped loud reject a
# client backs off on, distinct from REJECTED (malformed request).
SHED = "RESOURCE_EXHAUSTED"

# Per-stream assembly caps: chunks accumulate server-side until `last`, so an
# unbounded stream of never-finishing requests must hit a ceiling — on total
# buffered bytes AND on the number of open request entries (empty-payload
# chunks would never trip the byte cap).
MAX_PENDING_BYTES = 256 * 1024 * 1024
MAX_PENDING_REQUESTS = 1024


@dataclasses.dataclass
class _Pending:
    height: int
    width: int
    channels: int
    threshold: float
    deadline_ms: float
    chunks: bytearray = dataclasses.field(default_factory=bytearray)


def _reject(request_id: int, reason: str) -> pb.PredictResponse:
    return pb.PredictResponse(request_id=request_id, status=REJECTED, title=reason)


class ServeService:
    """The Predict handler over one engine + batcher + weights source."""

    def __init__(
        self, engine: Any, batcher: Any, weights: Any, stream_manager: Any = None
    ):
        self.engine = engine
        self.batcher = batcher
        self.weights = weights
        # Frame-coherent video serving (round 19): a StreamSessionManager
        # turns StreamPredict RPCs into per-stream tile-cached sessions.
        # None leaves the RPC registered but loudly rejecting.
        self.stream_manager = stream_manager
        self._lock = make_lock("serve.service.stats")
        self.tiled_served = 0
        self.rejected = 0
        self.shed = 0

    # ---- request assembly ----

    def _validate_chunk(self, msg: pb.PredictRequest, pending: dict) -> str | None:
        if msg.height <= 0 or msg.width <= 0:
            return f"bad dimensions {msg.height}x{msg.width}"
        if msg.channels != 3:
            return f"channels must be 3 (RGB), got {msg.channels}"
        if msg.HasField("crc32c"):
            from fedcrack_tpu.native import crc32c

            got = crc32c(msg.image)
            if got != msg.crc32c:
                return (
                    f"image chunk checksum mismatch at offset {msg.offset}: "
                    f"computed {got:#010x}, declared {msg.crc32c:#010x}"
                )
        total = sum(len(p.chunks) for p in pending.values())
        if total + len(msg.image) > MAX_PENDING_BYTES:
            return "per-stream pending image bytes exceed the assembly cap"
        if msg.request_id not in pending and len(pending) >= MAX_PENDING_REQUESTS:
            return "per-stream open request entries exceed the assembly cap"
        return None

    def _assemble(self, p: _Pending) -> np.ndarray | str:
        want = p.height * p.width * p.channels
        if len(p.chunks) != want:
            return f"image bytes {len(p.chunks)} != {p.height}x{p.width}x{p.channels}"
        return np.frombuffer(bytes(p.chunks), np.uint8).reshape(
            p.height, p.width, p.channels
        )

    # ---- routing ----

    async def _serve_one(
        self, request_id: int, image: np.ndarray, p: _Pending
    ) -> pb.PredictResponse:
        h, w, _ = image.shape
        threshold = p.threshold if 0.0 < p.threshold < 1.0 else 0.5
        deadline = p.deadline_ms if p.deadline_ms > 0 else None
        bucket = self.engine.bucket_for(h, w)
        t0 = time.monotonic()
        if bucket is not None:
            canvas = image
            if (h, w) != (bucket, bucket):
                canvas = np.zeros((bucket, bucket, 3), np.uint8)
                canvas[:h, :w] = image
            fut = self.batcher.submit(canvas, deadline_ms=deadline)
            res = await asyncio.wrap_future(fut)
            probs = res.probs[:h, :w]
            version = res.model_version
            queue_ms, latency_ms = res.queue_ms, res.latency_ms
        else:
            # Tiled path: pin ONE snapshot for the whole request.
            version, variables = self.weights.snapshot()
            probs = await asyncio.to_thread(
                self.engine.predict_tiled, variables, image
            )
            queue_ms = 0.0
            latency_ms = (time.monotonic() - t0) * 1e3
            with self._lock:
                self.tiled_served += 1
        mask = ((probs[..., 0] > threshold).astype(np.uint8) * 255).tobytes()
        return pb.PredictResponse(
            request_id=request_id,
            status=OK,
            mask=mask,
            model_version=version,
            latency_ms=latency_ms,
            queue_ms=queue_ms,
            height=h,
            width=w,
        )

    # ---- the stream handler ----

    async def predict_session(
        self, request_iterator: AsyncIterator[pb.PredictRequest], context
    ) -> AsyncIterator[pb.PredictResponse]:
        pending: dict[int, _Pending] = {}
        # request_ids already REJECTED mid-assembly: exactly ONE response per
        # request goes out (clients count responses 1:1 with requests), so
        # later chunks of a dead request are swallowed until its `last`
        # chunk retires the id.
        dead: set[int] = set()
        async for msg in request_iterator:
            if msg.request_id in dead:
                if msg.last:
                    dead.discard(msg.request_id)
                continue
            bad = self._validate_chunk(msg, pending)
            if bad is not None:
                pending.pop(msg.request_id, None)
                if not msg.last:
                    dead.add(msg.request_id)
                with self._lock:
                    self.rejected += 1
                yield _reject(msg.request_id, bad)
                continue
            p = pending.get(msg.request_id)
            if p is None:
                p = _Pending(
                    height=msg.height,
                    width=msg.width,
                    channels=msg.channels,
                    threshold=msg.threshold,
                    deadline_ms=msg.deadline_ms,
                )
                pending[msg.request_id] = p
            if msg.offset != len(p.chunks):
                pending.pop(msg.request_id, None)
                if not msg.last:
                    dead.add(msg.request_id)
                with self._lock:
                    self.rejected += 1
                yield _reject(
                    msg.request_id,
                    f"chunk offset {msg.offset} != received {len(p.chunks)}",
                )
                continue
            p.chunks.extend(msg.image)
            if not msg.last:
                continue
            del pending[msg.request_id]
            image = self._assemble(p)
            if isinstance(image, str):
                with self._lock:
                    self.rejected += 1
                yield _reject(msg.request_id, image)
                continue
            try:
                yield await self._serve_one(msg.request_id, image, p)
            except Exception as e:  # a failed batch errors THIS request only
                from fedcrack_tpu.serve.router import LoadShedError

                if isinstance(e, LoadShedError):
                    # Admission control fired: loud RESOURCE_EXHAUSTED with
                    # the shed reason — never a silent drop, never a stall.
                    with self._lock:
                        self.shed += 1
                    yield pb.PredictResponse(
                        request_id=msg.request_id, status=SHED, title=str(e)
                    )
                    continue
                log.exception("predict failed for request %d", msg.request_id)
                with self._lock:
                    self.rejected += 1
                yield _reject(msg.request_id, repr(e))

    # ---- the video-stream handler (round 19) ----

    async def stream_session(
        self, request_iterator: AsyncIterator[pb.StreamRequest], context
    ) -> AsyncIterator[pb.StreamResponse]:
        """One open/frames/close video session protocol over a bidi stream.

        Every Open, every completed frame, and every Close gets exactly one
        response (clients count 1:1); frame chunks reuse the LogChunk
        offset/last + optional CRC32C idiom. Frames within a stream are
        served in arrival order — the ordering the tile cache and the crack
        tracker are defined over. Sessions opened on this RPC are closed
        when the RPC ends, so a dropped connection cannot leak session
        slots toward the ``stream_max_sessions`` bound."""
        from fedcrack_tpu.serve.stream import tracks_to_json

        opened: dict[str, Any] = {}      # stream_id -> StreamSession
        frames: dict[str, dict] = {}     # stream_id -> in-flight chunk state
        try:
            async for msg in request_iterator:
                sid = msg.stream_id
                kind = msg.WhichOneof("msg")
                if self.stream_manager is None:
                    with self._lock:
                        self.rejected += 1
                    yield pb.StreamResponse(
                        status=REJECTED, title="video serving not enabled"
                    )
                    continue
                if kind == "open":
                    o = msg.open
                    if o.channels not in (0, 3):
                        bad = f"channels must be 3 (RGB), got {o.channels}"
                    elif sid in opened:
                        bad = f"stream {sid!r} is already open on this call"
                    else:
                        bad = None
                    if bad is None:
                        try:
                            opened[sid] = self.stream_manager.open(
                                sid,
                                height=o.height,
                                width=o.width,
                                track=o.track,
                                smooth_alpha=o.smooth_alpha,
                                threshold=o.threshold,
                            )
                        except ValueError as e:
                            bad = str(e)
                    if bad is not None:
                        with self._lock:
                            self.rejected += 1
                        yield pb.StreamResponse(status=REJECTED, title=bad)
                        continue
                    yield pb.StreamResponse(
                        status=OK,
                        title="OPENED",
                        height=o.height,
                        width=o.width,
                    )
                elif kind == "frame":
                    session = opened.get(sid)
                    if session is None:
                        with self._lock:
                            self.rejected += 1
                        yield pb.StreamResponse(
                            frame_id=msg.frame.frame_id,
                            status=REJECTED,
                            title=f"stream {sid!r} is not open",
                        )
                        continue
                    f = msg.frame
                    if f.HasField("crc32c"):
                        from fedcrack_tpu.native import crc32c

                        got = crc32c(f.image)
                        if got != f.crc32c:
                            frames.pop(sid, None)
                            with self._lock:
                                self.rejected += 1
                            yield pb.StreamResponse(
                                frame_id=f.frame_id,
                                status=REJECTED,
                                title=(
                                    f"frame chunk checksum mismatch at offset "
                                    f"{f.offset}: computed {got:#010x}, "
                                    f"declared {f.crc32c:#010x}"
                                ),
                            )
                            continue
                    st = frames.get(sid)
                    if st is None or st["frame_id"] != f.frame_id:
                        st = {"frame_id": f.frame_id, "chunks": bytearray()}
                        frames[sid] = st
                    if f.offset != len(st["chunks"]):
                        frames.pop(sid, None)
                        with self._lock:
                            self.rejected += 1
                        yield pb.StreamResponse(
                            frame_id=f.frame_id,
                            status=REJECTED,
                            title=(
                                f"chunk offset {f.offset} != received "
                                f"{len(st['chunks'])}"
                            ),
                        )
                        continue
                    st["chunks"].extend(f.image)
                    if not f.last:
                        continue
                    frames.pop(sid, None)
                    want = session.height * session.width * 3
                    if len(st["chunks"]) != want:
                        with self._lock:
                            self.rejected += 1
                        yield pb.StreamResponse(
                            frame_id=f.frame_id,
                            status=REJECTED,
                            title=(
                                f"frame bytes {len(st['chunks'])} != "
                                f"{session.height}x{session.width}x3"
                            ),
                        )
                        continue
                    image = np.frombuffer(bytes(st["chunks"]), np.uint8).reshape(
                        session.height, session.width, 3
                    )
                    try:
                        result = await asyncio.to_thread(
                            session.process_frame, image
                        )
                    except Exception as e:  # errors THIS frame only
                        log.exception(
                            "stream frame failed (%s, frame %d)", sid, f.frame_id
                        )
                        with self._lock:
                            self.rejected += 1
                        yield pb.StreamResponse(
                            frame_id=f.frame_id, status=REJECTED, title=repr(e)
                        )
                        continue
                    self.stream_manager.record(result)
                    yield pb.StreamResponse(
                        frame_id=f.frame_id,
                        status=OK,
                        mask=result.mask_bytes(session.threshold),
                        model_version=result.model_version,
                        latency_ms=result.latency_ms,
                        height=session.height,
                        width=session.width,
                        tiles_total=result.tiles_total,
                        tiles_computed=result.tiles_computed,
                        cache_hits=result.cache_hits,
                        full_rerun=result.full_rerun,
                        tracks_json=(
                            tracks_to_json(result.tracks)
                            if session.tracker is not None
                            else ""
                        ),
                    )
                elif kind == "close":
                    if opened.pop(sid, None) is None:
                        with self._lock:
                            self.rejected += 1
                        yield pb.StreamResponse(
                            status=REJECTED, title=f"stream {sid!r} is not open"
                        )
                        continue
                    self.stream_manager.close(sid)
                    frames.pop(sid, None)
                    yield pb.StreamResponse(status=OK, title="CLOSED")
                else:
                    with self._lock:
                        self.rejected += 1
                    yield pb.StreamResponse(
                        status=REJECTED, title="empty StreamRequest"
                    )
        finally:
            if self.stream_manager is not None:
                for sid in opened:
                    self.stream_manager.close(sid)


class ServeServer:
    """Binds a :class:`ServeService` on an asyncio gRPC server."""

    def __init__(
        self,
        service: ServeService,
        host: str = "127.0.0.1",
        port: int = 8890,
        max_message_mb: int = 64,
    ):
        self.service = service
        self._host = host
        self._port = port
        self._max_message_mb = max_message_mb
        self._server: grpc.aio.Server | None = None
        self.bound_port: int | None = None

    async def start(self) -> int:
        server = grpc.aio.server(options=channel_options(self._max_message_mb))
        handler = grpc.stream_stream_rpc_method_handler(
            self.service.predict_session,
            request_deserializer=pb.PredictRequest.FromString,
            response_serializer=pb.PredictResponse.SerializeToString,
        )
        stream_handler = grpc.stream_stream_rpc_method_handler(
            self.service.stream_session,
            request_deserializer=pb.StreamRequest.FromString,
            response_serializer=pb.StreamResponse.SerializeToString,
        )
        server.add_generic_rpc_handlers(
            (
                grpc.method_handlers_generic_handler(
                    SERVE_SERVICE_NAME,
                    {PREDICT_METHOD: handler, STREAM_METHOD: stream_handler},
                ),
            )
        )
        self.bound_port = server.add_insecure_port(f"{self._host}:{self._port}")
        await server.start()
        self._server = server
        log.info("serving plane on %s:%s", self._host, self.bound_port)
        return self.bound_port

    async def stop(self, grace: float = 1.0) -> None:
        if self._server is not None:
            await self._server.stop(grace)


class ServeServerThread:
    """Runs a :class:`ServeServer` on its own loop in a daemon thread — the
    in-process harness for tests, chip_smoke.py and load_gen smoke runs."""

    def __init__(self, server: ServeServer):
        self.server = server
        self.loop = asyncio.new_event_loop()
        self.port: int | None = None
        self._started = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)

    def _run(self) -> None:
        asyncio.set_event_loop(self.loop)
        self.port = self.loop.run_until_complete(self.server.start())
        self._started.set()
        self.loop.run_forever()

    def __enter__(self) -> "ServeServerThread":
        self._thread.start()
        if not self._started.wait(timeout=30):
            raise RuntimeError("serve server failed to start")
        return self

    def __exit__(self, *exc) -> None:
        fut = asyncio.run_coroutine_threadsafe(self.server.stop(grace=0.5), self.loop)
        try:
            fut.result(timeout=10)
        finally:
            self.loop.call_soon_threadsafe(self.loop.stop)
            self._thread.join(timeout=10)
