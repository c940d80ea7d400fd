"""Asyncio HTTP/SSE front door over the paged engine (DESIGN.md §12).

Hand-rolled on ``asyncio.start_server`` — no web framework, no new deps.
One connection carries one HTTP/1.1 request (``Connection: close``):

  ``POST /v1/generate``  JSON body -> per-token SSE stream (or one JSON
                         response with ``"stream": false``)
  ``GET  /healthz``      liveness probe
  ``GET  /v1/stats``     engine + scheduler counters

Threading model: the asyncio event loop owns sockets only.  The engine and
scheduler live on ONE background driver thread (JAX dispatch, block
accounting, and queue state are single-threaded by construction), which
loops ``scheduler.tick()`` whenever there is work.  The bridges between
the two worlds are explicit and small:

- submit: the HTTP handler builds a ``Request`` whose ``on_token`` closure
  posts ``(event, data)`` onto that stream's ``asyncio.Queue`` via
  ``loop.call_soon_threadsafe``, then hands it to the scheduler under
  ``self._lock`` and wakes the driver.
- completion: the driver thread posts the terminal ``done`` (or ``error``)
  event the same way.
- disconnect: a failed SSE write cancels the request through the
  scheduler, so an abandoned stream stops burning pool capacity.

Every generation response streams ``event: token`` frames
(``{"rid", "i", "token", "text", "t"}``) and ends with ``event: done``
(``{"rid", "tokens", "text", "ttft_s", "n_tokens", "preemptions",
"tenant"}``).  Preemption is invisible in the stream except as a pause:
tokens already streamed are never re-sent (the engine re-feeds them as
prompt on replay, emitting only genuinely new tokens).
"""
from __future__ import annotations

import asyncio
import contextlib
import itertools
import json
import signal
import threading
import traceback

import numpy as np

from ..engine import Request
from .scheduler import SchedConfig, Scheduler
from .sse import encode_event

_MAX_BODY = 1 << 20      # 1 MiB request-body cap


def _json_default(o):
    if isinstance(o, (np.floating,)):
        return float(o)
    if isinstance(o, (np.integer,)):
        return int(o)
    if isinstance(o, np.ndarray):
        return o.tolist()
    return str(o)


def _http_response(status: str, body: bytes, ctype: str = "application/json"
                   ) -> bytes:
    return (f"HTTP/1.1 {status}\r\n"
            f"Content-Type: {ctype}\r\n"
            f"Content-Length: {len(body)}\r\n"
            f"Connection: close\r\n\r\n").encode() + body


def _sse_headers() -> bytes:
    return (b"HTTP/1.1 200 OK\r\n"
            b"Content-Type: text/event-stream\r\n"
            b"Cache-Control: no-cache\r\n"
            b"Connection: close\r\n\r\n")


class FrontDoor:
    """The streaming HTTP server that owns a ``PagedServer`` + ``Scheduler``.

    ``tokenize`` / ``detokenize`` translate between request-body strings
    and model tokens; they default to the repo's ``ByteTokenizer`` over the
    engine's vocab.  ``port=0`` binds an ephemeral port; the chosen one is
    printed as ``frontdoor listening on HOST:PORT`` (the smoke tests parse
    that line) and stored back on ``self.port``.
    """

    def __init__(self, engine, cfg: SchedConfig | None = None, *,
                 host: str = "127.0.0.1", port: int = 8080,
                 tokenize=None, detokenize=None):
        self.engine = engine
        self.scheduler = Scheduler(engine, cfg)
        self.host, self.port = host, port
        if tokenize is None or detokenize is None:
            from repro.data import ByteTokenizer
            tok = ByteTokenizer(engine.cfg.vocab)
            tokenize = tokenize or tok.encode
            detokenize = detokenize or (
                lambda ids: tok.decode(np.asarray(ids, np.int32)))
        self.tokenize, self.detokenize = tokenize, detokenize
        self._rids = itertools.count()
        self._lock = threading.Lock()        # scheduler + engine state
        self._watchers: dict[int, asyncio.Queue] = {}
        self._wake = threading.Event()       # driver: new work submitted
        self._stopping = threading.Event()
        self.listening = threading.Event()   # set once self.port is bound
        self._loop: asyncio.AbstractEventLoop | None = None
        self._stop: asyncio.Event | None = None

    # -------------------------------------------------------- driver thread

    def _post(self, q: asyncio.Queue, item) -> None:
        self._loop.call_soon_threadsafe(q.put_nowait, item)

    def _drive(self) -> None:
        """The serving loop: tick the scheduler while there is work, sleep
        on the wake event while there isn't.  A crash here is fatal to the
        server (every open stream gets an ``error`` event first) — the
        engine's state cannot be trusted after an arbitrary exception."""
        self.engine.start_clock()
        try:
            while not self._stopping.is_set():
                with self._lock:
                    busy = self.scheduler.has_work()
                    finished = self.scheduler.tick() if busy else {}
                    done_watch = [(self._watchers.pop(rid, None), res)
                                  for rid, res in finished.items()]
                for q, res in done_watch:
                    if q is not None:
                        self._post(q, ("done", self._done_payload(res)))
                if not busy:
                    self._wake.wait(0.02)
                    self._wake.clear()
        except Exception:                                 # noqa: BLE001
            traceback.print_exc()
            with self._lock:
                watchers, self._watchers = dict(self._watchers), {}
            for q in watchers.values():
                self._post(q, ("error", {"error": "engine failure"}))
            self._stopping.set()

    def _done_payload(self, res) -> dict:
        toks = [int(t) for t in res.tokens]
        return {"rid": res.rid, "tokens": toks, "text": self.detokenize(toks),
                "n_tokens": len(toks), "ttft_s": float(res.ttft_s),
                "preemptions": int(res.preemptions), "tenant": res.tenant}

    # --------------------------------------------------------- HTTP parsing

    async def _handle(self, reader: asyncio.StreamReader,
                      writer: asyncio.StreamWriter) -> None:
        try:
            request_line = await reader.readline()
            parts = request_line.decode("latin-1").split()
            if len(parts) < 2:
                return
            method, path = parts[0], parts[1]
            headers = {}
            while True:
                line = await reader.readline()
                if line in (b"\r\n", b"\n", b""):
                    break
                k, _, v = line.decode("latin-1").partition(":")
                headers[k.strip().lower()] = v.strip()
            clen = int(headers.get("content-length", 0) or 0)
            if clen > _MAX_BODY:
                writer.write(_http_response(
                    "413 Payload Too Large",
                    b'{"error": "request body too large"}'))
                return
            body = await reader.readexactly(clen) if clen else b""
            if method == "POST" and path == "/v1/generate":
                await self._generate(body, writer)
            elif method == "GET" and path == "/healthz":
                writer.write(_http_response("200 OK", b'{"ok": true}'))
            elif method == "GET" and path == "/v1/stats":
                writer.write(_http_response("200 OK", self._stats_body()))
            else:
                writer.write(_http_response(
                    "404 Not Found", b'{"error": "no such route"}'))
            await writer.drain()
        except (ConnectionError, asyncio.IncompleteReadError):
            pass
        finally:
            with contextlib.suppress(Exception):
                writer.close()
                await writer.wait_closed()

    def _stats_body(self) -> bytes:
        with self._lock:
            stats = dict(self.engine.finalize_stats())
            sched = dict(self.scheduler.stats)
            snap = {"engine": stats, "scheduler": sched,
                    "queued": self.scheduler.queued(),
                    "slo_throttled": self.scheduler.throttled,
                    "slo_last_p95_ms": self.scheduler.last_p95_ms}
        # the raw per-step lists are internal accounting, not API surface
        snap["engine"].pop("occupancy", None)
        return json.dumps(snap, default=_json_default).encode()

    # ----------------------------------------------------------- generation

    def _build_request(self, spec: dict, q: asyncio.Queue) -> Request:
        if "tokens" in spec:
            prompt = np.asarray(spec["tokens"], np.int32)
        elif "prompt" in spec:
            prompt = np.asarray(self.tokenize(str(spec["prompt"])), np.int32)
        else:
            raise ValueError('body needs "prompt" (string) or "tokens"')
        rid = next(self._rids)
        detok = self.detokenize

        def on_token(rid_, tok, t):
            self._post(q, ("token", {"rid": rid_, "token": tok,
                                     "text": detok([tok]), "t": t}))

        return Request(
            rid=rid, prompt=prompt, max_new=int(spec.get("max_new", 16)),
            eos=spec.get("eos"), arrival=self.engine.now(),
            tenant=str(spec.get("tenant", "default")),
            priority=int(spec.get("priority", 0)),
            deadline=spec.get("deadline_s"),
            on_token=on_token if spec.get("stream", True) else None)

    async def _generate(self, body: bytes,
                        writer: asyncio.StreamWriter) -> None:
        try:
            spec = json.loads(body or b"{}")
            if not isinstance(spec, dict):
                raise ValueError("body must be a JSON object")
            q: asyncio.Queue = asyncio.Queue()
            req = self._build_request(spec, q)
            with self._lock:
                # validates under the lock so a bad request 400s here
                # instead of crashing the driver thread
                self.scheduler.submit(req, weight=float(spec.get("weight",
                                                                 1.0)))
                self._watchers[req.rid] = q
        except (ValueError, TypeError, KeyError, json.JSONDecodeError) as e:
            writer.write(_http_response(
                "400 Bad Request",
                json.dumps({"error": str(e)}).encode()))
            return
        self._wake.set()
        streaming = bool(spec.get("stream", True))
        if streaming:
            writer.write(_sse_headers())
            await writer.drain()
        collected: dict | None = None
        try:
            while True:
                event, data = await q.get()
                if streaming:
                    writer.write(encode_event(event, data))
                    await writer.drain()
                if event in ("done", "error"):
                    collected = data
                    break
            if not streaming:
                status = ("200 OK" if "error" not in (collected or {})
                          else "500 Internal Server Error")
                writer.write(_http_response(
                    status, json.dumps(collected,
                                       default=_json_default).encode()))
                await writer.drain()
        except (ConnectionError, asyncio.CancelledError):
            # client went away mid-stream: stop burning pool capacity
            with self._lock:
                self._watchers.pop(req.rid, None)
                self.scheduler.cancel(req.rid)
            raise

    # -------------------------------------------------------------- running

    async def _main(self) -> None:
        self._loop = asyncio.get_running_loop()
        server = await asyncio.start_server(self._handle, self.host,
                                            self.port)
        self.port = server.sockets[0].getsockname()[1]
        print(f"frontdoor listening on {self.host}:{self.port}", flush=True)
        driver = threading.Thread(target=self._drive, daemon=True,
                                  name="frontdoor-driver")
        driver.start()
        self._stop = stop = asyncio.Event()
        # signal handlers exist only on the main thread; a server run from
        # another thread is stopped with shutdown()
        for sig in (signal.SIGINT, signal.SIGTERM):
            with contextlib.suppress(NotImplementedError, ValueError,
                                     RuntimeError):
                self._loop.add_signal_handler(sig, stop.set)
        self.listening.set()
        # a driver crash must also bring the listener down
        async def watch_driver():
            while driver.is_alive() and not self._stopping.is_set():
                await asyncio.sleep(0.1)
            stop.set()
        watcher = asyncio.ensure_future(watch_driver())
        try:
            await stop.wait()
        finally:
            self._stopping.set()
            self._wake.set()
            watcher.cancel()
            server.close()
            await server.wait_closed()
            driver.join(timeout=5.0)
            print("frontdoor shut down cleanly", flush=True)

    def shutdown(self) -> None:
        """Stop a server that runs on another thread, as SIGTERM would."""
        self._loop.call_soon_threadsafe(self._stop.set)

    def serve_forever(self) -> None:
        """Run until SIGINT/SIGTERM or ``shutdown()`` (clean shutdown) or
        driver crash."""
        try:
            asyncio.run(self._main())
        except KeyboardInterrupt:
            pass
