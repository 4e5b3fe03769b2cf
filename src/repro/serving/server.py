"""Asyncio HTTP front end over the serving engine (DESIGN.md §11.2).

Stdlib-only (the container ships no web framework): a hand-rolled HTTP/1.1
server on `asyncio.start_server`, good enough for the four routes it speaks.

    POST /generate   JSON body (engine.SPEC_KEYS: prompt, max_tokens,
                     eos_id, priority, deadline_s, temperature, top_k,
                     top_p, seed + "stream"). With "stream": true the
                     response is application/x-ndjson, one JSON object per
                     token as it is sampled; otherwise one JSON object with
                     the terminal status and full token list.
    POST /cancel     {"rid": n} -> {"cancelled": bool}
    GET  /healthz    process liveness (always 200 while the loop runs)
    GET  /readyz     traffic-readiness: 503 while draining or when the
                     backend has died, else 200
    GET  /metrics    Prometheus text format: every numeric engine.stats()
                     counter plus the lifecycle counters (shed / timeout /
                     queue_depth / ...) under the `lutnn_serving_` prefix
    GET  /stats      the same stats as raw JSON

The engine itself is synchronous (blocking jitted forwards), so it is driven
by `EnginePump` — a daemon thread stepping the engine whenever work is
queued, diffing per-request token output through `TokenTap`, and firing
per-request event callbacks. The asyncio side bridges those callbacks into
per-connection `asyncio.Queue`s via `call_soon_threadsafe`. All engine
access (submit/cancel/step/stats) happens under one lock, preserving the
engine's single-threaded discipline. How long `submit` waits for that lock
is counted with the engine's counters (`submits`, `submit_wait_s`,
`submit_wait_max_s` in `stats()`), and the pump thread is always inside one
of the host spans `pump.step` (taking and holding the lock for one engine
step), `pump.dispatch` or `pump.idle`.

Graceful drain (SIGTERM or `FrontEnd.request_shutdown()`): stop admitting
(readyz -> 503, /generate -> 503), let in-flight requests finish, then stop
the server. `serve_forever()` returns the process exit code: 0 on a clean
drain, `EXIT_STRANDED` when `drain_timeout_s` expired with requests still
unresolved (those are aborted with status "error" so no rid is ever
silently lost).

`EngineSupervisor` (repro.serving.supervisor) implements the same backend
interface, so the front end serves a supervised multi-process engine with
zero changes — `launch/serve.py --port [--supervise]` wires both.
"""

from __future__ import annotations

import asyncio
import json
import signal
import threading
import time
from typing import Any, Callable

import jax

from repro.serving.engine import ServingEngine, TokenTap, submit_from_spec
from repro.serving.faults import InjectedKill

# event tuples fired at subscribers, from the pump/monitor thread:
#   ("tokens", list[int])            incremental output
#   ("restart", None)                generation restarted from scratch
#                                    (supervised backend, after a crash)
#   ("done", (status, out_tokens))   terminal
EventCallback = Callable[[tuple[str, Any]], None]

EXIT_STRANDED = 3


class EnginePump:
    """Drives a local ServingEngine on a daemon thread.

    Backend interface (shared with EngineSupervisor):
      submit(spec, on_event) -> rid ; cancel(rid) ; stats() ; pending() ;
      healthy ; close()
    """

    def __init__(self, engine: ServingEngine, *, idle_wait_s: float = 0.02):
        self.engine = engine
        self._tap = TokenTap(engine, consume=True)
        self._subs: dict[int, EventCallback] = {}
        self._live: set[int] = set()
        self._lock = threading.RLock()
        self._wake = threading.Event()
        self._stop = False
        self._dead: BaseException | None = None
        self._idle_wait_s = idle_wait_s
        self._thread = threading.Thread(
            target=self._run, name="engine-pump", daemon=True
        )
        self._thread.start()

    # -- backend interface -------------------------------------------------
    @property
    def healthy(self) -> bool:
        return self._dead is None and not self._stop

    def submit(self, spec: dict[str, Any], on_event: EventCallback | None = None) -> int:
        t0 = time.perf_counter()
        with self._lock:
            self.engine.count_submit_wait(time.perf_counter() - t0)
            if self._dead is not None:
                raise RuntimeError(f"engine died: {self._dead!r}")
            rid = submit_from_spec(self.engine, spec)
            self._live.add(rid)
            if on_event is not None:
                self._subs[rid] = on_event
        self._wake.set()
        return rid

    def cancel(self, rid: int) -> bool:
        with self._lock:
            hit = self.engine.cancel(rid)
        if hit:
            self._wake.set()       # pump dispatches the "cancelled" done event
        return hit

    def stats(self) -> dict[str, Any]:
        with self._lock:
            s = self.engine.stats()
        s["backend"] = "local"
        s["restarts"] = 0
        s["pending"] = self.pending()
        return s

    def pending(self) -> int:
        with self._lock:
            return len(self._live)

    def close(self) -> None:
        self._stop = True
        self._wake.set()
        self._thread.join(timeout=10)

    def abort_pending(self) -> int:
        """Force-resolve every live request with status "error" (used when a
        drain deadline expires). Returns how many were aborted."""
        with self._lock:
            n = len(self.engine.abort_all("error"))
        self._wake.set()
        return n

    # -- pump loop ---------------------------------------------------------
    def _dispatch(self, events: list[tuple[int, tuple[str, Any]]]) -> None:
        with jax.profiler.TraceAnnotation("pump.dispatch") as span:
            if span.is_enabled():
                span.set_metadata(events=len(events))
            for rid, ev in events:
                cb = self._subs.get(rid)
                if cb is not None:
                    try:
                        cb(ev)
                    except Exception:      # noqa: BLE001 — a bad subscriber
                        pass               # must not kill the pump
                if ev[0] == "done":
                    self._subs.pop(rid, None)

    def _run(self) -> None:
        while not self._stop:
            out: list[tuple[int, tuple[str, Any]]] = []
            with jax.profiler.TraceAnnotation("pump.step"), self._lock:
                work = self.engine.has_work()
                if work and self._dead is None:
                    try:
                        self.engine.step()
                    except (Exception, InjectedKill) as e:  # noqa: BLE001
                        # unsupervised backend: an engine fault is fatal —
                        # resolve every live rid as "error", refuse new work
                        self._dead = e
                        self.engine.abort_all("error")
                tokens, done = self._tap.poll()
                out.extend((rid, ("tokens", toks)) for rid, toks in tokens)
                for req in done:
                    self._live.discard(req.rid)
                    out.append((req.rid, ("done", (req.status, req.out_tokens))))
            self._dispatch(out)
            if not work:
                with jax.profiler.TraceAnnotation("pump.idle"):
                    self._wake.wait(self._idle_wait_s)
                    self._wake.clear()


# ---------------------------------------------------------------------------
# HTTP front end
# ---------------------------------------------------------------------------

_REASONS = {200: "OK", 400: "Bad Request", 404: "Not Found",
             405: "Method Not Allowed", 429: "Too Many Requests",
             503: "Service Unavailable"}


def metrics_text(stats: dict[str, Any], prefix: str = "lutnn_serving_") -> str:
    """Prometheus text exposition of every numeric stat.

    A `per_replica` sub-dict (EngineRouter) renders as labelled gauges —
    `lutnn_replica_<stat>{replica="i"}` — one TYPE line per family."""
    lines = []
    for k in sorted(stats):
        v = stats[k]
        if isinstance(v, bool) or not isinstance(v, (int, float)):
            continue
        name = prefix + k
        lines.append(f"# TYPE {name} gauge")
        lines.append(f"{name} {v}")
    per = stats.get("per_replica")
    if isinstance(per, dict):
        families: dict[str, list[str]] = {}
        for rep in sorted(per, key=lambda r: (len(r), r)):
            for k in sorted(per[rep]):
                v = per[rep][k]
                if isinstance(v, bool) or not isinstance(v, (int, float)):
                    continue
                families.setdefault(f"lutnn_replica_{k}", []).append(
                    f'lutnn_replica_{k}{{replica="{rep}"}} {v}')
        for name in sorted(families):
            lines.append(f"# TYPE {name} gauge")
            lines.extend(families[name])
    return "\n".join(lines) + "\n"


class FrontEnd:
    def __init__(
        self,
        backend: Any,
        host: str = "127.0.0.1",
        port: int = 0,
        *,
        drain_timeout_s: float = 30.0,
    ):
        self.backend = backend
        self.host = host
        self.port = port          # 0 = ephemeral; real port set by start()
        self.drain_timeout_s = drain_timeout_s
        self.draining = False
        self.exit_code = 0
        self._server: asyncio.AbstractServer | None = None
        self._loop: asyncio.AbstractEventLoop | None = None
        self._done = None         # asyncio.Event, created in start()

    # -- lifecycle ---------------------------------------------------------
    async def start(self) -> None:
        self._loop = asyncio.get_running_loop()
        self._done = asyncio.Event()
        self._server = await asyncio.start_server(self._handle, self.host, self.port)
        self.port = self._server.sockets[0].getsockname()[1]

    def install_signal_handlers(self) -> None:
        for sig in (signal.SIGTERM, signal.SIGINT):
            self._loop.add_signal_handler(sig, self.request_shutdown)

    def request_shutdown(self) -> None:
        """Begin a graceful drain: stop admitting, finish in-flight, exit.
        Safe to call more than once; signal-handler and test entry point."""
        if not self.draining:
            self.draining = True
            self._loop.create_task(self._drain())

    async def _drain(self) -> None:
        deadline = time.monotonic() + self.drain_timeout_s
        while self.backend.pending() and time.monotonic() < deadline:
            await asyncio.sleep(0.02)
        stranded = self.backend.pending()
        if stranded:
            self.exit_code = EXIT_STRANDED
            abort = getattr(self.backend, "abort_pending", None)
            if abort is not None:
                abort()            # stranded rids still resolve (as "error")
        self._server.close()
        await self._server.wait_closed()
        self._done.set()

    async def serve_forever(self) -> int:
        """Serve until a drain completes; returns the process exit code."""
        await self._done.wait()
        self.backend.close()
        return self.exit_code

    # -- request plumbing --------------------------------------------------
    async def _handle(self, reader: asyncio.StreamReader,
                      writer: asyncio.StreamWriter) -> None:
        try:
            request = await reader.readline()
            parts = request.decode("latin-1").split()
            if len(parts) < 2:
                return
            method, path = parts[0].upper(), parts[1]
            headers = {}
            while True:
                line = await reader.readline()
                if line in (b"\r\n", b"\n", b""):
                    break
                k, _, v = line.decode("latin-1").partition(":")
                headers[k.strip().lower()] = v.strip()
            body = b""
            n = int(headers.get("content-length", "0") or 0)
            if n:
                body = await reader.readexactly(n)
            await self._route(method, path, body, writer)
        except (asyncio.IncompleteReadError, ConnectionError):
            pass
        finally:
            try:
                writer.close()
                await writer.wait_closed()
            except (ConnectionError, RuntimeError):
                pass

    def _respond(self, writer: asyncio.StreamWriter, code: int, payload: Any,
                 content_type: str = "application/json") -> None:
        body = (json.dumps(payload).encode()
                if content_type == "application/json"
                else payload.encode())
        writer.write(
            f"HTTP/1.1 {code} {_REASONS.get(code, '')}\r\n"
            f"Content-Type: {content_type}\r\n"
            f"Content-Length: {len(body)}\r\n"
            f"Connection: close\r\n\r\n".encode() + body
        )

    async def _route(self, method: str, path: str, body: bytes,
                     writer: asyncio.StreamWriter) -> None:
        if path == "/healthz":
            self._respond(writer, 200, "ok\n", "text/plain")
        elif path == "/readyz":
            ready = not self.draining and self.backend.healthy
            self._respond(writer, 200 if ready else 503,
                          ("ready\n" if ready else "draining\n"), "text/plain")
        elif path == "/metrics":
            self._respond(writer, 200, metrics_text(self.backend.stats()),
                          "text/plain; version=0.0.4")
        elif path == "/stats":
            self._respond(writer, 200, self.backend.stats())
        elif path == "/generate":
            if method != "POST":
                self._respond(writer, 405, {"error": "POST required"})
            else:
                await self._generate(body, writer)
        elif path == "/cancel":
            if method != "POST":
                self._respond(writer, 405, {"error": "POST required"})
            else:
                try:
                    rid = int(json.loads(body or b"{}")["rid"])
                except (ValueError, KeyError, TypeError):
                    self._respond(writer, 400, {"error": "body must be {'rid': int}"})
                    return
                self._respond(writer, 200, {"cancelled": self.backend.cancel(rid)})
        else:
            self._respond(writer, 404, {"error": f"no route {path}"})

    async def _generate(self, body: bytes, writer: asyncio.StreamWriter) -> None:
        if self.draining or not self.backend.healthy:
            self._respond(writer, 503, {"error": "draining" if self.draining
                                        else "engine unavailable"})
            return
        try:
            spec = json.loads(body or b"{}")
            if not isinstance(spec, dict):
                raise ValueError("body must be a JSON object")
            stream = bool(spec.pop("stream", False))
        except ValueError as e:
            self._respond(writer, 400, {"error": str(e)})
            return

        q: asyncio.Queue = asyncio.Queue()
        loop = self._loop

        def on_event(ev: tuple[str, Any]) -> None:
            loop.call_soon_threadsafe(q.put_nowait, ev)

        try:
            rid = self.backend.submit(spec, on_event)
        except (ValueError, TypeError) as e:
            self._respond(writer, 400, {"error": str(e)})
            return
        except RuntimeError as e:           # backend died between checks
            self._respond(writer, 503, {"error": str(e)})
            return

        if stream:
            await self._stream_events(rid, q, writer)
        else:
            tokens: list[int] = []
            restarts = 0
            while True:
                kind, payload = await q.get()
                if kind == "tokens":
                    tokens.extend(payload)
                elif kind == "restart":
                    tokens.clear()
                    restarts += 1
                elif kind == "done":
                    status, out_tokens = payload
                    resp = {"rid": rid, "status": status, "tokens": out_tokens,
                            "n_tokens": len(out_tokens)}
                    if restarts:
                        resp["restarts"] = restarts
                    code = {"ok": 200, "shed": 429}.get(status, 200)
                    self._respond(writer, code, resp)
                    return

    async def _stream_events(self, rid: int, q: asyncio.Queue,
                             writer: asyncio.StreamWriter) -> None:
        def line(obj: dict) -> bytes:
            return (json.dumps(obj) + "\n").encode()

        writer.write(
            b"HTTP/1.1 200 OK\r\n"
            b"Content-Type: application/x-ndjson\r\n"
            b"Cache-Control: no-store\r\n"
            b"Connection: close\r\n\r\n"
        )
        writer.write(line({"rid": rid}))
        try:
            await writer.drain()
            while True:
                kind, payload = await q.get()
                if kind == "tokens":
                    for tok in payload:
                        writer.write(line({"rid": rid, "token": tok}))
                elif kind == "restart":
                    # supervised backend restarted generation from scratch:
                    # the client must discard tokens streamed so far
                    writer.write(line({"rid": rid, "restart": True}))
                elif kind == "done":
                    status, out_tokens = payload
                    writer.write(line({"rid": rid, "status": status,
                                       "tokens": out_tokens,
                                       "n_tokens": len(out_tokens)}))
                    await writer.drain()
                    return
                await writer.drain()
        except (ConnectionError, RuntimeError):
            # client went away mid-stream: cancel so the request stops
            # burning decode steps (best effort — it may already be done)
            self.backend.cancel(rid)


async def run_server(
    backend: Any,
    host: str = "127.0.0.1",
    port: int = 8000,
    *,
    drain_timeout_s: float = 30.0,
    signals: bool = True,
    on_started: Callable[["FrontEnd"], None] | None = None,
) -> int:
    """Start a FrontEnd and serve until SIGTERM/SIGINT drains it.
    Returns the process exit code (see module docstring)."""
    fe = FrontEnd(backend, host, port, drain_timeout_s=drain_timeout_s)
    await fe.start()
    if signals:
        fe.install_signal_handlers()
    if on_started is not None:
        on_started(fe)
    return await fe.serve_forever()
