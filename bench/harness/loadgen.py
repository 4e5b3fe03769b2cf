"""Drives a serving backend (`EnginePump`'s interface: `submit(spec,
on_event)`, `cancel(rid)`) with a traffic plan, from the client's side.

Each token is timestamped when the backend's ("tokens", ...) event reaches
the client callback. An open-loop request counts its time from when it was
due, so a late generator or a blocked submit shows as latency; a closed-loop
request counts from when its client sent it. One thread sends, as the HTTP
front end's event loop does: `EnginePump.submit` takes the pump's lock,
which a running step holds, and every later request waits behind it.

Every request due in the window is sent, also when the generator runs late
past the window's close, and the run waits (`drain`) until each has its
first token: the tail of time to first token is the tail of all of them.
"""

from __future__ import annotations

import dataclasses
import queue
import threading
import time
from typing import Any

from harness.traffic import Plan


@dataclasses.dataclass
class Sent:
    idx: int
    spec: dict[str, Any]
    due: float                     # perf_counter time the request counts from
    submit_start: float = 0.0
    submit_end: float = 0.0
    rid: int | None = None
    events: list[tuple[float, int]] = dataclasses.field(default_factory=list)
    tokens: list[int] = dataclasses.field(default_factory=list)
    status: str | None = None      # terminal status once done
    done_t: float | None = None


class LoadGen:
    def __init__(self, backend: Any, plan: Plan):
        self.backend = backend
        self.plan = plan
        self.sent: list[Sent] = []
        self._lock = threading.Lock()
        self._free: queue.Queue[int] = queue.Queue()     # closed loop: idle clients
        self._stop = threading.Event()
        self.t0 = self.t_end = self.t_drained = 0.0

    # -- client side ---------------------------------------------------------
    def _on_event(self, s: Sent, client: int | None):
        def cb(ev):
            now = time.perf_counter()
            kind, payload = ev
            if kind == "tokens":
                s.events.append((now, len(payload)))
                s.tokens.extend(payload)
            elif kind == "done":
                s.status, s.done_t = payload[0], now
                s.tokens = list(payload[1])
                if client is not None:
                    self._free.put(client)
        return cb

    def _send(self, idx: int, due: float, client: int | None) -> None:
        spec = self.plan.specs[idx]
        s = Sent(idx, spec, due)
        with self._lock:
            self.sent.append(s)
        s.submit_start = time.perf_counter()
        try:
            s.rid = self.backend.submit(spec, self._on_event(s, client))
        except Exception as e:  # noqa: BLE001 - a refused request is a failed one
            s.status, s.done_t = f"error: {e!r}", time.perf_counter()
            if client is not None:
                self._free.put(client)
        s.submit_end = time.perf_counter()

    def _open_loop(self) -> None:
        # every request is due within the window: the loop ends by itself
        for idx, due in enumerate(self.plan.due_s):
            t = self.t0 + due
            wait = t - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            self._send(idx, t, None)

    def _closed_loop(self) -> None:
        nxt = 0
        for c in range(self.plan.clients):
            self._free.put(c)
        while not self._stop.is_set():
            try:
                c = self._free.get(timeout=0.05)
            except queue.Empty:
                continue
            if self._stop.is_set() or nxt >= len(self.plan.specs):
                return
            self._send(nxt, time.perf_counter(), c)
            nxt += 1

    # -- window --------------------------------------------------------------
    def run(self, seconds: float, during=None, at_close=None,
            drain_s: float = 90.0) -> None:
        """Send for `seconds` from now; `during(t0, t_end)` runs in this
        thread meanwhile (the traced run starts and stops its profiler
        there) and must return by t_end; `at_close()` runs at t_end. Then
        drain: wait until every sent request has its first token or ended,
        at most `drain_s`."""
        target = self._open_loop if self.plan.due_s is not None else self._closed_loop
        worker = threading.Thread(target=target, name="loadgen", daemon=True)
        self.t0 = time.perf_counter()
        self.t_end = self.t0 + seconds
        worker.start()
        if during is not None:
            during(self.t0, self.t_end)
        rest = self.t_end - time.perf_counter()
        if rest > 0:
            time.sleep(rest)
        self._stop.set()                  # a closed loop sends no more
        if at_close is not None:
            at_close()
        deadline = self.t_end + drain_s
        worker.join(max(0.0, deadline - time.perf_counter()))
        while time.perf_counter() < deadline:
            with self._lock:
                waiting = [s for s in self.sent if not s.events and s.status is None]
            if not waiting and not worker.is_alive():
                break
            time.sleep(0.01)
        self.t_drained = time.perf_counter()

    def abandon(self) -> None:
        """After the window, with the backend stopped: what is still live
        goes unanswered, as a client that hangs up."""
        with self._lock:
            for s in self.sent:
                if s.status is None:
                    s.status = "cancelled"

    def finish(self, timeout_s: float = 60.0) -> None:
        """Cancel what is still live after the window and wait until every
        sent request has its terminal event (the backend still running)."""
        with self._lock:
            live = [s for s in self.sent if s.status is None]
        for s in live:
            if s.rid is not None:
                self.backend.cancel(s.rid)
        deadline = time.perf_counter() + timeout_s
        while any(s.status is None for s in live):
            if time.perf_counter() > deadline:
                raise RuntimeError(f"{sum(s.status is None for s in live)} "
                                   f"requests never resolved after the window")
            time.sleep(0.01)

    def late_s(self) -> list[float]:
        """How late the generator sent each request (open loop)."""
        return [s.submit_start - s.due for s in self.sent]
