"""Deterministic fault-injection harness for the serving stack (DESIGN.md §11.3).

Robustness code that is never exercised is decoration, so every failure mode
the serving layer claims to survive — slow steps, transient step exceptions,
a dead worker process — is injectable on demand, deterministically:

  * `FaultSpec`     — a declarative, JSON-round-trippable schedule of faults
                      (latency spikes, step exceptions, one worker kill).
  * `FaultInjector` — the live hook object built from a spec. The engine
                      calls `on_step()` at the top of every `step()`; the
                      injector sleeps (spike), raises `InjectedFault`
                      (transient error — retryable by `StepGuard` /
                      restartable by the supervisor), or raises
                      `InjectedKill` (simulated hard crash — a
                      `BaseException` so no `except Exception` guard can
                      accidentally absorb it; the supervised worker converts
                      it to `os._exit`).

Determinism: probabilistic faults are drawn from `random.Random` seeded with
`(spec.seed, call_index, channel)` (as one string), where the call index is the injector's own
monotonic counter — NOT the engine step counter. A retried step therefore
advances to the next draw, which is exactly what a transient fault should
look like (fail once, succeed on retry), while the full draw sequence stays
byte-reproducible for a given seed. Tests and `benchmarks/serving_faults.py`
rely on this to compare faulty runs against fault-free ones.
"""

from __future__ import annotations

import dataclasses
import random
import time
from typing import Any


class InjectedFault(RuntimeError):
    """A transient, retryable step failure (classified retryable by
    `repro.distributed.fault_tolerance.is_retryable`)."""


class InjectedKill(BaseException):
    """A simulated hard worker crash.

    Deliberately a `BaseException` (like `KeyboardInterrupt`): retry guards
    catching `Exception` must not absorb a dead process. The supervised
    worker turns it into `os._exit`; in-process harnesses catch it
    explicitly.
    """


@dataclasses.dataclass(frozen=True)
class FaultSpec:
    """Declarative fault schedule. All-zero defaults inject nothing."""

    seed: int = 0
    spike_p: float = 0.0                 # P(latency spike) per on_step call
    spike_s: float = 0.02                # spike duration (sleep)
    error_p: float = 0.0                 # P(InjectedFault) per on_step call
    error_steps: tuple[int, ...] = ()    # explicit call indices that raise
    kill_at_step: int | None = None      # call index that raises InjectedKill

    def __post_init__(self) -> None:
        for name in ("spike_p", "error_p"):
            p = getattr(self, name)
            if not 0.0 <= p <= 1.0:
                raise ValueError(f"{name}={p} must be a probability")
        if self.spike_s < 0:
            raise ValueError(f"spike_s={self.spike_s} must be >= 0")

    def to_dict(self) -> dict[str, Any]:
        d = dataclasses.asdict(self)
        d["error_steps"] = list(self.error_steps)
        return d

    @classmethod
    def from_dict(cls, d: dict[str, Any]) -> "FaultSpec":
        known = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in d.items() if k in known}
        if "error_steps" in kw:
            kw["error_steps"] = tuple(kw["error_steps"])
        return cls(**kw)

    @property
    def active(self) -> bool:
        return bool(
            self.spike_p or self.error_p or self.error_steps
            or self.kill_at_step is not None
        )


class FaultInjector:
    """Live hook object; one per engine/worker incarnation.

    `events` records every injected fault as `(call_index, kind)` so tests
    and benchmarks can assert exactly what fired.
    """

    def __init__(self, spec: FaultSpec, *, sleep=time.sleep):
        self.spec = spec
        self.calls = 0
        self.events: list[tuple[int, str]] = []
        self._sleep = sleep

    def _draw(self, n: int, channel: str) -> float:
        # independent stream per (seed, call, channel): a spike draw never
        # perturbs the error draw sequence. A str seed is hashed with
        # SHA-512, so the stream does not depend on PYTHONHASHSEED.
        return random.Random(f"{self.spec.seed}|{n}|{channel}").random()

    def on_step(self) -> None:
        """Engine hook, called at the top of every `ServingEngine.step()`.

        May sleep (latency spike), raise `InjectedFault` (transient), or
        raise `InjectedKill` (hard crash). At most one fault fires per call;
        kill > error > spike when schedules collide.
        """
        n = self.calls
        self.calls += 1
        s = self.spec
        if s.kill_at_step is not None and n == s.kill_at_step:
            self.events.append((n, "kill"))
            raise InjectedKill(f"injected worker kill at call {n}")
        if n in s.error_steps or (s.error_p and self._draw(n, "err") < s.error_p):
            self.events.append((n, "error"))
            raise InjectedFault(f"injected step fault at call {n}")
        if s.spike_p and self._draw(n, "spike") < s.spike_p:
            self.events.append((n, "spike"))
            self._sleep(s.spike_s)

    def counts(self) -> dict[str, int]:
        out = {"kill": 0, "error": 0, "spike": 0}
        for _, kind in self.events:
            out[kind] += 1
        return out
