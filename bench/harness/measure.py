"""End-to-end numbers of a window, from the client's timestamps."""

from __future__ import annotations

from typing import Any

import numpy as np

from harness.loadgen import Sent


def percentile(xs: list[float], q: float) -> float | None:
    return float(np.percentile(np.asarray(xs, np.float64), q)) if xs else None


def ttfts(sent: list[Sent], t0: float, t_end: float,
          t_drained: float | None = None) -> list[float]:
    """Time to first token of every request due (sent) in [t0, t_end],
    answered in the window or in the drain after it. One that got no token
    by `t_drained` counts from its due time to then."""
    out = []
    for s in sent:
        if not t0 <= s.due <= t_end:
            continue
        if s.events:
            out.append(s.events[0][0] - s.due)
        elif t_drained is not None:
            out.append(t_drained - s.due)
    return out


def gaps(sent: list[Sent], t0: float, t_end: float) -> list[float]:
    """Every inter-token gap that ends in the window. Tokens that reach the
    client in one event after the first of it arrive with a gap of 0."""
    out = []
    for s in sent:
        prev = None
        for t, n in s.events:
            if prev is not None and t0 <= t <= t_end:
                out.append(t - prev)
            if t0 <= t <= t_end:
                out.extend([0.0] * (n - 1))
            prev = t
    return out


def tokens_in(sent: list[Sent], t0: float, t_end: float) -> int:
    return sum(n for s in sent for t, n in s.events if t0 <= t <= t_end)


def end_to_end(sent: list[Sent], t0: float, t_end: float,
               t_drained: float) -> dict[str, Any]:
    tt, gg = ttfts(sent, t0, t_end, t_drained), gaps(sent, t0, t_end)
    return {
        "tokens_per_s": tokens_in(sent, t0, t_end) / (t_end - t0),
        "ttft_p90_ms": None if not tt else 1e3 * percentile(tt, 90),
        "gap_p95_ms": None if not gg else 1e3 * percentile(gg, 95),
        "n_ttft": len(tt), "n_gaps": len(gg),
        "ttft_p50_ms": None if not tt else 1e3 * percentile(tt, 50),
        "gap_p50_ms": None if not gg else 1e3 * percentile(gg, 50),
    }


def failed(sent: list[Sent]) -> int:
    """Requests the system refused or lost: shed, timed out or in error, or
    never answered by the end of the drain. Those the benchmark stops
    after their first token are not counted."""
    return sum(1 for s in sent
               if s.status not in ("ok", "cancelled", None) or not s.events)
