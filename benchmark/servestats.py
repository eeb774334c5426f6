"""Arithmetic of a serving window on plain event lists.

An *iteration* is ``{"t0", "t1", "landed": {uid: n_tokens}}``: one call of
the engine's step between two host-clock readings, with the tokens it
delivered. A token's time is the closing boundary ``t1`` of the iteration
that delivered it.
"""

from __future__ import annotations

import math


def percentile(values: list[float], q: float) -> float:
    """Linear-interpolated percentile (numpy's default) of a list."""
    v = sorted(values)
    if not v:
        raise ValueError("percentile of nothing")
    k = (len(v) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return v[lo] + (v[hi] - v[lo]) * (k - lo)


def inside(iterations: list[dict], open_t: float, close_t: float) -> list:
    """Iterations that lie wholly inside ``[open_t, close_t]``."""
    return [it for it in iterations
            if it["t0"] >= open_t and it["t1"] <= close_t]


def output_token_rate(iterations: list[dict], open_t: float,
                      close_t: float) -> float:
    """Output tokens delivered by iterations wholly inside the window, over
    the time between the first and the last of their boundaries."""
    its = inside(iterations, open_t, close_t)
    if not its:
        raise ValueError("no iteration lies inside the window")
    tokens = sum(sum(it["landed"].values()) for it in its)
    return tokens / (its[-1]["t1"] - its[0]["t0"])


def token_times(iterations: list[dict], open_t: float,
                close_t: float) -> dict[int, list[float]]:
    """``{uid: [time of each token delivered inside the window]}``."""
    times: dict[int, list[float]] = {}
    for it in inside(iterations, open_t, close_t):
        for uid, n in it["landed"].items():
            times.setdefault(uid, []).extend([it["t1"]] * n)
    return times


def tpot_ms(times: dict[int, list[float]]) -> list[float]:
    """Per request with two tokens or more inside the window: (time of its
    last token - time of its first) / (tokens - 1), in ms. A request still
    running at the close enters with what it has so far."""
    return [(t[-1] - t[0]) * 1e3 / (len(t) - 1)
            for t in times.values() if len(t) >= 2]


def ttft_ms(due: dict[int, float], first_token: dict[int, float],
            open_t: float, close_t: float) -> list[float]:
    """Per request due inside the window: from when it was due to its
    first token, in ms; one with no token yet at the close enters with its
    wait so far."""
    out = []
    for uid, d in due.items():
        if not (open_t <= d <= close_t):
            continue
        t = first_token.get(uid)
        out.append(((t if t is not None and t <= close_t else close_t) - d)
                   * 1e3)
    return out
