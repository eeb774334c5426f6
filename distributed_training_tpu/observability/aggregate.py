"""Cross-host flight aggregation: step-time skew + straggler attribution.

A multihost step is a barrier: every host's step time is the SLOWEST
host's step time, so a single straggling host taxes the whole job while
its own local percentiles look identical to everyone else's (each host
measures the same barrier). Per-host telemetry therefore cannot answer
"*which* host is slow" — the first question of every MegaScale-style
straggler hunt. This module answers it:

- each host serializes a fixed-shape payload of its recent per-step wall
  deltas (step-number-aligned) plus its WallClock phase totals;
- the payloads are all-gathered at meter-flush boundaries through jax's
  distributed COORDINATION SERVICE (the KV store every multihost run
  already rendezvoused through) rather than an XLA collective: telemetry
  exchange must not occupy the accelerators, insert programs between the
  trainer's steps, or depend on the backend supporting host collectives
  (the CPU test mesh does not). Every host flushes at the same
  deterministic step and receives the SAME gathered matrix (replicated
  result, no master-only path), so the exchange cannot strand a barrier;
- the summary attributes: per-host excess over the cross-host per-step
  median, a straggler score (mean positive excess in units of the median
  step time), and the single worst (host, step) cell.

With one process the cross-host baseline degenerates to the host's own
median step time, so the same summary pins *which step* stalled — the
single-process tier-1 variant of the multihost drill.

Determinism: the attribution reads injected delays (chaos slow-step:
tens-to-hundreds of ms) against CPU-step noise (sub-ms); the argmax is
stable across runs, which is what lets tests assert the exact injected
(host, step) twice (ISSUE acceptance).
"""

from __future__ import annotations

import base64
import itertools
from typing import Any

import numpy as np

# Canonical phase order — fixed so the gathered payload has one schema
# on every host (a host that never entered 'eval' contributes 0.0).
PHASES = ("step", "data", "log", "ckpt", "eval")

DEFAULT_WINDOW = 256


def local_payload(recorder, clock=None,
                  window: int = DEFAULT_WINDOW) -> np.ndarray:
    """This host's fixed-shape contribution: the last ``window``
    (step, delta_ms) pairs (−1-padded) + the :data:`PHASES` totals.

    Fixed shape is what makes the payload all-gatherable; step numbers
    ride along so hosts align on step IDENTITY, not array position (a
    host that dropped a ring entry must not shift everyone's columns).
    """
    deltas = recorder.step_deltas_ms()[-window:]
    arr = np.full((window, 2), -1.0, dtype=np.float64)
    if deltas:
        arr[:len(deltas)] = np.asarray(deltas, dtype=np.float64)
    phases = clock.snapshot() if clock is not None else {}
    ph = np.asarray([float(phases.get(p, 0.0)) for p in PHASES],
                    dtype=np.float64)
    return np.concatenate([arr.reshape(-1), ph])


# Exchange round counter. Every process performs the gathers in the same
# deterministic order (the flush schedule), so the per-process counters
# agree and round N's keys never collide with round N+1's.
_generation = itertools.count()


def _coordination_client():
    """jax's distributed-coordination KV client (None single-process).

    Private-module import (``jax._src.distributed``): there is no
    public host-side KV surface, and the alternative — an XLA
    all-gather — puts work on the accelerators at every meter flush.
    """
    from jax._src import distributed

    return distributed.global_state.client


def gather_payloads(payload: np.ndarray, num_processes: int, *,
                    timeout_ms: int = 300_000) -> np.ndarray:
    """All-gather ``payload`` across hosts → ``[num_hosts, len(payload)]``.

    Single-process is pure numpy (no device interaction — the
    transfer-guard contract on the flush path survives). Multihost
    exchanges base64 rows through the coordination-service KV store:
    set own row, blocking-read every row (replicated result on every
    host). Must be called from EVERY process at the same point — the
    meter-flush boundary is exactly such a point. Rows from two rounds
    back are deleted (a host can only be one round ahead of the slowest
    reader, so round N-2 is provably fully read).
    """
    if num_processes <= 1:
        return payload[None, :]
    import jax

    client = _coordination_client()
    if client is None:
        raise RuntimeError(
            "cross-host flight aggregation needs the jax distributed "
            "runtime (jax.distributed.initialize / "
            "runtime.distributed.initialize_distributed) — without it "
            "there is no coordination service to exchange payloads over")
    gen = next(_generation)
    me = jax.process_index()
    row = np.ascontiguousarray(payload, dtype=np.float64)
    client.key_value_set(f"flight_agg/{gen}/{me}",
                         base64.b64encode(row.tobytes()).decode())
    rows = []
    for p in range(num_processes):
        raw = client.blocking_key_value_get(f"flight_agg/{gen}/{p}",
                                            timeout_ms)
        rows.append(np.frombuffer(base64.b64decode(raw), np.float64))
    if gen >= 2:
        client.key_value_delete(f"flight_agg/{gen - 2}/{me}")
    return np.stack(rows)


def summarize_hosts(gathered: np.ndarray,
                    window: int = DEFAULT_WINDOW) -> dict[str, Any]:
    """The gathered matrix → skew/straggler summary (JSON-ready).

    Baseline per step: the cross-host median (H > 1), or the host's own
    median step time (H == 1, where cross-host skew does not exist).
    ``straggler`` names the worst (host, step) cell by excess over that
    baseline; ``score`` is that excess in units of the median step time
    (how many extra steps' worth of wall-time the stall cost).
    """
    g = np.asarray(gathered, dtype=np.float64)
    n_hosts = g.shape[0]
    pairs = g[:, :2 * window].reshape(n_hosts, window, 2)
    phase_totals = g[:, 2 * window:]

    per_host_steps = []
    for h in range(n_hosts):
        valid = pairs[h][pairs[h][:, 0] >= 0]
        per_host_steps.append({int(s): float(dt) for s, dt in valid})
    common = sorted(set.intersection(*[set(d) for d in per_host_steps])
                    if per_host_steps else set())
    out: dict[str, Any] = {
        "num_hosts": int(n_hosts),
        "common_steps": len(common),
        "per_host": [
            {"process_index": h,
             "phase_seconds": {p: float(phase_totals[h, i])
                               for i, p in enumerate(PHASES)}}
            for h in range(n_hosts)
        ],
    }
    if not common:
        return out
    # D[h, s]: host h's wall delta for common step s.
    d = np.asarray([[per_host_steps[h][s] for s in common]
                    for h in range(n_hosts)])
    if n_hosts > 1:
        baseline = np.median(d, axis=0)[None, :]
        out["baseline"] = "cross-host median"
    else:
        baseline = np.full((1, len(common)), np.median(d))
        out["baseline"] = "within-host median"
    excess = d - baseline
    median_ms = float(np.median(d))
    out["window"] = [int(common[0]), int(common[-1])]
    out["median_step_ms"] = median_ms
    for h in range(n_hosts):
        pos = excess[h][excess[h] > 0]
        worst = int(np.argmax(excess[h]))
        out["per_host"][h].update({
            "step_time_mean_ms": float(d[h].mean()),
            "step_time_max_ms": float(d[h].max()),
            "mean_excess_ms": float(excess[h].mean()),
            "max_excess_ms": float(excess[h].max()),
            "max_excess_step": int(common[worst]),
            "straggler_score": (float(pos.mean() / median_ms)
                                if pos.size and median_ms > 0 else 0.0),
        })
    flat = int(np.argmax(excess))  # row-major: lowest host, then step
    h_star, s_star = divmod(flat, len(common))
    out["straggler"] = {
        "host": int(h_star),
        "step": int(common[s_star]),
        "excess_ms": float(excess[h_star, s_star]),
        "score": (float(excess[h_star, s_star] / median_ms)
                  if median_ms > 0 else 0.0),
    }
    return out


def aggregate(recorder, clock=None, *, num_processes: int = 1,
              window: int = DEFAULT_WINDOW) -> dict[str, Any]:
    """One-call form: payload → gather → summary. Collective when
    ``num_processes > 1`` — call from every process at the same point."""
    payload = local_payload(recorder, clock, window)
    return summarize_hosts(gather_payloads(payload, num_processes), window)
