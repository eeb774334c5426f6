"""Async, master-gated training logs.

The reference fetches ``loss.item()`` every step — a device→host sync that
serializes the pipeline (SURVEY.md §2.5) — and gates tqdm on the master rank
(``resnet/colossal/colossal_train.py:88``). Here metrics stay on device as
jax.Arrays; the meter keeps references and only calls ``.item()`` (blocking)
at ``log_interval`` boundaries, so the steady-state step never waits on the
host. tqdm is used when available, plain prints otherwise.

The no-hidden-transfer claim is a PINNED contract, not prose:
``tests/test_transfer_guard.py`` runs steady-state train steps (image and
LM) with the whole between-flush window wrapped in
``jax.transfer_guard("disallow")`` — any implicit transfer the backend
can observe fails the suite (on the CPU test mesh that is every hidden
host→device upload, e.g. an unplaced numpy batch; on a real accelerator
the same wrapper also rejects implicit device→host fetches like a stray
``float(metric)``). The meter's flush itself uses the explicit
``jax.device_get``, which the guard permits by design: explicit fetches at
log intervals ARE the contract. The observability hooks
(``observability/hooks.py``) keep the same rule — per-step cost is one
host ``perf_counter()`` ring write; MFU, memory telemetry, and anomaly
detection all read at flush boundaries from values the meter already
fetched.
"""

from __future__ import annotations

import time
from typing import Any

import jax

from distributed_training_tpu.observability import trace as trace_lib

try:
    from tqdm import tqdm
except ImportError:  # pragma: no cover
    tqdm = None


class MetricMeter:
    """Holds device metric refs; materializes lazily at log intervals."""

    def __init__(self, log_interval: int = 100):
        self.log_interval = max(1, log_interval)
        self._pending: list[tuple[int, dict[str, Any]]] = []
        self.last: dict[str, float] = {}

    @property
    def pending(self) -> bool:
        """True when unfetched device metrics are queued (a flush now would
        materialize new values rather than repeat ``last``)."""
        return bool(self._pending)

    def push(self, step: int, metrics: dict[str, Any]) -> bool:
        """Record device metrics; returns True when a fetch happened."""
        self._pending.append((step, metrics))
        if len(self._pending) >= self.log_interval:
            self.flush()
            return True
        return False

    def flush(self) -> dict[str, float]:
        if not self._pending:
            return self.last
        # Only the newest entry is materialized; older refs are dropped
        # unfetched (their buffers were never copied to host).
        step, metrics = self._pending[-1]
        self._pending.clear()
        # The one place a training loop waits for the device by design.
        with trace_lib.span("train.metrics_fetch", key=step):
            self.last = {k: float(jax.device_get(v))
                         for k, v in metrics.items()}
        self.last["step"] = step
        return self.last


class EpochBar:
    """Master-only progress bar: tqdm parity with interval postfix updates."""

    def __init__(self, total: int, epoch: int, num_epochs: int, is_master: bool):
        self.is_master = is_master
        desc = f"Epoch [{epoch + 1}/{num_epochs}]"
        if is_master and tqdm is not None:
            self.bar = tqdm(total=total, desc=desc)
        else:
            self.bar = None
            self.desc = desc
            self.total = total
            self.count = 0
            self.t0 = time.time()

    def update(self, n: int = 1) -> None:
        if self.bar is not None:
            self.bar.update(n)
        else:
            self.count += n

    def set_postfix(self, metrics: dict[str, float]) -> None:
        if self.bar is not None:
            self.bar.set_postfix(
                {k: f"{v:.4g}" for k, v in metrics.items() if k != "step"})
        elif self.is_master:
            body = " ".join(f"{k}={v:.4g}" for k, v in metrics.items())
            rate = self.count / max(time.time() - self.t0, 1e-9)
            print(f"{self.desc} {self.count}/{self.total} {body} ({rate:.1f} it/s)",
                  flush=True)

    def close(self) -> None:
        if self.bar is not None:
            self.bar.close()
