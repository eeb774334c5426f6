"""The reader of ``expert_ffn_roofline.tokens`` on a synthetic context: the
held experts' least time over the device time of the kernel ``expert_ffn``,
bytes-bound and compute-bound, and ``None`` where the trace holds no such
kernel or the spans carry no expert counters. The kernel itself:
tests/test_expert_ffn.py."""

import json
import os

import pytest

from benchmark import run as harness
from benchmark import spanreaders

with open(os.path.join(harness.ROOT, "benchmark", "configs",
                       "ling-3.0-flash-ep8.json")) as _fh:
    REAL = json.load(_fh)
PEAKS = {"flops_per_s": 197e12, "bytes_per_s": 819e9, "memory_bytes": 16e9}


class _Span:
    def __init__(self, t0, t1, **attrs):
        self.t0, self.t1, self.attrs = t0, t1, attrs


def test_the_expert_ffn_reader_reads_the_kernel_and_the_steps(monkeypatch):
    read = harness.load_reader("expert_ffn_roofline.tokens")
    spans = [_Span(9.0, 10.5, program="decode", experts_hit=300,
                   expert_rows=700),                       # before
             _Span(11.0, 11.02, program="decode", experts_hit=312,
                   expert_rows=768),
             _Span(11.1, 11.15, program="fused", experts_hit=690,
                   expert_rows=6900),
             _Span(13.0, 13.01, program="decode")]         # no counter
    monkeypatch.setattr(spanreaders, "working_iterations",
                        lambda ctx: spans)
    red = {"custom_calls": {"expert_ffn f32[128,2560]": 0.02,
                            "kda_decode f32[128,32,128]": 1.0},
           "busy_s": 0.05, "n_devices": 1}
    ctx = {"config": REAL, "peaks": PEAKS, "trace_reduced": red,
           "window": {"traced": [10.0, 16.0]}}
    matrix = 2560 * 768
    # bytes-bound at a few rows an expert: the hit experts' weights once
    want = sum(max(hit * 3 * matrix * 2 / 819e9,
                   rows * 6 * matrix / 197e12)
               for hit, rows in ((312, 768), (690, 6900)))
    assert read(ctx) == pytest.approx(100 * want / 0.02)
    assert 0 < read(ctx) < 100
    # compute-bound where an expert takes many rows
    spans[1].attrs.update(experts_hit=1, expert_rows=100000)
    assert read(ctx) == pytest.approx(100 * (
        100000 * 6 * matrix / 197e12
        + max(690 * 6 * matrix / 819e9, 6900 * 6 * matrix / 197e12))
        / 0.02)
    # the parent's program: no such kernel in the trace
    other = {**ctx, "trace_reduced": {"custom_calls": {
        "kda_decode f32[128,32,128]": 1.0}, "busy_s": 1.0}}
    assert read(other) is None
    assert read({**ctx, "trace_reduced": None}) is None
    monkeypatch.setattr(spanreaders, "working_iterations",
                        lambda ctx: spans[-1:])
    assert read(ctx) is None
