"""DeepSeek-V3.2-Exp in the benchmark, at a toy size on the CPU: the
family's counts against hand sums, what its ``validate`` refuses, the
configuration file against the published widths, the toy cell through the
harness with its two counters, and the float8 control failing the toy
limits. The model itself: tests/test_deepseek_v32.py."""

import copy
import json
import math
import os

import jax
import pytest

import bm_toy
from benchmark import run as harness
from benchmark import trafficgen
from benchmark.drivers import serve
from benchmark.families import deepseek_v32 as family
from benchmark.reference import deepseek_v32 as ref
from distributed_training_tpu.observability import trace

TOY = bm_toy.toy_config("toy-dsv32")
with open(os.path.join(harness.ROOT, "benchmark", "configs",
                       "deepseek-v3.2-exp-ep16.json")) as _fh:
    REAL = json.load(_fh)
# toy readings (fp32 both sides, seeds 5 and 2**31 + 27): the program
# gap_max <= 1.3e-6, gap_mean <= 1.2e-7; the float8 control gap_max
# 3.5e-3 .. 1.1e-2, gap_mean 2.6e-4 .. 7.0e-4
TOY_LIMITS = {"gap_max": 1e-4, "gap_mean": 1e-5, "wrong_length": 0}


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


# -- the family's counts -----------------------------------------------------

def test_the_counts_against_hand_sums():
    d, h = 7168, 128
    mla = (d * 1536 + 1536 * h * 192 + d * 576 + 512 * h * 256
           + h * 128 * d)
    indexer = 1536 * 64 * 128 + d * 128 + d * 64
    norms = 2 * d + 1536 + 512 + 2 * 128
    expert = 3 * d * 2048
    dense_layer = mla + indexer + norms + 3 * d * 18432
    expert_layer = (mla + indexer + norms + d * 256 + 256 + expert
                    + 16 * expert)
    assert round(mla / 1e6, 1) == 187.1 and round(indexer / 1e6, 1) == 14.0
    assert round(dense_layer / 1e6, 1) == 597.4
    assert round(expert_layer / 1e6, 1) == 951.6
    total = dense_layer + 4 * expert_layer + 2 * 16160 * d + d
    assert family.param_count(REAL) == total == REAL["parameters"]
    assert total == sum(math.prod(v)
                        for v in ref.param_shapes(REAL).values())
    outside = total - 16160 * d - 4 * 16 * expert
    assert family.matmul_params_read(REAL) == outside
    # a decode iteration of 16 slots at 8000 rows: 6.37 experts a layer
    hit = 16 * (1 - (31 / 32) ** 16)
    want = 2 * (outside + 4 * hit * expert
                + 5 * 16 * (8000 * 128 + 2048 * 576))
    assert family.decode_iteration_bytes(REAL, [8000] * 16) == \
        pytest.approx(want)
    assert 5.6e9 < 2 * (outside + 4 * hit * expert) < 5.7e9
    # no decoding slot: no expert is read
    assert family.decode_iteration_bytes(REAL, []) == 2 * outside
    matrices = 2 * (outside - (d + 5 * norms + 4 * 256)
                    + 4 * 0.5 * expert)
    token = family.forward_flops_token(REAL, 8000)
    assert token == pytest.approx(
        family._token_matmul_flops(ref.sizes(REAL))
        + 5 * (2 * 64 * 128 * 8000 + 2 * 128 * 1088 * 2048))
    assert family._token_matmul_flops(ref.sizes(REAL)) == pytest.approx(
        matrices, rel=1e-4)      # the norms' gains are read, not multiplied
    # a prompt within the top-k is dense and causal; past it, capped
    assert family.prompt_forward_flops(REAL, 1000) == pytest.approx(
        1000 * family._token_matmul_flops(ref.sizes(REAL))
        + 5 * 500500 * (2 * 64 * 128 + 2 * 128 * 320))
    assert family.prompt_forward_flops(REAL, 6144) == pytest.approx(
        6144 * family._token_matmul_flops(ref.sizes(REAL))
        + 5 * (6144 * 6145 / 2 * 2 * 64 * 128
               + (2048 * 2049 / 2 + 4096 * 2048) * 2 * 128 * 320))
    # a chunk's attention: within the top-k causal, past it 2048 keys a query
    first = family.chunk_attention_call(REAL, 0, 1024)
    assert first["flops"] == 5 * 1024 * 1025 / 2 * 2 * 128 * 320
    assert first["bytes"] == 5 * 2 * (1024 * 128 * 320 + 1024 * 576)
    third = family.chunk_attention_call(REAL, 2048, 1000)
    assert third["flops"] == 5 * 1000 * 2048 * 2 * 128 * 320
    straddling = family.chunk_attention_call(REAL, 2040, 16)
    assert straddling["flops"] == 5 * (sum(range(2041, 2049)) + 8 * 2048) \
        * 2 * 128 * 320
    # the chunks of a prompt add up to the prompt's attention
    assert sum(family.chunk_attention_call(REAL, at, 1024)["flops"]
               for at in range(0, 6144, 1024)) == pytest.approx(
        5 * (2048 * 2049 / 2 + 4096 * 2048) * 2 * 128 * 320)


@pytest.mark.parametrize("edit,message", [
    ({"n_group": 5}, "groups"), ({"n_routed_experts": 257}, "held"),
    ({"first_k_dense_replace": 9}, "first_k_dense"),
    ({"num_nextn_predict_layers": 1}, "prediction"),
    ({"vocab_size": 1 << 20}, "vocabulary")])
def test_the_family_refuses_a_file_whose_keys_do_not_fit(edit, message):
    family.validate(REAL)
    family.validate(TOY)
    with pytest.raises(ValueError, match=message):
        family.validate({**REAL, **edit})
    with pytest.raises(NotImplementedError, match="served"):
        family.train_flags(REAL)


def test_the_configuration_file_holds_the_published_widths():
    """Every number of the catalog's row under the same key, but the five
    counts ``reduced`` names; the published counts beside them."""
    bench = harness.load_benchmark()
    entry = next(c for c in bench["configs"]
                 if c["name"] == "deepseek-v3.2-exp-ep16")
    assert entry["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size", "num_nextn_predict_layers"]
    published = {
        "hidden_size": 7168, "intermediate_size": 18432,
        "moe_intermediate_size": 2048, "q_lora_rank": 1536,
        "kv_lora_rank": 512, "num_attention_heads": 128,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "index_n_heads": 64, "index_head_dim": 128, "index_topk": 2048,
        "num_experts_per_tok": 8, "n_group": 8, "topk_group": 4,
        "routed_scaling_factor": 2.5, "n_shared_experts": 1,
        "max_position_embeddings": 163840, "rope_theta": 10000,
        "rms_norm_eps": 1e-06}
    assert {k: REAL[k] for k in published} == published
    assert REAL["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}
    assert {k: REAL["published"][k] for k in entry["reduced"]} == {
        "num_hidden_layers": 61, "first_k_dense_replace": 3,
        "n_routed_experts": 256, "vocab_size": 129280,
        "num_nextn_predict_layers": 1}
    assert REAL["deployment"]["chips_sharing_a_layer"] == 16
    assert REAL["vocab_size"] * 8 == 129280


# -- the toy cell through the harness ----------------------------------------

def toy_cell():
    bench = copy.deepcopy(harness.load_benchmark())
    bench["configs"].append({"name": "toy",
                             "file": "tests/benchmark/data/toy-dsv32.json"})
    cell = {"name": "toy.longctx", "config": "toy",
            "traffic": "toy-serve-longctx", "chips": 1, "why": "toy"}
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "dsv32-serve-longctx" in m.get("workloads", []):
            m["workloads"].append(cell["name"])
    bench["workloads"].append(cell)
    return cell, bench


def test_the_float8_control_fails_the_toy_limits_and_the_program_does_not():
    """The control: the reference in the program's place, operands in
    float8, on the sample a run of the toy cell compared."""
    cell, _ = toy_cell()
    seed = 5
    spec = trafficgen.load("toy-serve-longctx", bm_toy.DATA)
    ctx = harness.make_ctx(cell, TOY, spec, seed, 0.5)
    s = serve.setup(ctx)
    serve.measure(ctx, s)
    held = serve.release(ctx, s)
    program = dict(serve.check(ctx, held))
    control = serve.control(ctx, held)
    assert held["compared_tokens"] >= 20
    assert program["gap_max"] <= TOY_LIMITS["gap_max"]
    assert program["gap_mean"] <= TOY_LIMITS["gap_mean"]
    assert control["gap_max"] > 10 * TOY_LIMITS["gap_max"]
    assert control["gap_mean"] > 10 * TOY_LIMITS["gap_mean"]


def test_the_traced_toy_run_reports_the_two_new_counters(monkeypatch):
    from benchmark import spanreaders, tracereduce

    monkeypatch.setattr(tracereduce, "load", bm_toy.fake_trace)
    monkeypatch.setattr("benchmark.peaks.peaks_for", lambda kind: {
        "flops_per_s": 197e12, "bytes_per_s": 819e9, "memory_bytes": 16e9})
    # the span readers hand out nothing off a TPU: let them read this run's
    monkeypatch.setattr(spanreaders, "_ring",
                        lambda device: trace.host_spans)
    cell, bench = toy_cell()
    r = harness.run_cell(cell, bench, 2 ** 31 + 27, 1.0, True,
                         device=bm_toy.CPU, limits=TOY_LIMITS,
                         traffic_dir=bm_toy.DATA)
    m = {k: v["value"] for k, v in r["metrics"].items()}
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert {c["name"] for c in r["checks"]} == set(TOY_LIMITS)
    # every context is past the toy's 8 keys: 8 of 17..40 rows are read
    assert 100 * 8 / 40 <= m["kv_select_share.tokens"] <= 100 * 8 / 17
    assert 100 <= m["expert_imbalance.tokens"] <= 400
    assert 0 < m["mfu.tokens"] and 0 < m["decode_roofline.tokens"]
    # a program without the counters: the readers find nothing, no raise
    for name in ("kv_select_share.tokens", "expert_imbalance.tokens"):
        read = harness.load_reader(name)
        monkeypatch.setattr(spanreaders, "working_iterations",
                            lambda ctx: [type("S", (), {"attrs": {
                                "program": "decode"}})()])
        assert read({"config": TOY}) is None


def test_the_kernels_roofline_reads_its_chunks_and_its_device_time(
        monkeypatch):
    from benchmark import spanreaders

    read = harness.load_reader("masked_attention_roofline.tokens")
    peaks = {"flops_per_s": 197e12, "bytes_per_s": 819e9}

    def chunk(t0, t1, start, tokens):
        return type("S", (), {"t0": t0, "t1": t1, "attrs": {
            "start": start, "tokens": tokens}})()

    found = [chunk(9.0, 10.5, 0, 1024),         # began before the trace
             chunk(11.0, 11.2, 1024, 1024), chunk(12.0, 12.2, 2048, 500)]
    monkeypatch.setattr(spanreaders, "spans", lambda ctx, name: found)
    ctx = {"config": REAL, "peaks": peaks, "window": {"traced": [10.0, 16.0]},
           "trace_reduced": {"custom_calls": {
               "masked_attention f32[1024,16384]": 0.05,
               "paged_attention f32[32,1280]": 1.0}}}
    flops = sum(family.chunk_attention_call(REAL, at, n)["flops"]
                for at, n in ((1024, 1024), (2048, 500)))
    assert read(ctx) == pytest.approx(100 * flops / 197e12 / 0.05)
    assert 0 < read(ctx) < 100
    # no such kernel in the trace (the parent's program), no chunk, no trace
    other = {**ctx, "trace_reduced": {"custom_calls": {
        "paged_attention f32[32,1280]": 1.0}}}
    assert read(other) is None
    assert read({**ctx, "trace_reduced": None}) is None
    monkeypatch.setattr(spanreaders, "spans", lambda ctx, name: [])
    assert read(ctx) is None
