"""sarvam-105b in the benchmark, at a toy size on the CPU: the family's
counts against hand sums and against the model's own parameter tree, what
its ``validate`` refuses, the configuration file against the catalog's row,
the toy cell through the harness with its counters and the two new readers,
and the float8 control failing the toy limits. The model itself:
tests/test_sarvam_mla.py."""

import copy
import json
import math
import os

import jax
import jax.numpy as jnp
import pytest

import bm_toy
from benchmark import run as harness
from benchmark import trafficgen, weights
from benchmark.drivers import serve
from benchmark.families import sarvam_mla as family
from benchmark.reference import sarvam_mla as ref
from distributed_training_tpu.observability import trace

TOY = bm_toy.toy_config("toy-sarvam")
with open(os.path.join(harness.ROOT, "benchmark", "configs",
                       "sarvam-105b-ep4.json")) as _fh:
    REAL = json.load(_fh)
CELL = "sarvam105b-serve-reasoning"
# toy readings (fp32 both sides, seeds 5, 77 and 2**31 + 32; the sample
# follows where the window closes): the program 0 exactly (every served
# token is the reference's first); the float8 control gap_max 3.2e-3 ..
# 3.5e-2, gap_mean 7.0e-5 .. 1.5e-3
TOY_LIMITS = {"gap_max": 1e-4, "gap_mean": 5e-6, "wrong_length": 0}
PEAKS = {"flops_per_s": 197e12, "bytes_per_s": 819e9, "memory_bytes": 16e9}


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


# -- the family's counts -----------------------------------------------------

def test_the_counts_against_hand_sums():
    d, h = 4096, 64
    mla = d * h * 192 + d * 576 + 512 * h * 256 + h * 128 * d
    norms = 2 * d + 512
    expert = 3 * d * 2048
    dense_layer = mla + norms + 3 * d * 16384
    outside = mla + norms + d * 128 + 128 + expert
    expert_layer = outside + 32 * expert
    # ISSUE 32's arithmetic
    assert round(mla / 1e6, 2) == 94.63
    assert round(dense_layer / 1e6, 2) == 295.97
    assert round(outside / 1e6, 2) == 120.33
    assert round(expert_layer / 1e6, 2) == 925.64
    total = dense_layer + 4 * expert_layer + 2 * 65536 * d + d
    assert family.param_count(REAL) == total == REAL["parameters"]
    assert round(total / 1e6, 1) == 4535.4
    assert total == sum(math.prod(v)
                        for v in ref.param_shapes(REAL).values())
    read = total - 65536 * d - 4 * 32 * expert
    assert family.matmul_params_read(REAL) == read
    # a decode iteration of 64 slots at 2900 rows: 31.5 of 32 experts a layer
    hit = 32 * (1 - (15 / 16) ** 64)
    assert round(hit, 1) == 31.5
    want = 2 * (read + 4 * hit * expert + 5 * 64 * 2900 * 576)
    assert family.decode_iteration_bytes(REAL, [2900] * 64) == \
        pytest.approx(want)
    # 2.09 GB outside the routed experts + 6.34 of experts + 1.07 of cache
    # rows: 11.6 ms at 819 GB/s (ISSUE 32's "at least 11.8 ms")
    assert 9.4e9 < want < 9.6e9
    assert family.decode_iteration_bytes(REAL, []) == 2 * read
    s = ref.sizes(REAL)
    matrices = 2 * (read - (d + 5 * norms + 4 * 128) + 4 * 2 * expert)
    assert family._token_matmul_flops(s) == pytest.approx(matrices,
                                                          rel=1e-4)
    assert family.forward_flops_token(REAL, 2900) == pytest.approx(
        family._token_matmul_flops(s) + 5 * 2 * 64 * 1088 * 2900)
    assert family.prompt_forward_flops(REAL, 2048) == pytest.approx(
        2048 * family._token_matmul_flops(s)
        + 5 * 2048 * 2049 / 2 * 2 * 64 * 320)
    # a chunk's attention: every earlier key, causal inside the chunk
    first = family.chunk_attention_call(REAL, 0, 1024)
    assert first["flops"] == 5 * 1024 * 1025 / 2 * 2 * 64 * 320
    assert first["bytes"] == 5 * 2 * (1024 * 64 * 320 + 1024 * 576)
    third = family.chunk_attention_call(REAL, 2048, 1000)
    assert third["flops"] == 5 * (1000 * 2048 + 1000 * 1001 / 2) \
        * 2 * 64 * 320
    assert sum(family.chunk_attention_call(REAL, at, 1024)["flops"]
               for at in range(0, 4096, 1024)) == pytest.approx(
        5 * 4096 * 4097 / 2 * 2 * 64 * 320)
    # a layer's decode attention: 1152 B and 139 264 FLOPs a live row
    call = family.decode_attention_call(REAL, [100, 2900])
    assert call == {"bytes": 3000 * 1152, "flops": 3000 * 139264.0}


def test_the_counts_against_the_models_own_tree():
    """``param_count`` and ``param_shapes`` against what the program's
    model really creates, at the toy's sizes."""
    model = family.build_model(TOY, {"dtype": "fp32", "logits_dtype": "fp32"})
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    flat = weights.flatten(shapes["params"])
    assert {k: tuple(v.shape) for k, v in flat.items()} == {
        k: tuple(v) for k, v in ref.param_shapes(TOY).items()}
    assert family.param_count(TOY) == sum(
        math.prod(v.shape) for v in flat.values())


@pytest.mark.parametrize("edit,message", [
    ({"num_experts": 129}, "held"), ({"n_routed_experts": 16}, "held"),
    ({"first_k_dense_replace": 9}, "first_k_dense"),
    ({"q_head_dim": 128}, "q_head_dim"), ({"head_dim": 512}, "head_dim"),
    ({"q_lora_rank": 1536}, "query latent"),
    ({"vocab_size": 1 << 20}, "vocabulary")])
def test_the_family_refuses_a_file_whose_keys_do_not_fit(edit, message):
    family.validate(REAL)
    family.validate(TOY)
    with pytest.raises(ValueError, match=message):
        family.validate({**REAL, **edit})
    with pytest.raises(NotImplementedError, match="served"):
        family.train_flags(REAL)


def test_the_configuration_file_holds_the_catalogs_row():
    """Every key of the catalog's row for sarvam-105b under the same key
    and value, but the three counts ``reduced`` names; the published counts
    beside them."""
    bench = harness.load_benchmark()
    entry = next(c for c in bench["configs"] if c["name"] == "sarvam-105b-ep4")
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size"]
    catalog = {
        "attn_implementation": None, "default_theta": 10000,
        "first_k_dense_replace": 1, "head_dim": 576, "hidden_act": "silu",
        "hidden_size": 4096, "intermediate_size": 16384, "kv_lora_rank": 512,
        "max_position_embeddings": 131072, "model_type": "sarvam_mla",
        "moe_intermediate_size": 2048, "moe_router_enable_expert_bias": True,
        "num_attention_heads": 64, "num_experts": 128,
        "num_experts_per_tok": 8, "num_hidden_layers": 32,
        "num_shared_experts": 1, "q_head_dim": 192, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
        "rope_scaling": {
            "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
            "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
            "type": "deepseek_yarn"},
        "rope_theta": 10000, "routed_scaling_factor": 2.5,
        "tie_word_embeddings": False, "use_qk_norm": True, "v_head_dim": 128,
        "vocab_size": 262144}
    kept = {k: v for k, v in catalog.items() if k not in entry["reduced"]}
    assert {k: REAL[k] for k in kept} == kept
    assert {k: REAL["published"][k] for k in entry["reduced"]} == {
        k: catalog[k] for k in entry["reduced"]}
    assert (REAL["num_hidden_layers"], REAL["num_experts"],
            REAL["vocab_size"]) == (5, 32, 65536)
    assert REAL["deployment"]["chips_sharing_a_layer"] == 4
    assert REAL["num_experts"] * 4 == 128 and REAL["vocab_size"] * 4 == 262144
    assert "q_lora_rank" not in REAL


def test_the_cells_traffic_fills_the_pool_it_names():
    spec = trafficgen.load("serve-reasoning")
    e = spec["engine"]
    assert e["max_batch"] == 64 and e["kv_pages"] * e["kv_page_size"] \
        == 64 * e["max_len"] == 393216
    assert spec["prompt_tokens"]["max"] + spec["output_tokens"]["max"] \
        <= e["max_len"]
    assert spec["output_tokens"]["max"] == e["max_new_tokens"]
    # a first wave staggered over the pre-roll seats every slot once
    assert spec["preroll"]["iterations"] >= 64 * (
        spec["prompt_tokens"]["max"] // e["prefill_chunk"]) // 2


# -- the toy cell through the harness ----------------------------------------

def toy_cell():
    bench = copy.deepcopy(harness.load_benchmark())
    bench["configs"].append({"name": "toy",
                             "file": "tests/benchmark/data/toy-sarvam.json"})
    cell = {"name": "toy.reasoning", "config": "toy",
            "traffic": "toy-serve-reasoning", "chips": 1, "why": "toy"}
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append(cell["name"])
    bench["workloads"].append(cell)
    return cell, bench


def test_the_float8_control_fails_the_toy_limits_and_the_program_does_not():
    """The control: the reference in the program's place, operands in
    float8, on the sample a run of the toy cell compared."""
    cell, _ = toy_cell()
    spec = trafficgen.load("toy-serve-reasoning", bm_toy.DATA)
    ctx = harness.make_ctx(cell, TOY, spec, 5, 0.5)
    s = serve.setup(ctx)
    serve.measure(ctx, s)
    held = serve.release(ctx, s)
    program = dict(serve.check(ctx, held))
    control = serve.control(ctx, held)
    assert held["compared_tokens"] >= 20
    assert program["gap_max"] <= TOY_LIMITS["gap_max"]
    assert program["gap_mean"] <= TOY_LIMITS["gap_mean"]
    assert control["gap_max"] > 10 * TOY_LIMITS["gap_max"]
    assert control["gap_mean"] > 5 * TOY_LIMITS["gap_mean"]


def test_the_traced_toy_run_reports_the_cells_metrics(monkeypatch):
    from benchmark import spanreaders, tracereduce

    monkeypatch.setattr(tracereduce, "load", bm_toy.fake_trace)
    monkeypatch.setattr("benchmark.peaks.peaks_for", lambda kind: PEAKS)
    # the span readers hand out nothing off a TPU: let them read this run's
    monkeypatch.setattr(spanreaders, "_ring",
                        lambda device: trace.host_spans)
    cell, bench = toy_cell()
    r = harness.run_cell(cell, bench, 2 ** 31 + 32, 1.0, True,
                         device=bm_toy.CPU, limits=TOY_LIMITS,
                         traffic_dir=bm_toy.DATA)
    m = {k: v["value"] for k, v in r["metrics"].items()}
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert {c["name"] for c in r["checks"]} == set(TOY_LIMITS)
    assert m["kv_select_share.tokens"] == 100.0      # dense: every live row
    assert 100 <= m["expert_imbalance.tokens"] <= 400
    # 4 held experts x 2 expert layers: a chunk's 16 tokens hit nearly all,
    # 1-4 decoding tokens about half (a fused step counts as two passes)
    assert 25 <= m["expert_hit_share.tokens"] <= 100
    assert 0 < m["mfu.tokens"] and 0 < m["decode_roofline.tokens"]
    # no kernel of that name in the (fake) trace, no chunk kernel either
    assert "latent_decode_roofline.tokens" not in m
    assert "masked_attention_roofline.tokens" not in m
    # a program without the counter: the reader finds nothing, no raise
    read = harness.load_reader("expert_hit_share.tokens")
    monkeypatch.setattr(spanreaders, "working_iterations",
                        lambda ctx: [type("S", (), {"attrs": {
                            "program": "decode"}})()])
    assert read({"config": TOY}) is None


def test_the_decode_kernels_roofline_reads_its_steps_and_its_device_time():
    read = harness.load_reader("latent_decode_roofline.tokens")

    def it(t0, t1, contexts):
        return {"t0": t0, "t1": t1, "contexts": contexts, "landed": {},
                "live": len(contexts)}

    its = [it(9.0, 10.5, [3000] * 64),           # began before the trace
           it(11.0, 11.2, [2900] * 64), it(12.0, 12.2, [100, 4000]),
           it(13.0, 13.1, [])]                   # a chunk alone
    ctx = {"config": REAL, "peaks": PEAKS,
           "window": {"traced": [10.0, 16.0], "iterations": its},
           "trace_reduced": {"custom_calls": {
               "paged_latent_attention bf16[64,64,512]": 0.004,
               "masked_attention f32[1024,8192]": 1.0}}}
    rows = 64 * 2900 + 4100
    # bytes bound: 1152 B a row against 139 264 FLOPs
    assert 1152 / 819e9 > 139264 / 197e12
    assert read(ctx) == pytest.approx(100 * 5 * rows * 1152 / 819e9 / 0.004)
    assert 0 < read(ctx) < 100
    # no such kernel in the trace (the parent's program), no trace, a family
    # without the count
    other = {**ctx, "trace_reduced": {"custom_calls": {
        "paged_attention f32[32,1280]": 1.0}}}
    assert read(other) is None
    assert read({**ctx, "trace_reduced": None}) is None
    assert read({**ctx, "config": bm_toy.toy_config("toy-dsv32")}) is None
