"""Ling-3.0-flash in the benchmark, at a toy size on the CPU: the family's
counts against hand sums and against the model's own parameter tree,
what its ``validate`` refuses, the configuration file against the catalog's
row, the EP8 share against the uncut expert layer, the toy cell through
the harness with its counters and the two new readers, and the float8
control failing the toy limits. The model itself: tests/test_ling_hybrid.py."""

import copy
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bm_toy
from benchmark import run as harness
from benchmark import spanreaders, trafficgen, weights
from benchmark.drivers import serve
from benchmark.families import ling_hybrid as family
from benchmark.reference import ling_hybrid as ref
from distributed_training_tpu.observability import trace

TOY = bm_toy.toy_config("toy-ling")
with open(os.path.join(harness.ROOT, "benchmark", "configs",
                       "ling-3.0-flash-ep8.json")) as _fh:
    REAL = json.load(_fh)
CELL = "ling3flash-serve-longgen"
# toy readings (fp32 both sides, seeds 5, 77 and 2**31 + 32; the sample
# follows where the window closes): the program 0 exactly (every served
# token is the reference's first); the float8 control gap_max 1.3e-2 ..
# 7.9e-2, gap_mean 9.0e-4 .. 5.1e-3
TOY_LIMITS = {"gap_max": 1e-4, "gap_mean": 5e-6, "wrong_length": 0}
PEAKS = {"flops_per_s": 197e12, "bytes_per_s": 819e9, "memory_bytes": 16e9}
# the catalog's row for Ling-3.0-flash: its config, the two lists apart
CATALOG = {
    "first_k_dense_replace": 2,
    "gated_attention_proj_granularity_type": "head_wise",
    "group_norm_size": 1, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2560, "intermediate_size": 6144, "kda_lower_bound": -5,
    "kda_safe_gate": True, "kv_lora_rank": 512, "layer_group_size": 6,
    "linear_silu": True, "max_position_embeddings": 262144,
    "max_window_layers": 20, "model_type": "bailing_hybrid",
    "moe_intermediate_size": 768, "moe_router_enable_expert_bias": True,
    "moe_shared_expert_intermediate_size": 768, "mtp_loss_scaling_factor": 0,
    "mtp_use_kda": False, "n_group": 8, "no_kda_lora": True,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 512,
    "num_experts_per_tok": 8, "num_hidden_layers": 42,
    "num_key_value_heads": 32, "num_kv_heads_for_linear_attn": 0,
    "num_nextn_predict_layers": 1, "num_shared_experts": 1,
    "partial_rotary_factor": 0.5, "q_lora_rank": None, "qk_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True, "rope_scaling": None, "rope_theta": 6000000,
    "rotary_dim": 64, "routed_scaling_factor": 2.5,
    "scale_router_input": False, "score_function": "sigmoid",
    "scoring_func": "sigmoid", "seq_aux": True, "short_conv_kernel_size": 4,
    "tie_word_embeddings": False, "topk_group": 4, "topk_method": "noaux_tc",
    "up_proj_norm": False, "use_bias": False, "use_kda_lora": False,
    "use_mla_nope": False, "use_nGPT": False, "use_qk_norm": True,
    "use_qkv_bias": False, "v_head_dim": 128, "value_norm": False,
    "vocab_size": 157184,
    "expert_swiglu_limit_list": [0] * 35 + [4] * 7,
    "share_expert_swiglu_limit_list": [0] * 34 + [5] * 6 + [7] * 2,
}


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


# -- the family's counts -----------------------------------------------------

def test_the_counts_against_hand_sums():
    d, h, hd = 2560, 32, 128
    width = h * hd
    kda = 4 * d * width + width * d + 3 * 4 * width + width + h + 2 * d * h \
        + hd
    mla = d * h * 192 + d * 576 + 512 + 512 * h * 256 + h * 128 * d + d * h
    router, expert, dense = d * 512, 3 * d * 768, 3 * d * 6144
    assert [round(x / 1e6, 2) for x in (kda, mla, router, expert, dense)] \
        == [52.65, 31.97, 1.31, 5.90, 47.19]
    first = 2 * d + kda + dense
    kda_moe = 2 * d + kda + router + 512 + 65 * expert
    mla_moe = 2 * d + mla + router + 512 + 65 * expert
    assert [round(x / 1e6, 2) for x in (first, kda_moe, mla_moe)] == [
        99.84, 437.35, 416.67]
    total = first + 5 * kda_moe + mla_moe + 2 * 19648 * d + d
    assert round(2 * 19648 * d / 1e6, 2) == 100.60
    assert family.param_count(REAL) == total == REAL["parameters"]
    assert round(total / 1e6, 1) == 2803.8
    assert total == sum(math.prod(v)
                        for v in ref.param_shapes(REAL).values())
    read = total - 19648 * d - 6 * 64 * expert
    assert family.matmul_params_read(REAL) == read
    # a decode step of 128 slots at 3000 live rows: 55.5 of 64 experts hit
    hit = 64 * (1 - (63 / 64) ** 128)
    assert round(hit, 1) == 55.5
    slab = 6 * (h * hd * hd * 4 + 3 * 3 * width * 2)
    assert family.slab_bytes(REAL) == slab
    want = 2 * (read + 6 * hit * expert + 128 * 3000 * 576) \
        + 2 * 128 * slab
    assert family.decode_iteration_bytes(REAL, [3000] * 128) == \
        pytest.approx(want)
    # 0.98 GB outside the experts, 3.93 of experts, 3.33 of slabs, 0.44 of
    # latent rows: 10.6 ms at 819 GB/s
    assert 8.6e9 < want < 8.8e9
    assert family.decode_iteration_bytes(REAL, []) == 2 * read
    # the kernel over one step's KDA layers: the state read and written
    call = family.kda_decode_call(REAL, 128)
    assert call["bytes"] == 6 * 128 * (2 * h * hd * hd * 4
                                       + (4 * h * hd + h + h * hd) * 4)
    assert call["flops"] == 6 * 128 * 7 * h * hd * hd
    assert call["bytes"] / 819e9 > call["flops"] / 197e12      # bytes-bound
    s = ref.sizes(REAL)
    kda_tok = 7 * h * hd * hd + 2 * 4 * 3 * width
    assert family.forward_flops_token(REAL, 2000) == pytest.approx(
        family._token_matmul_flops(s) + 6 * kda_tok
        + 2 * h * (2 * 512 + 64) * 2000)
    assert family.prompt_forward_flops(REAL, 1024) == pytest.approx(
        1024 * (family._token_matmul_flops(s) + 6 * kda_tok)
        + 1024 * 1025 / 2 * 2 * h * 320)
    # a chunk's attention: the one latent layer, causal inside the chunk
    first_chunk = family.chunk_attention_call(REAL, 0, 1024)
    assert first_chunk["flops"] == 1024 * 1025 / 2 * 2 * h * 320
    assert first_chunk["bytes"] == 2 * (1024 * h * 320 + 1024 * 576)


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
    assert ref.layer_kinds(TOY) == ["kda", "mla", "kda"]


@pytest.mark.parametrize("edit,message", [
    ({"num_experts": 513}, "held"), ({"n_routed_experts": 16}, "held"),
    ({"n_group": 7}, "groups"), ({"num_experts_per_tok": 300}, "kept"),
    ({"held_layers": [0, 2]}, "held_layers"),
    ({"layer_kinds": ["kda"] * 7}, "layer_kinds"),
    ({"first_k_dense_replace": 9}, "first_k_dense"),
    ({"qk_head_dim": 128}, "qk_head_dim"),
    ({"moe_shared_expert_intermediate_size": 1024}, "shared"),
    ({"q_lora_rank": 1536}, "query latent"),
    ({"rope_scaling": {"type": "yarn"}}, "plain rotary"),
    ({"vocab_size": 1 << 20}, "vocabulary")])
def test_the_family_refuses_a_file_whose_keys_do_not_fit(edit, message):
    family.validate(REAL)
    family.validate(TOY)
    with pytest.raises(ValueError, match=message):
        family.validate({**REAL, **edit})
    with pytest.raises(NotImplementedError, match="served"):
        family.train_flags(REAL)


def test_the_configuration_file_holds_the_catalogs_row():
    """Every key of the catalog's row for Ling-3.0-flash under the same key
    and value, but the five counts ``reduced`` names; the published counts
    beside them; the held layers one whole period."""
    bench = harness.load_benchmark()
    entry = next(c for c in bench["configs"]
                 if c["name"] == "ling-3.0-flash-ep8")
    assert entry["reduced"] == ["num_hidden_layers", "first_k_dense_replace",
                                "num_experts", "vocab_size",
                                "num_nextn_predict_layers"]
    kept = {k: v for k, v in CATALOG.items() if k not in entry["reduced"]}
    assert {k: REAL[k] for k in kept} == kept
    assert {k: REAL["published"][k] for k in entry["reduced"]} == {
        k: CATALOG[k] for k in entry["reduced"]}
    assert [REAL[k] for k in entry["reduced"]] == [7, 1, 64, 19648, 0]
    assert REAL["deployment"]["chips_sharing_a_layer"] == 8
    assert REAL["num_experts"] * 8 == 512 and REAL["vocab_size"] == 157184 // 8
    assert REAL["held_layers"] == [0, 2, 3, 4, 5, 6, 7]
    assert ref.layer_kinds(REAL) == REAL["layer_kinds"] == [
        "kda", "kda", "kda", "kda", "mla", "kda", "kda"]
    # the held layers have no SwiGLU limit
    for key in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        assert not any(REAL[key][i] for i in REAL["held_layers"])


def test_the_cells_traffic_fills_the_pool_it_names():
    spec = trafficgen.load("serve-longgen")
    e = spec["engine"]
    assert e["max_batch"] == 128 and e["kv_pages"] * e["kv_page_size"] \
        == 128 * e["max_len"] == 786432
    assert spec["prompt_tokens"]["max"] + spec["output_tokens"]["max"] \
        <= e["max_len"]
    assert spec["output_tokens"]["max"] == e["max_new_tokens"]
    assert not e["prefix_cache"] and e["spec_k"] == 0
    # a first wave staggered over the pre-roll seats every slot once
    assert spec["preroll"]["iterations"] >= 128 * (
        spec["prompt_tokens"]["max"] // e["prefill_chunk"]) // 2


def test_the_ep8_shares_add_up_to_the_uncut_layer():
    """Eight shares of 4 experts of a 32-expert router (8 groups, 4 kept),
    each computing its held experts' part, plus the shared expert counted
    once, give the uncut layer's result."""
    cfg = {**TOY, "num_experts": 32, "n_routed_experts": 32,
           "n_group": 8, "topk_group": 4,
           "published": {**TOY["published"], "num_experts": 32},
           "assumed": {"first_held_expert": 0}}
    d, e, f = 64, 32, 32
    ks = jax.random.split(jax.random.key(9), 9)
    p = {"router": 0.3 * jax.random.normal(ks[0], (d, e)),
         "router_bias": 0.1 * jax.random.normal(ks[1], (e,)),
         "w1": 0.1 * jax.random.normal(ks[2], (e, d, f)),
         "w3": 0.1 * jax.random.normal(ks[3], (e, d, f)),
         "w2": 0.1 * jax.random.normal(ks[4], (e, f, d)),
         "shared/w1": 0.1 * jax.random.normal(ks[5], (d, f)),
         "shared/w3": 0.1 * jax.random.normal(ks[6], (d, f)),
         "shared/w2": 0.1 * jax.random.normal(ks[7], (f, d))}
    x = jax.random.normal(ks[8], (24, d))
    whole = ref.expert_layer(x, p, cfg)
    parts = []
    for rank in range(8):
        held = slice(4 * rank, 4 * rank + 4)
        share = {**cfg, "num_experts": 4, "n_routed_experts": 4,
                 "assumed": {"first_held_expert": 4 * rank}}
        p_share = {**p, "w1": p["w1"][held], "w3": p["w3"][held],
                   "w2": p["w2"][held]}
        parts.append(ref.expert_layer(x, p_share, share, shared=False))
    shared = ref._ffn(x, p["shared/w1"], p["shared/w3"], p["shared/w2"],
                      None)
    np.testing.assert_allclose(sum(parts) + shared, whole, rtol=1e-5,
                               atol=1e-6)
    assert all(float(jnp.abs(q).max()) > 0 for q in parts)


# -- the toy cell through the harness ----------------------------------------

def toy_cell():
    bench = copy.deepcopy(harness.load_benchmark())
    bench["configs"].append({"name": "toy",
                             "file": "tests/benchmark/data/toy-ling.json"})
    cell = {"name": "toy.longgen", "config": "toy",
            "traffic": "toy-serve-longgen", "chips": 1, "why": "toy"}
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append(cell["name"])
    bench["workloads"].append(cell)
    return cell, bench


def test_the_float8_control_fails_the_toy_limits_and_the_program_does_not():
    """The control: the reference in the program's place, operands in
    float8, on the sample a run of the toy cell compared."""
    cell, _ = toy_cell()
    spec = trafficgen.load("toy-serve-longgen", bm_toy.DATA)
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


def test_a_bfloat16_state_moves_the_reference_far_less_than_float8():
    """The control of a state kept below its stated float32: the KDA state
    stored in bfloat16 after each token, everything else float32. It moves
    the logits (so it is wired in), by under a hundredth of what float8
    operands move them: under the seeded weights exp(g) is about 0.03-0.26,
    so the state forgets a rounding within a few tokens."""
    params = weights.make(5, ref.param_shapes(TOY), jnp.float32)
    toks = jax.random.randint(jax.random.key(1), (1, 96), 0,
                              int(TOY["vocab_size"]))
    want = ref.forward(params, toks, TOY)
    state = jnp.abs(ref.forward(params, toks, TOY,
                                ref.state_in(jnp.bfloat16)) - want).max()
    f8 = jnp.abs(ref.forward(params, toks, TOY,
                             ref.round_to(jnp.float8_e4m3fn)) - want).max()
    assert 0 < float(state) < float(f8) / 100


def test_the_traced_toy_run_reports_the_cells_metrics(monkeypatch):
    from benchmark import tracereduce

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
    assert m["kv_select_share.tokens"] == 100.0      # dense: every live row
    assert 100 <= m["expert_imbalance.tokens"] <= 400
    assert 25 <= m["expert_hit_share.tokens"] <= 100
    assert 0 < m["mfu.tokens"] and 0 < m["decode_roofline.tokens"]
    # no kernel of these names in the (fake) trace: the readers find
    # nothing and the line leaves them out
    for name in ("kda_decode_roofline.tokens", "kda_busy_share.tokens",
                 "masked_attention_roofline.tokens",
                 "latent_decode_roofline.tokens"):
        assert name not in m
    its = [s for s in trace.host_spans() if s.name == "serve.iteration"
           and s.attrs.get("program") not in (None, "idle")]
    assert its and all(0 <= s.attrs["state_slots"] <= 4 for s in its[-20:])


class _Span:
    def __init__(self, t0, t1, **attrs):
        self.t0, self.t1, self.attrs = t0, t1, attrs


def test_the_kda_readers_read_the_kernel_and_the_steps(monkeypatch):
    roofline = harness.load_reader("kda_decode_roofline.tokens")
    busy = harness.load_reader("kda_busy_share.tokens")
    spans = [_Span(9.0, 10.5, program="decode", state_slots=128),  # before
             _Span(11.0, 11.02, program="decode", state_slots=128),
             _Span(11.1, 11.15, program="fused", state_slots=127),
             _Span(12.0, 12.02, program="decode", state_slots=0),
             _Span(13.0, 13.01, program="decode")]         # no counter
    monkeypatch.setattr(spanreaders, "working_iterations",
                        lambda ctx: spans)
    red = {"custom_calls": {"kda_decode f32[128,32,128]": 0.012,
                            "masked_attention f32[1024,8192]": 1.0},
           "busy_s": 0.05, "n_devices": 1}
    ctx = {"config": REAL, "peaks": PEAKS, "trace_reduced": red,
           "window": {"traced": [10.0, 16.0]}}
    want = sum(family.kda_decode_call(REAL, n)["bytes"] / 819e9
               for n in (128, 127, 0))
    assert roofline(ctx) == pytest.approx(100 * want / 0.012)
    assert 0 < roofline(ctx) < 100
    assert busy(ctx) == pytest.approx(100 * 0.012 / 0.05)
    # the parent's program: no such kernel in the trace, no counter
    other = {**ctx, "trace_reduced": {"custom_calls": {
        "paged_latent_attention bf16[64,64,512]": 1.0}, "busy_s": 1.0}}
    assert roofline(other) is None and busy(other) is None
    assert roofline({**ctx, "trace_reduced": None}) is None
    assert busy({**ctx, "trace_reduced": None}) is None
    monkeypatch.setattr(spanreaders, "working_iterations",
                        lambda ctx: spans[-1:])
    assert roofline(ctx) is None
    assert roofline({**ctx, "config": bm_toy.toy_config("toy-sarvam")}) \
        is None
