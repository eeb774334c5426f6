"""Keye-VL-2.0-30B-A3B's language model in the benchmark, at a toy size on
the CPU: the family's counts against hand sums and against the model's own
parameter tree, what its ``validate`` refuses, the configuration file
against the catalog's row, the toy cell through the harness with its
counters and the new reader, and the float8 control failing the toy limits.
The model itself: tests/test_keye_vl2.py."""

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
from benchmark.families import keye_vl2 as family
from benchmark.reference import keye_vl2 as ref
from distributed_training_tpu.observability import trace

TOY = bm_toy.toy_config("toy-keye")
with open(os.path.join(harness.ROOT, "benchmark", "configs",
                       "keye-vl-2.0-30b-a3b-pp8.json")) as _fh:
    REAL = json.load(_fh)
CELL = "keyevl2-serve-longctx"
# toy readings (fp32 both sides, seeds 5 and 2**31 + 34): the program
# gap_max <= 2.4e-7, gap_mean <= 2.1e-8 (sums in another order); the float8
# control gap_max 2.4e-3 .. 4.9e-3, gap_mean 2.9e-4 .. 3.4e-4
TOY_LIMITS = {"gap_max": 1e-4, "gap_mean": 1e-5, "wrong_length": 0}
PEAKS = {"flops_per_s": 197e12, "bytes_per_s": 819e9, "memory_bytes": 16e9}


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


# -- the family's counts -----------------------------------------------------

def test_the_counts_against_hand_sums():
    d = 2048
    gqa = d * 32 * 128 + 2 * d * 4 * 128 + 32 * 128 * d
    indexer = d * 16 * 64 + d * 64 + d * 16 + 2 * 64
    expert = 3 * d * 768
    outside = gqa + 2 * 128 + indexer + 2 * d + d * 128
    layer = outside + 128 * expert
    # ISSUE 34's arithmetic
    assert (gqa, indexer, expert) == (18874368, 2261120, 4718592)
    assert layer == 625381760
    total = 6 * layer + 2 * 151936 * d + d
    assert family.param_count(REAL) == total == REAL["parameters"] \
        == 4374622464
    assert total == sum(math.prod(v)
                        for v in ref.param_shapes(REAL).values())
    read = total - 151936 * d - 6 * 128 * expert
    assert family.matmul_params_read(REAL) == read
    # a decode iteration of 16 slots at 6400 rows: 82.4 of 128 experts a
    # layer, the index key of every live row (64 wide), K and V of 2048
    hit = 128 * (1 - (15 / 16) ** 16)
    assert round(hit, 1) == 82.4
    want = 2 * (read + 6 * hit * expert
                + 6 * 16 * (6400 * 64 + 2048 * 1024))
    assert family.decode_iteration_bytes(REAL, [6400] * 16) == \
        pytest.approx(want)
    # 0.88 GB outside the experts + 4.67 of hit experts + 0.48 of cache
    # rows: 7.4 ms at 819 GB/s
    assert 5.9e9 < want < 6.2e9
    assert family.decode_iteration_bytes(REAL, []) == 2 * read
    # under the top-k every live row is selected
    assert family.decode_iteration_bytes(REAL, [100]) - 2 * (
        read + 6 * 128 / 16 * expert) == pytest.approx(
        2 * 6 * 100 * (64 + 1024))
    s = ref.sizes(REAL)
    norms = 2 * 128 + 2 * 64 + 2 * d
    matrices = 2 * (read - (d + 6 * norms) + 6 * 8 * expert)
    assert family._token_matmul_flops(s) == pytest.approx(matrices,
                                                          rel=1e-4)
    assert family.forward_flops_token(REAL, 6400) == pytest.approx(
        family._token_matmul_flops(s)
        + 6 * (2 * 16 * 64 * 6400 + 2 * 32 * 256 * 2048))
    # a prompt within the top-k is dense and causal; past it, capped
    assert family.prompt_forward_flops(REAL, 1000) == pytest.approx(
        1000 * family._token_matmul_flops(s)
        + 6 * 500500 * (2 * 16 * 64 + 2 * 32 * 256))
    assert family.prompt_forward_flops(REAL, 4096) == pytest.approx(
        4096 * family._token_matmul_flops(s) + 6 * (
            4096 * 4097 / 2 * 2 * 16 * 64
            + (2048 * 2049 / 2 + 2048 * 2048) * 2 * 32 * 256))
    # a chunk's attention: each query over the keys it selects; a key's K
    # and V row (2 x 4 x 128) read once for its 8 query heads
    first = family.chunk_attention_call(REAL, 0, 1024)
    assert first["flops"] == 6 * 1024 * 1025 / 2 * 2 * 32 * 256
    assert first["bytes"] == 6 * 2 * (1024 * 2 * 32 * 128 + 1024 * 1024)
    fifth = family.chunk_attention_call(REAL, 4096, 1000)
    assert fifth["flops"] == 6 * 1000 * 2048 * 2 * 32 * 256
    assert fifth["bytes"] == 6 * 2 * (1000 * 2 * 32 * 128 + 5096 * 1024)
    assert sum(family.chunk_attention_call(REAL, at, 1024)["flops"]
               for at in range(0, 4096, 1024)) == pytest.approx(
        6 * (2048 * 2049 / 2 + 2048 * 2048) * 2 * 32 * 256)


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
    ({"num_local_experts": 64}, "held"),
    ({"first_k_dense_replace": 1}, "expert layer"),
    ({"mlp_only_layers": [0]}, "expert layer"),
    ({"num_key_value_heads": 5}, "divide"),
    ({"rope_scaling": {"mrope_section": [16, 24, 32]}}, "mrope_section"),
    ({"norm_topk_prob": False}, "renormalised"),
    ({"use_sliding_window": True}, "sliding"),
    ({"vocab_size": 1 << 20}, "vocabulary")])
def test_the_family_refuses_a_file_whose_keys_do_not_fit(edit, message):
    family.validate(REAL)
    family.validate(TOY)
    with pytest.raises(ValueError, match=message):
        family.validate({**REAL, **edit})
    with pytest.raises(NotImplementedError, match="served"):
        family.train_flags(REAL)


def test_the_configuration_file_holds_the_catalogs_row():
    """Every key of the catalog's row for Keye-VL-2.0-30B-A3B under the same
    key and value, but the depth ``reduced`` names; the published depth
    beside it; the two keys the accepted readers look for."""
    bench = harness.load_benchmark()
    entry = next(c for c in bench["configs"]
                 if c["name"] == "keye-vl-2.0-30b-a3b-pp8")
    assert entry["reduced"] == ["num_hidden_layers"]
    catalog = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
        "max_position_embeddings": 262144, "max_window_layers": 48,
        "mlp_only_layers": [], "model_type": "KeyeVL2",
        "moe_intermediate_size": 768, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts": 128,
        "num_experts_per_tok": 8, "num_hidden_layers": 48,
        "num_key_value_heads": 4, "num_local_experts": 128,
        "rms_norm_eps": 1e-06,
        "rope_scaling": {"mrope_section": [16, 24, 24],
                         "rope_type": "default", "type": "default"},
        "rope_theta": 10000000,
        "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                      "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                      "q_chunk_size": 512, "topk": 2048},
        "sliding_window": None, "tie_word_embeddings": False,
        "use_sliding_window": False, "vocab_size": 151936}
    kept = {k: v for k, v in catalog.items() if k != "num_hidden_layers"}
    assert {k: REAL[k] for k in kept} == kept
    assert REAL["published"]["num_hidden_layers"] == 48
    assert REAL["num_hidden_layers"] == 6
    assert REAL["deployment"]["chips_sharing_a_layer"] == 1
    assert REAL["deployment"]["pipeline_stages"] == 8
    assert REAL["n_routed_experts"] == 128 == REAL["num_experts"]
    assert REAL["first_k_dense_replace"] == 0
    assert set(REAL) - set(catalog) == {
        "source", "reference", "n_routed_experts", "first_k_dense_replace",
        "published", "deployment", "assumed", "precision", "not_run",
        "parameters"}


def test_the_cell_runs_the_long_context_traffic_as_it_is():
    bench = harness.load_benchmark()
    cell = harness.find_cell(bench, CELL)
    assert (cell["traffic"], cell["chips"]) == ("serve-longctx", 1)
    spec = trafficgen.load(cell["traffic"])
    e = spec["engine"]
    # 13 056 B of keys, values and index keys a token, 13 824 as held
    assert 6 * (2 * 4 * 128 + 128) * 2 == 13824
    assert e["kv_pages"] * e["kv_page_size"] == 16 * e["max_len"] == 270336
    assert spec["prompt_tokens"]["min"] > REAL["sa_config"]["topk"]
    with open(os.path.join(harness.ROOT, "benchmark", "limits",
                           CELL + ".json")) as fh:
        assert set(json.load(fh)["limits"]) == {"gap_max", "gap_mean",
                                                "wrong_length"}


# -- the toy cell through the harness ----------------------------------------

def toy_cell():
    bench = copy.deepcopy(harness.load_benchmark())
    bench["configs"].append({"name": "toy",
                             "file": "tests/benchmark/data/toy-keye.json"})
    cell = {"name": "toy.longctx", "config": "toy",
            "traffic": "toy-serve-longctx", "chips": 1, "why": "toy"}
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append(cell["name"])
    bench["workloads"].append(cell)
    return cell, bench


def test_the_float8_control_fails_the_toy_limits_and_the_program_does_not():
    """The control: the reference in the program's place, operands in
    float8, on the sample a run of the toy cell compared."""
    cell, _ = toy_cell()
    spec = trafficgen.load("toy-serve-longctx", bm_toy.DATA)
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
    assert control["gap_mean"] > 10 * TOY_LIMITS["gap_mean"]


def test_the_traced_toy_run_reports_the_cells_metrics(monkeypatch):
    from benchmark import spanreaders, tracereduce

    monkeypatch.setattr(tracereduce, "load", bm_toy.fake_trace)
    monkeypatch.setattr("benchmark.peaks.peaks_for", lambda kind: PEAKS)
    # the span readers hand out nothing off a TPU: let them read this run's
    monkeypatch.setattr(spanreaders, "_ring",
                        lambda device: trace.host_spans)
    cell, bench = toy_cell()
    r = harness.run_cell(cell, bench, 2 ** 31 + 34, 1.0, True,
                         device=bm_toy.CPU, limits=TOY_LIMITS,
                         traffic_dir=bm_toy.DATA)
    m = {k: v["value"] for k, v in r["metrics"].items()}
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert {c["name"] for c in r["checks"]} == set(TOY_LIMITS)
    # a query reads 8 of its 17-40 live rows; its lane scores the index
    # keys of the slot's whole budget of 40
    assert 20 < m["kv_select_share.tokens"] < 50
    assert 100 < m["index_scan_share.tokens"] < 250
    assert m["index_scan_share.tokens"] / m["kv_select_share.tokens"] \
        == pytest.approx(40 / 8, rel=1e-6)
    assert 100 <= m["expert_imbalance.tokens"] <= 800
    # 8 held experts x 2 layers: a chunk's 16 tokens hit nearly all, 1-3
    # decoding tokens a quarter to a half (a fused step is two passes)
    assert 20 <= m["expert_hit_share.tokens"] <= 100
    assert 0 < m["mfu.tokens"] and 0 < m["decode_roofline.tokens"]
    # no kernel of that name in the (fake) trace: the toy's heads keep XLA
    assert "masked_attention_roofline.tokens" not in m


def test_the_index_scan_reader_on_toy_spans(monkeypatch):
    from benchmark import spanreaders

    read = harness.load_reader("index_scan_share.tokens")

    def spans(*attrs):
        return lambda ctx: [type("S", (), {"attrs": a})() for a in attrs]

    monkeypatch.setattr(spanreaders, "working_iterations", spans(
        {"program": "decode", "kv_rows_live": 6000, "kv_rows_selected": 2048,
         "index_rows_scored": 16896},
        {"program": "fused", "kv_rows_live": 0, "kv_rows_selected": 0,
         "index_rows_scored": 0},
        {"program": "decode", "kv_rows_live": 2000 + 9000,
         "kv_rows_selected": 2000 + 2048, "index_rows_scored": 2 * 16896}))
    assert read({}) == pytest.approx(100 * 3 * 16896 / 17000)
    # a program without the counter (the parent's), and a model without an
    # indexer (nothing scored): the reader finds nothing, and does not raise
    monkeypatch.setattr(spanreaders, "working_iterations", spans(
        {"program": "decode", "kv_rows_live": 6000,
         "kv_rows_selected": 6000}))
    assert read({}) is None
    monkeypatch.setattr(spanreaders, "working_iterations", spans(
        {"program": "decode", "kv_rows_live": 6000, "kv_rows_selected": 6000,
         "index_rows_scored": 0}))
    assert read({}) is None
