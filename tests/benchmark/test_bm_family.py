"""The seam between the harness and a model family: a family whose file has
none of GPT-2's keys runs through both drivers from files under ``data/``
alone; which names ``reduced`` may hold; the comparison in blocks against
the whole one; and GPT-2's counts, moved into its family's file, against
the formulas they were moved from on the iterations a toy run records."""

import jax.numpy as jnp
import numpy as np
import pytest

import bm_toy
from benchmark import readers, trafficgen, weights, workmodel
from benchmark.drivers import serve
from test_bm_contract import is_width

GPT2_KEYS = {"n_embd", "n_head", "n_layer", "n_positions", "n_inner",
             "n_ctx", "assumed", "padded_vocab_size", "layer_norm_epsilon"}


@pytest.fixture
def toy_family(monkeypatch):
    monkeypatch.setenv("TQDM_DISABLE", "1")
    bm_toy.toy_family(monkeypatch)


@pytest.mark.parametrize("traffic,limits,metric", [
    ("toy-serve-batch", bm_toy.SERVE_LIMITS, "serve_tokens_per_s"),
    ("toy-train", bm_toy.TRAIN_LIMITS, "train_tokens_per_s")])
def test_a_family_without_gpt2s_keys_runs_through_the_harness(
        toy_family, traffic, limits, metric):
    cfg = bm_toy.toy_config("toy-lm")
    assert not GPT2_KEYS & (set(cfg) | set(cfg["as_run"]))
    r = bm_toy.run_toy(traffic, 2 ** 31 + 26, 1.0, limits, config="toy-lm")
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {metric, "setup_s"}
    assert {c["name"] for c in r["checks"]} == set(limits)


def test_the_family_refuses_a_file_whose_keys_do_not_fit(toy_family):
    cfg = bm_toy.toy_config("toy-lm")
    workmodel.family(cfg).validate(cfg)
    with pytest.raises(ValueError, match="multiple"):
        workmodel.family(cfg).validate({**cfg, "num_attention_heads": 3})


MAY_BE_CUT = ["num_hidden_layers", "n_layer", "n_routed_experts",
              "num_attention_heads", "vocab_size",
              "num_nextn_predict_layers", "first_k_dense_replace"]
WIDTHS = ["hidden_size", "n_embd", "intermediate_size",
          "moe_intermediate_size", "kv_lora_rank", "q_lora_rank",
          "qk_rope_head_dim", "v_head_dim", "index_head_dim", "head_dim",
          "num_experts_per_tok", "index_topk", "sliding_window", "n_inner"]


@pytest.mark.parametrize("key,width", [(k, False) for k in MAY_BE_CUT]
                         + [(k, True) for k in WIDTHS])
def test_reduced_may_name_what_is_counted_and_never_a_width(key, width):
    gpt2 = workmodel.family({"reference": "gpt2"})
    assert is_width(key, gpt2) is width


def _sample(seed, cfg, lengths):
    rng = np.random.default_rng(seed)
    ids = workmodel.family(cfg).token_ids(cfg)
    return [(rng.integers(0, ids, p).astype(np.int32),
             rng.integers(0, ids, n).astype(np.int32)) for p, n in lengths]


@pytest.mark.parametrize("block", [4, 7, 64])   # 20 = 5 x 4; 7 leaves 6
@pytest.mark.parametrize("lowp", [None, "float8_e5m2"])   # e4m3 reads 0
def test_the_comparison_in_blocks_is_the_whole_one(block, lowp):
    cfg = bm_toy.toy_config()
    spec = trafficgen.load("toy-serve-batch", bm_toy.DATA)
    ref = workmodel.reference(cfg)
    lowp = lowp and ref.round_to(jnp.dtype(lowp))
    sample = _sample(3, cfg, [(12, 8), (3, 8), (7, 3), (6, 5)])
    got = serve.reference_gaps(cfg, 5, spec, sample, lowp=lowp, block=block)
    params = weights.make(5, ref.param_shapes(cfg), jnp.float32)
    want = []
    for prompt, served in sample:      # the whole one: all logits at once
        seq = np.concatenate([prompt, served])
        logits = ref.forward(params, jnp.asarray(seq[None]), cfg)[0]
        chosen = (np.roll(seq, -1) if lowp is None else
                  ref.forward(params, jnp.asarray(seq[None]), cfg,
                              lowp)[0].argmax(-1))
        gap = logits.max(-1) - logits[np.arange(seq.size), chosen]
        want.append(np.asarray(gap)[prompt.size - 1:seq.size - 1])
    want = np.concatenate(want)
    assert got.shape == want.shape == (24,)
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-5)
    assert want.max() > 1e-3    # random tokens are not the best ones, nor
    # is the lower precision's first choice everywhere the reference's


@pytest.mark.parametrize("n,longest,padded", [
    (5, 320, 64), (64, 320, 64), (65, 320, 128), (257, 320, 320),
    (320, 320, 320), (20, 20, 20), (16000, 32768, 16384)])
def test_requests_are_padded_to_a_few_fixed_lengths(n, longest, padded):
    assert serve.padded_length(n, longest) == padded >= n


def _parent_counts(cfg):
    """``benchmark/workmodel.py`` as it stood before the counts moved."""
    d, layers = int(cfg["n_embd"]), int(cfg["n_layer"])
    rows = int(cfg["assumed"]["padded_vocab_size"])
    m = 4 * d

    def token(keys):
        return layers * (2 * d * 3 * d + 2 * d * d + 4 * d * m
                         + 4 * d * keys) + 2 * d * rows

    read = layers * (12 * d * d + 13 * d) + rows * d + 2 * d
    return {"token": token, "prompt": lambda n: n * token((n + 1) / 2.0),
            "bytes": lambda context_rows: 2 * read
            + 2 * layers * d * 2 * context_rows}


@pytest.mark.parametrize("config", ["toy-gpt2", "toy-lm"])
def test_moved_counts_are_the_parents_on_a_toy_runs_iterations(
        toy_family, config):
    cfg = bm_toy.toy_config(config)
    spec = trafficgen.load("toy-serve-batch", bm_toy.DATA)
    ctx = {"config": cfg, "traffic": spec, "seed": 9, "seconds": 0.5,
           "trace": False, "trace_seconds": 0.0, "trace_dir": ""}
    s = serve.setup(ctx)
    its = serve.measure(ctx, s)["iterations"]
    serve.release(ctx, s)
    old = _parent_counts(bm_toy.toy_config())
    counts = workmodel.family(cfg)
    decoding = 0
    for it in its:
        n_dec = sum(it["landed"].values()) - len(it["prompt_flops_tokens"])
        assert len(it["contexts"]) <= n_dec
        assert sum(it["contexts"]) == it["context_rows"]
        flops = sum(old["prompt"](p) for p in it["prompt_flops_tokens"])
        if n_dec > 0:
            flops += n_dec * old["token"](it["context_rows"] / n_dec + 1)
        assert readers.iteration_flops(cfg, it) == flops
        assert counts.decode_iteration_bytes(cfg, it["contexts"]) == \
            old["bytes"](it["context_rows"])
        decoding += len(it["contexts"])
    assert decoding > len(its)      # slots decoded side by side
    assert counts.train_flops_token(cfg, 16) == 3.0 * old["token"](8.5)
