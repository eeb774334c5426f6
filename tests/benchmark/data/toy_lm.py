"""A second family for CPU rehearsals of the seam: a configuration file with
a hub-style config's keys (``hidden_size``, ``num_hidden_layers``, ...) and
none of GPT-2's. The model is GPT-2's layout, so this file, which is both the
family (``benchmark.families.toy_lm``) and the plain reference
(``benchmark.reference.toy_lm``), translates the keys and hands on to
GPT-2's. ``bm_toy.toy_family`` puts it under those two names; the harness
and the drivers find it by the configuration's ``reference`` key alone.
"""

from benchmark.families import gpt2 as _family
from benchmark.reference import gpt2 as _reference

WIDTH_KEYS = ()
ADAM = _reference.ADAM
fine_leaves = _reference.fine_leaves
round_to = _reference.round_to


def _keys(cfg: dict) -> dict:
    """The file's sizes under the names GPT-2's code reads."""
    return {"n_layer": cfg["num_hidden_layers"],
            "n_head": cfg["num_attention_heads"],
            "n_embd": cfg["hidden_size"],
            "n_positions": cfg["max_position_embeddings"],
            "vocab_size": cfg["vocab_size"],
            "layer_norm_epsilon": cfg["layer_norm_eps"],
            "assumed": {"padded_vocab_size": cfg["as_run"]["embedding_rows"],
                        "head_bias": cfg["as_run"]["head_bias"]}}


def _family_side(name):
    fn = getattr(_family, name)
    return lambda cfg, *args: fn(_keys(cfg), *args)


validate = _family_side("validate")
token_ids = _family_side("token_ids")
build_model = _family_side("build_model")
train_flags = _family_side("train_flags")
param_count = _family_side("param_count")
matmul_params_read = _family_side("matmul_params_read")
forward_flops_token = _family_side("forward_flops_token")
prompt_forward_flops = _family_side("prompt_forward_flops")
train_flops_token = _family_side("train_flops_token")
decode_iteration_bytes = _family_side("decode_iteration_bytes")


def param_shapes(cfg):
    return _reference.param_shapes(_keys(cfg))


def hidden(params, tokens, cfg, lowp=None):
    return _reference.hidden(params, tokens, _keys(cfg), lowp)


def head(params, x, cfg, lowp=None):
    return _reference.head(params, x, _keys(cfg), lowp)


def forward(params, tokens, cfg, lowp=None):
    return _reference.forward(params, tokens, _keys(cfg), lowp)


def train_steps(params, batches, cfg, **plant):
    return _reference.train_steps(params, batches, _keys(cfg), **plant)
