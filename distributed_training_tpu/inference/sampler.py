"""Autoregressive generation: KV-cache decode loop + sampling transforms.

The reference is a training-only repo (no inference path anywhere in its
three trainers; SURVEY.md §0), but a complete LM framework needs a decode
story. TPU-native formulation:

- **Chunked prefill**: one forward over the whole prompt in decode mode
  fills every block's KV cache (``RingSelfAttention._decode_attend``) in a
  single MXU-shaped pass — no per-token prompt loop.
- **Jitted decode loop**: ``lax.scan`` over ``max_new_tokens`` steps with
  the cache pytree in the carry. The whole generate call is ONE compiled
  XLA program (two traces total: prefill shape + step shape); no host
  round-trips between tokens.
- **Static shapes**: the cache is ``max_len`` slots allocated up front;
  early EOS termination is a carried ``finished`` mask (emitting
  ``pad_id``), not a dynamic break — XLA-friendly control flow.

Sampling: greedy (``temperature=0``), temperature, top-k, and nucleus
(top-p) filtering, composable in the HF order (temperature → top-k → top-p).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


@dataclasses.dataclass(frozen=True)
class SampleConfig:
    """Decode-time knobs. All static: changing them retraces the loop."""

    max_new_tokens: int = 128
    temperature: float = 1.0  # 0 → greedy (argmax)
    top_k: int | None = None
    top_p: float | None = None
    eos_id: int | None = None  # stop emitting after this token appears
    pad_id: int = 0            # filler after EOS

    def __post_init__(self):
        if self.temperature < 0:
            raise ValueError(
                f"temperature must be >= 0, got {self.temperature} "
                "(negative values would invert the distribution)")
        if self.max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {self.max_new_tokens}")


def apply_top_k(logits: jnp.ndarray, k: int) -> jnp.ndarray:
    """Mask all but the k highest logits to -inf. [..., V] -> [..., V]."""
    if k < 1:
        raise ValueError(f"top_k must be >= 1, got {k}")
    k = min(k, logits.shape[-1])
    kth = lax.top_k(logits, k)[0][..., -1:]
    return jnp.where(logits < kth, -jnp.inf, logits)


def apply_top_p(logits: jnp.ndarray, p: float) -> jnp.ndarray:
    """Nucleus filtering: keep the smallest prefix of the probability-sorted
    vocab whose cumulative mass reaches ``p`` (the most-probable token always
    survives — the exclusive cumsum is 0 at rank 0)."""
    if not 0.0 < p <= 1.0:
        raise ValueError(f"top_p must be in (0, 1], got {p}")
    sorted_logits = jnp.flip(jnp.sort(logits, axis=-1), axis=-1)
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    exclusive_cum = jnp.cumsum(probs, axis=-1) - probs
    dropped = exclusive_cum >= p
    # Threshold = smallest kept logit; everything below it is filtered.
    thresh = jnp.min(
        jnp.where(dropped, jnp.inf, sorted_logits), axis=-1, keepdims=True)
    return jnp.where(logits < thresh, -jnp.inf, logits)


def sample_token(rng: jax.Array, logits: jnp.ndarray,
                 cfg: SampleConfig) -> jnp.ndarray:
    """Draw next-token ids [B] from logits [B, V] per the config."""
    logits = logits.astype(jnp.float32)
    if cfg.temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / cfg.temperature
    if cfg.top_k is not None:
        logits = apply_top_k(logits, cfg.top_k)
    if cfg.top_p is not None:
        logits = apply_top_p(logits, cfg.top_p)
    return jax.random.categorical(rng, logits, axis=-1).astype(jnp.int32)


def check_unsharded(model: Any) -> None:
    """Decode requires an unsharded model (shared by Generator/BeamSearcher)."""
    if getattr(model, "seq_axis", None) is not None:
        raise ValueError(
            "generation uses the unsharded decode path; build the model "
            "with seq_axis=None (params are layout-identical)")


class CacheBudgetError(ValueError):
    """A request's token footprint does not fit the KV cache.

    Subclasses ``ValueError`` so pre-existing callers that catch the old
    bare error keep working; serving admission catches this type to turn
    an oversized request into a rejection instead of a crash.
    """


def cache_budget(model: Any, max_len: int | None = None) -> int:
    """Token capacity of one sequence's KV cache (prompt + generated).

    The hard ceiling is ``model.max_len``, the most positions the model
    gives a sequence: the length of a learned position table (cache slots
    past it would decode at silently-clamped rows; ``models/gpt.py``
    poisons that case) or the limit a rotary model publishes.
    ``max_len`` optionally caps it further: the serving engine allocates
    that many slots per decode slot and admits only requests whose whole
    lifetime fits.
    """
    budget = int(model.max_len)
    if max_len is not None:
        if max_len < 1:
            raise ValueError(f"max_len must be >= 1, got {max_len}")
        budget = min(budget, int(max_len))
    return budget


def check_cache_fits(model: Any, prompt_len: int, max_new_tokens: int) -> None:
    """Thin wrapper over :func:`cache_budget` for the generate-call shape."""
    total = prompt_len + max_new_tokens
    budget = cache_budget(model)
    if total > budget:
        raise CacheBudgetError(
            f"prompt ({prompt_len}) + max_new_tokens ({max_new_tokens}) = "
            f"{total} exceeds the KV cache (max_len={budget})")


class Generator:
    """Jitted prompt→completion generation for a :class:`TransformerLM`.

    >>> gen = Generator(model, params, SampleConfig(max_new_tokens=64))
    >>> out = gen(prompt_tokens)   # [B, Tp] int -> [B, 64] int
    """

    def __init__(self, model: Any, params: Any, cfg: SampleConfig,
                 seed: int = 0):
        check_unsharded(model)
        self.model = model
        self.params = params
        self.cfg = cfg
        self._base_rng = jax.random.PRNGKey(seed)
        self._calls = 0
        self._generate = jax.jit(self._generate_impl)

    def _generate_impl(self, params, prompt, rng):
        cfg = self.cfg
        b, t_prompt = prompt.shape
        # Right-size the KV cache to this call's need (prompt + new tokens):
        # max_len slots would inflate the scan carry and every step's
        # attention width ~max_len/total×. clone() rebuilds config only —
        # params are unaffected.
        model = self.model.clone(
            cache_len=t_prompt + cfg.max_new_tokens)

        # Prefill: one decode-mode forward over the whole prompt creates and
        # fills the caches (mutable collection materialized by apply).
        positions = jnp.broadcast_to(jnp.arange(t_prompt), (b, t_prompt))
        logits, vars_out = model.apply(
            {"params": params}, prompt, positions=positions,
            train=False, decode=True, mutable=["cache"])
        cache = vars_out["cache"]
        rng, sub = jax.random.split(rng)
        tok = sample_token(sub, logits[:, -1, :], cfg)

        def step(carry, _):
            cache, tok, pos, rng, finished = carry
            rng, sub = jax.random.split(rng)
            emitted = jnp.where(finished, jnp.int32(cfg.pad_id), tok)
            logits, vars_out = model.apply(
                {"params": params, "cache": cache},
                tok[:, None], positions=pos[:, None],
                train=False, decode=True, mutable=["cache"])
            next_tok = sample_token(sub, logits[:, -1, :], cfg)
            if cfg.eos_id is not None:
                finished = finished | (tok == cfg.eos_id)
            return ((vars_out["cache"], next_tok, pos + 1, rng, finished),
                    emitted)

        # N-1 scan steps emit tokens 0..N-2 (each step emits its carried
        # token and decodes the next); the final carried token is emitted
        # directly — running a scan step for it would waste one full
        # forward whose sample is discarded.
        pos0 = jnp.full((b,), t_prompt, jnp.int32)
        finished0 = jnp.zeros((b,), bool)
        (_, tok, _, _, finished), out = lax.scan(
            step, (cache, tok, pos0, rng, finished0), None,
            length=cfg.max_new_tokens - 1)
        last = jnp.where(finished, jnp.int32(cfg.pad_id), tok)
        out = jnp.concatenate([out, last[None]], axis=0)
        return jnp.swapaxes(out, 0, 1)  # [steps, B] -> [B, steps]

    def __call__(self, prompt_tokens, rng: jax.Array | None = None):
        prompt = jnp.asarray(prompt_tokens, jnp.int32)
        if prompt.ndim == 1:
            prompt = prompt[None, :]
        check_cache_fits(self.model, prompt.shape[1], self.cfg.max_new_tokens)
        if rng is None:
            # Fresh stream per call (fold in a call counter): repeated
            # stochastic sampling without an explicit rng must not return
            # identical completions. Pass rng explicitly to reproduce.
            rng = jax.random.fold_in(self._base_rng, self._calls)
            self._calls += 1
        return np.asarray(self._generate(self.params, prompt, rng))
