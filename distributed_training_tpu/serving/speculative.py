"""Speculative decoding: draft-and-verify over the slot scheduler.

Leviathan et al. 2023 ("Fast Inference from Transformers via Speculative
Decoding") restated for this engine's invariants: a cheap **drafter**
proposes up to ``spec_k`` tokens per decode slot per iteration, and the
target model verifies all ``spec_k + 1`` positions in ONE dispatch — the
decode step generalized from a ``[max_batch, 1]`` batch to a fixed-width
``[max_batch, spec_k + 1]`` verify window (``serving/engine.py``). When
the drafter is right, one target dispatch lands several tokens; when it
is wrong, the iteration degrades to exactly the non-speculative step
(one token), never worse than one token per dispatch.

**Acceptance is lossless by construction — the engine's own twist.**
The textbook rejection-sampling correction (accept draft x with
probability ``min(1, p(x)/q(x))``, resample the residual on reject)
preserves the output *distribution* in aggregate. This engine pins a
stronger contract: sampling RNG is already a pure function of the
request and position (``fold_in(fold_in(seed, uid), position)``), so the
verify window simply computes, at every position ``i``, the token the
sequential decode loop *would have sampled there* —
``t_i = sample(fold_in(rng, pos_i), target_logits_i)`` — and accepts
draft position ``i`` iff every draft up to it matched the target stream
(``d_1..d_i == t_0..t_{i-1}``). Accepted prefixes emit the **target's
own samples** ``t_0..t_a`` (the last one is the free correction/bonus
token: its prefix is fully verified, so it is always emitted). Every
emitted token is therefore bitwise identical to the sequential path —
greedy (argmax) and sampled alike — which implies distribution-identity
and makes the round-8 bitwise oracle extend unchanged: drafts only
decide how many positions one dispatch computes, never what any of them
is. This is the rejection-sampling correction degenerated to a
deterministic proposal with the target's RNG stream pinned: acceptance
probability collapses to an exact token match and the residual
resample IS the target sample the window already drew.

**Static shapes.** The window width is a compile-time constant
(``spec_k + 1``); per-slot accept length is an argmax over a mismatch
mask inside the compiled step (first mismatching draft position, with a
sentinel column so an all-match window accepts ``k``); rows past a
slot's useful draft count (budget clamp, short proposals, inactive
lanes) are validity-masked, never shape changes. Rollback of the
rejected suffix is host-side bookkeeping only: the write head simply
does not advance past the accepted prefix, and the next window's
leading rows overwrite the stale K/V before any valid query can attend
it (every attended position is either verified history or written by
the current window's own valid rows — see docs/SERVING.md for the
induction).

Two drafter backends behind one protocol:

- :class:`NGramDrafter` (default) — prompt-lookup / self-speculation
  (Saxena-style): match the context's longest recent suffix n-gram
  earlier in the context and propose the tokens that followed it. Zero
  extra parameters, zero device work, no new compiled program; shines
  on repetitive continuations (code, extraction, cycles).
- :class:`GPTDrafter` — a small GPT draft model proposing greedily over
  a fixed right-aligned token window via one jitted ``lax.scan``
  program (ONE compiled shape: ``k`` and the window width are static).
  Restorable from a checkpoint via ``inference/restore.py``; with
  ``mirror_target=True`` it self-drafts with the serving model's own
  weights and the engine's hot-swap barrier rolls its params snapshot
  too (``on_weights_swap``), so there is no stale-drafter window after
  a live weight swap.

Drafters are *proposal* machinery: a wrong, stale, or empty proposal
costs acceptance rate, never correctness.

**Latency attribution** (serving/ledger.py): per request, the verify
window bills to the ledger's ``decode`` cause (the window IS the decode
dispatch), the host accept/rewind bookkeeping after tokens land bills
to ``spec_rollback``, and the per-request draft economics ride the
deterministic ``spec_draft``/``spec_accept`` token counters — the
request-level split of the engine-global ``drafted_tokens``/
``accepted_tokens`` zero-drift pair.
"""

from __future__ import annotations

from typing import Any, Protocol, runtime_checkable

import numpy as np

_EMPTY = np.zeros((0,), np.int32)


@runtime_checkable
class Drafter(Protocol):
    """Per-slot draft proposer for the engine's verify window.

    Implementations must be deterministic pure functions of the
    context (plus their own params): the engine's drafted/accepted
    telemetry is gated zero-drift by ``tools/bench_compare.py`` on the
    strength of that determinism, and acceptance itself is pinned
    batch-composition-independent because proposals depend only on the
    slot's own token stream.
    """

    def propose(self, context: np.ndarray, k: int) -> np.ndarray:
        """Up to ``k`` proposed continuation tokens (int32 [<=k]) for
        ``context`` (prompt + emitted tokens, host-side int32 [n]).
        Fewer (or zero) proposals shrink the window's valid width —
        cheaper than wrong guesses, never incorrect."""
        ...

    def on_weights_swap(self, params: Any, epoch: int) -> None:
        """Hot-swap barrier notification (engine thread, inside the
        swap barrier): the target model now serves ``params``. Drafters
        holding target-derived state must roll it here."""
        ...

    def compiled_programs(self) -> dict:
        """``{name: compiled-shape count}`` of any jit programs this
        drafter owns — merged into ``Engine.compiled_programs()`` so
        the recompile sanitizer pins the drafter's inventory too."""
        ...


class NGramDrafter:
    """Prompt-lookup drafter: propose the continuation of the most
    recent earlier occurrence of the context's suffix n-gram.

    Backs off from ``max_ngram`` down to ``min_ngram``: the longest
    suffix with an earlier match wins; within one ``n``, the MOST
    RECENT match wins (recency tracks the current phrase).

    ``fallback_repeat`` (default on) pads short or empty lookups to the
    full ``k`` by repeating the last proposed (else last context)
    token. The verify window is fixed-width, so an empty draft row is
    compute the engine pays for while carrying no bet — a
    low-confidence guess strictly dominates it on throughput (token
    runs like ``15 15 15`` are common decode attractors), at the cost
    of diluting the ``spec_acceptance_rate`` *metric* with cheap
    guesses. Turn it off to read acceptance as a pure lookup-quality
    signal.
    """

    def __init__(self, max_ngram: int = 3, min_ngram: int = 1,
                 fallback_repeat: bool = True):
        if min_ngram < 1 or max_ngram < min_ngram:
            raise ValueError(
                f"need 1 <= min_ngram <= max_ngram, got "
                f"[{min_ngram}, {max_ngram}]")
        self.max_ngram = int(max_ngram)
        self.min_ngram = int(min_ngram)
        self.fallback_repeat = bool(fallback_repeat)

    def propose(self, context: np.ndarray, k: int) -> np.ndarray:
        # graftlint: disable=hot-path-transfer -- context is host numpy by protocol (the engine's slot bookkeeping); input normalization only
        ctx = np.asarray(context, np.int32).reshape(-1)
        out = _EMPTY
        n_ctx = ctx.size
        for n in range(min(self.max_ngram, n_ctx - 1),
                       self.min_ngram - 1, -1):
            pat = ctx[n_ctx - n:]
            # Candidate starts: every EARLIER position whose n-gram
            # equals the suffix (the suffix's own start is excluded so
            # the proposal is a real continuation, not the suffix).
            # Vectorized — this runs per decoding slot per iteration,
            # and a python matching loop here measurably drags the
            # whole engine (the drafter must stay far cheaper than the
            # verify dispatch it feeds).
            grams = np.lib.stride_tricks.sliding_window_view(
                ctx, n)[: n_ctx - n]
            hits = np.flatnonzero((grams == pat).all(axis=1))
            if hits.size:
                s = int(hits[-1])  # most recent match wins
                out = ctx[s + n: s + n + k].astype(np.int32)
                break
        if self.fallback_repeat and out.size < k and n_ctx:
            last = out[-1] if out.size else ctx[-1]
            out = np.concatenate(
                [out, np.full((k - out.size,), last, np.int32)])
        return out

    def on_weights_swap(self, params: Any, epoch: int) -> None:
        pass  # context-only: nothing derived from the target weights

    def compiled_programs(self) -> dict:
        return {}  # host-side only


class GPTDrafter:
    """GPT draft model: greedy proposals over a fixed token window.

    One jitted program (the ``draft`` entry of the engine's compiled-
    program inventory), one shape: the context's last ``window`` tokens
    sit right-aligned in a pad-filled ``[window]`` buffer and a
    ``lax.scan`` of ``k`` steps re-runs the draft model's full forward
    on the rolling window, appending the argmax each step. Proposal
    positions are window-local (0..window-1) — an approximation the
    acceptance math is immune to (a mispositioned draft just gets
    rejected).

    ``model``/``params`` may be any :class:`TransformerLM` + matching
    tree — a separate small draft checkpoint restored via
    ``inference/restore.py::build_lm_and_restore``, or (the
    ``mirror_target=True`` default the engine wires for
    ``spec_drafter='gpt'``) the serving model itself, window-truncated:
    self-drafting spends a cheap short-window forward per draft token
    to win the per-dispatch overhead of the full-length verify; it then
    holds the engine's own tree, stacked where the engine stacks
    (serving/stacked.py), and the draft program indexes it back. In
    mirror mode :meth:`on_weights_swap` re-points the params snapshot
    at the engine's freshly swapped tree inside the swap barrier, so a
    mid-speculation deploy leaves no stale-drafter window (pinned by
    tests/test_speculative.py).
    """

    def __init__(self, model: Any, params: Any, *, window: int = 16,
                 pad_id: int = 0, mirror_target: bool = False):
        import jax

        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
        if window > int(model.max_len):
            raise ValueError(
                f"draft window {window} exceeds the draft model's "
                f"positional table (max_len={model.max_len})")
        self.model = model
        self.params = params
        self.window = int(window)
        self.pad_id = int(pad_id)
        self.mirror_target = bool(mirror_target)
        # k is static (the engine always asks for its fixed spec_k), so
        # the scan length is baked and the program holds one shape.
        self._propose = jax.jit(self._propose_impl, static_argnums=(2,))

    def _propose_impl(self, params, window_tokens, k: int):
        import jax
        import jax.numpy as jnp
        from jax import lax

        from distributed_training_tpu.serving.stacked import per_layer

        params = per_layer(params)

        def step(win, _):
            logits = self.model.apply({"params": params}, win[None],
                                      train=False)
            nxt = jnp.argmax(
                logits[0, -1, :].astype(jnp.float32)).astype(jnp.int32)
            return jnp.concatenate([win[1:], nxt[None]]), nxt

        _, toks = lax.scan(step, window_tokens, None, length=k)
        return toks

    def propose(self, context: np.ndarray, k: int) -> np.ndarray:
        import jax.numpy as jnp

        # graftlint: disable=hot-path-transfer -- context is host numpy by protocol; input normalization only
        ctx = np.asarray(context, np.int32).reshape(-1)[-self.window:]
        win = np.full((self.window,), self.pad_id, np.int32)
        win[self.window - ctx.size:] = ctx
        # graftlint: disable=hot-path-transfer -- the draft landing: proposals must reach the host to assemble the verify window (docs/SERVING.md "Speculative decoding")
        return np.asarray(self._propose(self.params, jnp.asarray(win),
                                        int(k)))

    def on_weights_swap(self, params: Any, epoch: int) -> None:
        """Roll the params snapshot at the engine's swap barrier when
        self-drafting (mirror mode): same shapes/dtypes (the barrier
        already validated the tree), so the draft program binds the new
        argument without a retrace — exactly the target step's
        contract. A separate draft model keeps its own weights."""
        if self.mirror_target:
            self.params = params

    def compiled_programs(self) -> dict:
        from distributed_training_tpu.observability.sanitizer import (
            jit_cache_size,
        )

        return {"draft": jit_cache_size(self._propose)}


def make_drafter(cfg, model: Any, params: Any):
    """Build ``ServeConfig.spec_drafter``'s backend for an engine.

    ``ngram`` needs nothing beyond the config; ``gpt`` self-drafts with
    the serving model's own weights (mirror mode — hot-swap keeps it
    fresh). A separate small draft model bypasses this factory:
    ``Engine(model, params, cfg, drafter=GPTDrafter(draft_model,
    draft_params, window=...))``.
    """
    if cfg.spec_drafter == "ngram":
        return NGramDrafter(max_ngram=cfg.spec_ngram)
    if cfg.spec_drafter == "gpt":
        return GPTDrafter(
            model, params,
            window=min(int(cfg.spec_draft_window), int(model.max_len)),
            pad_id=cfg.pad_id, mirror_target=True)
    raise ValueError(f"unknown spec_drafter {cfg.spec_drafter!r}")


def accept_counts(window_tokens: np.ndarray, targets: np.ndarray,
                  valid: np.ndarray) -> np.ndarray:
    """Host/numpy mirror of the compiled accept formulation (the test
    oracle for the device argmax-over-mismatch-mask): per batch row,
    the number of leading drafts (``window_tokens[:, 1:]``) that match
    the target stream (``targets[:, :-1]``) within the valid width.
    """
    mismatch = (window_tokens[:, 1:] != targets[:, :-1]) | ~valid[:, 1:]
    sentinel = np.ones((mismatch.shape[0], 1), bool)
    return np.argmax(np.concatenate([mismatch, sentinel], axis=1), axis=1)


def truncate_at_eos(tokens: np.ndarray, eos_id: int | None) -> np.ndarray:
    """Cut an accepted token run at its first EOS (inclusive): the
    sequential loop would have stopped there, so tokens past a
    mid-window EOS were never part of the sequential output."""
    if eos_id is None:
        return tokens
    hits = np.flatnonzero(tokens == eos_id)
    return tokens[: hits[0] + 1] if hits.size else tokens
