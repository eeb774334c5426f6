"""What the per-layer readers share. Each metric's own file under
``metrics/`` is a few lines that name one of these; a reader that finds
nothing to read returns ``None`` and the metric is left out of the line."""

from __future__ import annotations

from benchmark import servestats, workmodel


def traced_iterations(ctx: dict) -> list:
    """The serving iterations that lie inside the traced part."""
    w = ctx["window"]
    if not w.get("traced") or "iterations" not in w:
        return []
    lo, hi = w["traced"]
    return servestats.inside(w["iterations"], lo, hi)


def working(ctx: dict) -> list:
    """The window's iterations that had something to do."""
    return [it for it in ctx["window"].get("iterations", [])
            if it["live"] or it["landed"]]


def engine_iter_ms_p50(ctx: dict):
    its = working(ctx)
    if not its:
        return None
    return servestats.percentile([(it["t1"] - it["t0"]) * 1e3 for it in its],
                                 50)


def slot_occupancy(ctx: dict):
    """Tokens' worth of slots doing output work: the mean over working
    iterations of (requests that received a token) / slots, in %."""
    its = working(ctx)
    if not its:
        return None
    slots = ctx["window"]["slots"]
    return 100.0 * sum(len(it["landed"]) for it in its) / (len(its) * slots)


def ttft_ms(ctx: dict, q: float):
    v = ctx["window"].get("ttft_ms")
    return servestats.percentile(v, q) if v else None


def iteration_flops(cfg: dict, it: dict) -> float:
    """Forward FLOPs of what one engine iteration processed: the prompts
    whose prefill it finished, and one token for every decoding slot at its
    mean live context."""
    counts = workmodel.family(cfg)
    flops = sum(counts.prompt_forward_flops(cfg, p)
                for p in it["prompt_flops_tokens"])
    n_dec = sum(it["landed"].values()) - len(it["prompt_flops_tokens"])
    if n_dec > 0:
        flops += n_dec * counts.forward_flops_token(
            cfg, it["context_rows"] / n_dec + 1)
    return flops


def serve_mfu(ctx: dict):
    """Forward FLOPs of every token processed (prompt and output) per
    second of window, over the chips' peak, in %."""
    w = ctx["window"]
    its = w.get("iterations")
    if its and w.get("traced"):
        # the profiler's start stalls the host: rate the part before it
        its = [it for it in its if it["t1"] <= w["traced"][0]]
    if not its:
        return None
    flops = sum(iteration_flops(ctx["config"], it) for it in its)
    seconds = its[-1]["t1"] - its[0]["t0"]
    return 100.0 * flops / seconds / (
        ctx["chips"] * ctx["peaks"]["flops_per_s"])


def decode_roofline(ctx: dict):
    """Least time the chip could take for the traced iterations' decode
    work (the bytes the family counts for the iteration's per-slot live
    contexts, weights included, at the memory peak; or their FLOPs at the
    compute peak, whichever is longer) over the device time of those
    iterations, in %."""
    its = [it for it in traced_iterations(ctx) if it["landed"]]
    red = ctx["trace_reduced"]
    if not its or not red:
        return None
    cfg, peaks = ctx["config"], ctx["peaks"]
    counts = workmodel.family(cfg)
    least = 0.0
    for it in its:
        nbytes = counts.decode_iteration_bytes(cfg, it["contexts"])
        least += workmodel.least_seconds(iteration_flops(cfg, it), nbytes,
                                         peaks)[0]
    # Device time of the decode programs: the busy time of the iterations
    # counted, as their share of all traced working iterations.
    all_its = [it for it in traced_iterations(ctx)
               if it["live"] or it["landed"]]
    share = (sum(it["t1"] - it["t0"] for it in its)
             / sum(it["t1"] - it["t0"] for it in all_its))
    return 100.0 * least / (red["busy_s_busiest"] * share)


def train_mfu(ctx: dict):
    w = ctx["window"]
    if "tokens_per_s" not in w:
        return None
    per_token = workmodel.family(ctx["config"]).train_flops_token(
        ctx["config"], w["seq_len"])
    return 100.0 * per_token * w["tokens_per_s"] / (
        ctx["chips"] * ctx["peaks"]["flops_per_s"])


def step_ms(ctx: dict, q: float):
    v = ctx["window"].get("step_ms")
    return servestats.percentile(v, q) if v else None


def flash_roofline(ctx: dict):
    """Causal attention's least time at the peaks over the summed device
    time of the flash forward and backward kernels, in %. The kernels are
    the custom calls whose first result has the attention's own shape
    ``[.., batch x heads, seq, head size]`` (they carry no stable name
    yet)."""
    red, w = ctx["trace_reduced"], ctx["window"]
    if not red or not w.get("flash_calls"):
        return None
    shape = w["flash_call_shape"]
    tail = (f"{shape['batch'] * shape['heads']},{shape['seq']},"
            f"{shape['head_dim']}]")
    seconds = sum(s for name, s in red.get("custom_calls", {}).items()
                  if name.endswith(tail))
    if seconds <= 0:
        return None
    call = workmodel.causal_attention_call(**shape)
    per_step = sum(workmodel.least_seconds(call[f"{d}_flops"],
                                           call[f"{d}_bytes"],
                                           ctx["peaks"])[0]
                   for d in ("fwd", "bwd"))
    least = per_step * w["flash_calls"] * w["traced_steps"]
    return 100.0 * least / seconds


def device_idle_share(ctx: dict):
    red = ctx["trace_reduced"]
    return None if not red else 100.0 * red["idle_share"]


def hbm_peak_gb(ctx: dict):
    return ctx["memory_peak_bytes"] / 1e9 or None
