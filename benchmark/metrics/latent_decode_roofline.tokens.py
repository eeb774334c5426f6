"""Reader of the per-layer metric ``latent_decode_roofline.tokens`` (see
PERF.md, Layers): the least time the chip could take for the decode lane's
attention of the traced part (the family's ``decode_attention_call`` — one
layer's absorbed attention over the live rows of an iteration's decoding
slots: every live latent row read once, or its FLOPs at the compute peak if
longer — summed over the traced iterations and the layers) over the device
time of the kernel ``paged_latent_attention`` there, in %. ``None`` where
the trace holds no such kernel or the family counts no such call."""

from benchmark import readers, workmodel

KERNEL = "paged_latent_attention"


def read(ctx: dict):
    red = ctx["trace_reduced"]
    if not red:
        return None
    seconds = sum(s for name, s in red.get("custom_calls", {}).items()
                  if name.split(" ")[0] == KERNEL)
    count = getattr(workmodel.family(ctx["config"]), "decode_attention_call",
                    None)
    steps = [it["contexts"] for it in readers.traced_iterations(ctx)
             if it["contexts"]]
    if seconds <= 0 or count is None or not steps:
        return None
    layers = int(ctx["config"]["num_hidden_layers"])
    least = 0.0
    for contexts in steps:
        call = count(ctx["config"], contexts)
        least += layers * workmodel.least_seconds(
            call["flops"], call["bytes"], ctx["peaks"])[0]
    return 100.0 * least / seconds
