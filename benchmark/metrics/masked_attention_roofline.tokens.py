"""Reader of the per-layer metric ``masked_attention_roofline.tokens`` (see
PERF.md, Layers): the least time the chip could take for the attention of the
prefill chunks of the traced part (the family's ``chunk_attention_call``:
each query over the keys it selects, per-head form, at the compute peak or
the memory peak, whichever is longer) over the device time of the kernel
``masked_attention`` there, in %. The chunks are the program's
``serve.prefill_chunk`` spans that lie wholly inside the traced part. ``None``
where the trace holds no such kernel or the program opens no such span."""

from benchmark import spanreaders, workmodel

KERNEL = "masked_attention"


def read(ctx: dict):
    red, traced = ctx["trace_reduced"], ctx["window"].get("traced")
    if not red or not traced:
        return None
    seconds = sum(s for name, s in red.get("custom_calls", {}).items()
                  if name.split(" ")[0] == KERNEL)
    count = getattr(workmodel.family(ctx["config"]), "chunk_attention_call",
                    None)
    chunks = [s.attrs for s in spanreaders.spans(ctx, "serve.prefill_chunk")
              if traced[0] <= s.t0 and s.t1 <= traced[1]]
    if seconds <= 0 or count is None or not chunks:
        return None
    least = 0.0
    for a in chunks:
        call = count(ctx["config"], int(a["start"]), int(a["tokens"]))
        least += workmodel.least_seconds(call["flops"], call["bytes"],
                                         ctx["peaks"])[0]
    return 100.0 * least / seconds
