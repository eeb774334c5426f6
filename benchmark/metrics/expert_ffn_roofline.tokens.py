"""Reader of the per-layer metric ``expert_ffn_roofline.tokens`` (see
PERF.md, Layers): the least time the chip could take for the held experts'
grouped SwiGLU products of the traced part over the device time of the
kernel ``expert_ffn`` there, in %. Per working ``serve.iteration`` span
that lies wholly inside the traced part, the larger of the hit experts'
weights read once (``experts_hit`` x 3 x ``hidden_size`` x
``moe_intermediate_size`` x 2 bytes, bf16) at the memory peak and the
pairs' products (``expert_rows`` x 6 x ``hidden_size`` x
``moe_intermediate_size`` FLOPs) at the compute peak; both counters are
summed over the step's expert layers and passes. ``None`` where the trace
holds no such kernel or the program's spans carry no such counters."""

from benchmark import spanreaders, workmodel

KERNEL = "expert_ffn"


def read(ctx: dict):
    red, traced = ctx["trace_reduced"], ctx["window"].get("traced")
    if not red or not traced:
        return None
    seconds = sum(s for name, s in red.get("custom_calls", {}).items()
                  if name.split(" ")[0] == KERNEL)
    its = [s.attrs for s in spanreaders.working_iterations(ctx)
           if "experts_hit" in s.attrs
           and traced[0] <= s.t0 and s.t1 <= traced[1]]
    if seconds <= 0 or not its:
        return None
    cfg = ctx["config"]
    matrix = int(cfg["hidden_size"]) * int(cfg["moe_intermediate_size"])
    least = sum(workmodel.least_seconds(6.0 * a["expert_rows"] * matrix,
                                        6.0 * a["experts_hit"] * matrix,
                                        ctx["peaks"])[0] for a in its)
    return 100.0 * least / seconds
