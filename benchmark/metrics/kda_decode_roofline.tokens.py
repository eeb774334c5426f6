"""Reader of the per-layer metric ``kda_decode_roofline.tokens`` (see
PERF.md, Layers): the least time the chip could take for the decode lane's
KDA recurrence of the traced part (the family's ``kda_decode_call`` over
each delivered step's ``state_slots``: every decoding slot's float32 state
read and written once in every KDA layer, with its q, k, v, g, beta and o,
at the memory peak or the compute peak, whichever is longer) over the
device time of the kernel ``kda_decode`` there, in %. The steps are the
working ``serve.iteration`` spans that lie wholly inside the traced part.
``None`` where the trace holds no such kernel, the program's spans carry
no such counter, or the family counts no such call."""

from benchmark import spanreaders, workmodel

KERNEL = "kda_decode"


def read(ctx: dict):
    red, traced = ctx["trace_reduced"], ctx["window"].get("traced")
    if not red or not traced:
        return None
    seconds = sum(s for name, s in red.get("custom_calls", {}).items()
                  if name.split(" ")[0] == KERNEL)
    count = getattr(workmodel.family(ctx["config"]), "kda_decode_call", None)
    steps = [s.attrs["state_slots"]
             for s in spanreaders.working_iterations(ctx)
             if "state_slots" in s.attrs
             and traced[0] <= s.t0 and s.t1 <= traced[1]]
    if seconds <= 0 or count is None or not steps:
        return None
    least = 0.0
    for n in steps:
        call = count(ctx["config"], n)
        least += workmodel.least_seconds(call["flops"], call["bytes"],
                                         ctx["peaks"])[0]
    return 100.0 * least / seconds
