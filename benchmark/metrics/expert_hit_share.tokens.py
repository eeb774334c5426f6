"""Reader of the per-layer metric ``expert_hit_share.tokens`` (see PERF.md,
Layers): of the held experts of the expert layers, the share that got at
least one token-expert pair in a pass of the model — sum of ``experts_hit``
over the window's working ``serve.iteration`` spans over passes x experts
held x expert layers, in %. A decode step is one pass; a fused step is two
(the chunk's rows and the decoding slots' go through the layers apart, and
each reads the experts it hits). A pass reads the weights of the experts it
hits and of no other, so at a few rows an expert this is what the expert
layers' time follows. ``None`` where the program's spans carry no such
counter."""

from benchmark import spanreaders


def read(ctx: dict):
    its = [s.attrs for s in spanreaders.working_iterations(ctx)
           if "experts_hit" in s.attrs]
    if not its:
        return None
    cfg = ctx["config"]
    held = int(cfg["num_experts"])
    expert_layers = int(cfg["num_hidden_layers"]) \
        - int(cfg["first_k_dense_replace"])
    passes = sum(2 if a.get("program") == "fused" else 1 for a in its)
    return 100.0 * sum(a["experts_hit"] for a in its) / (
        passes * held * expert_layers)
