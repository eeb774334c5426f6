"""Reader of the per-layer metric ``compile_s`` (see PERF.md, Layers)."""

def read(ctx):
    return ctx['setup'].get('compile_s') or None
