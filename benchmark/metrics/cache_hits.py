"""Reader of the per-layer metric ``cache_hits`` (see PERF.md, Layers)."""

def read(ctx):
    hits = ctx['setup'].get('cache_hits')
    return float(hits) if hits else None
