"""Reader of the per-layer metric ``kda_busy_share.tokens`` (see PERF.md,
Layers): the device time of the kernel ``kda_decode`` (the decode lane's
KDA recurrence) over the device's busy time in the traced part, in %: how
much of the serving step the recurrent state's update is. ``None`` where
the trace holds no such kernel (a program without it)."""

KERNEL = "kda_decode"


def read(ctx: dict):
    red = ctx["trace_reduced"]
    if not red or red.get("busy_s", 0) <= 0:
        return None
    seconds = sum(s for name, s in red.get("custom_calls", {}).items()
                  if name.split(" ")[0] == KERNEL)
    if seconds <= 0:
        return None
    return 100.0 * seconds / (red["busy_s"] * red.get("n_devices", 1))
