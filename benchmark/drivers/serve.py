"""Drive ``serving.Engine`` (submit -> step) under a traffic file.

Closed loop: before every iteration the queue is topped up to
``backlog_slots_factor x slots`` waiting requests, so no slot waits for
traffic. Open loop: each request is submitted when it is due on a schedule
made from the seed, whatever the engine is doing, and timed from when it
was due. A pre-roll of the same traffic runs before the window opens, with
the first wave's output lengths cut to evenly spaced fractions so that
slots are of mixed age; its requests are not counted.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import servestats, tracing, trafficgen, weights, workmodel
from benchmark.tracereduce import WINDOW_SPAN


class Session:
    """The engine with the bookkeeping of one run."""

    def __init__(self, engine, stream, spec):
        self.engine = engine
        self.stream = stream
        self.spec = spec
        self.slots = int(spec["engine"]["max_batch"])
        self.closed = spec["loop"] == "closed"
        self.iterations: list[dict] = []
        self.requests: dict[int, dict] = {}     # uid -> record
        self.finished: dict[int, object] = {}   # uid -> FinishedRequest
        self.finished_iteration: dict[int, int] = {}
        self._landed: dict[int, int] = {}
        self.generated: dict[int, int] = {}
        self.sched_t0: float | None = None
        self.lateness_s: list[float] = []
        engine.set_token_listener(self._on_tokens)

    def _on_tokens(self, uid, new_tokens, fin) -> None:
        if new_tokens:
            self._landed[uid] = self._landed.get(uid, 0) + len(new_tokens)
        if fin is not None:
            self.finished[uid] = fin
            self.finished_iteration[uid] = len(self.iterations)

    def submit(self, req, *, cut: float | None = None,
               due_t: float | None = None) -> None:
        n_new = req.max_new_tokens
        if cut is not None:
            n_new = max(1, int(round(n_new * cut)))
        now = time.perf_counter()
        r = self.engine.submit(req.prompt, max_new_tokens=n_new,
                               arrival_t=due_t)
        self.requests[r.uid] = {
            "prompt": req.prompt, "max_new_tokens": n_new,
            "due_t": now if due_t is None else due_t, "submit_t": now,
            "preroll": cut is not None}
        if due_t is not None:
            self.lateness_s.append(now - due_t)

    def feed(self, now: float) -> None:
        """What the traffic owes the engine before its next iteration."""
        if self.closed:
            want = int(self.spec["backlog_slots_factor"]) * self.slots
            while len(self.engine.queue) < want:
                self.submit(self.stream.pop())
            return
        while self.sched_t0 + self.stream.peek().due_s <= now:
            req = self.stream.pop()
            self.submit(req, due_t=self.sched_t0 + req.due_s)

    def step(self) -> dict:
        """One engine iteration between two readings of the host clock."""
        self.feed(time.perf_counter())
        if (not self.closed and self.engine.idle):
            # Nothing to do until the next arrival: wait for it (bounded,
            # so that a window's close is never slept through).
            wait = self.sched_t0 + self.stream.peek().due_s \
                - time.perf_counter()
            if wait > 0:
                time.sleep(min(wait, 0.05))
            self.feed(time.perf_counter())
        self._landed = {}
        live = self.engine.scheduler.num_active
        with tracing.span("engine.step"):
            t0 = time.perf_counter()
            self.engine.step()
            t1 = time.perf_counter()
        it = {"t0": t0, "t1": t1, "landed": self._landed, "live": live,
              "queued": len(self.engine.queue), "context_rows": 0,
              "contexts": [], "prompt_flops_tokens": []}
        for uid, n in self._landed.items():
            before = self.generated.get(uid, 0)
            p_len = int(self.requests[uid]["prompt"].size)
            if before == 0:          # first token: the prefill finished
                it["prompt_flops_tokens"].append(p_len)
                n_decoded = n - 1
            else:
                n_decoded = n
            if n_decoded:     # a decoding slot: the rows its step attends
                live = p_len + max(before, 1)
                it["contexts"].append(live)
                it["context_rows"] += live
            self.generated[uid] = before + n
        self.iterations.append(it)
        return it


def build_engine(cfg: dict, spec: dict, seed: int,
                 mark=lambda name: None):
    """The program's model and engine around weights made from the seed."""
    import jax
    import jax.numpy as jnp

    from distributed_training_tpu.config import ServeConfig
    from distributed_training_tpu.serving.engine import Engine

    shapes = workmodel.reference(cfg).param_shapes(cfg)
    m = spec["model"]
    model = workmodel.family(cfg).build_model(cfg, m)
    theirs = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    theirs = {k: tuple(v.shape)
              for k, v in weights.flatten(theirs["params"]).items()}
    if theirs != {k: tuple(v) for k, v in shapes.items()}:
        raise SystemExit("the program's parameter tree is not the "
                         "reference's layout: "
                         f"{sorted(set(theirs) ^ set(shapes))[:8]}")
    params = weights.unflatten(jax.block_until_ready(
        weights.make(seed, shapes, jnp.dtype(m["params_dtype"]))))
    mark("weights_made")
    engine = Engine(model, params,
                    ServeConfig(seed=int(seed) & 0x7FFFFFFF,
                                **spec["engine"]))
    return engine


def setup(ctx: dict) -> Session:
    mark = ctx.get("mark", lambda name: None)
    engine = build_engine(ctx["config"], ctx["traffic"], ctx["seed"], mark)
    mark("engine_built")
    return prepare(engine, ctx["config"], ctx["traffic"], ctx["seed"], mark)


def prepare(engine, cfg: dict, spec: dict, seed: int,
            mark=lambda name: None) -> Session:
    """Warm the engine's programs and run the traffic's pre-roll."""
    stream = trafficgen.RequestStream(spec, seed,
                                      workmodel.family(cfg).token_ids(cfg))
    s = Session(engine, stream, spec)

    # Warm both compiled programs (fused chunk+decode, decode-only) at
    # this cell's shapes: one short request, stepped until the engine idles.
    warm = trafficgen.Request(
        index=-1, prompt=np.arange(1, 9, dtype=np.int32),
        max_new_tokens=3, due_s=None)
    s.submit(warm, cut=1.0)
    while not engine.idle:
        engine.step()
    mark("programs_warm")

    # Pre-roll: the cell's own traffic until slots are of mixed age.
    pre = spec["preroll"]
    first_wave = s.slots if s.closed else int(pre.get("fill", 0))
    for i in range(first_wave):
        cut = ((i + 1) / first_wave if pre.get("stagger_first_wave")
               else None)
        s.submit(stream.pop(), cut=cut)
    if not s.closed:
        # The open loop's first wave stands for the requests a steady state
        # would already hold; the schedule proper starts now, one mean gap
        # before its next arrival.
        s.sched_t0 = time.perf_counter() - stream.peek().due_s \
            + float(1.0 / spec["arrival_rate_per_s"])
    for _ in range(int(pre["iterations"])):
        s.step()
    s.preroll_iterations = len(s.iterations)
    return s


def measure(ctx: dict, s: Session) -> dict:
    seconds = ctx["seconds"]
    open_t = s.iterations[-1]["t1"] if s.iterations else time.perf_counter()
    trace_from = open_t + seconds - ctx["trace_seconds"]
    traced = None
    window_span = None
    while True:
        now = time.perf_counter()
        if ctx["trace"] and traced is None and now >= trace_from:
            tracing.start(ctx["trace_dir"])
            window_span = tracing.span(WINDOW_SPAN)
            window_span.__enter__()
            traced = [time.perf_counter(), None]
        it = s.step()
        if it["t1"] >= open_t + seconds:
            break
    close_t = s.iterations[-1]["t1"]
    if traced is not None:
        window_span.__exit__(None, None, None)
        traced[1] = time.perf_counter()
        tracing.stop()

    its = servestats.inside(s.iterations, open_t, close_t)
    times = servestats.token_times(s.iterations, open_t, close_t)
    first_token: dict[int, float] = {}
    for it in s.iterations:
        for uid in it["landed"]:
            first_token.setdefault(uid, it["t1"])
    due = {uid: r["due_t"] for uid, r in s.requests.items()
           if not r["preroll"]}
    # Attempted: requests due inside the window (open loop), or that the
    # window began to answer (closed loop: the standing backlog is not).
    in_window = [u for u, d in due.items()
                 if (open_t <= first_token.get(u, -1.0) <= close_t
                     if s.closed else open_t <= d <= close_t)]
    bad = [u for u in in_window if u in s.finished
           and s.finished[u].finish_reason not in ("length", "eos")]
    tpots = servestats.tpot_ms(times)
    end_to_end = {
        "serve_tokens_per_s":
            servestats.output_token_rate(s.iterations, open_t, close_t),
        "tpot_p90_ms": servestats.percentile(tpots, 90),
    }
    late = sorted(s.lateness_s) or [0.0]
    return {
        "end_to_end": end_to_end,
        "attempted": len(in_window), "failed": len(bad),
        "open_t": open_t, "close_t": close_t, "traced": traced,
        "iterations": its, "token_times": times,
        "ttft_ms": servestats.ttft_ms(due, first_token, open_t, close_t),
        "slots": s.slots,
        "notes": {
            "iterations": len(its), "requests_with_tpot": len(tpots),
            "iteration_ms": {
                q: servestats.percentile(
                    [(it["t1"] - it["t0"]) * 1e3 for it in its], p)
                for q, p in (("p10", 10), ("p50", 50), ("p90", 90),
                             ("p99", 99), ("max", 100))},
            "between_iterations_ms_max": max(
                [(b["t0"] - a["t1"]) * 1e3 for a, b in zip(its, its[1:])]
                or [0.0]),
            "fused_iterations": sum(
                1 for it in its if it["prompt_flops_tokens"]),
            "window_s": close_t - open_t,
            "generator_late_ms_p50": late[len(late) // 2] * 1e3,
            "generator_late_ms_max": late[-1] * 1e3,
            "preroll_iterations": s.preroll_iterations},
    }


def release(ctx: dict, s: Session) -> dict:
    """Keep on the host what the comparison needs (a sample, drawn from the
    seed, of the requests the window finished, the longest among them) and
    let go of the engine."""
    spec = ctx["traffic"]
    # Finished by an iteration of the window (the pre-roll's own iterations
    # come first in the list and are not the window's).
    done = [u for u, i in s.finished_iteration.items()
            if i >= s.preroll_iterations and u in s.requests]
    wrong_length = sum(
        1 for u in done
        if len(s.finished[u].tokens) != s.requests[u]["max_new_tokens"])
    size = {u: s.requests[u]["prompt"].size + len(s.finished[u].tokens)
            for u in done}
    want = int(spec["check"]["requests"])
    rng = trafficgen.rng_for(ctx["seed"], 99)
    picked = sorted(done, key=lambda u: -size[u])[:1]
    rest = [u for u in done if u not in picked]
    if rest:
        picked += [rest[i] for i in rng.permutation(len(rest))[:want - 1]]
    sample = [(np.asarray(s.requests[u]["prompt"], np.int32),
               np.asarray(s.finished[u].tokens, np.int32)) for u in picked]
    s.engine.set_token_listener(None)
    s.engine = None
    return {"sample": sample, "wrong_length": wrong_length,
            "finished": len(done)}


# Positions whose logits the comparison holds at once: the most it keeps on
# the device beside the weights is one request's states and ``CHECK_BLOCK x
# rows`` logits (twice that under the control), whatever the number of
# requests, their length or the vocabulary.
CHECK_BLOCK = 512


def padded_length(n: int, longest: int) -> int:
    """One of a few fixed lengths (64, 128, 256, ... and the traffic's
    longest sequence), so that the reference compiles a few times and not
    once a request."""
    length = 64
    while length < n:
        length *= 2
    return min(length, longest)


def reference_gaps(cfg: dict, seed: int, spec: dict, sample: list,
                   lowp=None, block: int = CHECK_BLOCK):
    """For each served token of each sampled request: how far its logit
    lies below the reference's best at that position. The reference runs
    once over each request's prompt + served tokens, one request at a time
    at its own (padded) length, in float32 at ``highest``, on the weights
    the seed gives (rounded to the type they are served in); its output
    head is applied to ``block`` positions at a time and each block's
    logits are reduced to the two numbers a position needs.

    With ``lowp`` (the control) it returns instead the gap of the token
    that the lower-precision reference puts first at those positions."""
    import jax
    import jax.numpy as jnp

    ref = workmodel.reference(cfg)
    longest = int(spec["prompt_tokens"]["max"]) \
        + int(spec["output_tokens"]["max"])
    params = weights.make(seed, ref.param_shapes(cfg),
                          jnp.dtype(spec["model"]["params_dtype"]))

    @jax.jit
    def gaps(params, toks):               # toks [1, length]
        length = toks.shape[1]
        size = min(block, length)

        def in_blocks(a):
            a = jnp.pad(a, [(0, -length % size)] + [(0, 0)] * (a.ndim - 1))
            return a.reshape(-1, size, *a.shape[1:])

        x = ref.hidden(params, toks, cfg)[0]
        if lowp is None:
            other = jnp.roll(toks[0], -1)         # the served tokens
        else:
            other = ref.hidden(params, toks, cfg, lowp)[0]

        def one(args):
            xb, ob = args
            logits = ref.head(params, xb, cfg)
            chosen = ob if lowp is None else \
                ref.head(params, ob, cfg, lowp).argmax(-1)
            at = jnp.take_along_axis(logits, chosen[:, None], -1)[:, 0]
            return logits.max(-1) - at

        g = jax.lax.map(one, (in_blocks(x), in_blocks(other)))
        return g.reshape(-1)[:length]

    out = []
    for prompt, served in sample:
        seq = np.concatenate([prompt, served])
        toks = np.zeros((1, padded_length(seq.size, longest)), np.int32)
        toks[0, :seq.size] = seq
        g = np.asarray(gaps(params, jnp.asarray(toks)))
        out.append(g[prompt.size - 1:seq.size - 1])   # predict a served
    return np.concatenate(out) if out else np.zeros((0,), np.float32)


def check(ctx: dict, held: dict) -> list:
    t0 = time.perf_counter()
    g = reference_gaps(ctx["config"], ctx["seed"], ctx["traffic"],
                       held["sample"])
    held["check_s"] = time.perf_counter() - t0
    held["compared_tokens"] = int(g.size)
    return [("gap_max", float(g.max()) if g.size else float("inf")),
            ("gap_mean", float(g.mean()) if g.size else float("inf")),
            ("wrong_length", float(held["wrong_length"]))]


def control(ctx: dict, held: dict) -> dict:
    """The control's readings on the sample a run compared: the reference
    in the program's place, computed with every matrix product's operands
    rounded to float8 (e4m3), the nearest precision below the bfloat16 the
    configuration states. Not run by the benchmark's own runs."""
    import jax.numpy as jnp

    ref = workmodel.reference(ctx["config"])
    g = reference_gaps(ctx["config"], ctx["seed"], ctx["traffic"],
                       held["sample"],
                       lowp=ref.round_to(jnp.float8_e4m3fn))
    return {"gap_max": float(g.max()), "gap_mean": float(g.mean())}
