"""Drive ``LMTrainer``'s own step loop (``train_epoch``, the loop
``gpt/jax_tpu/train.py`` runs) under a traffic file.

Set-up builds one trainer from the CLI's own flags, puts the seed's weights
in its state, and drives it through its first steps one ``train_epoch`` at a
time (that compiles the step and gives the comparison its readings); the
window then hands the same trainer, at step 4, batches until its time is
up. Nothing is fetched inside the window but what the trainer itself
fetches.
"""

from __future__ import annotations

import importlib.util
import os
import sys
import time

import numpy as np

from benchmark import tracing, trafficgen, weights, workmodel
from benchmark.tracereduce import WINDOW_SPAN

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


class Feed:
    """The trainer's ``TokenLoader`` behind a gate: so many batches, or
    batches until a deadline; epochs roll over inside it. Keeps host copies
    of the first batches it hands out, for the reference to follow."""

    def __init__(self, loader, keep: int):
        self.loader = loader
        self.keep = keep
        self.kept: list[np.ndarray] = []
        self.limit: int | None = None
        self.deadline: float | None = None
        self._epoch = 0

    def set_epoch(self, epoch: int) -> None:   # the trainer's call; epochs
        pass                                   # are counted here instead

    def __len__(self) -> int:
        return self.limit if self.limit is not None else 1_000_000

    def __iter__(self):
        given = 0
        while True:
            self.loader.set_epoch(self._epoch)
            self._epoch += 1
            it = iter(self.loader)
            while True:
                if self.limit is not None and given >= self.limit:
                    return
                if (self.deadline is not None
                        and time.perf_counter() >= self.deadline):
                    return
                with tracing.span("loader.next"):
                    batch = next(it, None)
                if batch is None:
                    break
                if len(self.kept) < self.keep:
                    self.kept.append(np.array(batch["tokens"]))
                given += 1
                yield batch


def _cli_config(cfg: dict, spec: dict, seed: int, work_dir: str):
    """The ``TrainConfig`` that ``gpt/jax_tpu/train.py`` builds from the
    traffic file's flags and the model flags the configuration's family
    gives."""
    path = os.path.join(ROOT, "gpt", "jax_tpu", "train.py")
    mspec = importlib.util.spec_from_file_location("bench_gpt_train_cli",
                                                   path)
    cli = importlib.util.module_from_spec(mspec)
    mspec.loader.exec_module(cli)
    argv = ["train.py", *workmodel.family(cfg).train_flags(cfg),
            "--seed", str(int(seed) & 0x7FFFFFFF),
            "-c", os.path.join(work_dir, "ckpt"),
            *[str(f) for f in spec["flags"]]]
    old = sys.argv
    sys.argv = argv
    try:
        args = cli.add_argument()
    finally:
        sys.argv = old
    return cli.build_config(args)


def _by_path(tree, flat: dict):
    """``tree`` with every leaf replaced by ``flat["a/b/c"]``."""
    import jax

    def pick(path, _leaf):
        return flat["/".join(str(getattr(k, "key", k)) for k in path)]

    return jax.tree_util.tree_map_with_path(pick, tree)


def _adam_mu(opt_state):
    """Adam's first moment inside the optimizer's state, as a param tree."""
    import jax

    found = [x for x in jax.tree.leaves(
        opt_state, is_leaf=lambda x: hasattr(x, "mu") and hasattr(x, "nu"))
        if hasattr(x, "mu")]
    if len(found) != 1:
        raise SystemExit(f"expected one Adam state, found {len(found)}")
    return found[0].mu


def _norms(ref, flat: dict, minus: dict | None = None) -> dict:
    """Norm of every leaf of ``flat`` (less ``minus``), the leaves cut as
    the reference cuts them, computed on the device in one call."""
    import jax
    import jax.numpy as jnp

    def fn(a, b):
        d = a if b is None else {k: a[k] - b[k] for k in a}
        return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
                for k, v in ref.fine_leaves(d).items()}

    return {k: float(v)
            for k, v in jax.device_get(jax.jit(fn)(flat, minus)).items()}


def setup(ctx: dict) -> dict:
    import jax.numpy as jnp

    from distributed_training_tpu.data.lm_text import TokenLoader
    from distributed_training_tpu.train.lm_trainer import LMTrainer

    cfg, spec, seed = ctx["config"], ctx["traffic"], ctx["seed"]
    ref = workmodel.reference(cfg)
    mark = ctx.get("mark", lambda name: None)
    trainer = LMTrainer(_cli_config(cfg, spec, seed, ctx["work_dir"]))
    mark("trainer_built")
    shapes = ref.param_shapes(cfg)
    theirs = {k: tuple(v.shape)
              for k, v in weights.flatten(trainer.state.params).items()}
    if theirs != {k: tuple(v) for k, v in shapes.items()}:
        raise SystemExit("the program's parameter tree is not the "
                         "reference's layout: "
                         f"{sorted(set(theirs) ^ set(shapes))[:8]}")
    shardings = dict(weights.flatten(trainer.shardings.params))
    made = weights.make(seed, shapes, jnp.float32, out_shardings=shardings)
    trainer.state = trainer.state.replace(
        params=_by_path(trainer.state.params, made))
    del made
    mark("weights_made")

    n_check = int(spec["check"]["steps"])
    rows = trafficgen.token_rows(spec, seed,
                                 workmodel.family(cfg).token_ids(cfg))
    feed = Feed(TokenLoader(rows, global_batch_size=trainer.train_gbs,
                            shuffle=True, seed=int(seed) & 0x7FFFFFFF),
                keep=n_check)

    def change_norms():
        p0 = weights.make(seed, shapes, jnp.float32, out_shardings=shardings)
        return _norms(ref, dict(weights.flatten(trainer.state.params)), p0)

    losses, first_grad = [], None
    for k in range(n_check):
        feed.limit = 1
        last = trainer.train_epoch(k, feed)
        losses.append(float(last["loss"]))
        if k == 0:
            mu = _norms(ref, dict(weights.flatten(
                _adam_mu(trainer.state.opt_state))))
            first_grad = {p: v / (1.0 - ref.ADAM["b1"])
                          for p, v in mu.items()}
    changes = change_norms()
    return {"trainer": trainer, "feed": feed, "losses": losses,
            "first_grad": first_grad, "changes": changes,
            "steps_before": trainer._global_step}


def _run_until(trainer, feed, deadline: float, epoch: int) -> None:
    import jax

    feed.limit, feed.deadline = None, deadline
    trainer.train_epoch(epoch, feed)
    jax.block_until_ready(trainer.state.step)


def measure(ctx: dict, s: dict) -> dict:
    import jax

    trainer, feed, spec = s["trainer"], s["feed"], ctx["traffic"]
    seconds = ctx["seconds"]
    tokens_per_step = trainer.train_gbs * int(spec["seq_len"])
    jax.block_until_ready(trainer.state.step)
    open_t = time.perf_counter()
    step0 = trainer._global_step
    traced, traced_steps, untraced_rate = None, 0, None
    if ctx["trace"]:
        _run_until(trainer, feed, open_t + seconds - ctx["trace_seconds"],
                   1000)
        # the rate that mfu.train reads: the part before the profiler
        # starts, which stalls the host
        untraced_rate = ((trainer._global_step - step0) * tokens_per_step
                         / (time.perf_counter() - open_t))
        flush, step_fn = trainer.meter.flush, trainer.train_step

        def traced_flush():
            with tracing.span("loss.fetch"):
                return flush()

        def traced_step(*a, **k):
            with tracing.span("train_step.dispatch"):
                return step_fn(*a, **k)

        trainer.meter.flush, trainer.train_step = traced_flush, traced_step
        tracing.start(ctx["trace_dir"])
        before = trainer._global_step
        with tracing.span(WINDOW_SPAN):
            traced = [time.perf_counter(), None]
            _run_until(trainer, feed, open_t + seconds, 1001)
            traced[1] = time.perf_counter()
        tracing.stop()
        traced_steps = trainer._global_step - before
    else:
        _run_until(trainer, feed, open_t + seconds, 1000)
    close_t = time.perf_counter()
    steps = trainer._global_step - step0
    rate = steps * tokens_per_step / (close_t - open_t)
    deltas = ([dt for n, dt in trainer.obs.recorder.step_deltas_ms()
               if n > step0 + 1] if trainer.obs.recorder is not None else [])
    lm = trainer.cfg.lm
    return {
        "end_to_end": {"train_tokens_per_s": rate},
        "attempted": steps, "failed": 0,
        "tokens_per_s": untraced_rate or rate,
        "seq_len": int(spec["seq_len"]),
        "step_ms": deltas, "traced": traced, "traced_steps": traced_steps,
        "flash_calls": lm.num_layers if lm.attn_impl == "flash" else 0,
        "flash_call_shape": {"batch": trainer.train_gbs,
                             "heads": lm.num_heads, "seq": lm.seq_len,
                             "head_dim": lm.hidden_dim // lm.num_heads},
        "notes": {"steps": steps, "window_s": close_t - open_t},
    }


def release(ctx: dict, s: dict) -> dict:
    """Keep the readings and the batches fed; let go of the trainer."""
    held = {"losses": s["losses"], "first_grad": s["first_grad"],
            "changes": s["changes"], "batches": list(s["feed"].kept)}
    s["trainer"].state = None
    s.clear()
    return held


def compare(prog: dict, ref_out: dict) -> list:
    """The numbers compared, program against reference: the second step's
    loss; the first gradient's norm and the parameters' change, by the worst
    leaf, as the gap of norms over the reference's norm of that leaf or of
    the median leaf, whichever is larger. Leaves whose reference gradient
    is under a thousandth of the median leaf's move by round-off alone
    under Adam and are left out of the change."""
    out = []
    loss_gaps = [abs(a - b) / abs(b)
                 for a, b in zip(prog["losses"], ref_out["losses"])]
    # Of the steps' losses only the second has an upper reading (half of the
    # batch left out reads ten times the sound runs' worst); the first and
    # the third overlap with the control and every fault, so they could only
    # fail sound runs and are recorded, not compared (PERF.md, section 6).
    out.append(("loss2_gap", loss_gaps[1]))
    g_ref = ref_out["grad_norms"]
    g_med = float(np.median(list(g_ref.values())))
    g_gap = {p: abs(prog["first_grad"][p] - g) / max(g, g_med)
             for p, g in g_ref.items()}
    out.append(("grad_norm_gap", max(g_gap.values())))
    c_ref = ref_out["change_norms"]
    moved = [p for p in c_ref if g_ref[p] >= 1e-3 * g_med]
    c_med = float(np.median([c_ref[p] for p in moved]))
    c_gap = {p: abs(prog["changes"][p] - c_ref[p]) / max(c_ref[p], c_med)
             for p in moved}
    out.append(("change_norm_gap", max(c_gap.values())))
    prog["worst_leaves"] = {
        "loss_gaps_recorded": loss_gaps,
        "grad": sorted(g_gap.items(), key=lambda kv: -kv[1])[:4],
        "change": [(p, v, prog["changes"][p], c_ref[p], g_ref[p] / g_med)
                   for p, v in sorted(c_gap.items(),
                                      key=lambda kv: -kv[1])[:6]],
        "left_out": sorted(set(c_ref) - set(moved))[:40],
        "median_gap": {"grad": float(np.median(list(g_gap.values()))),
                       "change": float(np.median(list(c_gap.values())))}}
    return out


def reference_run(cfg: dict, seed: int, batches: list, **plant) -> dict:
    """The plain reference over the batches that were fed, from the seed's
    weights in float32. ``plant`` passes the control's ``lowp`` or a fault
    through to ``train_steps``."""
    import jax.numpy as jnp

    ref = workmodel.reference(cfg)
    params = weights.make(seed, ref.param_shapes(cfg), jnp.float32)
    fed = [{"tokens": jnp.asarray(b[:, :-1]), "targets": jnp.asarray(b[:, 1:])}
           for b in batches]
    return ref.train_steps(params, fed, cfg, **plant)


def check(ctx: dict, held: dict) -> list:
    t0 = time.perf_counter()
    ref_out = reference_run(ctx["config"], ctx["seed"], held["batches"])
    held["check_s"] = time.perf_counter() - t0
    held["reference"] = ref_out
    return compare(held, ref_out)


def control(ctx: dict, held: dict) -> dict:
    """The control and the planted faults, each as the reference put in the
    program's place on the batches a run was fed: matrix products with
    operands rounded to float8 (e4m3), the nearest precision below the
    bfloat16 the configuration computes in; half of the batch left out, the
    mean taken over the rest; a step that returns its state unchanged. Not
    run by the benchmark's own runs."""
    import jax.numpy as jnp

    cfg, seed = ctx["config"], ctx["seed"]
    ref = workmodel.reference(cfg)
    truth = held.get("reference") or reference_run(cfg, seed,
                                                   held["batches"])
    rows = held["batches"][0].shape[0]
    plants = {
        "float8": {"lowp": ref.round_to(jnp.float8_e4m3fn)},
        "half_batch": {"rows": rows // 2},
        "state_unchanged": {"skip_update": True},
    }
    out = {}
    for name, plant in plants.items():
        got = reference_run(cfg, seed, held["batches"], **plant)
        out[name] = dict(compare(
            {"losses": got["losses"], "first_grad": got["grad_norms"],
             "changes": got["change_norms"]}, truth))
    return out


def seed_batches(ctx: dict) -> dict:
    """The first batches the seed's rows give, without the program: enough
    for the control, which compares the reference with itself."""
    spec, cfg = ctx["traffic"], ctx["config"]
    rows = trafficgen.token_rows(spec, ctx["seed"],
                                 workmodel.family(cfg).token_ids(cfg))
    n = int(spec["global_batch"])
    return {"batches": [rows[k * n:(k + 1) * n]
                        for k in range(int(spec["check"]["steps"]))]}
