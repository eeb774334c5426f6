"""Latency-hiding collective matmul: ring-overlapped all-gather/reduce-scatter.

Megatron-style tensor parallelism pays an exposed-communication gap on every
layer: the column-parallel matmul waits on a full ``all_gather`` of its
(sequence-sharded) input, and the row-parallel matmul serializes a full
``reduce_scatter`` after its compute (Shoeybi et al., *Megatron-LM*, 2019).
Decomposing each collective into per-shard ``lax.ppermute`` ring steps and
fusing every hop with the partial matmul it unblocks hides the communication
behind compute (Wang et al., *Overlap Communication with Dependent
Computation via Decomposition*, ASPLOS 2023) — on a TPU torus each hop is a
neighbor ICI transfer that XLA's scheduler runs concurrently with the
current chunk's MXU work.

Two primitives, both usable only inside a ``shard_map`` manual region where
``axis_name`` is bound:

- :func:`allgather_matmul` — ``all_gather(x) @ w`` where ``x`` is sharded on
  its second-to-last dim: N-1 hops ppermute the *next* input shard while the
  matmul of the shard in hand fills its output slice.
- :func:`matmul_reducescatter` — ``reduce_scatter(x @ w)``: the dual; a
  partial-result accumulator rotates the ring while each device adds the
  chunk matmul the arriving accumulator is missing.

Both carry custom VJPs so the backward is also ring-overlapped: the
transpose of an overlapped all-gather is an overlapped reduce-scatter and
vice versa, and the weight gradient re-runs the gather ring fused with the
per-chunk ``xᵀ·dy`` accumulation.

Static-HLO signature (pinned by ``tests/test_collectives.py``): the
monolithic ``all-gather``/``reduce-scatter``/``all-reduce`` ops of the
declarative TP schedule are replaced by ``collective-permute`` chains — one
static ppermute inside each ring's loop body.

The flax wiring (:func:`seq_overlap_interceptor`,
:func:`replicated_overlap_interceptor`) swaps these schedules into the
column/row-parallel dense layers of existing models *without touching model
code*: a ``nn.intercept_methods`` context replaces each projection's matmul
while reading the very same (model-axis-sharded) parameters the megatron
rule table places, so checkpoints, optimizer states, and the ZeRO
recruitment in ``tensor_parallel.py`` are unchanged.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from distributed_training_tpu.runtime.mesh import AXIS_MODEL


def _perm_next(n: int):
    """Ring shift by -1: after one application device i holds its right
    neighbor's block (the block originating at ring position i+1)."""
    return [(j, (j - 1) % n) for j in range(n)]


def _perm_prev(n: int):
    """Ring shift by +1 (accumulator rotation for reduce-scatter)."""
    return [(j, (j + 1) % n) for j in range(n)]


def _flat2(a):
    """Collapse all leading dims: [..., M, K] -> [prod(...)·M, K]."""
    return a.reshape(-1, a.shape[-1])


# ---------------------------------------------------------------------------
# allgather_matmul
# ---------------------------------------------------------------------------


def _allgather_matmul_impl(x, w, axis_name):
    """y[..., src·t:(src+1)·t, :] = x_from_src @ w, ring-overlapped.

    x: [..., t, K] local shard (sharded on dim -2 over ``axis_name``);
    w: [K, N] local (typically a column shard of the global weight).
    Returns [..., n·t, N]. Each of the n-1 hops ppermutes the next input
    shard while the current shard's matmul fills its output slice.
    """
    n = lax.axis_size(axis_name)
    if n == 1:
        return x @ w
    i0 = lax.axis_index(axis_name)
    t = x.shape[-2]
    dtype = jnp.result_type(x.dtype, w.dtype)
    y = jnp.zeros((*x.shape[:-2], n * t, w.shape[-1]), dtype)

    def hop(i, carry):
        y, xb = carry
        # After i next-shifts this device holds the block originating at
        # ring position (i0 + i); its product lands in that output slice.
        src = (i0 + i) % n
        y = lax.dynamic_update_slice_in_dim(
            y, (xb @ w).astype(dtype), src * t, axis=-2)
        xb = lax.ppermute(xb, axis_name, _perm_next(n))
        return y, xb

    y, xb = lax.fori_loop(0, n - 1, hop, (y, x))
    src = (i0 + n - 1) % n  # final block: matmul only, no trailing hop
    return lax.dynamic_update_slice_in_dim(
        y, (xb @ w).astype(dtype), src * t, axis=-2)


def _gather_xt_dy_ring(x, dy, axis_name):
    """dw = all_gather(x)ᵀ @ dy, ring-overlapped.

    x: [..., t, K] local shard; dy: [..., n·t, N] (this device's cotangent
    of the gathered product). Rotates x around the ring, accumulating each
    visiting shard's ``x_srcᵀ · dy[src block]`` — the weight-gradient half
    of the allgather_matmul backward.
    """
    n = lax.axis_size(axis_name)
    i0 = lax.axis_index(axis_name)
    t = x.shape[-2]

    def contrib(xb, src):
        dyb = lax.dynamic_slice_in_dim(dy, src * t, t, axis=-2)
        return _flat2(xb).T @ _flat2(dyb)

    if n == 1:
        return contrib(x, 0)

    def hop(i, carry):
        dw, xb = carry
        dw = dw + contrib(xb, (i0 + i) % n)
        xb = lax.ppermute(xb, axis_name, _perm_next(n))
        return dw, xb

    dw0 = jnp.zeros((x.shape[-1], dy.shape[-1]),
                    jnp.result_type(x.dtype, dy.dtype))
    dw, xb = lax.fori_loop(0, n - 1, hop, (dw0, x))
    return dw + contrib(xb, (i0 + n - 1) % n)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _allgather_matmul(x, w, axis_name):
    return _allgather_matmul_impl(x, w, axis_name)


def _allgather_matmul_fwd(x, w, axis_name):
    return _allgather_matmul_impl(x, w, axis_name), (x, w)


def _allgather_matmul_bwd(axis_name, res, dy):
    x, w = res
    # Transpose of the overlapped all-gather is an overlapped
    # reduce-scatter: dx = Σ_dev (dy_dev @ w_devᵀ)[own block].
    dx = _matmul_reducescatter_impl(dy, w.T, axis_name, -2)
    dw = _gather_xt_dy_ring(x, dy, axis_name)
    return dx.astype(x.dtype), dw.astype(w.dtype)


_allgather_matmul.defvjp(_allgather_matmul_fwd, _allgather_matmul_bwd)


def allgather_matmul(x, w, axis_name: str = AXIS_MODEL):
    """``all_gather(x, dim=-2) @ w`` with the gather decomposed into ring
    ppermute hops overlapped with per-shard partial matmuls.

    ``x`` [..., t, K] is the local shard of a dim--2-sharded activation;
    ``w`` [K, N] stays local (column-parallel weight shard). Returns the
    full-rows product [..., n·t, N]. The custom VJP ring-overlaps the
    backward too (reduce-scatter for dx, a second gather ring for dw).
    Must run inside ``shard_map`` with ``axis_name`` bound; ``n == 1``
    degenerates to a plain matmul.
    """
    if x.ndim < 2 or w.ndim != 2:
        raise ValueError(
            f"allgather_matmul wants x[..., t, K] and w[K, N]; got "
            f"x.ndim={x.ndim}, w.ndim={w.ndim}")
    if x.shape[-1] != w.shape[0]:
        raise ValueError(
            f"contraction mismatch: x[..., {x.shape[-1]}] @ w[{w.shape[0]}, :]")
    return _allgather_matmul(x, w, axis_name)


# ---------------------------------------------------------------------------
# matmul_reducescatter
# ---------------------------------------------------------------------------


def _rs_chunk(x, w, c, t, nc, scatter_dim):
    """This device's partial product for scatter chunk ``c``."""
    if scatter_dim == -2:
        return lax.dynamic_slice_in_dim(x, c * t, t, axis=-2) @ w
    return x @ lax.dynamic_slice_in_dim(w, c * nc, nc, axis=-1)


def _matmul_reducescatter_impl(x, w, axis_name, scatter_dim):
    """reduce_scatter(x @ w, scatter_dim), ring-overlapped.

    x: [..., T, K] full rows (every device holds different partial data,
    e.g. its column shard's activations); w: [K, N] local row shard.
    ``scatter_dim == -2`` scatters output rows (T must divide by n);
    ``scatter_dim == -1`` scatters output columns (N must divide by n).
    A partial accumulator rotates the ring (+1 shifts); device j adds its
    contribution for chunk (j - s - 1) mod n at step s, so after n-1 hops
    each device holds the fully-reduced chunk it owns.
    """
    n = lax.axis_size(axis_name)
    if n == 1:
        return x @ w
    if scatter_dim == -2 and x.shape[-2] % n:
        raise ValueError(
            f"matmul_reducescatter: rows dim {x.shape[-2]} must divide by "
            f"the {axis_name!r} axis size {n} (the ring would silently "
            f"drop the remainder rows)")
    if scatter_dim == -1 and w.shape[-1] % n:
        raise ValueError(
            f"matmul_reducescatter: output cols {w.shape[-1]} must divide "
            f"by the {axis_name!r} axis size {n} (the ring would silently "
            f"drop the remainder columns)")
    t = x.shape[-2] // n if scatter_dim == -2 else 0
    nc = w.shape[-1] // n if scatter_dim == -1 else 0
    i0 = lax.axis_index(axis_name)

    def hop(s, acc):
        c = (i0 - s - 1) % n
        acc = acc + _rs_chunk(x, w, c, t, nc, scatter_dim)
        return lax.ppermute(acc, axis_name, _perm_prev(n))

    out_shape = ((*x.shape[:-2], t, w.shape[-1]) if scatter_dim == -2
                 else (*x.shape[:-1], nc))
    acc = jnp.zeros(out_shape, jnp.result_type(x.dtype, w.dtype))
    acc = lax.fori_loop(0, n - 1, hop, acc)
    return acc + _rs_chunk(x, w, i0, t, nc, scatter_dim)  # own chunk last


def _gather_dy_bwd_ring(x, w, dy, axis_name, scatter_dim):
    """Fused backward ring for matmul_reducescatter.

    The transpose of the reduce-scatter is an all-gather of ``dy``; instead
    of materializing it, rotate ``dy`` around the ring and consume each
    visiting chunk twice — once into dx (rows of ``dz @ wᵀ`` for the rows
    mode; a rank-N/n update of ``dx`` for the cols mode) and once into dw.
    """
    n = lax.axis_size(axis_name)
    i0 = lax.axis_index(axis_name)
    dx0 = jnp.zeros(x.shape, jnp.result_type(dy.dtype, w.dtype))
    dw0 = jnp.zeros(w.shape, jnp.result_type(x.dtype, dy.dtype))
    t = x.shape[-2] // n if scatter_dim == -2 else 0
    nc = w.shape[-1] // n if scatter_dim == -1 else 0

    def consume(dx, dw, dyb, src):
        if scatter_dim == -2:
            # dyb is the cotangent of output rows [src·t, (src+1)·t).
            wc = w
            dx = lax.dynamic_update_slice_in_dim(
                dx, (dyb @ wc.T).astype(dx.dtype), src * t, axis=-2)
            xc = lax.dynamic_slice_in_dim(x, src * t, t, axis=-2)
            dw = dw + _flat2(xc).T @ _flat2(dyb)
        else:
            # dyb is the cotangent of output columns [src·nc, (src+1)·nc).
            wc = lax.dynamic_slice_in_dim(w, src * nc, nc, axis=-1)
            dx = dx + (dyb @ wc.T).astype(dx.dtype)
            dw = lax.dynamic_update_slice_in_dim(
                dw, (_flat2(x).T @ _flat2(dyb)).astype(dw.dtype),
                src * nc, axis=-1)
        return dx, dw

    if n == 1:
        return consume(dx0, dw0, dy, 0)

    def hop(i, carry):
        dx, dw, dyb = carry
        dx, dw = consume(dx, dw, dyb, (i0 + i) % n)
        dyb = lax.ppermute(dyb, axis_name, _perm_next(n))
        return dx, dw, dyb

    dx, dw, dyb = lax.fori_loop(0, n - 1, hop, (dx0, dw0, dy))
    return consume(dx, dw, dyb, (i0 + n - 1) % n)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _matmul_reducescatter(x, w, axis_name, scatter_dim):
    return _matmul_reducescatter_impl(x, w, axis_name, scatter_dim)


def _matmul_reducescatter_fwd(x, w, axis_name, scatter_dim):
    return _matmul_reducescatter_impl(x, w, axis_name, scatter_dim), (x, w)


def _matmul_reducescatter_bwd(axis_name, scatter_dim, res, dy):
    x, w = res
    dx, dw = _gather_dy_bwd_ring(x, w, dy, axis_name, scatter_dim)
    return dx.astype(x.dtype), dw.astype(w.dtype)


_matmul_reducescatter.defvjp(_matmul_reducescatter_fwd,
                             _matmul_reducescatter_bwd)


def matmul_reducescatter(x, w, axis_name: str = AXIS_MODEL,
                         scatter_dim: int = -2):
    """``reduce_scatter(x @ w, scatter_dim)`` with the reduction decomposed
    into ring ppermute hops overlapped with the chunk matmuls.

    ``x`` [..., T, K] holds this device's partial data (e.g. row-parallel
    activations whose contraction dim is sharded); ``w`` [K, N] is the
    local row shard. ``scatter_dim=-2`` returns the fully-reduced row chunk
    this device owns ([..., T/n, N]); ``scatter_dim=-1`` the column chunk
    ([..., T, N/n]). The custom VJP ring-overlaps the backward (one fused
    gather ring produces dx and dw together). Must run inside ``shard_map``
    with ``axis_name`` bound; ``n == 1`` degenerates to a plain matmul.
    """
    if x.ndim < 2 or w.ndim != 2:
        raise ValueError(
            f"matmul_reducescatter wants x[..., T, K] and w[K, N]; got "
            f"x.ndim={x.ndim}, w.ndim={w.ndim}")
    if x.shape[-1] != w.shape[0]:
        raise ValueError(
            f"contraction mismatch: x[..., {x.shape[-1]}] @ w[{w.shape[0]}, :]")
    if scatter_dim not in (-2, -1):
        raise ValueError(f"scatter_dim must be -2 (rows) or -1 (cols), "
                         f"got {scatter_dim}")
    return _matmul_reducescatter(x, w, axis_name, scatter_dim)


# ---------------------------------------------------------------------------
# ring all-gather (unfused; closes the replicated-layout schedule)
# ---------------------------------------------------------------------------


def _ring_all_gather_impl(x, axis_name, dim):
    n = lax.axis_size(axis_name)
    if n == 1:
        return x
    i0 = lax.axis_index(axis_name)
    t = x.shape[dim]
    shape = list(x.shape)
    shape[dim] = n * t
    y = jnp.zeros(shape, x.dtype)

    def hop(i, carry):
        y, xb = carry
        src = (i0 + i) % n
        y = lax.dynamic_update_slice_in_dim(y, xb, src * t, axis=dim)
        xb = lax.ppermute(xb, axis_name, _perm_next(n))
        return y, xb

    y, xb = lax.fori_loop(0, n - 1, hop, (y, x))
    return lax.dynamic_update_slice_in_dim(
        y, xb, ((i0 + n - 1) % n) * t, axis=dim)


def _ring_reduce_scatter_impl(x, axis_name, dim):
    """Σ_dev x_dev, scattered over ``dim`` (each device keeps its chunk)."""
    n = lax.axis_size(axis_name)
    if n == 1:
        return x
    if x.shape[dim] % n:
        raise ValueError(
            f"ring reduce-scatter: dim {dim} sized {x.shape[dim]} must "
            f"divide by the {axis_name!r} axis size {n}")
    i0 = lax.axis_index(axis_name)
    t = x.shape[dim] // n

    def chunk(c):
        return lax.dynamic_slice_in_dim(x, c * t, t, axis=dim)

    def hop(s, acc):
        acc = acc + chunk((i0 - s - 1) % n)
        return lax.ppermute(acc, axis_name, _perm_prev(n))

    shape = list(x.shape)
    shape[dim] = t
    acc = lax.fori_loop(0, n - 1, hop, jnp.zeros(shape, x.dtype))
    return acc + chunk(i0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _ring_all_gather(x, axis_name, dim):
    return _ring_all_gather_impl(x, axis_name, dim)


def _ring_all_gather_fwd(x, axis_name, dim):
    return _ring_all_gather_impl(x, axis_name, dim), None


def _ring_all_gather_bwd(axis_name, dim, _, dy):
    return (_ring_reduce_scatter_impl(dy, axis_name, dim),)


_ring_all_gather.defvjp(_ring_all_gather_fwd, _ring_all_gather_bwd)


def ring_all_gather(x, axis_name: str = AXIS_MODEL, dim: int = -1):
    """All-gather over ``dim`` as a ppermute chain (custom VJP: the
    transpose is a ring reduce-scatter). Used after a cols-mode
    :func:`matmul_reducescatter` to re-replicate the output when the
    consumer needs full features (the replicated-activation layout)."""
    return _ring_all_gather(x, axis_name, int(dim))


# ---------------------------------------------------------------------------
# shared step-builder helpers (one copy of the subtle gradient algebra)
# ---------------------------------------------------------------------------


def overlap_param_specs(params):
    """Rule-table PartitionSpecs (overlap variant) for a param tree.

    The in/out specs of the full-manual overlap regions: params enter AS
    SHARDS exactly where ``tp_state_shardings(overlap=True)`` placed them,
    so region entry costs no collective and grads reassemble
    shard-by-shard.
    """
    from distributed_training_tpu.parallel.tensor_parallel import (
        tp_spec_for_path,
    )
    from distributed_training_tpu.utils.tree import path_str

    return jax.tree_util.tree_map_with_path(
        lambda p, _: tp_spec_for_path(path_str(p), overlap=True), params)


def overlap_finalize_grads(grads, axis_name: str = AXIS_MODEL):
    """Per-leaf gradient completion for the ring-overlapped TP schedule.

    Inside the full-manual body every device's autodiff already routed
    cross-rank cotangents through the ring transposes, so a MODEL-SHARDED
    leaf's local gradient is complete for this replica's tokens — summing
    it over the model axis would mix different shards; it only needs the
    1/tp normalization of the global mean. A REPLICATED leaf's local
    gradient covers only this rank's paths, so the model-axis mean
    supplies both the missing contributions and the same 1/tp factor. The
    caller's data(-family) pmean then finishes the average for both
    kinds.
    """
    from distributed_training_tpu.parallel.tensor_parallel import (
        tp_spec_for_path,
    )
    from distributed_training_tpu.utils.tree import path_str

    tp = lax.axis_size(axis_name)

    def has_model(entry):
        return (entry == axis_name
                or (isinstance(entry, tuple) and axis_name in entry))

    def fin(path, g):
        spec = tp_spec_for_path(path_str(path), overlap=True)
        if any(has_model(e) for e in spec):
            return g / tp
        return lax.pmean(g, axis_name)

    return jax.tree_util.tree_map_with_path(fin, grads)


# ---------------------------------------------------------------------------
# flax wiring: schedule-swapping interceptors
# ---------------------------------------------------------------------------


def _raw_params(mod, *names):
    """Fetch raw param values, bypassing flax's init-shape check.

    Inside the manual region each module holds its LOCAL shard (e.g. an
    fc1 kernel [D, F/tp]); ``self.param`` would re-derive the GLOBAL init
    shape from the module config and raise. ``get_variable`` returns the
    stored value untouched.
    """
    return [mod.get_variable("params", n) for n in names]


def _divisible(what: str, n: int, by: int, hint: str):
    if n % by:
        raise ValueError(
            f"tp_overlap: {what} (= {n}) must divide by the model-axis size "
            f"{by} ({hint}); pick divisible dims or disable tp_overlap")
    return n // by


def seq_overlap_interceptor(axis_name: str = AXIS_MODEL):
    """Megatron-SP ring-overlap schedule for the TransformerLM stack.

    Activations are sharded over ``axis_name`` on the TIME dim through the
    whole decoder stack (the layout whose layer boundaries are the
    all-gather/reduce-scatter this module overlaps):

    - ``block0`` entry scatters the (model-axis-replicated) embedding
      output to time shards — a free static slice;
    - ``attn/qkv`` and ``mlp/fc1`` (column-parallel) gather time through
      :func:`allgather_matmul`;
    - ``attn/out`` and ``mlp/fc2`` (row-parallel) return to time shards
      through :func:`matmul_reducescatter`;
    - LayerNorms/residuals/CE are position-wise and stay sharded; the
      (replicated) lm_head consumes the local time shard directly, so the
      logits never re-gather.

    Install with ``nn.intercept_methods`` around ``model.apply`` inside a
    full-manual ``shard_map``; parameters enter pre-sharded by the megatron
    rule table (``tensor_parallel.tp_state_shardings(overlap=True)``).
    """
    import flax.linen as nn

    from distributed_training_tpu.parallel.ring_attention import (
        _OutProj,
        _QKVProj,
    )

    def interceptor(next_fun, args, kwargs, context):
        mod = context.module
        if mod.is_initializing() or context.method_name != "__call__":
            return next_fun(*args, **kwargs)
        name = mod.name or ""
        n = lax.axis_size(axis_name)

        if isinstance(mod, nn.Dense) and name == "fc1":
            x = args[0]
            k, b = _raw_params(mod, "kernel", "bias")
            d = mod.dtype or jnp.result_type(x.dtype, k.dtype)
            y = allgather_matmul(x.astype(d), k.astype(d), axis_name)
            return y + b.astype(d)

        if isinstance(mod, nn.Dense) and name == "fc2":
            x = args[0]
            k, b = _raw_params(mod, "kernel", "bias")
            d = mod.dtype or jnp.result_type(x.dtype, k.dtype)
            _divisible("sequence shard", x.shape[-2], n, "fc2 row scatter")
            y = matmul_reducescatter(x.astype(d), k.astype(d), axis_name, -2)
            # Bias is replicated and applies once per row — add AFTER the
            # scatter-sum (adding per rank would count it n times).
            return y + b.astype(d)

        if isinstance(mod, _QKVProj):
            x = args[0]  # [B, t, D] time shard
            k, b = _raw_params(mod, "kernel", "bias")  # [D,3,Hl,hd],[3,Hl,hd]
            d_in = x.shape[-1]
            hl, hd = k.shape[2], k.shape[3]
            y = allgather_matmul(
                x.astype(mod.dtype), k.reshape(d_in, -1).astype(mod.dtype),
                axis_name)  # [B, T, 3·Hl·hd]
            y = y.reshape(*y.shape[:-1], 3, hl, hd) + b.astype(mod.dtype)
            # -> three [B, Hl, T, hd] (the module's output contract).
            q, kk, v = (jnp.moveaxis(y[..., s, :, :], -2, -3)
                        for s in range(3))
            return q, kk, v

        if isinstance(mod, _OutProj):
            x = args[0]  # [B, Hl, T, hd] local heads, full time
            k, b = _raw_params(mod, "kernel", "bias")  # [Hl, hd, D], [D]
            _divisible("sequence length", x.shape[-2], n, "out-proj scatter")
            x2 = jnp.moveaxis(x, -3, -2)  # [B, T, Hl, hd]
            x2 = x2.reshape(*x2.shape[:-2], -1)
            y = matmul_reducescatter(
                x2.astype(mod.dtype),
                k.reshape(-1, k.shape[-1]).astype(mod.dtype), axis_name, -2)
            return y + b.astype(mod.dtype)

        if name == "block0" and hasattr(mod, "num_heads") and args:
            # Stack entry: embedding output is replicated over the model
            # axis; slice this rank's time shard so every block runs the
            # sharded invariant (blocks 1..L-1 already receive shards).
            x = args[0]
            tl = _divisible("per-stage sequence length", x.shape[1], n,
                            "time scatter at the stack entry")
            x = lax.dynamic_slice_in_dim(
                x, lax.axis_index(axis_name) * tl, tl, axis=1)
            return next_fun(x, *args[1:], **kwargs)

        return next_fun(*args, **kwargs)

    return interceptor


def replicated_overlap_interceptor(axis_name: str = AXIS_MODEL):
    """Ring-overlap schedule for the replicated-activation TP layout (ViT).

    ViT's token count (patches + cls) is rarely divisible by the model-axis
    size, so activations stay replicated between blocks (the declarative
    layout) and only the row-parallel reductions change schedule: each
    ``psum`` becomes a cols-mode :func:`matmul_reducescatter` (overlapped)
    followed by a :func:`ring_all_gather` — the same bytes as the
    all-reduce, with the reduce half hidden behind the chunk matmuls and
    every op a neighbor ppermute. Column-parallel projections (q/k/v, fc1)
    run locally on their shard as before (their input is replicated — no
    collective to overlap).
    """
    import flax.linen as nn

    def interceptor(next_fun, args, kwargs, context):
        mod = context.module
        if mod.is_initializing() or context.method_name != "__call__":
            return next_fun(*args, **kwargs)
        name = mod.name or ""
        n = lax.axis_size(axis_name)

        if isinstance(mod, nn.Dense) and name == "fc1":
            # Column-parallel, replicated input: local shard matmul (the
            # raw fetch bypasses the global-shape check).
            x = args[0]
            k, b = _raw_params(mod, "kernel", "bias")
            d = mod.dtype or jnp.result_type(x.dtype, k.dtype)
            return x.astype(d) @ k.astype(d) + b.astype(d)

        if isinstance(mod, nn.Dense) and name == "fc2":
            x = args[0]
            k, b = _raw_params(mod, "kernel", "bias")
            d = mod.dtype or jnp.result_type(x.dtype, k.dtype)
            _divisible("hidden dim", k.shape[-1], n, "fc2 column scatter")
            y = matmul_reducescatter(x.astype(d), k.astype(d), axis_name, -1)
            y = ring_all_gather(y, axis_name, -1)
            return y + b.astype(d)

        if isinstance(mod, nn.DenseGeneral) and name in (
                "query", "key", "value"):
            # Column-parallel over heads: local einsum on the head shard.
            x = args[0]
            names = ["kernel"] + (["bias"] if mod.use_bias else [])
            vs = _raw_params(mod, *names)
            k = vs[0]  # [D, Hl, hd]
            d = mod.dtype or jnp.result_type(x.dtype, k.dtype)
            y = jnp.einsum("...d,dhk->...hk", x.astype(d), k.astype(d))
            if mod.use_bias:
                y = y + vs[1].astype(d)
            return y

        if isinstance(mod, nn.DenseGeneral) and name == "out":
            x = args[0]  # [..., Hl, hd] local heads
            names = ["kernel"] + (["bias"] if mod.use_bias else [])
            vs = _raw_params(mod, *names)
            k = vs[0]  # [Hl, hd, D]
            d = mod.dtype or jnp.result_type(x.dtype, k.dtype)
            _divisible("hidden dim", k.shape[-1], n, "out-proj scatter")
            x2 = x.reshape(*x.shape[:-2], -1)
            y = matmul_reducescatter(
                x2.astype(d), k.reshape(-1, k.shape[-1]).astype(d),
                axis_name, -1)
            y = ring_all_gather(y, axis_name, -1)
            if mod.use_bias:
                y = y + vs[1].astype(d)
            return y

        return next_fun(*args, **kwargs)

    return interceptor
