"""Explicit-collective ZeRO-1: DeepSpeed's partitioning engine, hand-built.

The declarative GSPMD formulation in ``parallel/sharding.py`` expresses ZeRO
as sharding annotations and lets XLA choose the collectives. This module is
the *explicit* formulation — the direct TPU analogue of what DeepSpeed's
stage-1 engine does imperatively on GPU
(``resnet/deepspeed/deepspeed_train.py:210-219``: ``reduce_scatter: True``,
``allgather_partitions: True``, flat 50 MB buckets):

1. every device computes gradients for the full model from its local batch;
2. the gradient pytree is raveled into ONE flat buffer, padded to a multiple
   of the data-axis size (DeepSpeed pads its flat buckets the same way);
3. ``lax.psum_scatter`` reduce-scatters the buffer: each device receives the
   *sum* of one 1/N-slice — the only gradient communication in the step;
4. Adam moments exist **only for the local slice** (the 1/N optimizer-state
   memory saving that defines stage 1) and the update is computed on it;
5. ``lax.all_gather`` re-materializes the flat update, which is unraveled
   and applied to the (replicated) params.

Unlike DeepSpeed there is no bucketing/overlap knob surface: the whole step
is one XLA program and the latency-hiding scheduler overlaps the
reduce-scatter/all-gather with compute on its own (SURVEY.md §7 "hard
parts": DS knobs that are meaningful no-ops under XLA).

Equivalence contract (tested in ``tests/test_zero_explicit.py``): N-step
training with this step == replicated-Adam training on the same global
batch, bitwise-modulo float-reduction order.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from flax import struct
from jax import lax
from jax.flatten_util import ravel_pytree
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributed_training_tpu.runtime.mesh import AXIS_DATA
from distributed_training_tpu.utils.compat import shard_map


@dataclasses.dataclass(frozen=True)
class AdamConfig:
    """Adam hyperparameters (defaults = the reference DDP trainer's
    ``Adam(lr=1e-3)``, ``resnet/pytorch_ddp/ddp_train.py:97``; the DeepSpeed
    preset is ``AdamConfig(lr=1e-3, b1=0.8, weight_decay=3e-7)``,
    ``resnet/deepspeed/deepspeed_train.py:175-186``)."""

    lr: float = 1e-3
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8
    weight_decay: float = 0.0  # L2-style (added to the gradient), as torch Adam


class Zero1State(struct.PyTreeNode):
    """Carried state: replicated params + flat SHARDED Adam moments.

    ``mu``/``nu`` are [padded_size] flat buffers whose global sharding is
    ``P('data')``; inside the shard_map step each device sees its
    [padded_size / N] slice only.
    """

    step: jnp.ndarray
    params: Any
    mu: jnp.ndarray
    nu: jnp.ndarray


def _padded_size(n: int, world: int) -> int:
    return -(-n // world) * world


def zero1_create(params, mesh: Mesh) -> Zero1State:
    """Initialize and place a Zero1State on the mesh.

    Params replicate; the flat moment buffers shard over ``data``. Memory
    per device: params + 2 * params/N — stage-1's defining footprint.
    """
    flat, _ = ravel_pytree(params)
    world = dict(zip(mesh.axis_names, mesh.devices.shape)).get(AXIS_DATA, 1)
    pad = _padded_size(flat.size, world)
    zeros = jnp.zeros((pad,), jnp.float32)
    state = Zero1State(
        step=jnp.int32(0), params=params, mu=zeros, nu=zeros)
    shardings = Zero1State(
        step=NamedSharding(mesh, P()),
        params=jax.tree.map(lambda _: NamedSharding(mesh, P()), params),
        mu=NamedSharding(mesh, P(AXIS_DATA)),
        nu=NamedSharding(mesh, P(AXIS_DATA)),
    )
    return jax.tree.map(jax.device_put, state, shardings)


def make_zero1_train_step(
    mesh: Mesh,
    loss_fn: Callable[[Any, Any, jax.Array], jnp.ndarray],
    config: AdamConfig = AdamConfig(),
    *,
    schedule: Callable[[jnp.ndarray], jnp.ndarray] | None = None,
    donate: bool = True,
) -> Callable:
    """Build the explicit ZeRO-1 jitted step.

    Args:
      mesh: mesh with a ``data`` axis; the batch arrives sharded over it.
      loss_fn: ``(params, local_batch, rng) -> scalar`` mean loss over the
        local batch shard (the step pmeans across shards).
      config: Adam hyperparameters.
      schedule: optional ``step -> learning rate`` (an absolute lr, e.g.
        ``optax.linear_schedule(0, 1e-3, 1000)`` for WarmupLR parity);
        when given it *replaces* ``config.lr`` entirely.
      donate: donate the state buffers (steady-state training).

    Returns ``step(state, batch, rng) -> (state, metrics)`` with ``batch`` a
    pytree of global arrays whose leading dim is sharded over ``data``.
    """
    axis = AXIS_DATA

    def body(state: Zero1State, batch, rng):
        world = lax.axis_size(axis)
        rank = lax.axis_index(axis)
        rng = jax.random.fold_in(rng, rank)

        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(p, batch, rng))(state.params)

        flat_g, unravel = ravel_pytree(grads)
        true_size = flat_g.size
        pad = _padded_size(true_size, world)
        flat_g = jnp.pad(flat_g.astype(jnp.float32), (0, pad - true_size))

        # (3) one reduce-scatter: mean gradient, each device owns 1/N.
        g_shard = lax.psum_scatter(flat_g, axis, tiled=True) / world

        if config.weight_decay:
            flat_p, _ = ravel_pytree(state.params)
            flat_p = jnp.pad(
                flat_p.astype(jnp.float32), (0, pad - true_size))
            shard_len = pad // world
            p_shard = lax.dynamic_slice(
                flat_p, (rank * shard_len,), (shard_len,))
            g_shard = g_shard + config.weight_decay * p_shard

        # (4) Adam on the local moment slice only.
        t = (state.step + 1).astype(jnp.float32)
        mu = config.b1 * state.mu + (1 - config.b1) * g_shard
        nu = config.b2 * state.nu + (1 - config.b2) * jnp.square(g_shard)
        mu_hat = mu / (1 - config.b1 ** t)
        nu_hat = nu / (1 - config.b2 ** t)
        lr = schedule(state.step) if schedule is not None else config.lr
        upd_shard = -lr * mu_hat / (jnp.sqrt(nu_hat) + config.eps)

        # (5) re-materialize the flat update and apply to replicated params.
        flat_upd = lax.all_gather(upd_shard, axis, tiled=True)[:true_size]
        delta = unravel(flat_upd)
        params = jax.tree.map(
            lambda p, d: p + d.astype(p.dtype), state.params, delta)

        metrics = {
            "loss": lax.pmean(loss, axis).astype(jnp.float32),
            "grad_norm": jnp.sqrt(
                lax.psum(jnp.sum(jnp.square(g_shard)), axis)),
        }
        return Zero1State(
            step=state.step + 1, params=params, mu=mu, nu=nu), metrics

    state_specs = Zero1State(
        step=P(), params=None, mu=P(axis), nu=P(axis))

    @functools.partial(jax.jit, donate_argnums=(0,) if donate else ())
    def step(state: Zero1State, batch, rng):
        in_state_specs = state_specs.replace(
            params=jax.tree.map(lambda _: P(), state.params))
        batch_specs = jax.tree.map(lambda _: P(axis), batch)
        return shard_map(
            body, mesh,
            in_specs=(in_state_specs, batch_specs, P()),
            out_specs=(in_state_specs, P()),
        )(state, batch, rng)

    return step
