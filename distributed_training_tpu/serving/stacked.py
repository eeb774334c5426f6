"""Block weights stacked over the layers, as the step programs take them.

A launch of a step program hands the runtime one device buffer per leaf of
its arguments, and what the launch costs the host follows that count, not
the bytes (PERF.md §5: 9 KB and 80 KB of step inputs cost the same). A
decoder whose blocks repeat holds the same leaves under ``block0`` …
``block{L-1}``; stacked, each kind of small leaf is ONE ``[L, …]`` buffer
(:data:`STACK_LEAF_BYTES`: the weight matrices keep their own), and GPT-2
large's 437 parameter leaves become 157 — its 288 LayerNorm gains and
biases 8 buffers (docs/SERVING.md "The stacked launch").

:func:`stack_plan` groups a tree's leaves once; :meth:`StackPlan.stack`
makes the stacked tree, a :class:`StackedParams`, in one jitted call; inside
a step program :meth:`StackedParams.unstack` gives back the model's own
tree by static indexing, so the model, its parameter layout and its
checkpoints stay as they are. A :class:`~distributed_training_tpu.serving.
quantize.QuantizedTensor` counts as one leaf: an int8 tree stacks its
values and scales side by side.
"""

from __future__ import annotations

import re
from typing import Any

import jax
import jax.numpy as jnp
from jax.tree_util import DictKey

from distributed_training_tpu.serving.quantize import QuantizedTensor


# The largest block leaf that stacks. A weight matrix keeps its own buffer:
# the compiler prefetches each layer's matrix into the chip's fast memory
# ahead of its product, which it does not do for a layer's slice of a
# stacked [L, …] buffer (GPT-2 large's decode step took 52% longer on a
# TPU v5e with its matrices stacked). LayerNorm gains and biases stack:
# a stacked buffer of them is small enough to prefetch whole.
STACK_LEAF_BYTES = 1 << 16


def _is_leaf(x) -> bool:
    return isinstance(x, QuantizedTensor)


def _nbytes(leaf) -> int:
    return sum(int(jnp.size(a)) * jnp.dtype(jnp.result_type(a)).itemsize
               for a in jax.tree.leaves(leaf))


def _signature(leaf) -> tuple:
    """What two leaves must share to stack: structure, shapes, dtypes."""
    return (jax.tree.structure(leaf),
            tuple((jnp.shape(a), jnp.result_type(a))
                  for a in jax.tree.leaves(leaf)))


@jax.jit
def _stack_groups(groups):
    return tuple(jax.tree.map(lambda *xs: jnp.stack(xs), *members)
                 for members in groups)


class StackPlan:
    """Which leaves of one tree structure stack, and where each goes back.

    ``groups`` holds, per stacked kind of leaf, the positions (in the
    tree's flattened order, a ``QuantizedTensor`` as one leaf) of its
    members in block order; ``rest`` the positions of the leaves that do
    not repeat. A plan is compared by identity: an engine stacks every
    tree it serves with the one plan it made, so a stacked tree's
    structure names its plan and a swap candidate stacked elsewhere is
    told apart.
    """

    __slots__ = ("treedef", "groups", "rest")

    def __init__(self, treedef, groups: tuple[tuple[int, ...], ...],
                 rest: tuple[int, ...]):
        self.treedef = treedef
        self.groups = groups
        self.rest = rest

    def stack(self, params: Any) -> StackedParams:
        """``params`` (the plan's structure) stacked: the groups in one
        jitted call, into new buffers; the other leaves as they are."""
        leaves, treedef = jax.tree.flatten(params, is_leaf=_is_leaf)
        if treedef != self.treedef:
            raise ValueError(
                f"tree structure {treedef} is not the plan's "
                f"{self.treedef}")
        groups = _stack_groups(tuple(tuple(leaves[k] for k in members)
                                     for members in self.groups))
        return StackedParams(groups, tuple(leaves[k] for k in self.rest),
                             self)


def stack_plan(params: Any, prefix: str) -> StackPlan | None:
    """Group the leaves of ``params`` whose paths differ only in the block's
    number (one key ``f"{prefix}{i}"``), that share structure, shapes and
    dtypes and hold at most :data:`STACK_LEAF_BYTES` each; a kind with one
    member stays as it is. None when nothing repeats."""
    block = re.compile(re.escape(prefix) + r"(\d+)")
    flat, treedef = jax.tree_util.tree_flatten_with_path(params,
                                                         is_leaf=_is_leaf)
    kinds: dict[tuple, list[tuple[int, int]]] = {}
    rest = []
    for k, (path, leaf) in enumerate(flat):
        hits = [(j, int(m.group(1))) for j, key in enumerate(path)
                if isinstance(key, DictKey)
                and (m := block.fullmatch(str(key.key)))]
        if len(hits) != 1 or _nbytes(leaf) > STACK_LEAF_BYTES:
            rest.append(k)
            continue
        (j, layer), = hits
        kind = (path[:j] + path[j + 1:], j, _signature(leaf))
        kinds.setdefault(kind, []).append((layer, k))
    groups = []
    for members in kinds.values():
        if len(members) < 2:
            rest.extend(k for _, k in members)
        else:
            groups.append(tuple(k for _, k in sorted(members)))
    if not groups:
        return None
    return StackPlan(treedef, tuple(groups), tuple(sorted(rest)))


@jax.tree_util.register_pytree_node_class
class StackedParams:
    """A parameter tree with its repeating block leaves stacked over the
    layers (:class:`StackPlan`). A pytree: a jitted call takes one buffer
    per stacked kind and per other leaf."""

    __slots__ = ("groups", "rest", "plan")

    def __init__(self, groups: tuple, rest: tuple, plan: StackPlan):
        self.groups = groups
        self.rest = rest
        self.plan = plan

    def tree_flatten(self):
        return (self.groups, self.rest), self.plan

    @classmethod
    def tree_unflatten(cls, plan, children):
        return cls(*children, plan)

    # Equality of abstract trees (``ShapeDtypeStruct`` leaves), what an
    # engine's swap validation compares; arrays never reach it.
    def __eq__(self, other):
        return (isinstance(other, StackedParams) and other.plan is self.plan
                and other.groups == self.groups and other.rest == self.rest)

    def unstack(self) -> Any:
        """The per-layer tree the model applies: member ``i`` of a group is
        its ``[i]``, a static index."""
        leaves = [None] * self.plan.treedef.num_leaves
        for stacked, members in zip(self.groups, self.plan.groups):
            for i, k in enumerate(members):
                leaves[k] = jax.tree.map(
                    lambda a, i=i: jax.lax.index_in_dim(a, i,
                                                        keepdims=False),
                    stacked)
        for leaf, k in zip(self.rest, self.plan.rest):
            leaves[k] = leaf
        return self.plan.treedef.unflatten(leaves)


def per_layer(params: Any) -> Any:
    """The tree a model applies: ``params`` unstacked where stacked."""
    if isinstance(params, StackedParams):
        return params.unstack()
    return params
