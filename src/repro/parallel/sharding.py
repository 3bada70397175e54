"""Logical-axis sharding rules: DP / FSDP / TP / EP / SP on a named mesh.

Every parameter is annotated at init time with per-dimension *roles*
(``Px(value, axes)``); a ``Rules`` object resolves roles onto mesh axes:

  role        meaning                                resolved to
  ----------  -------------------------------------  --------------------
  None        replicated                             ()
  "batch"     data-parallel batch dim                ("pod", "data")
  "fsdp"      ZeRO-style parameter shard dim         "data"
  "tp"        Megatron tensor-parallel dim           "model"
  "vocab"     vocab-parallel embedding/head dim      "model"
  "expert"    expert-parallel MoE dim                "model"
  "seq"       sequence dim (activations)             per-Rules (SP)
  "seq_tp"    sequence-sharded KV cache dim (SP)     "model" (+ "data"
                                                     when batch=1)
  "layers"    stacked-scan layer dim                 ()

The same rule table drives parameter shardings, activation
``with_sharding_constraint``s and the in/out shardings of the jitted steps,
so a single object describes the whole distribution strategy.
"""
from __future__ import annotations

import collections
import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

class Px:
    """Parameter leaf: value (array or ShapeDtypeStruct) + logical role per
    dim.  Registered as a pytree node with the roles as static aux data so
    vmap/scan/jit treat the value as the only traced child."""
    __slots__ = ("v", "ax")

    def __init__(self, v, ax):
        self.v = v
        self.ax = tuple(ax)

    def __repr__(self):
        shape = getattr(self.v, "shape", None)
        return f"Px(shape={shape}, ax={self.ax})"


jax.tree_util.register_pytree_node(
    Px, lambda p: ((p.v,), p.ax), lambda ax, ch: Px(ch[0], ax))


def is_px(x) -> bool:
    return isinstance(x, Px)


def is_axes(x) -> bool:
    """A per-dim role annotation: a *plain* tuple of None/str (NamedTuples
    such as KVCache are pytree nodes, not axes leaves)."""
    return type(x) is tuple and all(
        e is None or isinstance(e, str) for e in x)


def split_tree(tree):
    """(params, axes) from a tree of Px leaves."""
    vals = jax.tree.map(lambda p: p.v, tree, is_leaf=is_px)
    axes = jax.tree.map(lambda p: p.ax, tree, is_leaf=is_px)
    return vals, axes


def stack_axes(axes_leaf: Tuple) -> Tuple:
    """Axes for a vmapped/stacked (scan-over-layers) parameter."""
    return ("layers",) + tuple(axes_leaf)


@dataclasses.dataclass(frozen=True)
class Rules:
    """Resolution of logical roles onto a concrete mesh."""
    mesh_axes: Tuple[str, ...] = ("data", "model")
    fsdp: bool = True
    tensor: bool = True
    # long-context decode with global_batch < |data|: shard sequence over
    # the data axis too and replicate batch.
    seq_over_data: bool = False
    # concrete mesh (needed by shard_map-based layers, e.g. MoE dispatch)
    mesh: Any = None

    def _has(self, name: str) -> bool:
        return name in self.mesh_axes

    def axis(self, role: Optional[str]):
        if role is None or role == "layers":
            return None
        if role == "batch":
            if self.seq_over_data:
                return None
            ax = tuple(a for a in ("pod", "data") if self._has(a))
            return ax if ax else None
        if role == "fsdp":
            return "data" if (self.fsdp and self._has("data")) else None
        if role in ("tp", "vocab", "expert"):
            return "model" if (self.tensor and self._has("model")) else None
        if role == "seq":
            return None
        if role == "seq_tp":
            if self.seq_over_data:
                ax = tuple(a for a in ("pod", "data") if self._has(a))
                return ax + ("model",) if self._has("model") else ax
            return "model" if self._has("model") else None
        raise ValueError(f"unknown sharding role {role!r}")

    def spec(self, *roles) -> P:
        return P(*[self.axis(r) for r in roles])

    def shard(self, x, *roles):
        """Activation constraint (requires an enclosing mesh context).
        A no-op under the empty (single-device / REPLICATED) rule set."""
        if x is None or not self.mesh_axes:
            return x
        return jax.lax.with_sharding_constraint(x, self.spec(*roles))

    def spec_tree(self, axes_tree):
        return jax.tree.map(lambda ax: self.spec(*ax), axes_tree,
                            is_leaf=is_axes)

    def sharding_tree(self, axes_tree, mesh: Mesh):
        return jax.tree.map(
            lambda ax: NamedSharding(mesh, self.spec(*ax)), axes_tree,
            is_leaf=is_axes)


REPLICATED = Rules(mesh_axes=(), fsdp=False, tensor=False)


def make_mesh(shape, axes, devices=None) -> Mesh:
    """``jax.make_mesh`` with every axis ``Auto``.

    ``jax.make_mesh`` builds ``Explicit`` axes by default, and
    ``with_sharding_constraint`` (``Rules.shard``) refuses those.  Every
    mesh in this repository is built here, so the rule table's
    constraints hold on all of them."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def rules_for_mesh(mesh: Mesh, **kw) -> Rules:
    return Rules(mesh_axes=tuple(mesh.axis_names), mesh=mesh, **kw)


def batch_axes(tree):
    """Role-annotation tree for batch-leading pytrees: leading dim "batch",
    everything else replicated.

    The solver-pytree counterpart of ``Px`` annotations on parameters: the
    serving executors feed the result straight into ``Rules.spec_tree`` /
    ``Rules.sharding_tree`` to get per-leaf ``P(("data",), None, ...)``
    in/out shardings for the batched Jacobi/PCA solvers, whose every leaf
    (inputs, eigenpairs, moments, off-norms) carries the microbatch S axis
    first.  Accepts arrays or ``ShapeDtypeStruct``s (``jax.eval_shape``
    output trees work directly)."""
    return jax.tree.map(
        lambda x: ("batch",) + (None,) * (getattr(x, "ndim", 0) - 1), tree)


def pad_to_multiple(n: int, m: int) -> int:
    return n + (-n) % m
