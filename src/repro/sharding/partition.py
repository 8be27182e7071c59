"""Logical-axis -> physical-mesh partitioning.

Weights and activations are annotated with *logical* axis names; this module
resolves them against a concrete mesh, with divisibility fallback (e.g.
smollm's 15 query heads cannot shard 16-way -> replicated; granite's 49155
vocab rows cannot shard 16-way -> embedding falls back to FSDP-only).

Resolution is the single place where DP/FSDP/TP/EP decisions live, so the
perf pass can hillclimb by editing one rule table.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

# logical axis -> ordered candidate mesh axes (first divisible wins; the
# batch/fsdp axis composes pod+data when a pod axis exists).
DEFAULT_RULES: Dict[str, Tuple[Tuple[str, ...], ...]] = {
    "batch":    (("pod", "data"), ("data",)),
    "embed_w":  (("pod", "data"), ("data",)),   # weight FSDP axis (ZeRO-3)
    "vocab":    (("model",),),
    "heads":    (("model",),),
    "kv_heads": (("model",),),
    "mlp":      (("model",),),
    "expert":   (("model",),),
    "ssm_heads": (("model",),),
    "ssm_inner": (("model",),),
    # expert weights: d-dim FSDP by default (same as embed_w); the serving
    # rule-set flips to {expert_embed: replicated, expert_ff: data} so the
    # (dominant) expert weights are never all-gathered per decode step.
    "expert_embed": (("pod", "data"), ("data",)),
    "expert_ff": ((),),
    "seq_kv":   (("data",),),                    # long-context decode KV shard
    "seq":      ((),),                           # train seq: unsharded
    "embed":    ((),),                           # activation d_model: unsharded
    "head_dim": (("model",),),                   # fallback TP when heads can't

    "layers":   ((),),                           # scan/group dim (PP would go here)
    "state":    ((),),
    None:       ((),),
}


def make_mesh(shape: Sequence[int], axes: Sequence[str] = ("data", "model"),
              devices=None) -> Mesh:
    """A mesh whose axes are all ``Auto``: the compiler propagates
    shardings, steered by ``ShardCtx.act``'s constraints (which JAX accepts
    only on ``Auto`` axes; ``jax.make_mesh`` defaults to ``Explicit``)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


class PartitionRules:
    def __init__(self, rules: Optional[Dict] = None):
        self.rules = dict(DEFAULT_RULES)
        if rules:
            self.rules.update(rules)

    def _axis_size(self, mesh: Mesh, axes: Tuple[str, ...]) -> int:
        return int(np.prod([mesh.shape[a] for a in axes])) if axes else 1

    def spec_for(self, logical: Sequence[Optional[str]], shape: Sequence[int],
                 mesh: Mesh) -> P:
        used = set()
        out = []
        for name, dim in zip(logical, shape):
            resolved = None
            for cand in self.rules.get(name, ((),)):
                cand = tuple(a for a in cand if a in mesh.shape)
                if not cand:
                    continue
                if any(a in used for a in cand):
                    continue
                sz = self._axis_size(mesh, cand)
                if sz > 1 and dim % sz == 0:
                    resolved = cand if len(cand) > 1 else cand[0]
                    used.update(cand)
                    break
            out.append(resolved)
        while out and out[-1] is None:
            out.pop()
        return P(*out)

    def sharding_for(self, logical, shape, mesh: Mesh) -> NamedSharding:
        return NamedSharding(mesh, self.spec_for(logical, shape, mesh))

    def tree_specs(self, axes_tree, shape_tree, mesh: Mesh):
        """Map a pytree of logical-axes tuples + matching shapes to specs."""
        return jax.tree.map(
            lambda ax, shp: self.spec_for(ax, shp.shape, mesh),
            axes_tree, shape_tree,
            is_leaf=lambda x: isinstance(x, tuple) and all(
                isinstance(e, (str, type(None))) for e in x),
        )


class ShardCtx:
    """Carries (mesh, rules) into model code; ``act`` constrains activations.

    A ``None`` ShardCtx (CPU smoke tests, single device) makes every
    constraint a no-op, so model code is written once.
    """

    def __init__(self, mesh: Optional[Mesh], rules: Optional[PartitionRules] = None):
        self.mesh = mesh
        self.rules = rules or PartitionRules()

    def act(self, x, logical: Sequence[Optional[str]]):
        if self.mesh is None:
            return x
        return jax.lax.with_sharding_constraint(
            x, self.rules.sharding_for(logical, x.shape, self.mesh))

    def spec(self, logical, shape) -> P:
        if self.mesh is None:
            return P()
        return self.rules.spec_for(logical, shape, self.mesh)


NULL_CTX = ShardCtx(None)
