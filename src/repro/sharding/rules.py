"""Logical-axis sharding rules (MaxText-style).

Model code annotates every parameter / activation dimension with a LOGICAL
axis name ("embed", "heads", "experts", "batch", ...). A rules table maps
logical names to physical mesh axes. Changing the parallelism layout (the
main §Perf hillclimb lever) means changing ONE table — model code never
hard-codes mesh axes.

Physical mesh axes (launch/mesh.py):
  single-pod: ("data", "model")          = (16, 16)
  multi-pod:  ("pod", "data", "model")   = (2, 16, 16)

Default layout = 2D sharding: FSDP over ("pod","data") for the non-TP
dimension of every weight, tensor/expert parallelism over "model".
"""
from __future__ import annotations

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# logical axis -> mesh axis (str), tuple of mesh axes, or None (replicated)
LogicalAxisRules = dict

DEFAULT_RULES: LogicalAxisRules = {
    # activations
    "batch": ("pod", "data"),    # data parallel over pod x data
    "seq": None,                 # sequence replicated by default (SP opt-in)
    "seq_model": "model",        # sequence-sharded decode KV (flash-decode)
    "embed_act": None,
    "heads_act": "model",
    "vocab_act": "model",
    "exp_act": "model",
    # parameters: TP dim -> "model", FSDP dim -> ("pod","data")
    "embed": ("pod", "data"),    # FSDP axis of most weights
    "embed_tp": "model",         # rows of attn-out / mlp-out (TP dim)
    "heads": "model",
    "kv_heads": "model",
    "mlp": "model",
    "vocab": "model",
    "experts": "model",
    "exp_mlp": None,
    "kv_lora": None,
    "q_lora": None,
    "conv": None,
    "state": None,
    "stack": None,               # scanned-layer leading axis: never sharded
    None: None,
}


def logical_to_pspec(axes: tuple, rules: LogicalAxisRules,
                     mesh: Mesh | None = None, shape: tuple | None = None) -> P:
    """Map a tuple of logical axis names to a PartitionSpec.

    Robustness rules (so ONE rules table serves every arch and both meshes):
    * mesh axes absent from ``mesh`` are dropped (pod axis on single-pod);
    * a mesh axis may shard at most one dim — first occurrence wins;
    * with ``shape`` given, mesh axes are applied greedily only while their
      product divides the dim (4 kv-heads never shard over a 16-way axis;
      batch=1 decode stays replicated).
    """
    have = set(mesh.axis_names) if mesh is not None else None
    sizes = dict(mesh.shape) if mesh is not None else {}
    out = []
    used: set[str] = set()
    for i, ax in enumerate(axes):
        phys = rules.get(ax, None)
        if phys is None:
            out.append(None)
            continue
        if isinstance(phys, str):
            phys = (phys,)
        phys = tuple(p for p in phys
                     if (have is None or p in have) and p not in used)
        if shape is not None and sizes:
            picked, prod = [], 1
            for p in phys:
                if shape[i] % (prod * sizes.get(p, 1)) == 0:
                    picked.append(p)
                    prod *= sizes.get(p, 1)
            phys = tuple(picked)
        used.update(phys)
        out.append(phys if len(phys) > 1 else (phys[0] if phys else None))
    return P(*out)


def spec_tree_to_pspecs(spec_tree, rules: LogicalAxisRules, mesh=None):
    """Map a pytree of logical-axes tuples to a pytree of PartitionSpecs."""
    return jax.tree.map(lambda axes: logical_to_pspec(axes, rules, mesh),
                        spec_tree, is_leaf=lambda x: isinstance(x, tuple))


def named_sharding(mesh: Mesh, axes: tuple, rules: LogicalAxisRules):
    return NamedSharding(mesh, logical_to_pspec(axes, rules, mesh))


def current_abstract_mesh():
    """The ambient abstract mesh (``jax.sharding.set_mesh``), or ``None``
    outside a mesh scope."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty:
        return None
    return mesh


def constrain(x, axes: tuple, rules: LogicalAxisRules | None = None):
    """with_sharding_constraint by logical axes. No-op outside a mesh scope
    (``jax.sharding.set_mesh``), so the same model code runs in
    single-device smoke tests and in the 512-device dry-run unchanged."""
    if rules is None:
        return x
    mesh = current_abstract_mesh()
    if mesh is None:
        return x
    spec = logical_to_pspec(axes, rules, mesh, shape=x.shape)
    return jax.lax.with_sharding_constraint(x, spec)
