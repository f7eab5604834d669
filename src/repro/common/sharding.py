"""Logical-axis sharding rules (MaxText-style) mapped onto the production mesh.

Every parameter / activation annotates its dims with *logical* axis names;
``logical_to_spec`` resolves them to mesh axes through a rule table. Hillclimb
iterations in EXPERIMENTS.md §Perf swap rule tables, not model code.

The fabric's ``expander`` mesh axis (DESIGN.md §17) also lives here:
``force_host_device_count(n)`` makes N CPU devices exist anywhere (CI
included) via the ``xla_force_host_platform_device_count`` flag, and
``expander_mesh(d)`` builds the 1-D mesh the sharded fabric drivers run
on. The force MUST happen before jax initializes its backend — importing
any ``repro.*`` engine module initializes it (module-level jnp constants),
so launchers set it as their literal first statement (launch/dryrun.py,
launch/fabric.py ``--devices``).
"""
from __future__ import annotations

import os
from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# the fabric's device axis: one shard of the stacked pool pytree per device
EXPANDER_AXIS = "expander"


def force_host_device_count(n: int) -> None:
    """Make ``n`` XLA host (CPU) devices exist, the SNIPPETS idiom:
    merge ``--xla_force_host_platform_device_count=n`` into XLA_FLAGS.
    Must run before the jax backend initializes (first trace/device query);
    a later call is silently ineffective, which ``host_device_count`` lets
    callers detect."""
    flags = os.environ.get("XLA_FLAGS", "")
    kept = [f for f in flags.split() if not f.startswith(
        "--xla_force_host_platform_device_count")]
    kept.append(f"--xla_force_host_platform_device_count={n}")
    os.environ["XLA_FLAGS"] = " ".join(kept)


def host_device_count() -> int:
    """Devices actually visible to jax (after any force took effect)."""
    return jax.device_count()


def expander_mesh(n_devices: int) -> Mesh:
    """1-D mesh over the first ``n_devices`` devices, axis ``expander``.
    The sharded fabric requires n_expanders % n_devices == 0 so every
    device owns an equal block of the stacked pool pytree."""
    import numpy as _np
    devs = jax.devices()
    if n_devices > len(devs):
        hint = ("" if devs[0].platform == "tpu" else
                "; call force_host_device_count before jax initializes")
        raise ValueError(
            f"expander_mesh({n_devices}) but only {len(devs)} "
            f"{devs[0].platform} devices visible{hint}")
    return Mesh(_np.asarray(devs[:n_devices]), (EXPANDER_AXIS,))

# Default rule table: FSDP over "data", tensor parallel over "model",
# batch over ("pod","data"). ``None`` -> replicated.
DEFAULT_RULES: Tuple[Tuple[str, Optional[object]], ...] = (
    ("batch", ("pod", "data")),
    ("seq", None),                   # sequence kept local by default
    ("seq_shard", ("data",)),        # long-context cells shard sequence over data
    ("embed", None),
    ("vocab", ("model",)),
    ("heads", ("model",)),
    ("kv_heads", ("model",)),
    ("head_dim", None),
    ("mlp", ("model",)),
    ("expert", ("model",)),          # expert parallelism
    ("expert_mlp", None),
    ("fsdp", ("data",)),             # parameter FSDP axis
    ("layers", None),
    ("kv_pages", None),
    ("kv_hot", None),   # hot-ring W axis (sharded over model when kv_heads cannot)
    ("latent", None),
    ("state", None),
    ("expander", ("expander",)),     # fabric pool stack: one shard per device
)


def rules_to_dict(rules: Sequence[Tuple[str, Optional[object]]]) -> dict:
    return {k: v for k, v in rules}


def logical_to_spec(logical_axes: Sequence[Optional[str]],
                    rules: Sequence[Tuple[str, Optional[object]]] = DEFAULT_RULES,
                    mesh_axes: Sequence[str] = ("data", "model")) -> P:
    """Map a tuple of logical axis names to a PartitionSpec, dropping mesh axes
    that do not exist on the current mesh (e.g. "pod" on the single-pod mesh)."""
    table = rules_to_dict(rules)
    out = []
    used: set = set()
    for name in logical_axes:
        if name is None:
            out.append(None)
            continue
        phys = table.get(name, None)
        if phys is None:
            out.append(None)
            continue
        if isinstance(phys, str):
            phys = (phys,)
        keep = tuple(a for a in phys if a in mesh_axes and a not in used)
        used.update(keep)
        if not keep:
            out.append(None)
        elif len(keep) == 1:
            out.append(keep[0])
        else:
            out.append(keep)
    # trim trailing Nones for cleanliness
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def named_sharding(mesh: Mesh, *logical_axes: Optional[str],
                   rules: Sequence[Tuple[str, Optional[object]]] = DEFAULT_RULES) -> NamedSharding:
    return NamedSharding(mesh, logical_to_spec(logical_axes, rules, mesh.axis_names))


def tree_shardings(mesh: Mesh, logical_tree,
                   rules: Sequence[Tuple[str, Optional[object]]] = DEFAULT_RULES):
    """Map a pytree of logical-axis tuples to NamedShardings."""
    return jax.tree_util.tree_map(
        lambda axes: NamedSharding(mesh, logical_to_spec(axes, rules, mesh.axis_names)),
        logical_tree, is_leaf=lambda x: isinstance(x, tuple) and all(
            a is None or isinstance(a, str) for a in x))


def batch_spec(mesh: Mesh, rules=DEFAULT_RULES) -> P:
    return logical_to_spec(("batch", "seq"), rules, mesh.axis_names)
