"""Sharding plan: logical parameter/activation axes -> mesh axes.

Meshes (launch/mesh.py):
  single pod: (data=16, model=16)      multi-pod: (pod=2, data=16, model=16)

Policy (DESIGN.md §4):
  * TP ("model"): attention q/kv features, FFN hidden, MoE experts, mamba
    inner channels, vocab/embedding table.
  * DP ("pod","data"): activation batch; gradients all-reduced (pod axis
    crosses DCN once per step).
  * FSDP ("data"): the *embed* (d_model) dim of every 2-D+ weight for archs
    over ``fsdp_threshold`` params — ZeRO-3-style gather-per-layer
    under scan.
  * Decode caches: seq dim on "model" (small tensors cross shards during
    attention: score partials, not the cache), batch on DP when divisible.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

FSDP_THRESHOLD = 500_000_000   # params; above this, shard "embed" on data


def shard_map_compat(f, *, mesh, in_specs, out_specs, check=False):
    """``jax.shard_map`` with its replication check off by default."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)


@dataclasses.dataclass(frozen=True)
class ShardingPlan:
    mesh: Mesh
    fsdp: bool
    dp_axes: tuple            # ("pod", "data") or ("data",)

    # -- logical-axis translation ----------------------------------------
    def _axis(self, logical: Optional[str]):
        if logical is None:
            return None
        table = {
            "vocab": "model",
            "q_features": "model",
            "kv_features": "model",
            "mlp": "model",
            "expert": "model",
            "mamba_inner": "model",
            "embed": "data" if self.fsdp else None,
            "fsdp": "data" if self.fsdp else None,
            "layers": None,
            "batch": self.dp_axes,
        }
        return table.get(logical, None)

    def _mesh_size(self, m) -> int:
        if isinstance(m, tuple):
            n = 1
            for a in m:
                n *= self.mesh.shape[a]
            return n
        return self.mesh.shape[m]

    def spec_for(self, axes: tuple, shape: Optional[tuple] = None) -> P:
        """Mesh spec for logical axes; dims not divisible by the mesh axis
        stay replicated (explicit in_shardings require divisibility)."""
        mesh_axes = []
        used = set()
        # embedding/unembedding tables: vocab-shard only — FSDP on the
        # embed dim of a gathered table triggers SPMD full-remat (b/433785288)
        no_fsdp = "vocab" in axes
        for i, a in enumerate(axes):
            m = self._axis(a)
            if a == "embed" and no_fsdp:
                m = None
            # never map two tensor dims to the same mesh axis
            if m is not None and not isinstance(m, tuple) and m in used:
                m = None
            if m is not None and shape is not None \
                    and shape[i] % self._mesh_size(m) != 0:
                m = None
            if m is not None:
                used.add(m if not isinstance(m, tuple) else "_dp")
            mesh_axes.append(m)
        return P(*mesh_axes)

    def param_shardings(self, logical_axes_tree, structs_tree=None):
        if structs_tree is None:
            return jax.tree.map(
                lambda axes: NamedSharding(self.mesh, self.spec_for(axes)),
                logical_axes_tree, is_leaf=lambda x: isinstance(x, tuple))
        return jax.tree.map(
            lambda axes, s: NamedSharding(self.mesh,
                                          self.spec_for(axes, s.shape)),
            logical_axes_tree, structs_tree,
            is_leaf=lambda x: isinstance(x, tuple))

    # -- activations / batch ---------------------------------------------
    def dp_size(self) -> int:
        n = 1
        for a in self.dp_axes:
            n *= self.mesh.shape[a]
        return n

    def batch_spec(self, global_batch: int, ndim: int) -> P:
        dp = self.dp_axes if global_batch % self.dp_size() == 0 else None
        return P(dp, *([None] * (ndim - 1)))

    def batch_shardings(self, batch_structs):
        def shard_one(s):
            if s.ndim == 0:
                return NamedSharding(self.mesh, P())
            # leading dim is batch except (3, B, S) M-RoPE positions
            if s.ndim == 3 and s.shape[0] == 3:
                spec = P(None, *self.batch_spec(s.shape[1], 2))
            else:
                spec = self.batch_spec(s.shape[0], s.ndim)
            return NamedSharding(self.mesh, spec)
        return jax.tree.map(shard_one, batch_structs)

    # -- decode caches -----------------------------------------------------
    def cache_shardings(self, cache_structs, batch_size: int):
        batched = batch_size % self.dp_size() == 0

        model_n = self.mesh.shape["model"]

        def shard_one(path, s):
            name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
            dp = self.dp_axes if batched else None

            def ns(*spec):
                # drop mesh axes whose tensor dim isn't divisible
                fixed = []
                for i, m in enumerate(spec):
                    if m == "model" and s.shape[i] % model_n != 0:
                        m = None
                    fixed.append(m)
                fixed += [None] * (s.ndim - len(fixed))
                return NamedSharding(self.mesh, P(*fixed))

            if name in ("k", "v", "cross_k", "cross_v"):
                return ns(None, dp, None, None, "model")  # (G,B,kv,h,S): seq
            if name == "ssm":
                return ns(None, dp, "model")          # (G,B,d_in,N)
            if name == "conv":
                return ns(None, dp, None, "model")    # (G,B,dc-1,d_in)
            if name == "C":
                return ns(None, dp, None, None, "model")  # (G,B,H,dk,dv)
            return ns(None, dp)

        return jax.tree.map_with_path(shard_one, cache_structs)


# ---------------------------------------------------------------------------
# Fleet-axis sharding: the packed (fleet, samples) layout's natural split.
# ---------------------------------------------------------------------------

def fleet_mesh(min_devices: int = 2) -> Optional[Mesh]:
    """1-D mesh over every LOCAL device for fleet-row sharding.

    Returns None on a single-device host — the fleet pipeline then runs
    exactly the unsharded path (parity oracle unchanged).  Local devices
    only: in a multi-process run (``jax.distributed``) ``jax.devices()``
    spans every host, and a shard_map over non-addressable devices would
    need cross-process XLA computations; the cross-host fleet split is
    the per-host packing layer (``distributed.multihost``) instead.
    """
    import numpy as np
    devices = jax.local_devices()
    if len(devices) < min_devices:
        return None
    return Mesh(np.asarray(devices), ("fleet",))


def fleet_rows_divisible(mesh: Optional[Mesh], n_rows: int) -> bool:
    """True when the padded fleet axis splits evenly over the mesh."""
    return mesh is not None and n_rows % mesh.shape["fleet"] == 0


def fleet_row_padding(mesh: Optional[Mesh], n_rows: int) -> int:
    """Masked rows to append so the fleet axis splits over the mesh.

    Non-divisible fleets used to fall back to unsharded execution; the
    fleet consumers now pad with degenerate zero-width rows (exactly the
    ``pack_traces`` all-padding convention: zero samples, zero energy)
    and keep the mesh — the padding integrates to zero and is sliced off
    the outputs.
    """
    if mesh is None:
        return 0
    return (-n_rows) % mesh.shape["fleet"]


def fleet_spec(ndim: int) -> P:
    """Row-sharded spec for a (fleet, ...) array: P("fleet", None, ...)."""
    return P("fleet", *([None] * (ndim - 1)))


def fleet_shard_map(fn, mesh: Mesh, n_in: int, n_out: int,
                    replicated_in: tuple = ()):
    """Wrap a row-independent fleet function for per-device execution.

    Every input/output is row-sharded on the fleet axis except the
    positions in ``replicated_in`` (e.g. a shared phase table).  The
    fleet kernels are embarrassingly parallel across rows, so this is a
    pure partition: no collectives, each device runs its row block.
    """
    in_specs = tuple(P() if i in replicated_in else fleet_spec(2)
                     for i in range(n_in))
    out_specs = tuple(fleet_spec(2) for _ in range(n_out))
    if n_out == 1:
        out_specs = out_specs[0]
    return shard_map_compat(fn, mesh=mesh, in_specs=in_specs,
                            out_specs=out_specs)


def make_plan(mesh: Mesh, arch_params: int) -> ShardingPlan:
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    fsdp = arch_params > FSDP_THRESHOLD and "data" in mesh.axis_names
    return ShardingPlan(mesh=mesh, fsdp=fsdp, dp_axes=dp_axes)


def constrain(x, mesh, spec: P):
    """Sharding-constraint helper usable inside jitted code."""
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
