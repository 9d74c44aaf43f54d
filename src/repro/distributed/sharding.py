"""Device-mesh sharding: the serving slot axis + LM logical-axis rules.

Two deliberate public surfaces, nothing else:

**Slot-axis helpers (mesh serving).**  The event-serving mesh backend
(`repro.serve.mesh_engine`) shards exactly one axis — the engine's slot
axis — across a 1-D device mesh named :data:`SLOT_AXIS`: per-shard
membrane slabs, replicated weights.  :func:`slot_mesh` builds the mesh,
:func:`slot_spec` / :func:`slot_sharding` place the slot-sharded tensors,
:func:`replicated` places the weights; ``jax.shard_map`` wraps the
fused window step over it.

**Logical-axis rules (the LM stack).**  Every parameter and activation in
the model stack is annotated with *logical* axis names ("embed", "mlp",
"heads", "vocab", "experts", "batch", "seq", ...). A :class:`MeshRules`
table maps logical names to physical mesh axes; resolution automatically
drops a mapping when the dimension size does not divide the mesh-axis
size (e.g. 40 attention heads on a 16-way model axis fall back to
replication while the 14336-wide FFN still shards) — the same policy
MaxText applies.  Parallelism encoding on the production mesh
``(pod, data, model)``:

  * DP    — "batch" -> ("pod", "data")
  * FSDP  — "p_embed" (the d_model axis of every weight) -> "data";
            gathered on use, so optimizer state & grads stay sharded.
  * TP    — "mlp" / "heads" / "vocab" / "kv" -> "model" (Megatron split).
  * EP    — "experts" -> "model".
  * SP    — "kv_seq" (decode KV cache length) -> "model"; long-context
            decode additionally folds "data" into the sequence shards.

Model code reaches the rules through the process-global context
(:func:`set_mesh_rules` / :func:`logical`) so annotations need no
plumbing; the serving mesh backend deliberately does NOT use the global
context — its mesh is engine-owned state, never ambient.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Axis = Union[str, Tuple[str, ...], None]


# ---------------------------------------------------------------------------
# Slot-axis helpers — the mesh serving surface (repro.serve.mesh_engine).
# ---------------------------------------------------------------------------

SLOT_AXIS = "slots"


def slot_mesh(devices=None) -> Mesh:
    """Build the 1-D serving mesh over the slot axis.

    ``devices`` is a device sequence or a device *count* (the first ``n``
    of ``jax.devices()``); by default every visible device joins.  On a
    CPU-only host, simulate devices with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (set before
    jax initialises its backend).
    """
    if devices is None:
        devs = jax.devices()
    elif isinstance(devices, int):
        if devices < 1:
            raise ValueError(f"need at least 1 device, got {devices}")
        devs = jax.devices()
        if devices > len(devs):
            raise ValueError(f"requested {devices} devices, "
                             f"only {len(devs)} visible")
        devs = devs[:devices]
    else:
        devs = list(devices)
    return Mesh(np.asarray(devs), (SLOT_AXIS,))


def slot_spec(ndim: int, axis: int = 0) -> P:
    """PartitionSpec sharding dimension ``axis`` of a rank-``ndim`` tensor.

    Membrane slabs are ``(N, Hp, Wp, C)`` -> ``slot_spec(4, 0)``;
    collector tensors are window-major ``(W, N, ...)`` ->
    ``slot_spec(ndim, 1)``.
    """
    return P(*[SLOT_AXIS if i == axis else None for i in range(ndim)])


def slot_sharding(mesh: Mesh, ndim: int, axis: int = 0) -> NamedSharding:
    """NamedSharding for a tensor slot-sharded along ``axis``."""
    return NamedSharding(mesh, slot_spec(ndim, axis))


def replicated(mesh: Mesh) -> NamedSharding:
    """NamedSharding replicating a tensor across the whole mesh (weights)."""
    return NamedSharding(mesh, P())


# ---------------------------------------------------------------------------
# Logical-axis rules — the LM model-stack surface.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class MeshRules:
    """Logical-axis -> physical mesh axis mapping."""

    rules: Tuple[Tuple[str, Axis], ...]

    def get(self, name: Optional[str]) -> Axis:
        """Look up the physical axis for one logical name (None = repl)."""
        if name is None:
            return None
        for k, v in self.rules:
            if k == name:
                return v
        return None

    def spec(self, axes: Sequence[Optional[str]], shape: Sequence[int],
             mesh: Mesh) -> P:
        """Resolve logical axes to a PartitionSpec, dropping non-divisible
        mappings (replication fallback) and duplicate mesh-axis uses."""
        out = []
        used: set = set()
        for name, dim in zip(axes, shape):
            phys = self.get(name)
            if phys is None:
                out.append(None)
                continue
            phys_t = (phys,) if isinstance(phys, str) else tuple(phys)
            # drop axes already used by an earlier dim of this tensor
            phys_t = tuple(a for a in phys_t if a not in used)
            size = int(np.prod([mesh.shape[a] for a in phys_t])) if phys_t else 1
            if not phys_t or dim % size != 0:
                # try the largest divisible prefix (e.g. ("pod","data"))
                while phys_t and dim % int(
                        np.prod([mesh.shape[a] for a in phys_t])) != 0:
                    phys_t = phys_t[:-1]
                if not phys_t:
                    out.append(None)
                    continue
            used.update(phys_t)
            out.append(phys_t[0] if len(phys_t) == 1 else phys_t)
        return P(*out)

    def sharding(self, axes: Sequence[Optional[str]], shape: Sequence[int],
                 mesh: Mesh) -> NamedSharding:
        """Resolve logical axes straight to a NamedSharding on ``mesh``."""
        return NamedSharding(mesh, self.spec(axes, shape, mesh))


def default_rules(multi_pod: bool, long_context: bool = False,
                  seq_shard: bool = False, serve: bool = False) -> MeshRules:
    """The production rule table (see module docstring).

    ``long_context=True`` switches the KV-sequence axes to fold in "data" as
    well — for B=1 half-megatoken decode the batch axis cannot shard, so the
    cache length takes both axes (flash-decoding over 256 shards).

    ``seq_shard=True`` selects the 2D fully-sharded layout (§Perf): the
    sequence axis shards over "model" instead of Megatron tensor
    parallelism, activations stay (batch x seq)-sharded through every
    layer (no per-layer TP all-reduces), and weights — still stored
    2D-FSDP-sharded — are gathered transiently at use (``use_*`` axes
    resolve to None).

    ``serve=True`` drops the FSDP axis (p_embed -> replicated over data):
    decode reads weights from local HBM instead of re-gathering them over
    ICI every token — FSDP-sharded storage is a training optimisation that
    is exactly wrong for serving (§Perf cell B).
    """
    batch: Axis = ("pod", "data") if multi_pod else ("data",)
    kv_seq: Axis = ("data", "model") if long_context else ("model",)
    tp: Axis = None if seq_shard else "model"
    p_embed: Axis = None if serve else "data"
    return MeshRules(rules=(
        # --- activations ---
        ("batch", batch),
        ("seq", "model" if seq_shard else None),
        ("act_embed", None),
        ("act_mlp", tp),
        ("act_heads", tp),
        ("act_kv_heads", tp),
        ("act_vocab", tp),
        # --- use-time weight constraints (ZeRO-3 gather discipline) ---
        ("use_mlp", tp),
        ("use_heads", tp),
        ("use_kv", tp),
        ("use_vocab", tp),
        ("use_embed", None if seq_shard else p_embed),
        ("kv_seq", kv_seq),           # decode-time KV cache length (SP)
        ("kv_window", kv_seq),        # sliding-window ring cache length
        # --- parameters ---
        ("p_embed", p_embed),        # FSDP shard of every weight's d_model
        ("p_mlp", "model"),           # TP: FFN inner
        ("p_heads", "model"),         # TP: attention heads
        ("p_kv_heads", "model"),
        ("p_vocab", "model"),         # TP: vocab/embedding
        ("p_experts", "model"),       # EP
        ("p_layers", None),           # stacked scan runs
        ("p_state", None),
    ))


# ---------------------------------------------------------------------------
# Global rule/mesh context so model code can annotate without plumbing.
# ---------------------------------------------------------------------------

_CTX: dict = {"rules": None, "mesh": None}


def set_mesh_rules(mesh: Mesh, rules: MeshRules) -> None:
    """Install the process-global mesh + rule table for :func:`logical`."""
    _CTX["mesh"] = mesh
    _CTX["rules"] = rules


def clear_mesh_rules() -> None:
    """Remove the global mesh/rules (single-device tests, teardown)."""
    _CTX["mesh"] = None
    _CTX["rules"] = None


def current_mesh() -> Optional[Mesh]:
    """The globally-installed mesh, or None outside a launch context."""
    return _CTX["mesh"]


def logical(x: jax.Array, *axes: Optional[str]) -> jax.Array:
    """`with_sharding_constraint` through the logical-axis table.

    No-op when no mesh/rules are installed (single-device tests) so model
    code is unconditionally annotated.
    """
    mesh, rules = _CTX["mesh"], _CTX["rules"]
    if mesh is None or rules is None:
        return x
    spec = rules.spec(axes, x.shape, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
