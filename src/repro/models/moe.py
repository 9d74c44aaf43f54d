"""Mixture-of-Experts with gather-based static-capacity dispatch (EP).

The SNE connection (DESIGN.md §Arch-applicability): top-k routing is the
LM-scale version of the paper's energy-proportional principle — compute is
performed only for routed "token events", and the static expert capacity
plays exactly the role of SNE's event-FIFO capacity (overflow tokens are
dropped and *counted*, the same back-pressure accounting as the event path).

Dispatch strategy: instead of the Switch-style one-hot dispatch einsum
(which adds a fake ``T x E x C x d`` FLOP term), each expert *gathers* its
top-C tokens (top_k over the masked router scores), runs a dense per-expert
GEMM batch ``(E, C, d)``, and scatter-adds results back weighted by the
router probability. HLO FLOPs are the true ``E*C*(6*d*f)`` expert math plus
the tiny router GEMM, so the roofline table reads real arithmetic.

Sharding: experts over "model" (EP), tokens over "data" (DP). The baseline
lets XLA derive the dispatch collectives; the shard_map all-to-all variant
is a §Perf hillclimb (see launch/dryrun.py --moe=shardmap).
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import logical
from repro.models.layers import DeclTree, ParamDecl, ParamTree, activation


class MoeStats(NamedTuple):
    aux_loss: jnp.ndarray       # load-balance auxiliary loss
    dropped_frac: jnp.ndarray   # fraction of (token, expert) routes dropped


def moe_decls(d_model: int, n_experts: int, expert_ff: int,
              shared: bool, d_ff: int) -> DeclTree:
    d: DeclTree = {
        "router": ParamDecl((d_model, n_experts), ("p_embed", None),
                            scale=d_model ** -0.5),
        "gate": ParamDecl((n_experts, d_model, expert_ff),
                          ("p_experts", "p_embed", "p_mlp")),
        "up": ParamDecl((n_experts, d_model, expert_ff),
                        ("p_experts", "p_embed", "p_mlp")),
        "down": ParamDecl((n_experts, expert_ff, d_model),
                          ("p_experts", "p_mlp", "p_embed")),
    }
    if shared:
        d["shared"] = {
            "gate": ParamDecl((d_model, d_ff), ("p_embed", "p_mlp")),
            "up": ParamDecl((d_model, d_ff), ("p_embed", "p_mlp")),
            "down": ParamDecl((d_ff, d_model), ("p_mlp", "p_embed")),
        }
    return d


def _capacity(n_tokens: int, n_experts: int, top_k: int,
              factor: float) -> int:
    c = int(n_tokens * top_k * factor / n_experts)
    c = max(8, -(-c // 8) * 8)  # round up to 8 (sublane alignment)
    return min(c, n_tokens)     # decode: can't gather more than T tokens


def moe_apply(p: ParamTree, x: jnp.ndarray, *, n_experts: int, top_k: int,
              capacity_factor: float, act: str,
              shared: bool) -> Tuple[jnp.ndarray, MoeStats]:
    """x: (B, S, d) -> (B, S, d). Gather-dispatch MoE (see module doc)."""
    B, S, d = x.shape
    T = B * S
    E, K = n_experts, top_k
    C = _capacity(T, E, K, capacity_factor)
    xf = x.reshape(T, d)

    # --- routing (f32 for a stable softmax) ---
    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)              # (T, E)
    top_p, top_i = jax.lax.top_k(probs, K)               # (T, K)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    # selection mask: gate value where expert e is in token t's top-k
    sel = jnp.zeros((T, E), jnp.float32)
    sel = sel.at[jnp.arange(T)[:, None], top_i].set(top_p)

    # --- per-expert top-C token choice (capacity) ---
    scores_et = jnp.where(sel.T > 0, sel.T, -1.0)        # (E, T)
    gate_ec, idx_ec = jax.lax.top_k(scores_et, C)        # (E, C)
    valid = (gate_ec > 0).astype(jnp.float32)
    gate_ec = gate_ec * valid

    # --- gather -> expert FFN -> weighted scatter-add ---
    xe = jnp.take(xf, idx_ec.reshape(-1), axis=0).reshape(E, C, d)
    xe = logical(xe, "p_experts", None, None)
    dt = x.dtype
    g = jnp.einsum("ecd,edf->ecf", xe.astype(dt), p["gate"].astype(dt))
    u = jnp.einsum("ecd,edf->ecf", xe.astype(dt), p["up"].astype(dt))
    h = activation(act)(g) * u
    ye = jnp.einsum("ecf,efd->ecd", h, p["down"].astype(dt))
    ye = ye * gate_ec[..., None].astype(dt)

    out = jnp.zeros((T, d), jnp.float32)
    out = out.at[idx_ec.reshape(-1)].add(
        ye.reshape(E * C, d).astype(jnp.float32))
    out = out.astype(dt).reshape(B, S, d)

    if shared:
        sp = p["shared"]
        g = jnp.einsum("bsd,df->bsf", x, sp["gate"].astype(dt))
        u = jnp.einsum("bsd,df->bsf", x, sp["up"].astype(dt))
        out = out + jnp.einsum("bsf,fd->bsd", activation(act)(g) * u,
                               sp["down"].astype(dt))

    # --- stats: Switch-style aux loss + capacity-drop accounting ---
    frac_routed = (sel > 0).astype(jnp.float32).mean(0)   # tokens per expert
    mean_prob = probs.mean(0)
    aux = E * jnp.sum(frac_routed * mean_prob)
    n_routes = jnp.sum(sel > 0)
    n_kept = jnp.sum(valid)
    dropped = 1.0 - n_kept / jnp.maximum(n_routes, 1.0)
    return out, MoeStats(aux_loss=aux, dropped_frac=dropped)


# ---------------------------------------------------------------------------
# shard_map expert-parallel dispatch (§Perf hillclimb: llama4 train_4k)
# ---------------------------------------------------------------------------


def moe_apply_shardmap(p: ParamTree, x: jnp.ndarray, *, n_experts: int,
                       top_k: int, capacity_factor: float, act: str,
                       shared: bool, mesh, model_axis: str = "model",
                       seq_shard: bool = False) -> Tuple[jnp.ndarray, MoeStats]:
    """Expert-parallel MoE: local routing + all-to-all dispatch.

    The baseline gather dispatch tops-k over the GLOBAL token axis, which
    forces the SPMD partitioner to replicate the (T, d) token matrix across
    the mesh (the dominant collective in the llama4 train_4k profile). Here
    each device routes only ITS token shard:

      * per-(shard, expert) static capacity bounds the dispatch batch —
        the event-FIFO discipline again, now per shard;
      * tokens travel to their expert's owner with one all_to_all over
        "model" (O(T_local x K x d) bf16) and return the same way — no
        re-replication, no psum combine;
      * expert weights stay 2D-FSDP stored; the d-axis gather over "data"
        is the inherent ZeRO-3 cost.

    ``seq_shard=True`` matches the 2D fully-sharded activation layout
    (tokens sharded over data x model).
    """
    import numpy as np
    from jax.sharding import PartitionSpec as P

    B, S, d = x.shape
    E, K = n_experts, top_k
    n_model = mesh.shape[model_axis]
    data_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    n_data = int(np.prod([mesh.shape[a] for a in data_axes]))
    if E % n_model or B % n_data or (seq_shard and S % n_model):
        return moe_apply(p, x, n_experts=E, top_k=K,
                         capacity_factor=capacity_factor, act=act,
                         shared=shared)
    T_local = (B // n_data) * (S // (n_model if seq_shard else 1))
    C = _capacity(T_local, E, K, capacity_factor)
    fsdp_axis = "data" if "data" in mesh.shape else None

    def body(xb, router_w, gate_w, up_w, down_w):
        dt = xb.dtype
        # explicit FSDP gather of this rank's expert weights (d axis)
        if fsdp_axis is not None:
            gate_w = jax.lax.all_gather(gate_w, fsdp_axis, axis=1,
                                        tiled=True)
            up_w = jax.lax.all_gather(up_w, fsdp_axis, axis=1, tiled=True)
            down_w = jax.lax.all_gather(down_w, fsdp_axis, axis=2,
                                        tiled=True)
        xf = xb.reshape(-1, d)                                # (T_loc, d)
        logits = jnp.einsum("td,de->te", xf.astype(jnp.float32),
                            router_w.astype(jnp.float32))
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_i = jax.lax.top_k(probs, K)
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
        sel = jnp.zeros((xf.shape[0], E), jnp.float32)
        sel = sel.at[jnp.arange(xf.shape[0])[:, None], top_i].set(top_p)
        # local per-(shard, expert) capacity selection, ALL experts
        scores = jnp.where(sel.T > 0, sel.T, -1.0)            # (E, T_loc)
        gate_ec, idx_ec = jax.lax.top_k(scores, C)            # (E, C)
        valid = (gate_ec > 0).astype(jnp.float32)
        gate_ec = gate_ec * valid
        xe = jnp.take(xf, idx_ec.reshape(-1), axis=0) \
            .reshape(E, C, d).astype(dt)
        if n_model > 1:
            # dispatch: rows for expert-set j travel to model rank j
            xe = jax.lax.all_to_all(xe, model_axis, split_axis=0,
                                    concat_axis=1, tiled=True)
        # xe: (E_local, C * n_model, d) — this rank's experts, all shards
        g = jnp.einsum("ecd,edf->ecf", xe, gate_w.astype(dt))
        u = jnp.einsum("ecd,edf->ecf", xe, up_w.astype(dt))
        h = activation(act)(g) * u
        ye = jnp.einsum("ecf,efd->ecd", h, down_w.astype(dt))
        if n_model > 1:
            # return trip: back to the token owners
            ye = jax.lax.all_to_all(ye, model_axis, split_axis=1,
                                    concat_axis=0, tiled=True)
        ye = ye * gate_ec[..., None].astype(dt)               # (E, C, d)
        out = jnp.zeros((xf.shape[0], d), jnp.float32)
        out = out.at[idx_ec.reshape(-1)].add(
            ye.reshape(E * C, d).astype(jnp.float32))
        # stats (local shard; averaged across the mesh)
        frac_routed = (sel > 0).astype(jnp.float32).mean(0)
        aux = E * jnp.sum(frac_routed * probs.mean(0))
        n_routes = jnp.sum(sel > 0)
        n_kept = jnp.sum(valid)
        dropped = 1.0 - n_kept / jnp.maximum(n_routes, 1.0)
        mean_axes = data_axes + ((model_axis,) if seq_shard else ())
        if mean_axes:
            aux = jax.lax.pmean(aux, mean_axes)
            dropped = jax.lax.pmean(dropped, mean_axes)
        return (out.astype(dt).reshape(xb.shape), aux[None], dropped[None])

    d_ax = (data_axes if len(data_axes) > 1
            else (data_axes[0] if data_axes else None))
    batch_spec = P(d_ax, model_axis if seq_shard else None, None)
    fs = fsdp_axis
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(batch_spec,
                  P(None, None),                      # router replicated
                  P(model_axis, fs, None),            # gate (E, d, f)
                  P(model_axis, fs, None),            # up
                  P(model_axis, None, fs)),           # down (E, f, d)
        out_specs=(batch_spec, P(None), P(None)),
        check_vma=False)
    out, aux, dropped = fn(x, p["router"], p["gate"], p["up"], p["down"])

    if shared:
        dt = x.dtype
        sp = p["shared"]
        g = jnp.einsum("bsd,df->bsf", x, sp["gate"].astype(dt))
        u = jnp.einsum("bsd,df->bsf", x, sp["up"].astype(dt))
        out = out + jnp.einsum("bsf,fd->bsd", activation(act)(g) * u,
                               sp["down"].astype(dt))
    return out, MoeStats(aux_loss=aux[0], dropped_frac=dropped[0])
