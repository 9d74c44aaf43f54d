"""Sigma-delta event-gated decode: SNE's execution model on LM matvecs.

The paper's central mechanism — explicit events + static event capacity +
state updated only where events land — applied to the weight-read-bound
B=1 decode of the RG-LRU (recurrentgemma) stack:

  * each linear map keeps a **reference input** ``x_ref`` and its exact
    output ``y_ref = W^T x_ref``;
  * per step, the ``cap`` largest input deltas are *events*; only their
    weight rows are read and accumulated (``y += dx[idx] @ W[idx]``), the
    rest of the input is represented by the reference — weight-read bytes
    become proportional to the event count, exactly the paper's
    energy-to-information proportionality, with the static ``cap`` playing
    the event-FIFO role (overflow = untransmitted deltas, bounded by the
    sigma-delta loop instead of dropped);
  * ``cap == d_in`` reproduces the exact network bit-for-bit (tested), the
    knob trades accuracy for bytes the same way the paper's activity knob
    trades accuracy for energy.

State per matvec: ``x_ref (B, d_in) f32`` and ``y_ref (B, d_out) f32`` —
KBs per layer, riding in the decode cache.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp



def sd_cap(d_in: int, frac: float) -> int:
    """Event budget: ``frac`` of the input width, aligned and floored."""
    return max(8, min(d_in, int(round(d_in * frac))))


def _events(x: jnp.ndarray, x_ref: jnp.ndarray, cap: int):
    """Top-cap input deltas: (idx (B,cap), dx (B,cap), new x_ref)."""
    delta = x.astype(jnp.float32) - x_ref
    _, idx = jax.lax.top_k(jnp.abs(delta), cap)            # (B, cap)
    dx = jnp.take_along_axis(delta, idx, axis=1)           # (B, cap)
    x_ref = x_ref.at[jnp.arange(x.shape[0])[:, None], idx].add(dx)
    return idx, dx, x_ref


def _apply_events(w: jnp.ndarray, idx: jnp.ndarray, dx: jnp.ndarray,
                  y_ref: jnp.ndarray) -> jnp.ndarray:
    """Event-proportional read: y_ref + dx @ W[idx] (cap rows of W)."""
    B, cap = idx.shape
    wg = jnp.take(w, idx.reshape(-1), axis=0).reshape(B, cap, -1)
    return y_ref + jnp.einsum("bc,bcd->bd", dx, wg.astype(jnp.float32))


def sd_matvec(w: jnp.ndarray, x: jnp.ndarray, x_ref: jnp.ndarray,
              y_ref: jnp.ndarray, cap: int
              ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Event-gated ``y = x @ w`` with reference state.

    w: (d_in, d_out); x: (B, d_in); x_ref/y_ref: f32 references.
    Returns (y (B, d_out) in x.dtype, new x_ref, new y_ref).

    On a live mesh the sharded variant runs instead: a global-top-k gather
    against a 2D-sharded weight would force the partitioner to replicate
    the full matrix (measured: a 40x wire regression — §Perf cell C). The
    shard_map form selects events *per data-rank row shard* — SNE's
    per-cluster event FIFO — so each device reads only its own rows'
    events; the only collectives are two tiny psums (y partials and the
    x_ref update vector).
    """
    from repro.distributed.sharding import current_mesh
    mesh = current_mesh()
    if mesh is not None and "data" in mesh.shape \
            and w.shape[0] % mesh.shape["data"] == 0:
        return _sd_matvec_sharded(w, x, x_ref, y_ref, cap, mesh)
    idx, dx, x_ref = _events(x, x_ref, cap)
    y = _apply_events(w, idx, dx, y_ref)
    return y.astype(x.dtype), x_ref, y


def sd_matvec_pair(w1: jnp.ndarray, w2: jnp.ndarray, x: jnp.ndarray,
                   x_ref: jnp.ndarray, y1_ref: jnp.ndarray,
                   y2_ref: jnp.ndarray, cap: int):
    """Shared-input event set driving two weight reads (w_in/w_gate,
    ffn gate/up). Returns (y1, y2, x_ref', y1_ref', y2_ref')."""
    from repro.distributed.sharding import current_mesh
    mesh = current_mesh()
    if mesh is not None and "data" in mesh.shape \
            and w1.shape[0] % mesh.shape["data"] == 0:
        y1, xr, y1r = _sd_matvec_sharded(w1, x, x_ref, y1_ref, cap, mesh)
        y2, _, y2r = _sd_matvec_sharded(w2, x, x_ref, y2_ref, cap, mesh)
        return y1, y2, xr, y1r, y2r
    idx, dx, xr = _events(x, x_ref, cap)
    y1r = _apply_events(w1, idx, dx, y1_ref)
    y2r = _apply_events(w2, idx, dx, y2_ref)
    return y1r.astype(x.dtype), y2r.astype(x.dtype), xr, y1r, y2r


def _sd_matvec_sharded(w, x, x_ref, y_ref, cap, mesh):
    """Per-row-shard event selection (see sd_matvec docstring)."""
    from jax.sharding import PartitionSpec as P

    B, d_in = x.shape
    n_data = mesh.shape["data"]
    rows = d_in // n_data
    cap_local = max(4, min(rows, -(-cap // n_data)))
    model_in_w = "model" if w.shape[1] % mesh.shape.get("model", 1) == 0 \
        else None

    def body(w_l, xb, xr, yr_l):
        i = jax.lax.axis_index("data")
        delta = xb.astype(jnp.float32) - xr                # (B, d_in) repl
        dloc = jax.lax.dynamic_slice(delta, (0, i * rows), (B, rows))
        _, idxl = jax.lax.top_k(jnp.abs(dloc), cap_local)  # (B, cap_l)
        dxl = jnp.take_along_axis(dloc, idxl, axis=1)
        wg = jnp.take(w_l, idxl.reshape(-1), axis=0) \
            .reshape(B, cap_local, -1)                     # local rows only
        y_part = jnp.einsum("bc,bcd->bd", dxl, wg.astype(jnp.float32))
        y_l = yr_l + jax.lax.psum(y_part, "data")
        # x_ref update: scatter local events into a zero vector, psum
        upd = jnp.zeros_like(delta)
        upd = jax.lax.dynamic_update_slice(
            upd, jnp.zeros((B, rows), jnp.float32).at[
                jnp.arange(B)[:, None], idxl].add(dxl), (0, i * rows))
        xr_new = xr + jax.lax.psum(upd, "data")
        return y_l, xr_new

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P("data", model_in_w), P(None, None), P(None, None),
                  P(None, model_in_w)),
        out_specs=(P(None, model_in_w), P(None, None)),
        check_vma=False)
    y, x_ref_new = fn(w, x, x_ref, y_ref)
    return y.astype(x.dtype), x_ref_new, y


def sd_state_decls(n: int, B: int, d: int, lru: int, d_ff: int):
    """ParamDecl tree for one rglru-layer's sigma-delta references.

    Hidden-side output references (yin/ygate/yg/yu) stay model-sharded so
    the shard_map boundary never reshards them; input references must be
    replicated (the event selection reads the full delta vector).
    """
    from repro.models.layers import ParamDecl

    def ref(dim, shard=False):
        return ParamDecl((n, B, dim),
                         ("p_layers", "batch", "act_mlp" if shard else None),
                         init="zeros", dtype=jnp.float32)

    return {
        "x1_ref": ref(d), "yin_ref": ref(lru, True),
        "ygate_ref": ref(lru, True),
        "x2_ref": ref(lru), "yout_ref": ref(d),
        "xf_ref": ref(d), "yg_ref": ref(d_ff, True),
        "yu_ref": ref(d_ff, True),
        "xd_ref": ref(d_ff), "yd_ref": ref(d),
    }


def rglru_step_sd(p: Dict, x_t: jnp.ndarray, cache: Dict, sd: Dict,
                  act, frac: float) -> Tuple[jnp.ndarray, Dict, Dict]:
    """Event-gated RG-LRU block decode step (mirror of rglru_block_step)."""
    from repro.models.recurrent import rglru_step
    d = x_t.shape[-1]
    dt = x_t.dtype
    xf = x_t[:, 0, :]                                      # (B, d)
    cap_d = sd_cap(d, frac)
    L = p["w_in"].shape[1]
    cap_l = sd_cap(L, frac)

    # shared-input pair: one event set drives both weight reads
    y1, y2, sd_x1, sd_yin, sd_ygate = sd_matvec_pair(
        p["w_in"], p["w_gate"], xf, sd["x1_ref"], sd["yin_ref"],
        sd["ygate_ref"], cap_d)
    x1 = y1.astype(dt)
    gate = jax.nn.gelu(y2.astype(dt))
    # causal depthwise conv over the ring of the last W-1 inputs
    w = p["conv_w"].astype(dt)
    hist = cache["conv"]                                   # (B, W-1, L)
    window = jnp.concatenate([hist, x1[:, None, :]], axis=1)
    xc = jnp.einsum("bwl,wl->bl", window, w) + p["conv_b"].astype(dt)
    h_out, h_new = rglru_step(p, xc, cache["h"])
    x2 = h_out * gate                                      # (B, L)
    out, sd_x2, sd_yout = sd_matvec(p["w_out"], x2, sd["x2_ref"],
                                    sd["yout_ref"], cap_l)
    new_cache = {"h": h_new, "conv": window[:, 1:, :].astype(hist.dtype)}
    new_sd = dict(sd)
    new_sd.update(x1_ref=sd_x1, yin_ref=sd_yin, ygate_ref=sd_ygate,
                  x2_ref=sd_x2, yout_ref=sd_yout)
    return out[:, None, :], new_cache, new_sd


def ffn_step_sd(p: Dict, x_t: jnp.ndarray, sd: Dict, act_name: str,
                frac: float) -> Tuple[jnp.ndarray, Dict]:
    """Event-gated SwiGLU decode step."""
    from repro.models.layers import activation
    xf = x_t[:, 0, :]
    d = xf.shape[-1]
    f = p["gate"].shape[1]
    cap_d = sd_cap(d, frac)
    cap_f = sd_cap(f, frac)
    g, u, sd_xf, sd_yg, sd_yu = sd_matvec_pair(
        p["gate"], p["up"], xf, sd["xf_ref"], sd["yg_ref"], sd["yu_ref"],
        cap_d)
    g, u = g.astype(xf.dtype), u.astype(xf.dtype)
    h = activation(act_name)(g) * u                        # (B, f)
    y, sd_xd, sd_yd = sd_matvec(p["down"], h, sd["xd_ref"], sd["yd_ref"],
                                cap_f)
    new_sd = dict(sd)
    new_sd.update(xf_ref=sd_xf, yg_ref=sd_yg, yu_ref=sd_yu,
                  xd_ref=sd_xd, yd_ref=sd_yd)
    return y[:, None, :], new_sd


def read_bytes_per_layer(d: int, lru: int, d_ff: int, frac: float,
                         dtype_bytes: int = 2) -> float:
    """Analytic weight bytes read by one gated rglru layer per token."""
    cap_d = sd_cap(d, frac)
    cap_l = sd_cap(lru, frac)
    cap_f = sd_cap(d_ff, frac)
    return dtype_bytes * (2 * cap_d * lru      # w_in + w_gate rows
                          + cap_l * d          # w_out rows
                          + 2 * cap_d * d_ff   # ffn gate + up rows
                          + cap_f * d)         # ffn down rows
