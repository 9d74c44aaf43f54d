"""Attention: blockwise (flash-style) training/prefill + decode paths.

Design notes (TPU roofline driven):

* **Blockwise online-softmax attention** — S x S score matrices are never
  materialised; q is processed in ``chunk_q`` tiles, each scanning kv in
  ``chunk_kv`` tiles carrying ``(acc, m, l)`` running softmax state. Live
  memory per step is ``B*Cq*H*Ckv`` — independent of sequence length,
  which is what makes the 32k prefill and 512k decode shapes lowerable.

* **Folded causal schedule** (``fold=True``, a beyond-paper optimisation,
  see EXPERIMENTS.md §Perf): plain blockwise causal attention computes all
  Nq x Nkv block pairs and masks half of them away — 2x the useful FLOPs.
  Folding pairs q-chunk ``p`` with q-chunk ``Nq-1-p``: the pair needs
  ``(p+1) + (Nq-p) = Nq+1`` kv blocks in total, a *constant*, so a scan of
  ``Nq+1`` steps per pair (each step routing one kv block to whichever
  member needs it) executes exactly the lower-triangular blocks. HLO FLOPs
  drop by ~2x at long sequence; this is the same load-balance trick striped
  /ring attention uses across devices, applied to a single core's schedule.

* **GQA** is computed in grouped form (q reshaped ``(B, S, Hk, G, hd)``)
  so kv tiles are contracted once per kv head, not once per q head.

* **Decode** is an einsum + masked softmax over the cache — O(S) per new
  token. The KV cache is sequence-sharded (SP) on the "model" axis; the
  baseline path lets XLA SPMD insert the partial-softmax reductions, and
  ``flash_decode_shardmap`` provides the explicit flash-decoding combine
  (max/sum/weighted-value psum) used by the optimised serve path.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.models.scan_util import xscan

NEG_INF = -1e30


def _mask_bias(q_pos: jnp.ndarray, kv_pos: jnp.ndarray, causal: bool,
               window: int, kv_len: Optional[int]) -> jnp.ndarray:
    """(…, Sq, Skv) additive bias: 0 where attendable, NEG_INF elsewhere."""
    ok = jnp.ones(q_pos.shape + kv_pos.shape, bool)
    if causal:
        ok &= kv_pos[None, :] <= q_pos[:, None]
    if window > 0:
        ok &= kv_pos[None, :] > (q_pos[:, None] - window)
    if kv_len is not None:
        ok &= (kv_pos < kv_len)[None, :]
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def _block_update(q, k, v, bias, acc, m, l, scale):
    """One online-softmax update. q:(B,Cq,Hk,G,hd) k/v:(B,Ckv,Hk,hd)."""
    s = jnp.einsum("bqkgd,bskd->bkgqs", q, k).astype(jnp.float32) * scale
    s = s + bias[None, None, None]                      # (B,Hk,G,Cq,Ckv)
    m_new = jnp.maximum(m, s.max(axis=-1))              # (B,Hk,G,Cq)
    p = jnp.exp(s - m_new[..., None])
    corr = jnp.exp(m - m_new)
    l_new = l * corr + p.sum(axis=-1)
    pv = jnp.einsum("bkgqs,bskd->bkgqd", p.astype(v.dtype), v)
    acc_new = acc * corr[..., None] + pv.astype(jnp.float32)
    return acc_new, m_new, l_new


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool, window: int = 0,
                    chunk_q: int = 1024, chunk_kv: int = 1024,
                    kv_len: Optional[int] = None,
                    fold: bool = False) -> jnp.ndarray:
    """Blockwise attention. q: (B,Sq,H,hd); k,v: (B,Skv,Hk,hd) -> (B,Sq,H,hd).

    ``fold=True`` activates the folded causal schedule (requires ``causal``
    and no window; falls back silently otherwise).
    """
    B, Sq, H, hd = q.shape
    Skv, Hk = k.shape[1], k.shape[2]
    G = H // Hk
    scale = hd ** -0.5
    Cq, Ckv = min(chunk_q, Sq), min(chunk_kv, Skv)
    if Sq % Cq or Skv % Ckv:
        # pad to chunk multiples; padded kv masked via kv_len, padded q rows
        # are computed on garbage and sliced off below.
        Sq_p = -(-Sq // Cq) * Cq
        Skv_p = -(-Skv // Ckv) * Ckv
        if kv_len is None:
            kv_len = Skv
        qp = jnp.pad(q, ((0, 0), (0, Sq_p - Sq), (0, 0), (0, 0)))
        kp = jnp.pad(k, ((0, 0), (0, Skv_p - Skv), (0, 0), (0, 0)))
        vp = jnp.pad(v, ((0, 0), (0, Skv_p - Skv), (0, 0), (0, 0)))
        out = flash_attention(qp, kp, vp, causal=causal, window=window,
                              chunk_q=Cq, chunk_kv=Ckv, kv_len=kv_len,
                              fold=fold)
        return out[:, :Sq]
    Nq, Nkv = Sq // Cq, Skv // Ckv

    qg = q.reshape(B, Nq, Cq, Hk, G, hd)
    kc = k.reshape(B, Nkv, Ckv, Hk, hd)
    vc = v.reshape(B, Nkv, Ckv, Hk, hd)

    if fold and causal and window == 0 and Sq == Skv and Cq == Ckv \
            and Nq % 2 == 0 and Nq >= 2:
        out = _folded_causal(qg, kc, vc, scale, kv_len)
    else:
        out = _plain_blockwise(qg, kc, vc, scale, causal, window, kv_len)
    return out.reshape(B, Sq, H, hd).astype(q.dtype)


def _plain_blockwise(qg, kc, vc, scale, causal, window, kv_len):
    B, Nq, Cq, Hk, G, hd = qg.shape
    Nkv, Ckv = kc.shape[1], kc.shape[2]

    def q_step(_, qi):
        qb, iq = qi                                     # (B,Cq,Hk,G,hd), idx
        q_pos = iq * Cq + jnp.arange(Cq)

        def kv_step(carry, kvj):
            acc, m, l = carry
            kb, vb, jk = kvj
            kv_pos = jk * Ckv + jnp.arange(Ckv)
            bias = _mask_bias(q_pos, kv_pos, causal, window, kv_len)
            acc, m, l = _block_update(qb, kb, vb, bias, acc, m, l, scale)
            return (acc, m, l), None

        acc0 = jnp.zeros((B, Hk, G, Cq, hd), jnp.float32)
        m0 = jnp.full((B, Hk, G, Cq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Hk, G, Cq), jnp.float32)
        (acc, m, l), _ = xscan(
            kv_step, (acc0, m0, l0),
            (kc.swapaxes(0, 1), vc.swapaxes(0, 1),
             jnp.arange(Nkv)))
        o = acc / jnp.maximum(l, 1e-30)[..., None]      # (B,Hk,G,Cq,hd)
        return None, o.transpose(0, 3, 1, 2, 4)         # (B,Cq,Hk,G,hd)

    _, outs = xscan(q_step, None,
                    (qg.swapaxes(0, 1), jnp.arange(Nq)))
    return outs.transpose(1, 0, 2, 3, 4, 5)             # (B,Nq,Cq,Hk,G,hd)


def _folded_causal(qg, kc, vc, scale, kv_len):
    """Folded schedule: exactly the lower-triangular blocks are computed."""
    B, Nq, Cq, Hk, G, hd = qg.shape
    Ckv = kc.shape[2]
    n_pairs = Nq // 2

    def pair_step(_, p):
        ia = p                       # low q chunk: needs kv blocks 0..p
        ib = Nq - 1 - p              # high q chunk: needs kv blocks 0..Nq-1-p
        qa = jax.lax.dynamic_index_in_dim(qg, ia, 1, keepdims=False)
        qb = jax.lax.dynamic_index_in_dim(qg, ib, 1, keepdims=False)
        pos_a = ia * Cq + jnp.arange(Cq)
        pos_b = ib * Cq + jnp.arange(Cq)

        def kv_step(carry, j):
            acc_a, m_a, l_a, acc_b, m_b, l_b = carry
            to_a = j <= p
            kv_idx = jnp.where(to_a, j, j - p - 1)
            kb = jax.lax.dynamic_index_in_dim(kc, kv_idx, 1, keepdims=False)
            vb = jax.lax.dynamic_index_in_dim(vc, kv_idx, 1, keepdims=False)
            kv_pos = kv_idx * Ckv + jnp.arange(Ckv)
            q_sel = jnp.where(to_a, qa, qb)
            q_pos = jnp.where(to_a, pos_a, pos_b)
            bias = _mask_bias(q_pos, kv_pos, True, 0, kv_len)
            acc_i = jnp.where(to_a, acc_a, acc_b)
            m_i = jnp.where(to_a, m_a, m_b)
            l_i = jnp.where(to_a, l_a, l_b)
            acc_n, m_n, l_n = _block_update(q_sel, kb, vb, bias,
                                            acc_i, m_i, l_i, scale)
            acc_a = jnp.where(to_a, acc_n, acc_a)
            m_a = jnp.where(to_a, m_n, m_a)
            l_a = jnp.where(to_a, l_n, l_a)
            acc_b = jnp.where(to_a, acc_b, acc_n)
            m_b = jnp.where(to_a, m_b, m_n)
            l_b = jnp.where(to_a, l_b, l_n)
            return (acc_a, m_a, l_a, acc_b, m_b, l_b), None

        z = jnp.zeros((B, Hk, G, Cq, hd), jnp.float32)
        neg = jnp.full((B, Hk, G, Cq), NEG_INF, jnp.float32)
        zl = jnp.zeros((B, Hk, G, Cq), jnp.float32)
        (acc_a, m_a, l_a, acc_b, m_b, l_b), _ = xscan(
            kv_step, (z, neg, zl, z, neg, zl), jnp.arange(Nq + 1))
        oa = (acc_a / jnp.maximum(l_a, 1e-30)[..., None]).transpose(0, 3, 1, 2, 4)
        ob = (acc_b / jnp.maximum(l_b, 1e-30)[..., None]).transpose(0, 3, 1, 2, 4)
        return None, (oa, ob)

    _, (oas, obs) = xscan(pair_step, None, jnp.arange(n_pairs))
    # oas[p] is q-chunk p; obs[p] is q-chunk Nq-1-p. Reassemble in order.
    oas = oas.transpose(1, 0, 2, 3, 4, 5)               # (B, n_pairs, ...)
    obs = obs.transpose(1, 0, 2, 3, 4, 5)[:, ::-1]      # chunks Nq/2..Nq-1
    return jnp.concatenate([oas, obs], axis=1)


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------

def decode_attention(q: jnp.ndarray, k_cache: jnp.ndarray,
                     v_cache: jnp.ndarray, pos: jnp.ndarray,
                     window: int = 0) -> jnp.ndarray:
    """One-token attention against a (possibly sequence-sharded) cache.

    q: (B, 1, H, hd); caches: (B, S, Hk, hd); pos: () or (B,) current
    position (per-slot positions support continuous batching).
    Slots with index > pos (or outside the sliding window) are masked. The
    softmax runs in f32; with the cache sharded over "model" on S, XLA SPMD
    lowers max/sum/PV into partial reductions + all-reduce (flash-decoding).
    """
    B, S, Hk, hd = k_cache.shape
    H = q.shape[2]
    G = H // Hk
    pos_b = jnp.broadcast_to(jnp.asarray(pos), (B,))
    qg = q.reshape(B, Hk, G, hd)
    s = jnp.einsum("bkgd,bskd->bkgs", qg, k_cache).astype(jnp.float32)
    s *= hd ** -0.5
    idx = jnp.arange(S)
    ok = idx[None, :] <= pos_b[:, None]                      # (B, S)
    if window > 0:
        ok &= idx[None, :] > (pos_b[:, None] - window)
    s = jnp.where(ok[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", p.astype(v_cache.dtype), v_cache)
    return o.reshape(B, 1, H, hd).astype(q.dtype)


def flash_decode_shardmap(q: jnp.ndarray, k_cache: jnp.ndarray,
                          v_cache: jnp.ndarray, pos: jnp.ndarray,
                          mesh: Mesh, seq_axes: Tuple[str, ...],
                          batch_axis: Optional[str] = "data",
                          window: int = 0) -> jnp.ndarray:
    """Explicit flash-decoding: each sequence shard computes a partial
    softmax (max, sum, weighted values); shards combine with three psums.

    This replaces XLA's derived schedule with the hand-scheduled one the
    flash-decoding paper uses; collective volume per layer drops from
    O(S_shard) worst case to O(B*H*hd) — measurable in §Perf.
    """
    B, S, Hk, hd = k_cache.shape
    H = q.shape[2]
    G = H // Hk
    shard_s = S // int(jax.numpy.prod(
        jnp.array([mesh.shape[a] for a in seq_axes])))
    bspec = batch_axis if (batch_axis and B % mesh.shape[batch_axis] == 0
                           and B >= mesh.shape[batch_axis]) else None

    q_spec = P(bspec, None, None, None)
    kv_spec = P(bspec, seq_axes if len(seq_axes) > 1 else seq_axes[0],
                None, None)

    def local(qb, kb, vb, pos_s):
        ax_idx = 0
        for a in seq_axes:
            ax_idx = ax_idx * mesh.shape[a] + jax.lax.axis_index(a)
        base = ax_idx * shard_s
        idx = base + jnp.arange(shard_s)
        qg = qb.reshape(qb.shape[0], Hk, G, hd)
        s = jnp.einsum("bkgd,bskd->bkgs", qg, kb).astype(jnp.float32)
        s *= hd ** -0.5
        ok = idx <= pos_s
        if window > 0:
            ok &= idx > (pos_s - window)
        s = jnp.where(ok[None, None, None, :], s, NEG_INF)
        m = s.max(axis=-1)                               # (b,Hk,G)
        m_g = jax.lax.pmax(m, seq_axes)
        p = jnp.exp(s - m_g[..., None])
        l = p.sum(axis=-1)
        l_g = jax.lax.psum(l, seq_axes)
        o = jnp.einsum("bkgs,bskd->bkgd", p.astype(vb.dtype), vb)
        o_g = jax.lax.psum(o.astype(jnp.float32), seq_axes)
        o_g = o_g / jnp.maximum(l_g, 1e-30)[..., None]
        return o_g.reshape(qb.shape[0], 1, H, hd).astype(qb.dtype)

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(q_spec, kv_spec, kv_spec, P()),
        out_specs=q_spec,
        check_vma=False)
    return fn(q, k_cache, v_cache, pos)


# ---------------------------------------------------------------------------
# Cache update
# ---------------------------------------------------------------------------

def cache_insert(cache: jnp.ndarray, new: jnp.ndarray,
                 pos: jnp.ndarray) -> jnp.ndarray:
    """Write one token's k/v at ``pos`` (ring-indexed by the caller if the
    cache is a sliding window). cache: (B,S,Hk,hd); new: (B,1,Hk,hd)."""
    return jax.lax.dynamic_update_slice(
        cache, new.astype(cache.dtype), (0, pos, 0, 0))
