"""Work counts and the plain reference against a brute-force count.

An independent event-by-event simulation of the tiny network, written
with Python loops, scatters every input event into the membranes one
synapse at a time and counts each update.  The reference's answers and
per-layer spikes must equal it, ``work.sops`` must equal its update count,
and ``work.kernel_work`` must equal the bytes and operations counted
array by array.
"""
from __future__ import annotations

import json
from pathlib import Path

import jax.numpy as jnp
import numpy as np

from bench import traffic, work
from bench.configs import ecnn_reference as ref

HERE = Path(__file__).resolve().parent
CFG = json.loads((HERE / "tiny_ecnn.json").read_text())
MIX = {"activity_band": [0.05, 0.08], "pool_size": 3, "n_blobs": 1}
SEED = 2 ** 32 + 99


def brute_force(layers, codes, spikes):
    """(class counts, spikes per layer and timestep, updates per layer)."""
    T = spikes.shape[0]
    v = [np.zeros(l["out"]) for l in layers]
    n_spikes = np.zeros((T, len(layers)))
    updates = np.zeros(len(layers))
    counts = np.zeros(layers[-1]["out"][2])
    for t in range(T):
        frame = spikes[t]
        for i, (l, w) in enumerate(zip(layers, codes)):
            Ho, Wo, Co = l["out"]
            syn = np.zeros(l["out"])
            for y, x, c in zip(*np.nonzero(frame)):
                if l["kind"] == "conv":
                    K, p = l["kernel"], l["padding"]
                    for ky in range(K):
                        for kx in range(K):
                            for co in range(Co):
                                updates[i] += 1
                                oy, ox = y + p - ky, x + p - kx
                                if 0 <= oy < Ho and 0 <= ox < Wo:
                                    syn[oy, ox, co] += w[ky, kx, c, co]
                elif l["kind"] == "pool":
                    s = l["stride"]
                    updates[i] += 1
                    if y // s < Ho and x // s < Wo:
                        syn[y // s, x // s, c] += w[c]
                else:
                    H, W, C = l["in"]
                    for co in range(Co):
                        updates[i] += 1
                        syn[0, 0, co] += w[(y * W + x) * C + c, co]
            vi = np.sign(v[i]) * np.maximum(np.abs(v[i]) - l["leak"], 0)
            vi = np.clip(vi + syn, -CFG["state_clip"], CFG["state_clip"])
            fired = (vi >= l["threshold"]).astype(float)
            v[i] = vi * (1 - fired)
            frame = fired
            n_spikes[t, i] = fired.sum()
        counts += frame.reshape(-1, frame.shape[-1]).sum(axis=0)
    return counts, n_spikes, updates


def setup():
    layers = ref.layer_shapes(CFG)
    codes = ref.make_codes(CFG, SEED)
    pool = traffic.make_pool(SEED, MIX, tuple(CFG["input"]),
                             CFG["n_timesteps"], CFG["n_classes"])
    return layers, codes, pool


def test_reference_and_sops_match_brute_force():
    layers, codes, pool = setup()
    x = jnp.stack([pool.dense(p) for p in range(len(pool))])
    cc, n = (np.asarray(a) for a in ref.forward(CFG, codes, x))
    fan = [ref.fan_out(l) for l in layers]
    host_codes = [np.asarray(c) for c in codes]
    for p in range(len(pool)):
        want, spikes, updates = brute_force(layers, host_codes,
                                            np.asarray(x[p]))
        assert np.array_equal(cc[p], want)
        assert np.array_equal(n[p], spikes)
        assert n[p][:, -1].sum() > 0          # the net does answer
        ev = work.input_events(
            np.full(CFG["n_timesteps"], pool.counts[p]), n[p])
        assert np.array_equal(ev[:, 0].sum(), np.asarray(x[p]).sum())
        assert np.array_equal(work.sops(ev, fan).sum(axis=0), updates)


def test_pool_counts_are_exact():
    _, _, pool = setup()
    for p in range(len(pool)):
        dense = np.asarray(pool.dense(p))
        per_t = dense.reshape(CFG["n_timesteps"], -1).sum(axis=1)
        assert (per_t == pool.counts[p]).all()
        t, y, x, c = pool.events[p]
        assert len(t) == pool.n_events(p)
        assert dense[t, y, x, c].all()


def test_kernel_work_counts_every_array():
    layers, _, _ = setup()
    fan = [ref.fan_out(l) for l in layers]
    n_slots, steps = 3, 4
    events = np.asarray([40.0, 300.0, 25.0])
    got = work.kernel_work(layers, fan, n_slots, steps, events)
    for i, l in enumerate(layers):
        H, W, C = l["in"]
        Ho, Wo, Co = l["out"]
        membrane = np.zeros((n_slots, Ho, Wo, Co), np.float32)
        frames = np.zeros((n_slots, steps, Ho, Wo, Co), np.float32)
        weights = np.zeros(ref.weight_shapes(CFG)[i], np.float32)
        want_bytes = (2 * membrane.nbytes + frames.nbytes + weights.nbytes
                      + 4 * events[i])
        want_ops = events[i] * fan[i] + membrane.size * steps
        assert got[i, 1] == want_bytes
        assert got[i, 0] == want_ops
    t, bytes_bound = work.least_seconds(got, 393e12, 819e9)
    assert bytes_bound.all()
    assert np.allclose(t, got[:, 1] / 819e9)
