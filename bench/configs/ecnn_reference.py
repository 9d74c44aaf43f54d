"""Plain dense reference of an event-camera spiking CNN (eCNN).

The reference against which every eCNN cell's answers are compared.  It
reads only a configuration file of ``bench/configs/`` and the integer
weight codes the benchmark made from the seed; it imports nothing of the
program under test.

The network is a chain of layers, each one of:

* ``pool``: spiking sum-pool, kernel == stride, one unit synapse per
  channel (``w[c]``);
* ``conv``: stride-1 cross-correlation with zero padding, weights
  ``(K, K, C_in, C_out)``;
* ``fc``: the input frame flattened row-major ``(H, W, C)``, weights
  ``(H*W*C, C_out)``.

Every layer runs the linearised LIF neuron of the paper (SNE,
arXiv:2204.10687, Sec. III-B) in the integer domain, at every timestep:

    v <- sign(v) * max(|v| - leak, 0)      leak toward zero
    v <- v + synaptic input                one add per synapse
    v <- clip(v, -clip, clip)              8-bit state
    s  = v >= threshold                    fire
    v <- v * (1 - s)                       reset to zero

Layer ``l`` at timestep ``t`` takes layer ``l-1``'s spikes of the same
timestep.  The answer of a request is the rate code of the last layer:
its spikes summed over time and space, per class.  Weights are integer
codes in float32 and every sum is an integer well inside float32's exact
range, so the answer is exact: float32 matrix products run at
``Precision.HIGHEST`` so no pass rounds an operand.

``state_bits`` below 8 gives the control: after each timestep the
membrane is stored as a ``state_bits`` code of the same range, a step of
``2 ** (8 - state_bits)`` (16 for int4), the next precision below the
int8 state the configuration states.
"""
from __future__ import annotations

import json
import math
from functools import lru_cache, partial
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
from jax.scipy.special import ndtri

HIGHEST = jax.lax.Precision.HIGHEST


def layer_shapes(cfg: Dict) -> List[Dict]:
    """Each layer of ``cfg`` with its ``in`` and ``out`` shapes filled in."""
    shape = tuple(cfg["input"])
    out = []
    for layer in cfg["layers"]:
        H, W, C = shape
        kind = layer["kind"]
        if kind == "conv":
            p, k = layer["padding"], layer["kernel"]
            o = (H + 2 * p - k + 1, W + 2 * p - k + 1, layer["out_channels"])
        elif kind == "pool":
            s = layer["stride"]
            o = (H // s, W // s, C)
        elif kind == "fc":
            o = (1, 1, layer["out_channels"])
        else:
            raise ValueError(f"unknown layer kind {kind!r}")
        out.append(dict(layer, **{"in": shape, "out": o}))
        shape = o
    return out


def fan_out(layer: Dict) -> int:
    """Synaptic operations one input event of ``layer`` triggers."""
    if layer["kind"] == "conv":
        return layer["kernel"] ** 2 * layer["out_channels"]
    if layer["kind"] == "pool":
        return 1
    return layer["out_channels"]


def weight_shapes(cfg: Dict) -> List[tuple]:
    """The weight-code array shape of every layer."""
    shapes = []
    for l in layer_shapes(cfg):
        H, W, C = l["in"]
        if l["kind"] == "conv":
            shapes.append((l["kernel"], l["kernel"], C, l["out_channels"]))
        elif l["kind"] == "pool":
            shapes.append((C,))
        else:
            shapes.append((H * W * C, l["out_channels"]))
    return shapes


def make_codes(cfg: Dict, seed: int) -> List[jax.Array]:
    """Integer weight codes of every layer, in float32, made on the device
    in one call.  A conv or fc layer's codes are the quantiles of
    ``N(0, code_std)`` at ``(i + 0.5) / n`` for its ``n`` synapses,
    rounded and clipped to int4 ``[-8, 7]``, in an order drawn from the
    seed: every seed gets the same multiset of weights, placed anew.
    Pool synapses are unit codes."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31)),
                             seed // (2 ** 31))
    stds = tuple(l.get("code_std") for l in cfg["layers"])
    return list(_codes(key, shapes=tuple(weight_shapes(cfg)), stds=stds))


@partial(jax.jit, static_argnames=("shapes", "stds"))
def _codes(key, *, shapes, stds):
    keys = jax.random.split(key, len(shapes))
    out = []
    for k, shape, std in zip(keys, shapes, stds):
        if std is None:
            out.append(jnp.ones(shape, jnp.float32))
            continue
        n = math.prod(shape)
        q = (jnp.arange(n, dtype=jnp.float32) + 0.5) / n
        z = jnp.clip(jnp.round(ndtri(q) * std), -8.0, 7.0)
        out.append(jax.random.permutation(k, z).reshape(shape))
    return tuple(out)


def _syn(layer: Dict, w: jax.Array, x: jax.Array) -> jax.Array:
    """Synaptic input of a batch of frames ``x`` (B, H, W, C)."""
    kind = layer["kind"]
    if kind == "conv":
        p = layer["padding"]
        return jax.lax.conv_general_dilated(
            x, w, window_strides=(1, 1), padding=[(p, p), (p, p)],
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST)
    if kind == "pool":
        s = layer["stride"]
        B, H, W, C = x.shape
        x = x[:, :H // s * s, :W // s * s]
        x = x.reshape(B, H // s, s, W // s, s, C).sum(axis=(2, 4))
        return x * w
    B = x.shape[0]
    return jnp.dot(x.reshape(B, -1), w, precision=HIGHEST)[:, None, None, :]


def _lif(layer: Dict, clip: float, v, syn, state_step):
    leak = float(layer["leak"])
    v = jnp.sign(v) * jnp.maximum(jnp.abs(v) - leak, 0.0)
    v = jnp.clip(v + syn, -clip, clip)
    s = (v >= float(layer["threshold"])).astype(jnp.float32)
    v = v * (1.0 - s)
    if state_step > 1:
        v = jnp.clip(jnp.round(v / state_step) * state_step, -clip, clip)
    return v, s


def forward(cfg: Dict, codes: Sequence[jax.Array], spikes: jax.Array,
            state_bits: int = 8):
    """Run a batch of recordings ``spikes`` (B, T, H, W, C).

    Returns ``(class_counts (B, n_classes), layer_spikes (B, T, L))``:
    the answers, and every layer's output spikes per timestep (the work
    counts of ``bench/work.py``).
    """
    fwd = _compiled(json.dumps(cfg, sort_keys=True), state_bits)
    return fwd(spikes, tuple(codes))


@lru_cache(maxsize=8)
def _compiled(cfg_json: str, state_bits: int):
    cfg = json.loads(cfg_json)
    return jax.jit(partial(_forward, layer_shapes(cfg),
                           float(cfg["state_clip"]), state_bits))


def _forward(layers, clip, state_bits, spikes, codes):
    # below 8 bits the membrane is kept on a coarser grid of the same range
    state_step = 2.0 ** max(8 - state_bits, 0)
    B = spikes.shape[0]
    v0 = tuple(jnp.zeros((B,) + l["out"], jnp.float32) for l in layers)

    def step(vs, x):
        vs = list(vs)
        n_out = []
        for i, (layer, w) in enumerate(zip(layers, codes)):
            vs[i], x = _lif(layer, clip, vs[i], _syn(layer, w, x),
                            state_step)
            n_out.append(jnp.sum(x, axis=(1, 2, 3)))
        return tuple(vs), (jnp.sum(x, axis=(1, 2)), jnp.stack(n_out, -1))

    _, (out, n) = jax.lax.scan(step, v0, jnp.swapaxes(spikes, 0, 1))
    return out.sum(axis=0), jnp.swapaxes(n, 0, 1)
