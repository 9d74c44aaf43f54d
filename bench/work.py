"""Work counts: synaptic operations, and each kernel's operations and bytes.

All of it is computed from a configuration's layer shapes and from the
events each layer received, as the plain reference counts them
(``layer_spikes``: every layer's output spikes per timestep).  Nothing is
read from the program, so the count is the same whatever implements a
layer.

One synaptic operation (SOP) is one membrane update caused by one input
event: a conv event updates ``K*K*C_out`` neurons, a pool event one, an
fc event ``C_out`` (``ecnn_reference.fan_out``).

A layer kernel's least work for ``A`` slots over ``T`` timesteps of one
window, counted low so that a share of the roofline is never too high:

* operations: its SOPs, plus one threshold test per output neuron per
  timestep;
* bytes: the membrane read and written once (``2 * A * sites * 4``), the
  spike frames written once (``A * T * sites * 4``), the weights read
  once, and 4 bytes per real input event.  Halo padding, padded event
  slots and dummy batch rows are not counted.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

F32 = 4


def input_events(in_counts: np.ndarray, layer_spikes: np.ndarray) -> np.ndarray:
    """Input events of every layer per timestep, ``(T, L)``: layer 0 gets
    the recording's events ``in_counts`` (T,), layer ``l`` gets layer
    ``l-1``'s spikes of the same timestep."""
    return np.concatenate([np.asarray(in_counts, np.float64)[:, None],
                           np.asarray(layer_spikes, np.float64)[:, :-1]],
                          axis=1)


def sops(events_in: np.ndarray, fan_outs: Sequence[int]) -> np.ndarray:
    """SOPs per timestep and layer, ``(T, L)``."""
    return events_in * np.asarray(fan_outs, np.float64)[None, :]


def out_sites(layer: Dict) -> int:
    """Output neurons of one layer."""
    H, W, C = layer["out"]
    return H * W * C


def weight_bytes(layer: Dict) -> int:
    """Bytes of the layer's float32 weight codes."""
    H, W, C = layer["in"]
    if layer["kind"] == "conv":
        return layer["kernel"] ** 2 * C * layer["out_channels"] * F32
    if layer["kind"] == "pool":
        return C * F32
    return H * W * C * layer["out_channels"] * F32


def kernel_work(layers: List[Dict], fan_outs: Sequence[int], n_slots: int,
                n_steps: int, events: np.ndarray) -> np.ndarray:
    """Least ``(ops, bytes)`` of every layer kernel in one window step,
    ``(L, 2)``: ``n_slots`` slots over ``n_steps`` timesteps that together
    received ``events[l]`` input events at layer ``l``."""
    out = np.zeros((len(layers), 2), np.float64)
    for l, layer in enumerate(layers):
        sites = out_sites(layer)
        out[l, 0] = (events[l] * fan_outs[l]
                     + n_slots * n_steps * sites)
        out[l, 1] = (n_slots * (2 + n_steps) * sites * F32
                     + weight_bytes(layer) + events[l] * F32)
    return out


def least_seconds(work: np.ndarray, peak_ops: float,
                  bytes_per_s: float) -> np.ndarray:
    """Per row of ``(ops, bytes)``: the larger of ops over the peak and
    bytes over the bandwidth, and which of the two bounds it
    (``(seconds, bytes_bound)``)."""
    t_ops = work[..., 0] / peak_ops
    t_bytes = work[..., 1] / bytes_per_s
    return np.maximum(t_ops, t_bytes), t_bytes >= t_ops
