"""Compile-only checks for a described TPU v5e chip (no chip attached).

The TPU compiler ships with the installed JAX and compiles for a
topology that is described rather than attached, so the Mosaic rules
that interpret mode cannot see — the (8, 128) block rule, the VMEM
limit, what lowers at all — are checked here on every run.  Nothing
executes: these tests say what the chip's compiler accepts, never what
it computes or how fast.

Shapes are the paper's DVS-Gesture network (`sne_net.dvs_gesture_net`)
with 4 slots; the event axis runs from the smallest bucket (8) to the
collector's top rung (32768), which must cost the same fast memory.

The topology is described inside a module fixture (never at import),
so every xdist worker collects the same tests and only the worker that
runs this file loads the TPU library.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import layer_program as lp
from repro.core.lif import LifParams
from repro.core.policies import ExecutionPolicy
from repro.core.sne_net import dvs_gesture_net, init_snn
from repro.kernels.event_conv import kernel as conv_k
from repro.kernels.event_conv import ops as conv_ops
from repro.kernels.event_fc import kernel as fc_k
from repro.kernels.event_fc import ops as fc_ops
from repro.kernels.event_pool import kernel as pool_k
from repro.kernels.event_pool import ops as pool_ops
from repro.kernels.window_common import tile_grid
from repro.serve.event_engine import batched_window_step

N_SLOTS = 4
WINDOW = 4
TOP_RUNG = 32768
LIF = LifParams(threshold=1.0, leak=0.03125)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip compile is written to the persistent cache but
    # cannot be read back without a chip; keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _shape(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _layer(kind):
    """The paper network's widest layer of ``kind`` and its op."""
    spec = dvs_gesture_net()
    idx = {"pool": 0, "conv": 1, "fc": 5}[kind]
    return spec.layers[idx], lp.layer_op(spec.layers[idx], index=idx)


def _events(sh, lead, E):
    return (_shape(sh, lead + (E, 3), jnp.int32),
            _shape(sh, lead + (E,)))


@pytest.mark.parametrize("E", [8, TOP_RUNG])
@pytest.mark.parametrize("lowering", ["per-step", "fused-window"])
@pytest.mark.parametrize("kind", ["conv", "pool", "fc"])
def test_kernel_compiles_for_v5e(one_chip, kind, lowering, E):
    spec, op = _layer(kind)
    sh = one_chip
    Ho, Wo, Co = spec.out_shape
    h = op.halo
    v = _shape(sh, (N_SLOTS, Ho + 2 * h, Wo + 2 * h, Co))
    if kind == "conv":
        w = _shape(sh, (spec.kernel, spec.kernel, spec.in_shape[2], Co))
    elif kind == "pool":
        w = _shape(sh, (spec.in_shape[2],))
    else:
        w = _shape(sh, (spec.in_shape[0] * spec.in_shape[1]
                        * spec.in_shape[2], Co))
    if lowering == "per-step":
        xyc, gate = _events(sh, (N_SLOTS,), E)
        fn = {"conv": functools.partial(conv_k.event_conv_batched_pallas,
                                        co_blk=Co),
              "pool": functools.partial(pool_k.event_pool_batched_pallas,
                                        stride=spec.stride),
              "fc": functools.partial(fc_k.event_fc_batched_pallas,
                                      in_shape=spec.in_shape)}[kind]
        compiled = _compile(fn, v, w, xyc, gate)
    else:
        xyc, gate = _events(sh, (N_SLOTS, WINDOW), E)
        alive = _shape(sh, (N_SLOTS, WINDOW))
        if kind == "fc":
            fn = functools.partial(fc_k.event_fc_window_pallas, lif=LIF,
                                   in_shape=spec.in_shape)
            compiled = _compile(fn, v, w, xyc, gate, alive)
        else:
            nTx, nTy, _, _ = tile_grid(Ho, Wo)
            tiles = _shape(sh, (N_SLOTS, nTx, nTy), jnp.int32)
            fn = (functools.partial(conv_k.event_conv_window_pallas,
                                    lif=LIF, halo=h, co_blk=Co)
                  if kind == "conv" else
                  functools.partial(pool_k.event_pool_window_pallas,
                                    lif=LIF, stride=spec.stride))
            compiled = _compile(fn, v, w, xyc, gate, alive, tiles)
    assert "tpu_custom_call" in compiled.as_text()


def test_window_step_compiles_for_v5e(one_chip, monkeypatch):
    """The engine's whole fused-window step at the top event rung."""
    for mod in (conv_ops, pool_ops, fc_ops):
        monkeypatch.setattr(mod, "_on_tpu", lambda: True)
    sh = one_chip
    spec = dvs_gesture_net()
    program = lp.compile_program(spec, policy=ExecutionPolicy())
    assert program.fusion_policy == lp.FUSED_WINDOW
    params = jax.eval_shape(lambda: init_snn(jax.random.PRNGKey(0), spec))
    params = jax.tree.map(lambda a: _shape(sh, a.shape, a.dtype), params)
    states = tuple(
        _shape(sh, (N_SLOTS,) + lp.padded_state(op).shape)
        for op in program.ops)
    cc = _shape(sh, (N_SLOTS, spec.n_classes))
    xyc, gate = _events(sh, (WINDOW, N_SLOTS), TOP_RUNG)
    alive = _shape(sh, (WINDOW, N_SLOTS))
    pre = _shape(sh, (N_SLOTS,), jnp.int32)
    step = functools.partial(lp.window_step, program=program)
    compiled = _compile(step, params, states, cc, xyc, gate, alive, pre)
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= len(program.ops)
    # each layer's launch carries its own stable name, once
    for op in program.ops:
        name = lp.window_kernel_name(op)
        launch = re.compile(rf"^\s*(ROOT )?%{name}(\.\d+)? = .*"
                            rf'custom_call_target="tpu_custom_call"')
        assert sum(bool(launch.match(line))
                   for line in text.splitlines()) == 1, name


def test_engine_window_program_compiles_for_v5e(one_chip, monkeypatch):
    """The engine's one window program — slot gather, fused-window step,
    scatter-back with the dummy tail dropped — for a compacted batch of 4
    of 16 slots at the smallest rung, the slabs donated: they must alias
    the outputs, so the scatter writes the resident state in place."""
    for mod in (conv_ops, pool_ops, fc_ops):
        monkeypatch.setattr(mod, "_on_tpu", lambda: True)
    sh = one_chip
    spec = dvs_gesture_net()
    program = lp.compile_program(spec, policy=ExecutionPolicy())
    params = jax.eval_shape(lambda: init_snn(jax.random.PRNGKey(0), spec))
    params = jax.tree.map(lambda a: _shape(sh, a.shape, a.dtype), params)
    N, Ab = 16, 4
    states = tuple(_shape(sh, (N,) + lp.padded_state(op).shape)
                   for op in program.ops)
    cc = _shape(sh, (N, spec.n_classes))
    sel = _shape(sh, (2, Ab), jnp.int32)
    xyc, gate = _events(sh, (WINDOW, Ab), 8)
    alive = _shape(sh, (WINDOW, Ab))
    pre = _shape(sh, (Ab,), jnp.int32)
    step = jax.jit(functools.partial(batched_window_step, program=program),
                   donate_argnums=(1, 2))
    compiled = step.lower(params, states, cc, sel, xyc, gate, alive,
                          pre).compile()
    assert compiled.as_text().count("tpu_custom_call") >= len(program.ops)
    slab_bytes = sum(4 * a.size for a in states + (cc,))
    assert compiled.memory_analysis().alias_size_in_bytes >= slab_bytes
