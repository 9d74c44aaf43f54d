"""Gradcheck suite for the surrogate-gradient training path.

Three independent lines of evidence that `core.lif.spike_fn`'s custom VJP
and everything stacked on it backpropagate correctly:

  1. the VJP itself against the closed-form SLAYER surrogate
     ``beta / (2 (1 + beta|v-th|)^2)`` — and against autodiff of the soft
     fast-sigmoid primitive ``0.5 (1 + beta x / (1 + beta|x|))``, whose
     *exact* derivative the surrogate is;
  2. full ``lif_rollout(train=True)`` gradients against an independently
     built straight-through-estimator twin (forward = hard threshold,
     backward = the soft primitive) — this covers the spiking/reset
     regime, where the hard forward is *not* differentiable and finite
     differences cannot apply;
  3. central differences (float64, `jax.enable_x64`) against
     ``jax.grad`` in sub-threshold regimes where the hard forward is
     locally smooth: `lif_rollout` over membranes kept away from the
     threshold and the leak's |v|=leak kink, and the *executor's own*
     `layer_timestep` (conv / fc / pool, prime geometries) with the loss
     read off the interior membrane.

Plus the glue the trainer depends on: `dense_program_forward` is bitwise
`sne_net.dense_apply` (the compiled op chain computes the same function
gradients flow through), and the QAT fake-quant ops are differentiable
with straight-through (identity) weight gradients.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

try:
    from hypothesis import given, settings, strategies as st
except ImportError:           # container has no hypothesis; see the shim
    from _hypothesis_compat import given, settings, strategies as st

from repro.core.econv import EConvParams, EConvSpec
from repro.core.layer_program import (compile_program, dense_program_forward,
                                      frame_to_events, interior, layer_op,
                                      layer_timestep, padded_state)
from repro.core.lif import LifParams, lif_rollout, spike_fn
from repro.core.quant import _ste_round, fake_quant_weights
from repro.core.sne_net import dense_apply, init_snn, tiny_net
from repro.data.events_ds import TINY, batch_at

BETA = 10.0


def _surrogate(v, th, beta=BETA):
    x = np.abs(np.asarray(v, np.float64) - th) * beta
    return beta / (2.0 * (1.0 + x) ** 2)


def _soft(v, th, beta=BETA):
    """The fast-sigmoid primitive whose exact derivative is the surrogate."""
    x = v - th
    return 0.5 * (1.0 + beta * x / (1.0 + beta * jnp.abs(x)))


def _central_diff(f, x, eps):
    """Dense central differences of scalar ``f`` at float64 ``x``."""
    x = np.asarray(x, np.float64)
    g = np.zeros_like(x)
    it = np.nditer(x, flags=["multi_index"])
    for _ in it:
        i = it.multi_index
        xp = x.copy()
        xp[i] += eps
        xm = x.copy()
        xm[i] -= eps
        g[i] = (f(xp) - f(xm)) / (2.0 * eps)
    return g


# ---------------------------------------------------------------------------
# 1. spike_fn's custom VJP
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 10 ** 6),
       th=st.floats(0.25, 2.0),
       beta=st.floats(2.0, 25.0))
def test_spike_fn_vjp_matches_analytic(seed, th, beta):
    v = jax.random.normal(jax.random.PRNGKey(seed), (13,)) * 1.5 + th
    g = jax.random.normal(jax.random.PRNGKey(seed + 1), (13,))
    out, vjp = jax.vjp(lambda v, t: spike_fn(v, t, beta),
                       v, jnp.float32(th))
    np.testing.assert_array_equal(np.asarray(out),
                                  (np.asarray(v) >= th).astype(np.float32))
    dv, dth = vjp(g)
    surr = _surrogate(np.asarray(v), th, beta)
    np.testing.assert_allclose(np.asarray(dv), np.asarray(g) * surr,
                               rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(float(dth),
                               -float(np.sum(np.asarray(g) * surr)),
                               rtol=1e-5, atol=1e-7)


def test_spike_fn_grad_is_soft_primitive_grad():
    # the surrogate is the *exact* derivative of the soft fast-sigmoid:
    # d/dv of both paths must agree everywhere, including at v == th
    v = jnp.linspace(-2.0, 3.0, 41)
    th = jnp.float32(1.0)
    g_hard = jax.grad(lambda v: jnp.sum(spike_fn(v, th, BETA)))(v)
    g_soft = jax.grad(lambda v: jnp.sum(_soft(v, th, BETA)))(v)
    np.testing.assert_allclose(np.asarray(g_hard), np.asarray(g_soft),
                               rtol=1e-5, atol=1e-7)


# ---------------------------------------------------------------------------
# 2. rollout gradients vs the straight-through twin (spiking regime)
# ---------------------------------------------------------------------------

def _ste_rollout(v0, syn, p):
    """Reference BPTT rollout: forward = lif_rollout's hard threshold,
    backward = autodiff of the soft primitive via stop_gradient — an
    independent reconstruction of what spike_fn's custom VJP encodes."""

    def step(v, x):
        v = jnp.sign(v) * jnp.maximum(jnp.abs(v) - p.leak, 0.0) \
            if p.leak_mode == "toward_zero" else v - p.leak
        v = v + x
        if p.state_clip is not None:
            v = jnp.clip(v, -p.state_clip, p.state_clip)
        hard = (v >= p.threshold).astype(v.dtype)
        soft = _soft(v, p.threshold, p.surrogate_beta)
        s = jax.lax.stop_gradient(hard - soft) + soft
        if p.reset_mode == "zero":
            v = v * (1.0 - s)
        else:
            v = v - s * p.threshold
        return v, s

    return jax.lax.scan(step, v0, syn)


@pytest.mark.parametrize("reset", ["zero", "subtract"])
@pytest.mark.parametrize("leak", [0.0, 0.0625])
@pytest.mark.parametrize("clip", [None, 1.5])
def test_rollout_grads_match_ste_twin(reset, leak, clip):
    p = LifParams(threshold=1.0, leak=leak, reset_mode=reset,
                  state_clip=clip, surrogate_beta=BETA)
    key = jax.random.PRNGKey(3)
    T, n = 7, 11
    syn = jax.random.uniform(key, (T, n)) * 0.8   # crosses threshold often
    v0 = jax.random.uniform(jax.random.PRNGKey(4), (n,)) * 0.5
    w = jax.random.normal(jax.random.PRNGKey(5), (T, n))

    def loss(roll):
        def f(v0, syn):
            vf, s = roll(v0, syn, p)
            return jnp.sum(s * w) + jnp.sum(vf ** 2)
        return f

    # identical forwards first (the twin must test the same function) ...
    vf_a, s_a = lif_rollout(v0, syn, p, train=True)
    vf_b, s_b = _ste_rollout(v0, syn, p)
    assert bool(jnp.any(s_a > 0)), "regime must actually spike"
    np.testing.assert_array_equal(np.asarray(s_a), np.asarray(s_b))
    np.testing.assert_allclose(np.asarray(vf_a), np.asarray(vf_b),
                               rtol=1e-6, atol=1e-7)
    # ... then identical gradients through both BPTT paths
    ga = jax.grad(loss(lambda v0, syn, p: lif_rollout(v0, syn, p,
                                                      train=True)),
                  argnums=(0, 1))(v0, syn)
    gb = jax.grad(loss(_ste_rollout), argnums=(0, 1))(v0, syn)
    for a, b in zip(ga, gb):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# 3. central differences in sub-threshold regimes (float64)
# ---------------------------------------------------------------------------

@settings(max_examples=5, deadline=None)
@given(seed=st.integers(0, 10 ** 6),
       T=st.integers(2, 5),
       n=st.integers(1, 7),
       leak_on=st.integers(0, 1),
       soft=st.integers(0, 1))
def test_rollout_fd_subthreshold(seed, T, n, leak_on, soft):
    # syn in [0.2, 0.25]: v stays in [0.14, 0.75] — well under th=1.0 and
    # clear of the toward_zero leak kink at |v| = leak — so the hard
    # forward is locally smooth and central differences are valid
    p = LifParams(threshold=1.0, leak=0.0625 * leak_on,
                  reset_mode="subtract" if soft else "zero")
    with jax.enable_x64(True):
        key = jax.random.PRNGKey(seed)
        syn = (0.2 + 0.05 * jax.random.uniform(key, (T, n))
               ).astype(jnp.float64)
        v0 = jnp.zeros((n,), jnp.float64)
        w = jax.random.normal(jax.random.PRNGKey(seed + 1),
                              (n,)).astype(jnp.float64)

        @jax.jit
        def loss(syn):
            vf, s = lif_rollout(v0, syn, p, train=False)
            return jnp.sum(vf * w) + jnp.sum(vf ** 2)

        g = np.asarray(jax.grad(loss)(syn))
        fd = _central_diff(lambda x: float(loss(jnp.asarray(x))), syn, 1e-5)
    np.testing.assert_allclose(g, fd, rtol=1e-3, atol=1e-8)
    # train=True computes the same forward here (no crossings), and its
    # surrogate backward must agree with the true derivative up to the
    # surrogate tails (checked exactly by the STE-twin test above)
    vf_h, _ = lif_rollout(v0.astype(jnp.float32), syn.astype(jnp.float32), p)
    vf_t, s_t = lif_rollout(v0.astype(jnp.float32),
                            syn.astype(jnp.float32), p, train=True)
    assert not bool(jnp.any(s_t > 0))
    np.testing.assert_array_equal(np.asarray(vf_h), np.asarray(vf_t))


def _fd_layer_case(spec, w, density, seed, cap=96):
    """FD-vs-grad over layer_timestep's weights in float64."""
    op = layer_op(spec)
    s_in = (jax.random.uniform(jax.random.PRNGKey(seed),
                               (1,) + spec.in_shape) < density
            ).astype(jnp.float64)
    xyc, gate, n_drop = frame_to_events(s_in, cap)
    assert int(n_drop[0]) == 0
    xyc = xyc.astype(jnp.int64)   # x64 mode: indices must match int literals
    wts = jax.random.uniform(jax.random.PRNGKey(seed + 1),
                             (1,) + spec.out_shape, dtype=jnp.float64)

    @jax.jit
    def loss(w):
        vp = padded_state(op, dtype=jnp.float64, n_slots=1)
        vp2, _ = layer_timestep(op, EConvParams(w=w), vp, xyc, gate,
                                jnp.ones((1,), jnp.float64),
                                use_pallas=False)
        return jnp.sum(interior(vp2, op.halo) * wts)

    g = np.asarray(jax.grad(loss)(w))
    fd = _central_diff(lambda x: float(loss(jnp.asarray(x))), w, 1e-5)
    np.testing.assert_allclose(g, fd, rtol=1e-3, atol=1e-9)
    return g


def test_layer_timestep_fd_conv_weights():
    # prime 5x7 geometry; |w| ~ 0.01 keeps every membrane sub-threshold
    with jax.enable_x64(True):
        spec = EConvSpec(kind="conv", in_shape=(5, 7, 2), out_channels=3,
                         kernel=3, stride=1, padding=1,
                         lif=LifParams(threshold=1.0, leak=0.0625))
        w = (0.01 * jax.random.normal(jax.random.PRNGKey(0), (3, 3, 2, 3))
             ).astype(jnp.float64)
        g = _fd_layer_case(spec, w, density=0.4, seed=1)
    assert np.any(g != 0.0)


def test_layer_timestep_fd_fc_weights():
    with jax.enable_x64(True):
        spec = EConvSpec(kind="fc", in_shape=(3, 5, 2), out_channels=7,
                         lif=LifParams(threshold=1.0, leak=0.0))
        w = (0.01 * jax.random.normal(jax.random.PRNGKey(2), (30, 7))
             ).astype(jnp.float64)
        g = _fd_layer_case(spec, w, density=0.5, seed=3)
    assert np.any(g != 0.0)


def test_layer_timestep_fd_pool_weights():
    # pool synapse 0.3 against th=1.0: one window never sums past 4*0.3=1.2?
    # keep density low so <=3 of 4 inputs fire per window -> max v 0.9
    with jax.enable_x64(True):
        spec = EConvSpec(kind="pool", in_shape=(6, 6, 2), out_channels=2,
                         kernel=2, stride=2,
                         lif=LifParams(threshold=1.0, leak=0.0))
        w = jnp.full((2,), 0.3, jnp.float64)
        g = _fd_layer_case(spec, w, density=0.25, seed=5)
    assert np.any(g != 0.0)


# ---------------------------------------------------------------------------
# The trainer's forward IS the executor's op chain
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("train", [False, True])
def test_dense_program_forward_matches_dense_apply(train):
    spec = tiny_net()
    params = init_snn(jax.random.PRNGKey(0), spec)
    program = compile_program(spec)
    spikes, _ = batch_at(0, 0, 1, TINY)
    a, acts_a = dense_program_forward(program, params, spikes[0],
                                      train=train)
    b, acts_b = dense_apply(params, spec, spikes[0], train=train)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert len(acts_a) == len(acts_b) == len(spec.layers)
    for x, y in zip(acts_a, acts_b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_dense_program_forward_qat_is_fake_quant_forward():
    from repro.core.quant import fake_quant_net
    spec = tiny_net()
    params = init_snn(jax.random.PRNGKey(1), spec)
    program = compile_program(spec)
    spikes, _ = batch_at(1, 0, 1, TINY)
    a, _ = dense_program_forward(program, params, spikes[0],
                                 train=True, qat=True)
    b, _ = dense_program_forward(program, fake_quant_net(params, spec),
                                 spikes[0], train=True)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_dense_program_forward_rejects_int8_program():
    from repro.core.layer_program import ExecutionPolicy
    from repro.core.quant import quantize_net
    spec = tiny_net()
    params = init_snn(jax.random.PRNGKey(0), spec)
    qn = quantize_net(params, spec, per_channel=False)
    program = compile_program(
        qn.spec, policy=ExecutionPolicy(dtype_policy="int8-native"))
    spikes, _ = batch_at(0, 0, 1, TINY)
    with pytest.raises(ValueError, match="f32-carrier"):
        dense_program_forward(program, qn.params_for("int8-native"),
                              spikes[0], train=True)


# ---------------------------------------------------------------------------
# QAT straight-through gradients
# ---------------------------------------------------------------------------

def test_ste_round_grad_is_identity():
    x = jnp.linspace(-3.3, 3.3, 23)
    np.testing.assert_array_equal(np.asarray(_ste_round(x)),
                                  np.round(np.asarray(x)))
    g = jax.grad(lambda x: jnp.sum(_ste_round(x) * 2.0))(x)
    np.testing.assert_array_equal(np.asarray(g), np.full((23,), 2.0))


def test_fake_quant_weight_grads_flow():
    w = jax.random.normal(jax.random.PRNGKey(7), (3, 3, 2, 4)) * 0.1
    g = jax.grad(lambda w: jnp.sum(fake_quant_weights(w, False) ** 2))(w)
    assert np.all(np.isfinite(np.asarray(g)))
    assert float(jnp.sum(jnp.abs(g))) > 0.0


def test_grad_through_program_loss_is_finite_and_nonzero():
    # end-to-end: the exact loss fit() optimises, differentiated through
    # the compiled op chain with QAT on
    from repro.train.snn_loop import batch_loss
    spec = tiny_net()
    params = init_snn(jax.random.PRNGKey(0), spec)
    program = compile_program(spec)
    spikes, labels = batch_at(0, 0, 2, TINY)
    grads = jax.grad(lambda p: batch_loss(program, p, spikes, labels,
                                          qat=True))(params)
    for i, (g, l) in enumerate(zip(grads, spec.layers)):
        assert np.all(np.isfinite(np.asarray(g.w))), i
        if l.kind != "pool":
            assert float(jnp.sum(jnp.abs(g.w))) > 0.0, i
