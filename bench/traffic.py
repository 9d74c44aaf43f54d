"""Traffic: the seeded payload pool and the arrival schedules.

A traffic mix is a data file ``bench/traffic/<name>.json``; this module
is the one generator that reads every such file.  Its keys:
``arrivals`` (``backlog`` or ``poisson``), ``activity_band`` (fractions of
the input sites firing per timestep), ``pool_size`` and ``n_blobs`` (the
payloads), ``warm_requests`` (a backlog's warm-up), and for ``poisson``
``rate_hz``, ``queue_capacity`` and ``latency_limit_ms``; every other
key is a reason in words.  Nothing here imports the program.

Payloads.  A synthetic DVS recording in the style of the paper's
IBM DVS-Gesture and N-MNIST sets: a few class-anchored Gaussian blobs
orbit the frame, and polarity follows their direction of motion (the
model of ``repro.data.events_ds``, copied here so the yardstick cannot
move with the program).  Unlike that sampler, a payload carries an exact
number of events at every timestep: ``k`` sites are drawn without
replacement from the blob intensity (Gumbel top-k), so the activity of a
payload is pinned, not drawn.  The pool's per-payload activities are a
fixed ladder across the mix's band; the seed only shuffles which payload
gets which activity and draws labels, phases and sites.  So every seed
does the same amount of work, in another order.

Arrivals.  ``backlog``: the queue is kept full, every request arrives at
the moment it is handed over.  ``poisson``: an open-loop schedule of
exponential gaps at the mix's rate (the schedule of
``repro.serve.runtime.loadgen``, stratified so that every seed offers
the same gaps in another order).
"""
from __future__ import annotations

import dataclasses
import json
from functools import partial
from pathlib import Path
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"


def load_mix(name: str) -> Dict:
    """The traffic mix ``bench/traffic/<name>.json``."""
    return json.loads((TRAFFIC_DIR / f"{name}.json").read_text())


def activity_ladder(n_sites: int, lo: float, hi: float, n: int) -> np.ndarray:
    """Events per timestep of the pool's ``n`` payloads: an even ladder
    over the activity band ``[lo, hi]`` (fractions of the input sites)."""
    if n == 1:
        return np.asarray([int(round(n_sites * (lo + hi) / 2))])
    return np.rint(n_sites * np.linspace(lo, hi, n)).astype(np.int64)


@partial(jax.jit, static_argnames=("shape", "n_timesteps", "n_classes",
                                   "n_blobs", "k_max"))
def _pool_sites(key, counts, *, shape, n_timesteps, n_classes, n_blobs,
                k_max):
    """(P, T, k_max) flat input-site indices, row-major sorted per
    timestep, with ``counts[p]`` real events at every timestep of payload
    ``p`` (the tail of each row is ``H*W*C``, a sentinel)."""
    H, W, C = shape
    T = n_timesteps
    S = H * W * C
    P = counts.shape[0]
    k_lab, k_phase, k_site = jax.random.split(key, 3)
    # every class equally often, in a seeded order
    labels = jax.random.permutation(k_lab, jnp.arange(P) % n_classes)
    phases = jax.random.uniform(k_phase, (P, n_blobs)) * 2 * jnp.pi
    site_keys = jax.random.split(k_site, P)
    yy = jnp.arange(H, dtype=jnp.float32)[:, None]
    xx = jnp.arange(W, dtype=jnp.float32)[None, :]
    sig2 = (0.06 * min(H, W)) ** 2
    t = jnp.arange(T, dtype=jnp.float32)[:, None]

    def one(label, phase0, skey, k):
        lab = label.astype(jnp.float32)
        b = jnp.arange(n_blobs, dtype=jnp.float32)
        omega = 0.05 + 0.035 * lab + 0.02 * b
        radius = (0.14 + 0.03 * b + 0.01 * lab) * min(H, W)
        ang = omega[None, :] * t + (phase0 + lab * 0.7)[None, :]  # (T, nb)
        theta = 2.0 * jnp.pi * lab / n_classes
        cy = H * (0.5 + 0.22 * jnp.sin(theta)) + radius * jnp.sin(ang)
        cx = W * (0.5 + 0.22 * jnp.cos(theta)) + radius * jnp.cos(ang)
        pol = 0.5 + 0.5 * jnp.sin(ang + 0.5)                      # (T, nb)
        pol = jnp.clip(pol, 1e-3, 1 - 1e-3)
        d2 = ((yy[None, None] - cy[:, :, None, None]) ** 2
              + (xx[None, None] - cx[:, :, None, None]) ** 2)     # (T,nb,H,W)
        logi = -d2 / (2 * sig2)
        on = jnp.max(logi + jnp.log(pol)[:, :, None, None], axis=1)
        off = jnp.max(logi + jnp.log(1 - pol)[:, :, None, None], axis=1)
        score = jnp.stack([on, off][:C], axis=-1).reshape(T, S)
        score = score + jax.random.gumbel(skey, (T, S))
        top = jax.lax.top_k(score, k_max)[1]                      # (T, k_max)
        top = jnp.where(jnp.arange(k_max)[None, :] < k, top, S)
        return jnp.sort(top, axis=1).astype(jnp.int32)

    return jax.lax.map(lambda a: one(*a), (labels, phases, site_keys, counts))


@dataclasses.dataclass
class PayloadPool:
    """The seeded payloads of one run, on the host and as sites on device.

    ``sites[p]`` is ``(T, k_max)`` flat site indices (sentinel-padded) on
    the device; ``counts[p]`` is payload ``p``'s events per timestep;
    ``events[p]`` the host arrays ``(t, x, y, c)`` in sensor order.
    """

    shape: tuple
    n_timesteps: int
    counts: np.ndarray
    sites: jax.Array
    events: List[tuple]

    def __len__(self) -> int:
        return len(self.counts)

    def n_events(self, p: int) -> int:
        """Input events of payload ``p`` over its whole recording."""
        return int(self.counts[p]) * self.n_timesteps

    def dense(self, p: int) -> jax.Array:
        """Payload ``p`` as a dense ``(T, H, W, C)`` float32 spike tensor."""
        return dense_spikes(self.sites[p], self.shape)


@partial(jax.jit, static_argnames=("shape",))
def dense_spikes(sites, shape):
    """(T, k) sentinel-padded flat sites -> dense (T, H, W, C) spikes."""
    H, W, C = shape
    S = H * W * C
    T = sites.shape[0]
    frame = jnp.zeros((T, S + 1), jnp.float32)
    frame = frame.at[jnp.arange(T)[:, None], sites].set(1.0)
    return frame[:, :S].reshape(T, H, W, C)


def make_pool(seed: int, mix: Dict, shape, n_timesteps: int,
              n_classes: int) -> PayloadPool:
    """The mix's payload pool for ``seed``, made on the device at once."""
    H, W, C = shape
    S = H * W * C
    lo, hi = mix["activity_band"]
    counts = activity_ladder(S, lo, hi, mix["pool_size"])
    rng = np.random.default_rng(np.random.SeedSequence([seed, 1]))
    counts = counts[rng.permutation(len(counts))]
    k_max = int(counts.max())
    key = jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31)),
                             seed // (2 ** 31))
    sites = _pool_sites(key, jnp.asarray(counts, jnp.int32),
                        shape=tuple(shape), n_timesteps=n_timesteps,
                        n_classes=n_classes, n_blobs=mix["n_blobs"],
                        k_max=k_max)
    host = np.asarray(sites)
    events = []
    for p, k in enumerate(counts):
        flat = host[p, :, :k].reshape(-1).astype(np.int64)
        t = np.repeat(np.arange(n_timesteps, dtype=np.int64), k)
        x = flat // (W * C)
        y = (flat // C) % W
        c = flat % C
        events.append((t, x, y, c))
    return PayloadPool(shape=tuple(shape), n_timesteps=n_timesteps,
                       counts=counts, sites=sites, events=events)


GAP_BLOCK = 64


def poisson_arrival_times(rate_hz: float, n: int, seed: int) -> np.ndarray:
    """Cumulative arrival times of ``n`` arrivals at ``rate_hz``.

    The gaps are exponential, stratified: every block of ``GAP_BLOCK``
    arrivals takes the exponential's quantiles at ``(j + 0.5) / 64``, in
    an order drawn from the seed.  So each block offers exactly the rate,
    with the exponential's bursts and lulls, and seeds differ only in the
    order of the same gaps."""
    if rate_hz <= 0:
        raise ValueError("rate_hz must be > 0")
    rng = np.random.default_rng(np.random.SeedSequence([seed, 2]))
    q = (np.arange(GAP_BLOCK) + 0.5) / GAP_BLOCK
    gaps = -np.log1p(-q) / rate_hz
    blocks = -(-n // GAP_BLOCK)
    seq = np.concatenate([rng.permutation(gaps) for _ in range(blocks)])
    return np.cumsum(seq[:n])


class Arrivals:
    """The arrival process the runtime polls each tick (duck-types the
    runtime's load generator: ``due``, ``exhausted``, ``next_arrival_s``).

    ``make(i, t)`` builds request ``i`` arriving at clock time ``t``.
    ``backlog`` hands over a request whenever the runtime's queue holds
    fewer than ``depth``; ``poisson`` follows its schedule from
    ``start_s``.  ``late_s`` records, per request, how late the hand-over
    was against the schedule; ``stop()`` ends the arrivals.
    """

    def __init__(self, kind: str, make, *, queue=None, depth: int = 0,
                 rate_hz: Optional[float] = None, seed: int = 0,
                 start_s: float = 0.0, horizon: int = 100_000):
        if kind not in ("backlog", "poisson"):
            raise ValueError(f"unknown arrival kind {kind!r}")
        self.kind = kind
        self.make = make
        self.queue = queue
        self.depth = depth
        self.times = (start_s + poisson_arrival_times(rate_hz, horizon, seed)
                      if kind == "poisson" else None)
        self.n = 0
        self.late_s: List[float] = []
        self.stopped = False

    @property
    def exhausted(self) -> bool:
        """True once :meth:`stop` was called."""
        return self.stopped

    def stop(self) -> None:
        """Hand over nothing more."""
        self.stopped = True

    def next_arrival_s(self) -> Optional[float]:
        """Clock time of the next scheduled arrival (None for a backlog)."""
        if self.stopped or self.times is None:
            return None
        return float(self.times[self.n])

    def due(self, now: float) -> list:
        """Every request due by ``now``, in schedule order."""
        out = []
        if self.stopped:
            return out
        if self.kind == "backlog":
            while len(self.queue) + len(out) < self.depth:
                out.append(self.make(self.n, now))
                self.late_s.append(0.0)
                self.n += 1
            return out
        while self.n < len(self.times) and self.times[self.n] <= now:
            t = float(self.times[self.n])
            out.append(self.make(self.n, t))
            self.late_s.append(now - t)
            self.n += 1
        return out


def warm_plan(mix: Dict, pool: PayloadPool, n_slots: int) -> Dict:
    """What set-up runs so that the window compiles nothing.

    A window step's shapes are set by how many slots it carries and by
    the event rung of its busiest payload.  The pool's least and most
    active payloads cover the rungs of a band that spans at most two;
    each runs one window at every slot count the arrivals can leave busy
    (all slots under a backlog, any count from 1 under Poisson arrivals).
    A backlog then serves ``warm_requests`` of its own traffic, so the
    window opens in its steady state.
    """
    ends = sorted({int(np.argmin(pool.counts)), int(np.argmax(pool.counts))})
    if mix["arrivals"] == "backlog":
        slots = [n_slots]
    else:
        slots = list(range(1, n_slots + 1))
    return {"payloads": ends, "slot_counts": slots,
            "requests": int(mix.get("warm_requests", 0))}
