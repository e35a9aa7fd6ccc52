"""Inputs made from ``--seed``: Kron kernels and planted training subsets.

Everything here belongs to the benchmark, not to the program under test,
so a later change to the program cannot move the data a cell runs on.

* ``kron_factors``: the paper's Sec. 5.1 random factors (L_i = X^T X +
  1e-3 I with X ~ U[0, sqrt 2]), made on the device in one jitted call,
  then scaled so that E|Y| = sum lambda / (1 + lambda) hits the
  configuration's expected size. The gain comes from a float64
  bisection on the factor spectra.
* ``planted_subsets``: n exact draws from a Kron DPP by a plain sampler
  (Bernoulli over the product spectrum, then the projection-DPP chain
  rule on the materialised eigenvector columns), batched on the device.
"""

from __future__ import annotations

import functools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

_HIGHEST = jax.lax.Precision.HIGHEST


def run_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any width (PRNGKey keeps 32 bits)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


@functools.partial(jax.jit, static_argnames=("sizes",))
def paper_factors(key, sizes: Tuple[int, ...]):
    """The paper's Sec. 5.1 init, float32: L_i = X^T X + 1e-3 I."""
    out = []
    for s in sizes:
        key, sub = jax.random.split(key)
        X = jax.random.uniform(sub, (s, s), jnp.float32, 0.0, np.sqrt(2.0))
        out.append(jnp.matmul(X.T, X, precision=_HIGHEST)
                   + 1e-3 * jnp.eye(s, dtype=jnp.float32))
    return tuple(out)


def log_spectrum(factors64: Sequence[np.ndarray]) -> np.ndarray:
    """log of the Kron product spectrum, row-major, float64 (-inf for 0)."""
    v = np.zeros(1)
    for f in factors64:
        lam = np.clip(np.linalg.eigvalsh(f), 0.0, None)
        with np.errstate(divide="ignore"):
            v = (v[:, None] + np.log(lam)[None, :]).reshape(-1)
    return v


def _expected_size(log_lam: np.ndarray, log_gain: float) -> float:
    return float(np.sum(0.5 * (1.0 + np.tanh(0.5 * (log_lam + log_gain)))))


def kron_factors(key: jax.Array, sizes: Sequence[int],
                 expected_size: float) -> Tuple[jax.Array, ...]:
    """Seeded float32 factors on the device with E|Y| = expected_size."""
    raw = paper_factors(key, tuple(int(s) for s in sizes))
    log_lam = log_spectrum([np.asarray(f, np.float64) for f in raw])
    lo, hi = -200.0, 200.0
    for _ in range(200):
        mid = 0.5 * (lo + hi)
        if _expected_size(log_lam, mid) < expected_size:
            lo = mid
        else:
            hi = mid
    scale = np.float32(np.exp(0.5 * (lo + hi) / len(sizes)))
    return tuple(f * scale for f in raw)


@functools.partial(jax.jit, static_argnames=("n", "width", "chunk"))
def planted_subsets(key, factors, n: int, width: int, chunk: int = 256):
    """(indices (n, width) int32, mask (n, width) bool): n exact DPP draws
    from L = factors[0] kron factors[1], each row in pick order. A draw
    larger than ``width`` keeps its lowest eigen-indices (at width
    E|Y| + 6 sigma that is a ~1e-9 event)."""
    L1, L2 = factors
    N2 = L2.shape[0]
    d1, P1 = jnp.linalg.eigh(L1)
    d2, P2 = jnp.linalg.eigh(L2)
    lam = jnp.maximum(jnp.outer(d1, d2).reshape(-1), 0.0)
    p = lam / (1.0 + lam)
    N = p.shape[0]

    def one(k):
        k1, k2 = jax.random.split(k)
        mask = jax.random.uniform(k1, (N,)) < p
        count = jnp.minimum(jnp.sum(mask), width)
        sel = jnp.nonzero(mask, size=width, fill_value=0)[0]
        live = jnp.arange(width) < count
        V = (P1[:, sel // N2][:, None, :] * P2[:, sel % N2][None, :, :])
        V = V.reshape(N, width) * live[None, :]
        us = jax.random.uniform(k2, (width,))

        def step(t, carry):
            norms, B, picks = carry
            c = jnp.cumsum(norms)
            i = jnp.minimum(jnp.searchsorted(c, us[t] * c[-1], side="right"),
                            N - 1)
            w = V[i]
            q = w - jnp.matmul(jnp.matmul(B, w, precision=_HIGHEST), B,
                               precision=_HIGHEST)
            q = q - jnp.matmul(jnp.matmul(B, q, precision=_HIGHEST), B,
                               precision=_HIGHEST)
            q = q / jnp.sqrt(jnp.maximum(jnp.sum(q * q), 1e-30))
            on = t < count
            vq = jnp.matmul(V, q, precision=_HIGHEST)
            norms2 = jnp.maximum(norms - vq * vq, 0.0).at[i].set(0.0)
            return (jnp.where(on, norms2, norms),
                    jnp.where(on, B.at[t].set(q), B),
                    jnp.where(on, picks.at[t].set(i), picks))

        norms0 = jnp.sum(V * V, axis=1)
        _, _, picks = jax.lax.fori_loop(
            0, width, step, (norms0, jnp.zeros((width, width), V.dtype),
                             jnp.full((width,), -1, jnp.int32)))
        return picks

    picks = jax.lax.map(one, jax.random.split(key, n), batch_size=chunk)
    mask = picks >= 0
    return jnp.where(mask, picks, 0).astype(jnp.int32), mask
