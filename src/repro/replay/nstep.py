"""n-step return transform over sampler chunks (APE-X-style).

Operates on a chunk of stacked transitions (T, N, ...) produced by the
vectorized sampler: row t becomes

  rew'      = sum_{i=0..k-1} gamma^i r[t+i]
  next_obs' = next_obs[t+k-1]
  disc'     = gamma^k * (1 - done[t+k-1])

where k <= n stops at episode ends (done) or the chunk boundary (the
standard local-buffer truncation — a tail row simply becomes a k-step
transition with k < n, still a valid target).
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp


def nstep_chunk(exps: Dict[str, jax.Array], n: int, gamma: float
                ) -> Dict[str, jax.Array]:
    """exps: {obs, act, rew, next_obs, done} each (T, N, ...) -> same keys
    + "disc", with n-step returns. n=1 just adds disc = gamma*(1-done)."""
    rew, done, nxt = exps["rew"], exps["done"], exps["next_obs"]
    T = rew.shape[0]

    R = rew
    cont = 1.0 - done                       # still accumulating after t+0
    new_next = nxt
    disc = gamma * cont

    def shift(a, i):
        """a[t+i] with zero padding past the chunk end."""
        pad = jnp.zeros((i,) + a.shape[1:], a.dtype)
        return jnp.concatenate([a[i:], pad], axis=0)

    for i in range(1, min(n, T)):         # steps past the chunk add 0
        valid = (jnp.arange(T) + i < T).astype(rew.dtype)  # (T,)
        valid = valid.reshape((T,) + (1,) * (rew.ndim - 1))
        take = cont * valid                  # rows still accumulating
        r_i = shift(rew, i)
        d_i = shift(done, i)
        R = R + (gamma ** i) * take * r_i
        mask = take
        new_next = jnp.where(
            mask.reshape(mask.shape + (1,) * (nxt.ndim - mask.ndim)) > 0,
            shift(nxt, i), new_next)
        disc = jnp.where(take > 0, (gamma ** (i + 1)) * (1.0 - d_i), disc)
        cont = take * (1.0 - d_i)

    out = dict(exps)
    out["rew"] = R
    out["next_obs"] = new_next
    out["disc"] = disc
    return out
