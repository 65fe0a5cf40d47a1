"""What every family's comparison shares: the check's tokens and the numbers
compared. The references themselves are the families' (families/__init__.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def zipf_tokens(seed: int, n: int, vocab: int, exponent: float = 1.2) -> np.ndarray:
    """A Zipf unigram draw: structure a model can learn in a few steps (the
    loss falls from ~ln V towards the distribution's entropy)."""
    rng = np.random.default_rng(seed)
    p = 1.0 / np.arange(1, vocab + 1) ** exponent
    return rng.choice(vocab, size=n, p=p / p.sum()).astype(np.int32)


def rel_rms(a, ref) -> float:
    """RMS of the difference over the RMS of the reference (on the host, in
    float64): steady from seed to seed where a worst single entry is not."""
    a, ref = np.asarray(a, np.float64), np.asarray(ref, np.float64)
    return float(np.sqrt(np.mean((a - ref) ** 2) / np.mean(ref ** 2)))


def chosen_gap(ref_rows: jax.Array, chosen: jax.Array) -> jax.Array:
    """How far below the reference's best logit each chosen token's reference
    logit lies, per position (0 where the choice is the reference's argmax)."""
    return ref_rows.max(axis=-1) - jnp.take_along_axis(ref_rows, chosen[:, None], axis=-1)[:, 0]
