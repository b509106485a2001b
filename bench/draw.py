"""Corpus and query draws, made on the device from ``--seed``.

The law is the repository's synthetic sparse-vector law (``repro.data.synth``,
paper §6.1.1 / §6.5), copied here so that the yardstick cannot move with the
program, with one repair: every vector carries the ψ non-zeros it is given.
ψ ~ Poisson(ψ̄) clipped to [1, pad]; the coordinates are the first ψ distinct
ones of ``DRAWS_PER_SLOT · pad`` activation draws (Zipf or uniform over
the n coordinates), that is ψ draws without replacement in proportion to the
activation weights.  (The repository's law takes the distinct ones of 2ψ
draws, which under Zipf(1.3) over 30,000 coordinates leaves about 85 of
ψ̄ = 119.)  Values lognormal(0, σ) or gaussian(0, σ), absolute for
non-negative collections, an exact zero replaced by 1e-6.  Each chunk is one
jitted program and its arrays stay on the device that drew them.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class Law:
    """The sparse-vector law of one configuration (hashable: a jit static)."""

    n: int
    psi_doc: int
    psi_query: int
    value_dist: str          # lognormal | gaussian
    value_param: float
    nonneg: bool
    activation: str          # zipf | uniform
    zipf_a: float = 1.3

    @classmethod
    def from_config(cls, cfg: dict) -> "Law":
        d = cfg["dataset"]
        return cls(n=d["n"], psi_doc=d["psi_doc"], psi_query=d["psi_query"],
                   value_dist=d["value_dist"], value_param=d["value_param"],
                   nonneg=d["nonneg"], activation=d["activation"],
                   zipf_a=d.get("zipf_a", 1.3))


def coord_cdf(law: Law) -> np.ndarray:
    """Cumulative activation probabilities over the n coordinates."""
    if law.activation == "uniform":
        w = np.full(law.n, 1.0 / law.n)
    elif law.activation == "zipf":
        w = np.arange(1, law.n + 1, dtype=np.float64) ** (-law.zipf_a)
        w /= w.sum()
    else:
        raise ValueError(f"unknown activation {law.activation!r}")
    return np.cumsum(w).astype(np.float32)


def _values(key, shape, law: Law):
    z = jax.random.normal(key, shape)
    if law.value_dist == "lognormal":
        v = jnp.exp(law.value_param * z)
    elif law.value_dist == "gaussian":
        v = law.value_param * z
    else:
        raise ValueError(f"unknown value law {law.value_dist!r}")
    if law.nonneg:
        v = jnp.abs(v)
    return jnp.where(v == 0.0, 1e-6, v).astype(jnp.float32)


# Enough draws that even the longest vectors find their ψ distinct
# coordinates: under Zipf(1.3) over 30,000 coordinates 4 · 176 draws hold
# about 190 distinct ones, against a largest ψ of about 175 in 2^20 vectors.
DRAWS_PER_SLOT = 4


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
def draw_chunk(key, cdf, law: Law, batch: int, psi: int, pad: int):
    """``batch`` sparse vectors: (idx int32[batch, pad] sorted, pad = -1;
    val f32[batch, pad], pad = 0)."""
    kc, kd, kv = jax.random.split(key, 3)
    counts = jnp.clip(jax.random.poisson(kc, psi, (batch,)), 1, pad)
    draws = DRAWS_PER_SLOT * pad
    if law.n * draws >= 2**31:
        raise ValueError(f"n={law.n} x {draws} draws overflows int32")
    coords = jnp.searchsorted(cdf, jax.random.uniform(kd, (batch, draws)),
                              side="right", method="sort")
    coords = jnp.minimum(coords, law.n - 1).astype(jnp.int32)
    # Sorted by (coordinate, draw): the first of each run is the draw at
    # which that coordinate first came up.
    keyed = jnp.sort(coords * draws + jnp.arange(draws, dtype=jnp.int32),
                     axis=1)
    by_coord = keyed // draws
    first = jnp.concatenate([jnp.ones((batch, 1), jnp.bool_),
                             by_coord[:, 1:] != by_coord[:, :-1]], axis=1)
    at = jnp.sort(jnp.where(first, keyed % draws, draws), axis=1)[:, :pad]
    coords = jnp.take_along_axis(coords, jnp.minimum(at, draws - 1), axis=1)
    keep = (at < draws) & (jnp.arange(pad) < counts[:, None])
    coords = jnp.sort(jnp.where(keep, coords, law.n), axis=1)
    valid = coords < law.n
    val = _values(kv, (batch, pad), law)
    return jnp.where(valid, coords, -1), jnp.where(valid, val, 0.0)


def seed_key(seed: int, stream: int):
    """A PRNG key for (seed, stream).  Seeds beyond 32 bits keep their high
    word: ``jax.random.key`` alone would drop it with 64-bit mode off."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(key, stream)


def corpus_chunks(seed: int, law: Law, n_docs: int, pad: int, chunk: int,
                  devices) -> list:
    """The corpus as ``[(first doc id, idx, val), ...]``; chunk ``j`` is
    drawn on (and stays on) ``devices[j % len(devices)]``.  The numbers do
    not depend on the devices."""
    if n_docs % chunk:
        raise ValueError(f"n_docs={n_docs} is not a multiple of the chunk "
                         f"{chunk}")
    cdf = coord_cdf(law)
    cdfs = [jax.device_put(cdf, d) for d in devices]
    out = []
    for j, lo in enumerate(range(0, n_docs, chunk)):
        dev = j % len(devices)
        key = jax.device_put(seed_key(seed, 1 + j), devices[dev])
        idx, val = draw_chunk(key, cdfs[dev], law, chunk, law.psi_doc, pad)
        out.append((lo, idx, val))
    return out


def query_pool(seed: int, law: Law, size: int, pad: int) -> tuple:
    """``size`` distinct-seeded queries as host arrays (idx, val)
    ``[size, pad]``; pads are idx = -1."""
    cdf = jnp.asarray(coord_cdf(law))
    idx, val = draw_chunk(seed_key(seed, 0), cdf, law, size, law.psi_query,
                          pad)
    return np.asarray(idx), np.asarray(val)
