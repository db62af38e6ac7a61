"""Graph500 Kronecker graph, generated on the device, relabelled from a seed.

The Graph500 specification's generator, with the parameters the LDBC
Graphalytics graph500-N data sets use: ``edgefactor << scale`` edges, each
placed by ``scale`` independent R-MAT quadrant draws with probabilities
A, B, C and D = 1 - A - B - C; self-loops removed; vertex labels permuted
by a random permutation drawn from the seed; then made undirected
(both directions of every edge) and deduplicated with one device sort.

Everything up to the deduplication runs in one jitted program on the device,
which sorts each undirected edge once, as its (low, high) pair: the device
holds half the pairs a sort of both directions would.  The sorted pairs and
their keep-mask come back to the host once, where the mask is applied and
each pair is listed in both directions: the result is an int32 ``[E, 2]``
array of (src, dst), ready for ``PMVEngine``.

The Kronecker draw and Graph500's own label permutation come from the
configuration's fixed ``graph_seed``.  The run's ``--seed`` then permutes the
residue classes modulo ``modulus`` (the layout's block count): vertex
``l * modulus + r`` becomes ``l * modulus + tau(r)``.  Under the engine's
cyclic partition a whole block moves to another block's place and every
vertex keeps its local id, so every seed gives the same blocks, the same
sets of local ids in every block pair, and the same compiled program (the
same work, and nothing to compile again), while the ids, the blocks each
vertex meets and the answers differ from seed to seed.  A relabelling
inside the blocks would not do: the packed exchange's plan counts the bytes
of each block pair's delta-coded id set, which hang on the local ids, and
the step compiles that count in as a constant.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int):
    """A PRNG key for any non-negative seed, also one over 32 bits."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


@functools.partial(jax.jit, static_argnames=("scale", "edgefactor", "abc"))
def _kronecker_sorted(key, *, scale: int, edgefactor: int, abc: tuple):
    a, b, c = abc
    n = 1 << scale
    m = edgefactor << scale
    k_draw, k_perm = jax.random.split(key)
    level_keys = jax.random.split(k_draw, scale)
    t1, t2, t3 = np.float32(a), np.float32(a + b), np.float32(a + b + c)

    def level(i, carry):
        src, dst = carry
        u = jax.random.uniform(level_keys[i], (m,), jnp.float32)
        # quadrant 0 (p=A) -> (0, 0); 1 (B) -> src bit; 2 (C) -> dst bit; 3 (D) -> both
        q = ((u >= t1).astype(jnp.int32) + (u >= t2).astype(jnp.int32)
             + (u >= t3).astype(jnp.int32))
        return (src << 1) | (q & 1), (dst << 1) | (q >> 1)

    zeros = jnp.zeros((m,), jnp.int32)
    src, dst = jax.lax.fori_loop(0, scale, level, (zeros, zeros))
    perm = jax.random.permutation(k_perm, n).astype(jnp.int32)
    src, dst = perm[src], perm[dst]
    # an undirected edge once, as (low, high); a self-loop becomes the
    # sentinel pair (n, n), which sorts last
    loop = src == dst
    lo = jnp.where(loop, n, jnp.minimum(src, dst))
    hi = jnp.where(loop, n, jnp.maximum(src, dst))
    lo, hi = jax.lax.sort((lo, hi), num_keys=2)
    fresh = jnp.concatenate([jnp.ones((1,), bool),
                             (lo[1:] != lo[:-1]) | (hi[1:] != hi[:-1])])
    return lo, hi, fresh & (lo < n)


@functools.partial(jax.jit, static_argnames=("n", "modulus"))
def _relabel(key, *, n: int, modulus: int):
    """A permutation of range(n) that moves each residue class mod
    ``modulus`` onto another, keeping every id's quotient by ``modulus``."""
    tau = jax.random.permutation(key, modulus).astype(jnp.int32)
    ids = jnp.arange(n, dtype=jnp.int32)
    return ids - ids % modulus + tau[ids % modulus]


def graph500(scale: int, edgefactor: int, a: float, b: float, c: float,
             graph_seed: int, seed: int, modulus: int) -> tuple[np.ndarray, int]:
    """(edges int32 [E, 2], n): the undirected, deduplicated Kronecker graph
    with n = 2**scale vertices drawn from ``graph_seed``, its residue classes
    mod ``modulus`` permuted from ``seed``; both directions of each edge
    listed."""
    n = 1 << scale
    if n % modulus:
        raise ValueError(f"modulus {modulus} does not divide n={n}")
    lo, hi, keep = _kronecker_sorted(seed_key(graph_seed), scale=scale,
                                     edgefactor=edgefactor, abc=(a, b, c))
    label = np.asarray(_relabel(seed_key(seed), n=n, modulus=modulus))
    keep = np.asarray(keep)
    lo, hi = label[np.asarray(lo)[keep]], label[np.asarray(hi)[keep]]
    edges = np.empty((2 * len(lo), 2), np.int32)
    edges[0::2, 0], edges[0::2, 1] = lo, hi
    edges[1::2, 0], edges[1::2, 1] = hi, lo
    return edges, n
