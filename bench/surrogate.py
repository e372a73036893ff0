"""Seeded stand-ins for published sparse tensors, with a planted Tucker
structure so that a decomposition has a fit worth comparing.

About half the nonzeros form ``components`` dense rank-1 blocks on disjoint,
randomly scattered index sets, with distinct block weights; they carry 3/4
of the energy, so a Tucker model of rank ``components`` per mode has a
well-defined fit (about 1/2 relative error) and a well-separated subspace.
The rest are distinct coordinates drawn uniformly over the shape with count
values 1 + Poisson(3), which touch every row of every mode. A uniform
pattern alone would keep ~1e-6 of its energy at rank 16 and leave nothing
for a comparison to see.

The pattern (coordinates) and the values are drawn apart, so a pattern can
be fixed by a configuration's seed, as a deployment's data is, while the
values come from the run's seed.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence, Tuple

import numpy as np


@dataclasses.dataclass(frozen=True)
class Pattern:
    """Coordinates of one surrogate tensor, with what is needed to draw its
    values: the planted block of each nonzero (-1 for noise) and its offset
    inside that block along every mode."""

    shape: Tuple[int, ...]
    indices: np.ndarray  # (nnz, N) int32
    block: np.ndarray  # (nnz,) int32, -1 for a noise nonzero
    offsets: np.ndarray  # (nnz, N) int32, offset inside the block
    sides: Tuple[int, ...]  # block extent along each mode
    components: int

    @property
    def nnz(self) -> int:
        return int(self.indices.shape[0])

    def values(self, rng: np.random.Generator) -> np.ndarray:
        """Fresh float32 values on this pattern: each block is
        ``2**(-r/8)`` times an outer product of uniform(0.5, 1.5) vectors,
        scaled so the blocks hold 3/4 of the energy over the noise counts."""
        noise = self.block < 0
        vals = np.empty(self.nnz, dtype=np.float64)
        vals[noise] = rng.poisson(3.0, int(noise.sum())) + 1.0
        planted = ~noise
        blk = self.block[planted]
        weight = 2.0 ** (-np.arange(self.components) / 8.0)
        pv = weight[blk]
        for n, side in enumerate(self.sides):
            loadings = rng.uniform(0.5, 1.5, (self.components, side))
            pv = pv * loadings[blk, self.offsets[planted, n]]
        pv *= np.sqrt(3.0 * np.sum(vals[noise] ** 2) / np.sum(pv**2))
        vals[planted] = pv
        return vals.astype(np.float32)


def pattern(shape: Sequence[int], nnz: int, components: int,
            rng: np.random.Generator) -> Pattern:
    """Draw the coordinates of a surrogate with exactly ``nnz`` distinct
    nonzeros, in a random order."""
    shape = tuple(int(s) for s in shape)
    ndim = len(shape)
    sides = tuple(
        max(1, min(s // components, round((nnz / 2 / components) ** (1 / ndim))))
        for s in shape
    )
    groups = [rng.permutation(s)[: components * d].reshape(components, d)
              for s, d in zip(shape, sides)]
    local = np.indices(sides).reshape(ndim, -1).T  # offsets inside one block
    block = np.repeat(np.arange(components, dtype=np.int32), local.shape[0])
    offs = np.tile(local, (components, 1)).astype(np.int32)
    planted = np.stack([groups[n][block, offs[:, n]] for n in range(ndim)], 1)
    if planted.shape[0] >= nnz:
        raise ValueError(f"{planted.shape[0]} planted nonzeros leave no room "
                         f"for noise in {nnz}")

    total = int(np.prod(shape, dtype=np.int64))
    taken = np.sort(np.ravel_multi_index(planted.T, shape))
    need = nnz - taken.size
    if total - taken.size < need:
        raise ValueError(f"shape {shape} holds fewer than {nnz} coordinates")
    lin = np.empty(0, dtype=np.int64)
    while lin.size < need:
        draw = rng.integers(0, total, size=need + need // 8 + 64)
        lin = np.unique(np.concatenate([lin, draw]))
        lin = lin[~np.isin(lin, taken, assume_unique=True)]
    lin = rng.permutation(lin)[:need]
    noise = np.stack(np.unravel_index(lin, shape), axis=1)

    order = rng.permutation(nnz)
    return Pattern(
        shape=shape,
        indices=np.concatenate([planted, noise])[order].astype(np.int32),
        block=np.concatenate([block, np.full(need, -1, np.int32)])[order],
        offsets=np.concatenate([offs, np.zeros((need, ndim), np.int32)])[order],
        sides=sides,
        components=components,
    )


def surrogate(shape: Sequence[int], nnz: int, components: int,
              rng: np.random.Generator) -> Tuple[np.ndarray, np.ndarray]:
    """``(indices, values)`` of one surrogate tensor."""
    p = pattern(shape, nnz, components, rng)
    return p.indices, p.values(rng)
