"""Virtualization layer (paper Algorithms 3-4, 7-9).

Maps arbitrarily-sized matrices onto a fixed physical multi-MCA system:
an ``R x C`` tile of MCAs, each with ``r x c`` cells, so the physical capacity is
``(R*r) x (C*c)``.  Three cases (paper section 4.4):

  * ideal:      problem == capacity        -> direct mapping
  * non-ideal:  problem <  capacity        -> zeroPadding
  * large:      problem >  capacity        -> blockPartition + per-block mapping,
                each MCA is *reassigned* once per block (the paper's
                normalization factor for energy/latency in Fig. 5).

Everything here is shape arithmetic + reshapes; it is used both by the
pure-jnp reference crossbar simulation and by the Pallas kernel's grid layout
(where one kernel block == one MCA assignment).
"""
from __future__ import annotations

import dataclasses
import math
from typing import List, Tuple

import jax.numpy as jnp

__all__ = [
    "MCAGeometry",
    "zero_padding",
    "block_partition",
    "generate_vec_chunks",
    "reassignment_count",
]


@dataclasses.dataclass(frozen=True)
class MCAGeometry:
    """Physical system: R x C tile of MCAs, each r x c cells."""

    tile_rows: int = 8      # R
    tile_cols: int = 8      # C
    cell_rows: int = 512    # r
    cell_cols: int = 512    # c

    @property
    def capacity(self) -> Tuple[int, int]:
        return (self.tile_rows * self.cell_rows, self.tile_cols * self.cell_cols)

    @property
    def n_mcas(self) -> int:
        return self.tile_rows * self.tile_cols

    @property
    def cells_per_mca(self) -> int:
        return self.cell_rows * self.cell_cols


def _ceil_to(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def zero_padding(a: jnp.ndarray, geom: MCAGeometry) -> jnp.ndarray:
    """Pad a (m, n) matrix or (n,) vector up to whole-block multiples (Alg. 7).

    Padding is to the next multiple of the *capacity* in each dim (so that a
    subsequent block partition tiles exactly)."""
    cap_m, cap_n = geom.capacity
    if a.ndim == 1:
        n = a.shape[0]
        return jnp.pad(a, (0, _ceil_to(n, cap_n) - n))
    m, n = a.shape
    return jnp.pad(a, ((0, _ceil_to(m, cap_m) - m), (0, _ceil_to(n, cap_n) - n)))


def block_partition(a: jnp.ndarray, geom: MCAGeometry) -> jnp.ndarray:
    """blockPartition (Alg. 3): split padded (M, N) into capacity-sized blocks.

    Returns an array of shape (mb, nb, cap_m, cap_n) -- blocks indexed [i, j].
    """
    cap_m, cap_n = geom.capacity
    a = zero_padding(a, geom)
    m, n = a.shape
    mb, nb = m // cap_m, n // cap_n
    return a.reshape(mb, cap_m, nb, cap_n).transpose(0, 2, 1, 3)


def generate_vec_chunks(x: jnp.ndarray, geom: MCAGeometry) -> jnp.ndarray:
    """generateVecChunksSet (Alg. 9): x -> (nb, C, c) chunks matching columns."""
    x = zero_padding(x, geom)
    cap_n = geom.capacity[1]
    nb = x.shape[0] // cap_n
    return x.reshape(nb, geom.tile_cols, geom.cell_cols)


def reassignment_count(m: int, n: int, geom: MCAGeometry) -> int:
    """How many times each physical MCA is (re)assigned for an (m, n) problem --
    the paper's virtualization normalization factor."""
    cap_m, cap_n = geom.capacity
    return math.ceil(m / cap_m) * math.ceil(n / cap_n)
