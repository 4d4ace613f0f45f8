"""Block-sparse boolean SpGEMM — the SGB composition primitive on TPU.

GPU/ASIC SpGEMM is hash/CSR based; the MXU wants dense tiles.  Adjacency is
stored as (T, T)-tiled dense 0/1 blocks plus a tile-occupancy bitmap; the
kernel multiplies only (m,k)x(k,n) tile pairs where both tiles are occupied
(pl.when skip), accumulating a saturating boolean OR.  Semantic graphs are
extremely block-sparse (real relations touch a tiny fraction of tile
pairs), so occupancy pruning removes most of the MACs — this is the
TPU-native analogue of the redundancy the CTT removes at plan level, and
benchmarks report the pruned-vs-dense MAC ratio.

Grid: (Mt, Nt, Kt), k innermost accumulating into the output tile.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import use_interpret

TILE = 128


def _spgemm_kernel(
    a_occ_ref, b_occ_ref,  # scalar-prefetch: (Mt*Kt,), (Kt*Nt,) int32
    a_ref, b_ref,  # (T, T) tiles
    o_ref,  # (T, T) output tile
    *, kt: int, nt: int,
):
    mi = pl.program_id(0)
    ni = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    live = (a_occ_ref[mi * kt + ki] > 0) & (b_occ_ref[ki * nt + ni] > 0)

    @pl.when(live)
    def _mac():
        acc = a_ref[...].astype(jnp.float32) @ b_ref[...].astype(jnp.float32)
        o_ref[...] += acc.astype(o_ref.dtype)

    @pl.when(ki == pl.num_programs(2) - 1)
    def _saturate():
        o_ref[...] = (o_ref[...] > 0).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def spgemm_bsr(
    a: jax.Array,  # (M, K) 0/1, M,K multiples of TILE
    b: jax.Array,  # (K, N) 0/1
    a_occ: jax.Array,  # (Mt*Kt,) int32 tile occupancy
    b_occ: jax.Array,  # (Kt*Nt,) int32
    interpret: Optional[bool] = None,
) -> jax.Array:
    if interpret is None:
        interpret = use_interpret()
    m, k = a.shape
    _, n = b.shape
    mt, kt, nt = m // TILE, k // TILE, n // TILE
    kern = functools.partial(_spgemm_kernel, kt=kt, nt=nt)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(mt, nt, kt),
        in_specs=[
            pl.BlockSpec((TILE, TILE), lambda mi, ni, ki, ao, bo: (mi, ki)),
            pl.BlockSpec((TILE, TILE), lambda mi, ni, ki, ao, bo: (ki, ni)),
        ],
        out_specs=pl.BlockSpec((TILE, TILE), lambda mi, ni, ki, ao, bo: (mi, ni)),
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), a.dtype),
        interpret=interpret,
    )(a_occ, b_occ, a, b)


def tile_occupancy(dense: np.ndarray, tile: int = TILE) -> np.ndarray:
    """Flattened (rows_t * cols_t,) int32 occupancy bitmap of a 0/1 matrix."""
    r, c = dense.shape
    rt, ct = r // tile, c // tile
    occ = dense.reshape(rt, tile, ct, tile).sum(axis=(1, 3)) > 0
    return occ.reshape(-1).astype(np.int32)


def pad_to_tiles(dense: np.ndarray, tile: int = TILE) -> np.ndarray:
    r, c = dense.shape
    rp, cp = -(-r // tile) * tile, -(-c // tile) * tile
    out = np.zeros((rp, cp), dense.dtype)
    out[:r, :c] = dense
    return out


def compose_padded_blocked(
    a: np.ndarray,  # (Mp, Kp) 0/1, tile-padded
    b: np.ndarray,  # (Kp, Np) 0/1, tile-padded
    a_occ: np.ndarray,  # (Mt*Kt,) int32
    b_occ: np.ndarray,  # (Kt*Nt,) int32
    interpret: Optional[bool] = None,
) -> Tuple[np.ndarray, np.ndarray, dict]:
    """Compose pre-padded operands; returns (padded result, its occupancy,
    pruning stats).

    This is the device SGB executor's hot path: along a composition chain
    (A@B)@C@... every intermediate stays in tile-padded layout with a
    cached occupancy bitmap, so only the chain's *inputs* ever pay the
    pad + occupancy-scan cost.
    """
    out = spgemm_bsr(
        jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32),
        jnp.asarray(a_occ), jnp.asarray(b_occ), interpret=interpret,
    )
    out = np.asarray(jax.block_until_ready(out))
    mt, kt = a.shape[0] // TILE, a.shape[1] // TILE
    nt = b.shape[1] // TILE
    live = int(
        ((a_occ.reshape(mt, kt, 1) > 0) & (b_occ.reshape(1, kt, nt) > 0)
         ).sum())
    stats = {
        "tile_pairs_total": int(mt * nt * kt),
        "tile_pairs_live": live,
        "macs_dense": int(mt * nt * kt) * TILE ** 3,
        "macs_live": live * TILE ** 3,
    }
    return out, tile_occupancy(out), stats


def compose_dense_blocked(
    a_dense: np.ndarray, b_dense: np.ndarray, interpret: Optional[bool] = None
) -> Tuple[np.ndarray, dict]:
    """Boolean compose via the kernel; returns (result, pruning stats)."""
    m0, k0 = a_dense.shape
    _, n0 = b_dense.shape
    a = pad_to_tiles(a_dense)
    b = pad_to_tiles(b_dense)
    out, _, stats = compose_padded_blocked(
        a, b, tile_occupancy(a), tile_occupancy(b), interpret=interpret)
    stats = {k: stats[k] for k in ("tile_pairs_total", "tile_pairs_live")}
    return out[:m0, :n0], stats
