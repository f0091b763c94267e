"""Pallas TPU kernel: segmented running sums via tiled equality matmuls.

The QoS token bucket needs, per lane i, the bytes attempted by earlier
same-bucket lanes (sequential TBF admission semantics, qos_ratelimit.c:
70-104 applied per packet). ops.qos recovers this with a stable
argsort + segment cumsum — O(B log B) with two sorts per batch, and XLA
sorts are the most serial op in the pipeline.

This kernel computes the same quantity on the MXU instead:

    prefix_incl[i] = sum_j [slot_j == slot_i][j <= i] * vec[j]
    total[i]       = sum_j [slot_j == slot_i]         * vec[j]

tiled as [T, T] equality blocks contracted against vec tiles — one
(T x T) @ (T, 1) matmul per grid cell. The full [B, B] equality matrix
is never materialized in HBM (at B=8192 it would be 256MB f32): each
tile lives in VMEM only. O(B^2/T) MXU work replaces the sort's serial
latency, and lane order IS arrival order — no sort, no unsort.

Grid iteration order is (i outer, j inner); the output tile for row
block i accumulates across the j sweep (revisited-output pattern),
initialized at j == 0.

f32 accumulation is exact for per-bucket byte sums < 2^24 — same
integer-exactness envelope ops.qos documents for its u32 path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

LANE_TILE = 256  # rows per grid cell; [256, 256] eq tiles feed the MXU
SUBLANES = 8  # Mosaic tiling: rank>=2 blocks need (8k, 128m) trailing dims


def _block(shape, index_map):
    return pl.BlockSpec(shape, index_map, memory_space=pltpu.VMEM)


def _seg_kernel(slot_i_ref, slot_j_ref, vec_ref, pref_ref, tot_ref,
                *, want_prefix: bool, want_total: bool):
    # refs are (1, SUBLANES, T): each tile's lane vector replicated across
    # 8 sublanes so the block's trailing dims are Mosaic-legal (8, 256) —
    # a (1, T) block is rejected ("block shape ... divisible by 8 and 128",
    # the round-2 lowering failure). Row 0 carries the data.
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        pref_ref[:] = jnp.zeros_like(pref_ref)
        tot_ref[:] = jnp.zeros_like(tot_ref)

    T = pref_ref.shape[2]
    slots_i = slot_i_ref[0, 0, :]
    slots_j = slot_j_ref[0, 0, :]
    vec_j = vec_ref[0, 0, :]
    eq = (slots_i[:, None] == slots_j[None, :]).astype(jnp.float32)
    contrib = jnp.dot(eq, vec_j[:, None],
                      preferred_element_type=jnp.float32)[:, 0]
    if want_total:
        tot_ref[0, 0, :] = tot_ref[0, 0, :] + contrib

    if want_prefix:
        # prefix: blocks left of the diagonal contribute fully; the
        # diagonal block takes its lower triangle (arrival order within
        # the block)
        @pl.when(j < i)
        def _():
            pref_ref[0, 0, :] = pref_ref[0, 0, :] + contrib

        @pl.when(j == i)
        def _():
            row = jax.lax.broadcasted_iota(jnp.int32, (T, T), 0)
            col = jax.lax.broadcasted_iota(jnp.int32, (T, T), 1)
            tri = jnp.where(col <= row, eq, 0.0)
            pref = jnp.dot(tri, vec_j[:, None],
                           preferred_element_type=jnp.float32)[:, 0]
            pref_ref[0, 0, :] = pref_ref[0, 0, :] + pref


@functools.partial(jax.jit, static_argnames=("interpret", "compute"))
def seg_prefix_total(slot: jax.Array, vec: jax.Array, interpret: bool = False,
                     compute: str = "both"):
    """Per-lane same-slot inclusive prefix sum and full segment total.

    slot: [B] int32 segment ids (make them unique-negative for lanes that
    must not group). vec: [B] values (cast to f32; per-bucket sums are
    exact below 2^24). compute: "prefix" | "total" | "both" — skip the
    unneeded half of the tile work.
    Returns (prefix_incl [B] f32, total [B] f32); the uncomputed output
    is zeros.
    """
    B = slot.shape[0]
    T = LANE_TILE
    nt = -(-B // T)
    Bp = nt * T
    slot = slot.astype(jnp.int32)
    vec = vec.astype(jnp.float32)
    if Bp != B:
        # pad lanes get unique negative ids that match nothing real
        pad_ids = -(jnp.arange(Bp - B, dtype=jnp.int32) + (1 << 30))
        slot = jnp.concatenate([slot, pad_ids])
        vec = jnp.concatenate([vec, jnp.zeros((Bp - B,), dtype=jnp.float32)])

    # lane vectors replicated across 8 sublanes for Mosaic-legal blocks
    slot3d = jnp.broadcast_to(slot.reshape(nt, 1, T), (nt, SUBLANES, T))
    vec3d = jnp.broadcast_to(vec.reshape(nt, 1, T), (nt, SUBLANES, T))

    kernel = functools.partial(_seg_kernel,
                               want_prefix=compute in ("prefix", "both"),
                               want_total=compute in ("total", "both"))
    pref, tot = pl.pallas_call(
        kernel,
        grid=(nt, nt),
        in_specs=[
            _block((1, SUBLANES, T), lambda i, j: (i, 0, 0)),
            _block((1, SUBLANES, T), lambda i, j: (j, 0, 0)),
            _block((1, SUBLANES, T), lambda i, j: (j, 0, 0)),
        ],
        out_specs=[
            _block((1, SUBLANES, T), lambda i, j: (i, 0, 0)),
            _block((1, SUBLANES, T), lambda i, j: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nt, SUBLANES, T), jnp.float32),
            jax.ShapeDtypeStruct((nt, SUBLANES, T), jnp.float32),
        ],
        interpret=interpret,
    )(slot3d, slot3d, vec3d)
    return pref[:, 0, :].reshape(Bp)[:B], tot[:, 0, :].reshape(Bp)[:B]
