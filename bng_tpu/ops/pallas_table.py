"""Pallas TPU kernel: the fused bucketized-cuckoo table probe.

`ops/table.py:device_lookup` is the repo's hottest code — every stage of
the fused pipeline (DHCP 3-tier chain, NAT44 forward/reverse, antispoof,
garden, PPPoE) funnels through it, and PERF_NOTES §2 measured the XLA
lowering of the composed cascade as the throughput ceiling: narrow
(<8-word-row) gathers serialize to ~7 ns/element loops, and even the
wide-row relayout leaves each probe as 3+ separate HBM gather fusions
that XLA stages through VMEM copies of its own choosing.

This kernel fuses the whole probe into ONE program over the batch:

    hash -> two wide bucket-row gathers from HBM (per-lane async DMA,
    driven by scalar-prefetched bucket indices) -> per-way lane compare
    -> stash broadcast compare -> value fetch (the candidate value
    blocks ride the same DMA wave; stash values select by mask)

Layout notes (Mosaic tiling wants (8k, 128m) trailing dims):

- Per-lane probe rows are DMA'd from HBM (`pl.ANY`) into VMEM scratch
  whose lane dim is padded to 128; the DMAs are contiguous row copies
  (the measured-fast shape), issued for a whole lane tile and then
  awaited — start-all/wait-all on one DMA semaphore.
- Query words arrive as [K, nt, 8, T] blocks (the ops/pallas_qos
  sublane-replication trick) and bucket indices are recomputed
  in-kernel from them (vectorized lowbias32) so slot arithmetic is
  vector math; the scalar-prefetch copy of the same indices drives the
  DMA descriptors.
- Stash rows/values are transposed to [word, stash] lane-major arrays
  so the stash compare is a (T, stash) broadcast and the value select
  a masked integer sum — never a float matmul (value words are uint32
  and must survive bit-exactly; f32 accumulation would corrupt words
  >= 2^24).
- All selects are first-match-wins in device_lookup's candidate order
  (b1 ways, b2 ways, stash) so the kernel is BIT-IDENTICAL to the XLA
  path and the host mirror — pinned by tests/test_pallas_table.py
  across every table geometry in the repo.

Interpret-mode caveats (PERF_NOTES §13): on every non-TPU backend the
kernel runs under `interpret=True` — same semantics, executed by the
Pallas interpreter — so the whole tier-1 suite exercises the kernel
without hardware. Mosaic lowering is a separate question, and today the
answer is NO: compiled for a described v5e
(tests/test_tpu_lowering.py), Mosaic refuses the per-lane row DMAs —
"Slice shape along dimension 1 must be aligned to tiling (128), but is
32": a packed [NB, WAYS*KW] probe row is 32 words, a quarter of a lane
tile. The kernel needs a 128-word row layout before it can run on a
chip (ROADMAP D11).

Impl selection lives in ops/table.py (`BNG_TABLE_IMPL=xla|pallas|auto`,
the qos_kernel[sort|pallas] mold); this module is only the kernel.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bng_tpu.ops.hashing import SEED1, SEED2, hash_words

LANE_TILE = 128  # lanes per grid step (the DMA wave size)
SUBLANES = 8  # Mosaic tiling: rank>=2 blocks need (8k, 128m) trailing dims


def _pad_to(n: int, m: int) -> int:
    return -(-n // m) * m


def _probe_kernel(idx_ref, krows_ref, vals_ref, qw_ref, stash_ref, svals_ref,
                  found_ref, slot_ref, vals_out_ref,
                  krows_scr, vrows_scr, sem,
                  *, K, KW, V, T, WS, WSP, VP, nbuckets, stash, SP, WAYS):
    i = pl.program_id(0)

    def _copies(lane):
        """The 4 DMA descriptors of one lane: 2 packed bucket probe rows
        + the 2 matching 4-way value blocks (contiguous in vals — slot
        layout is bucket-major). Built identically in the start and
        wait loops so each wait consumes its own copy's bytes from the
        shared semaphore."""
        b1 = idx_ref[0, i * T + lane]
        b2 = idx_ref[1, i * T + lane]
        out = []
        for side, b in ((0, b1), (1, b2)):
            out.append(pltpu.make_async_copy(
                krows_ref.at[b],
                krows_scr.at[lane, pl.ds(side * WSP, WS)], sem))
            out.append(pltpu.make_async_copy(
                vals_ref.at[pl.ds(b * WAYS, WAYS), :],
                vrows_scr.at[lane, pl.ds(side * WAYS, WAYS), pl.ds(0, V)],
                sem))
        return out

    def _start(lane, _):
        for c in _copies(lane):
            c.start()
        return 0

    jax.lax.fori_loop(0, T, _start, 0, unroll=False)

    def _wait(lane, _):
        for c in _copies(lane):
            c.wait()
        return 0

    jax.lax.fori_loop(0, T, _wait, 0, unroll=False)

    # query words as (T,) vectors; bucket ids recomputed in-kernel
    # (vectorized — the scalar-prefetch copy only drives the DMAs)
    qws = [qw_ref[k, 0, 0, :] for k in range(K)]
    mask = np.uint32(nbuckets - 1)
    b1v = (hash_words(qws, SEED1) & mask).astype(jnp.int32)
    b2v = (hash_words(qws, SEED2) & mask).astype(jnp.int32)

    rows = krows_scr[:]  # (T, 2*WSP) — gathered probe rows
    vrows = vrows_scr[:]  # (T, 2*WAYS, VP) — candidate value blocks

    # per-way match in device_lookup's candidate order: b1 ways, b2 ways
    m = []
    slots = []
    for side in range(2):
        base = side * WSP
        bv = b1v if side == 0 else b2v
        for w in range(WAYS):
            col = base + w * KW
            mk = rows[:, col + K] != 0  # used flag
            for k in range(K):
                mk = mk & (rows[:, col + k] == qws[k])
            m.append(mk)
            slots.append(bv * WAYS + w)
    any_before = jnp.zeros((T,), dtype=bool)
    first = []
    for w in range(2 * WAYS):
        first.append(m[w] & ~any_before)
        any_before = any_before | m[w]
    found_b = any_before

    slot = jnp.zeros((T,), dtype=jnp.int32)
    for w in range(2 * WAYS):
        slot = slot + jnp.where(first[w], slots[w], 0)

    # value select: masked integer sums (at most one `first` lane set) —
    # exact for all uint32 words, unlike an MXU f32 contraction
    vcols = []
    for v in range(V):
        col = jnp.zeros((T,), dtype=jnp.uint32)
        for w in range(2 * WAYS):
            col = col + jnp.where(first[w], vrows[:, w, v], np.uint32(0))
        vcols.append(col)

    if stash > 0:
        sm = stash_ref[K, :][None, :] != 0  # (1, SP) used row
        for k in range(K):
            sm = sm & (qws[k][:, None] == stash_ref[k, :][None, :])
        # first stash match per lane = the lowest matching stash index
        # (a lane-min; Mosaic has no cumsum lowering)
        lane_i = jax.lax.broadcasted_iota(jnp.int32, (T, SP), 1)
        sidx = jnp.min(jnp.where(sm, lane_i, np.int32(SP)), axis=1)
        sfirst = sm & (lane_i == sidx[:, None])
        found_s = sidx < SP
        sbase = np.int32(nbuckets * WAYS)
        slot = jnp.where(found_b, slot,
                         jnp.where(found_s, sbase + sidx, 0))
        for v in range(V):
            # one-hot sum in int32 (Mosaic reduces no unsigned ints);
            # the bitcast round-trip keeps every uint32 word exact
            srow = jax.lax.bitcast_convert_type(svals_ref[v, :], jnp.int32)
            sval = jax.lax.bitcast_convert_type(
                jnp.sum(jnp.where(sfirst, srow[None, :], np.int32(0)),
                        axis=1), jnp.uint32)
            vcols[v] = jnp.where(found_b, vcols[v],
                                 jnp.where(found_s, sval, 0))
        found = found_b | found_s
    else:
        found = found_b

    # not-found slot parity: xla_lookup's argmax over all-False picks
    # candidate 0 = b1*WAYS (slot is documented valid-only-where-found,
    # but bit-exactness is the contract the property tests pin)
    slot = jnp.where(found, slot, b1v * WAYS)
    found_ref[0, 0, :] = found.astype(jnp.uint32)
    slot_ref[0, 0, :] = slot
    for v in range(V):
        vals_out_ref[v, 0, 0, :] = jnp.where(found, vcols[v], np.uint32(0))


@functools.partial(jax.jit, static_argnames=("nbuckets", "stash",
                                             "interpret"))
def _probe_jit(krows, stash_rows, vals, query, nbuckets, stash, interpret):
    """Jitted entry (the ops/pallas_qos mold) so EAGER callers — tests,
    the bench impl race — pay one compile per geometry instead of a
    fresh kernel trace per call; traced callers (the engine programs)
    inline it."""
    from bng_tpu.ops.table import WAYS  # late: table.py imports us lazily
    B, K = query.shape
    KW = stash_rows.shape[1]
    V = vals.shape[1]
    WS = WAYS * KW
    WSP = _pad_to(WS, 128)
    VP = _pad_to(V, 128)
    T = LANE_TILE
    Bp = _pad_to(max(B, T), T)
    nt = Bp // T

    q = query
    if Bp != B:
        # pad lanes carry zero keys: their DMAs land on valid buckets
        # (hash & mask is always in range) and their lanes are sliced off
        q = jnp.concatenate([q, jnp.zeros((Bp - B, K), dtype=jnp.uint32)])
    words = [q[:, k] for k in range(K)]
    mask = np.uint32(nbuckets - 1)
    b1 = (hash_words(words, SEED1) & mask).astype(jnp.int32)
    b2 = (hash_words(words, SEED2) & mask).astype(jnp.int32)
    idx = jnp.stack([b1, b2])  # [2, Bp] scalar prefetch (SMEM)

    # query words replicated across sublanes: [K, nt, SUB, T] blocks
    qws = jnp.broadcast_to(q.T.reshape(K, nt, 1, T), (K, nt, SUBLANES, T))

    # stash probe rows + value rows transposed to [word, stash-lane]
    SP = max(128, _pad_to(max(stash, 1), 128))
    KP = _pad_to(K + 1, 8)
    VR = _pad_to(max(V, 1), 8)
    stash_t = jnp.zeros((KP, SP), dtype=jnp.uint32)
    svals_t = jnp.zeros((VR, SP), dtype=jnp.uint32)
    if stash > 0:
        stash_t = stash_t.at[: K + 1, :stash].set(stash_rows[:, : K + 1].T)
        svals_t = svals_t.at[:V, :stash].set(vals[nbuckets * WAYS:, :].T)

    kernel = functools.partial(
        _probe_kernel, K=K, KW=KW, V=V, T=T, WS=WS, WSP=WSP, VP=VP,
        nbuckets=nbuckets, stash=stash, SP=SP, WAYS=WAYS)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nt,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),  # krows stay in HBM
            pl.BlockSpec(memory_space=pl.ANY),  # vals stay in HBM
            pl.BlockSpec((K, 1, SUBLANES, T), lambda i, idx_ref: (0, i, 0, 0)),
            pl.BlockSpec((KP, SP), lambda i, idx_ref: (0, 0)),
            pl.BlockSpec((VR, SP), lambda i, idx_ref: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, SUBLANES, T), lambda i, idx_ref: (i, 0, 0)),
            pl.BlockSpec((1, SUBLANES, T), lambda i, idx_ref: (i, 0, 0)),
            pl.BlockSpec((V, 1, SUBLANES, T), lambda i, idx_ref: (0, i, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((T, 2 * WSP), jnp.uint32),
            pltpu.VMEM((T, 2 * WAYS, VP), jnp.uint32),
            pltpu.SemaphoreType.DMA,
        ],
    )
    found, slot, out_vals = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((nt, SUBLANES, T), jnp.uint32),
            jax.ShapeDtypeStruct((nt, SUBLANES, T), jnp.int32),
            jax.ShapeDtypeStruct((V, nt, SUBLANES, T), jnp.uint32),
        ],
        interpret=interpret,
    )(idx, krows, vals, qws, stash_t, svals_t)
    return (found[:, 0, :].reshape(Bp)[:B] != 0,
            slot[:, 0, :].reshape(Bp)[:B],
            out_vals[:, :, 0, :].reshape(V, Bp)[:, :B].T)


def pallas_probe(krows: jax.Array, stash_rows: jax.Array, vals: jax.Array,
                 query: jax.Array, nbuckets: int, stash: int,
                 interpret: bool | None = None):
    """The raw fused probe: returns (found [B] bool, slot [B] i32,
    vals [B, V] u32) bit-identical to ops.table.xla_lookup.

    interpret=None resolves per backend: Mosaic lowering is TPU-only,
    every other backend runs the Pallas interpreter (ADVICE r1: a GPU
    backend must not try to compile the Mosaic kernel).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _probe_jit(krows, stash_rows, vals, query, nbuckets, stash,
                      interpret)


def pallas_lookup(state, query: jax.Array, nbuckets: int, stash: int,
                  interpret: bool | None = None):
    """device_lookup-shaped wrapper: TableState in, LookupResult out."""
    from bng_tpu.ops.table import LookupResult

    found, slot, vals = pallas_probe(state.krows, state.stash_rows,
                                     state.vals, query, nbuckets, stash,
                                     interpret=interpret)
    return LookupResult(found=found, slot=slot, vals=vals)
