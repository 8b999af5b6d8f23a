"""Attention ops: Pallas flash attention + transformer contrib parity.

TPU-first design: the hot path is a Pallas flash-attention kernel
(online-softmax over K/V blocks, f32 accumulators in VMEM scratch,
grid = (batch*heads, q_blocks, k_blocks) with the k dimension innermost
so scratch persists across it).  Backward is the standard flash
split as two Pallas kernels — dk/dv (q innermost) and dq (k
innermost), recomputing scores per block pair from the saved
logsumexp so the (block, block) probability tiles never leave VMEM;
an XLA `lax.scan` backward is kept as the A/B oracle
(`MXNET_TPU_FLASH_BWD=scan`).  Per-row vectors (lse/delta) cross the
pallas boundary lane-broadcast (see `_LSE_LANES`) to satisfy the TPU
(8, 128) block-tiling rule — statically guarded on CPU by
tests/test_pallas_tiling_guard.py.

The schedule.  The work of the three kernels follows the scores that
count, not the rectangle.  For a call's ``(causal, seq_q, seq_k,
block_q, block_k)``, all known when it is traced, :func:`_live_tiles`
sorts every tile of 128 rows by 128 keys of the padded score matrix
into *dead* (wholly above the diagonal, or wholly in the padding: never
visited), *edge* (the diagonal, the end of the real keys or the end of
the real rows crosses it: the one kind that builds a mask, and only the
comparisons that crossing needs) and *interior* (every score counts: no
``iota``, no compare, no ``select``).  A grid step's block is unrolled
over its live tiles: neighbours of one kind are merged into one matmul
(:func:`_tile_groups`), so a block wholly under the diagonal runs the
whole-block body less its mask, and a block the diagonal crosses runs,
for each 128 rows, one online-softmax update over the keys at or under
them.  The forward walks its blocks at that grain; the two backward
kernels walk theirs at 256 (``_BWD_TILE``: fewer and larger float32
products for four more dead tiles of 128 in a head of 1024, measured).
Blocks that stand alike to the diagonal and to the padded rim
share one unrolled body, chosen by the program ids
(:func:`_block_cases`); under ``causal`` with ``block_q != block_k``
the diagonal's offset inside a block varies with the step, and a whole
block is then one tile whose kind (masked edge or plain interior) is
read from the program ids.  A grid step whose whole block is dead runs
nothing and fetches nothing: its index maps stay on the nearest live
block of the same inner loop, and the pipeline skips a fetch whose block
did not change.  ``_live_tiles`` also counts ``(visited, edge, all)``:
36 / 8 / 64 for a causal 1024 x 1024 call whatever its blocks, 64 / 0 /
64 without ``causal`` (no mask at all).  A masked score adds an exact 0
to every sum it is in, so leaving it out changes at most the order of a
float32 reduction inside a block.

Parity targets (API, not implementation):
- `_contrib_interleaved_matmul_selfatt_qk/valatt`,
  `_contrib_interleaved_matmul_encdec_qk/valatt`
  (reference: src/operator/contrib/transformer.cc:650-860 — fused
  interleaved-projection attention matmuls; semantics documented in the
  op describe() blocks there).
- `_contrib_div_sqrt_dim` (src/operator/contrib/transformer.cc).
- `flash_attention` itself is a capability the reference lacks — the
  long-context path called for by SURVEY.md §5 ("Long-context /
  sequence parallelism: absent in reference").

Sequence/context parallelism (ring attention over a mesh axis) builds
on `_online_block` below; see mxnet_tpu/parallel/ring_attention.py.
"""
from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import kernels as _kernels
from .registry import register

__all__ = ["flash_attention", "attention_reference", "online_block_update",
           "masked_softmax"]

_NEG_INF = -1e30  # finite -inf stand-in: keeps masked-row math NaN-free

# Per-row vectors (lse, delta) cross the pallas boundary with this many
# broadcast lanes: TPU block specs need the last two dims (sublane,
# lane) divisible by (8, 128) or equal to the array's, so a (1, block_q)
# block over a (BH, S) array cannot lower.  Upstream flash/splash
# attention store logsumexp the same way (NUM_LANES) and slice lane 0
# outside the kernel.  CPU interpret mode accepts anything — only a
# real-TPU run exercises this constraint.
_LSE_LANES = 128


# --------------------------------------------------------------------------
# reference (materialized-scores) attention — the numerics oracle
# --------------------------------------------------------------------------

def attention_reference(q, k, v, causal=False, sm_scale=None, bias=None):
    """Plain softmax(QK^T)V on (B, H, S, D) tensors."""
    d = q.shape[-1]
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if bias is not None:
        s = s + bias
    if causal:
        qpos = lax.broadcasted_iota(jnp.int32, s.shape, 2)
        kpos = lax.broadcasted_iota(jnp.int32, s.shape, 3)
        s = jnp.where(qpos >= kpos, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)


# --------------------------------------------------------------------------
# the live-tile schedule: which tiles of the score matrix a call visits,
# and which of them build a mask (module docstring, "The schedule")
# --------------------------------------------------------------------------

_TILE = 128  # the schedule's grain: rows and keys of one score tile
# The grain each kernel walks its blocks at, measured on a v5e (PERF.md,
# PR 34).  A coarser grain visits more dead scores (10 tiles of 256 are
# 40 of 128 where the triangle holds 36) in fewer, larger matmuls: the
# backward kernels' float32 transposed products (P^T dO, dS^T Q) stream
# a tile's rows past each block of weights and are fed badly by 128; the
# forward's products are in the input dtype and it gains from the finer
# triangle.
_FWD_TILE, _BWD_TILE = _TILE, 2 * _TILE


def _tile_kind(causal, q0, q1, k0, k1, seq_q, seq_k):
    """What the scores of rows ``[q0, q1)`` against keys ``[k0, k1)``
    need: ``(live, diag, k_crop, q_crop)``.  ``live``: at least one of
    them counts.  The other three name the masks an edge needs: the
    diagonal crosses the real part of the tile, keys past ``seq_k``,
    rows past ``seq_q``; none set on a live tile means every score
    counts (interior).  Plain comparisons, so the bounds may be Python
    ints (the static schedule) or traced scalars (a whole block of the
    fallback grid)."""
    live = (q0 < seq_q) & (k0 < seq_k)
    diag = False
    if causal:      # the diagonal is qpos >= kpos from the top-left corner
        live = live & (k0 < q1) & (k0 < seq_q)
        diag = (k1 - 1 > q0) & (seq_k - 1 > q0)
    return live, diag, k1 > seq_k, q1 > seq_q


@functools.lru_cache(maxsize=256)
def _live_tiles(causal, seq_q, seq_k, block_q, block_k, grain=_TILE):
    """The static schedule of one call: ``(blocks, (visited, edge,
    all))``.  ``blocks[i][j]`` lists the tiles grid block ``(i, j)``
    visits as ``(r, c, (diag, k_crop, q_crop))``, ``r`` and ``c`` in
    tiles of ``gcd(block, grain)`` rows and keys inside the block; a
    dead tile (wholly above the diagonal or wholly in the padding) is
    not listed, an edge tile has a flag set (:func:`_tile_kind`).  The
    triple counts tiles over the whole padded rectangle; it is static,
    so it is the mechanism's "how often it engages" figure."""
    gq, gk = math.gcd(block_q, grain), math.gcd(block_k, grain)
    nq, nk = -(-seq_q // block_q), -(-seq_k // block_k)
    visited = edge = 0
    blocks = []
    for i in range(nq):
        row = []
        for j in range(nk):
            tiles = []
            for r in range(block_q // gq):
                q0 = i * block_q + r * gq
                for c in range(block_k // gk):
                    k0 = j * block_k + c * gk
                    live, *flags = _tile_kind(causal, q0, q0 + gq, k0,
                                              k0 + gk, seq_q, seq_k)
                    if live:
                        tiles.append((r, c, tuple(flags)))
                        edge += any(flags)
            visited += len(tiles)
            row.append(tuple(tiles))
        blocks.append(tuple(row))
    return tuple(blocks), (visited, edge,
                           nq * nk * (block_q // gq) * (block_k // gk))


def _last_live_k(i, j, causal, block_q, block_k):
    """The k block grid step ``(i, j)`` fetches: ``j``, but a dead step
    of a causal grid (they close a row of the forward and ``dq`` grids)
    stays on the row's last live block, and the pipeline skips a fetch
    whose block did not change."""
    return jnp.minimum(j, ((i + 1) * block_q - 1) // block_k) if causal else j


def _first_live_q(j, i, causal, block_q, block_k, nq):
    """The q block step ``(j, i)`` of the ``dkv`` grid fetches: dead
    steps open a row there, and stay on its first live block."""
    if not causal:
        return i
    return jnp.minimum(jnp.maximum(i, (j * block_k) // block_q), nq - 1)


@functools.lru_cache(maxsize=256)
def _alike_blocks(causal, seq_q, seq_k, block_q, block_k, grain, crop_q):
    """The grid's blocks grouped by how they stand to the diagonal (under
    it, on it, above it) and to the padded rim (last row, last column):
    ``{(rel, last_q, last_k): (tiles, (a, b))}``, ``(a, b)`` one block of
    the group; ``None`` where two blocks of a group visit different tiles
    or stand differently to a diagonal they mask."""
    blocks, _ = _live_tiles(causal, seq_q, seq_k, block_q, block_k, grain)
    nq, nk = len(blocks), len(blocks[0])
    rim_q, rim_k = seq_q % block_q != 0, seq_k % block_k != 0
    groups, offsets = {}, {}
    for a in range(nq):
        for b in range(nk):
            key = ((a > b) - (a < b) if causal else 0,
                   rim_q and a == nq - 1, rim_k and b == nk - 1)
            tiles = tuple((r, c, (d, kc, qc and crop_q))
                          for r, c, (d, kc, qc) in blocks[a][b])
            # a diagonal's mask also depends on where the block stands
            off = (a * block_q - b * block_k
                   if any(t[2][0] for t in tiles) else None)
            if groups.setdefault(key, (tiles, (a, b)))[0] != tiles \
                    or offsets.setdefault(key, off) != off:
                return None
    return groups


def _block_cases(i, j, *, causal, seq_q, seq_k, block_q, block_k,
                 grain=_TILE, crop_q=True):
    """What grid step ``(i, j)`` (program ids) does, as ``[(pred, tiles,
    (gq, gk), (q0, k0))]``: under ``pred`` (``True``: always) visit
    ``tiles`` of ``gq`` rows by ``gk`` keys, the block's first score at
    ``(q0, k0)``.  Blocks that stand alike to the diagonal and to the
    padded rim visit the same tiles, so a call has a handful of cases
    and a dead block has none.  Where they do not (``block_q !=
    block_k`` under ``causal``: the diagonal's offset inside a block
    varies with the step) a block is one tile whose kind is read from
    the program ids: masked if an edge, plain if interior.  ``grain`` is
    the calling kernel's (``_FWD_TILE``, ``_BWD_TILE``); ``crop_q=False``
    (the forward, whose padded rows are cropped by the caller) leaves the
    ``qpos < seq_q`` mask out."""
    nq, nk = -(-seq_q // block_q), -(-seq_k // block_k)
    rim_q, rim_k = seq_q % block_q != 0, seq_k % block_k != 0
    alike = _alike_blocks(causal, seq_q, seq_k, block_q, block_k, grain,
                          crop_q)
    if alike is None:
        q0, k0 = i * block_q, j * block_k
        live, diag, kc, qc = _tile_kind(causal, q0, q0 + block_q, k0,
                                        k0 + block_k, seq_q, seq_k)
        edge = diag | kc | (qc & crop_q)
        return [(live & edge,
                 ((0, 0, (causal, rim_k, rim_q and crop_q)),),
                 (block_q, block_k), (q0, k0)),
                (live & jnp.logical_not(edge),
                 ((0, 0, (False, False, False)),),
                 (block_q, block_k), (q0, k0))]
    grain = (math.gcd(block_q, grain), math.gcd(block_k, grain))
    cases = []
    for (rel, last_q, last_k), (tiles, (a, b)) in alike.items():
        if not tiles:
            continue
        conds = []
        if causal:
            conds.append(i > j if rel > 0 else i == j if rel == 0 else i < j)
        if rim_q:
            conds.append(i == nq - 1 if last_q else i != nq - 1)
        if rim_k:
            conds.append(j == nk - 1 if last_k else j != nk - 1)
        cases.append((functools.reduce(jnp.logical_and, conds)
                      if conds else True,
                      tiles, grain, (a * block_q, b * block_k)))
    return cases


def _tile_groups(tiles, by):
    """Merge a block's live tiles into the matmuls a kernel runs:
    ``[(a0, a1, ((b0, b1, flags), ...))]``, ``a`` along axis ``by`` (0:
    q tiles, the forward's and ``dq``'s accumulator rows; 1: k tiles,
    ``dkv``'s) and ``b`` along the other.  Neighbours along ``b`` with
    equal flags are one piece, neighbours along ``a`` with equal pieces
    one group: an interior block is one group of one piece, the
    whole-block body less its mask."""
    lines = {}
    for tile in tiles:
        a, b, flags = tile[by], tile[1 - by], tile[2]
        pieces = lines.setdefault(a, [])
        if pieces and pieces[-1][1:] == (b, flags):
            pieces[-1] = (pieces[-1][0], b + 1, flags)
        else:
            pieces.append((b, b + 1, flags))
    groups = []
    for a in sorted(lines):
        pieces = tuple(lines[a])
        if groups and groups[-1][1:] == (a, pieces):
            groups[-1] = (groups[-1][0], a + 1, pieces)
        else:
            groups.append((a, a + 1, pieces))
    return groups


def _piece_mask(shape, q0, k0, flags, seq_q, seq_k):
    """The mask of one piece whose first score is ``(q0, k0)``: only the
    comparisons its flags name, ``None`` for an interior piece."""
    diag, k_crop, q_crop = flags
    if diag or q_crop:
        qpos = q0 + lax.broadcasted_iota(jnp.int32, shape, 0)
    if diag or k_crop:
        kpos = k0 + lax.broadcasted_iota(jnp.int32, shape, 1)
    terms = []
    if diag:
        terms.append(qpos >= kpos)
    if k_crop:
        terms.append(kpos < seq_k)
    if q_crop:
        terms.append(qpos < seq_q)
    return functools.reduce(jnp.logical_and, terms) if terms else None


def _visit(cases, body):
    """Run ``body(tiles, grain, origin)`` for the case the step is in."""
    for pred, *case in cases:
        if pred is True:
            body(*case)
        else:
            pl.when(pred)(functools.partial(body, *case))


_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_NN = (((1,), (0,)), ((), ()))      # a @ b
_TN = (((0,), (0,)), ((), ()))      # a.T @ b


# --------------------------------------------------------------------------
# pallas forward kernel
# --------------------------------------------------------------------------

def _fa_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                   acc_ref, m_ref, l_ref, *,
                   sm_scale, causal, block_q, block_k, seq_q, seq_k):
    i = pl.program_id(1)
    j = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def body(tiles, grain, origin):
        (gq, gk), (q0, k0) = grain, origin
        # one online-softmax update a group of q rows, over all the live
        # keys the block holds for them
        for r0, r1, pieces in _tile_groups(tiles, by=0):
            rows = slice(r0 * gq, r1 * gq)
            q = q_ref[0, rows, :]
            scores = []
            for c0, c1, flags in pieces:
                cols = slice(c0 * gk, c1 * gk)
                s = lax.dot_general(q, k_ref[0, cols, :], _NT,
                                    preferred_element_type=jnp.float32
                                    ) * sm_scale
                mask = _piece_mask(s.shape, q0 + r0 * gq, k0 + c0 * gk,
                                   flags, seq_q, seq_k)
                if mask is not None:
                    s = jnp.where(mask, s, _NEG_INF)
                scores.append((cols, s))
            m_prev = m_ref[rows, :1]
            m_cur = m_prev
            for _, s in scores:
                m_cur = jnp.maximum(m_cur, s.max(axis=1, keepdims=True))
            corr = jnp.exp(m_prev - m_cur)
            l_new = l_ref[rows, :1] * corr
            acc = acc_ref[rows, :] * corr
            for cols, s in scores:
                p = jnp.exp(s - m_cur)
                l_new = l_new + p.sum(axis=1, keepdims=True)
                acc = acc + lax.dot_general(
                    p.astype(v_ref.dtype), v_ref[0, cols, :], _NN,
                    preferred_element_type=jnp.float32)
            acc_ref[rows, :] = acc
            lanes = (m_cur.shape[0], m_ref.shape[1])
            m_ref[rows, :] = jnp.broadcast_to(m_cur, lanes)
            l_ref[rows, :] = jnp.broadcast_to(l_new, lanes)

    _visit(_block_cases(i, j, causal=causal, seq_q=seq_q, seq_k=seq_k,
                        block_q=block_q, block_k=block_k,
                        grain=_FWD_TILE, crop_q=False), body)

    @pl.when(j == nk - 1)
    def _finish():
        l = l_ref[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[0] = jnp.broadcast_to(
            m_ref[:, :1] + jnp.log(l),
            lse_ref.shape[1:]).astype(lse_ref.dtype)


def _ceil_to(x, m):
    return (x + m - 1) // m * m


def _fa_forward_pallas(q, k, v, causal, sm_scale, block_q, block_k):
    """q,k,v: (BH, S, D) → (out (BH, Sq, D), lse (BH, Sq))."""
    return _fa_forward_jit(q, k, v, causal, sm_scale, block_q, block_k,
                           jax.default_backend() != "tpu")


# Jitted on everything but the arrays: the layers of a model share ONE
# trace of a kernel's unrolled body (traced anew for each of 24 layers
# the three bodies cost a warm set-up 2.5 s: PERF.md, PR 34).
@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7))
def _fa_forward_jit(q, k, v, causal, sm_scale, block_q, block_k, interpret):
    bh, seq_q, d = q.shape
    seq_k = k.shape[1]
    block_q = min(block_q, _ceil_to(seq_q, 128))
    block_k = min(block_k, _ceil_to(seq_k, 128))
    pq = _ceil_to(seq_q, block_q) - seq_q
    pk = _ceil_to(seq_k, block_k) - seq_k
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0)))
    nq = q.shape[1] // block_q
    nk = k.shape[1] // block_k

    kernel = functools.partial(
        _fa_fwd_kernel, sm_scale=sm_scale, causal=causal,
        block_q=block_q, block_k=block_k, seq_q=seq_q, seq_k=seq_k)
    scratch_shapes = [
        pltpu.VMEM((block_q, d), jnp.float32),
        pltpu.VMEM((block_q, 128), jnp.float32),
        pltpu.VMEM((block_q, 128), jnp.float32),
    ]

    def kv_block(b, i, j):
        return (b, _last_live_k(i, j, causal, block_q, block_k), 0)

    out, lse = pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), kv_block),
            pl.BlockSpec((1, block_k, d), kv_block),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, _LSE_LANES),
                         lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, q.shape[1], d), q.dtype),
            jax.ShapeDtypeStruct((bh, q.shape[1], _LSE_LANES),
                                 jnp.float32),
        ],
        scratch_shapes=scratch_shapes,
        interpret=interpret,
        name="mxtpu_flash_fwd",
    )(q, k, v)
    lse = lse[..., 0]
    if pq:
        out = out[:, :seq_q]
        lse = lse[:, :seq_q]
    return out, lse


# --------------------------------------------------------------------------
# backward: recompute per q-block from saved lse (flash backward), scanned
# --------------------------------------------------------------------------

def _fa_backward(causal, sm_scale, block_q, res, do):
    q, k, v, out, lse = res           # (BH, Sq, D) ... lse (BH, Sq)
    bh, seq_q, d = q.shape
    block_q = min(block_q, _ceil_to(seq_q, 128))
    pq = _ceil_to(seq_q, block_q) - seq_q
    if pq:
        pad3 = ((0, 0), (0, pq), (0, 0))
        q = jnp.pad(q, pad3)
        out = jnp.pad(out, pad3)
        do = jnp.pad(do, pad3)
        lse = jnp.pad(lse, ((0, 0), (0, pq)))
    nq = q.shape[1] // block_q

    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)          # (BH, Sq')

    def body(carry, idx):
        dk, dv = carry
        qi = lax.dynamic_slice_in_dim(q, idx * block_q, block_q, 1)
        doi = lax.dynamic_slice_in_dim(do, idx * block_q, block_q, 1)
        lsei = lax.dynamic_slice_in_dim(lse, idx * block_q, block_q, 1)
        di = lax.dynamic_slice_in_dim(delta, idx * block_q, block_q, 1)
        s = jnp.einsum("bqd,bkd->bqk", qi, k,
                       preferred_element_type=jnp.float32) * sm_scale
        qpos = idx * block_q + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        kpos = lax.broadcasted_iota(jnp.int32, s.shape, 2)
        mask = qpos < seq_q
        if causal:
            mask = mask & (qpos >= kpos)
        s = jnp.where(mask, s, _NEG_INF)
        p = jnp.exp(s - lsei[..., None])          # (BH, bq, Sk)
        p = jnp.where(mask, p, 0.0)
        dv = dv + jnp.einsum("bqk,bqd->bkd", p, doi.astype(jnp.float32))
        dp = jnp.einsum("bqd,bkd->bqk", doi.astype(jnp.float32),
                        v.astype(jnp.float32))
        ds = p * (dp - di[..., None]) * sm_scale
        dqi = jnp.einsum("bqk,bkd->bqd", ds, k.astype(jnp.float32))
        dk = dk + jnp.einsum("bqk,bqd->bkd", ds, qi.astype(jnp.float32))
        return (dk, dv), dqi

    init = (jnp.zeros(k.shape, jnp.float32), jnp.zeros(v.shape, jnp.float32))
    (dk, dv), dq_chunks = lax.scan(body, init, jnp.arange(nq))
    dq = jnp.moveaxis(dq_chunks, 0, 1).reshape(bh, nq * block_q, d)
    if pq:
        dq = dq[:, :seq_q]
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


# --------------------------------------------------------------------------
# pallas backward kernels: the standard flash-backward split — one pass
# accumulates dk/dv per k-block (q innermost, f32 VMEM accumulators),
# one accumulates dq per q-block (k innermost).  Unlike the scan
# fallback above, the (block, block) score/probability recomputations
# never leave VMEM, so backward HBM traffic drops from O(S_q * S_k)
# temps to the O(S * D) operand streams.  Both walk the forward's
# schedule: dkv by groups of k tiles (a group's dk and dv rows are
# written once a block), dq by groups of q rows.
# --------------------------------------------------------------------------

def _fa_bwd_dkdv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                        dk_ref, dv_ref, dk_acc, dv_acc, *,
                        sm_scale, causal, block_q, block_k,
                        seq_q, seq_k):
    j = pl.program_id(1)              # k block
    i = pl.program_id(2)              # q block (innermost)
    nq = pl.num_programs(2)

    @pl.when(i == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def body(tiles, grain, origin):
        (gq, gk), (q0, k0) = grain, origin
        for c0, c1, pieces in _tile_groups(tiles, by=1):
            cols = slice(c0 * gk, c1 * gk)
            k = k_ref[0, cols, :]
            vf = v_ref[0, cols, :].astype(jnp.float32)
            dk = dk_acc[cols, :]
            dv = dv_acc[cols, :]
            for r0, r1, flags in pieces:
                rows = slice(r0 * gq, r1 * gq)
                q = q_ref[0, rows, :]
                dof = do_ref[0, rows, :].astype(jnp.float32)
                lse = lse_ref[0, rows, :][:, :1]    # lane 0 of broadcast
                delta = delta_ref[0, rows, :][:, :1]
                s = lax.dot_general(q, k, _NT,
                                    preferred_element_type=jnp.float32
                                    ) * sm_scale
                p = jnp.exp(s - lse)
                mask = _piece_mask(s.shape, q0 + r0 * gq, k0 + c0 * gk,
                                   flags, seq_q, seq_k)
                if mask is not None:
                    p = jnp.where(mask, p, 0.0)
                # dv_j += P^T dO ;  dP = dO V^T ;  dS = P*(dP - delta)*scale
                dv = dv + lax.dot_general(
                    p, dof, _TN, preferred_element_type=jnp.float32)
                dp = lax.dot_general(dof, vf, _NT,
                                     preferred_element_type=jnp.float32)
                ds = p * (dp - delta) * sm_scale
                dk = dk + lax.dot_general(
                    ds, q.astype(jnp.float32), _TN,
                    preferred_element_type=jnp.float32)
            dk_acc[cols, :] = dk
            dv_acc[cols, :] = dv

    _visit(_block_cases(i, j, causal=causal, seq_q=seq_q, seq_k=seq_k,
                        block_q=block_q, block_k=block_k,
                        grain=_BWD_TILE), body)

    @pl.when(i == nq - 1)
    def _finish():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _fa_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, dq_acc, *, sm_scale, causal, block_q,
                      block_k, seq_q, seq_k):
    i = pl.program_id(1)              # q block
    j = pl.program_id(2)              # k block (innermost)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def body(tiles, grain, origin):
        (gq, gk), (q0, k0) = grain, origin
        for r0, r1, pieces in _tile_groups(tiles, by=0):
            rows = slice(r0 * gq, r1 * gq)
            q = q_ref[0, rows, :]
            dof = do_ref[0, rows, :].astype(jnp.float32)
            lse = lse_ref[0, rows, :][:, :1]        # lane 0 of broadcast
            delta = delta_ref[0, rows, :][:, :1]
            dq = dq_acc[rows, :]
            for c0, c1, flags in pieces:
                cols = slice(c0 * gk, c1 * gk)
                k = k_ref[0, cols, :]
                s = lax.dot_general(q, k, _NT,
                                    preferred_element_type=jnp.float32
                                    ) * sm_scale
                p = jnp.exp(s - lse)
                mask = _piece_mask(s.shape, q0 + r0 * gq, k0 + c0 * gk,
                                   flags, seq_q, seq_k)
                if mask is not None:
                    p = jnp.where(mask, p, 0.0)
                dp = lax.dot_general(
                    dof, v_ref[0, cols, :].astype(jnp.float32), _NT,
                    preferred_element_type=jnp.float32)
                ds = p * (dp - delta) * sm_scale
                dq = dq + lax.dot_general(
                    ds, k.astype(jnp.float32), _NN,
                    preferred_element_type=jnp.float32)
            dq_acc[rows, :] = dq

    _visit(_block_cases(i, j, causal=causal, seq_q=seq_q, seq_k=seq_k,
                        block_q=block_q, block_k=block_k,
                        grain=_BWD_TILE), body)

    @pl.when(j == nk - 1)
    def _finish():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _fa_backward_pallas(causal, sm_scale, block_q, block_k, res, do,
                        delta=None):
    """``delta`` may be precomputed (rowsum(do*out), shape (BH, Sq)) —
    ring attention hoists it out of its per-step loop since do/out are
    loop-invariant there."""
    return _fa_backward_jit(causal, sm_scale, block_q, block_k, res, do,
                            delta, jax.default_backend() != "tpu")


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 7))
def _fa_backward_jit(causal, sm_scale, block_q, block_k, res, do, delta,
                     interpret):
    q, k, v, out, lse = res
    bh, seq_q, d = q.shape
    seq_k = k.shape[1]
    block_q = min(block_q, _ceil_to(seq_q, 128))
    block_k = min(block_k, _ceil_to(seq_k, 128))
    pq = _ceil_to(seq_q, block_q) - seq_q
    pk = _ceil_to(seq_k, block_k) - seq_k
    if delta is None:
        delta = jnp.sum(do.astype(jnp.float32)
                        * out.astype(jnp.float32), axis=-1)  # (BH, Sq)
    if pq:
        pad3 = ((0, 0), (0, pq), (0, 0))
        q = jnp.pad(q, pad3)
        out = jnp.pad(out, pad3)
        do = jnp.pad(do, pad3)
        lse = jnp.pad(lse, ((0, 0), (0, pq)))
        delta = jnp.pad(delta, ((0, 0), (0, pq)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0)))
    nq = q.shape[1] // block_q
    nk = k.shape[1] // block_k

    common = dict(sm_scale=sm_scale, causal=causal, block_q=block_q,
                  block_k=block_k, seq_q=seq_q, seq_k=seq_k)
    # per-row vectors cross the boundary lane-broadcast (see _LSE_LANES)
    lse = jnp.broadcast_to(lse[..., None], lse.shape + (_LSE_LANES,))
    delta = jnp.broadcast_to(delta[..., None],
                             delta.shape + (_LSE_LANES,))

    def qi_kj(sel_q, sel_k):
        # index maps for (b, j, i) / (b, i, j) grids
        return [
            pl.BlockSpec((1, block_q, d),
                         lambda b, x, y: (b, sel_q(x, y), 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda b, x, y: (b, sel_k(x, y), 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda b, x, y: (b, sel_k(x, y), 0)),
            pl.BlockSpec((1, block_q, d),
                         lambda b, x, y: (b, sel_q(x, y), 0)),
            pl.BlockSpec((1, block_q, _LSE_LANES),
                         lambda b, x, y: (b, sel_q(x, y), 0)),
            pl.BlockSpec((1, block_q, _LSE_LANES),
                         lambda b, x, y: (b, sel_q(x, y), 0)),
        ]

    # a dead step of a causal grid fetches nothing: its index maps stay
    # on the row's nearest live block
    first_q = functools.partial(_first_live_q, causal=causal,
                                block_q=block_q, block_k=block_k, nq=nq)
    last_k = functools.partial(_last_live_k, causal=causal,
                               block_q=block_q, block_k=block_k)

    dk, dv = pl.pallas_call(
        functools.partial(_fa_bwd_dkdv_kernel, **common),
        grid=(bh, nk, nq),            # q innermost: dk/dv scratch lives
        in_specs=qi_kj(first_q, lambda j, i: j),
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=interpret,
        name="mxtpu_flash_dkv",
    )(q, k, v, do, lse, delta)

    dq = pl.pallas_call(
        functools.partial(_fa_bwd_dq_kernel, **common),
        grid=(bh, nq, nk),            # k innermost: dq scratch lives
        in_specs=qi_kj(lambda i, j: i, last_k),
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype)],
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
        name="mxtpu_flash_dq",
    )(q, k, v, do, lse, delta)[0]

    if pq:
        dq = dq[:, :seq_q]
    if pk:
        dk = dk[:, :seq_k]
        dv = dv[:, :seq_k]
    return dq, dk, dv


# --------------------------------------------------------------------------
# public flash_attention on raw arrays (custom_vjp over the pallas fwd)
# --------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_attention(q, k, v, causal, sm_scale, block_q, block_k):
    out, _ = _fa_forward_pallas(q, k, v, causal, sm_scale, block_q, block_k)
    return out


def _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k):
    out, lse = _fa_forward_pallas(q, k, v, causal, sm_scale, block_q, block_k)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, sm_scale, block_q, block_k, res, do):
    import os
    if os.environ.get("MXNET_TPU_FLASH_BWD", "pallas") == "scan":
        # XLA-scan fallback (kept for A/B tuning and as the oracle the
        # pallas kernels are pinned against in tests).  NOTE: read at
        # TRACE time — a function already jitted has its backend baked
        # into the compile cache; set the env var before tracing (or
        # jax.clear_caches()) for an A/B comparison to measure both.
        return _fa_backward(causal, sm_scale, block_q, res, do)
    return _fa_backward_pallas(causal, sm_scale, block_q, block_k, res,
                               do)


_flash_attention.defvjp(_flash_fwd, _flash_bwd)


def _flash_block_default(which):
    """Parse one MXNET_TPU_FLASH_BLOCK_Q/_K override (invalid/non-
    positive values fall back to the registrant's default).  Only
    consulted when the env override
    is actually set — the default path resolves block sizes through the
    kernel registry (``_resolve_flash_blocks``), once per shape."""
    fallback = _kernels.get_kernel("flash_attention").default_config[
        f"block_{which.lower()}"]
    try:
        v = int(os.environ.get(f"MXNET_TPU_FLASH_BLOCK_{which}",
                               fallback))
    except ValueError:
        return fallback
    return v if v > 0 else fallback


# -- kernel-registry integration -------------------------------------------
# Block sizes come from mxnet_tpu.kernels: env override > in-process
# memo > on-disk autotune cache > tuner (MXNET_KERNEL_TUNE=1) > default.
# The env vars are observed as a SNAPSHOT tuple — two dict lookups per
# call instead of the old per-call int() parse — and any change
# invalidates the kernel's resolved configs so the override wins
# immediately in a live process.

_FLASH_ENV_KEYS = ("MXNET_TPU_FLASH_BLOCK_Q", "MXNET_TPU_FLASH_BLOCK_K")
_flash_env_snapshot: tuple = (False, False)      # impossible sentinel


def _pow2_bucket(n, floor=128):
    """Bucket a sequence length to the next power of two ≥ ``floor`` —
    ragged lengths share one tuned config per bucket instead of
    fragmenting the cache per exact length."""
    b = floor
    while b < n:
        b *= 2
    return b


def _flash_signature(q, k, v, causal=False, sm_scale=None):
    """(shape-sig, dtype) cache-key parts from (BH, S, D) arrays.  The
    dtype leg resolves through the AMP policy: under AMP an fp32 call
    site runs the kernel on policy-cast operands, so the key must name
    the compute dtype — otherwise a bf16 call after an fp32 tune would
    resolve the fp32 winner."""
    from ..amp import policy as _amp_policy
    return (f"sq{_pow2_bucket(q.shape[1])}_sk{_pow2_bucket(k.shape[1])}"
            f"_d{q.shape[2]}_c{int(bool(causal))}",
            _amp_policy.kernel_key_dtype(str(q.dtype)))


def _flash_kernel_run(config, q, k, v, causal=False, sm_scale=None):
    scale = (sm_scale if sm_scale is not None
             else 1.0 / math.sqrt(q.shape[-1]))
    return _flash_attention(q, k, v, bool(causal), float(scale),
                            int(config["block_q"]), int(config["block_k"]))


def _flash_kernel_fallback(q, k, v, causal=False, sm_scale=None):
    """XLA lowering on (BH, S, D) — the numerics oracle the Pallas
    kernel is pinned against in tests/test_kernels.py."""
    return attention_reference(q[None], k[None], v[None], causal=causal,
                               sm_scale=sm_scale)[0]


def _flash_make_args(case):
    import numpy as onp
    rng = onp.random.RandomState(11)
    bh, sq, sk, d = case["bh"], case["sq"], case["sk"], case["d"]
    dtype = case.get("dtype", "float32")
    q, k, v = (jnp.asarray(rng.randn(bh, s, d) * 0.5, dtype=dtype)
               for s in (sq, sk, sk))
    return (q, k, v), {"causal": bool(case.get("causal", False))}


_kernels.register_kernel(_kernels.KernelSpec(
    "flash_attention", version=2,       # 2: the live-tile schedule
    run=_flash_kernel_run, fallback=_flash_kernel_fallback,
    config_space={"block_q": (128, 256, 512, 1024),
                  "block_k": (128, 256, 512, 1024)},
    # swept on a v5e at (64, 1024, 64) bfloat16 causal under the
    # schedule (PERF.md, PR 34); a call shorter than a block runs one
    # block of its own length
    default_config={"block_q": 1024, "block_k": 1024},
    signature=_flash_signature, make_args=_flash_make_args,
    tune_grid=({"bh": 4, "sq": 128, "sk": 128, "d": 64, "causal": False},
               {"bh": 2, "sq": 256, "sk": 256, "d": 64, "causal": True}),
))


def _resolve_flash_blocks(qf, kf, vf, causal, scale):
    """(block_q, block_k) for one call, resolved once per shape bucket
    through the kernel registry (satellite fix: the old path re-parsed
    MXNET_TPU_FLASH_BLOCK_Q/_K from the environment on every call)."""
    global _flash_env_snapshot
    env = (os.environ.get(_FLASH_ENV_KEYS[0]),
           os.environ.get(_FLASH_ENV_KEYS[1]))
    if env != _flash_env_snapshot:
        _flash_env_snapshot = env
        _kernels.invalidate("flash_attention")
    if env[0] is not None or env[1] is not None:
        return _flash_block_default("Q"), _flash_block_default("K")
    sig, dt = _flash_signature(qf, kf, vf, causal=causal)
    cfg = _kernels.resolve(
        "flash_attention", sig, dt,
        tune_args=((qf, kf, vf), {"causal": causal, "sm_scale": scale}))
    return int(cfg["block_q"]), int(cfg["block_k"])


def flash_attention(q, k, v, *, causal=False, sm_scale=None,
                    block_q=None, block_k=None):
    """Flash attention on (B, H, S, D) (or (BH, S, D)) arrays.

    Supports grouped-query attention (GQA/MQA): ``k``/``v`` may carry
    fewer heads ``Hkv`` than ``q`` as long as ``H % Hkv == 0`` — each
    group of ``H // Hkv`` query heads attends to one shared KV head
    (MQA is ``Hkv == 1``).  KV heads are broadcast across the group
    before the kernel; the flash tiling itself is unchanged.
    """
    squeeze = q.ndim == 3
    if squeeze:
        q, k, v = q[None], k[None], v[None]
    b, h, sq, d = q.shape
    hkv = k.shape[1]
    if v.shape[1] != hkv:
        raise ValueError("k and v must have the same head count")
    if hkv != h:
        if hkv <= 0 or h % hkv != 0:
            raise ValueError(
                f"GQA requires q heads ({h}) divisible by kv heads "
                f"({hkv})")
        group = h // hkv
        k = jnp.repeat(k, group, axis=1)
        v = jnp.repeat(v, group, axis=1)
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    qf = q.reshape(b * h, sq, d)
    kf = k.reshape(b * h, k.shape[2], d)
    vf = v.reshape(b * h, v.shape[2], d)
    if block_q is None or block_k is None:
        rq, rk = _resolve_flash_blocks(qf, kf, vf, bool(causal),
                                       float(scale))
        block_q = rq if block_q is None else block_q
        block_k = rk if block_k is None else block_k
    out = _flash_attention(qf, kf, vf, bool(causal), float(scale),
                           int(block_q), int(block_k))
    out = out.reshape(b, h, sq, d)
    return out[0] if squeeze else out


register("flash_attention", aliases=("_npx_flash_attention",))(
    lambda q, k, v, causal=False, sm_scale=None, block_q=None,
    block_k=None:
    flash_attention(q, k, v, causal=causal, sm_scale=sm_scale,
                    block_q=block_q, block_k=block_k))


# --------------------------------------------------------------------------
# online-softmax block update — shared with ring attention
# --------------------------------------------------------------------------

def online_block_update(o, m, l, q, k, v, sm_scale, mask=None):
    """One flash/ring accumulator update with a new K/V block.

    o: (B,H,Sq,D) f32 accum; m,l: (B,H,Sq,1) f32 running max / normalizer.
    Returns updated (o, m, l).
    """
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * sm_scale
    if mask is not None:
        s = jnp.where(mask, s, _NEG_INF)
    m_cur = jnp.maximum(m, s.max(axis=-1, keepdims=True))
    corr = jnp.exp(m - m_cur)
    p = jnp.exp(s - m_cur)
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    l_new = l * corr + p.sum(axis=-1, keepdims=True)
    o_new = o * corr + jnp.einsum("bhqk,bhkd->bhqd", p,
                                  v.astype(jnp.float32))
    return o_new, m_cur, l_new


# --------------------------------------------------------------------------
# masked softmax (parity: softmax with length masking used by transformer)
# --------------------------------------------------------------------------

@register("masked_softmax", aliases=("_npx_masked_softmax",))
def masked_softmax(x, mask=None, *, axis=-1, temperature=1.0):
    if mask is not None:
        x = jnp.where(mask.astype(bool), x, _NEG_INF)
    p = jax.nn.softmax(x / temperature, axis=axis)
    if mask is not None:
        p = jnp.where(mask.astype(bool), p, 0.0)
    return p


@register("masked_log_softmax", aliases=("_npx_masked_log_softmax",))
def masked_log_softmax(x, mask=None, *, axis=-1, temperature=1.0):
    """Log-softmax with additive masking; masked positions yield -inf
    (parity: _npx_masked_log_softmax, src/operator/nn/softmax.cc)."""
    if mask is not None:
        x = jnp.where(mask.astype(bool), x, _NEG_INF)
    out = jax.nn.log_softmax(x / temperature, axis=axis)
    if mask is not None:
        out = jnp.where(mask.astype(bool), out, -jnp.inf)
    return out


# --------------------------------------------------------------------------
# contrib transformer parity ops (semantics per transformer.cc describe())
# --------------------------------------------------------------------------

@register("_contrib_div_sqrt_dim", aliases=("div_sqrt_dim",))
def _div_sqrt_dim(x):
    return x / math.sqrt(x.shape[-1])


def _split_interleaved(qkv, heads, n):
    """(S, B, heads*hd*n) → n tensors of (B*heads, S, hd)."""
    s, b, e = qkv.shape
    hd = e // (heads * n)
    t = qkv.reshape(s, b, heads, n, hd)
    outs = []
    for i in range(n):
        proj = jnp.transpose(t[:, :, :, i, :], (1, 2, 0, 3))
        outs.append(proj.reshape(b * heads, s, hd))
    return outs


@register("_contrib_interleaved_matmul_selfatt_qk",
          aliases=("interleaved_matmul_selfatt_qk",))
def _imm_selfatt_qk(queries_keys_values, *, heads):
    q, k, _ = _split_interleaved(queries_keys_values, heads, 3)
    q = q / math.sqrt(q.shape[-1])
    return jnp.einsum("nqd,nkd->nqk", q, k)


def _attend_and_merge_heads(attention, v, heads):
    """attention (B*H, Sq, Sk) × v (B*H, Sk, hd) → (Sq, B, H*hd)."""
    out = jnp.einsum("nqk,nkd->nqd", attention, v)
    bh, s, hd = out.shape
    b = bh // heads
    out = jnp.transpose(out.reshape(b, heads, s, hd), (2, 0, 1, 3))
    return out.reshape(s, b, heads * hd)


@register("_contrib_interleaved_matmul_selfatt_valatt",
          aliases=("interleaved_matmul_selfatt_valatt",))
def _imm_selfatt_valatt(queries_keys_values, attention, *, heads):
    _, _, v = _split_interleaved(queries_keys_values, heads, 3)
    return _attend_and_merge_heads(attention, v, heads)


@register("_contrib_interleaved_matmul_encdec_qk",
          aliases=("interleaved_matmul_encdec_qk",))
def _imm_encdec_qk(queries, keys_values, *, heads):
    sq, b, e = queries.shape
    hd = e // heads
    q = jnp.transpose(queries.reshape(sq, b, heads, hd), (1, 2, 0, 3))
    q = q.reshape(b * heads, sq, hd) / math.sqrt(hd)
    k, _ = _split_interleaved(keys_values, heads, 2)
    return jnp.einsum("nqd,nkd->nqk", q, k)


@register("_contrib_interleaved_matmul_encdec_valatt",
          aliases=("interleaved_matmul_encdec_valatt",))
def _imm_encdec_valatt(keys_values, attention, *, heads):
    _, v = _split_interleaved(keys_values, heads, 2)
    return _attend_and_merge_heads(attention, v, heads)


# -- multi-head attention convenience op (flash-backed) --------------------

def split_heads(x, heads):
    """(B, S, heads*hd) → (B, heads, S, hd)."""
    b, s_, e = x.shape
    return jnp.transpose(x.reshape(b, s_, heads, e // heads), (0, 2, 1, 3))


def merge_heads(x):
    """(B, H, S, hd) → (B, S, H*hd)."""
    b, h, s_, hd = x.shape
    return jnp.transpose(x, (0, 2, 1, 3)).reshape(b, s_, h * hd)


@register("multi_head_attention", aliases=("_npx_multi_head_attention",))
def _multi_head_attention(q, k, v, *, num_heads, causal=False,
                          use_flash=True, num_kv_heads=None):
    """(B, S, E) inputs pre-projected; splits heads, attends, re-merges.

    ``num_kv_heads`` enables grouped-query attention: k/v carry
    ``num_kv_heads * head_dim`` features and are shared across query
    groups (MQA with num_kv_heads=1)."""
    hkv = num_kv_heads if num_kv_heads is not None else num_heads
    qh, kh, vh = (split_heads(q, num_heads), split_heads(k, hkv),
                  split_heads(v, hkv))
    if use_flash:
        out = flash_attention(qh, kh, vh, causal=causal)
    else:
        if hkv != num_heads:
            kh = jnp.repeat(kh, num_heads // hkv, axis=1)
            vh = jnp.repeat(vh, num_heads // hkv, axis=1)
        out = attention_reference(qh, kh, vh, causal=causal)
    return merge_heads(out)
