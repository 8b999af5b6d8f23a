"""Flash attention + ring attention + contrib transformer op tests.

Parity model: the reference cross-checks kernels against a materialized
reference implementation (check_consistency, SURVEY.md §4); here the
oracle is plain softmax(QK^T)V.
"""
import numpy as onp
import pytest
import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.ops.attention import (flash_attention, attention_reference)
from mxnet_tpu.parallel import make_mesh, ring_self_attention


def _rand(*shape, seed=0):
    return onp.random.RandomState(seed).randn(*shape).astype("float32")


# (sq, sk, block_q, block_k): ragged and padded lengths, blocks from one
# tile to larger than the sequence, block_q != block_k (under causal the
# schedule then reads a block's kind from the program ids)
_FWD_CASES = [(128, 128, 128, 128), (256, 128, 128, 128),
              (100, 180, 128, 128), (384, 384, 128, 128),
              (384, 384, 256, 256), (384, 384, 512, 512),
              (1000, 1000, 256, 256), (1000, 1000, 512, 512),
              (1024, 1024, 512, 512), (1024, 1024, 1024, 1024),
              (1024, 1024, 256, 512), (1024, 1024, 512, 128),
              (128, 128, 1024, 1024), (260, 520, 256, 512)]


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("sq,sk,bq,bk", _FWD_CASES)
def test_flash_vs_reference(causal, sq, sk, bq, bk):
    if causal and sq != sk:
        pytest.skip("causal requires square")
    b, h = (2, 3) if sq <= 384 else (1, 2)
    q = jnp.asarray(_rand(b, h, sq, 64, seed=1))
    k = jnp.asarray(_rand(b, h, sk, 64, seed=2))
    v = jnp.asarray(_rand(b, h, sk, 64, seed=3))
    out = flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk)
    ref = attention_reference(q, k, v, causal=causal)
    onp.testing.assert_allclose(onp.asarray(out), onp.asarray(ref),
                                rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("s,bq,bk", [
    (128, 64, 64), (384, 128, 128), (384, 256, 256), (384, 128, 256),
    (1000, 512, 512), (1024, 256, 256), (1024, 512, 512),
    (1024, 1024, 1024), (1024, 512, 1024), (128, 1024, 1024)])
def test_flash_grads(causal, s, bq, bk):
    q = jnp.asarray(_rand(1, 2, s, 32, seed=4))
    k = jnp.asarray(_rand(1, 2, s, 32, seed=5))
    v = jnp.asarray(_rand(1, 2, s, 32, seed=6))

    def loss_flash(q, k, v):
        return flash_attention(q, k, v, causal=causal,
                               block_q=bq, block_k=bk).sum()

    def loss_ref(q, k, v):
        return attention_reference(q, k, v, causal=causal).sum()

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        onp.testing.assert_allclose(onp.asarray(a), onp.asarray(b),
                                    rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_full(causal):
    mesh = make_mesh({"sp": 8})
    q = jnp.asarray(_rand(1, 2, 8 * 16, 32, seed=7))
    k = jnp.asarray(_rand(1, 2, 8 * 16, 32, seed=8))
    v = jnp.asarray(_rand(1, 2, 8 * 16, 32, seed=9))
    out = ring_self_attention(q, k, v, mesh, causal=causal)
    ref = attention_reference(q, k, v, causal=causal)
    onp.testing.assert_allclose(onp.asarray(out), onp.asarray(ref),
                                rtol=2e-4, atol=2e-4)


def test_ring_attention_grad():
    mesh = make_mesh({"sp": 4})
    q = jnp.asarray(_rand(1, 1, 64, 16, seed=10))
    k = jnp.asarray(_rand(1, 1, 64, 16, seed=11))
    v = jnp.asarray(_rand(1, 1, 64, 16, seed=12))

    def loss_ring(q, k, v):
        return ring_self_attention(q, k, v, mesh, causal=True).sum()

    def loss_ref(q, k, v):
        return attention_reference(q, k, v, causal=True).sum()

    g1 = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        onp.testing.assert_allclose(onp.asarray(a), onp.asarray(b),
                                    rtol=1e-3, atol=1e-3)


def test_interleaved_selfatt_matches_unfused():
    """Against the documented equivalent-code semantics
    (transformer.cc:650 describe block)."""
    s, b, heads, hd = 6, 2, 4, 8
    qkv = mx.nd.array(_rand(s, b, heads * hd * 3, seed=13))
    att = mx.nd.interleaved_matmul_selfatt_qk(qkv, heads=heads)
    assert att.shape == (b * heads, s, s)

    tmp = qkv.asnumpy().reshape(s, b, heads, 3, hd)
    q = onp.transpose(tmp[:, :, :, 0, :], (1, 2, 0, 3)).reshape(-1, s, hd)
    kk = onp.transpose(tmp[:, :, :, 1, :], (1, 2, 0, 3)).reshape(-1, s, hd)
    expect = onp.einsum("nqd,nkd->nqk", q / onp.sqrt(hd), kk)
    onp.testing.assert_allclose(att.asnumpy(), expect, rtol=1e-5, atol=1e-5)

    out = mx.nd.interleaved_matmul_selfatt_valatt(qkv, att, heads=heads)
    assert out.shape == (s, b, heads * hd)
    vv = onp.transpose(tmp[:, :, :, 2, :], (1, 2, 0, 3)).reshape(-1, s, hd)
    eo = onp.einsum("nqk,nkd->nqd", att.asnumpy(), vv)
    eo = eo.reshape(b, heads, s, hd).transpose(2, 0, 1, 3).reshape(s, b, -1)
    onp.testing.assert_allclose(out.asnumpy(), eo, rtol=1e-5, atol=1e-5)


def test_interleaved_encdec_shapes():
    s, b, heads, hd = 5, 2, 2, 4
    qs = mx.nd.array(_rand(s, b, heads * hd, seed=14))
    kv = mx.nd.array(_rand(s + 2, b, heads * hd * 2, seed=15))
    att = mx.nd.interleaved_matmul_encdec_qk(qs, kv, heads=heads)
    assert att.shape == (b * heads, s, s + 2)
    out = mx.nd.interleaved_matmul_encdec_valatt(kv, att, heads=heads)
    assert out.shape == (s, b, heads * hd)


def test_masked_softmax():
    x = mx.nd.array(_rand(2, 3, 4, seed=16))
    mask = mx.nd.array((onp.arange(4) < 3).astype("float32").reshape(1, 1, 4)
                       * onp.ones((2, 3, 4), "float32"))
    p = mx.nd.masked_softmax(x, mask)
    pn = p.asnumpy()
    assert onp.allclose(pn[..., 3], 0.0)
    onp.testing.assert_allclose(pn.sum(-1), onp.ones((2, 3)), rtol=1e-5)


def test_multi_head_attention_op():
    b, s, e, h = 2, 32, 64, 4
    q = mx.nd.array(_rand(b, s, e, seed=17))
    k = mx.nd.array(_rand(b, s, e, seed=18))
    v = mx.nd.array(_rand(b, s, e, seed=19))
    out = mx.nd.multi_head_attention(q, k, v, num_heads=h, causal=True)
    ref = mx.nd.multi_head_attention(q, k, v, num_heads=h, causal=True,
                                     use_flash=False)
    assert out.shape == (b, s, e)
    onp.testing.assert_allclose(out.asnumpy(), ref.asnumpy(),
                                rtol=2e-4, atol=2e-4)


def test_flash_attention_gqa():
    """Grouped-query attention: Hkv < H with shared KV heads matches
    the reference computed with explicitly repeated heads; MQA is the
    Hkv=1 case."""
    import numpy as onp
    import jax.numpy as jnp
    from mxnet_tpu.ops.attention import (attention_reference,
                                         flash_attention)

    rng = onp.random.RandomState(0)
    B, H, HKV, S, D = 2, 8, 2, 64, 16
    q = jnp.asarray(rng.randn(B, H, S, D).astype("float32"))
    k = jnp.asarray(rng.randn(B, HKV, S, D).astype("float32"))
    v = jnp.asarray(rng.randn(B, HKV, S, D).astype("float32"))

    out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
    kr = jnp.repeat(k, H // HKV, axis=1)
    vr = jnp.repeat(v, H // HKV, axis=1)
    ref = attention_reference(q, kr, vr, causal=True)
    onp.testing.assert_allclose(onp.asarray(out), onp.asarray(ref),
                                rtol=2e-4, atol=2e-4)

    # MQA: single shared KV head
    k1 = k[:, :1]
    v1 = v[:, :1]
    out1 = flash_attention(q, k1, v1, block_q=32, block_k=32)
    ref1 = attention_reference(q, jnp.repeat(k1, H, axis=1),
                               jnp.repeat(v1, H, axis=1))
    onp.testing.assert_allclose(onp.asarray(out1), onp.asarray(ref1),
                                rtol=2e-4, atol=2e-4)

    # invalid grouping rejected
    import pytest
    k3 = jnp.asarray(rng.randn(B, 3, S, D).astype("float32"))
    with pytest.raises(ValueError, match="divisible"):
        flash_attention(q, k3, k3)


def test_ring_attention_gqa_small_kv_traffic_path():
    """GQA through the ring: hkv < H K/V rotate un-expanded and match
    the pre-expanded reference."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.parallel import make_mesh, ring_self_attention
    from mxnet_tpu.ops.attention import attention_reference

    mesh = make_mesh({"sp": 4})
    rng = onp.random.RandomState(0)
    B, H, HKV, S, D = 2, 4, 2, 16, 8
    q = jnp.asarray(rng.randn(B, H, S, D).astype("float32"))
    k = jnp.asarray(rng.randn(B, HKV, S, D).astype("float32"))
    v = jnp.asarray(rng.randn(B, HKV, S, D).astype("float32"))
    out = ring_self_attention(q, k, v, mesh, causal=True)
    ref = attention_reference(q, jnp.repeat(k, H // HKV, axis=1),
                              jnp.repeat(v, H // HKV, axis=1),
                              causal=True)
    onp.testing.assert_allclose(onp.asarray(out), onp.asarray(ref),
                                rtol=2e-4, atol=2e-4)


def test_flash_block_env_defaults(monkeypatch):
    """MXNET_TPU_FLASH_BLOCK_Q/_K set the default tile sizes (the
    tune_tpu sweep's delivery mechanism); invalid values fall back."""
    from mxnet_tpu import kernels
    from mxnet_tpu.ops.attention import _flash_block_default

    monkeypatch.setenv("MXNET_TPU_FLASH_BLOCK_Q", "256")
    monkeypatch.setenv("MXNET_TPU_FLASH_BLOCK_K", "oops")
    assert _flash_block_default("Q") == 256
    assert _flash_block_default("K") == kernels.get_kernel(
        "flash_attention").default_config["block_k"]
    # and the kernel still runs under an override
    q = jnp.asarray(onp.random.RandomState(0)
                    .randn(1, 2, 128, 16).astype("float32"))
    out = flash_attention(q, q, q, causal=True)
    assert out.shape == q.shape


# -- pallas flash backward (r5): pinned against the scan backward and
#    autodiff through the reference implementation -------------------------

@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("sq,sk,blocks", [
    (128, 128, None), (200, 136, None), (96, 256, None),
    (384, 384, (128, 128)), (384, 384, (256, 256)),
    (1000, 1000, (512, 512)), (1000, 1000, (256, 512)),
    (1024, 1024, (128, 128)), (1024, 1024, (512, 512)),
    (1024, 1024, (1024, 1024)), (260, 520, (256, 512))])
def test_flash_backward_pallas_matches_scan_and_reference(causal, sq, sk,
                                                          blocks):
    import os
    from mxnet_tpu.ops.attention import (attention_reference,
                                         flash_attention)
    if causal and sq != sk:
        pytest.skip("causal path assumes square q/k")
    rng = onp.random.RandomState(500 + sq + sk + causal)
    B, H, D = (2, 2, 64) if sq <= 384 else (1, 2, 64)
    bq, bk = blocks or (None, None)     # None: the registry's default
    q = jnp.asarray(rng.randn(B, H, sq, D).astype("float32") * 0.5)
    k = jnp.asarray(rng.randn(B, H, sk, D).astype("float32") * 0.5)
    v = jnp.asarray(rng.randn(B, H, sk, D).astype("float32") * 0.5)
    cot = jnp.asarray(rng.randn(B, H, sq, D).astype("float32"))

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal,
                                       block_q=bq, block_k=bk) * cot)

    def loss_ref(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=causal) * cot)

    monkeypatch = pytest.MonkeyPatch()
    try:
        monkeypatch.setenv("MXNET_TPU_FLASH_BWD", "pallas")
        gp = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        monkeypatch.setenv("MXNET_TPU_FLASH_BWD", "scan")
        gs = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    finally:
        monkeypatch.undo()      # restores any pre-existing setting
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, c, nm in zip(gp, gs, gr, "qkv"):
        onp.testing.assert_allclose(onp.asarray(a), onp.asarray(b),
                                    rtol=2e-4, atol=2e-4,
                                    err_msg=f"pallas vs scan d{nm}")
        onp.testing.assert_allclose(onp.asarray(a), onp.asarray(c),
                                    rtol=2e-3, atol=2e-3,
                                    err_msg=f"pallas vs reference d{nm}")


@pytest.mark.parametrize("S,blocks", [
    (128, None), (384, (128, 128)), (384, (256, 256)), (1000, (512, 512)),
    (1024, (512, 512)), (1024, (1024, 1024))])
def test_flash_backward_pallas_bf16(S, blocks):
    import ml_dtypes
    from mxnet_tpu.ops.attention import (attention_reference,
                                         flash_attention)
    rng = onp.random.RandomState(77)
    B, H, D = 1, 2, 64
    bq, bk = blocks or (None, None)
    qf = rng.randn(B, H, S, D).astype("float32") * 0.5
    q = jnp.asarray(qf).astype(jnp.bfloat16)

    def loss_flash(q):
        return jnp.sum(flash_attention(q, q, q, causal=True, block_q=bq,
                                       block_k=bk)
                       .astype(jnp.float32) ** 2)

    def loss_ref(q):
        return jnp.sum(attention_reference(q, q, q, causal=True)
                       .astype(jnp.float32) ** 2)

    gp = jax.grad(loss_flash)(q).astype(jnp.float32)
    gr = jax.grad(loss_ref)(q).astype(jnp.float32)
    onp.testing.assert_allclose(onp.asarray(gp), onp.asarray(gr),
                                rtol=8e-2, atol=8e-2)


# -- the live-tile schedule (ops/attention.py, "The schedule") -------------

# (causal, seq_q, seq_k, block_q, block_k[, grain]): the forward walks
# at the default grain of 128, the backward kernels at 256
_SCHEDULES = [
    (True, 1024, 1024, 1024, 1024, 256), (True, 1000, 1000, 512, 512, 256),
    (False, 260, 520, 256, 512, 256), (True, 384, 384, 128, 128, 256),
    (True, 1024, 1024, 512, 512), (True, 1024, 1024, 1024, 1024),
    (True, 1024, 1024, 128, 128), (True, 1024, 1024, 256, 512),
    (True, 1024, 1024, 512, 128), (True, 1000, 1000, 512, 512),
    (True, 384, 384, 256, 256), (True, 100, 100, 128, 128),
    (True, 200, 200, 128, 256), (True, 128, 128, 64, 64),
    (True, 2048, 2048, 512, 512), (True, 520, 520, 512, 512),
    (False, 1024, 1024, 512, 512), (False, 100, 180, 128, 128),
    (False, 260, 520, 256, 512), (False, 1000, 1000, 512, 512),
    (False, 257, 130, 128, 128), (False, 96, 256, 128, 256)]


def _live_mask(causal, seq_q, seq_k, rows, cols):
    """The scores that count, over the padded rectangle, by brute force."""
    qpos = onp.arange(rows)[:, None]
    kpos = onp.arange(cols)[None, :]
    live = (qpos < seq_q) & (kpos < seq_k)
    return live & (qpos >= kpos) if causal else live


@pytest.mark.parametrize("case", _SCHEDULES, ids=lambda c: "-".join(
    str(int(x)) for x in c))
def test_live_tiles_are_the_tiles_with_a_live_score(case):
    import math
    from mxnet_tpu.ops.attention import (_first_live_q, _last_live_k,
                                         _live_tiles, _tile_groups)
    causal, sq, sk, bq, bk = case[:5]
    grain = case[5] if len(case) > 5 else 128
    blocks, (visited, edge, total) = _live_tiles(causal, sq, sk, bq, bk,
                                                 grain)
    nq, nk = -(-sq // bq), -(-sk // bk)
    gq, gk = math.gcd(bq, grain), math.gcd(bk, grain)
    live = _live_mask(causal, sq, sk, nq * bq, nk * bk)
    want_visited = want_edge = 0
    for i in range(nq):
        for j in range(nk):
            listed = {(r, c): flags for r, c, flags in blocks[i][j]}
            for r in range(bq // gq):
                for c in range(bk // gk):
                    q0, k0 = i * bq + r * gq, j * bk + c * gk
                    tile = live[q0:q0 + gq, k0:k0 + gk]
                    assert ((r, c) in listed) == bool(tile.any())
                    if tile.any():
                        want_visited += 1
                        want_edge += not tile.all()
                        assert any(listed[r, c]) == (not tile.all())
            # the kernels' matmuls cover a block's live tiles exactly once
            for by in (0, 1):
                covered = []
                for a0, a1, pieces in _tile_groups(blocks[i][j], by):
                    for b0, b1, flags in pieces:
                        for a in range(a0, a1):
                            for b in range(b0, b1):
                                rc = (a, b) if by == 0 else (b, a)
                                assert listed[rc] == flags
                                covered.append(rc)
                assert sorted(covered) == sorted(listed)
    assert (visited, edge, total) == (
        want_visited, want_edge, nq * nk * (bq // gq) * (bk // gk))
    # a live step fetches its own block; a dead step of a causal grid
    # stays beside its row's live blocks (nothing new to fetch)
    for i in range(nq):
        live_k = [j for j in range(nk) if blocks[i][j]]
        for j in range(nk):
            k_at = int(_last_live_k(i, j, causal, bq, bk))
            q_at = int(_first_live_q(j, i, causal, bq, bk, nq))
            if blocks[i][j]:
                assert (q_at, k_at) == (i, j)
            else:
                assert causal and live_k[-1] <= k_at <= j
                live_q = [a for a in range(nq) if blocks[a][j]]
                assert i <= q_at <= (live_q[0] if live_q else nq - 1)


def test_live_tiles_at_the_training_cell():
    """``gpt2_train``: causal 1024 x 1024.  Whatever the blocks, 36 of
    the 64 tiles of 128 hold a live score and 8 of them the diagonal; a
    non-causal unpadded call has no edge, so no mask at all."""
    from mxnet_tpu.ops.attention import _live_tiles
    for blocks in ((512, 512), (1024, 1024), (256, 256), (128, 128)):
        assert _live_tiles(True, 1024, 1024, *blocks)[1] == (36, 8, 64)
    # the backward kernels' grain: 10 tiles of 256 (40 of 128), 4 edges
    assert _live_tiles(True, 1024, 1024, 1024, 1024, 256)[1] == (10, 4, 16)
    assert _live_tiles(False, 1024, 1024, 512, 512)[1] == (64, 0, 64)
    # ragged (100 x 180 in tiles of 128): the rim's tiles are edges
    assert _live_tiles(False, 100, 180, 128, 128)[1] == (2, 2, 2)
    # padded to the block: 520 rows in blocks of 512 are 1024, whose
    # last three tiles of rows are dead and whose fifth is an edge
    assert _live_tiles(False, 520, 1024, 512, 512)[1] == (40, 8, 64)


@pytest.mark.parametrize("causal,sq,sk,bq,bk", [
    # rows 0..127 of the one grid block have every key of its second
    # 128 masked (block_q != block_k: the whole block is one masked tile)
    (True, 200, 200, 128, 256),
    # 260 rows in blocks of 256: rows 384..511 are a tile wholly in the
    # padding and 256..383 an edge; 520 keys likewise
    (False, 260, 520, 256, 512), (True, 260, 260, 256, 256)])
def test_masked_rows_and_padded_tiles_give_zeros(causal, sq, sk, bq, bk):
    from mxnet_tpu.ops.attention import (_fa_backward, _fa_backward_pallas,
                                         _fa_forward_pallas)
    rng = onp.random.RandomState(34)
    bh, d = 2, 64
    q, k, v, do = (jnp.asarray(rng.randn(bh, s, d).astype("float32") * 0.5)
                   for s in (sq, sk, sk, sq))
    out, lse = _fa_forward_pallas(q, k, v, causal, d ** -0.5, bq, bk)
    s = jnp.einsum("bqd,bkd->bqk", q, k) * d ** -0.5
    if causal:
        s = jnp.where(jnp.asarray(_live_mask(True, sq, sk, sq, sk)), s,
                      -jnp.inf)
    onp.testing.assert_allclose(onp.asarray(lse),
                                onp.asarray(jax.nn.logsumexp(s, axis=-1)),
                                rtol=1e-5, atol=1e-5)
    ref = attention_reference(q[None], k[None], v[None], causal=causal)[0]
    onp.testing.assert_allclose(onp.asarray(out), onp.asarray(ref),
                                rtol=2e-4, atol=2e-4)
    got = _fa_backward_pallas(causal, d ** -0.5, bq, bk,
                              (q, k, v, out, lse), do)
    want = _fa_backward(causal, d ** -0.5, bq, (q, k, v, out, lse), do)
    for a, b, nm in zip(got, want, ("dq", "dk", "dv")):
        assert a.shape == b.shape and bool(jnp.isfinite(a).all()), nm
        onp.testing.assert_allclose(onp.asarray(a), onp.asarray(b),
                                    rtol=2e-4, atol=2e-4, err_msg=nm)
    if causal:
        # the last key is seen by the last row alone: every other row's
        # masked score there adds an exact zero to its dk and dv
        w = jnp.exp(s[:, -1, -1] - lse[:, -1])[:, None]
        onp.testing.assert_allclose(onp.asarray(got[2][:, -1]),
                                    onp.asarray(w * do[:, -1]),
                                    rtol=1e-5, atol=1e-6)


def _dots_by_kernel(fn, *args):
    """{kernel name: [(lhs dtype, rhs dtype, out dtype), ...]} of every
    ``dot_general`` inside the Pallas calls ``fn`` traces."""
    found = {}

    def walk(jaxpr, kernel):
        for eqn in jaxpr.eqns:
            name = kernel
            if eqn.primitive.name == "pallas_call":
                name = (eqn.params.get("name")
                        or eqn.params["name_and_src_info"].name)
            if eqn.primitive.name == "dot_general" and kernel:
                found.setdefault(kernel, []).append(tuple(
                    str(v.aval.dtype) for v in (*eqn.invars, *eqn.outvars)))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub, name)

    walk(jax.make_jaxpr(fn)(*args).jaxpr, None)
    return found


@pytest.mark.parametrize("causal", [False, True])
def test_flash_products_keep_their_precision(causal):
    """Every product of the three kernels has the operand and result
    types it had before the schedule: the forward's in the input dtype,
    a score recomputed in the backward too, everything else of the
    backward in float32; all accumulate in float32."""
    from mxnet_tpu.ops.attention import (_fa_backward_pallas,
                                         _fa_forward_pallas)
    q = jnp.zeros((1, 1024, 64), jnp.bfloat16)

    def both(q, k, v, do):
        out, lse = _fa_forward_pallas(q, k, v, causal, 0.125, 512, 512)
        return _fa_backward_pallas(causal, 0.125, 512, 512,
                                   (q, k, v, out, lse), do)

    dots = _dots_by_kernel(both, q, q, q, q)
    assert sorted(dots) == ["mxtpu_flash_dkv", "mxtpu_flash_dq",
                            "mxtpu_flash_fwd"]
    low = ("bfloat16", "bfloat16", "float32")
    f32 = ("float32",) * 3
    assert set(dots["mxtpu_flash_fwd"]) == {low}
    for name, per_score in (("mxtpu_flash_dkv", 3), ("mxtpu_flash_dq", 2)):
        kinds = dots[name]
        assert set(kinds) == {low, f32}
        assert kinds.count(f32) == per_score * kinds.count(low)
