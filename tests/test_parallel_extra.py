"""Multichip training evidence: pipeline parallelism, ring attention in
a training step, and compile-level scaling efficiency.

Addresses the round-1 gap ("no pp/sp training test, no ring-attention-
in-a-training-step test, no scaling-efficiency measurement") on the
8-device virtual CPU mesh (conftest).
"""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest
from jax.sharding import NamedSharding, PartitionSpec

from mxnet_tpu.parallel import (make_mesh, pipeline_forward,
                                ring_self_attention)


def _stage_fn(params, x):
    w, b = params
    return jax.nn.relu(x @ w + b)


def _stacked_params(rng, S, H):
    w = rng.randn(S, H, H).astype(onp.float32) * 0.3
    b = rng.randn(S, H).astype(onp.float32) * 0.1
    return (jnp.asarray(w), jnp.asarray(b))


def _sequential(params, x):
    w, b = params
    for s in range(w.shape[0]):
        x = jax.nn.relu(x @ w[s] + b[s])
    return x


def _jitted(schedule, stage_fn, mesh, **kw):
    """``schedule`` as ONE compiled program of (params, x).  Called
    eagerly a ``shard_map`` dispatches its body primitive by primitive
    to every device: minutes for an unrolled schedule on 8x4 arrays."""
    return jax.jit(lambda params, x: schedule(stage_fn, params, x, mesh,
                                              **kw))


def test_gpipe_forward_matches_sequential():
    S, H, B, M = 4, 8, 16, 4
    rng = onp.random.RandomState(0)
    mesh = make_mesh({"pp": S})
    params = _stacked_params(rng, S, H)
    x = jnp.asarray(rng.randn(B, H).astype(onp.float32))
    got = _jitted(pipeline_forward, _stage_fn, mesh, n_microbatches=M,
                  batch_axis_name=None)(params, x)
    ref = _sequential(params, x)
    onp.testing.assert_allclose(onp.asarray(got), onp.asarray(ref),
                                rtol=1e-5, atol=1e-5)


def test_gpipe_training_step_matches_sequential_grads():
    """jax.grad straight through the pipeline (backward runs the ring in
    reverse) must match the sequential model's gradients."""
    S, H, B, M = 4, 6, 8, 2
    rng = onp.random.RandomState(1)
    mesh = make_mesh({"pp": S})
    params = _stacked_params(rng, S, H)
    x = jnp.asarray(rng.randn(B, H).astype(onp.float32))
    y = jnp.asarray(rng.randn(B, H).astype(onp.float32))

    def pp_loss(p):
        out = pipeline_forward(_stage_fn, p, x, mesh, n_microbatches=M,
                               batch_axis_name=None)
        return jnp.mean((out - y) ** 2)

    def seq_loss(p):
        return jnp.mean((_sequential(p, x) - y) ** 2)

    l_pp, g_pp = jax.jit(jax.value_and_grad(pp_loss))(params)
    l_seq, g_seq = jax.jit(jax.value_and_grad(seq_loss))(params)
    onp.testing.assert_allclose(float(l_pp), float(l_seq), rtol=1e-5)
    for a, b in zip(g_pp, g_seq):
        onp.testing.assert_allclose(onp.asarray(a), onp.asarray(b),
                                    rtol=1e-4, atol=1e-5)


def test_gpipe_dp_x_pp():
    """dp2 x pp4: batch sharded over dp while stages stream over pp."""
    S, H, B, M = 4, 4, 16, 2
    rng = onp.random.RandomState(2)
    mesh = make_mesh({"dp": 2, "pp": S})
    params = _stacked_params(rng, S, H)
    x = jnp.asarray(rng.randn(B, H).astype(onp.float32))
    got = _jitted(pipeline_forward, _stage_fn, mesh,
                  n_microbatches=M)(params, x)
    ref = _sequential(params, x)
    onp.testing.assert_allclose(onp.asarray(got), onp.asarray(ref),
                                rtol=1e-5, atol=1e-5)


def test_ring_attention_inside_training_step():
    """Train one step where the forward runs ring attention over an sp
    axis; gradients must match the dense single-device attention."""
    B, H, S, D, NSP = 2, 2, 16, 4, 4
    rng = onp.random.RandomState(3)
    mesh = make_mesh({"sp": NSP})
    q = jnp.asarray(rng.randn(B, H, S, D).astype(onp.float32))
    k = jnp.asarray(rng.randn(B, H, S, D).astype(onp.float32))
    v = jnp.asarray(rng.randn(B, H, S, D).astype(onp.float32))
    wo = jnp.asarray(rng.randn(D, D).astype(onp.float32))

    def ring_loss(w):
        o = ring_self_attention(q, k, v, mesh)
        return jnp.mean((o @ w) ** 2)

    def dense_loss(w):
        s = (q @ jnp.swapaxes(k, -1, -2)) / (D ** 0.5)
        o = jax.nn.softmax(s, axis=-1) @ v
        return jnp.mean((o @ w) ** 2)

    l_r, g_r = jax.jit(jax.value_and_grad(ring_loss))(wo)
    l_d, g_d = jax.jit(jax.value_and_grad(dense_loss))(wo)
    onp.testing.assert_allclose(float(l_r), float(l_d), rtol=1e-4)
    onp.testing.assert_allclose(onp.asarray(g_r), onp.asarray(g_d),
                                rtol=1e-3, atol=1e-5)


def test_dp_scaling_efficiency_compile_level():
    """Per-device FLOPs must scale ~1/N under dp sharding — the
    compile-level scaling-efficiency check that virtual (1-core) devices
    can actually measure."""
    H, B = 64, 64

    def loss(w, x):
        return jnp.mean(jax.nn.relu(x @ w) ** 2)

    w = jnp.ones((H, H), jnp.float32)
    x = jnp.ones((B, H), jnp.float32)

    def flops_with_mesh(n):
        mesh = make_mesh({"dp": n})
        xs = jax.device_put(
            x, NamedSharding(mesh, PartitionSpec("dp")))
        ws = jax.device_put(w, NamedSharding(mesh, PartitionSpec()))
        compiled = jax.jit(jax.grad(loss)).lower(ws, xs).compile()
        ca = compiled.cost_analysis()
        if isinstance(ca, list):
            ca = ca[0]
        return float(ca.get("flops", 0.0))

    f1 = flops_with_mesh(1)
    f8 = flops_with_mesh(8)
    if f1 <= 0 or f8 <= 0:
        pytest.skip("cost_analysis reports no flops on this backend")
    # cost_analysis reports per-device program flops under SPMD
    ratio = f1 / f8
    assert ratio > 4.0, f"dp8 per-device flops only {ratio:.1f}x smaller"


def test_pipeline_validation_errors():
    import pytest as _pytest
    rng = onp.random.RandomState(0)
    mesh = make_mesh({"pp": 4})
    bad = (jnp.asarray(rng.randn(8, 4, 4).astype(onp.float32)),
           jnp.asarray(rng.randn(8, 4).astype(onp.float32)))
    x = jnp.ones((8, 4), jnp.float32)
    with _pytest.raises(ValueError, match="stage"):
        pipeline_forward(_stage_fn, bad, x, mesh, n_microbatches=2,
                         batch_axis_name=None)
    good = _stacked_params(rng, 4, 4)
    with _pytest.raises(ValueError, match="divisible"):
        pipeline_forward(_stage_fn, good, jnp.ones((10, 4), jnp.float32),
                         mesh, n_microbatches=4, batch_axis_name=None)


def _moe_params(rng, H=8, E=4, F=16):
    return (jnp.asarray(rng.randn(H, E).astype(onp.float32) * .5),
            jnp.asarray(rng.randn(E, H, F).astype(onp.float32) * .3),
            jnp.asarray(rng.randn(E, F).astype(onp.float32) * .1),
            jnp.asarray(rng.randn(E, F, H).astype(onp.float32) * .3),
            jnp.asarray(rng.randn(E, H).astype(onp.float32) * .1))


def _moe_dense_reference(x, gate_w, w1, b1, w2, b2):
    """Every token through its argmax expert, no capacity limit."""
    probs = jax.nn.softmax(x @ gate_w, axis=-1)
    idx = onp.asarray(jnp.argmax(probs, axis=-1))
    gate = onp.asarray(jnp.take_along_axis(
        probs, jnp.asarray(idx)[:, None], axis=1))[:, 0]
    out = onp.zeros_like(onp.asarray(x))
    for i, e in enumerate(idx):
        hdn = onp.maximum(onp.asarray(x)[i] @ onp.asarray(w1)[e]
                          + onp.asarray(b1)[e], 0)
        out[i] = (hdn @ onp.asarray(w2)[e] + onp.asarray(b2)[e]) * gate[i]
    return out


def test_switch_moe_matches_dense_routing():
    from mxnet_tpu.parallel import switch_moe
    rng = onp.random.RandomState(4)
    params = _moe_params(rng)
    x = jnp.asarray(rng.randn(16, 8).astype(onp.float32))
    # capacity ample → no dropped tokens, must match per-token routing
    y, aux = switch_moe(x, *params, capacity_factor=4.0)
    ref = _moe_dense_reference(x, *params)
    onp.testing.assert_allclose(onp.asarray(y), ref, rtol=1e-4, atol=1e-5)
    assert float(aux) > 0


def test_switch_moe_capacity_drops_tokens():
    from mxnet_tpu.parallel import switch_moe
    rng = onp.random.RandomState(5)
    params = _moe_params(rng)
    x = jnp.asarray(rng.randn(16, 8).astype(onp.float32))
    y_small, _ = switch_moe(x, *params, capacity_factor=0.25)
    ref = _moe_dense_reference(x, *params)
    # some tokens overflowed → zero rows where dense reference is nonzero
    dropped = (onp.abs(onp.asarray(y_small)).sum(1) == 0) & \
        (onp.abs(ref).sum(1) > 0)
    assert dropped.any()


def test_switch_moe_expert_parallel_compiles_and_matches():
    """ep-sharded experts under jit: same numerics as unsharded, and the
    training grad compiles over the mesh."""
    from mxnet_tpu.parallel import moe_expert_sharding, switch_moe
    rng = onp.random.RandomState(6)
    params = _moe_params(rng)
    x = jnp.asarray(rng.randn(32, 8).astype(onp.float32))
    y_ref, _ = switch_moe(x, *params, capacity_factor=4.0)

    mesh = make_mesh({"ep": 4})
    rep, *ex = moe_expert_sharding(mesh)
    sharded = [jax.device_put(p, s)
               for p, s in zip(params, [rep] + list(ex))]
    xs = jax.device_put(x, NamedSharding(mesh, PartitionSpec()))

    @jax.jit
    def fwd(gw, w1, b1, w2, b2, xx):
        return switch_moe(xx, gw, w1, b1, w2, b2, capacity_factor=4.0)[0]

    y = fwd(*sharded, xs)
    onp.testing.assert_allclose(onp.asarray(y), onp.asarray(y_ref),
                                rtol=1e-4, atol=1e-5)

    def loss(ps, xx):
        y, aux = switch_moe(xx, *ps, capacity_factor=4.0)
        return jnp.mean(y ** 2) + 0.01 * aux

    g = jax.jit(jax.grad(loss))(tuple(sharded), xs)
    assert all(onp.isfinite(onp.asarray(gi)).all() for gi in g)


def test_ulysses_matches_dense():
    """Ulysses all-to-all attention == dense single-device attention,
    forward + gradient, causal and non-causal."""
    from mxnet_tpu.parallel import ulysses_self_attention

    B, H, S, D, NSP = 2, 4, 16, 4, 4
    rng = onp.random.RandomState(5)
    mesh = make_mesh({"sp": NSP})
    q = jnp.asarray(rng.randn(B, H, S, D).astype(onp.float32))
    k = jnp.asarray(rng.randn(B, H, S, D).astype(onp.float32))
    v = jnp.asarray(rng.randn(B, H, S, D).astype(onp.float32))
    wo = jnp.asarray(rng.randn(D, D).astype(onp.float32))

    for causal in (False, True):
        # differentiate wrt q AND wo so gradients flow through BOTH
        # all-to-alls (their transpose rules), not just downstream
        def uly_loss(qq, w):
            o = ulysses_self_attention(qq, k, v, mesh, causal=causal)
            return jnp.mean((o @ w) ** 2)

        def dense_loss(qq, w):
            s = (qq @ jnp.swapaxes(k, -1, -2)) / (D ** 0.5)
            if causal:
                m = jnp.tril(jnp.ones((S, S), bool))
                s = jnp.where(m, s, -1e30)
            o = jax.nn.softmax(s, axis=-1) @ v
            return jnp.mean((o @ w) ** 2)

        l_u, (gq_u, gw_u) = jax.jit(jax.value_and_grad(
            uly_loss, argnums=(0, 1)))(q, wo)
        l_d, (gq_d, gw_d) = jax.jit(jax.value_and_grad(
            dense_loss, argnums=(0, 1)))(q, wo)
        onp.testing.assert_allclose(float(l_u), float(l_d), rtol=1e-4)
        onp.testing.assert_allclose(onp.asarray(gq_u),
                                    onp.asarray(gq_d),
                                    rtol=1e-3, atol=1e-5)
        onp.testing.assert_allclose(onp.asarray(gw_u),
                                    onp.asarray(gw_d),
                                    rtol=1e-3, atol=1e-5)


@pytest.mark.parametrize("H,HKV,NSP", [
    (4, 2, 4),    # hkv % p != 0: pre-expanded path
    (8, 4, 4),    # hkv % p == 0, group 2: small-K/V a2a + local repeat
    (4, 4, 4),    # MHA (no grouping)
    (8, 2, 2),    # group 4, small axis
])
def test_ulysses_gqa_expand(H, HKV, NSP):
    """GQA K/V must match the dense GQA reference on both the
    pre-expanded and the small-K/V-all-to-all paths."""
    from mxnet_tpu.parallel import ulysses_self_attention

    B, S, D = 1, 8 * NSP, 4
    rng = onp.random.RandomState(6)
    mesh = make_mesh({"sp": NSP})
    q = jnp.asarray(rng.randn(B, H, S, D).astype(onp.float32))
    k = jnp.asarray(rng.randn(B, HKV, S, D).astype(onp.float32))
    v = jnp.asarray(rng.randn(B, HKV, S, D).astype(onp.float32))

    got = ulysses_self_attention(q, k, v, mesh)
    ke = jnp.repeat(k, H // HKV, axis=1)
    ve = jnp.repeat(v, H // HKV, axis=1)
    s = (q @ jnp.swapaxes(ke, -1, -2)) / (D ** 0.5)
    want = jax.nn.softmax(s, axis=-1) @ ve
    onp.testing.assert_allclose(onp.asarray(got), onp.asarray(want),
                                rtol=1e-4, atol=1e-5)


def test_ulysses_bad_head_counts_raise():
    from mxnet_tpu.parallel import ulysses_self_attention

    mesh = make_mesh({"sp": 4})
    q = jnp.zeros((1, 4, 8, 4), jnp.float32)
    bad_kv = jnp.zeros((1, 3, 8, 4), jnp.float32)
    with pytest.raises(Exception, match="not divisible by kv heads"):
        ulysses_self_attention(q, bad_kv, bad_kv, mesh)
    q6 = jnp.zeros((1, 6, 8, 4), jnp.float32)
    with pytest.raises(Exception, match="not divisible by axis"):
        ulysses_self_attention(q6, q6, q6, mesh)


def test_mha_sp_mode_ulysses_matches_ring():
    """MultiHeadAttention(sp_mode='ulysses') trains to the same loss
    as sp_mode='ring' and as the dense single-device layer."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.transformer import MultiHeadAttention
    from mxnet_tpu.ndarray import NDArray

    B, S, U, H, NSP = 2, 16, 8, 4, 4
    rng = onp.random.RandomState(7)
    x = rng.randn(B, S, U).astype("float32")
    mesh = make_mesh({"sp": NSP})

    outs = {}
    for mode, m in (("dense", None), ("ring", mesh), ("ulysses", mesh)):
        mx.random.seed(11)
        kw = dict(causal=True, use_flash=False)
        if m is not None:
            kw.update(ring_mesh=m, sp_mode=mode)
        mha = MultiHeadAttention(U, H, **kw)
        mha.initialize(init=mx.initializer.Xavier())
        outs[mode] = mha(NDArray(x)).asnumpy()
    onp.testing.assert_allclose(outs["ring"], outs["dense"],
                                rtol=1e-4, atol=1e-5)
    onp.testing.assert_allclose(outs["ulysses"], outs["dense"],
                                rtol=1e-4, atol=1e-5)


# -- interleaved 1F1B schedule (VERDICT r3 item 6) --------------------------

def _layer_stack(rng, L, H):
    w = rng.randn(L, H, H).astype(onp.float32) * 0.3
    b = rng.randn(L, H).astype(onp.float32) * 0.1
    return (jnp.asarray(w), jnp.asarray(b))


def test_interleaved_forward_matches_sequential():
    from mxnet_tpu.parallel import pipeline_forward_interleaved
    S, V, H, B, M = 4, 2, 6, 8, 4
    rng = onp.random.RandomState(4)
    mesh = make_mesh({"pp": S})
    params = _layer_stack(rng, S * V, H)
    x = jnp.asarray(rng.randn(B, H).astype(onp.float32))
    got = _jitted(pipeline_forward_interleaved, _stage_fn, mesh,
                  n_microbatches=M, batch_axis_name=None)(params, x)
    ref = _sequential(params, x)
    onp.testing.assert_allclose(onp.asarray(got), onp.asarray(ref),
                                rtol=1e-5, atol=1e-5)


def test_interleaved_matches_gpipe_numerics_and_grads():
    """Same model through both schedules: identical losses and grads
    (the interleaved layout permutes parameter placement, not math)."""
    from mxnet_tpu.parallel import pipeline_forward_interleaved
    S, V, H, B, M = 4, 2, 4, 8, 4
    rng = onp.random.RandomState(5)
    mesh = make_mesh({"pp": S})
    layers = _layer_stack(rng, S * V, H)
    x = jnp.asarray(rng.randn(B, H).astype(onp.float32))
    y = jnp.asarray(rng.randn(B, H).astype(onp.float32))

    # GPipe: V contiguous layers per stage
    def gpipe_stage(params, xx):
        w, b = params
        for j in range(V):
            xx = jax.nn.relu(xx @ w[j] + b[j])
        return xx

    gpipe_params = tuple(a.reshape((S, V) + a.shape[1:]) for a in layers)

    def gpipe_loss(p):
        out = pipeline_forward(gpipe_stage, p, x, mesh, n_microbatches=M,
                               batch_axis_name=None)
        return jnp.mean((out - y) ** 2)

    def inter_loss(p):
        out = pipeline_forward_interleaved(_stage_fn, p, x, mesh,
                                    n_microbatches=M,
                                    batch_axis_name=None)
        return jnp.mean((out - y) ** 2)

    l_g, g_g = jax.jit(jax.value_and_grad(gpipe_loss))(gpipe_params)
    l_f, g_f = jax.jit(jax.value_and_grad(inter_loss))(layers)
    onp.testing.assert_allclose(float(l_f), float(l_g), rtol=1e-5)
    for a, b in zip(g_f, g_g):
        onp.testing.assert_allclose(
            onp.asarray(a).reshape(onp.asarray(b).shape), onp.asarray(b),
            rtol=1e-4, atol=1e-5)


def test_interleaved_bubble_lower_than_gpipe_at_m_eq_s():
    """The measured win: per-device schedule length (in single-layer
    time units) and compiled FLOPs are both lower than GPipe at M=S."""
    from mxnet_tpu.parallel import (gpipe_ticks, interleaved_ticks,
                                    pipeline_forward_interleaved)
    S, V, M = 4, 2, 4
    t_gpipe = gpipe_ticks(S, V, M)            # V*(S+M-1) = 14
    t_inter = interleaved_ticks(S, V, M)      # V*S+M-1  = 11
    assert t_inter < t_gpipe
    useful = V * M
    bubble_gpipe = (t_gpipe - useful) / t_gpipe
    bubble_inter = (t_inter - useful) / t_inter
    assert bubble_inter < bubble_gpipe        # 27% < 43%

    # compiled-FLOPs evidence on the virtual mesh: the schedules run the
    # same useful math, so total HLO flops per step ~ tick count
    H, B = 16, 8
    rng = onp.random.RandomState(6)
    mesh = make_mesh({"pp": S})
    layers = _layer_stack(rng, S * V, H)
    x = jnp.asarray(rng.randn(B, H).astype(onp.float32))

    def gpipe_stage(params, xx):
        w, b = params
        for j in range(V):
            xx = jax.nn.relu(xx @ w[j] + b[j])
        return xx

    gpipe_params = tuple(a.reshape((S, V) + a.shape[1:]) for a in layers)

    def flops_of(fn, *args):
        c = jax.jit(fn).lower(*args).compile().cost_analysis()
        if isinstance(c, list):
            c = c[0]
        return float(c.get("flops", 0.0))

    f_gpipe = flops_of(
        lambda p, xx: pipeline_forward(gpipe_stage, p, xx, mesh,
                                       n_microbatches=M,
                                       batch_axis_name=None),
        gpipe_params, x)
    f_inter = flops_of(
        lambda p, xx: pipeline_forward_interleaved(_stage_fn, p, xx, mesh,
                                            n_microbatches=M,
                                            batch_axis_name=None),
        layers, x)
    assert f_inter < f_gpipe, (f_inter, f_gpipe)


def test_interleaved_rejects_deep_microbatching():
    from mxnet_tpu.parallel import pipeline_forward_interleaved
    S, V, H, B = 4, 2, 4, 16
    rng = onp.random.RandomState(7)
    mesh = make_mesh({"pp": S})
    layers = _layer_stack(rng, S * V, H)
    x = jnp.asarray(rng.randn(B, H).astype(onp.float32))
    with pytest.raises(ValueError, match="M <= S"):
        pipeline_forward_interleaved(_stage_fn, layers, x, mesh,
                              n_microbatches=8, batch_axis_name=None)


def test_interleaved_dp_x_pp():
    from mxnet_tpu.parallel import pipeline_forward_interleaved
    S, V, H, B, M = 4, 2, 4, 16, 2
    rng = onp.random.RandomState(8)
    mesh = make_mesh({"dp": 2, "pp": S})
    layers = _layer_stack(rng, S * V, H)
    x = jnp.asarray(rng.randn(B, H).astype(onp.float32))
    got = _jitted(pipeline_forward_interleaved, _stage_fn, mesh,
                  n_microbatches=M)(layers, x)
    ref = _sequential(layers, x)
    onp.testing.assert_allclose(onp.asarray(got), onp.asarray(ref),
                                rtol=1e-5, atol=1e-5)

# --------------------------------------------------------------------------
# True 1F1B (activation-bounded): pipeline_value_and_grad_1f1b
# --------------------------------------------------------------------------

def _mse(y, t):
    return jnp.mean((y - t) ** 2)


def _seq_value_and_grad(params, x, t, M):
    """Reference: same microbatched mean-of-means loss, no pipeline."""
    def loss(p):
        xmb = x.reshape((M, x.shape[0] // M) + x.shape[1:])
        tmb = t.reshape((M, t.shape[0] // M) + t.shape[1:])
        def one(xm, tm):
            h = xm
            for s in range(p[0].shape[0]):
                h = _stage_fn(jax.tree.map(lambda a: a[s], p), h)
            return _mse(h, tm)
        return jnp.mean(jax.vmap(one)(xmb, tmb))
    return jax.jit(jax.value_and_grad(loss))(params)


def test_true_1f1b_matches_sequential_deep_microbatching():
    """M=16 > S=4 — the regime the interleaved schedule rejects; true
    1F1B runs it and matches sequential loss+grads exactly."""
    from mxnet_tpu.parallel import pipeline_value_and_grad_1f1b
    S, H, B, M = 4, 6, 32, 16
    rng = onp.random.RandomState(40)
    mesh = make_mesh({"pp": S})
    params = _layer_stack(rng, S, H)
    x = jnp.asarray(rng.randn(B, H).astype(onp.float32))
    t = jnp.asarray(rng.randn(B, H).astype(onp.float32))
    loss, grads = jax.jit(lambda p, xx, tt: pipeline_value_and_grad_1f1b(
        _stage_fn, _mse, p, xx, tt, mesh, n_microbatches=M,
        batch_axis_name=None))(params, x, t)
    lref, gref = _seq_value_and_grad(params, x, t, M)
    onp.testing.assert_allclose(float(loss), float(lref), rtol=1e-6)
    for g, gr in zip(grads, gref):
        onp.testing.assert_allclose(onp.asarray(g), onp.asarray(gr),
                                    rtol=1e-4, atol=1e-6)


def test_true_1f1b_dp_x_pp_matches_sequential():
    from mxnet_tpu.parallel import pipeline_value_and_grad_1f1b
    S, H, B, M = 4, 4, 32, 8
    rng = onp.random.RandomState(41)
    mesh = make_mesh({"dp": 2, "pp": S})
    params = _layer_stack(rng, S, H)
    x = jnp.asarray(rng.randn(B, H).astype(onp.float32))
    t = jnp.asarray(rng.randn(B, H).astype(onp.float32))
    loss, grads = jax.jit(lambda p, xx, tt: pipeline_value_and_grad_1f1b(
        _stage_fn, _mse, p, xx, tt, mesh, n_microbatches=M))(params, x, t)
    # dp shards see B/2 rows each with M microbatches; the reference is
    # the mean over both shards of the per-shard microbatched loss
    l0, g0 = _seq_value_and_grad(params, x[:B // 2], t[:B // 2], M)
    l1, g1 = _seq_value_and_grad(params, x[B // 2:], t[B // 2:], M)
    onp.testing.assert_allclose(float(loss), float((l0 + l1) / 2),
                                rtol=1e-6)
    for g, ga, gb in zip(grads, g0, g1):
        onp.testing.assert_allclose(onp.asarray(g),
                                    onp.asarray((ga + gb) / 2),
                                    rtol=1e-4, atol=1e-6)


def test_true_1f1b_activation_memory_bounded_in_M():
    """THE 1F1B property: XLA temp allocation stays flat as M grows
    (stash is a ring buffer of 2S-1 stage inputs), while GPipe-under-
    jax.grad keeps all M microbatches' activations live and its temp
    grows ~linearly.  Measured from compiled memory_analysis()."""
    from mxnet_tpu.parallel import (pipeline_forward,
                                    pipeline_value_and_grad_1f1b)
    S, H, mb = 4, 32, 4
    mesh = make_mesh({"pp": S})
    W = jnp.zeros((S, H, H), jnp.float32)
    b = jnp.zeros((S, H), jnp.float32)

    def temp_1f1b(M):
        x = jnp.zeros((M * mb, H), jnp.float32)
        f = jax.jit(lambda p, xx, tt: pipeline_value_and_grad_1f1b(
            _stage_fn, _mse, p, xx, tt, mesh, n_microbatches=M,
            batch_axis_name=None))
        return f.lower((W, b), x, x).compile() \
                .memory_analysis().temp_size_in_bytes

    def temp_gpipe(M):
        x = jnp.zeros((M * mb, H), jnp.float32)
        def loss(p, xx, tt):
            out = pipeline_forward(_stage_fn, p, xx, mesh,
                                   n_microbatches=M, batch_axis_name=None)
            return _mse(out, tt)
        f = jax.jit(jax.value_and_grad(loss))
        return f.lower((W, b), x, x).compile() \
                .memory_analysis().temp_size_in_bytes

    t8, t32 = temp_1f1b(8), temp_1f1b(32)
    g8, g32 = temp_gpipe(8), temp_gpipe(32)
    # GPipe temp grows with M (4x microbatches -> ~4x activations)
    assert g32 > 2.5 * g8, (g8, g32)
    # 1F1B temp is bounded: growing M 4x moves temp by < 10%
    assert t32 < 1.1 * t8, (t8, t32)
    # and at deep microbatching 1F1B uses far less temp than GPipe
    assert t32 < g32 / 4, (t32, g32)


def test_one_f_one_b_tick_accounting():
    from mxnet_tpu.parallel import one_f_one_b_ticks
    # schedule length: M + 2S - 2 paired ticks (the O(S) stash property
    # itself is pinned by the compiled-memory test above)
    assert one_f_one_b_ticks(4, 16) == 22
    assert one_f_one_b_ticks(8, 64) == 78


def test_pipeline_forward_1f1b_alias_warns():
    from mxnet_tpu.parallel import pipeline_forward_1f1b
    S, V, H, B, M = 4, 2, 4, 8, 4
    rng = onp.random.RandomState(42)
    mesh = make_mesh({"pp": S})
    layers = _layer_stack(rng, S * V, H)
    x = jnp.asarray(rng.randn(B, H).astype(onp.float32))
    with pytest.warns(DeprecationWarning, match="interleaved"):
        got = _jitted(pipeline_forward_1f1b, _stage_fn, mesh,
                      n_microbatches=M, batch_axis_name=None)(layers, x)
    ref = _sequential(layers, x)
    onp.testing.assert_allclose(onp.asarray(got), onp.asarray(ref),
                                rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------------------
# Ring FLASH attention: pallas local blocks + lse merge + ring backward
# --------------------------------------------------------------------------

@pytest.mark.parametrize("causal", [False, True])
def test_ring_flash_matches_reference_fwd_and_grads(causal):
    from mxnet_tpu.ops.attention import attention_reference
    from mxnet_tpu.parallel import ring_flash_self_attention

    mesh = make_mesh({"sp": 4})
    rng = onp.random.RandomState(60 + causal)
    B, H, S, D = 2, 2, 4 * 32, 16
    q = jnp.asarray(rng.randn(B, H, S, D).astype("float32") * .5)
    k = jnp.asarray(rng.randn(B, H, S, D).astype("float32") * .5)
    v = jnp.asarray(rng.randn(B, H, S, D).astype("float32") * .5)
    cot = jnp.asarray(rng.randn(B, H, S, D).astype("float32"))

    o_rf = ring_flash_self_attention(q, k, v, mesh, causal=causal,
                                     block_q=32, block_k=32)
    o_ref = attention_reference(q, k, v, causal=causal)
    onp.testing.assert_allclose(onp.asarray(o_rf), onp.asarray(o_ref),
                                rtol=1e-4, atol=1e-5)

    def loss_rf(q, k, v):
        return jnp.sum(ring_flash_self_attention(
            q, k, v, mesh, causal=causal, block_q=32, block_k=32) * cot)

    def loss_ref(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=causal) * cot)

    g_rf = jax.grad(loss_rf, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, nm in zip(g_rf, g_ref, "qkv"):
        onp.testing.assert_allclose(onp.asarray(a), onp.asarray(b),
                                    rtol=1e-3, atol=5e-4,
                                    err_msg=f"ring-flash d{nm}")


def test_ring_flash_gqa_expands_kv():
    from mxnet_tpu.ops.attention import attention_reference
    from mxnet_tpu.parallel import ring_flash_self_attention

    mesh = make_mesh({"sp": 4})
    rng = onp.random.RandomState(62)
    B, H, Hkv, S, D = 1, 4, 2, 4 * 16, 8
    q = jnp.asarray(rng.randn(B, H, S, D).astype("float32") * .5)
    k = jnp.asarray(rng.randn(B, Hkv, S, D).astype("float32") * .5)
    v = jnp.asarray(rng.randn(B, Hkv, S, D).astype("float32") * .5)
    o = ring_flash_self_attention(q, k, v, mesh, block_q=16, block_k=16)
    kx = jnp.repeat(k, H // Hkv, axis=1)
    vx = jnp.repeat(v, H // Hkv, axis=1)
    o_ref = attention_reference(q, kx, vx)
    onp.testing.assert_allclose(onp.asarray(o), onp.asarray(o_ref),
                                rtol=1e-4, atol=1e-5)
    # gradients through the pre-ring GQA expansion: the repeat's vjp
    # must group-sum dk/dv back to the hkv heads
    cot = jnp.asarray(rng.randn(B, H, S, D).astype("float32"))

    def loss_rf(q, k, v):
        return jnp.sum(ring_flash_self_attention(
            q, k, v, mesh, block_q=16, block_k=16) * cot)

    def loss_ref(q, k, v):
        return jnp.sum(attention_reference(
            q, jnp.repeat(k, H // Hkv, axis=1),
            jnp.repeat(v, H // Hkv, axis=1)) * cot)

    g_rf = jax.grad(loss_rf, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, nm in zip(g_rf, g_ref, "qkv"):
        assert a.shape == b.shape
        onp.testing.assert_allclose(onp.asarray(a), onp.asarray(b),
                                    rtol=1e-3, atol=5e-4,
                                    err_msg=f"ring-flash GQA d{nm}")


def test_ring_flash_matches_plain_ring():
    from mxnet_tpu.parallel import (ring_flash_self_attention,
                                    ring_self_attention)

    mesh = make_mesh({"sp": 4})
    rng = onp.random.RandomState(63)
    B, H, S, D = 2, 2, 4 * 16, 8
    q = jnp.asarray(rng.randn(B, H, S, D).astype("float32") * .5)
    o1 = ring_flash_self_attention(q, q, q, mesh, causal=True,
                                   block_q=16, block_k=16)
    o2 = ring_self_attention(q, q, q, mesh, causal=True)
    onp.testing.assert_allclose(onp.asarray(o1), onp.asarray(o2),
                                rtol=1e-4, atol=1e-5)


def test_ulysses_flash_local_engine_matches_dense():
    """use_flash routes the post-all-to-all local attention through the
    Pallas flash kernel; numerics (fwd + grads) match the dense local
    path."""
    from mxnet_tpu.parallel import ulysses_self_attention

    mesh = make_mesh({"sp": 4})
    rng = onp.random.RandomState(65)
    B, H, S, D = 2, 4, 4 * 32, 16
    q = jnp.asarray(rng.randn(B, H, S, D).astype("float32") * .5)
    cot = jnp.asarray(rng.randn(B, H, S, D).astype("float32"))

    for causal in (False, True):
        of = ulysses_self_attention(q, q, q, mesh, causal=causal,
                                    use_flash=True)
        od = ulysses_self_attention(q, q, q, mesh, causal=causal,
                                    use_flash=False)
        onp.testing.assert_allclose(onp.asarray(of), onp.asarray(od),
                                    rtol=1e-4, atol=1e-5)

        def lf(qq):
            return jnp.sum(ulysses_self_attention(
                qq, qq, qq, mesh, causal=causal, use_flash=True) * cot)

        def ld(qq):
            return jnp.sum(ulysses_self_attention(
                qq, qq, qq, mesh, causal=causal, use_flash=False) * cot)

        gf = jax.grad(lf)(q)
        gd = jax.grad(ld)(q)
        onp.testing.assert_allclose(onp.asarray(gf), onp.asarray(gd),
                                    rtol=1e-3, atol=5e-4)
