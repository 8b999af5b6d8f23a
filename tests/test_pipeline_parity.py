"""Pipeline-schedule numerics: every schedule is the SAME math.

GPipe, interleaved and true-1F1B run identical stage compute in
different orders over one set of stacked params — their
value_and_grad must agree to float32 parity (rtol 1e-6), pinning that
no schedule silently reorders accumulation into different numerics.
Ring/ulysses sequence-parallel attention must likewise match the
single-device reference in ops/attention.py when run on a composed
MeshPlan mesh whose pp/ep/tp axes sit at size 1 (the retained-axis
property of the 4-D plan).
"""
import jax
import jax.numpy as jnp
import numpy as onp

from mxnet_tpu.parallel import (MeshPlan, pipeline_forward,
                                pipeline_forward_interleaved,
                                pipeline_value_and_grad_1f1b,
                                ring_self_attention,
                                ulysses_self_attention)


def _stage_fn(params, x):
    w, b = params
    return jax.nn.relu(x @ w + b)


def _stacked(rng, S, H):
    return (jnp.asarray(rng.randn(S, H, H).astype(onp.float32) * 0.3),
            jnp.asarray(rng.randn(S, H).astype(onp.float32) * 0.1))


def _mse(y, t):
    return jnp.mean((y - t) ** 2)


def test_gpipe_interleaved_1f1b_value_and_grad_parity():
    """All three schedules, one set of stacked params, one loss: the
    (loss, grads) triple agrees pairwise at rtol 1e-6."""
    S, H, B, M = 4, 6, 16, 4
    rng = onp.random.RandomState(10)
    mesh = MeshPlan(dp=1, pp=S).mesh
    params = _stacked(rng, S, H)
    x = jnp.asarray(rng.randn(B, H).astype(onp.float32))
    t = jnp.asarray(rng.randn(B, H).astype(onp.float32))

    def gpipe_loss(p):
        out = pipeline_forward(_stage_fn, p, x, mesh, n_microbatches=M,
                               batch_axis_name=None)
        return _mse(out, t)

    def inter_loss(p):
        out = pipeline_forward_interleaved(_stage_fn, p, x, mesh,
                                           n_microbatches=M,
                                           batch_axis_name=None)
        return _mse(out, t)

    # each schedule as ONE compiled program: called eagerly, a shard_map
    # dispatches its body primitive by primitive to every device
    l_g, g_g = jax.jit(jax.value_and_grad(gpipe_loss))(params)
    l_i, g_i = jax.jit(jax.value_and_grad(inter_loss))(params)
    l_f, g_f = jax.jit(lambda p: pipeline_value_and_grad_1f1b(
        _stage_fn, _mse, p, x, t, mesh, n_microbatches=M,
        batch_axis_name=None))(params)

    for name, (l, g) in (("interleaved", (l_i, g_i)),
                         ("1f1b", (l_f, g_f))):
        onp.testing.assert_allclose(float(l), float(l_g), rtol=1e-6,
                                    err_msg=name)
        for a, b in zip(g, g_g):
            onp.testing.assert_allclose(onp.asarray(a), onp.asarray(b),
                                        rtol=1e-6, atol=1e-7,
                                        err_msg=name)


def test_schedules_agree_under_dp_x_pp():
    """Same pairwise parity with the batch sharded over dp as well —
    the composed-mesh regime the 4-D plan trains in."""
    S, H, B, M = 4, 4, 16, 4
    rng = onp.random.RandomState(11)
    mesh = MeshPlan(dp=2, pp=S).mesh
    params = _stacked(rng, S, H)
    x = jnp.asarray(rng.randn(B, H).astype(onp.float32))
    t = jnp.asarray(rng.randn(B, H).astype(onp.float32))

    def gpipe_loss(p):
        out = pipeline_forward(_stage_fn, p, x, mesh, n_microbatches=M)
        return _mse(out, t)

    l_g, g_g = jax.jit(jax.value_and_grad(gpipe_loss))(params)
    l_f, g_f = jax.jit(lambda p: pipeline_value_and_grad_1f1b(
        _stage_fn, _mse, p, x, t, mesh, n_microbatches=M))(params)
    onp.testing.assert_allclose(float(l_f), float(l_g), rtol=1e-6)
    for a, b in zip(g_f, g_g):
        onp.testing.assert_allclose(onp.asarray(a), onp.asarray(b),
                                    rtol=1e-6, atol=1e-7)


def test_ring_attention_matches_reference_on_composed_mesh():
    """ring attention on a MeshPlan(sp=4) mesh — pp/ep/tp present at
    size 1 — matches ops/attention.py's dense reference."""
    from mxnet_tpu.ops.attention import attention_reference
    B, H, S, D = 2, 2, 16, 4
    rng = onp.random.RandomState(12)
    plan = MeshPlan(dp=1, sp=4)
    assert plan.axis_sizes["pp"] == 1     # retained, not dropped
    q = jnp.asarray(rng.randn(B, H, S, D).astype(onp.float32))
    k = jnp.asarray(rng.randn(B, H, S, D).astype(onp.float32))
    v = jnp.asarray(rng.randn(B, H, S, D).astype(onp.float32))
    got = jax.jit(lambda q, k, v: ring_self_attention(
        q, k, v, plan.mesh))(q, k, v)
    want = attention_reference(q, k, v)
    onp.testing.assert_allclose(onp.asarray(got), onp.asarray(want),
                                rtol=1e-5, atol=1e-6)


def test_ulysses_attention_matches_reference_on_composed_mesh():
    from mxnet_tpu.ops.attention import attention_reference
    B, H, D = 2, 4, 4
    rng = onp.random.RandomState(13)
    plan = MeshPlan(dp=1, sp=4)
    S = 8 * plan.axis_sizes["sp"]
    q = jnp.asarray(rng.randn(B, H, S, D).astype(onp.float32))
    k = jnp.asarray(rng.randn(B, H, S, D).astype(onp.float32))
    v = jnp.asarray(rng.randn(B, H, S, D).astype(onp.float32))
    for causal in (False, True):
        got = jax.jit(lambda q, k, v: ulysses_self_attention(
            q, k, v, plan.mesh, causal=causal))(q, k, v)
        want = attention_reference(q, k, v, causal=causal)
        onp.testing.assert_allclose(onp.asarray(got),
                                    onp.asarray(want),
                                    rtol=1e-5, atol=1e-6,
                                    err_msg=f"causal={causal}")
