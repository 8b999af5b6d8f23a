"""CPU-side guard for the real-TPU Pallas tiling rule.

The TPU lowering requires every BlockSpec's last two dims to be
divisible by (8, 128) — sublane, lane — or equal to the respective
array dims.  CPU interpret mode (what this suite runs) never enforces
it, which is exactly how the round-5 flash-attention lse/delta specs
shipped broken for four rounds and only failed at the first real-TPU
contact.  This test intercepts pl.pallas_call for our flash kernels
and applies the rule statically, so a violating spec fails HERE, on
CPU, at test time."""
import functools

import jax
import jax.numpy as jnp
import numpy as onp
import pytest


def _check_block(block_shape, array_shape, where):
    """The documented TPU constraint on the last two dims."""
    if len(array_shape) < 2 or block_shape is None:
        return []
    errs = []
    # None entries are squeezed dims: drop the block dim AND its
    # aligned array dim together, so sub/lane compare against the
    # axes they actually tile
    dims, arr = [], []
    for b, a in zip(block_shape, array_shape):
        if b is not None:
            dims.append(b)
            arr.append(a)
    if len(dims) < 2:
        return []
    sub, lane = dims[-2], dims[-1]
    asub, alane = arr[-2], arr[-1]
    if not (lane % 128 == 0 or lane == alane):
        errs.append(f"{where}: lane dim {lane} not divisible by 128 "
                    f"nor equal to array's {alane}")
    if not (sub % 8 == 0 or sub == asub):
        errs.append(f"{where}: sublane dim {sub} not divisible by 8 "
                    f"nor equal to array's {asub}")
    return errs


def _spec_shapes(spec, aval_shape):
    bs = getattr(spec, "block_shape", None)
    if bs is None:
        return None
    return tuple(bs), tuple(aval_shape)


@pytest.fixture
def capture_specs(monkeypatch):
    """Wrap pl.pallas_call to record (in_specs, out_specs, shapes)."""
    from jax._src.pallas import pallas_call as pc_mod
    calls = []
    real = pc_mod.pallas_call

    def spy(kernel, *a, **kw):
        wrapped = real(kernel, *a, **kw)

        @functools.wraps(wrapped)
        def runner(*args):
            in_specs = kw.get("in_specs")
            out_specs = kw.get("out_specs")
            out_shape = kw.get("out_shape")
            calls.append({
                "name": getattr(kernel, "__name__",
                                getattr(getattr(kernel, "func", None),
                                        "__name__", "?")),
                "in": [(_spec_shapes(s, x.shape))
                       for s, x in zip(in_specs or [], args)],
                "out": [(_spec_shapes(s, o.shape))
                        for s, o in zip(out_specs or [],
                                        out_shape or [])],
            })
            return wrapped(*args)
        return runner

    import mxnet_tpu.ops.attention as att
    monkeypatch.setattr(att.pl, "pallas_call", spy)
    # the kernels' wrappers are jitted: a trace kept from an earlier test
    # of the same shapes would never reach the spy
    jax.clear_caches()
    return calls


def _assert_all_tileable(calls):
    errs = []
    checked = 0
    for c in calls:
        for i, pair in enumerate(c["in"]):
            if pair:
                checked += 1
                errs += _check_block(pair[0], pair[1],
                                     f"{c['name']} in[{i}]")
        for i, pair in enumerate(c["out"]):
            if pair:
                checked += 1
                errs += _check_block(pair[0], pair[1],
                                     f"{c['name']} out[{i}]")
    assert not errs, "TPU tile-rule violations:\n" + "\n".join(errs)
    assert calls, "no pallas_call was intercepted — guard is dead"
    # a refactor that moves specs out of kwargs (positional args,
    # grid_spec=...) or renames block_shape must break LOUDLY here,
    # not leave a green-but-vacuous guard
    assert checked >= 2 * len(calls), (
        f"guard went vacuous: {checked} spec pairs captured across "
        f"{len(calls)} pallas calls — pallas_call invocation style "
        f"changed; update the spy")


def test_flash_forward_specs_tileable(capture_specs):
    from mxnet_tpu.ops.attention import _fa_forward_pallas
    q = jnp.zeros((8, 128, 64), jnp.float32)
    _fa_forward_pallas(q, q, q, True, 0.125, 128, 128)
    _assert_all_tileable(capture_specs)


def test_flash_backward_specs_tileable(capture_specs):
    from mxnet_tpu.ops.attention import (_fa_backward_pallas,
                                         _fa_forward_pallas)
    q = jnp.zeros((8, 128, 64), jnp.float32)
    out, lse = _fa_forward_pallas(q, q, q, False, 0.125, 128, 128)
    _fa_backward_pallas(False, 0.125, 128, 128,
                        (q, q, q, out, lse), out)
    _assert_all_tileable(capture_specs)


def test_guard_catches_the_round5_bug():
    """The exact shape that failed on hardware: lse (1, block_q) block
    over a (8, 128) array must be flagged."""
    errs = _check_block((1, 128), (8, 128), "lse")
    assert errs and "sublane" in errs[0]
