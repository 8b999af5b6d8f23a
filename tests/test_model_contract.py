"""The model contract of the decode plane, the model alone: what every
model built from a benchmark configuration must pass, written once and
run for each family it applies to (``decode_families.py``), on the CPU.

- its dense oracle is its plain reference, and every branch of its
  layers weighs in the logits;
- a configuration that asks for what it does not implement is refused,
  and the abstract model has the published shapes and no weights;
- a router in a lower precision than the model's fails the margin the
  sound program keeps (routed families).
"""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest

from decode_families import (CHANGED, CONFIGURED, FAMILIES, ROUTED, Kit,
                             compare_one, config, load, published,
                             router_rounded, scaled, tokens)


@pytest.fixture(scope="module")
def kit():
    """One kit for the file: a family's model and reference, and the
    traced logits of its cached path, built once."""
    return Kit()


@pytest.mark.parametrize("family", CONFIGURED)
def test_dense_oracle_is_the_reference(kit, family):
    """The dense path the scheduler's tokens are pinned to against the
    plain float32 reference, within the family's float32 tolerance."""
    model, cfg = kit.model(family)
    toks = jnp.asarray(tokens(40, seed=1), jnp.int32)
    with jax.default_matmul_precision("highest"):
        want = onp.asarray(kit.ref(family).forward(model.params, toks, cfg))
        got = onp.asarray(model.dense_logits(model.params, toks))
    assert scaled(got, want) <= FAMILIES[family].tol["float32"]


@pytest.mark.parametrize("family,piece", [
    (family, piece) for family in CONFIGURED
    for piece in FAMILIES[family].branches])
def test_every_branch_weighs_in_the_logits(kit, family, piece):
    """Change one piece of every layer that has it (a matrix zeroed, the
    selection bias reversed) and the logits move by the family's floor
    or more, far past its tolerance: no branch hides, a routed expert
    under its divisor neither."""
    model, _ = kit.model(family)
    toks = jnp.asarray(tokens(40, seed=4), jnp.int32)
    change = CHANGED.get(piece, jnp.zeros_like)
    cut = dict(model.params, layers=[
        {k: (change(v) if k == piece else v) for k, v in lp.items()}
        for lp in model.params["layers"]])
    assert any(piece in lp for lp in model.params["layers"])
    with jax.default_matmul_precision("highest"):
        want = onp.asarray(model._dense_jit(model.params, toks))
        got = onp.asarray(model._dense_jit(cut, toks))
    assert scaled(got, want) > FAMILIES[family].branch_floor


@pytest.mark.parametrize("family", CONFIGURED)
def test_a_config_that_asks_for_what_is_not_there_is_refused(family):
    fam = FAMILIES[family]
    for over, says in fam.refused:
        with pytest.raises(ValueError, match=says):
            fam.build(config(fam.config, **over), abstract=True)
    cfg = config(fam.config)
    del cfg[fam.needs]
    with pytest.raises(ValueError, match="lacks"):
        fam.build(cfg, abstract=True)


@pytest.mark.parametrize("family", CONFIGURED)
def test_abstract_model_has_shapes_and_no_weights(family):
    """At the rehearsal size and at the published widths, the abstract
    model's parameters are shapes only, as many as the benchmark's own
    count of the configuration (a tied head counted once), and at the
    published widths as many as the configuration says."""
    fam = FAMILIES[family]
    counts = load(f"chipbench/models/{fam.counted_by}.py",
                  f"{fam.counted_by}_family")
    at_width = published(fam.config)
    for cfg in (config(fam.config), at_width):
        params = fam.build(cfg, abstract=True).params
        leaves = jax.tree_util.tree_leaves(params)
        assert all(isinstance(a, jax.ShapeDtypeStruct) for a in leaves)
        n = sum(a.size for a in leaves) - sum(
            params[name].size for name in fam.held_twice)
        assert n == counts.param_count(cfg)
    assert n == at_width["parameters"]


@pytest.mark.parametrize("family,dtype,mantissa_bits", [
    (family, dtype, bits) for family in ROUTED
    for dtype, bits in (("float32", 7), ("bfloat16", 3))])
def test_a_router_in_lower_precision_fails_the_margin(kit, monkeypatch,
                                                      family, dtype,
                                                      mantissa_bits):
    """The router's product taken in the nearest precision below the
    model's (bfloat16 under a float32 model, float8's mantissa under a
    bfloat16 one) moves the scores by more than the margin allows a tie
    to be: over the positions of one prompt some selection differs
    outside it (the sound program over the same positions keeps inside
    it: the family's own file holds that)."""
    fam = FAMILIES[family]
    control = fam.router_control
    model, _ = kit.model(family, dtype, **control["over"])
    monkeypatch.setattr(type(model), "_scores", router_rounded(mantissa_bits))
    _, flipped, widest = compare_one(
        kit, family, dtype, control["prompt"], 1,
        variant=f"router{mantissa_bits}",
        pages_per_slot=control["pages_per_slot"], **control["over"])
    assert flipped and widest > control["past"] * fam.margin[dtype], widest
