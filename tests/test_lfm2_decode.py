"""The decode plane with a fourth kind of model: LFM2's stack (gated
short convolutions and grouped-query attention in layers of their own,
a routed layer held whole, selection by biased scores) behind the model
protocol, over a cache laid out BY LAYER: the family's own controls
(what every model passes: ``test_decode_contract``,
``test_model_contract``, ``test_decode_in_place``).

All at the benchmark configuration's ``rehearsal`` size (both kinds of
operator under both kinds of feed-forward the cut has, 8 experts top-2,
three taps), seeded random weights, every branch at the plain fan-in
scale, on the CPU with the kernels interpreted:

- prefill in chunks (shorter than, equal to and longer than the
  convolution's tail; a chunk boundary inside a prompt) then decode
  through pages and tails against the plain reference's full pass
  (``chipbench/reference/lfm2_ref.py``), on LOGITS, with the routing
  ties handled as ``decode_families.compare`` says; a long table walked
  by its live pages; the router at the published 32 experts keeps the
  margin;
- the controls: a lower precision, a weight taken from the biased
  score, a tail carried wrongly across a chunk boundary each fail a
  limit;
- the router against a sort, with a bias that changes the selection and
  must not change a weight; the stacked product against the loop;
- the layout: a model of mixed layers is given pages for its attention
  layers alone, and the three models there were get the pools they got;
  the parameter count at the published widths by hand.
"""
import numpy as onp
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx  # noqa: F401  (registers ops + kernel specs)
from mxnet_tpu.parallel.moe import held_experts, route_topk, stacked_experts
from mxnet_tpu.serving import (AXK1, LFM2, DecodeEngine, DecodeModel,
                               FalconH1, PagedKVCache)
from mxnet_tpu.serving.decode import lfm2 as lfm2_mod
from mxnet_tpu.serving.decode import paged_kv

from decode_families import (CHUNK, MIXED, Kit, compare_one, config, engine,
                             load, paged_oracle, published)

TOL, MARGIN = MIXED.tol, MIXED.margin


@pytest.fixture(scope="module")
def kit():
    return Kit()


# prompt lengths by what the LAST chunk is to the tail of two rows: a
# chunk shorter than it (1; 17 = 16 + 1, where one row of the old tail
# stays), equal to it (2; 18), longer (5; 16; 37 = 16 + 16 + 5)
CASES = [1, 2, 5, CHUNK, 17, 18, 37]


@pytest.mark.parametrize("prompt_len", CASES)
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_prefill_then_cached_decode_matches_the_reference_on_logits(
        kit, dtype, prompt_len):
    worst, _, widest = compare_one(kit, "mixed", dtype, prompt_len)
    assert worst <= TOL[dtype], worst
    assert widest <= MARGIN[dtype], widest


def test_chunks_as_short_as_the_tail_carry_it(kit):
    """A prompt fed a row at a time: every chunk is shorter than the
    tail, so every new tail is one old row and one new."""
    worst, _, widest = compare_one(kit, "mixed", "float32", 11, chunk=1)
    assert worst <= TOL["float32"] and widest <= MARGIN["float32"]


@pytest.mark.parametrize("prompt_len", [5, 18, 37])
def test_a_long_table_is_walked_by_its_live_pages(kit, monkeypatch,
                                                  prompt_len):
    """Beyond ``_GATHER_ROWS`` positions a chunk walks the slot's live
    pages under an online softmax instead of gathering the whole table
    (here the limit is lowered under the tests' 64 positions): the same
    logits, and a loop in the program where the gather has none."""
    monkeypatch.setattr(paged_kv, "_GATHER_ROWS", 32)
    worst, _, widest = compare_one(kit, "mixed", "float32", prompt_len,
                                   variant="walk")
    assert worst <= TOL["float32"] and widest <= MARGIN["float32"], worst
    model, _ = kit.model("mixed")
    eng = engine(model)
    args = (model.params, eng.cache.pool, jnp.zeros((1, 8), jnp.int32),
            jnp.zeros((1,), jnp.int32), jnp.full((1,), 5, jnp.int32),
            jnp.asarray(eng.cache.tables[:1], jnp.int32),
            jnp.zeros((1,), jnp.int32))
    assert "while" in str(jax.make_jaxpr(model.prefill_logits)(*args))
    monkeypatch.setattr(paged_kv, "_GATHER_ROWS", 2048)
    assert "while" not in str(jax.make_jaxpr(model.prefill_logits)(*args))


# -- the controls: each fails a limit the sound program keeps ------------------

def _rounded(mantissa_bits):
    def rounded(a):
        return jax.lax.reduce_precision(a.astype(jnp.float32),
                                        exponent_bits=8,
                                        mantissa_bits=mantissa_bits)
    return rounded


def _coarse(names, mantissa_bits):
    """``coarse(params)``: the layers' entries that ``names`` picks
    rounded to ``mantissa_bits`` bits of mantissa."""
    rounded = _rounded(mantissa_bits)

    def coarse(params):
        return dict(params, layers=[
            {k: (rounded(v).astype(v.dtype) if names(k) else v)
             for k, v in lp.items()} for lp in params["layers"]])

    return coarse


def _experts(k):
    return k.startswith("experts_")


def _operators(k):
    return k in ("w_in", "w_out", "wq", "wk", "wv", "wo")


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_router_at_the_published_width_keeps_the_margin(kit, dtype):
    """At the published 32 experts top-4 (among 8 scores ties are too
    rare for 120 positions to meet one) the sound program keeps inside
    the tolerance and the margin over the positions on which a router in
    a lower precision fails it (``test_model_contract``)."""
    control = MIXED.router_control
    worst, _, widest = compare_one(
        kit, "mixed", dtype, control["prompt"], 1,
        pages_per_slot=control["pages_per_slot"], **control["over"])
    assert worst <= TOL[dtype] and widest <= MARGIN[dtype], (worst, widest)


@pytest.mark.parametrize("which,names", [("experts", _experts),
                                         ("operators", _operators)])
@pytest.mark.parametrize("dtype,mantissa_bits", [("float32", 7),
                                                 ("bfloat16", 3)])
def test_matrices_in_lower_precision_fail_the_tolerance(
        kit, dtype, mantissa_bits, which, names):
    """The stacked experts' matrices, or the operators', rounded to the
    nearest precision below the stated one (bfloat16 under float32,
    float8's mantissa under bfloat16), in the PROGRAM's place: over the
    limit the sound program keeps."""
    worst, _, _ = compare_one(kit, "mixed", dtype, 37,
                              coarse=_coarse(names, mantissa_bits))
    assert worst > 1.25 * TOL[dtype], worst


def test_a_gate_and_tail_in_bfloat16_fail_the_float32_tolerance(
        kit, monkeypatch):
    """``g = B * u`` and with it the tail rounded to bfloat16 where
    float32 is stated."""
    real_slot, real_chunk = lfm2_mod.slot_conv, lfm2_mod.chunk_conv
    rounded = _rounded(7)
    monkeypatch.setattr(lfm2_mod, "slot_conv", lambda tail, g, w, active:
                        real_slot(tail, rounded(g), w, active))
    monkeypatch.setattr(lfm2_mod, "chunk_conv",
                        lambda tail, g, w, slot, n:
                        real_chunk(tail, rounded(g), w, slot, n))
    worst, _, _ = compare_one(kit, "mixed", "float32", 37,
                              variant="gate_bf16")
    assert worst > 100 * TOL["float32"], worst


def test_a_weight_taken_from_the_biased_score_fails(kit, monkeypatch):
    """A router that weighs by ``s + b`` where ``s`` is stated selects
    the same experts in its own layer and moves the logits far over
    the float32 limit (3.7e-2: over bfloat16's too)."""
    def biased(scores, top_k, *, bias=None, **kw):
        return route_topk(scores + bias, top_k, **kw)

    monkeypatch.setattr(lfm2_mod, "route_topk", biased)
    worst, _, _ = compare_one(kit, "mixed", "float32", 37,
                              variant="biased_weight")
    assert worst > 1000 * TOL["float32"], worst


@pytest.mark.parametrize("prompt_len", [17, 18, 37])
def test_a_tail_carried_wrongly_across_a_chunk_boundary_fails(
        kit, monkeypatch, prompt_len):
    """A chunk that starts from a zero tail instead of the slot's: right
    inside the first chunk, wrong from the first boundary on."""
    real = lfm2_mod.chunk_conv
    monkeypatch.setattr(
        lfm2_mod, "chunk_conv", lambda tail, g, w, slot, n:
        (real(jnp.zeros_like(tail), g, w, slot, n)[0],
         real(tail, g, w, slot, n)[1]))
    worst, _, _ = compare_one(kit, "mixed", "float32", prompt_len,
                              variant="zero_tail")
    assert worst > 1000 * TOL["float32"], worst
    # the same fault inside one chunk is no fault: the slot's tail is
    # zero at admission
    worst, _, _ = compare_one(kit, "mixed", "float32", 5,
                              variant="zero_tail")
    assert worst <= TOL["float32"]


def test_the_convolution_operators_products_take_their_rows_as_two_numbers():
    """``_dot_wide``: float32 rows through a bfloat16 matrix as the
    rounded row and what rounding took away, one product over twice the
    rows: 2**-17 of the rows' size where one rounding leaves 2**-9."""
    rng = onp.random.RandomState(0)
    a = jnp.asarray(rng.randn(16, 256), jnp.float32)
    w = jnp.asarray(rng.randn(256, 64) / 16, jnp.bfloat16)
    exact = onp.asarray(a, onp.float64) @ onp.asarray(
        w.astype(jnp.float32), onp.float64)
    wide = onp.asarray(jax.jit(lfm2_mod._dot_wide)(a, w), onp.float64)
    once = onp.asarray(_rounded(7)(a), onp.float64) @ onp.asarray(
        w.astype(jnp.float32), onp.float64)
    assert onp.abs(wide - exact).max() < 3e-5
    assert onp.abs(once - exact).max() > 100 * onp.abs(wide - exact).max()
    # a float32 matrix takes the rows as they are
    w32 = w.astype(jnp.float32)
    assert onp.abs(onp.asarray(lfm2_mod._dot_wide(a, w32), onp.float64)
                   - exact).max() < 1e-5
    # and the twice-as-many rows are ONE product (the matrix is read once)
    jaxpr = str(jax.make_jaxpr(lfm2_mod._dot_wide)(a, w))
    assert jaxpr.count("dot_general") == 1 and "reduce_precision" in jaxpr


def test_rows_rounded_once_fail_the_bfloat16_tolerance(kit, monkeypatch):
    """The convolution operator's products fed with rows rounded once,
    as every other product is: over the limit the sound program keeps
    (the cubic gate carries its input's rounding three times)."""
    monkeypatch.setattr(
        lfm2_mod, "_dot_wide", lambda a, w: jnp.dot(
            _rounded(7)(a).astype(w.dtype), w,
            preferred_element_type=jnp.float32))
    worst = max(compare_one(kit, "mixed", "bfloat16", n, variant="once")[0]
                for n in (1, 5))
    assert worst > TOL["bfloat16"], worst


# -- the convolution and its tail, written out ---------------------------------

def test_the_three_convolutions_are_one_convolution():
    rng = onp.random.RandomState(3)
    g = rng.randn(13, 6).astype(onp.float32)
    w = rng.randn(3, 6).astype(onp.float32)
    want = onp.zeros_like(g)
    for t in range(13):
        for j in range(3):
            if t - 2 + j >= 0:
                want[t] += w[j] * g[t - 2 + j]
    dense = paged_kv.dense_conv(jnp.asarray(g), jnp.asarray(w))
    assert onp.abs(onp.asarray(dense) - want).max() < 1e-6
    # by chunks of 5 (padded to 8), 1 and 7 into slot 2 of 4, other
    # slots' tails untouched; then two rows decoded
    tail = jnp.asarray(rng.randn(4, 2, 6), jnp.float32)
    before = onp.asarray(tail)
    tail = tail.at[2].set(0)
    got, start = [], 0
    for n, bucket in ((5, 8), (1, 8), (5, 8)):
        x = onp.zeros((bucket, 6), onp.float32)
        x[:n] = g[start:start + n]
        y, tail = paged_kv.chunk_conv(tail, jnp.asarray(x), jnp.asarray(w),
                                      jnp.asarray([2]), jnp.asarray([n]))
        got.append(onp.asarray(y)[:n])
        start += n
    for t in (11, 12):
        x = jnp.asarray(rng.randn(4, 6), jnp.float32).at[2].set(g[t])
        y, tail = paged_kv.slot_conv(
            tail, x, jnp.asarray(w), jnp.asarray([False, False, True, False]))
        got.append(onp.asarray(y)[2:3])
    assert onp.abs(onp.concatenate(got) - want).max() < 1e-6
    assert onp.array_equal(onp.asarray(tail)[[0, 1, 3]], before[[0, 1, 3]])
    assert onp.allclose(onp.asarray(tail)[2], g[11:13])
    # a bias rides along (Falcon-H1's convolution has one)
    b = jnp.asarray(rng.randn(6), jnp.float32)
    assert onp.allclose(onp.asarray(paged_kv.dense_conv(
        jnp.asarray(g), jnp.asarray(w), b)), want + onp.asarray(b),
        atol=1e-6)


# -- grouped-query heads narrower than a lane tile --------------------------------

@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5),
                                       ("bfloat16", 2e-2)])
@pytest.mark.parametrize("h,kv_h,d", [(32, 8, 64), (8, 2, 64), (8, 4, 32),
                                      (12, 4, 64), (16, 8, 16)],
                         ids=["published", "one_tile", "four_a_tile",
                              "three_a_head", "eight_a_tile"])
@pytest.mark.parametrize("page_size,block_k", [(16, 8), (16, 32), (128, 64),
                                               (128, 128)])
def test_packed_grouped_query_heads_match_the_oracle(page_size, block_k, h,
                                                     kv_h, d, dtype, tol):
    """KV heads that share a lane tile are packed as one, their query
    heads the tile's rows with zeros in the neighbours' lanes: the
    published 32 over 8 of 64 (two a tile, eight rows), and shapes whose
    rows do not fill a sublane tile or overflow one."""
    from mxnet_tpu import kernels
    from mxnet_tpu.ops.paged_attention import (_pack_queries,
                                               _unpack_outputs,
                                               paged_attention)
    spec = kernels.get_kernel("paged_attention")
    arrays, _ = spec.make_args({"slots": 5,
                                "pages_per_slot": 4 if page_size == 16 else 2,
                                "page_size": page_size, "h": h, "kv_h": kv_h,
                                "d": d, "dtype": dtype})
    q, k_pool, v_pool, tables, lengths = arrays
    out = paged_attention(q, k_pool, v_pool, tables, lengths,
                          block_k=block_k)
    want = paged_oracle(q, k_pool, v_pool, tables, lengths)
    onp.testing.assert_allclose(onp.asarray(out, "float32"),
                                onp.asarray(want, "float32"),
                                rtol=tol, atol=tol)
    assert not onp.asarray(out, "float32")[0].any()      # the idle slot
    # packing places every query head once and unpacking finds it again
    packed = _pack_queries(q, kv_h, h // kv_h, d)
    assert packed.shape == (5, (128 // d) * (h // kv_h), kv_h * d)
    assert onp.array_equal(
        onp.asarray(_unpack_outputs(packed, kv_h, h // kv_h, d), "float32"),
        onp.asarray(q, "float32"))
    assert int((onp.asarray(packed, "float32") != 0).sum()) \
        == int((onp.asarray(q, "float32") != 0).sum())


def test_packed_heads_walk_four_pages_a_block(monkeypatch):
    """``paged_kv._kernel`` asks the kernel for ``_PACKED_BLOCK_ROWS``
    where the kernel packs the heads (its own predicate), one page of
    128 for every other shape, and leaves smaller pages to the
    registry."""
    from mxnet_tpu.ops.paged_attention import packs_heads
    asked = []
    monkeypatch.setattr(paged_kv, "paged_attention",
                        lambda q, k, v, t, l, block_k: asked.append(block_k))
    for hq, h, d, page in [(32, 8, 64, 128), (10, 2, 16, 128),
                           (20, 4, 128, 128), (16, 16, 64, 128),
                           (32, 8, 64, 16)]:
        paged_kv._kernel(jnp.zeros((2, hq, d)), jnp.zeros((4, page, h * d)),
                         jnp.zeros((4, page, h * d)), None, None)
    assert asked == [paged_kv._PACKED_BLOCK_ROWS, 128, 128, 128, None]
    assert paged_kv._PACKED_BLOCK_ROWS == 512
    assert [packs_heads(*s) for s in [(32, 8, 64), (10, 2, 16), (20, 4, 128),
                                      (16, 16, 64), (12, 4, 64)]] \
        == [True, False, False, False, True]


# -- the router, the stacked product --------------------------------------------

def test_a_bias_changes_the_selection_and_no_weight(kit):
    ref = kit.ref("mixed")
    rng = onp.random.RandomState(11)
    scores = jnp.asarray(1 / (1 + onp.exp(-rng.randn(60, 32))), jnp.float32)
    bias = jnp.asarray(0.3 * rng.randn(32), jnp.float32)
    index, weight = route_topk(scores, 4, bias=bias, eps=1e-6)
    plain, _ = route_topk(scores, 4, eps=1e-6)
    s, b = onp.asarray(scores), onp.asarray(bias)
    want = [sorted(range(32), key=lambda e: -(row[e] + b[e]))[:4]
            for row in s]
    assert [sorted(r) for r in onp.asarray(index).tolist()] \
        == [sorted(r) for r in want]
    # the bias moved some selections
    assert sum(set(a) != set(p) for a, p in zip(
        onp.asarray(index).tolist(), onp.asarray(plain).tolist())) > 10
    # and every weight is the UNBIASED score over the selected scores' sum
    picked = onp.take_along_axis(s, onp.asarray(index), 1)
    assert onp.allclose(onp.asarray(weight),
                        picked / (picked.sum(1, keepdims=True) + 1e-6),
                        rtol=1e-6)
    # the reference's matrix of weights says the same
    cfg = dict(num_experts_per_tok=4, norm_topk_prob=True,
               routed_scaling_factor=1)
    dense = onp.zeros((60, 32), onp.float32)
    onp.put_along_axis(dense, onp.asarray(index), onp.asarray(weight), 1)
    assert onp.allclose(onp.asarray(ref.route(scores, bias, cfg)), dense,
                        rtol=1e-6)
    # no bias: the function it was
    again, w2 = route_topk(scores, 4)
    assert onp.array_equal(onp.asarray(again), onp.asarray(plain))
    assert onp.allclose(onp.asarray(w2).sum(1), 1.0, rtol=1e-6)


def test_a_bias_moves_the_groups_too():
    """Group-limited selection ranks its groups by the biased scores."""
    scores = jnp.asarray([[0.9, 0.8, 0.1, 0.1, 0.5, 0.5, 0.4, 0.4]],
                         jnp.float32)
    bias = jnp.asarray([0, 0, 0, 0, 0.5, 0.5, 0, 0], jnp.float32)
    index, weight = route_topk(scores, 2, n_group=4, topk_group=1, bias=bias)
    assert sorted(onp.asarray(index)[0].tolist()) == [4, 5]
    assert onp.allclose(onp.asarray(weight)[0], [0.5, 0.5])
    index, _ = route_topk(scores, 2, n_group=4, topk_group=1)
    assert sorted(onp.asarray(index)[0].tolist()) == [0, 1]


def test_the_stacked_product_is_the_loop_and_counts_alike():
    rng = onp.random.RandomState(5)
    h = jnp.asarray(rng.randn(7, 16), jnp.float32)
    w1, w3 = (jnp.asarray(rng.randn(6, 16, 8) * 0.3, jnp.float32)
              for _ in range(2))
    w2 = jnp.asarray(rng.randn(6, 8, 16) * 0.3, jnp.float32)
    index = jnp.asarray([[4, 1], [5, 4], [0, 1], [2, 3], [4, 5], [5, 0],
                         [4, 2]])
    weight = jnp.asarray(rng.rand(7, 2), jnp.float32)
    valid = jnp.asarray([True, True, True, True, False, True, True])
    y, c = stacked_experts(h, index, weight, w1, w3, w2, valid)
    want, cw = held_experts(h, index, weight,
                            [(w1[e], w3[e], w2[e]) for e in range(6)], 0,
                            valid)
    assert onp.abs(onp.asarray(y) - onp.asarray(want)).max() < 1e-5
    assert {k: float(v) for k, v in c.items()} \
        == {k: float(v) for k, v in cw.items()}
    # experts 0..5 got 2, 2, 2, 1, 3, 2 of the valid rows
    assert (float(c["rows_max"]), float(c["rows_mean"]),
            float(c["idle"]), float(c["pairs"])) == (3.0, 2.0, 0.0, 12.0)


# -- the layout ---------------------------------------------------------------------

def test_a_mixed_model_is_given_pages_for_its_attention_layers_alone(kit):
    model, cfg = kit.model("mixed")
    kinds = cfg["layer_types"]
    assert kinds.count("full_attention") == 2 and len(kinds) == 6
    eng = engine(model)
    lanes = cfg["num_key_value_heads"] * (cfg["hidden_size"]
                                          // cfg["num_attention_heads"])
    for layer, kind in zip(eng.cache.pool, kinds):
        if kind == "conv":
            assert [buf.shape for buf in layer] == [(3, 2, 64)]
            assert layer[0].dtype == jnp.float32
        else:
            assert [buf.shape for buf in layer] == [(24, 8, lanes)] * 2
    st = eng.stats()
    # K and V of two layers of six, and the tails of four
    assert st["page_bytes"] == 2 * 2 * 24 * 8 * lanes * 4
    assert st["state_bytes"] == 4 * 3 * 2 * 64 * 4
    assert (st["page_layers"], st["state_layers"]) == (2, 4)
    assert eng.cache.paged == (0, 0, 2, 0, 0, 2)
    paged, state = eng.cache.split()
    assert [len(p) for p in paged] == [0, 0, 2, 0, 0, 2]
    assert [len(s) for s in state] == [1, 1, 0, 1, 1, 0]
    # a layout that gave every layer the same pages would hold three
    # times the bytes for the same positions
    alike = PagedKVCache(
        layout=paged_kv.uniform_layout(6, (lanes, lanes)), num_pages=24,
        page_size=8, max_slots=3, pages_per_slot=8)
    assert alike.page_bytes == 3 * st["page_bytes"]
    # one table a slot serves both attention layers
    eng.acquire_slot(1, 20)
    assert eng.cache.pages_used() == 3 and st["num_pages"] == 24


def _falcon():
    return FalconH1(config("falcon_h1_34b"), seed=1, dtype="float32")


def _axk1():
    return AXK1(config("axk1_519b"), seed=1, dtype="float32")


@pytest.mark.parametrize("build,per_layer,page_bytes,state_bytes", [
    # K and V of 4 heads of 8 lanes in 2 layers
    (lambda: DecodeModel(48, dim=32, n_heads=4, n_layers=2),
     [(24, 8, 32)] * 2, 2 * 2 * 24 * 8 * 32 * 4, 0),
    # K and V of two K/V heads of 16 lanes, then the state-space state
    # and the convolution's tail, in both layers
    (_falcon, [(24, 8, 32)] * 2 + [(3, 4, 16, 16), (3, 3, 128)],
     2 * 2 * 24 * 8 * 32 * 4, 2 * 3 * (4 * 16 * 16 + 3 * 128) * 4),
    # one latent page a layer, 32 + 8 lanes stored as 128, in 3 layers
    (_axk1, [(24, 8, 128)], 3 * 24 * 8 * 128 * 4, 0)],
    ids=["decode_model", "falcon_h1", "axk1"])
def test_the_three_models_there_were_get_the_pools_they_got(
        build, per_layer, page_bytes, state_bytes):
    model = build()
    eng = engine(model)
    assert len(eng.cache.pool) == model.n_layers
    for layer in eng.cache.pool:
        assert [buf.shape for buf in layer] == per_layer
    st = eng.stats()
    assert (st["page_bytes"], st["state_bytes"]) == (page_bytes, state_bytes)
    assert st["page_layers"] == model.n_layers
    assert st["state_layers"] == (model.n_layers if state_bytes else 0)
    assert model.cache_layout == paged_kv.uniform_layout(
        model.n_layers, model.page_widths, model.state_spec)


def test_a_model_with_state_in_some_layers_cannot_be_a_speculations_target(
        kit):
    model, _ = kit.model("mixed")
    with pytest.raises(ValueError, match="conv"):
        DecodeEngine(model, spec_k=2)
    with pytest.raises(NotImplementedError):
        model.verify_core(model.params, (), None, None, None, None)


# -- the configuration ------------------------------------------------------------

def test_param_count_by_hand():
    """The published widths as shapes only: the benchmark's count of the
    configuration, by layer, of the whole published stack, and the
    shapes of a layer of each kind (the total against the abstract
    model: ``test_model_contract``)."""
    cfg = published("lfm2_8b_a1b")
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types"]
    assert cfg["layer_types"] == cfg["layer_types_published"][:16]
    assert (cfg["num_experts"], cfg["num_experts_per_tok"],
            cfg["vocab_size"]) == (32, 4, 65536)
    model = LFM2(cfg, abstract=True)
    fam = load("chipbench/models/lfm2.py", "lfm2_family")
    assert fam.param_count(cfg) == cfg["parameters"] == 5_399_129_024
    assert fam.layer_param_count(cfg, 0) == 60_827_648
    assert fam.layer_param_count(cfg, 3) == 369_174_560
    assert fam.layer_param_count(cfg, 2) == 362_877_088
    whole = dict(cfg, num_hidden_layers=24,
                 layer_types=cfg["layer_types_published"])
    assert fam.param_count(whole) == 8_339_930_560
    lp = model.params["layers"][2]
    assert lp["experts_w1"].shape == (32, 2048, 1792) and "wo" in lp
    assert lp["expert_bias"].dtype == jnp.float32
    conv = model.params["layers"][3]
    assert "wo" not in conv and conv["conv_w"].shape == (3, 2048)
    assert conv["conv_w"].dtype == jnp.float32
    assert model.params["head"].shape == (2048, 65536)
    # K and V in four layers of sixteen, 2,048 bytes a token and layer
    assert [len(w) for w, _ in model.cache_layout].count(2) == 4
    assert fam.kv_row_bytes(cfg) == 2048
    assert fam.paged_attention_bytes(cfg, 1000) == 2_048_000
