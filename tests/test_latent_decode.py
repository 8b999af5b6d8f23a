"""The decode plane with a third kind of model: A.X-K1's block (latent
attention over a paged latent cache, a share of sigmoid-routed experts
beside a shared one) behind the model protocol: the family's own
controls (what every model passes: ``test_decode_contract``,
``test_model_contract``, ``test_decode_in_place``).

All at the benchmark configuration's ``rehearsal`` size (same ratios as
the published model: four query heads over one latent row, 48 experts
in sixteen shares of 3, top-2, a dense layer before two expert layers),
seeded random weights, on the CPU with the kernel interpreted:

- prefill in chunks then decode through the latent cache against the
  plain reference's full pass (``chipbench/reference/axk1_ref.py``), on
  LOGITS, with the routing ties handled as ``decode_families.compare``
  says; every branch at full strength; the group-limited router; an
  expert product in lower precision fails it;
- the absorbed form of the attention against the plain one;
- ``latent_attention`` (interpreted kernel, and its fallback) against a
  softmax written out here;
- the router in both forms against a sort; YaRN's frequencies;
- the share test: sixteen shares' partial results, the shared expert
  counted once, add up to the uncut reference's layer;
- the steps under a profiler capture are counted apart; the parameter
  count at the published widths by hand.
"""
import functools

import numpy as onp
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx  # noqa: F401  (registers ops + kernel specs)
from mxnet_tpu import kernels
from mxnet_tpu.ops import paged_attention
from mxnet_tpu.ops.paged_attention import latent_attention
from mxnet_tpu.ops.rope import rope_reference, rope_table, yarn_frequencies
from mxnet_tpu.parallel.moe import held_experts, route_topk
from mxnet_tpu.serving import (AXK1, DecodeEngine, DecodeModel,
                               DecodeScheduler, PagedKVCache)
from mxnet_tpu.serving.decode import paged_kv

from decode_families import (CHUNK, LATENT, Kit, compare_one, config,
                             engine, load, published, scaled, tokens)
from decode_families import clean  # noqa: F401  (a fixture)

TOL, MARGIN = LATENT.tol, LATENT.margin


@pytest.fixture(scope="module")
def kit():
    return Kit()


# The same with the held routed experts at full strength too
# (``routed_down_divisor`` 1, ISSUE 33's plain fan-in scale on every
# matrix): what the chip's token check cannot hold (a differing
# selection there moves a token's logits past its limit), this
# comparison can, because it takes the program's selection.  bfloat16:
# sound 8.0e-3 to 1.15e-2 with a selection differing in two of the
# three cases, up to 2.8e-4 wide; the HELD experts' matrices alone in
# float8 read 2.05e-2 to 3.4e-2: 1.55e-2.
PLAIN = {"routed_down_divisor": 1}
TOL_PLAIN = {"float32": 5e-6, "bfloat16": 1.55e-2}
MARGIN_PLAIN = {"float32": 1e-5, "bfloat16": 2.5e-3}


@pytest.mark.parametrize("prompt_len,n_decode", [
    (5, 4), (CHUNK, 4), (37, 4), (250, 1)],
    ids=["short", "one_chunk", "three_chunks", "sixteen_chunks"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_prefill_then_cached_decode_matches_the_reference_on_logits(
        kit, dtype, prompt_len, n_decode):
    worst, _, widest = compare_one(
        kit, "latent", dtype, prompt_len, n_decode,
        pages_per_slot=8 if prompt_len < 60 else 32)
    assert worst <= TOL[dtype], worst
    assert widest <= MARGIN[dtype], widest


@pytest.mark.parametrize("prompt_len", [5, CHUNK, 37],
                         ids=["short", "one_chunk", "three_chunks"])
def test_the_layers_at_the_plain_scale_match_the_reference(kit, prompt_len):
    """Every branch at full strength, in bfloat16: selections do differ
    here, and the comparison follows the program's."""
    worst, _, widest = compare_one(kit, "latent", "bfloat16", prompt_len,
                                   **PLAIN)
    assert worst <= TOL_PLAIN["bfloat16"], worst
    assert widest <= MARGIN_PLAIN["bfloat16"], widest


def test_the_group_limited_router_is_held_to_the_reference_too(kit):
    worst, _, widest = compare_one(kit, "latent", "float32", 21,
                                   topk_method="noaux_tc")
    assert worst <= TOL["float32"] and widest <= MARGIN["float32"]


# -- the controls: the nearest lower precision fails a limit -------------------

def _float8(names):
    """``coarse(params)``: the layers' matrices that ``names`` picks
    rounded to float8 as the benchmark's control rounds them."""
    drv = load("chipbench/drivers/decode_open_loop_model.py",
               "drv_for_round8")

    def coarse(params):
        return dict(params, layers=[
            {k: (drv._round8(v.astype(jnp.bfloat16)).astype(v.dtype)
                 if names(k) else v) for k, v in lp.items()}
            for lp in params["layers"]])

    return coarse


def _held(k):
    return k[0] == "e" and k[1].isdigit()


@pytest.mark.parametrize("prompt_len", [5, CHUNK, 37],
                         ids=["short", "one_chunk", "three_chunks"])
def test_experts_in_float8_fail_the_bfloat16_tolerance(kit, prompt_len):
    """The experts' matrices (held and shared) rounded to float8, the
    benchmark's control, in the PROGRAM's place at the stated precision:
    over the limit the sound program keeps, in every case it keeps it."""
    worst, _, _ = compare_one(
        kit, "latent", "bfloat16", prompt_len,
        coarse=_float8(lambda k: _held(k) or k.startswith("ws_")))
    assert worst > 1.25 * TOL["bfloat16"], worst


@pytest.mark.parametrize("prompt_len", [5, CHUNK, 37],
                         ids=["short", "one_chunk", "three_chunks"])
def test_held_experts_in_float8_fail_the_plain_scale_tolerance(
        kit, prompt_len):
    """The HELD experts alone in float8, every branch at full strength:
    over the plain scale's limit."""
    worst, _, widest = compare_one(kit, "latent", "bfloat16", prompt_len,
                                   coarse=_float8(_held), **PLAIN)
    assert worst > 1.25 * TOL_PLAIN["bfloat16"], worst
    assert widest <= MARGIN_PLAIN["bfloat16"]       # the router is sound


def test_experts_in_lower_precision_fail_the_tolerance(kit):
    """The held experts' matrices rounded to float8: over the float32
    limit, by the dense pass over 64 positions (the same router and the
    same scores on both sides, so the same selection; the limit is the
    cached path's)."""
    model, _ = kit.model("latent")
    toks = jnp.asarray(tokens(64, seed=3), jnp.int32)
    got = onp.asarray(model.dense_logits(_float8(_held)(model.params), toks))
    want = onp.asarray(model.dense_logits(model.params, toks))
    assert scaled(got, want) > 5 * TOL["float32"]


# -- the absorbed form, the kernel, the rotation --------------------------------

def test_the_absorbed_form_is_the_plain_one(kit):
    """One layer's attention over one sequence: the decode path's
    absorbed scores and latent-space output through a paged buffer
    against the oracle's up-projected keys and values."""
    model, _ = kit.model("latent")
    t_, h = 19, model.n_heads
    rng = onp.random.RandomState(7)

    def f(*shape):
        return jnp.asarray(rng.randn(*shape) * 0.5, jnp.float32)

    q_nope, q_rope = f(t_, h, model.nope), f(t_, h, model.rope_dim)
    c_kv, k_rope = f(t_, model.rank), f(t_, model.rope_dim)
    w_kvb = f(model.rank, h, model.nope + model.v_dim)
    kw = {"inv_freq": model.inv_freq, "sm_scale": model.sm_scale}
    want, _ = paged_kv.latent_dense_attention(t_, **kw)(
        q_nope, q_rope, c_kv, k_rope, w_kvb)
    pool = ((jnp.zeros((6, 8, model.width), jnp.float32),),)
    table = jnp.asarray([4, 1, 3, 0], jnp.int32)
    got, (buf,) = paged_kv.latent_chunk_attention(
        pool, jnp.asarray([0]), jnp.asarray([t_]), table[None], 32, **kw)(
        *(jnp.pad(a, ((0, 32 - t_),) + ((0, 0),) * (a.ndim - 1))
          for a in (q_nope, q_rope, c_kv, k_rope)), w_kvb, pool[0][0])
    assert float(jnp.abs(got[:t_] - want).max()) < 2e-5
    # and the decode step of the last position, over the rows just written
    slot = paged_kv.latent_slot_attention(
        ((buf,),), jnp.asarray([t_ - 1]), table[None], jnp.asarray([True]),
        **kw)
    last, _ = slot(q_nope[-1:], q_rope[-1:], c_kv[-1:], k_rope[-1:], w_kvb,
                   buf)
    assert float(jnp.abs(last[0] - want[-1]).max()) < 2e-5


# Lengths of six slots in rows of a block ``r``, for the staged walk
# (ops/paged_attention.py, _pa_walker): its stages cross slots, so what
# a slot's neighbours hold is what is tested.
_WALKS = {
    "blocks_1_2_3_4": lambda r: [r // 2 + 1, r + 3, 2 * r + 5, 3 * r + 1,
                                 0, 0],
    "exact_multiples": lambda r: [r, 2 * r, 3 * r, 0, r, 0],
    "zeros_between": lambda r: [0, r + 1, 0, 0, 2 * r, 0],
    "one_live": lambda r: [0, 0, 0, 2 * r + 3, 0, 0],
    "all_live": lambda r: [1, r - 1, r + 1, 2 * r, 3 * r + 2, 7],
    # slots 1-4 each one block: its only block is scored in the previous
    # slot's last stage and scores the next slot's first in its own
    "one_block_hand_overs": lambda r: [2 * r + 1, 5, 3, r, 9, r + 2],
}


def _latent_case(spec, case, block_k, dtype):
    """The registry's tuning case ``case``, or six slots of up to 2,560
    rows (pages of 128, the cell's) with the lengths ``_WALKS[case]``
    gives at this block."""
    if not isinstance(case, str):
        return spec.make_args(dict(spec.tune_grid[case], dtype=dtype))
    pages, page_size = 20, 128
    (q, pool, tables, _), kw = spec.make_args(dict(
        slots=6, pages_per_slot=pages, page_size=page_size, h=16, rank=128,
        rope=64, dtype=dtype))
    rows = paged_attention._block_rows(block_k, page_size, pages)
    return (q, pool, tables, jnp.asarray(_WALKS[case](rows), jnp.int32)), kw


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-6), ("bfloat16", 2e-2)])
@pytest.mark.parametrize("case", [0, 1, 2, *_WALKS])
@pytest.mark.parametrize("block_k", [8, 128, 512])
def test_latent_attention_matches_a_written_out_softmax(case, block_k,
                                                        dtype, tol):
    spec = kernels.get_kernel("latent_attention")
    (q, pool, tables, lengths), kw = _latent_case(spec, case, block_k, dtype)
    got = latent_attention(q, pool, tables, lengths, block_k=block_k, **kw)
    # the fallback as a program runs it: under ``jit``
    fall = jax.jit(functools.partial(spec.fallback, **kw))(q, pool, tables,
                                                            lengths)
    rank = kw["rank"]
    for s in range(q.shape[0]):
        n = int(lengths[s])
        if n == 0:
            assert not onp.asarray(got[s], onp.float32).any()
            continue
        rows = onp.asarray(pool, onp.float32)[onp.asarray(tables[s])]
        rows = rows.reshape(-1, rows.shape[-1])[:n]
        sc = onp.asarray(q[s], onp.float32) @ rows.T * kw["sm_scale"]
        p = onp.exp(sc - sc.max(axis=1, keepdims=True))
        want = (p / p.sum(axis=1, keepdims=True)) @ rows[:, :rank]
        for out in (got, fall):
            assert onp.abs(onp.asarray(out[s], onp.float32)
                           - want).max() <= tol


@pytest.mark.parametrize("lengths,want", [
    ([0, 0, 5, 0], 0.0),                # one slot of one block, no successor
    ([0, 0, 0, 0], 0.0),                # no slot live
    ([8, 0, 5, 0], 0.5),                # one block each: the second overlaps
    ([128, 128, 128, 128], 63 / 64),    # 16 blocks a slot
])
def test_latent_overlap_share_counts_the_staged_blocks(lengths, want):
    """Every block but the call's first has its scores issued beside
    another block's softmax: (blocks - 1) / blocks, blocks of 8 rows."""
    spec = kernels.get_kernel("latent_attention")
    (q, pool, tables, _), kw = spec.make_args(spec.tune_grid[1])
    lengths = jnp.asarray(lengths, jnp.int32)
    got = paged_attention.latent_overlap_share(q, pool, tables, lengths,
                                               block_k=8, **kw)
    assert got.dtype == jnp.float32 and float(got) == pytest.approx(want)


@pytest.mark.parametrize("live", [{47: 37}, {0: 64}, {95: 17},
                                  {3: 50, 91: 33}, {}],
                         ids=["one_middle", "one_first", "one_last",
                              "two_apart", "all_idle"])
def test_latent_attention_walks_a_lightly_loaded_server(live):
    """96 slots, none to two of them live (the walk visits those
    alone): the live slots' outputs are the oracle's, every idle
    slot's is exact zeros."""
    spec = kernels.get_kernel("latent_attention")
    (q, pool, tables, _), kw = spec.make_args(dict(
        slots=96, pages_per_slot=4, page_size=16, h=4, rank=128, rope=64))
    lengths = jnp.asarray([live.get(s, 0) for s in range(96)], jnp.int32)
    got = onp.asarray(latent_attention(q, pool, tables, lengths,
                                       block_k=32, **kw))
    want = onp.asarray(jax.jit(functools.partial(spec.fallback, **kw))(
        q, pool, tables, lengths))
    onp.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    idle = onp.asarray(lengths) == 0
    assert not got[idle].any()
    assert got[~idle].any(axis=(1, 2)).all()


def test_yarn_frequencies_and_the_table_rotation(kit):
    ref = kit.ref("latent")
    sc = config("axk1_519b")["rope_scaling"]
    inv = yarn_frequencies(64, 10000, **sc)
    plain = 10000.0 ** (-onp.arange(32) * 2 / 64)
    # pairs 0-10 turn more than 32 times in 4096 positions, 23-31 fewer
    # than once; between them the ramp
    assert onp.allclose(inv[:11], plain[:11], rtol=1e-6)
    assert onp.allclose(inv[23:], plain[23:] / 32, rtol=1e-6)
    assert all(plain[i] / 32 < inv[i] < plain[i] for i in range(11, 23))
    assert onp.allclose(inv, onp.asarray(ref._yarn_inv_freq(64, 10000, sc)),
                        rtol=1e-6)
    x = jnp.asarray(onp.random.RandomState(0).randn(5, 3, 64), jnp.float32)
    pos = jnp.asarray([0, 1, 17, 900, 4000])
    # float32 angles at position 4000 agree to 1.5e-4
    assert float(jnp.abs(rope_table(x, pos, plain)
                         - rope_reference(x, pos, 10000.0)).max()) < 1e-3
    assert float(jnp.abs(rope_table(x, pos, inv)
                         - ref._rotate(x, pos, jnp.asarray(inv), 1.0)).max()) \
        < 1e-5


# -- the router, the share --------------------------------------------------------

def _sorted_route(scores, k, n_group, topk_group):
    """Top-k by a stable sort, a row at a time."""
    out = []
    for row in onp.asarray(scores):
        allowed = onp.ones(row.shape, bool)
        if n_group > 1:
            per = row.size // n_group
            rank = [sum(sorted(row[g * per:(g + 1) * per])[-2:])
                    for g in range(n_group)]
            keep = sorted(range(n_group), key=lambda g: -rank[g])[:topk_group]
            allowed = onp.repeat([g in keep for g in range(n_group)], per)
        order = sorted((e for e in range(row.size) if allowed[e]),
                       key=lambda e: -row[e])[:k]
        out.append(order)
    return out


@pytest.mark.parametrize("n_group,topk_group", [(1, 1), (8, 4), (4, 1)])
def test_route_topk_is_a_sort(kit, n_group, topk_group):
    ref = kit.ref("latent")
    rng = onp.random.RandomState(11)
    scores = jnp.asarray(1 / (1 + onp.exp(-rng.randn(40, 48))), jnp.float32)
    index, weight = route_topk(scores, 6, n_group=n_group,
                               topk_group=topk_group, scale=2.5)
    want = _sorted_route(scores, 6, n_group, topk_group)
    assert [sorted(r) for r in onp.asarray(index).tolist()] \
        == [sorted(r) for r in want]
    picked = onp.take_along_axis(onp.asarray(scores), onp.asarray(index), 1)
    assert onp.allclose(onp.asarray(weight),
                        picked / picked.sum(1, keepdims=True) * 2.5,
                        rtol=1e-6)
    assert onp.allclose(onp.asarray(weight).sum(1), 2.5, rtol=1e-6)
    # the reference's matrix of weights says the same
    cfg = dict(num_experts_per_tok=6, n_group=n_group, topk_group=topk_group,
               topk_method="none" if n_group == 1 else "noaux_tc",
               norm_topk_prob=True, routed_scaling_factor=2.5)
    dense = onp.zeros((40, 48), onp.float32)
    onp.put_along_axis(dense, onp.asarray(index), onp.asarray(weight), 1)
    assert onp.allclose(onp.asarray(ref.route(scores, cfg)), dense,
                        rtol=1e-6)


def test_sixteen_shares_add_up_to_the_uncut_layer(kit):
    """The share test: one expert layer's weights with all 48 routed
    experts; the program as each of the sixteen chips (3 experts each)
    computes its partial result; those, with what every chip computes
    alike (the attention and the shared expert) counted once, are the
    uncut reference's layer."""
    ref = kit.ref("latent")
    cfg = config("axk1_519b", n_routed_experts=48, experts_first=0)
    whole = AXK1(dict(cfg, num_hidden_layers=2), seed=9, dtype="float32")
    lp = whole.params["layers"][1]
    common = {k: v for k, v in lp.items() if not k.startswith("e")}
    x = jnp.asarray(onp.random.RandomState(3).randn(23, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = ref.layer(lp, x, cfg)
        alike = ref.layer(common, x, cfg)    # attention + shared expert
    total = alike
    attend = paged_kv.latent_dense_attention(
        23, inv_freq=whole.inv_freq, sm_scale=whole.sm_scale)
    pairs = 0.0
    for share in range(16):
        part = AXK1(dict(cfg, num_hidden_layers=2, n_routed_experts=3,
                         experts_first=3 * share), dtype="float32",
                    abstract=True)
        held = dict(common)
        for j in range(3):
            for m in ("gate", "up", "down"):
                held[f"e{j}_{m}"] = lp[f"e{3 * share + j}_{m}"]
        out, _, counters = part._block(held, x, (), attend)
        total = total + (out - alike)
        pairs += float(counters["local_pairs"])
    assert float(jnp.abs(total - want).max()) \
        <= 1e-5 * float(jnp.abs(want).max())
    # every routed pair met exactly one share
    assert pairs == 23 * cfg["num_experts_per_tok"]


def test_held_experts_counts_what_it_was_given():
    rng = onp.random.RandomState(5)
    h = jnp.asarray(rng.randn(6, 16), jnp.float32)
    experts = [tuple(jnp.asarray(rng.randn(*s) * 0.3, jnp.float32)
                     for s in ((16, 8), (16, 8), (8, 16))) for _ in range(3)]
    index = jnp.asarray([[4, 9], [5, 4], [0, 1], [6, 20], [4, 5], [5, 6]])
    weight = jnp.asarray(rng.rand(6, 2), jnp.float32)
    valid = jnp.asarray([True, True, True, True, False, True])
    y, c = held_experts(h, index, weight, experts, 4, valid)
    # experts 4, 5, 6 are held: rows 0,1 | 1,5 | 3,5 of the valid ones
    assert (float(c["local_pairs"]), float(c["pairs"])) == (6.0, 10.0)
    assert (float(c["rows_mean"]), float(c["rows_max"]),
            float(c["idle"])) == (2.0, 2.0, 0.0)
    want = onp.zeros((6, 16), onp.float32)
    for r in range(6):
        for e, w in zip(onp.asarray(index[r]), onp.asarray(weight[r])):
            if 4 <= e < 7:
                g, u, d = (onp.asarray(m) for m in experts[e - 4])
                a = onp.asarray(h[r]) @ g
                want[r] += w * ((a / (1 + onp.exp(-a)))
                                * (onp.asarray(h[r]) @ u)) @ d
    assert onp.abs(onp.asarray(y) - want).max() < 1e-5
    assert not onp.asarray(y[2]).any()      # routed elsewhere: exactly 0


# -- the cache's kinds, the engine's counters ------------------------------------

def test_a_latent_page_is_one_buffer_a_layer_and_counts_its_bytes(kit):
    model, _ = kit.model("latent")
    assert model.page_widths == (128,) == (paged_kv.latent_width(32, 8),)
    assert paged_kv.latent_width(512, 64) == 640
    eng = engine(model)
    assert [len(layer) for layer in eng.cache.pool] == [1, 1, 1]
    assert eng.cache.pool[0][0].shape == (24, 8, 128)
    assert eng.stats()["page_bytes"] == 3 * 24 * 8 * 128 * 4
    assert eng.stats()["state_bytes"] == 0
    kv = PagedKVCache(layout=paged_kv.uniform_layout(2, (16, 16)),
                      num_pages=6, page_size=4, max_slots=2)
    assert kv.paged == (2, 2) and kv.page_bytes == 2 * 2 * 6 * 4 * 64
    plain = DecodeEngine(DecodeModel(48, dim=32, n_heads=4, n_layers=2),
                         max_slots=2, num_pages=8, page_size=8)
    assert plain.stats()["counters"] == {} and plain.counters == {}


def test_the_steps_under_a_capture_are_counted_apart(kit, monkeypatch,
                                                     clean):
    """``stats()["traced"]``: the live tokens and the model's counters
    of the decode steps dispatched while a profiler capture ran, and of
    no other: what a trace's kernel times are divided by."""
    from mxnet_tpu import tracing
    model, _ = kit.model("latent")
    eng = engine(model, max_slots=2)
    sch = DecodeScheduler(eng, start=False)
    fut = sch.submit(tokens(11, seed=11), max_new_tokens=9)
    on = {"now": False}
    monkeypatch.setattr(tracing, "capturing", lambda: on["now"])
    turns = 0
    while sch._has_work():
        # the capture covers the third and fourth decode dispatch
        on["now"] = eng._decode_steps in (2, 3)
        sch.step()
        turns += 1
    assert fut.result(0) == model.greedy_reference(tokens(11, seed=11), 9)
    st = eng.stats()
    assert eng._decode_steps == 8 and st["traced"]["decode_steps"] == 2
    # position 11 decodes first (context 12): the third and fourth steps
    # read 14 and 15 rows; the life's mean runs over all eight
    assert st["traced"]["live_tokens_mean"] == pytest.approx(14.5)
    assert st["live_tokens_mean"] == pytest.approx(sum(range(12, 20)) / 8)
    assert set(st["traced"]["counters"]) == set(st["counters"])
    assert not eng._in_flight


def test_a_latent_model_has_no_verify_core_yet(kit):
    """It cannot be a speculation's target until it has one, and says so
    when asked (ROADMAP R2)."""
    model, _ = kit.model("latent")
    with pytest.raises(NotImplementedError):
        model.verify_core(model.params, (), None, None, None, None)


def test_param_count_by_hand():
    """The published widths as shapes only: the benchmark's count of the
    configuration, by layer, and the shapes the float8 control reaches
    (the total against the abstract model: ``test_model_contract``)."""
    cfg = published("axk1_519b")
    model = AXK1(cfg, abstract=True)
    fam = load("chipbench/models/axk1.py", "axk1_family")
    assert fam.param_count(cfg) == cfg["parameters"] == 4_841_331_712
    assert fam.layer_param_count(cfg, True) == 497_500_160
    assert fam.layer_param_count(cfg, False) == 675_037_184
    # every held expert is a set of 2-D matrices the float8 control reaches
    lp = model.params["layers"][1]
    assert all(lp[f"e{j}_{m}"].ndim == 2 for j in range(12)
               for m in ("gate", "up", "down"))
    assert lp["w_router"].shape == (7168, 192) and "wo" in lp
    assert model.page_widths == (640,)
    assert model.sm_scale == pytest.approx(192 ** -0.5 * 1.3466 ** 2,
                                           rel=1e-4)
