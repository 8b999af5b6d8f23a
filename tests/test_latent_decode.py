"""The decode plane with a third kind of model: A.X-K1's block (latent
attention over a paged latent cache, a share of sigmoid-routed experts
beside a shared one) behind the model protocol.

All at the benchmark configuration's ``rehearsal`` size (same ratios as
the published model: four query heads over one latent row, 48 experts
in sixteen shares of 3, top-2, a dense layer before two expert layers),
seeded random weights, on the CPU with the kernel interpreted:

- prefill in chunks then decode through the latent cache against the
  plain reference's full pass (``chipbench/reference/axk1_ref.py``), on
  LOGITS, with the routing ties handled as the comparison's docstring
  says; a router or an expert product in lower precision fails it;
- the absorbed form of the attention against the plain one;
- ``latent_attention`` (interpreted kernel, and its fallback) against a
  softmax written out here;
- the router in both forms against a sort; YaRN's frequencies;
- the share test: sixteen shares' partial results, the shared expert
  counted once, add up to the uncut reference's layer;
- a core's counters ride with its tokens: one read a turn, the step
  record and ``stats()`` carry them; chained and synchronous turns give
  the same tokens; ``POST /generate`` answers.
"""
import importlib.util
import json
import pathlib

import numpy as onp
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx  # noqa: F401  (registers ops + kernel specs)
from mxnet_tpu import kernels, telemetry
from mxnet_tpu.ops import paged_attention
from mxnet_tpu.ops.paged_attention import latent_attention
from mxnet_tpu.ops.rope import rope_reference, rope_table, yarn_frequencies
from mxnet_tpu.parallel.moe import held_experts, route_topk
from mxnet_tpu.serving import (AXK1, DecodeEngine, DecodeModel,
                               DecodeScheduler, PagedKVCache, ServingServer)
from mxnet_tpu.serving.decode import axk1 as axk1_mod
from mxnet_tpu.serving.decode import paged_kv

REPO = pathlib.Path(__file__).resolve().parent.parent
CHUNK = 16


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _load(REPO / "chipbench" / "reference" / "axk1_ref.py",
                 "axk1_ref")


def _config(**over):
    with open(REPO / "chipbench" / "configs" / "axk1_519b.json") as f:
        cfg = json.load(f)
    cfg.update(cfg.pop("rehearsal"))
    cfg.update(over)
    return cfg


@pytest.fixture(scope="module")
def models():
    made = {}

    def get(dtype="float32", **over):
        key = (dtype,) + tuple(sorted(over.items()))
        if key not in made:
            cfg = _config(**over)
            made[key] = (AXK1(cfg, seed=5, dtype=dtype), cfg)
        return made[key]

    return get


def _engine(model, **kw):
    kw.setdefault("max_slots", 3)
    kw.setdefault("page_size", 8)
    kw.setdefault("pages_per_slot", 8)
    kw.setdefault("num_pages", 24)
    kw.setdefault("prefill_chunk", CHUNK)
    kw.setdefault("prefill_floor", 8)
    return DecodeEngine(model, **kw)


def _tokens(n, seed, vocab=128):
    return [int(t) for t in
            onp.random.RandomState(seed).randint(0, vocab, size=n)]


def _run(sch):
    while sch._has_work():
        sch.step()


@pytest.fixture
def _clean():
    telemetry.clear_sinks()
    yield
    telemetry.clear_sinks()
    telemetry.enabled()


# -- prefill, then decode through the latent cache, against the reference -----

def _with_picks(core):
    """``core`` returning, beside its own outputs, the experts its
    router selected in every expert layer ``(rows, top-k)`` each: the
    router is watched while the core is TRACED, so the jitted whole
    hands the selections back as outputs."""
    def run(*args):
        picks = []
        real = axk1_mod.route_topk

        def spy(scores, top_k, **kw):
            index, weight = real(scores, top_k, **kw)
            picks.append(index)
            return index, weight

        axk1_mod.route_topk = spy
        try:
            out = core(*args)
        finally:
            axk1_mod.route_topk = real
        return out, tuple(picks)

    return jax.jit(run)


_WATCHED = {}       # (model, its router's scores) -> the two watched cores


class _Through:
    """Drives an engine's cache by hand, keeping the logits the
    executables reduce to a token and the experts the program's router
    selected for every position and expert layer."""

    def __init__(self, model, eng):
        self.model, self.eng = model, eng
        self.picked = {}            # position -> [experts of each layer]
        key = (id(model), AXK1._scores)
        if key not in _WATCHED:
            _WATCHED[key] = (_with_picks(model.prefill_logits),
                             _with_picks(model.decode_logits), model)
        self._prefill, self._decode, _ = _WATCHED[key]

    def _keep(self, picks, rows):
        for row, pos in rows:
            self.picked.setdefault(pos, []).extend(
                set(onp.asarray(index[row]).tolist()) for index in picks)

    def feed_prompt(self, slot, prompt):
        eng, logits = self.eng, None
        for start in range(0, len(prompt), CHUNK):
            chunk = prompt[start:start + CHUNK]
            padded = onp.zeros((eng.prefill_bucket(len(chunk)),), onp.int32)
            padded[:len(chunk)] = chunk
            # one lane: the lanes == 1 case of the one prefill path
            (eng.cache.pool, logits), picks = self._prefill(
                self.model.params, eng.cache.pool, jnp.asarray(padded)[None],
                jnp.asarray([start], jnp.int32),
                jnp.asarray([len(chunk)], jnp.int32),
                jnp.asarray(eng.cache.tables[slot], jnp.int32)[None])
            self._keep(picks, [(i, start + i) for i in range(len(chunk))])
        return onp.asarray(logits[0], onp.float32)

    def step(self, slot, token, position):
        n = self.eng.max_slots
        tok, pos = onp.zeros((n,), onp.int32), onp.zeros((n,), onp.int32)
        act = onp.zeros((n,), bool)
        tok[slot], pos[slot], act[slot] = token, position, True
        (self.eng.cache.pool, logits, _), picks = self._decode(
            self.model.params, self.eng.cache.pool, jnp.asarray(tok),
            jnp.asarray(pos), jnp.asarray(self.eng.cache.tables, jnp.int32),
            jnp.asarray(act))
        self._keep(picks, [(slot, position)])
        return onp.asarray(logits[slot], onp.float32)


def _reference_with(ref, params, tokens, cfg, picked):
    """The reference's logits over ``tokens`` WITH THE PROGRAM'S
    SELECTION: in every expert layer and at every position the experts
    the program's router picked, weighted by the reference's own
    scores.  Where that selection differs from the reference's own, a
    tie that rounding upstream broke the other way, the position met a
    flip, and the flip is as wide as the differing expert's score lies
    from the reference's last selected score.  Returns ``(logits,
    positions that met a flip, the widest flip)``; the caller holds the
    widest to its margin, beyond which it is another routing and no
    tie."""
    own = ref.route
    k = cfg["num_experts_per_tok"]
    layer_no = [0]
    flipped, widest = set(), [0.0]

    def route(scores, cfg):
        w = own(scores, cfg)
        scores_h = onp.asarray(scores)
        sel = onp.asarray(w) > 0
        kth = onp.sort(scores_h, axis=1)[:, -k]
        for pos in range(scores_h.shape[0]):
            mine = picked[pos][layer_no[0]]
            theirs = set(onp.nonzero(sel[pos])[0].tolist())
            for e in mine ^ theirs:
                widest[0] = max(widest[0],
                                float(abs(scores_h[pos, e] - kth[pos])))
                flipped.add(pos)
            sel[pos] = False
            sel[pos, sorted(mine)] = True
        layer_no[0] += 1
        w = jnp.where(jnp.asarray(sel), scores, 0.0)
        return w / w.sum(axis=-1, keepdims=True) * cfg["routed_scaling_factor"]

    ref.route = route
    try:
        with jax.default_matmul_precision("highest"):
            logits = ref.forward(params, jnp.asarray(tokens, jnp.int32), cfg)
    finally:
        ref.route = own
    return onp.asarray(logits, onp.float32), flipped, widest[0]


def _scaled(got, want):
    return float(onp.abs(got - want).max() / onp.abs(want).max())


# Largest |logit - reference| over largest |reference| (TOL), and how
# wide a differing selection may be and still count as a tie (MARGIN), at
# the configuration's initialisation: every branch at the plain fan-in
# scale, the held routed experts' W_down alone divided by
# ``routed_down_divisor``.  Each limit lies between two readings on this
# CPU at the rehearsal size: the sound program's largest over the cases
# below and a control's smallest, the program run in the nearest lower
# precision (PERF.md section 6, PR 33).
# float32: the cached path and the reference differ in the order of
# float32 sums and in the attention's form (absorbed, online softmax
# over pages, against plain): 4.0e-7 to 7.7e-7, no selection differs;
# the experts' matrices in float8 read 3.2e-2 or more, the router's
# product in bfloat16 breaks ten ties up to 1.9e-3 wide: 5e-6 and 1e-5.
# bfloat16: the weights are the same bfloat16 numbers on both sides, the
# program rounds what it multiplies to 8 bits of mantissa; its residual
# stream and router are float32.  Logits: sound 3.8e-3 to 1.44e-2, the
# experts' matrices (held and shared) in float8 3.8e-2 to 4.5e-2, the
# attention's in float8 6.8e-2 or more: 2.3e-2.  Ties: with every
# branch at full strength the products upstream of the router break
# them, 14 over 250 positions and up to 1.26e-3 wide, as wide as a
# bfloat16 router's product would (1.25e-3: no control at this dtype);
# the control is the router's product in float8's 3 bits of mantissa
# (selections differ at 71 of 250 positions, up to 2.4e-2 wide): 2.5e-3.
TOL = {"float32": 5e-6, "bfloat16": 2.3e-2}
MARGIN = {"float32": 1e-5, "bfloat16": 2.5e-3}
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


def _compare(models, ref, dtype, prompt_len, n_decode=4,
             pages_per_slot=8, coarse=None, **over):
    """Prefill ``prompt_len`` tokens by chunks, decode ``n_decode`` more
    through the cache, and compare the logits of the last prompt
    position and of every decoded one with the reference's: ``(the
    worst scaled difference, positions that met a flip, the widest
    flip)``.  ``coarse(params)``: the PROGRAM runs with those parameters
    (a control in lower precision), the reference with the model's."""
    model, cfg = models(dtype, **over)
    through = _Through(model, _engine(model, pages_per_slot=pages_per_slot,
                                      num_pages=3 * pages_per_slot))
    toks = _tokens(prompt_len + n_decode, seed=prompt_len)
    through.eng.acquire_slot(1, len(toks))
    good = model.params
    try:
        if coarse is not None:
            model.params = coarse(good)
        got = {prompt_len - 1: through.feed_prompt(1, toks[:prompt_len])}
        for p in range(prompt_len, len(toks)):
            got[p] = through.step(1, toks[p], p)
    finally:
        model.params = good
    want, flipped, widest = _reference_with(ref, good, toks, cfg,
                                            through.picked)
    worst = max(_scaled(got[p], want[p]) for p in got)
    return worst, flipped, widest


@pytest.mark.parametrize("prompt_len,n_decode", [
    (5, 4), (CHUNK, 4), (37, 4), (250, 1)],
    ids=["short", "one_chunk", "three_chunks", "sixteen_chunks"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_prefill_then_cached_decode_matches_the_reference_on_logits(
        models, ref, dtype, prompt_len, n_decode):
    worst, _, widest = _compare(
        models, ref, dtype, prompt_len, n_decode,
        pages_per_slot=8 if prompt_len < 60 else 32)
    assert worst <= TOL[dtype], worst
    assert widest <= MARGIN[dtype], widest


@pytest.mark.parametrize("prompt_len", [5, CHUNK, 37],
                         ids=["short", "one_chunk", "three_chunks"])
def test_the_layers_at_the_plain_scale_match_the_reference(
        models, ref, prompt_len):
    """Every branch at full strength, in bfloat16: selections do differ
    here, and the comparison follows the program's."""
    worst, _, widest = _compare(models, ref, "bfloat16",
                                prompt_len, **PLAIN)
    assert worst <= TOL_PLAIN["bfloat16"], worst
    assert widest <= MARGIN_PLAIN["bfloat16"], widest


def test_the_group_limited_router_is_held_to_the_reference_too(models, ref):
    worst, _, widest = _compare(models, ref, "float32", 21,
                                topk_method="noaux_tc")
    assert worst <= TOL["float32"] and widest <= MARGIN["float32"]


# -- the controls: the nearest lower precision fails a limit -------------------

def _router_rounded(mantissa_bits):
    """The router's product as a matmul in a lower precision gives it:
    operands and result of ``mantissa_bits`` bits of mantissa (7:
    bfloat16, 3: float8; ``reduce_precision``, which no compiler takes
    for excess precision it may keep)."""
    def rounded(a):
        return jax.lax.reduce_precision(a.astype(jnp.float32),
                                        exponent_bits=8,
                                        mantissa_bits=mantissa_bits)

    def scores(self, h, w_router):
        return jax.nn.sigmoid(rounded(jnp.dot(
            rounded(h), rounded(w_router),
            precision=jax.lax.Precision.HIGHEST)))

    return scores


def _float8(names):
    """``coarse(params)``: the layers' matrices that ``names`` picks
    rounded to float8 as the benchmark's control rounds them."""
    drv = _load(REPO / "chipbench" / "drivers" / "decode_open_loop_model.py",
                "drv_for_round8")

    def coarse(params):
        return dict(params, layers=[
            {k: (drv._round8(v.astype(jnp.bfloat16)).astype(v.dtype)
                 if names(k) else v) for k, v in lp.items()}
            for lp in params["layers"]])

    return coarse


def _held(k):
    return k[0] == "e" and k[1].isdigit()


@pytest.mark.parametrize("dtype,mantissa_bits", [("float32", 7),
                                                 ("bfloat16", 3)])
def test_a_router_in_lower_precision_fails_the_margin(models, ref,
                                                      monkeypatch, dtype,
                                                      mantissa_bits):
    """The router's product taken in the nearest precision below the
    model's (bfloat16 under a float32 model, float8's mantissa under a
    bfloat16 one) moves the scores by more than the margin allows a tie
    to be: over the positions of one prompt some selection differs
    outside it (the sound program over the same positions: the
    sixteen-chunk case above)."""
    monkeypatch.setattr(AXK1, "_scores", _router_rounded(mantissa_bits))
    _, flipped, widest = _compare(models, ref, dtype, 250, 1,
                                  pages_per_slot=32)
    assert flipped and widest > 2 * MARGIN[dtype], widest


@pytest.mark.parametrize("prompt_len", [5, CHUNK, 37],
                         ids=["short", "one_chunk", "three_chunks"])
def test_experts_in_float8_fail_the_bfloat16_tolerance(models, ref,
                                                       prompt_len):
    """The experts' matrices (held and shared) rounded to float8, the
    benchmark's control, in the PROGRAM's place at the stated precision:
    over the limit the sound program keeps, in every case it keeps it."""
    worst, _, _ = _compare(
        models, ref, "bfloat16", prompt_len,
        coarse=_float8(lambda k: _held(k) or k.startswith("ws_")))
    assert worst > 1.25 * TOL["bfloat16"], worst


@pytest.mark.parametrize("prompt_len", [5, CHUNK, 37],
                         ids=["short", "one_chunk", "three_chunks"])
def test_held_experts_in_float8_fail_the_plain_scale_tolerance(
        models, ref, prompt_len):
    """The HELD experts alone in float8, every branch at full strength:
    over the plain scale's limit."""
    worst, _, widest = _compare(models, ref, "bfloat16",
                                prompt_len, coarse=_float8(_held), **PLAIN)
    assert worst > 1.25 * TOL_PLAIN["bfloat16"], worst
    assert widest <= MARGIN_PLAIN["bfloat16"]       # the router is sound


def test_experts_in_lower_precision_fail_the_tolerance(models):
    """The held experts' matrices rounded to float8: over the float32
    limit, by the dense pass over 64 positions (the same router and the
    same scores on both sides, so the same selection; the limit is the
    cached path's)."""
    model, _ = models("float32")
    toks = jnp.asarray(_tokens(64, seed=3), jnp.int32)
    got = onp.asarray(model.dense_logits(_float8(_held)(model.params), toks))
    want = onp.asarray(model.dense_logits(model.params, toks))
    assert _scaled(got, want) > 5 * TOL["float32"]


def test_dense_oracle_is_the_reference(models, ref):
    model, cfg = models("float32")
    toks = jnp.asarray(_tokens(29, seed=2), jnp.int32)
    with jax.default_matmul_precision("highest"):
        want = ref.forward(model.params, toks, cfg)
    assert _scaled(onp.asarray(model.dense_logits(model.params, toks)),
                   onp.asarray(want)) <= TOL["float32"]


@pytest.mark.parametrize("matrix", ["wo", "ws_down", "e1_down", "w_router",
                                    "wkv_b"])
def test_every_branch_weighs_in_the_logits(models, matrix):
    """Zero one matrix and the logits move by far more than the
    tolerance: no branch hides, the held routed experts under their
    divisor neither."""
    model, _ = models("float32")
    toks = jnp.asarray(_tokens(40, seed=4), jnp.int32)
    want = onp.asarray(model.dense_logits(model.params, toks))
    cut = dict(model.params, layers=[
        {k: (jnp.zeros_like(v) if k == matrix else v) for k, v in lp.items()}
        for lp in model.params["layers"]])
    got = onp.asarray(model.dense_logits(cut, toks))
    assert _scaled(got, want) > 100 * TOL["float32"]


# -- the absorbed form, the kernel, the rotation --------------------------------

def test_the_absorbed_form_is_the_plain_one(models):
    """One layer's attention over one sequence: the decode path's
    absorbed scores and latent-space output through a paged buffer
    against the oracle's up-projected keys and values."""
    model, _ = models("float32")
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
    fall = spec.fallback(q, pool, tables, lengths, **kw)
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
    want = onp.asarray(spec.fallback(q, pool, tables, lengths, **kw))
    onp.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    idle = onp.asarray(lengths) == 0
    assert not got[idle].any()
    assert got[~idle].any(axis=(1, 2)).all()


def test_yarn_frequencies_and_the_table_rotation(ref):
    sc = _config()["rope_scaling"]
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
def test_route_topk_is_a_sort(ref, n_group, topk_group):
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


def test_sixteen_shares_add_up_to_the_uncut_layer(ref):
    """The share test: one expert layer's weights with all 48 routed
    experts; the program as each of the sixteen chips (3 experts each)
    computes its partial result; those, with what every chip computes
    alike (the attention and the shared expert) counted once, are the
    uncut reference's layer."""
    cfg = _config(n_routed_experts=48, experts_first=0)
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

def test_a_latent_page_is_one_buffer_a_layer_and_counts_its_bytes(models):
    model, _ = models("float32")
    assert model.page_widths == (128,) == (paged_kv.latent_width(32, 8),)
    assert paged_kv.latent_width(512, 64) == 640
    eng = _engine(model)
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


def test_counters_ride_with_the_tokens(models, _clean):
    """A turn reads once; the step record and ``stats()`` carry the
    model's counters and the live tokens a step."""
    model, _ = models("float32")
    eng = _engine(model, max_slots=2)

    class Sink:
        records = []

        def emit(self, record):
            if "decode" in record:
                self.records.append(record["decode"])

    telemetry.add_sink(Sink())
    reads = []
    get = jax.device_get
    sch = DecodeScheduler(eng, start=False)
    prompts = [_tokens(n, seed=n) for n in (11, 20)]
    futs = [sch.submit(p, max_new_tokens=6) for p in prompts]
    try:
        jax.device_get = lambda tree: (reads.append(1), get(tree))[1]
        turns = 0
        while sch._has_work():
            sch.step()
            turns += 1
    finally:
        jax.device_get = get
    for p, f in zip(prompts, futs):
        assert f.result(0) == model.greedy_reference(p, 6)
    assert len(reads) <= turns           # never a second read in a turn
    names = {"moe_local_pair_share", "moe_expert_rows_mean",
             "moe_expert_rows_max", "moe_experts_idle_share",
             "latent_overlap_share"}
    decoded = [r for r in Sink.records if r["counters"]]
    assert decoded and all(set(r["counters"]) == names for r in decoded)
    st = eng.stats()
    assert set(st["counters"]) == names
    assert 0.0 <= st["counters"]["moe_local_pair_share"] <= 1.0
    assert st["counters"]["moe_expert_rows_max"] <= 2.0
    # a slot's context fits one block: two live slots read 1/2, one 0
    assert 0.0 < st["counters"]["latent_overlap_share"] <= 0.5
    # two slots decode positions 11.. and 20..: 5 steps each, the
    # context one longer a step, over the steps that were dispatched
    assert st["live_tokens_mean"] * eng._decode_steps == pytest.approx(
        sum(range(12, 17)) + sum(range(21, 26)))
    # held 3 of 48 at top-2: a sixteenth of the pairs when routing is even
    assert 0.0 < st["counters"]["moe_expert_rows_mean"] < 2.0
    # no profiler capture ran: nothing belongs to a trace
    traced = st["traced"]
    assert (traced["decode_steps"], traced["live_tokens_mean"],
            traced["counters"]) == (0, 0.0, {})
    assert traced["sched"]["turns"] == traced["requests"]["count"] == 0
    assert st["sched"]["turns"] == turns and st["requests"]["count"] == 2


def test_the_steps_under_a_capture_are_counted_apart(models, monkeypatch,
                                                     _clean):
    """``stats()["traced"]``: the live tokens and the model's counters
    of the decode steps dispatched while a profiler capture ran, and of
    no other: what a trace's kernel times are divided by."""
    from mxnet_tpu import tracing
    model, _ = models("float32")
    eng = _engine(model, max_slots=2)
    sch = DecodeScheduler(eng, start=False)
    fut = sch.submit(_tokens(11, seed=11), max_new_tokens=9)
    on = {"now": False}
    monkeypatch.setattr(tracing, "capturing", lambda: on["now"])
    turns = 0
    while sch._has_work():
        # the capture covers the third and fourth decode dispatch
        on["now"] = eng._decode_steps in (2, 3)
        sch.step()
        turns += 1
    assert fut.result(0) == model.greedy_reference(_tokens(11, seed=11), 9)
    st = eng.stats()
    assert eng._decode_steps == 8 and st["traced"]["decode_steps"] == 2
    # position 11 decodes first (context 12): the third and fourth steps
    # read 14 and 15 rows; the life's mean runs over all eight
    assert st["traced"]["live_tokens_mean"] == pytest.approx(14.5)
    assert st["live_tokens_mean"] == pytest.approx(sum(range(12, 20)) / 8)
    assert set(st["traced"]["counters"]) == set(st["counters"])
    assert not eng._in_flight


def test_scheduler_matches_the_dense_oracle_and_never_recompiles(models,
                                                                 _clean):
    """Chained turns (the scheduler's) and synchronous ones (a step
    dispatched and read at once, by hand) give the same tokens."""
    model, _ = models("float32")
    eng = _engine(model, max_slots=2)
    # two slots: one multi-lane executable, two lanes of the full chunk
    assert eng.warmup([8, CHUNK]) == ["decode", "state_edit", "prefill_b8",
                                      "prefill_b16", "prefill_b32"]
    compiled = eng.compiles
    sch = DecodeScheduler(eng, start=False)
    prompts = [_tokens(n, seed=n) for n in (3, 16, 23, 40, 9)]
    futs = [sch.submit(p, max_new_tokens=5) for p in prompts[:3]]
    sch.step()
    sch.step()
    futs += [sch.submit(p, max_new_tokens=5) for p in prompts[3:]]
    _run(sch)
    assert eng.compiles == compiled and eng.stats()["chained_share"] > 0.5
    sync = _engine(model, max_slots=2)
    for p, f in zip(prompts, futs):
        assert f.result(0) == model.greedy_reference(p, 5)
        sync.acquire_slot(0, len(p) + 5)
        tok = None
        for start in range(0, len(p), CHUNK):
            tok, = sync.prefill_chunks([(0, p[start:start + CHUNK], start)])
        sync.activate_slot(0, tok, len(p))
        out = [int(tok)]
        for _ in range(4):
            nxt, _ = sync.read(*sync.decode_step())
            out.append(int(nxt[0]))
        sync.release_slot(0)
        assert out == f.result(0)
    assert sync.stats()["chained_share"] == 0.0
    assert sch.stats()["pages_used"] == 0


def test_server_generate_answers_for_the_latent_model(models, _clean):
    from mxnet_tpu.gluon import nn
    model, _ = models("float32")
    mx.random.seed(0)
    net = nn.Sequential()
    net.add(nn.Dense(4, in_units=8))
    net.initialize()
    srv = ServingServer(net, engine_args={"example_shape": (8,),
                                          "dtype": "float32"})
    sch = DecodeScheduler(_engine(model), start=True)
    srv.attach_decoder(sch)
    p = _tokens(21, seed=8)
    assert srv.generate(p, max_new_tokens=4) == model.greedy_reference(p, 4)
    srv.stop(drain=True)
    assert sch.closed


def test_a_latent_model_has_no_verify_core_yet(models):
    """It cannot be a speculation's target until it has one, and says so
    when asked (ROADMAP R2)."""
    model, _ = models("float32")
    with pytest.raises(NotImplementedError):
        model.verify_core(model.params, (), None, None, None, None)


def test_a_config_that_asks_for_what_is_not_there_is_refused():
    for over in ({"scoring_func": "softmax"}, {"topk_method": "greedy"},
                 {"n_shared_experts": 2}, {"experts_first": 46},
                 {"rope_scaling": {"type": "linear", "factor": 2}}):
        with pytest.raises(ValueError):
            AXK1(_config(**over), abstract=True)
    cfg = _config()
    del cfg["kv_lora_rank"]
    with pytest.raises(ValueError, match="lacks"):
        AXK1(cfg, abstract=True)


def test_abstract_model_and_param_count_by_hand():
    """The published widths as shapes only, against the benchmark's own
    count and the sum written out in its configuration."""
    with open(REPO / "chipbench" / "configs" / "axk1_519b.json") as f:
        cfg = json.load(f)
    model = AXK1(cfg, abstract=True)
    leaves = jax.tree_util.tree_leaves(model.params)
    assert all(isinstance(l, jax.ShapeDtypeStruct) for l in leaves)
    n = sum(int(onp.prod(l.shape)) for l in leaves)
    fam = _load(REPO / "chipbench" / "models" / "axk1.py", "axk1_family")
    assert n == fam.param_count(cfg) == cfg["parameters"] == 4_841_331_712
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
