"""The decode plane with a second kind of model: Falcon-H1's hybrid
block (grouped-query attention beside Mamba-2 heads) behind the model
protocol, per-slot recurrent state beside the paged K/V, the
``ssm_update`` kernel and grouped-query ``paged_attention``: the
family's own controls (what every model passes: ``test_decode_contract``,
``test_model_contract``, ``test_decode_in_place``).

All at the benchmark configuration's ``rehearsal`` size (same ratios as
the published model: 5 query heads a KV head, 2 groups, convolution 4),
seeded random weights, on the CPU with the kernels interpreted:

- prefill in chunks then decode through the cache against the plain
  reference's full pass (``chipbench/reference/falcon_h1_ref.py``), on
  LOGITS, float32 and bfloat16 each with its own tolerance, prompts
  shorter than, equal to and longer than a chunk, two slots admitted at
  different times sharing decode steps, at the published multipliers and
  with every multiplier at 1;
- ``ssm_update`` against its oracle, the chunked scan against the
  time-step recurrence over ragged lengths and a non-zero initial state;
- grouped-query ``paged_attention`` against its oracle, multi-head kept;
- an inactive slot keeps its state, admission zeroes it under its span,
  a slot's next tenant is dispatched behind the stray step;
- speculation with a recurrent model is refused.
"""
import numpy as onp
import pytest

import jax.numpy as jnp

import mxnet_tpu as mx  # noqa: F401  (registers ops + kernel specs)
from mxnet_tpu import kernels, tracing
from mxnet_tpu.ops import ssm
from mxnet_tpu.ops.paged_attention import paged_attention
from mxnet_tpu.serving import DecodeEngine, DecodeModel, DecodeScheduler

from decode_families import (CHUNK, HYBRID, Kit, Through, compare, config,
                             engine, load, paged_oracle, published, run,
                             tokens)
from decode_families import clean  # noqa: F401  (a fixture)


@pytest.fixture(scope="module")
def kit():
    return Kit()


def _ones():
    """Every multiplier at 1: every mixer at full weight in the residual
    stream, so an error in one of them cannot hide behind a small
    multiplier; the model computes the same function from other numbers,
    which holds each multiplier to the reference's place for it."""
    over = {k: 1.0 for k in config("falcon_h1_34b")
            if k.endswith("_multiplier")}
    return dict(over, ssm_multipliers=[1.0] * 5, mlp_multipliers=[1.0, 1.0])


MULTIPLIERS = {"published": {}, "ones": _ones()}


@pytest.mark.parametrize("multipliers", ["published", "ones"])
@pytest.mark.parametrize("prompt_len", [5, CHUNK, 37],
                         ids=["short", "one_chunk", "three_chunks"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_prefill_then_cached_decode_matches_the_reference_on_logits(
        kit, dtype, prompt_len, multipliers):
    """Slot 1 prefills and decodes; two steps in, slot 2 is admitted with
    a prompt of its own, and both decode in the same steps (the
    tolerances: ``decode_families.HYBRID``)."""
    new = 6
    worst, _, _ = compare(kit, "hybrid", dtype, [
        (1, tokens(prompt_len + new, seed=prompt_len), prompt_len, 0),
        (2, tokens(21 + new, seed=100 + prompt_len), 21, 2)],
        **MULTIPLIERS[multipliers])
    assert worst <= HYBRID.tol[dtype], worst


def test_inactive_slots_keep_their_state(kit):
    model, _ = kit.model("hybrid")
    eng = engine(model)
    through = Through(kit, "hybrid", model, eng)
    eng.acquire_slot(0, 30)
    eng.acquire_slot(2, 30)
    through.feed_prompt(0, tokens(9, seed=5))
    through.feed_prompt(2, tokens(17, seed=6))
    before = [[onp.asarray(b[2]) for b in layer[2:]]
              for layer in eng.cache.pool]
    through.step({0: (3, 9)})
    for layer, was in zip(eng.cache.pool, before):
        for buf, old in zip(layer[2:], was):
            onp.testing.assert_array_equal(onp.asarray(buf[2]), old)
            assert float(jnp.abs(buf[1]).max()) == 0


@pytest.mark.parametrize("kw", [{"spec_k": 2},
                                {"draft_model": "draft"},
                                {"draft_model": "draft", "spec_k": 3}],
                         ids=["spec_k", "draft", "both"])
def test_speculation_with_recurrent_state_is_refused(kit, kw):
    model, _ = kit.model("hybrid")
    if "draft_model" in kw:
        kw = dict(kw, draft_model=DecodeModel(128, dim=16, n_heads=2,
                                              n_layers=1))
    with pytest.raises(ValueError, match="recurrent state.*snapshots"):
        engine(model, **kw)
    assert engine(model).spec_enabled is False       # and plain is fine


def test_param_count_by_hand():
    family = load("chipbench/models/falcon_h1.py", "falcon_h1_family")
    cfg = published("falcon_h1_34b")
    # attention 5120x2560 + 2 x 5120x512 + 2560x5120 = 31,457,280; mixer
    # 5120x9248 + 5120x4 + 5120 + 4096x5120 + 4096 + 96 = 68,351,072; MLP
    # 3 x 5120x21504 = 330,301,440; two norms 10,240
    assert family.layer_param_count(cfg) == 430_120_032
    assert family.vocab_param_count(cfg) == 2 * 261_120 * 5_120 \
        == 2_673_868_800
    assert family.param_count(cfg) == cfg["parameters"] \
        == 6 * 430_120_032 + 2_673_868_800 + 5_120
    # one slot's state: 32 x 128 x 256 float32 = 4 MiB, read and written
    assert family.ssm_state_bytes(cfg) == 4 << 20
    assert family.ssm_update_bytes(cfg) == 2 * (4 << 20) \
        + 4 * (2 * 4096 + 32 + 1024)
    assert family.ssm_update_flops(cfg, 96) == 5 * 96 * 32 * 128 * 256


# -- through the scheduler ----------------------------------------------------

def test_admission_zeroes_the_state_under_its_span(kit, clean):
    model, _ = kit.model("hybrid")
    sch = DecodeScheduler(engine(model), start=False)
    sch.submit(tokens(5, seed=1), max_new_tokens=2)
    tracing.enable()
    tracing.clear()
    try:
        run(sch)
        evs = sorted((e for e in tracing._completed_events()
                      if e["name"].startswith("decode.")),
                     key=lambda e: e["ts"])
    finally:
        tracing._env_default()
        tracing.clear()
    by_id = {e["args"]["span_id"]: e for e in evs}
    reset, = [e for e in evs if e["name"] == "decode.state_reset"]
    assert reset["args"]["slot"] == 0
    assert by_id[reset["args"]["parent_id"]]["name"] == "decode.admit_phase"
    prefill = [e for e in evs if e["name"] == "decode.prefill"][0]
    assert reset["ts"] + reset["dur"] <= prefill["ts"] + 1


def test_a_slots_next_tenant_is_dispatched_behind_the_stray_step(
        kit, clean):
    """What the chained turn's correctness rests on, for a model with
    state: when ``eos`` is read, one more step of the slot is already in
    flight and advances its state; the edit that switches the slot off,
    the next tenant's ``state_reset``, its chunk and the edit that
    switches it on all follow that step on the device's one stream, in
    this order, and the tenant decodes from zero state."""
    model, _ = kit.model("hybrid", **MULTIPLIERS["ones"])
    eng = engine(model, max_slots=1)
    eng.warmup([8])
    first, second = tokens(6, seed=21), tokens(7, seed=22)
    ref = model.greedy_reference(first, 8)
    eos = ref[2]
    calls = []
    call = eng._call

    def logged(key, args, donate=(1,)):
        calls.append(key)
        return call(key, args, donate)

    eng._call = logged
    sch = DecodeScheduler(eng, start=False)
    fut = sch.submit(first, max_new_tokens=8, eos=eos)
    while not fut.done():
        sch.step()
    assert fut.result(0) == ref[:ref.index(eos) + 1]
    ssm_buf = eng.cache.pool[0][2]
    assert float(jnp.abs(ssm_buf[0]).max()) > 0     # left behind
    mark = len(calls)
    assert calls[mark - 2:] == ["decode", "state_edit"]   # stray, then off
    late = sch.submit(second, max_new_tokens=3)
    run(sch)
    assert calls[mark:mark + 4] == ["state_reset", "prefill_b8",
                                    "state_edit", "decode"]
    assert late.result(0) == model.greedy_reference(second, 3)
    assert eng.stats()["state_resets"] == 2 and sch.stats()["pages_used"] == 0


def test_decode_model_keeps_no_state_and_its_counters_read_zero():
    eng = DecodeEngine(DecodeModel(48, dim=32, n_heads=4, n_layers=2),
                       max_slots=2, num_pages=8, page_size=8)
    assert eng.cache.state_layers == 0 and len(eng.cache.pool[0]) == 2
    eng.acquire_slot(0, 8)
    st = eng.stats()
    assert (st["state_bytes"], st["state_slots_live"],
            st["state_resets"]) == (0, 0, 0)
    assert "state_reset" not in eng.warmup([8])


# -- the state-space update and scan -----------------------------------------

def _ssm_args(slots, active, dtype="float32", seed=0):
    spec = kernels.get_kernel("ssm_update")
    arrays, _ = spec.make_args({"slots": slots, "h": 4, "p": 16, "n": 32,
                                "g": 2, "dtype": dtype})
    return arrays[:-1] + (jnp.asarray(active),)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("active", [
    [False, True, True, False, True, False],     # ragged
    [True] * 6, [False] * 6,                     # all, none
    [False, False, False, False, False, True],   # only the last
    [True, False, False, False, False, False]],  # only the first
    ids=["ragged", "all", "none", "last", "first"])
def test_ssm_update_matches_its_oracle(active, dtype):
    args = _ssm_args(6, active, dtype)
    state, y = ssm.ssm_update(*args)
    want_state, want_y = ssm.ssm_update_reference(*args)
    onp.testing.assert_allclose(onp.asarray(state), onp.asarray(want_state),
                                rtol=2e-5, atol=2e-5)
    onp.testing.assert_allclose(onp.asarray(y), onp.asarray(want_y),
                                rtol=2e-5, atol=2e-5)
    idle = ~onp.asarray(active)
    onp.testing.assert_array_equal(onp.asarray(state)[idle],
                                   onp.asarray(args[0])[idle])
    assert not onp.asarray(y)[idle].any()


@pytest.mark.parametrize("block_h", [1, 2, 8])
def test_ssm_update_head_blocks_stay_inside_a_group(block_h):
    args = _ssm_args(4, [True, False, True, True])
    state, y = ssm.ssm_update(*args, block_h=block_h)
    want_state, want_y = ssm.ssm_update_reference(*args)
    onp.testing.assert_allclose(onp.asarray(state), onp.asarray(want_state),
                                rtol=2e-5, atol=2e-5)
    onp.testing.assert_allclose(onp.asarray(y), onp.asarray(want_y),
                                rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("cuts", [(0, 40), (0, 16, 32, 40), (0, 1, 5, 6, 40),
                                  (0, 3, 40)],
                         ids=["whole", "chunks", "ragged", "short_first"])
@pytest.mark.parametrize("start", ["zero", "carried"])
def test_chunked_scan_matches_the_recurrence(cuts, start):
    rng = onp.random.RandomState(len(cuts))
    t_, h, p, n, g = 40, 4, 16, 32, 2
    x = jnp.asarray(rng.randn(t_, h, p), jnp.float32)
    dt = jnp.asarray(rng.uniform(1e-3, 1e-1, (t_, h)), jnp.float32)
    a = -jnp.asarray(rng.uniform(1, 16, (h,)), jnp.float32)
    b = jnp.asarray(rng.randn(t_, g, n), jnp.float32)
    c = jnp.asarray(rng.randn(t_, g, n), jnp.float32)
    d = jnp.asarray(rng.randn(h), jnp.float32)
    s0 = (jnp.zeros((h, p, n)) if start == "zero"
          else jnp.asarray(rng.randn(h, p, n), jnp.float32))
    want_s, want_y = ssm.ssm_scan_reference(s0, x, dt, a, b, c, d)
    s, ys = s0, []
    for lo, hi in zip(cuts, cuts[1:]):
        # each piece padded to 16 rows with dt = 0, as a prefill bucket is
        pad = -(hi - lo) % 16
        piece = [jnp.pad(v[lo:hi], ((0, pad),) + ((0, 0),) * (v.ndim - 1))
                 for v in (x, dt, b, c)]
        s, y = ssm.ssm_chunk_scan(s, piece[0], piece[1], a, piece[2],
                                  piece[3], d)
        ys.append(y[:hi - lo])
    onp.testing.assert_allclose(onp.asarray(s), onp.asarray(want_s),
                                rtol=1e-4, atol=1e-5)
    onp.testing.assert_allclose(onp.asarray(jnp.concatenate(ys)),
                                onp.asarray(want_y), rtol=1e-4, atol=1e-4)


def test_one_decode_update_is_one_step_of_the_recurrence():
    args = _ssm_args(3, [True, True, True])
    state, x, dt, a, b, c, d, _ = args
    new, y = ssm.ssm_update(*args)
    for s in range(3):
        want_s, want_y = ssm.ssm_scan_reference(
            state[s], x[s][None], dt[s][None], a, b[s][None], c[s][None], d)
        onp.testing.assert_allclose(onp.asarray(new[s]), onp.asarray(want_s),
                                    rtol=2e-5, atol=2e-5)
        onp.testing.assert_allclose(onp.asarray(y[s]),
                                    onp.asarray(want_y[0]),
                                    rtol=2e-5, atol=2e-5)


# -- grouped-query paged attention -------------------------------------------

@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5),
                                       ("bfloat16", 2e-2)])
@pytest.mark.parametrize("d", [32, 128], ids=["d32", "lanes128"])
@pytest.mark.parametrize("h,kv_h", [(4, 4), (10, 2), (8, 2), (6, 1),
                                    (20, 4)],
                         ids=["mha", "gqa5", "gqa4", "mqa", "published"])
@pytest.mark.parametrize("page_size,block_k", [(16, 8), (16, 32), (128, 64)],
                         ids=["half_of_16", "2_pages_of_16", "half_of_128"])
def test_paged_attention_grouped_query_matches_its_oracle(
        page_size, block_k, h, kv_h, d, dtype, tol):
    """Both bodies of the kernel under the one walker: heads narrower
    than a lane tile, and heads of a whole tile (the published 128),
    whose scores run on the MXU; a block of half a page (of 16, and of
    the benchmark cell's 128) and of two pages."""
    spec = kernels.get_kernel("paged_attention")
    arrays, _ = spec.make_args({"slots": 5,
                                "pages_per_slot": 4 if page_size == 16 else 2,
                                "page_size": page_size, "h": h, "kv_h": kv_h,
                                "d": d, "dtype": dtype})
    q, k_pool, v_pool, tables, lengths = arrays
    assert k_pool.shape[-1] == kv_h * d           # sized by the KV heads
    out = paged_attention(q, k_pool, v_pool, tables, lengths,
                          block_k=block_k)
    want = paged_oracle(q, k_pool, v_pool, tables, lengths)
    assert out.shape == q.shape
    onp.testing.assert_allclose(onp.asarray(out, "float32"),
                                onp.asarray(want, "float32"),
                                rtol=tol, atol=tol)
    assert not onp.asarray(out, "float32")[0].any()      # the idle slot


def test_grouped_query_is_multi_head_over_repeated_pools():
    """What the kernel must NOT do in HBM, done here to pin the head
    order: query head h reads KV head h // R."""
    spec = kernels.get_kernel("paged_attention")
    (q, k_pool, v_pool, tables, lengths), _ = spec.make_args(
        {"slots": 4, "pages_per_slot": 2, "page_size": 16, "h": 6,
         "kv_h": 2, "d": 16})
    out = paged_attention(q, k_pool, v_pool, tables, lengths, block_k=16)

    def repeated(pool):
        pages, ps, _ = pool.shape
        return jnp.repeat(pool.reshape(pages, ps, 2, 16), 3,
                          axis=2).reshape(pages, ps, 6 * 16)

    want = paged_attention(q, repeated(k_pool), repeated(v_pool), tables,
                           lengths, block_k=16)
    onp.testing.assert_allclose(onp.asarray(out), onp.asarray(want),
                                rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("start,chunk_len", [(0, 16), (16, 11), (5, 1)])
def test_chunk_attention_grouped_is_multi_head_over_repeated_heads(
        start, chunk_len):
    """The prefill chunk's attention, the form both models share: 6
    query heads over 2 K/V heads against the same queries over those
    heads repeated to 6 (``rep == 1``, ``DecodeModel``'s case), over a
    context that an earlier call left in the slot's pages."""
    from mxnet_tpu.serving.decode.paged_kv import chunk_attention
    rs = onp.random.RandomState(4)
    pages, ps, d, bucket = 6, 8, 16, 16
    table = jnp.asarray([4, 1, 3, 0], jnp.int32)

    def run(rep):
        """Positions [0, start) written by one call, the chunk by the
        next; K/V heads repeated ``rep`` times before they are handed
        over.  The output of the second call."""
        kvh = 2 * rep
        buf = jnp.zeros((pages, ps, kvh * d), jnp.float32)
        kv, out = (buf, buf), None
        rs.seed(4)
        for at, n in ((0, start), (start, chunk_len)):
            q = jnp.asarray(rs.randn(bucket, 6, d), jnp.float32)
            k, v = (jnp.repeat(jnp.asarray(rs.randn(bucket, 2, d),
                                           jnp.float32), rep, axis=1)
                    for _ in range(2))
            attend = chunk_attention(
                (kv,), jnp.asarray([at], jnp.int32),
                jnp.asarray([n], jnp.int32), table[None], bucket,
                rope_base=10000.0)
            out, kv = attend(q, k, v, *kv)
        return onp.asarray(out)[:chunk_len]

    grouped, multi_head = run(1), run(3)
    assert onp.abs(grouped).max() > 0.1
    onp.testing.assert_allclose(grouped, multi_head, rtol=1e-6, atol=1e-6)


def test_a_pool_that_is_no_whole_number_of_heads_is_refused():
    q = jnp.zeros((2, 6, 16))
    pool = jnp.zeros((4, 8, 4 * 16))              # 4 does not divide 6
    with pytest.raises(ValueError, match="divides"):
        paged_attention(q, pool, pool, jnp.zeros((2, 2), jnp.int32),
                        jnp.zeros((2,), jnp.int32), block_k=8)
