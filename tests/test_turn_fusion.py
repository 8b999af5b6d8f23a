"""A decode step carries a prefill dispatch's lanes: a turn that has
slots decoding and slots filling reads the weights ONCE for both
(``decode_fill_b<rows>``), for a model that supplies ``turn_logits``.

Held here, for ``FalconH1`` at CPU size: a fused dispatch leaves the
pages, the recurrent state, the convolution tails, the decode tokens and
the lanes' first tokens that the decode step followed by the same chunks'
prefill dispatch leave (one lane, two, a short final chunk padded to the
full chunk's bucket, a padding lane); a padding lane writes nothing; a
scheduler that fuses gives every request the tokens of one whose model
has no ``turn_logits``, which never dispatches a ``decode_fill``; and the
``fused`` counter counts what was dispatched.
"""
import functools

import numpy as onp
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx  # noqa: F401  (registers ops + kernel specs)
from mxnet_tpu import tracing
from mxnet_tpu.serving import DecodeScheduler, FalconH1
from mxnet_tpu.serving.decode import engine as E
from mxnet_tpu.serving.decode.engine import DecodePlaneModel
from mxnet_tpu.serving.decode.paged_kv import PageAllocator

import decode_families as families
from decode_families import CHUNK, build, config, tokens

# four slots: up to four lanes of the full chunk in a turn
FOUR = dict(max_slots=4, num_pages=32)


class _Unfused(FalconH1):
    """The same model with no ``turn_logits``: its chunks go through
    ``prefill_chunks`` whatever the turn holds."""
    turn_logits = DecodePlaneModel.turn_logits


@pytest.fixture(scope="module")
def engine():
    """One engine for the whole file: its executables are compiled once,
    and every case starts it from an empty cache and resident state."""
    eng = families.engine(build("hybrid"), **FOUR)

    def fresh():
        for slot in range(eng.max_slots):
            eng.release_slot(slot)
        # the same slots get the same pages in every case
        eng.cache.allocator = PageAllocator(eng.num_pages)
        eng.cache.pool = jax.tree_util.tree_map(jnp.zeros_like,
                                                eng.cache.pool)
        eng._resident = jax.tree_util.tree_map(jnp.zeros_like,
                                               eng._resident)
        eng._positions[:] = 0
        eng._in_flight.clear()
        return eng

    return fresh


# name -> (prompt lengths of the filling slots 1.., the tokens of each
# already fed before the turn): slot 0 decodes a prompt of 20 in every
# case, and each filling slot's next chunk rides
CASES = {
    # one lane: a later full chunk that is not its prompt's last
    "one_lane": ((40,), (16,)),
    # two lanes: a full chunk beside a first chunk that is also the last
    "two_lanes": ((40, 9), (16, 0)),
    # one lane whose chunk is short and final: padded to the full bucket
    "short_final_padded": ((21,), (16,)),
    # three chunks in four lanes: the fourth is padding
    "padding_lane": ((40, 16, 3), (0, 16, 0)),
}


def _turn(eng, case, fused):
    """Slot 0 decoding and the case's slots filling, then ONE turn:
    ``decode_step(chunks)`` when ``fused``, else ``decode_step()`` and
    ``prefill_chunks(chunks)``.  The decode tokens, the lanes' tokens,
    the cache as host arrays, and the executables' keys in order."""
    lengths, fed = CASES[case]
    prompt = tokens(20, seed=1)
    eng.acquire_slot(0, 30)
    tok, = eng.prefill_chunks([(0, prompt[:CHUNK], 0)])
    tok, = eng.prefill_chunks([(0, prompt[CHUNK:], CHUNK)])
    eng.activate_slot(0, tok, len(prompt))
    chunks = []
    for slot, (n, done) in enumerate(zip(lengths, fed), start=1):
        p = tokens(n, seed=7 * n + slot)
        eng.acquire_slot(slot, n + 4)
        if done:
            eng.prefill_chunks([(slot, p[:done], 0)])
        chunks.append((slot, p[done:done + CHUNK], done))
    keys = []
    real = eng._call
    eng._call = lambda key, args, **kw: (keys.append(key)
                                         or real(key, args, **kw))
    try:
        if fused:
            nxt, toks = eng.decode_step(chunks)
        else:
            nxt, _ = eng.decode_step()
            toks = eng.prefill_chunks(chunks)
    finally:
        del eng._call
    nxt, toks = eng.read(nxt, toks)
    return nxt, [int(t) for t in toks], jax.device_get(eng.cache.pool), keys


@pytest.mark.parametrize("case", sorted(CASES))
def test_a_fused_turn_leaves_what_a_step_then_a_dispatch_leaves(engine,
                                                                case):
    """The same decode tokens and lanes' first tokens and, to rounding,
    the same pages, state and tails, whether the chunks ride inside the
    decode step or go in a dispatch of their own after it; ONE
    executable, named by the lanes' rows (a pow2 of lanes of the full
    chunk's bucket), where the other way takes two."""
    want_nxt, want_toks, want_pool, want_keys = _turn(engine(), case, False)
    nxt, toks, pool, keys = _turn(engine(), case, True)
    lanes = 1 << (len(CASES[case][0]) - 1).bit_length()
    assert keys == [f"decode_fill_b{lanes * CHUNK}"]
    assert want_keys[0] == "decode" and len(want_keys) == 2
    assert toks == want_toks and len(toks) == len(CASES[case][0])
    onp.testing.assert_array_equal(nxt, want_nxt)
    for got, want in zip(jax.tree_util.tree_leaves(pool),
                         jax.tree_util.tree_leaves(want_pool)):
        assert onp.abs(want).max() > 0
        onp.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("lanes", [1, 2, 4])
def test_a_padding_lane_writes_nothing(engine, lanes):
    """A fused dispatch of padding alone over slots that do not decode
    (what ``warmup`` stages) hands back the cache bit for bit: no page,
    no state, no convolution tail; and the resident state as it was."""
    eng = engine()
    rng = onp.random.RandomState(3)
    eng.cache.pool = jax.tree_util.tree_map(
        lambda a: jnp.asarray(rng.randn(*a.shape), a.dtype), eng.cache.pool)
    before = jax.device_get(eng.cache.pool)
    eng.cache.pool, resident, _, _, toks = eng._call(
        f"decode_fill_b{lanes * CHUNK}",
        (eng.model.params, eng.cache.pool, eng._resident,
         eng._stage(eng.cache, (), lanes, CHUNK)), donate=(1, 2))
    eng._resident = resident
    assert len(toks) == lanes
    for got, want in zip(jax.tree_util.tree_leaves(
            jax.device_get(eng.cache.pool)),
            jax.tree_util.tree_leaves(before)):
        assert onp.array_equal(got, want)
    assert not onp.asarray(resident[1]).any()


def test_a_step_refuses_chunks_it_cannot_carry(engine):
    """A decoding slot's chunk, more chunks than lanes, or a model with
    no ``turn_logits``: the step refuses them before dispatching."""
    eng = engine()
    for slot in range(3):
        eng.acquire_slot(slot, 8)
    eng.activate_slot(0, eng._no_token, 2)
    with pytest.raises(ValueError):
        eng.decode_step([(0, [1, 2], 2)])
    with pytest.raises(ValueError):
        eng.decode_step([(1, [1], 0)] * (eng.prefill_lanes + 1))
    unfused = families.engine(_Unfused(config("falcon_h1_34b"), seed=5,
                                       dtype="float32"), **FOUR)
    assert eng.fuses and not unfused.fuses
    unfused.acquire_slot(1, 8)
    with pytest.raises(ValueError):
        unfused.decode_step([(1, [1], 0)])


def _schedule(eng, monkeypatch):
    """A seeded schedule through a scheduler over ``eng``: requests
    arriving at seeded turns, prompts of one to three chunks, ``max_new``
    from 1 on, the profiler's capture on for turns 4 to 9.  Every
    request's tokens, and the executables dispatched, by key."""
    rs = onp.random.RandomState(40)
    prompts = [tokens(int(n), seed=i)
               for i, n in enumerate(rs.randint(3, 45, size=9))]
    max_new = [int(n) for n in rs.randint(1, 10, size=9)]
    due = sorted(int(t) for t in rs.randint(0, 16, size=9))
    keys = []
    real = eng._call
    monkeypatch.setattr(eng, "_call", lambda key, args, **kw: (
        keys.append(key) or real(key, args, **kw)))
    turn = {"now": 0}
    monkeypatch.setattr(tracing, "capturing", lambda: 4 <= turn["now"] < 10)
    sch = DecodeScheduler(eng, start=False)
    futs, records = [], []
    while len(futs) < len(prompts) or sch._has_work():
        while len(futs) < len(prompts) and due[len(futs)] <= turn["now"]:
            i = len(futs)
            futs.append(sch.submit(prompts[i], max_new_tokens=max_new[i]))
        records.append(sch.step())
        turn["now"] += 1
        assert turn["now"] < 200
    sch.close(drain=True)
    monkeypatch.undo()
    return [f.result(0) for f in futs], keys, records


def test_the_scheduler_fuses_and_every_request_gets_the_sametokens(
        monkeypatch):
    """A scheduler over an engine that fuses gives every request the
    tokens of one over the same model with no ``turn_logits``, and no
    executable is compiled after warm-up; the unfused engine dispatches
    no ``decode_fill``.  ``stats()["prefill"]`` (and its traced twin:
    what was dispatched under a capture) counts every chunk dispatch in
    ``runs``, the fused ones in ``fused``, and ``fused_share`` is their
    ratio; the step record's ``prefill_fused`` sums to ``fused``."""
    got = {}
    for kind, cls in (("fused", FalconH1), ("unfused", _Unfused)):
        eng = families.engine(cls(config("falcon_h1_34b"), seed=5,
                                  dtype="float32"))
        warm = eng.warmup([8, 16])
        assert [k for k in warm if k.startswith("decode_fill")] == (
            ["decode_fill_b16", "decode_fill_b32"] if kind == "fused"
            else [])
        compiled = eng.compiles
        got[kind] = _schedule(eng, monkeypatch) + (eng.stats(),)
        assert eng.compiles == compiled and eng.cache.pages_used() == 0
    tokens, keys, records, stats = got["fused"]
    assert tokens == got["unfused"][0]
    assert not any(k.startswith("decode_fill") for k in got["unfused"][1])
    fills = [k for k in keys if k.startswith("decode_fill")]
    prefills = [k for k in keys if k.startswith("prefill")]
    assert fills and prefills
    life = stats["prefill"]
    assert life["fused"] == len(fills) == sum(
        r["prefill_fused"] for r in records)
    assert life["runs"] == len(fills) + len(prefills) == sum(
        r["prefill_runs"] for r in records)
    assert life["fused_share"] == life["fused"] / life["runs"]
    traced = stats["traced"]["prefill"]
    assert 0 < traced["runs"] < life["runs"]
    assert traced["fused_share"] == traced["fused"] / traced["runs"]
    unfused = got["unfused"][3]["prefill"]
    assert (unfused["fused"], unfused["fused_share"]) == (0, 0.0)
    assert unfused["chunks"] == life["chunks"]


def test_a_kernel_is_traced_once_for_every_layer_and_executable():
    """The Pallas kernels' wrappers and a lane's chunked scan are
    jitted on all but their arrays, so the layers of a step share ONE
    trace of each, the decode step with lanes inside it shares the
    decode step's kernels (the same grid of slots), and a prefill
    dispatch of the full chunk shares its lanes' scan (the same
    bucket): what keeps two more executables from costing a warm start
    every layer's tracing again (PERF.md, PR 40)."""
    model = build("hybrid")
    eng = families.engine(model, **FOUR)
    args = (model.params, eng.cache.pool, eng._resident)
    staged = eng._stage(eng.cache, (), 2, CHUNK)
    decode = jax.make_jaxpr(functools.partial(E._chained_decode_core,
                                              model))(*args)
    fill = jax.make_jaxpr(functools.partial(
        E._turn_core, model, eng.cache.pages_per_slot))(*args, staged)
    prefill = jax.make_jaxpr(eng._core(f"prefill_b{2 * CHUNK}"))(
        model.params, eng.cache.pool, staged)

    def traces(closed):
        """The inner jaxprs of the jitted calls, by name."""
        out = {}
        for eqn in closed.jaxpr.eqns:
            if eqn.primitive.name == "jit":
                out.setdefault(eqn.params["name"], []).append(
                    eqn.params["jaxpr"])
        return out

    step, turn, lanes = traces(decode), traces(fill), traces(prefill)
    layers = model.n_layers
    # one shape each, but rope: the queries' heads and the keys'
    for name, shapes in (("_ssm_update_jit", 1), ("_paged_attention_jit", 1),
                         ("_rope_jit", 2)):
        assert len(step[name]) == len(turn[name]) == shapes * layers, name
        assert len({id(j) for j in step[name]}) == shapes, name
    # the slots' own kernels: the decode step's traces, reused
    for name in ("_ssm_update_jit", "_paged_attention_jit"):
        assert turn[name][0] is step[name][0], name
    # the lanes' scan: one trace for both lanes of every layer, the
    # prefill dispatch's
    scans = turn["ssm_chunk_scan"]
    assert len(scans) == len(lanes["ssm_chunk_scan"]) == 2 * layers
    assert {id(j) for j in scans} == {id(lanes["ssm_chunk_scan"][0])}
