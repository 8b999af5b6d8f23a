"""A prefill dispatch has lanes: several slots' next chunks in ONE call
of a prefill executable, the weights read once for all of them.

Held here, for each of the decode plane's four models at CPU sizes: a
dispatch of lanes leaves the cache (pages, recurrent state, convolution
tails) and the first tokens that the same chunks leave when they are fed
one lane at a time, which is the ``lanes == 1`` case of the same code and
what the models' own files pin to their dense oracles; a padding lane
writes nothing; and the engine's rule for how many lanes a dispatch has.
"""
import numpy as onp
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx  # noqa: F401  (registers ops + kernel specs)
from mxnet_tpu.serving import DecodeEngine, DecodeModel
from mxnet_tpu.serving.decode.paged_kv import PageAllocator

from decode_families import ALL, CHUNK, build, engine, tokens


@pytest.fixture(scope="module")
def engines():
    """One engine a family for the whole file: its executables are
    compiled once, and every case starts it from an empty cache."""
    made = {}

    def get(family):
        if family not in made:
            made[family] = engine(build(family), max_slots=4,
                                  num_pages=32)
        eng = made[family]
        for slot in range(eng.max_slots):
            eng.release_slot(slot)
        # the same slots get the same pages in every case
        eng.cache.allocator = PageAllocator(eng.num_pages)
        eng.cache.pool = jax.tree_util.tree_map(jnp.zeros_like,
                                                eng.cache.pool)
        return eng

    return get


def _feed(eng, prompts, turns, together):
    """Admit ``prompts`` (slot ``i`` holds ``prompts[i]``) and feed
    their chunks turn by turn: ``turns[t]`` lists the slots that get
    their next chunk in turn ``t``, in one ``prefill_chunks`` call when
    ``together`` and one call a chunk when not.  Returns every chunk's
    token in order, the cache as host arrays, and the dispatches."""
    for slot, prompt in enumerate(prompts):
        eng.acquire_slot(slot, len(prompt) + 4)
    done = [0] * len(prompts)
    toks, runs = [], eng.prefill_runs
    for slots in turns:
        chunks = []
        for slot in slots:
            chunks.append((slot, prompts[slot][done[slot]:done[slot] + CHUNK],
                           done[slot]))
            done[slot] += len(chunks[-1][1])
        calls = [chunks] if together else [[c] for c in chunks]
        for call in calls:
            toks += [int(t) for t in eng.prefill_chunks(call)]
    assert done == [len(p) for p in prompts]
    return toks, jax.device_get(eng.cache.pool), eng.prefill_runs - runs


# name -> (prompt lengths by slot, the slots fed in each turn)
CASES = {
    # a full chunk that is not its prompt's last beside a short final one
    "mixed_lengths": ((40, 5), [(0, 1), (0,), (0,)]),
    # three chunks of three lengths in four lanes: one lane is padding
    "three_in_four_lanes": ((16, 9, 1), [(0, 1, 2)]),
    # later chunks of one prompt beside other slots' first ones: one,
    # then two, then three lanes (padded to four), then what is left
    "later_chunks_beside_first": ((40, 20, 7), [(0,), (0, 1), (0, 1, 2)]),
    # four slots fill in one turn, twice: the most a dispatch carries
    "four_slots_two_turns": ((17, 32, 24, 31), [(0, 1, 2, 3)] * 2),
}


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("family", ALL)
def test_lanes_leave_what_one_lane_at_a_time_leaves(engines, family, case):
    """The same first tokens and, to rounding, the same pages, state
    and convolution tails, whether a turn's chunks ride as lanes of one
    dispatch or each in a dispatch of its own; and every prompt's first
    token is the dense oracle's."""
    lengths, turns = CASES[case]
    prompts = [tokens(n, seed=7 * n + i) for i, n in enumerate(lengths)]
    want_toks, want_pool, want_runs = _feed(engines(family), prompts, turns,
                                            together=False)
    eng = engines(family)
    toks, pool, runs = _feed(eng, prompts, turns, together=True)
    assert toks == want_toks
    assert runs == len(turns) < want_runs == sum(len(t) for t in turns)
    for got, want in zip(jax.tree_util.tree_leaves(pool),
                         jax.tree_util.tree_leaves(want_pool)):
        assert onp.abs(want).max() > 0
        onp.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    # the token after a prompt's last chunk is the oracle's first token
    at = 0
    done = [0] * len(prompts)
    for slots in turns:
        for slot in slots:
            done[slot] += CHUNK
            if done[slot] - CHUNK < len(prompts[slot]) <= done[slot]:
                assert toks[at] == eng.model.greedy_reference(
                    prompts[slot], 1)[0], (slot, at)
            at += 1


@pytest.mark.parametrize("family", ALL)
def test_a_padding_lane_writes_nothing(engines, family):
    """A dispatch of padding alone (what ``warmup`` stages) hands back
    the cache bit for bit: no page, no state, no convolution tail
    (padding beside real lanes: the case ``three_in_four_lanes``)."""
    eng = engines(family)
    rng = onp.random.RandomState(3)
    eng.cache.pool = jax.tree_util.tree_map(
        lambda a: jnp.asarray(rng.randn(*a.shape), a.dtype), eng.cache.pool)
    before = jax.device_get(eng.cache.pool)
    for lanes in (2, 4):
        key = f"prefill_b{lanes * CHUNK}"
        eng.cache.pool, toks = eng._call(key, (
            eng.model.params, eng.cache.pool,
            eng._stage(eng.cache, (), lanes, CHUNK)))
        assert len(toks) == lanes
    for got, want in zip(jax.tree_util.tree_leaves(
            jax.device_get(eng.cache.pool)),
            jax.tree_util.tree_leaves(before)):
        assert onp.array_equal(got, want)


@pytest.mark.parametrize("chunk,slots,lanes", [
    (128, 96, 2), (256, 192, 1), (512, 8, 1), (64, 96, 4), (16, 4, 4),
    (16, 3, 2), (16, 1, 1), (96, 8, 2), (8, 200, 32)])
def test_lanes_come_from_shapes(chunk, slots, lanes):
    """At most 256 rows a dispatch, no more lanes than slots, a power
    of two: two lanes at the chat cells' chunk of 128, one at the
    reasoning cells' 256."""
    eng = DecodeEngine(DecodeModel(16, dim=8, n_heads=2, n_layers=1),
                       max_slots=slots, num_pages=slots, page_size=8,
                       prefill_chunk=chunk, prefill_floor=8)
    assert eng.prefill_lanes == lanes


@pytest.mark.parametrize("chunks,shapes", [
    (1, [(1, None)]), (2, [(2, 64)]), (3, [(4, 64)]), (4, [(4, 64)]),
    (5, [(4, 64), (1, None)]), (7, [(4, 64), (4, 64)])])
def test_more_filling_slots_than_lanes_take_further_dispatches(chunks,
                                                               shapes):
    """A group of one keeps its own pow2 bucket; more than one lane
    implies the full chunk's bucket and a pow2 of lanes, so
    ``prefill_b<lanes x bucket>`` names one shape only."""
    eng = DecodeEngine(DecodeModel(16, dim=8, n_heads=2, n_layers=1),
                       max_slots=8, num_pages=64, page_size=8,
                       prefill_chunk=64, prefill_floor=4)
    assert eng.prefill_lanes == 4
    for slot in range(chunks):
        eng.acquire_slot(slot, 8)
    seen = []
    real = eng._call
    eng._call = lambda key, args, **kw: (seen.append((key, args[2].shape))
                                         or real(key, args, **kw))
    toks = eng.prefill_chunks([(s, [1, 2, 3], 0) for s in range(chunks)])
    assert len(toks) == chunks
    width = 3 + eng.cache.pages_per_slot
    assert seen == [(f"prefill_b{lanes * (bucket or 4)}",
                     (lanes, (bucket or 4) + width))
                    for lanes, bucket in shapes]
    stats = eng.stats()["prefill"]
    assert (stats["runs"], stats["chunks"]) == (len(shapes), chunks)
    assert stats["rows"] == sum(l * (b or 4) for l, b in shapes)
    assert stats["chunks_per_run"] == chunks / len(shapes)
