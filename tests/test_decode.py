"""Autoregressive decode plane (mxnet_tpu/serving/decode/): paged KV
cache, continuous batching, speculative decode.

Tier-1 acceptance lives here, all in-process (CPU, no sockets), beside
what every model of the plane must pass (``test_decode_contract.py``):

- the page allocator recycles freed pages and fails atomically on
  exhaustion; the paged-attention kernel matches the gather-based
  oracle across ragged lengths including a length-0 slot;
- scheduler output is token-identical to the dense
  ``greedy_reference`` oracle across ragged prompts, eos and max_new;
- the fixed-shape contract: admissions/evictions after warmup never
  recompile (``engine.compiles`` stays flat across a second wave with
  staggered arrivals);
- greedy speculative decode is token-identical to the plain path with
  a matched draft (every proposal accepted) AND a mismatched draft;
- lifecycle: ``close(drain=True)`` completes in-flight work,
  ``close(drain=False)`` fails it with ``ServingClosedError`` and
  frees every page, per-request deadlines expire queued requests and
  evict running slots (``decode.evictions``);
- the pre-admission reject matrix + the batch-engine zero-size fixes;
- report reconciliation: telemetry_report / slo_report decode
  sections rebuild the run from the JSONL step records; a breached
  TTFT objective burns with cause ``ttft_slo``.
"""
import importlib.util
import json
import pathlib
import re
import time

import numpy as onp
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx  # noqa: F401  (registers ops + kernel specs)
from mxnet_tpu import profiler, telemetry, tracing
from mxnet_tpu.serving import (BadRequestError, DecodeModel,
                               DecodeScheduler, QueueFullError,
                               RequestTimeoutError, ServingClosedError,
                               ServingServer, slo)
from mxnet_tpu.serving.decode import OutOfPagesError
from mxnet_tpu.serving.decode.paged_kv import (PageAllocator, PagedKVCache,
                                               uniform_layout)

from decode_families import build, engine, paged_oracle, run
from decode_families import clean  # noqa: F401  (a fixture)

VOCAB = 48
REPO = pathlib.Path(__file__).resolve().parent.parent
pytestmark = pytest.mark.usefixtures("clean")


@pytest.fixture(scope="module")
def model():
    return DecodeModel(VOCAB, dim=32, n_heads=4, n_layers=2, seed=0)


@pytest.fixture(scope="module")
def draft():
    """Different architecture AND seed: near-zero accept rate, output
    must still be token-identical (the verify pass is the target)."""
    return DecodeModel(VOCAB, dim=16, n_heads=2, n_layers=1, seed=7)


# the engines of this file: four slots, 32 pages of 8, and the engine's
# own defaults of chunk, floor and pages a slot
PLAIN = dict(max_slots=4, num_pages=32, page_size=8, pages_per_slot=None,
             prefill_chunk=None, prefill_floor=16)


def _sched(eng, **kw):
    kw.setdefault("start", False)
    return DecodeScheduler(eng, **kw)


def _prompts(n, lo=3, hi=12, seed=1):
    rs = onp.random.RandomState(seed)
    return [[int(t) for t in rs.randint(0, VOCAB,
                                        size=rs.randint(lo, hi + 1))]
            for _ in range(n)]


def _gen(sch, prompts, max_new=8, **kw):
    futs = [sch.submit(p, max_new_tokens=max_new, **kw) for p in prompts]
    run(sch)
    return [f.result(0) for f in futs]


# -- page allocator / paged KV cache ----------------------------------------

def test_page_allocator_recycle_and_exhaustion():
    al = PageAllocator(4)
    a = al.alloc(3)
    assert len(a) == 3 and al.available == 1 and al.used == 3
    with pytest.raises(OutOfPagesError):
        al.alloc(2)
    assert al.available == 1            # failed alloc is atomic
    al.free(a)
    assert al.available == 4
    b = al.alloc(4)
    assert sorted(b) == sorted(set(b))  # recycled, no duplicates
    al.free(b)


def test_paged_kv_slot_acquire_release():
    # pool deliberately smaller than max_slots * pages_per_slot so a
    # full-budget acquire can exhaust the free list
    c = PagedKVCache(layout=uniform_layout(2, (16, 16)), num_pages=6,
                     page_size=4, max_slots=2, pages_per_slot=4)
    assert c.slot_capacity == 4 * 4     # pages_per_slot * page_size
    c.acquire(0, 9)                     # 9 tokens → 3 pages
    assert c.pages_used() == 3
    with pytest.raises(OutOfPagesError):
        c.acquire(1, 16)                # needs 4, only 3 free
    assert c.pages_used() == 3          # failed acquire is atomic
    with pytest.raises(mx.base.MXNetError):
        c.acquire(1, 17)                # over per-slot capacity
    freed = c.release(0)
    assert freed == 3 and c.pages_used() == 0
    c.acquire(1, 16)                    # recycled pages serve a new slot
    assert c.pages_used() == 4
    assert c.release(1) == 4 and c.release(1) == 0
    assert c.pages_used() == 0


# (page_size, pages_per_slot, block_k, lengths): block_k None leaves it to
# the registry.  A slot's capacity is page_size * pages_per_slot.
_PA_CASES = {
    "ragged": (4, 3, None, [5, 0, 12]),
    # the walk's edges at a block of 1, 2 and 4 pages of 16: nothing,
    # one row, a whole block, a block and a row, the slot's capacity
    "block_1_page": (16, 8, 16, [0, 1, 16, 17, 128]),
    "block_2_pages": (16, 8, 32, [0, 1, 32, 33, 128]),
    "block_4_pages": (16, 8, 64, [0, 1, 64, 65, 128]),
    # a block wider than the table: cut to the slot's pages
    "block_past_table": (16, 2, 128, [32, 3, 0, 17]),
    # a table no whole number of blocks: the tail block's last page is
    # past the table's width
    "table_of_3_pages": (16, 3, 32, [48, 33, 0, 1]),
    "half_a_page_of_128": (128, 2, 64, [0, 1, 64, 65, 128, 129, 256]),
    "all_idle": (16, 4, 32, [0, 0, 0, 0]),
    "live_first": (16, 4, 32, [37, 0, 0, 0, 0]),
    "live_last": (16, 4, 32, [0, 0, 0, 0, 37]),
    "live_alternating": (16, 4, 32, [0, 9, 0, 64, 0, 33, 0]),
}
# A lightly loaded server: 96 slots, one or two of them live, through
# each body: (query heads, KV heads, head width) of multi-head attention
# folded into the lanes, grouped-query heads of a lane tile, and
# grouped-query heads packed two KV heads a tile.  A case without heads
# runs 2 heads of 8, folded.
SERVER_SLOTS = 96
_PA_SERVER = {"one_middle": {47: 37}, "one_first": {0: 64},
              "one_last": {95: 17}, "two_apart": {3: 50, 91: 33}}
_PA_BODIES = {"folded": (4, 4, 64), "lanes": (4, 2, 128),
              "packed": (8, 2, 64)}


def _server_lengths(live):
    return [live.get(s, 0) for s in range(SERVER_SLOTS)]


_PA_CASES.update({
    f"server_{name}_{body}": (16, 4, 32, _server_lengths(live), heads)
    for name, live in _PA_SERVER.items()
    for body, heads in _PA_BODIES.items()})


def _pa_args(ps, p_, lengths, heads=(2, 2, 8)):
    """Random queries and pools for ``lengths``; the page tables are a
    random permutation of the pool."""
    rs = onp.random.RandomState(3)
    (h, kvh, d), s_ = heads, len(lengths)
    pages = s_ * p_ + 3
    q = jnp.asarray(rs.randn(s_, h, d), jnp.float32)
    kp = jnp.asarray(rs.randn(pages, ps, kvh, d), jnp.float32)
    vp = jnp.asarray(rs.randn(pages, ps, kvh, d), jnp.float32)
    tables = jnp.asarray(
        rs.permutation(pages)[:s_ * p_].reshape(s_, p_), jnp.int32)
    return q, kp, vp, tables, jnp.asarray(lengths, jnp.int32)


@pytest.mark.parametrize("case", sorted(_PA_CASES))
def test_paged_attention_ragged_parity_vs_oracle(case):
    """Kernel vs gather-oracle over ragged lengths, including an
    inactive (length-0) slot, through the public entry point; the page
    tables are a random permutation of the pool.  Every idle slot's
    output is exact zeros, though the kernel walks the live slots
    alone."""
    from mxnet_tpu.ops.paged_attention import paged_attention
    ps, p_, block_k, lengths, *heads = _PA_CASES[case]
    q, kp, vp, tables, lengths = _pa_args(ps, p_, lengths, *heads)
    out = paged_attention(q, kp, vp, tables, lengths, block_k=block_k)
    ref = paged_oracle(q, kp, vp, tables, lengths)
    onp.testing.assert_allclose(onp.asarray(out), onp.asarray(ref),
                                rtol=2e-4, atol=2e-4)
    idle = onp.asarray(lengths) == 0
    assert not onp.asarray(out)[idle].any()    # length-0 slot → zeros
    assert onp.asarray(out)[~idle].any(axis=(1, 2)).all()


def _walk_of(fn, *args):
    """The one Pallas call of ``fn(*args)`` and the values of its
    operands (the grid's dynamic bounds first), the jaxpr evaluated up
    to the call through every jit around it."""
    from jax.extend.core import ClosedJaxpr, jaxpr_as_fun

    def find(closed, vals):
        jaxpr = closed.jaxpr
        for i, eqn in enumerate(jaxpr.eqns):
            sub = eqn.params.get("jaxpr")
            if eqn.primitive.name != "pallas_call" and not (
                    isinstance(sub, ClosedJaxpr) and any(
                        e.primitive.name == "pallas_call"
                        for e in _equations(sub.jaxpr))):
                continue
            head = ClosedJaxpr(jaxpr.replace(eqns=jaxpr.eqns[:i],
                                             outvars=list(eqn.invars)),
                               closed.consts)
            ins = jaxpr_as_fun(head)(*vals)
            return (eqn, ins) if eqn.primitive.name == "pallas_call" \
                else find(sub, ins)

    return find(jax.make_jaxpr(fn)(*args), args)


@pytest.mark.parametrize("live", ["two_apart", "none"])
@pytest.mark.parametrize("body", [*_PA_BODIES, "latent"])
def test_the_walk_is_bounded_by_the_live_slots(body, live):
    """The kernel's grid has one dynamic bound, the count of live slots,
    and its scalar-prefetched slot list names them in slot order: a
    server with 2 of 96 slots live takes 2 grid steps, an empty one
    the one step that zeroes the output; never one step a slot of the
    table."""
    from mxnet_tpu import kernels
    from mxnet_tpu.ops.paged_attention import latent_attention, \
        paged_attention
    lengths = _server_lengths(_PA_SERVER.get(live, {}))
    if body == "latent":
        (q, pool, tables, _), kw = kernels.get_kernel(
            "latent_attention").make_args(dict(
                slots=SERVER_SLOTS, pages_per_slot=4, page_size=16, h=4,
                rank=128, rope=64))
        args = (q, pool, tables, jnp.asarray(lengths, jnp.int32))
        eqn, ins = _walk_of(lambda *a: latent_attention(*a, block_k=32,
                                                        **kw), *args)
    else:
        args = _pa_args(16, 4, lengths, _PA_BODIES[body])
        eqn, ins = _walk_of(lambda *a: paged_attention(*a, block_k=32),
                            *args)
    grid = eqn.params["grid_mapping"]
    assert len(grid.grid) == 1 and grid.num_dynamic_grid_bounds == 1
    bound, _, _, order, count = (onp.asarray(x) for x in ins[:5])
    slots = [s for s, n in enumerate(lengths) if n]
    assert int(count[0]) == len(slots)
    assert int(bound) == max(len(slots), 1)
    assert order.shape == (SERVER_SLOTS,)
    assert order[:len(slots)].tolist() == slots


# -- the pool's layout: one whole buffer per layer for K and for V -----------

def _equations(jaxpr):
    """Every equation of ``jaxpr``, nested ones included.  A Pallas
    call's body is the kernel's own (blocks, not buffers) and is not
    entered."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for sub in eqn.params.values():
            sub = getattr(sub, "jaxpr", sub)
            if hasattr(sub, "eqns"):
                yield from _equations(sub)


def _whole_buffer_equations(jaxpr, nelem):
    """Primitive names of every equation that produces an array of at
    least ``nelem`` elements."""
    return [eqn.primitive.name for eqn in _equations(jaxpr)
            if any(getattr(v.aval, "size", 0) >= nelem
                   for v in eqn.outvars)]


def _core_call(eng, core):
    """One of the model's traced cores and abstract-enough arguments
    for it, past ``params`` and ``pool``."""
    slots, mdl = eng.max_slots, eng.model
    ints = jnp.zeros((slots,), jnp.int32)
    tables, act = eng._tables(eng.cache), jnp.ones((slots,), bool)
    if core == "decode":
        return mdl.decode_core, (ints, ints, tables, act)
    if core == "verify":
        return mdl.verify_core, (jnp.zeros((slots, 3), jnp.int32), ints,
                                 tables, act)
    return mdl.prefill_core, (jnp.zeros((1, 8), jnp.int32),
                              jnp.zeros((1,), jnp.int32),
                              jnp.full((1,), 5, jnp.int32),
                              tables[:1], jnp.zeros((1,), jnp.int32))


@pytest.mark.parametrize("family,core", [
    ("transformer", "decode"), ("transformer", "prefill"),
    ("transformer", "verify"), ("hybrid", "decode"), ("hybrid", "prefill")])
def test_cores_touch_a_layer_buffer_only_by_its_scatter(model, family, core):
    """No core slices a layer's K or V out of a larger array, reshapes
    it or sets it back: per layer the only equations as large as a
    buffer are the two scatters of the paged format's one write and,
    for a model with recurrent state, the update of the state-space
    state (the kernel's aliased output in a decode step, the lanes'
    slots' rows set back in one scatter after a prefill dispatch).  (A slice of buffer size is a 201 MB
    copy in front of the Mosaic call on the chip.)"""
    if family == "hybrid":
        # 8 slots x (4 heads x 16 x 16) of state, as large as a K/V buffer
        model, kw = build("hybrid"), dict(max_slots=8, num_pages=32)
        # the kernel's call, and the jit around it on its static
        # arguments (PR 40), in a decode step
        state = {"decode": ["jit", "pallas_call"],
                 "prefill": ["scatter"]}[core]
    else:
        kw, state = {}, []
    # a fraction of the pool per slot table, so no gather is as large
    # as a buffer
    eng = engine(model, PLAIN, pages_per_slot=4, **kw)
    pool = eng.cache.pool
    assert all(buf.size >= pool[0][0].size for buf in pool[0][:3])
    fn, args = _core_call(eng, core)
    jaxpr = jax.make_jaxpr(fn)(model.params, pool, *args)
    big = _whole_buffer_equations(jaxpr.jaxpr, pool[0][0].size)
    assert sorted(big) == sorted(
        (["scatter"] * 2 + state) * model.n_layers), big


def _kernel_blocks(jaxpr):
    """Rows of the K block (a VMEM scratch ``(2, block_k, Hkv*D)``) of
    every ``paged_attention`` kernel call in ``jaxpr``."""
    found = []
    for eqn in _equations(jaxpr):
        if (eqn.primitive.name == "pallas_call"
                and eqn.params["name"] == "mxtpu_paged_attention"):
            inner = eqn.params["jaxpr"]
            n = eqn.params["grid_mapping"].num_scratch_operands
            found.append(inner.invars[len(inner.invars) - n + 3]
                         .aval.shape[1])
    return found


@pytest.mark.parametrize("family", ["transformer", "hybrid"])
@pytest.mark.parametrize("page_size,rows", [(16, 64), (128, 128)])
def test_paged_kernel_block_follows_the_page_size(model, family, page_size,
                                                  rows):
    """The block is the paged format's choice, not a model's: four
    pages of 16 (the kernel registry's default of 64 rows), one page of
    128 (``gpt2_decode_chat`` and ``falcon_h1_decode_chat``), whatever
    model asks, in the decode step and in every offset of a verify
    window."""
    if family == "hybrid":
        model = build("hybrid")
    eng = engine(model, PLAIN, page_size=page_size, num_pages=16,
                  pages_per_slot=4)
    for core in ["decode"] + ["verify"] * (family == "transformer"):
        fn, args = _core_call(eng, core)
        blocks = _kernel_blocks(jax.make_jaxpr(fn)(
            model.params, eng.cache.pool, *args).jaxpr)
        width = 3 if core == "verify" else 1
        assert blocks == [rows] * (model.n_layers * width), blocks


@pytest.mark.parametrize("spec", [False, True], ids=["plain", "spec"])
def test_pool_is_one_k_and_one_v_buffer_per_layer(model, draft, spec):
    """What every executable is handed and hands back: a tuple of
    ``(k, v)`` per layer, each ``(num_pages, page_size, H*D)``, the same
    structure after a prefill chunk and a decode (or speculative) step,
    and the step's rows written where the page table points."""
    import jax
    eng = engine(model, PLAIN, **(dict(draft_model=draft, spec_k=2)
                            if spec else {}))
    caches = [(eng.cache, model)]
    if spec:
        caches.append((eng.draft_cache, draft))
    before = [jax.tree_util.tree_structure(c.pool) for c, _ in caches]
    prompt = _prompts(1, lo=5, hi=5, seed=11)[0]
    eng.acquire_slot(0, 8)      # holds page 0 and stays inactive
    eng.acquire_slot(1, len(prompt) + 4)
    tok, = eng.prefill_chunks([(1, prompt, 0)])
    if spec:
        tokens = onp.zeros((eng.max_slots,), onp.int32)
        pos = onp.zeros((eng.max_slots,), onp.int32)
        active = onp.zeros((eng.max_slots,), bool)
        tokens[1], pos[1], active[1] = tok, len(prompt), True
        eng.spec_step(tokens, pos, active)
    else:
        eng.activate_slot(1, tok, len(prompt))
        eng.decode_step()
    for (c, m), structure in zip(caches, before):
        assert jax.tree_util.tree_structure(c.pool) == structure
        assert [len(pair) for pair in c.pool] == [2] * m.n_layers
        shape = (eng.num_pages, eng.page_size, m.n_heads * m.head_dim)
        assert all(leaf.shape == shape
                   for leaf in jax.tree_util.tree_leaves(c.pool))
        # the prompt and the step's positions (one, or the window of
        # spec_k + 1) in the slot's first page; every other page, the
        # inactive slots' among them, untouched: their rows were dropped
        first = c.slot_pages(1)[0]
        written = len(prompt) + (eng.spec_k + 1 if spec else 1)
        for leaf in jax.tree_util.tree_leaves(c.pool):
            rows = onp.asarray(leaf).any(axis=-1)      # (pages, page_size)
            assert rows[first, :written].all()
            assert not rows[first, written:].any()
            rows[first] = False
            assert not rows.any()
    eng.release_slot(0)
    eng.release_slot(1)


# -- continuous batching vs the dense oracle --------------------------------

@pytest.mark.parametrize("prompts,max_new,want,chained,edits", [
    # slot 0: a prompt of 6, then positions 6..10; slot 1: a prompt of
    # 15 in two chunks of 8, so from the second turn, positions 15, 16.
    # Pages of 8 under each turn's lengths (position + 1):
    # 1, 1+2, 2+3, 2, 2; the last turn dispatches nothing and commits
    # the one before.  Edits of the resident state: slot 0 switched on,
    # slot 1 on, slot 1 off behind its third token's step, slot 0 off
    ([6, 15], [6, 3], [1, 3, 5, 2, 2, 0], [0, 1, 1, 1, 1, 0],
     [1, 1, 1, 0, 1, 0]),
    # alone, its first turn decodes nothing
    ([15], [3], [0, 2, 3, 0], [0, 0, 1, 0], [0, 1, 1, 0]),
], ids=["two_slots", "prefill_only_turn"])
def test_kv_live_share_counts_the_pages_under_live_lengths(
        model, prompts, max_new, want, chained, edits):
    """The step record's ``kv_live_share`` and its running mean in
    ``engine.stats()`` against lengths counted by hand, ``chained`` and
    ``state_edits`` beside it; the keys the benchmark reads stay where
    they were."""
    eng = engine(model, PLAIN, prefill_chunk=8, prefill_floor=8)
    sch = _sched(eng)
    table = eng.max_slots * eng.cache.pages_per_slot
    assert eng.stats()["kv_live_share"] == 0.0
    assert eng.stats()["chained_share"] == 0.0
    for n, m in zip(prompts, max_new):
        sch.submit(list(range(1, n + 1)), max_new_tokens=m)
    records = []
    while sch._has_work():
        records.append(sch.step())
    sch.close(drain=True)
    assert [r["kv_live_share"] for r in records] == [
        round(w / table, 6) for w in want]
    assert [r["chained"] for r in records] == chained
    assert [r["state_edits"] for r in records] == edits
    decoded = [w for w in want if w]
    assert eng.stats()["kv_live_share"] == pytest.approx(
        sum(decoded) / len(decoded) / table)
    assert eng.stats()["chained_share"] == pytest.approx(
        sum(chained) / len(decoded))
    assert eng.stats()["state_edits"] == sum(edits)
    for r in records:
        assert {"tokens", "step_ms", "slots_active",
                "queue_depth"} <= set(r)
    assert sum("ttft_ms" in r for r in records) == len(prompts)
    # a record counts tokens where they are committed, a turn after
    # their dispatch: nothing in the first, the last one's in the last
    assert sum(r["tokens"] for r in records) == sum(max_new)
    assert records[0]["tokens"] == 0 and records[-1]["tokens"] > 0


def test_eos_stops_generation(model):
    p = _prompts(1, seed=4)[0]
    ref = model.greedy_reference(p, 12)
    eos = ref[3]                        # cut mid-stream
    sch = _sched(engine(model, PLAIN))
    got = _gen(sch, [p], max_new=12, eos=eos)[0]
    sch.close(drain=True)
    assert got == model.greedy_reference(p, 12, eos=eos)
    cut = ref.index(eos)                # first occurrence stops it
    assert got == ref[:cut + 1] and got[-1] == eos


def test_warm_admissions_never_recompile(model):
    """The fixed-shape contract: after warm-up has compiled the
    prefill bucket, the multi-lane prefills and the decode executables,
    neither a first wave (three slots filling in one turn: a dispatch
    of two lanes and one of one) nor a second with staggered admissions
    (requests joining mid-flight: one lane, then two) adds a compile, and every
    page returns to the free list."""
    eng = engine(model, PLAIN)
    sch = _sched(eng)
    prompts = _prompts(6, lo=3, hi=8, seed=5)   # one pow2 bucket
    eng.warmup([8])
    warm = eng.compiles
    _gen(sch, prompts[:3], max_new=6)
    assert eng.compiles == warm
    assert warm > 0 and eng.cache.pages_used() == 0
    futs = [sch.submit(prompts[3], max_new_tokens=6)]
    sch.step()                          # admit + begin while others queue
    futs += [sch.submit(p, max_new_tokens=6) for p in prompts[4:]]
    run(sch)
    assert [f.result(0) for f in futs] == [
        model.greedy_reference(p, 6) for p in prompts[3:]]
    assert eng.compiles == warm         # steady state: 0 new compiles
    assert eng.cache.pages_used() == 0
    sch.close(drain=True)


def test_a_burst_fills_its_slots_in_one_prefill_dispatch(model, monkeypatch):
    """Warmed as the benchmark's drivers warm an engine (every one-lane
    bucket from the floor to the chunk, nothing said of lanes), a burst
    that fills four slots in one turn is ONE prefill dispatch of four
    lanes and compiles nothing; the turn after carries the two prompts
    that have a chunk left as two lanes, the last turn one; the step
    record counts the dispatches beside the tokens, and
    ``stats()["prefill"]`` and its traced twin count runs, chunks and
    rows (the twin: what was dispatched under a capture)."""
    eng = engine(model, PLAIN, prefill_chunk=16, prefill_floor=8)
    assert eng.warmup([8, 16]) == ["decode", "state_edit", "prefill_b8",
                                   "prefill_b16", "prefill_b32",
                                   "prefill_b64"]
    compiled = eng.compiles
    on = {"now": False}
    monkeypatch.setattr(tracing, "capturing", lambda: on["now"])
    sch = _sched(eng)
    assert eng.prefill_lanes == 4           # 256 // 16, and four slots
    rs = onp.random.RandomState(21)
    prompts = [[int(t) for t in rs.randint(0, VOCAB, size=n)]
               for n in (5, 20, 16, 37)]
    futs = [sch.submit(p, max_new_tokens=3) for p in prompts]
    turns = [sch.step()]
    on["now"] = True            # the capture covers the second turn
    turns.append(sch.step())
    on["now"] = False
    turns.append(sch.step())
    assert [(t["prefill_runs"], t["prefill_tokens"]) for t in turns] == [
        (1, 5 + 16 + 16 + 16), (1, 4 + 16), (1, 5)]
    run(sch)
    assert [f.result(0) for f in futs] == [
        model.greedy_reference(p, 3) for p in prompts]
    assert eng.compiles == compiled
    stats = eng.stats()
    assert stats["prefill"] == {"runs": 3, "chunks": 7,
                                "rows": 4 * 16 + 2 * 16 + 8, "fused": 0,
                                "chunks_per_run": 7 / 3, "fused_share": 0.0}
    assert stats["traced"]["prefill"] == {"runs": 1, "chunks": 2,
                                          "rows": 32, "fused": 0,
                                          "chunks_per_run": 2.0,
                                          "fused_share": 0.0}
    assert engine(model, PLAIN).stats()["prefill"]["chunks_per_run"] == 0.0
    sch.close(drain=True)


# -- the chained turn: dispatch n, then read and commit n-1 -------------------

@pytest.fixture(scope="module")
def warm(model):
    """One warmed engine a family, shared by the chained-turn tests:
    each leaves every slot released, and what an earlier one left in
    the resident state of an idle slot is part of the test."""
    made = {}

    def get(family):
        if family not in made:
            mdl = model if family == "transformer" else build("hybrid")
            eng = engine(mdl, PLAIN, max_slots=3, num_pages=24,
                         pages_per_slot=8, prefill_chunk=16, prefill_floor=8)
            eng.warmup([8, 16])
            made[family] = (mdl, eng)
        mdl, eng = made[family]
        assert eng.cache.pages_used() == 0 and not eng._active.any()
        # on the device as on the host: nobody decodes, no row has a page
        assert not onp.asarray(eng._resident[2]).any()
        assert not onp.asarray(eng._resident[3]).any()
        return mdl, eng

    return get


FAMILIES = pytest.mark.parametrize("family", ["transformer", "hybrid"])


def _steps_until(sch, done, limit=200):
    records = []
    while not done():
        records.append(sch.step())
        assert len(records) < limit
    return records


@FAMILIES
def test_chained_open_schedule_matches_the_oracle(warm, family):
    """Requests arriving at seeded turns into three slots, prompts of
    one and two chunks, ``max_new`` from 1 on: admissions and finishes
    while a turn is in flight, slots and pages reused.  Request by
    request the dense oracle's tokens, and those of a run that serves
    them one at a time; no executable compiled after warm-up."""
    mdl, eng = warm(family)
    compiled = eng.compiles
    rs = onp.random.RandomState(21)
    prompts = _prompts(8, lo=3, hi=30, seed=21)
    max_new = [int(n) for n in rs.randint(1, 9, size=8)]
    due = sorted(int(t) for t in rs.randint(0, 14, size=8))
    sch = _sched(eng)
    futs, turn = [], 0
    while len(futs) < len(prompts) or sch._has_work():
        while len(futs) < len(prompts) and due[len(futs)] <= turn:
            i = len(futs)
            futs.append(sch.submit(prompts[i], max_new_tokens=max_new[i]))
        sch.step()
        turn += 1
        assert turn < 200
    got = [f.result(0) for f in futs]
    assert got == [mdl.greedy_reference(p, n)
                   for p, n in zip(prompts, max_new)]
    assert got == [_gen(sch, [p], max_new=n)[0]
                   for p, n in zip(prompts, max_new)]
    sch.close(drain=True)
    assert eng.compiles == compiled and eng.cache.pages_used() == 0
    assert eng.stats()["chained_share"] > 0.5


@FAMILIES
def test_eos_with_the_next_turn_in_flight(warm, family):
    """The host learns of ``eos`` a turn after the step that emitted
    it, with one more step of the slot already dispatched: that step's
    token reaches nobody and no record, the pages return, and the
    slot's next tenant (a recurrent model's: from zero state) decodes
    its own reference."""
    mdl, eng = warm(family)
    p, successor = _prompts(2, lo=5, hi=8, seed=4)
    ref = mdl.greedy_reference(p, 12)
    eos = ref[3]
    cut = ref.index(eos)
    steps0 = eng._decode_steps
    sch = _sched(eng)
    fut = sch.submit(p, max_new_tokens=12, eos=eos)
    records = _steps_until(sch, fut.done)
    assert fut.result(0) == ref[:cut + 1]
    # tokens 2..cut+1 took a step each, and one more was in flight (two
    # where the prompt's own token ends it: it is read with the second)
    assert eng._decode_steps - steps0 == max(cut + 1, 2)
    assert sum(r["tokens"] for r in records) == cut + 1
    assert eng.cache.pages_used() == 0 and not eng._active.any()
    fut = sch.submit(successor, max_new_tokens=5)
    assert sch.step()["slots_active"] == 1 and sch._slots[0] is not None
    records = _steps_until(sch, lambda: not sch._has_work())
    assert fut.result(0) == mdl.greedy_reference(successor, 5)
    assert records[-1]["tokens"] > 0 and records[-1]["chained"] == 0
    sch.close(drain=True)


@FAMILIES
@pytest.mark.parametrize("max_new,decodes,edits", [(1, 0, 0), (2, 1, 2)])
def test_chained_shortest_requests(warm, family, max_new, decodes, edits):
    """One token: the prompt's own, no slot is ever switched on.  Two:
    on behind the last chunk, off behind the one step."""
    mdl, eng = warm(family)
    p = _prompts(1, lo=20, hi=20, seed=31)[0]      # two chunks
    steps0 = eng._decode_steps
    sch = _sched(eng)
    fut = sch.submit(p, max_new_tokens=max_new)
    records = _steps_until(sch, lambda: not sch._has_work())
    sch.close(drain=True)
    assert fut.result(0) == mdl.greedy_reference(p, max_new)
    assert eng._decode_steps - steps0 == decodes
    assert sum(r["state_edits"] for r in records) == edits
    assert [r["tokens"] for r in records] == [0, 0, max_new]
    assert len(records[-1]["ttft_ms"]) == 1 and eng.cache.pages_used() == 0


@FAMILIES
def test_deadline_eviction_with_a_turn_in_flight(warm, family):
    mdl, eng = warm(family)
    p, successor = _prompts(2, lo=5, hi=8, seed=12)
    e0 = telemetry.counter("decode.evictions").value
    sch = _sched(eng)
    fut = sch.submit(p, max_new_tokens=20, timeout_ms=60_000.0)
    for _ in range(3):
        sch.step()
    assert sch._inflight is not None and eng._active[0]
    sch._slots[0].deadline = time.perf_counter() - 1.0
    late = sch.submit(successor, max_new_tokens=4)
    rec = sch.step()        # evicts, admits the successor into the slot
    with pytest.raises(RequestTimeoutError):
        fut.result(0)
    # the step in flight was the evicted request's alone: dropped
    assert rec["evictions"] == 1 and rec["tokens"] == 0
    assert telemetry.counter("decode.evictions").value == e0 + 1
    assert sch._slots[0] is not None and sch._slots[0].future is late
    run(sch)
    assert late.result(0) == mdl.greedy_reference(successor, 4)
    assert eng.cache.pages_used() == 0
    sch.close(drain=True)


@FAMILIES
def test_a_steady_turn_uploads_nothing_and_blocks_once(
        warm, family, _traced, monkeypatch):
    """Two slots decoding, nobody arriving or leaving: the turn hands
    no host array to any executable (the transfer guard refuses one),
    stages nothing, and waits once, for the turn before."""
    mdl, eng = warm(family)
    prompts = _prompts(2, lo=4, hi=8, seed=9)
    sch = _sched(eng)
    futs = [sch.submit(p, max_new_tokens=8) for p in prompts]
    sch.step()
    sch.step()
    reads = []
    get = jax.device_get
    monkeypatch.setattr(jax, "device_get",
                        lambda x: reads.append(x) or get(x))
    tracing.enable()
    tracing.clear()
    with jax.transfer_guard_host_to_device("disallow_explicit"):
        rec = sch.step()
    names = [e["name"] for e in tracing._completed_events()]
    tracing.disable()
    monkeypatch.undo()
    assert (rec["chained"], rec["state_edits"], rec["tokens"]) == (1, 0, 2)
    assert len(reads) == 1
    assert names.count("decode.step") == names.count("decode.sync") == 1
    assert "decode.stage" not in names and "decode.prefill" not in names
    # and this backend's guard does refuse an upload
    with jax.transfer_guard_host_to_device("disallow_explicit"):
        with pytest.raises(Exception, match="host-to-device"):
            jnp.asarray(onp.zeros((3,), onp.int32))
    sch.close(drain=False)
    assert all(f.done() for f in futs)
    assert eng.cache.pages_used() == 0 and not eng._active.any()


@FAMILIES
def test_close_drain_commits_the_turn_in_flight(warm, family):
    mdl, eng = warm(family)
    p = _prompts(1, lo=5, hi=8, seed=13)[0]
    sch = _sched(eng)
    fut = sch.submit(p, max_new_tokens=6)
    sch.step()
    sch.step()
    assert sch._inflight is not None and sch._has_work()
    sch.close(drain=True)
    assert fut.result(0) == mdl.greedy_reference(p, 6)
    assert sch._inflight is None and not sch._has_work()
    assert eng.cache.pages_used() == 0


# -- speculative decode ------------------------------------------------------

def test_spec_identical_with_matched_draft(model):
    """Same-weights draft: every proposal accepted, output bitwise
    identical, and the whole run takes fewer engine steps."""
    prompts = _prompts(4, seed=6)
    ref = [model.greedy_reference(p, 9) for p in prompts]
    eng = engine(model, PLAIN, num_pages=64, draft_model=model, spec_k=3)
    sch = _sched(eng)
    got = _gen(sch, prompts, max_new=9)
    st = sch.stats()
    sch.close(drain=True)
    assert got == ref
    assert st["spec_proposed"] > 0
    assert st["spec_accepted"] == st["spec_proposed"]
    assert eng.cache.pages_used() == 0


def test_spec_identical_with_mismatched_draft(model, draft):
    """A draft that almost never agrees must not change the output —
    the verify pass IS the target model's greedy decode."""
    prompts = _prompts(4, seed=8)
    eng = engine(model, PLAIN, num_pages=64, draft_model=draft, spec_k=3)
    sch = _sched(eng)
    got = _gen(sch, prompts, max_new=9)
    st = sch.stats()
    sch.close(drain=True)
    assert got == [model.greedy_reference(p, 9) for p in prompts]
    assert st["spec_proposed"] > 0
    assert st["spec_accepted"] <= st["spec_proposed"]


# -- names and phases a trace can read ---------------------------------------

PHASES = ("decode.expire", "decode.admit_phase", "decode.prefill",
          "decode.decode", "decode.account")


@pytest.fixture
def _traced():
    tracing._env_default()
    tracing.clear()
    yield
    tracing._env_default()
    tracing.clear()


def _mixed_turn(model, **kw):
    """A scheduler whose next ``step()`` both prefills (a request just
    submitted) and decodes (one admitted a turn earlier)."""
    sch = _sched(engine(model, PLAIN, **kw))
    first, second = _prompts(2, lo=4, hi=8, seed=9)
    sch.submit(first, max_new_tokens=6)
    sch.step()
    sch.submit(second, max_new_tokens=6)
    return sch


def test_one_turn_leaves_every_phase_span_nested(model, _traced):
    """One ``step()`` under ``tracing.enable()``: the eight names, each
    phase inside ``decode.step``, one ``decode.prefill`` per dispatch
    with its staging inside, and ONE ``decode.sync``: the turn's only wait,
    for the turn before."""
    sch = _mixed_turn(model)
    tracing.enable()
    tracing.clear()
    sch.step()
    evs = [e for e in tracing._completed_events()
           if e["name"].startswith("decode.")]
    tracing.disable()
    run(sch)
    sch.close(drain=True)
    by_id = {e["args"]["span_id"]: e for e in evs}

    def parent(e):
        return by_id[e["args"]["parent_id"]]["name"]

    names = [e["name"] for e in evs]
    step, = [e for e in evs if e["name"] == "decode.step"]   # one a turn
    assert "parent_id" not in step["args"]
    assert step["args"]["slots_active"] == 2     # as the step record has it
    for phase in PHASES:
        ev, = [e for e in evs if e["name"] == phase]    # once each
        assert parent(ev) == "decode.step"
        assert step["ts"] <= ev["ts"] and \
            ev["ts"] + ev["dur"] <= step["ts"] + step["dur"] + 1
    # the instant stays an instant, inside the admission phase
    admit, = [e for e in evs if e["name"] == "decode.admit"]
    assert admit["dur"] == 0 and parent(admit) == "decode.admit_phase"
    # one chunk, one prefill span (a `with`, no record_span beside it)
    prefill, = [e for e in evs if e["name"] == "decode.prefill"]
    assert prefill["args"]["lanes"] == 1
    assert prefill["args"]["tokens"] >= 4
    one = {e["name"]: e["args"] for e in evs}
    assert one["decode.admit_phase"]["admitted"] == 1
    # the slot that just prefilled its only chunk decodes in this turn too
    assert one["decode.decode"]["decoding"] == 2
    # the chunk stages its tokens, the edit that switches its slot on
    # its patch; the decode stages nothing; the turn waits once, last
    assert [parent(e) for e in evs if e["name"] == "decode.stage"] == [
        "decode.prefill", "decode.step"]
    sync, = [e for e in evs if e["name"] == "decode.sync"]
    assert parent(sync) == "decode.step"
    decode, = [e for e in evs if e["name"] == "decode.decode"]
    assert decode["ts"] + decode["dur"] <= sync["ts"] + 1
    assert sorted(set(names)) == sorted(
        PHASES + ("decode.step", "decode.admit", "decode.stage",
                  "decode.sync"))


def test_a_turn_under_a_capture_reaches_the_host_plane(
        model, _traced, xplane_capture):
    """MXNET_TRACE unset: a profiler capture still sees the turn and its
    phases as ``mxtpu.decode.*`` on one thread of ``/host:CPU``; the
    ring holds nothing."""
    sch = _mixed_turn(model)
    assert not tracing.enabled()
    with xplane_capture() as found:
        sch.step()
    run(sch)
    sch.close(drain=True)
    assert {e["plane"] for e in found} == {"/host:CPU"}
    assert len({e["line"] for e in found}) == 1
    step, = [e for e in found if e["name"] == "mxtpu.decode.step"]
    for e in found:
        assert step["lo"] <= e["lo"] and e["hi"] <= step["hi"]
    assert {e["name"] for e in found} == {
        "mxtpu." + n for n in PHASES + ("decode.step", "decode.stage",
                                        "decode.sync")}
    prefill, = [e for e in found if e["name"] == "mxtpu.decode.prefill"]
    assert prefill["stats"]["lanes"] == 1
    assert tracing._completed_events() == []


# -- the turn clock, and a request's waits ------------------------------------

def test_a_requests_waits_sum_to_its_first_answer(model):
    """One slot, three requests at once: the second and third wait in
    the queue no less than the service of those before them, and every
    request's ``queue_wait_ms + prefill_wait_ms`` is its ``ttft_ms``."""
    eng = engine(model, PLAIN, max_slots=1)
    sch = _sched(eng)
    futs = [sch.submit(p, max_new_tokens=5) for p in _prompts(3, seed=4)]
    t0 = time.perf_counter()
    records, done_at = [], {}
    while sch._has_work():
        records.append(sch.step())
        for i, f in enumerate(futs):
            if f.done() and i not in done_at:
                done_at[i] = time.perf_counter()
    ttft = [t for r in records for t in r.get("ttft_ms", ())]
    queue = [q for r in records for q in r.get("queue_wait_ms", ())]
    assert len(ttft) == len(queue) == 3
    for r in records:       # the two lists go together, in one order
        assert len(r.get("queue_wait_ms", ())) == len(r.get("ttft_ms", ()))
    assert queue[0] < ttft[0]
    for i in (1, 2):
        # admitted only once the one before it had left the slot
        assert queue[i] >= (done_at[i - 1] - t0) * 1e3
        assert queue[i] > queue[i - 1]
    st = eng.stats()["requests"]
    assert st["count"] == 3
    assert st["queue_wait_ms_mean"] == pytest.approx(sum(queue) / 3)
    assert st["queue_wait_ms_mean"] + st["prefill_wait_ms_mean"] == \
        pytest.approx(sum(ttft) / 3, abs=1e-6)
    assert sch.stats()["requests"] == st
    assert eng.stats()["traced"]["requests"]["count"] == 0
    sch.close(drain=True)


def test_observe_and_the_step_record_share_one_queue_wait(model):
    """What ``/requestz`` shows as a request's ``queue_ms`` is the
    ``queue_wait_ms`` of the step record: one expression, two stamps."""
    slo.declare(latency_ms=1e9)
    slo.clear_ring()
    sch = _sched(engine(model, PLAIN, max_slots=1))
    futs = [sch.submit(p, max_new_tokens=2) for p in _prompts(2, seed=6)]
    records = []
    while sch._has_work():
        records.append(sch.step())
    assert all(f.done() for f in futs)
    queue = [q for r in records for q in r.get("queue_wait_ms", ())]
    seen = sorted(e["queue_ms"] for e in slo.requestz()["slowest"]
                  if e.get("kind") == "generate")
    assert seen == sorted(queue) and len(seen) == 2
    sch.close(drain=True)


def test_turn_clock_books_every_moment_of_its_thread(model):
    """``empty_s + host_s + sync_s`` is the scheduler thread's elapsed
    time, over a run with an idle stretch in it; the shares are of that
    sum."""
    eng = engine(model, PLAIN)
    eng.warmup([8, 16])
    sch = _sched(eng)
    life = {}
    loop = sch._loop

    def timed():
        life["lo"] = time.perf_counter()
        loop()
        life["hi"] = time.perf_counter()

    sch._loop = timed
    sch.start()
    first, second = _prompts(2, seed=5)
    assert len(sch.submit(first, max_new_tokens=6).result(60)) == 6
    time.sleep(0.08)                    # nobody asks
    assert len(sch.submit(second, max_new_tokens=6).result(60)) == 6
    sch.close(drain=True)
    assert not sch._thread.is_alive()
    st = eng.stats()["sched"]
    booked = st["empty_s"] + st["host_s"] + st["sync_s"]
    assert booked == pytest.approx(life["hi"] - life["lo"], abs=1e-3)
    assert st["empty_s"] >= 0.07 and st["sync_s"] > 0 and st["host_s"] > 0
    assert st["turns"] >= 12            # two requests of six tokens
    assert st["empty_share"] + st["host_share"] + st["sync_share"] == \
        pytest.approx(1.0)
    assert st["empty_share"] == pytest.approx(st["empty_s"] / booked)
    assert sch.stats()["sched"] == st


def test_traced_clock_and_waits_count_only_under_a_capture(model,
                                                           monkeypatch):
    """``stats()["traced"]`` holds the turns begun while a capture ran
    and the requests whose first token such a turn committed: the rule
    ``traced.decode_steps`` follows."""
    eng = engine(model, PLAIN, max_slots=2)
    sch = _sched(eng)
    futs = [sch.submit(p, max_new_tokens=4) for p in _prompts(3, seed=8)]
    on = {"now": False}
    monkeypatch.setattr(tracing, "capturing", lambda: on["now"])
    turns = under = firsts_under = 0
    sync_under = 0.0
    while sch._has_work():
        # the capture covers the second to the fourth turn: the first
        # two requests' first tokens, not the third's
        on["now"] = 1 <= turns <= 3
        rec = sch.step()
        turns += 1
        if on["now"]:
            under += 1
            firsts_under += len(rec.get("ttft_ms", ()))
            sync_under += rec["sync_ms"]
    assert all(f.done() for f in futs)
    st = eng.stats()
    assert st["sched"]["turns"] == turns and st["requests"]["count"] == 3
    traced = st["traced"]
    assert traced["sched"]["turns"] == under == 3
    assert traced["requests"]["count"] == firsts_under == 2
    assert traced["sched"]["empty_s"] == 0.0
    assert traced["sched"]["sync_s"] == pytest.approx(sync_under / 1e3,
                                                      abs=1e-5)
    assert 0 < traced["sched"]["host_s"] < st["sched"]["host_s"]
    assert traced["sched"]["host_share"] + traced["sched"]["sync_share"] \
        == pytest.approx(1.0)
    sch.close(drain=True)


@pytest.mark.parametrize("kind", ["plain", "spec", "hybrid"])
def test_step_record_carries_the_waits_and_the_sync(warm_engines, warm,
                                                    kind):
    """``sync_ms`` beside ``step_ms`` in every record (the read of the
    turn before: none in a turn that found nothing in flight) and
    ``queue_wait_ms`` wherever ``ttft_ms`` is."""
    eng = warm("hybrid")[1] if kind == "hybrid" else warm_engines[kind][0]
    sch = _sched(eng)
    futs = [sch.submit(p, max_new_tokens=4) for p in _prompts(2, seed=12)]
    records = _steps_until(sch, lambda: not sch._has_work())
    assert all(len(f.result(0)) == 4 for f in futs)
    for rec in records:
        assert 0.0 <= rec["sync_ms"] <= rec["step_ms"]
        assert ("queue_wait_ms" in rec) == ("ttft_ms" in rec)
        for q, t in zip(rec.get("queue_wait_ms", ()), rec.get("ttft_ms", ())):
            assert 0.0 <= q <= t
    assert sum(len(r.get("queue_wait_ms", ())) for r in records) == 2
    assert any(r["sync_ms"] > 0 for r in records)
    # the first chained turn has nothing to read; a speculative turn
    # reads its own tokens
    assert (records[0]["sync_ms"] == 0.0) == (kind != "spec")
    # each a difference of the engine's running total, rounded to a us
    assert sum(r["sync_ms"] for r in records) / 1e3 <= \
        eng.sync_s + 1e-6 * len(records)
    sch.close(drain=True)


def test_an_empty_server_waits_under_a_span_on_the_host_plane(
        model, _traced, xplane_capture):
    """Under a real profiler capture the wait between turns, when
    nothing is to run, is ``mxtpu.decode.empty`` on the scheduler's own
    line of ``/host:CPU``, beside its turns; and the clock's traced
    figures count it."""
    eng = engine(model, PLAIN)
    eng.warmup([8, 16])
    sch = _sched(eng, start=True)
    with xplane_capture() as found:
        time.sleep(0.03)
        assert len(sch.submit(_prompts(1, seed=3)[0],
                              max_new_tokens=3).result(60)) == 3
        time.sleep(0.03)
    sch.close(drain=True)
    empty = [e for e in found if e["name"] == "mxtpu.decode.empty"]
    steps = [e for e in found if e["name"] == "mxtpu.decode.step"]
    assert len(empty) >= 4 and len(steps) >= 3
    assert {e["plane"] for e in empty} == {"/host:CPU"}
    assert {e["line"] for e in empty} == {e["line"] for e in steps}
    for e in empty:         # between turns, never inside one
        assert not any(s["lo"] < e["hi"] and e["lo"] < s["hi"]
                       for s in steps)
    traced = eng.stats()["traced"]
    assert traced["sched"]["empty_s"] >= 0.04
    assert traced["sched"]["turns"] >= len(steps) - 1
    assert traced["requests"]["count"] == 1
    assert tracing._completed_events() == []


@pytest.fixture(scope="module")
def warm_engines(model, draft):
    plain = engine(model, PLAIN)
    spec = engine(model, PLAIN, num_pages=64, draft_model=draft, spec_k=3)
    # a model that supplies turn_logits, at the Falcon cells' chunk of 128
    fused = engine(build("hybrid"), PLAIN, prefill_chunk=128)
    return {"plain": (plain, plain.warmup([8])),
            "spec": (spec, spec.warmup([8])),
            "fused": (fused, fused.warmup([8]))}


def _trace_patterns():
    """The executables a per-layer metric reads, by the pattern its file
    under ``chipbench/layer_metrics/`` gives the trace reader."""
    out = {}
    for name in ("decode_exec_ms_p50", "prefill_exec_ms_p50",
                 "prefill_runs_per_step"):
        with open(REPO / "chipbench" / "layer_metrics" / f"{name}.json") as f:
            out[name] = json.load(f)["args"]
    # a prefill run a decode run: the two executables' own patterns
    runs = out.pop("prefill_runs_per_step")
    assert (runs["pattern"], runs["per"]) == (
        out["prefill_exec_ms_p50"]["pattern"],
        out["decode_exec_ms_p50"]["pattern"])
    return {name: args["pattern"] for name, args in out.items()}


@pytest.mark.parametrize("kind,key,metric", [
    ("plain", "decode", "decode_exec_ms_p50"),
    ("plain", "state_edit", None),
    ("plain", "prefill_b16", "prefill_exec_ms_p50"),
    # the multi-lane prefill: two lanes of the full chunk (128)
    ("plain", "prefill_b256", "prefill_exec_ms_p50"),
    ("spec", "draft", None),
    ("spec", "verify", None),
    ("spec", "prefill_b16", "prefill_exec_ms_p50"),
    ("spec", "draft_prefill_b16", None),
    ("spec", "prefill_b256", "prefill_exec_ms_p50"),
    ("spec", "draft_prefill_b256", None),
    # the decode step with one and two lanes of 128 inside it
    ("fused", "decode_fill_b128", None),
    ("fused", "decode_fill_b256", None),
    ("fused", "decode", "decode_exec_ms_p50")])
def test_executable_carries_its_key_as_its_name(warm_engines, kind, key,
                                                metric):
    """A device trace shows an executable as its module's name: each of
    the engine's is ``jit_mxtpu_<key>``, not ``jit__lambda_``, warm-up
    materialises all a turn can dispatch (the chained turn's pair, or
    the speculative turn's, and beside the one-lane prefill buckets
    every multi-lane prefill, named by its rows, lanes x the full
    chunk, which no one-lane bucket can reach; where the model supplies
    ``turn_logits``, the decode step with one and two lanes inside it,
    named by the lanes' rows), and the benchmark's patterns match the
    decode and prefill executables and nothing else:
    ``prefill_runs_per_step`` reads the same pattern, and a decode step
    that carries lanes is neither a decode run nor a prefill run to
    them."""
    eng, keys = warm_engines[kind]
    assert sorted(eng._exec) == sorted(keys) == sorted(
        {"plain": ["decode", "state_edit", "prefill_b16", "prefill_b256"],
         "spec": ["draft", "verify", "prefill_b16", "draft_prefill_b16",
                  "prefill_b256", "draft_prefill_b256"],
         "fused": ["decode", "state_edit", "decode_fill_b128",
                   "decode_fill_b256", "state_reset", "prefill_b16",
                   "prefill_b256"]}[kind])
    text = eng._exec[key].as_text()
    assert f"HloModule jit_mxtpu_{key}," in text
    assert "lambda" not in text.split("\n", 1)[0]
    event = f"jit_mxtpu_{key}(1234567890)"       # as XLA Modules has it
    for name, pattern in _trace_patterns().items():
        assert bool(re.search(pattern, event)) == (name == metric)


# -- lifecycle ---------------------------------------------------------------

def test_close_drain_completes_inflight(model):
    sch = DecodeScheduler(engine(model, PLAIN), start=True)
    prompts = _prompts(5, seed=9)
    futs = [sch.submit(p, max_new_tokens=6) for p in prompts]
    sch.close(drain=True)
    assert [f.result(0) for f in futs] == [
        model.greedy_reference(p, 6) for p in prompts]
    with pytest.raises(ServingClosedError):
        sch.submit(prompts[0])


def test_close_no_drain_fails_pending_and_frees_pages(model):
    eng = engine(model, PLAIN)
    sch = _sched(eng)
    prompts = _prompts(6, seed=10)
    futs = [sch.submit(p, max_new_tokens=8) for p in prompts]
    sch.step()                          # some admitted, some queued
    assert eng.cache.pages_used() > 0
    sch.close(drain=False)
    for f in futs:
        with pytest.raises(ServingClosedError):
            f.result(0)
    assert eng.cache.pages_used() == 0
    with pytest.raises(ServingClosedError):
        sch.submit(prompts[0])


def test_queued_deadline_expires(model):
    sch = _sched(engine(model, PLAIN))
    t0 = telemetry.counter("serving.timeouts").value
    fut = sch.submit(_prompts(1, seed=11)[0], max_new_tokens=4,
                     timeout_ms=1.0)
    time.sleep(0.02)
    sch.step()
    with pytest.raises(RequestTimeoutError):
        fut.result(0)
    assert telemetry.counter("serving.timeouts").value == t0 + 1
    sch.close(drain=False)


def test_running_deadline_evicts_slot_and_frees_pages(model):
    eng = engine(model, PLAIN)
    sch = _sched(eng)
    e0 = telemetry.counter("decode.evictions").value
    p = _prompts(1, seed=12)[0]
    # full slot budget (~50 tokens at >=10ms/step) far outlasts the
    # deadline; the step loop must evict it mid-generation
    fut = sch.submit(p, max_new_tokens=eng.slot_capacity - len(p),
                     timeout_ms=60.0)
    sch.step()                          # admitted + generating
    assert eng.cache.pages_used() > 0
    deadline = time.monotonic() + 10.0
    while not fut.done() and time.monotonic() < deadline:
        time.sleep(0.01)
        sch.step()
    with pytest.raises(RequestTimeoutError):
        fut.result(0)
    assert telemetry.counter("decode.evictions").value == e0 + 1
    assert eng.cache.pages_used() == 0
    sch.close(drain=False)


# -- pre-admission rejects + batch-engine zero-size fixes --------------------

def test_submit_reject_matrix(model):
    eng = engine(model, PLAIN)
    sch = _sched(eng, queue_depth=1)
    r0 = telemetry.counter("serving.rejected.shape").value
    with pytest.raises(BadRequestError):
        sch.submit([])                  # empty prompt
    with pytest.raises(BadRequestError):
        sch.submit([1, 2], max_new_tokens=0)
    with pytest.raises(BadRequestError):
        sch.submit([1, VOCAB])          # token out of range
    with pytest.raises(BadRequestError):
        sch.submit([-1, 2])
    with pytest.raises(BadRequestError):  # budget exceeds slot capacity
        sch.submit([1, 2], max_new_tokens=eng.slot_capacity + 1)
    assert telemetry.counter("serving.rejected.shape").value == r0 + 5
    q0 = telemetry.counter("serving.rejected.queue_full").value
    sch.submit([1, 2, 3], max_new_tokens=2)
    with pytest.raises(QueueFullError):
        sch.submit([1, 2, 3], max_new_tokens=2)
    assert telemetry.counter(
        "serving.rejected.queue_full").value == q0 + 1
    sch.close(drain=False)


def test_batch_engine_rejects_zero_size():
    """Regression: a zero-size example (or an empty batch) must be
    rejected up front, not crash inside bucketing/dispatch."""
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.serving import InferenceEngine
    mx.random.seed(0)
    net = nn.Sequential()
    net.add(nn.Dense(4, in_units=8))
    net.initialize()
    eng = InferenceEngine(net, example_shape=(8,), dtype="float32")
    with pytest.raises(BadRequestError):
        eng.validate(onp.zeros((0,), "float32"))
    with pytest.raises(BadRequestError):
        eng.validate(onp.zeros((8, 0), "float32"))
    with pytest.raises(BadRequestError):
        eng._bucket_batch(0)
    with pytest.raises(BadRequestError):
        eng._bucket_batch(-1)


# -- server integration ------------------------------------------------------

@pytest.mark.slow
def test_server_generate_http(model):
    import urllib.error
    import urllib.request
    from mxnet_tpu.gluon import nn
    mx.random.seed(0)
    net = nn.Sequential()
    net.add(nn.Dense(4, in_units=8))
    net.initialize()
    srv = ServingServer(net, engine_args={"example_shape": (8,),
                                          "dtype": "float32"},
                        decoder=DecodeScheduler(engine(model, PLAIN),
                                                start=True))
    host, port = srv.start_http()
    base = f"http://{host}:{port}"
    try:
        p = _prompts(1, seed=14)[0]
        req = urllib.request.Request(
            base + "/generate",
            data=json.dumps({"prompt": p, "max_new_tokens": 5}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=30) as resp:
            body = json.loads(resp.read())
        assert body["tokens"] == model.greedy_reference(p, 5)
        bad = urllib.request.Request(
            base + "/generate",
            data=json.dumps({"prompt": []}).encode(),
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(bad, timeout=30)
        assert ei.value.code == 400
    finally:
        srv.stop(drain=True)


# -- telemetry / report reconciliation --------------------------------------

def test_reports_reconcile_decode_section(model, tmp_path, monkeypatch):
    """Every scheduler step emits one record; both report tools rebuild
    the run (tokens, TTFT, occupancy, completions) from the JSONL."""
    path = str(tmp_path / "decode.jsonl")
    monkeypatch.setenv("MXNET_TELEMETRY_JSONL", path)
    prompts = _prompts(3, seed=15)
    sch = _sched(engine(model, PLAIN))
    got = _gen(sch, prompts, max_new=5)
    sch.close(drain=True)
    monkeypatch.delenv("MXNET_TELEMETRY_JSONL")
    telemetry.enabled()                 # detach + close the sink

    tools = pathlib.Path(__file__).resolve().parents[1] / "tools"

    spec = importlib.util.spec_from_file_location(
        "telemetry_report", tools / "telemetry_report.py")
    trep = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(trep)
    records = trep.load(path)
    d = trep.summarize(records)["decode"]
    assert d["tokens"] == sum(len(g) for g in got) == 15
    assert d["completed"] == 3 and d["steps"] > 0
    assert d["ttft_ms"]["n"] == 3
    assert d["compiles"] > 0            # cold run compiled
    assert 0 < d["slot_occupancy_pct"] <= 100
    assert "Decode (continuous batching)" in trep.render(
        trep.summarize(records))
    c = profiler.counters()["decode"]
    assert c["tokens"] >= 15

    spec = importlib.util.spec_from_file_location(
        "slo_report", tools / "slo_report.py")
    srep = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(srep)
    out = srep.report([path], latency_ms=10_000.0, window_s=30.0,
                      threshold=14.4, slow_n=3, as_json=True,
                      ttft_ms=10_000.0)
    assert out["decode"]["tokens"] == 15
    assert out["decode"]["ttft"]["samples"] == 3
    assert out["decode"]["ttft"]["breaches"] == 0
    assert out["verdict"] == "healthy"


def test_ttft_objective_burns(tmp_path):
    """Latency healthy, TTFT blown: the burn opens with the decode
    plane's own cause and closes when TTFT recovers."""
    s = slo.declare(latency_ms=1000.0, window_s=30.0, min_samples=5,
                    ttft_ms=5.0, directory=str(tmp_path))
    b0 = telemetry.counter("serving_slo.ttft_breaches").value
    for _ in range(20):
        s.observe({"id": 1, "ok": True, "latency_ms": 2.0,
                   "ttft_ms": 100.0})
    v = s.evaluate()
    assert v["burning"]["cause"] == "ttft_slo"
    assert v["ttft"]["target_ms"] == 5.0
    assert v["ttft"]["burn_long"] >= 14.4
    assert telemetry.counter(
        "serving_slo.ttft_breaches").value == b0 + 20
    for _ in range(200):
        s.observe({"id": 2, "ok": True, "latency_ms": 2.0,
                   "ttft_ms": 1.0})
    assert s.evaluate()["burning"] is None
