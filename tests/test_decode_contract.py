"""The model contract of the decode plane, through the engine and the
scheduler: what every model must pass, written once and run for each
family it applies to (``decode_families.py``), on the CPU with the
kernels interpreted.

- the scheduler's tokens are the dense oracle's, chained turns and
  synchronous ones alike, and nothing compiles after warm-up;
- ``POST /generate`` answers in process;
- a model's counters ride with the tokens (routed families);
- a slot taken again starts from zero state (families with state).
"""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx  # noqa: F401  (registers ops + kernel specs)
from mxnet_tpu import telemetry
from mxnet_tpu.serving import (DecodeScheduler, ServingClosedError,
                               ServingServer)

from decode_families import (ALL, CHUNK, FAMILIES, ROUTED, STATEFUL, Kit,
                             Through, reference_logits, run, scaled, tokens)
from decode_families import clean  # noqa: F401  (a fixture)


@pytest.fixture(scope="module")
def kit():
    """One kit for the file: a family's model, and the executables of
    its engines, built once."""
    return Kit()


class _Records:
    """A telemetry sink that keeps every decode step record."""

    def __init__(self):
        self.records = []

    def emit(self, record):
        if "decode" in record:
            self.records.append(record["decode"])


@pytest.mark.parametrize("family", ALL)
def test_scheduler_matches_the_dense_oracle_and_never_recompiles(
        kit, family, clean):
    """Prompts shorter than, equal to and longer than a chunk, admitted
    at two times into two slots: every request's tokens are the dense
    oracle's, no executable compiles after warm-up (which builds the
    family's list: one multi-lane prefill, two lanes of the full chunk,
    and where the model fuses the decode step with one and two such
    lanes inside it), the step records carry the benchmark's five keys
    and the state's counters, and chained turns (the scheduler's) and
    synchronous ones (a step dispatched and read at once, by hand) give
    the same tokens."""
    model, _ = kit.model(family)
    eng = kit.engine(family, max_slots=2)
    assert eng.warmup([8, CHUNK]) == list(FAMILIES[family].warmup)
    compiled = eng.compiles
    sink = _Records()
    telemetry.add_sink(sink)
    sch = DecodeScheduler(eng, start=False)
    prompts = [tokens(n, seed=n) for n in (3, 16, 23, 40, 9)]
    futs = [sch.submit(p, max_new_tokens=5) for p in prompts[:3]]
    sch.step()
    sch.step()
    futs += [sch.submit(p, max_new_tokens=5) for p in prompts[3:]]
    run(sch)
    for p, f in zip(prompts, futs):
        assert f.result(0) == model.greedy_reference(p, 5)
    assert eng.compiles == compiled and eng.stats()["chained_share"] > 0.5
    assert {"ttft_ms", "tokens", "step_ms", "slots_active",
            "queue_depth"} <= set().union(*(set(r) for r in sink.records))
    last = sink.records[-1]
    stateful = eng.stats()["state_bytes"] > 0
    assert last["state_resets"] == (5 if stateful else 0)
    assert last["state_slots_live"] == 0
    assert last["state_bytes"] == eng.stats()["state_bytes"]
    assert sch.stats()["pages_used"] == 0
    sync = kit.engine(family, max_slots=2)
    for p, f in zip(prompts, futs):
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


@pytest.mark.parametrize("family", ALL)
def test_server_generate_answers_in_process(kit, family, clean):
    """``ServingServer.generate`` refuses with no decoder attached,
    answers the dense oracle's tokens through one, and stops the
    decoder with itself."""
    from mxnet_tpu.gluon import nn
    model, _ = kit.model(family)
    mx.random.seed(0)
    net = nn.Sequential()
    net.add(nn.Dense(4, in_units=8))
    net.initialize()
    srv = ServingServer(net, engine_args={"example_shape": (8,),
                                          "dtype": "float32"})
    with pytest.raises(ServingClosedError):    # no decoder attached
        srv.generate([1, 2, 3])
    sch = DecodeScheduler(kit.engine(family, max_slots=2), start=True)
    srv.attach_decoder(sch)
    p = tokens(21, seed=8)
    assert srv.generate(p, max_new_tokens=4) == model.greedy_reference(p, 4)
    srv.stop(drain=True)                # stops batcher AND decoder
    assert sch.closed
    with pytest.raises(ServingClosedError):
        srv.generate(p)


@pytest.mark.parametrize("family", ROUTED)
def test_counters_ride_with_the_tokens(kit, family, clean):
    """A turn reads once; the step records and ``stats()`` carry the
    model's counters, each within what its family's routing allows, and
    the live tokens a step; nothing belongs to a trace when no capture
    ran."""
    model, _ = kit.model(family)
    eng = kit.engine(family, max_slots=2)
    sink = _Records()
    telemetry.add_sink(sink)
    reads = []
    get = jax.device_get
    sch = DecodeScheduler(eng, start=False)
    prompts = [tokens(n, seed=n) for n in (11, 20)]
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
    want = FAMILIES[family].counters
    decoded = [r for r in sink.records if r["counters"]]
    assert decoded and all(set(r["counters"]) == set(want) for r in decoded)
    st = eng.stats()
    assert set(st["counters"]) == set(want)
    for name, holds in want.items():
        assert holds(st["counters"][name]), (name, st["counters"][name])
    # two slots decode positions 11.. and 20..: 5 steps each, the
    # context one longer a step, over the steps that were dispatched
    assert st["live_tokens_mean"] * eng._decode_steps == pytest.approx(
        sum(range(12, 17)) + sum(range(21, 26)))
    # no profiler capture ran: nothing belongs to a trace
    traced = st["traced"]
    assert (traced["decode_steps"], traced["live_tokens_mean"],
            traced["counters"]) == (0, 0.0, {})
    assert traced["sched"]["turns"] == traced["requests"]["count"] == 0
    assert st["sched"]["turns"] == turns and st["requests"]["count"] == 2


@pytest.mark.parametrize("family", STATEFUL)
def test_a_slot_taken_again_starts_from_zero_state(kit, family, clean):
    """By hand: a slot's state is non-zero after a prompt and a step,
    no other slot's is touched, and once the slot is released and
    acquired again every state row of it is zero and its next prompt's
    logits are the plain reference's.  Through the scheduler: two
    requests through one slot, one after the other, the second answers
    as if the slot had never been used, and the state's counters say
    so."""
    fam = FAMILIES[family]
    model, cfg = kit.model(family)
    eng = kit.engine(family, max_slots=2)
    paged = [len(widths) for widths, _ in model.cache_layout]

    def state_buffers():
        """Every layer's recurrent-state buffers, past its paged ones."""
        return [buf for layer, n in zip(eng.cache.pool, paged)
                for buf in layer[n:]]

    def state_rows(slot):
        return [buf[slot] for buf in state_buffers()]

    through = Through(kit, family, model, eng)
    first, second = tokens(30, seed=3), tokens(19, seed=4)
    eng.acquire_slot(0, 40)
    through.feed_prompt(0, first)
    through.step({0: (7, 30)})
    assert all(float(jnp.abs(row).max()) > 0 for row in state_rows(0))
    assert all(float(jnp.abs(row).max()) == 0 for row in state_rows(1))
    eng.release_slot(0)
    eng.acquire_slot(0, 40)
    assert all(float(jnp.abs(row).max()) == 0 for row in state_rows(0))
    through.picked.clear()
    got = through.feed_prompt(0, second)
    want, _, widest = reference_logits(kit, family, model.params, second,
                                       cfg, through.picked_by(0))
    assert scaled(got, want[-1]) <= fam.tol["float32"]
    assert widest <= fam.margin["float32"]
    st = eng.stats()
    assert st["state_resets"] == 2 and st["state_slots_live"] == 1
    assert st["state_bytes"] == sum(
        buf.size * buf.dtype.itemsize for buf in state_buffers()) > 0
    eng.release_slot(0)
    # the same through the scheduler: both requests take slot 0
    eng = kit.engine(family, max_slots=2)
    sch = DecodeScheduler(eng, start=False)
    first, second = tokens(19, seed=1), tokens(7, seed=2)
    f1 = sch.submit(first, max_new_tokens=4)
    run(sch)
    assert all(onp.asarray(row).any() for row in state_rows(0))  # left
    f2 = sch.submit(second, max_new_tokens=4)
    run(sch)
    assert f1.result(0) == model.greedy_reference(first, 4)
    assert f2.result(0) == model.greedy_reference(second, 4)
    st = eng.stats()
    assert st["state_resets"] == 2 and st["state_slots_live"] == 0
    assert sch.stats()["pages_used"] == 0
