"""The model contract of the decode plane at the chip's size: every
family's executables, compiled for a described TPU v5e at its benchmark
cell's geometry and its published widths (cut in depth), update the
cache in place.  No chip needed (``described_tpu.py``).

``memory_analysis`` of each compiled executable: the whole cache is
aliased to the outputs (and the resident decode state with it, in a
decode step), the arguments are the weights, the cache and a few small
arrays, and the temporaries hold no copy of even one buffer the
executable was donated (a slice in front of a Mosaic call, or a buffer
set back after a scatter, is hundreds of MB); beside that, what the
family's data says of each executable: a bound on its temporaries, its
Mosaic calls by name, its products.
"""
import json
import re

import jax
import jax.numpy as jnp
import pytest

from decode_families import ALL, FAMILIES, REPO, lower
from described_tpu import compile_for_tpu, v5e  # noqa: F401  (fixtures)


def _nbytes(a):
    return a.size * a.dtype.itemsize


@pytest.fixture(scope="module")
def at_cell():
    """A family's model at its cell's widths and the cell's engine
    geometry, built once for the file (the executables of a family
    share the traces of its kernels)."""
    made = {}

    def get(family):
        if family not in made:
            fam = FAMILIES[family]
            with open(REPO / "chipbench" / "workloads"
                      / f"{fam.cell}.json") as f:
                made[family] = fam.at_cell(), json.load(f)["engine"]
        return made[family]

    return get


@pytest.mark.parametrize("family,key", [
    (family, key) for family in ALL for key in FAMILIES[family].in_place])
def test_executables_update_the_cache_in_place(v5e, at_cell, family, key):
    fam = FAMILIES[family]
    want = fam.in_place[key]
    mdl, geo = at_cell(family)

    def spec(shape, dtype="bfloat16"):
        return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype),
                                    sharding=v5e)

    params = jax.tree_util.tree_map(lambda a: spec(a.shape, a.dtype),
                                    mdl.params)
    # the cache as the engine builds it from the model's layout
    pool = tuple(
        tuple(spec((geo["num_pages"], geo["page_size"], w)) for w in widths)
        + tuple(spec((geo["max_slots"],) + tuple(sh), dt)
                for _, sh, dt in state)
        for widths, state in mdl.cache_layout)
    cache = sum(_nbytes(b) for layer in pool for b in layer)
    assert cache == fam.cell_pool_bytes
    lowered, donated = lower(mdl, key, params, pool, spec, geo["max_slots"],
                             geo["pages_per_slot"], geo["prefill_chunk"])
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    donated_bytes = sum(_nbytes(b) for layer in donated for b in layer)
    # arguments: the weights (none to a state reset), what was donated,
    # and a few small arrays
    weights = (0 if key == "state_reset" else
               sum(_nbytes(a) for a in jax.tree_util.tree_leaves(params)))
    assert 0 <= mem.argument_size_in_bytes - weights - donated_bytes < 2 ** 16
    # all of it aliased; a decode step's resident state beside it (small
    # arrays are padded to a tile: under 64 KiB)
    state = mem.alias_size_in_bytes - donated_bytes
    assert 0 <= state < 2 ** 16
    assert (state > 0) == key.startswith("decode")
    largest = max(_nbytes(b) for layer in donated for b in layer)
    assert mem.temp_size_in_bytes < largest, mem.temp_size_in_bytes
    if "temp_bytes" in want:
        assert mem.temp_size_in_bytes < want["temp_bytes"], \
            mem.temp_size_in_bytes
    text = compiled.as_text()
    calls = re.findall(r'^\s*(?:ROOT )?%(\S+) = .*custom_call_target='
                       r'"tpu_custom_call"', text, re.M)
    assert all("mxtpu_" in c for c in calls), calls
    for name, n in want.get("calls", {}).items():
        assert sum(name in c for c in calls) == n, (name, calls)
    if "products" in want:
        products = re.findall(r"= \S+ (?:convolution|dot)\(", text)
        assert len(products) == want["products"], len(products)
