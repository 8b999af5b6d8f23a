"""The main path's Pallas kernels, compiled (not interpreted) for a
described TPU v5e at the shapes ``chip_smoke.py`` runs, and the chained
decode executable at the two decode cells' whole geometry — no chip
needed (every model's executables at its cell's pool:
``test_decode_in_place.py``).

Interpret mode accepts what the chip's compiler refuses (a batched
matmul with no free lhs dim, a float iota, a block past the scoped-VMEM
limit), so every other kernel test in the suite can pass on a kernel
that cannot start on a TPU.  These cases ask libtpu's compiler itself.
The kernels choose interpret mode from ``jax.default_backend()``; the
test steers that call (``described_tpu.py``), the program has no option
for it.
"""
import os
import re

import pytest

import jax
import jax.numpy as jnp

from described_tpu import compile_for_tpu, v5e  # noqa: F401  (fixtures)

# the decode model of chip_smoke.py: 8 heads x 64, bf16, 8 slots
H, D, SLOTS = 8, 64, 8


def _compile(fn, sharding, *specs):
    args = [jax.ShapeDtypeStruct(shape, jnp.dtype(dt), sharding=sharding)
            for shape, dt in specs]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "kernel was not compiled by Mosaic"
    return text


def _flash_loss(q, k, v):
    from mxnet_tpu.ops.attention import _flash_attention
    out = _flash_attention(q, k, v, True, D ** -0.5, 512, 512)
    return out.astype(jnp.float32).sum()


@pytest.mark.parametrize("with_bwd", [False, True], ids=["fwd", "fwd_bwd"])
def test_flash_attention_compiles(v5e, with_bwd):
    # TransformerLM bs 8 x 8 heads, seq 2048, head_dim 64, bf16, causal
    fn = jax.grad(_flash_loss, argnums=(0, 1, 2)) if with_bwd \
        else _flash_loss
    qkv = ((64, 2048, D), "bfloat16")
    text = _compile(fn, v5e, qkv, qkv, qkv)
    # forward is one kernel; backward adds the dk/dv and dq kernels
    assert text.count("tpu_custom_call") >= (3 if with_bwd else 1)


@pytest.mark.parametrize("with_bwd", [False, True], ids=["fwd", "fwd_bwd"])
def test_flash_attention_compiles_at_the_training_cell(v5e, with_bwd):
    """``gpt2_train``: 4 x 16 heads, 1024 positions, heads of 64,
    bfloat16, causal, at the blocks the registry resolves by default.
    The kernels' bodies are unrolled over the live tiles of a block
    (ops/attention.py, "The schedule"); each still compiles to ONE Mosaic
    call, under the name ``chipbench/layer_metrics/flash_*_time_share``
    match."""
    import json
    import re
    from mxnet_tpu import kernels
    from mxnet_tpu.ops.attention import _flash_attention
    cfg = kernels.get_kernel("flash_attention").default_config

    def loss(q, k, v):
        out = _flash_attention(q, k, v, True, D ** -0.5, cfg["block_q"],
                               cfg["block_k"])
        return out.astype(jnp.float32).sum()

    qkv = ((64, 1024, D), "bfloat16")
    text = _compile(jax.grad(loss, argnums=(0, 1, 2)) if with_bwd else loss,
                    v5e, qkv, qkv, qkv)
    calls = re.findall(r'^\s*(?:ROOT )?%(\S+) = .*custom_call_target='
                       r'"tpu_custom_call"', text, re.M)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for kernel in ("fwd", "dkv", "dq") if with_bwd else ("fwd",):
        with open(os.path.join(root, "chipbench", "layer_metrics",
                               f"flash_{kernel}_time_share.json")) as f:
            match = json.load(f)["args"]["match"]
        assert sum(match in c for c in calls) == 1, (match, calls)
    assert len(calls) == (3 if with_bwd else 1), calls


def _walks_the_live_slots(text, slots, name):
    """The Mosaic call ``name`` is ONE and takes its grid's bound as its
    first operand, a scalar (a grid of a fixed size takes none), then
    the scalar-prefetched table, lengths, the list of live slots and
    their count (``ops/paged_attention.py``, ``_walk``)."""
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 1 and name in calls[0], calls
    walk = (rf"operand_layout_constraints={{s32\[\], s32\[{slots},\d+\]\S*, "
            rf"s32\[{slots}\]\S*, s32\[{slots}\]\S*, s32\[1\]")
    assert re.search(walk, calls[0]), calls[0]
    return calls[0]


@pytest.mark.parametrize("page_size", [16, 128])
def test_paged_attention_compiles(v5e, page_size):
    from mxnet_tpu.ops.paged_attention import _paged_attention_pallas
    pps = 640 // page_size                    # 640-token slots
    pool = ((SLOTS * pps, page_size, H * D), "bfloat16")
    _compile(
        lambda q, k, v, t, l: _paged_attention_pallas(
            q, k, v, t, l, D ** -0.5, 64),
        v5e, ((SLOTS, H, D), "bfloat16"), pool, pool,
        ((SLOTS, pps), "int32"), ((SLOTS,), "int32"))


@pytest.mark.parametrize("block_k", [16, 32, 64, 128])
def test_paged_attention_compiles_at_the_decode_cell(v5e, block_k):
    """``gpt2_decode_chat``: 96 slots x 64 pages of 16, 16 heads of 64
    folded into 1024 lanes; a block of 1, 2, 4 and 8 pages, each page a
    copy of its own from the pool left in HBM."""
    from mxnet_tpu.ops.paged_attention import _paged_attention_pallas
    pool = ((6144, 16, 16 * 64), "bfloat16")
    text = _compile(
        lambda q, k, v, t, l: _paged_attention_pallas(
            q, k, v, t, l, 64 ** -0.5, block_k),
        v5e, ((96, 16, 64), "bfloat16"), pool, pool,
        ((96, 64), "int32"), ((96,), "int32"))
    _walks_the_live_slots(text, 96, "mxtpu_paged_attention")


def test_a_pool_of_no_whole_lane_tile_is_gathered_by_xla(v5e):
    """Mosaic slices whole lane tiles: a pool 64 lanes wide is no source
    of a copy, so on a TPU it takes the XLA lowering (the interpreter
    walks it like any other)."""
    from mxnet_tpu.ops.paged_attention import _paged_attention_pallas
    pool = jax.ShapeDtypeStruct((40, 16, 2 * 32), jnp.bfloat16, sharding=v5e)
    args = [jax.ShapeDtypeStruct(shape, jnp.dtype(dt), sharding=v5e)
            for shape, dt in (((5, 10, 32), "bfloat16"), ((5, 8), "int32"),
                              ((5,), "int32"))]
    text = jax.jit(lambda q, k, v, t, l: _paged_attention_pallas(
        q, k, v, t, l, 32 ** -0.5, 64)).lower(
            args[0], pool, pool, *args[1:]).compile().as_text()
    assert "tpu_custom_call" not in text


# Falcon-H1-34B's widths (chipbench/configs/falcon_h1_34b.json) at the
# benchmark cell's geometry: 96 slots x 8 pages of 128
FH_SLOTS, FH_PAGES, FH_PAGE = 96, 8, 128


@pytest.mark.parametrize("block_k", [64, 128])
def test_paged_attention_grouped_query_compiles(v5e, block_k):
    """20 query heads over pools of 4 KV heads of 128: 512 lanes."""
    from mxnet_tpu.ops.paged_attention import (_paged_attention_pallas,
                                               paged_attention_reference)
    pool = ((FH_SLOTS * FH_PAGES, FH_PAGE, 4 * 128), "bfloat16")
    args = (((FH_SLOTS, 20, 128), "bfloat16"), pool, pool,
            ((FH_SLOTS, FH_PAGES), "int32"), ((FH_SLOTS,), "int32"))
    _walks_the_live_slots(_compile(
        lambda q, k, v, t, l: _paged_attention_pallas(
            q, k, v, t, l, 128 ** -0.5, block_k), v5e, *args),
        FH_SLOTS, "mxtpu_paged_attention")
    # the oracle too: chip_smoke.py runs it in float32 on the chip
    f32 = tuple((shape, "float32" if dt == "bfloat16" else dt)
                for shape, dt in args)
    jax.jit(paged_attention_reference).lower(*[
        jax.ShapeDtypeStruct(shape, jnp.dtype(dt), sharding=v5e)
        for shape, dt in f32]).compile()


@pytest.mark.parametrize("block_k", [128, 512])
def test_paged_attention_packs_narrow_grouped_query_heads(v5e, block_k):
    """``lfm2_decode_reasoning``: 32 query heads over pools of 8 KV heads
    of 64 (512 lanes), 192 slots x 32 pages of 128: two KV heads share a
    lane tile and their eight query heads are its eight rows, on the
    MXU; no 0/1 head-membership operand (the folded form's) is left.  A
    block of one page, and of four (``paged_kv._PACKED_BLOCK_ROWS``)."""
    from mxnet_tpu.ops.paged_attention import _paged_attention_pallas
    pool = ((3072, 128, 8 * 64), "bfloat16")
    text = _compile(
        lambda q, k, v, t, l: _paged_attention_pallas(
            q, k, v, t, l, 64 ** -0.5, block_k),
        v5e, ((192, 32, 64), "bfloat16"), pool, pool,
        ((192, 32), "int32"), ((192,), "int32"))
    call = _walks_the_live_slots(text, 192, "mxtpu_paged_attention")
    # operands: the walk's scalars, the packed queries (192, 8, 512), K, V
    assert "bf16[192,8,512]" in call and "f32[8,512]" not in call


@pytest.mark.parametrize("block_k", [128, 512])
def test_latent_attention_compiles_at_the_reasoning_cell(v5e, block_k):
    """``axk1_decode_reasoning``: 128 slots x 32 pages of 128 rows, 64
    heads over one row of 640 lanes ([512 | 64 | 64 of padding]); a
    block is one page or four.  The walk is staged: four copy buffers,
    a block's scores and two slots' queries in VMEM beside the whole
    output (8.4 MB at 128 slots), inside the scoped limit."""
    from mxnet_tpu.ops.paged_attention import _latent_attention_pallas

    def fn(q, p, t, l):
        return _latent_attention_pallas(q, p, t, l, 512, 192 ** -0.5,
                                        block_k)

    specs = (((128, 64, 640), "bfloat16"), ((4096, 128, 640), "bfloat16"),
             ((128, 32), "int32"), ((128,), "int32"))
    text = _compile(fn, v5e, *specs)
    call = _walks_the_live_slots(text, 128, "mxtpu_latent_attention")
    # operands: the walk's scalars, the queries whole (a slot's are
    # copied by hand, once), the pool
    assert call.count("bf16[128,64,640]") == 1, call
    kernel = str(jax.make_jaxpr(fn)(*[
        jax.ShapeDtypeStruct(shape, jnp.dtype(dt)) for shape, dt in specs]))
    for scratch in ("Ref<any>{bf16[128,64,640]}",
                    f"Ref<vmem>{{bf16[4,{block_k},640]}}",
                    f"Ref<vmem>{{f32[64,{block_k}]}}",
                    "Ref<vmem>{bf16[2,64,640]}",
                    "Ref<semaphore_mem>{dma_sem[1,4]}",
                    "Ref<semaphore_mem>{dma_sem[2]}"):
        assert scratch in kernel, scratch


def test_the_paged_bodies_lower_as_before_the_latent_pipeline():
    """The staged walk is the latent body's alone: the folded, lanes and
    packed bodies keep two buffers, and their kernels at the
    ``gpt2_decode_chat``, Falcon-H1 and ``lfm2_decode_reasoning``
    geometries (blocks as ``paged_kv._kernel`` chooses) trace to the
    very jaxprs they did before it, the Mosaic calls' whole input (the
    text hashed: it carries no source location)."""
    import hashlib
    from mxnet_tpu.ops.paged_attention import _paged_attention_pallas
    want = {
        "gpt2": "cdac690ce87388f4ddad1985ff46db5b565beb0209c58bdfd65f07fdc5d2ef95",
        "falcon": "a837c8f37049b334bd30a81d763f9d357c35bb1d0c30deabeddfc63d80abd162",
        "lfm2": "c2209ad0f24322ae869deeb660c4769ac580d71a18efd511380699b2b02d603c",
    }
    geometry = {"gpt2": ((96, 16, 64), (6144, 16, 1024), (96, 64), 64),
                "falcon": ((96, 20, 128), (768, 128, 512), (96, 8), 128),
                "lfm2": ((192, 32, 64), (3072, 128, 512), (192, 32), 512)}
    got = {}
    for cell, (q, pool, tables, block_k) in geometry.items():
        spec = [jax.ShapeDtypeStruct(shape, jnp.dtype(dt)) for shape, dt in (
            (q, "bfloat16"), (pool, "bfloat16"), (pool, "bfloat16"),
            (tables, "int32"), (tables[:1], "int32"))]
        text = str(jax.make_jaxpr(
            lambda *a: _paged_attention_pallas(*a, q[-1] ** -0.5, block_k))(
                *spec))
        assert "interpret=False" in text and "dma_sem[2,2]" in text
        got[cell] = hashlib.sha256(text.encode()).hexdigest()
    assert got == want


def test_a_latent_row_of_no_whole_lane_tile_is_refused(v5e):
    # 576 lanes as the model defines the row: no source of a copy, and
    # no silent gather in its place (paged_kv.latent_width pads the row)
    from mxnet_tpu.ops.paged_attention import _latent_attention_pallas
    args = [jax.ShapeDtypeStruct(sh, jnp.dtype(dt), sharding=v5e)
            for sh, dt in (((8, 64, 576), "bfloat16"),
                           ((64, 128, 576), "bfloat16"),
                           ((8, 8), "int32"), ((8,), "int32"))]
    with pytest.raises(ValueError, match="128-lane tiles"):
        jax.jit(lambda q, p, t, l: _latent_attention_pallas(
            q, p, t, l, 512, 192 ** -0.5, 128)).lower(*args)


def _ssm_specs(sharding, dtype="bfloat16"):
    s_, h, p, n, g = FH_SLOTS, 32, 128, 256, 2
    return [jax.ShapeDtypeStruct(shape, jnp.dtype(dt), sharding=sharding)
            for shape, dt in (
                ((s_, h, p, n), "float32"), ((s_, h, p), dtype),
                ((s_, h), "float32"), ((h,), "float32"),
                ((s_, g, n), dtype), ((s_, g, n), dtype),
                ((h,), "float32"), ((s_,), "bool"))]


@pytest.mark.parametrize("block_h", [8, 16])
def test_ssm_update_compiles_in_place(v5e, block_h):
    """The state buffer (96 x 32 x 128 x 256 float32, 403 MB) is donated:
    the kernel's output is the buffer itself and nothing of its size is
    left among the temporaries."""
    from mxnet_tpu.ops.ssm import _ssm_kernel_run
    compiled = jax.jit(
        lambda *a: _ssm_kernel_run({"block_h": block_h}, *a),
        donate_argnums=(0,)).lower(*_ssm_specs(v5e)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "mxtpu_ssm_update" in text
    mem = compiled.memory_analysis()
    state = 96 * 32 * 128 * 256 * 4
    assert mem.alias_size_in_bytes == state
    assert mem.temp_size_in_bytes < state // 96, mem.temp_size_in_bytes


def test_ssm_oracles_compile(v5e):
    """The kernel's oracle at the kernel's shapes in float32, and the
    chunked scan and the recurrence at one prefill chunk of 128."""
    from mxnet_tpu.ops import ssm
    jax.jit(ssm.ssm_update_reference).lower(
        *_ssm_specs(v5e, "float32")).compile()
    t_, h, p, n, g = 128, 32, 128, 256, 2
    chunk = [jax.ShapeDtypeStruct(shape, jnp.float32, sharding=v5e)
             for shape in ((h, p, n), (t_, h, p), (t_, h), (h,),
                           (t_, g, n), (t_, g, n), (h,))]
    jax.jit(ssm.ssm_chunk_scan).lower(*chunk).compile()
    jax.jit(ssm.ssm_scan_reference).lower(*chunk).compile()


@pytest.mark.parametrize("rows,h,d,block_r", [
    (SLOTS, H, D, 128),           # decode step: one row per slot
    (SLOTS * 5, H, D, 128),       # verify window, spec_k = 4
    (128, H, D, 128),             # largest prefill bucket
    (16384, 16, 128, 512),        # block override past VMEM: clamped
])
def test_rope_compiles(v5e, rows, h, d, block_r):
    from mxnet_tpu.ops.rope import _rope_pallas
    _compile(lambda x, p: _rope_pallas(x, p, 10000.0, block_r),
             v5e, ((rows, h, d), "bfloat16"), ((rows,), "int32"))


def test_layer_norm_residual_compiles(v5e):
    from mxnet_tpu.ops.layernorm_residual import _lnr_pallas
    x = ((16384, 512), "bfloat16")
    g = ((512,), "bfloat16")
    _compile(lambda a, r, ga, be: _lnr_pallas(a, r, ga, be, 1e-5, 32),
             v5e, x, x, g, g)


# -- the chained decode executable at the two decode cells' whole geometry ----

@pytest.mark.parametrize("cell,pool_bytes", [
    ("gpt2_decode_chat", 9_663_676_416),
    ("falcon_h1_decode_chat", 3_659_268_096)])
def test_chained_decode_aliases_the_pool_and_the_resident_state(
        v5e, cell, pool_bytes):
    """Every layer of the cell: the whole pool and all four arrays of
    the resident state are the outputs' own buffers (a donated state
    that XLA copied would show as an alias short of it), the tokens for
    the host are one output more, the temporaries stay under one pool
    buffer, and ``state_edit`` rewrites the state where it is."""
    import json
    import re
    from decode_families import lower, resident
    from mxnet_tpu.serving import DecodeModel, FalconH1
    from mxnet_tpu.serving.decode import engine as E
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "chipbench")
    with open(os.path.join(bench, "workloads", f"{cell}.json")) as f:
        geo = json.load(f)["engine"]
    slots, pps = geo["max_slots"], geo["pages_per_slot"]

    def spec(shape, dtype="bfloat16"):
        return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype),
                                    sharding=v5e)

    if cell == "gpt2_decode_chat":
        layers = 24
        mdl = DecodeModel(512, dim=1024, n_heads=16, n_layers=1,
                          mlp_ratio=4, dtype="bfloat16")
        mdl.params = dict(mdl.params,
                          layers=mdl.params["layers"] * layers)
        mdl.n_layers = layers
        state_spec = ()
    else:
        with open(os.path.join(bench, "configs", "falcon_h1_34b.json")) as f:
            mdl = FalconH1(json.load(f), abstract=True)
        layers, state_spec = mdl.n_layers, mdl.state_spec
    params = jax.tree_util.tree_map(lambda a: spec(a.shape, a.dtype),
                                    mdl.params)
    kv = spec((geo["num_pages"], geo["page_size"],
               mdl.kv_heads * mdl.head_dim))
    pool = tuple((kv, kv) + tuple(spec((slots,) + sh, dt)
                                  for _, sh, dt in state_spec)
                 for _ in range(layers))
    nbytes = lambda a: a.size * a.dtype.itemsize           # noqa: E731
    assert sum(nbytes(b) for layer in pool for b in layer) == pool_bytes
    state = resident(spec, slots, pps)
    compiled = lower(mdl, "decode", params, pool, spec, slots, pps,
                     geo["prefill_chunk"])[0].compile()
    mem = compiled.memory_analysis()
    # small arrays are padded to a tile: at least their bytes, under 64 KiB
    resident = mem.alias_size_in_bytes - pool_bytes
    assert sum(nbytes(a) for a in state) <= resident < 2 ** 16, resident
    header = compiled.as_text().split("\n", 1)[0]
    aliased = re.findall(r"\{(\d+)\}: \(\d+, \{[\d, ]*\}", header)
    n_pool = len(jax.tree_util.tree_leaves(pool))
    assert len(aliased) == n_pool + 4, header[:400]
    assert mem.temp_size_in_bytes < max(
        nbytes(b) for b in pool[0]), mem.temp_size_in_bytes
    edit = jax.jit(lambda *a: E._state_edit_core(*a),
                   donate_argnums=(0,)).lower(
        state, spec((), "int32"), spec((3 + pps,), "int32")).compile()
    assert edit.memory_analysis().alias_size_in_bytes == resident


# -- the names a device trace shows ------------------------------------------
# An ``XLA Ops`` event of a TPU trace is the text of its HLO instruction
# and nothing else, so a kernel can be told from another only by the
# ``name`` its ``pallas_call`` was given.  JAX wraps that name in its
# transforms (``transpose_jvp_..._``) and numbers it, so readers match
# by substring (chipbench/harness/named.py).

def _named_texts(sharding):
    """The compiled HLO of every kernel of the main path, by family."""
    from mxnet_tpu.ops.layernorm_residual import _lnr_pallas
    from mxnet_tpu.ops.paged_attention import (_latent_attention_pallas,
                                               _paged_attention_pallas)
    from mxnet_tpu.ops.rope import _rope_pallas
    from mxnet_tpu.ops.ssm import _ssm_kernel_run
    qkv = ((16, 1024, D), "bfloat16")
    pool = ((SLOTS * 40, 16, H * D), "bfloat16")
    x, g = ((256, 512), "bfloat16"), ((512,), "bfloat16")
    return {
        "flash": _compile(jax.grad(_flash_loss, argnums=(0, 1, 2)),
                          sharding, qkv, qkv, qkv),
        "paged_attention": _compile(
            lambda q, k, v, t, l: _paged_attention_pallas(
                q, k, v, t, l, D ** -0.5, 64),
            sharding, ((SLOTS, H, D), "bfloat16"), pool, pool,
            ((SLOTS, 40), "int32"), ((SLOTS,), "int32")),
        "latent_attention": _compile(
            lambda q, p, t, l: _latent_attention_pallas(
                q, p, t, l, 128, 192 ** -0.5, 64),
            sharding, ((SLOTS, H, 256), "bfloat16"),
            ((SLOTS * 40, 16, 256), "bfloat16"),
            ((SLOTS, 40), "int32"), ((SLOTS,), "int32")),
        "rope": _compile(lambda a, p: _rope_pallas(a, p, 10000.0, 128),
                         sharding, ((SLOTS, H, D), "bfloat16"),
                         ((SLOTS,), "int32")),
        "layernorm_residual": _compile(
            lambda a, r, ga, be: _lnr_pallas(a, r, ga, be, 1e-5, 32),
            sharding, x, x, g, g),
        "ssm_update": jax.jit(
            lambda *a: _ssm_kernel_run({"block_h": 8}, *a)).lower(
                *_ssm_specs(sharding)).compile().as_text(),
    }


_TEXTS = {}


@pytest.mark.parametrize("kernel", [
    "flash_fwd", "flash_dkv", "flash_dq", "paged_attention", "rope",
    "layernorm_residual", "ssm_update", "latent_attention"])
def test_custom_call_carries_the_kernels_name(v5e, kernel):
    import re
    if not _TEXTS:
        _TEXTS.update(_named_texts(v5e))
    family = "flash" if kernel.startswith("flash") else kernel
    named = re.findall(
        r"^\s*(?:ROOT )?%(\S*mxtpu_" + kernel + r"\S*) = .*custom-call\("
        r'.*custom_call_target="tpu_custom_call"', _TEXTS[family], re.M)
    assert named, f"no Mosaic custom call named mxtpu_{kernel}"
    # and no Mosaic call of the main path is left anonymous
    calls = re.findall(r'^\s*(?:ROOT )?%(\S+) = .*custom_call_target='
                       r'"tpu_custom_call"', _TEXTS[family], re.M)
    assert all("mxtpu_" in c for c in calls), calls


# -- BatchNorm's training pass inside a compiled block -----------------------
# A ResNet step is bound by the bytes of its activations (PERF.md §5), so
# what BatchNorm costs is how often it makes the program read a conv
# output.  The op's one-pass statistics ride in the fusion that writes the
# conv output and its hand-derived gradient takes one reduce over (dy, x);
# the two-pass formulation it replaced (batch_norm_two_pass.py) reads the
# conv output again for the variance and again in the backward.

def _load_step_bytes():
    import importlib.util
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "step_bytes", os.path.join(root, "tools", "step_bytes.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _compile_bottleneck(sharding, batch, wrt_input):
    """``value_and_grad`` of one ``BottleneckV1(256, 1)`` in train mode on
    ``bf16[batch, 256, 56, 56]`` (ResNet-50's first stage), traced as
    ``SPMDTrainer`` traces a net."""
    import numpy as onp
    from mxnet_tpu import autograd as ag
    from mxnet_tpu.gluon.block import _TraceContext, _trace_scope
    from mxnet_tpu.gluon.model_zoo.vision.resnet import BottleneckV1
    from mxnet_tpu.ndarray import NDArray
    blk = BottleneckV1(256, 1)
    blk.initialize()
    blk(NDArray(onp.zeros((1, 256, 8, 8), onp.float32)))
    params = list(blk.collect_params().values())

    def loss(p_list, x):
        saved = [p._data for p in params]
        tc = _TraceContext(jax.random.PRNGKey(0))
        try:
            for p, a in zip(params, p_list):
                p._data = NDArray(a.astype(jnp.bfloat16))
            with _trace_scope(tc), ag.pause(train_mode=True):
                out = blk.forward(NDArray(x))
            return (out._data.astype(jnp.float32).mean(),
                    tuple(v for _, v in tc.aux))
        finally:
            for p, s in zip(params, saved):
                p._data = s

    args = ([jax.ShapeDtypeStruct(p.data().shape, jnp.float32,
                                  sharding=sharding) for p in params],
            jax.ShapeDtypeStruct((batch, 256, 56, 56), jnp.bfloat16,
                                 sharding=sharding))
    fn = jax.value_and_grad(loss, argnums=(0, 1) if wrt_input else 0,
                            has_aux=True)
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("batch,wrt_input", [(32, False), (64, True)],
                         ids=["params", "params_and_input"])
def test_batch_norm_reads_a_conv_output_once(v5e, monkeypatch, batch,
                                             wrt_input):
    """No entry fusion reads one conv output only to emit per-channel
    vectors, forward or backward, and XLA counts at least 12% fewer bytes
    than for the same block on the two-pass reference (which has such
    passes: the detector is shown to find them)."""
    from batch_norm_two_pass import two_pass_batch_norm
    from mxnet_tpu.ops import nn as nn_ops, registry
    sb = _load_step_bytes()

    def bytes_and_passes(compiled):
        out, _text = sb.report(compiled, top=0)
        return out["bytes_accessed"], out["statistics_only_passes"]

    def forget_traces():
        # the registry keeps one jitted partial per (op, params), and a
        # jit nested in a trace replays its cached body: drop them, so
        # that each block below traces the function then in place
        op = registry.get("BatchNorm")
        op._partials.clear()
        op._jits.clear()

    try:
        forget_traces()
        new_bytes, new_passes = bytes_and_passes(
            _compile_bottleneck(v5e, batch, wrt_input))
        monkeypatch.setattr(nn_ops, "_batch_norm_train",
                            two_pass_batch_norm)
        forget_traces()
        old_bytes, old_passes = bytes_and_passes(
            _compile_bottleneck(v5e, batch, wrt_input))
    finally:
        monkeypatch.undo()
        forget_traces()
    assert old_passes, "the reference block shows no statistics-only pass"
    assert new_passes == []
    assert new_bytes <= 0.88 * old_bytes, (new_bytes, old_bytes)
