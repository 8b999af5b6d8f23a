"""The decode plane's model families at CPU size, and what the tests
that hold them to the model contract drive them with.

Not a test file (no ``test_`` prefix): the files of the contract's cases
(``test_decode_contract.py``, ``test_model_contract.py``,
``test_decode_in_place.py``) and each family's own file of controls
import it.  A family is one of the decode plane's models built at the
``rehearsal`` block of its benchmark configuration
(``chipbench/configs/*.json``, read only), seeded, float32 unless asked.
What differs between families is data here (``FAMILIES``), never a
branch on a family's name.  A new model joins by its entry here; its
own controls go in a file of its own.

- ``Kit``: what one test file builds once and its cases share (a module
  fixture makes one): the models, their references, every engine's
  executables by geometry, the traced logits a ``Through`` drives;
- ``engine``, ``tokens``, ``run``, ``scaled``, ``clean``,
  ``paged_oracle``: an engine at the tests' geometry, a seeded prompt, a
  scheduler run to its end, a logit difference over the reference's
  size, a case's telemetry, the paged kernels' oracle jitted;
- ``Through`` and ``compare``: the cache driven by hand through a
  model's own logits and held to its plain reference on logits; for a
  routed model with the program's selection (``with_picks``,
  ``reference_with``), the differing selections held to a margin.
"""
import dataclasses
import importlib.util
import json
import pathlib
from typing import Callable, Dict, Optional, Tuple

import numpy as onp
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx  # noqa: F401  (registers ops + kernel specs)
from mxnet_tpu import telemetry
from mxnet_tpu.ops.paged_attention import paged_attention_reference
from mxnet_tpu.serving import (AXK1, LFM2, DecodeEngine, DecodeModel,
                               FalconH1, slo)
from mxnet_tpu.serving.decode import axk1 as axk1_mod
from mxnet_tpu.serving.decode import lfm2 as lfm2_mod
from mxnet_tpu.serving.decode.engine import _frozen

REPO = pathlib.Path(__file__).resolve().parent.parent
CHUNK, VOCAB = 16, 128
# the engine a case gets unless it asks for another
GEOMETRY = dict(max_slots=3, page_size=8, pages_per_slot=8, num_pages=24,
                prefill_chunk=CHUNK, prefill_floor=8)


def load(path, name):
    """A file of the repository as a module of its own."""
    spec = importlib.util.spec_from_file_location(name, REPO / path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _read(name):
    """``chipbench/configs/<name>.json``: the published widths, and the
    ``rehearsal`` block apart."""
    with open(REPO / "chipbench" / "configs" / f"{name}.json") as f:
        cfg = json.load(f)
    return cfg, cfg.pop("rehearsal")


def config(name, **over):
    """A benchmark configuration at its ``rehearsal`` size, with
    ``over`` on top."""
    cfg, rehearsal = _read(name)
    return {**cfg, **rehearsal, **over}


def published(name, **over):
    """A benchmark configuration at its published widths, with ``over``
    on top."""
    return {**_read(name)[0], **over}


def _within(lo, hi):
    return lambda v: lo <= v <= hi


@dataclasses.dataclass(frozen=True)
class Family:
    """One model of the decode plane and what its contract reads of it.

    ``config``: its benchmark configuration (``None``: not built from
    one); ``over``: what every model of the family sets on top of the
    rehearsal size; ``reference``: its plain float32 forward under
    ``chipbench/reference/``; ``router``: the module whose
    ``route_topk`` the model selects experts by (routed families).
    ``warmup``: the keys of ``warmup([8, CHUNK])`` on an engine of two
    slots.  ``counters``: a decode step's counters by name, each with
    what its mean over a run must satisfy.  ``tol`` and ``margin`` by
    dtype: the largest scaled difference of the cached path's logits
    from the reference's, and the widest selection that may differ from
    the reference's and still count as a tie.  ``branches``: pieces of
    a layer that each weigh in the logits by ``branch_floor`` or more
    (zeroed, or as ``CHANGED`` says).  ``refused``: configurations the
    model must refuse, each with what its refusal says; ``needs``: a key
    it cannot do without.  ``counted_by``: the benchmark's module under
    ``chipbench/models/`` that counts its parameters; ``held_twice``:
    parameters held twice and counted once (a tied head).
    ``router_control``: how the router's product in a lower precision
    is shown to fail the margin (prompt length, pages a slot, the
    configuration, how far past the margin).

    At the chip's size (``test_decode_in_place.py``): ``cell``, the
    benchmark cell whose engine geometry the executables are compiled
    at; ``at_cell()``, the model at its published widths, parameters as
    shapes, cut in depth (every layer's buffers are treated alike);
    ``cell_pool_bytes``, its cache at that geometry; ``in_place``, by
    executable key, what the compiled executable must show beside the
    cache aliased whole: ``temp_bytes``, a bound on its temporaries;
    ``calls``, its Mosaic calls by a piece of their name (the empty
    piece counts them all); ``products``, its dots and convolutions."""
    name: str
    build: Callable
    config: Optional[str] = None
    over: dict = dataclasses.field(default_factory=dict)
    reference: Optional[str] = None
    router: Optional[object] = None
    warmup: Tuple[str, ...] = ()
    counters: Dict[str, Callable] = dataclasses.field(default_factory=dict)
    tol: Dict[str, float] = dataclasses.field(default_factory=dict)
    margin: Dict[str, float] = dataclasses.field(default_factory=dict)
    branches: Tuple[str, ...] = ()
    branch_floor: float = 0.0
    refused: Tuple[Tuple[dict, str], ...] = ()
    needs: Optional[str] = None
    counted_by: Optional[str] = None
    held_twice: Tuple[str, ...] = ()
    router_control: Optional[dict] = None
    cell: Optional[str] = None
    at_cell: Optional[Callable] = None
    cell_pool_bytes: int = 0
    in_place: Dict[str, dict] = dataclasses.field(default_factory=dict)

    def model(self, dtype="float32", **over):
        """A model of the family and the configuration it was built
        from."""
        if self.config is None:
            return self.build(dtype=dtype, **over), None
        cfg = config(self.config, **{**self.over, **over})
        return self.build(cfg, seed=5, dtype=dtype), cfg


# A piece of a layer changed otherwise than zeroed: the selection bias
# reversed and made larger than the scores' spread
CHANGED = {"expert_bias": lambda v: -20 * v}

# -- the families -------------------------------------------------------------
#
# Tolerances (``tol``): largest |logit - reference| over largest
# |reference|; margins: how wide a differing selection may be and still
# count as a tie.  Each limit lies between two readings on a CPU at the
# rehearsal size: the sound program's largest over its family's cases
# (``test_prefill_then_cached_decode_matches_the_reference_on_logits``)
# and a control's smallest, the program run in the nearest lower
# precision.

# Falcon-H1.  float32: the cached path and the reference differ only in
# the order of float32 sums (chunked scan or kernel against the
# recurrence, online against plain softmax): measured 5.2e-7 to 8.7e-7.
# 1e-5 leaves an order of magnitude for another machine's sums and sits
# a thousand times under what bfloat16 shows, so a path that quietly ran
# in bfloat16 fails it.  bfloat16: the weights are the same bfloat16
# numbers on both sides; the program rounds every activation to 8 bits
# of mantissa (2^-9 = 2e-3 a rounding) where the reference keeps
# float32, through two layers and a head: measured 9.8e-3 to 1.5e-2, so
# 3e-2.  What a wrong term reads (the reference against itself,
# float32): without the convolution's oldest tap 0.47, with the keys
# zeroed 0.43.  The weights are drawn at the fan-in scale over their
# multiplier, as a muP-trained checkpoint has them, so every branch
# weighs in the logits at the published multipliers: with one branch's
# matrix zeroed (the keys, the attention heads' output, the state-space
# heads' output, the MLP's) the logits move by a tenth of their size or
# more (measured 0.33, 0.75, 0.97, 0.52 over 40 positions); drawn at the
# plain fan-in scale the keys read 4e-4.  No router: no selection
# differs.
HYBRID = Family(
    "hybrid", FalconH1, config="falcon_h1_34b",
    reference="falcon_h1_ref",
    warmup=("decode", "state_edit", "decode_fill_b16", "decode_fill_b32",
            "state_reset", "prefill_b8", "prefill_b16", "prefill_b32"),
    tol={"float32": 1e-5, "bfloat16": 3e-2},
    margin={"float32": 0.0, "bfloat16": 0.0},
    branches=("wk", "wo", "w_out", "w_down"), branch_floor=0.1,
    refused=(({"mamba_norm_before_gate": True}, "mamba_norm_before_gate"),),
    needs="mamba_d_ssm", counted_by="falcon_h1",
    # 768 pages x 128 x (4 KV heads x 128) bf16 and, a layer, 96 slots of
    # (32 x 128 x 256) + (3 x 5120) float32 state; 2 of its 6 layers
    cell="falcon_h1_decode_chat",
    at_cell=lambda: FalconH1(published("falcon_h1_34b",
                                       num_hidden_layers=2), abstract=True),
    cell_pool_bytes=2 * (2 * 768 * 128 * 512 * 2 + 96 * 32 * 128 * 256 * 4
                         + 96 * 3 * 5120 * 4),
    # The state buffer is 403 MB: the temporaries stay under it in every
    # executable, no copy of a buffer it was donated.  A prefill of
    # lanes (two and four slots' chunks of 128) reads its states a slice
    # a lane and writes them back in one scatter: a gather of the lanes'
    # states had the TPU compiler copy the buffer whole, a layer (PERF.md
    # section 6, PR 38); 17.8 MB (b128), 11.2 (b16), 33.9 (b256), 73.0
    # (b512) at these two layers and the whole vocabulary.  ONE product a
    # weight matrix whatever the lanes (nine a layer: q, k, v, o, the
    # mixer's in and out, the MLP's three), six a lane and layer for what
    # is a slot's own (two of its attention, four of its chunked scan),
    # and the head: a product over the lanes' last rows, a
    # multiply-reduce for one row.  The decode step with one or two
    # lanes inside it reads the lanes' states out of what the state
    # update left and scatters them back into it (39.6 MB and 17.7 at the
    # cell's six layers), one product a weight matrix for the slots' rows
    # and the lanes' together, and one head over both; a layer's decode
    # rows go through the state update and the paged kernel, and in the
    # decode step alone rope on q and on k beside them.
    in_place={
        "decode": dict(calls={"": 8, "mxtpu_ssm_update": 2}),
        "prefill_b16": dict(temp_bytes=96e6, products=2 * (9 + 6)),
        "prefill_b128": dict(temp_bytes=96e6, products=2 * (9 + 6)),
        "prefill_b256": dict(temp_bytes=96e6, products=2 * (9 + 12) + 1),
        "prefill_b512": dict(temp_bytes=96e6, products=2 * (9 + 24) + 1),
        "decode_fill_b128": dict(
            temp_bytes=100e6, products=2 * (9 + 6) + 1,
            calls={"mxtpu_ssm_update": 2, "mxtpu_paged_attention": 2}),
        "decode_fill_b256": dict(
            temp_bytes=100e6, products=2 * (9 + 12) + 1,
            calls={"mxtpu_ssm_update": 2, "mxtpu_paged_attention": 2}),
        "state_reset": {}})

# A.X-K1, at the configuration's initialisation: every branch at the
# plain fan-in scale, the held routed experts' W_down alone divided by
# ``routed_down_divisor`` (PERF.md section 6, PR 33).  float32: the
# cached path and the reference differ in the order of float32 sums and
# in the attention's form (absorbed, online softmax over pages, against
# plain): 4.0e-7 to 7.7e-7, no selection differs; the experts' matrices
# in float8 read 3.2e-2 or more, the router's product in bfloat16 breaks
# ten ties up to 1.9e-3 wide: 5e-6 and 1e-5.  bfloat16: the weights are
# the same bfloat16 numbers on both sides, the program rounds what it
# multiplies to 8 bits of mantissa; its residual stream and router are
# float32.  Logits: sound 3.8e-3 to 1.44e-2, the experts' matrices (held
# and shared) in float8 3.8e-2 to 4.5e-2, the attention's in float8
# 6.8e-2 or more: 2.3e-2.  Ties: with every branch at full strength the
# products upstream of the router break them, 14 over 250 positions and
# up to 1.26e-3 wide, as wide as a bfloat16 router's product would
# (1.25e-3: no control at this dtype); the control is the router's
# product in float8's 3 bits of mantissa (selections differ at 71 of 250
# positions, up to 2.4e-2 wide): 2.5e-3.
LATENT = Family(
    "latent", AXK1, config="axk1_519b", reference="axk1_ref",
    router=axk1_mod,
    warmup=("decode", "state_edit", "prefill_b8", "prefill_b16",
            "prefill_b32"),
    counters={
        "moe_local_pair_share": _within(0.0, 1.0),
        # held 3 of 48 at top-2: a sixteenth of the pairs when routing
        # is even
        "moe_expert_rows_mean": lambda v: 0.0 < v < 2.0,
        "moe_expert_rows_max": _within(0.0, 2.0),
        "moe_experts_idle_share": _within(0.0, 1.0),
        # a slot's context fits one block: two live slots read 1/2, one 0
        "latent_overlap_share": lambda v: 0.0 < v <= 0.5},
    tol={"float32": 5e-6, "bfloat16": 2.3e-2},
    margin={"float32": 1e-5, "bfloat16": 2.5e-3},
    branches=("wo", "ws_down", "e1_down", "w_router", "wkv_b"),
    branch_floor=5e-4,
    refused=(({"scoring_func": "softmax"}, "scoring_func"),
             ({"topk_method": "greedy"}, "topk_method"),
             ({"n_shared_experts": 2}, "n_shared_experts"),
             ({"experts_first": 46}, "held experts"),
             ({"rope_scaling": {"type": "linear", "factor": 2}},
              "rope_scaling")),
    needs="kv_lora_rank", counted_by="axk1",
    router_control=dict(prompt=250, pages_per_slot=32, over={}, past=2.0),
    # one latent page buffer a layer, 4096 pages x 128 x 640 lanes bf16
    # (671 MB); 2 of its 7 layers, the dense one and an expert layer with
    # its 12 held experts, the vocabulary's slice.  All seven layers,
    # compiled by hand (PERF.md section 4, PR 33): arguments 14.380 GB of
    # which the pool's 4.698 GB is aliased; temporaries 99.6 MB (decode),
    # 152.0 MB (prefill b256), 65.0 MB (b32).  At these two layers the
    # temporaries were 47.7, 103.7 and 35.1 MB when the cell was added;
    # ``prefill_b512`` is two lanes of 256 (PR 38): 161.2 MB against 103.8
    # at one lane.  The decode step's attention is the named kernel, once
    # a layer; a chunk walks the slot's live pages in XLA.
    cell="axk1_decode_reasoning",
    at_cell=lambda: AXK1(published("axk1_519b", num_hidden_layers=2),
                         abstract=True),
    cell_pool_bytes=2 * 4096 * 128 * 640 * 2,
    in_place={
        "decode": dict(temp_bytes=64e6,
                       calls={"": 2, "mxtpu_latent_attention": 2}),
        "prefill_b32": dict(temp_bytes=48e6, calls={"": 0}),
        "prefill_b256": dict(temp_bytes=140e6, calls={"": 0}),
        "prefill_b512": dict(temp_bytes=280e6, calls={"": 0})})

# LFM2, every branch at the plain fan-in scale (``routed_down_divisor``
# 1, whatever the benchmark's token check made the configuration
# choose).  float32: the cached path and the reference differ in the
# order of float32 sums (the stacked product sums over experts and width
# at once; the paged kernel's online softmax): sound 8.4e-7 to 1.12e-6,
# no selection differs; the experts' matrices in bfloat16 read 2.3e-3,
# the operators' 1.3e-2, the gate B * u and the tail in bfloat16 5.2e-3,
# a weight from the biased score 3.7e-2, a chunk that starts from a zero
# tail 0.28 or more: 5e-6.  A router's product in bfloat16 breaks three
# ties in 120 positions, up to 6.1e-4 wide: 1e-5.  bfloat16: the weights
# are the same bfloat16 numbers on both sides, the program rounds what
# it multiplies to 8 bits of mantissa (at 64 wide a product is a sum of
# few terms and its rounding shows more than at 2048), but for the
# convolution operator's two products, which take their rows as two
# numbers (``lfm2._dot_wide``); its residual stream, gate, tail and
# router are float32.  Logits: sound 8.5e-3 to 1.62e-2 over the cases
# (1.0e-2 to 2.5e-2 while the convolution's products took their rows
# rounded once); the experts' matrices in float8 read 2.7e-2 to 4.1e-2,
# the operators' 0.20 or more: 2.1e-2.  Ties: the products upstream of
# the router break them, at up to three positions of a case and up to
# 2.0e-3 wide (8.4e-3 before); the control is the router's product in
# float8's 3 bits of mantissa (selections differ at half of 121
# positions, up to 2.7e-2 wide): 7e-3.  The router's control runs at the
# published 32 experts top-4: among 8 scores ties are too rare for 120
# positions to meet one.
MIXED = Family(
    "mixed", LFM2, config="lfm2_8b_a1b", over={"routed_down_divisor": 1},
    reference="lfm2_ref", router=lfm2_mod,
    warmup=("decode", "state_edit", "state_reset", "prefill_b8",
            "prefill_b16", "prefill_b32"),
    counters={
        # every expert is held: a live slot gives top-k of the 8 experts
        # a row (the mean, times 8 / 2: the live slots)
        "moe_expert_rows_mean": lambda v: 1.0 <= v * 8 / 2 <= 2.0,
        "moe_expert_rows_max": _within(1.0, 2.0),
        "moe_experts_idle_share": _within(0.5, 0.75),
        "moe_load_imbalance": lambda v: v >= 1.0},
    tol={"float32": 5e-6, "bfloat16": 2.1e-2},
    margin={"float32": 1e-5, "bfloat16": 7e-3},
    branches=("wo", "w_out", "w_in", "conv_w", "experts_w2", "w_router",
              "w2", "wk", "expert_bias"),
    branch_floor=5e-4,
    refused=(({"conv_bias": True}, "conv_bias"),
             ({"tie_embedding": False}, "tie_embedding"),
             ({"layer_types": ["conv"] * 5}, "layer_types"),
             ({"layer_types": ["conv"] * 5 + ["sliding_attention"]},
              "layer_types"),
             ({"num_key_value_heads": 3}, "KV heads")),
    needs="conv_L_cache", counted_by="lfm2", held_twice=("head",),
    router_control=dict(prompt=120, pages_per_slot=16, past=1.5,
                        over={"num_experts": 32, "num_experts_per_tok": 4}),
    # The first 4 of its 16 layers, one whole period: two dense
    # convolution layers, a routed attention layer, a routed convolution
    # layer, all 32 experts stacked.  K and V pages in the one attention
    # layer, 3072 pages x 128 x 512 lanes bf16 (403 MB a buffer); a tail
    # of 192 slots x 2 x 2048 float32 (3.1 MB) in the other three.  All
    # sixteen layers, compiled by hand (PERF.md section 4, PR 35):
    # arguments 14.326 GB of which the cache's 3.259 GB is aliased;
    # temporaries 51.8 MB (decode), 18.4 MB (prefill b256), 17.1 MB (b32);
    # a chunk that gathered its slot's whole table of 4,096 positions for
    # one softmax kept 457.2 MB (b256).  ``prefill_b512`` is two lanes of
    # 256 (PR 38): 64.1 MB at these four layers (5.8 at one lane), the
    # stacked experts' rows; while two lanes' walks read the page buffers
    # themselves the compiler copied K and V into another layout, 818 MB.
    # The one attention layer: rope on q and on k, then the paged kernel
    # in the decode step; a chunk gathers its pages in XLA.
    cell="lfm2_decode_reasoning",
    at_cell=lambda: LFM2(published(
        "lfm2_8b_a1b", num_hidden_layers=4,
        layer_types=published("lfm2_8b_a1b")["layer_types"][:4]),
        abstract=True),
    cell_pool_bytes=2 * 3072 * 128 * 512 * 2 + 3 * 192 * 2 * 2048 * 4,
    in_place={
        "decode": dict(temp_bytes=64e6, calls={"mxtpu_paged_attention": 1,
                                               "mxtpu_rope": 2}),
        "prefill_b32": dict(temp_bytes=32e6, calls={
            "mxtpu_paged_attention": 0, "mxtpu_rope": 2}),
        "prefill_b256": dict(temp_bytes=32e6, calls={
            "mxtpu_paged_attention": 0, "mxtpu_rope": 2}),
        "prefill_b512": dict(temp_bytes=96e6, calls={
            "mxtpu_paged_attention": 0, "mxtpu_rope": 2})})

# The built-in model: no configuration, no plain reference of the same
# arithmetic here (its own oracle is its dense path), no state, no router.
TRANSFORMER = Family(
    "transformer",
    lambda dtype="float32", **over: DecodeModel(
        VOCAB, **{"dim": 32, "n_heads": 4, "n_layers": 2, "seed": 0,
                  "dtype": dtype, **over}),
    warmup=("decode", "state_edit", "prefill_b8", "prefill_b16",
            "prefill_b32"),
    # gpt2_decode_chat: 6144 pages x 16 x (16 heads x 64), bf16, 96 slots
    # x 64 pages; 2 of its 24 layers.  Before the kernel took the pools
    # as whole operands in HBM (PR 30) the temporaries were 3,064,320 and
    # 3,354,624 bytes at 2 layers: no more of them now.  ``prefill_b512``
    # is the multi-lane executable of a paged-only model: four slots'
    # chunks of 128 in one dispatch.
    cell="gpt2_decode_chat",
    at_cell=lambda: DecodeModel(512, dim=1024, n_heads=16, n_layers=2,
                                mlp_ratio=4, dtype="bfloat16"),
    cell_pool_bytes=2 * 2 * 6144 * 16 * 1024 * 2,
    in_place={key: dict(temp_bytes=4 << 20)
              for key in ("decode", "prefill_b128", "prefill_b512")})

FAMILIES = {f.name: f for f in (TRANSFORMER, HYBRID, LATENT, MIXED)}
ALL = list(FAMILIES)
# by what their contract reads: a plain reference and a configuration,
# recurrent state, a router
CONFIGURED = [f for f in ALL if FAMILIES[f].config]
STATEFUL = ["hybrid", "mixed"]
ROUTED = [f for f in ALL if FAMILIES[f].router is not None]


def build(family, dtype="float32", **over):
    """A model of ``family`` (no kit: built anew)."""
    return FAMILIES[family].model(dtype, **over)[0]


# -- what a case uses ---------------------------------------------------------

def engine(model, geometry=GEOMETRY, **kw):
    """A ``DecodeEngine`` over ``model`` at ``geometry``, ``kw`` on
    top."""
    return DecodeEngine(model, **{**geometry, **kw})


def tokens(n, seed, vocab=VOCAB):
    return [int(t) for t in
            onp.random.RandomState(seed).randint(0, vocab, size=n)]


# the paged kernels' oracle under one ``jit`` a shape: op by op it
# compiles every operation anew for each case's shapes
paged_oracle = jax.jit(paged_attention_reference)


def run(sch):
    """Step a scheduler by hand until it has nothing left to do."""
    while sch._has_work():
        sch.step()


def scaled(got, want):
    """Largest |got - want| over largest |want|."""
    return float(onp.abs(got - want).max() / onp.abs(want).max())


@pytest.fixture
def clean(monkeypatch):
    """A case leaves no telemetry sink and no declared objective behind."""
    telemetry.clear_sinks()
    slo.undeclare()
    yield
    slo.undeclare()
    telemetry.clear_sinks()
    telemetry.enabled()     # re-sync env cache after monkeypatch undo


class Kit:
    """What one test file builds once and its cases share: a module
    fixture of the file makes one.

    Models by family, dtype and configuration; the plain references;
    the executables of every engine by model and geometry (an engine a
    case asks for is new, its cache, resident state and accounting its
    own, and compiles only what no earlier case of the file compiled);
    a model's logits under ``jit``, watched for the router's picks, by
    what stands in the program's place (``variant``: a patched method
    is another program)."""

    def __init__(self):
        self._models, self._refs, self._execs, self._cores = {}, {}, {}, {}

    def model(self, family, dtype="float32", **over):
        """``(model, configuration)`` of ``family``, built once."""
        key = (family, dtype, _frozen(over))
        if key not in self._models:
            self._models[key] = FAMILIES[family].model(dtype, **over)
        return self._models[key]

    def ref(self, family):
        name = FAMILIES[family].reference
        if name not in self._refs:
            self._refs[name] = load(f"chipbench/reference/{name}.py", name)
        return self._refs[name]

    def engine(self, family, dtype="float32", **kw):
        """A new engine over the family's model, at ``GEOMETRY`` and
        ``kw``, that shares its executables with every engine of the
        same model and geometry in this kit."""
        model, _ = self.model(family, dtype)
        eng = engine(model, **kw)
        eng._exec = self._execs.setdefault((id(model), _frozen(kw)), {})
        return eng

    def cores(self, family, model, variant=None):
        """The model's prefill and decode logits under ``jit``, each
        handing back the router's picks beside its outputs."""
        key = (id(model), variant)
        if key not in self._cores:
            router = FAMILIES[family].router
            self._cores[key] = (with_picks(model.prefill_logits, router),
                                with_picks(model.decode_logits, router))
        return self._cores[key]


# -- the cache driven by hand, against the plain reference --------------------

def with_picks(core, router):
    """``core`` under ``jit`` returning, beside its own outputs, the
    experts ``router.route_topk`` selected in every routed layer
    ``(rows, top-k)`` each: the router is watched while the core is
    TRACED, so the jitted whole hands the selections back as outputs.
    No router: no picks."""
    if router is None:
        return jax.jit(lambda *args: (core(*args), ()))

    def run_(*args):
        picks = []
        real = router.route_topk

        def spy(scores, top_k, **kw):
            index, weight = real(scores, top_k, **kw)
            picks.append(index)
            return index, weight

        router.route_topk = spy
        try:
            out = core(*args)
        finally:
            router.route_topk = real
        return out, tuple(picks)

    return jax.jit(run_)


class Through:
    """Drives an engine's cache by hand through the model's own logits
    (one lane a prefill dispatch: the ``lanes == 1`` case of the one
    prefill path), keeping the logits the engine's executables reduce to
    a token and, for a routed model, the experts its router selected at
    every slot's position, routed layer by routed layer."""

    def __init__(self, kit, family, model, eng, variant=None):
        self.model, self.eng = model, eng
        self.picked = {}        # (slot, position) -> [experts a layer]
        self._prefill, self._decode = kit.cores(family, model, variant)

    def _keep(self, picks, rows):
        for row, at in rows:
            self.picked.setdefault(at, []).extend(
                set(onp.asarray(index[row]).tolist()) for index in picks)

    def feed_prompt(self, slot, prompt, chunk=CHUNK):
        """Chunked prefill of ``prompt``; the logits after its last
        token."""
        eng, logits = self.eng, None
        for start in range(0, len(prompt), chunk):
            piece = prompt[start:start + chunk]
            padded = onp.zeros((eng.prefill_bucket(len(piece)),), onp.int32)
            padded[:len(piece)] = piece
            (eng.cache.pool, logits), picks = self._prefill(
                self.model.params, eng.cache.pool, jnp.asarray(padded)[None],
                jnp.asarray([start], jnp.int32),
                jnp.asarray([len(piece)], jnp.int32),
                jnp.asarray(eng.cache.tables[slot], jnp.int32)[None],
                jnp.asarray([slot], jnp.int32))
            self._keep(picks, [(i, (slot, start + i))
                               for i in range(len(piece))])
        return onp.asarray(logits[0], onp.float32)

    def step(self, feed):
        """One decode step; ``feed`` maps slot -> (token, position).
        The logits of every slot fed."""
        n = self.eng.max_slots
        tok, pos = onp.zeros((n,), onp.int32), onp.zeros((n,), onp.int32)
        act = onp.zeros((n,), bool)
        for s, (t, p) in feed.items():
            tok[s], pos[s], act[s] = t, p, True
        (self.eng.cache.pool, logits, *_), picks = self._decode(
            self.model.params, self.eng.cache.pool, jnp.asarray(tok),
            jnp.asarray(pos), jnp.asarray(self.eng.cache.tables, jnp.int32),
            jnp.asarray(act))
        self._keep(picks, [(s, (s, p)) for s, (_, p) in feed.items()])
        return {s: onp.asarray(logits[s], onp.float32) for s in feed}

    def picked_by(self, slot):
        """The picks of ``slot``'s positions, by position."""
        return {p: experts for (s, p), experts in self.picked.items()
                if s == slot}


def reference_with(ref, params, toks, cfg, picked):
    """The reference's logits over ``toks`` WITH THE PROGRAM'S
    SELECTION: in every routed layer and at every position the experts
    the program's router picked, weighted by the reference's own
    (unbiased) scores.  Where that selection differs from the
    reference's own, a tie that rounding upstream broke the other way,
    the position met a flip, as wide as the differing expert's (biased)
    score lies from the reference's last selected one.  Returns
    ``(logits, positions that met a flip, the widest flip)``; the caller
    holds the widest to its margin, beyond which it is another routing
    and no tie."""
    own = ref.route
    k = cfg["num_experts_per_tok"]
    eps = getattr(ref, "TOPK_EPS", 0.0)
    layer_no = [0]
    flipped, widest = set(), [0.0]

    def route(scores, *rest):
        # ``(scores, cfg)``, or ``(scores, selection bias, cfg)``
        w = own(scores, *rest)
        bias = rest[0] if len(rest) > 1 else None
        ranked = onp.asarray(scores if bias is None
                             else scores + jnp.asarray(bias, jnp.float32))
        sel = onp.asarray(w) > 0
        kth = onp.sort(ranked, axis=1)[:, -k]
        for pos in range(ranked.shape[0]):
            mine = picked[pos][layer_no[0]]
            theirs = set(onp.nonzero(sel[pos])[0].tolist())
            for e in mine ^ theirs:
                widest[0] = max(widest[0],
                                float(abs(ranked[pos, e] - kth[pos])))
                flipped.add(pos)
            sel[pos] = False
            sel[pos, sorted(mine)] = True
        layer_no[0] += 1
        w = jnp.where(jnp.asarray(sel), scores, 0.0)
        return (w / (w.sum(axis=-1, keepdims=True) + eps)
                * rest[-1]["routed_scaling_factor"])

    ref.route = route
    try:
        with jax.default_matmul_precision("highest"):
            logits = ref.forward(params, jnp.asarray(toks, jnp.int32), cfg)
    finally:
        ref.route = own
    return onp.asarray(logits, onp.float32), flipped, widest[0]


def reference_logits(kit, family, params, toks, cfg, picked):
    """``(logits, positions that met a flip, the widest flip)`` of the
    family's plain reference over ``toks``: with the program's
    selection where the family routes."""
    ref = kit.ref(family)
    if FAMILIES[family].router is not None:
        return reference_with(ref, params, toks, cfg, picked)
    with jax.default_matmul_precision("highest"):
        logits = ref.forward(params, jnp.asarray(toks, jnp.int32), cfg)
    return onp.asarray(logits, onp.float32), set(), 0.0


def compare(kit, family, dtype, sequences, chunk=CHUNK, coarse=None,
            variant=None, pages_per_slot=8, **over):
    """Prefill then decode through the cache, and compare the logits
    with the reference's.  ``sequences``: ``(slot, tokens, prompt
    length, the step it joins at)`` each.  A sequence's prompt is fed by
    chunks of ``chunk`` when it joins, and from then on every step
    decodes its next token beside every other sequence that has one
    left, until none has.  The logits of each prompt's last position and
    of every decoded one go against the reference's: ``(the worst scaled
    difference, positions that met a flip, the widest flip)``.
    ``coarse(params)``: the PROGRAM runs with those parameters (a
    control in lower precision), the reference with the model's.
    ``variant`` names what else stands in the program's place (a
    patched method), so that its traced logits are its own."""
    model, cfg = kit.model(family, dtype, **over)
    eng = engine(model, pages_per_slot=pages_per_slot,
                 num_pages=3 * pages_per_slot)
    through = Through(kit, family, model, eng, variant)
    got, at, step = {}, {}, 0
    good = model.params
    try:
        if coarse is not None:
            model.params = coarse(good)
        while True:
            for slot, toks, n, join in sequences:
                if join == step:
                    eng.acquire_slot(slot, len(toks))
                    got[slot, n - 1] = through.feed_prompt(slot, toks[:n],
                                                           chunk)
                    at[slot] = n
            feed = {slot: (toks[at[slot]], at[slot])
                    for slot, toks, _, _ in sequences
                    if slot in at and at[slot] < len(toks)}
            if not feed and len(at) == len(sequences):
                break
            for slot, logits in through.step(feed).items():
                got[slot, at[slot]] = logits
                at[slot] += 1
            step += 1
    finally:
        model.params = good
    worst, flipped, widest = 0.0, set(), 0.0
    for slot, toks, _, _ in sequences:
        want, flips, wide = reference_logits(kit, family, good, toks, cfg,
                                             through.picked_by(slot))
        worst = max([worst] + [scaled(logits, want[p])
                               for (s, p), logits in got.items()
                               if s == slot])
        flipped |= {(slot, p) for p in flips}
        widest = max(widest, wide)
    return worst, flipped, widest


def compare_one(kit, family, dtype, prompt_len, n_decode=4, **kw):
    """``compare`` of one prompt of ``prompt_len`` tokens in slot 1 and
    ``n_decode`` more decoded after it."""
    toks = tokens(prompt_len + n_decode, seed=prompt_len)
    return compare(kit, family, dtype, [(1, toks, prompt_len, 0)], **kw)


def router_rounded(mantissa_bits):
    """A router's scores with its product as a matmul in a lower
    precision gives it: operands and result of ``mantissa_bits`` bits of
    mantissa (7: bfloat16, 3: float8; ``reduce_precision``, which no
    compiler takes for excess precision it may keep).  In the place of
    ``Float32Stream._scores``."""
    def rounded(a):
        return jax.lax.reduce_precision(a.astype(jnp.float32),
                                        exponent_bits=8,
                                        mantissa_bits=mantissa_bits)

    def scores(self, h, w_router):
        return jax.nn.sigmoid(rounded(jnp.dot(
            rounded(h), rounded(w_router),
            precision=jax.lax.Precision.HIGHEST)))

    return scores


# -- the executables as the engine compiles them on a TPU ---------------------

def resident(spec, slots, pps):
    """The engine's resident decode state as ``spec(shape, dtype)``s:
    tokens, positions, active, page tables."""
    return (spec((slots,), "int32"), spec((slots,), "int32"),
            spec((slots,), "bool"), spec((slots, pps), "int32"))


def lower(model, key, params, pool, spec, slots, pps, chunk):
    """The executable ``key`` of an engine over ``model`` lowered as
    ``DecodeEngine`` compiles it on a TPU, its donated arguments
    donated: ``(lowered, the donated cache buffers)``.  ``decode``: the
    model's step inside the engine's wrapper over the resident state;
    ``decode_fill_b<rows>``: lanes of the full chunk inside it;
    ``prefill_b<rows>``: more rows than the chunk are lanes of the full
    chunk, fewer one lane's bucket, staged in one array; ``state_reset``:
    the state buffers past each layer's paged ones."""
    from functools import partial
    from mxnet_tpu.serving.decode import engine as E
    if key == "state_reset":
        state = tuple(layer[len(widths):] for layer, (widths, _)
                      in zip(pool, model.cache_layout))
        return jax.jit(E._state_reset_core, donate_argnums=(0,)).lower(
            state, spec((), "int32")), state
    rows = int(key.rsplit("b", 1)[1]) if "_b" in key else 0
    if key == "decode" or key.startswith("decode_fill"):
        args = (params, pool, resident(spec, slots, pps))
        if key == "decode":
            core = partial(E._chained_decode_core, model)
        else:
            core = partial(E._turn_core, model, pps)
            args += (spec((rows // chunk, chunk + 3 + pps), "int32"),)
        return jax.jit(core, donate_argnums=(1, 2)).lower(*args), pool
    lanes = max(1, rows // chunk)
    return jax.jit(partial(E._prefill_core, model, pps),
                   donate_argnums=(1,)).lower(
        params, pool, spec((lanes, rows // lanes + 3 + pps), "int32")), pool
