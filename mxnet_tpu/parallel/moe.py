"""Mixture-of-experts with expert parallelism over a mesh axis.

TPU-native capability (no reference counterpart — the reference has no
MoE): Switch-style top-1 routing in the Mesh-TensorFlow einsum
formulation.  Expert weights carry a leading E axis sharded over the
``ep`` mesh axis; the dispatch/combine einsums contract token×expert
one-hots against expert-major activations, so under GSPMD the
token→expert shuffle lowers to all_to_all over ICI — no hand-written
collectives.

Shapes: tokens (N, H); gate (H, E); experts w1 (E, H, F), b1 (E, F),
w2 (E, F, H), b2 (E, H).

Beside it, for serving, the dropless layer of the sigmoid-routed
families (:func:`route_topk`, :func:`held_experts`): a chip that holds
``held`` of the layer's experts routes every row over ALL of them at the
published top-k and computes the part of the result its own experts
give.  No capacity, no dropped token, no exchange: what the absent
experts would add is another chip's to compute.  A chip that holds the
WHOLE layer keeps its experts stacked, three arrays a layer
(:func:`stacked_experts`): the same sum and the same time a step as the
loop of 2-D products, a tenth of its compile time (PERF.md section 6,
PR 35).
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from .. import telemetry

__all__ = ["switch_moe", "moe_expert_sharding", "route_topk",
           "held_experts", "stacked_experts"]


def switch_moe(x, gate_w, w1, b1, w2, b2, capacity_factor: float = 1.25,
               return_stats: bool = False):
    """Top-1 (Switch) MoE layer.

    Tokens route to their argmax expert, subject to a per-expert
    capacity of ``ceil(N/E * capacity_factor)`` — overflow tokens pass
    through with zero expert output (standard Switch behavior, which
    keeps every shape static for XLA).  Dropped tokens are ACCOUNTED,
    never silent: an eager call ticks the ``moe.dropped_tokens``
    telemetry counter directly; a traced caller passes
    ``return_stats=True`` and folds ``stats['dropped_tokens']`` out of
    the executable (Mesh4DTrainer records it per window via
    ``telemetry.record_moe_dropped``).

    Returns ``(y, aux_loss)`` where ``aux_loss`` is the Switch
    load-balancing loss (E · Σ_e f_e · p̄_e) to be added to the training
    objective — or ``(y, aux_loss, stats)`` with ``return_stats=True``,
    where ``stats`` carries ``dropped_tokens`` (int32 scalar),
    ``capacity`` (static int) and ``expert_load`` ((E,) tokens routed
    per expert, pre-drop).
    """
    n, h = x.shape
    e = gate_w.shape[1]
    cap = max(1, math.ceil(n / e * capacity_factor))

    logits = x @ gate_w                                   # (N, E)
    probs = jax.nn.softmax(logits, axis=-1)
    expert = jnp.argmax(probs, axis=-1)                   # (N,)
    gate = jnp.take_along_axis(probs, expert[:, None], axis=1)[:, 0]

    onehot = jax.nn.one_hot(expert, e, dtype=x.dtype)     # (N, E)
    # position of each token within its expert's queue
    pos = jnp.cumsum(onehot, axis=0) - onehot             # (N, E)
    keep = (pos < cap).astype(x.dtype) * onehot
    slot = jnp.einsum("ne,nec->nec", keep,
                      jax.nn.one_hot(pos.astype(jnp.int32), cap,
                                     dtype=x.dtype))      # (N,E,C)

    # dispatch: tokens → expert-major buffers (all_to_all under GSPMD)
    xe = jnp.einsum("nec,nh->ech", slot, x)               # (E, C, H)
    hdn = jax.nn.relu(jnp.einsum("ech,ehf->ecf", xe, w1)
                      + b1[:, None, :])                   # (E, C, F)
    ye = jnp.einsum("ecf,efh->ech", hdn, w2) + b2[:, None, :]

    # combine: expert outputs → token order, weighted by the gate
    combine = slot * gate[:, None, None]
    y = jnp.einsum("nec,ech->nh", combine, ye)            # (N, H)

    # load-balancing loss (Switch Transformer eq. 4)
    frac_tokens = jnp.mean(onehot, axis=0)                # f_e
    frac_probs = jnp.mean(probs, axis=0)                  # p̄_e
    aux = e * jnp.sum(frac_tokens * frac_probs)

    # capacity-overflow accounting: tokens the cap zeroed out.  keep is
    # exactly onehot minus the overflow rows, so N - Σkeep IS the drop.
    dropped = (n - jnp.sum(keep)).astype(jnp.int32)
    if return_stats:
        stats = {"dropped_tokens": dropped, "capacity": cap,
                 "expert_load": jnp.sum(onehot, axis=0)}
        return y, aux, stats
    if not isinstance(dropped, jax.core.Tracer):
        # eager call: the count is concrete — account it here
        telemetry.record_moe_dropped(int(dropped))
    return y, aux


def moe_expert_sharding(mesh: Mesh, axis_name: str = "ep"):
    """NamedShardings for (gate_w, w1, b1, w2, b2): gate replicated,
    expert weights sharded on the leading E axis over ``axis_name``."""
    rep = NamedSharding(mesh, PartitionSpec())
    ex = NamedSharding(mesh, PartitionSpec(axis_name))
    return rep, ex, ex, ex, ex


# -- dropless top-k over a share of the experts ---------------------------------

def route_topk(scores, top_k: int, *, n_group: int = 1, topk_group: int = 1,
               normalize: bool = True, scale: float = 1.0, bias=None,
               eps: float = 1e-20):
    """Select ``top_k`` experts a row from ``scores (rows, experts)``
    (float32, already through their sigmoid): ``(index (rows, top_k),
    weight (rows, top_k))``, the weight an expert's own score, divided
    by the selected scores' sum plus ``eps`` (``normalize``) and times
    ``scale``.

    ``bias (experts,)`` is a SELECTION bias (the load-balancing buffer
    of the families that train without an auxiliary loss): the experts
    are chosen by ``scores + bias``, and weighed by ``scores`` alone.

    ``n_group > 1`` is the family's group-limited form of the same
    function: the experts in ``n_group`` equal groups, a group scored by
    the sum of its two largest scores, the ``topk_group`` best groups
    kept and the selection made inside them.  ``n_group == 1`` is the
    plain top-k over all experts."""
    rows, experts = scores.shape
    pick = scores if bias is None else scores + bias
    if n_group > 1:
        per = experts // n_group
        grouped = pick.reshape(rows, n_group, per)
        rank = jax.lax.top_k(grouped, 2)[0].sum(axis=-1)    # (rows, groups)
        kept = jax.lax.top_k(rank, topk_group)[1]           # (rows, kept)
        open_ = (kept[:, :, None] == jnp.arange(n_group)).any(axis=1)
        pick = jnp.where(jnp.repeat(open_, per, axis=1), pick, -jnp.inf)
    index = jax.lax.top_k(pick, top_k)[1]
    weight = jnp.take_along_axis(scores, index, axis=1)
    if normalize:
        weight = weight / (weight.sum(axis=-1, keepdims=True) + eps)
    return index, weight * scale


def held_experts(h, index, weight, experts, first: int, valid=None):
    """The routed result's part that the experts held here give:
    ``sum_i weight_i E_i(h)`` over the selected experts ``i`` in
    ``[first, first + len(experts))``, ``E(h) = (silu(h W_gate) * h
    W_up) W_down``.  ``h (rows, hidden)``; ``index``/``weight (rows,
    top_k)`` from :func:`route_topk`; ``experts`` a sequence of
    ``(W_gate, W_up, W_down)``, expert ``first + j`` at ``j``.  Returns
    ``(y (rows, hidden) float32, counters)``.

    Every held expert is computed for every row and a row's product is
    taken at its routing weight, 0 where it was not routed there: exact
    over the routed pairs, a fixed shape, and no gather.  The experts'
    weights are read once whatever the rows, which is what a decode
    step's few rows are bound by (PERF.md section 6, PR 33).

    ``counters`` (float32 scalars, over the rows of ``valid``, all rows
    if None): ``local_pairs`` routed (row, expert) pairs that met a held
    expert, ``pairs`` all routed pairs, ``rows_mean`` and ``rows_max``
    rows a held expert got, ``idle`` held experts that got none."""
    rows = h.shape[0]
    live = jnp.ones((rows,), bool) if valid is None else valid
    y = jnp.zeros(h.shape, jnp.float32)
    got = []
    for j, (w_gate, w_up, w_down) in enumerate(experts):
        here = index == first + j                           # (rows, top_k)
        w_j = jnp.where(here, weight, 0.0).sum(axis=-1)     # (rows,)
        a = jax.nn.silu(h @ w_gate) * (h @ w_up)
        y = y + w_j[:, None] * jnp.dot(
            a, w_down, preferred_element_type=jnp.float32)
        got.append((here.any(axis=-1) & live).sum())
    return y, _counters(jnp.stack(got), live, index.shape[1])


def _counters(got, live, top_k: int) -> dict:
    """The experts' counters from ``got (experts,)``, the live rows each
    expert held here was given."""
    got = got.astype(jnp.float32)
    return {"local_pairs": got.sum(),
            "pairs": live.sum().astype(jnp.float32) * top_k,
            "rows_mean": got.mean(), "rows_max": got.max(),
            "idle": (got == 0).sum().astype(jnp.float32)}


def stacked_experts(h, index, weight, w_gate, w_up, w_down, valid=None):
    """:func:`held_experts` for a layer held WHOLE, its experts stacked:
    ``w_gate``/``w_up (experts, hidden, width)``, ``w_down (experts,
    width, hidden)``, expert ``e`` at ``e``.  ``(y (rows, hidden)
    float32, counters)``, the same sum and the same counters.

    Every expert is computed for every row, as there, in three batched
    products and not ``3 x experts`` of two dimensions: on the chip a
    step takes the same time (the weights' stream bounds both to 256
    rows) and an executable compiles in a tenth of it, which at 32
    experts in 14 layers is what a start pays (PERF.md section 6, PR
    35; rows sorted by expert through ``lax.ragged_dot`` took twice the
    time).  A row's routing weight, 0 where it was not routed to the
    expert, scales the gated activation in float32 before its one
    rounding to the weights' dtype; the down-projection sums over
    experts and width at once, in float32."""
    rows, experts = h.shape[0], w_gate.shape[0]
    live = jnp.ones((rows,), bool) if valid is None else valid
    here = index[:, :, None] == jnp.arange(experts)       # (rows, top_k, e)
    w_e = jnp.where(here, weight[:, :, None], 0.0).sum(axis=1)  # (rows, e)
    gate = jnp.einsum("rd,edf->erf", h, w_gate)
    up = jnp.einsum("rd,edf->erf", h, w_up)
    a = (jax.nn.silu(gate).astype(jnp.float32) * up.astype(jnp.float32)
         * w_e.T[:, :, None]).astype(h.dtype)
    y = jnp.einsum("erf,efd->rd", a, w_down,
                   preferred_element_type=jnp.float32)
    got = (here.any(axis=1) & live[:, None]).sum(axis=0)    # (experts,)
    return y, _counters(got, live, index.shape[1])
