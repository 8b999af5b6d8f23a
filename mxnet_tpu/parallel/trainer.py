"""SPMDTrainer: one fully-compiled, mesh-partitioned training step.

This is the TPU-native fast path that subsumes the reference's
KVStore+engine pipeline (SURVEY.md §3.4): forward, backward, gradient
all-reduce, and the optimizer update are one XLA executable; GSPMD
inserts the ICI collectives that `CommDevice`/NCCL provided.  Gluon's
eager Trainer remains for API parity; benchmarks and multi-chip training
use this.

Design notes:
- params stay replicated (pure DP) or follow per-parameter
  PartitionSpecs (TP/SP) set via ``Parameter.shard``.
- batch tensors are sharded on the 'dp' mesh axis.
- optimizer state lives as a pytree of arrays, donated every step
  (buffer donation == the reference's in-place update kernels).
- BatchNorm moving stats ride the trace-context aux mechanism and are
  folded back after each step.
"""
from __future__ import annotations

import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as onp
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from .. import telemetry
from .. import tracing
from ..base import MXNetError
from ..ndarray import NDArray
from .. import autograd as ag
from ..gluon.block import _TraceContext, _trace_scope
from ..ops import registry as _reg
from ..ops.random import next_key
from .. import optimizer as opt_mod
from .mesh import default_mesh

__all__ = ["SPMDTrainer"]


class SPMDTrainer:
    def __init__(self, net, loss_fn: Callable, optimizer="sgd",
                 optimizer_params: Optional[dict] = None,
                 mesh: Optional[Mesh] = None, batch_axis: int = 0,
                 donate: bool = True, dtype: Optional[str] = None,
                 remat: bool = False, seq_axis: Optional[int] = None,
                 micro_batches: int = 1, zero_stage: Optional[int] = None,
                 data_transform: Optional[Callable] = None,
                 zero: Optional[int] = None):
        self.net = net
        self.loss_fn = loss_fn
        # device-side input preprocessing: a jittable fn applied to each
        # step's data INSIDE the compiled step.  Lets the input pipeline
        # ship compact dtypes (uint8 pixels at 1/4 the f32 bytes over
        # PCIe/ICI) and do normalize/transpose on-chip, where it
        # fuses into the first conv.  (The reference bakes mean/std into
        # its C++ iter on the HOST — iter_image_recordio_2.cc normalize —
        # which quadruples the host->device transfer; on TPU the wire is
        # the scarce resource, so the transform belongs device-side.)
        self._data_transform = data_transform
        # ``mesh`` accepts a raw jax Mesh OR a parallel.mesh4d.MeshPlan
        # (the composed-axes front door); with neither, an exported
        # MXNET_MESH=dp2,tp2 lays out the run, else dp over all devices
        self.plan = None
        if mesh is not None and not isinstance(mesh, Mesh):
            self.plan = mesh
            mesh = mesh.mesh
        elif mesh is None:
            from .mesh4d import mesh_plan_from_env
            self.plan = mesh_plan_from_env()
            if self.plan is not None:
                mesh = self.plan.mesh
        self.mesh = mesh or default_mesh()
        self.batch_axis = batch_axis
        # sequence parallelism: shard this data axis over the mesh's
        # "sp" axis (ring attention inside the model exchanges K/V
        # between the sequence shards)
        self.seq_axis = seq_axis
        # rematerialization: recompute the forward during backward
        # instead of keeping activations live — trades FLOPs for HBM
        # (the jax.checkpoint knob the build targets for long-context /
        # big-batch training; the reference has no equivalent because
        # its engine frees activations eagerly per-op)
        self.remat = bool(remat)
        # gradient accumulation: split each step's batch into k
        # micro-batches scanned sequentially, averaging gradients —
        # activations live for one micro-batch at a time (the HBM lever
        # for big effective batches; composes with remat).  BatchNorm
        # batch statistics are per-micro-batch, like any accumulation
        # scheme's.
        if micro_batches < 1:
            raise MXNetError("micro_batches must be >= 1")
        self.micro_batches = int(micro_batches)
        # ZeRO-style memory sharding over the dp axis (the GSPMD
        # re-expression of the reference's server-held optimizer state,
        # kvstore_dist_server.h ApplyUpdates, and of ZeRO/FSDP):
        #   0 — off: params and optimizer state replicated across dp.
        #   1/2 — optimizer state sharded over dp; GSPMD turns the
        #       update into reduce-scatter(grad) -> sharded update ->
        #       all-gather(weight), so stages 1 and 2 coincide here.
        #   3 — FSDP: master params ALSO sharded over dp; each use in
        #       the forward all-gathers just-in-time.
        # Per-parameter TP shardings (Parameter.shard) take precedence;
        # tensors with no dp-divisible axis stay replicated.
        # ``zero=`` is the cross-funnel constructor knob (same name as
        # gluon.Trainer's); both default to MXNET_ZERO so `MXNET_ZERO=1`
        # turns on stage-1 sharding with no code change.
        if zero_stage is None:
            zero_stage = zero
        if zero_stage is None:
            from ..optimizer.fused_step import zero_enabled
            zero_stage = 1 if zero_enabled() else 0
        if zero_stage not in (0, 1, 2, 3):
            raise MXNetError("zero_stage must be 0, 1, 2 or 3")
        self.zero_stage = int(zero_stage)
        # mixed precision (parity: AMP bf16 — master weights stay f32,
        # forward/backward compute in bf16 on the MXU; bf16 needs no loss
        # scaling on TPU, SURVEY.md §7 stage 7)
        self.amp_dtype = (jnp.bfloat16
                          if dtype in ("bfloat16", "bf16", "float16")
                          else None)
        # the global AMP policy (amp.init / MXNET_AMP) reaches this
        # funnel too: compute dtype from the policy when the ctor did
        # not pin one, and a dynamic loss scaler whose state rides the
        # scan carry so a whole fused window still dispatches once
        from ..amp import policy as _amp_policy
        self._amp_scaler = None
        if _amp_policy.enabled():
            if self.amp_dtype is None:
                self.amp_dtype = jnp.dtype(_amp_policy.compute_dtype())
            from ..amp.loss_scaler import LossScaler
            init = (2.0 ** 16
                    if _amp_policy.compute_dtype_str() == "float16"
                    else 1.0)
            self._amp_scaler = LossScaler(init_scale=init)
        self.optimizer = opt_mod.create(optimizer, **(optimizer_params or {}))
        self._params = net.collect_params()
        self._pkeys = list(self._params.keys())
        for p in self._params.values():
            p._check_initialized()
        self._opt_state = {
            k: tuple(s._data for s in
                     self.optimizer.create_state(i, self._params[k].data()))
            for i, k in enumerate(self._pkeys)}
        self._step_cache: Dict[Any, Any] = {}
        self._donate = donate
        self.num_update = 0
        self._comm_model = None   # lazy (rs, ag, ar) analytic bytes/step

    # -- sharding ----------------------------------------------------------
    def _zero_spec(self, param):
        """PartitionSpec sharding ``param``'s largest dp-divisible axis
        over 'dp', or None when nothing divides (small biases etc. stay
        replicated — their memory is negligible)."""
        if "dp" not in self.mesh.axis_names:
            return None
        ndp = self.mesh.shape["dp"]
        if ndp <= 1:
            return None
        shape = param.shape
        best = None
        for ax, dim in enumerate(shape or ()):
            if dim % ndp == 0 and (best is None or dim > shape[best]):
                best = ax
        if best is None:
            return None
        spec = [None] * len(shape)
        spec[best] = "dp"
        return PartitionSpec(*spec)

    def _param_sharding(self, param):
        spec = param._sharding
        if spec is None and self.zero_stage >= 3:
            spec = self._zero_spec(param)
        return NamedSharding(self.mesh, spec or PartitionSpec())

    def _composed_zero_spec(self, param):
        """Compose the ZeRO dp-shard ONTO the param's existing spec:
        the largest still-unsharded dp-divisible axis takes 'dp', so a
        P(None, 'tp') row weight's optimizer state lands P('dp', 'tp')
        — 1/(dp·tp) per device, the 4-D composition rule.  Returns the
        spec unchanged when dp is absent/1, already used, or nothing
        divides."""
        spec = param._sharding
        if "dp" not in self.mesh.axis_names:
            return spec
        ndp = self.mesh.shape["dp"]
        if ndp <= 1:
            return spec
        shape = param.shape or ()
        base = list(spec) if spec is not None else []
        base += [None] * (len(shape) - len(base))
        for s in base:
            if s == "dp" or (isinstance(s, (tuple, list)) and "dp" in s):
                return spec
        best = None
        for ax, dim in enumerate(shape):
            if base[ax] is not None:
                continue            # already carries tp/pp/sp/ep
            if dim % ndp == 0 and (best is None or dim > shape[best]):
                best = ax
        if best is None:
            return spec
        base[best] = "dp"
        return PartitionSpec(*base)

    def _opt_state_sharding(self, param):
        """Optimizer-state sharding: follows the param (TP etc.), plus
        the ZeRO dp-shard composed onto whatever axes the param already
        carries."""
        spec = param._sharding
        if self.zero_stage >= 1:
            spec = self._composed_zero_spec(param)
        return NamedSharding(self.mesh, spec or PartitionSpec())

    def _batch_sharding(self, ndim):
        spec = [None] * ndim
        if "dp" in self.mesh.axis_names and self.batch_axis < ndim:
            spec[self.batch_axis] = "dp"
        if (self.seq_axis is not None and "sp" in self.mesh.axis_names
                and self.seq_axis < ndim
                and self.seq_axis != self.batch_axis):
            spec[self.seq_axis] = "sp"
        return NamedSharding(self.mesh, PartitionSpec(*spec))

    # -- compiled step -----------------------------------------------------
    def _make_step_fn(self):
        """The raw (un-jitted) step function + its aux-discovery cell.

        Shared by the single-step jit and the fused multi-step scan
        (``run_steps``).  BatchNorm-style aux state (running stats) is
        folded into ``new_params`` so a device-side loop threads the
        updated stats into the next iteration."""
        net, loss_fn, opt = self.net, self.loss_fn, self.optimizer
        pkeys = self._pkeys
        params = [self._params[k] for k in pkeys]
        pindex = {id(p): i for i, p in enumerate(params)}
        cell = {"aux": []}

        amp = self.amp_dtype

        scaler = self._amp_scaler

        def step(key, lr, wd, p_arrays, opt_state, data, label,
                 amp_state=None):
            if self._data_transform is not None:
                data = self._data_transform(data)
            # traced loss scale: a dynamic-scale update never recompiles
            scale = amp_state[0] if scaler is not None else None

            def loss_of(p_list):
                tc = _TraceContext(key)
                saved = [p._data for p in params]
                if amp is not None:
                    p_list = [a.astype(amp) if jnp.issubdtype(
                        a.dtype, jnp.floating) else a for a in p_list]
                    d_in = data.astype(amp) if jnp.issubdtype(
                        data.dtype, jnp.floating) else data
                else:
                    d_in = data
                try:
                    for p, a in zip(params, p_list):
                        p._data = NDArray(a)
                    with _trace_scope(tc), ag.pause(train_mode=True):
                        out = net.forward(NDArray(d_in))
                        loss = loss_fn(out, NDArray(label))
                    cell["aux"] = list(tc.aux)
                    loss_mean = loss._data.astype(jnp.float32).mean()
                    if scale is not None:
                        # power-of-two multiply: exact for f32/bf16
                        loss_mean = loss_mean * scale
                    return loss_mean, tuple(v for _, v in tc.aux)
                finally:
                    for p, s in zip(params, saved):
                        p._data = s

            grad_target = (jax.checkpoint(loss_of) if self.remat
                           else loss_of)
            n_micro = self.micro_batches
            if n_micro == 1:
                (loss_val, aux), grads = jax.value_and_grad(
                    grad_target, has_aux=True)(list(p_arrays))
            else:
                saved_batch = (data, label)
                ba = self.batch_axis

                def split_mb(x):
                    # arrays of lower rank (e.g. (B,) labels beside
                    # time-major (T, B, F) data) batch on axis 0
                    ax = ba if ba < x.ndim else 0
                    if x.shape[ax] % n_micro:
                        raise MXNetError(
                            f"batch {x.shape[ax]} (axis {ax}) not "
                            f"divisible by micro_batches={n_micro}")
                    # micro chunks along the batch axis, scan dim in
                    # front
                    moved = jnp.moveaxis(x, ax, 0)
                    moved = moved.reshape(
                        (n_micro, moved.shape[0] // n_micro)
                        + moved.shape[1:])
                    return jnp.moveaxis(moved, 1, ax + 1)

                dmb = split_mb(data)
                lmb = split_mb(label)

                def micro(acc, mb):
                    d, l = mb
                    # rebind the closed-over batch for this micro-step
                    nonlocal data, label
                    data, label = d, l
                    (lv, aux), g = jax.value_and_grad(
                        grad_target, has_aux=True)(list(p_arrays))
                    acc = [a + gi for a, gi in zip(acc, g)]
                    return acc, (lv, aux)

                zero = [jnp.zeros(a.shape,
                                  a.dtype if jnp.issubdtype(
                                      a.dtype, jnp.floating)
                                  else jnp.float32)
                        for a in p_arrays]
                gsum, (losses, aux_stack) = jax.lax.scan(
                    micro, zero, (dmb, lmb))
                grads = [g / n_micro for g in gsum]
                loss_val = losses.mean()
                # BN-style aux keeps the LAST micro-batch's update
                aux = jax.tree_util.tree_map(lambda x: x[-1], aux_stack)
                data, label = saved_batch

            def do_update(p_in, g_in, s_in):
                new_params, new_state = [], []
                for k, w, g, st in zip(pkeys, p_in, g_in, s_in):
                    param = self._params[k]
                    if param.grad_req == "null":
                        new_params.append(w)
                        new_state.append(st)
                        continue
                    sp = dict(opt.static_params(0))
                    sp.setdefault("rescale_grad", 1.0)
                    sp.setdefault("clip_gradient",
                                  float(opt.clip_gradient)
                                  if opt.clip_gradient is not None else -1.0)
                    from ..optimizer.optimizer import _lowp_guard
                    fn = _lowp_guard(_reg.get(opt.op_name).fn)
                    eff_lr = lr * param.lr_mult
                    eff_wd = wd * param.wd_mult
                    if opt.uses_lr:
                        out = fn(w, g, *st, lr=eff_lr, wd=eff_wd, **sp)
                    else:
                        out = fn(w, g, *st, wd=eff_wd, **sp)
                    outs = out if isinstance(out, tuple) else (out,)
                    new_params.append(outs[0])
                    new_state.append(tuple(outs[1:]))
                return new_params, new_state

            amp_out = None
            if scaler is None:
                new_params, new_state = do_update(p_arrays, grads,
                                                  opt_state)
            else:
                good = amp_state[1]
                inv = 1.0 / scale
                loss_val = loss_val * inv
                grads = [g * inv.astype(g.dtype)
                         if jnp.issubdtype(g.dtype, jnp.floating) else g
                         for g in grads]
                finite = jnp.bool_(True)
                for g in grads:
                    if jnp.issubdtype(g.dtype, jnp.floating):
                        finite = jnp.logical_and(finite,
                                                 jnp.isfinite(g).all())
                # wire discipline: the gradient collective GSPMD inserts
                # rides next to this round-trip, so the dp ring carries
                # the policy storage dtype; masters update from the
                # dequantized value (checked BEFORE the cast — fp8 e4m3
                # has no inf and would fold overflow into NaN)
                from ..amp import policy as _amp_policy
                wire = jnp.dtype(_amp_policy.storage_dtype())
                grads = [g.astype(wire).astype(g.dtype)
                         if (jnp.issubdtype(g.dtype, jnp.floating)
                             and g.dtype.itemsize > wire.itemsize) else g
                         for g in grads]

                def _apply(opnds):
                    p_in, g_in, s_in = opnds
                    return do_update(p_in, g_in, s_in)

                def _skip(opnds):
                    p_in, _g, s_in = opnds
                    return list(p_in), [tuple(s) for s in s_in]

                new_params, new_state = jax.lax.cond(
                    finite, _apply, _skip,
                    (list(p_arrays), grads, list(opt_state)))
                factor = scaler._scale_factor
                window = scaler._scale_window
                good1 = good + 1.0
                grown = jnp.where(good1 >= window, scale * factor, scale)
                new_scale = jnp.where(
                    finite, grown,
                    jnp.maximum(scale * (1.0 / factor), 1.0))
                new_good = jnp.where(
                    finite, jnp.where(good1 >= window, 0.0, good1), 0.0)
                amp_out = (new_scale, new_good,
                           jnp.logical_not(finite).astype(jnp.float32))
            # fold traced aux updates (BN running stats) into new_params
            # so they flow through the step output — a scanned step sees
            # iteration i's stats at iteration i+1
            for (pobj, _), v in zip(cell["aux"], aux):
                idx = pindex.get(id(pobj))
                if idx is not None:
                    new_params[idx] = v.astype(p_arrays[idx].dtype)
            if scaler is not None:
                return new_params, new_state, loss_val, aux, amp_out
            return new_params, new_state, loss_val, aux

        return step, cell, params

    def _state_shardings(self, params):
        p_shardings = [self._param_sharding(p) for p in params]
        s_shardings = [tuple(self._opt_state_sharding(p) for _ in st)
                       for p, st in zip(
                           params,
                           (self._opt_state[k] for k in self._pkeys))]
        return p_shardings, s_shardings

    def _build_step(self, data_shape, data_dtype, label_shape, label_dtype):
        step, cell, params = self._make_step_fn()
        p_shardings, s_shardings = self._state_shardings(params)
        rep = NamedSharding(self.mesh, PartitionSpec())
        in_shardings = (rep, rep, rep, p_shardings, s_shardings,
                        self._batch_sharding(len(data_shape)),
                        self._batch_sharding(len(label_shape)))
        donate = (3, 4) if self._donate else ()
        # pin outputs to the declared state shardings: without this,
        # GSPMD may hand back e.g. a bias sharded like the matmul it
        # feeds, and the next call's replicated in_sharding rejects it
        out_shardings = (p_shardings, s_shardings, rep, rep)
        if self._amp_scaler is not None:
            in_shardings = in_shardings + (rep,)
            out_shardings = out_shardings + (rep,)
        jitted = jax.jit(step, in_shardings=in_shardings,
                         out_shardings=out_shardings,
                         donate_argnums=donate)
        return jitted, cell

    # -- executable-artifact store (zero-compile restart) ------------------
    def _artifact_fp(self):
        """Content fingerprint of everything a compiled step bakes in
        beyond the (data, label) signature: model identity + parameter
        spec (shapes/dtypes/shardings/mults), optimizer statics, mesh
        geometry, and the trainer's compile-relevant knobs.  Part of
        every ``spmd_step`` artifact key, so a different model, mesh or
        optimizer can never replay this trainer's executables."""
        opt = self.optimizer
        try:
            statics = tuple(sorted(opt.static_params(0).items()))
        except Exception:
            statics = ()
        pspec = tuple(
            (k, tuple(self._params[k].data().shape),
             str(self._params[k].data().dtype),
             repr(self._params[k]._sharding),
             float(self._params[k].lr_mult),
             float(self._params[k].wd_mult),
             self._params[k].grad_req,
             tuple((tuple(a.shape), str(a.dtype))
                   for a in self._opt_state[k]))
            for k in self._pkeys)
        return (type(self.net).__name__,
                getattr(self.loss_fn, "__name__",
                        type(self.loss_fn).__name__),
                pspec, type(opt).__name__, opt.op_name, statics,
                repr(opt.clip_gradient),
                bool(self._donate), self.batch_axis, self.seq_axis,
                self.remat, self.micro_batches, self.zero_stage,
                str(self.amp_dtype), self._data_transform is not None,
                tuple(self.mesh.axis_names),
                tuple(int(self.mesh.shape[a])
                      for a in self.mesh.axis_names))

    def _resolve_exec(self, sig, jitted, cell, args):
        """First execution of a step signature: consult the executable-
        artifact store.  Hit → deserialize (no compile recorded;
        aux-param discovery re-runs as a compile-free abstract trace so
        ``cell`` matches what a real trace would have found).  Miss
        with the store on → AOT-compile here and commit.  Store off →
        keep the lazy jit wrapper (it compiles at first call, as
        before).  Returns ``(executable, compiled_now)``."""
        from .. import artifacts
        if not artifacts.enabled():
            return jitted, True
        asig = (self._artifact_fp(), sig)
        art = artifacts.load("spmd_step", asig)
        if art is not None:
            try:
                jitted.eval_shape(*args)    # trace-only: fills cell
            except Exception:
                pass
            self._step_cache[sig] = (art.compiled, cell)
            return art.compiled, False
        try:
            ex = jitted.lower(*args).compile()
        except Exception:
            # lowering declined (or AOT unsupported): the lazy wrapper
            # still works, the store just stays cold for this signature
            return jitted, True
        artifacts.save("spmd_step", asig, ex,
                       meta={"trainer_fp": repr(self._artifact_fp()),
                             "sig": sig})
        self._step_cache[sig] = (ex, cell)
        return ex, True

    def warm_start(self) -> int:
        """Drain every compatible ``spmd_step`` artifact into the step
        cache in ONE call, so a restarted trainer reaches its first
        ``step()``/``run_steps()`` with ``compile.count == 0``.  Only
        artifacts recorded under this trainer's exact fingerprint (and
        the store's own amp/jax/backend key) install; everything else
        is skipped silently.  Returns the number of executables
        installed."""
        from .. import artifacts
        if not artifacts.enabled():
            return 0
        fp = repr(self._artifact_fp())
        n = 0
        for art in artifacts.load_all("spmd_step"):
            sig = art.meta.get("sig")
            if art.meta.get("trainer_fp") != fp or sig is None \
                    or sig in self._step_cache:
                continue
            self._step_cache[sig] = (art.compiled, {"aux": []})
            n += 1
        if n:
            from ..log import get_logger
            get_logger("mxnet_tpu.parallel").info(
                "warm_start: %d step executable(s) loaded from %s",
                n, artifacts.store_dir())
        return n

    def _window_sharding(self, ndim):
        """Sharding for a (n_steps, batch, ...) window: the leading
        step axis is replicated, batch/seq axes shift right by one."""
        inner = self._batch_sharding(ndim - 1)
        return NamedSharding(self.mesh,
                             PartitionSpec(None, *inner.spec))

    def _build_multi(self, data_shape, data_dtype, label_shape, label_dtype,
                     n_steps, per_step_data=False):
        """Fused multi-step: ``n_steps`` full train steps inside ONE
        executable via lax.scan — the engine-bulking idea
        (MXNET_EXEC_BULK_EXEC_*, SURVEY.md §3.3) taken to its XLA-native
        limit.  One launch per n steps amortizes dispatch/launch
        latency; lr/wd are held fixed across the fused window.

        ``per_step_data``: data/label carry a leading ``n_steps`` axis
        and the scan consumes one batch per step — the data-fed window
        (input pipeline → device once per window, not per step)."""
        step, cell, params = self._make_step_fn()
        amp = self._amp_scaler is not None

        if amp:
            # the loss-scale pair rides the scan carry: the whole fused
            # window stays one executable, overflow steps inside it skip
            # their own update, and the skip count accumulates so the
            # scaler's host-side telemetry stays exact
            def many(key, lr, wd, p_arrays, opt_state, data, label,
                     amp_state):
                def body(carry, xs):
                    key, p, s, scale, good, nskip = carry
                    d, l = (data, label) if xs is None else xs
                    key, sub = jax.random.split(key)
                    new_p, new_s, loss, _aux, (ns, ng, sk) = step(
                        sub, lr, wd, p, s, d, l, (scale, good))
                    return (key, new_p, new_s, ns, ng, nskip + sk), loss
                carry0 = (key, list(p_arrays), list(opt_state),
                          amp_state[0], amp_state[1], jnp.float32(0.0))
                (key, p, s, scale, good, nskip), losses = jax.lax.scan(
                    body, carry0,
                    (data, label) if per_step_data else None,
                    length=None if per_step_data else n_steps)
                return p, s, losses, (scale, good, nskip)
        else:
            def many(key, lr, wd, p_arrays, opt_state, data, label):
                def body(carry, xs):
                    key, p, s = carry
                    d, l = (data, label) if xs is None else xs
                    key, sub = jax.random.split(key)
                    new_p, new_s, loss, _aux = step(sub, lr, wd, p, s,
                                                    d, l)
                    return (key, new_p, new_s), loss
                (key, p, s), losses = jax.lax.scan(
                    body, (key, list(p_arrays), list(opt_state)),
                    (data, label) if per_step_data else None,
                    length=None if per_step_data else n_steps)
                return p, s, losses

        p_shardings, s_shardings = self._state_shardings(params)
        rep = NamedSharding(self.mesh, PartitionSpec())
        shard_of = (self._window_sharding if per_step_data
                    else self._batch_sharding)
        in_shardings = (rep, rep, rep, p_shardings, s_shardings,
                        shard_of(len(data_shape)),
                        shard_of(len(label_shape)))
        out_shardings = (p_shardings, s_shardings, rep)
        if amp:
            in_shardings = in_shardings + (rep,)
            out_shardings = out_shardings + (rep,)
        donate = (3, 4) if self._donate else ()
        jitted = jax.jit(many, in_shardings=in_shardings,
                         out_shardings=out_shardings,
                         donate_argnums=donate)
        return jitted, cell

    @staticmethod
    def _put(arr, sharding):
        """Place ``arr`` under ``sharding``.  No-op when already
        there.  An uncommitted array is placed too: an array's mesh is
        part of its traced type (jax >= 0.7), so a step first called
        on unplaced parameters and then on its own mesh-placed outputs
        traced and compiled twice."""
        if getattr(arr, "sharding", None) == sharding:
            return arr
        return jax.device_put(arr, sharding)

    def _stage_input(self, x, sharding):
        """Stage one batch tensor for the compiled step.  A batch the
        device-feed pipeline already committed under this trainer's
        sharding (data.DevicePrefetcher) passes through untouched — the
        step path performs NO transfer.  Host inputs (numpy/list) and
        mis-committed arrays pay an inline H2D/reshard here, accounted
        as ``input.step_h2d`` so the telemetry report can see the input
        pipeline sitting on the critical path."""
        if isinstance(x, NDArray):
            arr, was_host = x._data, False
        elif isinstance(x, jax.Array):
            arr, was_host = x, False
        else:
            arr, was_host = jnp.asarray(x), True
        out = self._put(arr, sharding)
        if was_host or out is not arr:
            telemetry.record_h2d_bytes(out.nbytes, step_path=True)
        return out

    def step(self, data, label, batch_size: Optional[int] = None):
        """One training step; returns the (device) loss as NDArray."""
        d = self._stage_input(data, self._batch_sharding(
            data.ndim if hasattr(data, "ndim") else onp.ndim(data)))
        l = self._stage_input(label, self._batch_sharding(
            label.ndim if hasattr(label, "ndim") else onp.ndim(label)))
        self._last_tokens = self._token_count(d)
        sig = (d.shape, str(d.dtype), l.shape, str(l.dtype))
        entry = self._step_cache.get(sig)
        fresh = entry is None
        if fresh:
            entry = self._build_step(*sig)
            self._step_cache[sig] = entry
        jitted, cell = entry
        from .. import profiler
        # step funnel #2: the SPMD compiled-step path
        tok = telemetry.begin_step()
        _prof_t0 = profiler.op_timer()
        try:
            with tracing.span("step.spmd") as _sp:
                self.num_update += 1
                _sp.annotate(step=self.num_update)
                lr = jnp.float32(self.optimizer.learning_rate)
                wd = jnp.float32(self.optimizer.wd)
                self.optimizer.num_update = self.num_update
                p_arrays, opt_state = self._gather_state()
                args = (next_key(), lr, wd, p_arrays, opt_state, d, l)
                if self._amp_scaler is not None:
                    args = args + (self._amp_state_in(),)
                tc = time.perf_counter() if fresh else None
                if fresh:
                    jitted, compiled_now = self._resolve_exec(
                        sig, jitted, cell, args)
                    if not compiled_now:    # artifact hit: no compile
                        tc, fresh = None, False
                with tracing.span("compile.spmd_step" if fresh
                                  else "step.dispatch"):
                    if self._amp_scaler is not None:
                        new_p, new_s, loss, aux, amp_out = jitted(*args)
                        self._amp_scaler.adopt_traced(*amp_out)
                    else:
                        new_p, new_s, loss, aux = jitted(*args)
                    telemetry.record_dispatch()
                if tc is not None:
                    telemetry.record_compile(time.perf_counter() - tc,
                                             "spmd_step")
                _sp.annotate(fresh_compile=fresh)
                self._fold_back(new_p, new_s, cell, aux)
                self._account_step_telemetry()
            profiler.op_record("SPMDTrainer::step", _prof_t0)
        finally:
            telemetry.end_step(tok, "SPMDTrainer")
        return NDArray(loss)

    def _amp_state_in(self):
        """(scale, clean-step count) as device scalars.  Reading
        ``loss_scale`` folds the PREVIOUS step's traced triple — those
        arrays are long computed, so this never blocks on in-flight
        work."""
        s = self._amp_scaler
        return (jnp.float32(s.loss_scale), jnp.float32(s._unskipped))

    def opt_state_bytes_per_device(self) -> int:
        """Optimizer-state bytes resident on the busiest device —
        ~1/dp of the replicated total under zero_stage>=1 (plus
        non-dp-divisible stragglers that stay replicated)."""
        from ..optimizer.fused_step import opt_state_bytes_per_device
        return opt_state_bytes_per_device(
            a for k in self._pkeys for a in self._opt_state[k])

    @staticmethod
    def _spec_has_dp(spec) -> bool:
        for s in spec or ():
            if s == "dp" or (isinstance(s, (tuple, list)) and "dp" in s):
                return True
        return False

    @staticmethod
    def _spec_axis_names(spec) -> set:
        used = set()
        for s in spec or ():
            if isinstance(s, (tuple, list)):
                used.update(s)
            elif s is not None:
                used.add(s)
        return used

    @staticmethod
    def _token_count(d) -> int:
        """Token count of one batch for the tp activation-volume model:
        integer inputs of rank >= 2 are (B, T) id grids — B·T tokens;
        anything else contributes its batch rows."""
        shape = getattr(d, "shape", None)
        if not shape:
            return 1
        try:
            is_int = jnp.issubdtype(d.dtype, jnp.integer)
        except Exception:
            is_int = False
        if is_int and len(shape) >= 2:
            return int(shape[0]) * int(shape[1])
        return int(shape[0])

    def _account_step_telemetry(self, n_steps: int = 1) -> None:
        """Per-step collective-byte split + opt-state residency gauge.
        GSPMD inserts the collectives inside the compiled program, where
        no host-side hook can count them, so the funnel records the
        ring-cost model instead: a replicated-update param's gradient
        allreduce moves 2(n-1)/n·bytes; a dp-sharded update moves
        reduce-scatter + all-gather (n-1)/n·bytes each — equal wire
        volume, the arxiv 2004.13336 identity the ZeRO tradeoff rests
        on.  The model is computed once (shapes and shardings are
        static per trainer)."""
        tokens = getattr(self, "_last_tokens", 1)
        model = self._comm_model
        if model is not None and model[4] != tokens:
            model = None        # batch geometry changed: re-derive
        if model is None:
            ndp = int(self.mesh.shape.get("dp", 1)) \
                if "dp" in self.mesh.axis_names else 1
            ntp = int(self.mesh.shape.get("tp", 1)) \
                if "tp" in self.mesh.axis_names else 1
            # gradient legs (reduce-scatter / allreduce) ship in the AMP
            # storage dtype under the policy; the all-gather leg returns
            # f32 master weights and stays full-width
            from ..amp import policy as _amp_policy
            gfrac = 1.0
            if self._amp_scaler is not None:
                gfrac = min(_amp_policy.compute_itemsize(), 4) / 4.0
            isz = (_amp_policy.compute_itemsize()
                   if self._amp_scaler is not None else 4)
            rs = ag = ar = tpb = 0
            for k in self._pkeys:
                p = self._params[k]
                nbytes = int(p.data()._data.nbytes)
                if ndp > 1:
                    if self._spec_has_dp(self._opt_state_sharding(p).spec):
                        rs += int(nbytes * gfrac) * (ndp - 1) // ndp
                        ag += nbytes * (ndp - 1) // ndp
                    else:
                        ar += 2 * int(nbytes * gfrac) * (ndp - 1) // ndp
                # tp activation partial-sum allreduce, one per sharded
                # matmul per direction: a column-parallel (out,in)
                # weight pays it on the backward dx (tokens × in), a
                # row-parallel one on the forward y (tokens × out) —
                # the dim the shard does NOT split
                spec = p._sharding
                shape = p.shape or ()
                if (ntp > 1 and len(shape) >= 2
                        and "tp" in self._spec_axis_names(spec)):
                    first = spec[0] if len(spec) else None
                    col = first == "tp" or (
                        isinstance(first, (tuple, list)) and "tp" in first)
                    dim = int(shape[1]) if col else int(shape[0])
                    tpb += 2 * tokens * dim * isz * (ntp - 1) // ntp
            model = self._comm_model = (rs, ag, ar, tpb, tokens)
        rs, ag, ar, tpb, _ = model
        if rs or ag:
            telemetry.record_comm_bytes(rs * n_steps, "reduce_scatter")
            telemetry.record_comm_bytes(ag * n_steps, "all_gather")
        if ar:
            telemetry.record_comm_bytes(ar * n_steps, "allreduce")
        if rs or ag or ar:
            telemetry.record_axis_comm_bytes((rs + ag + ar) * n_steps,
                                             "dp")
        if tpb:
            telemetry.record_comm_bytes(tpb * n_steps, "allreduce")
            telemetry.record_axis_comm_bytes(tpb * n_steps, "tp")
        telemetry.record_opt_state_bytes(self.opt_state_bytes_per_device())

    def _gather_state(self):
        """Current param/opt-state arrays, resharded onto the step's
        declared shardings where needed (first call after eager init
        or load: everything is committed to one device)."""
        p_arrays, opt_state = [], []
        for k in self._pkeys:
            p = self._params[k]
            p_arrays.append(self._put(p.data()._data,
                                      self._param_sharding(p)))
            shd = self._opt_state_sharding(p)
            opt_state.append(tuple(self._put(a, shd)
                                   for a in self._opt_state[k]))
        return p_arrays, opt_state

    def _fold_back(self, new_p, new_s, cell, aux=None):
        covered = set()
        for k, w, st in zip(self._pkeys, new_p, new_s):
            with ag.pause():
                self._params[k].data()._rebind(w)
            self._opt_state[k] = tuple(st)
            covered.add(id(self._params[k]))
        # aux params outside collect_params (none in practice) still get
        # their traced update; covered ones already flowed through new_p
        # in the step's own dtype discipline
        if aux is not None:
            for (param, _), new in zip(cell["aux"], aux):
                if id(param) not in covered:
                    param._data._rebind(new)

    def run_steps(self, data, label, n_steps: int,
                  per_step_data: bool = False):
        """Run ``n_steps`` fused training steps in ONE device program
        (lax.scan); returns the per-step losses as an (n_steps,)
        NDArray.

        This is the device-side training loop: one launch per window, so
        per-step dispatch/launch latency is amortized away — the XLA
        analogue of the reference executing a whole bulked segment as a
        single engine op (cached_op.cc:499-513).  lr/wd are frozen for
        the window; ``num_update`` advances by ``n_steps``.

        With ``per_step_data=True``, ``data``/``label`` carry a leading
        ``n_steps`` axis and the scan consumes one REAL batch per step —
        the feed-the-chip window: stage a whole window of input-pipeline
        batches onto the device in one transfer, then train through them
        in one launch."""
        shard_of = (self._window_sharding if per_step_data
                    else self._batch_sharding)
        d = self._stage_input(data, shard_of(
            data.ndim if hasattr(data, "ndim") else onp.ndim(data)))
        l = self._stage_input(label, shard_of(
            label.ndim if hasattr(label, "ndim") else onp.ndim(label)))
        if per_step_data and (d.shape[0] != n_steps
                              or l.shape[0] != n_steps):
            raise MXNetError(
                f"run_steps(per_step_data=True): leading axis must be "
                f"n_steps={n_steps}, got data {d.shape} label {l.shape}")
        self._last_tokens = self._token_count(
            d[0] if per_step_data else d)
        sig = (d.shape, str(d.dtype), l.shape, str(l.dtype), int(n_steps),
               bool(per_step_data))
        entry = self._step_cache.get(sig)
        fresh = entry is None
        if fresh:
            entry = self._build_multi(d.shape, str(d.dtype), l.shape,
                                      str(l.dtype), int(n_steps),
                                      per_step_data=per_step_data)
            self._step_cache[sig] = entry
        jitted, cell = entry
        # one telemetry record for the whole fused window (it IS one
        # device program / one dispatch)
        tok = telemetry.begin_step()
        try:
            with tracing.span("step.spmd_window", n_steps=int(n_steps),
                              step=self.num_update + 1):
                # read lr/wd BEFORE advancing num_update — matching what
                # the first of n sequential step() calls would use (the
                # whole fused window trains at the window-entry schedule
                # point)
                lr = jnp.float32(self.optimizer.learning_rate)
                wd = jnp.float32(self.optimizer.wd)
                self.num_update += int(n_steps)
                self.optimizer.num_update = self.num_update
                p_arrays, opt_state = self._gather_state()
                args = (next_key(), lr, wd, p_arrays, opt_state, d, l)
                if self._amp_scaler is not None:
                    args = args + (self._amp_state_in(),)
                tc = time.perf_counter() if fresh else None
                if fresh:
                    jitted, compiled_now = self._resolve_exec(
                        sig, jitted, cell, args)
                    if not compiled_now:    # artifact hit: no compile
                        tc, fresh = None, False
                with tracing.span("compile.spmd_step" if fresh
                                  else "step.dispatch"):
                    if self._amp_scaler is not None:
                        new_p, new_s, losses, amp_out = jitted(*args)
                        self._amp_scaler.adopt_traced(*amp_out)
                    else:
                        new_p, new_s, losses = jitted(*args)
                    # the whole fused window is ONE executable launch —
                    # the record's ``dispatches`` delta asserts it
                    telemetry.record_dispatch()
                if tc is not None:
                    telemetry.record_compile(time.perf_counter() - tc,
                                             "spmd_step")
                self._fold_back(new_p, new_s, cell)
                self._account_step_telemetry(n_steps=int(n_steps))
        finally:
            telemetry.end_step(tok, "SPMDTrainer",
                               extra={"n_steps": int(n_steps)})
        return NDArray(losses)

    def predict(self, data):
        """Jitted inference forward on the training mesh (params stay
        sharded; the batch is dp-sharded like in ``step``).  Fills the
        gap users hit right after SPMD training: an eager ``net(x)``
        would collide single-device inputs with mesh-committed params."""
        d = data._data if isinstance(data, NDArray) else jnp.asarray(data)
        sig = ("predict", d.shape, str(d.dtype))
        entry = self._step_cache.get(sig)
        if entry is None:
            net = self.net
            params = [self._params[k] for k in self._pkeys]
            amp = self.amp_dtype
            key0 = next_key()   # fetched outside the trace; eval mode
                                # draws no entropy in practice

            def fwd(p_arrays, x):
                from ..gluon.block import _TraceContext, _trace_scope
                tc = _TraceContext(key0)
                saved = [p._data for p in params]
                if self._data_transform is not None:
                    # same device-side preprocessing as the train step
                    # (a uint8-wire trainer must not see raw pixels at
                    # inference either)
                    x = self._data_transform(x)
                if amp is not None:
                    p_arrays = [a.astype(amp) if jnp.issubdtype(
                        a.dtype, jnp.floating) else a for a in p_arrays]
                    x = x.astype(amp) if jnp.issubdtype(
                        x.dtype, jnp.floating) else x
                try:
                    for p, a in zip(params, p_arrays):
                        p._data = NDArray(a)
                    with _trace_scope(tc), ag.pause(train_mode=False):
                        out = net.forward(NDArray(x))
                    return out._data.astype(jnp.float32)
                finally:
                    for p, s in zip(params, saved):
                        p._data = s

            p_shardings, _ = self._state_shardings(params)
            jitted = jax.jit(fwd, in_shardings=(
                p_shardings, self._batch_sharding(d.ndim)))
            entry = (jitted, None)
            self._step_cache[sig] = entry
        jitted, _ = entry
        d = self._put(d, self._batch_sharding(d.ndim))
        p_arrays = [self._put(self._params[k].data()._data,
                              self._param_sharding(self._params[k]))
                    for k in self._pkeys]
        return NDArray(jitted(p_arrays, d))

    @staticmethod
    def _step_sig(data, label, n_steps=None):
        """``(data array, label array, step-cache key)`` of a call."""
        d = data._data if isinstance(data, NDArray) else jnp.asarray(data)
        l = label._data if isinstance(label, NDArray) else jnp.asarray(label)
        sig = (d.shape, str(d.dtype), l.shape, str(l.dtype))
        if n_steps is not None:
            sig = sig + (int(n_steps), False)
        return d, l, sig

    def compiled_step(self, data, label, n_steps=None):
        """The AOT-compiled executable of the step that matches
        ``(data, label)``'s signature (``as_text()`` is its HLO,
        collectives included); the step must have been run at least
        once.

        Note: the AOT ``lower().compile()`` path does not share the jit
        call cache, so this costs one extra compile per signature (a
        disk hit when JAX's persistent compilation cache is on, see
        ``base.use_compile_cache``)."""
        d, l, sig = self._step_sig(data, label, n_steps)
        jitted, _ = self._step_cache[sig]
        if not hasattr(jitted, "lower"):
            return jitted       # already AOT (artifact store on)
        p_arrays = [self._params[k].data()._data for k in self._pkeys]
        opt_state = [self._opt_state[k] for k in self._pkeys]
        args = (next_key(), jnp.float32(self.optimizer.learning_rate),
                jnp.float32(self.optimizer.wd), p_arrays, opt_state, d, l)
        if self._amp_scaler is not None:
            args = args + (self._amp_state_in(),)
        return jitted.lower(*args).compile()

    def cost_analysis(self, data, label, n_steps=None):
        """XLA cost analysis (flops/bytes) for the compiled step that
        matches ``(data, label)``'s signature (see
        :meth:`compiled_step`); the result is memoized."""
        sig = self._step_sig(data, label, n_steps)[2]
        cached = getattr(self, "_cost_cache", {}).get(sig)
        if cached is not None:
            return cached
        compiled = self.compiled_step(data, label, n_steps)
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        out = dict(ca or {})
        try:
            ma = compiled.memory_analysis()
            out["temp_size_in_bytes"] = int(ma.temp_size_in_bytes)
            out["argument_size_in_bytes"] = int(ma.argument_size_in_bytes)
            out["output_size_in_bytes"] = int(ma.output_size_in_bytes)
        except Exception:
            pass        # some backends expose cost but not memory stats
        if not hasattr(self, "_cost_cache"):
            self._cost_cache = {}
        self._cost_cache[sig] = out
        return out

    def save_states(self, fname):
        """Checkpoint optimizer state + step counter + the global PRNG
        key chain (parity: Trainer.save_states / kvstore get_states).
        Sharded state is gathered to host — on a multi-host mesh call
        on every process; rank 0's file is authoritative (identical
        contents by construction).

        Format: numpy .npz with a JSON header under ``__header__`` and
        one entry per state slot named ``<param>::<slot>`` — no pickle,
        so untrusted checkpoints cannot execute code on load."""
        import json
        from ..ops import random as _rand
        arrays = {}
        slots = {}
        dtypes = {}
        for k, st in self._opt_state.items():
            slots[k] = len(st)
            dtypes[k] = []
            for i, s in enumerate(st):
                d = onp.asarray(jax.device_get(s))
                dtypes[k].append(str(d.dtype))
                if d.dtype.kind not in "biufc":
                    # ml_dtypes (bfloat16, fp8) save as raw void in npz;
                    # store the bit pattern as uint of the same width
                    d = d.view(onp.dtype(f"u{d.dtype.itemsize}"))
                arrays[f"{k}::{i}"] = d
        header = json.dumps({"format": "mxnet_tpu-trainer-states-v1",
                             "num_update": self.num_update,
                             "rng_key": [int(w) for w in
                                         _rand.get_state_bits().ravel()],
                             "slots": slots, "dtypes": dtypes})
        arrays["__header__"] = onp.frombuffer(
            header.encode("utf-8"), dtype=onp.uint8)
        with open(fname, "wb") as f:
            onp.savez(f, **arrays)

    def load_states(self, fname):
        """Restore optimizer state (and, when present, the global PRNG
        chain) saved by :meth:`save_states`; arrays are re-placed under
        each parameter's declared sharding.  Only the .npz format
        written by :meth:`save_states` is accepted
        (``allow_pickle=False`` — loading never executes code)."""
        import json
        from ..ops import random as _rand
        with onp.load(fname, allow_pickle=False) as z:
            if "__header__" not in z:
                raise MXNetError(
                    f"{fname}: not a mxnet_tpu trainer-states file")
            header = json.loads(bytes(z["__header__"]).decode("utf-8"))
            if header.get("format") != "mxnet_tpu-trainer-states-v1":
                raise MXNetError(
                    f"{fname}: unknown trainer-states format "
                    f"{header.get('format')!r}")
            self.num_update = int(header["num_update"])
            self.optimizer.num_update = self.num_update
            if header.get("rng_key"):
                _rand.set_state_bits(header["rng_key"])
            dtypes = header.get("dtypes", {})

            def _restore(k, i):
                raw = z[f"{k}::{i}"]
                # per-key lookup with default (no magic-length list:
                # an optimizer with any number of state slots works)
                key_dtypes = dtypes.get(k) or []
                want = key_dtypes[i] if i < len(key_dtypes) else None
                if want is not None and str(raw.dtype) != want:
                    import ml_dtypes  # noqa: F401 (registers dtype names)
                    raw = raw.view(onp.dtype(want))
                return raw

            for k, n in header["slots"].items():
                if k not in self._opt_state:
                    raise MXNetError(f"unknown optimizer-state key {k!r}")
                shd = self._opt_state_sharding(self._params[k])
                self._opt_state[k] = tuple(
                    jax.device_put(jnp.asarray(_restore(k, i)), shd)
                    for i in range(int(n)))

    # -- checkpoint/resume (the recovery story, SURVEY §5: no elastic
    #    restart in the reference either — checkpoint/resume IS the
    #    failure-handling design; here it is turnkey and ASYNC) --------
    def save_checkpoint(self, directory, tag="latest", meta=None,
                        block=True):
        """Checkpoint params + optimizer state + step counter + global
        PRNG chain through the async sharded checkpoint service
        (``mxnet_tpu.checkpoint``): the step path pays only a
        non-blocking per-shard D2H snapshot; per-device shard files and
        the crash-durable manifest/rename publish happen on the writer
        thread.  ``meta``: extra JSON (e.g. fit progress / data cursor)
        stored in the manifest header.

        ``block=True`` (default) waits for the publish and returns the
        final checkpoint path, raising ``MXNetError`` if the save
        failed after retries.  ``block=False`` returns a
        ``checkpoint.PendingSave`` immediately — a failed async save
        logs + increments ``checkpoint.failures`` telemetry, never
        raises into the training step.

        Multi-process runs route through the rank-0 commit protocol:
        every rank calls this with its OWN addressable shards (the
        snapshot only captures what this process holds), writes a
        ready marker, and only rank 0 publishes the merged manifest —
        rank/world come from ``checkpoint.rank_world()`` (env >
        kvstore plumbing > ``jax.process_index()``)."""
        from .. import checkpoint as _ckpt
        from ..ops import random as _rand

        tree = {}
        for k in self._pkeys:
            tree[f"param/{k}"] = self._params[k].data()._data
        for k in self._pkeys:
            for i, s in enumerate(self._opt_state[k]):
                tree[f"opt/{k}/{i}"] = s
        header = {
            "num_update": int(self.num_update),
            "rng_key": [int(w) for w in _rand.get_state_bits().ravel()],
            "slots": {k: len(self._opt_state[k]) for k in self._pkeys},
            "meta": dict(meta or {}),
            # mesh provenance (informational — restore re-places global
            # arrays under the LOADING trainer's mesh, so a dp2×tp2
            # save restores onto dp4×tp1; the header just records where
            # the bytes came from for post-mortems)
            "mesh_axes": {ax: int(self.mesh.shape[ax])
                          for ax in self.mesh.axis_names},
        }
        # AMP provenance: the tree always holds fp32 MASTER weights (the
        # compute-dtype casts live in the traced step, never in the
        # stored arrays), so a checkpoint written under AMP loads into an
        # AMP-off run — and across compute dtypes — unchanged.  The
        # header records the policy + scaler state for deterministic
        # loss-scale resume.
        if self._amp_scaler is not None:
            from ..amp import policy as _amp_policy
            header["amp"] = {
                "enabled": True,
                "compute_dtype": _amp_policy.compute_dtype_str(),
                "scaler": self._amp_scaler.state(),
            }
        rank, world = _ckpt.rank_world()
        job = _ckpt.save(directory, tree, header, tag=tag, block=block,
                         rank=rank, world=world)
        return job.result() if block else job

    def load_checkpoint(self, directory, tag="latest"):
        """Restore a :meth:`save_checkpoint` snapshot (falling back to
        the ``tag.old`` backup if a crash interrupted a publish, then
        to the newest ``step-<n>`` directory the keep-last-N GC
        retains when both are missing or digest-corrupt).
        Shards are reassembled to GLOBAL arrays and re-placed under
        THIS trainer's mesh/shardings — a dp=8 save restores onto a
        dp=1 trainer bit-identically (resharded restore).  Also
        restores the step counter and the global PRNG chain, so a
        resumed run continues the exact key sequence.  Returns the
        checkpoint's meta dict (always truthy — contains at least
        ``num_update``) or None when nothing was found."""
        from .. import checkpoint as _ckpt
        from ..ops import random as _rand

        loaded = _ckpt.load(directory, tag)
        if loaded is None:
            return self._load_checkpoint_v1(directory, tag)
        leaves, header = loaded
        for k in self._pkeys:
            name = f"param/{k}"
            if name not in leaves:
                raise MXNetError(
                    f"checkpoint {directory!r} has no entry for "
                    f"parameter {k!r}")
            p = self._params[k]
            if tuple(leaves[name].shape) != tuple(p.shape):
                raise MXNetError(
                    f"checkpoint parameter {k!r} has shape "
                    f"{tuple(leaves[name].shape)}, model expects "
                    f"{tuple(p.shape)}")
            arr = jax.device_put(jnp.asarray(leaves[name]),
                                 self._param_sharding(p))
            with ag.pause():
                p.data()._rebind(arr)
        slots = header.get("slots") or {}
        for k in self._pkeys:
            n = int(slots.get(k, len(self._opt_state[k])))
            shd = self._opt_state_sharding(self._params[k])
            st = []
            for i in range(n):
                name = f"opt/{k}/{i}"
                if name not in leaves:
                    raise MXNetError(
                        f"checkpoint {directory!r} has no entry for "
                        f"optimizer state {name!r}")
                st.append(jax.device_put(jnp.asarray(leaves[name]), shd))
            self._opt_state[k] = tuple(st)
        self.num_update = int(header.get("num_update", self.num_update))
        self.optimizer.num_update = self.num_update
        if header.get("rng_key"):
            _rand.set_state_bits(header["rng_key"])
        # deterministic loss-scale resume; an AMP-on checkpoint into an
        # AMP-off trainer (or vice versa) just drops/starts the scaler
        # schedule — the weights themselves are dtype-portable masters
        amp_hdr = header.get("amp")
        if amp_hdr and self._amp_scaler is not None \
                and amp_hdr.get("scaler"):
            self._amp_scaler.load_state(amp_hdr["scaler"])
        meta = dict(header.get("meta") or {})
        meta["num_update"] = self.num_update
        return meta

    def _load_checkpoint_v1(self, directory, tag):
        """Legacy (pre-manifest) checkpoint layout: a directory with
        ``model.params`` + ``trainer.npz`` (+ optional ``meta.json``)."""
        import json
        import os

        for cand in (os.path.join(directory, tag),
                     os.path.join(directory, f"{tag}.old")):
            if os.path.isfile(os.path.join(cand, "model.params")):
                break
        else:
            return None
        meta = {}
        meta_path = os.path.join(cand, "meta.json")
        if os.path.exists(meta_path):   # optional (hand-copied ckpts)
            with open(meta_path) as f:
                meta = dict(json.load(f))
        self.net.load_parameters(os.path.join(cand, "model.params"))
        self.load_states(os.path.join(cand, "trainer.npz"))
        meta["num_update"] = self.num_update
        return meta

    def fit(self, data_iter, epochs=1, verbose=False,
            checkpoint_dir=None, checkpoint_every=0, resume=True):
        """Epoch loop over ``data_iter``.  With ``checkpoint_dir``,
        checkpoints every ``checkpoint_every`` steps (async — the step
        path pays only the device snapshot) and at the end (blocking,
        so a returned fit implies a published checkpoint), and
        auto-resumes from the latest checkpoint on start — kill the
        process anywhere and re-running ``fit`` continues from the
        last published checkpoint.

        Resume is deterministic: the checkpoint carries the global
        PRNG key chain (restored on load — the resumed run draws the
        exact dropout/shuffle keys the uninterrupted run would have)
        and the data cursor (epoch + batch index; already-consumed
        batches replay without training, via
        ``DevicePrefetcher.fast_forward`` when the iterator supports
        it so the replay skips the H2D transfers too)."""
        skip = 0
        if checkpoint_dir and resume:
            meta = self.load_checkpoint(checkpoint_dir)
            if meta:
                # skip exactly the batches THIS fit already consumed
                # (recorded in the checkpoint's meta — the global
                # num_update may include steps taken outside fit)
                skip = int(meta.get("fit_seen", 0))
        losses = []
        seen = 0
        fast_forward = getattr(data_iter, "fast_forward", None)
        for epoch in range(epochs):
            batch_idx = 0
            if seen < skip and fast_forward is not None:
                # skip whole prefixes device-free when the source knows
                # its epoch length (DevicePrefetcher over a sized
                # loader); otherwise fall through to consume-and-drop
                try:
                    epoch_len = len(data_iter)
                except TypeError:
                    epoch_len = None
                if epoch_len is not None:
                    n = min(skip - seen, epoch_len)
                    fast_forward(n)
                    seen += n
                    batch_idx = n
            for batch in data_iter:
                seen += 1
                if seen <= skip:
                    continue        # replayed data before resume point
                batch_idx += 1
                d, l = batch[0], batch[1]
                losses.append(self.step(d, l))
                if (checkpoint_dir and checkpoint_every
                        and len(losses) % checkpoint_every == 0):
                    self.save_checkpoint(
                        checkpoint_dir, block=False,
                        meta={"fit_seen": seen,
                              "cursor": {"epoch": epoch,
                                         "batch": batch_idx}})
        if checkpoint_dir:
            # blocking final save: the writer queue is FIFO, so this
            # also drains every earlier async save before returning
            self.save_checkpoint(
                checkpoint_dir,
                meta={"fit_seen": seen,
                      "cursor": {"epoch": epochs - 1, "batch": seen}})
        return losses
