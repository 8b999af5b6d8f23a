"""Spec catalog for the systematic finite-difference gradient sweep.

Every unique primary op in the registry must appear either in SPECS
(with inputs/params that make a finite-difference check well-posed) or
in EXEMPT (with an explicit reason).  test_grad_sweep.py enforces the
completeness of this classification, so a newly registered op fails the
suite until it is classified.

Parity: the reference finite-difference oracle
(python/mxnet/test_utils.py:1039 check_numeric_gradient) as applied
throughout tests/python/unittest/test_operator.py — here driven
systematically over the whole registry instead of op by op.

Sampling discipline: inputs are drawn per-op from a deterministic seed;
ops with kinks (relu/abs/max/...) draw values bounded away from the
kink by >> eps, ordering ops (sort/topk/max) draw well-separated
values, and domain-restricted ops (log/arccos/...) draw inside the
domain with margin.
"""
from __future__ import annotations

import zlib

import numpy as onp

SPECS = {}
EXEMPT = {}


def _rng(name: str) -> onp.random.RandomState:
    return onp.random.RandomState(zlib.crc32(name.encode()) % (2**31))


class S:
    """Input samplers. Each returns a builder(rng) so arrays are drawn
    per-op deterministically."""

    @staticmethod
    def f(*shape, lo=-1.0, hi=1.0):
        return lambda r: r.uniform(lo, hi, size=shape).astype("float32")

    @staticmethod
    def pos(*shape, lo=0.5, hi=2.0):
        return lambda r: r.uniform(lo, hi, size=shape).astype("float32")

    @staticmethod
    def away(*shape, lo=0.25, hi=1.0):
        """Values with |x| in [lo,hi] — bounded away from 0-kinks."""
        def build(r):
            mag = r.uniform(lo, hi, size=shape)
            sign = onp.where(r.uniform(size=shape) < 0.5, -1.0, 1.0)
            return (mag * sign).astype("float32")
        return build

    @staticmethod
    def offint(*shape, span=3):
        """Values at least 0.2 from any integer (floor/round kinks)."""
        def build(r):
            base = r.randint(-span, span, size=shape).astype("float64")
            frac = r.uniform(0.2, 0.8, size=shape)
            return (base + frac).astype("float32")
        return build

    @staticmethod
    def sep(*shape, step=0.37):
        """Well-separated distinct values (ordering ops: max/sort/topk)."""
        def build(r):
            n = int(onp.prod(shape)) if shape else 1
            vals = (onp.arange(n) - n / 2.0) * step
            return r.permutation(vals).reshape(shape).astype("float32")
        return build

    @staticmethod
    def unit(*shape, margin=0.15):
        """Inside (-1+margin, 1-margin) — arcsin/arccos/arctanh/erfinv."""
        return lambda r: r.uniform(-1 + margin, 1 - margin,
                                   size=shape).astype("float32")

    @staticmethod
    def gt1(*shape, lo=1.2, hi=2.5):
        return lambda r: r.uniform(lo, hi, size=shape).astype("float32")

    @staticmethod
    def spd(n, k=None):
        """Symmetric positive definite matrix (cholesky/potrf/...)."""
        def build(r):
            a = r.uniform(-1, 1, size=(n, n))
            m = a @ a.T + n * onp.eye(n)
            return m.astype("float32")
        return build

    @staticmethod
    def wellcond(n, m=None):
        """Well-conditioned square-ish matrix (det/inverse/solve/svd)."""
        def build(r):
            a = r.uniform(-1, 1, size=(n, m or n))
            a = a + 0.0
            # push singular values away from 0
            u = a + 2.0 * onp.eye(n, m or n)
            return u.astype("float32")
        return build

    @staticmethod
    def tril(n, unit=False):
        """Lower-triangular with strong diagonal (trsm/trmm/potri)."""
        def build(r):
            a = onp.tril(r.uniform(0.2, 1.0, size=(n, n)))
            a[onp.arange(n), onp.arange(n)] = r.uniform(1.0, 2.0, size=n)
            if unit:
                a[onp.arange(n), onp.arange(n)] = 1.0
            return a.astype("float32")
        return build

    @staticmethod
    def ints(*shape, lo=0, hi=4, dtype="int32"):
        return lambda r: r.randint(lo, hi, size=shape).astype(dtype)

    @staticmethod
    def mask(*shape, p=0.6):
        return lambda r: (r.uniform(size=shape) < p).astype("float32")

    @staticmethod
    def const(arr):
        a = onp.asarray(arr)
        return lambda r: a.copy()


def spec(name, arrays, params=None, diff=None, out=None, rtol=2e-2,
         atol=2e-3, eps=1e-3, train_mode=False, obj=None):
    """Register a finite-difference check spec.

    arrays: list of samplers (or None for dropped optional inputs)
    diff:   indices of inputs to differentiate (default: all float)
    out:    None = sum all outputs; int = pick one; callable(outs)->nd
    """
    if name in SPECS or name in EXEMPT:
        raise ValueError(f"{name} classified twice")
    SPECS[name] = dict(arrays=arrays, params=params or {}, diff=diff,
                       out=out, rtol=rtol, atol=atol, eps=eps,
                       train_mode=train_mode, obj=obj)


def exempt(names, reason):
    if isinstance(names, str):
        names = [names]
    for n in names:
        if n in SPECS or n in EXEMPT:
            raise ValueError(f"{n} classified twice")
        EXEMPT[n] = reason


# ==========================================================================
# Exemptions
# ==========================================================================

exempt([
    "_arange", "_eye", "_full", "_linspace", "_ones", "_zeros",
    "_zeros_without_dtype", "_npi_arange", "_npi_eye", "_npi_full",
    "_npi_identity", "_npi_indices", "_npi_linspace", "_npi_logspace",
    "_npi_ones", "_npi_zeros", "_npi_tri", "_npi_tril_indices",
    "_npi_blackman", "_npi_hamming", "_npi_hanning", "ones_like",
    "zeros_like", "full_like", "_npi_full_like", "shape_array",
    "size_array", "_contrib_index_array", "_contrib_arange_like",
], "creation op: output values do not depend on input values "
   "(zero/undefined jacobian by construction)")

exempt([
    "broadcast_equal", "broadcast_greater", "broadcast_greater_equal",
    "broadcast_lesser", "broadcast_lesser_equal", "broadcast_not_equal",
    "broadcast_logical_and", "broadcast_logical_or",
    "broadcast_logical_xor", "_equal_scalar", "_greater_scalar",
    "_greater_equal_scalar", "_lesser_scalar", "_lesser_equal_scalar",
    "_not_equal_scalar", "_logical_and_scalar", "_logical_or_scalar",
    "_logical_xor_scalar", "logical_not", "_npi_logical_not",
    "_npi_isnan", "_npi_isinf", "_npi_isfinite", "_npi_isneginf",
    "_npi_isposinf", "isnan", "isinf", "isfinite", "_npi_all",
    "_npi_any", "allclose", "_contrib_allclose", "all_finite",
    "multi_all_finite", "_npx_constraint_check",
], "boolean-valued output: jacobian is identically zero by type "
   "(value semantics pinned in test_op_sweep/test_operator)")

exempt([
    "_npi_bitwise_and", "_npi_bitwise_or", "_npi_bitwise_xor",
    "_npi_bitwise_not", "_npi_bitwise_and_scalar",
    "_npi_bitwise_or_scalar", "_npi_bitwise_xor_scalar", "_npi_lcm",
    "_npi_lcm_scalar",
], "integer-only op: no real-valued jacobian exists")

exempt([
    "argmax", "argmin", "argsort", "argmax_channel", "one_hot",
    "_histogram", "histogram", "_npi_bincount", "_npi_unique",
    "_contrib_getnnz", "_ravel_multi_index", "_unravel_index",
    "_npx_nonzero", "boolean_mask_nonzero", "_npi_diag_indices_from",
    "_contrib_edge_id", "topk", "_npi_argmax", "_npi_argmin",
], "index/count-valued output: integer outputs, no jacobian "
   "(topk default ret_typ='indices'; its value path is the same gather "
   "as `pick`/`take`, which are swept)")

exempt([
    "_random_bernoulli", "_random_exponential", "_random_gamma",
    "_random_generalized_negative_binomial", "_random_gumbel",
    "_random_laplace", "_random_logistic", "_random_negative_binomial",
    "_random_normal", "_random_poisson", "_random_randint",
    "_random_rayleigh", "_random_uniform", "_sample_exponential",
    "_sample_gamma", "_sample_generalized_negative_binomial",
    "_sample_multinomial", "_sample_negative_binomial",
    "_sample_normal", "_sample_poisson", "_sample_uniform", "_shuffle",
    "_npi_bernoulli", "_npi_choice", "_npi_dirichlet",
    "_npi_exponential", "_npi_gamma", "_npi_gumbel", "_npi_laplace",
    "_npi_logistic", "_npi_multinomial", "_npi_normal",
    "_npi_normal_n", "_npi_pareto", "_npi_powerd", "_npi_rayleigh",
    "_npi_uniform", "_npi_uniform_n", "_npi_weibull", "Dropout",
], "stochastic sampler: output is a fresh draw per call, so finite "
   "differences are ill-posed (distribution moments chi-square-checked "
   "in test_utils-based random tests)")

exempt([
    "sgd_update", "sgd_mom_update", "mp_sgd_update", "mp_sgd_mom_update",
    "adam_update", "adamw_update", "_mp_adamw_update", "adamax_update",
    "nadam_update", "adagrad_update", "adadelta_update", "ftml_update",
    "ftrl_update", "lamb_update", "lamb_update_phase1",
    "lamb_update_phase2", "mp_lamb_update_phase1",
    "mp_lamb_update_phase2", "lans_update", "lars_update",
    "multi_lars", "nag_mom_update", "mp_nag_mom_update",
    "rmsprop_update", "rmspropalex_update", "sgld_update",
    "signsgd_update", "signum_update", "dcasgd_update",
    "group_adagrad_update", "multi_sgd_update", "multi_sgd_mom_update",
    "multi_mp_sgd_update", "multi_mp_sgd_mom_update",
    "preloaded_multi_sgd_update", "preloaded_multi_sgd_mom_update",
    "preloaded_multi_mp_sgd_update", "preloaded_multi_mp_sgd_mom_update",
    "_sparse_adagrad_update", "reset_arrays",
], "optimizer update kernel: applied outside the autograd graph by "
   "contract (reference registers no FGradient; numerics pinned in "
   "test_optimizer_extra and compare_optimizer tests)")

exempt([
    "_contrib_quantize", "_contrib_quantize_v2", "_contrib_dequantize",
    "_contrib_requantize", "_contrib_quantized_concat",
    "_contrib_quantized_conv", "_contrib_quantized_elemwise_add",
    "_contrib_quantized_flatten", "_contrib_quantized_fully_connected",
    "_contrib_quantized_pooling",
], "int8 inference stack: integer arithmetic, inference-only by design "
   "(reference quantized ops register no gradient)")

exempt([
    "_contrib_MultiBoxDetection", "_contrib_MultiBoxPrior",
    "_contrib_MultiBoxTarget", "_contrib_MultiProposal",
    "_contrib_Proposal", "_contrib_box_nms", "_contrib_box_iou",
    "_contrib_box_encode", "_contrib_box_decode",
], "detection geometry op: non-differentiable selection/matching logic "
   "(the reference registers no or zero gradients for these); value "
   "semantics pinned in test_proposal/test_operator detection tests")

exempt([
    "BlockGrad", "MakeLoss", "_contrib_gradientmultiplier",
    "_contrib_round_ste", "_contrib_sign_ste", "SoftmaxOutput",
    "LinearRegressionOutput", "LogisticRegressionOutput",
    "MAERegressionOutput", "IdentityAttachKLSparseReg",
    "_identity_with_attr_like_rhs",
], "gradient-contract op: backward is DEFINED to differ from the "
   "forward jacobian (stop-grad, straight-through, fused loss "
   "gradients), so a finite-difference check must not match; the "
   "contracted backward is pinned in test_autograd/test_operator")

exempt([
    "RNN",
], "fused stateful op with custom vjp: gradients verified against "
   "unfused cell references in test_rnn_op (fd on the fused op would "
   "re-test the same path at much higher cost)")

exempt([
    "flash_attention", "multi_head_attention",
], "Pallas/custom-vjp attention: gradients asserted equal to the exact "
   "softmax-attention vjp in test_attention")

exempt([
    "layer_norm_residual",
], "Pallas/custom-vjp fused kernel: gradients asserted equal to the "
   "unfused reference vjp in test_kernels "
   "(test_layer_norm_residual_op_and_grads)")

exempt([
    "rope", "paged_attention", "ssm_update", "latent_attention",
], "decode-serving inference kernels (rotary embedding, paged-KV and "
   "latent-cache attention, the state-space decode update): forward-only "
   "registrants pinned against their XLA oracles in "
   "test_kernels/test_decode/test_decode_hybrid; no training path "
   "invokes them, so there is no vjp to fd-check")

exempt([
    "_subgraph_exec",
], "framework-internal executor op (runs a captured subgraph); "
   "covered by subgraph/control-flow tests")

exempt([
    "_slice_assign", "_slice_assign_scalar", "_scatter_set_nd",
    "_npi_boolean_mask_assign_scalar", "_npi_boolean_mask_assign_tensor",
    "_npi_fill_diagonal", "_npx_index_update",
], "assignment op: functional-update semantics (writes a constant/"
   "other tensor into a region); value semantics pinned in "
   "test_operator — jacobian w.r.t. the written-over input is a "
   "trivial mask and the reference registers no gradient")

exempt([
    "cast", "amp_cast", "amp_multicast", "_copy", "_np_copy",
], "identity/cast op: jacobian is the identity by construction; "
   "dtype-cast round trips are pinned in test_dtype_consistency")

exempt([
    "_npi_share_memory",
], "aliasing predicate helper (returns whether buffers share memory)")

exempt([
    "_npi_where_scalar2",
], "both branches are scalars: only the boolean condition is a tensor "
   "input, so there is no differentiable input")

exempt([
    "_contrib_boolean_mask",
], "data-dependent output shape: eager-only, cannot be traced for "
   "vjp replay (registry raises with guidance); the autograd-"
   "compatible nd.contrib.boolean_mask path is tested in test_operator")

exempt([
    "_sparse_retain",
], "sparse-storage-only op (row_sparse container in, container out): "
   "eager container path, no dense jacobian; semantics in test_sparse")

exempt([
    "_npi_insert_scalar", "_npi_insert_slice", "_npi_insert_tensor",
    "_npi_delete",
], "structural edit op with data-dependent output shape: eager-only "
   "(cannot trace/vjp under XLA static shapes); value semantics pinned "
   "in test_numpy_namespace")

exempt([
    "_contrib_fft", "_contrib_ifft",
], "complex-output op (ri-packed): linear transform, value parity "
   "pinned in test_op_sweep; fd over packed complex pairs is ill-"
   "conditioned in float32")

exempt([
    "_npi_eig", "_npi_eigvals",
], "general (non-symmetric) eigendecomposition: complex-valued for "
   "real inputs, no stable real jacobian; value parity in test_op_sweep")

exempt([
    "_linalg_gelqf", "_linalg_syevd", "_npi_qr",
], "factorization with sign/rotation gauge freedom: factors are unique "
   "only up to signs, so scalar objectives over raw factors are not "
   "differentiable functions of the input; reconstruction identities "
   "pinned in test_op_sweep linalg tests")

exempt([
    "_npi_lstsq",
], "least-squares solver returning (x, residuals, rank, sv): rank is "
   "integer and residuals vanish for consistent systems; solve-path "
   "gradients covered by _npi_solve spec")

exempt([
    "_npi_matrix_rank", "_npi_matrix_rank_none_tol",
], "integer-valued output (rank)")

exempt([
    "_random_pdf_dirichlet",
], "pdf over a simplex-constrained sample: fd perturbation leaves the "
   "simplex, making the check ill-posed; value parity in random tests")

exempt([
    "_npi_around",
], "alias family of round: piecewise-constant, zero gradient "
   "(rounding kink avoidance covered by `round`/`rint`/`fix` specs)")

exempt([
    "CTCLoss",
], "dynamic-programming loss with label-length-dependent paths: "
   "gradients verified against torch.nn.CTCLoss in test_operator")

exempt([
    "_npi_percentile",
], "order-statistic interpolation: subgradient at data points depends "
   "on interpolation tie-breaks; value parity in test_numpy_namespace")


# ==========================================================================
# Specs — elementwise unary
# ==========================================================================

_UNARY = {
    # name -> (sampler, kwargs)
    "abs": S.away(2, 3),
    "negative": S.f(2, 3),
    "reciprocal": S.away(2, 3, lo=0.4),
    "rcbrt": S.pos(2, 3),
    "rsqrt": S.pos(2, 3),
    "cbrt": S.away(2, 3, lo=0.4),
    "sqrt": S.pos(2, 3),
    "square": S.f(2, 3),
    "exp": S.f(2, 3),
    "expm1": S.f(2, 3),
    "log": S.pos(2, 3),
    "log10": S.pos(2, 3),
    "log1p": S.pos(2, 3, lo=-0.4, hi=1.5),
    "log2": S.pos(2, 3),
    "sin": S.f(2, 3, lo=-1.3, hi=1.3),
    "cos": S.f(2, 3, lo=-1.3, hi=1.3),
    "tan": S.f(2, 3, lo=-1.2, hi=1.2),
    "sinh": S.f(2, 3),
    "cosh": S.f(2, 3),
    "tanh": S.f(2, 3),
    "arcsin": S.unit(2, 3),
    "arccos": S.unit(2, 3),
    "arctan": S.f(2, 3),
    "arcsinh": S.f(2, 3),
    "arccosh": S.gt1(2, 3),
    "arctanh": S.unit(2, 3),
    "erf": S.f(2, 3),
    "erfinv": S.unit(2, 3, margin=0.25),
    "gamma": S.pos(2, 3),
    "gammaln": S.pos(2, 3),
    "digamma": S.pos(2, 3),
    "relu": S.away(2, 3),
    "sigmoid": S.f(2, 3),
    "softsign": S.f(2, 3),
    "hard_sigmoid": S.f(2, 3, lo=-0.4, hi=0.4),
    "degrees": S.f(2, 3),
    "radians": S.f(2, 3),
    "sign": S.away(2, 3),
    "floor": S.offint(2, 3),
    "ceil": S.offint(2, 3),
    "round": S.offint(2, 3),
    "rint": S.offint(2, 3),
    "trunc": S.offint(2, 3),
    "fix": S.offint(2, 3),
    "_npi_log": S.pos(2, 3),
    "_npi_deg2rad": S.f(2, 3),
    "_npi_rad2deg": S.f(2, 3),
    "_npx_relu": S.away(2, 3),
    "_npx_sigmoid": S.f(2, 3),
}
for _n, _s in _UNARY.items():
    spec(_n, [_s])

spec("_npi_nan_to_num", [S.f(2, 3)])
spec("clip", [S.f(2, 3, lo=-2, hi=2)], params=dict(a_min=-0.9, a_max=0.9))
spec("smooth_l1", [S.away(2, 3, lo=0.3, hi=2.0)], params=dict(scalar=1.0))
spec("_contrib_quadratic", [S.f(2, 3)],
     params=dict(a=1.5, b=-0.5, c=0.25))
spec("_contrib_div_sqrt_dim", [S.f(2, 4)])

# ==========================================================================
# Specs — elementwise binary (+broadcast)
# ==========================================================================

_BINARY = {
    "elemwise_add": (S.f(2, 3), S.f(2, 3)),
    "elemwise_sub": (S.f(2, 3), S.f(2, 3)),
    "elemwise_mul": (S.f(2, 3), S.f(2, 3)),
    "elemwise_div": (S.f(2, 3), S.away(2, 3, lo=0.5)),
    "_grad_add": (S.f(2, 3), S.f(2, 3)),
    "_npi_add": (S.f(2, 3), S.f(1, 3)),
    "_npi_subtract": (S.f(2, 3), S.f(1, 3)),
    "_npi_multiply": (S.f(2, 3), S.f(1, 3)),
    "_npi_true_divide": (S.f(2, 3), S.away(1, 3, lo=0.5)),
    "_npi_power": (S.pos(2, 3), S.f(1, 3)),
    "_npi_copysign": (S.away(2, 3), S.away(2, 3)),
    "_npi_fmax": (S.sep(2, 3), S.sep(2, 3, step=0.41)),
    "_npi_fmin": (S.sep(2, 3), S.sep(2, 3, step=0.41)),
    "_npi_hypot": (S.away(2, 3), S.away(2, 3)),
    "_npi_ldexp": (S.f(2, 3), S.f(2, 3)),
    "_maximum": (S.sep(2, 3), S.sep(2, 3, step=0.41)),
    "_minimum": (S.sep(2, 3), S.sep(2, 3, step=0.41)),
    "_hypot": (S.away(2, 3), S.away(2, 3)),
    "arctan2": (S.away(2, 3), S.away(2, 3)),
    "broadcast_maximum": (S.sep(2, 3), S.sep(1, 3, step=0.41)),
    "broadcast_minimum": (S.sep(2, 3), S.sep(1, 3, step=0.41)),
    "broadcast_hypot": (S.away(2, 3), S.away(1, 3)),
    "broadcast_power": (S.pos(2, 3), S.f(1, 3)),
    "add_n": (S.f(2, 3), S.f(2, 3), S.f(2, 3)),
    "_npi_arctan2_scalar": None,  # filled below
}
del _BINARY["_npi_arctan2_scalar"]
for _n, _arrs in _BINARY.items():
    spec(_n, list(_arrs))

# mod family: differentiable a.e.; keep divisor and quotient away from
# integer boundaries
spec("broadcast_mod", [S.offint(2, 3, span=4), S.pos(1, 3, lo=1.3, hi=1.9)])
spec("_npi_mod", [S.offint(2, 3, span=4), S.pos(1, 3, lo=1.3, hi=1.9)])
spec("_npi_fmod", [S.offint(2, 3, span=4), S.pos(1, 3, lo=1.3, hi=1.9)])

# ==========================================================================
# Specs — scalar-arg elementwise
# ==========================================================================

_SCALAR = {
    "_plus_scalar": (S.f(2, 3), 1.7),
    "_minus_scalar": (S.f(2, 3), 1.7),
    "_rminus_scalar": (S.f(2, 3), 1.7),
    "_mul_scalar": (S.f(2, 3), -0.6),
    "_div_scalar": (S.f(2, 3), 1.6),
    "_rdiv_scalar": (S.away(2, 3, lo=0.5), 2.0),
    "_mod_scalar": (S.offint(2, 3, span=4), 1.7),
    "_rmod_scalar": (S.pos(2, 3, lo=1.2, hi=1.8), 5.3),
    "_power_scalar": (S.pos(2, 3), 1.6),
    "_rpower_scalar": (S.f(2, 3), 1.8),
    "_hypot_scalar": (S.away(2, 3), 1.2),
    "_maximum_scalar": (S.away(2, 3, lo=0.3), 0.05),
    "_minimum_scalar": (S.away(2, 3, lo=0.3), 0.05),
    "_scatter_plus_scalar": (S.f(2, 3), 1.3),
    "_scatter_minus_scalar": (S.f(2, 3), 1.3),
    "_npi_add_scalar": (S.f(2, 3), 1.7),
    "_npi_subtract_scalar": (S.f(2, 3), 1.7),
    "_npi_rsubtract_scalar": (S.f(2, 3), 1.7),
    "_npi_multiply_scalar": (S.f(2, 3), -0.6),
    "_npi_true_divide_scalar": (S.f(2, 3), 1.6),
    "_npi_rtrue_divide_scalar": (S.away(2, 3, lo=0.5), 2.0),
    "_npi_mod_scalar": (S.offint(2, 3, span=4), 1.7),
    "_npi_rmod_scalar": (S.pos(2, 3, lo=1.2, hi=1.8), 5.3),
    "_npi_fmod_scalar": (S.offint(2, 3, span=4), 1.7),
    "_npi_rfmod_scalar": (S.pos(2, 3, lo=1.2, hi=1.8), 5.3),
    "_npi_power_scalar": (S.pos(2, 3), 1.6),
    "_npi_rpower_scalar": (S.f(2, 3), 1.8),
    "_npi_copysign_scalar": (S.away(2, 3), 0.7),
    "_npi_rcopysign_scalar": (S.away(2, 3), 0.7),
    "_npi_arctan2_scalar": (S.away(2, 3), 0.8),
    "_npi_rarctan2_scalar": (S.away(2, 3), 0.8),
    "_npi_ldexp_scalar": (S.f(2, 3), 2.0),
    "_npi_rldexp_scalar": (S.f(2, 3), 0.7),
    "_npi_fmax_scalar": (S.away(2, 3, lo=0.3), 0.05),
    "_npi_fmin_scalar": (S.away(2, 3, lo=0.3), 0.05),
}
for _n, (_s, _v) in _SCALAR.items():
    spec(_n, [_s], params=dict(scalar=_v))

spec("_scatter_elemwise_div", [S.f(2, 3), S.away(2, 3, lo=0.5)])

# ==========================================================================
# Specs — reductions / cumulative
# ==========================================================================

spec("sum", [S.f(2, 3)], params=dict(axis=1))
spec("mean", [S.f(2, 3)], params=dict(axis=0))
spec("prod", [S.away(2, 3, lo=0.4)], params=dict(axis=1))
spec("nansum", [S.f(2, 3)])
spec("nanprod", [S.away(2, 3, lo=0.4)])
spec("max", [S.sep(2, 3)], params=dict(axis=1))
spec("min", [S.sep(2, 3)], params=dict(axis=1))
spec("norm", [S.away(2, 3)], params=dict(ord=2, axis=1))
spec("logsumexp", [S.f(2, 3)], params=dict(axis=1))
spec("moments", [S.f(2, 3)], params=dict(axes=(0,)))
spec("_square_sum", [S.f(2, 3)], params=dict(axis=1))
spec("cumsum", [S.f(2, 3)], params=dict(axis=1))
spec("cumprod", [S.away(2, 3, lo=0.4)], params=dict(axis=1))
spec("_npi_sum", [S.f(2, 3)], params=dict(axis=1))
spec("_npi_mean", [S.f(2, 3)], params=dict(axis=0))
spec("_npi_prod", [S.away(2, 3, lo=0.4)], params=dict(axis=1))
spec("_npi_max", [S.sep(2, 3)], params=dict(axis=1))
spec("_npi_min", [S.sep(2, 3)], params=dict(axis=1))
spec("_npi_std", [S.f(3, 4)], params=dict(axis=1), rtol=3e-2)
spec("_npi_var", [S.f(3, 4)], params=dict(axis=1))
spec("_npi_average", [S.f(2, 3)])
spec("_npi_norm", [S.away(2, 3)])
spec("_npi_cumsum", [S.f(2, 3)], params=dict(axis=1))
spec("_npi_trace", [S.f(3, 3)])
spec("_npi_diff", [S.f(2, 4)], params=dict(axis=1))
spec("_npi_ediff1d", [S.f(5)])
spec("multi_sum_sq", [S.f(2, 3), S.f(4)], params=dict(num_arrays=2))

# softmax family
spec("softmax", [S.f(2, 4)], params=dict(axis=-1))
spec("softmin", [S.f(2, 4)], params=dict(axis=-1))
spec("log_softmax", [S.f(2, 4)], params=dict(axis=-1))
spec("SoftmaxActivation", [S.f(2, 4)])
spec("masked_softmax", [S.f(2, 4), S.mask(2, 4)], diff=[0])
def _mask_objective(out, arrs):
    # masked positions are -inf by contract; zero them out of the
    # objective so the finite differences stay finite
    from mxnet_tpu.ops.registry import invoke
    from mxnet_tpu.ndarray import NDArray
    import numpy as _np
    zeros = NDArray(_np.zeros(out.shape, "float32"))
    return invoke("where", [arrs[1], out, zeros])


spec("masked_log_softmax", [S.f(2, 4), S.mask(2, 4)], diff=[0],
     obj=_mask_objective)
spec("softmax_cross_entropy",
     [S.f(2, 4), S.ints(2, lo=0, hi=4, dtype="float32")], diff=[0])

# ==========================================================================
# Specs — shape / layout / gather (linear ops)
# ==========================================================================

spec("reshape", [S.f(2, 6)], params=dict(shape=(3, 4)))
spec("_np_reshape", [S.f(2, 6)], params=dict(newshape=(3, 4)))
spec("_npx_reshape", [S.f(2, 6)], params=dict(newshape=(3, 4)))
spec("reshape_like", [S.f(2, 6), S.f(3, 4)], diff=[0])
spec("flatten", [S.f(2, 3, 2)])
spec("expand_dims", [S.f(2, 3)], params=dict(axis=1))
spec("squeeze", [S.f(2, 1, 3)], params=dict(axis=1))
spec("_npi_squeeze", [S.f(2, 1, 3)], params=dict(axis=1))
spec("transpose", [S.f(2, 3, 2)], params=dict(axes=(2, 0, 1)))
spec("_npi_transpose", [S.f(2, 3, 2)], params=dict(axes=(2, 0, 1)))
spec("swapaxes", [S.f(2, 3, 2)], params=dict(dim1=0, dim2=2))
spec("_np_moveaxis", [S.f(2, 3, 2)], params=dict(source=0, destination=2))
spec("_npi_rollaxis", [S.f(2, 3, 2)], params=dict(axis=2))
spec("roll", [S.f(2, 4)], params=dict(shift=1, axis=1))
spec("_npi_roll", [S.f(2, 4)], params=dict(shift=1, axis=1))
spec("flip", [S.f(2, 3)], params=dict(axis=1))
spec("_npi_flip", [S.f(2, 3)], params=dict(axis=1))
spec("_npi_rot90", [S.f(2, 3)], params=dict(k=1, axes=(0, 1)))
spec("tile", [S.f(2, 3)], params=dict(reps=(2, 1)))
spec("repeat", [S.f(2, 3)], params=dict(repeats=2, axis=1))
spec("_npi_repeats", [S.f(2, 3)], params=dict(repeats=2, axis=1))
spec("pad", [S.f(1, 1, 3, 3)],
     params=dict(mode="constant", pad_width=(0, 0, 0, 0, 1, 1, 1, 1)))
spec("_npi_pad", [S.f(2, 3)],
     params=dict(pad_width=((1, 1), (0, 2)), mode="constant"))
spec("slice", [S.f(3, 4)], params=dict(begin=(1, 0), end=(3, 3)))
spec("slice_axis", [S.f(3, 4)], params=dict(axis=1, begin=1, end=3))
spec("slice_like", [S.f(3, 4), S.f(2, 3)], diff=[0])
spec("Crop", [S.f(1, 1, 4, 4), S.f(1, 1, 2, 2)], diff=[0],
     params=dict(num_args=2))
spec("concat", [S.f(2, 2), S.f(2, 3)], params=dict(dim=1))
spec("_npi_concatenate", [S.f(2, 2), S.f(2, 3)], params=dict(axis=1))
spec("stack", [S.f(2, 3), S.f(2, 3)], params=dict(axis=1))
spec("_npi_stack", [S.f(2, 3), S.f(2, 3)], params=dict(axis=1))
spec("_npi_vstack", [S.f(2, 3), S.f(1, 3)])
spec("_npi_hstack", [S.f(2, 2), S.f(2, 3)])
spec("_npi_dstack", [S.f(2, 3, 1), S.f(2, 3, 2)])
spec("_npi_column_stack", [S.f(3), S.f(3)])
spec("_rnn_param_concat", [S.f(4), S.f(6)], params=dict(dim=0))
spec("split", [S.f(2, 4)], params=dict(num_outputs=2, axis=1))
spec("_npi_hsplit", [S.f(2, 4)], params=dict(indices_or_sections=2))
spec("_npi_dsplit", [S.f(2, 3, 4)], params=dict(indices_or_sections=2))
spec("depth_to_space", [S.f(1, 4, 2, 2)], params=dict(block_size=2))
spec("space_to_depth", [S.f(1, 1, 4, 4)], params=dict(block_size=2))
spec("broadcast_to", [S.f(1, 3)], params=dict(shape=(4, 3)))
spec("_npi_broadcast_to", [S.f(1, 3)], params=dict(shape=(4, 3)))
spec("broadcast_axis", [S.f(1, 3)], params=dict(axis=0, size=4))
spec("broadcast_like", [S.f(1, 3), S.f(4, 3)], diff=[0])
spec("_npi_atleast_1d", [S.f(3)])
spec("_npi_atleast_2d", [S.f(3)])
spec("_npi_atleast_3d", [S.f(2, 3)])
spec("diag", [S.f(3, 3)])
spec("_npi_diag", [S.f(3, 3)])
spec("_npi_diagflat", [S.f(3)])
spec("_npi_diagonal", [S.f(3, 3)])
spec("_npi_tril", [S.f(3, 3)])
spec("_npi_triu", [S.f(3, 3)])

# gather / scatter (differentiate the data input only)
spec("take", [S.f(4, 3), S.ints(2, lo=0, hi=4)], diff=[0])
spec("batch_take", [S.f(3, 4), S.ints(3, lo=0, hi=4)], diff=[0])
spec("take_along_axis",
     [S.f(3, 4), S.ints(3, 2, lo=0, hi=4, dtype="int64")],
     params=dict(axis=1), diff=[0])
spec("gather_nd", [S.f(3, 4), S.ints(2, 2, lo=0, hi=3, dtype="int64")],
     diff=[0])
spec("scatter_nd", [S.f(2), S.ints(2, 2, lo=0, hi=2, dtype="int64")],
     params=dict(shape=(3, 3)), diff=[0])
spec("_npx_index_add",
     [S.f(3, 4), S.ints(1, 2, lo=0, hi=3, dtype="int64"), S.f(2, 4)],
     diff=[0, 2])
spec("_contrib_index_add",
     [S.f(3, 4), S.ints(1, 2, lo=0, hi=3, dtype="int64"), S.f(2, 4)],
     diff=[0, 2])
spec("_contrib_index_copy",
     [S.f(4, 3), S.ints(2, lo=0, hi=4, dtype="int64"), S.f(2, 3)],
     diff=[0, 2])
spec("pick", [S.f(3, 4), S.ints(3, lo=0, hi=4, dtype="float32")],
     diff=[0], params=dict(axis=1))
spec("Embedding", [S.ints(5, lo=0, hi=7, dtype="float32"), S.f(7, 3)],
     diff=[1], params=dict(input_dim=7, output_dim=3))

spec("where", [S.mask(2, 3), S.f(2, 3), S.f(2, 3)], diff=[1, 2])
spec("_npi_where", [S.mask(2, 3), S.f(2, 3), S.f(2, 3)], diff=[1, 2])
spec("_npi_where_lscalar", [S.mask(2, 3), S.f(2, 3)], diff=[1],
     params=dict(scalar=0.5))
spec("_npi_where_rscalar", [S.mask(2, 3), S.f(2, 3)], diff=[1],
     params=dict(scalar=0.5))

spec("sort", [S.sep(2, 4)], params=dict(axis=1))
spec("_npi_interp",
     [S.const(onp.array([0.7, 1.9, 3.1], "float32")),
      S.const(onp.array([0.0, 1.0, 2.0, 4.0], "float32")),
      S.const(onp.array([0.0, 1.0, 0.5, 2.0], "float32"))],
     diff=[0, 2])

# sequence ops (data diff; lengths fixed)
spec("SequenceMask",
     [S.f(4, 2, 3), S.const(onp.array([2, 3], "float32"))], diff=[0],
     params=dict(use_sequence_length=True, value=0.0))
spec("SequenceLast",
     [S.f(4, 2, 3), S.const(onp.array([2, 4], "float32"))], diff=[0],
     params=dict(use_sequence_length=True))
spec("SequenceReverse",
     [S.f(4, 2, 3), S.const(onp.array([2, 3], "float32"))], diff=[0],
     params=dict(use_sequence_length=True))

# ==========================================================================
# Specs — matmul / contraction
# ==========================================================================

spec("dot", [S.f(2, 3), S.f(3, 2)])
spec("batch_dot", [S.f(2, 2, 3), S.f(2, 3, 2)])
spec("matmul", [S.f(2, 3), S.f(3, 2)])
spec("_np_dot", [S.f(2, 3), S.f(3, 2)])
spec("inner", [S.f(2, 3), S.f(2, 3)])
spec("outer", [S.f(3), S.f(2)])
spec("vdot", [S.f(4), S.f(4)])
spec("tensordot", [S.f(2, 3), S.f(3, 2)], params=dict(axes=1))
spec("_npi_tensordot", [S.f(2, 3), S.f(3, 2)],
     params=dict(a_axes_summed=(1,), b_axes_summed=(0,)))
spec("_npi_tensordot_int_axes", [S.f(2, 3), S.f(3, 2)], params=dict(axes=1))
spec("_npi_kron", [S.f(2, 2), S.f(2, 2)])
spec("kron", [S.f(2, 2), S.f(2, 2)])
spec("_npi_cross", [S.f(2, 3), S.f(2, 3)])
spec("khatri_rao", [S.f(2, 3), S.f(2, 3)])
spec("_npi_einsum", [S.f(2, 3), S.f(3, 2)],
     params=dict(subscripts="ij,jk->ik"))
spec("_npi_polyval", [S.f(3), S.f(4)])

# ==========================================================================
# Specs — linalg
# ==========================================================================

spec("_linalg_gemm", [S.f(2, 3), S.f(3, 2), S.f(2, 2)],
     params=dict(alpha=1.0, beta=1.0))
spec("_linalg_gemm2", [S.f(2, 3), S.f(3, 2)], params=dict(alpha=1.0))
spec("_linalg_potrf", [S.spd(3)], rtol=3e-2)
spec("_linalg_potri", [S.tril(3)], rtol=4e-2, atol=5e-3)
spec("_linalg_trmm", [S.tril(3), S.f(3, 2)])
spec("_linalg_trsm", [S.tril(3), S.f(3, 2)], rtol=3e-2)
spec("_linalg_syrk", [S.f(2, 3)], params=dict(alpha=1.0))
spec("_linalg_det", [S.wellcond(3)], rtol=3e-2)
spec("_linalg_slogdet", [S.wellcond(3)], out=1)
spec("_linalg_inverse", [S.wellcond(3)], rtol=3e-2)
spec("_linalg_extractdiag", [S.f(3, 3)])
spec("_linalg_extracttrian", [S.f(3, 3)])
spec("_linalg_makediag", [S.f(3)])
spec("_linalg_maketrian", [S.f(6)])
spec("_linalg_sumlogdiag", [S.tril(3)])
spec("_npi_cholesky", [S.spd(3)], rtol=3e-2)
spec("_npi_solve", [S.wellcond(3), S.f(3, 2)], rtol=3e-2)
spec("_npi_tensorinv", [S.wellcond(3)], params=dict(ind=1), rtol=3e-2)
spec("_npi_tensorsolve", [S.wellcond(3), S.f(3)], rtol=3e-2)
spec("_npi_pinv", [S.wellcond(3, 2)], rtol=4e-2, atol=5e-3)
spec("_npi_pinv_scalar_rcond", [S.wellcond(3, 2)], rtol=4e-2, atol=5e-3)
spec("_npi_svd", [S.wellcond(2, 3)], out=1, rtol=3e-2)
spec("_npi_eigh", [S.spd(3)], out=1, rtol=3e-2)
spec("_npi_eigvalsh", [S.spd(3)], rtol=3e-2)

# ==========================================================================
# Specs — NN ops
# ==========================================================================

spec("Activation", [S.f(2, 4)], params=dict(act_type="softrelu"))
spec("LeakyReLU", [S.away(2, 4)], params=dict(act_type="leaky", slope=0.3))
spec("FullyConnected", [S.f(2, 4), S.f(3, 4), S.f(3)],
     params=dict(num_hidden=3))
spec("Convolution", [S.f(1, 2, 4, 4), S.f(2, 2, 3, 3), S.f(2)],
     params=dict(kernel=(3, 3), num_filter=2), rtol=3e-2, eps=2e-3)
spec("Deconvolution", [S.f(1, 2, 3, 3), S.f(2, 2, 3, 3), S.f(2)],
     params=dict(kernel=(3, 3), num_filter=2), rtol=3e-2, eps=2e-3)
spec("Pooling", [S.sep(1, 1, 4, 4)],
     params=dict(kernel=(2, 2), pool_type="max", stride=(2, 2)))
spec("BatchNorm", [S.f(2, 3, 2, 2), S.pos(3), S.f(3), S.f(3), S.pos(3)],
     diff=[0, 1, 2], params=dict(fix_gamma=False), train_mode=True,
     rtol=4e-2, atol=5e-3, eps=2e-3)
spec("LayerNorm", [S.f(2, 4), S.pos(4), S.f(4)], rtol=3e-2)
spec("GroupNorm", [S.f(1, 4, 3), S.pos(4), S.f(4)],
     params=dict(num_groups=2), rtol=3e-2)
spec("InstanceNorm", [S.f(2, 3, 4), S.pos(3), S.f(3)], rtol=3e-2)
spec("RMSNorm", [S.f(2, 4), S.pos(4)], rtol=3e-2)
spec("L2Normalization", [S.away(2, 4)], rtol=3e-2)
spec("LRN", [S.f(1, 3, 2, 2)], params=dict(nsize=3), rtol=3e-2)
spec("UpSampling", [S.f(1, 1, 2, 2)],
     params=dict(scale=2, sample_type="nearest", num_args=1))
spec("BilinearResize2D", [S.f(1, 1, 3, 3)], params=dict(height=5, width=5))
spec("adaptive_avg_pool2d", [S.f(1, 1, 4, 4)], params=dict(output_size=2))
spec("im2col", [S.f(1, 1, 4, 4)], params=dict(kernel=(3, 3)))
spec("col2im", [S.f(1, 9, 4)],
     params=dict(input_size=(4, 4), kernel=(3, 3)))
spec("GridGenerator", [S.f(1, 6)],
     params=dict(transform_type="affine", target_shape=(3, 3)))
spec("BilinearSampler",
     [S.f(1, 1, 4, 4), S.unit(1, 2, 3, 3, margin=0.3)], eps=5e-4,
     rtol=4e-2, atol=5e-3)
spec("SpatialTransformer", [S.f(1, 1, 4, 4), S.f(1, 6, lo=-0.2, hi=0.2)],
     params=dict(transform_type="affine", sampler_type="bilinear",
                 target_shape=(3, 3)), eps=5e-4, rtol=4e-2, atol=5e-3)
spec("ROIPooling",
     [S.sep(1, 1, 6, 6), S.const(onp.array([[0, 0, 0, 3, 3]], "float32"))],
     diff=[0], params=dict(pooled_size=(2, 2), spatial_scale=1.0))
spec("_contrib_ROIAlign",
     [S.f(1, 1, 6, 6), S.const(onp.array([[0, 0.5, 0.5, 3.5, 3.5]],
                                         "float32"))],
     diff=[0], params=dict(pooled_size=(2, 2), spatial_scale=1.0),
     eps=5e-4, rtol=4e-2, atol=5e-3)
spec("_contrib_PSROIPooling",
     [S.f(1, 4, 4, 4), S.const(onp.array([[0, 0, 0, 3, 3]], "float32"))],
     diff=[0], params=dict(pooled_size=2, output_dim=1, spatial_scale=1.0))
spec("_contrib_DeformableConvolution",
     [S.f(1, 1, 4, 4), S.f(1, 18, 2, 2, lo=-0.1, hi=0.1),
      S.f(1, 1, 3, 3)],
     params=dict(kernel=(3, 3), num_filter=1), diff=[0, 2],
     eps=5e-4, rtol=4e-2, atol=5e-3)
spec("_contrib_ModulatedDeformableConvolution",
     [S.f(1, 1, 4, 4), S.f(1, 18, 2, 2, lo=-0.1, hi=0.1),
      S.mask(1, 9, 2, 2), S.f(1, 1, 3, 3)],
     params=dict(kernel=(3, 3), num_filter=1), diff=[0, 3],
     eps=5e-4, rtol=4e-2, atol=5e-3)
spec("Correlation", [S.f(1, 1, 4, 4), S.f(1, 1, 4, 4)],
     params=dict(kernel_size=1, max_displacement=1, stride1=1, stride2=1),
     rtol=3e-2)
spec("_contrib_count_sketch", [S.f(2, 4),
                               S.const(onp.array([0, 2, 1, 3], "float32")),
                               S.const(onp.array([1, -1, 1, -1],
                                                 "float32"))],
     diff=[0], params=dict(out_dim=4))
spec("_contrib_hawkesll",
     [S.pos(1, 2),                                   # lda (N,K)
      S.pos(2, lo=0.3, hi=0.8),                      # alpha (K,)
      S.pos(2),                                      # beta (K,)
      S.pos(1, 2, lo=0.1, hi=0.4),                   # state (N,K)
      S.const(onp.array([[0.5, 1.2, 2.0]], "float32")),   # lags
      S.const(onp.array([[0, 1, 0]], "float32")),         # marks
      S.const(onp.array([3], "int32")),                   # valid_length
      S.const(onp.array([4.0], "float32"))],              # max_time
     diff=[0, 1, 2], out=0, rtol=3e-2)
spec("_contrib_interleaved_matmul_selfatt_qk", [S.f(3, 1, 12)],
     params=dict(heads=2))
spec("_contrib_interleaved_matmul_selfatt_valatt",
     [S.f(3, 1, 12), S.f(2, 3, 3)], params=dict(heads=2))
spec("_contrib_interleaved_matmul_encdec_qk",
     [S.f(3, 1, 4), S.f(3, 1, 8)], params=dict(heads=2))
spec("_contrib_interleaved_matmul_encdec_valatt",
     [S.f(3, 1, 8), S.f(2, 3, 3)], params=dict(heads=2))

# ==========================================================================
# Specs — random pdf ops (deterministic functions of (sample, params))
# ==========================================================================

spec("_random_pdf_normal", [S.f(2, 4), S.f(2), S.pos(2)])
spec("_random_pdf_uniform",
     [S.pos(2, 4, lo=0.1, hi=0.9), S.const(onp.zeros((2,), "float32")),
      S.const(onp.ones((2,), "float32") * 1.5)], diff=[0])
spec("_random_pdf_exponential", [S.pos(2, 4), S.pos(2)])
spec("_random_pdf_gamma", [S.pos(2, 4), S.pos(2), S.pos(2)], rtol=3e-2)
spec("_random_pdf_poisson", [S.ints(2, 4, lo=0, hi=5, dtype="float32"),
                             S.pos(2)], diff=[1])
spec("_random_pdf_negative_binomial",
     [S.ints(2, 4, lo=0, hi=5, dtype="float32"),
      S.const(onp.array([3.0, 4.0], "float32")),
      S.const(onp.array([0.4, 0.6], "float32"))], diff=[2], rtol=3e-2)
spec("_random_pdf_generalized_negative_binomial",
     [S.ints(2, 4, lo=0, hi=5, dtype="float32"), S.pos(2),
      S.pos(2, lo=0.3, hi=0.8)], diff=[1, 2], rtol=3e-2)

# ==========================================================================
# Specs — image ops (float paths)
# ==========================================================================

spec("_image_normalize", [S.f(3, 4, 4)],
     params=dict(mean=(0.2, 0.3, 0.4), std=(0.9, 1.0, 1.1)))
spec("_image_to_tensor", [S.pos(4, 4, 3, lo=0.0, hi=1.0)])
spec("_image_resize", [S.f(4, 4, 1)], params=dict(size=6))
spec("_image_crop", [S.f(5, 5, 1)],
     params=dict(x=1, y=1, width=3, height=3))
exempt(["_image_random_crop", "_image_random_resized_crop"],
       "stochastic augmentation (random geometry per call); "
       "deterministic crop/resize paths are swept above")
