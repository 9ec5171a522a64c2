"""Quantized-gradient training (use_quantized_grad).

Reference: src/treelearner/gradient_discretizer.cpp:22 — per-iteration
gradient/hessian discretization to num_grad_quant_bins levels with
stochastic rounding (truncation toward zero of x/scale +- u), scales
g_scale = max|g| / (bins/2), h_scale = max|h| / bins, and optional
true-gradient leaf renewal (quant_train_renew_leaf,
RenewIntGradTreeOutput).

TPU formulation: the quantized levels flow through the standard
histogram kernel as DEQUANTIZED f32 values (level * scale) — the
accumulated sums equal the reference's int-histogram sums times the
scales up to f32 addition rounding, so split decisions match the
quantized semantics without new kernels. The deferred perf half
(int8 one-hot matmuls on the MXU + int16 psum payloads, the analog of
bin.h:63-81 wire reducers) slots in behind this same interface.

Randomness is keyed on (seed, iteration) — the reference's
pre-generated random value table with a rotating start offset
(gradient_discretizer.cpp:25-41) serves the same purpose.
"""

from __future__ import annotations

from typing import Optional, Tuple

# internal discretization levels per hist_dtype policy: int16 channels
# carry 256 levels (g in [-128, 128], h in [0, 256] — bf16-exact ints
# and far inside the int16 accumulation range), int8 carries 127 so the
# slot kernel can run s8 x s8 -> s32 on the MXU (histogram.int8_oh_shift
# bounds the SWAR scale against s32 cell overflow)
HIST_DTYPE_LEVELS = {"int16": 256, "int8": 127}


def resolve_hist_dtype(
    requested: str,
    use_quantized_grad: bool,
    num_grad_quant_bins: int,
    use_rounds: bool,
    on_tpu: bool = True,
) -> Tuple[str, int, Optional[str]]:
    """Resolve the tpu_hist_dtype policy to the histogram channel
    layout one tree actually accumulates with.

    Returns (resolved, internal_levels, warning):

    - resolved: "bf16x2" | "int16" | "int8" — the channel layout;
    - internal_levels: discretization levels for the INTERNAL int-packed
      default path (0 when bf16x2 or when use_quantized_grad supplies
      its own levels);
    - warning: a message when an explicit request had to fall back.

    Under use_quantized_grad the quantized-API levels govern: the
    resolved name just reports what that path does (int8/int16 slot
    channels on the rounds grower, dequantized bf16x2 otherwise).
    Off the rounds growth path the int-packed channels do not exist
    (the sequential growers accumulate f32 hi/lo), so explicit
    int16/int8 requests fall back to bf16x2 with a warning.

    "auto" flips to int16 only when use_rounds AND on_tpu: off-chip
    rounds runs (tests, CPU fallbacks) keep the bit-exact bf16x2
    layout — same contract as tpu_growth_mode=auto, which keeps CPU
    runs reference-exact. An EXPLICIT int16/int8 request on the rounds
    path is honored on any backend (that is how the parity suites
    exercise the packed channels off-chip).
    """
    if use_quantized_grad:
        if use_rounds and num_grad_quant_bins <= 127:
            return "int8", 0, None
        if use_rounds and num_grad_quant_bins <= 256:
            return "int16", 0, None
        return "bf16x2", 0, None
    req = requested
    if req == "auto":
        req = "int16" if (use_rounds and on_tpu) else "bf16x2"
    if req in HIST_DTYPE_LEVELS and not use_rounds:
        return "bf16x2", 0, (
            f"tpu_hist_dtype={requested} needs the rounds growth path "
            "(tpu_growth_mode=rounds, or auto on TPU hardware); "
            "falling back to bf16x2 channels"
        )
    return req, HIST_DTYPE_LEVELS.get(req, 0), None


def discretize_gradients_int(
    grad,
    hess,
    key,
    num_bins: int,
    stochastic: bool,
):
    """(grad, hess) -> ((grad_q, hess_q) INTEGER levels, (2,) scales).

    Matches DiscretizeGradients: grad levels in [-bins/2, bins/2],
    hess levels in [0, bins]; stochastic rounding truncates toward zero
    after adding signed uniform noise, plain rounding truncates after
    adding 0.5. The integer levels feed the rounds grower's 3-channel
    exact-int histogram path (spec.quant)."""
    import jax
    import jax.numpy as jnp

    g_scale = jnp.maximum(jnp.max(jnp.abs(grad)), 1e-30) / (num_bins // 2)
    h_scale = jnp.maximum(jnp.max(jnp.abs(hess)), 1e-30) / num_bins
    if stochastic:
        kg, kh = jax.random.split(key)
        ug = jax.random.uniform(kg, grad.shape)
        uh = jax.random.uniform(kh, hess.shape)
    else:
        ug = 0.5
        uh = 0.5
    gq = jnp.trunc(grad / g_scale + jnp.sign(grad) * ug)
    hq = jnp.trunc(hess / h_scale + uh)  # hessians are non-negative
    return gq, hq, jnp.stack([g_scale, h_scale])


def discretize_gradients(
    grad,
    hess,
    key,
    num_bins: int,
    stochastic: bool,
):
    """(grad, hess) -> dequantized (grad_q, hess_q) at num_bins levels
    (level * scale), for the growers that consume plain f32 channels."""
    gq, hq, scale = discretize_gradients_int(
        grad, hess, key, num_bins, stochastic
    )
    return gq * scale[0], hq * scale[1]


def renew_leaf_with_true_gradients(leaf_value, row_leaf, grad, hess, mask,
                                   params, num_leaves: int):
    """quant_train_renew_leaf: recompute leaf outputs from the TRUE
    (unquantized) per-leaf gradient/hessian sums
    (gradient_discretizer RenewIntGradTreeOutput)."""
    import jax.numpy as jnp

    from ..timer import device_phase
    from .histogram import seg_sum
    from .split import leaf_output

    L = num_leaves
    with device_phase("boosting.renew"):
        idx = jnp.where((row_leaf >= 0) & (mask > 0), row_leaf, L)
        sums = seg_sum(jnp.stack([grad * mask, hess * mask]), idx, L)
        sum_g, sum_h = sums[0], sums[1]
        renewed = leaf_output(sum_g, sum_h, params)
        return jnp.where(sum_h > 0, renewed, leaf_value)
