"""Quantized matmul dispatch — TPU equivalent of the reference matmul layer.

The reference dispatches on (weightType x inputType) pairs of hand-written SIMD loops
(src/funcs.cpp:424-465, hot path matmulQ40vQ80 at funcs.cpp:287-396). Here there is ONE
logical op: y[..., out] = x[..., in] · W[out, in], where W may be dense or block-quantized.

Execution paths:
- decode (one row of activations) with i8-layout weights: `pallas_q8.q8_matvec`, the
  fused int8-plane MXU kernel (HBM-bandwidth-bound, zero per-weight VPU work).
- everything else: dequantize-to-dtype + `dot_general`; XLA fuses the scale broadcast
  into the matmul's operand pipeline. Prefill lands here on purpose — with many
  activation rows the per-weight dequant amortizes and the MXU runs dense bf16.

Weights keep the reference's (out, in) row-major orientation with quant blocks along `in`
(src/commands.cpp:22-39), so TP row/col splits slice whole blocks.
"""

from __future__ import annotations

import math
import threading

import jax
import jax.numpy as jnp

from ..obs import metrics
from ..platform_env import interpret_requested
from ..quants import QTensor
from ..resilience import faults
from ..resilience.errors import FaultInjected, TransientDispatchError

# "fused" is a strict superset of "all": everything "all" lowers plus the
# residual-add / silu·mul epilogue fusions wired through models/forward.py
FUSED_POLICIES = ("all", "fused")

_KERNEL_SELECTED = metrics.counter(
    "matmul_kernel_selected_total",
    "matmul kernel lowerings by selected kernel (counted at trace time: one "
    "per compiled program per call site, not per dispatch)",
    labelnames=("kernel",))

# trace-time record of which kernel served each (M, N, K, layout) bucket —
# the per-shape truth behind bench.py's provenance fields and /v1/stats'
# kernel block. Keys are dispatch-shape buckets (bounded: one per distinct
# lowered matmul shape), values are kernel names.
_selections: dict[str, str] = {}
_selections_lock = threading.Lock()


def _record(kernel: str, m: int, w: QTensor, op: str = "mm") -> None:
    n, kin = w.shape
    key = f"m={m},n={n},k={kin},layout={w.layout},op={op}"
    with _selections_lock:
        if _selections.get(key) != kernel:
            _selections[key] = kernel
            _KERNEL_SELECTED.labels(kernel=kernel).inc()


def kernel_selections() -> dict[str, str]:
    """Snapshot of {shape-bucket: kernel} selections recorded at trace time
    (bench.py provenance + /v1/stats). Kernel names: q4_matvec, q8_matvec,
    q4_mm, q4_mm+res, q4_gated_mm, xla, xla-fallback."""
    with _selections_lock:
        return dict(_selections)


def reset_kernel_selections() -> None:
    """Tests/bench only: drop the recorded selection map."""
    with _selections_lock:
        _selections.clear()


def qmatmul(x: jax.Array, w: QTensor, *, use_pallas: bool | str = False,
            out_dtype=None, residual: jax.Array | None = None) -> jax.Array:
    """y = x @ W^T for W of logical shape (out, in); x: (..., in) -> (..., out).

    use_pallas: False = XLA everywhere; True = fused kernels for decode (one
    activation row); "all" = additionally the fused dequant-matmul for M>1
    (prefill / batched decode — ops/pallas_q4_mm.py); "fused" = "all" plus the
    fused epilogues (--fused-matmul / DLT_FUSED_MATMUL).

    residual: optional (..., out) tensor; the result is residual + x @ W^T on
    EVERY path (under "fused" the add runs inside the kernel's accumulator;
    the fallbacks add in f32 before the out_dtype cast — same rounding as one
    fused f32 accumulate, so a shape-gated fallback stays token-identical)."""
    m = math.prod(x.shape[:-1])
    if use_pallas and m == 1:
        if w.layout == "i4p":
            from .pallas_q4 import q4_decode_supported, q4_matvec

            if w.groups == 1 and q4_decode_supported(w):
                _record("q4_matvec", m, w)
                y = q4_matvec(x, w, out_dtype=out_dtype or x.dtype)
                return y if residual is None else _res_add(y, residual,
                                                           out_dtype or x.dtype)
        else:
            from .pallas_q8 import q8_decode_supported, q8_matvec

            if q8_decode_supported(w):
                _record("q8_matvec", m, w)
                y = q8_matvec(x, w, out_dtype=out_dtype or x.dtype)
                return y if residual is None else _res_add(y, residual,
                                                           out_dtype or x.dtype)
    if use_pallas in FUSED_POLICIES and m > 1 and w.layout == "i4p":
        from .pallas_q4_mm import q4_matmul, q4_mm_supported

        if not _kernel_select_ok(m, w):
            return _qmatmul_xla(x, w, out_dtype=out_dtype, residual=residual)
        if q4_mm_supported(w, m):
            fuse_res = residual is not None and use_pallas == "fused"
            y = q4_matmul(x, w, out_dtype=out_dtype or x.dtype,
                          residual=residual if fuse_res else None)
            _record("q4_mm+res" if fuse_res else "q4_mm", m, w)
            if residual is not None and not fuse_res:
                return _res_add(y, residual, out_dtype or x.dtype)
            return y
    _record("xla", m, w)
    return _qmatmul_xla(x, w, out_dtype=out_dtype, residual=residual)


def _kernel_select_ok(m: int, w: QTensor, op: str = "mm") -> bool:
    """The `matmul.kernel_select` injection point (docs/ROBUSTNESS.md): fires
    BEFORE the shape gate so the fault-matrix cells are non-vacuous on any
    fused engine. An injected fault degrades that call site to the XLA
    lowering, recorded as `xla-fallback`; nothing else is caught here, so a
    kernel that fails to trace or lower fails the step that asked for it."""
    try:
        faults.fire("matmul.kernel_select", m=m, n=w.shape[0])
    except (FaultInjected, TransientDispatchError):
        _record("xla-fallback", m, w, op=op)
        return False
    return True


def _res_add(y: jax.Array, residual: jax.Array, out_dtype) -> jax.Array:
    return (residual.astype(jnp.float32)
            + y.astype(jnp.float32)).astype(out_dtype)


def _qmatmul_xla(x: jax.Array, w: QTensor, *, out_dtype=None,
                 residual: jax.Array | None = None) -> jax.Array:
    """The oracle path: dequantize + dot_general; XLA fuses the scale
    broadcast into the operand pipeline. Residual adds in f32 before the
    cast (identical rounding to the kernel's f32 accumulator-init)."""
    wd = w.dequantize(dtype=x.dtype)
    y = jax.lax.dot_general(
        x, wd,
        dimension_numbers=(((x.ndim - 1,), (wd.ndim - 1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    if residual is not None:
        y = residual.astype(jnp.float32) + y
    return y.astype(out_dtype or x.dtype)


def qmatmul_gated(x: jax.Array, w1: QTensor, w3: QTensor, *, act,
                  act_name: str, use_pallas: bool | str = False,
                  out_dtype=None) -> jax.Array:
    """FFN gate-pair: act(x @ w1^T) * (x @ w3^T). Under use_pallas == "fused"
    with M>1 and a kernel-eligible i4p pair this lowers to ONE
    q4_gated_matmul (both weight streams at packed density, intermediates
    VMEM-only); every other configuration runs two qmatmul calls + the jnp
    activation (`act`, matching the kernel's `act_name` epilogue)."""
    m = math.prod(x.shape[:-1])
    if (use_pallas == "fused" and m > 1
            and w1.layout == "i4p" and w3.layout == "i4p"
            and act_name in ("silu", "gelu_tanh")):
        from .pallas_q4_mm import q4_gated_matmul, q4_gated_supported

        if not _kernel_select_ok(m, w1, op="gated"):
            return (act(_qmatmul_xla(x, w1, out_dtype=out_dtype))
                    * _qmatmul_xla(x, w3, out_dtype=out_dtype))
        if q4_gated_supported(w1, w3, m):
            y = q4_gated_matmul(x, w1, w3, act=act_name,
                                out_dtype=out_dtype or x.dtype)
            _record("q4_gated_mm", m, w1, op="gated")
            return y
    return (act(qmatmul(x, w1, use_pallas=use_pallas, out_dtype=out_dtype))
            * qmatmul(x, w3, use_pallas=use_pallas, out_dtype=out_dtype))


def qmatmul_q80(xq: jax.Array, sx: jax.Array, w: QTensor, *,
                use_pallas: bool = False, out_dtype=jnp.float32) -> jax.Array:
    """Decode matvec against a PRE-QUANTIZED activation row.

    xq (1, K) int8 + sx (1, K//32) f32 are the Q80 form of the activation (from
    ops.pallas_prologue); returns (1, 1, N). Routes into the inline-Xexp matvec
    variants so the quantized row is the only activation HBM traffic; the XLA
    fallback dequantizes x̂ = xq·sx and runs the dense path (same numerics —
    activation quantization already happened upstream either way).
    """
    from ..quants import jnp_dequantize_i8

    if use_pallas:
        if w.layout == "i4p":
            from .pallas_q4 import _q4_matvec_inline, q4_decode_supported

            if w.groups == 1 and q4_decode_supported(w):
                y = _q4_matvec_inline(xq, sx, w.data, w.scales,
                                      interpret=interpret_requested())
                return y.reshape(1, 1, y.shape[0]).astype(out_dtype)
        elif w.layout == "i8":
            from .pallas_q8 import _q8_matvec_inline, q8_decode_supported

            if q8_decode_supported(w):
                y = _q8_matvec_inline(xq, sx, w.data, w.scales,
                                      interpret=interpret_requested())
                return y.reshape(1, 1, y.shape[0]).astype(out_dtype)
    xhat = jnp_dequantize_i8(xq, sx, dtype=jnp.float32)  # (1, K)
    wd = w.dequantize(dtype=jnp.float32)
    y = jax.lax.dot_general(xhat, wd, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    return y.reshape(1, 1, y.shape[-1]).astype(out_dtype)
