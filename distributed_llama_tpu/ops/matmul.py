"""Quantized matmul dispatch — TPU equivalent of the reference matmul layer.

The reference dispatches on (weightType x inputType) pairs of hand-written SIMD loops
(src/funcs.cpp:424-465, hot path matmulQ40vQ80 at funcs.cpp:287-396). Here there is ONE
logical op: y[..., out] = x[..., in] · W[out, in], where W may be dense or block-quantized.

Execution paths (`qmatmul`, one rule from shapes):
- one row of activations: the matvec kernels (`pallas_q4.q4_matvec` on split-plane
  Q40, `pallas_q8.q8_matvec` on int8 planes), HBM-bandwidth-bound. Their bound on K
  (`pallas_q8.q8_shape_supported`, `pallas_q4.q4_shape_supported`: the resident Xexp
  operand, K <= 17378) is the one-row matvec's and is asked at one row only: a
  split-plane weight over it (A.X-K1's dense `w2`, K 18432) is dequantized from its
  pack by XLA at one row and read by the dequant-matmul at 2 to 512.
- 2 to 512 rows on split-plane Q40: `pallas_q4_mm.q4_matmul`, the packed weights
  decoded in VMEM and fed to the MXU as bf16.
- everything else, and `use_pallas=False`: dequantize-to-dtype + `dot_general`.

Weights keep the reference's (out, in) row-major orientation with quant blocks along `in`
(src/commands.cpp:22-39), so TP row/col splits slice whole blocks.
"""

from __future__ import annotations

import math
import threading
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..obs import metrics
from ..quants import QTensor
from ..resilience import faults
from ..resilience.errors import FaultInjected, TransientDispatchError

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


class LayerOf(NamedTuple):
    """The matrix at leading indices `at` (traced) of a weight stacked over
    layers, or over layers and experts, not yet sliced: `(layer,)` of an
    (L, N, K) stack, `(layer, expert)` of an (L, E, N, K) one, `(expert,)`
    of a layer's (E, N, K). With fewer indices than leading axes it names
    the stack under them (a layer's experts). The fused dequant-matmul and
    the grouped expert kernels read their blocks out of the whole stack, and
    a slice made for them would be one more copy of every layer's packed
    weights a dispatch (ops/pallas_q4_mm.py `_q4_matmul`). Any other lowering
    takes `one()`."""
    stack: QTensor
    at: tuple

    @property
    def shape(self):
        return self.stack.shape[len(self.at):]

    def of(self, i) -> "LayerOf":
        """Entry i of the stack this names."""
        return LayerOf(self.stack, (*self.at, i))

    def one(self) -> QTensor:
        w = self.stack
        for i in self.at:
            w = jax.tree.map(lambda a, i=i: jax.lax.dynamic_index_in_dim(
                a, i, 0, keepdims=False), w)
        return w


def reads_the_stack(w, m: int, use_pallas: bool) -> bool:
    """Whether at m rows a kernel would read this stacked weight (one
    leading axis or more: layers, experts) in place, stack and all: what the
    layer scan (models/forward.py) keeps out of its sliced operands, and what
    `qmatmul` hands the fused dequant-matmul whole."""
    from .pallas_q4_mm import q4_mm_supported

    return bool(use_pallas and isinstance(w, QTensor) and w.data.ndim > 2
                and q4_mm_supported(w, m, stacked=w.data.ndim - 2))


def _record(kernel: str, m: int, w: QTensor) -> None:
    n, kin = w.shape[-2:]
    key = f"m={m},n={n},k={kin},layout={w.layout},op=mm"
    with _selections_lock:
        if _selections.get(key) != kernel:
            _selections[key] = kernel
            _KERNEL_SELECTED.labels(kernel=kernel).inc()


def kernel_selections() -> dict[str, str]:
    """Snapshot of {shape-bucket: kernel} selections recorded at trace time
    (bench.py provenance + /v1/stats). Kernel names: q4_matvec, q8_matvec,
    q4_mm, xla, xla-fallback."""
    with _selections_lock:
        return dict(_selections)


def reset_kernel_selections() -> None:
    """Tests/bench only: drop the recorded selection map."""
    with _selections_lock:
        _selections.clear()


def qmatmul(x: jax.Array, w: QTensor | LayerOf, *, use_pallas: bool = False,
            out_dtype=None, name: str | None = None) -> jax.Array:
    """y = x @ W^T for W of logical shape (out, in); x: (..., in) -> (..., out).

    use_pallas: False = XLA everywhere (the tests' oracle); truthy = every
    kernel whose gate admits the shape, chosen from what is visible here
    (rows M, the weight's shape, layout and `groups`) and nothing else:
    M == 1 the matvec kernels, 2 <= M <= 512 on a split-plane Q40 weight the
    fused dequant-matmul (ops/pallas_q4_mm.py), anything else XLA's
    dequantize-then-dot. On the chip the dequant-matmul read 1.9 to 7.8 ps a
    weight at 8 and 64 rows and 6.5 to 10.6 at 512 against XLA's 5.6 to 11.4
    and 10.3 to 14.1 at every matmul shape of the benchmark's three
    configurations, the ragged 151936-row head and the expert scan's
    (28672, 4096) slices among them: level with XLA on SmallThinker's two
    small projections at 8 and 64 rows, faster everywhere else (PERF.md
    section 6, PR 31), so the gate declines no shape for speed.

    w may be a `LayerOf`: the dequant-matmul then reads the matrix out of
    the stack, every other lowering gets the slice. `name`: what a device
    trace shows the dequant-matmul as where a caller tells its projections
    apart (`q4_mm_conv_in`; "q4_mm" otherwise)."""
    m = math.prod(x.shape[:-1])
    dt = out_dtype or x.dtype
    at = ()
    if isinstance(w, LayerOf):
        if reads_the_stack(w.stack, m, use_pallas):
            w, at = w
        else:
            w = w.one()
    if use_pallas and m == 1:
        if w.layout == "i4p":
            from .pallas_q4 import q4_decode_supported, q4_matvec

            if w.groups == 1 and q4_decode_supported(w):
                _record("q4_matvec", m, w)
                return q4_matvec(x, w, out_dtype=dt)
        else:
            from .pallas_q8 import q8_decode_supported, q8_matvec

            if q8_decode_supported(w):
                _record("q8_matvec", m, w)
                return q8_matvec(x, w, out_dtype=dt)
    if use_pallas and m > 1 and w.layout == "i4p":
        from .pallas_q4_mm import q4_matmul, q4_mm_supported

        if not _kernel_select_ok(m, w):
            return _qmatmul_xla(x, LayerOf(w, at).one(), out_dtype=out_dtype)
        if q4_mm_supported(w, m, stacked=len(at)):
            _record("q4_mm", m, w)
            return q4_matmul(x, w, at=at, out_dtype=dt,
                             **({"name": name} if name else {}))
    _record("xla", m, w)
    return _qmatmul_xla(x, w, out_dtype=out_dtype)


def _kernel_select_ok(m: int, w: QTensor) -> bool:
    """The `matmul.kernel_select` injection point (docs/ROBUSTNESS.md): fires
    BEFORE the shape gate so the fault-matrix cells are non-vacuous on any
    engine with the kernels on. An injected fault degrades that call site to
    the XLA lowering, recorded as `xla-fallback`; nothing else is caught here, so a
    kernel that fails to trace or lower fails the step that asked for it."""
    try:
        faults.fire("matmul.kernel_select", m=m, n=w.shape[-2])
    except (FaultInjected, TransientDispatchError):
        _record("xla-fallback", m, w)
        return False
    return True


def _qmatmul_xla(x: jax.Array, w: QTensor, *, out_dtype=None) -> jax.Array:
    """The oracle path: dequantize + dot_general; XLA fuses the scale
    broadcast into the operand pipeline."""
    wd = w.dequantize(dtype=x.dtype)
    y = jax.lax.dot_general(
        x, wd,
        dimension_numbers=(((x.ndim - 1,), (wd.ndim - 1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return y.astype(out_dtype or x.dtype)
