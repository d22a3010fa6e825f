"""Operations and bytes the algorithm needs, counted from shapes.

These are the yardstick's numerators: a roofline share or a utilization
divides them by a measured time.  They count the work the algorithm
requires, not what an implementation happens to do (recomputation, lane
shuffles done as matrix products), so a faster implementation can never
read above 100%.

This module keeps the arithmetic of the GWT update, which every
architecture shares, over the architecture's own ``layout``; what a
token's forward and backward need is the architecture module's
``model_flops_per_token`` (``bench/model.py``).
"""

from __future__ import annotations

from bench.model import numel

# bytes of one element of each dtype name the configurations use
DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def gwt_elements(arch, spec, level: int) -> int:
    """Parameters the wavelet rule updates (the fused kernel's input)."""
    return sum(numel(lf.shape) for lf in arch.layout(spec, level)
               if lf.rule == "gwt")


def gwt_bytes_per_element(level: int, grad_bytes: int, param_bytes: int,
                          moment_bytes: int = 4) -> float:
    """Least HBM traffic per updated element: one read of the gradient,
    one read and one write of the parameter, one read and one write of
    both moments, which live on the approximation band (``1/2**level`` of
    the width)."""
    return (grad_bytes + 2 * param_bytes
            + 4 * moment_bytes / (1 << level))


def gwt_flops_per_element(level: int) -> float:
    """Arithmetic of one update per gradient element, each operation once:

    * forward Haar butterfly: 2 per input element per level, on a width
      that halves each level;
    * Adam on the approximation band (11 per band element: two moment
      updates, square root, add, divide, multiply);
    * scaling the detail bands (1 per detail element);
    * inverse butterfly (as the forward);
    * the norm-growth limiter's sum of squares (2) and the write
      ``p - step·scale·g̃`` (3).
    """
    butterfly = sum(2.0 / (1 << k) for k in range(level))
    band = 1.0 / (1 << level)
    return 2 * butterfly + 11 * band + (1 - band) + 2 + 3


def gwt_kernel_work(arch, spec, level: int) -> tuple:
    """``(flops, bytes)`` of one optimizer step's wavelet updates."""
    n = gwt_elements(arch, spec, level)
    b = DTYPE_BYTES[spec.dtype]
    return (n * gwt_flops_per_element(level),
            n * gwt_bytes_per_element(level, b, b))
