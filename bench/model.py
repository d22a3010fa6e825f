"""What the benchmark needs of a model architecture, and the parameter
leaf every architecture lists.

A configuration is a JSON file under ``bench/configs/``: the model's public
``config.json`` numbers under their own keys, with the keys changed from
the source listed in ``reduced``, and ``bench_arch`` naming the
architecture module ``bench/arch/<bench_arch>.py`` that reads it.  That
module is the only place that knows the architecture.  It provides:

* ``load_spec(path) -> Spec`` — its own reading of the file, a frozen
  dataclass with at least ``name``, ``vocab``, ``dtype`` (the
  ``torch_dtype``) and ``init_std`` (the ``initializer_range``);
* ``layout(spec, level) -> list[Leaf]`` — every parameter, sorted by path
  (the order the weights are drawn in), with the update rule the
  benchmark holds the optimizer to and its initialisation;
* ``model_flops_per_token(spec, seq) -> float`` — the forward and
  backward operations one trained token needs, counting only the weights
  it is routed to; ``step_mfu`` reads it;
* ``program_config(spec, seq)`` — the program's configuration object;
* ``Reference(spec, opt, seq, precision=, fault=, limiter=)`` — the plain
  reference, a :class:`bench.reference.Trainer` that gives the model's
  loss and gradients.

The driver, the weights, the counts and the check take everything
architecture-specific from that module, so a new architecture enters as
new files only: its module, a configuration naming it, a traffic mix and
the cells' limits.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple


@dataclasses.dataclass(frozen=True)
class Leaf:
    path: str                 # '/'-joined, as the program's tree has it
    shape: Tuple[int, ...]    # as stored (layer stacks lead with L)
    rule: str                 # "gwt" | "adam"
    stacked: bool             # leading axis is the layer axis
    init: str                 # "normal" | "zeros"
    axis: Optional[int] = None  # wavelet transform axis (gwt only: last)


def numel(shape) -> int:
    return math.prod(shape)
