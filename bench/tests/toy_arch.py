"""A toy architecture for the tests: the dense decoder with attention-only
blocks (no MLP).  It enters a benchmark root as one new file,
``bench/arch/toy.py``, beside the ``dense.py`` it builds on."""

import dataclasses
import pathlib

from bench.harness import load_module

dense = load_module(pathlib.Path(__file__).with_name("dense.py"))
program_config = dense.program_config      # d_ff 0: the program builds no MLP


def load_spec(path):
    return dataclasses.replace(dense.load_spec(path), ff=0)


def layout(spec, level):
    return [lf for lf in dense.layout(spec, level)
            if not lf.path.startswith(("layers/b0/ffn/", "layers/b0/norm2"))]


def model_flops_per_token(spec, seq):
    """PaLM's count for attention-only blocks: q, k, v and o, the head."""
    n = (spec.layers * (2 * spec.d * spec.q_width + 2 * spec.d * spec.kv_width)
         + spec.d * spec.vocab)
    return 6.0 * n + 12 * spec.layers * spec.heads * spec.head_dim * seq


class Reference(dense.Reference):
    def __init__(self, spec, opt, seq, **kw):
        super().__init__(spec, opt, seq, **kw)
        self.leaves = layout(spec, opt["level"])

    def _layer(self, lp, x, cos, sin):
        return self._attention(lp, x, cos, sin)
