"""The main path's Pallas kernels compile for a TPU v5e at llama-1b widths.

Nothing runs: each test lowers one kernel for a described (not attached)
v5e chip and compiles it with the chip's own compiler, which refuses what
interpret mode accepts — block shapes off the (8, 128) tiling, lane
reshapes Mosaic cannot lower, tiles past the scoped VMEM limit.  The
shapes are llama-1b's GWT buckets as the optimizer hands them over: each
``(24, m, n)`` layer stack merged into ``24·m`` rows, so the MLP buckets
are 5461-row stripes stacked 24 deep.

The topology is described inside a module fixture, never at import: only
one process may hold the TPU library, and the test workers all import
this file.
"""

import functools
import re

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.gwt_adam import kernel as kg
from repro.kernels.haar_dwt import kernel as kf

F32, BF16, I8, U32 = jnp.float32, jnp.bfloat16, jnp.int8, jnp.uint32
LEVEL = 2
BLOCK = 64
# (L, rows, n): wq/wk/wv/wo, and the w_gate/w_up pair (transposed: DHT
# over d_model) / w_down
ATTN = (4, 24 * 2048, 2048)
MLP = (2, 24 * 5461, 2048)
MLP_DOWN = (1, 24 * 5461, 2048)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without one, so keep the cache out of these tests
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo
    return hlo


def _fused_args(L, m, n):
    na = n >> LEVEL
    return [((L, m, n), BF16), ((L, m, n), BF16), ((L, m, na), F32),
            ((L, m, na), F32), ((L,), F32), ((), F32), ((), F32)]


def _fused_q8_args(L, m, n):
    na = n >> LEVEL
    scales = (L, -(-na // BLOCK), m)  # codec.scale_shape per leaf
    return [((L, m, n), BF16), ((L, m, n), BF16), ((L, m, na), I8),
            (scales, F32), ((L, m, na), I8), (scales, F32),
            ((L,), U32), ((L,), U32), ((L,), F32), ((), F32), ((), F32)]


# The benchmark cells' widest and most distinct buckets (bf16 g/p, so the
# one-pass schedule), rows cut to four row tiles: Qwen2.5-3B's gate/up
# (11008), down (2048) and k/v (256) stacks, Mistral-7B's gate/up (14336)
# and down (4096).
# Qwen3-30B-A3B's expert gate/up stacks (768 wide, four-dimensional
# leaves merged into rows).
CELLS = [(2, 128, 11008), (1, 256, 2048), (2, 128, 14336), (1, 128, 4096),
         (2, 2048, 256), (2, 128, 768)]
# One llama-1b MLP stripe as a leaf of its own: 5461 rows, a partial last
# row tile.
CORE = (1, 5461, 2048)


# The fused launch's in-place outputs: p, m, v alias the g, p, m, v
# operands after the scalar prefetch, the norms and the scalars.
FUSED_ALIASES = {(0, 4), (1, 5), (2, 6)}


def _aliases(hlo: str):
    """The ``(output, operand)`` aliases of each Mosaic call in ``hlo``."""
    out = []
    for line in hlo.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            seg = line.partition("output_to_operand_aliasing=")[2]
            out.append({(int(o), int(a)) for o, a in re.findall(
                r"\{(\d+)\}: \((\d+), \{\}\)", seg)})
    return out


@pytest.mark.parametrize("shape", [ATTN, MLP] + CELLS + [CORE],
                         ids=["attn", "mlp", "qwen-gate_up", "qwen-down",
                              "mistral-gate_up", "mistral-down", "qwen-kv",
                              "qwen3moe-gate_up", "core-5461"])
def test_fused_f32_compiles(one_chip, monkeypatch, shape):
    """With the limiter's norm pass on its pinned windows (the p input and
    the p, m, v outputs on block 0 through phase 0, tile i in phase 1),
    and p, m, v updated in place (outputs 0-2 alias operands 4-6)."""
    specs = []
    real = kg._fused_specs
    monkeypatch.setattr(kg, "_fused_specs",
                        lambda *a: specs.append(real(*a)) or specs[-1])
    fn = functools.partial(kg.gwt_adam_tile_fused, level=LEVEL, gamma=1.01,
                           use_limiter=True, weight_decay=False)
    hlo = _compile(fn, one_chip, *_fused_args(*shape))
    ((ins, outs),) = specs
    leaf0 = jnp.zeros((1,), jnp.int32)
    for spec in [ins[1]] + outs:
        assert int(spec.index_map(0, 0, 3, leaf0)[1]) == 0
        assert int(spec.index_map(0, 1, 3, leaf0)[1]) == 3
    assert _aliases(hlo) == [FUSED_ALIASES]


def _big_ops(comp: str, limit: int = 4096):
    """The instructions of one HLO computation's text, other than
    parameters, bitcasts and tuple plumbing, whose array result has more
    than ``limit`` elements (the kernel launches return tuples)."""
    out = []
    for line in comp.splitlines()[1:]:
        m = re.match(r"\s*(?:ROOT )?%?\S+ = (\w+)\[([\d,]*)\]\S* ([\w\-]+)\(",
                     line)
        if not m:
            continue
        dtype, dims, op = m.groups()
        size = 1
        for d in filter(None, dims.split(",")):
            size *= int(d)
        if size > limit and op not in ("parameter", "bitcast",
                                       "get-tuple-element"):
            out.append(f"{op} {dtype}[{dims}]")
    return out


@pytest.mark.parametrize("leaf", [(9, 2048, 11008), (6, 32, 2048, 768)],
                         ids=["qwen-gate_up", "qwen3moe-gate_up"])
def test_fused_update_takes_leaves_in_place(one_chip, leaf):
    """The optimizer's fused update of a two-leaf bucket at a cell's size
    (Qwen2.5-3B's gate/up layer stacks, Qwen3-30B-A3B's expert stacks),
    run in a loop that carries the parameters and moments as the train
    superstep does, compiles to one launch per leaf on the carried and
    the gradients' own buffers, each updating p, m and v in place:
    nothing in the loop stacks, slices, copies or transposes a leaf or
    the moments."""
    from repro.kernels.gwt_adam import ops as gops
    L = 2
    a_shape = (L,) + leaf[:-1] + (leaf[-1] >> LEVEL,)
    sds = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one_chip)
    args = ([sds(leaf, BF16)] * L, [sds(leaf, BF16)] * L,
            {"m": sds(a_shape, F32), "v": sds(a_shape, F32)},
            sds((L,), F32))

    def steps(gs, ps, st, pn):
        def step(i, carry):
            ps, st, pn = carry
            ps, pn, st = gops.fused_write_update(
                gs, ps, st, i, pn, lr_t=jnp.float32(1e-3), alpha=0.25,
                weight_decay=0.0, gamma=1.01, use_limiter=True,
                level=LEVEL, impl="pallas")
            return ps, st, pn
        return jax.lax.fori_loop(0, 3, step, (ps, st, pn))
    hlo = jax.jit(steps, donate_argnums=(1, 2)).lower(*args).compile() \
        .as_text()
    (body,) = re.findall(r" while\(.*?body=%?([\w.\-]+)", hlo)
    comp = hlo[hlo.index(f"\n%{body} ") + 1:]
    comp = comp[:comp.index("\n}")]
    assert comp.count('custom_call_target="tpu_custom_call"') == L
    assert _aliases(comp) == [FUSED_ALIASES] * L
    assert _big_ops(comp) == []


@pytest.mark.parametrize("shape,use_limiter", [
    (ATTN, True), (MLP, True), (MLP_DOWN, False), (ATTN, False)],
    ids=["attn-limiter", "mlp-limiter", "mlp_down-nolimiter",
         "attn-nolimiter"])
def test_fused_q8_compiles(one_chip, shape, use_limiter):
    fn = functools.partial(kg.gwt_adam_tile_fused_q8, level=LEVEL,
                           block=BLOCK, gamma=1.01, use_limiter=use_limiter,
                           weight_decay=True)
    _compile(fn, one_chip, *_fused_q8_args(*shape))


@pytest.mark.parametrize("m,n", [(24 * 5461, 2048), (2048, 32000)],
                         ids=["mlp_rows", "lm_head"])
def test_dwt_fwd_compiles(one_chip, m, n):
    _compile(functools.partial(kf.haar_dwt_fwd, level=LEVEL), one_chip,
             ((m, n), F32))


@pytest.mark.parametrize("detail", [BF16, jnp.float8_e4m3fn],
                         ids=["bf16", "f8"])
def test_dwt_wire_compiles(one_chip, detail):
    """The compressed DP wire: f32 approximation band, narrow details, on
    a layer stack flattened to rows."""
    _compile(functools.partial(kf.haar_dwt_fwd_q, level=LEVEL,
                               detail_dtype=detail),
             one_chip, ((24 * 2048, 2048), F32))


def test_dwt_inv_compiles(one_chip):
    m, n = 5461, 2048
    bands = [((m, n >> LEVEL), F32)] + [((m, n >> k), F32)
                                       for k in range(LEVEL, 0, -1)]
    _compile(lambda a, *d: kf.haar_dwt_inv(a, d), one_chip, *bands)


@pytest.mark.parametrize("k,n", [(2048, 768), (768, 2048)],
                         ids=["gate_up", "down"])
def test_grouped_matmul_compiles(one_chip, monkeypatch, k, n):
    """The expert layer's grouped matmuls (Megablox ``gmm``, and ``tgmm``
    in the backward) at Qwen3-30B-A3B's widths: 32 held experts, rows cut
    to 4096 of the cell's 65,536 (the grid's extent is read at run time)."""
    from repro.models import moe
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "pallas")

    def fwd_bwd(x, w, sizes):
        y, vjp = jax.vjp(lambda x, w: moe.grouped_matmul(x, w, sizes), x, w)
        return y, vjp(y)

    hlo = _compile(fwd_bwd, one_chip, ((4096, k), BF16), ((32, k, n), BF16),
                   ((33,), jnp.int32))
    calls = re.findall(r'op_name="[^"]*jit\((t?gmm)\)[^"]*/pallas_call"',
                       hlo)
    assert sorted(calls) == ["gmm", "gmm", "tgmm"]
