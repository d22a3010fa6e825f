"""Sharded multi-device train path (DESIGN.md §3, wired): simulated-mesh
equivalence tiers in subprocess isolation.

* **Topology equivalence (exact reduce)** — a 1-device run (`--mesh 1
  --accum 8`) and an 8-device run (`--mesh 8`) of the SAME logical shard
  grid produce byte-identical final checkpoints: per-shard grads are
  bitwise reproducible across batch sizes (row-independent forward math),
  the accumulation scan sums shards sequentially, and the CPU backend's
  ``psum`` reduces in device order — the same order.  Donation must be
  off for THIS tier only: ``donate_argnums`` changes XLA fusion (and
  hence float rounding) differently per topology.
* **Compressed reduce** — same trajectory within the detail-band
  quantization tolerance, under the full production config (donation,
  FSDP param/state sharding, wavelet-compressed wire).
* **Preempt/resume on a mesh** — SIGTERM → checkpoint → ``--resume`` is
  bitwise against the uninterrupted run with sharding + donation +
  compression all on (same-topology donation IS deterministic).
* **Cross-topology resume** — a checkpoint saved by the 1-device run
  continues on the 8-device mesh (and vice versa) bit-for-bit.
* **psum ≡ emulated sequential sum** — anchors the in-process property
  tests (tests/test_distributed.py) that drive
  ``compression.emulated_mean`` instead of a real mesh.
"""

import os
import re
import shutil
import signal
import subprocess
import sys
import time

import pytest

from conftest import device_env, run_in_devices

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

BASE = ["--arch", "llama-60m", "--smoke", "--optimizer", "gwt", "--level",
        "2", "--lr", "0.01", "--steps", "24", "--batch", "16", "--seq",
        "32", "--log-every", "4", "--ckpt-every", "8"]
EXACT_1DEV = ["--mesh", "1", "--accum", "8", "--dp-reduce", "exact",
              "--shard-params", "none", "--no-donate"]
EXACT_8DEV = ["--mesh", "8", "--dp-reduce", "exact",
              "--shard-params", "none", "--no-donate"]
# full production surface: donated, FSDP-sharded state, compressed wire
PROD_8DEV = ["--mesh", "8", "--dp-reduce", "compressed", "--dp-level", "2",
             "--shard-params", "auto"]


def _launch(ckpt_dir, n_devices, extra=(), wait=True, timeout=600):
    cmd = [sys.executable, "-m", "repro.launch.train", *BASE,
           "--ckpt-dir", str(ckpt_dir), *extra]
    proc = subprocess.Popen(cmd, cwd=REPO, env=device_env(n_devices),
                            stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True)
    if not wait:
        return proc
    out, err = proc.communicate(timeout=timeout)
    assert proc.returncode == 0, out + err
    return out + err


def _blobs(ckpt_dir, step=24):
    """{filename: bytes} of every leaf in the committed checkpoint."""
    d = os.path.join(str(ckpt_dir), f"step_{step:09d}")
    assert os.path.exists(os.path.join(d, "COMMITTED")), \
        os.listdir(str(ckpt_dir))
    out = {}
    for name in sorted(os.listdir(d)):
        if name.endswith(".bin"):
            with open(os.path.join(d, name), "rb") as f:
                out[name] = f.read()
    return out


def _losses(log: str):
    return [float(m) for m in re.findall(r"step \d+: loss=([\d.]+)", log)]


def _assert_blobs_equal(a, b, tag):
    assert a.keys() == b.keys()
    diff = [n for n in a if a[n] != b[n]]
    assert not diff, f"{tag}: {len(diff)} leaves differ: {diff[:6]}"


@pytest.fixture(scope="module")
def topo(tmp_path_factory):
    """The three shared launcher runs: 1-dev exact, 8-dev exact (same
    logical shard grid), 8-dev production (donated FSDP compressed)."""
    root = tmp_path_factory.mktemp("sharded")
    dirs = {"one": root / "one", "eight": root / "eight",
            "prod": root / "prod"}
    logs = {"one": _launch(dirs["one"], 1, EXACT_1DEV),
            "eight": _launch(dirs["eight"], 8, EXACT_8DEV),
            "prod": _launch(dirs["prod"], 8, PROD_8DEV)}
    return {"dirs": dirs, "logs": logs}


# ---------------------------------------------------------------------------
# Tier 1: topology equivalence
# ---------------------------------------------------------------------------

def test_exact_reduce_topology_bitwise(topo):
    """8-device exact-reduce ≡ 1-device, bitwise, through params AND
    optimizer state: the logical shard grid (16 rows → 8 contiguous
    shards) is what defines the numerics, not the device count."""
    _assert_blobs_equal(_blobs(topo["dirs"]["one"]),
                        _blobs(topo["dirs"]["eight"]), "1dev vs 8dev")


def test_exact_reduce_loss_streams_identical(topo):
    l1, l8 = _losses(topo["logs"]["one"]), _losses(topo["logs"]["eight"])
    assert len(l1) == len(l8) == 6          # 24 steps / log_every 4
    assert l1 == l8                          # printed at 4 decimals


def test_mesh_wire_accounting_logged(topo):
    """The launcher reports the per-step DP wire bytes; the compressed
    production run must claim a real saving over exact f32."""
    m = re.search(r"dp_reduce=compressed dp=8 wire=([\d.]+)MiB/step vs "
                  r"exact ([\d.]+)MiB \(([\d.]+)x\)", topo["logs"]["prod"])
    assert m, topo["logs"]["prod"]
    assert float(m.group(3)) > 1.3           # bf16 smoke model ratio


# ---------------------------------------------------------------------------
# Tier 2: compressed reduction — bounded deviation
# ---------------------------------------------------------------------------

def test_compressed_reduce_loss_within_tolerance(topo):
    """The production run (compressed wire, FSDP, donation) tracks the
    exact-reduce trajectory within the documented band: bf16 detail
    quantization perturbs each step ~1e-3 relative, compounding to a few
    percent over 24 GWT steps on the smoke config."""
    exact = _losses(topo["logs"]["eight"])
    comp = _losses(topo["logs"]["prod"])
    assert len(exact) == len(comp) == 6
    for i, (e, c) in enumerate(zip(exact, comp)):
        assert abs(e - c) / e < 0.10, (i, e, c)


# ---------------------------------------------------------------------------
# Tier 3: preempt → resume on a mesh, full production config
# ---------------------------------------------------------------------------

def test_mesh_sigterm_resume_bitwise(topo, tmp_path):
    """SIGTERM a donated+sharded+compressed 8-device run mid-training,
    --resume, and require the final checkpoint byte-identical to the
    uninterrupted production run: the absolute chunk grid and the
    restored per-bucket state survive sharding."""
    d = tmp_path / "interrupted"
    proc = _launch(d, 8, PROD_8DEV, wait=False)
    first_ckpt = os.path.join(str(d), "step_000000008", "COMMITTED")
    deadline = time.time() + 570
    while time.time() < deadline and proc.poll() is None \
            and not os.path.exists(first_ckpt):
        time.sleep(0.05)
    if proc.poll() is None:
        proc.send_signal(signal.SIGTERM)
    out, err = proc.communicate(timeout=120)
    assert proc.returncode == 0, out + err

    finished = os.path.exists(
        os.path.join(str(d), "step_000000024", "COMMITTED"))
    log = _launch(d, 8, [*PROD_8DEV, "--resume"])
    if not finished:
        assert "resumed from step" in log, log
    _assert_blobs_equal(_blobs(d), _blobs(topo["dirs"]["prod"]),
                        "mesh sigterm+resume")


# ---------------------------------------------------------------------------
# Tier 4: cross-topology checkpoint restore (satellite)
# ---------------------------------------------------------------------------

def _resume_from(src_dir, dst, drop_step=24):
    shutil.copytree(str(src_dir), str(dst))
    shutil.rmtree(os.path.join(str(dst), f"step_{drop_step:09d}"))


def test_checkpoint_saved_1dev_resumes_on_mesh_bitwise(topo, tmp_path):
    """Save on 1 device, --resume on the 8-device mesh: path-keyed bucket
    state restores under the mesh NamedShardings without migration, and —
    because the logical shard grid is topology-free — the continued run
    lands byte-identical to the straight 8-device run."""
    d = tmp_path / "to8"
    _resume_from(topo["dirs"]["one"], d)
    log = _launch(d, 8, [*EXACT_8DEV, "--resume"])
    assert "resumed from step 16" in log, log
    _assert_blobs_equal(_blobs(d), _blobs(topo["dirs"]["eight"]),
                        "1dev ckpt → 8dev mesh")


def test_checkpoint_saved_on_mesh_resumes_1dev_bitwise(topo, tmp_path):
    """...and the reverse: a mesh-written checkpoint continues on a single
    device bit-for-bit."""
    d = tmp_path / "to1"
    _resume_from(topo["dirs"]["eight"], d)
    log = _launch(d, 1, [*EXACT_1DEV, "--resume"])
    assert "resumed from step 16" in log, log
    _assert_blobs_equal(_blobs(d), _blobs(topo["dirs"]["one"]),
                        "8dev ckpt → 1dev")


def test_fsdp_state_restores_under_different_mesh(tmp_path):
    """FSDP-sharded optimizer state saved on an 8-way mesh restores onto a
    4-way mesh (different NamedShardings, same path-keyed buckets) with no
    migration step."""
    d = tmp_path / "fsdp"
    _launch(d, 8, [*PROD_8DEV, "--steps", "8"])
    log = _launch(d, 8, ["--mesh", "4", "--dp-reduce", "compressed",
                         "--shard-params", "auto", "--steps", "12",
                         "--resume"])
    assert "resumed from step 8" in log, log
    assert _blobs(d, step=12)


# ---------------------------------------------------------------------------
# Tier 5: donation stays single-buffered under sharding
# ---------------------------------------------------------------------------

def test_donation_single_buffered_under_sharding():
    """XLA buffer assignment of the mesh-aware step: donating
    (params, opt_state) must still alias them through when they are
    FSDP-sharded and the gradient reduction runs inside shard_map."""
    code = """
    import jax, jax.numpy as jnp
    from repro import compat, configs, optim
    from repro.models import lm
    from repro.data.pipeline import SyntheticLM
    from repro.runtime.context import MeshContext
    from repro.distributed import sharding as shr
    from repro.optim.engine import live_update_bytes

    cfg = configs.get_smoke("llama-60m")
    mesh = compat.make_mesh((8,), ("data",))
    ctx = MeshContext.create(mesh=mesh)
    data = SyntheticLM(cfg.vocab, 32, 16, seed=0)
    batch = {k: jnp.asarray(v) for k, v in data.batch(0).items()}
    batch_abs = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                 for k, v in batch.items()}
    sh = shr.train_step_shardings(cfg, lm, batch_abs, mesh,
                                  shard_params=True)
    opt = optim.make("gwt", lr=1e-2, level=2,
                     state_shardings=sh.opt["buckets"])
    params = jax.device_put(lm.init(cfg, jax.random.key(0)), sh.params)
    st = opt.init(params)
    with ctx.activate():
        plain = jax.jit(lm.make_train_step(
            cfg, opt, ctx=ctx, dp_reduce="compressed", shardings=sh)) \
            .lower(params, st, batch).compile()
        donated = lm.make_train_step(
            cfg, opt, ctx=ctx, dp_reduce="compressed", shardings=sh,
            donate=True).lower(params, st, batch).compile()
    lp, ld = live_update_bytes(plain), live_update_bytes(donated)
    assert lp is not None and ld is not None
    assert ld < lp, (ld, lp)
    ma = donated.memory_analysis()
    assert ma.alias_size_in_bytes > 0
    # the step's named scopes label the compiled ops, the reduction apart
    hlo = donated.as_text()
    for scope in ("train.fwd_bwd/", "train.dp_reduce/", "train.update/"):
        assert scope in hlo, scope
    print("DONATION_OK", lp, ld)
    """
    r = run_in_devices(8, code)
    assert "DONATION_OK" in r.stdout, r.stdout + r.stderr


def test_dp_reduce_rejects_tp_meshes():
    """Leaving a 'model' axis to GSPMD inside the manual DP region
    miscompiles on the pinned jax/XLA (hard IsManualSubgroup abort), so
    the step builder must refuse TP meshes with a real error instead."""
    from repro import compat, configs, optim
    from repro.models import lm
    from repro.runtime.context import MeshContext

    cfg = configs.get_smoke("llama-60m")
    ctx = MeshContext.create(mesh=compat.make_mesh((1, 1),
                                                   ("data", "model")))
    opt = optim.make("gwt", lr=1e-2, level=2)
    with pytest.raises(ValueError, match="pure-DP mesh"):
        lm.make_train_step(cfg, opt, ctx=ctx, dp_reduce="exact")
    with pytest.raises(ValueError, match="'data' axis"):
        lm.make_train_step(cfg, opt, ctx=MeshContext.create(),
                           dp_reduce="exact")
    # the string 'none' routes to the plain auto-sharded step, not a crash
    step = lm.make_train_step(cfg, opt, ctx=MeshContext.create(),
                              dp_reduce="none")
    assert callable(step)


# ---------------------------------------------------------------------------
# Tier 6: the reduction-order anchor for the in-process property tests
# ---------------------------------------------------------------------------

def test_psum_matches_emulated_sequential_sum():
    """``compressed_psum_mean`` on a real 8-device axis is bitwise equal
    to ``compression.emulated_mean`` (sequential worker-order sum) for
    the exact and bf16 modes — licensing the hypothesis properties in
    test_distributed.py to run meshless.  f8 payloads match within one
    detail ulp: the backend's f8 all-reduce accumulation strategy is
    buffer-size-dependent (bitwise contracts ride the exact mode only)."""
    code = """
    import functools
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro import compat
    from repro.distributed import compression

    mesh = compat.make_mesh((8,), ("data",))
    for shape, level, dtype, tag in [
            ((8, 16, 64), 2, None, "exact"),
            ((8, 16, 64), 2, jnp.bfloat16, "bf16"),
            ((8, 16, 64), 3, jnp.float8_e4m3fn, "f8"),
            ((8, 32), 2, jnp.bfloat16, "1d_divisible_compresses"),
            ((8, 33), 2, jnp.bfloat16, "fallback_1d"),
            ((8, 4, 30), 2, jnp.bfloat16, "fallback_odd")]:
        g = jax.random.normal(jax.random.key(0), shape, jnp.float32) * 2.3
        fn = compat.shard_map(
            functools.partial(compression.compressed_psum_mean,
                              axis_name="data", level=level,
                              detail_dtype=dtype),
            mesh, in_specs=P("data"), out_specs=P("data"))
        with compat.use_mesh(mesh):
            out = np.asarray(jax.jit(fn)(g))[0]
        ref = np.asarray(compression.emulated_mean(g, level, dtype))
        if tag == "f8":
            ulp = float(jnp.finfo(dtype).eps) * np.abs(ref).max()
            assert np.abs(out - ref).max() <= ulp, \\
                (tag, np.abs(out - ref).max(), ulp)
        else:
            assert np.array_equal(out, ref), (tag, np.abs(out - ref).max())
    print("PSUM_EMULATION_OK")
    """
    r = run_in_devices(8, code)
    assert "PSUM_EMULATION_OK" in r.stdout, r.stdout + r.stderr
