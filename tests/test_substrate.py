"""Substrate: optimizer, schedules, compression, checkpointing,
trainer resume, telemetry monitor, straggler detection, sharding rules."""
import json
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")  # optional test dep; skip cleanly if absent
from hypothesis import given, settings, strategies as st

from repro.optim import (AdamWConfig, adamw_init, adamw_update,
                         apply_updates, clip_by_global_norm,
                         cosine_warmup, dequantize_int8, global_norm,
                         quantize_int8)


# ----------------------------------------------------------------------
# optimizer
# ----------------------------------------------------------------------
def test_adamw_converges_quadratic():
    """AdamW must minimize a convex quadratic."""
    cfg = AdamWConfig(lr=0.1, weight_decay=0.0)
    params = {"w": jnp.array([5.0, -3.0])}
    state = adamw_init(params)
    target = jnp.array([1.0, 2.0])
    for step in range(200):
        g = {"w": 2 * (params["w"] - target)}
        upd, state = adamw_update(g, state, params, 0.1, cfg)
        params = apply_updates(params, upd)
    assert float(jnp.abs(params["w"] - target).max()) < 1e-2


def test_adamw_weight_decay_only_on_matrices():
    cfg = AdamWConfig(lr=0.1, weight_decay=1.0)
    params = {"m": jnp.ones((2, 2)), "b": jnp.ones((2,))}
    state = adamw_init(params)
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    upd, _ = adamw_update(zeros, state, params, 0.1, cfg)
    assert float(jnp.abs(upd["m"]).max()) > 0      # decayed
    assert float(jnp.abs(upd["b"]).max()) == 0     # not decayed


def test_cosine_warmup_shape():
    lr0 = float(cosine_warmup(0, peak_lr=1.0, warmup_steps=10,
                              total_steps=100))
    lr10 = float(cosine_warmup(10, peak_lr=1.0, warmup_steps=10,
                               total_steps=100))
    lr100 = float(cosine_warmup(100, peak_lr=1.0, warmup_steps=10,
                                total_steps=100))
    assert lr0 == 0.0 and abs(lr10 - 1.0) < 1e-6
    assert abs(lr100 - 0.1) < 1e-6                 # min_ratio floor


def test_clip_by_global_norm():
    tree = {"a": jnp.full((10,), 3.0)}
    clipped, norm = clip_by_global_norm(tree, 1.0)
    assert float(global_norm(clipped)) == pytest.approx(1.0, rel=1e-5)
    assert float(norm) == pytest.approx(np.sqrt(90.0), rel=1e-5)


# ----------------------------------------------------------------------
# int8 compression
# ----------------------------------------------------------------------
@settings(max_examples=30, deadline=None)
@given(seed=st.integers(0, 1000), scale=st.floats(1e-3, 1e3))
def test_quantize_roundtrip_error_bounded(seed, scale):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(scale * rng.normal(size=64), jnp.float32)
    q, s = quantize_int8(x)
    err = jnp.abs(dequantize_int8(q, s) - x)
    assert float(err.max()) <= float(s) * 0.5 + 1e-6


def test_compressed_psum_error_feedback():
    """Error feedback makes the *accumulated* compressed sum track the
    true sum even though each step quantizes (8 devices, subprocess)."""
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np, json
from jax.sharding import Mesh, PartitionSpec as P
from repro.optim.compress import compressed_psum

mesh = Mesh(np.array(jax.devices()), ("d",))
G = np.random.default_rng(0).normal(size=(8, 256)).astype(np.float32)

def body(g):
    red, err = compressed_psum({"g": g}, "d")
    return red["g"], err["g"]

f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("d"),
                          out_specs=(P("d"), P("d"))))
red, err = f(G.reshape(-1))
red = np.asarray(red).reshape(8, 256)
true_mean = G.mean(axis=0)
rel = float(np.abs(red[0] - true_mean).max() / np.abs(true_mean).max())
print(json.dumps({"rel": rel}))
"""
    p = subprocess.run([sys.executable, "-c", script],
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    rel = json.loads(p.stdout.strip().splitlines()[-1])["rel"]
    assert rel < 0.02                                # int8-accurate mean


# ----------------------------------------------------------------------
# checkpointing
# ----------------------------------------------------------------------
def test_checkpoint_roundtrip(tmp_path):
    from repro.checkpoint import restore_checkpoint, save_checkpoint
    tree = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "b": {"c": jnp.ones((4,), jnp.bfloat16)}}
    save_checkpoint(tmp_path, 7, tree)
    out, step = restore_checkpoint(tmp_path, tree)
    assert step == 7
    assert np.allclose(np.asarray(out["a"], np.float32),
                       np.asarray(tree["a"]))
    assert out["b"]["c"].dtype == jnp.bfloat16


def test_checkpoint_corruption_self_heals(tmp_path):
    from repro.checkpoint import latest_step, save_checkpoint
    tree = {"a": jnp.ones((3,))}
    save_checkpoint(tmp_path, 10, tree)
    save_checkpoint(tmp_path, 20, tree)
    # corrupt the newest
    (tmp_path / "step_00000020" / "arrays.npz").write_bytes(b"garbage")
    assert latest_step(tmp_path) == 10


def test_checkpoint_manager_gc(tmp_path):
    from repro.checkpoint import CheckpointManager
    m = CheckpointManager(tmp_path, every=1, keep=2)
    for s in range(1, 6):
        m.maybe_save(s, {"a": jnp.ones(2) * s})
    steps = sorted(int(p.name.split("_")[1])
                   for p in tmp_path.glob("step_*"))
    assert steps == [4, 5]


def test_trainer_resume_after_kill(tmp_path):
    from repro.configs import get_smoke_config
    from repro.data import synthetic_token_batches
    from repro.train.trainer import Trainer, TrainerConfig
    cfg = get_smoke_config("internlm2-1.8b")
    mk = lambda total: Trainer(cfg, TrainerConfig(
        total_steps=total, warmup=2, ckpt_every=5,
        ckpt_dir=str(tmp_path), log_every=100))
    batches = synthetic_token_batches(vocab_size=cfg.vocab_size,
                                      batch=2, seq_len=16)
    st = mk(10).run(batches)
    assert st.step == 10
    st2 = mk(15).init_or_restore()
    assert st2.step == 10                            # resumed, not reset
    st2 = mk(15).run(batches, st2)
    assert st2.step == 15


# ----------------------------------------------------------------------
# telemetry: the paper's technique inside the trainer
# ----------------------------------------------------------------------
def test_monitor_flags_loss_spike():
    from repro.telemetry import DiscordMonitor, MetricBuffer
    rng = np.random.default_rng(0)
    buf = MetricBuffer()
    for i in range(600):
        v = 2.0 + 0.01 * rng.normal()
        if 400 <= i < 416:
            v += 1.5                                 # injected spike
        buf.log(i, {"loss": v})
    rep = DiscordMonitor(buf, window=16, k=2).scan_metric("loss")
    assert rep is not None and rep.any_flagged
    assert any(380 <= p <= 430 for p in rep.flagged)


def test_monitor_quiet_on_clean_series():
    from repro.telemetry import DiscordMonitor, MetricBuffer
    rng = np.random.default_rng(1)
    buf = MetricBuffer()
    for i in range(600):
        buf.log(i, {"loss": 2.0 + 0.01 * rng.normal()})
    rep = DiscordMonitor(buf, window=16, k=2, z=6.0).scan_metric("loss")
    assert rep is not None and not rep.any_flagged


def test_straggler_detector():
    from repro.telemetry import StragglerDetector
    det = StragglerDetector(n_hosts=8, ratio=1.4, patience=2)
    rng = np.random.default_rng(0)
    for step in range(80):
        t = 1.0 + 0.02 * rng.normal(size=8)
        if step >= 60:
            t[3] *= 2.2                              # host 3 goes bad
        det.log_step(step, t)
        d = det.decide()
    assert 3 in d["evict"], d
    assert all(h == 3 for h in d["evict"])


# ----------------------------------------------------------------------
# sharding rules (AbstractMesh — no devices needed)
# ----------------------------------------------------------------------
def test_param_specs_divide_everywhere():
    from jax.sharding import AbstractMesh
    from repro.configs import get_config, list_archs
    from repro.models import init_params
    from repro.parallel import param_specs

    mesh = AbstractMesh((16, 16), ("data", "model"))
    for arch in list_archs():
        cfg = get_config(arch)
        abs_params = jax.eval_shape(
            lambda k, c=cfg: init_params(k, c), jax.random.PRNGKey(0))
        specs = param_specs(abs_params, cfg, mesh)

        def check(leaf, spec):
            for dim, ax in zip(leaf.shape, spec):
                if ax is None:
                    continue
                size = (np.prod([mesh.shape[a] for a in ax])
                        if isinstance(ax, tuple) else mesh.shape[ax])
                assert dim % size == 0, (arch, leaf.shape, spec)
        jax.tree_util.tree_map(check, abs_params, specs,
                               is_leaf=lambda x: hasattr(x, "shape"))


def test_fit_spec_drops_indivisible():
    from jax.sharding import AbstractMesh, PartitionSpec as P
    from repro.parallel import fit_spec
    mesh = AbstractMesh((16, 16), ("data", "model"))
    spec = fit_spec(P("data", "model"), (20, 32), mesh)
    assert spec == P(None, "model")                  # 20 % 16 != 0
