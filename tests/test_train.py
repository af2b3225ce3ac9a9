"""Training substrate tests: learning, QAT, compression, 8-bit Adam,
microbatching, checkpoint/restart, preemption recovery."""

import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import RunConfig
from repro.data import TokenStream
from repro.optim import OptConfig, adamw_init, adamw_update
from repro.train.loop import build_train_step, init_state, train_loop

CFG = get_config("qwen2-0.5b").reduced()


def _run(steps=40, **kw):
    run = RunConfig(arch="t", steps=steps, lr=3e-3, warmup_steps=5,
                    checkpoint_every=0, **kw)
    data = TokenStream(vocab=CFG.vocab, seq_len=64, global_batch=8)
    state = init_state(jax.random.PRNGKey(0), CFG, run)
    step = build_train_step(CFG, run)
    losses = []
    for _ in range(steps):
        state, m = step(state, data.next_batch())
        losses.append(float(m["loss"]))
    return losses, state


def test_loss_decreases_plain():
    losses, _ = _run()
    assert losses[-1] < losses[0] - 0.5


def test_loss_decreases_with_all_paper_features():
    losses, _ = _run(qat=True, precision_policy="mixed",
                     opt_state_dtype="posit8", grad_compression="posit8",
                     microbatch=2)
    assert losses[-1] < losses[0] - 0.5
    assert np.isfinite(losses).all()


def test_qat_quantizes_forward():
    """With a uniform fp4 policy, effective weights lie on the fp4 grid."""
    from repro.core.policy import PrecisionPolicy
    from repro.core.qat import quantize_tree
    from repro.core import formats as F
    params = {"blk": {"w": jnp.asarray(
        np.random.default_rng(0).normal(size=(32, 32)).astype(np.float32))}}
    q = quantize_tree(params, PrecisionPolicy.uniform("fp4"))
    w = np.asarray(q["blk"]["w"])
    scale = np.asarray(jnp.exp2(jnp.ceil(jnp.log2(
        jnp.max(jnp.abs(params["blk"]["w"])) / 6.0))))
    grid = F.code_values(F.FP4)
    grid = np.unique(grid[np.isfinite(grid)]) * scale
    dist = np.min(np.abs(w[..., None] - grid[None, None]), -1)
    assert np.max(dist) < 1e-6


def test_adamw_8bit_tracks_fp32():
    """8-bit moments keep the update *direction* (cosine) and magnitude
    envelope of fp32 Adam; elementwise equality is not expected at 2
    significant digits (convergence equivalence is asserted end-to-end by
    test_loss_decreases_with_all_paper_features)."""
    rng = np.random.default_rng(0)
    params = {"w": jnp.asarray(rng.normal(size=(64, 64)).astype(np.float32))}
    g = {"w": jnp.asarray(rng.normal(size=(64, 64)).astype(np.float32) * .1)}
    out = {}
    for dt in ("float32", "posit8"):
        cfg = OptConfig(moment_dtype=dt, weight_decay=0.0)
        st = adamw_init(params, cfg)
        p = params
        for _ in range(20):
            p, st = adamw_update(p, g, st, 1e-3, cfg)
        out[dt] = np.asarray(p["w"]) - np.asarray(params["w"])
    a, b = out["float32"].ravel(), out["posit8"].ravel()
    cos = float(a @ b / (np.linalg.norm(a) * np.linalg.norm(b)))
    assert cos > 0.95, cos
    assert 0.5 < np.linalg.norm(b) / np.linalg.norm(a) < 2.0


def test_grad_compression_error_feedback_converges():
    """Error feedback makes the compressed-gradient average unbiased:
    accumulated residuals stay bounded."""
    from repro.parallel import collectives
    rng = np.random.default_rng(0)
    g = {"w": jnp.asarray(rng.normal(size=(128,)).astype(np.float32))}
    res = jax.tree.map(jnp.zeros_like, g)
    total_q = jnp.zeros((128,))
    for i in range(50):
        gq, res = collectives.error_feedback_update(g, res)
        total_q = total_q + gq["w"]
    # mean of quantized grads ~= true grad (residual bounded, not growing:
    # it stays within one quantization step of the po2 block scale)
    err = np.abs(np.asarray(total_q) / 50 - np.asarray(g["w"])).max()
    assert err < 0.02, err
    assert float(jnp.max(jnp.abs(res["w"]))) < 0.5


def test_microbatch_equals_full_batch_grads():
    run1 = RunConfig(arch="t", steps=1, lr=0.0, warmup_steps=0,
                     grad_clip=0.0, checkpoint_every=0)
    run2 = RunConfig(arch="t", steps=1, lr=0.0, warmup_steps=0,
                     grad_clip=0.0, checkpoint_every=0, microbatch=4)
    data = TokenStream(vocab=CFG.vocab, seq_len=32, global_batch=8)
    batch = data.next_batch()
    s1 = init_state(jax.random.PRNGKey(0), CFG, run1)
    s2 = init_state(jax.random.PRNGKey(0), CFG, run2)
    _, m1 = build_train_step(CFG, run1)(s1, batch)
    _, m2 = build_train_step(CFG, run2)(s2, batch)
    assert abs(float(m1["ce"]) - float(m2["ce"])) < 1e-3


def test_train_loop_checkpoint_resume(tmp_path):
    ck = str(tmp_path / "ck")
    run = RunConfig(arch="t", steps=20, lr=1e-3, warmup_steps=2,
                    checkpoint_every=10, checkpoint_dir=ck)
    data = TokenStream(vocab=CFG.vocab, seq_len=32, global_batch=4)
    state, _ = train_loop(CFG, run, data, log_every=100)
    assert int(state.step) == 20
    # continue to 30 from the persisted checkpoint; data state restored
    run2 = RunConfig(**{**run.__dict__, "steps": 30})
    data2 = TokenStream(vocab=CFG.vocab, seq_len=32, global_batch=4)
    state2, _ = train_loop(CFG, run2, data2, log_every=100)
    assert int(state2.step) == 30
    assert data2.step >= 20  # iterator state resumed, not restarted


def test_train_loop_preemption_recovery(tmp_path):
    """A step that raises mid-run is retried from the last checkpoint."""
    ck = str(tmp_path / "ck")
    run = RunConfig(arch="t", steps=16, lr=1e-3, warmup_steps=2,
                    checkpoint_every=5, checkpoint_dir=ck)
    data = TokenStream(vocab=CFG.vocab, seq_len=32, global_batch=4)
    boom = {"armed": True}

    class FlakyStream(TokenStream):
        def next_batch(self):
            if self.step == 8 and boom["armed"]:
                boom["armed"] = False
                raise RuntimeError("simulated preemption")
            return super().next_batch()

    flaky = FlakyStream(vocab=CFG.vocab, seq_len=32, global_batch=4)
    try:
        state, _ = train_loop(CFG, run, flaky, log_every=100)
    except RuntimeError:
        # raised outside the step; loop restarts fresh -> second call resumes
        state, _ = train_loop(CFG, run, flaky, log_every=100)
    assert int(state.step) == 16


def test_train_loop_reraises_compile_errors(tmp_path, monkeypatch):
    """A step that cannot be lowered is a fault, not a preemption: it
    raises at once even with a checkpoint to restore from."""
    import repro.train.loop as loop
    ck = str(tmp_path / "ck")
    run = RunConfig(arch="t", steps=2, lr=1e-3, warmup_steps=0,
                    checkpoint_every=2, checkpoint_dir=ck)
    train_loop(CFG, run, TokenStream(vocab=CFG.vocab, seq_len=32,
                                     global_batch=4), log_every=100)

    def broken_step(*_a, **_k):
        def step(state, batch):
            raise ValueError("cannot lower")
        return jax.jit(step)

    pulls = {"n": 0}

    class Bounded(TokenStream):
        # a loop that swallowed the error would restore (rewinding the
        # stream's own state) and retry forever; stop it from outside
        # the retry instead
        def next_batch(self):
            pulls["n"] += 1
            if pulls["n"] > 3:
                raise RuntimeError("the failed step was retried")
            return super().next_batch()

    monkeypatch.setattr(loop, "build_train_step", broken_step)
    run2 = RunConfig(**{**run.__dict__, "steps": 6})
    with pytest.raises(ValueError, match="cannot lower"):
        train_loop(CFG, run2, Bounded(vocab=CFG.vocab, seq_len=32,
                                      global_batch=4), log_every=100)
