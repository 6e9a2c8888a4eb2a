"""Training substrate tests: loss goes down, microbatch invariance,
gradient-compression sanity."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_smoke_config
from repro.data import TokenPipeline
from repro.models import init_params
from repro.training import build_train_step, init_train_state

jax.config.update("jax_platform_name", "cpu")


def _setup(microbatches=1, steps=40, family_arch="smollm-135m"):
    cfg = dataclasses.replace(get_smoke_config(family_arch), dtype="float32")
    params, _ = init_params(jax.random.PRNGKey(0), cfg)
    state = init_train_state(params)
    step = jax.jit(build_train_step(
        cfg, microbatches=microbatches, base_lr=1e-2, warmup=5,
        total_steps=steps, remat="none"))
    pipe = TokenPipeline(vocab_size=cfg.vocab_size, seq_len=32,
                         global_batch=8, seed=7)
    return cfg, state, step, pipe


def test_loss_decreases():
    _, state, step, pipe = _setup(steps=30)
    losses = []
    for i in range(30):
        state, metrics = step(state, pipe.jax_batch(i % 4))  # cycle 4 batches
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0] * 0.8, losses[::6]
    assert np.isfinite(losses).all()


def test_microbatch_invariance():
    """Grad accumulation must not change the training trajectory."""
    _, s1, step1, pipe = _setup(microbatches=1)
    _, s4, step4, _ = _setup(microbatches=4)
    b = pipe.jax_batch(0)
    s1, m1 = step1(s1, b)
    s4, m4 = step4(s4, b)
    np.testing.assert_allclose(float(m1["loss"]), float(m4["loss"]),
                               rtol=1e-5)
    d = jax.tree.map(lambda a, b_: float(jnp.max(jnp.abs(a - b_))),
                     s1.params, s4.params)
    assert max(jax.tree.leaves(d)) < 1e-4, sorted(
        jax.tree.leaves(d))[-3:]


def test_moe_train_smoke():
    cfg = dataclasses.replace(get_smoke_config("mixtral-8x22b"),
                              dtype="float32")
    params, _ = init_params(jax.random.PRNGKey(1), cfg)
    state = init_train_state(params)
    step = jax.jit(build_train_step(cfg, microbatches=2, base_lr=5e-3,
                                    warmup=2, total_steps=20, remat="full"))
    pipe = TokenPipeline(vocab_size=cfg.vocab_size, seq_len=16,
                         global_batch=4, seed=3)
    losses = []
    for i in range(12):
        state, metrics = step(state, pipe.jax_batch(i % 2))
        losses.append(float(metrics["loss"]))
        assert float(metrics["dropped_frac"]) <= 1.0
    assert losses[-1] < losses[0]


def test_grad_compression_preserves_convergence():
    from repro.distributed.compression import ef_int8_roundtrip
    # int8 EF roundtrip error must be < 1% of tensor scale
    g = jnp.asarray(np.random.default_rng(0).normal(size=(256,)), jnp.float32)
    r = ef_int8_roundtrip(g)
    rel = float(jnp.max(jnp.abs(g - r)) / jnp.max(jnp.abs(g)))
    assert rel < 1 / 127 + 1e-6
    # and training still converges with compression on
    cfg = dataclasses.replace(get_smoke_config("smollm-135m"),
                              dtype="float32")
    params, _ = init_params(jax.random.PRNGKey(0), cfg)
    state = init_train_state(params)
    step = jax.jit(build_train_step(cfg, microbatches=1, base_lr=1e-2,
                                    warmup=5, total_steps=30, remat="none",
                                    compress_grads=True))
    pipe = TokenPipeline(vocab_size=cfg.vocab_size, seq_len=32,
                         global_batch=8, seed=7)
    losses = []
    for i in range(25):
        state, metrics = step(state, pipe.jax_batch(i % 4))
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0] * 0.85


def test_compressed_psum_matches_psum_within_quant_error():
    from repro.distributed.compression import CompressedPsum
    mesh = jax.make_mesh((1,), ("pod",))

    grads = {"w": jnp.asarray(
        np.random.default_rng(1).normal(size=(64,)), jnp.float32)}
    res = CompressedPsum.init_state(grads)

    def f(g, r):
        return CompressedPsum.psum(g, r, "pod")

    out, new_res = jax.jit(jax.shard_map(
        f, mesh=mesh,
        in_specs=(jax.sharding.PartitionSpec(),) * 2,
        out_specs=jax.sharding.PartitionSpec()))(grads, res)
    np.testing.assert_allclose(np.asarray(out["w"]),
                               np.asarray(grads["w"]), atol=2e-2)
    # residual bookkeeping: g ≈ sent + residual
    np.testing.assert_allclose(
        np.asarray(out["w"] + new_res["w"]), np.asarray(grads["w"]),
        atol=1e-6)
