"""Substrate tests: optimizer, train loop, data pipeline determinism,
checkpoint/restart (preemption simulation), compressed collectives."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.data import Prefetcher, SyntheticLM, SyntheticLMConfig
from repro.models import build_model
from repro.training import adamw, compress_bf16, make_train_step, warmup_cosine
from repro import checkpoint as ckpt


@pytest.fixture(scope="module")
def tiny_setup():
    cfg = get_config("qwen1.5-4b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    # peak lr tuned for the reduced (d=64, 2-layer) model: 3e-3 learns the
    # Markov stream too slowly to clear test_loss_decreases' margin in 30
    # steps (drop 0.45); 2e-2 with a short warmup drops ~1.3 nats.
    opt = adamw(warmup_cosine(2e-2, 3, 100), weight_decay=0.01)
    opt_state = opt.init(params)
    data = SyntheticLM(SyntheticLMConfig(cfg.vocab_size, seq_len=32, global_batch=8))
    step_fn = jax.jit(make_train_step(model, opt))
    return cfg, model, params, opt, opt_state, data, step_fn


def test_loss_decreases(tiny_setup):
    """End-to-end training sanity: 30 steps on the synthetic Markov stream
    must reduce loss substantially (the stream is learnable)."""
    _, _, params, _, opt_state, data, step_fn = tiny_setup
    losses = []
    for step in range(30):
        batch = {k: jnp.asarray(v) for k, v in data.batch_at(step).items()}
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0] - 0.5, losses[:3] + losses[-3:]
    assert np.isfinite(losses).all()


def test_grad_accumulation_matches_full_batch(tiny_setup):
    cfg, model, params, opt, opt_state, data, _ = tiny_setup
    batch = {k: jnp.asarray(v) for k, v in data.batch_at(0).items()}
    s1 = jax.jit(make_train_step(model, opt))
    s4 = jax.jit(make_train_step(model, opt, microbatches=4))
    p1, _, m1 = s1(params, opt_state, batch)
    p4, _, m4 = s4(params, opt_state, batch)
    # means of per-microbatch grads == full-batch grad (loss is per-token mean
    # over equal-sized microbatches)
    d = jax.tree_util.tree_map(
        lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))),
        p1, p4)
    assert max(jax.tree_util.tree_leaves(d)) < 5e-2


def test_remat_matches(tiny_setup):
    cfg, model, params, opt, opt_state, data, _ = tiny_setup
    batch = {k: jnp.asarray(v) for k, v in data.batch_at(1).items()}
    a = jax.jit(make_train_step(model, opt))(params, opt_state, batch)[2]
    b = jax.jit(make_train_step(model, opt, remat=True))(params, opt_state, batch)[2]
    assert abs(float(a["loss"]) - float(b["loss"])) < 1e-5


def test_bf16_compression_close(tiny_setup):
    cfg, model, params, opt, opt_state, data, _ = tiny_setup
    batch = {k: jnp.asarray(v) for k, v in data.batch_at(2).items()}
    a = jax.jit(make_train_step(model, opt))(params, opt_state, batch)[0]
    b = jax.jit(make_train_step(model, opt, compress=compress_bf16))(
        params, opt_state, batch)[0]
    d = jax.tree_util.tree_map(
        lambda x, y: float(jnp.max(jnp.abs(x.astype(jnp.float32) - y.astype(jnp.float32)))),
        a, b)
    assert max(jax.tree_util.tree_leaves(d)) < 1e-2


def test_data_pipeline_determinism_and_elasticity():
    cfg = SyntheticLMConfig(vocab_size=100, seq_len=16, global_batch=8, seed=3)
    one_host = SyntheticLM(cfg, host=0, n_hosts=1)
    two_a = SyntheticLM(cfg, host=0, n_hosts=2)
    two_b = SyntheticLM(cfg, host=1, n_hosts=2)
    b1 = one_host.batch_at(7)
    assert (one_host.batch_at(7)["tokens"] == b1["tokens"]).all()  # replayable
    # different hosts generate disjoint deterministic shards of the same step
    a = two_a.batch_at(7)["tokens"]
    b = two_b.batch_at(7)["tokens"]
    assert a.shape == (4, 16) and b.shape == (4, 16)
    assert not (a == b).all()


def test_prefetcher_orders_steps():
    cfg = SyntheticLMConfig(vocab_size=50, seq_len=8, global_batch=2)
    pf = Prefetcher(SyntheticLM(cfg), start_step=5, depth=2)
    try:
        steps = [pf.next()[0] for _ in range(4)]
        assert steps == [5, 6, 7, 8]
    finally:
        pf.close()


def test_file_backed_pipeline(tmp_path):
    from repro.data import FileBackedLM

    tokens = np.arange(10_000, dtype=np.int32) % 97
    FileBackedLM.write_corpus(tmp_path, tokens, n_hosts=2)
    ds = FileBackedLM(tmp_path, seq_len=16, local_batch=4, host=1, n_hosts=2)
    b0 = ds.batch_at(0)["tokens"]
    assert b0.shape == (4, 16)
    assert (ds.batch_at(0)["tokens"] == b0).all()


def test_checkpoint_resume_bitwise(tiny_setup, tmp_path):
    """Preemption simulation: train 6 steps, checkpoint at 3, 'crash',
    restore, continue — final params must be bitwise identical."""
    _, model, params0, opt, opt_state0, data, step_fn = tiny_setup

    params, opt_state = params0, opt_state0
    for step in range(6):
        batch = {k: jnp.asarray(v) for k, v in data.batch_at(step).items()}
        params, opt_state, _ = step_fn(params, opt_state, batch)
        if step == 2:
            ckpt.save(tmp_path, step + 1, {"params": params, "opt": opt_state},
                      extra={"data_step": step + 1})
    want = jax.tree_util.tree_map(np.asarray, params)

    # "crash" -> fresh process state: restore and replay remaining steps
    step, tree, extra = ckpt.restore(
        tmp_path, {"params": params0, "opt": opt_state0})
    assert step == 3 and extra["data_step"] == 3
    params, opt_state = tree["params"], tree["opt"]
    for s in range(extra["data_step"], 6):
        batch = {k: jnp.asarray(v) for k, v in data.batch_at(s).items()}
        params, opt_state, _ = step_fn(params, opt_state, batch)
    got = jax.tree_util.tree_map(np.asarray, params)
    for a, b in zip(jax.tree_util.tree_leaves(want), jax.tree_util.tree_leaves(got)):
        assert (np.asarray(a) == np.asarray(b)).all()


def test_checkpoint_rotation_and_atomicity(tmp_path):
    tree = {"a": jnp.arange(5), "b": jnp.ones((2, 2), jnp.bfloat16)}
    for s in (1, 2, 3, 4):
        ckpt.save(tmp_path, s, tree, keep=2)
    names = sorted(p.name for p in tmp_path.iterdir() if p.name.startswith("step_"))
    assert names == ["step_0000000003", "step_0000000004"]
    assert ckpt.latest_step(tmp_path) == 4
    # a stale tmp dir must never be picked up
    (tmp_path / ".tmp_step_0000000009").mkdir()
    assert ckpt.latest_step(tmp_path) == 4
    _, restored, _ = ckpt.restore(tmp_path, tree)
    assert restored["b"].dtype == jnp.bfloat16


def test_async_checkpoint(tmp_path):
    tree = {"w": jnp.arange(12).reshape(3, 4)}
    t = ckpt.save_async(tmp_path, 7, tree)
    t.join(timeout=30)
    s, restored, _ = ckpt.restore(tmp_path, tree)
    assert s == 7 and (np.asarray(restored["w"]) == np.arange(12).reshape(3, 4)).all()


def test_compressed_psum_shard_map():
    """bf16/int8-EF psum == exact psum within tolerance on a 1-dev mesh."""
    from jax.sharding import Mesh
    from jax import shard_map
    from repro.distributed import psum_bf16, psum_int8_ef

    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    g = {"w": jnp.asarray(np.random.default_rng(0).normal(size=(64,)), jnp.float32)}
    e0 = jax.tree_util.tree_map(jnp.zeros_like, g)

    def body(g):
        return psum_bf16(g, ("data",))

    out = jax.jit(shard_map(body, mesh=mesh, in_specs=({"w": jax.sharding.PartitionSpec()},),
                            out_specs={"w": jax.sharding.PartitionSpec()}))(g)
    np.testing.assert_allclose(np.asarray(out["w"]), np.asarray(g["w"]), rtol=1e-2, atol=1e-2)

    def body2(g, e):
        return psum_int8_ef(g, e, ("data",))

    out2, err = jax.jit(shard_map(
        body2, mesh=mesh,
        in_specs=({"w": jax.sharding.PartitionSpec()}, {"w": jax.sharding.PartitionSpec()}),
        out_specs=({"w": jax.sharding.PartitionSpec()}, {"w": jax.sharding.PartitionSpec()})))(g, e0)
    np.testing.assert_allclose(np.asarray(out2["w"]), np.asarray(g["w"]), atol=0.05)
    # error feedback captures the quantization residual
    assert float(jnp.max(jnp.abs(err["w"]))) <= 0.05


def test_greedy_generate_runs():
    cfg = get_config("deepseek-7b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    from repro.serving import greedy_generate

    prompt = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 8)).astype(np.int32)
    out = greedy_generate(model, params, prompt, n_new=4)
    assert out.shape == (2, 4)
    out_q = greedy_generate(model, params, prompt, n_new=4, kv_quant=True)
    assert out_q.shape == (2, 4)
