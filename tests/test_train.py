"""Training/serving substrate: loss decreases under QAT, microbatch
equivalence, checkpoint roundtrip + resume, data determinism, serve engine."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.checkpoint import store
from repro.core.policy import get_policy
from repro.data.pipeline import Pipeline, make_batch
from repro.models import model as M
from repro.serve.engine import Request, ServeEngine
from repro.train import optimizer as opt
from repro.train import step as T

jax.config.update("jax_platform_name", "cpu")

TINY = configs.reduced(configs.get_arch("internlm2-1.8b"))
POLICY = get_policy("w4a8")
SHAPE = configs.ShapeCfg("tiny", seq_len=16, global_batch=4, kind="train")


def _tcfg(**kw):
    return T.TrainCfg(opt=opt.OptCfg(lr=3e-3, warmup_steps=5, total_steps=100), **kw)


def test_train_loss_decreases_qat():
    tcfg = _tcfg()
    state = T.init_train_state(jax.random.key(0), TINY, POLICY, tcfg)
    step_fn = jax.jit(T.make_train_step(TINY, POLICY, tcfg, impl="jnp"))
    # overfit one small batch: loss must drop under fake-quant training
    batch = jax.tree.map(jnp.asarray, make_batch(TINY, SHAPE, 0))
    losses = []
    for _ in range(30):
        state, metrics = step_fn(state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0] - 0.5, losses[:3] + losses[-3:]
    assert np.isfinite(losses).all()


def test_microbatch_equivalence():
    """grad(batch) == mean of grads(microbatches) -> same first update."""
    b = jax.tree.map(jnp.asarray, make_batch(TINY, SHAPE, 1))
    g1, m1 = T.grads_fn(
        T.init_train_state(jax.random.key(1), TINY, POLICY, _tcfg())["params"],
        b, TINY, POLICY, _tcfg(), impl="jnp")
    g2, m2 = T.grads_fn(
        T.init_train_state(jax.random.key(1), TINY, POLICY, _tcfg())["params"],
        b, TINY, POLICY, _tcfg(microbatches=2), impl="jnp")
    jax.tree.map(
        lambda a, c: np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(c, np.float32), rtol=2e-2, atol=2e-3),
        g1, g2)


def test_launcher_setup_inits_sharded_state_equal_to_eager_init():
    """launch/train.setup creates the state through a jit with
    out_shardings (never whole on one device): the eager init's values (to
    one bf16 rounding: the jit may fuse the init's scale-and-cast), placed
    as the launcher's shardings say, and its step trains."""
    from repro.launch import train as TL

    tcfg = _tcfg()
    ts = TL.setup(TINY, POLICY, tcfg, SHAPE, TL.make_mesh(1, 1))
    state = ts.init()
    eager = T.init_train_state(jax.random.key(0), TINY, POLICY, tcfg)
    assert (jax.tree.structure(state) == jax.tree.structure(eager)
            == jax.tree.structure(ts.state_shape))
    for got, want, shd in zip(jax.tree.leaves(state), jax.tree.leaves(eager),
                              jax.tree.leaves(ts.state_shardings)):
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=2**-7, atol=0)
        assert got.sharding.is_equivalent_to(shd, got.ndim)
    batch = jax.tree.map(jnp.asarray, make_batch(TINY, SHAPE, 0))
    losses = []
    for _ in range(3):
        state, metrics = ts.step(state, batch)
        losses.append(float(metrics["loss"]))
    assert np.isfinite(losses).all() and losses[-1] < losses[0]


def test_moe_training_runs():
    cfg = configs.reduced(configs.get_arch("granite-moe-1b-a400m"))
    tcfg = _tcfg()
    state = T.init_train_state(jax.random.key(0), cfg, POLICY, tcfg)
    step_fn = jax.jit(T.make_train_step(cfg, POLICY, tcfg, impl="jnp"))
    batch = jax.tree.map(jnp.asarray, make_batch(cfg, SHAPE, 0))
    for _ in range(3):
        state, metrics = step_fn(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert float(metrics["moe_aux"]) > 0.0


def test_checkpoint_roundtrip_and_resume(tmp_path):
    tcfg = _tcfg()
    state = T.init_train_state(jax.random.key(2), TINY, POLICY, tcfg)
    root = str(tmp_path / "ckpt")
    store.save(root, 7, state)
    assert store.latest_step(root) == 7
    target = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), state)
    # load into abstract target (elastic restore pattern)
    restored, step = store.load(root, jax.eval_shape(lambda: state))
    assert step == 7
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a, np.float32), np.asarray(b, np.float32)), state, restored)


def test_checkpoint_gc_and_atomicity(tmp_path):
    root = str(tmp_path / "ck")
    ck = store.Checkpointer(root, keep=2)
    tree = {"w": jnp.arange(8, dtype=jnp.float32)}
    for s in (1, 2, 3):
        ck.save_async(s, tree)
    ck.wait()
    assert store.latest_step(root) == 3
    steps = sorted(int(d.split("_")[1]) for d in os.listdir(root) if d.startswith("step_"))
    assert steps == [2, 3]
    # a stale tmp dir must be invisible
    os.makedirs(os.path.join(root, ".tmp_99"), exist_ok=True)
    assert store.latest_step(root) == 3


def test_data_determinism_and_sharding():
    b1 = make_batch(TINY, SHAPE, step=5, host=0, n_hosts=2)
    b2 = make_batch(TINY, SHAPE, step=5, host=0, n_hosts=2)
    b3 = make_batch(TINY, SHAPE, step=5, host=1, n_hosts=2)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    assert not np.array_equal(b1["tokens"], b3["tokens"])
    assert b1["tokens"].shape == (SHAPE.global_batch // 2, SHAPE.seq_len)

    pipe = Pipeline(TINY, SHAPE, start_step=3)
    s, b = next(pipe)
    assert s == 3
    np.testing.assert_array_equal(b["tokens"], make_batch(TINY, SHAPE, 3)["tokens"])
    pipe.close()


def test_serve_engine_continuous_batching():
    params = M.init_params(jax.random.key(3), TINY, POLICY, mode="serve")
    eng = ServeEngine(params, TINY, POLICY, n_slots=2, s_max=32, impl="jnp")
    reqs = [Request(rid=i, prompt=np.array([1 + i, 2, 3], np.int32), max_new=4)
            for i in range(3)]
    out = eng.run(reqs)
    assert set(out) == {0, 1, 2}
    assert all(len(v) == 4 for v in out.values())
    assert all(0 <= t < TINY.vocab for v in out.values() for t in v)
