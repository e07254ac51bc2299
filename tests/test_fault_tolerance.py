"""Fault tolerance: checkpoint/restart byte-exactness + elastic re-mesh.

Runs on 8 fake CPU devices (set in conftest for this module via env is not
possible per-module — instead we use the devices the session has and skip
if fewer than 4).
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_smoke_config
from repro.data.synthetic import DataConfig, TokenStream
from repro.models import params as pp
from repro.optim import adamw
from repro.train import checkpoint as ckpt
from repro.train.loop import RunConfig, train_loop
from repro.train import elastic


@pytest.fixture(scope="module")
def setup(tmp_path_factory):
    cfg = get_smoke_config("qwen3_4b")
    data = DataConfig(seed=0, batch=4, seq_len=16)
    stream = TokenStream(cfg, data)
    return cfg, stream, tmp_path_factory.mktemp("ckpt")


def small_mesh(n_model=1):
    n = len(jax.devices())
    from jax.sharding import AxisType
    return jax.make_mesh(((n // n_model) or 1, n_model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


class TestCheckpoint:
    def test_save_restore_roundtrip(self, setup):
        cfg, stream, tmp = setup
        params = pp.init_params(cfg, jax.random.PRNGKey(0))
        opt = adamw.init_state(params)
        path = ckpt.save(str(tmp / "a"), params, opt, 7, blocking=True)
        assert os.path.isdir(path)
        like = {"params": params, "opt": opt}
        state, step = ckpt.restore(str(tmp / "a"), 7, like)
        assert step == 7
        for a, b in zip(jax.tree.leaves(state["params"]),
                        jax.tree.leaves(params)):
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))

    def test_latest_step(self, setup):
        cfg, stream, tmp = setup
        params = pp.init_params(cfg, jax.random.PRNGKey(0))
        opt = adamw.init_state(params)
        ckpt.save(str(tmp / "b"), params, opt, 3, blocking=True)
        ckpt.save(str(tmp / "b"), params, opt, 9, blocking=True)
        assert ckpt.latest_step(str(tmp / "b")) == 9

    def test_atomicity_no_tmp_left(self, setup):
        cfg, stream, tmp = setup
        params = pp.init_params(cfg, jax.random.PRNGKey(0))
        opt = adamw.init_state(params)
        ckpt.save(str(tmp / "c"), params, opt, 1, blocking=True)
        assert not any(d.endswith(".tmp") for d in os.listdir(tmp / "c"))


class TestElasticRestart:
    def test_restart_continues_loss_curve(self, setup):
        """Train 4 steps, checkpoint at 2, restart from 2 — steps 2-3 match
        byte-for-byte (deterministic data stream + restored state)."""
        cfg, stream, tmp = setup
        run = RunConfig(fsdp=False, remat=False, donate=False)
        mesh = small_mesh()
        losses_a = {}
        train_loop(cfg, adamw.AdamWConfig(lr=1e-3), mesh, stream, 5, run,
                   checkpoint_dir=str(tmp / "d"), checkpoint_every=2,
                   on_metrics=lambda s, m: losses_a.__setitem__(s, m["loss"]))
        ckpt.wait_for_writes()
        params, opt, step = elastic.resume(cfg, adamw.AdamWConfig(lr=1e-3),
                                           str(tmp / "d"), mesh, run)
        assert step == 4          # saved after steps 2 and 4
        losses_b = {}
        train_loop(cfg, adamw.AdamWConfig(lr=1e-3), mesh, stream, 5, run,
                   start_step=step, params=params, opt_state=opt,
                   on_metrics=lambda s, m: losses_b.__setitem__(s, m["loss"]))
        np.testing.assert_allclose(losses_a[4], losses_b[4], rtol=1e-5)

    def test_shrink_mesh_preserves_tp(self):
        devs = jax.devices()
        if len(devs) < 2:
            pytest.skip("needs >=2 devices")
        m = elastic.shrink_mesh(devs[: len(devs) - 1], model_parallel=1)
        assert m.shape["model"] == 1
        assert m.shape["data"] == len(devs) - 1

    def test_resume_on_smaller_mesh(self, setup):
        """The elastic path: checkpoint on mesh A, resume on half of it."""
        cfg, stream, tmp = setup
        if len(jax.devices()) < 2:
            pytest.skip("needs >=2 devices")
        run = RunConfig(fsdp=False, remat=False, donate=False)
        mesh = small_mesh()
        train_loop(cfg, adamw.AdamWConfig(), mesh, stream, 2, run,
                   checkpoint_dir=str(tmp / "e"), checkpoint_every=2)
        ckpt.wait_for_writes()
        survivors = jax.devices()[: max(len(jax.devices()) // 2, 1)]
        mesh2 = elastic.shrink_mesh(survivors, model_parallel=1)
        params, opt, step = elastic.resume(cfg, adamw.AdamWConfig(),
                                           str(tmp / "e"), mesh2, run)
        # one more step must run on the shrunken mesh
        p2, o2, metrics = train_loop(cfg, adamw.AdamWConfig(), mesh2, stream,
                                     3, run, start_step=step,
                                     params=params, opt_state=opt)
        assert np.isfinite(metrics["loss"])


class TestGradCompression:
    def test_int8_roundtrip_error_feedback(self):
        from repro.optim.grad_compress import (compress_tree, dequantize_int8,
                                               quantize_int8)
        rng = np.random.default_rng(0)
        g = {"a": jnp.asarray(rng.standard_normal((64, 64)), jnp.float32)}
        q, s, resid = compress_tree(g, None)
        deq = dequantize_int8(q["a"], s["a"])
        err = np.abs(np.asarray(deq + resid["a"]) - np.asarray(g["a"])).max()
        assert err < 1e-5       # error feedback captures quantization residual
        assert q["a"].dtype == jnp.int8
