"""Expert-parallel MoE and pipeline-parallel tests on the 8-device
virtual CPU mesh (SURVEY §4: multi-node-without-a-cluster testing).
"""
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from deeplearning4j_tpu.parallel import make_mesh
from deeplearning4j_tpu.parallel.moe import MixtureOfExperts, top_k_gating
from deeplearning4j_tpu.parallel.pipeline import (
    pipeline_apply, make_mlp_stage, pipeline_train_step)


class TestGating:
    def test_dispatch_combine_shapes_and_capacity(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (12, 4))
        w = jax.random.normal(jax.random.PRNGKey(1), (4, 4)) * 0.1
        disp, comb, aux = top_k_gating(x, w, top_k=2, capacity=3)
        assert disp.shape == (12, 4, 3)
        # no expert slot double-booked
        assert float(jnp.max(jnp.sum(disp, axis=0))) <= 1.0 + 1e-6
        # per-expert load ≤ capacity
        assert float(jnp.max(jnp.sum(disp, axis=(0, 2)))) <= 3 + 1e-6
        assert np.isfinite(float(aux))

    def test_combine_weights_sum_to_one_for_kept_tokens(self):
        x = jax.random.normal(jax.random.PRNGKey(2), (8, 4))
        w = jax.random.normal(jax.random.PRNGKey(3), (4, 4)) * 0.1
        # generous capacity: nothing dropped
        disp, comb, _ = top_k_gating(x, w, top_k=2, capacity=16)
        sums = jnp.sum(comb, axis=(1, 2))
        assert np.allclose(sums, 1.0, atol=1e-5)


class TestMoE:
    def test_forward_and_grad_single_device(self):
        moe = MixtureOfExperts(d_model=8, d_hidden=16, num_experts=4,
                               top_k=2)
        params = moe.init()
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 6, 8))
        out, aux = moe.apply(params, x)
        assert out.shape == x.shape

        def loss(p):
            o, a = moe.apply(p, x)
            return jnp.sum(jnp.square(o)) + 0.01 * a
        g = jax.jit(jax.grad(loss))(params)
        for leaf in jax.tree.leaves(g):
            assert np.all(np.isfinite(np.asarray(leaf)))

    def test_expert_parallel_on_mesh(self):
        if len(jax.devices()) < 8:
            pytest.skip("needs 8 devices")
        mesh = make_mesh({"expert": 8})
        moe = MixtureOfExperts(d_model=8, d_hidden=16, num_experts=8,
                               top_k=2)
        params = moe.shard(moe.init(), mesh, axis="expert")
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 8))

        @jax.jit
        def step(p, x):
            def loss(p):
                o, a = moe.apply(p, x)
                return jnp.mean(jnp.square(o)) + 0.01 * a
            return jax.value_and_grad(loss)(p)

        val, g = step(params, x)
        assert np.isfinite(float(val))
        # sharded leaves keep their expert-axis sharding
        assert g["w_in"].shape == (8, 8, 16)

    def test_ep_matches_single_device(self):
        """Same params, same input: EP-sharded == unsharded output."""
        if len(jax.devices()) < 8:
            pytest.skip("needs 8 devices")
        moe = MixtureOfExperts(d_model=4, d_hidden=8, num_experts=8,
                               top_k=2, seed=3)
        params = moe.init()
        x = jax.random.normal(jax.random.PRNGKey(2), (2, 8, 4))
        ref, _ = moe.apply(params, x)
        mesh = make_mesh({"expert": 8})
        sharded = moe.shard(params, mesh, axis="expert")
        out, _ = jax.jit(moe.apply)(sharded, x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-6)


class TestPipeline:
    def _stacked_params(self, S, d, seed=0):
        key = jax.random.PRNGKey(seed)
        k1, k2 = jax.random.split(key)
        return {"W": jax.random.normal(k1, (S, d, d)) * 0.1,
                "b": jax.random.normal(k2, (S, d)) * 0.01}

    def test_pipeline_matches_sequential(self):
        if len(jax.devices()) < 8:
            pytest.skip("needs 8 devices")
        S, M, mb, d = 8, 4, 2, 6
        mesh = make_mesh({"stage": S})
        params = self._stacked_params(S, d)
        stage_fn = make_mlp_stage()
        x = jax.random.normal(jax.random.PRNGKey(1), (M, mb, d))
        out = pipeline_apply(stage_fn, params, x, mesh=mesh,
                             axis="stage")
        # sequential reference: stage 0..S-1 applied in order
        ref = x
        for s in range(S):
            p_s = jax.tree.map(lambda p: p[s], params)
            ref = jax.vmap(lambda xm: stage_fn(p_s, xm))(ref)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-6)

    def test_pipeline_train_step_learns(self):
        if len(jax.devices()) < 8:
            pytest.skip("needs 8 devices")
        S, M, mb, d = 8, 4, 2, 6
        mesh = make_mesh({"stage": S})
        params = self._stacked_params(S, d, seed=5)
        stage_fn = make_mlp_stage()
        x = jax.random.normal(jax.random.PRNGKey(2), (M, mb, d))
        y = jax.random.normal(jax.random.PRNGKey(3), (M, mb, d))

        def loss_fn(out, target):
            return jnp.mean(jnp.square(out - target))

        step, opt = pipeline_train_step(
            stage_fn, loss_fn, mesh=mesh, axis="stage",
            optimizer=optax.adam(1e-2))
        opt_state = opt.init(params)
        losses = []
        for _ in range(10):
            params, opt_state, loss = step(params, opt_state, x, y)
            losses.append(float(loss))
        assert losses[-1] < losses[0]
        assert all(np.isfinite(l) for l in losses)
