"""System-level tests: optimizers, checkpoint round-trip, data determinism,
policy plumbing, serving engine, train driver integration."""
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from conftest import hypothesis_or_stubs
given, settings, st = hypothesis_or_stubs()

from repro.checkpoint import io as ckpt
from repro.core.policy import (BoundaryPolicy, CompressionPolicy, NO_POLICY,
                               quant_policy, topk_policy)
from repro.data.synthetic import ImageClassData, LMData
from repro.optim.optimizers import (OptimizerConfig, apply_updates,
                                    init_opt_state, schedule_lr)


class TestOptimizers:
    def _quadratic_steps(self, opt, steps=200):
        params = {"w": jnp.array([3.0, -2.0]), "b": jnp.array(1.5)}
        state = init_opt_state(opt, params)
        for _ in range(steps):
            grads = jax.grad(
                lambda p: jnp.sum(p["w"] ** 2) + p["b"] ** 2)(params)
            params, state = apply_updates(opt, params, grads, state)
        return params

    def test_sgd_momentum_converges(self):
        p = self._quadratic_steps(OptimizerConfig(
            kind="sgd", lr=0.1, momentum=0.9, schedule="constant"))
        assert float(jnp.abs(p["w"]).max()) < 1e-2

    def test_adamw_converges(self):
        p = self._quadratic_steps(OptimizerConfig(
            kind="adamw", lr=0.05, schedule="constant"))
        assert float(jnp.abs(p["w"]).max()) < 1e-2

    def test_weight_decay_shrinks(self):
        opt = OptimizerConfig(kind="sgd", lr=0.1, weight_decay=0.5,
                              schedule="constant")
        params = {"w": jnp.ones((4,))}
        state = init_opt_state(opt, params)
        zero = {"w": jnp.zeros((4,))}
        params, _ = apply_updates(opt, params, zero, state)
        assert float(params["w"][0]) < 1.0

    def test_cosine_schedule_endpoints(self):
        opt = OptimizerConfig(kind="sgd", lr=1.0, schedule="cosine",
                              t_max=100)
        assert float(schedule_lr(opt, jnp.int32(0))) == pytest.approx(1.0)
        assert float(schedule_lr(opt, jnp.int32(100))) < 0.01

    def test_grad_clip_bounds_update(self):
        opt = OptimizerConfig(kind="sgd", lr=1.0, grad_clip=1.0,
                              schedule="constant")
        params = {"w": jnp.zeros((3,))}
        state = init_opt_state(opt, params)
        huge = {"w": jnp.full((3,), 1e6)}
        new, _ = apply_updates(opt, params, huge, state)
        assert float(jnp.abs(new["w"]).max()) <= 1.0 + 1e-5


class TestCheckpoint:
    def test_roundtrip_mixed_dtypes(self, tmp_path):
        tree = {"a": jnp.arange(6, dtype=jnp.int32).reshape(2, 3),
                "b": [jnp.ones((4,), jnp.bfloat16) * 1.5,
                      jnp.zeros((2, 2), jnp.float32)],
                "c": {"d": jnp.array(7.0)}}
        p = str(tmp_path / "ck.npz")
        ckpt.save(p, tree, step=42, extra={"arch": "x"})
        back, step = ckpt.restore(p, jax.eval_shape(lambda: tree))
        assert step == 42
        for l1, l2 in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
            assert l1.dtype == l2.dtype
            np.testing.assert_array_equal(np.asarray(l1, np.float32),
                                          np.asarray(l2, np.float32))


class TestData:
    def test_image_data_deterministic(self):
        a, b = ImageClassData(num_train=64, num_test=16), \
               ImageClassData(num_train=64, num_test=16)
        np.testing.assert_array_equal(a.x_train, b.x_train)
        xa = list(a.epoch(16, 3))
        xb = list(b.epoch(16, 3))
        np.testing.assert_array_equal(xa[0][2], xb[0][2])

    def test_lm_data_ids_stable_across_epochs(self):
        d = LMData(num_train=64, num_test=16)
        seen = {}
        for ep in range(2):
            for toks, ids in d.epoch(16, ep):
                for t, i in zip(toks, ids):
                    key = int(i)
                    if key in seen:
                        np.testing.assert_array_equal(seen[key], t)
                    seen[key] = t.copy()
        assert len(seen) == 64

    def test_synthetic_stream_deals_ids_per_replica_under_dp(self):
        """AQ-SGD dp routing contract: contiguous batch shard r must carry
        ids from its own block [r*N/dp, (r+1)*N/dp) every step."""
        from repro.configs.registry import get
        from repro.launch.train import synthetic_stream
        cfg = get("gpt2-small", smoke=True)
        dp, batch, ns = 2, 8, 16
        stream = synthetic_stream(cfg, batch, 32, num_samples=ns, dp=dp)
        seen = [set() for _ in range(dp)]
        for _ in range(6):
            _, ids = next(stream)
            for r in range(dp):
                shard = ids[r * (batch // dp):(r + 1) * (batch // dp)]
                lo, hi = r * ns // dp, (r + 1) * ns // dp
                assert ((shard >= lo) & (shard < hi)).all(), (r, shard)
                seen[r].update(int(i) for i in shard)
        # the cycling still revisits every row of each replica's block
        assert all(len(s) == ns // dp for s in seen)

    def test_lm_task_learnable_structure(self):
        """Order-2 Markov: the same (t-2,t-1) context has <=4 successors."""
        d = LMData(num_train=32)
        succ_count = {}
        for row in d.train:
            for t in range(2, d.seq_len):
                succ_count.setdefault(
                    (row[t - 2], row[t - 1]), set()).add(row[t])
        assert max(len(v) for v in succ_count.values()) <= 4


class TestPolicy:
    def test_cut_layers_even_partition(self):
        pol = CompressionPolicy(num_stages=4)
        assert pol.cut_layers(40) == (9, 19, 29)
        cuts = pol.cut_layers(46)
        assert len(cuts) == 3
        # stage sizes differ by at most 1 layer
        sizes = [cuts[0] + 1, cuts[1] - cuts[0], cuts[2] - cuts[1],
                 46 - 1 - cuts[2]]
        assert max(sizes) - min(sizes) <= 1, sizes
        assert len(pol.cut_layers(12)) == 3

    def test_overrides(self):
        bp = quant_policy(2, 8)
        pol = CompressionPolicy(num_stages=4, boundary=topk_policy(0.1),
                                overrides=((1, bp),))
        assert pol.at(0).fw.kind == "topk"
        assert pol.at(1).fw.bits == 2

    def test_reuse_requires_topk(self):
        with pytest.raises(ValueError):
            BoundaryPolicy(fw=quant_policy(4, 4).fw, reuse_indices=True)

    @given(st.integers(1, 8), st.integers(8, 64))
    @settings(max_examples=20, deadline=None)
    def test_cuts_sorted_in_range(self, stages, layers):
        pol = CompressionPolicy(num_stages=stages)
        cuts = pol.cut_layers(layers)
        assert len(cuts) == stages - 1
        assert all(0 <= c < layers for c in cuts)
        assert list(cuts) == sorted(set(cuts))


class TestServeEngine:
    def test_generate_shapes_and_determinism(self):
        from repro.configs.registry import get
        from repro.models import transformer
        from repro.serve.engine import Request, ServeEngine
        cfg = get("granite-8b", smoke=True)
        params = transformer.init_params(jax.random.PRNGKey(0), cfg)
        eng = ServeEngine(params, cfg, NO_POLICY, max_batch=2, max_seq=64)
        rng = np.random.RandomState(0)
        mk = lambda: [Request(rng_.randint(0, 100, 8).astype(np.int32), 6)
                      for rng_ in [np.random.RandomState(1),
                                   np.random.RandomState(2)]]
        r1, r2 = eng.generate(mk()), eng.generate(mk())
        for a, b in zip(r1, r2):
            assert a.out.shape == (6,)
            np.testing.assert_array_equal(a.out, b.out)

    def test_compression_changes_generation(self):
        from repro.configs.registry import get
        from repro.models import transformer
        from repro.serve.engine import Request, ServeEngine
        cfg = get("granite-8b", smoke=True)
        params = transformer.init_params(jax.random.PRNGKey(0), cfg)
        pol = CompressionPolicy(num_stages=4, boundary=topk_policy(0.05))
        prompt = np.random.RandomState(3).randint(0, 100, 16).astype(np.int32)
        outs = []
        for compress in (True, False):
            eng = ServeEngine(params, cfg, pol, compress=compress,
                              max_batch=1, max_seq=64)
            outs.append(eng.generate([Request(prompt.copy(), 8)])[0].out)
        # not a hard guarantee, but with top5% at 3 boundaries the
        # trajectories essentially always diverge
        assert not np.array_equal(outs[0], outs[1])


class TestTrainDriver:
    def test_train_main_runs_and_learns(self, tmp_path):
        from repro.launch.train import main
        js = str(tmp_path / "m.json")
        ck = str(tmp_path / "ck.npz")
        rc = main(["--arch", "gpt2-small", "--smoke", "--steps", "12",
                   "--batch", "4", "--seq", "32", "--policy", "top10reuse",
                   "--log-every", "4", "--json", js, "--ckpt", ck,
                   "--ckpt-every", "12", "--no-remat"])
        assert rc == 0
        import json as j
        hist = j.load(open(js))
        assert hist[-1]["loss"] < hist[0]["loss"] + 0.5
        assert os.path.exists(ck)

    def test_gradient_accumulation_matches_single_batch(self):
        """grad_accum=2 must give (numerically close) the same update
        as one full batch — the accumulation preserves the paper's
        per-example semantics.  The deprecated ``microbatches=`` alias
        still selects accumulation (with a DeprecationWarning)."""
        import warnings
        from repro.configs.registry import get
        from repro.models import transformer
        from repro.optim.optimizers import OptimizerConfig, init_opt_state
        from repro.train.steps import make_lm_train_step
        cfg = get("granite-8b", smoke=True)
        params = transformer.init_params(jax.random.PRNGKey(0), cfg)
        opt = OptimizerConfig(kind="sgd", lr=0.1, momentum=0.0,
                              weight_decay=0.0, schedule="constant",
                              moment_dtype=jnp.float32)
        batch = {"tokens": jnp.asarray(
            np.random.RandomState(0).randint(0, cfg.vocab_size, (4, 16))
            .astype(np.int32))}
        ids = jnp.arange(4, dtype=jnp.int32)
        outs = []
        for mb in (1, 2):
            step = make_lm_train_step(cfg, NO_POLICY, opt, remat=False,
                                      donate=False, grad_accum=mb)
            p, _, _, m = step(params, init_opt_state(opt, params), [],
                              batch, ids)
            outs.append((jax.tree.leaves(p)[0].astype(jnp.float32),
                         float(m["loss"])))
        assert abs(outs[0][1] - outs[1][1]) < 0.05
        np.testing.assert_allclose(np.asarray(outs[0][0]),
                                   np.asarray(outs[1][0]), atol=0.02)
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            step = make_lm_train_step(cfg, NO_POLICY, opt, remat=False,
                                      donate=False, microbatches=2)
            assert any(issubclass(x.category, DeprecationWarning)
                       for x in w), w
        p, _, _, m = step(params, init_opt_state(opt, params), [],
                          batch, ids)
        np.testing.assert_allclose(
            np.asarray(jax.tree.leaves(p)[0].astype(jnp.float32)),
            np.asarray(outs[1][0]), atol=1e-6)

    def test_serve_main_runs(self):
        from repro.launch.serve import main
        rc = main(["--arch", "gpt2-small", "--smoke", "--policy", "top10",
                   "--batch", "2", "--prompt-len", "8", "--new-tokens", "4",
                   "--max-seq", "32"])
        assert rc == 0

    def test_compile_cache_placement(self, monkeypatch, tmp_path):
        """JAX_COMPILATION_CACHE_DIR wins and no other directory is set
        in code; without it the cache goes to <checkout>/.jax_cache."""
        from repro.launch import compile_cache
        prev = jax.config.jax_compilation_cache_dir
        try:
            jax.config.update("jax_compilation_cache_dir", "unchanged")
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
            assert compile_cache.enable_compile_cache() == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == "unchanged"
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
            where = compile_cache.enable_compile_cache()
            repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
            assert where == os.path.join(repo, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == where
        finally:
            jax.config.update("jax_compilation_cache_dir", prev)
