"""Sparse-gradient path (reference ``runtime/sparse_tensor.py`` +
``engine.sparse_allreduce`` ``engine.py:2286-2301``)."""

import jax

import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.runtime.sparse_tensor import SparseTensor, sparse_all_reduce

from tests.unit.simple_model import EmbedModel, TiedEmbedModel


def _dense_with_rows(rows, shape, seed=0):
    rs = np.random.RandomState(seed)
    d = np.zeros(shape, np.float32)
    for r in rows:
        d[r] = rs.randn(*shape[1:])
    return jnp.asarray(d)


class TestSparseTensor:
    def test_roundtrip_eager(self):
        d = _dense_with_rows([3, 17, 40], (64, 8))
        st = SparseTensor.from_dense(d)
        assert st.indices.shape == (3,)
        np.testing.assert_allclose(st.to_dense(), d)

    def test_roundtrip_bounded_jit(self):
        d = _dense_with_rows([3, 17, 40], (64, 8))

        @jax.jit
        def f(x):
            st, count = SparseTensor.from_dense_bounded(x, capacity=10)
            return st.to_dense(), count

        dense, count = f(d)
        np.testing.assert_allclose(dense, d)
        assert int(count) == 3

    def test_bounded_overflow_detected(self):
        d = _dense_with_rows(range(12), (64, 8))
        st, count = SparseTensor.from_dense_bounded(d, capacity=4)
        assert int(count) == 12  # > capacity: caller must not trust st

    def test_zero_row_not_duplicated(self):
        # padding entries point at row 0; their values must be zeroed even
        # when row 0 itself carries real gradient
        d = _dense_with_rows([0, 5], (16, 4))
        st, _ = SparseTensor.from_dense_bounded(d, capacity=8)
        np.testing.assert_allclose(st.to_dense(), d)

    def test_add_and_sparse_size(self):
        a = SparseTensor.from_dense(_dense_with_rows([1], (32, 4)))
        b = SparseTensor.from_dense(_dense_with_rows([2], (32, 4), seed=1))
        c = a.add(b)
        assert c.indices.shape == (2,)
        sparse, dense = c.sparse_size()
        assert sparse == 2 + 2 * 4 and dense == 32 * 4
        np.testing.assert_allclose(c.to_dense(), a.to_dense() + b.to_dense())

    def test_sparse_all_reduce_matches_pmean(self):
        from jax.sharding import Mesh, PartitionSpec as P

        devs = np.array(jax.devices()[:4])
        mesh = Mesh(devs, ("data",))
        dense = jnp.asarray(np.random.RandomState(0).randn(4, 32, 8),
                            np.float32)
        # keep rows sparse: zero all but 3 rows per shard
        mask = np.zeros((32, 1), np.float32)
        mask[[2, 9, 30]] = 1
        dense = dense * mask

        def spmd(x):
            x = x[0]
            st, _ = SparseTensor.from_dense_bounded(x, capacity=3)
            return sparse_all_reduce(st, "data").to_dense()[None]

        out = jax.jit(jax.shard_map(spmd, mesh=mesh, in_specs=P("data"),
                                    out_specs=P("data")))(dense)
        expect = jnp.mean(dense, axis=0)
        for shard in range(4):
            # atol for float32 reduction-order noise: the sparse psum
            # folds shards in a different order than jnp.mean (observed
            # |abs| ~2e-8 on values ~1e-2, i.e. |rel| just over 1e-6)
            np.testing.assert_allclose(out[shard], expect, rtol=1e-6,
                                       atol=1e-7)


def _train(model, config, batch, steps=3, seed=7):
    engine, *_ = ds.initialize(model=model, config=config,
                               example_batch={k: v[:2] for k, v in batch.items()},
                               rng=jax.random.PRNGKey(seed))
    for _ in range(steps):
        loss = engine.train_batch(batch=batch)
    return engine, float(loss)


def _embed_batch(batch_size=16, seq=8, vocab=512, seed=0):
    rs = np.random.RandomState(seed)
    # touch FEW rows so the sparse path actually compresses
    ids = rs.randint(0, 40, (batch_size, seq))
    y = rs.randn(batch_size).astype(np.float32)
    return {"ids": ids, "y": y}


BASE_CONFIG = {
    "train_batch_size": 16,
    "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
    "gradient_clipping": 1.0,
    "steps_per_print": 0,
}


class TestSparseEngine:
    def test_matches_dense_path(self):
        batch = _embed_batch()
        dense_engine, dense_loss = _train(
            EmbedModel(), dict(BASE_CONFIG), batch)
        sparse_engine, sparse_loss = _train(
            EmbedModel(), {**BASE_CONFIG, "sparse_gradients": True}, batch)
        assert sparse_engine.sparse_tensor_module_names == {"wte/embedding"}
        assert abs(dense_loss - sparse_loss) < 1e-5
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-6),
            jax.device_get(dense_engine.state.params),
            jax.device_get(sparse_engine.state.params))

    def test_matches_dense_path_gas(self):
        batch = _embed_batch()
        cfg = {**BASE_CONFIG, "gradient_accumulation_steps": 2}
        _, dense_loss = _train(EmbedModel(), cfg, batch)
        _, sparse_loss = _train(
            EmbedModel(), {**cfg, "sparse_gradients": True}, batch)
        assert abs(dense_loss - sparse_loss) < 1e-5

    def test_comm_volume_logged_smaller(self):
        from deepspeed_tpu.comm.comm import comms_logger

        batch = _embed_batch()
        comms_logger.comms_dict.clear()
        engine, _ = _train(
            EmbedModel(vocab=512),
            {**BASE_CONFIG, "sparse_gradients": True,
             "comms_logger": {"enabled": True}}, batch, steps=1)
        logged = comms_logger.comms_dict
        assert "sparse_allreduce" in logged
        sparse_bytes = max(b for b, _ in logged["sparse_allreduce"])
        # dense exchange would be vocab*hidden*4 bytes
        assert sparse_bytes < 512 * 16 * 4

    def test_tied_embedding_excluded_and_progresses(self):
        # the tied table's grad is dense; the init-time probe must detect it,
        # route it through the dense allreduce, and training must PROGRESS
        # (round-2 behavior skipped every step silently)
        rs = np.random.RandomState(0)
        batch = {"ids": rs.randint(0, 40, (16, 8))}
        engine, *_ = ds.initialize(
            model=TiedEmbedModel(),
            config={**BASE_CONFIG, "sparse_gradients": True},
            example_batch={k: v[:2] for k, v in batch.items()},
            rng=jax.random.PRNGKey(7))
        assert engine.sparse_tensor_module_names == set()
        first = float(engine.train_batch(batch=batch))
        for _ in range(4):
            last = float(engine.train_batch(batch=batch))
        assert int(jax.device_get(engine.state.skipped_steps)) == 0
        assert int(jax.device_get(engine.state.step)) == 5
        assert last < first

    def test_stall_guard_raises_when_every_step_skipped(self, monkeypatch):
        # defense in depth: if the dense-leaf probe ever misses (simulated by
        # disabling it), 16 consecutive capacity-overflow skips must raise
        # instead of silently training nowhere
        from deepspeed_tpu.runtime import sparse_engine

        monkeypatch.setattr(sparse_engine, "probe_dense_sparse_leaves",
                            lambda engine, names: set())
        rs = np.random.RandomState(0)
        batch = {"ids": rs.randint(0, 40, (16, 8))}
        engine, *_ = ds.initialize(
            model=TiedEmbedModel(),
            config={**BASE_CONFIG, "sparse_gradients": True},
            example_batch={k: v[:2] for k, v in batch.items()},
            rng=jax.random.PRNGKey(7))
        with pytest.raises(RuntimeError, match="ALL +skipped|were ALL"):
            for _ in range(16):
                engine.train_batch(batch=batch)

    def test_rejects_zero_stage(self):
        batch = _embed_batch()
        with pytest.raises(ValueError, match="ZeRO stage 0"):
            _train(EmbedModel(),
                   {**BASE_CONFIG, "sparse_gradients": True,
                    "zero_optimization": {"stage": 2}}, batch, steps=1)
