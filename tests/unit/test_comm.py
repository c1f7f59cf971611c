import jax

import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from deepspeed_tpu import comm
from deepspeed_tpu.parallel import build_mesh


@pytest.fixture
def mesh(request):
    return build_mesh(data=8)


def _smap(mesh, fn, in_specs, out_specs):
    return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs))


def test_all_reduce_sum(mesh):
    x = jnp.arange(8.0)
    out = _smap(mesh, lambda v: comm.all_reduce(v, group="data"), P("data"), P("data"))(x)
    np.testing.assert_allclose(out, np.full(8, 28.0))


def test_all_reduce_avg_max_min(mesh):
    x = jnp.arange(8.0)
    avg = _smap(mesh, lambda v: comm.all_reduce(v, comm.ReduceOp.AVG, "data"), P("data"),
                P("data"))(x)
    np.testing.assert_allclose(avg, np.full(8, 3.5))
    mx = _smap(mesh, lambda v: comm.all_reduce(v, comm.ReduceOp.MAX, "data"), P("data"),
               P("data"))(x)
    np.testing.assert_allclose(mx, np.full(8, 7.0))
    mn = _smap(mesh, lambda v: comm.all_reduce(v, comm.ReduceOp.MIN, "data"), P("data"),
               P("data"))(x)
    np.testing.assert_allclose(mn, np.full(8, 0.0))


def test_all_gather_tiled(mesh):
    x = jnp.arange(16.0)

    def fn(v):
        g = comm.all_gather(v, group="data", axis=0, tiled=True)
        assert g.shape == (16,)
        return g[None]

    out = np.asarray(_smap(mesh, fn, P("data"), P("data"))(x))
    assert out.shape == (8, 16)
    np.testing.assert_allclose(out[0], np.arange(16.0))


def test_reduce_scatter_roundtrip(mesh):
    # reduce_scatter(all same x) == 8 * local shard
    x = jnp.arange(16.0)

    def fn(v):
        full = comm.all_gather(v, group="data", tiled=True)
        return comm.reduce_scatter(full, group="data")

    out = _smap(mesh, fn, P("data"), P("data"))(x)
    np.testing.assert_allclose(out, 8.0 * np.arange(16.0))


def test_all_to_all(mesh):
    x = jnp.arange(64.0).reshape(64, 1)

    def fn(v):
        return comm.all_to_all_single(v, group="data", split_axis=0, concat_axis=0)

    out = _smap(mesh, fn, P("data", None), P("data", None))(x)
    # shard i gets element j of every source shard j block
    expected = np.arange(64.0).reshape(8, 8).T.reshape(64, 1)
    np.testing.assert_allclose(out, expected)


def test_broadcast(mesh):
    x = jnp.arange(8.0)
    out = _smap(mesh, lambda v: comm.broadcast(v, src=3, group="data"), P("data"), P("data"))(x)
    np.testing.assert_allclose(out, np.full(8, 3.0))


def test_all_reduce_product_and_bitwise(mesh):
    # product with negatives and a zero must be exact (no log-space tricks)
    x = jnp.array([1.0, -2.0, 3.0, -1.0, 1.0, 1.0, 2.0, 0.5])
    out = _smap(mesh, lambda v: comm.all_reduce(v, comm.ReduceOp.PRODUCT, "data"), P("data"),
                P("data"))(x)
    np.testing.assert_allclose(out, np.full(8, 6.0))
    b = jnp.array([1, 2, 4, 8, 16, 32, 64, 128], dtype=jnp.int32)
    out = _smap(mesh, lambda v: comm.all_reduce(v, comm.ReduceOp.BOR, "data"), P("data"),
                P("data"))(b)
    np.testing.assert_array_equal(np.asarray(out), np.full(8, 255, dtype=np.int32))


def test_broadcast_ignores_nan_in_non_source(mesh):
    x = jnp.where(jnp.arange(8.0) == 3, 7.0, jnp.nan)
    out = _smap(mesh, lambda v: comm.broadcast(v, src=3, group="data"), P("data"), P("data"))(x)
    np.testing.assert_allclose(out, np.full(8, 7.0))


def test_send_recv_ring(mesh):
    x = jnp.arange(8.0)
    nxt = _smap(mesh, lambda v: comm.send_recv_next(v, group="data"), P("data"), P("data"))(x)
    np.testing.assert_allclose(nxt, np.roll(np.arange(8.0), 1))
    prv = _smap(mesh, lambda v: comm.send_recv_prev(v, group="data"), P("data"), P("data"))(x)
    np.testing.assert_allclose(prv, np.roll(np.arange(8.0), -1))


def test_comms_logger_records():
    comm.comms_logger.enabled = True
    comm.comms_logger.reset()
    mesh = build_mesh(data=8)
    x = jnp.arange(8.0)
    _smap(mesh, lambda v: comm.all_reduce(v, group="data"), P("data"), P("data"))(x)
    assert "all_reduce" in comm.comms_logger.comms_dict
    comm.comms_logger.enabled = False
    comm.comms_logger.reset()


def test_get_bw():
    alg, bus = comm.get_bw("all_reduce", 1_000_000_000, 1.0, 8)
    assert alg == 8.0
    np.testing.assert_allclose(bus, 8.0 * 2 * 7 / 8)
