"""distributed package: auto-parallel API, mpu layers, fleet, collectives.

Mirrors the reference's test/auto_parallel/ (shard_tensor/reshard matrix)
and test/collective/ API tests, on the 8-device CPU mesh.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

import paddle_tpu as pt
import paddle_tpu.distributed as dist
from paddle_tpu.distributed import Shard, Replicate, Partial, ProcessMesh
from paddle_tpu.parallel import init_hybrid_mesh


@pytest.fixture
def mesh2d():
    return ProcessMesh(np.arange(8).reshape(2, 4), dim_names=["x", "y"])


def test_shard_tensor_layout(mesh2d):
    t = pt.to_tensor(np.arange(64, dtype=np.float32).reshape(8, 8))
    d = dist.shard_tensor(t, mesh2d, [Shard(0), Shard(1)])
    assert d.data.sharding.spec == P("x", "y")
    # values unchanged
    np.testing.assert_array_equal(d.numpy(), t.numpy())


def test_reshard_transitions(mesh2d):
    t = pt.to_tensor(np.random.randn(8, 8).astype(np.float32))
    d = dist.shard_tensor(t, mesh2d, [Shard(0), Replicate()])
    r = dist.reshard(d, mesh2d, [Replicate(), Shard(0)])
    assert r.data.sharding.spec == P("y", None)
    np.testing.assert_array_equal(r.numpy(), t.numpy())
    u = dist.unshard_dtensor(r)
    np.testing.assert_array_equal(u.numpy(), t.numpy())


def test_partial_roundtrip_preserves_value(mesh2d):
    # r -> p -> r: the reference lattice edge pair (r_to_p zero-pads
    # non-owner ranks; p_to_r all-reduces)
    t = pt.to_tensor(np.random.RandomState(0).randn(8, 8).astype(np.float32))
    p = dist.shard_tensor(t, mesh2d, [Partial(), Replicate()])
    from paddle_tpu.distributed.auto_parallel_api import _placements_of
    pls = _placements_of(p, mesh2d)
    assert pls[0].is_partial() and pls[1].is_replicated(), pls
    # payload carries the contribution stack, sharded over the mesh dim
    assert p.data.shape == (2, 8, 8)
    assert p.data.sharding.spec[0] == "x"
    r = dist.reshard(p, mesh2d, [Replicate(), Replicate()])
    np.testing.assert_allclose(r.numpy(), t.numpy())
    assert not getattr(r, "_partial_dims", ())


def test_partial_really_sums_contributions(mesh2d):
    # simulate what per-rank computation produces: DIFFERENT terms per
    # mesh slice; p->r must be their sum, p->s(d) the sum sharded on d
    rng = np.random.RandomState(1)
    contribs = rng.randn(2, 8, 8).astype(np.float32)
    base = dist.shard_tensor(pt.to_tensor(contribs[0]), mesh2d,
                             [Partial(), Replicate()])
    stacked = pt.to_tensor(contribs)
    stacked.data = jax.device_put(stacked.data, base.data.sharding)
    stacked._partial_dims = base._partial_dims
    stacked._partial_reduce = base._partial_reduce

    r = dist.reshard(stacked, mesh2d, [Replicate(), Replicate()])
    np.testing.assert_allclose(r.numpy(), contribs.sum(0), rtol=1e-6)

    s = dist.reshard(stacked, mesh2d, [Replicate(), Shard(1)])
    assert s.data.sharding.spec == P(None, "y")
    np.testing.assert_allclose(s.numpy(), contribs.sum(0), rtol=1e-6)


def test_partial_mean_reduce_type(mesh2d):
    t = pt.to_tensor(np.random.RandomState(2).randn(4, 4).astype(np.float32))
    p = dist.shard_tensor(t, mesh2d, [Partial("avg"), Replicate()])
    r = dist.reshard(p, mesh2d, [Replicate(), Replicate()])
    np.testing.assert_allclose(r.numpy(), t.numpy(), rtol=1e-6)
    u = dist.unshard_dtensor(p)  # reduces pending partials too
    np.testing.assert_allclose(u.numpy(), t.numpy(), rtol=1e-6)


def test_cross_mesh_reshard():
    # same 8 devices, different mesh topology/dim names — the reference
    # needs dedicated cross-mesh reshard functions; here it is one
    # resharding device_put
    t = pt.to_tensor(np.random.RandomState(3).randn(8, 8).astype(np.float32))
    mesh_a = ProcessMesh(np.arange(8), dim_names=["x"])
    mesh_b = ProcessMesh(np.arange(8).reshape(4, 2), dim_names=["a", "b"])
    da = dist.shard_tensor(t, mesh_a, [Shard(0)])
    db = dist.reshard(da, mesh_b, [Shard(1), Shard(0)])
    assert db.data.sharding.spec == P("b", "a")
    np.testing.assert_array_equal(db.numpy(), t.numpy())
    # and partials survive a mesh change (reduced on the OLD mesh axis)
    pa = dist.shard_tensor(t, mesh_a, [Partial()])
    rb = dist.reshard(pa, mesh_b, [Replicate(), Shard(0)])
    # mesh_b dim 1 ("b") shards tensor dim 0
    assert rb.data.sharding.spec == P("b", None)
    np.testing.assert_allclose(rb.numpy(), t.numpy())


def test_shard_tensor_validation(mesh2d):
    t = pt.to_tensor(np.zeros((4, 4), np.float32))
    with pytest.raises(ValueError):
        dist.shard_tensor(t, mesh2d, [Shard(0)])  # wrong placement count
    with pytest.raises(ValueError):
        dist.shard_tensor(t, mesh2d, [Shard(5), Replicate()])


def test_mpu_layers_match_dense():
    init_hybrid_mesh(dp=2, pp=1, tp=4)
    try:
        col = dist.mpu.ColumnParallelLinear(16, 32, gather_output=False)
        row = dist.mpu.RowParallelLinear(32, 16, input_is_parallel=True)
        x = pt.to_tensor(np.random.randn(4, 16).astype(np.float32))
        out = row(col(x))
        assert out.shape == [4, 16]
        # numerics match composing plain matmuls on the same weights
        ref = (x.numpy() @ col.weight.numpy() + col.bias.numpy()) \
            @ row.weight.numpy() + row.bias.numpy()
        np.testing.assert_allclose(out.numpy(), ref, rtol=1e-5, atol=1e-5)
        # weights really are tp-sharded
        assert col.weight.data.sharding.spec == P(None, "tp")
        assert row.weight.data.sharding.spec == P("tp", None)
        emb = dist.mpu.VocabParallelEmbedding(64, 8)
        tok = pt.to_tensor(np.array([[1, 2], [3, 63]]))
        assert emb(tok).shape == [2, 2, 8]
        with pytest.raises(ValueError):
            dist.mpu.ColumnParallelLinear(16, 30)  # 30 % 4 != 0
    finally:
        from paddle_tpu.parallel import mesh as M
        M._GLOBAL_MESH = None


def test_fleet_init_and_wrappers():
    strategy = dist.fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 2, "mp_degree": 2,
                               "pp_degree": 2, "sharding_degree": 1}
    try:
        dist.fleet.init(is_collective=True, strategy=strategy)
        hm = dist.fleet.get_hybrid_communicate_group()
        assert (hm.dp_degree, hm.pp_degree, hm.tp_degree) == (2, 2, 2)
        m = pt.nn.Linear(4, 4)
        assert dist.fleet.distributed_model(m) is m
        assert dist.fleet.worker_num() == 1
    finally:
        from paddle_tpu.parallel import mesh as M
        M._GLOBAL_MESH = None


def test_single_process_collectives_identity():
    t = pt.to_tensor(np.ones((4,), np.float32))
    out = dist.all_reduce(t)
    np.testing.assert_array_equal(out.numpy(), np.ones(4, np.float32))
    got = dist.all_gather(tensor=t)
    assert len(got) == 1
    objs = []
    dist.all_gather_object(objs, {"a": 1})
    assert objs == [{"a": 1}]
    assert dist.get_rank() == 0 and dist.get_world_size() == 1
    dist.barrier()


def test_functional_collectives_in_shard_map():
    from jax import shard_map
    hm = init_hybrid_mesh(dp=8, pp=1, tp=1, set_global=False)
    x = jnp.arange(8.0)

    f = shard_map(lambda v: dist.functional.all_reduce(v, "dp"),
                  mesh=hm.mesh, in_specs=P("dp"), out_specs=P("dp"))
    np.testing.assert_allclose(np.asarray(f(x)), np.full(8, 28.0))

    g = shard_map(lambda v: dist.functional.send_recv_next(v, "dp", 8),
                  mesh=hm.mesh, in_specs=P("dp"), out_specs=P("dp"))
    np.testing.assert_allclose(np.asarray(g(x)),
                               np.roll(np.arange(8.0), 1))


def test_shard_layer_and_optimizer():
    mesh = ProcessMesh(np.arange(8).reshape(8), dim_names=["dp"])
    m = pt.nn.Linear(4, 4)
    dist.shard_layer(m, mesh)
    assert m.weight.data.sharding is not None
    opt = pt.optimizer.AdamW(learning_rate=1e-3, parameters=m.parameters())
    dist.shard_optimizer(opt)
    x = pt.to_tensor(np.random.randn(8, 4).astype(np.float32))
    loss = (m(x) ** 2).mean()
    loss.backward()
    opt.step()
    opt.clear_grad()


def test_group_sharded_parallel_stages():
    hm = init_hybrid_mesh(dp=8, pp=1, tp=1)
    try:
        m = pt.nn.Linear(8, 8)
        opt = pt.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=m.parameters())
        m, opt, _ = dist.group_sharded_parallel(m, opt, level="p_g_os")
        assert m.weight.data.sharding.spec == P("dp", None)
        x = pt.to_tensor(np.random.randn(8, 8).astype(np.float32))
        loss = (m(x) ** 2).mean()
        loss.backward()
        opt.step()
        opt.clear_grad()
        # stage-1: moment accumulators got the dp layout
        mom = opt._accumulators["moment1"][id(m.weight)]
        assert mom.data.sharding.spec in (P("dp"), P("dp", None))
        with pytest.raises(ValueError):
            dist.group_sharded_parallel(m, opt, level="bogus")
    finally:
        from paddle_tpu.parallel import mesh as M
        M._GLOBAL_MESH = None


def test_sequence_parallel_layers():
    hm = init_hybrid_mesh(dp=1, pp=1, tp=8)
    try:
        from paddle_tpu.distributed import sequence_parallel as sp
        col = sp.ColumnSequenceParallelLinear(16, 32, gather_output=False)
        row = sp.RowSequenceParallelLinear(32, 16)
        x = pt.to_tensor(np.random.randn(2, 8, 16).astype(np.float32))
        out = row(col(x))
        assert out.shape == [2, 8, 16]
        assert out.data.sharding.spec == P(None, "tp", None)
    finally:
        from paddle_tpu.parallel import mesh as M
        M._GLOBAL_MESH = None
