"""Fleet runtime unit suite (ISSUE 12): strict-parse bootstrap env,
cross-host primitives (single-host degenerate forms), the poison-flag
sentinel with its watchdog hook, partitioner-sharded checkpoints
(forced-sharded on the single-process 8-device mesh), DataLoader per-host
sharding, sync-BN parity, and the LARS large-batch pieces. One drill here
starts REAL ``jax.distributed`` workers (1 and 2, through the executor
spine); kill-and-resume and poison propagation across processes are in
test_fleet_crash_resume.py."""
import json
import os

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers as L
from shared_programs import run_fleet_script


# One trainer of a data-parallel fleet through the product spine: fleet env
# bootstrap, fleet.distributed_optimizer, an Executor fed THIS host's rows of
# the global batch (weak scaling: the per-host batch is fixed, so the global
# batch grows with the fleet). Rank 0 writes what it saw.
FLEET_WORKER = r'''
import json, sys
import numpy as np
from paddle_tpu.fleet_runtime import bootstrap
bootstrap()
import jax
import paddle_tpu as fluid
from paddle_tpu import layers as L
from paddle_tpu.parallel import DistributedStrategy, fleet

result_path, per_host, steps = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
n, rank = jax.process_count(), jax.process_index()
global_batch = per_host * n

fluid.seed(7)
main, start = fluid.Program(), fluid.Program()
with fluid.program_guard(main, start):
    x = L.data('bx', [32], dtype='float32')
    y = L.data('by', [1], dtype='float32')
    h = L.fc(L.fc(x, size=32, act='relu'), size=32, act='relu')
    loss = L.mean(L.square_error_cost(L.fc(h, size=1), y))
    fleet.init()
    fleet.distributed_optimizer(
        fluid.optimizer.Momentum(0.01, momentum=0.9),
        strategy=DistributedStrategy()).minimize(loss)

exe = fluid.Executor()
exe.run(start)
rng = np.random.RandomState(0)
X = rng.randn(global_batch, 32).astype('float32')
Y = rng.randn(global_batch, 1).astype('float32')
feed = {'bx': X[rank::n], 'by': Y[rank::n]}
losses = [float(np.asarray(exe.run(main, feed=feed, fetch_list=[loss])[0]))
          for _ in range(steps)]
if rank == 0:
    with open(result_path, 'w') as f:
        json.dump({'nproc': n, 'global_batch': global_batch,
                   'rows_fed_here': int(feed['bx'].shape[0]),
                   'losses': losses}, f)
'''


@pytest.mark.parametrize('nproc', [1, 2])
def test_real_distributed_workers_train_to_the_end(tmp_path, nproc):
    """1 and 2 real ``jax.distributed`` CPU workers (one process a trainer,
    gloo collectives, the full PADDLE_* env from ``local_fleet``) both run
    the data-parallel loop to its end: every rank exits 0, the global
    batch is the per-host batch times the fleet, and the loss falls."""
    result = tmp_path / 'result.json'
    rcs, output = run_fleet_script(tmp_path, nproc, FLEET_WORKER,
                                   [result, 2048, 6])
    assert rcs == [0] * nproc, output
    got = json.loads(result.read_text())
    assert got['nproc'] == nproc
    assert got['global_batch'] == 2048 * nproc
    assert got['rows_fed_here'] == 2048
    assert np.isfinite(got['losses']).all()
    assert got['losses'][-1] < got['losses'][0]


# ---------------------------------------------------------------------------
# strict-parse env discovery
# ---------------------------------------------------------------------------

def _env(**kw):
    return {k: str(v) for k, v in kw.items()}


def test_discover_none_when_unset():
    from paddle_tpu.fleet_runtime.bootstrap import discover_fleet_env
    assert discover_fleet_env({}) is None


def test_discover_single_host():
    from paddle_tpu.fleet_runtime.bootstrap import discover_fleet_env
    spec = discover_fleet_env(_env(PADDLE_TRAINERS_NUM=1))
    assert spec.num_trainers == 1 and spec.trainer_id == 0


def test_discover_full_fleet_env():
    from paddle_tpu.fleet_runtime.bootstrap import discover_fleet_env
    spec = discover_fleet_env(_env(
        PADDLE_TRAINERS_NUM=2, PADDLE_TRAINER_ID=1,
        PADDLE_TRAINER_ENDPOINTS='a:1,b:2', PADDLE_CURRENT_ENDPOINT='b:2'))
    assert spec.num_trainers == 2 and spec.trainer_id == 1
    assert spec.coordinator_address == 'a:1'      # endpoint 0 convention
    assert spec.endpoints == ['a:1', 'b:2']


@pytest.mark.parametrize('env, frag', [
    (_env(PADDLE_TRAINERS_NUM='two'), 'must be an integer'),
    (_env(PADDLE_TRAINER_ID=0), 'PADDLE_TRAINERS_NUM is missing'),
    (_env(PADDLE_TRAINERS_NUM=2), 'PADDLE_TRAINER_ID is missing'),
    (_env(PADDLE_TRAINERS_NUM=2, PADDLE_TRAINER_ID=2,
          PADDLE_TRAINER_ENDPOINTS='a:1,b:2'), 'outside'),
    (_env(PADDLE_TRAINERS_NUM=2, PADDLE_TRAINER_ID=0,
          PADDLE_TRAINER_ENDPOINTS='a:1'), 'lists 1 endpoints'),
    (_env(PADDLE_TRAINERS_NUM=2, PADDLE_TRAINER_ID=0,
          PADDLE_TRAINER_ENDPOINTS='a:1,a:1'), 'duplicate'),
    (_env(PADDLE_TRAINERS_NUM=2, PADDLE_TRAINER_ID=0,
          PADDLE_TRAINER_ENDPOINTS='a:1,b:2',
          PADDLE_CURRENT_ENDPOINT='c:3'), 'not in'),
    (_env(PADDLE_TRAINERS_NUM=2, PADDLE_TRAINER_ID=0,
          PADDLE_TRAINER_ENDPOINTS='a:1,b:2',
          PADDLE_CURRENT_ENDPOINT='b:2'), 'contradictory rank'),
    (_env(PADDLE_TRAINERS_NUM=2, PADDLE_TRAINER_ID=0), 'rendezvous'),
    (_env(PADDLE_TRAINERS_NUM=2, PADDLE_TRAINER_ID=0,
          PADDLE_TRAINER_ENDPOINTS='bare'), 'host:port'),
])
def test_discover_strict_parse_raises_listing_vars(env, frag):
    from paddle_tpu.fleet_runtime.bootstrap import discover_fleet_env
    with pytest.raises(ValueError) as ei:
        discover_fleet_env(env)
    msg = str(ei.value)
    assert frag in msg
    # every error names the full expected-variable contract
    for var in ('PADDLE_TRAINERS_NUM', 'PADDLE_TRAINER_ID',
                'PADDLE_TRAINER_ENDPOINTS', 'PADDLE_CURRENT_ENDPOINT'):
        assert var in msg


def test_role_maker_reads_env_and_raises_on_contradiction(monkeypatch):
    from paddle_tpu.parallel.fleet import PaddleCloudRoleMaker
    monkeypatch.setenv('PADDLE_TRAINERS_NUM', '4')
    monkeypatch.setenv('PADDLE_TRAINER_ID', '3')
    monkeypatch.setenv('PADDLE_TRAINER_ENDPOINTS', 'a:1,b:2,c:3,d:4')
    monkeypatch.setenv('PADDLE_CURRENT_ENDPOINT', 'd:4')
    rm = PaddleCloudRoleMaker()
    assert rm.worker_num() == 4
    assert rm.worker_index() == 3
    assert not rm.is_first_worker()
    assert rm.worker_endpoints() == ['a:1', 'b:2', 'c:3', 'd:4']

    monkeypatch.setenv('PADDLE_TRAINER_ID', '9')
    with pytest.raises(ValueError, match='outside'):
        PaddleCloudRoleMaker().generate_role()


def test_incubate_role_maker_module_exports():
    from paddle_tpu.incubate.fleet.base import role_maker
    assert role_maker.MPISymetricRoleMaker is role_maker.PaddleCloudRoleMaker
    assert role_maker.GeneralRoleMaker is role_maker.PaddleCloudRoleMaker


# ---------------------------------------------------------------------------
# cross-host primitives: single-host degenerate forms
# ---------------------------------------------------------------------------

def test_primitives_single_host():
    from paddle_tpu import fleet_runtime as fr
    fr.fleet_barrier('t')                       # no-op, no raise
    assert fr.broadcast_from_host0({'a': 1}) == {'a': 1}
    assert fr.all_hosts_agree({'step': 3})
    assert fr.fleet_allreduce_scalars([1.0, 2.5]) == [1.0, 2.5]
    with pytest.raises(ValueError, match='unknown op'):
        fr.fleet_allreduce_scalars([1.0], op='median')


def test_bootstrap_single_host_wires_mesh():
    from paddle_tpu import fleet_runtime as fr
    from paddle_tpu.partition import get_partitioner, reset_partitioner
    reset_partitioner()
    try:
        assert fr.bootstrap() is None            # no fleet env → None spec
        import jax
        assert get_partitioner().axis_sizes() == {'dp': jax.device_count()}
    finally:
        reset_partitioner()


# ---------------------------------------------------------------------------
# the poison-flag sentinel (file backend) + watchdog hook
# ---------------------------------------------------------------------------

def test_sentinel_post_check_clear(tmp_path, monkeypatch):
    from paddle_tpu.fleet_runtime.coordinator import FleetSentinel
    monkeypatch.setenv('PADDLE_TPU_FLEET_DIR', str(tmp_path))
    a = FleetSentinel(source=0)
    b = FleetSentinel(source=1)
    assert b.check() is None
    rec = a.post('divergence detected', step=12, kind='supervisor')
    assert rec['source'] == 0
    # the poster never poisons itself; every OTHER host sees it
    assert a.check() is None or a.check()['source'] != 0
    got = b.check()
    assert got is not None and got['source'] == 0
    assert got['reason'] == 'divergence detected' and got['step'] == 12
    b.clear()
    assert b.check() is None


def test_sentinel_raise_if_poisoned(tmp_path, monkeypatch):
    from paddle_tpu.fleet_runtime.coordinator import (FleetSentinel,
                                                      FleetPoisoned)
    monkeypatch.setenv('PADDLE_TPU_FLEET_DIR', str(tmp_path))
    FleetSentinel(source=0).post('boom', step=1)
    with pytest.raises(FleetPoisoned, match='boom'):
        FleetSentinel(source=1).raise_if_poisoned()


def test_watchdog_breach_posts_poison(tmp_path, monkeypatch):
    """The fleet propagation ladder's watchdog rung: a deadline breach on
    one host posts the poison flag BEFORE the abort exit."""
    from paddle_tpu.fleet_runtime import coordinator as coord
    from paddle_tpu.resilience.watchdog import Watchdog
    monkeypatch.setenv('PADDLE_TPU_FLEET_DIR', str(tmp_path))
    coord.clear_sentinel()
    try:
        coord.install_sentinel(source=0)
        wd = Watchdog(floor_s=0.05, cold_s=0.05, abort=False,
                      dump_dir=str(tmp_path), poll_s=0.01)
        lease = wd.arm('fleet_step')
        import time
        deadline = time.monotonic() + 5
        while not wd.breaches and time.monotonic() < deadline:
            time.sleep(0.02)
        wd.stop()
        assert wd.breaches, 'watchdog never fired'
        observer = coord.FleetSentinel(source=1)
        rec = observer.check()
        assert rec is not None and rec['kind'] == 'watchdog'
        assert 'fleet_step' in rec['reason']
    finally:
        coord.clear_sentinel()


def test_manager_exits_for_resume_on_poison(tmp_path, monkeypatch):
    """CheckpointManager.end_of_step returns True (exit-for-resume) when
    another host poisoned the fleet, without saving."""
    from paddle_tpu import resilience
    from paddle_tpu.fleet_runtime import coordinator as coord
    monkeypatch.setenv('PADDLE_TPU_FLEET_DIR', str(tmp_path))
    coord.clear_sentinel()
    try:
        coord.install_sentinel(source=0)
        mgr = resilience.CheckpointManager(
            str(tmp_path / 'ck'), every_n_steps=1, async_save=False,
            install_signal_handlers=False)
        coord.FleetSentinel(source=9).post('peer died', step=3)
        calls = []
        stop = mgr.end_of_step(4, lambda: calls.append(1) or {})
        assert stop is True
        assert mgr.fleet_poisoned['reason'] == 'peer died'
        assert not calls, 'poisoned boundary must not capture state'
        assert mgr.latest() is None, 'poisoned boundary must not save'
        mgr.close()
    finally:
        coord.clear_sentinel()


# ---------------------------------------------------------------------------
# sharded checkpoints (forced, single process, 8-device mesh)
# ---------------------------------------------------------------------------

@pytest.fixture
def fsdp_mesh():
    from paddle_tpu.partition import configure, reset_partitioner
    reset_partitioner()
    configure(mesh_shape={'fsdp': 8})
    yield
    reset_partitioner()


def _sharded_state(part):
    import jax
    import jax.numpy as jnp
    rng = np.random.RandomState(0)
    W = rng.randn(16, 8).astype(np.float32)
    V = rng.randn(16, 8).astype(np.float32)
    w = jax.device_put(jnp.asarray(W), part.param_sharding('w', W.shape))
    v = jax.device_put(jnp.asarray(V), part.param_sharding('w_velocity',
                                                           V.shape))
    lr = jnp.asarray([0.1], jnp.float32)        # replicated scalar-ish
    return {'scope/w': w, 'scope/w_velocity': v, 'scope/lr': lr}, \
        {'scope/w': W, 'scope/w_velocity': V,
         'scope/lr': np.asarray([0.1], np.float32)}


def test_forced_sharded_roundtrip_bitwise(tmp_path, fsdp_mesh, monkeypatch):
    from paddle_tpu import resilience
    from paddle_tpu.partition import get_partitioner
    monkeypatch.setenv('PADDLE_TPU_FLEET_SHARDED', '1')
    state, want = _sharded_state(get_partitioner())
    mgr = resilience.CheckpointManager(str(tmp_path), every_n_steps=1,
                                       async_save=False,
                                       install_signal_handlers=False)
    mgr.save(7, state, {'rng': {'global_seed': 3},
                        'loader': {'epoch': 1, 'batch': 2}})
    ck = mgr.latest()
    assert ck.sharded and ck.manifest['world'] == 1
    arrays, meta = mgr.restore(ck)
    for k in want:
        assert np.array_equal(arrays[k], want[k]), k
    # this host's own meta came back through the shard manifest overlay
    assert meta['rng'] == {'global_seed': 3}
    assert meta['loader'] == {'epoch': 1, 'batch': 2}
    mgr.close()


def test_forced_sharded_tile_layout(tmp_path, fsdp_mesh, monkeypatch):
    """Tiles mirror the fsdp placement: the 2-D fsdp-sharded arrays are
    stored as 8 row tiles, replicated values as ONE full tile."""
    from paddle_tpu.fleet_runtime import sharded_ckpt as sc
    from paddle_tpu.partition import get_partitioner
    monkeypatch.setenv('PADDLE_TPU_FLEET_SHARDED', '1')
    state, _ = _sharded_state(get_partitioner())
    sm = sc.write_host_shard(str(tmp_path), 3, state, rank=0, world=1)
    tiles_w = sm['arrays']['scope/w']['tiles']
    assert len(tiles_w) == 8
    assert sorted(t['index'][0] for t in tiles_w) == \
        [[2 * i, 2 * i + 2] for i in range(8)]
    assert len(sm['arrays']['scope/lr']['tiles']) == 1


def test_sharded_strict_env(monkeypatch):
    from paddle_tpu.fleet_runtime.sharded_ckpt import sharded_save_enabled
    monkeypatch.setenv('PADDLE_TPU_FLEET_SHARDED', 'yes')
    with pytest.raises(ValueError, match='must be 0 or 1'):
        sharded_save_enabled()


def test_torn_host_shard_skipped_by_discovery(tmp_path, fsdp_mesh,
                                              monkeypatch):
    """A missing or truncated HOST SHARD makes the whole fleet checkpoint
    invisible — discovery falls back to the previous valid one."""
    from paddle_tpu import resilience
    from paddle_tpu.partition import get_partitioner
    monkeypatch.setenv('PADDLE_TPU_FLEET_SHARDED', '1')
    state, _ = _sharded_state(get_partitioner())
    mgr = resilience.CheckpointManager(str(tmp_path), async_save=False,
                                       install_signal_handlers=False)
    mgr.save(3, dict(state), {})
    mgr.save(6, dict(state), {})
    assert mgr.latest().step == 6
    shard6 = tmp_path / 'ckpt-00000006.shard00of01.npz'
    with open(shard6, 'r+b') as f:
        f.truncate(64)                           # torn shard write
    assert mgr.latest().step == 3
    os.unlink(shard6)                            # shard vanished entirely
    assert mgr.latest().step == 3
    mgr.close()


def test_sharded_gc_deletes_shard_files(tmp_path, fsdp_mesh, monkeypatch):
    from paddle_tpu import resilience
    from paddle_tpu.partition import get_partitioner
    monkeypatch.setenv('PADDLE_TPU_FLEET_SHARDED', '1')
    state, _ = _sharded_state(get_partitioner())
    mgr = resilience.CheckpointManager(str(tmp_path), keep=1,
                                       async_save=False,
                                       install_signal_handlers=False)
    for step in (1, 2, 3):
        mgr.save(step, dict(state), {})
    names = sorted(os.listdir(tmp_path))
    assert not any('00000001' in n or '00000002' in n for n in names), names
    assert any('00000003' in n for n in names)
    mgr.close()


def test_read_rejects_incomplete_tiles(tmp_path, fsdp_mesh, monkeypatch):
    """Tile coverage is validated: a shard manifest claiming fewer
    elements than the global shape raises instead of returning
    silently-partial state."""
    from paddle_tpu.fleet_runtime import sharded_ckpt as sc
    from paddle_tpu.resilience import snapshot as snap
    from paddle_tpu.partition import get_partitioner
    monkeypatch.setenv('PADDLE_TPU_FLEET_SHARDED', '1')
    state, _ = _sharded_state(get_partitioner())
    sc.write_host_shard(str(tmp_path), 5, state, rank=0, world=1)
    sc.commit_fleet_manifest(str(tmp_path), 5, 1)
    # drop one tile from the shard manifest (simulated writer bug)
    mpath = tmp_path / 'ckpt-00000005.shard00of01.json'
    m = json.loads(mpath.read_text())
    m['arrays']['scope/w']['tiles'] = m['arrays']['scope/w']['tiles'][:-1]
    mpath.write_text(json.dumps(m))
    # shard payload is untouched so discovery still validates...
    ck = snap.latest_checkpoint(str(tmp_path))
    assert ck is not None
    with pytest.raises(ValueError, match='cover'):
        snap.read_checkpoint(ck)


# ---------------------------------------------------------------------------
# DataLoader per-host sharding
# ---------------------------------------------------------------------------

def _loader(batches):
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        x = L.data('flx', [4], dtype='float32')
    loader = fluid.DataLoader.from_generator(
        feed_list=[main.global_block().var('flx')], capacity=2)
    loader.set_batch_generator(lambda: iter(batches))
    return loader


def test_loader_shard_slices_rows():
    rng = np.random.RandomState(0)
    batches = [(rng.randn(8, 4).astype('float32'),) for _ in range(3)]
    loader = _loader(batches).shard_for_fleet(num_shards=2, shard_id=1)
    got = [b['flx'] for b in loader()]
    assert len(got) == 3
    for full, mine in zip(batches, got):
        assert np.array_equal(np.asarray(mine), full[0][1::2])


def test_loader_shard_identity_and_validation():
    batches = [(np.zeros((4, 4), np.float32),)]
    loader = _loader(batches)
    assert loader.shard_for_fleet(num_shards=1, shard_id=0) is loader
    assert loader._shard_n is None               # 1-host fleet = no-op
    with pytest.raises(ValueError, match='outside'):
        loader.shard_for_fleet(num_shards=2, shard_id=2)


def test_loader_shard_batch_too_small():
    loader = _loader([(np.zeros((1, 4), np.float32),)])
    loader.shard_for_fleet(num_shards=2, shard_id=0)
    with pytest.raises(ValueError, match='smaller than'):
        list(loader())


def test_loader_shard_cursor_is_global(tmp_path):
    """The resume cursor counts GLOBAL batches: skipping applies before
    the shard slice, so a restored host re-reads exactly its own rows of
    the remaining stream."""
    rng = np.random.RandomState(1)
    batches = [(rng.randn(4, 4).astype('float32'),) for _ in range(4)]
    loader = _loader(batches).shard_for_fleet(num_shards=2, shard_id=0)
    it = iter(loader())
    next(it), next(it)
    st = loader.state_dict()
    assert st['batch'] == 2
    del it
    loader2 = _loader(batches).shard_for_fleet(num_shards=2, shard_id=0)
    loader2.set_state_dict(st)
    rest = [b['flx'] for b in loader2()]
    assert len(rest) == 2
    assert np.array_equal(np.asarray(rest[0]), batches[2][0][0::2])


# ---------------------------------------------------------------------------
# sync-BN
# ---------------------------------------------------------------------------

def test_sync_bn_matches_single_process_global_batch():
    """sync_stats under explicit SPMD (shard_map over the 8-way data
    mesh) reproduces single-process global-batch statistics; without it,
    per-shard stats diverge."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from paddle_tpu.core import compat
    from paddle_tpu.ops.nn_ops import batch_norm
    from paddle_tpu.partition import configure, get_partitioner, \
        reset_partitioner
    reset_partitioner()
    try:
        configure(mesh_shape={'dp': 8})
        mesh = get_partitioner().mesh
        rng = np.random.RandomState(0)
        X = (rng.randn(32, 4, 6, 6) * 3 + 1).astype('float32')
        scale = np.ones(4, 'float32')
        bias = np.zeros(4, 'float32')
        mean = np.zeros(4, 'float32')
        var = np.ones(4, 'float32')
        y_ref, m_ref, v_ref = batch_norm(X, scale, bias, mean, var)

        def body(x, sync):
            y, m, v = batch_norm(x, scale, bias, mean, var,
                                 sync_stats=sync)
            return (y, compat.pcast(m, 'dp', to='varying'),
                    compat.pcast(v, 'dp', to='varying'))

        f = compat.shard_map(lambda x: body(x, True), mesh=mesh,
                             in_specs=P('dp'),
                             out_specs=(P('dp'), P(), P()))
        y, m, v = f(jnp.asarray(X))
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(m), np.asarray(m_ref),
                                   atol=1e-6)
        np.testing.assert_allclose(np.asarray(v), np.asarray(v_ref),
                                   atol=1e-5)

        f0 = compat.shard_map(lambda x: body(x, False)[0], mesh=mesh,
                              in_specs=P('dp'), out_specs=P('dp'))
        y_unsync = f0(jnp.asarray(X))
        assert not np.allclose(np.asarray(y_unsync), np.asarray(y_ref),
                               atol=1e-5)
    finally:
        reset_partitioner()


def test_sync_bn_static_layer_attr_and_gspmd_identity():
    """The layer threads sync_stats through; on the GSPMD executor (no
    bound axis) it is the identity — same losses with and without."""
    def run(sync):
        fluid.seed(77)
        main, start = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, start):
            x = L.data('sx', [4, 6, 6], dtype='float32')
            y = L.data('sy', [1], dtype='float32')
            h = L.batch_norm(L.conv2d(x, num_filters=4, filter_size=3,
                                      padding=1),
                             act='relu', sync_stats=sync)
            pred = L.fc(h, size=1)
            loss = L.mean(L.square_error_cost(pred, y))
            fluid.optimizer.SGD(0.1).minimize(loss)
        assert any(op.attrs.get('sync_stats') == sync
                   for op in main.global_block().ops
                   if op.type == 'batch_norm')
        exe = fluid.Executor()
        rng = np.random.RandomState(5)
        X = rng.randn(8, 4, 6, 6).astype('float32')
        Y = rng.randn(8, 1).astype('float32')
        with fluid.scope_guard(fluid.Scope()):
            exe.run(start)
            return [np.asarray(exe.run(main, feed={'sx': X, 'sy': Y},
                                       fetch_list=[loss])[0])
                    for _ in range(3)]
    a, b = run(False), run(True)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))


# ---------------------------------------------------------------------------
# LARS large-batch pieces
# ---------------------------------------------------------------------------

def test_lars_exclude_from_weight_decay_static():
    main, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, start):
        x = L.data('lx', [4], dtype='float32')
        y = L.data('ly', [1], dtype='float32')
        pred = L.fc(x, size=1)
        loss = L.mean(L.square_error_cost(pred, y))
        fluid.optimizer.LarsMomentumOptimizer(
            0.1, exclude_from_weight_decay_fn=lambda p: '.b_' in p.name,
        ).minimize(loss)
    ops = [op for op in main.global_block().ops
           if op.type == 'lars_momentum']
    assert len(ops) == 2
    by_wd = {op.attrs['lars_weight_decay'] for op in ops}
    assert by_wd == {0.0, 0.0005}, by_wd
    assert all('epsilon' in op.attrs for op in ops)


def test_lamb_exclude_fn_now_live():
    main, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, start):
        x = L.data('bx', [4], dtype='float32')
        pred = L.fc(x, size=1)
        loss = L.mean(pred)
        fluid.optimizer.LambOptimizer(
            0.01, exclude_from_weight_decay_fn=lambda p: '.b_' in p.name,
        ).minimize(loss)
    wds = sorted(op.attrs['weight_decay']
                 for op in main.global_block().ops if op.type == 'lamb')
    assert wds == [0.0, 0.01]


def test_fused_lars_bitwise_vs_per_param():
    """The multi-tensor LARS bundle is bit-identical to N per-param
    lars_momentum ops (trust-ratio norms reduced at member shape)."""
    from paddle_tpu.ops.fused_ops import fused_lars_momentum
    from paddle_tpu.ops.optimizer_ops import lars_momentum
    rng = np.random.RandomState(3)
    shapes = [(16, 8), (8,), (8, 4)]
    params = [rng.randn(*s).astype('float32') for s in shapes]
    grads = [rng.randn(*s).astype('float32') * 0.1 for s in shapes]
    vels = [np.zeros(s, np.float32) for s in shapes]
    lr = np.float32(0.05)
    fused_p, fused_v = fused_lars_momentum(params, grads, vels, lr)
    for i in range(len(shapes)):
        p_ref, v_ref = lars_momentum(params[i], grads[i], vels[i], lr)
        assert np.array_equal(np.asarray(fused_p[i]), np.asarray(p_ref)), i
        assert np.array_equal(np.asarray(fused_v[i]), np.asarray(v_ref)), i


def test_lars_fuse_pass_groups_and_bitwise():
    """fuse_all_optimizer_ops now covers lars_momentum: N update ops
    collapse into fused groups (excluded params in their OWN group), and
    the trajectory is bitwise pass-on/off."""
    from paddle_tpu.compiler import BuildStrategy, CompiledProgram

    def build():
        fluid.seed(11)
        main, start = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, start):
            x = L.data('fx', [8], dtype='float32')
            y = L.data('fy', [1], dtype='float32')
            h = L.fc(x, size=16, act='relu')
            h = L.fc(h, size=16, act='relu')
            pred = L.fc(h, size=1)
            loss = L.mean(L.square_error_cost(pred, y))
            fluid.optimizer.LarsMomentumOptimizer(
                0.05,
                exclude_from_weight_decay_fn=lambda p: '.b_' in p.name,
            ).minimize(loss)
        return main, start, loss

    from paddle_tpu import ir
    main, start, loss = build()
    bs = BuildStrategy()
    bs.fuse_all_optimizer_ops = True
    opt, ctx = ir.apply_pipeline(main, fetch_names=[loss.name],
                                 build_strategy=bs)
    stats = ctx.stats.get('fuse_all_optimizer_ops', {})
    assert stats.get('fused_groups', 0) >= 2     # wd group + excluded group
    assert any(op.type == 'fused_lars_momentum'
               for op in opt.global_block().ops)

    rng = np.random.RandomState(0)
    X = rng.randn(16, 8).astype('float32')
    Y = rng.randn(16, 1).astype('float32')
    runs = {}
    for tag, on in (('off', False), ('on', True)):
        main, start, loss = build()
        bs = BuildStrategy()
        bs.fuse_all_optimizer_ops = on
        exe = fluid.Executor()
        with fluid.scope_guard(fluid.Scope()):
            exe.run(start)
            cp = CompiledProgram(main, build_strategy=bs)
            runs[tag] = [np.asarray(exe.run(cp, feed={'fx': X, 'fy': Y},
                                            fetch_list=[loss])[0])
                         for _ in range(5)]
    assert all(np.array_equal(a, b)
               for a, b in zip(runs['off'], runs['on']))


def test_lars_example_program_verifies():
    """The large-batch example's program shape passes the static
    verifier: LARS + sync-BN + warmup/poly LR emit only ops with infer
    rules (rule coverage for the new attrs/ops)."""
    from paddle_tpu import analysis
    fluid.seed(1)
    main, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, start):
        x = L.data('image', shape=[3, 8, 8], dtype='float32')
        y = L.data('label', shape=[1], dtype='int64')
        h = L.conv2d(x, num_filters=4, filter_size=3, padding=1)
        h = L.batch_norm(h, act='relu', sync_stats=True)
        h = L.pool2d(h, pool_size=2, pool_type='avg', global_pooling=True)
        logits = L.fc(h, size=10)
        loss = L.mean(L.softmax_with_cross_entropy(logits, y))
        lr = L.linear_lr_warmup(
            L.polynomial_decay(0.1, decay_steps=10,
                               end_learning_rate=1e-4, power=2.0),
            warmup_steps=2, start_lr=0.0, end_lr=0.1)
        fluid.optimizer.LarsMomentumOptimizer(
            lr, exclude_from_weight_decay_fn=lambda p: '.b_' in p.name,
        ).minimize(loss)
    diags = analysis.verify_program(main, fetch_names=[loss.name])
    errors = [d for d in diags if d.severity == 'error']
    assert not errors, errors
