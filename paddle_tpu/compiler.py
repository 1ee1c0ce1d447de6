"""CompiledProgram (ref: python/paddle/fluid/compiler.py).

The reference's with_data_parallel clones the graph per GPU and inserts NCCL
allreduce. TPU redesign: the program is unchanged; data parallelism = shard
the feed batch over the mesh 'dp' axis, replicate params, and let XLA insert
AllReduce over ICI inside the already-jitted step.

BuildStrategy knobs fall in three groups on TPU:
- `fuse_elewise_add_act_ops` / `fuse_all_optimizer_ops` /
  `fuse_all_reduce_ops` drive the program-level IR pass pipeline
  (paddle_tpu/ir/): the Program's op list is rewritten BEFORE the
  Executor traces it — op fusion cuts trace/lower time and jaxpr size,
  and the allreduce bucketing pass regroups gradient sync for
  comm/compute overlap (ir/bucket_allreduce.py);
- `enable_inplace` / `memory_optimize` map onto XLA buffer donation of
  the training state (executor.py);
- the rest (reduce_strategy, …) are subsumed by XLA/GSPMD and accepted
  for API compat only.
"""
from __future__ import annotations

import jax
from jax.sharding import NamedSharding, PartitionSpec


class BuildStrategy:
    """ref: framework/details/build_strategy.h knobs.

    Live on TPU:
    - `fuse_elewise_add_act_ops`: IR pass collapsing elementwise_add +
      relu/sigmoid/tanh pairs into one fused op before tracing
      (ir/fuse_act.py);
    - `fuse_all_optimizer_ops`: IR pass coalescing the per-param
      sgd/momentum/adam update ops into one multi-tensor op over a
      flattened param bundle (ir/fuse_optimizer.py) — traced op count and
      jaxpr size drop by O(#params);
    - `fuse_all_reduce_ops` (default True): IR pass splitting the
      per-gradient `c_allreduce_sum` ops fleet's minimize emits into
      size-capped buckets (`PADDLE_TPU_ALLREDUCE_BUCKET_MB`, one fused
      collective per bucket dispatched right after its gradients exist,
      ir/bucket_allreduce.py) so bucket comm overlaps the remaining
      backward compute instead of one tail-synchronous reduction;
      bitwise-identical to the unbucketed ops at `comm_dtype=f32`;
    - `enable_inplace` / `memory_optimize`, which map onto XLA buffer
      donation as described below.
    reduce_strategy etc. are XLA's job and remain accepted-for-compat
    no-ops.

    `enable_inplace` and `memory_optimize` map
    onto XLA buffer donation of the training state. The default (None) lets
    the Executor donate parameter/optimizer-state buffers into the jitted
    step (in-place HBM update, no transient 2× parameter footprint);
    setting either to False runs the step copy-in/copy-out — pre-step
    buffers stay valid, at the cost of peak memory. Fetch-aliased
    persistables are always excluded from donation regardless of the knob
    (the Executor guards them; see executor.py)."""

    class ReduceStrategy:
        AllReduce = 0
        Reduce = 1

    class GradientScaleStrategy:
        CoeffNumDevice = 0
        One = 1
        Customized = 2

    def __init__(self):
        self.reduce_strategy = BuildStrategy.ReduceStrategy.AllReduce
        self.gradient_scale_strategy = \
            BuildStrategy.GradientScaleStrategy.CoeffNumDevice
        self.fuse_all_reduce_ops = True
        self.fuse_elewise_add_act_ops = False
        self.fuse_bn_act_ops = False
        self.fuse_all_optimizer_ops = False
        self.memory_optimize = None
        self.enable_inplace = None
        self.sync_batch_norm = False
        self.num_trainers = 1
        self.trainer_id = 0


class ExecutionStrategy:
    """ref: framework/details/execution_strategy.h knobs.

    Live on TPU: `num_inflight_steps` — setting it > 1 turns the
    Executor's training loop into the async pipeline (executor.py): up to
    that many dispatched steps stay outstanding, fetches come back as
    non-blocking :class:`~paddle_tpu.core.fetch_handle.FetchHandle` s, and
    the executor blocks on the oldest handle only when the window is full.
    `2` is classic double buffering (host feed prep + dispatch of step N+1
    overlap device execution of step N). The
    `PADDLE_TPU_ASYNC` env var overrides it either way; `num_threads` /
    `num_iteration_per_drop_scope` stay accepted-for-compat no-ops (the
    step is one XLA program; scopes hold no transient kernels)."""

    def __init__(self):
        self.num_threads = 1
        self.num_iteration_per_drop_scope = 100
        self.use_experimental_executor = False
        self.num_inflight_steps = 1


class CompiledProgram:
    def __init__(self, program_or_graph, build_strategy=None,
                 exec_strategy=None):
        self._program = program_or_graph
        self._build_strategy = build_strategy or BuildStrategy()
        self._exec_strategy = exec_strategy
        self._data_sharding = None
        self._places = None

    def with_data_parallel(self, loss_name=None, build_strategy=None,
                           exec_strategy=None, share_vars_from=None,
                           places=None):
        """Shard feeds over the partitioner's data axes (the 'batch'
        logical axis — 'dp', or dp×fsdp on a composed mesh); without a
        configured mesh, a flat all-device 'dp' mesh is built."""
        from .partition import get_partitioner, make_mesh
        if build_strategy is not None:
            self._build_strategy = build_strategy
        if exec_strategy is not None:
            self._exec_strategy = exec_strategy
        sharding = get_partitioner().data_sharding()
        if sharding is None:
            n = len(jax.devices())
            sharding = NamedSharding(make_mesh({'dp': n}),
                                     PartitionSpec('dp'))
        self._data_sharding = sharding
        self._places = places
        return self

    def _compile(self, *a, **k):
        return self
