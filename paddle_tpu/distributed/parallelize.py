"""One-call hybrid-parallel orchestration: ``dist.parallelize``.

ref: the reference's three entry points for composing parallelism —
  * `dist.parallelize(model, optimizer, config={dp_config, mp_config,
    pp_config})` (python/paddle/distributed/auto_parallel/intermediate/
    parallelize.py:51,298,322) with per-layer plans ColWiseParallel /
    RowWiseParallel (intermediate/tensor_parallel.py:91,176),
  * `fleet.init(strategy)` -> HybridCommunicateGroup per-axis groups
    (fleet/base/topology.py:189),
  * `fleet.distributed_model` (fleet/model.py:32).

TPU-native form: parallelism degrees become named mesh axes; plans become
GSPMD placements; ZeRO becomes optimizer-state placements
(distributed/sharding.py); PP routes through the single-program pipeline
schedules (distributed/pipeline.py) with Megatron TP *inside* the
pipelined region (models/llama.py LlamaPipeline tp_axis). One call wires
DP x TP x PP x ZeRO from config — the capability the reference's
HybridCommunicateGroup exists for, without its per-axis process groups
(GSPMD + shard_map place the collectives).

Config schema (all keys optional; degree 1 = axis absent):
    {
      "dp_degree": int, "mp_degree": int, "pp_degree": int,
      "dp_config": {"sharding_level": 0|1|2|3},
      "mp_config": {"parallelize_plan": "auto" | {pattern: plan}},
      "pp_config": {"schedule": "1f1b"|"gpipe"|"vpp"|"zero_bubble",
                    "micro_batches": int, "virtual_pp": int,
                    "remat": bool (gpipe/vpp only),
                    "dtype": "bfloat16"|None},
    }
"""
from __future__ import annotations

import fnmatch

import numpy as np

from ..core.tensor import Tensor
from .dist_tensor import shard_tensor
from .parallel import shard_layer
from .placement import Replicate, Shard
from .process_mesh import ProcessMesh
from .sharding import ShardingStage1, ShardingStage2, ShardingStage3
from .sharding import shard_optimizer as _shard_optimizer

__all__ = [
    "parallelize", "ColWiseParallel", "RowWiseParallel",
    "PipelineParallel",
]


class _Plan:
    """Per-layer TP plan marker (ref intermediate/tensor_parallel.py)."""

    def placements_for(self, pname, ndim, mesh, tp_idx):
        raise NotImplementedError


class ColWiseParallel(_Plan):
    """Column-parallel Linear/Embedding: weight [in, out] sharded on the
    output dim, bias sharded (ref tensor_parallel.py:91)."""

    def placements_for(self, pname, ndim, mesh, tp_idx):
        placements = [Replicate()] * mesh.ndim
        placements[tp_idx] = Shard(ndim - 1) if ndim > 1 else Shard(0)
        return placements


class RowWiseParallel(_Plan):
    """Row-parallel Linear: weight [in, out] sharded on the input dim;
    bias replicated (ref tensor_parallel.py:176)."""

    def placements_for(self, pname, ndim, mesh, tp_idx):
        placements = [Replicate()] * mesh.ndim
        if ndim > 1 or pname != "bias":
            placements[tp_idx] = Shard(0)
        return placements


class PipelineParallel:
    """Marker result: the parallelized model for pp_degree > 1. Callable
    like the original causal-LM model — ``model(ids, labels)`` returns
    ``(None, loss)`` with the loss computed inside the pipelined region."""

    def __init__(self, pipe, mesh):
        self._pipe = pipe
        self.mesh = mesh

    def __call__(self, input_ids, labels=None, **kw):
        if labels is None:
            raise ValueError(
                "pipeline-parallel model computes the loss inside the "
                "pipeline; call with labels"
            )
        return None, self._pipe(input_ids, labels)

    def forward(self, *a, **kw):
        return self(*a, **kw)

    def parameters(self):
        return self._pipe.parameters()

    def train_batch(self, input_ids, labels):
        """fleet-style helper (ref fleet/model.py train_batch)."""
        return self._pipe(input_ids, labels)


# The auto plan for Llama-family decoders: the same Megatron layout the
# reference's llama integration model declares by hand
# (test/auto_parallel/hybrid_strategy/semi_auto_parallel_llama_model.py).
_LLAMA_AUTO_PLAN = {
    "*embed_tokens": RowWiseParallel(),   # [vocab, h]: vocab-sharded
                                          # (VocabParallelEmbedding,
                                          # mp_layers.py:49; GSPMD places
                                          # the gather/partial-sum)
    "*q_proj": ColWiseParallel(),
    "*k_proj": ColWiseParallel(),
    "*v_proj": ColWiseParallel(),
    "*gate_proj": ColWiseParallel(),
    "*up_proj": ColWiseParallel(),
    "*o_proj": RowWiseParallel(),
    "*down_proj": RowWiseParallel(),
    "*lm_head": ColWiseParallel(),        # vocab-sharded logits
}


def _build_mesh(dp, mp, pp):
    import jax

    n = dp * mp * pp
    devs = len(jax.devices())
    if n > devs:
        raise ValueError(
            f"dp*mp*pp = {n} exceeds available devices ({devs})"
        )
    shape, names = [], []
    # axis order matches the reference's topology order [data, pipe, model]
    # (fleet/base/topology.py:70) so dp is outermost (DCN-friendly) and tp
    # innermost (ICI-friendly, the scaling-book layout rule)
    for deg, name in ((dp, "dp"), (pp, "pp"), (mp, "tp")):
        if deg > 1:
            shape.append(deg)
            names.append(name)
    if not shape:
        shape, names = [1], ["dp"]
    arr = np.arange(int(np.prod(shape))).reshape(shape)
    return ProcessMesh(arr, names)


def _apply_mp_plan(model, mesh, plan):
    tp_idx = mesh.dim_names.index("tp")
    matched = set()
    for lname, sub in model.named_sublayers(include_self=True):
        hit = None
        for pattern, p in plan.items():
            if fnmatch.fnmatch(lname, pattern):
                hit = p
                break
        if hit is None:
            continue
        matched.add(lname)
        for pname, param in sub.named_parameters(include_sublayers=False):
            size = mesh.shape[tp_idx]
            placements = hit.placements_for(pname, param.ndim, mesh, tp_idx)
            pl = placements[tp_idx]
            if pl.is_shard() and param.shape[pl.get_dim()] % size != 0:
                placements[tp_idx] = Replicate()  # indivisible: keep whole
            d = shard_tensor(param, mesh, placements,
                             stop_gradient=param.stop_gradient)
            param._rebind(d._data, dist_meta=d._dist_meta)
    # everything unmatched is replicated on the mesh so the whole state
    # lives on one device_set (GSPMD requirement)
    shard_layer(model, mesh)
    return matched


class _ShardedInputModel:
    """Shards leading-batch inputs over the dp axis before calling the
    model (the DataParallel input contract, parallel.py:219)."""

    def __init__(self, model, mesh):
        self._model = model
        self.mesh = mesh
        self._dp_idx = (
            mesh.dim_names.index("dp") if "dp" in mesh.dim_names else None
        )

    def _shard_in(self, x):
        if (
            self._dp_idx is not None
            and isinstance(x, Tensor)
            and x._dist_meta is None
            and x.ndim > 0
            and x.shape[0] % self.mesh.shape[self._dp_idx] == 0
        ):
            placements = [Replicate()] * self.mesh.ndim
            placements[self._dp_idx] = Shard(0)
            return shard_tensor(x, self.mesh, placements,
                                stop_gradient=x.stop_gradient)
        return x

    def __call__(self, *args, **kwargs):
        import jax

        # function-scoped like every pallas import: plain
        # `import paddle_tpu` must not load the TPU kernel stack
        from ..kernels.pallas._compat import spmd_axes

        is_t = lambda v: isinstance(v, Tensor)  # noqa: E731
        args = jax.tree_util.tree_map(self._shard_in, args, is_leaf=is_t)
        kwargs = jax.tree_util.tree_map(self._shard_in, kwargs, is_leaf=is_t)
        names = self.mesh.dim_names
        # tell the Pallas kernels traced below which mesh axes the batch
        # and head dims ride on (Mosaic kernels have no SPMD rule)
        with spmd_axes(
            self.mesh.jax_mesh(),
            batch_axis="dp" if "dp" in names else None,
            head_axis="tp" if "tp" in names else None,
        ):
            return self._model(*args, **kwargs)

    def __getattr__(self, name):
        return getattr(self._model, name)


def _rebind_optimizer(optimizer, params):
    optimizer._param_groups = []
    optimizer._accumulators = {}
    optimizer._compiled_step = None
    optimizer._add_param_group(
        {"params": list(params),
         "weight_decay": optimizer._default_weight_decay}
    )


def parallelize(model, optimizer=None, mesh=None, config=None):
    """Wire DP x TP x PP x ZeRO from one config (module docstring has the
    schema). Returns ``(model, optimizer)``:

      * pp_degree == 1: the original model with GSPMD placements applied
        (wrapped to shard batch inputs over dp), optimizer state sharded
        per ``sharding_level``; train with ``jit.TrainStep`` as usual.
      * pp_degree > 1 (Llama-family causal LM): a ``PipelineParallel``
        wrapper running the 1F1B/GPipe schedule with Megatron TP inside
        the pipelined region; the optimizer is re-bound to the pipeline's
        stage-stacked parameters.
    """
    config = dict(config or {})
    dp = int(config.get("dp_degree", 1))
    mp = int(config.get("mp_degree", 1))
    pp = int(config.get("pp_degree", 1))
    dp_cfg = dict(config.get("dp_config") or {})
    mp_cfg = dict(config.get("mp_config") or {})
    pp_cfg = dict(config.get("pp_config") or {})
    level = int(dp_cfg.get("sharding_level", 0))

    if mesh is None:
        mesh = _build_mesh(dp, mp, pp)
    else:
        for name, deg in (("dp", dp), ("tp", mp), ("pp", pp)):
            if deg > 1 and name not in mesh.dim_names:
                raise ValueError(
                    f"degree {deg} for axis {name!r} but mesh has axes "
                    f"{mesh.dim_names}"
                )

    if pp > 1:
        from ..models.llama import LlamaForCausalLM, LlamaPipeline

        if not isinstance(model, LlamaForCausalLM):
            raise NotImplementedError(
                "pp_degree > 1 currently supports Llama-family causal LMs "
                "(the reference's pp plans are likewise per-model: "
                "pp_layers.py partitions nn.Sequential-style descs)"
            )
        pipe = LlamaPipeline(
            model, mesh,
            axis_name="pp",
            num_micro_batches=pp_cfg.get("micro_batches"),
            schedule=pp_cfg.get("schedule", "1f1b"),
            remat=bool(pp_cfg.get("remat", False)),
            data_axis="dp" if dp > 1 else None,
            tp_axis="tp" if mp > 1 else None,
            dtype=pp_cfg.get("dtype"),
            virtual_pp=int(pp_cfg.get("virtual_pp", 1)),
        )
        pmodel = PipelineParallel(pipe, mesh)
        if optimizer is not None:
            _rebind_optimizer(optimizer, pipe.parameters())
            if level:
                stage = {1: ShardingStage1, 2: ShardingStage2,
                         3: ShardingStage3}[level]
                # ZeRO over the dp axis (the reference shards optimizer
                # state across data-parallel ranks); falls back to no-op
                # when there is no dp axis
                if "dp" in mesh.dim_names:
                    optimizer = _shard_optimizer(
                        optimizer, stage("dp", mesh)
                    )
        return pmodel, optimizer

    # ---- GSPMD path (dp x tp x ZeRO) ------------------------------------
    if mp > 1:
        plan = mp_cfg.get("parallelize_plan", "auto")
        if plan == "auto":
            plan = _LLAMA_AUTO_PLAN
        _apply_mp_plan(model, mesh, plan)
    else:
        shard_layer(model, mesh)  # replicate everything on the mesh

    wrapped = _ShardedInputModel(model, mesh)
    if optimizer is not None and level:
        stage = {1: ShardingStage1, 2: ShardingStage2,
                 3: ShardingStage3}[level]
        axis = "dp" if "dp" in mesh.dim_names else mesh.dim_names[0]
        optimizer = _shard_optimizer(optimizer, stage(axis, mesh))
    return wrapped, optimizer
