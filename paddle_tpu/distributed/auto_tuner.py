"""Parallel-config auto-tuner.

ref: python/paddle/distributed/auto_tuner/{tuner.py:21 (search loop),
search.py (grid), prune.py (constraint pruning), cost_model.py (memory
prediction)}. The reference launches a real trial job per candidate; on
TPU the virtual-mesh dryrun makes probing nearly free, so the tuner is:
grid -> hard-constraint prune -> analytic HBM model (its single-chip
transient factor calibrated against one chip run, see
``TuneConfig.transient_single``) -> throughput score (MXU efficiency x pipeline-bubble x comm
discounts) -> optional compile probe of the top candidates via
``dist.parallelize`` on the virtual mesh.
"""
from __future__ import annotations

from dataclasses import dataclass, field

__all__ = ["TuneConfig", "Candidate", "tune"]

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


@dataclass
class TuneConfig:
    """Workload description (the reference's tuner_cfg dict,
    auto_tuner/tuner.py)."""

    num_params: float                 # total model params
    hidden_size: int
    num_layers: int
    num_heads: int
    vocab_size: int
    seq_len: int
    global_batch: int
    n_devices: int
    hbm_gb: float = 15.75             # per-chip HBM (v5e default)
    dtype: str = "bfloat16"
    moments_dtype: str = "bfloat16"   # fp32 for master-weight AdamW
    recompute: bool = False
    # calibration: transiently-resident multiple of the STATE bytes
    # (params+grads+moments). Single chip, donated TrainStep (PR 21 chip
    # run, one v5e): 747.7M params, bf16 + fp32 master/moments, batch 2 x
    # seq 2048 peaked at 9.97 GiB = 10.7 GB against 8.97 GB of modelled
    # state + 2.85 GB of modelled activations — donation aliases the
    # update in place, so nothing like a second copy of the state is ever
    # live; 1.2 covers the fp32 master weights the state term leaves out.
    # Sharded multi-chip programs leave collective staging buffers =>
    # ~1.3x (not re-measured).
    transient_single: float = 1.2
    transient_sharded: float = 1.3
    max_sharding_level: int = 3


@dataclass
class Candidate:
    dp: int
    mp: int
    pp: int
    micro_batches: int
    sharding_level: int
    est_hbm_gb: float = 0.0
    score: float = 0.0
    fits: bool = False
    pruned: str = ""
    probe_ok: bool | None = None
    extras: dict = field(default_factory=dict)

    @property
    def config(self):
        """dist.parallelize config for this candidate."""
        return {
            "dp_degree": self.dp, "mp_degree": self.mp,
            "pp_degree": self.pp,
            "dp_config": {"sharding_level": self.sharding_level},
            "pp_config": {"micro_batches": self.micro_batches},
        }


def _divisors(n):
    return [d for d in range(1, n + 1) if n % d == 0]


def _grid(cfg: TuneConfig):
    """All (dp, mp, pp, micro, stage) filling the device count (the
    reference's grid search, auto_tuner/search.py)."""
    out = []
    for dp in _divisors(cfg.n_devices):
        for mp in _divisors(cfg.n_devices // dp):
            pp = cfg.n_devices // (dp * mp)
            micro_opts = {pp, 2 * pp, 4 * pp} if pp > 1 else {1}
            for micro in sorted(micro_opts):
                levels = (
                    range(0, cfg.max_sharding_level + 1) if dp > 1 else [0]
                )
                for stage in levels:
                    out.append(Candidate(dp, mp, pp, micro, stage))
    return out


def _prune(c: Candidate, cfg: TuneConfig):
    """Hard constraints (ref auto_tuner/prune.py: _prune_by_mp/_pp/_mbs):
    divisibility of heads/layers/vocab/batch."""
    if cfg.num_heads % c.mp:
        return f"heads {cfg.num_heads} % mp {c.mp}"
    if cfg.vocab_size % c.mp:
        return f"vocab {cfg.vocab_size} % mp {c.mp}"
    if cfg.num_layers % c.pp:
        return f"layers {cfg.num_layers} % pp {c.pp}"
    if cfg.global_batch % (c.dp * c.micro_batches):
        return (f"batch {cfg.global_batch} % dp*micro "
                f"{c.dp * c.micro_batches}")
    if c.pp > 1 and c.micro_batches < c.pp:
        return "micro_batches < pp (bubble-dominated)"
    return ""


def _est_hbm_gb(c: Candidate, cfg: TuneConfig):
    """Per-device HBM estimate (ref cost_model.py memory model, re-fit to
    the GSPMD layouts this framework actually emits)."""
    pb = _BYTES[cfg.dtype]
    mb = _BYTES[cfg.moments_dtype]
    shard = c.mp * c.pp
    p_local = cfg.num_params / shard
    params = p_local * pb
    grads = p_local * pb / (c.dp if c.sharding_level >= 2 else 1)
    moments = 2 * p_local * mb / (c.dp if c.sharding_level >= 1 else 1)
    if c.sharding_level >= 3:
        params = params / c.dp
    # activations: full per-layer tensors live for ONE in-flight
    # micro-batch (1F1B recomputes the rest from its stage-input ring,
    # which stashes O(pp) micro-batch INPUTS only)
    mb_size = cfg.global_batch // (c.dp * c.micro_batches)
    act_per_layer = mb_size * cfg.seq_len * cfg.hidden_size * 14 * pb
    layers_local = cfg.num_layers / c.pp
    acts = act_per_layer * (1 if cfg.recompute else layers_local)
    stage_in = mb_size * cfg.seq_len * cfg.hidden_size * pb
    stash = (2 * c.pp * stage_in) if c.pp > 1 else 0
    # fused-loss chunking keeps logits out of the picture; embedding +
    # head activations ~ 2 * mb * seq * h
    edge = 2 * mb_size * cfg.seq_len * cfg.hidden_size * pb
    state = params + grads + moments
    tf = (cfg.transient_single
          if (c.dp == c.mp == c.pp == 1) else cfg.transient_sharded)
    return (tf * state + acts + stash + edge) / 1e9


def _score(c: Candidate, cfg: TuneConfig):
    """Relative step-time estimate (smaller is better -> score is its
    inverse). Terms: pipeline bubble, TP collective tax, ZeRO-3 gather
    tax, MXU-width efficiency falling with mp (matmul columns shrink)."""
    from .pipeline import schedule_bubble_fraction

    bubble = (
        schedule_bubble_fraction("1f1b", c.pp, c.micro_batches)
        if c.pp > 1 else 0.0
    )
    tp_tax = 0.04 * (c.mp - 1)          # 2 psums/block over ICI
    zero3_tax = 0.10 if c.sharding_level >= 3 else 0.0
    width = cfg.hidden_size / c.mp
    mxu_eff = min(1.0, width / 2048.0) ** 0.5  # MFU rises with width
    time_rel = (1.0 + tp_tax + zero3_tax) / ((1.0 - bubble) * mxu_eff)
    return 1.0 / time_rel


def tune(cfg: TuneConfig, top_k=5, probe=None):
    """Rank parallel configs for the workload. Returns (ranked_fitting,
    all_candidates). ``probe(candidate) -> bool`` optionally validates
    the top-k (e.g. a compile-only dryrun through dist.parallelize);
    failures drop the candidate (the reference's trial-job loop,
    tuner.py:21, with compiles instead of jobs)."""
    cands = _grid(cfg)
    for c in cands:
        c.pruned = _prune(c, cfg)
        if c.pruned:
            continue
        c.est_hbm_gb = round(_est_hbm_gb(c, cfg), 2)
        c.fits = c.est_hbm_gb <= cfg.hbm_gb
        c.score = round(_score(c, cfg), 4)
    fitting = sorted(
        (c for c in cands if not c.pruned and c.fits),
        key=lambda c: -c.score,
    )
    if probe is not None:
        validated = []
        for c in fitting[:top_k]:
            c.probe_ok = bool(probe(c))
            if c.probe_ok:
                validated.append(c)
        fitting = validated + fitting[top_k:]
    return fitting[:top_k], cands
