"""Optimizer base class.

Capability match for the reference's ``paddle.optimizer.Optimizer`` (ref:
python/paddle/optimizer/optimizer.py:127 — param groups, LRScheduler
integration, grad clip, regularization, accumulator state_dict). The update
machinery is TPU-first instead of per-op fused CUDA kernels
(ref: phi/kernels/gpu/adamw_kernel.cu): every ``step()`` runs ONE jitted XLA
program over the full parameter pytree — clip, regularize, and the
per-parameter update rule fuse into a single device launch; learning rate and
step count enter as scalar operands so LR schedules never recompile.

GradScaler integration: ``_set_found_inf`` installs a device bool; the staged
update keeps old params/state where it is True (the reference re-launches
kernels conditionally on the host instead).
"""
from __future__ import annotations

import collections
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import autograd
from ..core.tensor import Tensor
from ..nn.clip import ClipGradBase
from ..regularizer import L1Decay, L2Decay, WeightDecayRegularizer
from .lr import LRScheduler

__all__ = ["Optimizer"]


def _malloc_trim():
    """Hand freed glibc arena back to the OS (near-host-RAM chunked
    sweeps: freed device buffers otherwise stay resident as arena and
    the next group's temps OOM the box)."""
    try:
        import ctypes

        ctypes.CDLL("libc.so.6").malloc_trim(0)
    except (OSError, AttributeError):
        pass  # non-glibc libc (musl/macOS): no malloc_trim to call


class _PAttr(NamedTuple):
    """Static (hashable) per-parameter attributes baked into the staged
    update: jit sees them as compile-time constants."""

    lr_scale: float
    reg_kind: str | None  # 'l1' | 'l2' | None  (coupled regularizer)
    reg_coeff: float
    need_clip: bool
    multi_precision: bool
    decoupled_decay: float = 0.0  # AdamW-style p *= (1 - lr*coeff)
    lr_ratio: float = 1.0  # AdamW lr_ratio(param) hook


def _found_inf_operand(opt):
    """GradScaler found_inf as a staged scalar operand. The dtype is
    pinned: a bare ``jnp.asarray(False)`` yields a weakly-typed scalar
    that can silently promote downstream (analysis rule dtype-drift)."""
    fi = opt._found_inf
    return fi if fi is not None else jnp.asarray(False, dtype=jnp.bool_)


def _normalize_weight_decay(wd):
    if wd is None:
        return None, 0.0
    if isinstance(wd, L1Decay):
        return "l1", wd.coeff
    if isinstance(wd, (L2Decay,)):
        return "l2", wd.coeff
    if isinstance(wd, (int, float)):
        return "l2", float(wd)
    if isinstance(wd, WeightDecayRegularizer):
        raise TypeError(f"unsupported regularizer {wd!r}")
    raise TypeError(f"weight_decay must be float or L1Decay/L2Decay, got {wd!r}")


class Optimizer:
    """Base optimizer. Subclasses define ``_acc_names`` (state slot names) and

    * ``_init_state(p_array) -> dict[name, array]``
    * ``_update(p, g, state, lr, t, attr) -> (new_p, new_state)`` — pure jnp.

    ``p`` arrives as fp32 master weight when ``multi_precision`` and the
    param is half-precision; the base class handles the down-cast.
    """

    _acc_names: tuple = ()

    def __init__(
        self,
        learning_rate=0.001,
        parameters=None,
        weight_decay=None,
        grad_clip=None,
        name=None,
        multi_precision=False,
    ):
        if parameters is None:
            raise ValueError(
                "parameters is required in dygraph mode (pass model.parameters())"
            )
        parameters = list(parameters)
        if grad_clip is not None and not isinstance(grad_clip, ClipGradBase):
            raise TypeError("grad_clip must be a paddle.nn.ClipGradBy* instance")
        if not isinstance(learning_rate, (int, float, LRScheduler)):
            raise TypeError("learning_rate must be float or LRScheduler")

        self._name = name
        self._learning_rate = learning_rate
        self._grad_clip = grad_clip
        self._multi_precision = multi_precision
        self._default_weight_decay = weight_decay
        self._param_groups = []
        self._accumulators = {}  # id(param) -> {acc_name: jax.Array}
        self._global_step = 0
        self._found_inf = None
        self._compiled_step = None
        self._param_name_counter = 0

        if parameters and isinstance(parameters[0], dict):
            for group in parameters:
                self._add_param_group(dict(group))
        else:
            self._add_param_group(
                {"params": parameters, "weight_decay": weight_decay}
            )

    # -- param groups ------------------------------------------------------
    def _add_param_group(self, group):
        params = group["params"]
        if isinstance(params, Tensor):
            params = [params]
        group["params"] = list(params)
        group.setdefault("weight_decay", self._default_weight_decay)
        group.setdefault("learning_rate", 1.0)
        for p in group["params"]:
            if p.name is None:
                p.name = f"param_{self._param_name_counter}"
                self._param_name_counter += 1
        self._param_groups.append(group)
        self._compiled_step = None

    @property
    def _parameter_list(self):
        return [p for g in self._param_groups for p in g["params"]]

    # -- lr ----------------------------------------------------------------
    def get_lr(self):
        if isinstance(self._learning_rate, LRScheduler):
            return float(self._learning_rate())
        return float(self._learning_rate)

    def set_lr(self, value):
        if isinstance(self._learning_rate, LRScheduler):
            raise RuntimeError(
                "cannot set_lr when the learning rate is an LRScheduler; "
                "call scheduler.step() instead"
            )
        self._learning_rate = float(value)

    def set_lr_scheduler(self, scheduler):
        if not isinstance(scheduler, LRScheduler):
            raise TypeError("expected an LRScheduler")
        self._learning_rate = scheduler

    # -- state -------------------------------------------------------------
    def _init_state(self, p_array):
        return {}

    def _ensure_state(self, p):
        st = self._accumulators.get(id(p))
        if st is None:
            arr = p._data
            if self._use_master(p):
                master = arr.astype(jnp.float32)
                st = self._init_state(master)
                st["master_weight"] = master
            else:
                st = self._init_state(arr)
            self._accumulators[id(p)] = st
        return st

    def _use_master(self, p):
        return self._multi_precision and p._data.dtype in (
            jnp.bfloat16,
            jnp.float16,
        )

    def _set_found_inf(self, found_inf):
        """GradScaler hook: device bool; when True the step is a no-op."""
        self._found_inf = found_inf

    # -- the staged update -------------------------------------------------
    def _group_weight_decay(self, group):
        return _normalize_weight_decay(group.get("weight_decay"))

    def _collect(self):
        """Gather (param, grad_array, attr) for every trainable param with a
        grad. Param-level regularizer overrides the group's."""
        out = []
        for group in self._param_groups:
            g_kind, g_coeff = self._group_weight_decay(group)
            lr_scale = float(group.get("learning_rate", 1.0))
            for p in group["params"]:
                if not getattr(p, "trainable", not p.stop_gradient):
                    continue
                grad = p.grad
                if grad is None:
                    continue
                kind, coeff = g_kind, g_coeff
                preg = getattr(p, "regularizer", None)
                if preg is not None:
                    kind, coeff = _normalize_weight_decay(preg)
                decoupled, lr_ratio = self._param_extras(p, group)
                attr = _PAttr(
                    lr_scale=lr_scale
                    * float(
                        getattr(p, "optimize_attr", {}).get("learning_rate", 1.0)
                    ),
                    reg_kind=kind,
                    reg_coeff=coeff,
                    need_clip=getattr(p, "need_clip", True),
                    multi_precision=self._use_master(p),
                    decoupled_decay=decoupled,
                    lr_ratio=lr_ratio,
                )
                g_arr = grad._data if isinstance(grad, Tensor) else jnp.asarray(grad)
                out.append((p, g_arr, attr))
        return out

    def _param_extras(self, p, group=None):
        """Hook for subclasses: (decoupled_decay_coeff, lr_ratio) baked into
        the per-param static attrs (AdamW overrides)."""
        return 0.0, 1.0

    def _make_step_fn(self, use_clip=True):
        clip = self._grad_clip if use_clip else None

        def step_fn(attrs, out_shardings, lr, t, found_inf, params, grads,
                    states):
            if clip is not None:
                grads = clip._clip_arrays(
                    params, grads, [a.need_clip for a in attrs]
                )
            new_params, new_states = [], []
            for p, g, s, a, (target, state_targets) in zip(
                params, grads, states, attrs, out_shardings
            ):
                compute_p = s["master_weight"] if a.multi_precision else p
                g = g.astype(compute_p.dtype)
                if a.reg_kind == "l2":
                    g = g + a.reg_coeff * compute_p
                elif a.reg_kind == "l1":
                    g = g + a.reg_coeff * jnp.sign(compute_p)
                eff_lr = lr * a.lr_scale * a.lr_ratio
                if a.decoupled_decay != 0.0:
                    compute_p = compute_p * (1.0 - eff_lr * a.decoupled_decay)
                np_, ns = self._update(compute_p, g, s, eff_lr, t, a)
                # lr and t are float32 operands, so the update math
                # promotes: every slot (and the parameter) is handed back
                # in the dtype it came in, or a bf16 model without master
                # weights turns float32 after one step and a staged step
                # retraces twice while the dtypes settle
                ns = {k: v.astype(s[k].dtype) if k in s else v
                      for k, v in ns.items()}
                if a.multi_precision:
                    ns["master_weight"] = np_
                np_ = np_.astype(p.dtype)
                np_ = jnp.where(found_inf, p, np_)
                if target is not None:
                    # ZeRO: sharded-state updates must hand the param back
                    # in its own layout (GSPMD emits the all-gather here)
                    np_ = jax.lax.with_sharding_constraint(np_, target)
                st_map = dict(state_targets)
                ns = {
                    # keep old value under found_inf; each slot keeps its
                    # declared layout
                    k: jax.lax.with_sharding_constraint(v, st_map[k])
                    if st_map.get(k) is not None else v
                    for k, v in (
                        (k, jnp.where(found_inf, s[k], v) if k in s else v)
                        for k, v in ns.items()
                    )
                }
                new_params.append(np_)
                new_states.append(ns)
            return new_params, new_states

        # Donating params + optimizer state runs the update in place
        # (old buffers are rebound right after) — the knob that lets an
        # 8B-state dryrun fit host RAM. OPT-IN via donate_state: a donated
        # update invalidates any user-held alias of a parameter buffer
        # ('Array has been deleted'); TrainStep owns donation on the
        # staged path. Grads stay undonated so p.grad remains readable
        # after step().
        donate = (5, 7) if self.donate_state else ()
        return jax.jit(
            step_fn, static_argnums=(0, 1), donate_argnums=donate
        )

    @staticmethod
    def _param_out_sharding(p_arr, state):
        """Static layout contract for one param's staged update:
        (param_target, ((state_key, target), ...)). The updated param comes
        back in the param's own NamedSharding — or replicated over the
        state's mesh when only the state is sharded (ZeRO stage 1/2: the
        all-gather) — and each state slot keeps its declared layout."""
        from jax.sharding import NamedSharding, PartitionSpec

        sh = getattr(p_arr, "sharding", None)
        mesh = sh.mesh if isinstance(sh, NamedSharding) else None
        for arr in state.values():
            ssh = getattr(arr, "sharding", None)
            if isinstance(ssh, NamedSharding):
                mesh = ssh.mesh
                break
        if mesh is None:
            return None, ()
        replicated = NamedSharding(mesh, PartitionSpec())
        state_targets = tuple(
            (
                k,
                arr.sharding
                if isinstance(getattr(arr, "sharding", None), NamedSharding)
                else replicated,
            )
            for k, arr in state.items()
        )
        param_target = sh if isinstance(sh, NamedSharding) else replicated
        return param_target, state_targets

    # When set (int), step() updates parameters in groups of this many
    # instead of one whole-tree program: transient memory per update
    # call drops to O(group bytes) — the knob that lets an 8B-state
    # virtual-mesh dryrun fit host RAM (one program per group shape is
    # cached by jit as usual). None = single fused program (default,
    # fastest on a real chip).
    step_chunk: int | None = None
    # Donate param/state buffers into the update program (in-place
    # semantics; see _build_step). Off by default — user-held aliases of
    # parameter buffers stay valid. The virtual-mesh 8B dryrun turns it
    # on to fit host RAM.
    donate_state: bool = False
    # With step_chunk: drop each group's p.grad right after its update,
    # so gradient memory shrinks as the chunked sweep advances (for
    # state sizes near host RAM). Off by default — p.grad stays
    # readable after step() otherwise.
    chunk_free_grads: bool = False

    @autograd.no_grad()
    def step(self):
        if getattr(self, "gradient_accumulation_steps", 1) > 1:
            raise RuntimeError(
                "gradient_accumulation_steps is set on this optimizer "
                "but eager step() does not accumulate — run the step "
                "through paddle.jit.TrainStep (it stages the k-micro-"
                "batch accumulation + single update), or unset the "
                "attribute to step eagerly per batch"
            )
        triples = self._collect()
        if not triples:
            self._global_step += 1
            return
        if self.step_chunk:
            k = int(self.step_chunk)
            if k <= 0:
                raise ValueError(
                    f"step_chunk must be a positive int, got {k}"
                )
            if self._grad_clip is not None:
                # global-norm clipping must see the WHOLE gradient tree;
                # clip once up front, then update chunks with clipping
                # disabled (per-chunk clipping would re-normalize by each
                # chunk's own norm)
                params = [p for p, _, _ in triples]
                grads = [g for _, g, _ in triples]
                clipped = self._grad_clip._clip_arrays(
                    [p._data for p in params], grads,
                    [a.need_clip for _, _, a in triples],
                )
                triples = [
                    (p, g, a) for (p, _, a), g in zip(triples, clipped)
                ]
            for i in range(0, len(triples), k):
                group = triples[i:i + k]
                self._step_group(group, use_clip=False)
                if self.chunk_free_grads:
                    for j in range(i, min(i + k, len(triples))):
                        # release BOTH references to the grad array (the
                        # triples list pins it too) so the buffer is
                        # actually reclaimable mid-sweep
                        p = triples[j][0]
                        p.grad = None
                        triples[j] = None
                    _malloc_trim()
            self._global_step += 1
            return
        self._step_group(triples)
        self._global_step += 1

    def _step_group(self, triples, use_clip=True):
        params = [p for p, _, _ in triples]
        grads = [g for _, g, _ in triples]
        attrs = tuple(a for _, _, a in triples)
        states = [self._ensure_state(p) for p in params]

        lr = jnp.float32(self.get_lr())
        t = jnp.float32(self._global_step + 1)
        found_inf = _found_inf_operand(self)  # dtype-pinned bool

        grad_sharding = getattr(self, "_grad_sharding_for", None)
        if grad_sharding is not None:
            # ZeRO stage>=2 eager path: lay each grad out sharded before the
            # update (device_put = the reduce-scatter's memory effect here;
            # inside jit.TrainStep the constraint stages the real one)
            grads = [
                jax.device_put(g, s)
                if (s := grad_sharding(p)) is not None else g
                for p, g in zip(params, grads)
            ]
        targets = tuple(
            self._param_out_sharding(p._data, st)
            for p, st in zip(params, states)
        )
        if getattr(self, "_compiled_donate", None) != self.donate_state:
            # donate_state toggled after a build: drop stale programs
            self._compiled_step = None
            self._compiled_step_noclip = None
            self._compiled_donate = self.donate_state
        if use_clip:
            if self._compiled_step is None:
                self._compiled_step = self._make_step_fn()
            compiled = self._compiled_step
        else:
            if getattr(self, "_compiled_step_noclip", None) is None:
                self._compiled_step_noclip = self._make_step_fn(
                    use_clip=False
                )
            compiled = self._compiled_step_noclip
        try:
            new_params, new_states = compiled(
                attrs, targets, lr, t, found_inf,
                [p._data for p in params], grads, states,
            )
        except Exception as e:
            if self.donate_state:
                # params/states were DONATED into the failed call and are
                # gone; say so instead of letting later accesses die with
                # an opaque "Array has been deleted"
                raise RuntimeError(
                    "optimizer update failed AFTER its parameter/state "
                    "buffers were donated — training state is destroyed; "
                    "restore from a checkpoint"
                ) from e
            raise
        for p, np_, ns in zip(params, new_params, new_states):
            p._rebind(np_)
            self._accumulators[id(p)] = ns

    def _update(self, p, g, state, lr, t, attr):
        raise NotImplementedError

    # -- paddle API parity -------------------------------------------------
    def clear_grad(self, set_to_zero=False):
        for p in self._parameter_list:
            if set_to_zero and p.grad is not None:
                p.grad = Tensor(
                    jnp.zeros_like(p.grad._data), stop_gradient=True
                )
            else:
                p.grad = None

    clear_gradients = clear_grad

    def minimize(self, loss, startup_program=None, parameters=None,
                 no_grad_set=None):
        """Dygraph minimize = backward + step (ref: optimizer.py minimize)."""
        loss.backward()
        self.step()
        params_grads = [
            (p, p.grad) for p in self._parameter_list if p.grad is not None
        ]
        return None, params_grads

    # -- checkpointing -----------------------------------------------------
    def state_dict(self):
        """Accumulators keyed ``{param.name}_{acc}_0`` plus LR scheduler state
        (ref: optimizer.py state_dict / python/paddle/framework/io.py)."""
        out = collections.OrderedDict()
        for p in self._parameter_list:
            st = self._accumulators.get(id(p))
            if not st:
                continue
            for acc, arr in st.items():
                out[f"{p.name}_{acc}_0"] = Tensor(arr, stop_gradient=True)
        out["global_step"] = self._global_step
        if isinstance(self._learning_rate, LRScheduler):
            out["LR_Scheduler"] = self._learning_rate.state_dict()
        return out

    def set_state_dict(self, state_dict):
        if "LR_Scheduler" in state_dict and isinstance(
            self._learning_rate, LRScheduler
        ):
            self._learning_rate.set_state_dict(state_dict["LR_Scheduler"])
        if "global_step" in state_dict:
            self._global_step = int(
                np.asarray(state_dict["global_step"]).item()
            )
        for p in self._parameter_list:
            st = self._ensure_state(p)
            for acc in list(st):
                key = f"{p.name}_{acc}_0"
                if key in state_dict:
                    src = state_dict[key]
                    arr = src._data if isinstance(src, Tensor) else jnp.asarray(src)
                    if tuple(arr.shape) != tuple(st[acc].shape):
                        raise ValueError(
                            f"shape mismatch for optimizer state {key}: "
                            f"{tuple(arr.shape)} vs {tuple(st[acc].shape)}"
                        )
                    st[acc] = arr.astype(st[acc].dtype)
        return self

    set_dict = set_state_dict

    def __repr__(self):
        lr = (
            self._learning_rate
            if isinstance(self._learning_rate, (int, float))
            else type(self._learning_rate).__name__
        )
        return f"{type(self).__name__}(learning_rate={lr})"
