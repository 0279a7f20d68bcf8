"""Mixture-of-Experts with expert parallelism.

ref: python/paddle/incubate/distributed/models/moe/moe_layer.py:263
(MoELayer + gshard/switch gates over global_scatter/global_gather a2a
ops) and phi/kernels/fusion/cutlass/fused_moe_kernel.cu.

TPU-first re-design: routing is SORT-BASED (ops/impl/moe_ops.py):
top-k + stable argsort by expert id builds an [e, capacity, m] buffer
with one scatter and reads it back with one gather — O(s*k*m) routing
memory instead of the dense GShard one-hot formulation's O(s*e*c)
dispatch/combine tensors (which this layer used before, and which
TopKGate.forward still provides for compatibility). The expert FFN is a
grouped GEMM over the stacked [E, ...] weights — the einsum batches all
experts' projections into single [e, c, f] MXU contractions, the XLA
analogue of fused_moe_kernel.cu's grouped cutlass GEMMs; sharding E over
an 'ep' mesh axis makes GSPMD insert the dispatch/combine all-to-alls
the reference launches by hand (global_scatter/global_gather).

Capacity padding (factor 1.25) bounds the wasted expert FLOPs of the
dense path at ~25%. Step time on the current stack: not measured.

A layer can be told which experts it holds (``held=(start, count)``):
one expert-parallel rank's share of a layer whose router keeps all its
outputs. It routes over every expert, keeps the assignments whose expert
it holds, and computes their part of the result on the ragged path with
no capacity and no drops; what the absent experts would add is left out
(their rank adds it). ``shared_expert=`` is computed whole beside it,
behind a sigmoid gate or (``shared_gate=False``) added as it is, and
``router_dtype="float32"`` takes the router's product in float32.
``scoring="sigmoid"`` is DeepSeek-V3's router in place of softmax top-k:
the float32 buffer ``gate.e_score_correction_bias`` chooses the experts
and does not weigh them. The int32 buffer ``expert_load`` holds the rows
each held expert was sent in the last step.
"""
from __future__ import annotations

import numpy as np

from .. import ops as F
from ..nn.layer.layers import Layer
from ..nn.parameter import ParamAttr

__all__ = ["TopKGate", "MoELayer", "SwiGLUExperts"]


# one pass over a held layer's kept assignments holds this many times the
# share a uniform router sends here (MoELayer.held_rows)
HELD_ROW_SLACK = 2.0


class TopKGate(Layer):
    """Softmax top-k router (ref moe/gate/gshard_gate.py, switch_gate.py).
    Returns (dispatch [s,e,c], combine [s,e,c], aux_loss)."""

    def __init__(self, d_model, num_experts, k=2, capacity_factor=1.25):
        super().__init__()
        self.num_experts = num_experts
        self.k = k
        self.capacity_factor = capacity_factor
        from ..nn import initializer as I

        self.weight = self.create_parameter(
            shape=[d_model, num_experts],
            attr=ParamAttr(initializer=I.XavierUniform()),
        )

    def capacity(self, num_tokens):
        return int(
            np.ceil(self.k * num_tokens / self.num_experts
                    * self.capacity_factor)
        )

    def forward(self, x):
        """x: [s, m] flattened tokens."""
        s, m = x.shape
        e = self.num_experts
        c = self.capacity(s)
        logits = F.matmul(x, self.weight)          # [s, e]
        gates = F.softmax(logits, -1)

        # top-k expert choice per token (iterative masking keeps the
        # whole routing jit-traceable: no dynamic shapes)
        remaining = gates
        dispatch_parts = []
        combine_parts = []
        # position counters per expert, built via cumsum of assignments
        occupancy = None
        top1_onehot = None
        for _ in range(self.k):
            idx = F.argmax(remaining, -1)          # [s]
            onehot = F.one_hot(idx, e)             # [s, e]
            if top1_onehot is None:
                top1_onehot = onehot
            # position of each token within its chosen expert's buffer
            prev = occupancy if occupancy is not None else None
            running = F.cumsum(onehot, 0) - onehot  # exclusive prefix count
            pos = running if prev is None else running + prev
            occupancy = (
                F.sum(onehot, 0, keepdim=True) + (
                    occupancy if occupancy is not None else 0.0
                )
            )
            in_cap = F.cast(pos < float(c), "float32") * onehot
            posc = F.cast(F.sum(pos * onehot, -1), "int32")  # [s]
            pos_onehot = F.one_hot(F.minimum(
                posc, F.full_like(posc, c - 1)
            ), c)                                   # [s, c]
            part = in_cap.unsqueeze(-1) * pos_onehot.unsqueeze(1)  # [s,e,c]
            gate_k = F.sum(gates * onehot, -1, keepdim=True)       # [s,1]
            dispatch_parts.append(part)
            combine_parts.append(part * gate_k.unsqueeze(-1))
            remaining = remaining * (1.0 - onehot)

        dispatch = dispatch_parts[0]
        combine = combine_parts[0]
        for dp, cp in zip(dispatch_parts[1:], combine_parts[1:]):
            dispatch = dispatch + dp
            combine = combine + cp

        # renormalize combine over selected experts (Mixtral convention)
        denom = F.sum(combine, [1, 2], keepdim=True) + 1e-9
        combine = combine / denom

        # GShard aux load-balancing loss: e * sum(mean_gate * top1_fraction)
        # ce is the PRE-capacity top-1 dispatch fraction (the paper's
        # c_e/S), matching the sort-based fast path (ops/impl/moe_ops.py) —
        # all-k post-capacity counting would rescale the loss by ~k and
        # couple it to capacity drops
        me = F.mean(gates, 0)                      # [e]
        ce = F.mean(top1_onehot, 0)                # [e]
        aux = F.sum(me * ce) * float(e)
        return dispatch, combine, aux


class SwiGLUExperts(Layer):
    """Stacked expert FFNs [E, ...] — one grouped GEMM per projection
    (ref fused_moe_kernel.cu's grouped cutlass GEMMs)."""

    def __init__(self, num_experts, d_model, d_ff):
        super().__init__()
        from ..nn import initializer as I

        def mk(shape):
            return self.create_parameter(
                shape=shape, attr=ParamAttr(initializer=I.XavierUniform())
            )

        self.w_gate = mk([num_experts, d_model, d_ff])
        self.w_up = mk([num_experts, d_model, d_ff])
        self.w_down = mk([num_experts, d_ff, d_model])
        # weight-only int8 state (quantization.quantize_moe_experts):
        # None until quantized, then one f32 per-expert-per-channel
        # scale Tensor per projection. Registered as BUFFERS so a
        # quantized model's state_dict carries the scales next to the
        # int8 weights (quantize the target layer before loading one).
        self.register_buffer("w_gate_scale", None)
        self.register_buffer("w_up_scale", None)
        self.register_buffer("w_down_scale", None)

    @property
    def quantized(self):
        return self.w_gate_scale is not None

    def forward(self, dispatched):
        """dispatched: [e, c, m] -> [e, c, m]."""
        if self.quantized:
            raise RuntimeError(
                "int8-quantized experts only run through the ragged "
                'path: use MoELayer(impl="ragged")'
            )
        g = F.einsum("ecm,emf->ecf", dispatched, self.w_gate)
        u = F.einsum("ecm,emf->ecf", dispatched, self.w_up)
        h = F.swiglu(g, u)
        return F.einsum("ecf,efm->ecm", h, self.w_down)

    def forward_ragged(self, x_sorted, group_sizes, impl="auto"):
        """Ragged form: ``x_sorted`` [n, m] expert-sorted rows with
        ``group_sizes`` [e] segment lengths -> [n, m]. Each projection
        is one ``grouped_matmul`` (Pallas kernel on TPU, ragged_dot
        fallback elsewhere); int8-quantized experts dequantize
        in-kernel via their per-channel scales."""
        g = F.grouped_matmul(x_sorted, self.w_gate, group_sizes,
                             self.w_gate_scale, impl=impl)
        u = F.grouped_matmul(x_sorted, self.w_up, group_sizes,
                             self.w_up_scale, impl=impl)
        h = F.swiglu(g, u)
        return F.grouped_matmul(h, self.w_down, group_sizes,
                                self.w_down_scale, impl=impl)


class MoELayer(Layer):
    """ref: incubate moe_layer.py:263. forward: [b, s, m] -> ([b, s, m],
    aux_loss). Shard the expert dim of the three expert weights over an
    'ep' mesh axis (Shard(0)) for expert parallelism — GSPMD inserts the
    dispatch/combine all-to-alls."""

    def __init__(self, d_model, num_experts, d_ff=None, k=2,
                 capacity_factor=1.25, gate=None, experts=None,
                 impl="dense", held=None, shared_expert=None,
                 router_dtype=None, scoring="softmax", norm_topk_prob=True,
                 routed_scaling_factor=1.0, shared_gate=True,
                 shared_expert_name="shared_expert"):
        """``held=(start, count)``: the layer holds ``count`` of the
        ``num_experts`` the router chooses among (its expert weights are
        ``[count, ...]``) and computes their part alone, on the ragged
        path. ``shared_expert``: a callable that makes a Layer ``[n, m] ->
        [n, m]``, added whole behind ``sigmoid(x @ shared_gate)``; it is
        called after the routed experts exist, so parameters() lists them
        in that order; ``shared_gate=False`` adds it ungated, and
        ``shared_expert_name`` is the attribute (so the parameters' names)
        it is kept under. ``router_dtype``: the dtype the router's product
        is taken in (None: the activations'). ``scoring`` (a held layer's):
        ``"softmax"``, or ``"sigmoid"`` with the selection bias
        ``gate.e_score_correction_bias`` (a float32 buffer [num_experts]
        at 0, no gradient; its update rule is the trainer's), the chosen
        scores normalised over the k if ``norm_topk_prob`` and multiplied
        by ``routed_scaling_factor``."""
        super().__init__()
        if impl not in ("dense", "ragged"):
            raise ValueError(
                f'MoELayer impl must be "dense" or "ragged", got '
                f"{impl!r}"
            )
        self.d_model = d_model
        self.num_experts = num_experts
        self.held = None
        if held is not None:
            start, count = (int(v) for v in held)
            if not (0 <= start and 0 < count
                    and start + count <= num_experts):
                raise ValueError(
                    f"MoELayer held=({start}, {count}) is not a range of "
                    f"the {num_experts} experts")
            if gate is not None or experts is not None:
                raise ValueError(
                    "MoELayer(held=) routes with the stock TopKGate over "
                    "its own SwiGLUExperts")
            self.held, impl = (start, count), "ragged"
        if scoring not in ("softmax", "sigmoid"):
            raise ValueError(
                f'MoELayer scoring must be "softmax" or "sigmoid", got '
                f"{scoring!r}")
        if scoring != "sigmoid" and (
                not norm_topk_prob or routed_scaling_factor != 1.0):
            raise ValueError(
                "MoELayer: norm_topk_prob and routed_scaling_factor are "
                'the sigmoid router\'s (scoring="sigmoid")')
        if held is None and scoring != "softmax":
            raise ValueError(
                f"MoELayer: scoring={scoring!r} is the held path's (held=)")
        self.scoring = scoring
        self.norm_topk_prob = bool(norm_topk_prob)
        self.routed_scaling_factor = float(routed_scaling_factor)
        self.router_dtype = router_dtype
        self.gate = gate or TopKGate(d_model, num_experts, k,
                                     capacity_factor)
        self.experts = experts or SwiGLUExperts(
            num_experts if held is None else self.held[1], d_model,
            d_ff or 4 * d_model
        )
        if scoring == "sigmoid":
            self.gate.register_buffer(
                "e_score_correction_bias",
                F.zeros([num_experts], "float32"))
        self._shared_name = shared_expert and shared_expert_name
        self.shared_gate = None
        if shared_expert is not None:
            setattr(self, shared_expert_name, shared_expert())
            if shared_gate:
                from ..nn.layer.common import Linear

                self.shared_gate = Linear(d_model, 1, bias_attr=False)
        if self.held is not None:
            self.register_buffer(
                "expert_load", F.zeros([self.held[1]], "int32"))
        # "dense": the capacity-padded [e, c, m] grouped einsum (the
        # bit-reference path). "ragged": dropless sort-by-expert +
        # ragged grouped_matmul over contiguous expert segments — no
        # capacity padding, no drops (capacity_factor is ignored), aux
        # loss bit-identical. Requires the stock TopKGate routing and a
        # SwiGLUExperts-compatible `forward_ragged`.
        if impl == "ragged":
            if gate is not None and type(gate) is not TopKGate:
                raise ValueError(
                    'MoELayer(impl="ragged") needs the stock TopKGate '
                    "routing (custom gates keep the dense dispatch/"
                    "combine contract)"
                )
            if not hasattr(self.experts, "forward_ragged"):
                raise ValueError(
                    'MoELayer(impl="ragged") needs experts exposing '
                    "forward_ragged(x_sorted, group_sizes)"
                )
        self.impl = impl

    def held_rows(self, tokens):
        """The rows of one pass over the kept assignments (the static
        length of its buffers): ``HELD_ROW_SLACK`` times the share a uniform
        router sends here, all of them when every expert is held. A step
        that is sent more takes further passes; none is dropped."""
        k, e = self.gate.k, self.num_experts
        count = self.held[1]
        if count == e:
            return tokens * k
        rows = int(np.ceil(HELD_ROW_SLACK * tokens * k * count / e))
        return min(tokens * k, -(-rows // 128) * 128)

    def record_load(self, load):
        """Keep the rows each held expert was sent this step."""
        self.expert_load._rebind(load.detach()._data)

    def _forward_held(self, flat):
        """[n, m] -> ([n, m], expert_load): the held experts' part of the
        routed sum, plus the shared expert."""
        from ..core.autograd import scope
        from ..observability import counter

        start, count = self.held
        k = self.gate.k
        counter(
            "paddle_tpu_moe_held",
            "Traced calls of an expert layer that holds a share",
            labelnames=("experts", "held", "k"),
        ).inc(experts=self.num_experts, held=count, k=k)
        counter(
            "paddle_tpu_moe_router",
            "Traced calls of a held expert layer's router, by its scoring",
            labelnames=("scoring", "experts", "k"),
        ).inc(scoring=self.scoring, experts=self.num_experts, k=k)
        n = flat.shape[0]
        with scope("moe.router"):
            if self.router_dtype is None:
                logits = F.matmul(flat, self.gate.weight)
            else:
                logits = F.moe_router_logits(
                    flat, self.gate.weight, dtype=self.router_dtype)
            rows = self.held_rows(n)
            if self.scoring == "softmax":
                row_token, row_weight, load = F.moe_held_dispatch(
                    flat, logits, k=k, start=start, count=count, rows=rows)
            else:
                row_token, row_weight, load = F.moe_held_dispatch(
                    flat, logits, k=k, start=start, count=count, rows=rows,
                    renormalize=self.norm_topk_prob, scoring=self.scoring,
                    bias=self.gate.e_score_correction_bias,
                    scale=self.routed_scaling_factor)
        with scope("moe.experts"):
            ex = self.experts
            out = F.moe_held_experts(
                flat, ex.w_gate, ex.w_up, ex.w_down, row_token, row_weight,
                load, rows=rows)
        if self._shared_name:
            with scope("moe.shared_expert"):
                shared = getattr(self, self._shared_name)
                if self.shared_gate is None:
                    out = out + shared(flat)
                else:
                    out = out + F.sigmoid(self.shared_gate(flat)) * (
                        shared(flat))
        return out, load

    def forward(self, x, return_stats=False):
        """[b, s, m] -> ([b, s, m], aux_loss). With return_stats=True a
        third dict carries token-drop counters (host diagnostics; do not
        request inside a staged TrainStep).

        A stock TopKGate routes through the sort-based fast path. A
        custom ``gate=`` (including TopKGate subclasses overriding
        forward) keeps the documented dense contract: its forward is
        called for (dispatch [s,e,c], combine [s,e,c], aux)."""
        b, s, m = x.shape
        flat = F.reshape(x, [b * s, m])
        if self.held is not None:
            out, load = self._forward_held(flat)
            out = F.reshape(out, [b, s, m])
            aux = F.zeros([], "float32")
            if return_stats:
                # inside a recomputed segment the caller carries the load
                # out and calls record_load there (a buffer written inside
                # jax.checkpoint would keep a dead tracer)
                return out, aux, {"expert_load": load}
            self.record_load(load)
            return out, aux
        if type(self.gate) is not TopKGate:
            dispatch, combine, aux = self.gate(flat)
            dispatched = F.einsum("sec,sm->ecm", dispatch, flat)
            expert_out = self.experts(dispatched)
            out = F.einsum("sec,ecm->sm", combine, expert_out)
            if return_stats:
                return F.reshape(out, [b, s, m]), aux, {}
            return F.reshape(out, [b, s, m]), aux
        if self.impl == "ragged":
            logits = F.matmul(flat, self.gate.weight)
            xs, group_sizes, order, cw, _eids, aux = (
                F.moe_ragged_dispatch(flat, logits, k=self.gate.k)
            )
            ys = self.experts.forward_ragged(xs, group_sizes)
            out = F.moe_ragged_combine(ys, order, cw)
            out = F.reshape(out, [b, s, m])
            if return_stats:
                # dropless by construction: the counters exist so
                # callers can swap impls without changing their
                # accounting
                stats = {
                    "dropped_assignments": 0,
                    "total_assignments": b * s * self.gate.k,
                    "capacity": None,
                }
                return out, aux, stats
            return out, aux
        logits = F.matmul(flat, self.gate.weight)
        cap = self.gate.capacity(b * s)
        dispatched, cw, eids, slots, aux, n_drop = F.moe_gate_dispatch(
            flat, logits, k=self.gate.k, capacity=cap
        )
        expert_out = self.experts(dispatched)
        out = F.moe_combine(expert_out, cw, eids, slots)
        out = F.reshape(out, [b, s, m])
        if return_stats:
            total = b * s * self.gate.k
            stats = {
                "dropped_assignments": n_drop,
                "total_assignments": total,
                "capacity": cap,
            }
            return out, aux, stats
        return out, aux
