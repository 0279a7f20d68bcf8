"""Sort-based MoE dispatch/combine.

ref: the reference's moe_gate_dispatch op (phi/infermeta/spmd_rules/
moe_gate_dispatch.cc, phi/kernels/moe_gate_dispatch_kernel.h) and the
expert-sorted row layout of fusion/cutlass/fused_moe_kernel.cu (tokens
permuted so each expert's rows are contiguous, then grouped GEMMs).

TPU form: everything static-shape so it stages — top_k + stable argsort
by expert id + searchsorted segment starts replace the CUDA kernel's
atomic counters; the [e, capacity, m] buffer is built with one scatter
(unique indices, out-of-bounds rows dropped), and combine is one gather.
Routing cost is O(s*k*m + s*e) memory instead of the dense GShard
one-hot formulation's O(s*e*c) dispatch/combine tensors.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def moe_gate_dispatch(x, gate_logits, *, k=2, capacity=0,
                      renormalize=True):
    """Route tokens to experts, expert-sorted.

    x: [s, m] tokens; gate_logits: [s, e].
    Returns (dispatched [e, c, m], combine_weights [s, k],
    expert_ids [s, k] int32, slots [s, k] int32 (-1 = dropped),
    aux_loss scalar, n_dropped scalar int32).

    An explicit capacity is honored EXACTLY (the caller's load-
    regularization contract). capacity == 0 means "dropless for balanced
    loads": c = ceil(s*k/e) rounded up to a multiple of 8 (sublane tile).
    Tokens past an expert's capacity are dropped (slot -1, weight 0) —
    the reference's capacity semantics.
    """
    s, m = x.shape
    e = gate_logits.shape[-1]
    gates = jax.nn.softmax(gate_logits.astype(jnp.float32), axis=-1)
    vals, idx = jax.lax.top_k(gates, k)               # [s, k]
    if capacity:
        c = int(capacity)
    else:
        c = -(-(s * k) // e)
        c = max(8, -(-c // 8) * 8)

    flat_e = idx.reshape(-1).astype(jnp.int32)        # [s*k]
    # capacity priority matches the reference's k-pass gate (and the
    # dense GShard formulation): within an expert, ALL first-choice
    # assignments outrank second choices, ties by token order — sort by
    # the composite (expert, choice_rank, token) key
    ar = jnp.arange(s * k, dtype=jnp.int32)
    if e * (s * k) >= 2 ** 31:
        # composite key would overflow int32: sort lexicographically via
        # two stable argsorts (secondary key first, then expert)
        rank2 = (ar % k) * s + ar // k
        pre = jnp.argsort(rank2)
        order = pre[jnp.argsort(flat_e[pre], stable=True)]
    else:
        composite = flat_e * (s * k) + (ar % k) * s + ar // k
        order = jnp.argsort(composite)
    sorted_e = flat_e[order]
    seg_start = jnp.searchsorted(
        sorted_e, jnp.arange(e, dtype=sorted_e.dtype), side="left"
    )
    pos_within = jnp.arange(s * k, dtype=jnp.int32) - seg_start[sorted_e]
    keep = pos_within < c

    tok = order // k                                  # token per assignment
    # OOB expert index -> scatter drops the row (capacity overflow)
    esc = jnp.where(keep, sorted_e, e)
    psc = jnp.where(keep, pos_within, c)
    dispatched = jnp.zeros((e, c, m), x.dtype).at[esc, psc].set(
        x[tok], mode="drop"
    )

    # map each (token, k) assignment back to its slot (-1 = dropped)
    slot_sorted = jnp.where(keep, pos_within, -1).astype(jnp.int32)
    slots = (
        jnp.full((s * k,), -1, jnp.int32).at[order].set(slot_sorted)
    ).reshape(s, k)

    # renormalize over the KEPT assignments (the dense GShard contract:
    # a token whose secondary expert overflowed pushes its full weight
    # onto the surviving expert), matching TopKGate's post-capacity
    # combine renormalization
    if renormalize:
        kept_w = vals * (slots >= 0).astype(vals.dtype)
        vals = kept_w / (kept_w.sum(-1, keepdims=True) + 1e-9)

    # GShard load-balancing aux: e * sum(mean_gate * top1_fraction).
    # ce is the PRE-capacity top-1 dispatch fraction (the paper's c_e/S) —
    # counting all k kept assignments would rescale the loss by ~k and
    # couple it to capacity drops
    me = gates.mean(0)                                # [e]
    ce = jnp.zeros((e,), jnp.float32).at[idx[:, 0]].add(1.0 / s)
    aux = jnp.sum(me * ce) * float(e)
    n_dropped = jnp.sum(~keep).astype(jnp.int32)
    return (dispatched, vals.astype(x.dtype), idx.astype(jnp.int32),
            slots, aux, n_dropped)


def moe_ragged_dispatch(x, gate_logits, *, k=2, renormalize=True):
    """Dropless sort-by-expert dispatch for the ragged grouped GEMM.

    The megablocks-style counterpart of :func:`moe_gate_dispatch`: the
    same top-k + stable composite-key sort, but instead of scattering
    into a capacity-padded [e, c, m] buffer the tokens are gathered in
    expert-sorted order — each expert's rows form one CONTIGUOUS
    segment, sized by ``group_sizes`` — so the expert FFN runs as a
    ragged ``grouped_matmul`` with zero capacity padding and zero
    drops.

    x: [s, m] tokens; gate_logits: [s, e].
    Returns (x_sorted [s*k, m], group_sizes [e] int32, order [s*k]
    int32 (sorted row r holds assignment ``order[r]`` = token
    ``order[r]//k`` choice ``order[r]%k``), combine_weights [s, k],
    expert_ids [s, k] int32, aux_loss scalar).

    The gate math (softmax, top-k, renormalization, aux loss) is the
    exact expression sequence of ``moe_gate_dispatch`` with nothing
    dropped, so the aux loss is bit-identical to the dense path and the
    combine weights match it whenever the dense capacity drops nothing.
    """
    s, m = x.shape
    e = gate_logits.shape[-1]
    gates = jax.nn.softmax(gate_logits.astype(jnp.float32), axis=-1)
    vals, idx = jax.lax.top_k(gates, k)               # [s, k]

    flat_e = idx.reshape(-1).astype(jnp.int32)        # [s*k]
    # the same composite (expert, choice_rank, token) ordering as
    # moe_gate_dispatch: within an expert, first choices before second
    # choices, ties by token — rank is irrelevant to dropless math but
    # keeps the two paths' segment layouts interchangeable
    ar = jnp.arange(s * k, dtype=jnp.int32)
    if e * (s * k) >= 2 ** 31:
        rank2 = (ar % k) * s + ar // k
        pre = jnp.argsort(rank2)
        order = pre[jnp.argsort(flat_e[pre], stable=True)]
    else:
        composite = flat_e * (s * k) + (ar % k) * s + ar // k
        order = jnp.argsort(composite)
    order = order.astype(jnp.int32)
    group_sizes = jnp.zeros((e,), jnp.int32).at[flat_e].add(1)
    x_sorted = x[order // k]                          # [s*k, m]

    # dropless renormalization == the dense contract with nothing
    # dropped (same expression, same epsilon)
    if renormalize:
        vals = vals / (vals.sum(-1, keepdims=True) + 1e-9)

    # GShard aux — identical expression to moe_gate_dispatch
    me = gates.mean(0)                                # [e]
    ce = jnp.zeros((e,), jnp.float32).at[idx[:, 0]].add(1.0 / s)
    aux = jnp.sum(me * ce) * float(e)
    return (x_sorted, group_sizes, order, vals.astype(x.dtype),
            idx.astype(jnp.int32), aux)


def moe_ragged_combine(y_sorted, order, combine_weights):
    """Inverse of moe_ragged_dispatch: weight each expert-sorted row by
    its assignment's combine weight and scatter-add back per token.

    y_sorted: [s*k, m]; order: [s*k] int32; combine_weights: [s, k].
    Returns [s, m]."""
    sk, m = y_sorted.shape
    s, k = combine_weights.shape
    w = combine_weights.reshape(-1)[order]            # weight per row
    weighted = y_sorted * w[:, None].astype(y_sorted.dtype)
    return jnp.zeros((s, m), y_sorted.dtype).at[order // k].add(weighted)


def moe_router_logits(x, weight, *, dtype="float32"):
    """x @ weight with both cast to ``dtype`` first. In float32 the
    product is taken at ``highest`` precision (a TPU's default rounds
    float32 operands to bf16), so that the experts a token is sent to do
    not depend on the activations' dtype but for ties."""
    dt = jnp.dtype(dtype)
    return jnp.matmul(
        x.astype(dt), weight.astype(dt),
        precision=jax.lax.Precision.HIGHEST if dt == jnp.float32 else None)


def moe_held_dispatch(x, gate_logits, bias=None, *, k, start, count, rows,
                      renormalize=True, scoring="softmax", scale=1.0):
    """Routing for an expert layer that holds experts ``[start, start +
    count)`` of the ``e`` the router chooses among (one expert-parallel
    rank's share; the whole layer when ``count == e``).

    Routing is over all ``e`` experts in float32: softmax, top-k, the
    weights normalised over all k chosen (``norm_topk_prob``). With
    ``scoring="sigmoid"`` (DeepSeek-V3's router, one group) the scores
    are ``sigmoid(gate_logits)``, the k experts are chosen by ``scores +
    bias`` (``e_score_correction_bias`` [e], float32, no gradient: it
    chooses and does not weigh), and the weights are the chosen scores,
    normalised over the k (+ 1e-20) when ``renormalize``, times ``scale``
    (``routed_scaling_factor``). The assignments whose expert is held
    here are kept, every one of them, and sorted by expert: the first
    ``sum(expert_load)`` entries of the returned lists, each expert's one
    contiguous segment.

    x: [s, m] (only its length is read); gate_logits: [s, e]. Returns
    (row_token [n] int32: the token of each sorted assignment, row_weight
    [n] float32: its combine weight, both 0 past the last kept one, with
    ``n`` = s * k rounded up to whole passes of ``rows``; expert_load
    [count] int32: the rows each held expert was sent)."""
    s = x.shape[0]
    if scoring == "softmax":
        gates = jax.nn.softmax(gate_logits.astype(jnp.float32), axis=-1)
        vals, idx = jax.lax.top_k(gates, k)           # [s, k]
        if renormalize:
            vals = vals / vals.sum(-1, keepdims=True)
    elif scoring == "sigmoid":
        scores = jax.nn.sigmoid(gate_logits.astype(jnp.float32))
        choose = scores if bias is None else scores + jax.lax.stop_gradient(
            bias.astype(jnp.float32))
        _, idx = jax.lax.top_k(choose, k)
        vals = jnp.take_along_axis(scores, idx, axis=-1)
        if renormalize:
            vals = vals / (vals.sum(-1, keepdims=True) + 1e-20)
        vals = vals * scale
    else:
        raise ValueError(
            f'moe_held_dispatch scoring must be "softmax" or "sigmoid", '
            f"got {scoring!r}")
    local = idx.reshape(-1).astype(jnp.int32) - start
    # not held: the key `count`, which sorts behind every held expert
    key = jnp.where((local >= 0) & (local < count), local, count)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    load = jnp.zeros((count + 1,), jnp.int32).at[key].add(1)[:count]
    kept = jnp.arange(s * k, dtype=jnp.int32) < load.sum()
    pad = (-(s * k)) % rows
    row_token = jnp.pad(jnp.where(kept, order // k, 0), (0, pad))
    row_weight = jnp.pad(
        jnp.where(kept, vals.reshape(-1)[order], 0.0), (0, pad))
    return row_token, row_weight, load


def _swiglu_grouped(xs, sizes, w_gate, w_up, w_down, impl):
    """Each group's SwiGLU expert over its own rows: three grouped
    matmuls."""
    from ...kernels.pallas.grouped_matmul import grouped_matmul as _gmm

    g = _gmm(xs, w_gate, sizes, impl=impl)
    u = _gmm(xs, w_up, sizes, impl=impl)
    return _gmm(jax.nn.silu(g) * u, w_down, sizes, impl=impl)


def _passes(load, rows):
    """Passes a step takes: at least one (shapes are static, so a pass
    without rows costs what a full one does, and a step's time does not
    depend on how few rows the router sent)."""
    return jnp.maximum(1, -(-load.sum() // rows))


def _pass_rows(p, rows, row_token, row_weight, load):
    """Pass p's window of the sorted assignments: their tokens, weights,
    which of them are kept, and each expert's rows inside the window."""
    base = p * rows
    tok = jax.lax.dynamic_slice(row_token, (base,), (rows,))
    w = jax.lax.dynamic_slice(row_weight, (base,), (rows,))
    ends = jnp.cumsum(load)
    kept = (base + jnp.arange(rows, dtype=jnp.int32)) < ends[-1]
    sizes = jnp.diff(jnp.clip(ends - base, 0, rows), prepend=0)
    return base, tok, w, kept[:, None], sizes.astype(jnp.int32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _held_experts(x, w_gate, w_up, w_down, row_token, row_weight, load,
                  rows, impl):
    n_pass = _passes(load, rows)

    def one_pass(carry):
        p, out = carry
        _, tok, w, kept, sizes = _pass_rows(
            p, rows, row_token, row_weight, load)
        xs = jnp.where(kept, x[tok], 0)
        ys = _swiglu_grouped(xs, sizes, w_gate, w_up, w_down, impl)
        # rows past the last kept one hold anything (a grouped matmul
        # leaves them unwritten): selected away, not multiplied
        add = jnp.where(kept, ys.astype(jnp.float32) * w[:, None], 0.0)
        return p + 1, out.at[tok].add(add)

    _, out = jax.lax.while_loop(
        lambda c: c[0] < n_pass, one_pass,
        (jnp.int32(0), jnp.zeros(x.shape, jnp.float32)))
    return out.astype(x.dtype)


def _held_experts_fwd(x, w_gate, w_up, w_down, row_token, row_weight, load,
                      rows, impl):
    out = _held_experts(x, w_gate, w_up, w_down, row_token, row_weight,
                        load, rows, impl)
    return out, (x, w_gate, w_up, w_down, row_token, row_weight, load)


def _held_experts_bwd(rows, impl, res, d_out):
    """The passes again: each recomputes its rows' expert outputs and
    takes their vector-Jacobian product (the grouped matmul's own dlhs and
    drhs kernels); nothing of a pass is kept between the two sweeps."""
    import numpy as np

    x, w_gate, w_up, w_down, row_token, row_weight, load = res
    n_pass = _passes(load, rows)

    def one_pass(carry):
        p, dx, dwg, dwu, dwd, dw_rows = carry
        base, tok, w, kept, sizes = _pass_rows(
            p, rows, row_token, row_weight, load)
        xs = jnp.where(kept, x[tok], 0)
        ys, vjp = jax.vjp(
            lambda a, b, c, d: _swiglu_grouped(a, sizes, b, c, d, impl),
            xs, w_gate, w_up, w_down)
        d_rows = d_out[tok].astype(jnp.float32)
        d_ys = jnp.where(kept, d_rows * w[:, None], 0.0).astype(ys.dtype)
        d_w = jnp.where(
            kept[:, 0], jnp.sum(d_rows * ys.astype(jnp.float32), -1), 0.0)
        d_xs, a, b, c = vjp(d_ys)
        dx = dx.at[tok].add(jnp.where(kept, d_xs.astype(jnp.float32), 0.0))
        return (p + 1, dx, dwg + a, dwu + b, dwd + c,
                jax.lax.dynamic_update_slice(dw_rows, d_w, (base,)))

    _, dx, dwg, dwu, dwd, dw_rows = jax.lax.while_loop(
        lambda c: c[0] < n_pass, one_pass,
        (jnp.int32(0), jnp.zeros(x.shape, jnp.float32),
         jnp.zeros_like(w_gate), jnp.zeros_like(w_up),
         jnp.zeros_like(w_down), jnp.zeros_like(row_weight)))
    no_grad = lambda a: np.zeros(a.shape, jax.dtypes.float0)
    return (dx.astype(x.dtype), dwg, dwu, dwd, no_grad(row_token), dw_rows,
            no_grad(load))


_held_experts.defvjp(_held_experts_fwd, _held_experts_bwd)


def moe_held_experts(x, w_gate, w_up, w_down, row_token, row_weight,
                     expert_load, *, rows, impl="auto"):
    """The held experts' part of the routed sum, without drops and
    without a capacity: ``out[t] = sum of row_weight[r] * E_g(r)(x[t])``
    over the kept assignments r of token t (``moe_held_dispatch``'s
    lists), E_g the SwiGLU expert ``(silu(x w_gate) * x w_up) w_down``.

    The sorted assignments are worked off in passes of ``rows`` (a static
    length: the buffers of one pass), as many as ``sum(expert_load)``
    needs and never fewer than one: one when the router sends this rank
    its share or less, more when it sends more. Each pass gathers its rows,
    runs three grouped matmuls over the experts' segments and adds the
    weighted rows to their tokens. The backward sweeps the passes again
    and recomputes them.

    x: [s, m]; w_gate, w_up: [count, m, f]; w_down: [count, f, m].
    Returns [s, m] in x's dtype."""
    return _held_experts(x, w_gate, w_up, w_down, row_token, row_weight,
                         expert_load, int(rows), impl)


def grouped_matmul(lhs, rhs, group_sizes, rhs_scales=None, *,
                   impl="auto"):
    """Ragged grouped GEMM over contiguous expert segments — the public
    op face of ``kernels.pallas.grouped_matmul`` (Pallas kernel on TPU,
    ``jax.lax.ragged_dot`` fallback elsewhere; int8 ``rhs`` with
    per-channel ``rhs_scales`` dequantizes in-kernel). Pallas imports
    stay function-scoped (the nn_ops pattern)."""
    from ...kernels.pallas.grouped_matmul import grouped_matmul as _gmm

    return _gmm(lhs, rhs, group_sizes, rhs_scales=rhs_scales, impl=impl)


def moe_combine(expert_out, combine_weights, expert_ids, slots):
    """Inverse of moe_gate_dispatch: gather each assignment's expert
    output and weight it; dropped assignments (slot -1) contribute 0.

    expert_out: [e, c, m]; combine_weights/expert_ids/slots: [s, k].
    Returns [s, m]."""
    e, c, m = expert_out.shape
    s, k = expert_ids.shape
    safe = jnp.maximum(slots, 0).reshape(-1)
    rows = expert_out[expert_ids.reshape(-1), safe]   # [s*k, m]
    w = (
        combine_weights * (slots >= 0).astype(combine_weights.dtype)
    ).reshape(-1, 1)
    return (rows * w.astype(rows.dtype)).reshape(s, k, m).sum(1)
