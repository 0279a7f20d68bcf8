"""Model adapters: the compiled compute behind the serving engine.

The engine schedules REQUESTS; an adapter turns one scheduler decision
into array math over the paged KV pool. Two entry points, both pure
functions of (weights, pool, scheduler arrays) so the engine can jit and
donate them:

  * ``prefill(w, kp, vp, ids, length, block_table)`` — run one prompt
    (padded to a length bucket) through the model, WRITE its K/V into the
    request's pages, return last-valid-position logits.
  * ``decode(w, kp, vp, tokens, positions, block_tables, active)`` — one
    token for every batch slot at once: write each token's K/V at its
    per-slot position, attend over the per-slot block table, return
    [slots, vocab] logits. Inactive slots are masked: their page write is
    routed out of bounds (dropped by XLA scatter semantics, same trick as
    ``paged_attention.update_pages``) and their logits are garbage the
    engine never reads.

``LlamaServingAdapter`` follows the ``models.llama.LlamaPipeline``
precedent of re-owning the model's weights as raw arrays and rebuilding
the block in jnp + ops.impl functions (the same math the Tensor ops
dispatch to, so serving numerics match ``generate``'s). Decode attention
is selected by the adapter's ``decode_kernel`` attribute
(``EngineConfig(decode_kernel=)`` sets it): ``"auto"`` uses the Pallas
paged kernel on TPU and the XLA reference path elsewhere; ``"pallas"``
requests the kernel, which off-TPU degrades to the XLA fallback — warned
and counted in ``paddle_tpu_kernels_fallbacks_total`` — unless
``FLAGS_pallas_interpret`` pins the interpreted kernel for parity
testing; ``"xla"`` pins the fallback. On a TPU the selected kernel is
compiled by Mosaic and a refusal is a compile error, never a silent
switch of path.

Quantized KV (``EngineConfig(kv_cache_dtype="int8")``): every per-layer
pool entry is an int8 ``(pages, scales)`` pair. All page writes
quantize-on-write (per-token-per-head absmax, the scale landing in the
same slot of the scale plane) and every read path dequantizes
in-attention — the paged kernel from its scale operands, the gather
paths right after the gather. Nothing else changes shape: the same
routing drives both layouts.

Any object exposing the same five attributes and two methods (see
``required_attrs``) can serve — the engine duck-types, it never imports a
model class. An optional ``dtype`` attribute names the KV-pool dtype;
without it the engine reads ``weights["embed"].dtype``. Two optional
entry points extend the surface: ``prefill_ext(w, kp, vp, ids, length,
cache_len, block_table)`` continues a prefill whose first ``cache_len``
tokens are already in the pages — required only when the engine enables
prefix caching or chunked prefill — and ``verify(w, kp, vp, tokens,
positions, draft_lens, block_tables, active)`` scores a K+1-token draft
window for every slot in one launch — required only when the engine
enables speculative decoding (``EngineConfig(speculate_tokens=)``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..ops.impl.activation import swiglu as _swiglu
from ..ops.impl.fused_ops import rope_qk as _rope_qk
from ..ops.impl.nn_ops import (
    scaled_dot_product_attention as _sdpa,
)
from ..ops.impl.nn_ops import rms_norm as _rms_norm

__all__ = ["LlamaServingAdapter", "build_adapter", "required_attrs"]

# the duck-typed adapter surface the engine relies on
required_attrs = (
    "num_layers", "num_kv_heads", "head_dim", "vocab_size", "weights",
    "prefill", "decode",
)


def _split_pages(pages):
    """(pages, scales) for an int8-quantized per-layer entry,
    (pages, None) for a plain float one."""
    if isinstance(pages, (tuple, list)):
        return pages[0], pages[1]
    return pages, None


def _row_matmul(x, w, spec):
    """The row-parallel contraction (attention output projection / FFN
    down projection) under tensor parallelism. ``spec`` is the engine's
    ``serving.sharding.TPSpec`` (None on an unsharded engine — this
    compiles the exact ``x @ w`` jaxpr the inline form did). Under
    ``tp_numerics="exact"`` BOTH operands are constrained to replicated
    before the dot — an all-gather of the sharded weight — so the
    reduction runs whole on every chip and the result is bit-identical
    to the unsharded program. ``"fast"`` leaves the operands sharded
    and GSPMD emits the Megatron partial-sum + all-reduce, whose
    cross-chip reduction order drifts ~1 ulp (docs/serving.md)."""
    if spec is not None and spec.exact:
        x = jax.lax.with_sharding_constraint(x, spec.replicated)
        w = jax.lax.with_sharding_constraint(w, spec.replicated)
    return x @ w


def _paged_attn(q, kp, vp, block_tables, lengths, kernel="auto"):
    # pallas imports stay function-scoped (the nn_ops.py pattern): plain
    # `import paddle_tpu` must not load — nor fail on — the TPU kernel
    # stack; these run at trace time only
    from ..core import flags
    from ..core.device import on_tpu
    from ..kernels.pallas._compat import record_fallback
    from ..kernels.pallas.paged_attention import (
        paged_attention,
        paged_attention_xla,
    )

    tpu = on_tpu()
    if kernel == "pallas":
        # explicit request: off-TPU it degrades (warn + count) unless
        # FLAGS_pallas_interpret pins the interpreted kernel (tests)
        use_pallas = tpu or bool(
            flags.get_flag("FLAGS_pallas_interpret")
        )
        if not use_pallas:
            record_fallback(
                "paged_attention", "backend",
                hint="set FLAGS_pallas_interpret to run the kernel "
                     "under the Pallas interpreter off-TPU instead",
            )
    elif kernel == "auto":
        use_pallas = tpu and flags.get_flag("FLAGS_use_pallas_kernels")
    elif kernel == "xla":
        use_pallas = False
    else:
        raise ValueError(
            f'decode_kernel must be "auto", "pallas" or "xla", got '
            f"{kernel!r}"
        )
    # on a TPU the selected kernel is compiled by Mosaic and a refusal
    # raises from the decode program's compile: every block of the
    # kernel spans whole trailing array dims, so no page_size / head_dim
    # / pool dtype is pre-screened here
    if use_pallas:
        return paged_attention(q, kp, vp, block_tables, lengths)
    return paged_attention_xla(q, kp, vp, block_tables, lengths)


def _write_prompt_pages(pages, kv, block_table, length):
    """Scatter a prompt's [S, kv_heads, d] K or V into its pages. Token t
    lands in page ``block_table[t // block_size]`` slot ``t % block_size``;
    padded tail positions (t >= length) are routed to a nonexistent page
    so the scatter drops them. The degenerate (offset 0) case of
    ``_write_chunk_pages`` — one routing implementation keeps the
    one-shot and chunked write paths bit-identical by construction."""
    return _write_chunk_pages(pages, kv, block_table, length, 0)


def _write_chunk_pages(pages, kv, block_table, length, cache_len):
    """``_write_prompt_pages`` with a position offset: chunk token t
    lands at GLOBAL position ``cache_len + t`` (chunked prefill / cached
    prefix continuation). Padded tail positions route out of bounds; the
    block-table gather clamps for them, then the write is dropped.

    Int8 pools quantize-on-write: the token's per-head scale is
    scattered into the scale plane with the same routing (dropped
    together with its page write)."""
    buf, scales = _split_pages(pages)
    n_blocks = buf.shape[1]
    block_size = buf.shape[2]
    s = kv.shape[0]
    t = jnp.arange(s)
    gpos = cache_len + t
    phys = jnp.where(t < length, block_table[gpos // block_size], n_blocks)
    slot = gpos % block_size
    if scales is None:
        return buf.at[:, phys, slot].set(
            jnp.swapaxes(kv, 0, 1).astype(buf.dtype)
        )
    from ..kernels.pallas.paged_attention import quantize_tokens

    q8, sc = quantize_tokens(kv)           # [S, kvh, d], [S, kvh]
    buf = buf.at[:, phys, slot].set(jnp.swapaxes(q8, 0, 1))
    scales = scales.at[:, phys, slot].set(jnp.swapaxes(sc, 0, 1))
    return (buf, scales)


def _write_window_pages(pages, kv, phys, slot):
    """Batched form of ``_write_chunk_pages``: scatter a [slots, S,
    kv_heads, d] token window into the pages at precomputed physical
    coordinates ``phys``/``slot`` [slots, S] (invalid positions carry
    ``phys == num_blocks`` so the scatter drops them — the same
    out-of-bounds routing every other page write uses)."""
    buf, scales = _split_pages(pages)
    if scales is None:
        vals = jnp.moveaxis(kv, 2, 0).astype(buf.dtype)  # [kv,slots,S,d]
        return buf.at[:, phys, slot].set(vals)
    from ..kernels.pallas.paged_attention import quantize_tokens

    q8, sc = quantize_tokens(kv)           # [slots,S,kvh,d], [slots,S,kvh]
    buf = buf.at[:, phys, slot].set(jnp.moveaxis(q8, 2, 0))
    scales = scales.at[:, phys, slot].set(jnp.moveaxis(sc, 2, 0))
    return (buf, scales)


def _window_routing(block_tables, pos, valid, n_blocks, bs_pg):
    """Physical scatter coordinates (phys, slot) for a [slots, S]
    window of GLOBAL positions: row token ``pos`` lands in page
    ``block_table[pos // bs_pg]`` at slot ``pos % bs_pg``; invalid
    positions route to the nonexistent page ``n_blocks`` so the
    scatter drops them — the out-of-bounds-drop contract every page
    write shares (the gather clamp alone would silently overwrite a
    live slot). One implementation serves the verify window write and
    decode's tensor-parallel write (a 1-token window)."""
    phys = jnp.where(
        valid,
        jnp.take_along_axis(
            block_tables,
            jnp.minimum(pos // bs_pg, block_tables.shape[1] - 1),
            axis=1,
        ),
        n_blocks,
    )
    return phys, pos % bs_pg


def _gather_context_batch(pages, block_tables):
    """``_gather_context`` for every slot at once: ``block_tables``
    [slots, P] gathers to ``[slots, P*bs, kv_heads, d]`` — slot s's
    logical KV timeline, position p at row p. Same layout, same
    reduction order as the single-sequence gather, just batched."""
    buf, scales = _split_pages(pages)
    g = buf[:, block_tables]               # [kv, slots, P, bs, d]
    if scales is not None:
        sc = scales[:, block_tables]       # [kv, slots, P, bs]
        g = g.astype(jnp.float32) * sc[..., None]
    g = jnp.moveaxis(g, 0, 3)              # [slots, P, bs, kv, d]
    return g.reshape(g.shape[0], -1, g.shape[3], g.shape[4])


def _gather_context(pages, block_table):
    """Materialize one sequence's logical KV timeline from its pages:
    ``[kv_heads, blocks, bs, d]`` gathered through ``block_table [P]``
    to ``[P*bs, kv_heads, d]`` — position p is row p. This is the
    chunk-prefill context layout: attention over it is computed in the
    exact ``scaled_dot_product_attention`` form the one-shot prefill
    (and ``generate``'s cached branch) uses, which keeps chunked and
    prefix-cached prefill BIT-identical to the one-shot program (the
    paged-einsum form of ``paged_attention_xla`` reduces in a different
    order and drifts by ~1 ulp — enough to flip a greedy argmax). An
    int8 pool dequantizes right after the gather — the byte-parity
    contract then becomes the documented int8 tolerance contract
    (docs/serving.md)."""
    buf, scales = _split_pages(pages)
    g = buf[:, block_table]                # [kv, P, bs, d]
    if scales is not None:
        sc = scales[:, block_table]        # [kv, P, bs]
        g = g.astype(jnp.float32) * sc[..., None]
    g = jnp.moveaxis(g, 0, 2)              # [P, bs, kv, d]
    return g.reshape(-1, g.shape[2], g.shape[3])


def _pages_geometry(entry):
    """(num_blocks, block_size) of one per-layer pool entry (plain
    array or int8 (pages, scales) pair)."""
    buf, _ = _split_pages(entry)
    return buf.shape[1], buf.shape[2]


class LlamaServingAdapter:
    """Paged-KV serving forward for a ``models.llama.LlamaForCausalLM``.

    Snapshots the model's weights at construction (serving is inference;
    call ``refresh()`` after a weight swap). Tied embeddings resolve the
    LM head to ``embed.T`` inside the staged program.
    """

    # decode attention path: "auto" | "pallas" | "xla" (module
    # docstring); the engine sets this from EngineConfig(decode_kernel=)
    decode_kernel = "auto"
    # tensor-parallel sharding spec (serving.sharding.TPSpec); the
    # engine sets this from EngineConfig(tp_degree=) — None (the
    # default) keeps every traced body byte-identical to the
    # single-chip program. The traced bodies consult it at two points:
    # the row-parallel matmuls (_row_matmul numerics contract) and the
    # decode-step page write (head-sliced scatter that stays
    # shard-local where update_pages' explicit head indices would
    # re-shard the pool under GSPMD).
    tp_spec = None

    def __init__(self, model):
        cfg = model.config
        if getattr(cfg, "num_experts", 0) > 0:
            raise NotImplementedError(
                "serving adapter: MoE Llama not supported yet (dense only)"
            )
        self.num_layers = cfg.num_hidden_layers
        self.num_heads = cfg.num_attention_heads
        self.num_kv_heads = cfg.num_key_value_heads
        self.head_dim = cfg.hidden_size // cfg.num_attention_heads
        self.hidden_size = cfg.hidden_size
        self.vocab_size = cfg.vocab_size
        self.rope_theta = cfg.rope_theta
        self.eps = cfg.rms_norm_eps
        self._model = model
        self.refresh()

    def refresh(self):
        """Re-snapshot weights from the source model."""
        m = self._model
        layers = []
        for blk in m.llama.layers:
            layers.append({
                "ln1": blk.input_layernorm.weight._data,
                "wq": blk.self_attn.q_proj.weight._data,
                "wk": blk.self_attn.k_proj.weight._data,
                "wv": blk.self_attn.v_proj.weight._data,
                "wo": blk.self_attn.o_proj.weight._data,
                "ln2": blk.post_attention_layernorm.weight._data,
                "wg": blk.mlp.gate_proj.weight._data,
                "wu": blk.mlp.up_proj.weight._data,
                "wd": blk.mlp.down_proj.weight._data,
            })
        self.weights = {
            "embed": m.llama.embed_tokens.weight._data,
            "layers": layers,
            "norm": m.llama.norm.weight._data,
            "head": (
                m.lm_head.weight._data if m.lm_head is not None else None
            ),
        }
        self.dtype = self.weights["embed"].dtype  # KV pool dtype

    # -- shared block math ---------------------------------------------------
    def _qkv(self, wl, h, b, s):
        q = (h @ wl["wq"]).reshape(b, s, self.num_heads, self.head_dim)
        k = (h @ wl["wk"]).reshape(b, s, self.num_kv_heads, self.head_dim)
        v = (h @ wl["wv"]).reshape(b, s, self.num_kv_heads, self.head_dim)
        return q, k, v

    def _prompt_attention(self, q, k, v):
        """Causal attention over a whole prompt. At long buckets sdpa
        routes to the Pallas flash kernel, which under tensor parallelism
        must be told the head dim rides the ``tp`` axis."""
        if self.tp_spec is None:
            return _sdpa(q, k, v, is_causal=True)
        from ..kernels.pallas._compat import spmd_axes

        with spmd_axes(self.tp_spec.mesh, head_axis="tp"):
            return _sdpa(q, k, v, is_causal=True)

    def _mlp(self, wl, x):
        with jax.named_scope("mlp"):
            h = _rms_norm(x, wl["ln2"], epsilon=self.eps)
            return x + _row_matmul(
                _swiglu(h @ wl["wg"], h @ wl["wu"]), wl["wd"], self.tp_spec
            )

    def _logits(self, w, x):
        head = w["head"]
        if head is None:
            head = jnp.swapaxes(w["embed"], 0, 1)
        return x @ head

    # -- the two serving entry points ---------------------------------------
    def prefill(self, w, kp, vp, ids, length, block_table):
        """ids [S] (padded to a bucket), length scalar, block_table [P].
        Returns (logits [vocab] at position length-1, kp, vp)."""
        s = ids.shape[0]
        with jax.named_scope("embedding"):
            x = w["embed"][ids][None]                      # [1, S, hid]
        pos = jnp.arange(s, dtype=jnp.int32)[None]     # prompts start at 0
        kp, vp = list(kp), list(vp)
        for li in range(self.num_layers):
            wl = w["layers"][li]
            with jax.named_scope("attention"):
                h = _rms_norm(x, wl["ln1"], epsilon=self.eps)
                q, k, v = self._qkv(wl, h, 1, s)
                q, k = _rope_qk(q, k, pos, base=self.rope_theta)
                kp[li] = _write_prompt_pages(kp[li], k[0], block_table, length)
                vp[li] = _write_prompt_pages(vp[li], v[0], block_table, length)
                if self.num_kv_heads != self.num_heads:
                    rep = self.num_heads // self.num_kv_heads
                    k = jnp.repeat(k, rep, axis=2)
                    v = jnp.repeat(v, rep, axis=2)
                # causal attention over the in-flight prompt; right-padding is
                # invisible to valid queries under causality
                attn = self._prompt_attention(q, k, v)
                x = x + _row_matmul(
                    attn.reshape(1, s, -1), wl["wo"], self.tp_spec
                )
            x = self._mlp(wl, x)
        with jax.named_scope("lm_head_loss"):
            x = _rms_norm(x, w["norm"], epsilon=self.eps)
            h_last = jnp.take(x[0], length - 1, axis=0)    # [hid]
            logits = self._logits(w, h_last)
        return logits, tuple(kp), tuple(vp)

    def prefill_ext(self, w, kp, vp, ids, length, cache_len, block_table):
        """Prefill CONTINUATION: run one chunk of a prompt whose first
        ``cache_len`` tokens are already in the pages (an earlier chunk,
        or a shared prefix forked from the cache). ids [S] (padded to a
        bucket) hold the chunk, length is its valid token count; chunk
        token t sits at global position ``cache_len + t``. Writes the
        chunk's K/V into the pages, attends every chunk token over the
        gathered page timeline (cached prefix + chunk-so-far, causal),
        and returns (logits [vocab] at the chunk's last valid position,
        kp, vp).

        Bit-parity contract: for the same tokens, any chunking of a
        prompt through this entry point yields page contents and final
        logits BYTE-identical to one ``prefill`` call (float32 pool;
        see docs/serving.md for the reduced-precision-pool caveat) —
        the attention is the same ``_sdpa`` masked form over the same
        values, and padded/garbage context rows are exact zeros in the
        softmax."""
        s = ids.shape[0]
        with jax.named_scope("embedding"):
            x = w["embed"][ids][None]                       # [1, S, hid]
        pos = (cache_len + jnp.arange(s, dtype=jnp.int32))[None]
        kp, vp = list(kp), list(vp)
        capacity = block_table.shape[0] * _pages_geometry(kp[0])[1]
        # keep[q, c]: context position c visible to chunk token q
        # (causal over the global timeline; unwritten/garbage rows fall
        # outside it and contribute exact zeros after the softmax)
        keep = (
            jnp.arange(capacity, dtype=jnp.int32)[None, :]
            <= pos[0][:, None]
        )[None, None]                                   # [1, 1, S, C]
        for li in range(self.num_layers):
            wl = w["layers"][li]
            with jax.named_scope("attention"):
                h = _rms_norm(x, wl["ln1"], epsilon=self.eps)
                q, k, v = self._qkv(wl, h, 1, s)
                q, k = _rope_qk(q, k, pos, base=self.rope_theta)
                kp[li] = _write_chunk_pages(
                    kp[li], k[0], block_table, length, cache_len
                )
                vp[li] = _write_chunk_pages(
                    vp[li], v[0], block_table, length, cache_len
                )
                # [1, C, kv, d]
                kc = _gather_context(kp[li], block_table)[None]
                vc = _gather_context(vp[li], block_table)[None]
                if self.num_kv_heads != self.num_heads:
                    rep = self.num_heads // self.num_kv_heads
                    kc = jnp.repeat(kc, rep, axis=2)
                    vc = jnp.repeat(vc, rep, axis=2)
                attn = _sdpa(q, kc, vc, keep, is_causal=False)
                x = x + _row_matmul(
                    attn.reshape(1, s, -1), wl["wo"], self.tp_spec
                )
            x = self._mlp(wl, x)
        with jax.named_scope("lm_head_loss"):
            x = _rms_norm(x, w["norm"], epsilon=self.eps)
            h_last = jnp.take(x[0], length - 1, axis=0)     # [hid]
            logits = self._logits(w, h_last)
        return logits, tuple(kp), tuple(vp)

    def decode(self, w, kp, vp, tokens, positions, block_tables, active):
        """tokens/positions [slots], block_tables [slots, P], active
        [slots] bool. Returns (logits [slots, vocab], kp, vp)."""
        from ..kernels.pallas.paged_attention import update_pages

        b = tokens.shape[0]
        n_blocks, bs_pg = _pages_geometry(kp[0])
        capacity = block_tables.shape[1] * bs_pg
        # inactive slots: write position at capacity -> update_pages drops
        write_pos = jnp.where(active, positions, capacity)
        lengths = positions + 1   # the new token attends to itself
        if self.tp_spec is not None:
            # sharded pool: precompute the head-sliced scatter routing
            # (_write_window_pages with a 1-token window). update_pages
            # scatters with EXPLICIT kv-head indices, which GSPMD
            # cannot prove shard-local on a head-sharded pool — the
            # window form leaves the head dim a full slice, so every
            # chip scatters only its own heads. Values written are
            # identical either way (same routing trick, same casts).
            wpos = write_pos[:, None]                  # [slots, 1]
            dphys, dslot = _window_routing(
                block_tables, wpos, wpos < capacity, n_blocks, bs_pg,
            )
        with jax.named_scope("embedding"):
            x = w["embed"][tokens]                         # [slots, hid]
        kp, vp = list(kp), list(vp)
        for li in range(self.num_layers):
            wl = w["layers"][li]
            with jax.named_scope("attention"):
                h = _rms_norm(x, wl["ln1"], epsilon=self.eps)
                q, k, v = self._qkv(wl, h[:, None, :], b, 1)
                q, k = _rope_qk(q, k, positions[:, None], base=self.rope_theta)
                if self.tp_spec is not None:
                    kp[li] = _write_window_pages(kp[li], k, dphys, dslot)
                    vp[li] = _write_window_pages(vp[li], v, dphys, dslot)
                else:
                    kp[li], vp[li] = update_pages(
                        kp[li], vp[li], k[:, 0], v[:, 0], block_tables,
                        write_pos,
                    )
                attn = _paged_attn(
                    q[:, 0], kp[li], vp[li], block_tables, lengths,
                    kernel=self.decode_kernel,
                )                                          # [slots, heads, d]
                x = x + _row_matmul(
                    attn.reshape(b, -1), wl["wo"], self.tp_spec
                )
            x = self._mlp(wl, x)
        with jax.named_scope("lm_head_loss"):
            x = _rms_norm(x, w["norm"], epsilon=self.eps)
            logits = self._logits(w, x)
        return logits, tuple(kp), tuple(vp)

    def verify(self, w, kp, vp, tokens, positions, draft_lens,
               block_tables, active):
        """Speculative verification: score a K+1-token window for every
        slot in ONE launch. ``tokens`` [slots, S] (S = K+1) holds each
        slot's pending ``last_token`` at column 0 and its drafted
        continuation after it; window token j sits at GLOBAL position
        ``positions[slot] + j``. ``draft_lens`` [slots] counts valid
        draft tokens, so columns 0..draft_lens are real and columns
        with index > ``draft_lens`` are padding: their page writes are
        routed out of bounds and their logits are garbage the engine
        never reads — same for inactive slots.
        Returns (logits [slots, S, vocab], kp, vp) where row j scores
        the token FOLLOWING position ``positions[slot] + j``.

        Bit-parity contract: attention runs in the exact ``_sdpa``
        masked form over the gathered page timeline that ``prefill_ext``
        (and ``generate``'s cached branch) uses — the form PR 8 proved
        byte-identical to the one-shot program — and each slot's rows
        reduce independently of the batch dimension, so row 0's logits
        (and the K/V written for accepted positions) are byte-identical
        to what the plain decode step would have produced. A rejected
        position's write is DEAD: the engine advances ``num_cached``
        only by the accepted count, the causal ``keep`` mask of every
        later launch stops at the query's own position, and a later
        write at the same position overwrites it."""
        b, s = tokens.shape
        n_blocks, bs_pg = _pages_geometry(kp[0])
        capacity = block_tables.shape[1] * bs_pg
        offs = jnp.arange(s, dtype=jnp.int32)[None]        # [1, S]
        pos = positions[:, None] + offs                    # [slots, S]
        valid = (
            active[:, None]
            & (offs <= draft_lens[:, None])
            & (pos < capacity)
        )
        phys, slot = _window_routing(
            block_tables, pos, valid, n_blocks, bs_pg,
        )
        # keep[q, c] per slot: context position c visible to window
        # token q — causal over the global timeline, so a valid query
        # only ever sees history plus THIS launch's earlier writes
        # (stale rejected-draft rows sit beyond it and mask to exact
        # zeros after the softmax)
        keep = (
            jnp.arange(capacity, dtype=jnp.int32)[None, None, :]
            <= pos[:, :, None]
        )[:, None]                                         # [b, 1, S, C]
        with jax.named_scope("embedding"):
            x = w["embed"][tokens]                             # [b, S, hid]
        kp, vp = list(kp), list(vp)
        for li in range(self.num_layers):
            wl = w["layers"][li]
            with jax.named_scope("attention"):
                h = _rms_norm(x, wl["ln1"], epsilon=self.eps)
                q, k, v = self._qkv(wl, h, b, s)
                q, k = _rope_qk(q, k, pos, base=self.rope_theta)
                kp[li] = _write_window_pages(kp[li], k, phys, slot)
                vp[li] = _write_window_pages(vp[li], v, phys, slot)
                kc = _gather_context_batch(kp[li], block_tables)
                vc = _gather_context_batch(vp[li], block_tables)
                if self.num_kv_heads != self.num_heads:
                    rep = self.num_heads // self.num_kv_heads
                    kc = jnp.repeat(kc, rep, axis=2)
                    vc = jnp.repeat(vc, rep, axis=2)
                attn = _sdpa(q, kc, vc, keep, is_causal=False)
                x = x + _row_matmul(
                    attn.reshape(b, s, -1), wl["wo"], self.tp_spec
                )
            x = self._mlp(wl, x)
        with jax.named_scope("lm_head_loss"):
            x = _rms_norm(x, w["norm"], epsilon=self.eps)
            logits = self._logits(w, x)
        return logits, tuple(kp), tuple(vp)


def build_adapter(model):
    """Resolve the adapter for ``model``: pass-through for objects already
    exposing the adapter surface, ``LlamaServingAdapter`` for Llama."""
    if all(hasattr(model, a) for a in required_attrs):
        return model
    from ..models.llama import LlamaForCausalLM

    if isinstance(model, LlamaForCausalLM):
        return LlamaServingAdapter(model)
    raise TypeError(
        f"cannot serve {type(model).__name__}: pass an adapter exposing "
        f"{required_attrs} or a LlamaForCausalLM"
    )
