"""jit staging implementation.

The functionalization contract: eager Tensors are Python objects whose
payload (`_data`) we swap for tracers during the single trace, then restore.
Anything the traced body mutates (parameters via the optimizer update,
buffers via BatchNorm, the RNG key) is lifted to explicit inputs/outputs of
the staged function — the XLA analogue of the reference's inplace pass +
variable-scope binding (fluid/pir/transforms/general/inplace_pass.cc;
new_executor/pir_adaptor value binding).

Because Tensor is pytree-registered, jax.jit moves whole Tensor-bearing
structures across the staging boundary directly; outputs come back as fresh
detached Tensors.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core import autograd
from ..core import random as random_mod
from ..core.tensor import Tensor
from ..nn.layer.layers import Layer
from ..observability import jit_events
from ..observability.spans import span

_NOT_TO_STATIC = set()

# monotonic instance tokens for the compile-log signatures: id(self)
# is reused by the allocator after collection (and truncating it can
# collide two LIVE instances), which would alias a fresh instance's
# first compile onto a dead one's warm signature — a false
# retrace-after-warmup alarm
import itertools as _itertools  # noqa: E402
import re as _re  # noqa: E402

_instance_tokens = _itertools.count(1)

# default object.__repr__ shape: "<pkg.Cls object at 0x7f...>" — a
# process-local address that must never reach a cross-process cache key
_ADDR_REPR = _re.compile(r" at 0x[0-9a-fA-F]+>")


def not_to_static(fn):
    """Mark a function to stay eager (ref: jit/api.py not_to_static)."""
    _NOT_TO_STATIC.add(fn)
    return fn


def ignore_module(modules):
    """API-parity no-op: jax tracing handles arbitrary modules."""
    return None


def _swap_payloads(tensors, arrays):
    old = [t._data for t in tensors]
    for t, a in zip(tensors, arrays):
        t._data = a
    return old


class _rng_lift:
    """Swap the global generator key for a per-call traced key during
    staging, so dropout etc. draw from a fresh key every execution instead
    of a constant baked at trace time."""

    def __init__(self, key):
        self._key = key

    def __enter__(self):
        self._saved = random_mod.default_generator._key
        random_mod.default_generator._key = self._key
        return self

    def final_key(self):
        return random_mod.default_generator._key

    def __exit__(self, *exc):
        random_mod.default_generator._key = self._saved
        return False


def _to_arrays(tree):
    return jax.tree_util.tree_map(
        lambda x: x._data if isinstance(x, Tensor) else x,
        tree,
        is_leaf=lambda x: isinstance(x, Tensor),
    )


class _nan_net:
    """Staged NaN/Inf debug net (FLAGS_check_nan_inf inside jit).

    While tracing, collects each dispatched op's isfinite-violated flag
    (core.dispatch routes them here instead of a host callback — pure
    dataflow, so it works on backends without callback support). The
    flags become ONE stacked bool output of the staged program; `raise_if`
    checks it on the host after execution and names the first bad op —
    the staged analogue of the reference's static-executor check
    (fluid/framework/new_executor/nan_inf_utils.cc)."""

    def __init__(self, enabled):
        self.enabled = enabled
        self.names = []
        self._collector = [] if enabled else None

    def __enter__(self):
        if self.enabled:
            from ..core import dispatch

            self._prev = dispatch.set_nan_collector(self._collector)
        return self

    def __exit__(self, *exc):
        if self.enabled:
            from ..core import dispatch

            dispatch.set_nan_collector(self._prev)
        return False

    def flags_output(self):
        if not self.enabled or not self._collector:
            return jnp.zeros((0,), jnp.bool_)
        self.names = [n for n, _ in self._collector]
        return jnp.stack([b for _, b in self._collector])

    def raise_if(self, flags_value):
        if not self.enabled or flags_value is None:
            return
        import numpy as np

        vals = np.asarray(flags_value)
        if vals.size and vals.any():
            from ..core import flags as flags_mod
            from ..core.dispatch import _nan_inf_report

            idx = int(np.argmax(vals))
            _nan_inf_report(
                True, self.names[idx],
                flags_mod.get_flag("FLAGS_check_nan_inf_level"),
            )


def _nan_check_enabled():
    from ..core import flags as flags_mod

    return bool(flags_mod.get_flag("FLAGS_check_nan_inf"))


class StaticFunction:
    """Stage a tensor function or Layer forward into one XLA computation
    (ref: jit/dy2static/program_translator.py:397 StaticFunction).

    Parameters/buffers are lifted to inputs on every call (cheap: array
    handles), so eager updates between calls are honoured without
    retracing; buffer mutations inside forward (BatchNorm running stats)
    come back as outputs and are rebound after execution. jax.jit is the
    compile cache (keyed on input shapes/dtypes — the reference keys its
    _ExecutorCache on program+scope, base/executor.py:869).

    Training works: when grads are enabled, the staged program is recorded
    on the eager tape as ONE op whose vjp is the transposed compiled
    program (jax.vjp of a jitted function runs compiled in both
    directions) — the analogue of the reference's RunProgramOp wrapping a
    fwd/bwd partial-program pair (jit/dy2static/partial_program.py).
    """

    def __init__(self, function, layer=None, check=None, cache=None):
        self._function = function
        self._layer = layer
        if layer is not None:
            self._params = [p for _, p in layer.named_parameters()]
            self._buffers = [b for _, b in layer.named_buffers()]
        else:
            self._params = []
            self._buffers = []
        self._core = None
        self._out_tree = None
        self._nan_nets = {}
        self._cur_nan_key = None
        if check not in (None, "warn", "error"):
            raise ValueError(
                f'check must be None, "warn" or "error", got {check!r}'
            )
        self._check = check
        self._checked_sigs = set()
        self._instance_tok = next(_instance_tokens)
        # persistent compile cache (paddle_tpu.compilecache): eval-mode
        # calls run through AOT executables keyed on the function's
        # bytecode fingerprint + abstract signature, loaded from disk
        # by a later process with zero tracing. None disables.
        self._cache_spec = cache
        self._cc = None            # resolved lazily
        self._code_fp = None
        self._aot = {}             # sig -> (compiled, user out_tree)
        self._warned_unstable = False

    def _run_check(self, args, kwargs, sig):
        """``to_static(check=...)`` choke point: on the first call per
        input signature (``sig`` — the same key the nan net uses), run
        the static analyzer over the function (trace only, nothing
        executes) and warn/raise per mode BEFORE the real staging trace
        — so e.g. a host-sync lands as a structured AnalysisError with
        provenance instead of a raw TracerBoolConversionError."""
        if sig in self._checked_sigs:
            return
        from .. import analysis

        # check_call, not check: user kwargs named mode/passes/... must
        # reach the analyzed function, not the analyzer's options
        report = analysis.check_call(self, args, kwargs, mode=self._check)
        analysis.enforce(
            report, self._check,
            what=f"to_static(check={self._check!r}) analysis of "
            f"{getattr(self._function, '__name__', self._function)!r}",
        )
        # marked checked only on a pass: a blocking finding re-raises
        # (as a structured AnalysisError) on every call, instead of
        # degrading to the raw tracer error on the second one
        self._checked_sigs.add(sig)

    def _build_core(self):
        fn = self._function
        params, buffers = self._params, self._buffers
        outer = self
        self._built_nan = _nan_check_enabled()

        def core(param_arrays, buffer_arrays, key, in_flat, in_meta,
                 mode=None):
            """in_flat: flat tensor-slot arrays; in_meta: (treedef, flat
            template with None at tensor slots, slot indices) — static.
            ``mode`` (static) carries the layer's train/eval flag into
            the trace-cache key: the flag shapes the traced program
            (dropout, batchnorm) but is invisible to the abstract
            signature, and jax caches lowerings per signature — without
            it, lowering after a train()/eval() flip would silently
            reuse the other mode's trace."""
            jit_events.mark_traced()  # compile/retrace event log
            treedef, template, slots = in_meta
            flat = list(template)
            for i, a in zip(slots, in_flat):
                flat[i] = Tensor(a, stop_gradient=True)
            args, kwargs = jax.tree_util.tree_unflatten(treedef, flat)
            old_p = _swap_payloads(params, param_arrays)
            old_b = _swap_payloads(buffers, buffer_arrays)
            net = _nan_net(outer._built_nan)
            try:
                with _rng_lift(key) as lift:
                    with net, autograd.no_grad():
                        out = fn(*args, **kwargs)
                    new_key = lift.final_key()
                out_flat, out_tree = jax.tree_util.tree_flatten(
                    out, is_leaf=lambda x: isinstance(x, Tensor)
                )
                outer._out_tree = out_tree
                out_arrays = [
                    o._data if isinstance(o, Tensor) else o for o in out_flat
                ]
                new_buf = [b._data for b in buffers]
                nan_flags = net.flags_output()
                # one net per trace: jax.jit caches per shape signature,
                # so flag indices must decode with THAT trace's op list
                outer._nan_nets[outer._cur_nan_key] = net
            finally:
                _swap_payloads(params, old_p)
                _swap_payloads(buffers, old_b)
            return out_arrays, new_buf, new_key, nan_flags

        return jax.jit(core, static_argnames=("in_meta", "mode"))

    @staticmethod
    def _is_data(x):
        import numpy as np

        return isinstance(x, (Tensor, jax.Array, np.ndarray))

    def _split_inputs(self, args, kwargs):
        """Split (args, kwargs) into traced data slots and a hashable
        static template (treedef + non-data leaves)."""
        flat, treedef = jax.tree_util.tree_flatten(
            (args, kwargs), is_leaf=lambda x: isinstance(x, Tensor)
        )
        slots = tuple(i for i, x in enumerate(flat) if self._is_data(x))
        arrays = [
            flat[i]._data if isinstance(flat[i], Tensor) else flat[i]
            for i in slots
        ]
        template = tuple(
            None if self._is_data(x) else x for x in flat
        )
        return arrays, (treedef, template, slots)

    # -- persistent compile cache (paddle_tpu.compilecache) ------------------
    def _call_aot(self, sig, param_arrays, buf_arrays, key, in_arrays,
                  in_meta):
        """Run this signature through an AOT executable: loaded from
        the persistent compile cache (zero traces — recorded as an
        ``aot-hit`` event) or compiled once via ``self._core.lower``
        (the probes fire normally) and serialized for the next process.
        Returns ``(outs, new_buf, nan_flags)``; ``self._out_tree`` is
        restored from the artifact so unflattening works without the
        trace that normally populates it."""
        # the layer's train/eval flag shapes the traced program (dropout,
        # batchnorm) but is invisible to the abstract signature — key on
        # it, or a train()-mode call could replay an eval-mode executable
        # (in-process or from a previous process's artifact)
        mode = getattr(self._layer, "training", None)
        entry = self._aot.get((sig, mode))
        if entry is None:
            entry = self._aot_load_or_compile(
                sig, param_arrays, buf_arrays, key, in_arrays, in_meta,
                mode,
            )
            self._aot[(sig, mode)] = entry
        exe, out_tree = entry
        self._out_tree = out_tree
        outs, new_buf, _, nflags = exe(
            param_arrays, buf_arrays, key, in_arrays
        )
        return outs, new_buf, nflags

    def _aot_load_or_compile(self, sig, param_arrays, buf_arrays, key,
                             in_arrays, in_meta, mode=None):
        import pickle

        from .. import compilecache as cc_mod

        if self._cc is None:
            self._cc = cc_mod.resolve(self._cache_spec)
        cc = self._cc
        if self._code_fp is None:
            self._code_fp = cc_mod.code_fingerprint(self._function) or ""
        name = getattr(self._function, "__name__", "staged_fn")
        cache_name = f"to_static.{name}"
        # disk key: bytecode fingerprint + abstract input signature +
        # the static input template — NOT the instance token (a fresh
        # process's instance must hit the previous process's artifact).
        # Caveat (docs/compilecache.md): the fingerprint covers this
        # function's own bytecode, not its callees' — see
        # compilecache.code_fingerprint.
        meta_token = repr(in_meta)
        # a static arg with a default object repr embeds a process-local
        # address: the key would be unique per process — every restart
        # a miss plus a freshly-stored orphan artifact. Such signatures
        # compile in-memory only.
        disk_ok = bool(self._code_fp) and not _ADDR_REPR.search(
            meta_token
        )
        if self._code_fp and not disk_ok and not self._warned_unstable:
            self._warned_unstable = True
            import sys

            sys.stderr.write(
                f"[compilecache] {cache_name}: a static argument has no "
                "stable repr (address-bearing); this signature is "
                "compiled per process, not disk-cached\n"
            )
        sig_str = (
            f"to_static:{self._code_fp}:"
            + cc_mod.signature_str((
                cc_mod.abstractify(param_arrays),
                cc_mod.abstractify(buf_arrays),
                cc_mod.abstractify(key),
                cc_mod.abstractify(in_arrays),
            ))
            + f":meta={meta_token}:mode={mode}"
        )
        store_key = cc.key(cache_name, sig_str)
        if disk_ok:
            # the out-tree sidecar unpickles inside finish= so a damaged
            # sidecar falls back (counted + warned, no aot-hit recorded)
            # exactly like a damaged executable
            got = cc.load_executable_bundle(
                store_key, name=cache_name, signature=sig_str,
                finish=lambda exe, meta, blobs: (
                    exe, pickle.loads(blobs["out_tree"])
                ),
            )
            if got is not None:
                return got
        # fresh compile: lowering traces core once (mark_traced fires
        # under the caller's watch), which also populates
        # self._out_tree as a trace side effect
        exe = self._core.lower(
            param_arrays, buf_arrays, key, in_arrays, in_meta, mode
        ).compile()
        out_tree = self._out_tree
        if disk_ok:
            cc.store_executable(
                store_key, exe, name=cache_name, signature=sig_str,
                extra_blobs={"out_tree": pickle.dumps(out_tree)},
            )
        return exe, out_tree

    def __call__(self, *args, **kwargs):
        if self._core is not None and (
            getattr(self, "_built_nan", False) != _nan_check_enabled()
        ):
            self._core = None  # debug-net toggle changes the program
        if self._core is None:
            self._core = self._build_core()
        in_arrays, in_meta = self._split_inputs(args, kwargs)
        sig = (
            in_meta,
            tuple(
                (tuple(a.shape), str(a.dtype))
                for a in in_arrays if hasattr(a, "shape")
            ),
        )
        if self._check is not None:
            self._run_check(args, kwargs, sig)
        self._cur_nan_key = sig
        buf_arrays = [b._data for b in self._buffers]
        key = random_mod.default_generator.split_key()
        params = self._params
        n_out = [None]

        train_mode = autograd.is_grad_enabled() and any(
            not p.stop_gradient for p in params
        )
        # compile/retrace event log: the watch supplies identity +
        # elapsed for any trace core fires during this call; train and
        # eval trace distinct programs (vjp vs plain), so they are
        # distinct signatures, not retraces of each other
        # the instance token keeps two DISTINCT functions that share a
        # name (every Layer's 'forward') from reading as retraces of
        # each other — the alarm must only fire when THIS function's
        # already-warm signature traces again
        _watch = jit_events.watch(
            getattr(self._function, "__name__", "staged_fn"),
            kind="to_static",
            signature=f"{self._instance_tok:x}:"
            f"{hash(sig) & 0xFFFFFFFF:08x}"
            f":{'train' if train_mode else 'eval'}",
        )
        if train_mode:
            core = self._core
            n_p = len(params)

            def impl(*arrays):
                outs, new_buf, _, nflags = core(
                    list(arrays[:n_p]), buf_arrays, key,
                    list(arrays[n_p:]), in_meta,
                )
                n_out[0] = len(outs)
                return tuple(outs) + tuple(new_buf) + (nflags,)

            from ..core import dispatch

            flat_all = jax.tree_util.tree_flatten(
                (args, kwargs), is_leaf=lambda x: isinstance(x, Tensor)
            )[0]
            slot_vals = [flat_all[i] for i in in_meta[2]]
            in_tensors = [
                v if isinstance(v, Tensor) else Tensor(v, stop_gradient=True)
                for v in slot_vals
            ]
            with _watch:
                results = dispatch.call(
                    "jit_program", impl,
                    tuple(params) + tuple(in_tensors), {},
                )
            results = (
                list(results) if isinstance(results, (tuple, list))
                else [results]
            )
            k = n_out[0]
            out_flat = results[:k]
            new_buf = results[k:-1]
            nflags = results[-1]
            if self._built_nan and nflags is not None:
                self._nan_nets[self._cur_nan_key].raise_if(nflags._data)
            for b, nb in zip(self._buffers, new_buf):
                if nb is not None:
                    b._rebind(nb.detach()._data)
            return jax.tree_util.tree_unflatten(self._out_tree, out_flat)

        if self._cache_spec is not None and not self._built_nan:
            # persistent-compile-cache path (eval only: the train path
            # routes through the tape's vjp machinery, and the nan
            # debug net needs a live trace to decode its flag indices)
            with _watch:
                outs, new_buf, nflags = self._call_aot(
                    sig, [p._data for p in params], buf_arrays, key,
                    in_arrays, in_meta,
                )
        else:
            with _watch:
                outs, new_buf, _, nflags = self._core(
                    [p._data for p in params], buf_arrays, key,
                    in_arrays, in_meta,
                )
        if self._built_nan:
            self._nan_nets[self._cur_nan_key].raise_if(nflags)
        for b, a in zip(self._buffers, new_buf):
            b._rebind(a)
        out_flat = [
            Tensor(a, stop_gradient=True) if isinstance(a, jax.Array) else a
            for a in outs
        ]
        return jax.tree_util.tree_unflatten(self._out_tree, out_flat)


def to_static(function=None, input_spec=None, build_strategy=None,
              backend=None, full_graph=True, check=None, cache=None,
              **kwargs):
    """Decorator/wrapper staging a function or Layer (ref: jit/api.py:197).

    ``input_spec``/``build_strategy``/``backend`` are accepted for API
    parity; shapes are taken from the first call (jax.jit caches per
    shape signature, recompiling per new signature — the bucketing
    policy replacing the reference's symbolic-shape DimExpr machinery).

    ``check="warn"|"error"`` runs the static analyzer
    (``paddle_tpu.analysis``) over the function on the first call per
    input signature: host syncs, retrace hazards, dtype drift etc.
    surface as structured findings (warned or raised) before staging.

    ``cache=`` (a directory path or ``compilecache.CompileCache``)
    persists eval-mode compiled executables to disk: a later process
    staging the same function over the same signature loads the
    executable with zero tracing and zero compilation
    (docs/compilecache.md). Training calls and the NaN debug net bypass
    the cache.
    """
    if check is not None and not full_graph:
        raise ValueError(
            "check= requires full_graph=True (the graph-break fallback "
            "intentionally tolerates host syncs)"
        )
    if cache is not None and not full_graph:
        raise ValueError(
            "cache= requires full_graph=True (graph-break segments "
            "trace per-branch and are not AOT-serializable as one "
            "program)"
        )

    def _wrap(obj):
        if isinstance(obj, Layer):
            if full_graph:
                sf = StaticFunction(obj.forward, layer=obj, check=check,
                                    cache=cache)
            else:
                from .graph_break import GraphBreakFunction

                sf = GraphBreakFunction(obj.forward, layer=obj)
            obj.forward = sf
            return obj
        if obj in _NOT_TO_STATIC:
            return obj
        if not full_graph:
            from .graph_break import GraphBreakFunction

            return GraphBreakFunction(obj)
        return StaticFunction(obj, check=check, cache=cache)

    if function is not None:
        return _wrap(function)
    return _wrap


class TrainStep:
    """Whole-train-step staging: fwd + bwd + clip + optimizer update in ONE
    XLA program with donated parameter/optimizer-state buffers.

    The analogue of the reference's Plan/Job executor path
    (new_executor/standalone_executor.cc:47) composed with its inplace pass:
    XLA sees the complete step, fuses across the fwd/bwd boundary, and
    writes parameter updates in place via donation.

    One boundary it may NOT fuse across: each gradient crosses from the
    backward pass to the optimizer through a
    ``jax.lax.optimization_barrier`` of its own (the identity; one a leaf,
    so no two gradients are held live together for it). Without it XLA
    makes the optimizer's update of a weight the epilogue of the matmul
    that produces the weight's gradient, ``dW = x^T dy``: every output
    tile then reads both moments and the master weight in float32, and
    the matmul runs at half the speed of the step's others: on a v5e the
    barrier took a Granite-4.0-h training step from 1,129 to 990 ms and a
    Mistral-7B step from 513 to 479 ms (PERF.md, PR 32).

        step = paddle.jit.TrainStep(model, loss_fn, optimizer)
        loss = step(x, y)      # loss_fn(model, x, y) -> scalar loss

    ``loss_fn(model, *args, **kwargs)`` runs the forward and returns the
    scalar loss; everything it does is staged. The LR schedule and
    GradScaler found_inf enter as scalar operands (no recompile per step).

    ``accum_steps=k`` stages GRADIENT ACCUMULATION (the reference's
    gradient-merge pass, distributed/passes/auto_parallel_gradient_merge.py)
    as a ``lax.scan`` over k micro-batches: every data input's leading
    batch axis is split [B] -> [k, B//k], the scan body runs fwd+bwd on
    one micro-batch (so only ONE micro-batch's activations are ever
    live), gradients accumulate in fp32 through the carry, and a single
    optimizer update runs on the mean gradient — numerically the step a
    k-times-larger batch would take. Composes with ZeRO: stage>=2
    gradient shardings constrain the carry, so the running sum stays
    reduce-scattered across the mesh inside the scan.
    """

    def __init__(self, model, loss_fn, optimizer, donate=True,
                 accum_steps=None):
        self._model = model
        self._loss_fn = loss_fn
        self._opt = optimizer
        self._donate = donate
        if accum_steps is None:
            accum_steps = getattr(
                optimizer, "gradient_accumulation_steps", 1
            )
        self._accum = int(accum_steps)
        if self._accum < 1:
            raise ValueError(
                f"accum_steps must be >= 1, got {accum_steps}"
            )
        self._params = [
            p for p in optimizer._parameter_list
            if getattr(p, "trainable", not p.stop_gradient)
        ]
        self._buffers = [b for _, b in model.named_buffers()]
        self._compiled = None
        self._live_idx = None  # params that actually received grads
        self._nan_nets = {}
        self._cur_nan_key = None
        self._instance_tok = next(_instance_tokens)

    def _build(self):
        model, loss_fn, opt = self._model, self._loss_fn, self._opt
        params, buffers = self._params, self._buffers
        opt_step_fn = opt._make_step_fn()
        self._built_nan = _nan_check_enabled()
        k = self._accum

        def constrain(i, g):
            # ZeRO stage>=2: constrain the gradient's layout in-program
            # so XLA reduce-scatters instead of all-reducing. Shardings
            # were precomputed from concrete payloads in _prepare (params
            # are tracers here).
            shardings = self._grad_shardings
            if shardings is None or shardings[i] is None:
                return g
            return jax.lax.with_sharding_constraint(g, shardings[i])

        def fwd_bwd(key, tree):
            """Forward and backward of one batch; its gradients are left
            on the parameters."""
            for p in params:
                p.grad = None
                p._grad_node = None
            net = _nan_net(self._built_nan)
            with _rng_lift(key) as lift:
                args, kwargs = tree
                with net:
                    loss = loss_fn(model, *args, **kwargs)
                    loss.backward()
                new_key = lift.final_key()
            self._nan_nets[self._cur_nan_key] = net
            live_idx = [i for i, p in enumerate(params) if p.grad is not None]
            return loss._data, live_idx, net, new_key

        def split(a):
            if not hasattr(a, "shape") or a.ndim == 0:
                raise ValueError(
                    "accum_steps requires every data input to "
                    "have a leading batch axis to micro-split; "
                    f"got {a!r}"
                )
            if a.shape[0] % k:
                raise ValueError(
                    f"batch axis {a.shape[0]} not divisible by "
                    f"accum_steps={k}"
                )
            return a.reshape((k, a.shape[0] // k) + a.shape[1:])

        def staged(param_arrays, buffer_arrays, states, lr, t, found_inf,
                   key, tree_args):
            jit_events.mark_traced()  # compile/retrace event log
            old_p = _swap_payloads(params, param_arrays)
            old_b = _swap_payloads(buffers, buffer_arrays)
            saved = [(p.grad, p._grad_node, p._out_index, p.stop_gradient)
                     for p in params]
            try:
                for p in params:
                    p.stop_gradient = False
                if k == 1:
                    loss_val, live_idx, net, new_key = fwd_bwd(key, tree_args)
                    # the seam (class docstring): one barrier a leaf, not
                    # one over the list, which would hold every gradient
                    # live at once
                    live_grads = [
                        jax.lax.optimization_barrier(
                            constrain(i, params[i].grad._data))
                        for i in live_idx]
                    new_buffer_arrays = [b._data for b in buffers]
                    flags = net.flags_output
                else:
                    # scan k micro-batches: gradients accumulate in fp32
                    # through the carry (ZeRO layouts constrain it, so the
                    # running sum stays sharded), buffers ride beside them
                    keys = jax.random.split(key, k + 1)
                    new_key = keys[0]
                    grad_acc0 = []
                    for i, a in enumerate(param_arrays):
                        half = a.dtype in (jnp.bfloat16, jnp.float16)
                        dt = jnp.float32 if half else a.dtype
                        grad_acc0.append(constrain(i, jnp.zeros(a.shape, dt)))
                    live_holder = []

                    def body(carry, xs):
                        grad_acc, bufs = carry
                        micro, key_i = xs
                        _swap_payloads(buffers, bufs)
                        loss_i, live_i, net, _ = fwd_bwd(key_i, micro)
                        live_holder.append(live_i)
                        new_acc = list(grad_acc)
                        for i in live_i:
                            g = params[i].grad._data.astype(grad_acc[i].dtype)
                            new_acc[i] = grad_acc[i] + constrain(i, g)
                        return ((new_acc, [b._data for b in buffers]),
                                (loss_i, net.flags_output()))

                    ((grad_acc, new_buffer_arrays),
                     (losses, nan_stack)) = jax.lax.scan(
                        body, (grad_acc0, list(buffer_arrays)),
                        (jax.tree_util.tree_map(split, tree_args),
                         keys[1:]),
                    )
                    live_idx = live_holder[0]
                    live_grads = [
                        (grad_acc[i] * (1.0 / k)).astype(param_arrays[i].dtype)
                        for i in live_idx
                    ]
                    loss_val = losses.mean()
                    flags = lambda: nan_stack.any(axis=0)  # noqa: E731

                if self._live_idx is None:
                    self._live_idx = live_idx
                live = [params[i] for i in live_idx]
                attrs = tuple(self._attr_for(p) for p in live)
                targets = tuple(self._out_shardings[i] for i in live_idx)
                with jax.named_scope("optimizer"):
                    new_live, new_states = opt_step_fn(
                        attrs, targets, lr, t, found_inf,
                        [p._data for p in live],
                        live_grads,
                        [states[i] for i in live_idx],
                    )
                new_param_arrays = list(param_arrays)
                out_states = list(states)
                for j, i in enumerate(live_idx):
                    new_param_arrays[i] = new_live[j]
                    out_states[i] = new_states[j]
                nan_flags = flags()  # after the update: the plain step's place
            finally:
                _swap_payloads(params, old_p)
                _swap_payloads(buffers, old_b)
                for p, (g, node, oi, sg) in zip(params, saved):
                    p.grad = g
                    p._grad_node = node
                    p._out_index = oi
                    p.stop_gradient = sg
            return (new_param_arrays, new_buffer_arrays, out_states,
                    loss_val, new_key, nan_flags)

        donate = (0, 2) if self._donate else ()
        return jax.jit(staged, donate_argnums=donate)

    def _attr_for(self, p):
        """Per-param static attrs, mirroring Optimizer._collect for one
        param (group lookup preserved)."""
        from ..optimizer.optimizer import _PAttr, _normalize_weight_decay

        opt = self._opt
        for group in opt._param_groups:
            if any(q is p for q in group["params"]):
                g_kind, g_coeff = opt._group_weight_decay(group)
                lr_scale = float(group.get("learning_rate", 1.0))
                break
        else:
            group, g_kind, g_coeff, lr_scale = None, None, 0.0, 1.0
        preg = getattr(p, "regularizer", None)
        if preg is not None:
            g_kind, g_coeff = _normalize_weight_decay(preg)
        decoupled, lr_ratio = opt._param_extras(p, group)
        return _PAttr(
            lr_scale=lr_scale
            * float(getattr(p, "optimize_attr", {}).get("learning_rate", 1.0)),
            reg_kind=g_kind,
            reg_coeff=g_coeff,
            need_clip=getattr(p, "need_clip", True),
            multi_precision=opt._use_master(p),
            decoupled_decay=decoupled,
            lr_ratio=lr_ratio,
        )

    def __call__(self, *args, **kwargs):
        """One step. Its host side is three spans under ``train_step``
        (``step=`` the optimizer's global step): ``prepare`` builds the
        arguments, ``launch`` is the compiled call, which returns before
        the device is done, ``rebind`` writes the results back."""
        opt = self._opt
        with span("train_step", step=opt._global_step + 1):
            with span("train_step.prepare"):
                operands = self._prepare(args, kwargs)
            with span("train_step.launch"), jit_events.watch(
                getattr(self._loss_fn, "__name__", "train_step"),
                kind="train_step",
                signature=f"{self._instance_tok:x}:"
                f"{hash(self._cur_nan_key) & 0xFFFFFFFF:08x}",
            ):
                (new_params, new_buffers, new_states, loss_val, _,
                 nan_flags) = self._compiled(*operands)
            with span("train_step.rebind"), autograd.no_grad():
                for p, a, ns in zip(self._params, new_params, new_states):
                    p._rebind(a)
                    p.grad = None
                    opt._accumulators[id(p)] = ns
                for b, a in zip(self._buffers, new_buffers):
                    b._rebind(a)
                opt._global_step += 1
        if self._built_nan:
            # raise AFTER rebinding: the pre-step buffers were donated,
            # so the new (NaN-carrying but valid) arrays must land on the
            # params or a caught error leaves the model pointing at
            # deleted buffers — resume from checkpoint to recover values
            self._nan_nets[self._cur_nan_key].raise_if(nan_flags)
        return Tensor(loss_val, stop_gradient=True)

    def _prepare(self, args, kwargs):
        """The compiled step's operands: optimizer state, the layouts the
        staged update is constrained to, lr, step, key, argument arrays."""
        opt = self._opt
        if self._compiled is not None and (
            getattr(self, "_built_nan", False) != _nan_check_enabled()
        ):
            self._compiled = None  # debug-net toggle changes the program
        if self._compiled is None:
            with span("train_step.build"):
                self._compiled = self._build()
        if all(id(p) in opt._accumulators for p in self._params):
            states = [opt._ensure_state(p) for p in self._params]
        else:
            # masters and moments are made here, on the first step: a
            # copy and two zeros a leaf, each shape its own small compile
            with span("optimizer.init_state",
                      leaves=len(self._params)) as made:
                states = [opt._ensure_state(p) for p in self._params]
                made.attrs["bytes"] = sum(
                    a.nbytes for a in jax.tree_util.tree_leaves(states))
        # concrete layouts, read before payloads become tracers (static
        # per-param out constraints for the staged optimizer update)
        self._out_shardings = tuple(
            opt._param_out_sharding(p._data, st)
            for p, st in zip(self._params, states)
        )
        grad_sharding = getattr(opt, "_grad_sharding_for", None)
        self._grad_shardings = (
            tuple(grad_sharding(p) for p in self._params)
            if grad_sharding is not None else None
        )
        from ..optimizer.optimizer import _found_inf_operand

        lr = jnp.float32(opt.get_lr())
        t = jnp.float32(opt._global_step + 1)
        found_inf = _found_inf_operand(opt)
        key = random_mod.default_generator.split_key()
        tree_args = (_to_arrays(args), _to_arrays(kwargs))
        self._cur_nan_key = (
            jax.tree_util.tree_structure(tree_args),
            tuple(
                (tuple(a.shape), str(a.dtype))
                for a in jax.tree_util.tree_leaves(tree_args)
                if hasattr(a, "shape")
            ),
        )
        return (
            [p._data for p in self._params],
            [b._data for b in self._buffers],
            states, lr, t, found_inf, key, tree_args,
        )
