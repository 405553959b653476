"""Gluon Block / HybridBlock.

TPU-native re-design of ``python/mxnet/gluon/block.py`` (1,755 LoC).

``Block`` keeps the reference's contract: attribute assignment registers
children/parameters, ``collect_params`` walks the tree with structural names,
``__call__`` runs ``forward`` with hook support, save/load_parameters use
structural names.

``HybridBlock.hybridize()`` is where the design diverges on purpose: the
reference traces forward under *deferred compute* into an nnvm graph and
compiles a ``CachedOp`` (block.py:993 _build_cache → cached_op.cc).  Here the
whole forward (including parameter reads, RNG, and BatchNorm state updates)
is staged into ONE pure JAX function and handed to ``jax.jit`` — XLA then
owns CSE/fusion/memory-planning, which is the entire point of a TPU-first
executor (SURVEY.md §7 step 3: CachedOp-analog = whole-graph jit).  Under
``autograd.record()`` the compiled graph is differentiated as a single tape
node via ``jax.vjp`` — the analog of CachedOp recording itself as one
``_CachedOp`` node on the tape (cached_op.cc:776).
"""
from __future__ import annotations

import json
import re
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as onp

from .. import autograd
from .. import config as _config
from .. import random as _random
from ..base import MXNetError
from ..context import Context, cpu, current_context
from ..ndarray import NDArray
from ..ndarray.ndarray import _wrap
from .parameter import Constant, DeferredInitializationError, Parameter

__all__ = ["Block", "HybridBlock", "SymbolBlock"]


def _flatten_args(args):
    """Flatten nested (tuple/list/dict) args into NDArray leaves + treedef."""
    leaves: List[Any] = []

    def rec(x):
        if isinstance(x, NDArray):
            leaves.append(x)
            return ("_leaf_", len(leaves) - 1)
        if isinstance(x, (list, tuple)):
            return type(x)(rec(v) for v in x)
        if isinstance(x, dict):
            return {k: rec(v) for k, v in x.items()}
        return ("_const_", x)

    struct = rec(list(args))
    return leaves, struct


def _unflatten_args(struct, leaves):
    def rec(x):
        if isinstance(x, tuple) and len(x) == 2 and x[0] == "_leaf_":
            return leaves[x[1]]
        if isinstance(x, tuple) and len(x) == 2 and x[0] == "_const_":
            return x[1]
        if isinstance(x, (list, tuple)):
            return type(x)(rec(v) for v in x)
        if isinstance(x, dict):
            return {k: rec(v) for k, v in x.items()}
        return x

    out = rec(struct)
    return tuple(out)


def _flatten_output(out):
    """Flatten forward() output into NDArray leaves + rebuild closure."""
    leaves: List[NDArray] = []

    def rec(x):
        if isinstance(x, NDArray):
            leaves.append(x)
            return ("_leaf_", len(leaves) - 1)
        if isinstance(x, (list, tuple)):
            return type(x)(rec(v) for v in x)
        if isinstance(x, dict):
            return {k: rec(v) for k, v in x.items()}
        return ("_const_", x)

    struct = rec(out)
    return leaves, struct


def _rebuild_output(struct, leaves):
    def rec(x):
        if isinstance(x, tuple) and len(x) == 2 and x[0] == "_leaf_":
            return leaves[x[1]]
        if isinstance(x, tuple) and len(x) == 2 and x[0] == "_const_":
            return x[1]
        if isinstance(x, (list, tuple)):
            return type(x)(rec(v) for v in x)
        if isinstance(x, dict):
            return {k: rec(v) for k, v in x.items()}
        return x

    return rec(struct)


def _stage_fn(fn, params, names, in_struct, training, wrap_ctx, flavor=None):
    """Stage an NDArray-level callable into a PURE function of
    ``(param_arrays, input_arrays, rng_key)`` suitable for ``jax.jit``.

    This is the CachedOp-analog staging machinery shared by
    ``HybridBlock._build_cache`` (whole-forward compilation) and
    ``cached_step.TrainStep`` (whole-train-step compilation): traced
    parameter arrays are installed into the live Parameter replicas for
    the duration of one call of ``fn`` (recording off, ``training`` mode
    set, RNG drawing from the traced key chain), and parameter MUTATION
    (BatchNorm running stats etc.) is detected via version bumps and
    returned as extra functional outputs.

    Returns ``(raw_fn, out_struct, mutated_names)``; ``out_struct[0]``
    and ``mutated_names`` are filled in during the first trace.
    ``raw_fn`` returns ``([out_leaf_arrays], [mutated_param_arrays])``.
    """
    out_struct: List[Any] = [None]
    mutated_names: List[str] = []

    def raw_fn(param_arrays, input_arrays, rng_key):
        installed = []
        for n, arr in zip(names, param_arrays):
            for d in params[n]._data:
                installed.append((d, d._data, d._version))
                d._data = arr
        _random.push_trace_key(rng_key)
        prev_rec = autograd.set_recording(False)
        prev_train = autograd.set_training(training)
        try:
            leaves = [_wrap(a, wrap_ctx, flavor) for a in input_arrays]
            call_args = _unflatten_args(in_struct, leaves)
            # the one place a program's forward is traced: every block
            # call below runs under its device scope (_device_scope)
            out = fn(*call_args)
            out_leaves, struct = _flatten_output(out)
            out_struct[0] = struct
            # detect mutation per param via version bump on any replica
            # (BatchNorm running stats etc. become extra functional
            # outputs); must read BEFORE the finally restores buffers
            mutated_names.clear()
            mut_vals = []
            offset = 0
            for n in names:
                reps = params[n]._data
                entries = installed[offset : offset + len(reps)]
                offset += len(reps)
                if any(d._version != ver for (d, _o, ver) in entries):
                    mutated_names.append(n)
                    mut_vals.append(reps[0]._data)
        finally:
            autograd.set_recording(prev_rec)
            autograd.set_training(prev_train)
            _random.pop_trace_key()
            # restore in the finally so a FAILED trace (non-stageable
            # forward) cannot leak tracers into live parameter buffers —
            # TrainStep's eager fallback runs on these same Parameters
            for d, old, ver in installed:
                d._data = old
                d._version = ver
        return [o._data for o in out_leaves], mut_vals

    return raw_fn, out_struct, mutated_names


def _device_scope(block):
    """The ``jax.named_scope`` a block's forward is traced under while a
    program is staged (``_stage_fn``'s ``raw_fn`` is running, which
    ``random.in_trace`` tells): ``<ClassName>`` for a block nobody
    registered, ``<ClassName>.<key>`` for a child, ``<key>`` being the
    attribute name or index it was registered under.  The scopes nest as
    the calls do and land in the ``op_name`` of every HLO instruction
    (``program_store.Program.op_names``), so device time can be read by
    block and, under jax's own ``jvp(``/``transpose(`` wrappers, by pass.
    Entered only while tracing: a steady step pays nothing, an eager call
    tests one flag (docs/OBSERVABILITY.md, "Device scopes")."""
    cls = type(block).__name__
    key = block._scope_key
    return jax.named_scope(cls if key is None else f"{cls}.{key}")


def remat_call(blocks, x):
    """``blocks`` called one after another on ``x``.  While a program is
    staged (``_stage_fn`` is tracing) the run is ONE rematerialised segment:
    ``jax.checkpoint`` keeps nothing of it for the backward but its input
    and the parameters it reads, and the backward recomputes the rest.  For
    a run of cheap elementwise blocks behind a tensor the backward keeps
    anyway (a batch norm keeps its input): XLA left to itself keeps such a
    run's outputs when they are narrow and recomputes them when they are
    wide, so the peak would depend on the activations' type.  An eager call
    is a plain call.

    A parameter the run writes (a batch norm's running statistics) leaves
    the segment as an output and is written back outside it, so no tracer
    of the segment's own trace stays in a live buffer.  A block that draws
    random numbers cannot be in the run (the key chain would keep one)."""
    if not _random.in_trace():
        for block in blocks:
            x = block(x)
        return x
    replicas = [d for block in blocks
                for p in block.collect_params().values()
                for d in (p._data or ())]
    written = []

    def segment(arr):
        held = [d._data for d in replicas]
        y = _wrap(arr, x.ctx, type(x))
        for block in blocks:
            y = block(y)
        written[:] = [d for d, old in zip(replicas, held)
                      if d._data is not old]
        new = [d._data for d in written]
        for d, old in zip(replicas, held):
            d._data = old
        return y._data, new

    out, new = jax.checkpoint(segment)(x._data)
    for d, val in zip(written, new):
        d._data = val           # its version was bumped inside the segment
    return _wrap(out, x.ctx, type(x))


class _BlockScope:
    """Tracks hook handles."""

    _counter = [0]

    @classmethod
    def next_uid(cls):
        cls._counter[0] += 1
        return cls._counter[0]


class HookHandle:
    """Removable hook handle (reference block.py:62)."""

    def __init__(self, hooks_dict, hid):
        self._hooks_dict = hooks_dict
        self._id = hid

    def detach(self):
        self._hooks_dict.pop(self._id, None)

    remove = detach

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.detach()


class Block:
    """Base class for all neural network layers and models (reference
    ``python/mxnet/gluon/block.py`` class Block)."""

    # the name the newest parent registered this block under (an attribute
    # name or an index); _device_scope reads it
    _scope_key: Optional[str] = None

    def __init__(self):
        self._children: "OrderedDict[str, Block]" = OrderedDict()
        self._reg_params: "OrderedDict[str, Parameter]" = OrderedDict()
        self._forward_hooks: "OrderedDict[int, Callable]" = OrderedDict()
        self._forward_pre_hooks: "OrderedDict[int, Callable]" = OrderedDict()

    # -- registration ----------------------------------------------------
    def __setattr__(self, name, value):
        if isinstance(value, Block):
            existing = self.__dict__.get("_children", {})
            existing[name] = value
            value._scope_key = name
        elif isinstance(value, Parameter):
            if not hasattr(self, "_reg_params"):
                raise RuntimeError(
                    "Block.__init__() must be called before assigning Parameters"
                )
            self._reg_params[name] = value
            if value._name == "weight" and name != "weight":
                # attribute name is the canonical leaf name in 2.0 naming
                value._name = name
        super().__setattr__(name, value)

    def register_child(self, block: "Block", name: Optional[str] = None):
        if name is None:
            name = str(len(self._children))
        self._children[name] = block
        block._scope_key = name
        return block

    # -- hooks -----------------------------------------------------------
    def register_forward_pre_hook(self, hook):
        hid = _BlockScope.next_uid()
        self._forward_pre_hooks[hid] = hook
        return HookHandle(self._forward_pre_hooks, hid)

    def register_forward_hook(self, hook):
        hid = _BlockScope.next_uid()
        self._forward_hooks[hid] = hook
        return HookHandle(self._forward_hooks, hid)

    def register_op_hook(self, callback, monitor_all=False):
        """Per-op monitoring (reference MXCachedOpRegisterOpHook).  On the
        TPU backend per-op hooks only fire on non-hybridized execution."""
        from ..ndarray import ndarray as _ndmod

        _ndmod._op_monitor = (callback, monitor_all)

    def apply(self, fn):
        for child in self._children.values():
            child.apply(fn)
        fn(self)
        return self

    # -- params ----------------------------------------------------------
    @property
    def params(self) -> Dict[str, Parameter]:
        return dict(self._reg_params)

    def collect_params(self, select: Optional[str] = None) -> Dict[str, Parameter]:
        """Structural-name → Parameter over the whole tree (reference
        block.py collect_params; 2.0 structural naming '0.weight')."""
        out: "OrderedDict[str, Parameter]" = OrderedDict()

        def walk(block: "Block", prefix: str):
            for name, p in block._reg_params.items():
                out[prefix + name] = p
            for cname, child in block._children.items():
                walk(child, prefix + cname + ".")

        walk(self, "")
        if select is not None:
            pat = re.compile(select)
            out = OrderedDict((k, v) for k, v in out.items() if pat.match(k))
        for k, v in out.items():
            v._structure = k
        return out

    def initialize(self, init=None, ctx=None, verbose=False, force_reinit=False):
        from ..initializer import Uniform, create

        params = self.collect_params()
        if init is None:
            init = Uniform()
        else:
            init = create(init) if not callable(init) else init
        if verbose and hasattr(init, "set_verbosity"):
            init.set_verbosity(verbose=verbose)
        for p in params.values():
            p.initialize(None, ctx, default_init=init, force_reinit=force_reinit)

    def save_parameters(self, filename: str, deduplicate: bool = False):
        """Save with structural names (reference block.py:339)."""
        params = self.collect_params()
        arrays = {}
        seen = {}
        for name, p in params.items():
            if p._data is None and p._deferred_init:
                p._finish_deferred_init()
            if deduplicate and id(p) in seen:
                continue
            seen[id(p)] = name
            arrays[name] = p._reduce().asnumpy()
        onp.savez(_npz_path(filename), **arrays)
        import os

        if os.path.exists(filename + ".npz") and filename != filename + ".npz":
            os.replace(filename + ".npz", filename)

    def load_parameters(
        self,
        filename: str,
        ctx=None,
        allow_missing: bool = False,
        ignore_extra: bool = False,
        cast_dtype: bool = False,
        dtype_source: str = "current",
    ):
        """Load structural-name keyed file (reference block.py:381)."""
        loaded = _load_param_file(filename)
        params = self.collect_params()
        if not allow_missing:
            missing = [k for k in params if k not in loaded]
            if missing:
                raise AssertionError(
                    f"Parameter(s) {missing} are missing in file '{filename}'. "
                    "Set allow_missing=True to ignore."
                )
        extra = [k for k in loaded if k not in params]
        if extra and not ignore_extra:
            raise AssertionError(
                f"Parameter(s) {extra} loaded from file '{filename}' are not "
                "present in this Block. Set ignore_extra=True to ignore."
            )
        if ctx is not None and isinstance(ctx, Context):
            ctx = [ctx]
        for k, v in loaded.items():
            if k in params:
                params[k]._load_init(v, ctx, cast_dtype=cast_dtype,
                                     dtype_source=dtype_source)

    def load_dict(self, param_dict, ctx=None, allow_missing=False,
                  ignore_extra=False, cast_dtype=False, dtype_source="current"):
        params = self.collect_params()
        if not allow_missing:
            missing = [k for k in params if k not in param_dict]
            if missing:
                raise AssertionError(f"Parameter(s) {missing} missing from dict")
        for k, v in param_dict.items():
            if k in params:
                params[k]._load_init(v, [ctx] if isinstance(ctx, Context) else ctx,
                                     cast_dtype=cast_dtype, dtype_source=dtype_source)
            elif not ignore_extra:
                raise AssertionError(f"Parameter {k} not present in this Block")

    def share_parameters(self, shared: Dict[str, Parameter]):
        """Share parameters from another block (reference 2.0 API)."""
        params = self.collect_params()
        for k, v in shared.items():
            if k not in params:
                raise ValueError(f"no parameter named {k} in this block")
            self._replace_param(k, v)
        return self

    def _replace_param(self, structural_name: str, new_param: Parameter):
        parts = structural_name.split(".")
        block = self
        for part in parts[:-1]:
            block = block._children[part]
        attr = parts[-1]
        block._reg_params[attr] = new_param
        object.__setattr__(block, attr, new_param)

    def cast(self, dtype):
        for p in self.collect_params().values():
            p.cast(dtype)
        for b in self._children.values():
            b._on_cast(dtype)
        self._on_cast(dtype)
        return self

    def _on_cast(self, dtype):
        pass

    def zero_grad(self):
        for p in self.collect_params().values():
            p.zero_grad()

    def reset_ctx(self, ctx):
        for p in self.collect_params().values():
            p.reset_ctx(ctx)

    def hybridize(self, active=True, **kwargs):
        for child in self._children.values():
            child.hybridize(active, **kwargs)

    def infer_shape(self, *args):
        raise ValueError(
            f"{type(self).__name__} has parameters with unknown shape. You "
            "must implement infer_shape(self, *args) for deferred "
            "initialization, or specify input sizes explicitly."
        )

    def _deferred_infer_shape(self, *args):
        self.infer_shape(*args)
        for p in self._reg_params.values():
            if p._deferred_init:
                p._finish_deferred_init()

    # -- execution -------------------------------------------------------
    def __call__(self, *args, **kwargs):
        if _random.in_trace():
            with _device_scope(self):
                return self._call_forward(args, kwargs)
        return self._call_forward(args, kwargs)

    def _call_forward(self, args, kwargs):
        for hook in self._forward_pre_hooks.values():
            hook(self, args)
        try:
            out = self.forward(*args, **kwargs)
        except DeferredInitializationError:
            self._deferred_infer_shape(*args)
            out = self.forward(*args, **kwargs)
        for hook in self._forward_hooks.values():
            hook(self, args, out)
        return out

    def forward(self, *args, **kwargs):
        # 1.x-style migration shim: a subclass that defines
        # hybrid_forward(self, F, x, ..., <param kwargs>) but no forward
        # runs through it with F = the nd namespace and its registered
        # parameters passed as kwargs — the reference 1.x calling
        # convention (block.py hybrid_forward dispatch).
        if hasattr(self, "hybrid_forward"):
            from .. import ndarray as F

            ctx = None
            for a in args:
                if hasattr(a, "ctx"):
                    ctx = a.ctx
                    break
            params = {}
            for name, p in self._reg_params.items():
                try:
                    params[name] = p.data(ctx)
                except DeferredInitializationError:
                    raise DeferredInitializationError(
                        f"hybrid_forward compatibility path cannot infer "
                        f"the shape of parameter '{name}' — give the "
                        f"layer explicit input sizes (in_units/"
                        f"in_channels) or define forward() instead")
            return self.hybrid_forward(F, *args, **params, **kwargs)
        raise NotImplementedError

    def summary(self, *inputs):
        """Print a per-layer summary table (reference block.py summary)."""
        summary: "OrderedDict[str, dict]" = OrderedDict()
        hooks = []

        def register(block, prefix):
            def hook(blk, inp, out):
                name = f"{prefix}{type(blk).__name__}"
                n = len(summary)
                key = f"{name}-{n + 1}"
                leaves, _ = _flatten_output(out)
                summary[key] = {
                    "output_shape": [tuple(l.shape) for l in leaves],
                    "n_params": sum(
                        int(onp.prod(p.shape)) if p.shape else 0
                        for p in blk._reg_params.values()
                        if p.shape is not None
                    ),
                }

            hooks.append(block.register_forward_hook(hook))
            for cname, child in block._children.items():
                register(child, prefix)

        register(self, "")
        try:
            self(*inputs)
        finally:
            for h in hooks:
                h.detach()
        lines = [f"{'Layer':<40}{'Output Shape':<30}{'Params':<12}", "=" * 82]
        total = 0
        for k, v in summary.items():
            lines.append(f"{k:<40}{str(v['output_shape']):<30}{v['n_params']:<12}")
            total += v["n_params"]
        lines.append("=" * 82)
        lines.append(f"Total params (leaf blocks): {total}")
        print("\n".join(lines))

    def __repr__(self):
        s = f"{type(self).__name__}(\n"
        for name, child in self._children.items():
            child_repr = repr(child).replace("\n", "\n  ")
            s += f"  ({name}): {child_repr}\n"
        return s + ")"


class HybridBlock(Block):
    """Block compilable into a single XLA computation (reference
    HybridBlock, gluon/block.py:900+)."""

    def __init__(self):
        super().__init__()
        self._active = False
        self._flags: Dict[str, Any] = {}
        # cache: (training, input treedef signature) -> compiled record,
        # this block's keyspace in the ProgramStore 'hybrid_forward'
        # namespace — shared LRU/metrics surface, capped by
        # MXNET_FORWARD_CACHE / MXNET_PROGRAM_CACHE_CAPS.  Records stay
        # plain jit callables (shape-level programs live inside each
        # record's jax.jit cache — one treedef key serves every shape,
        # and the recording path differentiates THROUGH the callable —
        # so no AOT executable pinning here; the bucket policy is what
        # bounds shape proliferation on variable-shape streams)
        from .. import program_store as _pstore

        self._cached = _pstore.scope("hybrid_forward")
        # opt-in shape bucketing for the inference path
        # (hybridize(bucket=True) + MXNET_SHAPE_BUCKETS): batch axis pads
        # up to the bucket grid, outputs slice back, verified bit-exact
        # once per bucket — refused (sticky, reason kept) on mismatch
        self._bucket = False
        self._bucket_refused: Optional[str] = None
        self._bucket_verified: set = set()
        self._backend = None
        self._backend_flags: Dict[str, Any] = {}
        self._in_specs = None  # (struct, [(shape, dtype)]) from last call
        from .. import config as _config

        # reference MXNET_BACKWARD_DO_MIRROR: recompute-in-backward default
        self._remat = bool(_config.get("MXNET_BACKWARD_DO_MIRROR"))
        self._remat_policy = None

    def hybridize(self, active=True, backend=None, clear=True, remat=None,
                  remat_policy=None, bucket=None, **kwargs):
        """Activate whole-graph compilation.  ``static_alloc``/``static_shape``
        are accepted for API parity; XLA's buffer assignment subsumes them.

        ``bucket=True`` opts the INFERENCE path (not training, not
        recording) into shape bucketing (``serving.BucketPolicy`` /
        ``MXNET_SHAPE_BUCKETS``): the batch axis pads up to the bucket
        grid so a variable-length stream compiles a bounded program set,
        and outputs slice back to the true length.  The first call per
        bucket is verified bit-exact against the unpadded eager forward;
        a model whose outputs couple across the batch axis fails that
        check and bucketing is refused (sticky,
        ``self._bucket_refused``) — results stay correct either way.

        ``remat=True`` rematerializes the forward during backward
        (``jax.checkpoint``): activations are not kept alive between the
        passes, trading one extra forward's FLOPs for peak-memory — the
        TPU-native analog of the reference's gradient mirroring
        (MXNET_BACKWARD_DO_MIRROR, src/nnvm/gradient.cc mirror path).
        ``remat_policy`` names a jax.checkpoint_policies entry (e.g.
        'dots_saveable') for selective saving.  Default follows the
        MXNET_BACKWARD_DO_MIRROR env var."""
        if remat is not None:
            self._remat = bool(remat)
        if bucket is not None:
            self._bucket = bool(bucket)
        if remat_policy is not None:     # keep a previously-set policy
            import jax

            if not hasattr(jax.checkpoint_policies, remat_policy):
                valid = [p for p in dir(jax.checkpoint_policies)
                         if not p.startswith("_")]
                raise ValueError(
                    f"unknown remat_policy {remat_policy!r}; valid "
                    f"jax.checkpoint_policies names: {valid}")
            self._remat_policy = remat_policy
        self._active = active
        self._backend = backend
        self._flags.update(kwargs)
        # flags destined for the backend transform are only those passed
        # alongside THIS backend selection (parity flags like static_alloc
        # accumulate in _flags but never leak into backend transforms)
        self._backend_flags = dict(kwargs) if backend is not None else {}
        if clear:
            self._cached.clear()
        super().hybridize(active=False if active else active)
        # note: only the outermost hybridized block compiles; children run
        # inside its trace (the reference inlines children the same way).

    def optimize_for(self, x, *args, backend=None, **kwargs):
        self.hybridize(True, backend=backend, **kwargs)
        return self(x, *args)

    def _ensure_initialized(self, *args):
        """Complete any deferred param init by probing with abstract eval."""
        params = self.collect_params()
        deferred = [p for p in params.values() if p._data is None]
        if not deferred:
            return False
        # run one eager forward: layer-local infer_shape hooks complete init
        return True

    def __call__(self, *args, **kwargs):
        leaves, struct = _flatten_args((list(args), dict(kwargs)))
        self._in_specs = (struct,
                          [(l.shape, l._data.dtype) for l in leaves])
        if not self._active:
            return super().__call__(*args, **kwargs)
        params = self.collect_params()
        if any(p._data is None for p in params.values()):
            # first call completes deferred init eagerly, like the reference's
            # infer-shape-then-build-cache dance (block.py:993)
            out = super().__call__(*args, **kwargs)
            return out
        for hook in self._forward_pre_hooks.values():
            hook(self, args)
        out = self._call_cached(*args, **kwargs)
        for hook in self._forward_hooks.values():
            hook(self, args, out)
        return out

    # -- the CachedOp analog --------------------------------------------
    def _call_cached(self, *args, **kwargs):
        if kwargs:
            # keyword args become part of the static signature
            args = args + tuple(kwargs.values())
        training = autograd.is_training()
        if (self._bucket and not training and not autograd.is_recording()
                and self._bucket_refused is None):
            out = self._call_bucketed(args)
            if out is not _NO_BUCKET:
                return out
        in_leaves, in_struct = _flatten_args(args)
        from ..ndarray import ndarray as _ndmod

        ctx = in_leaves[0].ctx if in_leaves else current_context()
        # array FLAVOR of the call (np vs legacy nd) is part of the
        # signature: the trace wraps its tracers in that flavor so
        # flavor-sensitive semantics inside forward (np comparisons yield
        # bool; nd yields float 0/1) match the eager path exactly
        out_cls = _ndmod._flavor_of(in_leaves)
        # ctx is part of the signature: the trace wraps its tracers in
        # that ctx so layers doing ``weight.data(x.ctx)`` resolve a
        # replica that actually exists (a net re-homed by reset_ctx and
        # called on the new device would otherwise trace against the
        # stale default ctx and fail the replica lookup)
        sig = (training, _ndmod._amp_generation, _struct_key(in_struct),
               ctx, out_cls)
        rec = self._cached.lookup(sig)
        if rec is None:
            rec = self._build_cache(in_struct, training, ctx, out_cls)
            self._cached.insert(sig, rec)
        jitted, names, params, ctx_idx, out_struct, mutated_names = rec
        param_arrays = [params[n]._data[_ctx_index(params[n], ctx)]._data
                        for n in names]
        input_arrays = [l._data for l in in_leaves]
        key = _random.next_key()

        recording = autograd.is_recording() and (
            any(p.grad_req != "null" for p in params.values())
            or any(l._ag_node is not None or l._ag_grad_req != "null"
                   for l in in_leaves)
        )
        if recording:
            fn = lambda ps, ins: jitted(ps, ins, key)
            (out_arrays, mut_vals), vjp_fn = jax.vjp(fn, param_arrays, input_arrays)
            node_inputs = [params[n]._data[_ctx_index(params[n], ctx)]
                           for n in names] + list(in_leaves)

            def node_vjp(out_cts, _vjp=vjp_fn, _muts=mut_vals):
                cts = list(out_cts) if isinstance(out_cts, tuple) else [out_cts]
                mct = [_zero_ct(m) for m in _muts]
                pcts, icts = _vjp((cts, mct))
                return tuple(list(pcts) + list(icts))

            def node_fn(*flat, _fn=fn, _np=len(names)):
                # replayable pure fn over the flat node_inputs layout
                # (params then data); mutated aux state is dropped — only
                # the differentiable outputs are replayed
                outs, _muts = _fn(list(flat[:_np]), list(flat[_np:]))
                return tuple(outs)

            node = autograd.TapeNode(
                node_vjp,
                node_inputs,
                len(out_arrays),
                [tuple(o.shape) for o in out_arrays],
                [o.dtype for o in out_arrays],
                name=type(self).__name__,
                fn=node_fn,
                input_vals=list(param_arrays) + list(input_arrays),
            )
            out_nd = []
            for i, o in enumerate(out_arrays):
                w = _wrap(o, ctx, out_cls)
                w._ag_node = node
                w._ag_out_index = i
                out_nd.append(w)
        else:
            out_arrays, mut_vals = jitted(param_arrays, input_arrays, key)
            out_nd = [_wrap(o, ctx, out_cls) for o in out_arrays]

        for n, v in zip(mutated_names, mut_vals):
            params[n]._data[_ctx_index(params[n], ctx)]._set_data(v)
        return _rebuild_output(out_struct[0], out_nd)

    def _call_bucketed(self, args):
        """Shape-bucketed inference dispatch (hybridize(bucket=True)):
        pad the batch axis to its bucket, run the padded program, slice
        outputs back — a variable-length stream then compiles one
        program per bucket instead of one per length.  The first call
        per bucketed signature is verified bit-exact against the
        unpadded eager forward; mismatch (outputs coupling across the
        batch axis) refuses bucketing for this block, sticky, and the
        verified-correct eager result is returned.  Returns
        ``_NO_BUCKET`` when padding does not apply (exact fit, policy
        off, no common batch axis)."""
        from .. import serving as _serving

        policy = _serving.BucketPolicy()
        if not policy.enabled:
            return _NO_BUCKET
        leaves, struct = _flatten_args(args)
        if not leaves or any(len(l.shape) < 1 for l in leaves):
            return _NO_BUCKET
        n = int(leaves[0].shape[0])
        if any(int(l.shape[0]) != n for l in leaves):
            return _NO_BUCKET
        b = policy.bucket(n)
        if b is None or b == n:
            return _NO_BUCKET
        padded = [_wrap(_serving.pad_axis0(l._data, b), l.ctx, type(l))
                  for l in leaves]
        out = self._call_cached(*_unflatten_args(struct, padded))
        out_leaves, out_struct = _flatten_output(out)
        if any(len(o.shape) < 1 or int(o.shape[0]) != b
               for o in out_leaves):
            self._bucket_refused = (
                "output does not carry the batch axis — cannot slice "
                "padded rows back")
            with autograd.pause():
                return self.forward(*args)
        sliced = [_wrap(o._data[:n], o.ctx, type(o)) for o in out_leaves]
        result = _rebuild_output(out_struct, sliced)
        key = (_struct_key(struct), b,
               tuple((tuple(l.shape), str(l._data.dtype)) for l in leaves))
        verify = int(_config.get("MXNET_SERVE_VERIFY"))
        if verify and key not in self._bucket_verified:
            with autograd.pause():
                ref = self.forward(*args)
            ref_leaves, _ = _flatten_output(ref)
            for g, r in zip(sliced, ref_leaves):
                gn, rn = g.asnumpy(), r.asnumpy()
                if gn.shape == rn.shape and onp.array_equal(gn, rn):
                    continue
                # last-ulp kernel rounding is accepted at the default
                # level (same compiled-vs-eager property as hybridize);
                # real cross-batch coupling lands far outside and
                # refuses; MXNET_SERVE_VERIFY=2 refuses both
                if gn.shape == rn.shape and verify < 2 and \
                        onp.allclose(gn, rn, rtol=1e-5, atol=1e-6):
                    continue
                self._bucket_refused = (
                    "padded+sliced forward not bit-exact vs unpadded "
                    "eager (outputs couple across the batch axis) — "
                    "bucketing refused for this block")
                return ref
            self._bucket_verified.add(key)
        return result

    def _build_cache(self, in_struct, training, ctx=None, flavor=None):
        wrap_ctx = ctx or current_context()
        params = OrderedDict(
            (n, p) for n, p in self.collect_params().items() if p._data is not None
        )
        names = list(params)
        ctx_idx = 0

        def forward(*call_args):
            # the compiled block's own scope: its __call__ went to
            # _call_cached, not through Block.__call__
            with _device_scope(self):
                return self.forward(*call_args)

        raw_fn, out_struct, mutated_names = _stage_fn(
            forward, params, names, in_struct, training, wrap_ctx, flavor)

        if self._backend:
            # optimize_for backend: a registered transform of the traced
            # pure function, applied before jit (the SubgraphProperty/
            # MXOptimizeForBackend analog — see library.register_backend)
            from ..library import get_backend
            from ..symbol.subgraph import SubgraphProperty

            backend = get_backend(self._backend)
            if isinstance(backend, SubgraphProperty):
                raise MXNetError(
                    f"backend '{self._backend}' is a SubgraphProperty — "
                    "apply it on the exported Symbol via "
                    "Symbol.optimize_for (hybridized blocks take "
                    "traced-function transforms)")
            raw_fn = backend(raw_fn, **getattr(self, "_backend_flags", {}))
        if getattr(self, "_remat", False):
            # recompute-in-backward (reference mirror path): checkpoint the
            # traced forward so vjp keeps only the inputs alive
            policy = None
            if getattr(self, "_remat_policy", None):
                policy = getattr(jax.checkpoint_policies, self._remat_policy)
            raw_fn = jax.checkpoint(raw_fn, policy=policy)

        def fwd_fn(param_arrays, input_arrays, rng_key,
                   _raw_fn=raw_fn):
            from .. import program_store as _pstore

            _pstore.count_trace("hybrid_forward")
            return _raw_fn(param_arrays, input_arrays, rng_key)

        jitted = jax.jit(fwd_fn)
        return (jitted, names, params, ctx_idx, out_struct, mutated_names)

    # -- trace to Symbol / export ---------------------------------------
    def _trace_symbol(self):
        """Trace ``forward`` under deferred compute into a Symbol whose
        variables are ``dataN`` inputs + structurally-named parameters
        (reference _build_cache tracing, block.py:993 → dc.get_symbol)."""
        from .. import _deferred_compute as dc

        if self._in_specs is None:
            raise MXNetError(
                "run at least one forward pass before export/tracing so "
                "input shapes are known")
        struct, specs = self._in_specs
        params = OrderedDict(
            (n, p) for n, p in self.collect_params().items()
            if p._data is not None)
        saved = []
        leaves = []
        try:
            with autograd.pause(), dc.deferred_compute():
                for i, (shp, dt) in enumerate(specs):
                    arr = _wrap(jnp.zeros(shp, dt), current_context())
                    dc.set_variable(arr, f"data{i}" if len(specs) > 1
                                    else "data")
                    leaves.append(arr)
                for n, p in params.items():
                    for rep in p._data:
                        saved.append((rep, rep._dc_sym))
                        dc.set_variable(rep, n)
                call_args, call_kwargs = _unflatten_args(struct, leaves)
                out = self.forward(*call_args, **call_kwargs)
            out_leaves, _ = _flatten_output(out)
            return dc.get_symbol(out_leaves)
        finally:
            for rep, prev in saved:
                rep._dc_sym = prev

    def export(self, path: str, epoch: int = 0, remove_amp_cast=True):
        """Serialize the traced graph + params (reference block.py:1299
        export → path-symbol.json + path-NNNN.params)."""
        sym = self._trace_symbol()
        params_file = f"{path}-{epoch:04d}.params"
        self.save_parameters(params_file)
        sym.save(f"{path}-symbol.json")
        return f"{path}-symbol.json", params_file


class SymbolBlock(HybridBlock):
    """Run a symbolic graph as a Block (reference block.py:1485).

    Holds a :class:`mxnet_tpu.symbol.Symbol`; variables found in the params
    file become trainable Parameters, the rest are runtime inputs.  The
    whole graph executes as one jit-compiled XLA program per input shape.
    """

    def __init__(self, outputs, inputs=None, params=None, ctx=None):
        super().__init__()
        from ..symbol.symbol import Symbol

        if isinstance(outputs, (list, tuple)):
            from ..symbol import Group

            outputs = Group(list(outputs))
        if not isinstance(outputs, Symbol):
            raise TypeError("SymbolBlock needs a Symbol")
        self._sym = outputs
        args = outputs.list_arguments()
        if inputs is None:
            inputs = [a for a in args if a == "data" or a.startswith("data")]
        elif isinstance(inputs, str):
            inputs = [inputs]
        else:
            inputs = [i.name if hasattr(i, "name") else i for i in inputs]
        self._input_names = inputs
        param_names = [a for a in args if a not in inputs]
        params = params or {}
        for n in param_names:
            if n in params:
                arr = params[n]
                np_arr = (arr.asnumpy() if isinstance(arr, NDArray)
                          else onp.asarray(arr))
                p = Parameter(n, shape=np_arr.shape, dtype=np_arr.dtype)
                p._load_init(np_arr,
                             [ctx] if isinstance(ctx, Context) else ctx)
            else:
                raise MXNetError(
                    f"SymbolBlock: no value provided for argument '{n}' "
                    f"(inputs={inputs})")
            self._reg_params[n] = p

    def forward(self, *args):
        from ..symbol.symbol import _jit_graph

        if len(args) != len(self._input_names):
            raise MXNetError(
                f"expected {len(self._input_names)} inputs "
                f"{self._input_names}, got {len(args)}")
        ctx = args[0].ctx if args else current_context()
        feed = {n: a._data for n, a in zip(self._input_names, args)}
        for n, p in self._reg_params.items():
            feed[n] = p._data[0]._data
        # differentiable through the tape: route via a single vjp node when
        # recording, like _call_cached does for hybridized blocks
        if autograd.is_recording():
            names = list(self._reg_params)
            pvals = [feed[n] for n in names]
            ivals = [feed[n] for n in self._input_names]

            def fn(ps, ins):
                f = dict(zip(names, ps))
                f.update(dict(zip(self._input_names, ins)))
                from ..symbol.symbol import execute_graph

                return execute_graph(self._sym._outputs, f)

            raw, vjp_fn = jax.vjp(fn, pvals, ivals)
            node_inputs = [self._reg_params[n]._data[0] for n in names] + \
                list(args)

            def node_vjp(out_cts, _vjp=vjp_fn):
                cts = list(out_cts) if isinstance(out_cts, tuple) \
                    else [out_cts]
                pcts, icts = _vjp(cts)
                return tuple(list(pcts) + list(icts))

            def node_fn(*flat, _fn=fn, _np=len(names)):
                return tuple(_fn(list(flat[:_np]), list(flat[_np:])))

            node = autograd.TapeNode(
                node_vjp, node_inputs, len(raw),
                [tuple(o.shape) for o in raw], [o.dtype for o in raw],
                name="SymbolBlock", fn=node_fn,
                input_vals=list(pvals) + list(ivals))
            outs = []
            for i, o in enumerate(raw):
                w = _wrap(o, ctx)
                w._ag_node = node
                w._ag_out_index = i
                outs.append(w)
        else:
            raw = _jit_graph(self._sym)(feed)
            outs = [_wrap(o, ctx) for o in raw]
        return outs[0] if len(outs) == 1 else outs

    @staticmethod
    def imports(symbol_file, input_names=None, param_file=None, ctx=None):
        """Load an exported model from symbol-json + params (reference
        block.py:1517)."""
        from .. import symbol as sym_mod
        from ..ndarray.utils import load as nd_load

        sym = sym_mod.load(symbol_file)
        params = {}
        if param_file:
            loaded = _load_param_file(param_file)
            params = {k: v for k, v in loaded.items()}
        if input_names is None:
            args = sym.list_arguments()
            input_names = [a for a in args if a not in params]
        return SymbolBlock(sym, input_names, params, ctx=ctx)


# sentinel: _call_bucketed declined (exact fit / policy off / no batch axis)
_NO_BUCKET = object()


# ---------------------------------------------------------------------------
def _npz_path(filename: str) -> str:
    return filename if filename.endswith(".npz") else filename


def _load_param_file(filename: str) -> Dict[str, onp.ndarray]:
    # reference-format .params (magic 0x112) load transparently — real
    # Apache-MXNet checkpoints feed load_parameters directly
    from ..ndarray import legacy_format

    loaded = legacy_format.load_if_legacy(filename)
    if loaded is not None:
        if not isinstance(loaded, dict):
            raise ValueError(
                f"{filename} is a legacy NDArray LIST; load_parameters "
                "needs a name-keyed save")
        # strip only the literal reference prefixes; anything else in the
        # key (scoped names containing ':') is part of the name
        out = {}
        for k, v in loaded.items():
            name = k[4:] if k.startswith(("arg:", "aux:")) else k
            if name in out:
                raise ValueError(
                    f"legacy checkpoint has colliding entries for {name!r} "
                    "(both arg: and aux:?)")
            out[name] = v
        return out
    with onp.load(filename, allow_pickle=False) as z:
        return {k: z[k] for k in z.files}


def _struct_key(struct):
    def rec(x):
        if isinstance(x, (list, tuple)):
            if len(x) == 2 and x[0] == "_leaf_":
                return ("L", x[1])
            if len(x) == 2 and x[0] == "_const_":
                return ("C", repr(x[1]))
            return tuple(rec(v) for v in x)
        if isinstance(x, dict):
            return tuple(sorted((k, rec(v)) for k, v in x.items()))
        return repr(x)

    return rec(struct)


def _ctx_index(param: Parameter, ctx: Context) -> int:
    if param._ctx_list is None or len(param._ctx_list) == 1:
        return 0
    for i, c in enumerate(param._ctx_list):
        if c == ctx:
            return i
    return 0


def _zero_ct(arr):
    if jnp.issubdtype(arr.dtype, jnp.floating) or jnp.issubdtype(
        arr.dtype, jnp.complexfloating
    ):
        return jnp.zeros(arr.shape, arr.dtype)
    return onp.zeros(arr.shape, jax.dtypes.float0)


def jax_bridge(fn, *inputs):
    """Differentiable eager-tape bridge for a pure-jax function.

    ``fn(*raw_arrays) -> pytree of arrays`` runs under ``jax.vjp``; the
    returned vjp closure is spliced into the autograd tape as ONE node
    (:class:`mxnet_tpu.autograd.Function`), so gradients flow through
    arbitrary jax code (``shard_map`` pipelines, MoE dispatch einsums)
    on the eager path exactly as they do inside the compiled step.
    ``inputs`` are NDArrays; the output pytree is NDArray-wrapped.
    """
    from .. import autograd as _ag

    state = {}

    def _flat_fn(*raw):
        out = fn(*raw)
        leaves, treedef = jax.tree_util.tree_flatten(out)
        state["treedef"] = treedef
        return tuple(leaves)

    class _Bridge(_ag.Function):
        def forward(self, *nd_in):
            ctx = nd_in[0].ctx
            self._ctx = ctx
            leaves, self._vjp = jax.vjp(
                _flat_fn, *[a._data for a in nd_in])
            return tuple(_wrap(l, ctx) for l in leaves)

        def backward(self, *out_grads):
            cts = tuple(g._data for g in out_grads)
            gins = self._vjp(cts)
            return tuple(_wrap(g, self._ctx) for g in gins)

    outs = _Bridge()(*inputs)
    return jax.tree_util.tree_unflatten(state["treedef"], list(outs))
