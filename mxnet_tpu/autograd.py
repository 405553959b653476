"""Autograd: imperative tape + reverse-mode differentiation.

TPU-native re-design of the reference's autograd
(``src/imperative/imperative.cc`` RecordOp/Backward, ``python/mxnet/autograd.py``).

Design: while ``record()`` is active, every operator dispatch that touches a
tape-connected array runs through ``jax.vjp`` — the forward executes eagerly
(XLA op-by-op) and the returned ``vjp_fn`` closure is stored on a tape node.
``backward()`` walks nodes in reverse creation order, feeding output
cotangents into each node's ``vjp_fn`` and accumulating into leaf ``.grad``
buffers honouring ``grad_req`` ('write'/'add'/'null' — the reference's
kWriteTo/kAddTo/kNullOp in ``include/mxnet/op_attr_types.h``).

This replaces the reference's explicit gradient-graph construction
(``src/nnvm/gradient.cc`` MXGradient pass): jax's vjp machinery *is* the
FGradient registry, and XLA recompiles/fuses each backward segment.
"""
from __future__ import annotations

import heapq
import threading
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as onp

__all__ = [
    "record",
    "pause",
    "train_mode",
    "predict_mode",
    "is_recording",
    "is_training",
    "set_recording",
    "set_training",
    "mark_variables",
    "backward",
    "grad",
    "get_symbol",
    "Function",
]


class _AGState(threading.local):
    def __init__(self):
        super().__init__()
        self.recording = False
        self.training = False


_STATE = _AGState()


def is_recording() -> bool:
    return _STATE.recording


def is_training() -> bool:
    return _STATE.training


def set_recording(is_record: bool) -> bool:
    prev = _STATE.recording
    _STATE.recording = bool(is_record)
    return prev


def set_training(train_mode: bool) -> bool:
    prev = _STATE.training
    _STATE.training = bool(train_mode)
    return prev


class _RecordingStateScope:
    """Scope manager flipping (recording, training) — reference
    ``python/mxnet/autograd.py:93-120``."""

    def __init__(self, is_record: Optional[bool], train_mode: Optional[bool]):
        self._enter_is_record = is_record
        self._enter_train_mode = train_mode
        self._prev_is_record = None
        self._prev_train_mode = None

    def __enter__(self):
        if self._enter_is_record is not None:
            self._prev_is_record = set_recording(self._enter_is_record)
        if self._enter_train_mode is not None:
            self._prev_train_mode = set_training(self._enter_train_mode)
        return self

    def __exit__(self, ptype, value, trace):
        if self._enter_is_record is not None:
            set_recording(self._prev_is_record)
        if self._enter_train_mode is not None:
            set_training(self._prev_train_mode)


def record(train_mode: bool = True):
    """Returns a scope enabling recording (and by default training mode)."""
    return _RecordingStateScope(True, train_mode)


def pause(train_mode: bool = False):
    return _RecordingStateScope(False, train_mode)


def train_mode():
    return _RecordingStateScope(None, True)


def predict_mode():
    return _RecordingStateScope(None, False)


# ---------------------------------------------------------------------------
# Tape
# ---------------------------------------------------------------------------

_node_counter = [0]
_node_counter_lock = threading.Lock()


class TapeNode:
    """One recorded op: holds the vjp closure and the input wiring.

    ``inputs`` are the NDArray objects passed to the op (kept alive so leaf
    grads can be written); ``vjp_fn`` maps output cotangents -> input
    cotangents.  Analog of the reference's per-node ``AGInfo``
    (``include/mxnet/imperative.h:54-88``).
    """

    __slots__ = (
        "nid",
        "vjp_fn",
        "inputs",
        "num_outputs",
        "out_shapes",
        "out_dtypes",
        "name",
        "fn",
        "input_vals",
    )

    def __init__(self, vjp_fn, inputs, num_outputs, out_shapes, out_dtypes,
                 name="", fn=None, input_vals=None):
        with _node_counter_lock:
            _node_counter[0] += 1
            self.nid = _node_counter[0]
        self.vjp_fn = vjp_fn
        self.inputs = inputs
        self.num_outputs = num_outputs
        self.out_shapes = out_shapes
        self.out_dtypes = out_dtypes
        self.name = name
        # pure callable raw-arrays -> raw output(s); enables graph REPLAY
        # for create_graph (higher-order) gradients.  None for nodes whose
        # forward isn't a pure function of its inputs (custom Function).
        self.fn = fn
        # raw input arrays AT RECORD TIME: replay must see the values the
        # op actually consumed, not whatever the NDArrays hold later
        # (mutation-as-replacement can swap _data between record and grad)
        self.input_vals = input_vals


def mark_variables(variables, gradients, grad_reqs="write"):
    """Associate gradient buffers with variables (reference
    ``Imperative::MarkVariables``, imperative.cc:134)."""
    if isinstance(grad_reqs, str):
        grad_reqs = [grad_reqs] * len(variables)
    for var, g, req in zip(variables, gradients, grad_reqs):
        var._mark_variable(g, req)


def _toposort_backward(heads, head_grads, variables=None):
    """Reverse-order traversal over tape nodes reachable from heads.

    Returns (leaf_grads, var_cts): leaf_grads accumulates cotangents for
    node-less arrays with a grad_req; var_cts captures the full accumulated
    cotangent of any requested *intermediate* (op-output) array — possible
    because nodes are processed in strictly decreasing creation order, so by
    the time a node pops, all contributions to its outputs have arrived.
    """
    import jax.numpy as jnp

    capture = {}
    if variables:
        for v in variables:
            node = getattr(v, "_ag_node", None)
            if node is not None:
                capture.setdefault((node.nid, v._ag_out_index), []).append(v)
    var_cts: Dict[int, Any] = {}

    # cotangent accumulator per (node id) -> list per output slot
    node_cts: Dict[int, List[Any]] = {}
    nodes: Dict[int, TapeNode] = {}
    pq: List[Tuple[int, int]] = []  # max-heap via negative nid

    def _seed(node: TapeNode, slot: int, ct):
        if node.nid not in nodes:
            nodes[node.nid] = node
            node_cts[node.nid] = [None] * node.num_outputs
            heapq.heappush(pq, (-node.nid, node.nid))
        cur = node_cts[node.nid][slot]
        node_cts[node.nid][slot] = ct if cur is None else cur + ct

    leaf_grads: Dict[int, Tuple[Any, Any]] = {}  # id(arr) -> (arr, ct)

    def _accum_leaf(arr, ct):
        key = id(arr)
        if key in leaf_grads:
            leaf_grads[key] = (arr, leaf_grads[key][1] + ct)
        else:
            leaf_grads[key] = (arr, ct)

    for head, hg in zip(heads, head_grads):
        node = getattr(head, "_ag_node", None)
        if hg is None:
            ct = jnp.ones(head.shape, dtype=head._data.dtype)
        else:
            ct = hg._data if hasattr(hg, "_data") else jnp.asarray(hg)
        if node is not None:
            _seed(node, head._ag_out_index, ct)
        elif getattr(head, "_ag_grad_req", "null") != "null":
            _accum_leaf(head, ct)

    while pq:
        _, nid = heapq.heappop(pq)
        node = nodes.pop(nid)
        cts = node_cts.pop(nid)
        filled = [
            c
            if c is not None
            else jnp.zeros(node.out_shapes[i], dtype=node.out_dtypes[i])
            for i, c in enumerate(cts)
        ]
        for i in range(node.num_outputs):
            for arr in capture.get((nid, i), ()):
                var_cts[id(arr)] = filled[i]
        in_cts = node.vjp_fn(tuple(filled) if node.num_outputs > 1 else filled[0])
        for arr, ct in zip(node.inputs, in_cts):
            if ct is None or (hasattr(ct, "dtype") and ct.dtype == jax.dtypes.float0):
                continue
            sub = getattr(arr, "_ag_node", None)
            if sub is not None:
                _seed(sub, arr._ag_out_index, ct)
            elif getattr(arr, "_ag_grad_req", "null") != "null":
                _accum_leaf(arr, ct)

    return leaf_grads, var_cts


def backward(heads, head_grads=None, retain_graph=False, train_mode=True):
    """Compute gradients of heads w.r.t. all marked variables on the tape.

    Reference: ``MXAutogradBackwardEx`` -> ``Imperative::Backward``
    (imperative.cc:377).  ``retain_graph`` keeps the vjp closures alive for a
    second call; with False we drop tape links on the heads' upstream graph
    lazily (closures die with the arrays).
    """
    if not isinstance(heads, (list, tuple)):
        heads = [heads]
    if head_grads is None:
        head_grads = [None] * len(heads)
    elif not isinstance(head_grads, (list, tuple)):
        head_grads = [head_grads]

    leaf_grads, _ = _toposort_backward(heads, head_grads)

    for _, (arr, ct) in leaf_grads.items():
        req = getattr(arr, "_ag_grad_req", "null")
        if req == "null":
            continue
        if arr._grad is None:
            # the buffer was released (Parameter._release_grad, the
            # compiled train step): the tape re-creates it
            from .ndarray import ndarray as _nd

            arr._grad = _nd._wrap(ct.astype(arr._data.dtype), arr.ctx)
            continue
        ct = ct.astype(arr._grad._data.dtype) if ct.dtype != arr._grad._data.dtype else ct
        if req == "add":
            arr._grad._set_data(arr._grad._data + ct)
        else:  # write
            arr._grad._set_data(ct)

    if not retain_graph:
        for h in heads:
            h._ag_node = None


def _collect_subgraph(heads, variables=()) -> List[TapeNode]:
    """Tape nodes reachable from heads WITHOUT passing through a
    requested variable, ascending nid (creation order = a valid
    topological order).  Stopping at variables keeps nodes upstream of
    the differentiation cut out of the replay — they are constants there,
    and may legitimately be un-replayable (custom Function nodes)."""
    var_ids = {id(v) for v in variables}
    seen: Dict[int, TapeNode] = {}
    stack = [h._ag_node for h in heads
             if id(h) not in var_ids
             and getattr(h, "_ag_node", None) is not None]
    while stack:
        node = stack.pop()
        if node.nid in seen:
            continue
        seen[node.nid] = node
        for arr in node.inputs:
            if id(arr) in var_ids:
                continue            # the variable is a replay input — cut
            sub = getattr(arr, "_ag_node", None)
            if sub is not None and sub.nid not in seen:
                stack.append(sub)
    return [seen[k] for k in sorted(seen)]


def _build_pure(heads, variables):
    """Reconstruct the heads' computation as a PURE function of the
    variables' raw arrays by replaying recorded node fns in creation
    order.  Everything not in ``variables`` enters as a constant — the
    value captured when the op was RECORDED (node.input_vals), so later
    mutation of those arrays cannot skew the replay.  This is what makes
    ``create_graph=True`` possible on an eager tape: the replayed
    function can be re-differentiated by jax to any order.
    """
    nodes = _collect_subgraph(heads, variables)
    for n in nodes:
        if n.fn is None:
            raise NotImplementedError(
                f"create_graph through node '{n.name}' (a custom "
                "autograd.Function) is not supported: its forward is not "
                "recorded as a pure function")
    var_ids = {id(v): i for i, v in enumerate(variables)}
    replayed = {n.nid for n in nodes}

    def value_of(arr, env, var_vals, recorded=None):
        if id(arr) in var_ids:
            return var_vals[var_ids[id(arr)]]
        node = getattr(arr, "_ag_node", None)
        if node is not None and node.nid in replayed:
            return env[(node.nid, arr._ag_out_index)]
        return recorded if recorded is not None else arr._data

    def pure(*var_vals):
        env = {}
        for n in nodes:
            vals = n.input_vals or [None] * len(n.inputs)
            ins = [value_of(a, env, var_vals, recorded=vals[j])
                   for j, a in enumerate(n.inputs)]
            out = n.fn(*ins)
            outs = list(out) if isinstance(out, (tuple, list)) else [out]
            for i, o in enumerate(outs):
                env[(n.nid, i)] = o
        return tuple(value_of(h, env, var_vals) for h in heads)

    return pure


def grad(heads, variables, head_grads=None, retain_graph=None, create_graph=False,
         train_mode=True):
    """Functional-style gradient (reference ``python/mxnet/autograd.py:272``).

    Returns gradients of heads w.r.t. ``variables`` without touching ``.grad``
    buffers.  ``create_graph=True`` replays the recorded subgraph as a pure
    function and dispatches its gradient through the recording machinery, so
    the returned grads are themselves tape-connected (differentiable to any
    order — each grad node carries its own pure fn for further replay).
    """
    if create_graph:
        import jax as _jax
        import jax.numpy as jnp

        from .ndarray import ndarray as _nd

        heads_l = heads if isinstance(heads, (list, tuple)) else [heads]
        single = not isinstance(variables, (list, tuple))
        vars_l = [variables] if single else list(variables)
        hg_l = (head_grads if isinstance(head_grads, (list, tuple))
                else [head_grads] * len(heads_l))
        pure = _build_pure(heads_l, vars_l)
        cts = tuple(
            jnp.ones(h.shape, h._data.dtype) if g is None
            else (g._data if hasattr(g, "_data") else jnp.asarray(g))
            for h, g in zip(heads_l, hg_l))

        def g_fn(*var_vals):
            _, vjp = _jax.vjp(pure, *var_vals)
            return vjp(cts)

        var_arrays = [v._data for v in vars_l]
        record = is_recording()
        if record:
            raw_out, vjp2 = _jax.vjp(g_fn, *var_arrays)
        else:
            raw_out = g_fn(*var_arrays)
        outs = [_nd._wrap(o, v._ctx) for o, v in zip(raw_out, vars_l)]
        if record:
            def vjp2_shim(cts, _v=vjp2):
                # g_fn returns a tuple even for one variable; the tape
                # passes a bare cotangent when num_outputs == 1
                if not isinstance(cts, tuple):
                    cts = (cts,)
                return _v(cts)

            node = TapeNode(
                vjp2_shim, list(vars_l), len(outs),
                [tuple(o.shape) for o in raw_out],
                [o.dtype for o in raw_out], name="autograd_grad", fn=g_fn,
                input_vals=list(var_arrays))
            for i, o in enumerate(outs):
                o._ag_node = node
                o._ag_out_index = i
        return outs[0] if single else outs

    if not isinstance(heads, (list, tuple)):
        heads = [heads]
    single = not isinstance(variables, (list, tuple))
    if single:
        variables = [variables]
    if head_grads is None:
        head_grads = [None] * len(heads)
    elif not isinstance(head_grads, (list, tuple)):
        head_grads = [head_grads]

    # temporarily mark node-less variables so leaf accumulation catches them;
    # intermediates (op outputs) are captured via their tape node instead
    from .ndarray import ndarray as _nd

    tmp_marked = []
    for v in variables:
        if getattr(v, "_ag_node", None) is None and \
                getattr(v, "_ag_grad_req", "null") == "null":
            v._ag_grad_req = "write"
            tmp_marked.append(v)

    leaf_grads, var_cts = _toposort_backward(heads, head_grads, variables)

    out = []
    for v in variables:
        if id(v) in var_cts:
            out.append(_nd._wrap(var_cts[id(v)], v.ctx))
            continue
        entry = leaf_grads.get(id(v))
        if entry is None:
            import jax.numpy as jnp

            out.append(_nd._wrap(jnp.zeros(v.shape, v._data.dtype), v.ctx))
        else:
            out.append(_nd._wrap(entry[1], v.ctx))
    for v in tmp_marked:
        v._ag_grad_req = "null"
    if retain_graph is False:
        for h in heads:
            h._ag_node = None
    return out[0] if single else out


def get_symbol(x):
    """Return the traced graph of ``x`` as a Symbol (reference
    ``MXAutogradGetSymbol``).  Requires the computation to have run inside a
    ``mx._deferred_compute.deferred_compute()`` scope."""
    from . import _deferred_compute as dc

    return dc.get_symbol(x)


class Function:
    """User-defined differentiable function (reference
    ``python/mxnet/autograd.py:369-519``).

    Subclass and implement ``forward(self, *inputs)`` and
    ``backward(self, *output_grads)`` operating on NDArrays.  Inside
    ``forward`` recording is paused; the custom ``backward`` is spliced into
    the tape as a single node.
    """

    def __init__(self):
        self._saved = None

    def save_for_backward(self, *args):
        self._saved = args

    @property
    def saved_tensors(self):
        return self._saved

    def forward(self, *inputs):
        raise NotImplementedError

    def backward(self, *output_grads):
        raise NotImplementedError

    def __call__(self, *inputs):
        from .ndarray import ndarray as _nd

        with pause():
            outputs = self.forward(*inputs)
        single_out = not isinstance(outputs, (list, tuple))
        outs = [outputs] if single_out else list(outputs)

        if is_recording() and any(_nd._on_tape(i) for i in inputs):
            fn = self

            def vjp_fn(out_cts):
                # the tape hands a BARE cotangent whenever num_outputs
                # == 1 — including a forward that returned a 1-element
                # tuple (single_out False), so branch on the ct itself
                cts = out_cts if isinstance(out_cts, tuple) \
                    else (out_cts,)
                with pause():
                    in_grads = fn.backward(*[_nd._wrap(c, inputs[0].ctx) for c in cts])
                if not isinstance(in_grads, (list, tuple)):
                    in_grads = [in_grads]
                return tuple(g._data if g is not None else None for g in in_grads)

            node = TapeNode(
                vjp_fn,
                list(inputs),
                len(outs),
                [o.shape for o in outs],
                [o._data.dtype for o in outs],
                name=type(self).__name__,
            )
            for i, o in enumerate(outs):
                o._ag_node = node
                o._ag_out_index = i
        return outputs
