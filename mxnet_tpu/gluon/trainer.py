"""Gluon Trainer (reference ``python/mxnet/gluon/trainer.py``, 541 LoC).

Applies an Optimizer to a set of Parameters, synchronizing gradients through
a KVStore.  Call stack mirrors the reference (SURVEY.md §3.3):
``step() → _allreduce_grads() → _update()``.  On TPU the per-key reduce is a
fused XLA computation; with ``kvstore='tpu'`` the compiled step
(:meth:`Trainer.compile_step`) traces under a data-parallel SPMD mesh
(``parallel.spmd``, knob ``MXNET_SPMD_MESH``) — batch sharded over
``'dp'``, params/optimizer state replicated — so the gradient reduce is an
ICI-native all-reduce the XLA partitioner schedules INSIDE the one donated
program (docs/PERF.md "Pod-scale SPMD train step").  Existing user code is
unchanged: the kvstore string is the whole opt-in.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Union

from .. import faults as _faults
from .. import kvstore as kvs
from .. import optimizer as opt
from ..ndarray import NDArray
from .parameter import Parameter

__all__ = ["Trainer"]


class Trainer:
    def __init__(self, params, optimizer, optimizer_params=None,
                 kvstore="device", compression_params=None,
                 update_on_kvstore=None):
        self._param_dict = {}
        if isinstance(params, (dict,)):
            for key in sorted(list(params.keys())):
                self._param_dict[key] = params[key]
            params = [params[k] for k in sorted(params.keys())]
        elif not isinstance(params, (list, tuple)):
            raise ValueError(
                "First argument must be a list or dict of Parameters, "
                f"got {type(params)}."
            )
        self._params: List[Parameter] = []
        self._param2idx = {}
        for i, param in enumerate(params):
            if not isinstance(param, Parameter):
                raise ValueError(
                    "First argument must be a list or dict of Parameters, "
                    f"got list of {type(param)}."
                )
            if param.grad_req != "null":
                # reference semantics: _trainer is a weakref-like pointer —
                # a NEW trainer takes the parameter over (the old one,
                # usually discarded, goes stale); only SPARSE parameters
                # reject multiple live trainers, and this backend is
                # dense-on-device by design (gluon/parameter.py).
                self._param2idx[id(param)] = i
                self._params.append(param)
                param._trainer = self
        self._compression_params = compression_params
        self._contexts = self._check_contexts()
        optimizer_params = optimizer_params or {}
        self._init_optimizer(optimizer, optimizer_params)
        self._scale = self._optimizer.rescale_grad
        self._kvstore_params = {
            "kvstore": kvstore,
            "update_on_kvstore": update_on_kvstore,
        }
        self._kv_initialized = False
        self._kvstore = None
        self._update_on_kvstore = None
        self._params_to_init = []
        self._reset_kvstore()

    # -- setup -----------------------------------------------------------
    def _check_contexts(self):
        contexts = None
        for param in self._params:
            ctx = param.list_ctx() if param._data or param._deferred_init else None
            if ctx is None:
                continue
            assert contexts is None or contexts == ctx, (
                f"All Parameters must be initialized on the same set of "
                f"contexts, but Parameter {param.name} is initialized on "
                f"{ctx} while previous Parameters are initialized on "
                f"{contexts}."
            )
            contexts = ctx
        return contexts or []

    def _init_optimizer(self, optimizer, optimizer_params):
        param_dict = {i: param for i, param in enumerate(self._params)}
        if isinstance(optimizer, opt.Optimizer):
            assert not optimizer_params, (
                "optimizer_params must be None if optimizer is an Optimizer "
                "instance"
            )
            self._optimizer = optimizer
            self._optimizer.param_dict = param_dict
        else:
            self._optimizer = opt.create(optimizer, param_dict=param_dict,
                                         **optimizer_params)
        self._updaters = [opt.get_updater(self._optimizer)
                          for _ in self._contexts] or [
            opt.get_updater(self._optimizer)
        ]

    def _reset_kvstore(self):
        if self._kvstore and "dist" in self._kvstore.type:
            raise RuntimeError(
                "Cannot reset distributed KVStore."
            )
        self._kv_initialized = False
        self._kvstore = None
        self._update_on_kvstore = None
        self._params_to_init = [param for param in self._params]

    def _init_kvstore(self):
        config = self._kvstore_params
        kvstore = config["kvstore"]
        update_on_kvstore = config["update_on_kvstore"]
        if kvstore is None:
            self._kvstore = None
            self._update_on_kvstore = False
        else:
            kv = kvs.create(kvstore) if isinstance(kvstore, str) else kvstore
            self._kvstore = kv
            if update_on_kvstore is None:
                # server-side update only for dist stores with optimizer
                # capability (reference trainer.py:188-275 decision table)
                update_on_kvstore = ("dist" in kv.type) and kv.is_capable(
                    kvs.KVStoreBase.OPTIMIZER)
            if update_on_kvstore and not kv.is_capable(
                    kvs.KVStoreBase.OPTIMIZER):
                raise ValueError(
                    f"kvstore '{kv.type}' does not support optimizer updates"
                )
            self._update_on_kvstore = update_on_kvstore
            if update_on_kvstore:
                self._kvstore.set_optimizer(self._optimizer)
        self._kv_initialized = True

    def _init_params(self):
        assert self._kv_initialized
        params_to_init = []
        for param in self._params_to_init:
            if param._deferred_init:
                params_to_init.append(param)
            elif self._kvstore is not None:
                idx = self._param2idx[id(param)]
                value = param.data(param.list_ctx()[0])
                if hasattr(self._kvstore, "init"):
                    # the built-in store's copy is the SERVER-side weight.
                    # With local updates nothing reads it (pushpull puts
                    # the reduced gradient in its place), so it would be
                    # a second model in device memory
                    if self._update_on_kvstore or \
                            not isinstance(self._kvstore, kvs.KVStore):
                        self._kvstore.init(idx, value)
                else:
                    # hvd-style adapters have no server-side store: param
                    # init is a rank-0 broadcast into every replica
                    # (reference trainer.py horovod branch)
                    self._kvstore.broadcast(idx, value, param.list_data())
        self._params_to_init = params_to_init

    # -- properties ------------------------------------------------------
    @property
    def learning_rate(self):
        return self._optimizer.learning_rate

    @property
    def optimizer(self):
        return self._optimizer

    def set_learning_rate(self, lr):
        self._optimizer.set_learning_rate(lr)

    def compile_step(self, net, loss_fn, bucket=False, accum_steps=1):
        """Compile forward + backward + gradient reduce + fused optimizer
        update (+ AMP gate) into ONE donated XLA program — the CachedOp
        analog for training (``cached_step.TrainStep``).  ``loss_fn(net,
        *args)`` returns the loss; the returned step object is called as
        ``step(*args, batch_size=...)`` and replaces the record/backward/
        step() triple.  With ``kvstore='tpu'`` the step traces under the
        data-parallel SPMD mesh (``MXNET_SPMD_MESH``): batch sharded
        over ``'dp'``, params replicated, the all-reduce ICI-native
        inside the program — stage inputs with ``step.batch_sharding``
        (``engine.prefetch(sharding=)`` / ``DataLoader(sharding=)``) to
        skip re-placement.  Ineligible setups (non-stageable forwards,
        grad_req='add', host-driven dist stores, server-side updates,
        optimizers without a fused_update rule, or
        ``MXNET_COMPILED_STEP=0``) fall back to the eager tape
        transparently.

        ``bucket=True`` pads variable-length batches up to the
        ``MXNET_SHAPE_BUCKETS`` grid (``serving.BucketPolicy``) so they
        stop blowing the shape-keyed program cache; requires a PAD-SAFE
        (masked) loss — verified once per bucket, refused sticky
        otherwise (``step.bucket_refused``).

        ``accum_steps=N`` turns every N calls into ONE gradient-
        accumulation window: N microbatch grad dispatches into donated
        accumulator buffers, then one fused update — exactly N+1
        dispatches, one optimizer update-count bump, and one AMP gate
        decision per window, numerically the mean over the combined
        N×batch_size batch.  Accumulation requires the compiled path
        (the eager tape refuses it loudly rather than applying N
        updates)."""
        from ..cached_step import TrainStep

        return TrainStep(net, loss_fn, self, bucket=bucket,
                         accum_steps=accum_steps)

    def precompile(self, net, loss_fn, specs, bucket=False,
                   accum_steps=1):
        """Ahead-of-time warm-up: compile the whole train step for the
        given input signature BEFORE the first batch arrives (the
        deploy-time / elastic-restore counterpart of ``compile_step``;
        ROADMAP item 4 — on chip a train-step program costs 26–98 s of
        XLA compile, and this moves that off the first-batch path).

        ``specs`` is a sequence of the step's positional inputs, each a
        ``(shape, dtype)`` pair or a real example NDArray.  The program
        is traced and XLA-compiled through the ProgramStore exactly as
        the first dispatch would be; with ``MXNET_PROGRAM_CACHE_DIR``
        set the executable also persists, so a later process (an
        elastic restart, a second serving replica) re-tracing the same
        signature gets a disk hit instead of a fresh compile.  No step
        runs and no parameter/optimizer value changes.  Returns the
        ready :class:`~mxnet_tpu.cached_step.TrainStep` — use THAT
        object for training (each TrainStep owns its program keyspace).
        Raises when the step would fall back to the eager tape."""
        return self.compile_step(
            net, loss_fn, bucket=bucket,
            accum_steps=accum_steps).precompile(*specs)

    def step_spans(self, limit=None):
        """Per-step span records of the compiled train step (cat
        ``train_step``) from the unified telemetry span buffer: one
        record per ``TrainStep.__call__`` with wall duration, the step
        index, and whether the step ran compiled or fell back eager."""
        from .. import telemetry as _telemetry

        return _telemetry.spans(cat="train_step", limit=limit,
                                name="train_step.step")

    # -- the step --------------------------------------------------------
    def step(self, batch_size, ignore_stale_grad=False):
        """Normalize by batch_size, all-reduce grads, apply updates
        (reference trainer.py:334)."""
        # train-step injection site (fail-fast: a step is not idempotent;
        # recovery is run_elastic's restore-and-replay, not a retry here).
        # Zero overhead when no FaultPlan is installed.
        _faults.inject("trainer.step")
        if not self._kv_initialized:
            self._init_kvstore()
        if self._params_to_init:
            self._init_params()
        self._optimizer.rescale_grad = self._scale / batch_size
        scaler = getattr(self, "_amp_loss_scaler", None)
        if scaler is not None and self._update_on_kvstore:
            # must refuse BEFORE allreduce: with update_on_kvstore the
            # reduce applies the (possibly overflowed) update server-side
            from ..base import MXNetError

            raise MXNetError(
                "AMP loss scaling cannot skip server-side kvstore updates; "
                "recreate the Trainer with update_on_kvstore=False")
        self._allreduce_grads()
        if scaler is not None:
            from ..optimizer import fused as _fused

            if _fused.enabled(self._optimizer):
                # fold the overflow check into the fused step: ONE compiled
                # all-finite program whose device flag gates each group
                # program (the update is skipped on-device via where(ok)),
                # then a single host read for the scale policy — instead of
                # a host sync standing between the check and the update
                grads = [g._data for p in self._params
                         if p.grad_req != "null"
                         for g in p.list_grad() if g is not None]
                ok = _fused.all_finite(grads)
                self._optimizer._fused_skip_ok = ok
                try:
                    self._update(ignore_stale_grad)
                finally:
                    self._optimizer._fused_skip_ok = None
                scaler.update_scale(not bool(ok))
                return
            # fp16 AMP scalar path: skip the update and shrink the scale on
            # overflow (reference amp trainer patching + LossScaler policy);
            # amp.init_trainer rejects update_on_kvstore trainers, so the
            # weights are untouched at this point
            overflow = scaler.has_overflow(
                [p for p in self._params if p.grad_req != "null"])
            scaler.update_scale(overflow)
            if overflow:
                return
        self._update(ignore_stale_grad)

    def allreduce_grads(self):
        """Reduce gradients over devices without updating (for gradient
        accumulation / manual update flows, reference trainer.py:417)."""
        if not self._kv_initialized:
            self._init_kvstore()
        if self._params_to_init:
            self._init_params()
        assert not (self._kvstore and self._update_on_kvstore), (
            "allreduce_grads() when parameters are updated on kvstore is not "
            "supported. Try setting `update_on_kvstore` to False when "
            "creating trainer."
        )
        self._allreduce_grads()

    def _allreduce_grads(self):
        if self._kvstore is None:
            return
        if self._update_on_kvstore:
            from ..optimizer import fused as _fused

            if _fused.enabled(self._optimizer):
                # ONE batched pushpull over every key: the store reduces
                # each key, then applies the optimizer over the whole key
                # set as grouped compiled programs (server-side fused
                # update, kvstore.py), then pulls the new weights back
                idxs, grads, outs = [], [], []
                for param in self._params:
                    if param.grad_req == "null":
                        continue
                    idxs.append(self._param2idx[id(param)])
                    grads.append(param.list_grad())
                    outs.append(param.list_data())
                if idxs:
                    self._kvstore.pushpull(idxs, grads, out=outs)
                return
        for param in self._params:
            if param.grad_req == "null":
                continue
            idx = self._param2idx[id(param)]
            grads = param.list_grad()
            if self._update_on_kvstore:
                # push grads; server updates weight; pull new weight back
                self._kvstore.pushpull(idx, grads, out=param.list_data())
            elif len(grads) > 1 or self._kvstore.num_workers > 1:
                self._kvstore.pushpull(idx, grads, out=grads)

    def _update(self, ignore_stale_grad=False):
        if self._update_on_kvstore and self._kvstore is not None:
            return  # weights already updated server-side in _allreduce_grads
        from ..optimizer import fused as _fused

        if _fused.enabled(self._optimizer):
            # fused multi-tensor path: ONE updater call per device slot
            # carrying every trainable parameter; the optimizer groups
            # them by (dtype, hyper-param signature, multi-precision) and
            # applies each group as one donated compiled program
            batches = [[] for _ in self._updaters]
            for param in self._params:
                if param.grad_req == "null":
                    continue
                idx = self._param2idx[id(param)]
                for i, (weight, grad) in enumerate(
                        zip(param.list_data(), param.list_grad())):
                    if i >= len(batches):
                        break
                    batches[i].append((idx, grad, weight))
            for updater, batch in zip(self._updaters, batches):
                if batch:
                    idxs, grads, weights = (list(t) for t in zip(*batch))
                    updater(idxs, grads, weights)
            return
        for param in self._params:
            if param.grad_req == "null":
                continue
            idx = self._param2idx[id(param)]
            for updater, weight, grad in zip(
                    self._updaters, param.list_data(), param.list_grad()):
                updater(idx, grad, weight)

    def update(self, batch_size, ignore_stale_grad=False):
        """Apply updates assuming grads were already reduced (reference
        trainer.py:444)."""
        if not self._kv_initialized:
            self._init_kvstore()
        if self._params_to_init:
            self._init_params()
        assert not (self._kvstore and self._update_on_kvstore), (
            "update() when parameters are updated on kvstore is not "
            "supported."
        )
        self._optimizer.rescale_grad = self._scale / batch_size
        self._update(ignore_stale_grad)

    # -- states ----------------------------------------------------------
    def save_states(self, fname):
        """Save optimizer/updater states (reference trainer.py:482)."""
        assert self._optimizer is not None
        if not self._kv_initialized:
            self._init_kvstore()
        if self._params_to_init:
            self._init_params()
        if self._update_on_kvstore:
            self._kvstore.save_optimizer_states(fname, dump_optimizer=True)
        else:
            with open(fname, "wb") as fout:
                fout.write(self._updaters[0].get_states(dump_optimizer=False))

    def load_states(self, fname):
        """Load optimizer/updater states (reference trainer.py:501)."""
        if not self._kv_initialized:
            self._init_kvstore()
        if self._params_to_init:
            self._init_params()
        if self._update_on_kvstore:
            self._kvstore.load_optimizer_states(fname)
            self._optimizer = self._kvstore._optimizer
        else:
            with open(fname, "rb") as f:
                states = f.read()
            for updater in self._updaters:
                updater.set_states(states)
                updater.optimizer = self._optimizer
