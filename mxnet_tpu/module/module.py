"""Module — symbolic training on a bound executor.

Parity: reference ``python/mxnet/module/module.py``. TPU-native design:
where the reference builds a DataParallelExecutorGroup with one executor
per GPU and reduces through KVStore (executor_group.py:128,
model.py:106-138), this Module binds ONE executor whose compiled program
covers the whole (possibly mesh-sharded) computation — multi-chip data
parallelism is expressed as sharding on the same program
(mxnet_tpu.parallel), not as replicated executors, because XLA then
schedules the ICI all-reduce inside the step. The KVStore push/pull
protocol is still honoured when a kvstore is provided
(update_on_kvstore ≙ reference semantics).
"""
from __future__ import annotations

import logging

import numpy as np

from ..base import MXNetError
from ..config import fused_fit
from ..context import Context, cpu, current_context
from ..executor import record_dispatch
from .. import telemetry
from ..initializer import Uniform, InitDesc
from ..model import _create_kvstore, save_checkpoint, load_checkpoint
from .. import optimizer as opt
from ..ndarray.ndarray import NDArray, zeros, _wrap
from .base_module import BaseModule, FusedFallback, _as_list


class Module(BaseModule):
    """(parity: module.Module)"""

    def __init__(self, symbol, data_names=("data",),
                 label_names=("softmax_label",), logger=logging, context=None,
                 work_load_list=None, fixed_param_names=None,
                 state_names=None, group2ctxs=None,
                 compression_params=None, partition_rules=None,
                 mesh_axes=None):
        """``partition_rules`` (a ``parallel.partition.PartitionRules``
        tree) + ``mesh_axes`` (ordered ``{axis: size}``, e.g.
        ``{"dp": 2, "mp": 4}``; one size may be -1) lay a multi-device
        context list out as a rule-sharded dp x mp mesh: the batch
        shards over ``dp``, each parameter takes its first-matching
        rule's PartitionSpec (UNMATCHED policy: replicate or error),
        and the fused train step runs ONE donated SPMD program with
        gradients reduced over ``dp`` only and mp-sharded parameters
        never gathered. Ignored on a single-context bind."""
        super().__init__(logger=logger)
        if context is None:
            context = current_context()
        if isinstance(context, Context):
            context = [context]
        self._context = context
        self._symbol = symbol
        self._data_names = list(data_names or [])
        self._label_names = list(label_names or [])
        self._state_names = list(state_names or [])
        self._fixed_param_names = list(fixed_param_names or [])
        arg_names = symbol.list_arguments()
        input_names = self._data_names + self._label_names + self._state_names
        self._param_names = [n for n in arg_names if n not in input_names]
        self._group2ctxs = group2ctxs
        self._aux_names = symbol.list_auxiliary_states()
        self._output_names = symbol.list_outputs()
        self._arg_params = None
        self._aux_params = None
        self._params_dirty = False
        self._optimizer = None
        self._kvstore = None
        self._update_on_kvstore = None
        self._updater = None
        self._exec = None
        self._data_shapes = None
        self._label_shapes = None
        self._grad_req = None
        self._mesh = None
        self._dp_spec = None
        self._data_sharding = None
        self._repl_sharding = None
        self._partition_rules = partition_rules
        self._mesh_axes = dict(mesh_axes) if mesh_axes else None
        self._fused_fallback_reason = None
        self._fused_plan = None
        # fused steps run since the ops' counters were last published
        self._steps_unpublished = 0
        # the dist tier (multi-process dist_* kvstore): a PROCESS-
        # SPANNING dp mesh the fused step jits over, committed lazily
        # and dropped whenever a step must phase-split (the explicit
        # kvstore wire needs LOCAL gradients, not psummed ones)
        self._dist_spec = None
        self._dist_committed = False
        self._dist_synced = False
        self._step_gate = None
        self._dist_sync_handle = None

    # -- introspection -----------------------------------------------------
    @property
    def data_names(self):
        return self._data_names

    @property
    def label_names(self):
        return self._label_names

    @property
    def output_names(self):
        return self._output_names

    @property
    def data_shapes(self):
        assert self.binded
        return self._data_shapes

    @property
    def label_shapes(self):
        assert self.binded
        return self._label_shapes

    @property
    def output_shapes(self):
        assert self.binded
        if self._exec.outputs:
            return [(n, o.shape) for n, o in
                    zip(self._output_names, self._exec.outputs)]
        # before the first forward, infer from the bound input shapes —
        # the reference has these available right after bind (executor
        # group infers at bind time), and SequentialModule.bind chains
        # stages through this property
        shape_kwargs = {d.name: d.shape for d in self._data_shapes}
        shape_kwargs.update({l.name: l.shape for l in self._label_shapes})
        _, out_shapes, _ = self._symbol.infer_shape(**shape_kwargs)
        return list(zip(self._output_names, out_shapes))

    # -- bind --------------------------------------------------------------
    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req="write"):
        """(parity: module.py bind:363)"""
        if force_rebind:
            self._exec = None
            self.binded = False
        if self.binded:
            self.logger.warning("Already bound, ignoring bind()")
            return
        with telemetry.span("bind"):
            self.for_training = for_training
            self.inputs_need_grad = inputs_need_grad

            self._data_shapes = [_as_desc(d) for d in data_shapes]
            self._label_shapes = [_as_desc(l) for l in label_shapes] \
                if label_shapes else []

            shape_kwargs = {d.name: d.shape for d in self._data_shapes}
            shape_kwargs.update({l.name: l.shape for l in self._label_shapes})
            # input dtypes flow from DataDesc into the joint InferShape/Type
            # pass, so a bf16 data desc binds a bf16 executor end to end.
            # Labels included: without an explicit entry the inference pass
            # would anchor the label var to the data dtype (bf16 truncates
            # class indices > 256).
            type_dict = {d.name: d.dtype
                         for d in self._data_shapes + self._label_shapes
                         if getattr(d, "dtype", None) is not None}

            reqs = {}
            for name in self._symbol.list_arguments():
                if name in self._data_names:
                    reqs[name] = "write" if inputs_need_grad else "null"
                elif name in self._label_names or name in self._state_names:
                    reqs[name] = "null"
                elif name in self._fixed_param_names:
                    reqs[name] = "null"
                else:
                    reqs[name] = grad_req if for_training else "null"
            self._grad_req = reqs
            ctx = self._context[0]
            g2c = self._group2ctxs
            if isinstance(g2c, (list, tuple)):
                # reference group2ctxs is a per-context list; the single-exec
                # module uses the first entry
                g2c = g2c[0] if g2c else None
            if g2c and len(self._context) > 1:
                # grouped programs pin ops to concrete devices (eager
                # per-segment execution); the dp mesh shards ONE jitted
                # program — the two placements are mutually exclusive
                raise MXNetError(
                    "group2ctxs cannot be combined with a multi-device "
                    "context list; use a single context for model "
                    "parallelism or drop group2ctxs for data parallelism")
            self._exec = self._symbol.simple_bind(ctx=ctx, grad_req=reqs,
                                                  type_dict=type_dict,
                                                  group2ctx=g2c,
                                                  **shape_kwargs)
            if len(self._context) > 1:
                self._init_mesh()
            self.binded = True
            if shared_module is not None and shared_module.params_initialized:
                arg_p, aux_p = shared_module.get_params()
                self.set_params(arg_p, aux_p)
            elif self.params_initialized and self._arg_params is not None:
                # Module.load path: checkpointed params install at bind time
                self.set_params(self._arg_params, self._aux_params or {})

    # -- multi-device mesh (TPU-native DataParallelExecutorGroup) ----------
    def _init_mesh(self):
        """N contexts = a mesh over N chips: the reference builds one
        executor per device and reduces grads through KVStore
        (executor_group.py:128, comm.h:102-720); here the SAME single
        program is GSPMD-sharded — batch over the ``dp`` axis, params
        replicated (or rule-sharded over ``mp`` when a
        ``PartitionRules`` tree is bound) — so XLA inserts the gradient
        all-reduce over ICI inside the fused fwd+bwd step. The batch
        divisibility check is against the DP AXIS size, not the device
        count: on a 2x4 dp x mp mesh a batch of 6 divides fine."""
        from ..parallel import mesh as _pmesh, spmd as _spmd
        if self._partition_rules is not None or self._mesh_axes:
            mesh = _pmesh.mesh_from_contexts(
                self._context, axes=self._mesh_axes or {_spmd.DP_AXIS: -1})
            spec = _spmd.rule_spec(mesh, self._partition_rules)
        else:
            spec = _spmd.dp_spec(_pmesh.mesh_from_contexts(self._context))
        for d in self._data_shapes + self._label_shapes:
            if d.shape:
                _spmd.check_batch_divisible(d.shape[0], spec.dp_size,
                                            "batch size",
                                            axis=spec.data_axis)
        self._dp_spec = spec
        self._mesh = spec.mesh
        self._data_sharding = spec.data_sharding
        self._repl_sharding = spec.repl_sharding
        self._shard_exec_arrays()

    def _shard_exec_arrays(self):
        """Commit shardings: data/label batch-sharded over ``dp``;
        params/grads/aux on their rule-resolved placement (replicated
        without a rule tree). GSPMD propagates from these committed
        placements."""
        from ..parallel import spmd as _spmd
        input_names = set(self._data_names) | set(self._label_names) \
            | set(self._state_names)
        _spmd.commit_dp_placements(self._exec, input_names, self._dp_spec)

    def partition_summary(self):
        """JSON-safe layout description of this module's mesh spec (or
        None on a single-device bind): mesh axes, data axis, the rule
        tree and the resolved sharded-parameter specs — recorded into
        checkpoint meta, fused plans and program cards."""
        if self._dp_spec is None:
            return None
        from ..parallel.partition import partition_summary as _summary
        shapes = None
        if self.binded and self._exec is not None:
            arg_dict = self._exec.arg_dict
            shapes = {n: arg_dict[n].shape for n in self._param_names
                      if n in arg_dict}
        return _summary(self._dp_spec, shapes)

    # -- multi-process dist mesh (the elastic dist_* tier) -----------------
    def _input_name_set(self):
        return set(self._data_names) | set(self._label_names) \
            | set(self._state_names)

    def _init_dist_spec(self):
        """Build the PROCESS-SPANNING dp mesh for a multi-process
        ``dist_*`` sync store: every live worker's context devices
        become a slab of one global ``dp`` axis, so the SAME fused
        donated-buffer train step jits across processes and XLA
        compiles the cross-host gradient psum INTO the step (the
        kvstore wire path becomes the recovery/compression fallback,
        not the steady state). A single-process job (or the last
        survivor after re-meshes) keeps ``_dist_spec=None`` and runs
        the plain local program."""
        from .. import dist as _dist
        from ..parallel import spmd as _spmd
        kv = self._kvstore
        live = kv.live_ranks if kv is not None else (0,)
        if len(live) <= 1 or _dist.process_count() <= 1:
            self._dist_spec = None
            return
        if self._partition_rules is not None:
            # re-sharding a rule tree across worker processes is not
            # wired yet (ROADMAP: multi-host mp); the dist tier keeps
            # the replicated dp layout
            raise MXNetError(
                "partition_rules cannot be combined with a "
                "multi-process dist_* kvstore yet; drop the rules or "
                "run single-process")
        for d in self._data_shapes + self._label_shapes:
            if d.shape:
                _spmd.check_batch_divisible(
                    d.shape[0], max(1, len(self._context)),
                    "local batch size")
        self._dist_spec = _spmd.dist_dp_spec(self._context,
                                             live_ranks=live)
        self._step_gate = None

    def _dist_gate(self):
        """Per-module pre-collective liveness gate for the fused dist
        step (channel ``step``; the kvstore wire path gates on its own
        ``kv`` channel). Lazy; rebuilt after a re-mesh."""
        if self._step_gate is None:
            from .. import heartbeat
            kv = self._kvstore
            self._step_gate = heartbeat.CollectiveGate(
                kv.rank, kv.live_ranks, channel="step")
        return self._step_gate

    def _await_dist_step(self, handle):
        """Liveness-aware completion wait for the previous spanning
        step: poll readiness alongside peer heartbeats, so a member
        that dies INSIDE an in-flight exchange (SIGKILL between its
        gate crossing and its part of the collective) surfaces as
        ``DeadWorkerError`` instead of an unbounded silent block.
        Best-effort beyond that point: the wedged execution cannot be
        aborted runtime-side, so recovery may still require the
        launcher-level restart — but the death is named, postmortem'd
        and bounded.

        The time spent here is WAIT, not work: it is reported to the
        step gate (``note_wait``) so the self-time this rank publishes
        at its next crossing excludes it — otherwise a fast rank
        blocked on a slow peer's half of the collective would itself
        read as a straggler in the fleet-wide skew comparison."""
        import time as _time
        t0 = _time.monotonic()
        try:
            if not hasattr(handle, "is_ready"):
                import jax
                jax.block_until_ready(handle)
                return
            from .. import heartbeat
            kv = self._kvstore
            peers = [r for r in kv.live_ranks if r != kv.rank]
            next_liveness = _time.monotonic() + 0.25
            while not handle.is_ready():
                if _time.monotonic() >= next_liveness:
                    next_liveness = _time.monotonic() + 0.25
                    dead = heartbeat.stale_ranks(peers)
                    if dead:
                        raise heartbeat.DeadWorkerError(
                            dead, channel="step-execution",
                            generation=self._dist_gate().generation,
                            evidence={r: "died with the collective "
                                         "in flight" for r in dead})
                _time.sleep(0.002)
        finally:
            try:
                self._dist_gate().note_wait(
                    (_time.monotonic() - t0) * 1e3)
            except Exception:
                pass

    def _ensure_dist_placement(self):
        """Commit the executor's storage onto the process-spanning mesh
        (idempotent). The FIRST commit broadcasts rank 0's replicated
        state to every worker (parity: kv.init server seeding) — after
        that the SPMD discipline keeps replicas identical and
        re-commits (post-fallback, post-re-mesh) are local-only."""
        if self._dist_spec is None or self._dist_committed:
            return
        from .. import dist as _dist
        from ..parallel import spmd as _spmd
        # the broadcast spans every LAUNCHED process — after a member
        # loss it would hang on the dead ones, and the survivors'
        # values are already consistent (same checkpoint restore)
        sync = not self._dist_synced and not _dist.dead_ranks()
        # the first-commit broadcast is a cross-process collective:
        # cross the step gate before it so a peer that died during
        # startup raises DeadWorkerError instead of hanging the sync
        _spmd.commit_dp_placements(self._exec, self._input_name_set(),
                                   self._dist_spec, sync=sync,
                                   gate=self._dist_gate() if sync
                                   else None)
        self._dist_synced = True
        self._dist_committed = True

    def _drop_dist_placement(self):
        """Detach every bound array from the process-spanning mesh back
        to this worker's LOCAL placement (replicated values read
        locally, batch-sharded values keep their local rows). Runs
        before any phase-split step — the explicit kvstore wire needs
        LOCAL gradients, a globally-committed executor would psum them
        inside forward_backward and the push would double-reduce — and
        during elastic recovery, where arrays still committed to a mesh
        containing dead devices would hang any eager op."""
        if not self._dist_committed:
            return
        import jax
        from ..parallel import spmd as _spmd
        ex = self._exec
        input_names = self._input_name_set()

        def _localize(arr, name=None):
            if arr is None:
                return
            val = _spmd.local_value(arr._data)
            if self._mesh is not None:
                sh = self._data_sharding if name in input_names \
                    else self._repl_sharding
                arr._set_data(jax.device_put(val, sh))
            else:
                arr._set_data(jax.device_put(
                    val, self._context[0].jax_device()))

        for name, arr in ex.arg_dict.items():
            _localize(arr, name)
        for arr in list(ex.grad_arrays) + list(ex.aux_arrays):
            _localize(arr)
        # optimizer state lives with the updater; kvstore weight copies
        # with the store — both were donated into the spanning program
        updater = self._kvstore._updater \
            if (self._kvstore is not None and self._update_on_kvstore) \
            else self._updater
        for st in (getattr(updater, "states", None) or {}).values():
            for leaf in _flatten_state(st):
                _localize(leaf)
        if self._kvstore is not None:
            for arr in self._kvstore._store.values():
                if isinstance(arr, NDArray) \
                        and getattr(arr, "stype", "default") == "default":
                    _localize(arr)
        ex.outputs = [_wrap(jax.device_put(
            _spmd.local_value(o._data), self._context[0].jax_device()),
            o.context) for o in ex.outputs]
        self._dist_committed = False

    def _elastic_remesh(self, dead_ranks):
        """Adopt the surviving membership after a member loss: record
        the dead ranks, detach from the dead mesh, rebuild the dp spec
        over the survivors (or drop to the local program when this
        worker is the last one standing) and invalidate the fused
        plan. The caller (``fit``'s elastic path) then restores the
        last checkpoint and resumes."""
        from .. import dist as _dist
        _dist.mark_member_lost(dead_ranks)
        live = _dist.live_ranks()
        kv = self._kvstore
        if kv is not None:
            kv._remesh(live)
        self._drop_dist_placement()
        self._fused_plan = None
        self._dist_sync_handle = None
        self._step_gate = None
        self._dist_spec = None
        if kv is not None and kv.fused_dist_step:
            self._init_dist_spec()
        telemetry.counter_inc("elastic.remesh")
        telemetry.record_event("elastic.remesh",
                               dead=list(dead_ranks), live=list(live))
        self.logger.warning(
            "elastic re-mesh: worker(s) %s dead, continuing on %s "
            "(%d live)", sorted(dead_ranks), list(live), len(live))

    # -- params ------------------------------------------------------------
    def init_params(self, initializer=Uniform(0.01), arg_params=None,
                    aux_params=None, allow_missing=False, force_init=False,
                    allow_extra=False):
        """(parity: module.py init_params)"""
        if self.params_initialized and not force_init:
            return
        with telemetry.span("init_params"):
            assert self.binded, "call bind before init_params"
            if arg_params is None and self._arg_params is not None:
                arg_params = self._arg_params
            if aux_params is None and self._aux_params is not None:
                aux_params = self._aux_params
            attrs = self._symbol.attr_dict()

            for name, arr in self._exec.arg_dict.items():
                if name in self._data_names or name in self._label_names \
                        or name in self._state_names:
                    continue
                given = (arg_params or {}).get(name)
                if given is not None:
                    given.copyto(arr) if isinstance(given, NDArray) \
                        else arr.__setitem__(slice(None), given)
                elif not allow_missing or initializer is not None:
                    if initializer is None:
                        if not allow_missing:
                            raise MXNetError(
                                "no initializer and no value for %r" % name)
                        continue
                    desc = InitDesc(name, attrs.get(name))
                    initializer(desc, arr)
            for name, arr in self._exec.aux_dict.items():
                given = (aux_params or {}).get(name)
                if given is not None:
                    given.copyto(arr)
                elif initializer is not None:
                    desc = InitDesc(name, attrs.get(name))
                    initializer(desc, arr)
            self.params_initialized = True
            self._params_dirty = False
            if self._mesh is not None:
                # re-commit: initializer writes land on the default device
                self._shard_exec_arrays()

    def get_params(self):
        """(parity: module.get_params) returns host copies."""
        assert self.binded and self.params_initialized
        arg_params = {n: arr.copy() for n, arr in self._exec.arg_dict.items()
                      if n in self._param_names}
        aux_params = {n: arr.copy() for n, arr in self._exec.aux_dict.items()}
        return arg_params, aux_params

    # -- optimizer ---------------------------------------------------------
    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),),
                       force_init=False):
        """(parity: module.py init_optimizer:472)"""
        assert self.binded and self.params_initialized
        if self.optimizer_initialized and not force_init:
            return
        with telemetry.span("init_optimizer"):
            arg_dict = self._exec.arg_dict
            kv, update_on_kvstore = _create_kvstore(
                kvstore, len(self._context),
                {n: arg_dict[n] for n in self._param_names})

            if isinstance(optimizer, str):
                idx2name = {i: n for i, n in enumerate(self._param_names)}
                optimizer_params = dict(optimizer_params)
                optimizer_params.setdefault("rescale_grad", 1.0)
                optimizer = opt.create(optimizer, sym=self._symbol,
                                       param_idx2name=idx2name,
                                       **optimizer_params)
            self._optimizer = optimizer
            if kv is not None:
                if kv.type.startswith("dist"):
                    # EVERY dist_* type runs the optimizer kvstore-side
                    # (reference semantics: the server applies updates for
                    # dist_sync, dist_sync_device, dist_device_sync AND
                    # dist_async alike). The old predicate named only
                    # "dist_sync" and let the other dist types ride
                    # whatever _create_kvstore defaulted to — the same
                    # outcome today, silently, and one heuristic change
                    # away from divergent update paths across workers.
                    update_on_kvstore = True
                for i, name in enumerate(self._param_names):
                    kv.init(i, arg_dict[name])
                if update_on_kvstore:
                    kv.set_optimizer(self._optimizer)
            self._kvstore = kv
            self._update_on_kvstore = update_on_kvstore
            self._updater = None
            if not update_on_kvstore:
                self._updater = opt.get_updater(optimizer)
            if kv is not None and kv.fused_dist_step:
                self._init_dist_spec()
            self.optimizer_initialized = True

    # -- compute -----------------------------------------------------------
    def forward(self, data_batch, is_train=None):
        """(parity: module.forward)"""
        assert self.binded and self.params_initialized
        if is_train is None:
            is_train = self.for_training
        # phase-split surface: runs on LOCAL placement (score/predict
        # between dist epochs, monitors) — no-op unless the fused dist
        # step left a process-spanning commit behind
        self._drop_dist_placement()
        self._set_batch(data_batch)
        self._exec.forward(is_train=is_train)

    def backward(self, out_grads=None):
        assert self.binded and self.params_initialized
        self._exec.backward(out_grads=out_grads)

    def forward_backward(self, data_batch):
        """Fused single-XLA-program step (overrides the base two-call path)."""
        assert self.binded and self.params_initialized
        self._drop_dist_placement()
        self._set_batch(data_batch)
        self._exec.forward_backward()

    def _set_batch(self, data_batch):
        with telemetry.span("feed"):
            self._set_batch_impl(data_batch)

    def _set_batch_impl(self, data_batch):
        data = data_batch.data
        if not isinstance(data, (list, tuple)):
            data = [data]
        arg_dict = self._exec.arg_dict
        # variable batch shapes (e.g. eval batch != train batch): the
        # reference reshapes its executors (executor.py reshape); here the
        # same program simply jits a second signature, so just swap storage.
        reshaped = False
        for desc, arr in zip(self._data_shapes, data):
            if tuple(arr.shape) != arg_dict[desc.name].shape:
                arg_dict[desc.name]._set_data(
                    np.zeros(arr.shape, dtype=arg_dict[desc.name].dtype))
                reshaped = True
        if reshaped and data_batch.label is not None:
            labels = data_batch.label
            if not isinstance(labels, (list, tuple)):
                labels = [labels]
            for desc, arr in zip(self._label_shapes, labels):
                if tuple(arr.shape) != arg_dict[desc.name].shape:
                    arg_dict[desc.name]._set_data(
                        np.zeros(arr.shape, dtype=arg_dict[desc.name].dtype))
        for desc, arr in zip(self._data_shapes, data):
            self._write_input(arg_dict[desc.name], arr)
        label = data_batch.label
        if label is not None:
            if not isinstance(label, (list, tuple)):
                label = [label]
            for desc, arr in zip(self._label_shapes, label):
                self._write_input(arg_dict[desc.name], arr)

    def _write_input(self, dst, src):
        if self._mesh is not None:
            # commit the batch sharded over dp so GSPMD splits the step;
            # keep the bound placeholder's dtype (as copyto/setitem do).
            # A reshaped (variable-batch) feed must stay divisible — the
            # sharded device_put would otherwise die inside XLA
            from ..parallel import spmd as _spmd
            raw = src._data if isinstance(src, NDArray) else np.asarray(src)
            if raw.shape:
                _spmd.check_batch_divisible(raw.shape[0],
                                            self._dp_spec.dp_size,
                                            "batch size",
                                            axis=self._dp_spec.data_axis)
            dt = dst._data.dtype
            if isinstance(raw, np.ndarray):
                raw = _spmd.shard_put(raw.astype(dt, copy=False),
                                      self._data_sharding)
            else:
                raw = _spmd.shard_put(raw, self._data_sharding).astype(dt)
            dst._set_data(raw)
        elif isinstance(src, NDArray):
            src.copyto(dst)
        else:
            raw = np.asarray(src)
            telemetry.record_transfer(raw.nbytes)
            dst[:] = raw

    def update(self):
        """Apply one optimizer step (parity: module.update →
        model._update_params(_on_kvstore):106-138)."""
        assert self.binded and self.params_initialized \
            and self.optimizer_initialized
        self._params_dirty = True
        arg_dict = self._exec.arg_dict
        grad_dict = self._exec.grad_dict
        # push/pull whole key LISTS: in dist mode kvstore then reduces all
        # keys in one jitted collective instead of one dispatch per param
        live = [(i, name) for i, name in enumerate(self._param_names)
                if grad_dict.get(name) is not None]
        if not live:
            return
        keys = [i for i, _ in live]
        grads = [grad_dict[name] for _, name in live]
        with telemetry.span("opt_update"):
            if self._kvstore is not None and self._update_on_kvstore:
                self._kvstore.push(keys, grads)
                self._kvstore.pull(keys,
                                   out=[arg_dict[name] for _, name in live])
            else:
                if self._kvstore is not None:
                    self._kvstore.push(keys, grads)
                    self._kvstore.pull(keys, out=grads)
                # one fused dispatch for the whole parameter set
                # (FusedUpdater)
                self._updater.update_batch(
                    keys, grads, [arg_dict[name] for _, name in live])

    # -- whole-step fused training -----------------------------------------
    def _fused_batch_step(self, data_batch, eval_metric=None):
        """Fused-step entry: run the impl, and on ANY fallback drop the
        process-spanning placement first — the phase-split oracle
        computes LOCAL gradients for the explicit kvstore wire, and a
        globally-committed executor would psum them inside
        forward_backward so the push would double-reduce."""
        ok = self._fused_batch_step_impl(data_batch, eval_metric)
        if not ok:
            self._drop_dist_placement()
        return ok

    def _fused_batch_step_impl(self, data_batch, eval_metric=None):
        """Forward + backward + optimizer update (+ metric accumulation
        when the metric has a device kernel) as ONE jitted XLA program
        with params/optimizer-state/metric/aux buffers donated
        (``executor._GraphProgram.train_step_fn``) — the whole-step
        program compilation that closes the Module.fit dispatch gap
        (PERF.md "Module.fit gap"). Batch arrays ride as jit arguments,
        so no copy into bound storage either. Returns True when the
        fused program ran; on False the caller must run the phase-split
        path (forward_backward/update/update_metric), which stays the
        correctness oracle. The reason for the last fallback is kept in
        ``_fused_fallback_reason``.

        Fallback rules (each mirrors a real constraint; the recorded
        reason is a ``FusedFallback`` — a str with a stable ``.code``):
        - ``MXNET_MODULE_FUSED_STEP=0`` — the A/B pin (``env_pin``)
        - grouped (group2ctx) programs — eager per-segment execution
        - monitor installed — per-op taps need the phase-split programs
        - ``dist_*`` kvstores (``kvstore_dist``) — push/pull crosses
          worker processes outside the compiled program — and stores
          with gradient compression (``kvstore_compression``). The
          in-process types (``local``/``device``/``nccl``) are SUBSUMED:
          on the dp mesh the gradient all-reduce rides inside the SPMD
          step program, so their push/pull is an identity round-trip
          the fused step skips (store weights are kept coherent so a
          mid-training fallback continues seamlessly)
        - optimizers without a pure batch kernel (no SPMD kernel
          mapping, centered RMSProp, inexpressible state layouts) or a
          non-Fused updater
        - ``inputs_need_grad`` — data gradients are phase-split only

        The expensive eligibility cascade + program lookup runs once and
        is cached as a per-module PLAN (``_fused_plan``), invalidated on
        any identity change (rebind, new optimizer/updater/metric);
        conditions that can flip without an identity change (the env
        pin, monitors, kvstore, hyperparameter statics, optimizer-state
        layout) are re-checked every step — they are attribute reads,
        not program rebuilds.
        """
        if not fused_fit():
            self._fused_fallback_reason = FusedFallback(
                "env_pin", "MXNET_MODULE_FUSED_STEP=0")
            return False
        ex = self._exec
        if ex is not None and ex._monitor_callback is not None:
            self._fused_fallback_reason = FusedFallback(
                "monitor", "monitor installed")
            return False
        kv = self._kvstore
        if kv is not None and not kv.fused_step_subsumable:
            if kv.fused_dist_step:
                # the dist sync tier: the SAME fused donated-buffer
                # step jits over the process-spanning dp mesh with the
                # cross-host psum inside the program (a single-process
                # job or the last survivor runs it locally) — dist_sync
                # no longer falls back. EXCEPT when this module never
                # committed a spanning mesh (borrowed optimizer /
                # bucketing switch paths skip _init_dist_spec): fusing
                # LOCALLY there would silently train divergent
                # replicas, so the explicit wire stays
                if self._dist_spec is None and len(kv.live_ranks) > 1:
                    self._fused_fallback_reason = FusedFallback(
                        "kvstore_dist", "kvstore-mediated update",
                        "multi-process dist store without a committed "
                        "process-spanning mesh (borrowed optimizer / "
                        "bucketing)")
                    return False
            elif kv.type.startswith("dist"):
                self._fused_fallback_reason = FusedFallback(
                    "kvstore_dist", "kvstore-mediated update",
                    "kvstore type %r keeps the explicit wire path "
                    "(async application is wire-emulated)" % kv.type)
                return False
            else:
                self._fused_fallback_reason = FusedFallback(
                    "kvstore_compression", "kvstore-mediated update",
                    "gradient compression changes the pushed values")
                return False
        # an in-process kvstore's reduce is subsumed by the SPMD step;
        # with update_on_kvstore the kvstore's server-side updater owns
        # the optimizer state, so the plan runs THAT updater's kernels
        updater = kv._updater if (kv is not None
                                  and self._update_on_kvstore) \
            else self._updater
        plan = self._fused_plan
        packed = None
        if (plan is None or plan["exec"] is not ex
                or plan["updater"] is not updater
                or plan["kvstore"] is not kv
                or plan["optimizer"] is not self._optimizer
                or plan["metric"] is not eval_metric
                or plan["has_label"] != (data_batch.label is not None)):
            plan = self._fused_plan = self._build_fused_plan(
                data_batch, eval_metric, updater)
        else:
            # hyperparameters baked into the program as statics can be
            # mutated on the live optimizer object — verify per step
            try:
                kname, hyper = plan["hyper_fn"](self._optimizer)
            except MXNetError as e:
                self._fused_fallback_reason = FusedFallback(
                    "optimizer_kernel", str(e))
                self._fused_plan = None
                return False
            statics = tuple(sorted(
                (k, v) for k, v in hyper.items() if k not in ("lr", "wd")))
            if kname != plan["kname"] or statics != plan["statics"]:
                plan = self._fused_plan = self._build_fused_plan(
                    data_batch, eval_metric, updater)
            else:
                # optimizer state re-gathered every step: layouts can
                # drift under the plan (load_optimizer_states swaps the
                # state NDArrays) and states for late parameters are
                # created here
                packed, mp, inner_n = updater._gather_batch(
                    plan["kname"], plan["indices"], plan["weights"])
                if packed is None or tuple(mp) != plan["mp"] \
                        or tuple(inner_n) != plan["inner_n"]:
                    packed = None
                    plan = self._fused_plan = self._build_fused_plan(
                        data_batch, eval_metric, updater)
        if plan is None:
            return False
        if packed is None:
            # a just-built plan carries the state its own gather packed
            packed = plan.pop("packed")
        return self._run_fused_step(plan, packed, data_batch, eval_metric)

    def _build_fused_plan(self, data_batch, eval_metric, updater=None):
        """Run the full fusion-eligibility cascade and assemble the
        per-module plan ``_fused_batch_step`` executes from: parameter
        ordering, the jitted whole-step program (SPMD-sharded over the
        dp mesh for a multi-context bind), and the metric device kernel.
        ``updater`` is the EFFECTIVE updater (the kvstore's server-side
        one under update_on_kvstore, else the module's). Returns None
        (with ``_fused_fallback_reason`` set) when any piece can't
        ride."""
        if not (self.binded and self.params_initialized
                and self.optimizer_initialized):
            self._fused_fallback_reason = FusedFallback(
                "not_initialised", "module not fully initialised")
            return None
        ex = self._exec
        if ex._prog.node_devices:
            self._fused_fallback_reason = FusedFallback(
                "group2ctx", "group2ctx grouped program")
            return None
        if updater is None:
            updater = self._updater
        if not isinstance(updater, opt.FusedUpdater):
            self._fused_fallback_reason = FusedFallback(
                "no_fused_updater", "updater has no fused batch path")
            return None
        if self.inputs_need_grad:
            self._fused_fallback_reason = FusedFallback(
                "inputs_need_grad", "inputs_need_grad")
            return None
        optimizer = self._optimizer
        from ..parallel import opt_kernels as _ok
        try:
            kname, hyper = _ok.hyper_from_optimizer(optimizer)
        except MXNetError as e:
            self._fused_fallback_reason = FusedFallback(
                "optimizer_kernel", str(e))
            return None
        if getattr(optimizer, "centered", False):
            self._fused_fallback_reason = FusedFallback(
                "centered_rmsprop", "centered RMSProp state layout")
            return None

        arg_dict = ex.arg_dict
        live = [(i, n) for i, n in enumerate(self._param_names)
                if self._grad_req.get(n, "null") != "null"]
        if not live:
            self._fused_fallback_reason = FusedFallback(
                "no_trainable_params", "no trainable parameters")
            return None
        indices = [i for i, _ in live]
        update_names = tuple(n for _, n in live)
        add_names = frozenset(n for _, n in live
                              if self._grad_req[n] == "add")
        weights = [arg_dict[n] for n in update_names]
        packed, mp, inner_n = updater._gather_batch(kname, indices, weights)
        if packed is None:
            self._fused_fallback_reason = FusedFallback(
                "state_layout",
                "optimizer state layout not expressible as a kernel step")
            return None

        has_label = data_batch.label is not None
        graph_args = frozenset(ex._prog.arg_names)
        bound_labels = [l.name for l in self._label_shapes] \
            if self._label_shapes else []
        # only GRAPH-CONSUMED labels ride as program inputs: a label
        # bound purely for metric use (e.g. a MakeLoss custom loss) is
        # not a graph argument, and feeding it would blow the trace
        label_inputs = [n for n in bound_labels if n in graph_args]
        # metric: fuse only a plain (no output/label renaming) metric
        # with a device kernel and a 1:1 BOUND, graph-fed label/output
        # pairing — the kernel reads the label arrays the step actually
        # feeds; anything else accumulates phase-split on the step's
        # outputs
        kernel = None
        if eval_metric is not None and has_label \
                and eval_metric.output_names is None \
                and eval_metric.label_names is None \
                and bound_labels and label_inputs == bound_labels \
                and len(bound_labels) == len(self._output_names):
            kernel = eval_metric.device_kernel()

        input_names = [d.name for d in self._data_shapes]
        if has_label:
            input_names += label_inputs
        input_names += list(self._state_names)
        if any(n not in arg_dict for n in input_names):
            self._fused_fallback_reason = FusedFallback(
                "missing_input",
                "bound input(s) missing from the executor arg dict: "
                + ", ".join(sorted(n for n in input_names
                                   if n not in arg_dict)))
            return None
        input_dtypes = {n: arg_dict[n]._data.dtype for n in input_names}

        # every graph argument must be fed (as a param or an input): a
        # label-consuming graph bound without label shapes, or handed a
        # label-less batch, cannot ride the pure-function program
        missing = graph_args.difference(self._param_names, input_names)
        if missing:
            self._fused_fallback_reason = FusedFallback(
                "unfed_graph_arg",
                "graph argument(s) not fed by the fused step: "
                + ", ".join(sorted(missing)))
            return None

        statics = tuple(sorted(
            (k, v) for k, v in hyper.items() if k not in ("lr", "wd")))
        metric_key = None if kernel is None else \
            (type(eval_metric).__module__, type(eval_metric).__qualname__,
             getattr(eval_metric, "axis", None), tuple(bound_labels))
        cache_key = (kname, statics, tuple(mp), tuple(inner_n), metric_key)
        label_names = bound_labels

        def build_metric_fn():
            def metric_fn(outs, ins, acc):
                return kernel([ins[n] for n in label_names], list(outs), acc)
            return metric_fn

        # the dist tier overrides the local dp spec: ONE program over
        # the process-spanning mesh, cross-host psum compiled inside
        spmd_spec = self._dist_spec if self._dist_spec is not None \
            else self._dp_spec

        build_shardings = None
        if spmd_spec is not None \
                and getattr(spmd_spec, "rules", None) is not None:
            spec = spmd_spec
            param_names = list(self._param_names)
            aux_pairs = [(n, a.shape)
                         for n, a in zip(ex._aux_names, ex.aux_arrays)]
            state_shapes = [tuple(tuple(x.shape) for x in tup)
                            for tup in packed]

            def build_shardings():
                # per-leaf NamedShardings from the rule tree: optimizer
                # state the shape of its weight (momenta, fp32 masters)
                # rides the weight's placement; any other leaf shape
                # replicates on the same mesh
                psh = {n: spec.param_sharding(n, arg_dict[n].shape)
                       for n in param_names}
                repl = spec.repl_sharding
                ssh = []
                for n, shapes in zip(update_names, state_shapes):
                    wshape = tuple(arg_dict[n].shape)
                    ssh.append(tuple(psh[n] if s == wshape else repl
                                     for s in shapes))
                return {
                    "params": psh,
                    "states": ssh,
                    "aux": {n: spec.param_sharding(n, s)
                            for n, s in aux_pairs},
                    "add_grads": {n: psh[n] for n in add_names},
                }
        fn = ex._prog.train_step_fn(
            update_names, add_names, input_dtypes, cache_key,
            build_update_fn=lambda: opt._make_batch_update(
                kname, dict(statics), list(mp), list(inner_n)),
            build_metric_fn=build_metric_fn if kernel is not None else None,
            spmd=spmd_spec, build_shardings=build_shardings)
        # a SUBSUMED update_on_kvstore store holds its own canonical
        # weight copies (push updates them, pull serves them); the fused
        # step keeps them coherent with zero-cost pointer swaps so a
        # mid-training fallback (or save_checkpoint via pull) continues
        # from the right values
        kv = self._kvstore
        store_sync = []
        if kv is not None and self._update_on_kvstore:
            store_sync = [(n, kv._store[i]) for i, n in live
                          if i in kv._store]
        return {
            "exec": ex, "updater": updater, "optimizer": optimizer,
            "kvstore": kv, "store_sync": store_sync,
            "metric": eval_metric, "has_label": has_label,
            "kname": kname, "statics": statics,
            "hyper_fn": _ok.hyper_from_optimizer,
            "indices": indices, "update_names": update_names,
            "add_names": add_names, "weights": weights,
            "mp": tuple(mp), "inner_n": tuple(inner_n),
            "kernel": kernel, "fn": fn,
            "label_inputs": frozenset(label_inputs),
            "spmd_spec": spmd_spec,
            # the resolved layout rides in the plan (and from there
            # into checkpoint meta / the tuner's corpus records)
            "layout": self.partition_summary(),
            # per-process gradient payload of the in-program psum (the
            # dist wire-bytes estimate bumped per spanning step)
            "dist_wire_bytes": sum(
                int(w._data.size) * w._data.dtype.itemsize
                for w in weights),
            # the state gathered above, consumed (popped) by the step
            # that built the plan — later steps re-gather fresh
            "packed": packed,
        }

    def _run_fused_step(self, plan, packed, data_batch, eval_metric):   # mxlint: hot
        """Execute one whole-step fused program from a validated plan:
        marshal raw buffers, launch, reinstall the donated results."""
        ex = self._exec
        arg_dict = ex.arg_dict
        optimizer = plan["optimizer"]
        kernel = plan["kernel"]
        data = data_batch.data
        if not isinstance(data, (list, tuple)):
            data = [data]
        label = data_batch.label
        if label is not None and not isinstance(label, (list, tuple)):
            label = [label]

        from ..parallel import spmd as _spmd
        spec = plan["spmd_spec"]
        spanning = spec is not None \
            and _spmd.is_process_spanning(spec.mesh)
        mesh = spec.mesh if spec is not None else None
        sharding = spec.data_sharding if spec is not None else None
        import jax
        dev = None if mesh is not None else self._context[0].jax_device()
        if spanning:
            self._ensure_dist_placement()
            if self._dist_sync_handle is not None:
                # complete the PREVIOUS spanning step before gating:
                # every member that crosses the gate has finished its
                # part of step N-1's collective, so a member that dies
                # at the gate can never leave peers hung inside an
                # in-flight exchange — the price is one host sync per
                # dist step (they are wire-bound anyway)
                self._await_dist_step(self._dist_sync_handle)
                self._dist_sync_handle = None
            # liveness gate BEFORE entering the collective step: a dead
            # peer raises DeadWorkerError here (elastic recovery), a
            # live job pays two tiny file writes + a poll
            self._dist_gate().arrive_and_wait()

        def _raw(arr):
            raw = arr._data if isinstance(arr, NDArray) else np.asarray(arr)   # mxlint: disable=host-sync -- feed-path marshalling of a HOST-side batch array (lists/np inputs); device arrays take the _data branch
            if spanning:
                # this worker's LOCAL rows become its shard of the
                # global batch (no host gather, no peer traffic)
                raw = _spmd.dist_shard_put(np.asarray(raw), spec)   # mxlint: disable=host-sync -- same feed-path marshalling: the process-local constructor needs the host view of the local batch
            elif mesh is not None:
                # one sharded device_put of the GLOBAL batch — each
                # device receives its shard, no host-side splitting
                if raw.shape:
                    _spmd.check_batch_divisible(
                        raw.shape[0], spec.dp_size, "batch size",
                        axis=spec.data_axis)
                raw = _spmd.shard_put(raw, sharding)
            else:
                # batch arrays ride as jit arguments without a copy into
                # bound storage, so THIS is where they must commit to
                # the module's device (a module on a non-default device
                # fed default-device arrays would otherwise crash the
                # program with mixed committed inputs; same-device puts
                # are a no-op)
                if isinstance(raw, np.ndarray):
                    telemetry.record_transfer(raw.nbytes)
                raw = jax.device_put(raw, dev)
            return raw

        with telemetry.span("feed"):
            inputs = {}
            for desc, arr in zip(self._data_shapes, data):
                inputs[desc.name] = _raw(arr)
            label_raws = []
            if label is not None and self._label_shapes:
                for desc, arr in zip(self._label_shapes, label):
                    r = _raw(arr)
                    # the jit signature carries only graph-consumed
                    # labels
                    if desc.name in plan["label_inputs"]:
                        inputs[desc.name] = r
                    label_raws.append(r)
            for name in self._state_names:
                inputs[name] = arg_dict[name]._data

        # host-side bookkeeping exactly as the phase-split update() does
        # it — same Updater states, same count/lr/wd schedule, so a
        # fallback mid-training continues seamlessly. ``step_prep``
        # runs from the end of the feed to the dispatch: these loops
        # over every parameter are host time a span has to name
        with telemetry.span("step_prep"):
            indices = plan["indices"]
            for i in indices:
                optimizer._update_count(i)
            counts = optimizer._index_update_count
            ts = np.asarray([counts[i] for i in indices], np.float32)
            lrs = np.asarray([optimizer._get_lr(i) for i in indices],
                             np.float32)
            wds = np.asarray([optimizer._get_wd(i) for i in indices],
                             np.float32)

            params_raw = {n: arg_dict[n]._data for n in self._param_names}
            states_raw = [tuple(x._data for x in tup) for tup in packed]
            aux_raw = {n: a._data
                       for n, a in zip(ex._aux_names, ex.aux_arrays)}
            grad_dict = ex.grad_dict
            add_names = plan["add_names"]
            add_grads = {n: grad_dict[n]._data for n in add_names}
            acc = None
            if kernel is not None:
                acc = getattr(eval_metric, "_dev_sum", None)
                if acc is None:
                    import jax.numpy as jnp
                    # a fresh accumulator commits to the module's placement
                    # (the mesh program reshards via in_shardings; a single-
                    # device module must not introduce a default-device
                    # operand)
                    acc = jnp.zeros((), jnp.float32)
                    if spanning:
                        acc = _spmd.put_replicated_local(acc, spec)
                    elif dev is not None:
                        acc = jax.device_put(acc, dev)
            rng = ex._step_key()
            if spanning:
                # per-step scalars install as replicated WITHOUT a
                # collective (every worker computes identical values —
                # the SPMD discipline put_replicated_local documents);
                # letting jit auto-commit them would pay a cross-host
                # equality collective per array per step
                rng = _spmd.put_replicated_local(rng, spec)
                lrs = _spmd.put_replicated_local(lrs, spec)
                wds = _spmd.put_replicated_local(wds, spec)
                ts = _spmd.put_replicated_local(ts, spec)

        record_dispatch("train_step")
        self._steps_unpublished += 1
        with telemetry.span("step"):
            new_params, new_states, new_acc, new_aux, outs, grads_out = \
                plan["fn"](params_raw, states_raw, acc, aux_raw, inputs, rng,   # mxlint: donates 0-3
                           lrs, wds, ts, add_grads)
        with telemetry.span("step_install"):
            if spanning:
                # the in-program cross-host psum IS the dist wire now:
                # account it next to the explicit push path's counters, and
                # keep a handle for the pre-gate sync of the NEXT step
                self._dist_sync_handle = \
                    new_params[plan["update_names"][0]] \
                    if plan["update_names"] else None
                telemetry.counter_inc("kvstore.dist.fused_steps")
                telemetry.counter_inc("kvstore.dist.collectives")
                telemetry.counter_inc("kvstore.dist.wire_bytes",
                                      plan["dist_wire_bytes"])
                telemetry.counter_inc("kvstore.dist.wire_bytes_raw",
                                      plan["dist_wire_bytes"])

            # donation invalidated the old buffers — reinstall everything
            for n in self._param_names:
                arg_dict[n]._set_data(new_params[n])
            for tup, ntup in zip(packed, new_states):
                for x, nx in zip(tup, ntup):
                    x._set_data(nx)
            for n, a in zip(ex._aux_names, ex.aux_arrays):
                a._set_data(new_aux[n])
            # only 'add' accumulators come back (next step's input); 'write'
            # grads are consumed inside the program and never materialized
            # (add_grads above already established every 'add' grad exists)
            for n in add_names:
                grad_dict[n]._set_data(grads_out[n])
            # subsumed update_on_kvstore: refresh the store's canonical
            # weight copies (pointer swaps — no device work)
            for n, store_arr in plan["store_sync"]:
                store_arr._set_data(new_params[n])
            ex.outputs = [_wrap(o, ex._out_ctx(i)) for i, o in enumerate(outs)]
            if kernel is not None:
                n_inst = sum(int(r.size) for r in label_raws)
                eval_metric._install_fused(new_acc, n_inst)
            elif eval_metric is not None:
                self.update_metric(eval_metric, data_batch.label)
            self._params_dirty = True
            self._fused_fallback_reason = None
        return True

    def get_outputs(self, merge_multi_context=True):
        assert self.binded
        return list(self._exec.outputs)

    def _publish_aux_counters(self):
        steps, self._steps_unpublished = self._steps_unpublished, 0
        self._exec.publish_aux_counters(steps)

    def get_input_grads(self, merge_multi_context=True):
        assert self.binded and self.inputs_need_grad
        gd = self._exec.grad_dict
        return [gd[n] for n in self._data_names]

    def update_metric(self, eval_metric, labels):
        # "metric_update" — the NON-blocking per-batch accumulate; the
        # blocking host fetch records separately as "metric_fetch"
        # (EvalMetric._flush_device), so the fetch histogram stays the
        # stall detector PERF.md reads
        with telemetry.span("metric_update"):
            eval_metric.update(labels if isinstance(labels, (list, tuple))
                               else [labels], self.get_outputs())

    def finite_check(self):
        """Device-side divergence sentinel (overrides the base host
        fold): ONE jitted program (``executor.finite_fold_fn``) folds
        ``isfinite`` over the last step's outputs (the loss head),
        every materialised gradient, and every parameter — a NaN
        gradient poisons the params on the step it appears, so a
        periodic check over params catches mid-interval divergence —
        then fetches the single scalar verdict."""
        from ..executor import finite_fold_fn
        assert self.binded and self.params_initialized
        ex = self._exec
        leaves = [o._data for o in ex.outputs]
        leaves += [g._data for g in ex.grad_dict.values()
                   if g is not None]
        leaves += [ex.arg_dict[n]._data for n in self._param_names]
        if not leaves:
            return True
        record_dispatch("finite_check")
        with telemetry.span("divergence_check"):
            verdict = finite_fold_fn()(leaves)
            return bool(np.asarray(verdict))

    # -- checkpoints -------------------------------------------------------
    def save_checkpoint(self, prefix, epoch, save_optimizer_states=False):
        """(parity: module.py save_checkpoint:164)"""
        arg_params, aux_params = self.get_params()
        save_checkpoint(prefix, epoch, self._symbol, arg_params, aux_params)
        if save_optimizer_states:
            self.save_optimizer_states("%s-%04d.states" % (prefix, epoch))

    @staticmethod
    def load(prefix, epoch, load_optimizer_states=False, **kwargs):
        """(parity: module.py Module.load:126)"""
        symbol, arg_params, aux_params = load_checkpoint(prefix, epoch)
        mod = Module(symbol, **kwargs)
        mod._arg_params = arg_params
        mod._aux_params = aux_params
        # reference Module.load marks params initialised; bind() installs
        # them into the executor (module.py:126-183)
        mod.params_initialized = True
        mod._preloaded_params = (arg_params, aux_params)
        if load_optimizer_states:
            mod._preload_opt_states = "%s-%04d.states" % (prefix, epoch)
        return mod

    def borrow_optimizer(self, shared_module):
        """Share another Module's optimizer/updater (parity:
        module.borrow_optimizer)."""
        assert shared_module.optimizer_initialized
        self._optimizer = shared_module._optimizer
        self._kvstore = shared_module._kvstore
        self._update_on_kvstore = shared_module._update_on_kvstore
        self._updater = shared_module._updater
        self.optimizer_initialized = True

    def get_input_grads(self, merge_multi_context=True):
        """Gradients w.r.t. inputs from the last backward (parity:
        module.get_input_grads — requires inputs_need_grad)."""
        assert self.binded and self.params_initialized
        assert self.inputs_need_grad
        grads = self._exec.grad_dict
        return [grads[name] for name in self._data_names if name in grads]

    def save_optimizer_states(self, fname):
        """(parity: module.save_optimizer_states:759) — atomic
        (temp+fsync+rename) so a preemption mid-save never truncates
        the previous states file."""
        from ..checkpoint import atomic_write
        assert self.optimizer_initialized
        if self._update_on_kvstore:
            self._kvstore.save_optimizer_states(fname)
        else:
            atomic_write(fname, self._updater.get_states())

    def load_optimizer_states(self, fname):
        assert self.optimizer_initialized
        if self._update_on_kvstore:
            self._kvstore.load_optimizer_states(fname)
        else:
            with open(fname, "rb") as f:
                self._updater.set_states(f.read())

    def install_monitor(self, mon):
        assert self.binded
        mon.install(self._exec)

    def reshape(self, data_shapes, label_shapes=None):
        """(parity: module.reshape) — on TPU just a new jit signature."""
        assert self.binded
        arg_p, aux_p = self.get_params() if self.params_initialized else (None, None)
        self.binded = False
        self._exec = None
        self.bind(data_shapes, label_shapes, self.for_training,
                  self.inputs_need_grad, force_rebind=True)
        if arg_p is not None:
            self.set_params(arg_p, aux_p)

    def init_params_from_preloaded(self):
        if getattr(self, "_preloaded_params", None) and self.binded:
            arg_p, aux_p = self._preloaded_params
            self.set_params(arg_p, aux_p)


def _flatten_state(st):
    """NDArray leaves of one updater state entry (states are NDArrays,
    tuples of them — multi-precision nests master weights — or None)."""
    if st is None:
        return []
    if isinstance(st, (list, tuple)):
        out = []
        for x in st:
            out.extend(_flatten_state(x))
        return out
    return [st] if isinstance(st, NDArray) else []


def _as_desc(d):
    from ..io import DataDesc
    if isinstance(d, DataDesc):
        return d
    name, shape = d[0], d[1]
    return DataDesc(name, shape)
