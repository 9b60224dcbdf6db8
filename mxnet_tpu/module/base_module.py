"""BaseModule: the high-level train/eval loop.

Parity: reference ``python/mxnet/module/base_module.py`` (fit:376,
forward_backward:189, score, predict). The training loop is unchanged at
the API level; the speed comes from Module's fused jitted step underneath.
"""
from __future__ import annotations

import logging
import time

import numpy as np

from ..base import MXNetError
from .. import metric as _metric
from .. import telemetry
from ..model import BatchEndParam
from ..initializer import Uniform


# stable fallback reason codes -> what they mean. Bench lanes and tests
# assert on CODES; the human-readable message may reword freely.
FUSED_FALLBACK_CODES = {
    "env_pin": "MXNET_MODULE_FUSED_STEP=0 pins the phase-split A/B leg",
    "monitor": "per-op monitor taps need the phase-split programs",
    "kvstore_dist": "dist_* kvstore push/pull crosses worker processes",
    "kvstore_compression": "gradient compression changes pushed values",
    "group2ctx": "grouped (group2ctx) programs run eagerly per segment",
    "no_fused_updater": "updater has no fused batch path",
    "inputs_need_grad": "data gradients are phase-split only",
    "optimizer_kernel": "optimizer has no pure SPMD batch kernel",
    "centered_rmsprop": "centered RMSProp state layout",
    "no_trainable_params": "nothing to update",
    "state_layout": "optimizer state layout not expressible as a kernel",
    "missing_input": "bound input missing from the executor arg dict",
    "unfed_graph_arg": "graph argument not fed by the fused step",
    "not_initialised": "module not fully initialised",
}


class FusedFallback(str):
    """Why one step ran phase-split instead of fused. A ``str`` subclass
    so every existing message-text consumer (tests, bench JSON, logs)
    keeps working unchanged; ``code`` is the STABLE enumerable identity
    (one of ``FUSED_FALLBACK_CODES``) for bench lanes and tests to
    assert on, and ``detail`` carries the free-form specifics."""
    __slots__ = ("code", "detail")

    def __new__(cls, code, message, detail=None):
        assert code in FUSED_FALLBACK_CODES, code
        self = str.__new__(cls, message)
        self.code = code
        self.detail = message if detail is None else detail
        return self


class BaseModule:
    """(parity: base_module.BaseModule)"""

    def __init__(self, logger=logging):
        self.logger = logger
        self.binded = False
        self.for_training = False
        self.inputs_need_grad = False
        self.params_initialized = False
        self.optimizer_initialized = False
        self._symbol = None
        self._total_exec_bytes = 0

    # -- things subclasses implement --------------------------------------
    def forward(self, data_batch, is_train=None):
        raise NotImplementedError

    def backward(self, out_grads=None):
        raise NotImplementedError

    def update(self):
        raise NotImplementedError

    def get_outputs(self, merge_multi_context=True):
        raise NotImplementedError

    def update_metric(self, eval_metric, labels):
        raise NotImplementedError

    def bind(self, *args, **kwargs):
        raise NotImplementedError

    def init_params(self, *args, **kwargs):
        raise NotImplementedError

    def init_optimizer(self, *args, **kwargs):
        raise NotImplementedError

    # -- composite ---------------------------------------------------------
    def forward_backward(self, data_batch):
        """(parity: base_module.forward_backward:189)"""
        self.forward(data_batch, is_train=True)
        self.backward()

    def _fused_batch_step(self, data_batch, eval_metric=None):
        """Whole-train-step fusion hook: run forward+backward+optimizer
        (+metric) as ONE compiled program and return True, or return
        False when the caller must use the phase-split path. Subclasses
        with a fused program override (Module, BucketingModule); the
        base class always phase-splits."""
        return False

    def _note_fused_fallback(self):
        """Account one phase-split step: count the fallback event in the
        telemetry registry (keyed by the stable ``FusedFallback.code``)
        and log it through log.py as a structured warning ONCE per
        module per code — the reason used to sit silently in
        ``_fused_fallback_reason``."""
        reason = getattr(self, "_fused_fallback_reason", None)
        if reason is None:
            return
        code = getattr(reason, "code", "unknown")
        telemetry.record_fallback(code)
        logged = self.__dict__.setdefault("_fused_fallback_logged", set())
        if code not in logged:
            logged.add(code)
            from .. import log as _log
            _log.get_logger("mxnet_tpu.module").warning(
                "fused-step fallback code=%s: %s (detail: %s) — this "
                "module trains phase-split (see "
                "mx.mod.FUSED_FALLBACK_CODES)",
                code, str(reason), getattr(reason, "detail", str(reason)))

    def telemetry_snapshot(self):
        """The process-wide ``telemetry.snapshot()`` (dispatch counts,
        jit compiles vs. cache hits, fused-fallback codes, transfer
        bytes, blocking host syncs, span p50/p95/p99, the PROGRAM CARDS
        of every compiled XLA program with their cost/memory figures,
        the online MFU estimate and the device-buffer ledger) plus this
        module's last fused-fallback reason/code. JSON-serializable end
        to end — bench/probe artifacts embed it per leg."""
        snap = telemetry.snapshot()
        reason = getattr(self, "_fused_fallback_reason", None)
        snap["fused_fallback_reason"] = None if reason is None else str(reason)
        snap["fused_fallback_code"] = getattr(reason, "code", None)
        return snap

    def fused_step(self, data, label=None, eval_metric=None):
        """Run ONE whole training step — forward, backward, optimizer
        update, and (when ``eval_metric`` can accumulate on device)
        metric update — as a single compiled XLA program with parameter /
        optimizer-state / metric buffers donated. This is the
        ``Module.fit`` inner loop exposed for manual training loops:

            for batch in train_iter:
                mod.fused_step(batch, eval_metric=metric)

        ``data`` may be a DataBatch (then ``label`` is ignored) or an
        NDArray/list of NDArrays with ``label`` alongside. When any
        piece cannot fuse (see Module._fused_batch_step for the rules)
        the step still runs — phase-split — and False is returned;
        True means the single fused program ran."""
        from ..io import DataBatch
        if not isinstance(data, DataBatch):
            d = list(data) if isinstance(data, (list, tuple)) else [data]
            lab = None if label is None else (
                list(label) if isinstance(label, (list, tuple)) else [label])
            data = DataBatch(data=d, label=lab)
        if self._fused_batch_step(data, eval_metric):
            return True
        self._note_fused_fallback()
        self.forward_backward(data)
        self.update()
        if eval_metric is not None:
            self.update_metric(eval_metric, data.label)
        return False

    def score(self, eval_data, eval_metric, num_batch=None,
              batch_end_callback=None, score_end_callback=None, reset=True,
              epoch=0, sparse_row_id_fn=None):
        """(parity: base_module.score)"""
        assert self.binded and self.params_initialized
        if reset:
            eval_data.reset()
        if not isinstance(eval_metric, _metric.EvalMetric):
            eval_metric = _metric.create(eval_metric)
        eval_metric.reset()
        actual_num_batch = 0
        for nbatch, eval_batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            self.forward(eval_batch, is_train=False)
            self.update_metric(eval_metric, eval_batch.label)
            if batch_end_callback is not None:
                param = BatchEndParam(epoch=epoch, nbatch=nbatch,
                                      eval_metric=eval_metric, locals=locals())
                for cb in _as_list(batch_end_callback):
                    cb(param)
            actual_num_batch += 1
        if score_end_callback:
            param = BatchEndParam(epoch=epoch, nbatch=actual_num_batch,
                                  eval_metric=eval_metric, locals=locals())
            for cb in _as_list(score_end_callback):
                cb(param)
        return eval_metric.get_name_value()

    def predict(self, eval_data, num_batch=None, merge_batches=True,
                reset=True, always_output_list=False,
                sparse_row_id_fn=None):
        """(parity: base_module.predict)"""
        from ..ndarray import concatenate
        assert self.binded and self.params_initialized
        if reset:
            eval_data.reset()
        output_list = []
        for nbatch, eval_batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            self.forward(eval_batch, is_train=False)
            pad = eval_batch.pad or 0
            outs = [out[0:out.shape[0] - pad] for out in self.get_outputs()]
            output_list.append(outs)
        if len(output_list) == 0:
            return output_list
        if merge_batches:
            num_outputs = len(output_list[0])
            for out in output_list:
                if len(out) != num_outputs:
                    raise MXNetError("Cannot merge batches: different number "
                                     "of outputs per batch")
            output_list2 = [concatenate([out[i] for out in output_list])
                            for i in range(num_outputs)]
            if num_outputs == 1 and not always_output_list:
                return output_list2[0]
            return output_list2
        return output_list

    def fit(self, train_data, eval_data=None, eval_metric="acc",
            epoch_end_callback=None, batch_end_callback=None, kvstore="local",
            optimizer="sgd", optimizer_params=(("learning_rate", 0.01),),
            eval_end_callback=None, eval_batch_end_callback=None,
            initializer=Uniform(0.01), arg_params=None, aux_params=None,
            allow_missing=False, force_rebind=False, force_init=False,
            begin_epoch=0, num_epoch=None, validation_metric=None,
            monitor=None, sparse_row_id_fn=None,
            checkpoint=None, resume=None,
            divergence_check_every=0, divergence_policy="halt"):
        """Train (parity: base_module.fit:376 — bind → init_params →
        init_optimizer → per-batch forward_backward/update/metric loop).

        Fault-tolerance extensions (no reference counterpart):

        - ``checkpoint``: a ``CheckpointManager`` (or prefix string)
          that (a) saves an atomic keep-last-K checkpoint at every
          epoch end and (b) ARMS SIGTERM/SIGINT for the duration of
          fit: a signal sets a flag checked at batch boundaries, the
          in-flight batch completes, a mid-epoch checkpoint
          (epoch, nbatch) is written, and ``TrainingPreempted`` is
          raised — the preemption grace window buys one atomic save,
          not a stack unwind.
        - ``resume``: ``True`` (resume from ``checkpoint``'s latest),
          or a ``CheckpointManager``/prefix. Restores params,
          optimizer states + per-parameter update counts, and the
          global RNG key, then continues from the recorded
          epoch+batch (already-applied batches of the resumed epoch
          are consumed from the iterator without compute). No
          checkpoint found = fresh start, not an error.
        - ``divergence_check_every`` / ``divergence_policy``: every N
          batches run the divergence sentinel (``finite_check()`` — a
          device-side isfinite fold over the step outputs and, for
          Module, every parameter). On non-finite values the policy
          applies: ``"halt"`` raises ``DivergenceError``, ``"skip"``
          logs + counts and keeps training, ``"rollback"`` restores
          the ``checkpoint`` manager's latest checkpoint and
          continues (halts when there is nothing to roll back to).
        """
        from ..checkpoint import CheckpointManager, TrainingPreempted
        assert num_epoch is not None, "please specify number of epochs"
        if divergence_policy not in ("halt", "skip", "rollback"):
            raise MXNetError("divergence_policy must be halt|skip|"
                             "rollback, got %r" % (divergence_policy,))
        # the preamble, from here to the first batch of _fit_loop (which
        # leaves it; leaving again is a no-op, so it is left however fit
        # ends)
        setup = telemetry.span("fit_setup").__enter__()
        try:
            ckpt = checkpoint
            if isinstance(ckpt, str):
                ckpt = CheckpointManager(ckpt)
            rmgr = None
            if resume is not None and resume is not False:
                rmgr = ckpt if resume is True else resume
                if isinstance(rmgr, str):
                    rmgr = CheckpointManager(rmgr)
                if rmgr is None:
                    raise MXNetError("fit(resume=True) needs checkpoint=")
            resume_meta = rmgr.latest() if rmgr is not None else None
            self.bind(data_shapes=train_data.provide_data,
                      label_shapes=train_data.provide_label,
                      for_training=True, force_rebind=force_rebind)
            if monitor is not None:
                self.install_monitor(monitor)
            self.init_params(initializer=initializer,
                             arg_params=arg_params, aux_params=aux_params,
                             allow_missing=allow_missing,
                             force_init=force_init)
            self.init_optimizer(kvstore=kvstore, optimizer=optimizer,
                                optimizer_params=optimizer_params)
            skip_batches = 0
            if resume_meta is not None:
                rmgr.restore(self, resume_meta)
                begin_epoch = int(resume_meta["epoch"])
                skip_batches = int(resume_meta.get("nbatch", 0))
                self.logger.info(
                    "Resuming from checkpoint %s: epoch=%d nbatch=%d",
                    rmgr.prefix, begin_epoch, skip_batches)
            if validation_metric is None:
                validation_metric = eval_metric
            if not isinstance(eval_metric, _metric.EvalMetric):
                eval_metric = _metric.create(eval_metric)

            from ..heartbeat import DeadWorkerError
            if ckpt is not None:
                ckpt.clear_preempt()
                ckpt.arm_signals()
            try:
                while True:
                    try:
                        self._fit_loop(setup, train_data, eval_data,
                                       eval_metric, validation_metric,
                                       epoch_end_callback,
                                       batch_end_callback, eval_end_callback,
                                       eval_batch_end_callback, monitor,
                                       sparse_row_id_fn, begin_epoch,
                                       num_epoch, skip_batches, ckpt,
                                       divergence_check_every,
                                       divergence_policy)
                        break
                    except DeadWorkerError as e:
                        # ELASTIC RECOVERY: a peer died before a
                        # collective (the liveness gate aborted the step —
                        # nothing is hung). Postmortem the death, re-mesh
                        # over the survivors, restore the last atomic
                        # checkpoint and continue the SAME fit call from
                        # its (epoch, nbatch). Work since that checkpoint
                        # is lost — that is the recovery contract (README
                        # "Distributed training").
                        meta = self._elastic_recover(e, ckpt)
                        begin_epoch = int(meta["epoch"])
                        skip_batches = int(meta.get("nbatch", 0))
            finally:
                if ckpt is not None:
                    ckpt.disarm_signals()
        finally:
            setup.__exit__(None, None, None)

    # mxlint: hot
    def _fit_loop(self, setup, train_data, eval_data, eval_metric,
                  validation_metric, epoch_end_callback,
                  batch_end_callback, eval_end_callback,
                  eval_batch_end_callback, monitor, sparse_row_id_fn,
                  begin_epoch, num_epoch, skip_batches, ckpt,
                  divergence_check_every, divergence_policy):
        from ..checkpoint import TrainingPreempted
        from ..heartbeat import DeadWorkerError
        train_data.reset()
        fit_step = 0        # batches since fit began: the profiler's step
        for epoch in range(begin_epoch, num_epoch):
            tic = time.time()
            eval_metric.reset()
            nbatch = 0
            data_iter = iter(train_data)
            end_of_batch = False
            if epoch == begin_epoch and skip_batches:
                # mid-epoch resume: the checkpoint already holds these
                # batches' updates — consume them without compute so
                # the remaining epoch sees the SAME data the
                # interrupted run would have
                for _ in range(skip_batches):
                    try:
                        next(data_iter)
                    except StopIteration:
                        break
                nbatch = skip_batches
            try:
                next_data_batch = next(data_iter)
            except StopIteration:
                end_of_batch = True
                next_data_batch = None
            setup.__exit__(None, None, None)    # fit_setup ends here
            while not end_of_batch:
                data_batch = next_data_batch
                if monitor is not None:
                    monitor.tic()
                # whole-step fused program when every piece can ride
                # (one device dispatch, buffers donated, metric
                # accumulated in-program); phase-split otherwise — see
                # Module._fused_batch_step for the fallback rules. The
                # loop itself never blocks on device values: batch N+1
                # dispatches while batch N executes, metric values are
                # fetched lazily (sync happens only at epoch end and in
                # callbacks that read the metric).
                # The causal() scope stamps (epoch, nbatch) step ids on
                # every span this batch records (fit_batch, feed, step,
                # opt_update, ...): they ride as stats of each span's
                # annotation in a profiler trace, and a postmortem's
                # ring says which step each interval served. fit_batch
                # is the profiler's STEP annotation (its step view
                # groups the device's work by it).
                step_ids = telemetry.causal(epoch=epoch, nbatch=nbatch)
                try:
                    with step_ids, \
                            telemetry.span("fit_batch", step_num=fit_step):
                        fused = self._fused_batch_step(data_batch,
                                                       eval_metric)
                        if not fused:
                            self._note_fused_fallback()
                            self.forward_backward(data_batch)
                            self.update()
                        try:
                            next_data_batch = next(data_iter)
                            self.prepare(next_data_batch,
                                         sparse_row_id_fn=sparse_row_id_fn)
                        except StopIteration:
                            end_of_batch = True
                        if not fused:
                            self.update_metric(eval_metric,
                                               data_batch.label)
                except DeadWorkerError as e:
                    # stamp the step the death aborted — the elastic
                    # handler's postmortem names it
                    if e.epoch is None:
                        e.epoch, e.nbatch = epoch, nbatch
                    raise
                if monitor is not None:
                    monitor.toc_print()
                if divergence_check_every > 0 \
                        and (nbatch + 1) % divergence_check_every == 0 \
                        and not self.finite_check():   # mxlint: disable=host-sync -- opt-in divergence sentinel: the user asked for a blocking verdict once per divergence_check_every batches
                    self._handle_divergence(divergence_policy, ckpt,
                                            epoch, nbatch)
                fit_step += 1
                if batch_end_callback is not None:
                    with step_ids, telemetry.span("callbacks"):
                        param = BatchEndParam(epoch=epoch, nbatch=nbatch,
                                              eval_metric=eval_metric,
                                              locals=locals())
                        for cb in _as_list(batch_end_callback):
                            cb(param)
                nbatch += 1
                # batch-boundary preemption point: the armed signal set
                # the flag; nbatch batches of this epoch are applied, so
                # (epoch, nbatch) resumes exactly here
                if ckpt is not None and ckpt.preempt_requested:
                    source = ckpt.preempt_requested
                    ckpt.save(self, epoch, nbatch)
                    telemetry.counter_inc("training.preempted")
                    telemetry.record_event("training.preempted",
                                           source=source, epoch=epoch,
                                           nbatch=nbatch)
                    from .. import flight as _flight
                    _flight.postmortem(
                        "training_preempted",
                        extra={"source": source, "epoch": epoch,
                               "nbatch": nbatch,
                               "prefix": ckpt.prefix})
                    raise TrainingPreempted(
                        "training preempted by %s at epoch %d batch %d; "
                        "checkpoint saved under %r — fit(resume=...) "
                        "continues from here" % (source, epoch, nbatch,
                                                 ckpt.prefix),
                        epoch=epoch, nbatch=nbatch, prefix=ckpt.prefix)

            for name, val in eval_metric.get_name_value():
                self.logger.info("Epoch[%d] Train-%s=%f", epoch, name, val)
            self.logger.info("Epoch[%d] Time cost=%.3f", epoch,
                             time.time() - tic)
            # the ops' summed counters come back from the device here, so
            # this waits for the epoch's last steps
            with telemetry.span("epoch_sync"):
                self._publish_aux_counters()

            # epoch-end host param sync ONLY at a callback boundary: the
            # executor already holds the canonical values, so the
            # reference's unconditional get_params→set_params round trip
            # (every parameter through the host, every epoch) buys
            # nothing without a consumer
            if epoch_end_callback is not None:
                with telemetry.span("epoch_sync"):
                    arg_p, aux_p = self.get_params()
                with telemetry.span("callbacks"):
                    for cb in _as_list(epoch_end_callback):
                        cb(epoch, self.symbol, arg_p, aux_p)
            if ckpt is not None:
                # epoch complete: resume point is the NEXT epoch's start
                ckpt.save(self, epoch + 1, 0)

            if eval_data is not None:
                res = self.score(eval_data, validation_metric,
                                 score_end_callback=eval_end_callback,
                                 batch_end_callback=eval_batch_end_callback,
                                 epoch=epoch)
                for name, val in res:
                    self.logger.info("Epoch[%d] Validation-%s=%f", epoch,
                                     name, val)
            train_data.reset()

    def _publish_aux_counters(self):
        """Telemetry counters that ops sum in auxiliary states on the
        device (``Executor.publish_aux_counters``); fetched here, after
        the metric's own fetch, once an epoch."""

    def _elastic_remesh(self, dead_ranks):
        """Adopt the surviving membership after a member loss. The base
        class has no mesh to rebuild; ``Module`` overrides with the
        real detach + re-mesh."""
        from .. import dist as _dist
        _dist.mark_member_lost(dead_ranks)

    def _elastic_recover(self, e, ckpt):
        """Handle a :class:`heartbeat.DeadWorkerError` raised by the
        pre-collective liveness gate: write the postmortem naming the
        dead rank(s) and the step they died on, re-mesh over the
        survivors, restore the last atomic checkpoint and return its
        meta (the resume point). Re-raises when there is no checkpoint
        to recover from — a member loss without a checkpoint is fatal
        by design (there is nothing consistent to resume)."""
        telemetry.counter_inc("elastic.dead_workers", len(e.ranks))
        telemetry.record_event("elastic.dead_worker",
                               dead=list(e.ranks), channel=e.channel,
                               generation=e.generation, epoch=e.epoch,
                               nbatch=e.nbatch,
                               timed_out=bool(e.timed_out))
        from .. import flight as _flight
        from .. import dist as _dist
        _flight.postmortem(
            "dead_worker", exc=e,
            extra={"dead_ranks": list(e.ranks),
                   "channel": e.channel,
                   "generation": e.generation,
                   "epoch": e.epoch, "nbatch": e.nbatch,
                   "timed_out": bool(e.timed_out),
                   "survivor_rank": _dist.rank(),
                   "live_ranks": [r for r in _dist.live_ranks()
                                  if r not in e.ranks],
                   # every reachable peer's newest dump from the shared
                   # flight dir — a dying rank banks a worker_abort on
                   # its way through dist.abort, so the cluster view
                   # shows the VICTIM's last seconds too, not just this
                   # survivor's keyhole
                   "peer_postmortems": _flight.gather_peer_postmortems()})
        from .. import log as _log
        logger = _log.get_logger("mxnet_tpu.module")
        if ckpt is None or ckpt.latest() is None:
            logger.error(
                "worker(s) %s died at epoch %s batch %s and no "
                "checkpoint manager (fit(checkpoint=...)) is armed — "
                "cannot re-mesh without a consistent state to resume "
                "from", list(e.ranks), e.epoch, e.nbatch)
            raise e
        logger.warning(
            "worker(s) %s died at epoch %s batch %s — re-meshing over "
            "the survivors and resuming from the last checkpoint",
            list(e.ranks), e.epoch, e.nbatch)
        self._elastic_remesh(e.ranks)
        meta = ckpt.restore(self)
        telemetry.counter_inc("elastic.resumed")
        telemetry.record_event("elastic.resumed",
                               epoch=int(meta["epoch"]),
                               nbatch=int(meta.get("nbatch", 0)))
        return meta

    def finite_check(self):
        """The divergence sentinel's predicate: True when the last
        step's values are all finite. Base implementation folds the
        OUTPUT heads on the host; ``Module`` overrides with a
        device-side fold that also covers every parameter (a NaN
        gradient poisons the params on the very step it appears, so
        the fold catches it at the next check)."""
        for o in self.get_outputs():
            a = o.asnumpy()
            if np.issubdtype(a.dtype, np.floating) \
                    and not np.isfinite(a).all():
                return False
        return True

    def _handle_divergence(self, policy, ckpt, epoch, nbatch):
        """Apply the divergence policy after ``finite_check()`` failed:
        count it, then skip / rollback / halt."""
        from ..checkpoint import DivergenceError
        telemetry.counter_inc("divergence.detected")
        telemetry.record_event("divergence.detected", epoch=epoch,
                               nbatch=nbatch, policy=policy)
        where = "epoch %d batch %d" % (epoch, nbatch)
        from .. import log as _log
        logger = _log.get_logger("mxnet_tpu.module")
        if policy == "skip":
            telemetry.counter_inc("divergence.skipped")
            logger.warning(
                "divergence sentinel: non-finite loss/params at %s — "
                "policy=skip, continuing (the next finite batches may "
                "recover, or may not: consider policy=rollback)", where)
            return
        if policy == "rollback":
            if ckpt is not None and ckpt.latest() is not None:
                meta = ckpt.restore(self)
                telemetry.counter_inc("divergence.rollback")
                logger.warning(
                    "divergence sentinel: non-finite loss/params at %s "
                    "— rolled back to checkpoint epoch=%d nbatch=%d",
                    where, meta["epoch"], meta.get("nbatch", 0))
                return
            logger.warning(
                "divergence sentinel: policy=rollback but no checkpoint "
                "to roll back to — halting")
        err = DivergenceError(
            "divergence sentinel: non-finite loss/params at %s "
            "(policy=%s)" % (where, policy))
        from .. import flight as _flight
        _flight.postmortem("divergence", exc=err,
                           extra={"epoch": epoch, "nbatch": nbatch,
                                  "policy": policy})
        raise err

    def prepare(self, data_batch, sparse_row_id_fn=None):
        pass

    def install_monitor(self, mon):
        pass

    # -- params ------------------------------------------------------------
    def iter_predict(self, eval_data, num_batch=None, reset=True):
        """Iterate over (outputs, batch_index, batch) during prediction
        (parity: base_module.iter_predict)."""
        assert self.binded and self.params_initialized
        if reset:
            eval_data.reset()
        for nbatch, eval_batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            self.forward(eval_batch, is_train=False)
            pad = eval_batch.pad
            outputs = [out[0:out.shape[0] - (pad or 0)]
                       for out in self.get_outputs()]
            yield outputs, nbatch, eval_batch

    def get_states(self, merge_multi_context=True):
        """States of stateful modules — none here (parity:
        base_module.get_states; mirrors the reference default)."""
        assert self.binded and self.params_initialized
        return []

    def set_states(self, states=None, value=None):
        """(parity: base_module.set_states — no-op for stateless)"""
        assert self.binded and self.params_initialized
        assert not states and not value

    def get_input_grads(self, merge_multi_context=True):
        """Gradients w.r.t. the input data (parity:
        base_module.get_input_grads)."""
        raise NotImplementedError()

    def get_params(self):
        raise NotImplementedError

    def set_params(self, arg_params, aux_params, allow_missing=False,
                   force_init=True, allow_extra=False):
        self.init_params(initializer=None, arg_params=arg_params,
                         aux_params=aux_params, allow_missing=allow_missing,
                         force_init=force_init, allow_extra=allow_extra)

    def save_params(self, fname):
        from ..ndarray import save
        arg_params, aux_params = self.get_params()
        save_dict = {("arg:%s" % k): v for k, v in arg_params.items()}
        save_dict.update({("aux:%s" % k): v for k, v in aux_params.items()})
        save(fname, save_dict)

    def load_params(self, fname):
        from ..ndarray import load
        save_dict = load(fname)
        arg_params, aux_params = {}, {}
        for k, value in save_dict.items():
            arg_type, name = k.split(":", 1)
            if arg_type == "arg":
                arg_params[name] = value
            elif arg_type == "aux":
                aux_params[name] = value
            else:
                raise MXNetError("Invalid param file: " + fname)
        self.set_params(arg_params, aux_params)

    @property
    def symbol(self):
        return self._symbol

    # introspection defaults
    @property
    def data_names(self):
        raise NotImplementedError

    @property
    def output_names(self):
        raise NotImplementedError

    @property
    def data_shapes(self):
        raise NotImplementedError

    @property
    def label_shapes(self):
        raise NotImplementedError

    @property
    def output_shapes(self):
        raise NotImplementedError


def _as_list(obj):
    if obj is None:
        return []
    if isinstance(obj, (list, tuple)):
        return list(obj)
    return [obj]
