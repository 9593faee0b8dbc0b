"""BaseModule — the symbolic training-loop interface.

Reference: python/mxnet/module/base_module.py (BaseModule :?, fit :409 —
epoch loop of forward_backward :193 / update / metrics / checkpoints).
The TPU build keeps the exact interface; the compute underneath is the
jit-compiled Executor (executor.py) instead of GraphExecutor.
"""
from __future__ import annotations

import logging
import time

import numpy as _np

from ..base import MXNetError, unpad_outputs
from .. import env as _env
from .. import metric as metric_mod
from .. import io as io_mod
from .. import ndarray as nd


def _as_metric(m):
    if isinstance(m, metric_mod.EvalMetric):
        return m
    return metric_mod.create(m)


def _parse_data(data, data_names, label_names):
    if isinstance(data, io_mod.DataIter):
        return data
    raise MXNetError("expected a DataIter, got %r" % (type(data),))


class BaseModule(object):
    def __init__(self, logger=logging):
        self.logger = logger
        self.binded = False
        self.for_training = False
        self.params_initialized = False
        self.optimizer_initialized = False
        self.inputs_need_grad = False
        self._symbol = None

    # -- abstract interface (Module implements) ----------------------------
    def forward(self, data_batch, is_train=None):
        raise NotImplementedError()

    def backward(self, out_grads=None):
        raise NotImplementedError()

    def update(self):
        raise NotImplementedError()

    def get_outputs(self, merge_multi_context=True):
        raise NotImplementedError()

    def update_metric(self, eval_metric, labels):
        raise NotImplementedError()

    def bind(self, *args, **kwargs):
        raise NotImplementedError()

    def init_params(self, *args, **kwargs):
        raise NotImplementedError()

    # -- composite ops -----------------------------------------------------
    def forward_backward(self, data_batch):
        """reference: base_module.py:193."""
        self.forward(data_batch, is_train=True)
        self.backward()

    def supports_fused_step(self):
        """Whether fit() may replace forward_backward()+update() with one
        fused compiled step (Module overrides; everything else stays on
        the op-by-op composite path)."""
        return False

    def fused_step(self, data_batch):
        raise NotImplementedError()

    def score(self, eval_data, eval_metric, num_batch=None,
              batch_end_callback=None, score_end_callback=None, reset=True,
              epoch=0, sparse_row_id_fn=None):
        """reference: base_module.py score."""
        assert self.binded and self.params_initialized
        eval_metric = _as_metric(eval_metric)
        eval_metric.reset()
        if reset:
            eval_data.reset()
        for nbatch, eval_batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            self.forward(eval_batch, is_train=False)
            self.update_metric(eval_metric, eval_batch.label)
            if batch_end_callback is not None:
                for cb in _as_list(batch_end_callback):
                    cb(BatchEndParam(epoch=epoch, nbatch=nbatch,
                                     eval_metric=eval_metric, locals=locals()))
        if score_end_callback is not None:
            for cb in _as_list(score_end_callback):
                cb(BatchEndParam(epoch=epoch, nbatch=nbatch,
                                 eval_metric=eval_metric, locals=locals()))
        return eval_metric.get_name_value()

    def predict(self, eval_data, num_batch=None, merge_batches=True,
                reset=True, always_output_list=False,
                sparse_row_id_fn=None):
        """reference: base_module.py predict."""
        assert self.binded and self.params_initialized
        if reset:
            eval_data.reset()
        output_list = []
        for nbatch, eval_batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            self.forward(eval_batch, is_train=False)
            pad = getattr(eval_batch, "pad", 0) or 0
            outs = unpad_outputs(self.get_outputs(), pad, copy=True)
            output_list.append(outs)
        if not output_list:
            return output_list
        if merge_batches:
            num_outputs = len(output_list[0])
            for o in output_list:
                if len(o) != num_outputs:
                    raise MXNetError("cannot merge batches with different "
                                     "numbers of outputs")
            merged = [nd.concatenate([o[i] for o in output_list])
                      for i in range(num_outputs)]
            if num_outputs == 1 and not always_output_list:
                return merged[0]
            return merged
        return output_list

    def iter_predict(self, eval_data, num_batch=None, reset=True):
        assert self.binded and self.params_initialized
        if reset:
            eval_data.reset()
        for nbatch, eval_batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            self.forward(eval_batch, is_train=False)
            pad = getattr(eval_batch, "pad", 0) or 0
            outs = unpad_outputs(self.get_outputs(), pad)
            yield outs, nbatch, eval_batch

    def fit(self, train_data, eval_data=None, eval_metric="acc",
            epoch_end_callback=None, batch_end_callback=None, kvstore="local",
            optimizer="sgd", optimizer_params=(("learning_rate", 0.01),),
            eval_end_callback=None, eval_batch_end_callback=None,
            initializer=None, arg_params=None, aux_params=None,
            allow_missing=False, force_rebind=False, force_init=False,
            begin_epoch=0, num_epoch=None, validation_metric=None,
            monitor=None, sparse_row_id_fn=None,
            checkpoint_dir=None, checkpoint_period=1, resume=None):
        """The canonical symbolic training loop (reference:
        base_module.py:409; call stack SURVEY §3.1).

        Fault tolerance (beyond the reference — docs/fault_tolerance.md):
        `checkpoint_dir` enables crash-consistent end-of-epoch checkpoints
        (params + optimizer states, atomic rename, keep-last-N) every
        `checkpoint_period` epochs via parallel.resilience.CheckpointManager;
        `resume='auto'` restores the newest COMPLETE checkpoint from that
        directory — params, optimizer states, RNG chain and epoch cursor —
        so a restarted generation (tools/launch.py --max-restarts) continues
        training instead of starting from epoch 0. `resume=<int>` pins an
        epoch explicitly (raises MXNetError if that step is corrupt)."""
        assert num_epoch is not None, "please specify number of epochs"
        from ..initializer import Uniform

        initializer = initializer or Uniform(0.01)

        mgr = None
        if checkpoint_dir is not None:
            from ..parallel.resilience import CheckpointManager

            mgr = CheckpointManager(checkpoint_dir)
        elif resume is not None:
            raise MXNetError("fit(resume=...) needs checkpoint_dir=")

        self.bind(data_shapes=train_data.provide_data,
                  label_shapes=train_data.provide_label,
                  for_training=True, force_rebind=force_rebind)
        if monitor is not None:
            self.install_monitor(monitor)
        self.init_params(initializer=initializer, arg_params=arg_params,
                         aux_params=aux_params, allow_missing=allow_missing,
                         force_init=force_init)
        self.init_optimizer(kvstore=kvstore, optimizer=optimizer,
                            optimizer_params=optimizer_params)
        resume_skip = 0
        data_restored = False
        if mgr is not None and resume is not None:
            header = mgr.restore(
                load_params=self.load_params,
                load_states=self.load_optimizer_states,
                step=None if resume == "auto" else int(resume))
            # restore() returns None only for resume='auto' with no complete
            # checkpoint (fresh start); an explicit epoch that is missing or
            # corrupt raises its own MXNetError inside restore()
            if header is not None:
                begin_epoch = int(header["meta"].get(
                    "epoch", header["step"])) + 1
                # a preemption checkpoint lands MID-epoch: its weights
                # already include the first `batches_done` updates of the
                # interrupted epoch, so the resumed epoch fast-forwards
                # the iterator past them instead of re-applying them
                resume_skip = int(header["meta"].get("batches_done", 0))
                # a checkpointable iterator (mxnet_tpu.data StreamDataIter
                # and friends) restores its exact mid-epoch cursor instead
                # of blind fast-forwarding: set_state() arms a one-shot
                # reset skip so the epoch-top reset below keeps it
                data_state = header["meta"].get("data_state")
                if data_state is not None and \
                        hasattr(train_data, "set_state"):
                    train_data.set_state(data_state)
                    data_restored = True
                self.logger.info(
                    "resumed from checkpoint step %d (%s); continuing at "
                    "epoch %d%s%s", header["step"], mgr.directory,
                    begin_epoch,
                    " batch %d" % resume_skip if resume_skip else "",
                    " (exact data cursor)" if data_restored else "")
        if validation_metric is None:
            validation_metric = eval_metric
        eval_metric = _as_metric(eval_metric)

        from ..parallel import resilience
        from ..parallel.resilience import maybe_inject_fault
        from .. import telemetry

        # Graceful preemption (docs/fault_tolerance.md): once checkpoints
        # are configured, SIGTERM stops killing the process mid-step —
        # the handler just raises a flag, the in-flight step finishes,
        # and the step-boundary check below lands an emergency checkpoint
        # inside MXTPU_PREEMPT_GRACE_S before exiting with the
        # preemption rc (a free restart under tools/launch.py).
        if mgr is not None:
            resilience.install_preemption_handler()

        # input-pipeline starvation metrics: seconds spent WAITING on the
        # data iterator vs. seconds spent in forward/backward/update — the
        # first thing to read when a run is slow (is it the loader or the
        # chip?)
        tm_wait = telemetry.counter("mxtpu_data_wait_seconds_total",
                                    {"src": "fit"})
        tm_compute = telemetry.counter("mxtpu_data_compute_seconds_total",
                                       {"src": "fit"})

        # MXTPU_SHARDED_STEP: run forward+backward+update as ONE compiled
        # donated executable per step (module doc: docs/sharded_training.md).
        # A monitor needs per-op intermediate outputs, so it forces the
        # op-by-op composite path.
        use_fused = (monitor is None and _env.get("MXTPU_SHARDED_STEP")
                     and self.supports_fused_step())

        # MXTPU_DATA_PREFETCH: overlap batch N+1's host decode + async
        # host->device copy with batch N's compute (docs/data_pipeline.md).
        # The fused path places with the trainer's mesh so step_batch
        # consumes already-sharded arrays (executor._place_inputs no-ops).
        use_prefetch = _env.get("MXTPU_DATA_PREFETCH")

        fit_updates = 0
        for epoch in range(begin_epoch, num_epoch):
            tic = time.time()
            eval_metric.reset()
            nbatch = 0
            train_data.reset()
            batch_iter = iter(train_data)
            if epoch == begin_epoch and resume_skip:
                if data_restored:
                    # the restored cursor already sits past these batches;
                    # only the batch numbering needs to catch up
                    nbatch = resume_skip
                else:
                    for _ in range(resume_skip):
                        try:
                            next(batch_iter)
                        except StopIteration:
                            break
                        nbatch += 1
            prefetcher = None
            if use_prefetch:
                from ..data import DevicePrefetcher

                batch_iter = prefetcher = DevicePrefetcher(
                    batch_iter, mesh=getattr(self, "_mesh", None),
                    src="fit")
            while True:
                t_wait = time.perf_counter()
                try:
                    data_batch = next(batch_iter)
                except StopIteration:
                    break
                t_step = time.perf_counter()
                tm_wait.inc(t_step - t_wait)
                # goodput bracket opens back-dated to t_wait (the iterator
                # wait belongs to the step) but only after a successful
                # next() — StopIteration must not leave a dangling bracket
                telemetry.goodput.step_start(kind="fit", t0=t_wait,
                                             step=fit_updates + 1)
                telemetry.goodput.add("data_wait", t_step - t_wait)
                if monitor is not None:
                    monitor.tic()
                # distributed tracing: one root span per fit step; the
                # data wait predates the root, so it is emitted
                # retroactively as a child with measured times
                with telemetry.tracing.root(
                        "train.step", component="train",
                        attrs={"step": fit_updates + 1,
                               "kind": "fit"}) as t_span:
                    telemetry.tracing.emit_span(
                        "train.data_wait",
                        time.time() - (t_step - t_wait), t_step - t_wait,
                        t_span, component="train")
                    telemetry.goodput.mark_launch()
                    if use_fused:
                        with telemetry.tracing.span("train.fused_step"), \
                                telemetry.goodput.phase("compute"):
                            self.fused_step(data_batch)
                    else:
                        with telemetry.tracing.span("train.fwd_bwd"), \
                                telemetry.goodput.phase("compute"):
                            self.forward_backward(data_batch)
                        with telemetry.tracing.span("train.optimizer"), \
                                telemetry.goodput.phase("compute"):
                            self.update()
                    fit_updates += 1
                    examples = None
                    try:
                        examples = int(data_batch.data[0].shape[0])
                    except (AttributeError, IndexError, TypeError):
                        pass
                    telemetry.observe_step(time.perf_counter() - t_step,
                                           examples=examples,
                                           step=fit_updates, kind="fit")
                    telemetry.goodput.step_end(step=fit_updates)
                # step-boundary fault hook: counts updates since THIS
                # process started (no-op unless MXTPU_FAULT_INJECT is set)
                maybe_inject_fault(fit_updates)
                if mgr is not None and resilience.preemption_requested():
                    if prefetcher is not None:
                        # freeze the pipeline first: producer threads are
                        # joined and the delivered-batch cursor is final
                        # before it lands in the checkpoint meta
                        prefetcher.close()

                    def _emergency_save(_epoch=epoch, _done=nbatch + 1,
                                        _cursor=prefetcher or train_data):
                        arg_p, aux_p = self.get_params()
                        self.set_params(arg_p, aux_p)  # sync exec copies
                        # meta epoch = _epoch - 1 + batches_done: resume
                        # re-enters the interrupted epoch but fast-forwards
                        # past the batches whose updates these weights
                        # already carry (exact resume-equivalence)
                        meta = {"epoch": _epoch - 1, "preempt": True,
                                "batches_done": _done}
                        if hasattr(_cursor, "state"):
                            try:
                                # exact mid-epoch cursor: resume restores
                                # it via set_state instead of blind
                                # fast-forwarding (data/sharded_stream.py)
                                meta["data_state"] = _cursor.state()
                            except MXNetError:
                                pass  # inner iterator has no cursor
                        mgr.save(_epoch, save_params=self.save_params,
                                 save_states=self.save_optimizer_states,
                                 meta=meta)
                    resilience.maybe_preempt_exit(
                        emergency_save=_emergency_save)
                self.update_metric(eval_metric, data_batch.label)
                if monitor is not None:
                    monitor.toc_print()
                if batch_end_callback is not None:
                    for cb in _as_list(batch_end_callback):
                        cb(BatchEndParam(epoch=epoch, nbatch=nbatch,
                                         eval_metric=eval_metric,
                                         locals=locals()))
                nbatch += 1
                tm_compute.inc(time.perf_counter() - t_step)

            if prefetcher is not None:
                prefetcher.close()  # join the producer between epochs
            for name, val in eval_metric.get_name_value():
                self.logger.info("Epoch[%d] Train-%s=%f", epoch, name, val)
            self.logger.info("Epoch[%d] Time cost=%.3f", epoch,
                             time.time() - tic)

            arg_p, aux_p = self.get_params()
            self.set_params(arg_p, aux_p)  # sync exec copies

            if mgr is not None and (epoch + 1) % checkpoint_period == 0:
                mgr.save(epoch, save_params=self.save_params,
                         save_states=self.save_optimizer_states,
                         meta={"epoch": epoch})

            if epoch_end_callback is not None:
                for cb in _as_list(epoch_end_callback):
                    cb(epoch, self.symbol, arg_p, aux_p)

            if eval_data is not None:
                res = self.score(eval_data, validation_metric,
                                 score_end_callback=eval_end_callback,
                                 batch_end_callback=eval_batch_end_callback,
                                 epoch=epoch)
                for name, val in res:
                    self.logger.info("Epoch[%d] Validation-%s=%f", epoch,
                                     name, val)

    # -- misc --------------------------------------------------------------
    @property
    def symbol(self):
        return self._symbol

    def get_params(self):
        raise NotImplementedError()

    def set_params(self, arg_params, aux_params, allow_missing=False,
                   force_init=True, allow_extra=False):
        self.init_params(initializer=None, arg_params=arg_params,
                         aux_params=aux_params, allow_missing=allow_missing,
                         force_init=force_init, allow_extra=allow_extra)

    def install_monitor(self, mon):
        raise NotImplementedError()

    def get_input_grads(self, merge_multi_context=True):
        raise NotImplementedError()


class BatchEndParam(object):
    """reference: callback BatchEndParam namedtuple."""

    def __init__(self, epoch, nbatch, eval_metric, locals=None):
        self.epoch = epoch
        self.nbatch = nbatch
        self.eval_metric = eval_metric
        self.locals = locals


def _as_list(obj):
    if obj is None:
        return []
    if isinstance(obj, (list, tuple)):
        return list(obj)
    return [obj]
