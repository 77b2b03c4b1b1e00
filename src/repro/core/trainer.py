"""Compiled-once strategy Trainer with a host-side view prefetch pipeline.

The paper's training strategies (global-, mini-, cluster-batch, §2.3/§4.3)
all reduce to streams of :class:`GraphView` masks over one partitioned
graph, so a single jitted train step — whose shapes are fixed by the
:class:`PartitionPlan`, not by the view — serves every strategy. At scale
the bottleneck is not the device math but the host-side batch preparation
(DistDGL's observation); the Trainer attacks it on three fronts:

1. **Vectorized sharding** — views are mapped onto the plan with the
   ``np.take``-based :func:`repro.core.strategies.shard_view` (O(1) Python
   per step instead of a per-partition loop).
2. **Multi-stream prefetch** — for an indexable
   :class:`repro.core.views.ViewStream` (what ``strategy_views`` returns),
   a pool of ``prefetch_workers`` threads builds + shards + stages views
   ahead of the consumer, each worker owning a private
   :class:`~repro.core.views.ViewBuilder` (reused mask buffers). Because
   view i is a pure function of ``(seed, i)`` and the pool emits in index
   order, the loss trajectory is **bit-identical** for any worker count
   and for prefetch disabled — parallelism never costs reproducibility.
   Plain iterators fall back to the single-thread double-buffered
   pipeline.
3. **Compiled-once contract** — the jitted step donates its view buffers
   and carries a compile counter; :meth:`Trainer.assert_compiled_once`
   turns a silent retrace (a 10x regression in disguise) into a hard
   failure. CI asserts it across all three strategies
   (``benchmarks/strategies_bench.py --smoke``).

Periodic evaluation runs through the engine's (equally compiled-once)
distributed ``infer``; checkpoints go through
:mod:`repro.checkpoint.store` and restores resume mid-stream without
triggering a retrace.

**Fault tolerance** (:mod:`repro.runtime`): both trainers take a
``fault_policy`` (retry/backoff, per-stage timeouts, divergence action)
and an optional ``injector`` (deterministic chaos for tests). View
builds, device staging, step dispatch and checkpoint saves become
retryable units; prefetch workers are supervised (killed workers
respawn, their claimed view indices requeue, emit order is preserved);
``check_finite`` guards each step's loss and ``on_divergence`` picks
``raise | skip_view | rollback`` (rollback restores the last valid
checkpoint and continues past the poison view — no retrace, because the
restored leaves match the compiled step's signature).
``fit(..., resume=True)`` auto-resumes from the newest *valid*
checkpoint in ``checkpoint_dir``. Because every retried unit is a pure
function of its inputs, the loss trajectory under injected faults is
bit-identical to a fault-free run — the chaos contract
``tests/test_faults.py`` asserts.

Usage::

    engine = HybridParallelEngine(model, build_partitions(g, P))
    trainer = Trainer(engine, adam(1e-2), seed=0)
    trainer.fit(strategy_views(g, "cluster", K=2), steps=200,
                eval_every=50, eval_view=global_batch_view(g, 2))
    trainer.assert_compiled_once()
"""
from __future__ import annotations

import itertools
import math
import os
import threading
from typing import Any, Optional

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint import load_checkpoint, save_checkpoint
from repro.core.aggregate import backend_name
from repro.core.strategies import GraphView, shard_view
from repro.core.views import CompactBlockBuilder, ViewStream
from repro.kernels.backward import launch_tally
from repro.runtime.faults import (DivergenceError, FaultInjector,
                                  FaultPolicy, Retrier, sync_with_timeout)
from repro.runtime.prefetch import StreamPrefetcher, ViewPrefetcher
from repro.runtime.procpool import (ProcessViewService,
                                    ProcPoolUnavailable,
                                    warn_unavailable_once)
from repro.utils.timing import annotate, span

# the pipelines moved to repro.runtime.prefetch (where supervision
# lives); these aliases keep the long-standing private import paths of
# tests/benches working
_ViewPrefetcher = ViewPrefetcher
_MultiStreamPrefetcher = StreamPrefetcher

_END = object()      # the staged-view iterator ran out


class RetraceError(AssertionError):
    """The compiled-once contract was broken (or never exercised)."""


def _make_runtime(fault_policy: Optional[FaultPolicy],
                  injector: Optional[FaultInjector]) -> Optional[Retrier]:
    """A Retrier when any fault handling is configured, else None (the
    zero-overhead production default)."""
    if fault_policy is None and injector is None:
        return None
    return Retrier(fault_policy or FaultPolicy(), injector)


def _handle_divergence(tr, prev, loss_val: float,
                       checkpoint_dir: Optional[str],
                       events: list) -> None:
    """Apply ``tr.runtime.policy.on_divergence`` to a non-finite step.
    ``prev`` is the pre-step (params, opt_state, step_num) — the poison
    update is always discarded first (jax arrays are immutable, so the
    held refs ARE the pre-step state). Shared by both trainers; ``tr``
    needs params/opt_state/step_num/view_cursor/restore/_resume_cursor.
    """
    tr.params, tr.opt_state, tr.step_num = prev
    action = tr.runtime.policy.on_divergence
    events.append({"stage": "diverge", "step": prev[2] + 1,
                   "loss": loss_val, "action": action,
                   "view_cursor": tr.view_cursor})
    if action == "skip_view":
        return   # poison view consumed, update discarded — move on
    if action == "rollback":
        if checkpoint_dir:
            try:
                # load_checkpoint(None) already falls back past any
                # corrupt file to the newest valid step
                tr.restore(checkpoint_dir)
            except FileNotFoundError:
                # no checkpoint yet — fall through to the raise below
                pass  # lint: waive=src.silent-except
            else:
                # mid-fit: the stream already stands past the poison
                # view; the armed resume cursor must not rewind a
                # LATER fit to the checkpoint's older position
                tr._resume_cursor = None
                return
        raise DivergenceError(
            f"non-finite loss {loss_val} at step {prev[2] + 1} with "
            "on_divergence='rollback' but no valid checkpoint to "
            "roll back to (pass checkpoint_dir and checkpoint_every)")
    raise DivergenceError(
        f"non-finite loss {loss_val} at step {prev[2] + 1} "
        f"(view cursor {tr.view_cursor})")


def _assert_once_per_bucket(traces: int, touched: int, what: str) -> None:
    """The bucketed trace-count contract, shared by the train step
    (:meth:`CompactTrainer.assert_compiled_per_bucket`) and the serving
    infer steps (:class:`BucketedFn`): exactly one trace per touched
    bucket shape."""
    if touched == 0:
        raise RetraceError(
            f"{what} never ran — exercise it before asserting the "
            "once-per-bucket contract")
    if traces != touched:
        raise RetraceError(
            f"{what} was traced {traces} times over {touched} touched "
            f"bucket shapes (expected exactly one trace per bucket): "
            "an input was staged with a shape or plan geometry not "
            "determined by its bucket")


class BucketedFn:
    """One jitted ``fn(params, block)`` over bucket-padded compact blocks
    with once-per-bucket trace accounting — the infer-path extraction of
    :class:`CompactTrainer`'s train-step contract, which
    :mod:`repro.serving` programs against. ``jit``'s signature cache keys
    on leaf shapes (pure functions of the bucket), so the callable holds
    exactly one executable per touched ``(n_pad, e_pad)`` shape;
    :meth:`assert_compiled_per_bucket` certifies it."""

    def __init__(self, fn, name: str = "infer"):
        self.name = name
        self.traces = 0
        self.buckets_touched: set = set()

        def counted(params, block):
            # runs only while tracing: one increment per (bucket) compile
            self.traces += 1
            return fn(params, block)

        self.jitted = jax.jit(counted)

    def __call__(self, params, block):
        self.buckets_touched.add((int(block.x.shape[0]),
                                  int(block.src.shape[0])))
        return self.jitted(params, block)

    def assert_compiled_per_bucket(self) -> None:
        _assert_once_per_bucket(self.traces, len(self.buckets_touched),
                                f"{self.name} step")

    def jaxpr(self, params, block):
        """Jaxpr over ``block`` for :mod:`repro.analysis` rules; tracing
        runs the counted body, so the counters are saved/restored (the
        certificate must survive analysis)."""
        saved, saved_b = self.traces, set(self.buckets_touched)
        try:
            return jax.make_jaxpr(self.jitted)(params, block)
        finally:
            self.traces, self.buckets_touched = saved, saved_b


class BaseTrainer:
    """The shared trainer surface: one ``fit`` loop (prefetch pipelines,
    loss sync policy, divergence handling, eval/checkpoint cadence), plus
    ``save``/``restore``/``reset`` — everything that is identical between
    the partition-plan :class:`Trainer` and the bucketed
    :class:`CompactTrainer`. ``repro.runtime``, ``repro.serving`` and the
    :mod:`repro.api` facade program against this type instead of
    ``isinstance`` forks.

    Subclasses provide four hooks:

    - ``_init_params(seed)`` — fresh model params;
    - ``_make_prepare()`` — a ``view -> staged`` callable for one fit
      (prefetch workers call it concurrently);
    - ``_dispatch(staged)`` — one raw step call, returning
      ``(params, opt_state, loss)``;
    - ``assert_trace_contract()`` — the subclass's compile-count
      certificate (compiled-once vs once-per-bucket).
    """

    # subclasses set in __init__: opt, runtime, params, opt_state,
    # step_num, history, prefetch_depth, view_cursor, _resume_cursor

    def _init_common(self, opt, prefetch_depth: int,
                     fault_policy: Optional[FaultPolicy],
                     injector: Optional[FaultInjector]) -> None:
        self.opt = opt
        # fault-tolerance runtime: None = production fast path (no retry
        # wrappers, no per-step loss sync). The injector only ever fires
        # on host-side supervision points — traced code never sees it.
        self.runtime = _make_runtime(fault_policy, injector)
        self.step_num = 0
        self.history: list = []
        self.prefetch_depth = prefetch_depth
        # view-stream position (checkpointed so restore() can fast-forward
        # the stream itself instead of asking the caller to)
        self.view_cursor = 0
        self._resume_cursor: Optional[int] = None
        # backward Sum-stage launches by path, per compiled step (set
        # while the step traces; see bwd_launch_counts)
        self.bwd_launches: dict = {}

    # -- subclass hooks --------------------------------------------------------

    def _init_params(self, seed: int):
        raise NotImplementedError

    def _make_prepare(self):
        raise NotImplementedError

    def _dispatch(self, staged):
        raise NotImplementedError

    def _on_reset(self) -> None:
        """Subclass-specific reset extras (e.g. eval caches)."""

    def _place(self, tree):
        """Device placement of params / optimizer state before they enter
        the compiled step (identity unless the step pins a sharding)."""
        return tree

    def evaluate(self, view, mask: Optional[np.ndarray] = None) -> float:
        raise NotImplementedError

    def assert_trace_contract(self) -> None:
        raise NotImplementedError

    # -- the training loop ----------------------------------------------------

    def fit(self, views, steps: Optional[int] = None,
            prefetch: bool = True, prefetch_workers: Optional[int] = None,
            prefetch_mode: str = "thread",
            eval_every: int = 0, eval_view=None,
            eval_mask: Optional[np.ndarray] = None,
            checkpoint_every: int = 0,
            checkpoint_dir: Optional[str] = None,
            max_in_flight: int = 2,
            log_every: int = 0, log=print,
            resume: bool = False) -> dict:
        """Run ``steps`` views (all of ``views`` if None) through the
        compiled step. Returns ``{"losses", "evals", "steps", "events"}``;
        losses are synced once at the end so per-step host/device overlap
        is never serialized by a blocking ``float()``.

        ``resume=True`` restores the newest *valid* checkpoint in
        ``checkpoint_dir`` before training (fresh start if there is
        none) and fast-forwards a ViewStream to its recorded cursor.
        With a ``fault_policy`` whose ``check_finite`` is on (or whose
        ``on_divergence`` is not ``"raise"``), each step's loss is
        synced and guarded: a non-finite loss triggers the policy's
        divergence action — ``skip_view`` discards the poison update,
        ``rollback`` restores the last valid checkpoint and continues
        past the poison view (no retrace: restored leaves match the
        compiled signature). A ``step`` timeout in the policy arms a
        watchdog around the loss sync.

        When ``views`` is an indexable :class:`ViewStream` (what
        ``strategy_views`` returns) and ``prefetch`` is on, view
        construction fans out over ``prefetch_workers`` builder threads —
        deterministically: the loss trajectory is bit-identical for any
        worker count and for ``prefetch=False``, because view i only
        depends on ``(seed, i)`` and views are emitted in index order.
        The default (None) leaves one core for the device executor and
        caps at 4 — ``min(4, cpu_count - 1)`` — so builder threads never
        oversubscribe the box the step runs on. Plain iterators use the
        single-thread double-buffered pipeline.

        ``prefetch_mode`` picks the pool implementation for stream
        views: ``"thread"`` (default) is the in-process
        :class:`~repro.runtime.prefetch.StreamPrefetcher`;
        ``"process"`` fans view construction out to supervised sampler
        *processes* over shared-memory slots
        (:class:`~repro.runtime.procpool.ProcessViewService`) —
        GIL-free builds, same bit-identical trajectory. When shared
        memory is unavailable the process mode degrades to threads with
        a one-time warning; plain (non-stream) iterators always use the
        in-process pipeline (their builds are not pure in an index, so
        they cannot be farmed out).

        ``max_in_flight`` bounds the async-dispatch run-ahead: before
        dispatching step *i* the loop blocks on step *i - max_in_flight*,
        so at most that many steps' view/activation buffers are live at
        once — deep run-ahead piles up device memory and (on CPU) slows
        the executor more than the overlap buys.

        The call is one ``train.fit`` span, whose ``steps`` counts the
        steps it returns losses for, and whose ``bwd_vmem`` and
        ``bwd_dma`` are :meth:`bwd_launch_counts`.
        """
        with span("train.fit"):
            out = self._fit(views, steps, prefetch, prefetch_workers,
                            prefetch_mode, eval_every, eval_view, eval_mask,
                            checkpoint_every, checkpoint_dir, max_in_flight,
                            log_every, log, resume)
            annotate(steps=len(out["losses"]), **self.bwd_launch_counts())
            return out

    def bwd_launch_counts(self) -> dict:
        """The backward Sum-stage launches of the trainer's compiled
        steps by path (``kernels/backward.py``): ``bwd_vmem`` read a
        VMEM-resident node table, ``bwd_dma`` one row DMA per lane;
        summed over the executables (one a bucket for the bucketed
        trainer), as counted when each traced."""
        return {k: sum(c[k] for c in self.bwd_launches.values())
                for k in ("bwd_vmem", "bwd_dma")}

    def _fit(self, views, steps, prefetch, prefetch_workers, prefetch_mode,
             eval_every, eval_view, eval_mask, checkpoint_every,
             checkpoint_dir, max_in_flight, log_every, log,
             resume) -> dict:
        rt = self.runtime
        if resume and checkpoint_dir:
            from repro.checkpoint import latest_step
            if latest_step(checkpoint_dir) is not None:
                self.restore(checkpoint_dir)
        prepare = self._make_prepare()
        stream = views if isinstance(views, ViewStream) else None
        # any fit consumes a pending restore cursor — a plain-iterator fit
        # must not leave it armed to silently fast-forward a later,
        # unrelated stream
        resume_cur, self._resume_cursor = self._resume_cursor, None
        if stream is not None and resume_cur is not None \
                and stream.cursor < resume_cur:
            # a checkpoint restore recorded where the view stream stood —
            # fast-forward the stream itself (per-index RNG makes the
            # cursor the entire stream state)
            stream.seek(resume_cur)
        # non-prefetch paths run prepare inline; with a runtime it is
        # still a retryable view_build stage (the prefetchers wrap their
        # own build+prepare internally)
        prep = prepare if rt is None else (
            lambda v: rt("view_build", lambda: prepare(v)))
        if prefetch_mode not in ("thread", "process"):
            raise ValueError(
                f"prefetch_mode={prefetch_mode!r} — expected 'thread' "
                "or 'process'")
        if stream is not None:
            # indexable stream: the worker pool path (workers=1 is the
            # double-buffered pipeline with exact cursor accounting)
            if prefetch:
                if prefetch_workers is None:
                    prefetch_workers = max(
                        1, min(4, (os.cpu_count() or 2) - 1))
                staged_iter = None
                if prefetch_mode == "process":
                    try:
                        staged_iter = ProcessViewService(
                            stream, prepare, steps,
                            workers=prefetch_workers,
                            depth=self.prefetch_depth, runtime=rt)
                    except ProcPoolUnavailable as e:
                        warn_unavailable_once(str(e))
                if staged_iter is None:
                    staged_iter = _MultiStreamPrefetcher(
                        stream, prepare, steps, workers=prefetch_workers,
                        depth=self.prefetch_depth, runtime=rt)
            else:
                bounded = (itertools.islice(stream, steps)
                           if steps is not None else stream)
                staged_iter = (prep(v) for v in bounded)
        else:
            if steps is not None:
                views = itertools.islice(views, steps)
            staged_iter = (_ViewPrefetcher(views, prepare,
                                           self.prefetch_depth,
                                           runtime=rt)
                           if prefetch else (prep(v) for v in views))

        policy = rt.policy if rt is not None else None
        inj = rt.injector if rt is not None else None
        # the finite guard syncs every loss (serializes the pipeline) —
        # on only when asked for, or when a non-raise divergence action
        # implies it must observe the loss to act
        guard = policy is not None and (policy.check_finite
                                        or policy.on_divergence != "raise")
        watchdog = policy.timeout("step") if policy is not None else None
        sync_now = guard or watchdog is not None
        events = rt.events if rt is not None else []
        losses, pending, evals = [], [], []
        try:
            # idx counts views consumed THIS fit — monotonic even across
            # a rollback (which rewinds step_num), so a keyed "diverge"
            # injection fires exactly once per poison view. Every
            # pipeline yields at most ``steps`` views, so the loop stops
            # there without a last wait for the end of the stream
            staged_views = iter(staged_iter)
            for idx in itertools.count():
                if steps is not None and idx >= steps:
                    break
                with span("train.view_wait", view=idx):
                    staged = next(staged_views, _END)
                if staged is _END:
                    break
                if max_in_flight > 0 and len(pending) >= max_in_flight:
                    # backpressure: wait on the oldest in-flight step (one
                    # scalar readiness wait, not a pipeline-wide sync) and
                    # retire its loss to a host float so live device
                    # arrays stay O(max_in_flight), not O(steps)
                    with span("train.backpressure"):
                        losses.append(float(pending.pop(0)))
                # pre-step refs: jax arrays are immutable, so holding the
                # old (params, opt_state) costs nothing and is the whole
                # skip_view recovery
                prev = (self.params, self.opt_state, self.step_num)
                with span("train.dispatch"):
                    if rt is None:
                        self.params, self.opt_state, loss = \
                            self._dispatch(staged)
                    else:
                        # step dispatch is a retryable stage too: a
                        # transient failure re-dispatches the same
                        # (params, staged) — deterministic by construction
                        self.params, self.opt_state, loss = rt(
                            "step", lambda: self._dispatch(staged),
                            key=self.step_num)
                self.step_num += 1
                self.view_cursor = (stream.cursor if stream is not None
                                    else self.step_num)
                if sync_now:
                    loss_val = sync_with_timeout(
                        lambda: float(loss), watchdog)
                    if inj is not None and inj.fires(
                            "diverge", key=idx):
                        loss_val = float("nan")   # simulated divergence
                    if guard and not math.isfinite(loss_val):
                        self._diverged(prev, loss_val, checkpoint_dir,
                                       events)
                        continue
                    losses.append(loss_val)
                else:
                    pending.append(loss)
                if (eval_every and eval_view is not None
                        and self.step_num % eval_every == 0):
                    rec = {"step": self.step_num, "loss": float(loss),
                           "eval_acc": self.evaluate(eval_view, eval_mask)}
                    evals.append(rec)
                    if log_every:
                        log(f"step {rec['step']:5d}  "
                            f"loss {rec['loss']:.4f}  "
                            f"eval_acc {rec['eval_acc']:.4f}")
                if (checkpoint_every and checkpoint_dir
                        and self.step_num % checkpoint_every == 0):
                    self.save(checkpoint_dir)
        finally:
            if isinstance(staged_iter,
                          (_ViewPrefetcher, _MultiStreamPrefetcher,
                           ProcessViewService)):
                staged_iter.close()
            if isinstance(staged_iter, ProcessViewService) and rt is None:
                # with a runtime the service already appended its
                # supervision events into rt.events
                events.extend(staged_iter.events)
        losses.extend(float(l) for l in pending)
        self.history.extend(evals)
        return {"losses": losses, "evals": evals, "steps": self.step_num,
                "events": list(events)}

    def _diverged(self, prev, loss_val: float,
                  checkpoint_dir: Optional[str], events: list) -> None:
        _handle_divergence(self, prev, loss_val, checkpoint_dir, events)

    # -- checkpointing ---------------------------------------------------------

    def save(self, directory: str) -> str:
        # view_cursor is the entire state of a per-index ViewStream (the
        # RNG stream of view i is derived from (seed, i)), so storing it
        # lets restore() fast-forward the stream itself
        rt = self.runtime
        keep = rt.policy.keep_checkpoints if rt is not None else 0

        def do():
            return save_checkpoint(directory, self.step_num, {
                "params": self.params,
                "opt_state": self.opt_state,
                "step": np.asarray(self.step_num, np.int64),
                "view_cursor": np.asarray(self.view_cursor, np.int64),
            }, keep=keep)

        if rt is None:
            return do()
        # a failed save never poisons disk (atomic rename) — retry it.
        # Saves are sequential host calls, so the injector's occurrence
        # counter is already deterministic (no key needed)
        return rt("checkpoint_save", do)

    def restore(self, directory: str, step: Optional[int] = None) -> int:
        """Load params/opt state/step from a checkpoint. The restored
        leaves match the compiled step's signature (per bucket, for the
        bucketed trainer), so resuming does not retrace. If the
        checkpoint recorded a view-stream cursor, the next ``fit`` over a
        :class:`ViewStream` fast-forwards the stream to it automatically;
        for plain iterators the returned step lets the caller
        fast-forward by hand (legacy behavior)."""
        rt = self.runtime
        if rt is None:
            ck = load_checkpoint(directory, step)
        else:
            ck = rt("checkpoint_load",
                    lambda: load_checkpoint(directory, step))
        self.params = self._place(ck["params"])
        self.opt_state = self._place(ck["opt_state"])
        self.step_num = int(ck["step"])
        if "view_cursor" in ck:      # older checkpoints predate the key
            self.view_cursor = int(ck["view_cursor"])
            self._resume_cursor = self.view_cursor
        return self.step_num

    # -- lifecycle -------------------------------------------------------------

    def reset(self, params: Optional[Any] = None, seed: int = 0):
        """Fresh params/opt state **keeping the compiled step(s)**, so one
        compile serves many runs (strategy comparisons reset between
        strategies and still certify the trace contract)."""
        if params is None:
            params = self._init_params(seed)
        self.params = self._place(params)
        self.opt_state = self._place(self.opt.init(params))
        self.step_num = 0
        self.history = []
        self.view_cursor = 0
        self._resume_cursor = None
        self._on_reset()


class Trainer(BaseTrainer):
    """Drives any GraphView iterator through a :class:`HybridParallelEngine`
    with one shape-stable, compiled-once train step.

    The step's shapes are fixed by the partition plan — ``(P, K, n_m_pad)``
    node masks, ``(P, K, e_pad)`` edge masks — so global-, mini- and
    cluster-batch views all hit the same executable. View buffers are
    donated to XLA (every step stages a fresh view, so the device-side
    mask buffers are reused in place). ``trace_counts`` records how often
    the step (and the eval ``infer``) were actually traced.
    """

    def __init__(self, engine, opt, params: Optional[Any] = None,
                 seed: int = 0, prefetch_depth: int = 2,
                 fault_policy: Optional[FaultPolicy] = None,
                 injector: Optional[FaultInjector] = None):
        self.engine = engine
        self.plan = engine.plan
        self._init_common(opt, prefetch_depth, fault_policy, injector)
        if params is None:
            params = self._init_params(seed)
        self.params = self._place(params)
        self.opt_state = self._place(opt.init(params))
        self.trace_counts = {"train_step": 0, "infer": 0}

        lg = engine.make_loss_and_grad()

        def _step(params, opt_state, data, view):
            # runs only while tracing — this is the compile counter the
            # compiled-once contract is certified against
            self.trace_counts["train_step"] += 1
            with launch_tally() as launches:
                loss, grads = lg(params, data, view)
            self.bwd_launches["train_step"] = launches
            new_params, new_state = opt.update(grads, opt_state, params)
            return new_params, new_state, loss

        # view buffers are donated so XLA reuses the device-side mask
        # buffers in place step over step (donation is a no-op warning on
        # the CPU backend, so only ask for it where it exists)
        self._donate_views = jax.default_backend() != "cpu"
        donate = (3,) if self._donate_views else ()
        self._step = jax.jit(_step, donate_argnums=donate)
        self._infer = engine.make_infer(on_trace=self._count_infer_trace)
        # single-slot (view, staged-arrays) cache; holding the view object
        # itself both bounds the cache and keeps the identity check sound
        # (an id() key could be reused by a garbage-collected view)
        self._eval_cache: Optional[tuple] = None

    def _count_infer_trace(self):
        self.trace_counts["infer"] += 1

    # -- BaseTrainer hooks -----------------------------------------------------

    def _init_params(self, seed: int):
        return self.engine.model.init(jax.random.PRNGKey(seed),
                                      self.engine.sg.feature_dim)

    def _make_prepare(self):
        # shard staging retries transient device_put failures when a
        # runtime is configured (engine-side hook)
        rt = self.runtime

        def stage(v):
            with span("engine.shard"):
                arrays = shard_view(self.plan, v)
            with span("engine.stage"):
                annotate(staged_bytes=sum(a.nbytes for a in arrays.values()))
                return self.engine.stage_view(arrays, retry=rt)

        if self._donate_views:
            # donated buffers are consumed by the step — always restage
            return stage
        # static streams (global batch yields one GraphView object)
        # are staged exactly once and the device buffers reused; the
        # cache holds the view itself so the identity check can't be
        # fooled by a freed view's id being reused. Multiple prefetch
        # workers may race here: staged is written BEFORE the view key
        # and misses return their locally staged value, so a racing
        # reader can at worst duplicate work, never observe a
        # half-written entry
        cache = {"view": None, "staged": None}

        def prepare(v):
            if cache["view"] is v:
                return cache["staged"]
            staged = stage(v)
            cache["staged"] = staged
            cache["view"] = v
            return staged

        return prepare

    def _dispatch(self, staged):
        return self._step(self.params, self.opt_state,
                          self.engine._device_data, staged)

    def _on_reset(self) -> None:
        self._eval_cache = None

    def _place(self, tree):
        # replicated on the engine's mesh: the shard_map step returns
        # params and opt state with that sharding, so the first call must
        # see the same input types as every later one or jit traces twice
        return jax.device_put(tree, NamedSharding(self.engine.mesh, P()))

    def assert_trace_contract(self) -> None:
        self.assert_compiled_once()

    # -- eval / infer -----------------------------------------------------------

    def evaluate(self, view: GraphView,
                 mask: Optional[np.ndarray] = None) -> float:
        """Distributed inference over ``view`` (compiled once, shared with
        every later eval); accuracy on ``mask`` (default: the graph's test
        mask, falling back to the view's loss mask)."""
        if self._eval_cache is None or self._eval_cache[0] is not view:
            self._eval_cache = (view, shard_view(self.plan, view))
        logits = self._infer(self.params, dict(self._eval_cache[1]))
        preds = self.engine.gather_predictions(np.asarray(logits)).argmax(-1)
        g = view.graph
        if mask is None:
            mask = (g.test_mask if g.test_mask is not None
                    else view.loss_mask > 0)
        mask = np.asarray(mask) > 0
        if not mask.any():
            return 0.0
        return float((preds[mask] == g.labels[mask]).mean())

    # -- contracts ---------------------------------------------------------------

    def assert_compiled_once(self):
        """The trace-count contract: after any number of steps across any
        mix of strategies, the train step must have been traced exactly
        once (and the eval infer at most once). A retrace is a silent
        ~10x slowdown — fail loudly instead."""
        n = self.trace_counts["train_step"]
        if n == 0:
            raise RetraceError(
                "assert_compiled_once: the train step never ran — call "
                "fit() before asserting the contract")
        if n != 1:
            raise RetraceError(
                f"train step was traced {n} times (expected exactly 1): "
                "some input changed shape/dtype between steps — view "
                "arrays must come from shard_view over one PartitionPlan")
        if self.trace_counts["infer"] > 1:
            raise RetraceError(
                f"eval infer was traced {self.trace_counts['infer']} "
                "times (expected at most 1)")

    # -- static analysis hooks ---------------------------------------------------

    @property
    def expected_donated(self) -> int:
        """How many step invars must carry donation flags: the three view
        leaves (node_active/edge_active/loss_mask) on accelerator
        backends, none on cpu (where donation is a no-op warning)."""
        return 3 if self._donate_views else 0

    def traced_step_jaxpr(self, view: GraphView):
        """Jaxpr of the jitted train step over ``view`` — what
        ``repro.analysis`` rules walk. Tracing runs the step's Python
        body (the compile counter), so the counters are saved/restored:
        analysis must not break the compiled-once certificate."""
        staged = self.engine.stage_view(shard_view(self.plan, view))
        saved = dict(self.trace_counts), dict(self.bwd_launches)
        try:
            return jax.make_jaxpr(self._step)(
                self.params, self.opt_state, self.engine._device_data,
                staged)
        finally:
            self.trace_counts, self.bwd_launches = saved

    def traced_infer_jaxpr(self, view: GraphView):
        """Jaxpr of the jitted eval/infer computation over ``view``."""
        staged = self.engine.stage_view(shard_view(self.plan, view))
        saved = dict(self.trace_counts)
        try:
            return jax.make_jaxpr(self._infer.jitted)(
                self.params, self.engine._device_data, staged)
        finally:
            self.trace_counts = saved


class CompactTrainer(BaseTrainer):
    """Single-process trainer over size-bucketed compact blocks.

    Where :class:`Trainer` fixes the step's shapes with a PartitionPlan,
    this trainer fixes them with a :class:`~repro.core.views.BucketSpec`:
    every :class:`~repro.core.views.CompactView` is staged by a
    :class:`~repro.core.views.CompactBlockBuilder` into one of a small
    fixed menu of padded ``(n_pad, e_pad)`` shapes, so device compute and
    memory scale with the *view* while the jitted step still compiles at
    most once per bucket — the bucketed analog of the compiled-once
    contract, certified by :meth:`assert_compiled_per_bucket`.

    Dense GraphViews pass straight through (full-graph shape = its own
    bucket), so the same loop drives the dense parity oracle.
    """

    def __init__(self, model, g, opt, params: Optional[Any] = None,
                 seed: int = 0, buckets=None, slots: int = 2,
                 gcn_norm: bool = True, prefetch_depth: int = 2,
                 fault_policy: Optional[FaultPolicy] = None,
                 injector: Optional[FaultInjector] = None):
        from repro.core.mpgnn import accuracy_block, loss_block
        self.model = model
        self.g = g
        self._init_common(opt, prefetch_depth, fault_policy, injector)
        self.stager = CompactBlockBuilder(
            g, model.K, buckets=buckets, slots=slots, gcn_norm=gcn_norm,
            csc_plan=backend_name(model) == "csc")
        self.buckets = self.stager.buckets
        if params is None:
            params = self._init_params(seed)
        self.params = params
        self.opt_state = opt.init(params)
        self.trace_counts = {"train_step": 0}
        # (n_pad, e_pad) shapes actually staged — the denominator of the
        # once-per-bucket contract
        self.buckets_touched: set = set()
        # staging mutates per-bucket ring buffers; prefetch workers must
        # not interleave fills (device_put copies on every backend we run,
        # so the staged block is detached before the lock releases)
        self._stage_lock = threading.Lock()

        def _step(params, opt_state, block):
            # runs only while tracing: one increment per (bucket) compile
            self.trace_counts["train_step"] += 1
            with launch_tally() as launches:
                loss, grads = jax.value_and_grad(
                    lambda p: loss_block(model, p, block))(params)
            self.bwd_launches[(block.x.shape[0], block.src.shape[0])] = \
                launches
            new_params, new_state = opt.update(grads, opt_state, params)
            return new_params, new_state, loss

        # jit's signature cache keys on leaf shapes + the plan's static
        # geometry — both pure functions of the bucket, so this single
        # jitted callable holds exactly one executable per touched bucket
        self._step = jax.jit(_step)
        self._acc = jax.jit(
            lambda params, block, mask: accuracy_block(model, params,
                                                       block, mask))

    def _prepare(self, view):
        with self._stage_lock:
            block = self.stager.stage(view)
            self.buckets_touched.add((int(block.x.shape[0]),
                                      int(block.src.shape[0])))
            # the staged block aliases the builder's ring buffers (and a
            # dense view's masks alias its ViewBuilder's ring). Handing
            # those to jax directly is unsafe: the CPU backend ZERO-COPIES
            # sufficiently aligned numpy arrays, and even an explicit
            # jax-side copy materializes asynchronously — either way a
            # later fill of the same ring slot races an in-flight step's
            # input. A numpy copy is synchronous by construction, so the
            # block is detached before the lock releases.
            return jax.tree_util.tree_map(np.array, block)

    # -- BaseTrainer hooks -----------------------------------------------------

    def _init_params(self, seed: int):
        return self.model.init(jax.random.PRNGKey(seed),
                               self.g.node_features.shape[1])

    def _make_prepare(self):
        return self._prepare

    def _dispatch(self, staged):
        return self._step(self.params, self.opt_state, staged)

    def assert_trace_contract(self) -> None:
        self.assert_compiled_per_bucket()

    # -- eval -------------------------------------------------------------------

    def evaluate(self, view, mask: Optional[np.ndarray] = None) -> float:
        """Accuracy over ``view``'s block (a dense GraphView stages the
        cached base block; a CompactView a tight-padded one-off)."""
        block = view.as_block(gcn_norm=self.stager.gcn_norm,
                              csc_plan=self.stager.csc_plan)
        if mask is None:
            g = view.graph
            mask = (g.test_mask if g.test_mask is not None else None)
        if mask is not None:
            flat = np.asarray(mask).astype(np.float32)
            if hasattr(view, "nodes"):   # CompactView: global -> local ids
                flat = flat[view.nodes]
            m = np.zeros(block.x.shape[0], np.float32)
            m[:len(flat)] = flat
        else:
            m = block.loss_mask
        return float(self._acc(self.params, block, m))

    # -- contracts ---------------------------------------------------------------

    def assert_compiled_per_bucket(self):
        """The bucketed trace-count contract: the step must have been
        traced exactly once per *touched* bucket shape — repeat epochs
        over the same buckets add zero traces."""
        _assert_once_per_bucket(self.trace_counts["train_step"],
                                len(self.buckets_touched), "train step")

    # -- static analysis hooks ---------------------------------------------------

    def traced_step_jaxpr(self, view):
        """Jaxpr of the bucketed step over ``view``'s staged block — what
        the O(view) compact-step rules walk. Staging and tracing both
        perturb the contract counters (buckets_touched / trace_counts),
        so they are saved and restored: analysis must not change the
        once-per-bucket certificate."""
        saved_counts = dict(self.trace_counts)
        saved_buckets = set(self.buckets_touched)
        saved_launches = dict(self.bwd_launches)
        try:
            block = self._prepare(view)
            return jax.make_jaxpr(self._step)(
                self.params, self.opt_state, block)
        finally:
            self.trace_counts = saved_counts
            self.buckets_touched = saved_buckets
            self.bwd_launches = saved_launches
