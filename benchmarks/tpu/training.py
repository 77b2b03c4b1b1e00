"""What both training drivers share: build the trainer through
``repro.api.make_trainer``, hand it the benchmark's own weights, drive
it through its first three steps with the window's own call and feed,
time one ``fit`` call as the window, and compare those three steps with
the plain reference.

A driver subclass names the job (``job``), says how many labeled
targets a step trains (``targets_per_step``), warms any shape the window
will meet (``warm``), and gives the reference's inputs per step
(``reference_batches``).
"""
from __future__ import annotations

import gc
import time

import numpy as np

import bench
import check
import graphs

PROLOGUE = 3          # steps driven in set-up and followed by the reference


class TrainDriver:
    def __init__(self, cell: dict, seed: int, seconds: float):
        self.cell = cell
        self.config = cell["config_data"]
        self.traffic = cell["traffic_data"]
        self.model_cfg = self.config["model"]
        self.seconds = float(seconds)
        self.seed = int(seed)
        self.w_seed, self.view_seed = bench.sub_seeds(seed, 2)
        self.ref = bench.load_module(
            bench.HERE / "reference" / f"{self.model_cfg['model']}.py")

    # -- hooks ---------------------------------------------------------------

    def job(self, graph):
        raise NotImplementedError

    def targets_per_step(self) -> int:
        raise NotImplementedError

    def warm(self, n_steps: int) -> None:
        """Compile, outside the window, every shape the window's steps
        will meet that the prologue did not."""

    def reference_batches(self):
        raise NotImplementedError

    def trace_count(self) -> int:
        return int(self.trainer.trace_counts["train_step"])

    # -- set-up --------------------------------------------------------------

    def setup(self) -> None:
        import jax
        import repro.api as api
        self.g = graphs.make_graph(self.config)
        self.G = graphs.to_program_graph(self.g)
        self.trainer, self.views, *_ = api.make_trainer(self.job(self.G))
        key = jax.random.PRNGKey(self.w_seed)
        init = jax.jit(lambda k: self.ref.init(k, self.model_cfg,
                                               self.g["x"].shape[1]))
        params0 = init(key)
        self.p0 = jax.device_get(params0)
        self.trainer.reset(params=params0)
        b1 = 0.9
        # step 1 alone (its gradient is read from Adam's first moment),
        # then steps 2 and 3 in one call, as the window runs its steps
        self.losses, times = [], []
        for i, steps in enumerate((1, PROLOGUE - 1)):
            t = time.perf_counter()
            out = self.trainer.fit(self.views, steps=steps, eval_every=0,
                                   log_every=0)
            jax.block_until_ready(self.trainer.params)
            times.append((time.perf_counter() - t) / steps)
            self.losses.extend(float(v) for v in out["losses"])
            if i == 0:
                self.grad1 = jax.tree_util.tree_map(
                    lambda m: np.asarray(m) / (1 - b1),
                    jax.device_get(self.trainer.opt_state["m"]))
        self.p3 = jax.device_get(self.trainer.params)
        step_s = times[1]
        self.n_steps = max(1, int(round(self.seconds / step_s)))
        self.warm(self.n_steps)
        self.traces_before = self.trace_count()

    # -- the window ----------------------------------------------------------

    def window(self) -> None:
        import jax
        t0 = time.perf_counter()
        out = self.trainer.fit(self.views, steps=self.n_steps, eval_every=0,
                               log_every=0)
        jax.block_until_ready(self.trainer.params)
        self.window_s = time.perf_counter() - t0
        self.window_losses = out["losses"]
        if self.trace_count() != self.traces_before:
            raise bench.BenchError(
                f"the train step compiled inside the window "
                f"({self.traces_before} -> {self.trace_count()} traces)")

    def e2e(self) -> dict:
        return {"train_nodes_per_s":
                self.n_steps * self.targets_per_step() / self.window_s}

    def counts(self) -> tuple:
        bad = sum(1 for v in self.window_losses if not np.isfinite(v))
        return self.n_steps, bad

    def release(self) -> None:
        """Free the program's state before the reference runs (a view
        stream holds no device state and may stay)."""
        self.trainer = None
        gc.collect()

    # -- the comparison ------------------------------------------------------

    def reference_numbers(self, precision: str) -> dict:
        """The plain reference's three steps, at ``precision``."""
        import jax
        from reference.common import adam_init, adam_update
        tr = self.traffic
        params = jax.tree_util.tree_map(np.asarray, self.p0)
        state = adam_init(params)
        losses, extra, compiled = [], {}, {}
        for i, (loss_fn, args) in enumerate(self.reference_batches()):
            if loss_fn not in compiled:
                compiled[loss_fn] = jax.jit(jax.value_and_grad(
                    lambda p, *a, f=loss_fn: f(p, *a, precision)))
            loss, grads = compiled[loss_fn](params, *args)
            params, state, g_seen = adam_update(
                params, grads, state, tr["lr"], tr["weight_decay"])
            losses.append(float(loss))
            if i == 0:
                extra = {"grad1": jax.device_get(g_seen),
                         "raw_grad1": jax.device_get(grads)}
            if i + 1 == PROLOGUE:
                break
        return {"losses": losses, "p0": self.p0,
                "p3": jax.device_get(params), **extra}

    def program_numbers(self) -> dict:
        return {"losses": self.losses, "grad1": self.grad1, "p0": self.p0,
                "p3": self.p3}

    def compare(self, precision: str) -> dict:
        numbers = check.train_numbers(self.program_numbers(),
                                      self.reference_numbers(precision))
        numbers.update(self.sample_numbers())
        return numbers

    def sample_numbers(self) -> dict:
        return {}
