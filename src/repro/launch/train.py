"""Runnable trainers: (a) the GraphTheta GNN trainer (the paper's system),
(b) a transformer LM trainer over the arch zoo (reduced configs run on CPU;
full configs on a real pod with the same code path).

GNN:
    PYTHONPATH=src python -m repro.launch.train gnn --dataset reddit_like \
        --model gcn --strategy cluster --steps 200
LM:
    PYTHONPATH=src python -m repro.launch.train lm --arch qwen3-4b \
        --reduced --steps 100 --batch 8 --seq 128
"""
from __future__ import annotations

import argparse
import sys
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import GNNConfig, get_arch_config
from repro.utils import get_logger
from repro.utils.compile_cache import enable_compile_cache

log = get_logger("train")


# ---------------------------------------------------------------------------
# GNN trainer (single-host path; the distributed engine is exercised when
# multiple devices exist — tests use subprocesses with fake devices)
# ---------------------------------------------------------------------------


def train_gnn(dataset: str, model_name: str, strategy: str, steps: int,
              hidden: int = 64, lr: float = 1e-2, seed: int = 0,
              num_layers: int = 2, eval_every: int = 20,
              use_engine: Optional[int] = None,
              partition_method: str = "1d_src",
              prefetch_workers: Optional[int] = None,
              prefetch_mode: str = "thread",
              compact: bool = False, fault_policy=None,
              checkpoint_dir: Optional[str] = None,
              checkpoint_every: int = 0, resume: bool = False) -> dict:
    """Deprecated shim — construct a :class:`repro.api.TrainJob` and call
    :func:`repro.api.train` instead (same knobs, one typed surface; see
    the README migration table). Kept for the legacy kwargs + return
    dict; single-process runs now always go through the bucketed
    :class:`~repro.core.trainer.CompactTrainer` (dense views stage as
    one full-graph bucket, so the math is unchanged)."""
    import repro.api as api
    job = api.TrainJob(
        dataset=dataset, model=model_name, strategy=strategy, steps=steps,
        hidden=hidden, lr=lr, seed=seed, num_layers=num_layers,
        eval_every=eval_every, engine_partitions=use_engine or 0,
        partition_method=partition_method,
        prefetch_workers=prefetch_workers, prefetch_mode=prefetch_mode,
        compact=compact,
        fault_policy=fault_policy, checkpoint_dir=checkpoint_dir,
        checkpoint_every=checkpoint_every, resume=resume)
    return api.train(job, log=log.info).as_dict()


# ---------------------------------------------------------------------------
# LM trainer
# ---------------------------------------------------------------------------


def train_lm(arch: str, steps: int, batch: int, seq: int,
             reduced: bool = True, lr: float = 3e-4, seed: int = 0,
             log_every: int = 10, checkpoint_dir: Optional[str] = None,
             vocab_cap: int = 1024) -> dict:
    from repro.arch import build_model
    from repro.data import SyntheticLMDataset
    from repro.optim import adamw, warmup_cosine_schedule
    from repro.checkpoint import save_checkpoint
    import repro.arch.model as arch_model

    cfg = get_arch_config(arch)
    if reduced:
        cfg = cfg.reduced().replace(dtype="float32",
                                    vocab_size=min(cfg.reduced().vocab_size,
                                                   vocab_cap))
    arch_model.LOSS_CHUNK = min(arch_model.LOSS_CHUNK, seq)
    model = build_model(cfg, remat=not reduced)
    params = model.init(jax.random.PRNGKey(seed))
    opt = adamw(warmup_cosine_schedule(lr, max(10, steps // 20), steps))
    opt_state = opt.init(params)
    ds = SyntheticLMDataset(cfg.vocab_size, seq, batch, seed=seed)
    rng = np.random.default_rng(seed)

    def make_batch(i):
        b = ds.batch(i)
        out = {"tokens": jnp.asarray(b["tokens"]),
               "labels": jnp.asarray(b["labels"])}
        if cfg.embed_inputs:
            # stub frontend: embed via the table (frontends are stubs)
            out["embeds"] = params["embed"]["table"][out["tokens"]]
        if cfg.mrope:
            pos = np.broadcast_to(np.arange(seq)[None], (batch, seq))
            out["mrope_positions"] = jnp.asarray(
                np.stack([pos, pos, pos]), jnp.int32)
        if cfg.encoder_layers:
            out["enc_frames"] = jnp.asarray(rng.normal(
                size=(batch, cfg.encoder_seq, cfg.d_model)), jnp.float32)
        return out

    @jax.jit
    def step_fn(params, opt_state, batch_):
        loss, grads = jax.value_and_grad(model.loss)(params, batch_)
        params, opt_state = opt.update(grads, opt_state, params)
        return params, opt_state, loss

    history = []
    t0 = time.perf_counter()
    for i in range(steps):
        params, opt_state, loss = step_fn(params, opt_state, make_batch(i))
        if i % log_every == 0 or i == steps - 1:
            lv = float(loss)
            history.append({"step": i, "loss": lv})
            log.info("arch=%s step=%d loss=%.4f", arch, i, lv)
    wall = time.perf_counter() - t0
    if checkpoint_dir:
        save_checkpoint(checkpoint_dir, steps, {"params": params})
    return {"history": history, "wall_s": wall, "params": params,
            "final_loss": history[-1]["loss"], "model": model, "cfg": cfg}


def main(argv=None):
    ap = argparse.ArgumentParser()
    sub = ap.add_subparsers(dest="cmd", required=True)
    g = sub.add_parser("gnn")
    g.add_argument("--dataset", default="cora")
    g.add_argument("--model", default="gcn",
                   choices=["gcn", "sage", "gat", "gat_e"])
    g.add_argument("--strategy", default="global",
                   choices=["global", "mini", "cluster"])
    g.add_argument("--steps", type=int, default=100)
    g.add_argument("--hidden", type=int, default=64)
    g.add_argument("--layers", type=int, default=2)
    g.add_argument("--engine-partitions", type=int, default=0,
                   help="use the distributed engine with P partitions "
                        "(requires that many jax devices)")
    g.add_argument("--partition-method", default="1d_src",
                   choices=["1d_src", "1d_dst", "vertex_cut"])
    g.add_argument("--prefetch-workers", type=int, default=None,
                   help="view-builder threads for the engine path "
                        "(default: min(4, cores-1); deterministic for "
                        "any count)")
    g.add_argument("--prefetch-mode", default="thread",
                   choices=["thread", "process"],
                   help="view construction pool: in-process threads "
                        "(default) or supervised sampler processes over "
                        "shared memory (GIL-free builds, bit-identical "
                        "trajectory; degrades to threads with a warning "
                        "where shared memory is unavailable)")
    g.add_argument("--compact", action="store_true",
                   help="compact sampled-subgraph views (relabeled "
                        "local-id blocks, size-bucketed padding) for "
                        "mini/cluster; dense masks stay the parity oracle")
    ft = g.add_argument_group(
        "fault tolerance",
        "supervised training runtime (repro.runtime): retries with "
        "capped exponential backoff, divergence recovery, hardened "
        "checkpoints. Off by default (zero overhead); any flag here "
        "enables the runtime (single-process runs switch to the "
        "bucketed trainer, which handles dense views too).")
    ft.add_argument("--fault-retries", type=int, default=None,
                    metavar="N",
                    help="retry transient view-build / staging / step / "
                         "checkpoint failures up to N times (default "
                         "policy: 3)")
    ft.add_argument("--fault-backoff", type=float, default=None,
                    metavar="SECONDS",
                    help="base backoff before the first retry; grows "
                         "exponentially with deterministic jitter "
                         "(default 0.05s, capped at 2s)")
    ft.add_argument("--on-divergence", default=None,
                    choices=["raise", "skip_view", "rollback"],
                    help="reaction to a non-finite loss: raise (default),"
                         " skip_view (discard the poison update and move "
                         "on), or rollback (restore the last valid "
                         "checkpoint and continue past the poison view)")
    ft.add_argument("--check-finite", action="store_true",
                    help="sync and guard every step's loss (serializes "
                         "the step pipeline; implied by a non-raise "
                         "--on-divergence)")
    ft.add_argument("--step-timeout", type=float, default=None,
                    metavar="SECONDS",
                    help="watchdog: fail loudly if a step's loss is not "
                         "available within this many seconds")
    ft.add_argument("--checkpoint-dir", default=None,
                    help="directory for step_<N>.npz checkpoints "
                         "(atomic, checksummed; required by "
                         "--on-divergence rollback)")
    ft.add_argument("--checkpoint-every", type=int, default=0,
                    metavar="STEPS",
                    help="save a checkpoint every N steps (0 = never)")
    ft.add_argument("--resume", action="store_true",
                    help="resume from the newest VALID checkpoint in "
                         "--checkpoint-dir (corrupt files are skipped); "
                         "fresh start if none")
    ft.add_argument("--keep-checkpoints", type=int, default=0,
                    metavar="K",
                    help="retain only the newest K checkpoints "
                         "(0 = keep all)")
    lm = sub.add_parser("lm")
    lm.add_argument("--arch", required=True)
    lm.add_argument("--steps", type=int, default=50)
    lm.add_argument("--batch", type=int, default=8)
    lm.add_argument("--seq", type=int, default=128)
    lm.add_argument("--reduced", action="store_true", default=True)
    lm.add_argument("--checkpoint-dir", default=None)
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.cmd == "gnn":
        fault_policy = None
        ft_flags = (args.fault_retries, args.fault_backoff,
                    args.on_divergence, args.step_timeout)
        if args.check_finite or any(f is not None for f in ft_flags):
            from repro.runtime import FaultPolicy
            kw = {"check_finite": args.check_finite,
                  "keep_checkpoints": args.keep_checkpoints}
            if args.fault_retries is not None:
                kw["max_retries"] = args.fault_retries
            if args.fault_backoff is not None:
                kw["backoff_base"] = args.fault_backoff
            if args.on_divergence is not None:
                kw["on_divergence"] = args.on_divergence
            if args.step_timeout is not None:
                kw["timeouts"] = {"step": args.step_timeout}
            fault_policy = FaultPolicy(**kw)
        # SIGINT/SIGTERM during fit: raise in the main thread so fit's
        # finally drains the prefetch service (no orphaned sampler
        # processes), api.train saves a final checkpoint, and the CLI
        # exits nonzero (128 + signum, the shell convention)
        import signal
        from repro.runtime.faults import TrainingInterrupted

        def _interrupt(signum, frame):
            raise TrainingInterrupted(signum)

        previous = {s: signal.signal(s, _interrupt)
                    for s in (signal.SIGINT, signal.SIGTERM)}
        try:
            out = train_gnn(args.dataset, args.model, args.strategy,
                            args.steps,
                            hidden=args.hidden, num_layers=args.layers,
                            use_engine=args.engine_partitions or None,
                            partition_method=args.partition_method,
                            prefetch_workers=args.prefetch_workers,
                            prefetch_mode=args.prefetch_mode,
                            compact=args.compact,
                            fault_policy=fault_policy,
                            checkpoint_dir=args.checkpoint_dir,
                            checkpoint_every=args.checkpoint_every,
                            resume=args.resume)
        except TrainingInterrupted as e:
            where = (f"checkpoint saved to {args.checkpoint_dir}"
                     if args.checkpoint_dir else "no --checkpoint-dir, "
                     "progress discarded")
            print(f"interrupted by signal {e.signum} — {where}",
                  file=sys.stderr)
            return 128 + e.signum
        finally:
            for s, h in previous.items():
                signal.signal(s, h)
        print(f"final test acc: {out['final_acc']:.4f} "
              f"({out['wall_s']:.1f}s)")
    else:
        out = train_lm(args.arch, args.steps, args.batch, args.seq,
                       reduced=args.reduced,
                       checkpoint_dir=args.checkpoint_dir)
        print(f"final loss: {out['final_loss']:.4f} ({out['wall_s']:.1f}s)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
