"""Process-pool sampler service (PR 10): supervision and recovery.

The contract under test extends PR 8's to real OS processes: a view is
pure in ``(seed, i)``, so a sampler process SIGKILLed mid-build, hung
without heartbeats, or handing back a corrupted shared-memory slot must
all recover into a loss trajectory **bit-identical** to the fault-free
(and to the thread-mode) run — and a clean ``close()`` must leave zero
child processes behind.
"""
import multiprocessing
import os
import signal
import subprocess
import sys
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import GNNConfig
from repro.core.strategies import strategy_views
from repro.core.trainer import CompactTrainer
from repro.core.views import MiniBatchViewStream
from repro.graph import sbm_graph
from repro.models import make_gnn
from repro.optim import adam
from repro.runtime import (FaultInjector, FaultPolicy,
                           FaultRetriesExceeded, ProcessViewService,
                           StreamPrefetcher, shared_memory_available)
from repro.runtime import procpool

# no real sleeping between retries
FAST = dict(backoff_base=0.0, backoff_cap=0.0, jitter=0.0)

pytestmark = pytest.mark.skipif(
    not shared_memory_available(),
    reason="multiprocessing.shared_memory unavailable on this platform")


def _graph(n=120, seed=0):
    return sbm_graph(num_nodes=n, num_classes=4, feature_dim=8,
                     p_in=0.06, p_out=0.006, seed=seed).add_self_loops()


@pytest.fixture(scope="module")
def g():
    return _graph()


def _trainer(g, **kw):
    cfg = GNNConfig(model="gcn", num_layers=2, hidden_dim=16,
                    num_classes=4, feature_dim=8)
    return CompactTrainer(make_gnn(cfg), g, adam(1e-2), seed=0, **kw)


def _views(g, compact=True, seed=0):
    return strategy_views(g, "mini", K=2, seed=seed, batch_nodes=24,
                          compact=compact)


def _fit(g, steps=6, mode="thread", workers=2, plan=None, policy_kw=None,
         hang_seconds=0.5, **kw):
    tkw = {}
    if plan is not None:
        tkw["fault_policy"] = FaultPolicy(**{**FAST, **(policy_kw or {})})
        tkw["injector"] = FaultInjector(plan, seed=0,
                                        hang_seconds=hang_seconds)
    tr = _trainer(g, **tkw)
    out = tr.fit(_views(g), steps=steps, prefetch_workers=workers,
                 prefetch_mode=mode, **kw)
    return tr, out


def _no_children():
    # reap any zombies first, then require an empty nursery
    deadline = time.monotonic() + 5.0
    while multiprocessing.active_children() and time.monotonic() < deadline:
        time.sleep(0.05)
    return multiprocessing.active_children() == []


# ---------------------------------------------------------------------------
# service-level parity (no trainer in the loop)
# ---------------------------------------------------------------------------


def test_service_emits_bit_identical_views(g):
    def run(cls, workers):
        stream = _views(g)
        it = cls(stream, lambda v: v, 6, workers=workers)
        try:
            return list(it)
        finally:
            it.close()

    ref = run(StreamPrefetcher, 1)
    for workers in (1, 4):
        got = run(ProcessViewService, workers)
        assert len(got) == len(ref)
        for va, vb in zip(ref, got):
            for f in ("nodes", "hop_offsets", "src_local", "dst_local",
                      "edge_ids", "loss_local"):
                assert np.array_equal(getattr(va, f), getattr(vb, f)), f
    assert _no_children()


def test_service_cursor_tracks_emission(g):
    stream = _views(g)
    it = ProcessViewService(stream, lambda v: v, 5, workers=2)
    try:
        assert stream.cursor == 0
        next(it)
        assert stream.cursor == 1   # cursor counts *emitted* views only
        next(it)
        assert stream.cursor == 2
    finally:
        it.close()
    assert _no_children()


class _DeviceTouchingStream(MiniBatchViewStream):
    """A stream whose view build brings a JAX backend up — what a sampler
    process must never do while the trainer holds the accelerator."""

    def build(self, i, builder=None):
        jnp.zeros(1).block_until_ready()
        return super().build(i, builder)


def test_sampler_refuses_device_arrays(g):
    """A device array in the shipped stream would initialise a backend
    in the child as it is unpickled: the parent refuses to ship it."""
    stream = _views(g)
    stream.stray = jnp.ones(3)
    with pytest.raises(TypeError, match="device array"):
        ProcessViewService(stream, lambda v: v, 2, workers=1)
    assert _no_children()


def test_sampler_never_initialises_jax_backend(g):
    """Every sampler build is checked: one that initialised a JAX
    backend fails as a typed build error instead of contending for the
    trainer's chip. Plain streams pass the same check (every other test
    in this file)."""
    stream = _views(g)
    stream.__class__ = _DeviceTouchingStream
    it = ProcessViewService(stream, lambda v: v, 2, workers=1)
    try:
        with pytest.raises(RuntimeError, match="initialised a JAX backend"):
            list(it)
    finally:
        it.close()
    assert _no_children()


# ---------------------------------------------------------------------------
# trainer matrix: every (mode, workers) cell bit-identical
# ---------------------------------------------------------------------------


def test_mode_worker_matrix_bit_identical(g):
    _, ref = _fit(g, mode="thread", workers=1)
    for mode in ("thread", "process"):
        for workers in (1, 4):
            tr, out = _fit(g, mode=mode, workers=workers)
            assert out["losses"] == ref["losses"], (mode, workers)
            tr.assert_compiled_per_bucket()
    assert _no_children()


# ---------------------------------------------------------------------------
# fault recovery: kill -9, hang, corrupt — all invisible in the stream
# ---------------------------------------------------------------------------


def test_proc_kill_recovers_bit_identical(g):
    _, ref = _fit(g)
    tr, out = _fit(g, mode="process", plan={"proc_kill": {1}})
    assert out["losses"] == ref["losses"]
    assert any(e.get("stage") == "proc_kill" for e in out["events"])
    tr.assert_compiled_per_bucket()
    assert _no_children()


def test_proc_hang_watchdog_respawns(g):
    _, ref = _fit(g)
    # the child sleeps 30s WITHOUT heartbeats; the claim-age watchdog
    # must kill + respawn it well before the sleep would end
    t0 = time.monotonic()
    tr, out = _fit(g, mode="process", plan={"proc_hang": {1}},
                   hang_seconds=30.0,
                   policy_kw={"worker_heartbeat_s": 0.6})
    elapsed = time.monotonic() - t0
    assert out["losses"] == ref["losses"]
    assert any(e.get("stage") == "proc_hang" for e in out["events"])
    assert elapsed < 25.0, "watchdog waited the hang out instead of killing"
    tr.assert_compiled_per_bucket()
    assert _no_children()


def test_slot_corruption_detected_and_rebuilt(g):
    _, ref = _fit(g)
    tr, out = _fit(g, mode="process", plan={"slot_corrupt": {1}})
    assert out["losses"] == ref["losses"]
    corrupt = [e for e in out["events"]
               if e.get("stage") == "slot_corrupt"]
    assert corrupt and corrupt[0]["view"] == 1
    assert "crc" in corrupt[0]["error"]
    tr.assert_compiled_per_bucket()
    assert _no_children()


def test_respawn_cap_exceeded_raises_typed(g):
    with pytest.raises(FaultRetriesExceeded):
        _fit(g, mode="process", plan={"proc_kill": {0, 1, 2}},
             policy_kw={"max_proc_respawns": 1})
    assert _no_children()


def test_thread_mode_analogs_fire_and_recover(g):
    # the same process-fault plan drives StreamPrefetcher's in-process
    # analogs, so one chaos plan covers both prefetch modes
    _, ref = _fit(g)
    for plan in ({"proc_kill": {1}}, {"proc_hang": {1}},
                 {"slot_corrupt": {1}}):
        tr = _trainer(g, fault_policy=FaultPolicy(**FAST),
                      injector=FaultInjector(plan, seed=0,
                                             hang_seconds=0.2))
        out = tr.fit(_views(g), steps=6, prefetch_workers=2,
                     prefetch_mode="thread")
        assert out["losses"] == ref["losses"], plan
        assert tr.runtime.injector.total_fired() > 0, plan


# ---------------------------------------------------------------------------
# degradation + argument validation
# ---------------------------------------------------------------------------


def test_degrades_to_threads_with_one_warning(g, monkeypatch):
    _, ref = _fit(g)
    monkeypatch.setattr(procpool, "shared_memory_available",
                        lambda: False)
    monkeypatch.setattr(procpool, "_DEGRADE_WARNED", False)
    with pytest.warns(RuntimeWarning, match="degrading"):
        _, out = _fit(g, mode="process")
    assert out["losses"] == ref["losses"]
    # second degrade is silent (one-time warning)
    _, out2 = _fit(g, mode="process")
    assert out2["losses"] == ref["losses"]


def test_unknown_prefetch_mode_rejected(g):
    with pytest.raises(ValueError, match="prefetch_mode"):
        _fit(g, mode="fibers")


# ---------------------------------------------------------------------------
# SIGTERM mid-fit: checkpoint saved, samplers drained, nonzero exit
# ---------------------------------------------------------------------------


def _spawn_helper_pids():
    """Pids of alive multiprocessing spawn children system-wide (the
    orphan detector for the signal test)."""
    pids = set()
    for pid in os.listdir("/proc"):
        if not pid.isdigit():
            continue
        try:
            with open(f"/proc/{pid}/cmdline", "rb") as f:
                cmd = f.read()
        except OSError:
            continue
        if b"multiprocessing.spawn" in cmd:
            pids.add(int(pid))
    return pids


@pytest.mark.slow
def test_sigterm_saves_checkpoint_and_resumes(tmp_path):
    ckpt = tmp_path / "ckpt"
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(
        os.path.dirname(__file__), "..", "src")
    args = [sys.executable, "-m", "repro.launch.train", "gnn",
            "--dataset", "cora", "--strategy", "mini", "--compact",
            "--steps", "5000", "--prefetch-mode", "process",
            "--prefetch-workers", "2",
            "--checkpoint-dir", str(ckpt), "--checkpoint-every", "5"]
    orphans_before = _spawn_helper_pids()
    proc = subprocess.Popen(args, env=env, stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True)
    try:
        # wait for training to be genuinely underway (first checkpoint)
        deadline = time.monotonic() + 240
        while time.monotonic() < deadline:
            if ckpt.is_dir() and any(ckpt.glob("step_*.npz")):
                break
            if proc.poll() is not None:
                break
            time.sleep(0.2)
        assert proc.poll() is None, (
            f"run ended before first checkpoint:\n{proc.stderr.read()}")
        proc.send_signal(signal.SIGTERM)
        out, err = proc.communicate(timeout=120)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.communicate()
    assert proc.returncode == 128 + signal.SIGTERM, (out, err)
    assert "interrupted by signal" in err
    # the final checkpoint is valid and resumable
    from repro.checkpoint import load_checkpoint
    state = load_checkpoint(str(ckpt))
    assert state["params"] is not None
    # no orphaned sampler processes survived the interrupt
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        leaked = _spawn_helper_pids() - orphans_before
        if not leaked:
            break
        time.sleep(0.2)
    assert not leaked, f"orphaned sampler processes: {leaked}"
    # and a --resume run picks the work back up and exits cleanly
    resumed = subprocess.run(
        args[:args.index("--steps") + 1] + ["3"]
        + args[args.index("--steps") + 2:] + ["--resume"],
        env=env, capture_output=True, text=True, timeout=240)
    assert resumed.returncode == 0, resumed.stderr[-2000:]
    assert "final test acc" in resumed.stdout
