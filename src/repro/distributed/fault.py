"""Fault tolerance: restartable trainer state machine, straggler detection,
preemption handling, elastic rescale.

Designed for the 1000+-node posture and exercised locally:

  * ``FaultTolerantLoop`` wraps a step function with periodic checkpointing
    and auto-resume: on construction it restores the newest valid checkpoint
    (if any) and resumes from the following data step — crash-at-any-point
    recovery is tested by killing the loop mid-run.
  * ``Heartbeats`` tracks per-host step latencies in a ring and flags
    stragglers (latency > multiplier × rolling median) — the mitigation hook
    point (re-shard away, evict, or alert).  Single-process runs feed it one
    host; the logic is host-count agnostic.
  * ``PreemptionGuard`` converts SIGTERM (the cloud eviction signal) into a
    final checkpoint + clean exit.
  * Elastic rescale = restore_checkpoint(..., shardings=new_mesh_shardings);
    batches are (seed, step)-deterministic so the data stream continues
    exactly (see data/pipeline.py).
"""
from __future__ import annotations

import collections
import dataclasses
import signal
import statistics
import time
from typing import Any, Callable, Dict, List, Optional

import jax

from .checkpoint import latest_step, restore_checkpoint, save_checkpoint


@dataclasses.dataclass
class StragglerReport:
    host: int
    latency: float
    median: float

    @property
    def slowdown(self) -> float:
        return self.latency / max(self.median, 1e-9)


class Heartbeats:
    """Rolling per-host step-latency monitor with straggler flagging."""

    def __init__(self, n_hosts: int, window: int = 16,
                 straggler_factor: float = 2.0):
        self.n_hosts = n_hosts
        self.window = window
        self.factor = straggler_factor
        self._lat: List[collections.deque] = [
            collections.deque(maxlen=window) for _ in range(n_hosts)]

    def record(self, host: int, latency_s: float):
        self._lat[host].append(latency_s)

    def medians(self) -> List[float]:
        return [statistics.median(d) if d else 0.0 for d in self._lat]

    def stragglers(self) -> List[StragglerReport]:
        latest = [d[-1] if d else 0.0 for d in self._lat]
        flat = [x for d in self._lat for x in d]
        if not flat:
            return []
        med = statistics.median(flat)
        return [
            StragglerReport(host=h, latency=l, median=med)
            for h, l in enumerate(latest)
            if med > 0 and l > self.factor * med
        ]


class PreemptionGuard:
    """SIGTERM/SIGINT -> graceful 'checkpoint and exit' flag."""

    def __init__(self, install: bool = True):
        self.requested = False
        self._prev: Dict[int, Any] = {}
        if install:
            for sig in (signal.SIGTERM,):
                self._prev[sig] = signal.signal(sig, self._handler)

    def _handler(self, signum, frame):
        self.requested = True

    def uninstall(self):
        for sig, prev in self._prev.items():
            signal.signal(sig, prev)


# --------------------------------------------------------------------- #
# retry, watchdog, and the profiling degradation ladder
# --------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    retries: int = 3
    base_delay: float = 0.05
    max_delay: float = 2.0
    backoff: float = 2.0


def _inputs_consumed(args, kwargs) -> bool:
    """True when a call has deleted one of its array inputs (a jitted step
    that donates them): calling it again could only fail on the deleted
    buffers and would hide the error that mattered."""
    return any(getattr(leaf, "is_deleted", lambda: False)()
               for leaf in jax.tree_util.tree_leaves((args, kwargs)))


def retry_with_backoff(fn: Callable, *args, policy: RetryPolicy = RetryPolicy(),
                       retryable=(RuntimeError, OSError), on_retry=None,
                       sleep=time.sleep, **kwargs):
    """Call ``fn``; on a retryable exception, back off exponentially and
    retry up to ``policy.retries`` times, then re-raise the last error.

    A failed call that consumed (donated) its inputs is not retried: its
    error is raised as it is.
    """
    delay = policy.base_delay
    for attempt in range(policy.retries + 1):
        try:
            return fn(*args, **kwargs)
        except retryable as e:
            if attempt == policy.retries or _inputs_consumed(args, kwargs):
                raise
            if on_retry is not None:
                on_retry(attempt, e, delay)
            sleep(delay)
            delay = min(delay * policy.backoff, policy.max_delay)


class Watchdog:
    """Per-step wall-clock budget monitor.

    ``observe`` returns True when the step breached its budget;
    ``breaches`` counts consecutive breaches (reset by a healthy step) —
    the supervisor's overhead trigger.
    """

    def __init__(self, budget_s: float):
        self.budget_s = budget_s
        self.breaches = 0
        self.total_breaches = 0

    def observe(self, latency_s: float) -> bool:
        if latency_s > self.budget_s:
            self.breaches += 1
            self.total_breaches += 1
            return True
        self.breaches = 0
        return False


PROFILING_LADDER = ("inline", "shortcut", "off")


@dataclasses.dataclass
class DegradationEvent:
    step: int
    from_policy: str
    to_policy: str
    reason: str


class ProfilingSupervisor:
    """Graceful degradation of the profiling path: inline → shortcut → off.

    The data path always keeps serving; only the *profiling* fidelity is
    traded away.  Each rung down is taken after ``failure_threshold``
    consecutive integrity failures or overhead-budget breaches; healthy
    steps reset the streak.  The ladder never climbs back up on its own —
    re-arming is an operator decision (``reset``).
    """

    def __init__(self, policy: str = "inline", *, failure_threshold: int = 2,
                 overhead_budget: float = 0.25):
        if policy not in PROFILING_LADDER:
            raise ValueError(f"policy must be one of {PROFILING_LADDER}")
        self.policy = policy
        self.failure_threshold = failure_threshold
        self.overhead_budget = overhead_budget
        self.events: List[DegradationEvent] = []
        self._streak = 0
        self._hb_streak = 0
        self._step = 0

    @property
    def active(self) -> bool:
        return self.policy != "off"

    def step_ok(self) -> str:
        """A healthy profiled step: resets the failure streak."""
        self._step += 1
        self._streak = 0
        return self.policy

    def record_integrity_failure(self, detail: str = "") -> str:
        return self._strike(f"profile-integrity failure {detail}".strip())

    def record_overhead(self, overhead_frac: float) -> str:
        """Report profiling overhead as a fraction of the step budget."""
        self._step += 1
        if overhead_frac <= self.overhead_budget:
            self._streak = 0
            return self.policy
        return self._strike(
            f"profiling overhead {overhead_frac:.2f} > "
            f"budget {self.overhead_budget:.2f}", counted=True)

    def observe_heartbeats(self, heartbeats: "Heartbeats") -> str:
        """Fold straggler reports into the degradation ladder.

        A straggling host starves the profile-stream drain the same way an
        overhead breach does, so persistent stragglers step profiling down a
        rung.  Straggler strikes accumulate on their *own* streak — healthy
        heartbeats clear it, healthy ingests (``step_ok``) do not — so a
        slow-host signal interleaved with clean decodes still reaches the
        threshold.
        """
        reports = heartbeats.stragglers()
        if not reports:
            self._hb_streak = 0
            return self.policy
        self._hb_streak += 1
        if self._hb_streak >= self.failure_threshold and self.active:
            worst = max(reports, key=lambda r: r.slowdown)
            self._step_down(
                f"straggler host {worst.host}: latency {worst.latency:.3f}s "
                f"= {worst.slowdown:.1f}x median")
            self._hb_streak = 0
        return self.policy

    def _strike(self, reason: str, counted: bool = False) -> str:
        if not counted:
            self._step += 1
        self._streak += 1
        if self._streak >= self.failure_threshold and self.active:
            self._step_down(reason)
            self._streak = 0
        return self.policy

    def _step_down(self, reason: str) -> None:
        i = PROFILING_LADDER.index(self.policy)
        nxt = PROFILING_LADDER[min(i + 1, len(PROFILING_LADDER) - 1)]
        self.events.append(DegradationEvent(
            step=self._step, from_policy=self.policy, to_policy=nxt,
            reason=reason))
        self.policy = nxt

    def reset(self, policy: str = "inline") -> None:
        self.policy = policy
        self._streak = 0
        self._hb_streak = 0

    def summary(self) -> str:
        if not self.events:
            return f"profiling policy: {self.policy} (no degradations)"
        path = " -> ".join([self.events[0].from_policy]
                           + [e.to_policy for e in self.events])
        return (f"profiling policy: {path}; "
                + "; ".join(f"step {e.step}: {e.reason}" for e in self.events))


class FaultTolerantLoop:
    """Checkpointed training loop with auto-resume.

    step_fn(state, batch) -> (state, metrics); state is any pytree.
    ``ckpt_dir=None`` runs the loop without checkpoints.
    """

    def __init__(
        self,
        ckpt_dir,
        state: Any,
        step_fn: Callable,
        *,
        ckpt_every: int = 50,
        keep: int = 3,
        shardings: Any = None,
        heartbeat: Optional[Heartbeats] = None,
        preemption: Optional[PreemptionGuard] = None,
    ):
        self.ckpt_dir = ckpt_dir
        self.step_fn = step_fn
        self.ckpt_every = ckpt_every
        self.keep = keep
        self.heartbeat = heartbeat or Heartbeats(1)
        self.preemption = preemption
        self.start_step = 0
        self.state = state
        prev = latest_step(ckpt_dir) if ckpt_dir is not None else None
        if prev is not None:
            self.start_step, self.state = restore_checkpoint(
                ckpt_dir, state, shardings=shardings)
            self.start_step += 1  # resume AFTER the checkpointed step

    def run(self, batch_iter, n_steps: int, on_metrics=None) -> int:
        """Runs up to ``n_steps`` more steps; returns the next step index."""
        step = self.start_step
        end = self.start_step + n_steps
        for batch in batch_iter:
            if step >= end:
                break
            t0 = time.time()
            self.state, metrics = self.step_fn(self.state, batch)
            self.heartbeat.record(0, time.time() - t0)
            if on_metrics is not None:
                on_metrics(step, metrics)
            must_stop = self.preemption is not None and self.preemption.requested
            if step % self.ckpt_every == self.ckpt_every - 1 or must_stop:
                self._save(step)
            if must_stop:
                return step + 1
            step += 1
        if step > self.start_step:
            self._save(step - 1)
        return step

    def _save(self, step: int) -> None:
        if self.ckpt_dir is not None:
            save_checkpoint(self.ckpt_dir, step, self.state, keep=self.keep)
