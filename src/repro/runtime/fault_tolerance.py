"""Fault-tolerance runtime: checkpointed training driver with restart, range
re-assignment for stragglers/failures, and elastic re-meshing.

Design for 1000+ nodes (DESIGN.md §8):
- the training driver checkpoints every `ckpt_every` steps (atomic manifest
  commit) and restarts from the last durable state after any failure;
- stream work is assigned as contiguous [start, end) ranges; a failed or
  straggling shard's range is re-issued to survivors (`rebalance_ranges`).
  The StreamSVM ball merge is order-insensitive (commutative fold, property-
  tested), so re-assignment does not change the model class;
- `remesh_state` restores a checkpoint onto a different mesh (elastic scale
  up/down) by re-slicing — sharding lives in the restore target, not the
  checkpoint (see checkpoint/ckpt.py).

The injected-failure test (tests/test_fault_tolerance.py) proves
bit-equivalent recovery: train K steps with a crash at step j == train K
steps without a crash.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Iterable, List, Optional, Sequence, Tuple, Type

import jax

from repro.checkpoint import ckpt


class InjectedFailure(RuntimeError):
    pass


class DeviceLostError(RuntimeError):
    """A shard's device disappeared mid-chunk (host preemption, ICI link
    loss, accelerator reset). Always classified as retryable infrastructure
    failure — the work range is re-issued or the process restarts — never as
    a programming error."""


def runtime_device_errors() -> Tuple[Type[BaseException], ...]:
    """The exception classes the JAX/XLA runtime raises for device-level
    faults: ``jax.errors.JaxRuntimeError`` for a lost or wedged device.

    It is also what a Mosaic compile refusal or a device out-of-memory
    raises, so a policy built on it retries those too (see
    ``default_live_retryable``).
    """
    return (jax.errors.JaxRuntimeError,)


def default_live_retryable() -> Tuple[Type[BaseException], ...]:
    """Default retryable classes for the live restart driver
    (``repro.live.run_live_with_restarts``): injected test failures, our
    own ``DeviceLostError``, and the JAX/XLA runtime's device-fault
    exceptions — so a transient device fault burns a restart (resume from
    the last durable checkpoint) instead of propagating as if it were a
    programming error."""
    return (InjectedFailure, DeviceLostError) + runtime_device_errors()


@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    """Failure classification + capped exponential backoff, as one object.

    ``retryable`` names the exception classes worth restarting for —
    transient infrastructure faults (preemption, flaky I/O, injected test
    failures). Everything else is treated as a programming error and
    propagates immediately: retrying a ValueError re-raises the same
    ValueError ``max_retries`` times slower.

    ``delay(attempt)`` is ``backoff_base * 2**attempt`` capped at
    ``backoff_cap`` seconds (attempt counts from 0). Both the restart driver
    (``run_with_restarts``) and the live loop's chunk-fetch retry
    (repro.live) share this policy object.
    """

    retryable: Tuple[Type[BaseException], ...] = (InjectedFailure,)
    max_retries: int = 8
    backoff_base: float = 0.05
    backoff_cap: float = 2.0

    def is_retryable(self, exc: BaseException) -> bool:
        return isinstance(exc, self.retryable)

    def delay(self, attempt: int) -> float:
        return min(self.backoff_cap, self.backoff_base * (2.0 ** attempt))


@dataclasses.dataclass
class RunReport:
    steps_done: int
    restarts: int
    metrics: list


def run_with_restarts(
    step_fn: Callable,
    state,
    batches: Sequence,
    *,
    ckpt_dir: str,
    ckpt_every: int = 10,
    fail_at: Optional[Sequence[int]] = None,
    max_restarts: int = 8,
    shardings=None,
    retryable: Sequence[Type[BaseException]] = (InjectedFailure,),
    backoff_base: float = 0.05,
    backoff_cap: float = 2.0,
    sleep: Callable[[float], None] = time.sleep,
) -> Tuple[object, RunReport]:
    """Run `step_fn` over `batches` with checkpoint/restart semantics.

    `fail_at`: steps at which an InjectedFailure fires *after* the step
    executes but *before* its checkpoint would commit — the worst case
    (work lost back to the last checkpoint).

    `retryable` classifies failures: exceptions of these classes restart
    from the last durable checkpoint after a capped exponential backoff
    (``backoff_base * 2**restart``, capped at ``backoff_cap``; ``sleep`` is
    injectable for tests); anything else — a programming error — propagates
    immediately with no restart burned.
    """
    policy = RetryPolicy(
        retryable=tuple(retryable), max_retries=max_restarts,
        backoff_base=backoff_base, backoff_cap=backoff_cap,
    )
    fail_at = set(fail_at or ())
    restarts = 0
    metrics_log: list = []

    while True:
        # resume point
        if ckpt.exists(ckpt_dir):
            meta = ckpt.load_meta(ckpt_dir)
            start = int(meta["step"])
            state = ckpt.restore(ckpt_dir, state, shardings=shardings)
        else:
            start = 0
            ckpt.save(ckpt_dir, state, meta={"step": 0})
        # Steps between the last checkpoint and a crash re-run from `start`:
        # drop their already-logged metrics so RunReport.metrics matches the
        # uninterrupted run exactly (one entry per step, no duplicates).
        del metrics_log[start:]
        try:
            for i in range(start, len(batches)):
                state, m = step_fn(state, batches[i])
                if (i + 1) in fail_at:
                    fail_at.discard(i + 1)
                    raise InjectedFailure(f"injected at step {i + 1}")
                if (i + 1) % ckpt_every == 0 or (i + 1) == len(batches):
                    ckpt.save(ckpt_dir, state, meta={"step": i + 1})
                metrics_log.append(m)
            return state, RunReport(len(batches), restarts, metrics_log)
        except Exception as e:
            if not policy.is_retryable(e):
                raise  # programming error: no restart to burn
            restarts += 1
            if restarts > max_restarts:
                raise
            sleep(policy.delay(restarts - 1))


def rebalance_ranges(
    ranges: List[Tuple[int, int]], dead: Iterable[int], *, grouped: bool = False
):
    """Re-issue dead shards' [start, end) ranges to survivors (round-robin
    splits). Survivor count = len(ranges) - len(dead); each dead range is
    split evenly among survivors, appended to their work queues.

    ``grouped=True`` returns the per-survivor work queues as a dict
    ``{survivor_index: [(lo, hi), ...]}`` (each queue starts with the
    survivor's own range) instead of the flattened list — the form the
    elastic live loop needs to charge re-issued ranges to the surviving
    shard whose fetch channel delivers them."""
    dead = set(dead)
    survivors = [i for i in range(len(ranges)) if i not in dead]
    if not survivors:
        raise ValueError(
            f"rebalance_ranges: all {len(ranges)} shard(s) are dead "
            f"(dead={sorted(dead)}) — no survivors to re-issue ranges to"
        )
    out = {i: [ranges[i]] for i in survivors}
    # sorted(): set iteration order is hash-dependent; the re-issued work
    # queues must be deterministic across processes.
    for d in sorted(dead):
        lo, hi = ranges[d]
        n = len(survivors)
        width = (hi - lo + n - 1) // n
        for j, s in enumerate(survivors):
            a = lo + j * width
            b = min(lo + (j + 1) * width, hi)
            if a < b:
                out[s].append((a, b))
    if grouped:
        return out
    return [r for s in survivors for r in out[s]]


def remesh_state(ckpt_dir: str, target_state, new_mesh, sharding_fn):
    """Elastic rescale: restore onto `new_mesh` with shardings from
    `sharding_fn(target_state, new_mesh)`."""
    shardings = sharding_fn(target_state, new_mesh)
    return ckpt.restore(ckpt_dir, target_state, shardings=shardings)


@dataclasses.dataclass
class StragglerPolicy:
    """Deadline-based straggler mitigation for the streaming fit.

    In a real deployment the controller observes per-shard heartbeats; here
    the policy object carries the decision logic (pure, testable): after
    `deadline_factor` x median shard time, a shard is declared straggling and
    its remaining range re-issued via rebalance_ranges. Because ball merging
    is commutative and idempotent-per-example-set, duplicated suffixes are
    avoided by splitting at the straggler's last-acked position.
    """

    deadline_factor: float = 3.0

    def stragglers(self, elapsed: Sequence[float]) -> List[int]:
        if not elapsed:
            return []
        med = sorted(elapsed)[len(elapsed) // 2]
        return [i for i, t in enumerate(elapsed) if t > self.deadline_factor * max(med, 1e-9)]
