"""Fault tolerance: step retry with backoff.

At 1000+ nodes, the relevant failures are (a) transient device/runtime errors
(retry the step — state is functional, so a retry is safe by construction)
and (b) lost nodes (restore from the last checkpoint onto the surviving mesh —
ckpt/manager.py + runtime/elastic.py).
"""

from __future__ import annotations

import dataclasses
import logging
import time
from typing import Callable, TypeVar

T = TypeVar("T")
log = logging.getLogger("repro.fault")


@dataclasses.dataclass(frozen=True)
class FaultConfig:
    max_retries: int = 3
    backoff_s: float = 0.5
    backoff_mult: float = 2.0
    # exceptions considered transient (retryable); XlaRuntimeError subclasses
    # RuntimeError, so device-side faults are covered.
    transient: tuple = (RuntimeError, OSError)


def retry_step(fn: Callable[[], T], cfg: FaultConfig = FaultConfig(),
               on_retry: Callable[[int, BaseException], None] | None = None) -> T:
    """Run ``fn`` with bounded retry + exponential backoff.

    Functional JAX steps are idempotent (no in-place state), so re-execution
    after a transient XLA/runtime error is safe.  Non-transient exceptions
    propagate immediately.
    """
    delay = cfg.backoff_s
    for attempt in range(cfg.max_retries + 1):
        try:
            return fn()
        except cfg.transient as e:  # noqa: PERF203
            if attempt == cfg.max_retries:
                raise
            log.warning("transient failure (attempt %d/%d): %s", attempt + 1, cfg.max_retries, e)
            if on_retry is not None:
                on_retry(attempt, e)
            time.sleep(delay)
            delay *= cfg.backoff_mult
    raise AssertionError("unreachable")
