"""Shared serving-metric definitions, and the host spans of the serving loop.

There is exactly ONE notion of decode throughput in this repo (DESIGN.md
§13): tokens *accepted* — i.e. actually delivered to the caller — divided by
decode wall time.  For non-speculative decode every decoded token is
accepted, so the definition degenerates to the old ``decoded / decode_s``;
speculative decode *proposes* more tokens than it delivers, and those
rejected drafts must never inflate a throughput number.  Both
``Engine.generate`` and ``Scheduler.stats`` report through this helper so
the two can never drift apart again.

:func:`span` names a stretch of host work on the profiler's clock
(``jax.profiler.TraceAnnotation``, the clock the device trace uses) and,
given a :class:`SpanTimes`, adds its seconds and one count there, read on
that object's injectable clock.  Spans mark phases of a serving round
(``serve.*``), never single tokens or slots.
"""

from __future__ import annotations

from collections import defaultdict
from typing import Callable, Optional

from jax.profiler import TraceAnnotation

__all__ = ["tok_per_s", "acceptance_rate", "span", "SpanTimes"]


class SpanTimes:
    """Host seconds and entry counts of named spans, on ``clock``."""

    def __init__(self, clock: Callable[[], float]):
        self.clock = clock
        self.seconds: dict = defaultdict(float)
        self.counts: dict = defaultdict(int)

    def reset(self) -> None:
        self.seconds.clear()
        self.counts.clear()

    def span(self, name: str, **meta) -> "span":
        return span(name, self, **meta)


class span:
    """``with span(name, times, **meta):`` -- a profiler annotation named
    ``name`` with scalar ``meta``; with ``times``, its duration and a count
    land in ``times`` on exit.  With no trace being taken it costs a few
    microseconds of host time, so it marks phases, not tokens or slots."""

    __slots__ = ("name", "times", "meta", "_ann", "_t0")

    def __init__(self, name: str, times: Optional[SpanTimes] = None, **meta):
        self.name, self.times, self.meta = name, times, meta

    def __enter__(self) -> "span":
        self._ann = TraceAnnotation(self.name, **self.meta)
        self._ann.__enter__()
        if self.times is not None:
            self._t0 = self.times.clock()
        return self

    def __exit__(self, *exc) -> None:
        if self.times is not None:
            self.times.seconds[self.name] += self.times.clock() - self._t0
            self.times.counts[self.name] += 1
        self._ann.__exit__(*exc)


def tok_per_s(accepted_tokens: int, decode_s: float) -> float:
    """Canonical decode throughput: accepted tokens per decode wall second.

    ``accepted_tokens`` counts tokens delivered to the caller beyond the
    first (prefill-billed) token; ``decode_s`` is decode wall time only —
    prefill/admission time is accounted separately.
    """
    return accepted_tokens / max(decode_s, 1e-9)


def acceptance_rate(accepted_drafts: int, proposed_drafts: int) -> float:
    """Fraction of drafter-proposed tokens the verifier accepted.  NaN when
    nothing was proposed (non-speculative runs must not read as 0% or
    100%)."""
    return accepted_drafts / proposed_drafts if proposed_drafts else float("nan")
