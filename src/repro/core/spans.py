"""Phase spans of the search: each phase timed once, written two ways.

A phase's one ``perf_counter`` interval becomes a span in JAX's profiler
trace (a ``jax.profiler.TraceAnnotation``, on the clock the device trace is
mapped onto) and is added into a counter dict that the caller owns.  With no
profiler session a span costs a check in C++; the counter is always kept.

Spans are leaf phases, named ``repro.<layer>.<phase>``.  A span that belongs
to one trial carries the trial's configuration as its ``config`` argument
(``config_arg``), so one trial's compile and measure spans can be joined.
"""

from __future__ import annotations

import contextlib
import time
from typing import Any, Iterator, Mapping, MutableMapping

import jax


def config_arg(config: Mapping[str, Any]) -> str:
    """A configuration as one span argument: ``k:v`` pairs in key order,
    joined by ``;`` (the trace's argument encoding reserves ``,``, ``#``
    and ``=``)."""
    return ";".join(f"{k}:{v}" for k, v in sorted(config.items()))


@contextlib.contextmanager
def phase(name: str, counters: MutableMapping[str, float], key: str,
          **args: Any) -> Iterator[None]:
    """Time the body as the span ``name`` (with ``args``) and add its
    seconds into ``counters[key]``, also when the body raises."""
    t0 = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(name, **args):
            yield
    finally:
        counters[key] = counters.get(key, 0.0) + time.perf_counter() - t0
