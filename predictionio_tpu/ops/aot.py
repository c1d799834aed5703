"""Shared AOT-executable cache (the PR-6 ``_aot_bucketed`` pattern,
extracted so training and serving warm the same way).

``jax.jit`` compiles lazily: the FIRST call with a new abstract
signature pays the XLA compile inline, on whatever thread happened to
issue it — a training step, or worse, a live query. The AOT alternative
is ``jitted.lower(*args).compile()``: trace + compile NOW, execute
never, and keep the resulting ``jax.stages.Compiled`` for the hot path
to call directly. Two consumers share this module:

- ``ops/als.py`` warms the bucketed training program on a background
  thread while the ingest pipeline's H2D transfers stream (PR 6);
- ``ops/serving.py`` precompiles the query bucket LADDER at deploy so
  no live query ever pays a serve-time compile (SURVEY hard part #4,
  asserted by ``tests/test_serving_load.py::TestZeroCompileSteadyState``).

A cache MISS falls back to the plain jit wrapper, which compiles as
before — correctness never depends on the cache, only latency does. A
compile FAILURE is not a miss: :func:`lower_compile` lets the
compiler's exception out, so a program the device compiler refuses
fails the deploy (or the train) that asked for it instead of leaving a
server that reports ready and compiles, or fails, on a live query.

Observability (PR 12): evictions are counted and logged WITH the
dropped key — a fold-in-growth recompile storm shows up as a rising
``pio_aot_cache_evictions_total`` instead of a mystery — and
:meth:`AOTCache.memory_report` reads ``memory_analysis()`` of every
compiled entry so the query server's ``/stats.json`` can say how much
scratch and code the ladder's programs themselves need.
"""

from __future__ import annotations

import logging
import threading
from typing import Any, Dict, Hashable, Iterator, Optional, Tuple

from predictionio_tpu.utils import tracing as _tracing

logger = logging.getLogger("pio.aot")


class AOTCache:
    """Bounded, thread-safe FIFO of AOT-compiled executables.

    Bounded because each entry pins device code: a long-lived process
    warming ever-new shapes must not accumulate executables forever
    (the PR-6 rationale). Races on ``put`` are benign — worst case one
    redundant compile wins the slot.
    """

    def __init__(self, max_entries: int = 8, name: str = "aot"):
        self._max = int(max_entries)
        self.name = str(name)
        self._lock = threading.Lock()
        self._entries: Dict[Hashable, Any] = {}
        self._evictions = 0
        # memory_analysis is not free and the answer is immutable per
        # executable — cache the per-entry byte estimate by object id
        self._mem_cache: Dict[int, Optional[Tuple[int, int]]] = {}

    def get(self, key: Hashable) -> Optional[Any]:
        with self._lock:
            return self._entries.get(key)

    def put(self, key: Hashable, compiled: Any) -> None:
        dropped = []
        with self._lock:
            if key in self._entries:
                return
            while len(self._entries) >= self._max:
                old_key = next(iter(self._entries))
                old = self._entries.pop(old_key)
                self._mem_cache.pop(id(old), None)
                self._evictions += 1
                dropped.append(old_key)
            self._entries[key] = compiled
        if dropped:
            from predictionio_tpu.utils import metrics

            metrics.AOT_CACHE_EVICTIONS.inc(amount=len(dropped))
            for old_key in dropped:
                # name WHICH signature fell out: under fold-in growth a
                # store reshape can thrash the ladder, and a silent FIFO
                # makes the resulting recompiles look like random
                # latency instead of a cache too small for its shapes
                logger.warning(
                    "%s cache full (%d entries): evicted executable for "
                    "%r to admit %r", self.name, self._max, old_key, key)

    def discard(self, match) -> None:
        """Drop every entry whose key ``match`` accepts — a deliberate
        release (the shape they were compiled for is gone), so it is
        not counted as an eviction."""
        with self._lock:
            for key in [k for k in self._entries if match(k)]:
                self._mem_cache.pop(id(self._entries.pop(key)), None)

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def __contains__(self, key: Hashable) -> bool:
        with self._lock:
            return key in self._entries

    def keys(self) -> Iterator[Hashable]:
        with self._lock:
            return iter(tuple(self._entries))

    @property
    def evictions(self) -> int:
        with self._lock:
            return self._evictions

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {"entries": len(self._entries),
                    "maxEntries": self._max,
                    "evictions": self._evictions}

    @staticmethod
    def _entry_bytes(compiled: Any) -> Optional[Tuple[int, int]]:
        """One executable's OWN footprint from XLA's
        ``memory_analysis()``: (temporaries, generated code). Its
        arguments and outputs are not its own — every ladder program
        takes the whole store as arguments, and summing those counted
        the store once per program. None where this backend/jax version
        has no stats."""
        try:
            ma = compiled.memory_analysis()
        except Exception:
            return None
        if ma is None:
            return None
        own = [getattr(ma, attr, None) for attr in
               ("temp_size_in_bytes", "generated_code_size_in_bytes")]
        if all(v is None for v in own):
            return None
        return int(own[0] or 0), int(own[1] or 0)

    def memory_report(self) -> Dict[str, Any]:
        """What the cached programs themselves need on the device:
        ``tempBytes`` is the LARGEST single program's temporaries (one
        program runs at a time on a chip, so the runtime sets scratch
        aside for the hungriest, not for the sum), ``codeBytes`` the sum
        of generated code, ``totalBytes`` the two together. The
        per-entry answer is cached (executables are immutable), so a
        scrape pays the XLA query once per compile, not once per poll."""
        with self._lock:
            entries = list(self._entries.values())
        temp = code = 0
        analyzed = 0
        for compiled in entries:
            cached = self._mem_cache.get(id(compiled), "?")
            if cached == "?":
                cached = self._entry_bytes(compiled)
                with self._lock:
                    # only cache while the executable is still resident:
                    # caching an id() of a concurrently-evicted (and
                    # later garbage-collected) executable could hand a
                    # future executable reusing that id a stale size —
                    # and the orphan slot would never be reclaimed
                    if any(v is compiled for v in self._entries.values()):
                        self._mem_cache[id(compiled)] = cached
            if cached is not None:
                temp = max(temp, cached[0])
                code += cached[1]
                analyzed += 1
        return {"entries": len(entries), "entriesAnalyzed": analyzed,
                "tempBytes": temp, "codeBytes": code,
                "totalBytes": temp + code}


# Tracing + lowering happens one program at a time, process-wide. It is
# Python-bound, so threads gain nothing from overlapping it — and
# tracing ONE jit object from several threads at once is not
# deterministic: on the four-chip host the second `pio deploy` of the
# same model re-keyed 12 of 44 ladder programs (the three racers per
# fresh k-bucket program) and compiled them again instead of finding
# them in the persistent cache (PR 21). The XLA compile itself runs
# outside the lock, which is where a thread pool does help.
_lower_lock = threading.Lock()


def lower_compile(jitted, *args, **kwargs) -> Any:
    """``jitted.lower(*args, **kwargs).compile()``.

    ``args`` may mix concrete arrays (their shape/dtype/sharding is
    baked into the executable — pass the REAL factor stores so a
    sharded model compiles for its own mesh) and
    ``jax.ShapeDtypeStruct`` placeholders for per-call inputs. A
    lowering or compile error propagates with the compiler's message.

    The serialized trace + lower is a ``ladder.lower`` span: from a
    pool of workers those spans never overlap, so under one parent span
    round the pool they and the parent's self time (compile or cache
    load, whichever thread does it) add up to the wall clock. The
    compile overlaps other workers' lowering, so it is only a profiler
    annotation on its own thread."""
    with _lower_lock, _tracing.span("ladder.lower"):
        lowered = jitted.lower(*args, **kwargs)
    with _tracing.annotation("ladder.compile"):
        return lowered.compile()
