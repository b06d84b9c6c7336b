"""Single-flight request coalescing: N concurrent readers, ONE decode.

Under high fan-out, the worst cache behavior is the *miss storm*: a popular
tensor expires (or is read for the first time) and every in-flight request
for it starts its own decode — N× the CPU for N byte-identical results.
:class:`SingleFlight` collapses the storm: the first caller of a key becomes
the **leader** and runs the decode; every concurrent caller of the same key
**waits** on the leader's completion and shares the one result.

Semantics (docs/serving.md §Coalescing):

* results are shared by reference — callers must treat them as immutable
  (the serving layer freezes decoded spans before they get here);
* a leader *exception* propagates to the leader and every waiter (the same
  exception object — a failed decode fails the whole cohort loudly, nobody
  silently retries);
* the in-flight entry is removed *after* the result is published, so a
  late caller either joins the flight or finds the span already cached —
  there is no window where it would re-decode for nothing;
* ``leaders`` / ``coalesced`` are cumulative counters (exact: one leader
  per decode, one coalesced count per avoided decode), gated exactly by
  the traffic-replay benchmark.
"""
from __future__ import annotations

import threading

from .. import obs


class _Call:
    __slots__ = ("event", "result", "exc")

    def __init__(self):
        self.event = threading.Event()
        self.result = None
        self.exc: BaseException | None = None


class SingleFlight:
    """``do(key, fn)`` — run ``fn`` once per key across concurrent callers."""

    def __init__(self):
        self._lock = threading.Lock()
        self._calls: dict = {}
        self.leaders = 0
        self.coalesced = 0

    def inflight(self) -> int:
        """Number of keys currently being computed (observability)."""
        with self._lock:
            return len(self._calls)

    def do(self, key, fn) -> tuple[object, bool]:
        """Returns ``(result, shared)``: ``shared=True`` means this caller
        coalesced onto another caller's in-flight decode."""
        with self._lock:
            call = self._calls.get(key)
            if call is None:
                call = _Call()
                self._calls[key] = call
                self.leaders += 1
                leader = True
            else:
                self.coalesced += 1
                leader = False
        if not leader:
            with obs.span("flight.wait"):
                call.event.wait()
            if call.exc is not None:
                raise call.exc
            return call.result, True
        try:
            call.result = fn()
        except BaseException as e:
            call.exc = e
            raise
        finally:
            # publish-then-unregister under the lock: a caller arriving
            # after this either sees the cache (fn populated it) or starts
            # a fresh flight — never waits on a dead entry
            with self._lock:
                self._calls.pop(key, None)
            call.event.set()
        return call.result, False

    def reset_stats(self) -> None:
        with self._lock:
            self.leaders = self.coalesced = 0
