"""Host spans of the serving engine and scheduler.

``with span("engine.drain", rid=3) as sp:`` times the block on
``time.perf_counter`` and, when it closes, keeps ``(name, start, end,
parent, args)`` in a bounded ring held by the process; :func:`recent`
returns what the ring holds. The same block opens a
``jax.profiler.TraceAnnotation`` of the same name, so in a profile the
span lies on the device timeline. A span adds no device sync and no
device->host read.

``enabled = False`` keeps nothing and opens no annotation; the span still
times its block, so callers that read ``sp.seconds`` keep working. The
serve loop runs in one thread, so the stack of open spans is the
process's.
"""

from __future__ import annotations

import collections
import itertools
import time
from typing import Any, Deque, Dict, List

from jax.profiler import TraceAnnotation

#: spans the ring keeps: a serving boundary records about ten, so this
#: holds thousands of boundaries, more than a minute of serving
RING = 1 << 15
#: the recorder's switch, for tests and for measuring what it costs
enabled = True
#: the recorder's clock
now = time.perf_counter

_ring: Deque["Span"] = collections.deque(maxlen=RING)
_open: List["Span"] = []
_ids = itertools.count()


class Span:
    """One timed block; ``parent`` is the ``id`` of the span open around
    it when it opened (-1 at the top). ``args`` may grow while it is
    open."""

    __slots__ = ("name", "start", "end", "parent", "args", "id", "_note")

    def __init__(self, name: str, args: Dict[str, Any]):
        self.name, self.args = name, args
        self.start = self.end = -1.0
        self.parent = self.id = -1
        self._note = None

    @property
    def seconds(self) -> float:
        return self.end - self.start

    def __enter__(self) -> "Span":
        if enabled:
            self.id = next(_ids)
            self.parent = _open[-1].id if _open else -1
            _open.append(self)
            self._note = TraceAnnotation(self.name)
            self._note.__enter__()
        self.start = now()
        return self

    def __exit__(self, *exc) -> None:
        self.end = now()
        if self._note is not None:
            self._note.__exit__(*exc)
            self._note = None
            _open.pop()
            _ring.append(self)


def span(name: str, **args) -> Span:
    """A span named ``name`` carrying ``args``; use it in ``with``."""
    return Span(name, args)


def recent() -> List[Span]:
    """The closed spans the ring holds, in the order they closed."""
    return list(_ring)


def clear() -> None:
    """Empty the ring."""
    _ring.clear()
