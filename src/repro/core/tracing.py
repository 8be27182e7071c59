"""Program spans and marks on the profiler's clock.

``span(name, uid, ...)`` is a ``jax.profiler.TraceAnnotation`` while a
profiler session is active (``jax.profiler.start_trace``) and one shared
null context otherwise.  Its events land in the trace beside the device's
operations, on the same clock, so a gap on the device can be put down to
the program's work open at the time.  ``mark`` is a span of zero length,
for instants such as a task's state transition.

There is no setting: the profiler being on is the switch.  The check is
one C++ call; the metadata is built only after it, so a span that is off
formats no string and allocates no dict.  Every name starts with ``rp.``
and every span of a task carries the task's ``uid``, so the spans of one
task share an identifier.  Metadata values go into the event's name
(``name#key=value,...#``), so a value holds no ``,``, ``#`` or ``=``:
lists of uids or slots are joined with spaces.

The benchmark's per-layer metrics read them (``bench/program_trace.py``).
Spans inside a spawned ``proc`` transport worker run in another process
and do not reach the trace.
"""
from __future__ import annotations

import contextlib
from typing import Optional

from jax._src.lib import _profiler
from jax.profiler import TraceAnnotation

_on = _profiler.TraceMe.is_enabled
_OFF = contextlib.nullcontext()


def span(name: str, uid: Optional[str] = None, *, slots=None):
    """A span over the ``with`` block, carrying the task's ``uid`` and the
    device ``slots`` it runs on where given.  Entering it gives the
    annotation while the profiler is on, for metadata known only later
    (``set_metadata``), and None otherwise."""
    if not _on():
        return _OFF
    meta = {}
    if uid is not None:
        meta["uid"] = uid
    if slots is not None:
        meta["slots"] = " ".join(map(str, slots))
    return TraceAnnotation(name, **meta)


def mark(name: str, uid: str, state: str):
    """An instant: a zero-length span carrying ``uid`` and ``state``."""
    if _on():
        with TraceAnnotation(name, uid=uid, state=state):
            pass
