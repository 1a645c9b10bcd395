"""Periodic stderr heartbeat for long, silent blocking calls.

A TPU compile through the local libtpu can block the main thread for minutes
with zero output; a hang looks identical from outside. Wrapping the call in
:func:`heartbeat` makes the difference visible: a legit compile shows bounded
"still compiling…" ticks and then a result. Used by
``scripts/aot_compile_check.py``.
"""

from __future__ import annotations

import contextlib
import sys
import threading
import time
from collections.abc import Iterator


@contextlib.contextmanager
def heartbeat(label: str, every_s: float = 60.0, *,
              stream=None) -> Iterator[None]:
    """Print ``label … Ns`` to ``stream`` (default stderr) every ``every_s``
    seconds until the with-block exits."""
    out = stream or sys.stderr
    t0 = time.perf_counter()
    done = threading.Event()

    def _tick() -> None:
        while not done.wait(every_s):
            print(f"{label}… {time.perf_counter() - t0:.0f}s",
                  file=out, flush=True)

    t = threading.Thread(target=_tick, name="photon-heartbeat", daemon=True)
    t.start()
    try:
        yield
    finally:
        done.set()
        # the ticker wakes from done.wait() immediately; joining makes the
        # context manager the thread's owner (no orphaned ticker can print
        # over a later phase's output)
        t.join(timeout=5)
