"""Host stack sampler for the traced run.

A daemon thread reads the main thread's Python stack every
``interval_s`` and keeps, per sample, the host time since the previous
sample and the (file, qualified name) of each frame from the innermost
outwards. The sampler can only read the stack between two bytecodes of
the main thread, so a long C or NumPy call delays the next sample, and
its time goes, with that sample, to the Python frame that called it.
While sampling, the interpreter's switch interval is cut to
``switch_s`` so that the sampler gets its turn within that time of
asking. The reducers give each sample's time to the innermost frame
that a metric's layer map lists, so a share is that time over all the
sampled time of the window. Sampling, unlike a profile hook on every
call, leaves the program's speed nearly as it is.
"""
from __future__ import annotations

import sys
import threading
import time
from typing import Optional

__all__ = ["StackSampler", "frame_key", "matches"]


def frame_key(code) -> tuple[str, str]:
    return code.co_filename.replace("\\", "/"), code.co_qualname


def matches(key: tuple[str, str], pattern: str) -> int:
    """How specifically ``pattern`` names the frame ``key``: 0 when it
    does not. A pattern is ``<path suffix>:<qualified name>`` (exact),
    ``<path suffix>:<prefix>*`` (a class or nested scope) or
    ``<path suffix>:*`` (the whole module); the longer the named part,
    the more specific."""
    path, _, name = pattern.partition(":")
    if not key[0].endswith(path):
        return 0
    if name.endswith("*"):
        return 1 + len(name) if key[1].startswith(name[:-1]) else 0
    return 1000 + len(name) if key[1] == name else 0


class StackSampler:
    """Samples one thread's stack until stopped."""

    def __init__(self, interval_s: float = 0.001, switch_s: float = 5e-5,
                 thread_id: Optional[int] = None):
        self.interval_s = interval_s
        self.switch_s = switch_s
        self.thread_id = thread_id if thread_id is not None else threading.get_ident()
        self.samples: list[tuple[float, tuple[tuple[str, str], ...]]] = []
        self._switch = sys.getswitchinterval()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._keys: dict = {}

    def _stack(self, frame) -> tuple:
        keys = self._keys
        out = []
        while frame is not None:
            code = frame.f_code
            k = keys.get(code)
            if k is None:
                k = keys[code] = frame_key(code)
            out.append(k)
            frame = frame.f_back
        return tuple(out)

    def _run(self) -> None:
        tid = self.thread_id
        last = time.perf_counter()
        while not self._stop.is_set():
            frame = sys._current_frames().get(tid)
            now = time.perf_counter()
            if frame is not None:
                self.samples.append((now - last, self._stack(frame)))
            del frame
            last = now
            time.sleep(self.interval_s)

    def __enter__(self) -> "StackSampler":
        sys.setswitchinterval(self.switch_s)
        self._thread = threading.Thread(target=self._run, name="stack-sampler", daemon=True)
        self._thread.start()
        return self

    def __exit__(self, *exc) -> None:
        self._stop.set()
        self._thread.join(timeout=10.0)
        sys.setswitchinterval(self._switch)
        if self._thread.is_alive():
            raise RuntimeError("stack sampler did not stop")
