"""Fixture: idiomatic patterns every rule must accept unflagged."""
import heapq
import threading

import jax

from jax import shard_map                       # the QBS001-clean route


def make_step(fn, mesh):
    return jax.jit(shard_map(fn, mesh=mesh))    # factory: QBS004 ok


class Stream:
    _QBS_GUARDED_FIELDS = ("_pending", "_heap")

    def __init__(self):
        self._lock = threading.RLock()
        self._pending = {}
        self._heap = []

    def submit(self, key):
        with self._lock:
            self._pending[key] = 1
            heapq.heappush(self._heap, key)
            self._locked_helper(key)

    def _locked_helper(self, key):              # qbslint: locked
        self._pending.pop(key, None)

    def snapshot(self):
        with self._lock:
            return dict(self._pending)
