"""Event-driven KV waits over GCS pubsub.

Replaces sleep-polling of GCS KV keys (the collective rendezvous spun at
2ms: polling where there should be events).
One background thread per (gcs_address, namespace) holds a long-poll
subscription to the ``kv:<namespace>`` channel and wakes registered waiters
when their key is written.  Reference counterpart: the long-poll subscriber
of src/ray/pubsub/subscriber.h:216 feeding object/actor waits.

Waiters follow the check-register-check discipline::

    ev = watcher.register(key)      # BEFORE the check: no lost-wakeup window
    try:
        while kv_get(key) is None:
            ev.wait(...); ev.clear()
    finally:
        watcher.unregister(key, ev)

A subscription gap (watcher fell behind the server's event ring, or the GCS
restarted) wakes ALL waiters so they re-check state — spurious wakeups are
safe by construction.
"""

from __future__ import annotations

import threading
import time

from ray_tpu._private.gcs import GcsSubscriber

_watchers: dict = {}
_watchers_lock = threading.Lock()


def get_watcher(gcs_address: str, namespace: str) -> "KvWatcher":
    key = (gcs_address, namespace)
    with _watchers_lock:
        w = _watchers.get(key)
        if w is None:
            w = KvWatcher(gcs_address, namespace)
            _watchers[key] = w
        return w


class KvWatcher:
    def __init__(self, gcs_address: str, namespace: str):
        self._gcs_address = gcs_address
        self._channel = f"kv:{namespace}"
        self._lock = threading.Lock()
        self._waiters: dict[bytes, list[threading.Event]] = {}
        self._started = False

    def register(self, key: bytes) -> threading.Event:
        ev = threading.Event()
        with self._lock:
            self._waiters.setdefault(key, []).append(ev)
            if not self._started:
                self._started = True
                threading.Thread(target=self._loop, name="kv-watch",
                                 daemon=True).start()
        return ev

    def unregister(self, key: bytes, ev: threading.Event) -> None:
        with self._lock:
            lst = self._waiters.get(key)
            if lst is not None:
                try:
                    lst.remove(ev)
                except ValueError:
                    pass
                if not lst:
                    del self._waiters[key]

    def _loop(self):
        sub = None
        while True:
            try:
                if sub is None:
                    sub = GcsSubscriber(self._gcs_address, [self._channel])
                events, gap = sub.poll(timeout_s=10.0)
            except Exception:
                # GCS unreachable (restarting head): wake everyone so their
                # kv_get re-check drives the retry/timeout policy, then
                # rebuild the subscription.
                sub = None
                gap, events = True, []
                time.sleep(0.2)
            with self._lock:
                if gap:
                    for lst in self._waiters.values():
                        for ev in lst:
                            ev.set()
                else:
                    for e in events:
                        for ev in self._waiters.get(e.get("key"), ()):
                            ev.set()
