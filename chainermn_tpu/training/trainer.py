"""Trainer: the outer loop.

Standalone equivalent of the Chainer ``Trainer`` the reference wires up
in its examples (``train_mnist.py:99-121``): run the updater until a
stop trigger, firing extensions (evaluation, logging, snapshots) on
their own triggers, with observations flowing through a per-iteration
dict instead of Chainer's global reporter.
"""

import os
import time

from chainermn_tpu.training import triggers as triggers_mod


class _ExtensionEntry:
    def __init__(self, extension, trigger, name, priority):
        self.extension = extension
        self.trigger = triggers_mod.get_trigger(trigger)
        self.name = name
        self.priority = priority


class Trainer:
    """``async_metrics=True`` keeps per-iteration metrics on the
    device: the updater is called with ``sync=False`` so the loop
    dispatches step n+1 while step n still runs, instead of blocking a
    full host-device round trip every iteration.  Extensions convert
    to floats lazily (see ``extensions._as_float``); a lightweight
    sync every ``sync_interval`` iterations bounds the in-flight
    queue."""

    def __init__(self, updater, stop_trigger=(1, 'epoch'), out='result',
                 async_metrics=False, sync_interval=16):
        self.updater = updater
        self.stop_trigger = triggers_mod.get_trigger(stop_trigger)
        self.out = out
        self.observation = {}
        self._extensions = []
        self._done = False
        self.elapsed_time = 0.0
        self._async = bool(async_metrics)
        self._sync_interval = max(1, int(sync_interval))
        self._stop_requested = False
        self.stop_reason = None

    def stop(self, reason=None):
        """Request a clean stop at the current iteration boundary
        (used by the preemption handler after its checkpoint; any
        extension may call it).  ``run()`` returns normally with
        ``stop_reason`` set."""
        self._stop_requested = True
        self.stop_reason = reason

    def extend(self, extension, trigger=None, name=None, priority=None):
        if trigger is None:
            trigger = getattr(extension, 'trigger', (1, 'epoch'))
        if priority is None:
            priority = getattr(extension, 'priority', 100)
        if name is None:
            name = getattr(extension, 'name', None) or getattr(
                extension, '__name__', type(extension).__name__)
        self._extensions.append(
            _ExtensionEntry(extension, trigger, name, priority))
        return self

    def run(self):
        if self.out and not os.path.isdir(self.out):
            os.makedirs(self.out, exist_ok=True)
        start = time.time()
        stop = self.stop_trigger
        try:
            while not (self._stop_requested or stop(self)):
                if self._async:
                    self.observation = self.updater.update(sync=False)
                    if self.updater.iteration % self._sync_interval == 0:
                        # fetch ONE scalar: completes everything queued
                        # up to this step (params chain), bounding
                        # run-ahead
                        import jax
                        for v in self.observation.values():
                            jax.device_get(v)  # noqa: shardlint
                            break
                else:
                    self.observation = self.updater.update()
                self.elapsed_time = time.time() - start
                for entry in sorted(self._extensions,
                                    key=lambda e: -e.priority):
                    if entry.trigger(self):
                        result = entry.extension(self)
                        if isinstance(result, dict):
                            self.observation.update(result)
                    if self._stop_requested:
                        break  # e.g. preemption checkpoint just written
        finally:
            self._done = True
            self._finalize_extensions()

    def _finalize_extensions(self):
        """Run every extension's ``finalize`` (when it has one) --
        resource teardown that must happen however the loop ended:
        ``heartbeat_extension`` stops its beat thread here (and
        stamps ``stopped: true``) so a finished trainer cannot keep
        signalling "alive" to a liveness watcher forever.  A raising
        finalizer must not mask the loop's own exception or starve
        its siblings."""
        for entry in self._extensions:
            fin = getattr(entry.extension, 'finalize', None)
            if fin is None:
                continue
            try:
                fin()
            except Exception:
                import traceback
                traceback.print_exc()
