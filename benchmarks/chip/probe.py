"""Captures of a run's weights, for the check. Each keeps references to
device buffers only; the check reads them once the window has closed.

``Versions``: each pipeline stage keeps its weight versions in a
``VerticalSyncStash``; version v is the slice after v updates. While
active, it records per worker thread the slice each stage pushes as one
of the wanted versions (with version 0, still held when version 1
arrives). It is taken off before the measured window opens.

``Redistribution``: the §III-F hand-off. While active, it records for
every worker the newest weights it held at its latest chain replication
(what its neighbour's replica copies), and for every install of a
refit the weights it trains on afterwards beside those it held before.
It stays on through the window, where the measured recovery happens, and
adds no device work there.
"""
from __future__ import annotations

import threading


class Versions:
    def __init__(self, stash_cls, wanted):
        self._cls = stash_cls
        self._orig = stash_cls.push
        self._lock = threading.Lock()
        self.wanted = frozenset(wanted)
        self.by_worker: dict[str, dict[int, object]] = {}

    def __enter__(self) -> "Versions":
        orig, lock, seen, wanted = (self._orig, self._lock, self.by_worker,
                                    self.wanted)

        def push(stash, version, slice_params):
            if version in wanted:
                name = threading.current_thread().name
                with lock:
                    got = seen.setdefault(name, {})
                    if version not in got:
                        got[version] = slice_params
                        if version == 1 and 0 in stash.versions:
                            got[0] = stash.versions[0]
            return orig(stash, version, slice_params)

        self._cls.push = push
        return self

    def __exit__(self, *exc) -> None:
        self._cls.push = self._orig

    def stage_slices(self, version: int, worker_ids: list) -> dict | None:
        """{stage: buffer} of one version, stage i being worker
        ``worker_ids[i]``'s thread; None where a stage never pushed it."""
        out = {}
        with self._lock:
            for i, dev in enumerate(worker_ids):
                got = self.by_worker.get(f"worker-{dev}", {})
                if version not in got:
                    return None
                out[i] = got[version]
        return out


class Redistribution:
    def __init__(self, worker_cls):
        self._cls = worker_cls
        self._orig = (worker_cls._do_replicate, worker_cls.install)
        self._lock = threading.Lock()
        self.chain_rounds: dict[int, tuple] = {}   # dev -> (batch, range, buf)
        self.installs: list[dict] = []

    def __enter__(self) -> "Redistribution":
        orig_replicate, orig_install = self._orig
        lock, rounds, installs = self._lock, self.chain_rounds, self.installs

        def _do_replicate(worker, spec):
            if worker.stash is not None and spec.get("chain"):
                with lock:
                    rounds[worker.dev] = (spec["batch"], worker.layer_range,
                                          worker.stash.newest())
            return orig_replicate(worker, spec)

        def install(worker, layer_range, flats, version=0):
            if worker.stash is None:            # the start-up install
                return orig_install(worker, layer_range, flats, version)
            old = (tuple(worker.layer_range), worker.stash.newest())
            out = orig_install(worker, layer_range, flats, version)
            with lock:
                installs.append({
                    "dev": worker.dev, "version": version,
                    "range": tuple(worker.layer_range),
                    "installed": worker.stash.newest(),
                    "old_range": old[0], "old_newest": old[1]})
            return out

        self._cls._do_replicate = _do_replicate
        self._cls.install = install
        return self

    def __exit__(self, *exc) -> None:
        self._cls._do_replicate, self._cls.install = self._orig
