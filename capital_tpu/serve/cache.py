"""The engine's executable cache: in-memory AOT entries backed by an
optional on-disk persistent store.

The memory tier is PR 4's cache unchanged — one compiled executable per
key, hit/miss/warmup counters that make "steady-state traffic hits zero
recompiles" assertable.  The persistent tier answers the cold-start half of
that story: a process restart (or a fresh replica pointed at a shared cache
directory) re-pays every warmup compile, which for a full bucket ladder is
tens of seconds of dead time per process.  `persist_dir` spills every
compiled executable to disk via ``jax.experimental.serialize_executable``
so the NEXT engine's warmup deserializes instead of compiling — the
`make serve-smoke` cold-start proof is ``compiles == 0`` on the second run.

Disk entries are keyed by ``sha1(repr(cache key) + repr(fingerprint))``
where the cache key already carries the engine's config-hash and grid
topology, and the fingerprint pins jax/jaxlib versions, platform, and
device kind — an executable compiled by a different jaxlib or for a
different chip must never load (PJRT serialization is not stable across
versions).  Every disk failure mode degrades to *compile-and-overwrite*:

* **missing / stale entry** (fingerprint or key drift inside the file) →
  counted in ``disk_misses``, recompile, overwrite;
* **corrupt entry** (unpicklable bytes, truncated write, deserialization
  error) → counted in ``disk_errors``, recompile, overwrite;
* **unserializable executable or unwritable dir** on store → counted in
  ``disk_errors``, the in-memory entry still serves;
* **non-persistable program** (on CPU, anything reaching a LAPACK/BLAS
  custom call — PJRT serializes those as process-local addresses and a
  deserialized copy segfaults elsewhere) → never written, counted in
  ``disk_skips``, memory-only (`persistable_program`).

Writes are atomic (`os.replace` of a uniquely-named temp file), so two
engines sharing a cache directory race benignly: a reader never observes a
half-written file, and a writer that finds a valid same-fingerprint entry
already present (the multi-replica warmup pattern — every cold replica
compiles the same first-touch programs) skips the redundant write and
counts it in ``disk_races``, keeping ``disk_errors`` a real-failure
signal.  Nothing in this module raises to the caller for a disk reason.
"""

from __future__ import annotations

import hashlib
import logging
import os
import pickle
import uuid
from typing import Callable, Optional, Sequence

import jax

_log = logging.getLogger(__name__)

#: Bump when the on-disk entry layout changes; part of the fingerprint so
#: old entries read as stale, not corrupt.
ENTRY_VERSION = 1


def persistable_program(exe) -> bool:
    """Whether one compiled executable may spill to disk.  On CPU, PJRT
    serialization records custom-call targets (the LAPACK/BLAS FFI
    handlers) as process-local host addresses, so a deserialized program
    that reaches one SEGFAULTS in any other process — not an exception the
    never-raise contract could absorb.  Only pure-HLO programs persist on
    CPU (the pallas interpret kernels discharge to plain HLO and are
    safe); accelerator backends serialize their kernels by payload, not
    address.  A skipped program still caches in memory and is counted
    (``disk.skips``) so a cold-start audit can see why an entry recompiled.
    """
    if jax.default_backend() != "cpu":
        return True
    try:
        return "custom-call" not in exe.as_text()
    except Exception as e:  # noqa: BLE001 — unserializable introspection
        # means "cannot prove safe": keep it off disk and say why.
        _log.warning("cannot inspect executable for persistability "
                     "(%s: %s); keeping it memory-only", type(e).__name__, e)
        return False


def fingerprint(devices) -> dict:
    """What must match for a serialized executable to be loadable: the
    compiler that produced it and the devices it was compiled for — kind
    AND ids: a program compiled for one chip does not load onto another,
    so engines pinned to different chips keep separate entries."""
    import jaxlib

    return {
        "entry_version": ENTRY_VERSION,
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "platform": devices[0].platform,
        "device": devices[0].device_kind,
        "device_ids": [d.id for d in devices],
    }


class ExecutableCache:
    """Two-tier executable cache.  `get(key, build)` resolves memory ->
    disk -> ``build()`` (a fresh ``jit().lower().compile()``), maintaining
    the counters `SolveEngine.cache_stats()` reports:

    * ``hits`` / ``misses`` — request-driven MEMORY lookups (the
      steady-state zero-recompile gate reads these; a disk load still
      counts as a memory miss, because the request paid a load);
    * ``warmup_compiles`` — fresh compiles during warmup lookups (kept
      out of hit_rate, PR 4 semantics);
    * ``compiles`` — every fresh XLA compile, warmup or not: the number
      the cold-start proof pins at 0 for a warm persistent dir;
    * ``disk_hits`` / ``disk_misses`` / ``disk_errors`` — persistent-tier
      outcomes (errors = corrupt entries and failed stores, both
      non-fatal by contract).
    """

    def __init__(self, persist_dir: Optional[str] = None,
                 devices: Optional[Sequence] = None):
        self.persist_dir = persist_dir
        # the devices a loaded executable runs on (the engine grid's): a
        # deserialized program is otherwise bound to every device of the
        # backend and refuses single-device arguments
        self.devices = list(devices) if devices else jax.devices()[:1]
        self._mem: dict[tuple, object] = {}
        self._fp = fingerprint(self.devices) if persist_dir else None
        self.hits = 0
        self.misses = 0
        self.warmup_compiles = 0
        self.compiles = 0
        self.disk_hits = 0
        self.disk_misses = 0
        self.disk_errors = 0
        self.disk_skips = 0  # programs persistable_program() kept off disk
        # benign lost writer races: another engine sharing the dir already
        # stored a valid entry for this exact (key, fingerprint) — the
        # write is redundant, not broken.  Counted apart from disk_errors
        # so N replicas warming one shared dir don't read as N-1 disk
        # failures and the --max-compiles 0 warm gate stays meaningful.
        self.disk_races = 0

    def __len__(self) -> int:
        return len(self._mem)

    def __contains__(self, key: tuple) -> bool:
        return key in self._mem

    def items(self) -> list[tuple[tuple, object]]:
        """(key, executable) pairs of the memory tier."""
        return list(self._mem.items())

    # ---- the one entry point ----------------------------------------------

    def get(self, key: tuple, build: Callable[[], object], *,
            warmup: bool = False, persistable: bool = True):
        """Resolve `key` to an executable.  `build()` compiles one fresh
        (only called on a full miss).  `warmup` keeps the lookup out of the
        hit/miss counters; `persistable=False` opts a key out of the disk
        tier (nothing in serve uses it today — the hook exists so a future
        non-serializable program class degrades explicitly, not by
        error-counting on every warmup)."""
        exe = self._mem.get(key)
        if exe is not None:
            if not warmup:
                self.hits += 1
            return exe
        if not warmup:
            self.misses += 1
        if self.persist_dir and persistable:
            exe = self._load(key)
            if exe is not None:
                self._mem[key] = exe
                return exe
        self.compiles += 1
        if warmup:
            self.warmup_compiles += 1
        exe = build()
        self._mem[key] = exe
        if self.persist_dir and persistable:
            self._store(key, exe)
        return exe

    def stats(self) -> dict:
        """The cache block of `SolveEngine.cache_stats()` /
        serve:request_stats.  hit_rate covers request-driven lookups only
        (warmup excluded), PR 4 semantics."""
        lookups = self.hits + self.misses
        out = {
            "hits": self.hits,
            "misses": self.misses,
            "warmup_compiles": self.warmup_compiles,
            "compiles": self.compiles,
            "entries": len(self._mem),
            "hit_rate": (self.hits / lookups) if lookups else 1.0,
        }
        if self.persist_dir:
            out["disk"] = {
                "hits": self.disk_hits,
                "misses": self.disk_misses,
                "errors": self.disk_errors,
                "skips": self.disk_skips,
                "races": self.disk_races,
            }
        return out

    # ---- persistent tier ---------------------------------------------------

    def entry_path(self, key: tuple) -> str:
        ident = repr(key) + repr(self._fp)
        name = hashlib.sha1(ident.encode()).hexdigest()
        return os.path.join(self.persist_dir, f"{name}.exe")

    def _load(self, key: tuple):
        """One disk lookup; None on miss/stale/corrupt (counters tell the
        three apart, behavior does not: all three recompile)."""
        from jax.experimental import serialize_executable

        path = self.entry_path(key)
        if not os.path.exists(path):
            self.disk_misses += 1
            return None
        try:
            with open(path, "rb") as f:
                entry = pickle.load(f)
            # the filename hash already covers key+fingerprint; re-checking
            # the in-file copies catches a hash collision or a tool that
            # rewrote the file in place (the jaxlib-mismatch failure mode)
            if (entry.get("fingerprint") != self._fp
                    or entry.get("key") != repr(key)):
                self.disk_misses += 1
                return None
            exe = serialize_executable.deserialize_and_load(
                entry["payload"], entry["in_tree"], entry["out_tree"],
                execution_devices=self.devices,
            )
            self.disk_hits += 1
            return exe
        except Exception as e:  # noqa: BLE001 — any disk/pickle/PJRT
            # failure means "treat as absent and recompile"; the fallback
            # IS the contract (a poisoned cache file must never take the
            # serving process down), so log and count rather than raise.
            _log.warning("persistent cache entry %s unreadable (%s: %s); "
                         "recompiling and overwriting", path,
                         type(e).__name__, e)
            self.disk_errors += 1
            return None

    def _peek_valid(self, key: tuple) -> bool:
        """Whether a valid entry for (key, fingerprint) already sits on
        disk — metadata check only (no PJRT deserialization), used to tell
        a benign lost race from a real store failure.  False on ANY doubt:
        a wrong answer here only misfiles one counter."""
        try:
            with open(self.entry_path(key), "rb") as f:
                entry = pickle.load(f)
            return (entry.get("fingerprint") == self._fp
                    and entry.get("key") == repr(key)
                    and entry.get("payload") is not None)
        except Exception:  # lint: allow-broad-except — absent/corrupt/
            # unreadable all mean "no valid entry", which is the answer
            return False

    def _store(self, key: tuple, exe) -> None:
        """Spill one compiled executable; atomic via temp-file + replace so
        concurrent writers sharing the dir never expose torn entries.  A
        writer that LOST the race (a valid same-fingerprint entry is
        already there — N replicas warming one shared dir all compile the
        same first-touch programs) skips the redundant write and counts
        ``disk_races``, not ``disk_errors``."""
        from jax.experimental import serialize_executable

        if not persistable_program(exe):
            self.disk_skips += 1
            return
        if self._peek_valid(key):
            self.disk_races += 1
            return
        try:
            payload, in_tree, out_tree = serialize_executable.serialize(exe)
            blob = pickle.dumps({
                "fingerprint": self._fp,
                "key": repr(key),
                "payload": payload,
                "in_tree": in_tree,
                "out_tree": out_tree,
            })
            os.makedirs(self.persist_dir, exist_ok=True)
            path = self.entry_path(key)
            tmp = f"{path}.tmp.{os.getpid()}.{uuid.uuid4().hex[:8]}"
            with open(tmp, "wb") as f:
                f.write(blob)
            os.replace(tmp, path)
        except Exception as e:  # noqa: BLE001 — a store failure costs the
            # NEXT process a compile, never this one a crash; log + count.
            if self._peek_valid(key):
                # lost the race mid-write (e.g. tmp replace under a
                # concurrent writer): a valid entry is there, so the next
                # cold start is still covered — benign, not an error
                self.disk_races += 1
                return
            _log.warning("persistent cache store for %r failed (%s: %s); "
                         "entry serves from memory only", key,
                         type(e).__name__, e)
            self.disk_errors += 1
