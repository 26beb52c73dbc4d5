"""Persistent AOT compile cache: serialized XLA executables shared
across fleet processes, keyed on a canonical program fingerprint.

Every replica cold-start, autoscale spin-up, hot-swap warmup, and
restart used to re-pay XLA compilation invisibly (ROADMAP "Compile
plane"). This module makes the executor's compiles *portable*: the
first process to compile a (program, shape, mesh) serializes the
executable here (``jax.experimental.serialize_executable``), and every
later process — a fresh replica, a restarted trainer, a warmup pass —
loads it in O(read) instead of O(compile).

Key design points:

  - **Canonical key.** The fingerprint is a SHA-256 over the program's
    lowered StableHLO text — which is independent of process-local
    identities (``Program._uid``, object ids, scope addresses): two
    processes that build the same program the same way produce the
    same text, so they share cache entries. The full disk key adds
    everything else that changes the produced executable: backend
    platform, device count, jax/jaxlib versions, and the mesh
    fingerprint (shapes/dtypes are already inside the HLO).
  - **Observable.** Every hit/miss/store/evict bumps labeled registry
    counters and emits a journal event; a hit's journal record carries
    the ORIGIN of the entry (pid/role/wall-time of the process that
    paid the compile, and what it paid), so a fleet journal shows who
    compiled what and who rode for free.
  - **Crash-safe.** Entries are written tmp-file + ``os.replace``
    (atomic on POSIX); readers of a torn/garbage entry treat it as a
    miss and overwrite. Concurrent writers of the same key converge on
    identical bytes.
  - **Bounded.** ``max_bytes`` arms LRU eviction (by last-use mtime,
    ``get`` touches entries); evicted keys are remembered in
    ``evicted.jsonl`` so the executor can attribute a later recompile
    to ``evicted`` rather than a cold cache.

Enable per process with ``configure(dir)`` or the
``PADDLE_TPU_COMPILE_CACHE_DIR`` env var (the launcher stamps one
shared directory per fleet); ``PADDLE_TPU_COMPILE_CACHE_MAX_BYTES``
bounds it. Disabled (the default) the executor compiles exactly as
before — the cache is strictly additive.

``enable()`` is the one place that decides WHERE persistent compile
state lives, for this store and for JAX's own compilation cache
together (``resolve_root``): an explicit directory, else
``JAX_COMPILATION_CACHE_DIR``, else ``.jax_cache/`` in the checkout.
The path is part of JAX's cache key, so it is never a temp name, a pid
or a home directory. Entry points (chip_smoke.py, bench.py, the tools,
the launcher) call it; nothing else sets JAX's cache directory.

See docs/compile.md for the on-disk layout and the provenance record
schema this feeds.
"""

from __future__ import annotations

import hashlib
import json
import os
import pickle
import threading
import time
import zlib
from typing import Optional

from . import observability as _obs

__all__ = ["CompileCache", "CacheHit", "configure", "active",
           "canonical_fingerprint", "cache_key", "memory_record",
           "stats", "reset_stats", "resolve_root", "store_dir", "enable"]

ENV_DIR = "PADDLE_TPU_COMPILE_CACHE_DIR"
ENV_MAX_BYTES = "PADDLE_TPU_COMPILE_CACHE_MAX_BYTES"
JAX_ENV_DIR = "JAX_COMPILATION_CACHE_DIR"
EVICTED_INDEX = "evicted.jsonl"
# this store's subdirectory of the resolved root (JAX's own cache
# files sit directly in the root)
STORE_SUBDIR = "paddle_tpu_executables"
_CHECKOUT_ROOT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")

_MU = threading.Lock()
_ACTIVE: Optional["CompileCache"] = None
_ENV_CHECKED = False


def canonical_fingerprint(hlo_text: str) -> str:
    """SHA-256 hex of a program's lowered (StableHLO) text — the
    ``_uid``-independent identity the provenance ledger and the disk
    cache share. The text is deterministic for a program built the
    same way in any process (verified cross-process by tests)."""
    return hashlib.sha256(hlo_text.encode()).hexdigest()


def cache_key(fingerprint: str, mesh_fp=None) -> str:
    """Full disk key: the canonical fingerprint plus everything else
    that changes the produced executable — backend platform + device
    count (an executable deserializes only onto the topology it was
    compiled for) and jax/jaxlib versions (serialization format and
    codegen both move between releases). Shapes, dtypes, and sharding
    annotations are already inside the fingerprinted HLO; the mesh
    fingerprint is included for explicitness (axis names/sizes)."""
    import jax
    import jaxlib
    backend = jax.default_backend()
    material = "|".join([
        fingerprint, backend, str(jax.device_count()),
        jax.__version__, jaxlib.__version__, repr(mesh_fp)])
    return hashlib.sha256(material.encode()).hexdigest()


# memory_record's keys beside the compiler's own field names
_MEMORY_FIELDS = (("argument_bytes", "argument_size_in_bytes"),
                  ("output_bytes", "output_size_in_bytes"),
                  ("alias_bytes", "alias_size_in_bytes"),
                  ("temp_bytes", "temp_size_in_bytes"),
                  ("generated_code_bytes", "generated_code_size_in_bytes"))


def memory_record(compiled) -> Optional[dict]:
    """What one executable holds in device memory, per device, as the
    compiler counted it (``memory_analysis()`` of a
    jax.stages.Compiled, compiled here or loaded from the store): its
    arguments, its outputs, the outputs that alias arguments (donated
    state), its temporaries and its code; ``peak_bytes`` where the
    runtime fills ``peak_memory_in_bytes``, else None. None where the
    executable gives no analysis: the executor hands the compiled
    one's record to ``put`` with the entry's meta, so a process that
    loads the entry can still read what the compiling one read."""
    try:
        ma = compiled.memory_analysis()
    except Exception:    # a backend without the analysis
        return None
    if ma is None:
        return None
    rec = {ours: int(getattr(ma, theirs, 0) or 0)
           for ours, theirs in _MEMORY_FIELDS}
    if not any(rec.values()):
        return None
    rec["peak_bytes"] = int(getattr(ma, "peak_memory_in_bytes", 0)
                            or 0) or None
    return rec


class CacheHit:
    """One successful load: the callable ``loaded`` executable plus
    the stored origin metadata and what the load itself cost."""

    def __init__(self, loaded, meta, load_seconds, nbytes):
        self.loaded = loaded
        self.meta = meta
        self.load_seconds = load_seconds
        self.nbytes = nbytes


class CompileCache:
    """On-disk store of serialized XLA executables (see module doc).

    Layout under ``dir``: ``<key>.bin`` (deflated pickle of the
    ``serialize_executable`` triple plus the ids of the devices the
    executable was compiled for), ``<key>.json`` (origin + cost
    metadata, human-readable), ``evicted.jsonl`` (one key per line,
    append-only memory of LRU evictions)."""

    def __init__(self, dir: str, max_bytes: Optional[int] = None):
        self.dir = os.path.abspath(dir)
        self.max_bytes = int(max_bytes) if max_bytes else None
        os.makedirs(self.dir, exist_ok=True)
        self._mu = threading.Lock()
        reg = _obs.registry()
        self._m_hit = reg.counter("compile_cache_hits_total")
        self._m_miss = reg.counter("compile_cache_misses_total")
        self._m_store = reg.counter("compile_cache_stores_total")
        self._m_evict = reg.counter("compile_cache_evictions_total")
        self._m_bytes_in = reg.counter("compile_cache_bytes_loaded_total")
        self._m_bytes_out = reg.counter("compile_cache_bytes_stored_total")
        self._h_load = reg.histogram("compile_cache_load_seconds")

    # -- paths ---------------------------------------------------------
    def _bin(self, key: str) -> str:
        return os.path.join(self.dir, key + ".bin")

    def _meta(self, key: str) -> str:
        return os.path.join(self.dir, key + ".json")

    # -- read ----------------------------------------------------------
    def get(self, key: str, entry: str = "?") -> Optional[CacheHit]:
        """Load + deserialize one executable; None on miss (including
        torn/undeserializable entries, which are misses by contract —
        the caller recompiles and overwrites)."""
        path = self._bin(key)
        t0 = time.perf_counter()
        try:
            try:
                st = os.stat(path)
            except OSError:
                st = None
            with open(path, "rb") as f:
                blob = f.read()
            payload, in_tree, out_tree, device_ids = \
                pickle.loads(zlib.decompress(blob))
            import jax
            from jax.experimental import serialize_executable as _se
            # load onto exactly the devices it was compiled for: the
            # default is EVERY device of the backend, which breaks a
            # one-device executable on any multi-device process
            by_id = {d.id: d for d in jax.devices()}
            loaded = _se.deserialize_and_load(
                payload, in_tree, out_tree,
                execution_devices=[by_id[i] for i in device_ids])
        except FileNotFoundError:
            self._m_miss.inc()
            return None
        except Exception as e:
            # torn write / version skew / foreign topology: a miss,
            # and the entry is dead weight — drop it so the recompile
            # can overwrite cleanly. Only if UNCHANGED since our read:
            # a sibling process may have re-stored a good entry in the
            # window, and deleting that would cost the fleet a compile.
            self._m_miss.inc()
            _obs.emit("compile_cache_corrupt", key=key, entry=entry,
                      error=repr(e))
            try:
                st2 = os.stat(path)
                if st is not None and (st2.st_mtime == st.st_mtime
                                       and st2.st_size == st.st_size):
                    self._remove(key)
            except OSError:
                pass
            return None
        dt = time.perf_counter() - t0
        meta = self._read_meta(key)
        # touch for LRU recency (best effort)
        try:
            os.utime(path, None)
        except OSError:
            pass
        self._m_hit.inc()
        self._m_bytes_in.inc(len(blob))
        self._h_load.observe(dt)
        return CacheHit(loaded, meta, dt, len(blob))

    def _read_meta(self, key: str) -> dict:
        try:
            with open(self._meta(key)) as f:
                return json.load(f)
        except Exception:
            return {}

    def contains(self, key: str) -> bool:
        return os.path.exists(self._bin(key))

    # -- write ---------------------------------------------------------
    def put(self, key: str, compiled, meta: dict) -> Optional[int]:
        """Serialize ``compiled`` (a jax.stages.Compiled/Loaded) under
        ``key`` with ``meta`` stamped with this process's identity.
        Returns the stored byte count, or None when the executable
        does not support serialization on this backend (the cache
        degrades to ledger-only, never raises into the compile
        path)."""
        try:
            from jax.experimental import serialize_executable as _se
            payload, in_tree, out_tree = _se.serialize(compiled)
            device_ids = [d.id for d in
                          compiled.runtime_executable().local_devices()]
            # level 1: a TPU train step serializes to ~300 MB and
            # deflates ~5x at disk speed
            blob = zlib.compress(pickle.dumps(
                (payload, in_tree, out_tree, device_ids)), 1)
        except Exception as e:
            _obs.emit("compile_cache_unserializable", key=key,
                      error=repr(e), entry=meta.get("entry"))
            return None
        m = dict(meta)
        m.update(key=key, origin_pid=os.getpid(),
                 origin_role=_obs.get_role(), origin_t_wall=time.time(),
                 bytes=len(blob))
        tmp = self._bin(key) + ".tmp.%d" % os.getpid()
        mtmp = self._meta(key) + ".tmp.%d" % os.getpid()
        try:
            with open(tmp, "wb") as f:
                f.write(blob)
            os.replace(tmp, self._bin(key))
            with open(mtmp, "w") as f:
                json.dump(m, f, indent=1, default=repr)
            os.replace(mtmp, self._meta(key))
        except OSError as e:
            _obs.emit("compile_cache_write_failed", key=key,
                      error=repr(e))
            for p in (tmp, mtmp):
                try:
                    os.remove(p)
                except OSError:
                    pass
            return None
        # a re-stored key is no longer "evicted": prune it from the
        # index or a later unrelated miss (corrupt entry, wiped dir)
        # would misclassify as evicted forever
        self._unmark_evicted(key)
        self._m_store.inc()
        self._m_bytes_out.inc(len(blob))
        _obs.emit("compile_cache_store", key=key,
                  entry=meta.get("entry"),
                  fingerprint=meta.get("fingerprint"),
                  bytes=len(blob),
                  compile_seconds=meta.get("compile_seconds"))
        if self.max_bytes is not None:
            self._evict_lru()
        return len(blob)

    def _remove(self, key: str):
        for p in (self._bin(key), self._meta(key)):
            try:
                os.remove(p)
            except OSError:
                pass

    # -- eviction ------------------------------------------------------
    # a tmp file this old was orphaned by a killed writer (a live
    # put() holds one for milliseconds) — reaped during eviction scans
    TMP_ORPHAN_AGE_S = 3600.0

    def _evict_lru(self):
        """Drop least-recently-used entries until under ``max_bytes``;
        remember each evicted key so a later recompile of it can be
        attributed (miss reason ``evicted``, not ``cache_cold``). The
        budget counts each entry's .bin AND .json sidecar, and the
        scan reaps tmp files orphaned by killed writers — a shared
        fleet dir must not outgrow max_bytes through invisible
        bookkeeping bytes."""
        now = time.time()
        with self._mu:
            sizes = {}
            try:
                for n in os.listdir(self.dir):
                    p = os.path.join(self.dir, n)
                    if ".tmp." in n:
                        try:
                            if now - os.path.getmtime(p) \
                                    > self.TMP_ORPHAN_AGE_S:
                                os.remove(p)
                        except OSError:
                            pass
                        continue
                    try:
                        sizes[n] = (os.path.getmtime(p),
                                    os.path.getsize(p))
                    except OSError:
                        pass
            except OSError:
                return
            entries = []  # (mtime, bin+json bytes, key)
            for n, (mt, sz) in sizes.items():
                if not n.endswith(".bin"):
                    continue
                key = n[:-4]
                sz += sizes.get(key + ".json", (0, 0))[1]
                entries.append((mt, sz, key))
            total = sum(sz for _, sz, _ in entries)
            if total <= self.max_bytes:
                return
            entries.sort()  # oldest mtime first
            idx = os.path.join(self.dir, EVICTED_INDEX)
            for _, sz, key in entries:
                if total <= self.max_bytes:
                    break
                self._remove(key)
                total -= sz
                try:
                    with open(idx, "a") as f:
                        f.write(json.dumps(
                            {"key": key, "t_wall": time.time()}) + "\n")
                except OSError:
                    pass
                self._m_evict.inc()
                _obs.emit("compile_cache_evict", key=key, bytes=sz)
            self._compact_index_locked()

    # keep the append-only index bounded: compact to
    # last-record-per-key once it exceeds this many lines (evictions
    # are rare relative to compiles, so the O(N) rewrite is rarer
    # still). The rewrite can in principle drop a line a concurrent
    # process appends during it — worst case one later miss reads
    # cache_cold instead of evicted, a benign telemetry skew.
    INDEX_COMPACT_LINES = 4096

    def _compact_index_locked(self):
        idx = os.path.join(self.dir, EVICTED_INDEX)
        try:
            with open(idx) as f:
                lines = f.readlines()
            if len(lines) <= self.INDEX_COMPACT_LINES:
                return
            last = {}
            for line in lines:
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                if "key" in rec:
                    last[rec["key"]] = rec
            tmp = idx + ".tmp.%d" % os.getpid()
            with open(tmp, "w") as f:
                for rec in last.values():
                    if not rec.get("restored"):
                        f.write(json.dumps(rec) + "\n")
            os.replace(tmp, idx)
        except OSError:
            pass

    def was_evicted(self, key: str) -> bool:
        """True when ``key`` is absent AND the eviction index's LAST
        record for it is an eviction (``put`` appends a ``restored``
        tombstone when a key is re-stored, so eviction status does not
        outlive the eviction). The index is append-only — concurrent
        evictors/restorers across processes each append one small
        O_APPEND line and never rewrite each other's records."""
        if self.contains(key):
            return False
        idx = os.path.join(self.dir, EVICTED_INDEX)
        evicted = False
        try:
            with open(idx) as f:
                for line in f:
                    try:
                        rec = json.loads(line)
                    except ValueError:
                        continue
                    if rec.get("key") == key:
                        evicted = not rec.get("restored", False)
        except OSError:
            return False
        return evicted

    def _unmark_evicted(self, key: str):
        """Append a ``restored`` tombstone for a re-stored key (only
        when the index currently ends on an eviction for it) — see
        was_evicted for the last-record-wins contract."""
        if not self.contains(key):
            return
        idx = os.path.join(self.dir, EVICTED_INDEX)
        if not os.path.exists(idx):
            return
        # cheap pre-check: no record, nothing to tombstone
        try:
            with open(idx) as f:
                pending = False
                for line in f:
                    try:
                        rec = json.loads(line)
                    except ValueError:
                        continue
                    if rec.get("key") == key:
                        pending = not rec.get("restored", False)
            if not pending:
                return
            with open(idx, "a") as f:
                f.write(json.dumps({"key": key, "restored": True,
                                    "t_wall": time.time()}) + "\n")
        except OSError:
            pass

    # -- accounting ----------------------------------------------------
    def stats(self) -> dict:
        """Registry-backed snapshot of this process's cache activity
        (the counters are process-wide: one active cache per
        process)."""
        return {
            "dir": self.dir,
            "hits": self._m_hit.value,
            "misses": self._m_miss.value,
            "stores": self._m_store.value,
            "evictions": self._m_evict.value,
            "bytes_loaded": self._m_bytes_in.value,
            "bytes_stored": self._m_bytes_out.value,
            "load_seconds_total": self._h_load.sum,
        }

    def disk_entries(self) -> int:
        try:
            return sum(1 for n in os.listdir(self.dir)
                       if n.endswith(".bin"))
        except OSError:
            return 0


# ---------------------------------------------------------------------------
# process-wide active cache
# ---------------------------------------------------------------------------

def configure(dir: Optional[str] = None,
              max_bytes: Optional[int] = None) -> Optional[CompileCache]:
    """Set (or with ``dir=None`` disable) this process's persistent
    compile cache; overrides the env var. Returns the active cache."""
    global _ACTIVE, _ENV_CHECKED
    with _MU:
        _ENV_CHECKED = True
        _ACTIVE = CompileCache(dir, max_bytes=max_bytes) if dir \
            else None
        return _ACTIVE


def active() -> Optional[CompileCache]:
    """The process's active cache, lazily picked up from
    ``PADDLE_TPU_COMPILE_CACHE_DIR`` on first use (the launcher stamps
    one shared dir per fleet); None when disabled."""
    global _ACTIVE, _ENV_CHECKED
    if _ENV_CHECKED:
        return _ACTIVE
    with _MU:
        if not _ENV_CHECKED:
            _ENV_CHECKED = True
            path = os.environ.get(ENV_DIR)
            if path:
                try:
                    mb = int(os.environ.get(ENV_MAX_BYTES, "0")) or None
                except ValueError:
                    mb = None
                try:
                    _ACTIVE = CompileCache(path, max_bytes=mb)
                except OSError as e:
                    # a bad/read-only fleet-stamped dir must degrade
                    # to cache-disabled, not crash the first compile —
                    # the cache is strictly additive (explicit
                    # configure() still raises: the caller asked)
                    _obs.emit("compile_cache_unavailable", dir=path,
                              error=repr(e))
                    _ACTIVE = None
        return _ACTIVE


def resolve_root(explicit: Optional[str] = None) -> str:
    """Where persistent compile state lives: ``explicit`` if given,
    else ``JAX_COMPILATION_CACHE_DIR``, else ``.jax_cache/`` in the
    checkout."""
    return os.path.abspath(explicit or os.environ.get(JAX_ENV_DIR)
                           or _CHECKOUT_ROOT)


def store_dir() -> str:
    """This store's default directory: under ``resolve_root()``."""
    return os.path.join(resolve_root(), STORE_SUBDIR)


def enable(dir: Optional[str] = None,
           max_bytes: Optional[int] = None) -> str:
    """Turn on both persistent caches under one root and return it:
    JAX's compilation cache in ``resolve_root(dir)`` and this
    module's executable store in its ``STORE_SUBDIR``. Call before the
    first compile — JAX latches its cache directory then. When the
    root came from ``JAX_COMPILATION_CACHE_DIR`` JAX has already read
    it, and this sets nothing."""
    root = resolve_root(dir)
    if dir or not os.environ.get(JAX_ENV_DIR):
        import jax
        jax.config.update("jax_compilation_cache_dir", root)
    configure(os.path.join(root, STORE_SUBDIR), max_bytes=max_bytes)
    return root


def stats() -> Optional[dict]:
    """Stats of the active cache (None when disabled) — what
    ``Executor.telemetry()`` surfaces under ``compile_cache``."""
    c = active()
    return c.stats() if c is not None else None


def reset_stats():
    """Zero the cache counters (tests/bench probes)."""
    c = active()
    if c is None:
        return
    for m in (c._m_hit, c._m_miss, c._m_store, c._m_evict,
              c._m_bytes_in, c._m_bytes_out, c._h_load):
        m.reset()
