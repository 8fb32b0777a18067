"""Persistent filestore tier for the KV residency ladder (ISSUE 14).

The ladder so far: HBM (PageAllocator + PrefixCache) -> host RAM
(``HostPagePool``, PR 6) -> peer runner (request snapshots, PR 11).
This module adds the bottom rung: a **persistent, content-addressed
blob store** for full prefix-cache pages, backed by the same rooted
local-FS ``control.filestore.Filestore`` the control plane serves user
files from (a shared filesystem in production, a local dir in dev).

Why it exists: agent fleets replay the same system prompts for days.
The HBM prefix cache dies with the process and the host tier dies with
the host; the filestore tier survives restarts, so a rolling deploy (or
a brand-new decode-pool runner) serves a warm prefix without
recomputing it.

Contract (the degrade-to-local discipline):

- blobs are **content-addressed** by the engine's prefix-chain digest
  (``PrefixCache.page_hashes``) namespaced by model + KV geometry, so a
  blob can only ever be adopted by an engine whose pool it is
  bit-compatible with;
- every blob carries the same ``page_checksum`` digest the host tier
  and request snapshots use, verified on EVERY read BEFORE any engine
  state is touched — a corrupt or truncated blob is dropped, counted
  (``helix_filestore_kv_corrupt_total``) and treated as a miss: the
  prompt recomputes, it never errors and never attends wrong KV;
- writes are **quota'd per tenant** (PR 7 identity): the adopting
  request's tenant is charged; past ``HELIX_FILESTORE_KV_QUOTA_BYTES``
  new writes are rejected with a typed counter, reads are never gated.

The ``helix_filestore_kv_*`` metric family is minted ONLY here
(``tools/lint_metrics.py`` contract 10); the runner's /metrics calls
``collect_filestore_kv``.
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import threading
from typing import Optional

import numpy as np

log = logging.getLogger("helix.kv_filestore")

# ---------------------------------------------------------------------------
# metric vocabulary (lint_metrics contract 10: minted only in this module)
# ---------------------------------------------------------------------------

FILESTORE_KV_HITS = "helix_filestore_kv_hits_total"
FILESTORE_KV_MISSES = "helix_filestore_kv_misses_total"
FILESTORE_KV_CORRUPT = "helix_filestore_kv_corrupt_total"
FILESTORE_KV_STORES = "helix_filestore_kv_stores_total"
FILESTORE_KV_QUOTA_REJECTS = "helix_filestore_kv_quota_rejects_total"
FILESTORE_KV_STORE_DROPS = "helix_filestore_kv_store_drops_total"
FILESTORE_KV_BYTES = "helix_filestore_kv_bytes"

_PAGE_FIELDS = ("k", "v", "k_scale", "v_scale")


def kv_filestore_dir() -> str:
    """HELIX_FILESTORE_KV_DIR: root of the persistent KV blob store
    ('' = tier off)."""
    return os.environ.get("HELIX_FILESTORE_KV_DIR", "")


def kv_filestore_quota_bytes() -> int:
    """HELIX_FILESTORE_KV_QUOTA_BYTES: per-tenant write quota (0 =
    unlimited)."""
    try:
        return int(os.environ.get("HELIX_FILESTORE_KV_QUOTA_BYTES", "0")
                   or 0)
    except (TypeError, ValueError):
        return 0


def _encode_array(a) -> Optional[dict]:
    if a is None:
        return None
    import base64

    a = np.ascontiguousarray(a)
    return {
        "dtype": str(a.dtype),
        "shape": list(a.shape),
        "b64": base64.b64encode(a.tobytes()).decode("ascii"),
    }


def _decode_array(doc) -> Optional[np.ndarray]:
    if doc is None:
        return None
    import base64

    raw = base64.b64decode(doc["b64"])
    a = np.frombuffer(raw, dtype=np.dtype(doc["dtype"]))
    return a.reshape([int(d) for d in doc["shape"]]).copy()


class KVFilestore:
    """Content-addressed page-blob store over ``control.filestore``.

    Thread contract: ``contains``/``get``/``put`` run on the engine
    thread; the /metrics collector reads the counter snapshot from the
    scrape thread (plain GIL-atomic int reads)."""

    # blobs live under one reserved owner prefix in the backing store —
    # user file traffic and KV blobs can share a filestore root without
    # colliding (Filestore._resolve keeps owners disjoint)
    OWNER = "kv-pages"

    def __init__(self, root: str, namespace: str,
                 quota_bytes: Optional[int] = None):
        from helix_tpu.control.filestore import Filestore

        self.store = Filestore(root)
        # geometry namespace: blobs are only visible to bit-compatible
        # pools (model + page_size + layers + heads + head_dim + dtype)
        self.namespace = namespace
        self.quota_bytes = (
            quota_bytes if quota_bytes is not None
            else kv_filestore_quota_bytes()
        )
        self._lock = threading.Lock()
        # typed counters (the degrade ladder's observability)
        self.hits = 0
        self.misses = 0
        self.corrupt = 0
        self.stores = 0
        self.quota_rejects = 0
        self.store_drops = 0
        # single background writer for put_async (lazily started): the
        # engine thread must not pay D2H fetch + encode + disk latency
        # at adoption time
        self._writeq = None
        self._writer = None
        # positive-presence cache: contains() is called per page per
        # admission retry; misses fall through to the filesystem so
        # blobs written by a PEER process (shared filesystem) are found
        self._known: set = set()
        # per-tenant usage ledger, persisted next to the blobs so the
        # quota survives restarts (advisory across processes)
        self._usage: dict = self._load_usage()

    @staticmethod
    def namespace_for(model: str, page_size: int, num_layers: int,
                      kv_heads: int, head_dim: int, kv_dtype: str) -> str:
        h = hashlib.blake2b(digest_size=8)
        h.update(
            f"{model}|{page_size}|{num_layers}|{kv_heads}|{head_dim}|"
            f"{kv_dtype}".encode()
        )
        return h.hexdigest()

    # -- paths / ledger ----------------------------------------------------
    def _path(self, digest) -> str:
        d = digest.hex() if isinstance(digest, bytes) else str(digest)
        return f"{self.namespace}/{d[:2]}/{d}.json"

    def _usage_path(self) -> str:
        return f"{self.namespace}/usage.json"

    def _load_usage(self) -> dict:
        try:
            doc = json.loads(
                self.store.read(self.OWNER, self._usage_path())
            )
            return {str(k): int(v) for k, v in doc.items()}
        except FileNotFoundError:
            return {}
        except Exception:  # noqa: BLE001 — a mangled ledger resets, never errors
            return {}

    def _save_usage(self) -> None:
        try:
            self.store.write(
                self.OWNER, self._usage_path(),
                json.dumps(self._usage).encode(),
            )
        except OSError:
            log.warning("could not persist KV filestore usage ledger")

    def usage(self, tenant: str) -> int:
        with self._lock:
            return int(self._usage.get(tenant, 0))

    # -- blob operations ---------------------------------------------------
    def contains(self, digest) -> bool:
        d = digest.hex() if isinstance(digest, bytes) else str(digest)
        if d in self._known:
            return True
        try:
            self.store.stat(self.OWNER, self._path(d))
        except (FileNotFoundError, PermissionError, OSError):
            return False
        self._known.add(d)
        return True

    def get(self, digest) -> Optional[dict]:
        """The stored page entry for ``digest`` (the ``gather_pages``
        field layout, checksum-verified), or None on miss/corruption.
        A corrupt blob is DELETED and counted — the caller recomputes;
        the next writer re-stores a good copy."""
        from helix_tpu.engine.kv_cache import page_checksum

        d = digest.hex() if isinstance(digest, bytes) else str(digest)
        try:
            raw = self.store.read(self.OWNER, self._path(d))
        except (FileNotFoundError, PermissionError, OSError):
            self.misses += 1
            self._known.discard(d)
            return None
        try:
            doc = json.loads(raw)
            entry = {
                f: _decode_array((doc.get("page") or {}).get(f))
                for f in _PAGE_FIELDS
            }
            claimed = str(doc.get("checksum", ""))
            # "v" is None in a latent pool's page (one array); the
            # namespace keeps the two kinds of pool apart
            if entry["k"] is None:
                raise ValueError("page missing its k buffer")
            if page_checksum(entry).hex() != claimed:
                raise ValueError("page checksum mismatch")
        except Exception as e:  # noqa: BLE001 — corrupt blob = typed miss
            self.corrupt += 1
            self._known.discard(d)
            log.warning(
                "dropping corrupt KV filestore blob %s: %s", d, e
            )
            try:
                self.store.delete(self.OWNER, self._path(d))
            except (PermissionError, OSError):
                pass
            return None
        self.hits += 1
        self._known.add(d)
        return entry

    def put(self, digest, entry: dict, tenant: str = "") -> bool:
        """Store one page blob, charged to ``tenant``'s quota.  False =
        not stored (already present is True, quota reject is False with
        a typed counter).  Never raises into the engine."""
        from helix_tpu.engine.kv_cache import page_checksum

        d = digest.hex() if isinstance(digest, bytes) else str(digest)
        if self.contains(d):
            return True
        charged = 0
        try:
            host = {
                f: None if entry.get(f) is None
                else np.asarray(entry[f])
                for f in _PAGE_FIELDS
            }
            doc = {
                "namespace": self.namespace,
                "tenant": tenant,
                "checksum": page_checksum(host).hex(),
                "page": {
                    f: _encode_array(host[f]) for f in _PAGE_FIELDS
                },
            }
            raw = json.dumps(doc).encode()
            with self._lock:
                if self.quota_bytes and (
                    self._usage.get(tenant, 0) + len(raw)
                    > self.quota_bytes
                ):
                    self.quota_rejects += 1
                    return False
                self._usage[tenant] = (
                    self._usage.get(tenant, 0) + len(raw)
                )
                charged = len(raw)
            self.store.write(self.OWNER, self._path(d), raw)
            self._save_usage()
        except Exception:  # noqa: BLE001 — the tier degrades, never errors
            if charged:
                # the blob never landed: un-charge the tenant, or
                # repeated write failures would eat the quota with
                # nothing stored against it
                with self._lock:
                    self._usage[tenant] = max(
                        0, self._usage.get(tenant, 0) - charged
                    )
            log.exception("KV filestore store failed for %s", d)
            return False
        self.stores += 1
        self._known.add(d)
        return True

    def put_async(self, digest, entry: dict, tenant: str = "") -> None:
        """Queue ``put`` on the store's single writer thread.  The
        engine calls this at adoption time with still-on-device arrays;
        the worker pays the D2H fetch (``np.asarray`` inside ``put``),
        the encode, and the disk write so the serving hot path never
        stalls on the persistent tier.  Bounded queue: under sustained
        pressure writes DROP with a typed counter — the tier degrades
        (a dropped page is just a future miss), serving never blocks."""
        import queue as _queue

        with self._lock:
            if self._writer is None:
                self._writeq = _queue.Queue(maxsize=256)
                self._writer = threading.Thread(
                    target=self._write_loop, daemon=True,
                    name="kv-filestore-writer",
                )
                self._writer.start()
        try:
            self._writeq.put_nowait((digest, entry, tenant))
        except _queue.Full:
            self.store_drops += 1

    def _write_loop(self) -> None:
        while True:
            digest, entry, tenant = self._writeq.get()
            try:
                self.put(digest, entry, tenant=tenant)
            except Exception:  # noqa: BLE001 — the tier degrades, never dies
                log.exception(
                    "async KV filestore store failed for %s", digest
                )
            finally:
                self._writeq.task_done()

    def flush(self, timeout: float = 30.0) -> None:
        """Block until every queued async write has landed (tests and
        graceful shutdown — NOT the serving path)."""
        import time as _time

        q = self._writeq
        if q is None:
            return
        deadline = _time.monotonic() + timeout
        while q.unfinished_tasks and _time.monotonic() < deadline:
            _time.sleep(0.005)

    # -- observability -----------------------------------------------------
    def total_bytes(self) -> int:
        with self._lock:
            return sum(self._usage.values())

    def stats(self) -> dict:
        return {
            "hits": self.hits,
            "misses": self.misses,
            "corrupt": self.corrupt,
            "stores": self.stores,
            "quota_rejects": self.quota_rejects,
            "store_drops": self.store_drops,
            "bytes": self.total_bytes(),
            "quota_bytes": self.quota_bytes,
            "namespace": self.namespace,
        }


def filestore_for_engine(root: str, model_cfg, cache_cfg,
                         quota_bytes: Optional[int] = None) -> KVFilestore:
    """Bind a store to one engine's KV geometry (the namespace that
    makes content addressing safe across mixed fleets)."""
    # as a snapshot states the pool: a latent pool's pages are one array
    ns = KVFilestore.namespace_for(
        model_cfg.name, cache_cfg.page_size, model_cfg.num_layers,
        *cache_cfg.geometry(model_cfg), cache_cfg.dtype,
    )
    return KVFilestore(root, ns, quota_bytes=quota_bytes)


def collect_filestore_kv(c, loop, labels: dict) -> None:
    """Runner-side filestore-tier series for one engine loop (called
    from the OpenAI server's scrape-time collector; no-op when the tier
    is off)."""
    fs = getattr(loop.engine, "kv_filestore", None)
    if fs is None:
        return
    c.counter(
        FILESTORE_KV_HITS, fs.hits, labels,
        help="Prefix pages restored from the persistent filestore tier",
    )
    c.counter(
        FILESTORE_KV_MISSES, fs.misses, labels,
        help="Filestore lookups that found no blob (prompt recomputed)",
    )
    c.counter(
        FILESTORE_KV_CORRUPT, fs.corrupt, labels,
        help="Corrupt/truncated blobs dropped pre-adoption "
             "(recompute, never an error)",
    )
    c.counter(
        FILESTORE_KV_STORES, fs.stores, labels,
        help="Full prefix pages persisted to the filestore tier",
    )
    c.counter(
        FILESTORE_KV_QUOTA_REJECTS, fs.quota_rejects, labels,
        help="Writes rejected by the per-tenant filestore quota",
    )
    c.counter(
        FILESTORE_KV_STORE_DROPS, fs.store_drops, labels,
        help="Async write-throughs dropped at the bounded writer queue",
    )
    c.gauge(
        FILESTORE_KV_BYTES, fs.total_bytes(), labels,
        help="Bytes of KV blobs this engine's namespace holds",
    )
