"""Persistent AOT-compiled executable store for the serving engine.

Cold-starting a replica (or crash-restarting one through the router's
factory) pays full XLA compilation for every `_fns` entry the engine
touches — on real topologies that is minutes of stall before the first
token. This module turns that stall into a disk read: compiled
executables are serialized with `jax.experimental.serialize_executable`
and parked in an on-disk store keyed by a sha256 over the SAME
structural cache-key parts graftlint already audits (`unstable-cache-key`
— no repr()/id()/f-strings may reach a key) plus a device/topology/
jax-version fingerprint and a hash of the package source tree.

Safety contract: a stale, corrupt, torn or foreign-topology entry
degrades SILENTLY to a fresh compile — `load()` never raises and never
returns an executable whose manifest, payload checksum or device
fingerprint fails verification. Writes reuse the checkpoint idiom
(stage to a hidden sibling tmp file, fsync, rename; payload first,
manifest LAST so the manifest's presence is the commit point) — a torn
write can never be loaded.

Store layout (flat directory)::

    <root>/<key>.exec   pickled {payload, in_tree, out_tree, device_ids}
    <root>/<key>.json   manifest: schema, family, byte count,
                        payload sha256, device fingerprint, timestamps

`perf.CompileTimed` consults the store before lowering and accounts
the outcome on `paddle_tpu_compile_total{family,outcome=disk_hit|compile}`.
`tools/exec_cache.py` is the operator CLI (list / --verify / --prune).
"""
from __future__ import annotations

import hashlib
import json
import os
import pickle
import threading
import time
import uuid
from typing import Any, Dict, List, Optional, Tuple

from ..utils.fs import fsync_dir

__all__ = [
    "ExecCache", "fingerprint", "device_fingerprint",
    "code_fingerprint", "SCHEMA_VERSION", "ENV_DIR", "default_dir",
]

SCHEMA_VERSION = 2
#: environment variable naming the default store directory; when unset
#: the engine runs without a persistent cache.
ENV_DIR = "PADDLE_TPU_EXEC_CACHE"

_PAYLOAD_EXT = ".exec"
_MANIFEST_EXT = ".json"


def default_dir() -> Optional[str]:
    """The store directory named by ``PADDLE_TPU_EXEC_CACHE`` (or None:
    persistent caching disabled)."""
    d = os.environ.get(ENV_DIR)
    return d or None


def _plain(v):
    """Coerce key parts to canonical-JSON-safe plain data. Tuples
    become lists; any type without a stable value representation is a
    TypeError — the runtime twin of graftlint's unstable-cache-key
    rule (never fall back to repr())."""
    if v is None or isinstance(v, (bool, int, float, str)):
        return v
    if isinstance(v, bytes):
        return "hex:" + v.hex()
    if isinstance(v, (list, tuple)):
        return [_plain(x) for x in v]
    if isinstance(v, dict):
        out = {}
        for k, x in v.items():
            if not isinstance(k, str):
                raise TypeError(
                    "exec-cache key part has non-string dict key: "
                    + type(k).__name__)
            out[k] = _plain(x)
        return out
    raise TypeError(
        "exec-cache key part of unstable type " + type(v).__name__
        + " — keys must be built from plain value-comparable data")


def fingerprint(parts: Dict[str, Any]) -> str:
    """sha256 hex digest of the canonical JSON encoding of `parts`.
    This IS the on-disk key: two processes building structurally equal
    parts land on the same entry; any unstable component raises
    instead of silently keying per-process."""
    blob = json.dumps(_plain(parts), sort_keys=True,
                      separators=(",", ":"))
    return hashlib.sha256(blob.encode("utf-8")).hexdigest()


def device_fingerprint(mesh=None) -> Dict[str, Any]:
    """Structural identity of the runtime an executable was compiled
    for: jax/jaxlib versions, backend platform + device kind, local
    device population, process count, and (when the engine shards over
    a sub-mesh) the mesh axes/shape/device ids. An entry whose
    fingerprint differs from the loader's is FOREIGN and is never
    deserialized."""
    import jax

    devs = jax.local_devices()
    fp: Dict[str, Any] = {
        "jax": jax.__version__,
        "jaxlib": getattr(
            __import__("jaxlib"), "__version__", "unknown"),
        "platform": devs[0].platform if devs else "none",
        "device_kind": devs[0].device_kind if devs else "none",
        "n_local_devices": len(devs),
        "process_count": jax.process_count(),
    }
    if mesh is not None:
        fp["mesh_axes"] = [str(a) for a in mesh.axis_names]
        fp["mesh_shape"] = [int(s) for s in mesh.devices.shape]
        fp["mesh_device_ids"] = sorted(
            int(d.id) for d in mesh.devices.flat)
    return fp


_CODE_FP_LOCK = threading.Lock()
_CODE_FP: Optional[str] = None


def code_fingerprint() -> str:
    """sha256 over every .py source file in the paddle_tpu package.
    Any source change invalidates every entry: a persisted executable
    traced from old code must never serve for new code (that would be
    a silently WRONG executable, the one failure mode this store is
    forbidden to have). Computed once per process."""
    global _CODE_FP
    with _CODE_FP_LOCK:
        if _CODE_FP is not None:
            return _CODE_FP
        pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        h = hashlib.sha256()
        for dirpath, dirnames, files in sorted(os.walk(pkg)):
            dirnames[:] = sorted(
                d for d in dirnames if d != "__pycache__")
            for fn in sorted(files):
                if not fn.endswith(".py"):
                    continue
                rel = os.path.relpath(os.path.join(dirpath, fn), pkg)
                h.update(rel.encode("utf-8"))
                h.update(b"\0")
                try:
                    with open(os.path.join(dirpath, fn), "rb") as f:
                        h.update(f.read())
                except OSError:
                    h.update(b"<unreadable>")
                h.update(b"\0")
        _CODE_FP = h.hexdigest()
        return _CODE_FP


_KEY_OK = frozenset("0123456789abcdef")


def _valid_key(key: str) -> bool:
    return (isinstance(key, str) and 8 <= len(key) <= 128
            and set(key) <= _KEY_OK)


class ExecCache:
    """On-disk executable store. All methods are best-effort and
    exception-free at the load path: anything wrong with an entry
    (torn write, bit rot, schema drift, foreign topology, jax unable
    to deserialize) counts as a miss. `stats()` exposes plain counters
    so callers/tests can pin WHY a load missed."""

    def __init__(self, root: str):
        self.root = os.path.abspath(root)
        os.makedirs(self.root, exist_ok=True)
        self._lock = threading.Lock()
        self.counters = {
            "hits": 0, "misses": 0, "corrupt": 0, "foreign": 0,
            "saves": 0, "save_errors": 0,
        }

    # -- paths ---------------------------------------------------------
    def _payload_path(self, key: str) -> str:
        return os.path.join(self.root, key + _PAYLOAD_EXT)

    def _manifest_path(self, key: str) -> str:
        return os.path.join(self.root, key + _MANIFEST_EXT)

    def _bump(self, name: str) -> None:
        with self._lock:
            self.counters[name] += 1

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return dict(self.counters)

    # -- write path ----------------------------------------------------
    def save(self, key: str, compiled, *, family: str = "",
             device: Optional[Dict[str, Any]] = None) -> bool:
        """Serialize `compiled` (a jax Compiled) under `key`.
        Atomic: payload staged+fsynced+renamed first, manifest LAST —
        readers treat the manifest as the commit record, so a crash at
        any point leaves either no entry or a complete one. Returns
        False (never raises) when serialization or IO fails."""
        if not _valid_key(key):
            self._bump("save_errors")
            return False
        try:
            from jax.experimental import serialize_executable as se
            payload, in_tree, out_tree = se.serialize(compiled)
            # the devices it runs on: the loader would otherwise assume
            # every device of the backend
            device_ids = [d.id for d in
                          compiled.runtime_executable().local_devices()]
            blob = pickle.dumps(
                {"payload": payload, "in_tree": in_tree,
                 "out_tree": out_tree, "device_ids": device_ids},
                protocol=pickle.HIGHEST_PROTOCOL)
            manifest = {
                "schema": SCHEMA_VERSION,
                "key": key,
                "family": family,
                "payload_bytes": len(blob),
                "payload_sha256": hashlib.sha256(blob).hexdigest(),
                "device": device if device is not None
                else device_fingerprint(),
                "created_unix": time.time(),
            }
            self._commit(key, blob, manifest)
        except Exception:
            self._bump("save_errors")
            return False
        self._bump("saves")
        return True

    def _commit(self, key: str, blob: bytes, manifest: dict) -> None:
        suffix = ".tmp-%d-%s" % (os.getpid(), uuid.uuid4().hex[:8])
        ptmp = self._payload_path(key) + suffix
        mtmp = self._manifest_path(key) + suffix
        try:
            with open(ptmp, "wb") as f:
                f.write(blob)
                f.flush()
                os.fsync(f.fileno())
            os.replace(ptmp, self._payload_path(key))
            with open(mtmp, "w", encoding="utf-8") as f:
                json.dump(manifest, f)
                f.flush()
                os.fsync(f.fileno())
            os.replace(mtmp, self._manifest_path(key))
            fsync_dir(self.root)
        except BaseException:
            for t in (ptmp, mtmp):
                try:
                    os.unlink(t)
                except OSError:
                    pass
            raise

    # -- read path -----------------------------------------------------
    def verify(self, key: str,
               device: Optional[Dict[str, Any]] = None
               ) -> Tuple[bool, str]:
        """Integrity check without deserializing into a live
        executable. Returns (ok, reason) — reason is '' when ok, else
        one of missing/corrupt/foreign with detail."""
        if not _valid_key(key):
            return False, "corrupt: malformed key"
        mpath = self._manifest_path(key)
        ppath = self._payload_path(key)
        try:
            with open(mpath, "r", encoding="utf-8") as f:
                manifest = json.load(f)
        except (OSError, ValueError):
            return False, "missing: no readable manifest"
        if not isinstance(manifest, dict) or \
                manifest.get("schema") != SCHEMA_VERSION:
            return False, "corrupt: schema mismatch"
        if manifest.get("key") != key:
            return False, "corrupt: manifest/key mismatch"
        try:
            with open(ppath, "rb") as f:
                blob = f.read()
        except OSError:
            return False, "missing: no payload"
        if len(blob) != manifest.get("payload_bytes") or \
                hashlib.sha256(blob).hexdigest() != \
                manifest.get("payload_sha256"):
            return False, "corrupt: payload checksum mismatch"
        if device is not None and manifest.get("device") != _plain(device):
            return False, "foreign: device fingerprint mismatch"
        return True, ""

    def load(self, key: str,
             device: Optional[Dict[str, Any]] = None):
        """Return a live Compiled for `key`, or None. Every failure
        mode — absent, torn, corrupt, foreign topology, deserializer
        exception — is a silent miss; the caller falls through to a
        fresh compile."""
        try:
            ok, why = self.verify(key, device=device)
            if not ok:
                if why.startswith("corrupt"):
                    self._bump("corrupt")
                elif why.startswith("foreign"):
                    self._bump("foreign")
                self._bump("misses")
                return None
            with open(self._payload_path(key), "rb") as f:
                rec = pickle.loads(f.read())
            import jax
            from jax.experimental import serialize_executable as se
            by_id = {d.id: d for d in jax.devices()}
            compiled = se.deserialize_and_load(
                rec["payload"], rec["in_tree"], rec["out_tree"],
                execution_devices=[by_id[i] for i in rec["device_ids"]])
        except Exception:
            self._bump("corrupt")
            self._bump("misses")
            return None
        self._bump("hits")
        return compiled

    # -- operator surface (tools/exec_cache.py) ------------------------
    def keys(self) -> List[str]:
        out = []
        try:
            names = os.listdir(self.root)
        except OSError:
            return out
        for n in names:
            if n.endswith(_MANIFEST_EXT) and ".tmp-" not in n:
                k = n[:-len(_MANIFEST_EXT)]
                if _valid_key(k):
                    out.append(k)
        return sorted(out)

    def entries(self) -> List[Dict[str, Any]]:
        """Manifest records for listing: key, family, bytes, device
        fingerprint, age. Unreadable manifests are reported with
        family='<corrupt>' so the operator sees them."""
        now = time.time()
        recs = []
        for k in self.keys():
            try:
                with open(self._manifest_path(k), "r",
                          encoding="utf-8") as f:
                    m = json.load(f)
                recs.append({
                    "key": k,
                    "family": m.get("family", ""),
                    "payload_bytes": int(m.get("payload_bytes", 0)),
                    "device": m.get("device", {}),
                    "age_s": max(0.0, now - float(
                        m.get("created_unix", now))),
                })
            except (OSError, ValueError, TypeError):
                recs.append({"key": k, "family": "<corrupt>",
                             "payload_bytes": 0, "device": {},
                             "age_s": 0.0})
        return recs

    def remove(self, key: str) -> None:
        for p in (self._manifest_path(key), self._payload_path(key)):
            try:
                os.unlink(p)
            except OSError:
                pass

    def prune(self, max_age_s: Optional[float] = None,
              max_bytes: Optional[int] = None) -> List[str]:
        """Drop entries older than `max_age_s`, then (oldest-first)
        until the store fits under `max_bytes`. Manifest removed
        first so a concurrent reader can never commit to a pruned
        payload. Returns removed keys."""
        removed = []
        recs = self.entries()
        if max_age_s is not None:
            for r in recs:
                if r["age_s"] > max_age_s or r["family"] == "<corrupt>":
                    self.remove(r["key"])
                    removed.append(r["key"])
            recs = [r for r in recs if r["key"] not in set(removed)]
        if max_bytes is not None:
            total = sum(r["payload_bytes"] for r in recs)
            for r in sorted(recs, key=lambda r: -r["age_s"]):
                if total <= max_bytes:
                    break
                self.remove(r["key"])
                removed.append(r["key"])
                total -= r["payload_bytes"]
        # stale staging files from crashed writers (older than 1h)
        try:
            now = time.time()
            for n in os.listdir(self.root):
                if ".tmp-" in n:
                    p = os.path.join(self.root, n)
                    try:
                        if now - os.path.getmtime(p) > 3600.0:
                            os.unlink(p)
                    except OSError:
                        pass
        except OSError:
            pass
        return removed
