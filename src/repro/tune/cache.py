"""Persistent schedule cache — the autotuner's memory.

A JSON artifact maps a *canonical scene signature* (problem dims + dtype +
backend + tuner code version) to the tuned record produced by
``tune/autotune.py``.  Layered:

  disk   JSON file, merge-on-save (concurrent tuning runs union their
         results; on key collision higher measurement fidelity wins, then
         the faster measured choice), atomic tmp+rename write;
  memory an LRU-bounded dict fronting the file, with hit/miss counters so
         tests (and the ``schedule="auto"`` dispatch path) can observe
         resolution behavior.

Path resolution order: explicit argument > ``$REPRO_TUNE_CACHE`` >
``~/.cache/repro/tune_cache.json``.
"""
from __future__ import annotations

import collections
import json
import os
import sys
import tempfile
import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.core.mapping import SCHEDULES, ScheduleChoice
from repro.core.scene import ConvScene
from repro.kernels import interpret_mode
from repro.obs.metrics import default_metrics
from repro.obs.trace import default_tracer

# Bump when kernels / the measurement harness change meaning of cached µs.
CODE_VERSION = "mg3m-tune-v1"
ENV_VAR = "REPRO_TUNE_CACHE"
DEFAULT_PATH = os.path.join("~", ".cache", "repro", "tune_cache.json")
_SCHEMA = 1


def resolve_cache_path(path: Optional[str] = None) -> str:
    """Explicit path > $REPRO_TUNE_CACHE > ~/.cache default."""
    p = path or os.environ.get(ENV_VAR) or DEFAULT_PATH
    return os.path.abspath(os.path.expanduser(p))


def default_backend() -> str:
    """Backend tag for cache keys, derived like the kernel mode: timings
    in the Pallas interpreter are not timings on a real TPU, so they must
    never alias."""
    base = jax.default_backend()
    return f"{base}+interpret" if interpret_mode() else base


def scene_signature(scene: ConvScene, *, backend: str,
                    version: str = CODE_VERSION) -> str:
    """Canonical cache key for a scene.

    Stable across cosmetic aliases of the same problem — notably dtype
    spellings (``"float32"`` / ``"<f4"`` / ``"f4"`` all canonicalize through
    ``jnp.dtype().name``) — and explicit about everything that changes the
    measured answer: every geometric dim, dtype, backend, code version.
    The dilation axes (lhs/rhs dilation + asymmetric padding — the backward
    scenes of strided forwards) are appended only when active, so every
    pre-dilation cache entry keeps its exact key.
    """
    dt = jnp.dtype(scene.dtype).name
    return (f"v={version}|be={backend}|dt={dt}"
            f"|B={scene.B}|IC={scene.IC}|OC={scene.OC}"
            f"|in={scene.inH}x{scene.inW}|flt={scene.fltH}x{scene.fltW}"
            f"|pad={scene.padH},{scene.padW}|std={scene.stdH},{scene.stdW}"
            f"{scene.dilation_suffix()}")


def parse_signature(key: str) -> Dict[str, str]:
    """Split a ``scene_signature`` key into its ``field=value`` parts."""
    parts = {}
    for tok in key.split("|"):
        field, _, value = tok.partition("=")
        parts[field] = value
    return parts


def scene_from_signature(key: str) -> ConvScene:
    """Inverse of ``scene_signature`` (sans backend/version): rebuild the
    scene a cache entry was tuned for, so calibration can re-derive the cost
    terms of stored records without a side-channel scene table.  The
    dilation fields are optional in the key (absent = undilated)."""
    p = parse_signature(key)
    inH, inW = p["in"].split("x")
    fltH, fltW = p["flt"].split("x")
    padH, padW = p["pad"].split(",")
    stdH, stdW = p["std"].split(",")
    extra = {}
    if "dil" in p:
        dilH, dilW = p["dil"].split(",")
        extra.update(dilH=int(dilH), dilW=int(dilW))
    if "fdil" in p:
        fdilH, fdilW = p["fdil"].split(",")
        extra.update(fdilH=int(fdilH), fdilW=int(fdilW))
    if "apad" in p:
        apadH, apadW = p["apad"].split(",")
        extra.update(apadH=int(apadH), apadW=int(apadW))
    return ConvScene(B=int(p["B"]), IC=int(p["IC"]), OC=int(p["OC"]),
                     inH=int(inH), inW=int(inW), fltH=int(fltH),
                     fltW=int(fltW), padH=int(padH), padW=int(padW),
                     stdH=int(stdH), stdW=int(stdW), dtype=p["dt"], **extra)


def choice_to_dict(choice: ScheduleChoice) -> Dict:
    return {
        "schedule": choice.schedule, "bm": choice.bm, "bn": choice.bn,
        "bk": choice.bk, "predicted_s": choice.predicted_s,
        "compute_s": choice.compute_s, "hbm_s": choice.hbm_s,
        "vmem_bytes": choice.vmem_bytes, "notes": choice.notes,
    }


def choice_from_dict(d: Dict) -> ScheduleChoice:
    return ScheduleChoice(
        schedule=d["schedule"], bm=int(d["bm"]), bn=int(d["bn"]),
        bk=int(d["bk"]), predicted_s=float(d["predicted_s"]),
        compute_s=float(d["compute_s"]), hbm_s=float(d["hbm_s"]),
        vmem_bytes=int(d["vmem_bytes"]), notes=d.get("notes", ""),
    )


_REQUIRED_CHOICE_KEYS = ("schedule", "bm", "bn", "bk", "predicted_s",
                         "compute_s", "hbm_s", "vmem_bytes")


def valid_record(rec) -> bool:
    """Schema check for one tuned record as stored in the JSON artifact.

    A hand-edited, truncated, or old-schema entry must be skipped on
    load/merge rather than detonate as a ``KeyError`` on the
    ``schedule="auto"`` hot path the first time its scene is resolved.
    """
    if not isinstance(rec, dict):
        return False
    ch = rec.get("choice")
    if not isinstance(ch, dict) or any(k not in ch
                                       for k in _REQUIRED_CHOICE_KEYS):
        return False
    if ch["schedule"] not in SCHEDULES:
        return False
    if not isinstance(rec.get("measured_us", 0.0), (int, float)):
        return False
    try:
        choice_from_dict(ch)
    except (KeyError, TypeError, ValueError):
        return False
    return True


def _beats(rec: Dict, mine: Dict) -> bool:
    """Collision rule: higher measurement fidelity wins (an exact-scene
    timing beats any proxy-capped one — their µs are not comparable);
    at equal fidelity the faster measured choice wins."""
    rank = lambda r: (r.get("proxy") is not None,
                      r.get("measured_us", float("inf")))
    return rank(rec) < rank(mine)


class ScheduleCache:
    """LRU-fronted persistent map: scene signature -> tuned record dict."""

    def __init__(self, path: Optional[str] = None, *, max_entries: int = 4096):
        self.path = resolve_cache_path(path)
        self.max_entries = max_entries
        self._mem: "collections.OrderedDict[str, Dict]" = collections.OrderedDict()
        self.hits = 0
        self.misses = 0
        if os.path.exists(self.path):
            # Tolerant on construction: a half-written artifact must not
            # brick the schedule="auto" hot path (explicit load() is strict).
            try:
                self.load()
            except (json.JSONDecodeError, OSError) as e:
                print(f"repro.tune: ignoring unreadable cache {self.path}: {e}",
                      file=sys.stderr)

    def __len__(self) -> int:
        return len(self._mem)

    def records(self) -> Dict[str, Dict]:
        """Snapshot of signature -> record (calibration's training data)."""
        return dict(self._mem)

    # -- key plumbing ------------------------------------------------------
    def key(self, scene: ConvScene, backend: Optional[str] = None) -> str:
        return scene_signature(scene, backend=backend or default_backend())

    # -- memory layer ------------------------------------------------------
    def get(self, scene: ConvScene, backend: Optional[str] = None
            ) -> Optional[Dict]:
        """Tuned record for a scene, or None on miss (LRU-touching)."""
        k = self.key(scene, backend)
        rec = self._mem.get(k)
        if rec is None:
            self.misses += 1
            default_metrics().counter("repro.tune.cache.misses").inc()
            return None
        self._mem.move_to_end(k)
        self.hits += 1
        default_metrics().counter("repro.tune.cache.hits").inc()
        return rec

    def get_choice(self, scene: ConvScene, backend: Optional[str] = None
                   ) -> Optional[ScheduleChoice]:
        rec = self.get(scene, backend)
        return choice_from_dict(rec["choice"]) if rec else None

    def put(self, scene: ConvScene, record: Dict,
            backend: Optional[str] = None) -> str:
        k = self.key(scene, backend)
        self._mem[k] = record
        self._mem.move_to_end(k)
        self._evict()
        return k

    def _evict(self) -> None:
        while len(self._mem) > self.max_entries:
            self._mem.popitem(last=False)  # evict least-recently used

    # -- disk layer --------------------------------------------------------
    def load(self, path: Optional[str] = None) -> int:
        """Merge entries from a JSON artifact into memory; returns count."""
        p = resolve_cache_path(path) if path else self.path
        m = default_metrics()
        m.counter("repro.tune.cache.loads").inc()
        t0 = time.perf_counter()
        with default_tracer().span("repro.tune.cache.load", path=p), \
                open(p) as f:
            doc = json.load(f)
        m.histogram("repro.tune.cache.load_s").observe(
            time.perf_counter() - t0)
        entries = doc.get("entries", {})
        bad = {k for k, rec in entries.items() if not valid_record(rec)}
        if bad:
            print(f"repro.tune: skipping {len(bad)} malformed cache "
                  f"entr{'y' if len(bad) == 1 else 'ies'} in {p} "
                  f"(first: {sorted(bad)[0]!r})", file=sys.stderr)
        for k, rec in entries.items():
            if k not in bad:
                self._merge_entry(k, rec)
        self._evict()
        return len(entries) - len(bad)

    def _merge_entry(self, k: str, rec: Dict) -> None:
        mine = self._mem.get(k)
        if mine is None or _beats(rec, mine):
            self._mem[k] = rec

    def save(self, path: Optional[str] = None) -> str:
        """Merge-on-save: union with whatever is on disk, write atomically.

        The union happens in the artifact only — disk entries beyond the
        LRU bound are preserved on disk without inflating memory."""
        p = resolve_cache_path(path) if path else self.path
        m = default_metrics()
        m.counter("repro.tune.cache.saves").inc()
        t0 = time.perf_counter()
        with default_tracer().span("repro.tune.cache.save", path=p):
            entries = dict(self._mem)
            if os.path.exists(p):
                try:
                    with open(p) as f:
                        doc = json.load(f)
                    disk = (doc.get("entries", {})
                            if isinstance(doc, dict) else {})
                    for k, rec in (disk
                                   if isinstance(disk, dict) else {}).items():
                        if not valid_record(rec):
                            continue   # drop malformed disk entries on save
                        if k not in entries or _beats(rec, entries[k]):
                            entries[k] = rec
                except (json.JSONDecodeError, OSError):
                    pass  # corrupt artifact: overwrite with our state
            os.makedirs(os.path.dirname(p), exist_ok=True)
            doc = {"schema": _SCHEMA, "version": CODE_VERSION,
                   "entries": entries}
            fd, tmp = tempfile.mkstemp(dir=os.path.dirname(p), suffix=".tmp")
            try:
                with os.fdopen(fd, "w") as f:
                    json.dump(doc, f, indent=1, sort_keys=True)
                os.replace(tmp, p)
            finally:
                if os.path.exists(tmp):
                    os.unlink(tmp)
        m.histogram("repro.tune.cache.save_s").observe(
            time.perf_counter() - t0)
        return p


# -- process-wide default cache (consulted by the schedule="auto" path) -----
_default: Optional[ScheduleCache] = None


def default_cache() -> ScheduleCache:
    global _default
    if _default is None:
        _default = ScheduleCache()
    return _default


def set_default_cache(cache: Optional[ScheduleCache]) -> None:
    """Install (or with None, reset) the process-wide cache — used by the
    tuning CLI after a batch run and by tests."""
    global _default
    _default = cache
