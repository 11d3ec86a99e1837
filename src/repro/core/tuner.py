"""Tuna tuner — the public entry point tying Eq. (1) together:

    argmin_{t ∈ T_e}  c(f(g(e, t), a))

``tune(space, target)`` runs the ES search (Alg. 4) with the static cost
model as fitness; ``rank_space`` exhaustively scores a space (used by the
top-k experiments and by the kernel library's block-spec picker, whose spaces
are small). Results are memoised per (space signature, target) so model code
can call ``tuned_matmul_blocks`` at trace time for free.

Persistence: because scores are pure functions of (op signature, target,
cost-model version), both entry points consult the ``repro.tuna`` schedule
database before searching and write back on miss. ``db`` arguments accept a
``ScheduleDatabase``, a path, ``None`` (= the process default set via
``set_default_db`` / the ``REPRO_TUNA_DB`` env var), or ``False`` (bypass —
used by the orchestrator, which manages its own store). An immutable
serving snapshot (``repro.tuna.cache.ScheduleCache``, installed via
``set_default_cache`` / ``$REPRO_TUNA_CACHE``) is consulted before the DB
on every read — the lock-free hot path for serving processes.
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
import json
import os
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro.core import cost_model, es
from repro.core.cost_model import COST_MODEL_VERSION
from repro.core.spaces import MatmulSpace, Space
from repro.hw import resolve_target
from repro.hw.target import HardwareTarget

_UNSET = object()
_DEFAULT_DB = _UNSET  # _UNSET = fall back to $REPRO_TUNA_DB; None = off
_DEFAULT_CACHE = _UNSET  # _UNSET = fall back to $REPRO_TUNA_CACHE
_DEFAULT_BUNDLE = _UNSET  # _UNSET = fall back to $REPRO_TUNA_BUNDLE
_DEFAULT_LEARNED = _UNSET  # _UNSET = fall back to $REPRO_TUNA_LEARNED
_DEFAULT_CACHE_PATH: Optional[str] = None  # where the default snapshot was
#                                   installed from — what hot reload rechecks
_PATH_DBS: Dict[str, object] = {}  # abspath -> ScheduleDatabase (one load
#                                    per path per process, not per call)
_PATH_CACHES: Dict[str, object] = {}  # abspath -> ScheduleCache snapshot
_MEMO_CLEARERS: List = []  # block-spec lru cache_clear hooks (kernels/ops
#                            registers tuned_flash_blocks here — tuner can't
#                            import kernels, which pulls in jax)


def register_memo_clearer(fn) -> None:
    _MEMO_CLEARERS.append(fn)


def _clear_memos() -> None:
    tuned_matmul_blocks.cache_clear()
    for fn in _MEMO_CLEARERS:
        fn()


def _open_db(path):
    key = os.path.abspath(os.fspath(path))
    if key not in _PATH_DBS:
        from repro.tuna.db import ScheduleDatabase

        _PATH_DBS[key] = ScheduleDatabase(key)
    return _PATH_DBS[key]


def set_default_db(db) -> None:
    """Install the process-wide warm schedule DB (path or ScheduleDatabase).
    ``None`` switches the default OFF, including the ``$REPRO_TUNA_DB``
    fallback. Clears the block-spec memo caches so already-traced shapes
    re-resolve against the new store."""
    global _DEFAULT_DB
    if isinstance(db, (str, os.PathLike)):
        db = _open_db(db)
    _DEFAULT_DB = db
    _clear_memos()


def get_default_db():
    """The installed default DB, else one opened from ``$REPRO_TUNA_DB``."""
    global _DEFAULT_DB
    if _DEFAULT_DB is _UNSET:
        path = os.environ.get("REPRO_TUNA_DB")
        _DEFAULT_DB = _open_db(path) if path else None
    return _DEFAULT_DB


def resolve_db(db):
    """Coerce a ``db`` argument to a ScheduleDatabase or None: ``False`` →
    off, ``None`` → the process default, a path → the per-path cached
    instance (one log read per process), an instance → itself (a
    ``ScheduleCache`` instance acts as a read-only store)."""
    if db is False:
        return None
    if db is None:
        return get_default_db()
    if isinstance(db, (str, os.PathLike)):
        return _open_db(db)
    return db


def _writable(store) -> bool:
    """Write-back gate: ``ScheduleCache`` is an immutable snapshot, so
    results found by a live search are not persisted through it."""
    return store is not None and not getattr(store, "immutable", False)


def _open_cache(path):
    """Per-path snapshot instances, revalidated by the snapshot's *stored
    content digest* (a cheap header read — no record parsing): a snapshot
    is immutable once loaded, so a republished file must hand out a fresh
    instance. stat-based stamps (mtime+size) are not enough — a transport
    pull that preserves timestamps (rsync ``--times``, object-store
    metadata) with an equal-size payload would serve the stale instance
    forever. ``latest`` pointer files revalidate the same way: the pointer
    header carries the target's sha1, so repointing changes the stamp."""
    key = os.path.abspath(os.fspath(path))
    from repro.tuna.cache import ScheduleCache, read_snapshot_header

    stamp = read_snapshot_header(key).get("sha1")
    cached = _PATH_CACHES.get(key)
    if cached is None or stamp is None or cached[0] != stamp:
        _PATH_CACHES[key] = (stamp, ScheduleCache.load(key))
    return _PATH_CACHES[key][1]


def set_default_cache(cache) -> None:
    """Install the process-wide serving snapshot (path or ScheduleCache),
    consulted *before* the schedule DB on every read. ``None`` switches it
    OFF, including the ``$REPRO_TUNA_CACHE`` fallback. Clears the
    block-spec memo caches so already-traced shapes re-resolve. Installing
    a path remembers it, so ``refresh_default_cache`` can hot-swap when
    the snapshot is republished. A missing, corrupt, or stale (wrong
    ``COST_MODEL_VERSION``) snapshot raises — an explicit install must
    never silently serve nothing."""
    global _DEFAULT_CACHE, _DEFAULT_CACHE_PATH
    if isinstance(cache, (str, os.PathLike)):
        path = os.path.abspath(os.fspath(cache))
        cache = _open_cache(path)
        _DEFAULT_CACHE_PATH = path
    else:
        _DEFAULT_CACHE_PATH = None
    _DEFAULT_CACHE = cache
    _clear_memos()


def get_default_cache():
    """The installed snapshot, else one loaded from ``$REPRO_TUNA_CACHE``.
    An env-var path that does not exist yet (snapshot not built) resolves
    to OFF instead of failing every lookup — unlike ``set_default_cache``,
    where an explicit install of a missing file raises. A *stale* env
    snapshot (built under a different ``COST_MODEL_VERSION``) also
    resolves to OFF, but loudly: a ``StaleSnapshotWarning`` says why every
    lookup is about to pay a full search and how to rebuild. Either way
    the path is remembered so ``refresh_default_cache`` picks up the
    rebuilt snapshot without a restart."""
    global _DEFAULT_CACHE, _DEFAULT_CACHE_PATH
    if _DEFAULT_CACHE is _UNSET:
        path = os.environ.get("REPRO_TUNA_CACHE")
        if not path:
            _DEFAULT_CACHE = None
        else:
            from repro.tuna.cache import (StaleSnapshotError,
                                          StaleSnapshotWarning)

            _DEFAULT_CACHE_PATH = os.path.abspath(path)
            try:
                _DEFAULT_CACHE = _open_cache(path)
            except FileNotFoundError:
                _DEFAULT_CACHE = None  # not built yet; refresh may find it
                _clear_memos()
            except StaleSnapshotError as e:
                import warnings

                warnings.warn(f"$REPRO_TUNA_CACHE disabled: {e}",
                              StaleSnapshotWarning, stacklevel=2)
                _DEFAULT_CACHE = None
                # degrading to OFF changes what every block-spec lookup
                # resolves to — without this, shapes memoised while an
                # earlier (now-rejected) snapshot was installed keep
                # serving its block specs until process restart
                _clear_memos()
    return _DEFAULT_CACHE


def refresh_default_cache() -> bool:
    """Hot-reload the default serving snapshot if its content changed.

    Long-running serve processes call this between waves: it re-reads the
    snapshot header at the installed path (following a ``latest``
    pointer), compares the stored sha1 against the instance being served,
    and swaps in a fresh ``ScheduleCache`` — clearing the block-spec
    memos — when a republish landed. Returns True iff a swap happened
    (the new instance starts with zeroed hit/miss counters). While the
    new file is missing, torn, mid-publish, or stale, the current
    instance keeps serving — a failed poll never takes the cache away."""
    global _DEFAULT_CACHE
    cur = get_default_cache()  # resolves the env var on first use
    path = _DEFAULT_CACHE_PATH
    if path is None:
        return False
    try:
        new = _open_cache(path)
    except (OSError, ValueError):
        # missing/unreadable file (NFS blips included) or a stale/corrupt
        # snapshot (StaleSnapshotError is a ValueError): keep serving
        return False
    if new is cur:
        return False
    _DEFAULT_CACHE = new
    _clear_memos()
    return True


def set_default_bundle(bundle) -> None:
    """Install the process-wide golden kernel bundle
    (``repro.tuna.golden.KernelBundle``, or a path/`latest` pointer to
    one), consulted before the snapshot cache *and* the DB on every read —
    the blessed-release tier. ``None`` switches it OFF, including the
    ``$REPRO_TUNA_BUNDLE`` fallback. Clears the block-spec memo caches so
    already-traced shapes re-resolve against the release."""
    global _DEFAULT_BUNDLE
    if isinstance(bundle, (str, os.PathLike)):
        from repro.tuna.golden import KernelBundle

        bundle = KernelBundle.load(bundle)
    _DEFAULT_BUNDLE = bundle
    _clear_memos()


def get_default_bundle():
    """The installed kernel bundle, else one loaded from
    ``$REPRO_TUNA_BUNDLE``. Mirrors ``get_default_cache``'s env handling:
    a path that does not exist resolves to OFF; a stale bundle (different
    ``COST_MODEL_VERSION``) resolves to OFF with a ``StaleSnapshotWarning``
    — and both degrade paths clear the block-spec memos."""
    global _DEFAULT_BUNDLE
    if _DEFAULT_BUNDLE is _UNSET:
        path = os.environ.get("REPRO_TUNA_BUNDLE")
        if not path:
            _DEFAULT_BUNDLE = None
        else:
            from repro.tuna.cache import (StaleSnapshotError,
                                          StaleSnapshotWarning)
            from repro.tuna.golden import KernelBundle

            try:
                _DEFAULT_BUNDLE = KernelBundle.load(path)
            except FileNotFoundError:
                _DEFAULT_BUNDLE = None
                _clear_memos()
            except StaleSnapshotError as e:
                import warnings

                warnings.warn(f"$REPRO_TUNA_BUNDLE disabled: {e}",
                              StaleSnapshotWarning, stacklevel=2)
                _DEFAULT_BUNDLE = None
                _clear_memos()
    return _DEFAULT_BUNDLE


def set_default_learned(model) -> None:
    """Install the process-wide learned ranker
    (``repro.core.learned.LearnedRanker``, or a path/`latest` pointer to a
    saved artifact) used by ``rank_space``/``best_schedule`` to re-rank the
    statically-pruned top candidates. ``None`` switches it OFF, including
    the ``$REPRO_TUNA_LEARNED`` fallback. Clears the block-spec memo
    caches so already-traced shapes re-resolve under the hybrid version.
    An explicit install of a missing, corrupt, tampered, or stale (wrong
    ``COST_MODEL_VERSION``) artifact raises — never silently served."""
    global _DEFAULT_LEARNED
    if isinstance(model, (str, os.PathLike)):
        from repro.core.learned import load_ranker

        model = load_ranker(model)
    _DEFAULT_LEARNED = model
    _clear_memos()


def get_default_learned():
    """The installed learned ranker, else one loaded from
    ``$REPRO_TUNA_LEARNED``. Mirrors ``get_default_cache``'s env handling:
    a path that does not exist (model not trained yet) resolves to OFF; a
    stale artifact (different ``COST_MODEL_VERSION``) resolves to OFF with
    a ``StaleSnapshotWarning`` — and both degrade paths clear the
    block-spec memos, so shapes memoised under an earlier model never
    outlive its rejection."""
    global _DEFAULT_LEARNED
    if _DEFAULT_LEARNED is _UNSET:
        path = os.environ.get("REPRO_TUNA_LEARNED")
        if not path:
            _DEFAULT_LEARNED = None
        else:
            from repro.core.learned import load_ranker
            from repro.tuna.cache import (StaleSnapshotError,
                                          StaleSnapshotWarning)

            try:
                _DEFAULT_LEARNED = load_ranker(path)
            except FileNotFoundError:
                _DEFAULT_LEARNED = None  # not trained yet
                _clear_memos()
            except StaleSnapshotError as e:
                import warnings

                warnings.warn(f"$REPRO_TUNA_LEARNED disabled: {e}",
                              StaleSnapshotWarning, stacklevel=2)
                _DEFAULT_LEARNED = None
                _clear_memos()
    return _DEFAULT_LEARNED


def resolve_learned(learned):
    """Coerce a ``learned`` argument: ``False`` → off, ``None`` → the
    process default, a path → a loaded (and verified) artifact, an
    instance → itself."""
    if learned is False:
        return None
    if learned is None:
        return get_default_learned()
    if isinstance(learned, (str, os.PathLike)):
        from repro.core.learned import load_ranker

        return load_ranker(learned)
    return learned


def _lookup(op: str, target_name: str, version: str, db):
    """Read path shared by tune/best_schedule/block-spec pickers: golden
    kernel bundle first (the blessed release), then the snapshot cache
    (O(1), lock-free), then the schedule DB. Returns
    ``(record or None, "bundle"|"cache"|"db"|"")`` and never searches."""
    bundle = get_default_bundle()
    if bundle is not None:
        rec = bundle.best(op, target_name, version)
        if rec is not None:
            return rec, "bundle"
    cache = get_default_cache()
    if cache is not None:
        rec = cache.best(op, target_name, version)
        if rec is not None:
            return rec, "cache"
    store = resolve_db(db)
    if store is not None and store is not cache:
        rec = store.best(op, target_name, version)
        if rec is not None:
            return rec, "db"
    return None, ""


def lookup_best(op: str, target_name: str,
                version: str = COST_MODEL_VERSION, db=None):
    """Best stored record for a key — serving-cache first, then the DB
    (``db`` follows ``resolve_db`` semantics). None on a full miss."""
    return _lookup(op, target_name, version, db)[0]


def record_version(coeffs: Optional[Dict[str, float]] = None) -> str:
    """Cost-model version tag for a schedule record. Datasheet coefficients
    → plain ``cm1``. Custom (calibrated) coefficients are host-specific, so
    their scores are only comparable to records from the same fit — the
    coefficient fingerprint becomes part of the key, keeping merged stores
    from mixing incomparable score scales."""
    if coeffs is None:
        return COST_MODEL_VERSION
    blob = json.dumps(coeffs, sort_keys=True, default=float)
    fp = hashlib.sha1(blob.encode()).hexdigest()[:8]
    return f"{COST_MODEL_VERSION}-cal-{fp}"


@dataclasses.dataclass
class TuneResult:
    config: Dict
    score: float
    evaluations: int
    wall_seconds: float
    history: List[float]
    default_score: float  # score of the space's centre config (no tuning)
    from_db: bool = False  # True when served from the schedule database
    from_cache: bool = False  # True when the hit came from a ScheduleCache
    default_score_missing: bool = False  # True on warm hits whose stored
    #   record carries no default_score (e.g. written by rank_space with
    #   the centre config outside the enumeration limit): default_score is
    #   NaN then, and speedup math / JSON emitters must treat it as absent
    #   rather than serialize bare NaN (invalid JSON)


def _score_config(space: Space, target: HardwareTarget, cfg: Dict,
                  coeffs: Optional[Dict[str, float]] = None) -> float:
    prog, meta = space.instantiate(cfg)
    return cost_model.evaluate(prog, target, meta, coeffs=coeffs)


def tune(
    space: Space,
    target: HardwareTarget,
    iterations: int = 12,
    population: int = 16,
    seed: int = 0,
    workers: int = 8,
    db=None,
) -> TuneResult:
    """ES search (Alg. 4); warm-DB hits return with **zero** cost-model
    evaluations, misses are written back under strategy ``es``."""
    t0 = time.perf_counter()
    if db is not False:  # False = full bypass, snapshot cache included
        rec, source = _lookup(space.signature(), target.name,
                              COST_MODEL_VERSION, db)
        if rec is not None:
            # NaN when the stored record carries no default_score (e.g. it
            # was written by rank_space) — a warm hit spends zero
            # evaluations, so we won't recompute it here; the explicit
            # default_score_missing flag is what downstream speedup math
            # and JSON emitters key off (bare NaN is invalid JSON)
            has_default = "default_score" in rec.meta
            return TuneResult(
                config=dict(rec.config),
                score=rec.score,
                evaluations=0,
                wall_seconds=time.perf_counter() - t0,
                history=[],
                default_score=float(
                    rec.meta.get("default_score", float("nan"))),
                from_db=True,
                from_cache=source in ("cache", "bundle"),
                default_score_missing=not has_default,
            )

    store = resolve_db(db)  # resolved on the miss path only: a snapshot
    #                         hit must not pay a JSONL log load
    cache: Dict[Tuple, float] = {}

    def fitness(theta: np.ndarray) -> float:
        cfg = space.decode(theta)
        key = tuple(sorted(cfg.items()))
        if key not in cache:
            cache[key] = _score_config(space, target, cfg)
        return -cache[key]

    res = es.evolve(
        fitness,
        dim=space.dim,
        iterations=iterations,
        population=population,
        seed=seed,
        workers=workers,
    )
    best_cfg = space.decode(res.best_theta)
    best_score = _score_config(space, target, best_cfg)
    result = TuneResult(
        config=best_cfg,
        score=best_score,
        evaluations=res.evaluations,
        wall_seconds=time.perf_counter() - t0,
        history=res.history,
        default_score=_score_config(space, target, space.default_config()),
    )
    if _writable(store):
        from repro.tuna.db import ScheduleRecord, stamp_tuned_at

        store.add(ScheduleRecord(
            op=space.signature(),
            target=target.name,
            config=dict(best_cfg),
            score=best_score,
            evaluations=res.evaluations,
            meta=stamp_tuned_at(
                {"strategy": "es", "default_score": result.default_score}),
        ))
    return result


def rank_space(
    space: Space, target: HardwareTarget, limit: int = 4096,
    coeffs: Optional[Dict[str, float]] = None,
    db=False,
    learned=False,
    rerank_top: int = 32,
) -> List[Tuple[Dict, float]]:
    """Static exhaustive ranking (ascending score = predicted fastest first).

    Callers need the full ranking, which the DB does not store, so this is a
    *write-back* integration: when a store resolves, the winning record is
    appended under strategy ``exhaustive`` (``best_schedule`` is the
    read path). Calibrated-coefficient rankings are stored under a
    fingerprinted version (``cm1-cal-<hash>``, see ``record_version``) so
    they never collide with datasheet scores or other hosts' fits.

    ``learned`` (``resolve_learned`` semantics; default OFF) makes the
    ranking *hybrid*: static ``cm1`` scores and prunes the space, the
    learned ranker re-orders the statically-best ``rerank_top`` candidates
    — still zero hardware measurements. Hybrid write-backs go under the
    model's fingerprinted version (``<base>+lr<fp>``, strategy ``hybrid``)
    so they never collide with pure static records.
    """
    scored = [
        (cfg, _score_config(space, target, cfg, coeffs))
        for cfg in space.enumerate(limit)
    ]
    scored.sort(key=lambda cs: cs[1])
    model = resolve_learned(learned)
    if model is not None:
        scored = model.rerank(space, target, scored, top=rerank_top)
    store = resolve_db(db)
    if _writable(store) and scored:
        from repro.tuna.db import ScheduleRecord, stamp_tuned_at

        version = record_version(coeffs)
        meta = {"strategy": "exhaustive", "limit": limit}
        if model is not None:
            version = model.hybrid_version(version)
            meta["strategy"] = "hybrid"
            meta["rerank_top"] = rerank_top
        dflt = space.default_config()
        default_score = next((s for c, s in scored if c == dflt), None)
        if default_score is not None:  # centre config inside the limit
            meta["default_score"] = default_score
        meta = stamp_tuned_at(meta)
        store.add(ScheduleRecord(
            op=space.signature(),
            target=target.name,
            config=dict(scored[0][0]),
            score=scored[0][1],
            evaluations=len(scored),
            meta=meta,
            version=version,
        ))
    return scored


def best_schedule(
    space: Space, target: HardwareTarget, limit: int = 1024, db=None,
    coeffs: Optional[Dict[str, float]] = None,
    version: Optional[str] = None,
    learned=None,
    rerank_top: int = 32,
) -> Tuple[Dict, float]:
    """Best (config, score) for a space: bundle/snapshot-cache/DB hit →
    zero evaluations; miss → exhaustive rank + write back (to a writable
    store only). The kernel block-spec pickers sit on this.

    ``version`` pins the record version consulted (and nothing else is
    tried) — the passthrough that lets calibrated ``cm1-cal-<fp>`` writes
    be calibrated warm hits instead of silently re-ranking under plain
    ``cm1``. Without it the version is derived: ``record_version(coeffs)``,
    and when a learned ranker resolves (``learned``; default = the process
    default, see ``set_default_learned``) the hybrid lineage
    (``<base>+lr<fp>``) is consulted first with the static lineage as
    fallback — existing cm1 bundles/caches keep their warm hits."""
    model = resolve_learned(learned) if version is None else None
    if db is not False:
        if version is not None:
            versions = [version]
        else:
            base = record_version(coeffs)
            versions = ([model.hybrid_version(base), base]
                        if model is not None else [base])
        for v in versions:
            rec = lookup_best(space.signature(), target.name, version=v,
                              db=db)
            if rec is not None:
                return dict(rec.config), rec.score
    store = resolve_db(db)  # miss path only, like tune()
    ranked = rank_space(space, target, limit=limit, coeffs=coeffs,
                        db=store if _writable(store) else False,
                        learned=model if model is not None else False,
                        rerank_top=rerank_top)
    return ranked[0]


@functools.lru_cache(maxsize=256)
def tuned_matmul_blocks(
    M: int, N: int, K: int, dtype_bytes: int = 2,
    target_name: Optional[str] = None,
) -> Tuple[int, int, int]:
    """Statically tuned Pallas block sizes for a matmul — used by kernels/ops.

    Exhaustive over the (small) block space: this is what a production
    compilation service would run at model-compile time, on any host, with no
    TPU attached (the paper's cross-compilation requirement). Consults the
    default schedule DB first, so a warm store makes this a pure lookup.
    ``target_name=None`` tunes for the device JAX runs on
    (``hw.resolve_target``)."""
    target = resolve_target(target_name)
    space = MatmulSpace(M, N, K, dtype_bytes, target_kind="tpu")
    best, _ = best_schedule(space, target, limit=1024)
    return best["bm"], best["bn"], best["bk"]
