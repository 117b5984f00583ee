"""Persistent compile cache + the AOT warm-up ladder (kill the host tail).

Cold start is a production outage in miniature: a restarted scheduler
pays the full XLA compile ladder before its first bind (43.2s cold cycle
at 100k x 50k, MULTICHIP_r06; 1.53s restart-to-first-bind wall even at
sim scale, CHURN_r03). Two layers close it:

  1. **Persistent XLA compilation cache** — ``JAX_COMPILATION_CACHE_DIR``
     (jax's own variable) arms jax's on-disk executable cache so a
     re-traced program whose HLO already compiled in ANY prior process
     deserializes instead of recompiling. When it is set, no other
     directory is ever configured. The process entry points (the
     ``cmd/`` binaries, ``chip_smoke.py``) fall back to
     :func:`default_compile_cache_dir` — a fixed path, because the path is
     part of the cache key — while a library ``Scheduler()`` with the
     variable unset stays cache-off (the determinism tests' default).
     The thresholds are pinned to cache every step program: on the CPU
     backend the default min-compile-time threshold would skip the small
     rungs.

  2. **Warm-up ladder** (:class:`WarmupRunner`) — every step compile the
     cycle driver (and the rebalance/colo passes) performs is recorded in
     a tiny JSON index next to the XLA entries: the builder metadata
     (padded-shape signature, mesh device-id tuple, explain mode, wave
     depth, side tags) plus the call arguments' shape/dtype spec and the
     **program fingerprint**. A restarted scheduler replays the index at
     startup — rebuilding each rung through the SAME keyed step caches
     (``Scheduler._get_step`` / ``_get_fused_step`` / ``_get_chain_step``)
     and triggering its compile against zero-filled bucket-shaped inputs
     — so the first real cycle's step lookup is an in-memory HIT and the
     XLA work was disk-served during warm-up, in the background (or
     synchronously, for the deterministic gates) instead of on the first
     pod's critical path.

Fingerprint discipline: index entries are keyed by
:func:`program_fingerprint` (a hash over the kernel/model sources;
``KOORD_TPU_PROGRAM_FINGERPRINT`` overrides it for deploy pipelines that
version artifacts themselves). A fingerprint change invalidates every
recorded rung — warm-up skips them (counted ``invalidated``) and the
next write purges them — so a code-version bump can never replay stale
shapes against new programs. A corrupted/truncated index (or XLA cache
entry: jax already recovers with a warning) degrades to an empty index
and a clean compile; warm-up must never crash the scheduler.

Observability: ``koord_scheduler_warmup_*`` metrics (rungs by outcome,
wall seconds, the completion gauge) and a ``warmup`` span tree with one
``rung`` child per replayed entry. After warm-up completes the owner
flips into *steady state*: any further step-cache miss in the hot path is
flagged (``koord_scheduler_steady_state_compiles_total`` + the owner's
``compile_miss_hook``) — the runtime half of koordlint rule 20
(``compile-in-steady-state``); the AST half pins that step builders are
only ever called through the keyed ``_get_*step`` chokepoints.
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import threading
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

logger = logging.getLogger(__name__)

INDEX_VERSION = 1
INDEX_NAME = "koord_warmup_index.json"

# warm-up replay call-arg reconstruction: the namedtuple classes a
# recorded aval spec may reference. Lazy import targets — the registry
# stays import-light so configure_compile_cache can run before jax does.
_NT_REGISTRY = {
    "FullChainInputs": ("koordinator_tpu.models.full_chain",
                        "FullChainInputs"),
    "ScheduleInputs": ("koordinator_tpu.models.scheduler_model",
                       "ScheduleInputs"),
    "WaveSideInputs": ("koordinator_tpu.models.fused_waves",
                       "WaveSideInputs"),
    "ProdSides": ("koordinator_tpu.models.fused_waves", "ProdSides"),
    "ClaimSides": ("koordinator_tpu.models.fused_waves", "ClaimSides"),
    "ResSides": ("koordinator_tpu.models.fused_waves", "ResSides"),
}

# sources the default fingerprint hashes: the compiled programs' shape
# is fully determined by these packages (kernel bodies, wave state
# layout, sharding rules) plus the shape metadata the index records
_FINGERPRINT_PACKAGES = ("models", "ops", "parallel", "balance", "colo")


def compile_cache_dir_from_env() -> Optional[str]:
    """``JAX_COMPILATION_CACHE_DIR=<dir>`` arms the persistent compile
    cache + the warm-up index; unset/empty keeps both off for a library
    ``Scheduler()`` (the deterministic default for tests)."""
    raw = os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()
    return raw or None


def default_compile_cache_dir() -> str:
    """``<checkout>/.jax_compile_cache`` (listed in .gitignore): the fixed
    cache path of the process entry points when
    ``JAX_COMPILATION_CACHE_DIR`` is unset."""
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(pkg), ".jax_compile_cache")


def warmup_mode_from_env() -> str:
    """KOORD_TPU_WARMUP=off|sync|background ("auto" = background when a
    compile-cache dir is configured, else off). sync runs the ladder
    inside Scheduler construction — what the crash-restart gates use, so
    restart-to-first-bind includes the whole warm-up and the steady-state
    guard arms deterministically."""
    raw = os.environ.get("KOORD_TPU_WARMUP", "auto").strip().lower()
    if raw in ("auto", ""):
        return "auto"
    if raw in ("0", "off", "false", "no"):
        return "off"
    if raw in ("sync", "1", "on", "true"):
        return "sync" if raw == "sync" else "background"
    if raw == "background":
        return "background"
    logger.warning("KOORD_TPU_WARMUP=%r unknown; warm-up stays off", raw)
    return "off"


_configured_dir: Optional[str] = None


def configure_compile_cache(dir_path: Optional[str] = None) -> Optional[str]:
    """Point jax's persistent compilation cache at
    ``JAX_COMPILATION_CACHE_DIR`` when it is set (``dir_path`` is then
    ignored), else at ``dir_path``. Idempotent and process-global — jax's
    cache config is global, so the first caller wins and later calls are
    no-ops (a different dir logs and keeps the first: two schedulers in
    one process must share one cache). Returns the effective dir, or None
    when the cache stays off."""
    global _configured_dir
    env_dir = compile_cache_dir_from_env()
    want = env_dir if env_dir is not None else dir_path
    if want is None:
        return _configured_dir
    if _configured_dir is not None:
        if _configured_dir != want:
            logger.warning(
                "compile cache already configured at %s; ignoring %s "
                "(jax's cache config is process-global)",
                _configured_dir, want)
        return _configured_dir
    os.makedirs(want, exist_ok=True)
    import jax

    # jax read JAX_COMPILATION_CACHE_DIR when it was imported; setting
    # the same value again covers a variable exported after that import
    jax.config.update("jax_compilation_cache_dir", want)
    # threshold discipline: every STEP program (>= hundreds of ms even at
    # sim scale) must cache, so the default 1s min-compile-time is
    # lowered — but NOT to zero. Empirically (PR 15), persisting the
    # sub-100ms utility jits (the donated row scatters and friends) made
    # scheduler DECISIONS diverge run-to-run on the CPU backend once
    # their deserialized executables served the hot path; a 0.1s floor
    # keeps every rung the coldstart gate measures while leaving the
    # tiny jits to compile fresh — the determinism gates (lint parity +
    # sim --check-determinism) run with the cache armed to pin this.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    _configured_dir = want
    return want


_fingerprint_cache: Optional[str] = None


def program_fingerprint() -> str:
    """The code-version key for persistent-cache entries.
    ``KOORD_TPU_PROGRAM_FINGERPRINT`` pins it (deploy pipelines, and the
    invalidation tests' simulated version bump); the default hashes the
    kernel/model/parallel sources, so editing a wave body invalidates
    every recorded rung without any manual bump."""
    env = os.environ.get("KOORD_TPU_PROGRAM_FINGERPRINT", "").strip()
    if env:
        return env
    global _fingerprint_cache
    if _fingerprint_cache is not None:
        return _fingerprint_cache
    h = hashlib.sha256()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for pkg in _FINGERPRINT_PACKAGES:
        pkg_dir = os.path.join(root, pkg)
        if not os.path.isdir(pkg_dir):
            continue
        for name in sorted(os.listdir(pkg_dir)):
            if not name.endswith(".py"):
                continue
            path = os.path.join(pkg_dir, name)
            h.update(name.encode())
            try:
                with open(path, "rb") as f:
                    h.update(f.read())
            except OSError:
                continue
    _fingerprint_cache = h.hexdigest()[:32]
    return _fingerprint_cache


# ---------------------------------------------------------------------------
# call-argument shape specs (recorded at compile time, replayed as zeros)
# ---------------------------------------------------------------------------

def aval_spec(obj):
    """JSON-able (shape, dtype) tree of one call argument. Handles the
    pytrees the dispatch sites actually pass: namedtuples (registered in
    ``_NT_REGISTRY``), plain tuples/lists (the wave carry), ``None``
    slots (feature-absent leafless subtrees), arrays (host or device)
    and numpy scalars. Small Python ints/floats are recorded BY VALUE —
    ``np.int32(n_real)``-style operands must replay with a concrete
    value, not a zero aval, in case the builder treats them statically."""
    if obj is None:
        return {"t": "none"}
    if isinstance(obj, bool):
        return {"t": "v", "v": bool(obj)}
    if isinstance(obj, (int, float)):
        return {"t": "v", "v": obj}
    if isinstance(obj, np.generic):
        # numpy scalars (the np.int32(n_real) operand): by value, typed
        return {"t": "np", "v": obj.item(), "d": str(obj.dtype)}
    fields = getattr(obj, "_fields", None)
    if fields is not None:
        name = type(obj).__name__
        if name not in _NT_REGISTRY:
            raise TypeError(f"unregistered namedtuple {name!r} in aval spec")
        return {"t": "nt", "c": name,
                "f": [aval_spec(getattr(obj, f)) for f in fields]}
    if isinstance(obj, (tuple, list)):
        return {"t": "tuple", "i": [aval_spec(v) for v in obj]}
    shape = getattr(obj, "shape", None)
    dtype = getattr(obj, "dtype", None)
    if shape is not None and dtype is not None:
        return {"t": "a", "s": [int(d) for d in shape], "d": str(dtype)}
    raise TypeError(f"unsupported aval-spec value {type(obj).__name__}")


def zeros_from_spec(spec):
    """Rebuild one call argument from its spec as zero-filled host
    arrays (padding-row semantics: every kernel masks invalid rows, so a
    zero world traces the exact program and converges immediately)."""
    t = spec["t"]
    if t == "none":
        return None
    if t == "v":
        return spec["v"]
    if t == "np":
        return np.dtype(spec["d"]).type(spec["v"])
    if t == "a":
        return np.zeros(tuple(spec["s"]), np.dtype(spec["d"]))
    if t == "tuple":
        return tuple(zeros_from_spec(s) for s in spec["i"])
    if t == "nt":
        import importlib

        mod_name, cls_name = _NT_REGISTRY[spec["c"]]
        cls = getattr(importlib.import_module(mod_name), cls_name)
        return cls(*(zeros_from_spec(s) for s in spec["f"]))
    raise ValueError(f"bad aval spec {t!r}")


# ---------------------------------------------------------------------------
# the persistent rung index
# ---------------------------------------------------------------------------


# process-wide: CompileCacheIndex instances are constructed per record
# call, so a per-instance lock would never exclude anyone — recorders
# in one process serialize here (cross-process writers are last-writer-
# wins on the atomic rename, which can drop a concurrent rung but can
# never corrupt the file: every writer renames its OWN unique tmp)
_index_lock = threading.Lock()  # koordlint: guards(rung-index-file)


class CompileCacheIndex:
    """The warm-up rung index living next to the XLA cache entries.

    One JSON file, atomically rewritten (unique tmp + rename) on every
    ``record``; entries dedupe on (kind, meta) and carry the recording
    fingerprint. A corrupted/truncated/absent file loads as EMPTY — the
    cache layer must degrade to a clean compile, never crash the
    ladder (pinned by tests)."""

    def __init__(self, dir_path: str) -> None:
        # immutable after construction; only the index FILE needs the lock
        self.path = os.path.join(dir_path, INDEX_NAME)  # koordlint: guarded-by(none)
        self._lock = _index_lock

    def load(self) -> Dict[str, dict]:
        try:
            with open(self.path) as f:
                data = json.load(f)
        except (OSError, ValueError):
            return {}
        if not isinstance(data, dict) or data.get("v") != INDEX_VERSION:
            return {}
        entries = data.get("entries")
        return entries if isinstance(entries, dict) else {}

    @staticmethod
    def entry_key(kind: str, meta: dict) -> str:
        return hashlib.sha256(
            json.dumps([kind, meta], sort_keys=True).encode()
        ).hexdigest()[:24]

    def record(self, kind: str, meta: dict, args_spec: List[dict]) -> None:
        """Merge one rung; stale-fingerprint entries are purged on the
        same write (the invalidation discipline: a version bump leaves
        no replayable residue behind)."""
        fp = program_fingerprint()
        with self._lock:
            entries = self.load()
            entries = {k: e for k, e in entries.items()
                       if isinstance(e, dict) and e.get("fp") == fp}
            entries[self.entry_key(kind, meta)] = {
                "kind": kind, "meta": meta, "args": args_spec, "fp": fp,
            }
            import tempfile

            fd, tmp = tempfile.mkstemp(
                prefix=INDEX_NAME + ".", suffix=".tmp",
                dir=os.path.dirname(self.path))
            try:
                with os.fdopen(fd, "w") as f:
                    json.dump({"v": INDEX_VERSION, "entries": entries},
                              f, sort_keys=True)
                os.replace(tmp, self.path)
            except BaseException:
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
                raise


def record_step_compile(kind: str, meta: dict, args: Tuple) -> bool:
    """Record one freshly-compiled step rung into the configured cache
    dir's index (no-op when the persistent cache is off). Never raises:
    recording is pure observability for the NEXT process — a bad entry
    must not cost this one its dispatch."""
    dir_path = _configured_dir
    if dir_path is None:
        return False
    try:
        CompileCacheIndex(dir_path).record(
            kind, meta, [aval_spec(a) for a in args])
        return True
    except Exception:
        logger.exception("compile-cache index record failed (kind=%s)",
                         kind)
        return False


# ---------------------------------------------------------------------------
# the warm-up ladder
# ---------------------------------------------------------------------------

# background ladders serialize process-wide: two schedulers warming at
# once would just contend for the same XLA compile threads, and the
# atexit join below must have a bounded set to wait on
_ladder_lock = threading.Lock()
# koordlint: guarded-by(_ladder_lock)
_live_threads: List[threading.Thread] = []
_atexit_registered = False


def _join_live_ladders() -> None:
    """Interpreter-exit guard: a daemon ladder thread killed MID-XLA-
    COMPILE aborts the process in native teardown ("terminate called
    without an active exception") — give outstanding ladders a bounded
    window to finish before the runtime unwinds. The ladder lock is
    held for a whole run(), so taking it here would turn the bounded
    join into an unbounded wait — the bare snapshot is a list() copy
    (atomic under the GIL) of threads only ever appended before start;
    the pragma below records that deliberate exception."""
    for t in list(_live_threads):  # koordlint: disable=unguarded-shared-field
        t.join(timeout=30.0)


class WarmupRunner:
    """Replay the recorded rung index against a fresh Scheduler.

    Scheduler rungs (serial/fused/chain) rebuild through the SAME keyed
    ``_get_*step`` chokepoints — populating the in-memory step cache
    under the exact production keys — then trigger the XLA compile (disk
    hit on a warm dir) with zero-filled inputs of the recorded shapes.
    Rebalance/colo rungs replay through their module builders: the colo
    reconciler and rebalancer own separate step caches, so the value
    there is the warmed XLA disk entry, not an in-memory hit.

    Mesh discipline: a rung recorded under a mesh device-id tuple only
    replays when the scheduler's CURRENT placement matches (koordguard:
    two same-size submeshes never share a step); mismatches count as
    ``skipped``. A mesh step rung runs on zeros placed as the cycle
    places its operands, and the mesh DeviceSnapshot's row-delta
    programs replay as ``scatter`` rungs, one per field shape and pad
    bucket a process met. Every rung runs inside try/except — a corrupted entry
    or a failed zero-call counts as ``failed`` and warm-up continues."""

    def __init__(self, scheduler, background: bool = False) -> None:
        from koordinator_tpu.obs import Tracer

        self.scheduler = scheduler
        self.background = background
        # own tracer: the background ladder must not interleave spans
        # into the cycle thread's ring mid-cycle
        self.tracer = Tracer()
        self.stats = {"rungs": 0, "warmed": 0, "skipped": 0,
                      "failed": 0, "invalidated": 0, "seconds": 0.0,
                      "complete": False}
        self._thread: Optional[threading.Thread] = None

    # -- rung replay ----------------------------------------------------
    def _replay_scheduler_rung(self, entry: dict):
        sched = self.scheduler
        meta = entry["meta"]
        if tuple(meta.get("mesh_tag", ())) != sched._mesh_tag():
            return "skipped"
        # config the program structure bakes in must match THIS
        # scheduler, or the recorded avals describe a different carry
        # pytree (a co-resident scheduler with another prod/transformer
        # config recorded the rung): skip, never trip
        if "prod" in meta and meta["prod"] != bool(
                sched.args.score_according_prod_usage):
            return "skipped"
        if "score_tag" in meta and [
                [name, int(epoch)]
                for name, epoch in sched._score_pass_tag()
        ] != meta["score_tag"]:
            return "skipped"
        kind = entry["kind"]
        mesh_rung = bool(meta.get("mesh_tag"))
        signature = tuple(meta["signature"])
        active = list(meta["active"])
        explain = meta.get("explain")
        if kind == "serial":
            step = sched._get_step(signature, meta["ng"], meta["ngroups"],
                                   active, explain=explain)
        elif kind == "fused":
            step = sched._get_fused_step(
                signature, meta["ng"], meta["ngroups"], active,
                meta["waves"], explain=explain,
                sides_tag=tuple(meta["sides_tag"]),
                volumes=meta.get("volumes", True))
        elif kind == "chain":
            step = sched._get_chain_step(
                signature, meta["ng"], meta["ngroups"], active,
                explain=explain, sides_tag=tuple(meta["sides_tag"]),
                volumes=meta.get("volumes", True))
        else:
            return "skipped"
        args = [zeros_from_spec(s) for s in entry["args"]]
        if mesh_rung:
            # a mesh rung runs on operands placed as the cycle places
            # them (the DeviceSnapshot's shardings, the chain's wave-0
            # carry): host operands would commit other input shardings
            # and compile a program no dispatch runs
            args = self._place_mesh_args(kind, meta, args)
        self._zero_call(step, args)
        return "warmed"

    def _place_mesh_args(self, kind: str, meta: dict, args: list):
        """A mesh rung's zero operands on the scheduler's mesh: the
        packed batch through ``DeviceSnapshot.place``, the wave side
        inputs under ``wave_side_shardings``, the chain's carry rebuilt
        from them as a cycle builds it."""
        import jax

        from koordinator_tpu.parallel.full_chain_mesh import (
            wave_side_shardings,
        )
        from koordinator_tpu.parallel.mesh import put_on_mesh

        sched = self.scheduler
        snap = sched.device_snapshot  # a mesh scheduler always holds one
        args[0] = snap.place(args[0])
        if kind == "serial":
            return args
        sides = args[2] if kind == "chain" else args[1]
        sides = jax.tree.map(
            put_on_mesh, sides,
            wave_side_shardings(snap.mesh, prod=sides.prod is not None,
                                claims=sides.claims is not None,
                                res=sides.res is not None))
        if kind == "chain":
            args[1] = sched._initial_chain_carry(args[0], sides,
                                                 meta.get("explain"))
            args[2] = sides
        else:
            args[1] = sides
        return args

    def _replay_scatter_rung(self, entry: dict):
        """A DeviceSnapshot row-delta program recorded under this mesh:
        compiled into the scheduler's mirror before its first cycle."""
        sched = self.scheduler
        meta = entry["meta"]
        snap = sched.device_snapshot
        if (snap is None or snap.mesh is None
                or tuple(meta.get("mesh_tag", ())) != sched._mesh_tag()):
            return "skipped"
        snap.warm_scatter(tuple(meta["shape"]), np.dtype(meta["dtype"]),
                          meta["pad"], meta["donate"], meta["node_axis"])
        return "warmed"

    def _replay_standalone_rung(self, entry: dict):
        """Rebalance/colo rungs: module builders, single-device only —
        a mesh build needs the live Mesh object, which belongs to the
        process that recorded it."""
        meta = entry["meta"]
        if tuple(meta.get("mesh_tag", ())):
            return "skipped"
        if entry["kind"] == "rebalance":
            from koordinator_tpu.balance.step import build_rebalance_step

            step = build_rebalance_step(meta["cap"])
        elif entry["kind"] == "colo":
            from koordinator_tpu.colo.step import build_colo_step

            step = build_colo_step(meta["policies"][0], meta["policies"][1])
        else:
            return "skipped"
        self._zero_call(step, [zeros_from_spec(s) for s in entry["args"]])
        return "warmed"

    def _zero_call(self, step, args) -> None:
        import jax

        t0 = time.perf_counter()
        out = step(*args)
        # startup-time ladder, not a dispatch window: a hung device
        # surfaces at process start instead of wedging a cycle, and the
        # background mode keeps it off the bind path entirely
        # koordlint: disable=naked-device-sync-without-deadline
        jax.block_until_ready(
            [leaf for leaf in jax.tree_util.tree_leaves(out)])
        # the ladder's XLA work is compile wall: the restart report's
        # compile/pack split must attribute warm-up to compile
        # (lock-guarded — the background ladder adds from its thread)
        self.scheduler._add_compile_wall(time.perf_counter() - t0)

    # -- the ladder -----------------------------------------------------
    def run(self) -> dict:
        from koordinator_tpu.scheduler import metrics as scheduler_metrics

        sched = self.scheduler
        t0 = time.perf_counter()
        fp = program_fingerprint()
        entries: Dict[str, dict] = {}
        if _configured_dir is not None:
            entries = CompileCacheIndex(_configured_dir).load()
        with self.tracer.span("warmup", rungs=str(len(entries))):
            for key in sorted(entries):
                entry = entries[key]
                self.stats["rungs"] += 1
                if not isinstance(entry, dict) or entry.get("fp") != fp:
                    # fingerprint mismatch (or a mangled entry): the
                    # recorded shapes belong to another code version —
                    # never replay them; the next record purges them
                    self.stats["invalidated"] += 1
                    scheduler_metrics.WARMUP_RUNGS.inc(outcome="invalidated")
                    continue
                kind = entry.get("kind", "")
                with self.tracer.span("rung", kind=kind, key=key):
                    try:
                        if kind in ("serial", "fused", "chain"):
                            outcome = self._replay_scheduler_rung(entry)
                        elif kind == "scatter":
                            outcome = self._replay_scatter_rung(entry)
                        else:
                            outcome = self._replay_standalone_rung(entry)
                    except Exception:
                        # a wrecked rung (stale spec, corrupted XLA
                        # entry jax could not recover) falls back to the
                        # on-demand compile — warm-up NEVER crashes
                        logger.exception("warm-up rung failed (%s)", kind)
                        outcome = "failed"
                self.stats[outcome] += 1
                scheduler_metrics.WARMUP_RUNGS.inc(outcome=outcome)
        self.stats["seconds"] = time.perf_counter() - t0
        self.stats["complete"] = True
        scheduler_metrics.WARMUP_SECONDS.set(self.stats["seconds"])
        sched.note_warmup_complete(self.stats)
        return self.stats

    def start(self) -> None:
        if not self.background:
            with _ladder_lock:
                self.run()
            return
        global _atexit_registered
        if not _atexit_registered:
            import atexit

            atexit.register(_join_live_ladders)
            _atexit_registered = True
        self._thread = threading.Thread(
            target=self._run_guarded, name="koord-warmup", daemon=True)
        with _ladder_lock:
            _live_threads.append(self._thread)
        self._thread.start()

    def _run_guarded(self) -> None:
        try:
            with _ladder_lock:
                self.run()
        except Exception:  # the ladder is best-effort by contract
            logger.exception("warm-up ladder failed")
        finally:
            try:
                with _ladder_lock:
                    _live_threads.remove(self._thread)
            except ValueError:  # pragma: no cover - defensive
                pass

    def join(self, timeout: Optional[float] = None) -> None:
        if self._thread is not None:
            self._thread.join(timeout)
