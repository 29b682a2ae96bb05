"""Distributed checkpoint save/load + HF interchange.

Capability parity with the reference checkpoint stack
(runtime/checkpoint/llama_adapter.py:30-172 save/load, tools/
checkpoint_convert_{h2g,g2h}.py, hybrid_parallel_config.py:132-144 config
assert-on-resume): sharded save/restore of params + optimizer state + step,
the parallel-plan JSON stored alongside and verified on resume, and
HuggingFace state-dict import/export for GPT-2- and Llama-family models.

TPU-native: orbax-checkpoint writes each array shard from the device that
owns it (the reference hand-rolls per-(layer, tp-rank) files with dp-rank-0
writers); restore takes a target sharding tree, so a checkpoint saved under
one parallel plan reloads under another — the resharding the reference does
with TP-slicing loaders (llama_adapter.py:51-163) falls out of GSPMD.
"""

from __future__ import annotations

import json
import contextlib
import importlib.metadata
import os
import shutil
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import numpy as np


@contextlib.contextmanager
def _no_distribution_scan():
    """While orbax is imported, ``importlib.metadata.packages_distributions``
    answers with an empty map. orbax's cloud logger imports Google-cloud
    stubs whose ``google.api_core.check_python_version`` calls it on import,
    twice, only to word a warning it then does not issue (with an empty map
    it names the import package instead of the distribution). The real one
    reads the metadata and stats the files of every installed distribution:
    4.5 and 22 to 28 s a call on a TPU VM, over half of a cached run's
    set-up, and by how much depended on unrelated program text (PERF.md
    section 6, PR 33)."""
    real = importlib.metadata.packages_distributions
    importlib.metadata.packages_distributions = dict
    try:
        yield
    finally:
        importlib.metadata.packages_distributions = real


with _no_distribution_scan():
    import orbax.checkpoint as ocp

from hetu_galvatron_tpu.core.args_schema import ModelArgs
from hetu_galvatron_tpu.runtime import ckpt_paths
from hetu_galvatron_tpu.runtime.ckpt_paths import (
    clear_resume_pin,
    read_resume_pin,
    write_resume_pin,
)
from hetu_galvatron_tpu.utils.retrying import retry_call

Params = Dict[str, Any]


class WorldSizeMismatchError(ValueError):
    """The checkpoint's recorded world_size differs from the live world.

    Before this error existed a topology-changed resume surfaced as a
    shape error deep inside orbax/device_put; now it surfaces at load with
    both worlds named. The elastic resume path (``cli/train_dist.py``)
    catches exactly this condition to trigger re-search + reshard
    (``runtime/reshard.py``)."""

    def __init__(self, ckpt_dir: str, stored_world: int, live_world: int,
                 stored_plan: Optional[Dict[str, Any]] = None):
        self.ckpt_dir = ckpt_dir
        self.stored_world = int(stored_world)
        self.live_world = int(live_world)
        self.stored_plan = stored_plan
        super().__init__(
            f"checkpoint {ckpt_dir} was committed by a "
            f"{stored_world}-device world but the live world has "
            f"{live_world} devices: its arrays are laid out for the old "
            "plan and will not restore here. Re-search a plan for the "
            "live topology and reshard (runtime/reshard.py) — "
            "cli/train_dist.py does this automatically on resume when "
            "ckpt.load is set.")

# Atomic-commit protocol: a step directory is materialized under
# ``step_<n>.tmp``, fully written (params/opt_state shards + meta.json),
# stamped with the marker file below, and only then renamed to
# ``step_<n>``. Readers treat a step dir without the marker as partial
# garbage from a mid-save crash: never selected, eligible for GC. The
# marker (not just the rename) is kept because object stores mounted via
# FUSE can surface a directory rename non-atomically.
# The protocol's pure-path half (these constants, step parsing, commit
# detection, the cross-process RESUME_PIN lease) is defined ONCE in
# runtime/ckpt_paths.py so the jax-free process supervisor speaks the
# same protocol; the aliases below keep this module's historical names.
COMMIT_MARKER = ckpt_paths.COMMIT_MARKER
_TMP_SUFFIX = ckpt_paths.TMP_SUFFIX
_OLD_SUFFIX = ckpt_paths.OLD_SUFFIX

# transient-read retry policy for checkpoint I/O (flaky object-store
# mounts); override attempts via HGTPU_CKPT_RETRIES
def _io_retries() -> int:
    return max(int(os.environ.get("HGTPU_CKPT_RETRIES", "3")), 1)


# total-elapsed watchdog for one retried checkpoint I/O call (meta read
# or shard restore): a mount that hangs rather than erroring must not
# stall resume for attempts x hang; override via HGTPU_CKPT_DEADLINE_S
def _io_deadline() -> float:
    return max(float(os.environ.get("HGTPU_CKPT_DEADLINE_S", "120")), 0.1)


def _count(name: str, **labels) -> None:
    from hetu_galvatron_tpu.observability.registry import get_registry

    get_registry().counter(f"checkpoint/{name}", **labels).inc()


# ``step_<int>`` -> int (else None) / committed-dir detection: shared
# with the jax-free supervisor via ckpt_paths
_step_of = ckpt_paths.step_of
is_committed = ckpt_paths.is_committed


def _plan_fingerprint(hpc) -> Dict[str, Any]:
    from hetu_galvatron_tpu.utils.strategy import strategy_list2config

    cfg = strategy_list2config(
        hpc.layers, global_bsz=hpc.global_bsz, chunks=hpc.chunks,
        pipeline_type=hpc.pipeline_type,
        default_dp_type=hpc.default_dp_type.short, vocab=hpc.vocab,
        pp_division=hpc.pp_division,
        num_encoder_layers=hpc.num_encoder_layers or None)
    cfg["world_size"] = hpc.world_size
    return cfg


class PlanMismatchError(ValueError):
    """``strict_plan`` resume found a different plan fingerprint in the
    checkpoint. Typed (vs a bare ValueError) so the resilient resume
    loop can tell an OPERATOR error that reproduces on every candidate
    apart from per-checkpoint corruption it should fall back past."""


@dataclass
class _PendingSave:
    """An async save still being written by orbax: the commit (marker +
    rename + retention GC) runs only after ``wait_until_finished``."""

    ckptrs: List[Any]
    tmp_dir: str
    final_dir: str
    root: str
    keep_last: int = 0
    # chaos/test seam: hooks["before_commit"](tmp_dir) runs after the
    # payload is fully staged, before the marker/rename — the window a
    # kill-mid-save drill tears
    hooks: Dict[str, Callable[..., Any]] = field(default_factory=dict)


_PENDING: List[_PendingSave] = []


def _commit(tmp_dir: str, final_dir: str) -> None:
    """Publish a fully-written staging dir: marker first (fsynced), then
    the atomic rename onto the final step name."""
    marker = os.path.join(tmp_dir, COMMIT_MARKER)
    with open(marker, "w") as f:
        f.write("committed\n")
        f.flush()
        os.fsync(f.fileno())
    old = None
    if os.path.isdir(final_dir):
        # overwriting an existing step (re-save after a rollback): keep
        # the previous payload selectable until the new one lands — rename
        # aside, replace, then delete, so a crash at any point in between
        # still leaves a committed dir (the .old name is never selected
        # and is GC'd as stale)
        old = final_dir + _OLD_SUFFIX
        if os.path.isdir(old):
            shutil.rmtree(old)
        os.replace(final_dir, old)
    os.replace(tmp_dir, final_dir)
    if old is not None:
        shutil.rmtree(old, ignore_errors=True)
    _count("committed")


def save_checkpoint(
    path: str,
    step: int,
    params: Params,
    opt_state: Any = None,
    hpc=None,
    *,
    async_save: bool = False,
    train_state: Optional[Dict[str, Any]] = None,
    keep_last: int = 0,
    hooks: Optional[Dict[str, Callable[..., Any]]] = None,
) -> str:
    """Write step directory ``<path>/step_<n>`` with params/opt_state plus
    the hybrid-parallel plan JSON (reference hybrid_parallel_configs.json).

    The write is atomic: everything lands in ``step_<n>.tmp`` and is
    renamed into place only once complete, so a crash mid-save can never
    produce a directory :func:`latest_checkpoint` would select.
    ``train_state`` is an arbitrary JSON-serializable dict stored in
    meta.json (data-iterator position, RNG seed, rerun records, telemetry
    step — the full-state-resume payload). ``keep_last > 0`` prunes all
    but the newest N committed steps after this one commits."""
    ckpt_dir = os.path.abspath(os.path.join(path, f"step_{step}"))
    tmp_dir = ckpt_dir + _TMP_SUFFIX
    # multi-controller pods share the filesystem: only the commit runner
    # (process 0) cleans stale staging dirs and writes meta — a lagging
    # peer must never rmtree a dir its neighbors already stream into
    primary = jax.process_index() == 0
    if primary:
        if os.path.isdir(tmp_dir):
            # stale staging dir from a crashed earlier attempt at this step
            shutil.rmtree(tmp_dir)
        os.makedirs(tmp_dir, exist_ok=True)
    if jax.process_count() > 1:
        # barrier: no peer may start streaming shards into tmp_dir until
        # the primary's stale-dir cleanup above has finished
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices(f"hgtpu_ckpt_stage_{step}")
    ckptrs = [ocp.StandardCheckpointer()]
    ckptrs[0].save(os.path.join(tmp_dir, "params"), params, force=True)
    if opt_state is not None:
        # separate checkpointer: StandardCheckpointer serializes saves, a
        # second handle lets both trees stream concurrently
        ckptrs.append(ocp.StandardCheckpointer())
        ckptrs[-1].save(os.path.join(tmp_dir, "opt_state"), opt_state,
                        force=True)
    meta: Dict[str, Any] = {"step": step}
    if hpc is not None:
        meta["hybrid_parallel_config"] = _plan_fingerprint(hpc)
    if train_state is not None:
        meta["train_state"] = train_state
    if primary:
        with open(os.path.join(tmp_dir, "meta.json"), "w") as f:
            json.dump(meta, f, indent=2)
    _count("saved")
    pending = _PendingSave(ckptrs, tmp_dir, ckpt_dir,
                           os.path.abspath(path), keep_last,
                           dict(hooks or {}))
    if async_save:
        # orbax streams shards in the background; training overlaps the
        # write and wait_for_checkpoints() commits it at the next barrier
        # (before any read of the ckpt, and at exit)
        _PENDING.append(pending)
    else:
        _finish(pending)
    return ckpt_dir


def _finish(p: _PendingSave) -> None:
    # await EVERY checkpointer even when an earlier one fails: an
    # abandoned background write would keep streaming into a staging dir
    # a restarted attempt is about to clean
    first_err: Optional[BaseException] = None
    for c in p.ckptrs:
        try:
            c.wait_until_finished()
        except BaseException as e:  # noqa: BLE001 — re-raised below
            if first_err is None:
                first_err = e
    if first_err is not None:
        raise first_err
    # multi-controller pods: every process streams its shards through
    # orbax, but exactly one performs the marker/rename commit and the
    # retention GC (shared filesystem)
    if jax.process_index() == 0:
        before_commit = p.hooks.get("before_commit")
        if before_commit is not None:
            # fully staged, not yet committed: the exact window a
            # kill-mid-save chaos drill tears (and a hung-save drill
            # stalls) — real faults die here too, so resume must treat
            # the unmarked staging dir as garbage
            before_commit(p.tmp_dir)
        _commit(p.tmp_dir, p.final_dir)
        if p.keep_last > 0:
            gc_checkpoints(p.root, keep_last=p.keep_last)


def wait_for_checkpoints() -> None:
    """Block until every async save has committed (reference async_save
    drains at exit). The queue drains completely even when one save
    fails: every checkpointer is awaited (a per-entry except keeps the
    loop going, so no abandoned background write keeps the process alive
    or races a later save) and the first error re-raises after the
    drain. Each entry is popped before finishing so its own final dir is
    not counted as in-flight by its retention GC."""
    first_err: Optional[BaseException] = None
    while _PENDING:
        p = _PENDING.pop(0)
        try:
            _finish(p)
        except BaseException as e:  # noqa: BLE001 — re-raised after drain
            if first_err is None:
                first_err = e
    if first_err is not None:
        raise first_err


def _in_flight_dirs() -> set:
    return {p.tmp_dir for p in _PENDING} | {p.final_dir for p in _PENDING}


# The step dir a live resume just selected, per checkpoint root: retention
# pruning racing a concurrent resume (an async save committing keep_last
# GC between latest_checkpoint() and the meta/shard reads) must never
# delete it out from under the restore. latest_checkpoint() records its
# selection here; the NEXT selection on the same root releases the
# previous one, so a long run retains at most one extra step dir.
# SCOPE: process-local — it closes the in-process race (the async-save
# commit GC and maybe_resume share this process). The CROSS-process half
# is the RESUME_PIN lease (runtime/ckpt_paths.py): the relaunching
# supervisor stamps the step dir the next child attempt will restore
# from, and gc_checkpoints below holds a live (unexpired) pin out of the
# retention prune set even though the pinning process is not this one.
_RESUME_PROTECTED: Dict[str, str] = {}


def _recover_orphaned_old(path: str) -> None:
    """Roll back a crash mid-overwrite: if ``step_<n>.old`` (the previous
    committed payload renamed aside by :func:`_commit`) exists without a
    ``step_<n>``, the crash hit between the two renames — restore the old
    payload so the step stays selectable."""
    for entry in os.listdir(path):
        if not entry.endswith(_OLD_SUFFIX):
            continue
        base = entry[:-len(_OLD_SUFFIX)]
        if _step_of(base) is None:
            continue
        full = os.path.join(path, entry)
        final = os.path.join(path, base)
        if not os.path.exists(final) and is_committed(full):
            try:
                os.replace(full, final)
                _count("old_recovered")
            except OSError:
                pass  # a concurrent reader raced the same rollback


def gc_checkpoints(path: str, *, keep_last: int = 0) -> List[str]:
    """Remove partial step dirs (crashed saves) and, with ``keep_last > 0``,
    all but the newest N committed steps. In-flight async saves are never
    touched. Returns the removed paths."""
    if not os.path.isdir(path):
        return []
    _recover_orphaned_old(path)
    busy = _in_flight_dirs()
    protected = {_RESUME_PROTECTED.get(os.path.abspath(path))}
    # cross-process lease: a supervisor that just relaunched a child has
    # pinned the step dir that child is about to restore from — this
    # process's retention GC must not prune it mid-restore
    pinned = read_resume_pin(path)
    if pinned is not None:
        protected.add(os.path.abspath(pinned))
    protected.discard(None)
    removed: List[str] = []
    committed: List[tuple] = []
    for entry in sorted(os.listdir(path)):
        full = os.path.join(path, entry)
        if not os.path.isdir(full) or full in busy:
            continue
        step = _step_of(entry)
        if step is not None and is_committed(full):
            committed.append((step, full))
            continue
        # our own staging/partial/old dirs only — a stray step_x or
        # step_5.partial we did not create is skipped, never deleted.
        # A surviving .old here is superseded (its final dir exists, or
        # _recover_orphaned_old would have rolled it back).
        stale_ours = step is not None or any(
            entry.endswith(suf) and _step_of(entry[:-len(suf)]) is not None
            for suf in (_TMP_SUFFIX, _OLD_SUFFIX))
        if stale_ours:
            shutil.rmtree(full, ignore_errors=True)
            removed.append(full)
            _count("gc_removed", kind="partial")
    if keep_last > 0 and len(committed) > keep_last:
        committed.sort()
        for _, full in committed[:-keep_last]:
            if os.path.abspath(full) in protected:
                # a live resume (in-process selection or cross-process
                # RESUME_PIN) holds this step out of the prune set
                _count("gc_protected")
                continue
            shutil.rmtree(full, ignore_errors=True)
            removed.append(full)
            _count("gc_removed", kind="retention")
    return removed


def latest_checkpoint(path: str) -> Optional[str]:
    """Newest COMMITTED step dir, or None. Stray ``step_*`` entries with a
    non-integer suffix (orbax temp dirs, ``step_5.partial``) are skipped
    instead of crashing resume, and uncommitted partial dirs from a
    mid-save crash are never selected."""
    if not os.path.isdir(path):
        return None
    _recover_orphaned_old(path)
    best_step, best = -1, None
    for entry in os.listdir(path):
        step = _step_of(entry)
        if step is None:
            continue
        full = os.path.join(path, entry)
        if not os.path.isdir(full) or not is_committed(full):
            _count("partial_skipped")
            continue
        if step > best_step:
            best_step, best = step, full
    root = os.path.abspath(path)
    if best is not None:
        # shield the selection from retention pruning until the next
        # selection on this root (see _RESUME_PROTECTED)
        _RESUME_PROTECTED[root] = os.path.abspath(best)
    else:
        _RESUME_PROTECTED.pop(root, None)
    return best


def read_checkpoint_meta(ckpt_dir: str) -> Dict[str, Any]:
    """The step dir's meta.json (step, plan fingerprint, train_state) —
    {} when absent. Reads retry transient I/O errors (flaky object-store
    mounts) through the shared backoff policy."""
    mp = os.path.join(ckpt_dir, "meta.json")
    if not os.path.exists(mp):
        return {}

    def _read():
        with open(mp) as f:
            return json.load(f)

    return retry_call(_read, attempts=_io_retries(), base=0.2, cap=5.0,
                      retryable=lambda e: isinstance(e, OSError),
                      op="checkpoint.read_meta",
                      deadline_s=_io_deadline())


def try_read_checkpoint_meta(
        ckpt_dir: str) -> Tuple[Dict[str, Any], Optional[Exception]]:
    """:func:`read_checkpoint_meta` that never raises: ``(meta, None)``
    on success, ``({}, error)`` on a corrupt/truncated/unreadable
    meta.json. Resume paths must degrade to the previous committed step
    (or a fresh start) with a warning, not a traceback."""
    try:
        return read_checkpoint_meta(ckpt_dir), None
    except Exception as e:  # noqa: BLE001 — defensive read by contract
        return {}, e


def committed_checkpoints(path: str) -> List[str]:
    """Every committed step dir under ``path``, NEWEST first — the
    candidate order for a resilient resume (try the newest, fall back
    on corruption)."""
    return [d for _, d in reversed(ckpt_paths.committed_steps(path))]


def load_latest_resilient(
    path: str,
    params_target: Params,
    opt_target: Any = None,
    hpc=None,
    *,
    strict_plan: bool = False,
    expected_world: Optional[int] = None,
    log: Callable[[str], None] = lambda m: print(m, flush=True),
) -> Optional[Tuple[Params, Any, int, str]]:
    """Restore from the newest READABLE committed checkpoint under
    ``path``: corruption (truncated/garbled meta.json, a missing payload
    leaf, a stray COMMITTED marker over a torn payload) falls back to
    the previous committed step with a warning
    (``checkpoint/corrupt_fallback``), never a traceback.

    Returns ``(params, opt_state, step, ckpt_dir)`` or None when no
    committed checkpoint exists. Two error classes still PROPAGATE by
    contract: :class:`WorldSizeMismatchError` (the elastic resume
    trigger — a topology change is not corruption) and
    :class:`PlanMismatchError` (a strict-plan operator error reproduces
    on every candidate; silently "falling back" to an older step would
    train the wrong plan). If candidates exist but every one is
    unreadable, raises RuntimeError naming them — silently restarting a
    long run from scratch is worse than a loud stop."""
    candidates = committed_checkpoints(path)
    if not candidates:
        return None
    last_err: Optional[Exception] = None
    for ckdir in candidates:
        try:
            params, opt_state, step = load_checkpoint(
                ckdir, params_target, opt_target, hpc=hpc,
                strict_plan=strict_plan, expected_world=expected_world)
        except (WorldSizeMismatchError, PlanMismatchError):
            raise
        except Exception as e:  # noqa: BLE001 — corruption class varies
            # (json decode errors, orbax restore errors, missing files,
            # OSErrors that exhausted the retry budget)
            last_err = e
            _count("corrupt_fallback")
            log(f"warning: checkpoint {ckdir} is unreadable "
                f"({type(e).__name__}: {e}); falling back to the "
                "previous committed step")
            continue
        # success: shield the selection from retention pruning (same
        # registration latest_checkpoint performs)
        _RESUME_PROTECTED[os.path.abspath(path)] = os.path.abspath(ckdir)
        return params, opt_state, step, ckdir
    raise RuntimeError(
        f"all {len(candidates)} committed checkpoint(s) under {path} are "
        f"unreadable (last error: {type(last_err).__name__}: {last_err}); "
        "refusing to silently restart from scratch")


def load_checkpoint(
    ckpt_dir: str,
    params_target: Params,
    opt_target: Any = None,
    hpc=None,
    *,
    strict_plan: bool = False,
    expected_world: Optional[int] = None,
):
    """Restore into the target sharding/shape tree. ``strict_plan`` asserts
    the stored plan matches (the reference asserts equality on resume,
    hybrid_parallel_config.py:132-144); by default a mismatch is allowed —
    orbax reshards into the new plan's shardings. ``expected_world``
    validates the checkpoint's recorded world_size against the live world
    and raises the typed :class:`WorldSizeMismatchError` naming both
    (instead of a shape error deep in device_put) — the condition the
    elastic resume path catches to trigger re-search + reshard. Restores
    retry transient I/O errors with jittered backoff (preemptible fleets
    resume through flaky object-store reads)."""
    ckpt_dir = os.path.abspath(ckpt_dir)  # orbax rejects relative paths
    meta = read_checkpoint_meta(ckpt_dir)
    if "step" not in meta:
        raise FileNotFoundError(
            f"{ckpt_dir} has no meta.json — not a committed checkpoint")
    if expected_world is not None:
        stored_plan = meta.get("hybrid_parallel_config") or {}
        sw = stored_plan.get("world_size")
        if sw is not None and int(sw) != int(expected_world):
            raise WorldSizeMismatchError(ckpt_dir, int(sw),
                                         int(expected_world), stored_plan)
    if strict_plan and hpc is not None:
        stored = meta.get("hybrid_parallel_config")
        current = _plan_fingerprint(hpc)
        if stored != current:
            raise PlanMismatchError(
                f"checkpoint plan mismatch:\nstored  {stored}\n"
                f"current {current}")
    ckptr = ocp.StandardCheckpointer()

    def _restore(sub, target):
        return retry_call(
            lambda: ckptr.restore(os.path.join(ckpt_dir, sub), target),
            attempts=_io_retries(), base=0.2, cap=5.0,
            retryable=lambda e: isinstance(e, OSError),
            op="checkpoint.restore",
            deadline_s=_io_deadline())

    params = _restore("params", params_target)
    opt_state = None
    if opt_target is not None and os.path.isdir(
            os.path.join(ckpt_dir, "opt_state")):
        opt_state = _restore("opt_state", opt_target)
    return params, opt_state, meta["step"]


# ---------------------------------------------------------------------------
# Async snapshot checkpointing
# ---------------------------------------------------------------------------


def _gauge(name: str, value: float) -> None:
    try:
        from hetu_galvatron_tpu.observability.registry import get_registry

        get_registry().gauge(f"checkpoint/{name}").set(float(value))
    except Exception:  # noqa: BLE001 — observability is best-effort
        pass


@dataclass
class _Snapshot:
    """A donation-safe on-device copy of the model state, queued for the
    background writer."""

    step: int
    params: Any
    opt_state: Any
    train_state: Optional[Dict[str, Any]] = None


class AsyncCheckpointer:
    """Split saves: on-step jitted device snapshot + background commit.

    ``snapshot(step, params, opt_state)`` dispatches ONE jitted
    copy-program over the state's device arrays (donation-safe: XLA's
    data dependencies order the copies before the next step may reuse
    donated buffers) and returns immediately — the measured dispatch
    stall is the only step time a save costs, exported as the
    ``checkpoint/snapshot_stall_ms`` gauge. A single daemon writer
    thread host-gathers the copies (``jax.device_get`` blocks until the
    device copies land) and writes/commits through
    :func:`save_checkpoint`'s atomic COMMITTED-marker protocol.

    Single-writer overlap rule: the queue holds at most ONE pending
    snapshot — a new snapshot supersedes an unstarted write
    (``checkpoint/snapshot_superseded``; the newer state strictly
    dominates), but never interrupts a STARTED write (a half-written
    staging dir would just be torn garbage for GC).

    A hung write (exceeding ``save_timeout_s``) is declared by the
    watchdog (``checkpoint/hung_saves``) and :meth:`drain` stops waiting
    on it — the daemon thread cannot block process exit. Writer errors
    are latched and re-raised at the next ``snapshot()``/``drain()``.

    Single-controller only: the writer thread cannot participate in
    multi-process save barriers (``CheckpointCadence`` falls back to the
    orbax async path on pods, with a logged reason).
    """

    def __init__(self, root: str, *, hpc=None, keep_last: int = 0,
                 save_timeout_s: float = 120.0,
                 hooks: Optional[Dict[str, Callable[..., Any]]] = None,
                 log: Callable[[str], None] = lambda m: print(m,
                                                              flush=True)):
        self.root = root
        self.hpc = hpc
        self.keep_last = keep_last
        self.save_timeout_s = float(save_timeout_s)
        self.hooks = dict(hooks or {})
        self._log = log
        self._cv = threading.Condition()
        self._queue: Optional[_Snapshot] = None
        self._inflight: Optional[_Snapshot] = None
        self._started_at: Optional[float] = None
        self._hung_step: Optional[int] = None
        self._closed = False
        self._thread: Optional[threading.Thread] = None
        self._copy_fn = None
        self.error: Optional[BaseException] = None
        self.last_commit: Optional[Dict[str, Any]] = None

    # -- on-step half -------------------------------------------------------

    def _device_copy(self, tree):
        import jax.numpy as jnp

        leaves, treedef = jax.tree.flatten(tree)
        idx = [i for i, x in enumerate(leaves) if isinstance(x, jax.Array)]
        if idx:
            if self._copy_fn is None:
                self._copy_fn = jax.jit(
                    lambda xs: tuple(jnp.copy(x) for x in xs))
            copies = self._copy_fn(tuple(leaves[i] for i in idx))
            for i, c in zip(idx, copies):
                leaves[i] = c
        return jax.tree.unflatten(treedef, leaves)

    def snapshot(self, step: int, params: Params, opt_state: Any = None,
                 *, train_state: Optional[Dict[str, Any]] = None) -> float:
        """Queue a device snapshot of the state at ``step``; returns the
        dispatch stall in ms (the step's entire save cost)."""
        self.check_watchdog()
        if self.error is not None:
            err, self.error = self.error, None
            raise err
        t0 = time.perf_counter()
        params_c, opt_c = self._device_copy((params, opt_state))
        stall_ms = (time.perf_counter() - t0) * 1e3
        _gauge("snapshot_stall_ms", stall_ms)
        _count("snapshots")
        snap = _Snapshot(step, params_c, opt_c, train_state)
        with self._cv:
            if self._queue is not None:
                _count("snapshot_superseded")
                self._log(
                    f"checkpoint: snapshot at step {step} supersedes the "
                    f"unstarted write at step {self._queue.step}")
            self._queue = snap
            self._cv.notify_all()
        self._ensure_thread()
        return stall_ms

    # -- background half ----------------------------------------------------

    def _ensure_thread(self) -> None:
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(
                target=self._worker, daemon=True, name="ckpt-writer")
            self._thread.start()

    def _worker(self) -> None:
        while True:
            with self._cv:
                while self._queue is None and not self._closed:
                    self._cv.wait(timeout=0.2)
                if self._queue is None:
                    return  # closed and drained
                snap, self._queue = self._queue, None
                self._inflight = snap
                self._started_at = time.monotonic()
            try:
                before_write = self.hooks.get("before_write")
                if before_write is not None:
                    before_write(snap.step)
                # device_get blocks until the on-device copies land, then
                # the write streams from host memory — the training loop
                # is untouched either way
                host_params, host_opt = jax.device_get(
                    (snap.params, snap.opt_state))
                save_checkpoint(
                    self.root, snap.step, host_params, host_opt,
                    hpc=self.hpc, async_save=False,
                    train_state=snap.train_state,
                    keep_last=self.keep_last, hooks=self.hooks)
                self.last_commit = {"step": snap.step,
                                    "t_wall": time.time()}
                _count("async_committed")
            except BaseException as e:  # noqa: BLE001 — latched for caller
                self.error = e
                _count("async_save_errors")
                try:
                    self._log("warning: async checkpoint write at step "
                              f"{snap.step} failed: {e}")
                except Exception:  # noqa: BLE001 — log must not kill worker
                    pass
            finally:
                with self._cv:
                    self._inflight = None
                    self._started_at = None
                    self._cv.notify_all()

    # -- watchdog / drain ---------------------------------------------------

    def check_watchdog(self) -> bool:
        """True when the in-flight write has exceeded ``save_timeout_s``
        (counted once per hung save as ``checkpoint/hung_saves``)."""
        with self._cv:
            started, inflight = self._started_at, self._inflight
        if (started is None or inflight is None
                or time.monotonic() - started <= self.save_timeout_s):
            return False
        if self._hung_step != inflight.step:
            self._hung_step = inflight.step
            _count("hung_saves")
            self._log(f"warning: checkpoint write at step {inflight.step} "
                      f"exceeded the {self.save_timeout_s:.1f}s watchdog "
                      "deadline; it will not be waited on")
        return True

    def pending(self) -> bool:
        with self._cv:
            return self._queue is not None or self._inflight is not None

    def drain(self, timeout_s: Optional[float] = None) -> bool:
        """Block until queued + in-flight writes finish. Returns False
        (after declaring the save hung) instead of blocking forever when
        the writer exceeds the deadline; re-raises a latched writer
        error once drained."""
        if timeout_s is None:
            timeout_s = self.save_timeout_s
        deadline = time.monotonic() + timeout_s
        with self._cv:
            while self._queue is not None or self._inflight is not None:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    break
                self._cv.wait(timeout=min(remaining, 0.2))
            drained = self._queue is None and self._inflight is None
        if not drained:
            self.check_watchdog()
            if self._hung_step is None:
                # not yet past the per-save watchdog, but the caller's
                # drain budget is spent — same give-up contract
                _count("hung_saves")
                self._hung_step = (self._inflight.step
                                   if self._inflight else -1)
        if self.error is not None:
            err, self.error = self.error, None
            raise err
        return drained

    def close(self, timeout_s: Optional[float] = None) -> bool:
        drained = self.drain(timeout_s)
        with self._cv:
            self._closed = True
            self._cv.notify_all()
        if self._thread is not None and drained:
            self._thread.join(timeout=5.0)
        return drained


class CheckpointCadence:
    """One save policy for both cadences and both write modes.

    ``due(it)`` is true when the step cadence (``ckpt.save_interval``)
    OR the wall-clock cadence (``ckpt.interval_s``) has elapsed — the
    time cadence bounds elastic RPO in seconds even when steps slow
    down. ``save(step, ...)`` dispatches through the
    :class:`AsyncCheckpointer` snapshot path when ``ckpt.snapshot_async``
    is set (single-controller), else through the classic synchronous /
    orbax-async :func:`save_checkpoint`. Goodput booking matches the
    mode: async saves bill only the snapshot stall (+ the final drain)
    to ``checkpoint_save``, moving write time out of
    ``productive_step``."""

    def __init__(self, ck, *, hpc=None, goodput=None,
                 log: Callable[[str], None] = lambda m: print(m,
                                                              flush=True),
                 hooks: Optional[Dict[str, Callable[..., Any]]] = None,
                 clock: Callable[[], float] = time.monotonic):
        self.ck = ck
        self.hpc = hpc
        self.goodput = goodput
        self.hooks = dict(hooks or {})
        self._log = log
        self._clock = clock
        self._last_save_t = clock()
        self.async_ckptr: Optional[AsyncCheckpointer] = None
        if ck.save and ck.snapshot_async:
            if jax.process_count() > 1:
                log("ckpt.snapshot_async: multi-process pod — the writer "
                    "thread cannot join save barriers; falling back to "
                    "the synchronous/orbax-async path")
            else:
                self.async_ckptr = AsyncCheckpointer(
                    ck.save, hpc=hpc, keep_last=ck.keep_last,
                    save_timeout_s=ck.save_timeout_s, hooks=self.hooks,
                    log=log)

    def due(self, it: int) -> bool:
        ck = self.ck
        if not ck.save:
            return False
        if ck.save_interval and (it + 1) % ck.save_interval == 0:
            return True
        if ck.interval_s and \
                self._clock() - self._last_save_t >= ck.interval_s:
            return True
        return False

    def save(self, step: int, params: Params, opt_state: Any = None,
             *, train_state: Optional[Dict[str, Any]] = None) -> None:
        self._last_save_t = self._clock()
        if self.async_ckptr is not None:
            stall_ms = self.async_ckptr.snapshot(
                step, params, opt_state, train_state=train_state)
            if self.goodput is not None:
                # only the dispatch stall steals step time; the write
                # overlaps training and its drain bills at exit
                self.goodput.add("checkpoint_save", stall_ms / 1e3)
            return

        def _save():
            save_checkpoint(self.ck.save, step, params, opt_state,
                            hpc=self.hpc, async_save=self.ck.async_save,
                            train_state=train_state,
                            keep_last=self.ck.keep_last, hooks=self.hooks)

        if self.goodput is not None:
            with self.goodput.measure("checkpoint_save"):
                _save()
        else:
            _save()

    def drain(self) -> None:
        """Exit/preempt barrier: nothing in-flight may outlive (or race)
        what follows — a synchronous exit save, or process exit. A hung
        async write is abandoned after its watchdog deadline rather than
        blocking shutdown."""
        if self.async_ckptr is not None:
            if not self.async_ckptr.drain():
                self._log("warning: abandoning a hung checkpoint write "
                          "at exit (see checkpoint/hung_saves)")
        wait_for_checkpoints()


# ---------------------------------------------------------------------------
# HuggingFace interchange (h2g / g2h)
# ---------------------------------------------------------------------------


# public names of an expert layer, by ``cfg.moe_hf_layout``: the router, and
# one expert's gate, up and down projections
_MOE_HF_NAMES = {
    "mixtral": ("block_sparse_moe.gate.weight",
                "block_sparse_moe.experts.{e}.w1.weight",
                "block_sparse_moe.experts.{e}.w3.weight",
                "block_sparse_moe.experts.{e}.w2.weight"),
    "olmoe": ("mlp.gate.weight",
              "mlp.experts.{e}.gate_proj.weight",
              "mlp.experts.{e}.up_proj.weight",
              "mlp.experts.{e}.down_proj.weight"),
    "lfm2": ("feed_forward.gate.weight",
             "feed_forward.experts.{e}.w1.weight",
             "feed_forward.experts.{e}.w3.weight",
             "feed_forward.experts.{e}.w2.weight"),
}
# DeepSeek-V3's released layout: olmoe's names for the router and experts
_MOE_HF_NAMES["deepseek"] = _MOE_HF_NAMES["olmoe"]
# Kimi Linear's released layout (``modeling_kimi.py``): mixtral's names
_MOE_HF_NAMES["kimi"] = _MOE_HF_NAMES["mixtral"]
# Laguna's: olmoe's names again, with ONE shared expert (singular)
_MOE_HF_NAMES["laguna"] = _MOE_HF_NAMES["olmoe"]
# the selection bias of an expert layer, where the layout has a name for it
_MOE_HF_BIAS = {"lfm2": "feed_forward.expert_bias",
                "deepseek": "mlp.gate.e_score_correction_bias",
                "kimi": "block_sparse_moe.gate.e_score_correction_bias"}
# the shared expert's gate, up and down projections, where the layout has a
# slot for one
_MOE_HF_SHARED = {
    layout: tuple(f"{at}.{m}_proj.weight" for m in ("gate", "up", "down"))
    for layout, at in (("deepseek", "mlp.shared_experts"),
                       ("kimi", "block_sparse_moe.shared_experts"),
                       ("laguna", "mlp.shared_expert"))}
# the gate a head of an attention block (``ModelArgs.gating``)
_ATTN_GATE_HF_NAME = "self_attn.g_proj.weight"

# public names of a block's norms, attention projections, q/k norms, dense
# MLP and of the final norm, by ``cfg.hf_layout``; a ``conv`` block's names
# are LFM2's, the one published family that has one
_BLOCK_HF_NAMES = {
    "llama": {"ln1": "input_layernorm.weight",
              "ln2": "post_attention_layernorm.weight",
              "o": "self_attn.o_proj.weight",
              "q_norm": "self_attn.q_norm.weight",
              "k_norm": "self_attn.k_norm.weight",
              "gate": "mlp.gate_proj.weight", "up": "mlp.up_proj.weight",
              "down": "mlp.down_proj.weight", "final": "model.norm.weight"},
    "lfm2": {"ln1": "operator_norm.weight", "ln2": "ffn_norm.weight",
             "o": "self_attn.out_proj.weight",
             "q_norm": "self_attn.q_layernorm.weight",
             "k_norm": "self_attn.k_layernorm.weight",
             "gate": "feed_forward.w1.weight", "up": "feed_forward.w3.weight",
             "down": "feed_forward.w2.weight",
             "final": "model.embedding_norm.weight"},
}
# granite: llama's norms and attention; the shared MLP keeps gate | up in
# ONE matrix, as ``win`` does
_BLOCK_HF_NAMES["granite"] = {
    **{k: v for k, v in _BLOCK_HF_NAMES["llama"].items()
       if k not in ("gate", "up")},
    "gate_up": "shared_mlp.input_linear.weight",
    "down": "shared_mlp.output_linear.weight"}
# olmo_hybrid: llama's names; an attention block's two norms sit on its
# branches' outputs under Olmo 3's names, a linear_attention block's on the
# inputs under the released block's (``_BLOCK_NORM_HF_NAMES``)
_BLOCK_HF_NAMES["olmo_hybrid"] = {
    **_BLOCK_HF_NAMES["llama"],
    "ln1": "post_attention_layernorm.weight",
    "ln2": "post_feedforward_layernorm.weight"}
# the names of a block's two norms where they differ by the block's mixer
# kind: (hf_layout, mixer) -> the entries of ``_BLOCK_HF_NAMES`` it replaces
_BLOCK_NORM_HF_NAMES = {
    ("olmo_hybrid", "linear_attention"): {
        "ln1": "attention_layer_norm.weight",
        "ln2": "feedforward_layer_norm.weight"}}
_CONV_HF_NAMES = ("conv.in_proj.weight", "conv.conv.weight",
                  "conv.out_proj.weight")
# a ``mamba`` block's names are Granite-4.0-H's (HF
# ``GraniteMoeHybridMambaLayer``): the program's leaf -> the public name;
# in_proj's rows are z | x | B | C | dt as ``win``'s columns are
_MAMBA_HF_NAMES = {"win": "mamba.in_proj.weight",
                   "taps": "mamba.conv1d.weight",
                   "conv_bias": "mamba.conv1d.bias",
                   "dt_bias": "mamba.dt_bias", "A_log": "mamba.A_log",
                   "D": "mamba.D", "norm": "mamba.norm.weight",
                   "wout": "mamba.out_proj.weight"}


def _mamba_to_hf(mp: Params, get) -> Dict[str, np.ndarray]:
    """A mamba block's leaves by ``_MAMBA_HF_NAMES``' public names: the two
    projections transposed, Conv1d's depthwise kernel [channels, 1, taps]."""
    out = {}
    for leaf, name in _MAMBA_HF_NAMES.items():
        if leaf not in mp:
            continue
        w = get(mp[leaf]["scale"] if leaf == "norm" else mp[leaf])
        out[name] = (w.T if leaf in ("win", "wout")
                     else w[:, None, :] if leaf == "taps" else w)
    return out
# a ``latent_attention`` block's names are DeepSeek-V3's
# (``DeepseekV3Attention``): the program's leaf -> the public name (a block
# holds ``wq`` or the three of the low-rank step, ``ModelArgs.q_lora_rank``)
_LATENT_HF_NAMES = {"wq": "self_attn.q_proj.weight",
                    "wq_a": "self_attn.q_a_proj.weight",
                    "q_norm": "self_attn.q_a_layernorm.weight",
                    "wq_b": "self_attn.q_b_proj.weight",
                    "wkv_a": "self_attn.kv_a_proj_with_mqa.weight",
                    "kv_norm": "self_attn.kv_a_layernorm.weight",
                    "wkv_b": "self_attn.kv_b_proj.weight",
                    "wo": "self_attn.o_proj.weight"}
# a block's two sets of residual maps (``ModelArgs.hc_mult`` > 1): no public
# checkpoint names them, so the names are this exporter's own
_HC_HF_NAMES = {"hc1": "attn_hc.", "hc2": "mlp_hc."}
# the further prediction depth is DeepSeek-V3's released layout: a block at
# index ``num_hidden_layers`` with these beside its own weights (its
# ``embed_tokens`` and ``shared_head.head`` are the model's and not repeated)
_MTP_HF_NAMES = {"enorm": "enorm.weight", "hnorm": "hnorm.weight",
                 "eh_proj": "eh_proj.weight",
                 "norm": "shared_head.norm.weight"}
# a ``kda`` block's names are Kimi Linear's (``modeling_kimi.py``'s
# ``KimiDeltaAttention``, under ``self_attn``): the program's leaf -> the
# public name; ``wqkv``, ``taps`` and ``wlow`` hold three public tensors
# each, side by side in this order
_KDA_HF_NAMES = {"wf_b": "self_attn.f_b_proj.weight",
                 "wg_b": "self_attn.g_b_proj.weight",
                 "dt_bias": "self_attn.dt_bias", "A_log": "self_attn.A_log",
                 "norm": "self_attn.o_norm.weight",
                 "wout": "self_attn.o_proj.weight"}
_KDA_HF_THIRDS = {
    "wqkv": tuple(f"self_attn.{m}_proj.weight" for m in "qkv"),
    "taps": tuple(f"self_attn.{m}_conv1d.weight" for m in "qkv"),
    "wlow": tuple(f"self_attn.{m}_proj.weight" for m in ("f_a", "g_a", "b"))}


# a ``linear_attention`` block's names are HF ``Qwen3NextGatedDeltaNet``'s
# projections one by one, as Olmo Hybrid's released block holds them (under
# ``linear_attn``): the program's leaf -> the public name; ``wqkv`` and
# ``taps`` hold three public tensors each and ``wab`` two, side by side
_GDN_HF_NAMES = {"wg": "linear_attn.g_proj.weight",
                 "dt_bias": "linear_attn.dt_bias",
                 "A_log": "linear_attn.A_log",
                 "norm": "linear_attn.o_norm.weight",
                 "wout": "linear_attn.o_proj.weight"}
_GDN_HF_PARTS = {
    "wqkv": tuple(f"linear_attn.{m}_proj.weight" for m in "qkv"),
    "taps": tuple(f"linear_attn.{m}_conv1d.weight" for m in "qkv"),
    "wab": tuple(f"linear_attn.{m}_proj.weight" for m in "ab")}


def _rope_columns_to_hf(width: int) -> np.ndarray:
    """Column order that turns the half layout ``modules.apply_rope``
    rotates (first halves, then second halves) into the interleaved one a
    DeepSeek checkpoint stores: public column ``2 i`` is the program's ``i``,
    ``2 i + 1`` its ``i + width / 2``."""
    return np.stack([np.arange(width // 2),
                     np.arange(width // 2) + width // 2], axis=1).reshape(-1)


def _latent_hf_names(cfg: ModelArgs) -> Dict[str, str]:
    """``_LATENT_HF_NAMES`` of the leaves this model's latent blocks hold:
    ``wq`` alone, or the low-rank step's three."""
    absent = ("wq",) if cfg.q_lora_rank else ("wq_a", "q_norm", "wq_b")
    return {leaf: name for leaf, name in _LATENT_HF_NAMES.items()
            if leaf not in absent}


def _latent_rope_orders(cfg: ModelArgs, to_hf: bool) -> Dict[str, Any]:
    """Column order, by leaf (the query's last projection and ``wkv_a``),
    between the program's layout and the public one: the rotated columns of
    every query head and of the shared key permuted, everything else in
    place. Empty for a model that rotates nothing (no RoPE): its columns
    are stored as they are."""
    if cfg.position_embedding_type != "rope":
        return {}
    dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    order = _rope_columns_to_hf(dr)
    if not to_hf:
        order = np.argsort(order)
    head = np.concatenate([np.arange(dn), dn + order])
    q = (np.arange(cfg.num_attention_heads)[:, None] * (dn + dr)
         + head[None, :]).reshape(-1)
    kv = np.concatenate([np.arange(cfg.kv_lora_rank),
                         cfg.kv_lora_rank + order])
    return {"wq_b" if cfg.q_lora_rank else "wq": q, "wkv_a": kv}


# a model with a tower (Kimi-VL's ``KimiVLForConditionalGeneration``): the
# decoder's names under ``language_model.``, the tower's under
# ``vision_tower.`` and the projector's under ``multi_modal_projector.``
_DECODER_PREFIX = "language_model."
_TOWER_BLOCK = "vision_tower.encoder.blocks.{i}."
# a tower block's leaves (models/tower.py) and their public stems
_TOWER_BLOCK_HF = {"ln0": "norm0", "ln1": "norm1", "qkv": "wqkv",
                   "out": "wo", "fc0": "mlp.fc0", "fc1": "mlp.fc1"}
_TOWER_NORMS_HF = {"final_norm": "vision_tower.encoder.final_layernorm",
                   "pre_norm": "multi_modal_projector.pre_norm"}
_PROJECTOR_HF = {"fc1": "multi_modal_projector.linear_1",
                 "fc2": "multi_modal_projector.linear_2"}
_PATCH_HF = "vision_tower.patch_embed."


def _tower_hf_leaves(cfg: ModelArgs):
    """(path in ``params["tower"]``, public stem, kind) of every leaf pair:
    ``norm`` = scale and bias, ``linear`` = w [in, out] and b."""
    for i in range(cfg.tower_layers):
        for leaf, stem in _TOWER_BLOCK_HF.items():
            yield (("blocks", i, leaf), _TOWER_BLOCK.format(i=i) + stem,
                   "norm" if leaf.startswith("ln") else "linear")
    yield ("final_norm",), _TOWER_NORMS_HF["final_norm"], "norm"
    yield ("projector", "pre_norm"), _TOWER_NORMS_HF["pre_norm"], "norm"
    for leaf, stem in _PROJECTOR_HF.items():
        yield ("projector", leaf), stem, "linear"


def _tower_to_hf(tower: Params, cfg: ModelArgs) -> Dict[str, np.ndarray]:
    get = lambda t: np.asarray(jax.device_get(t))
    pe = tower["patch_embed"]
    side = cfg.tower_patch_size
    sd = {
        # a patch's numbers channel-major, as a convolution's weight
        # [out, channels, side, side] flattens
        _PATCH_HF + "proj.weight": get(pe["w"]).T.reshape(
            -1, cfg.tower_in_channels, side, side),
        _PATCH_HF + "proj.bias": get(pe["b"]),
        _PATCH_HF + "pos_emb.weight": get(pe["pos_emb"])}
    for path, stem, kind in _tower_hf_leaves(cfg):
        node = tower
        for k in path:
            node = node[k]
        if kind == "norm":
            sd[stem + ".weight"] = get(node["scale"])
            sd[stem + ".bias"] = get(node["bias"])
        else:
            sd[stem + ".weight"] = get(node["w"]).T
            sd[stem + ".bias"] = get(node["b"])
    return sd


def _tower_from_hf(sd: Dict[str, Any], cfg: ModelArgs) -> Params:
    leaves: Dict[Any, Any] = {}
    for path, stem, kind in _tower_hf_leaves(cfg):
        leaves[path] = (
            {"scale": sd[stem + ".weight"], "bias": sd[stem + ".bias"]}
            if kind == "norm" else
            {"w": sd[stem + ".weight"].T, "b": sd[stem + ".bias"]})
    return {
        "patch_embed": {
            "w": sd[_PATCH_HF + "proj.weight"].reshape(
                cfg.tower_hidden_size, -1).T,
            "b": sd[_PATCH_HF + "proj.bias"],
            "pos_emb": sd[_PATCH_HF + "pos_emb.weight"]},
        "blocks": tuple({leaf: leaves[("blocks", i, leaf)]
                         for leaf in _TOWER_BLOCK_HF}
                        for i in range(cfg.tower_layers)),
        "final_norm": leaves[("final_norm",)],
        "projector": {leaf: leaves[("projector", leaf)]
                      for leaf in ("pre_norm", *_PROJECTOR_HF)}}


# the ``phi4flash`` layout (``cfg.hf_layout``; microsoft/Phi-4-mini-flash-
# reasoning's ``modeling_phi4flash.py``): every block's operator under
# ``attn``, LayerNorms with a bias, gate | up in one matrix. A mamba1 or gmu
# block's leaves -> their names under ``attn.``; Conv1d's depthwise kernel
# is [channels, 1, taps]
_PHI4FLASH_MIXER_NAMES = {
    "mamba1": {"win": "in_proj.weight", "taps": "conv1d.weight",
               "conv_bias": "conv1d.bias", "wx": "x_proj.weight",
               "wdt": "dt_proj.weight", "dt_bias": "dt_proj.bias",
               "A_log": "A_log", "D": "D", "wout": "out_proj.weight"},
    "gmu": {"win": "in_proj.weight", "wout": "out_proj.weight"}}
_PHI4FLASH_LAMBDAS = ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")
_PHI4FLASH_NORMS = {"ln1": "input_layernorm", "ln2": "post_attention_layernorm"}


def _diff_query_columns(cfg: ModelArgs, i: int, to_hf: bool) -> np.ndarray:
    """The order of a query projection's columns on the other side: a block
    under differential attention keeps its query heads as the core reads
    them (``modules.diff_core_order``), the public layout pairs heads ``2j``
    and ``2j + 1``; the identity without it."""
    from hetu_galvatron_tpu.models.modules import diff_core_order

    nq, hd = cfg.block_heads(i), cfg.head_dim
    if not cfg.differential_attention:
        return np.arange(nq * hd)
    heads = np.asarray(diff_core_order(nq, cfg.kv_heads))
    if to_hf:
        heads = np.argsort(heads)
    return (heads[:, None] * hd + np.arange(hd)).reshape(-1)


def _phi4flash_params_to_hf(params: Params, cfg: ModelArgs
                            ) -> Dict[str, np.ndarray]:
    get = lambda t: np.asarray(jax.device_get(t))
    sd = {"model.embed_tokens.weight":
          get(params["embed"]["wte"])[:cfg.vocab_size]}

    def put_norm(name, p):
        sd[name + ".weight"] = get(p["scale"])
        sd[name + ".bias"] = get(p["bias"])

    for i, (lp, (mixer, ff)) in enumerate(zip(params["layers"],
                                              cfg.block_kinds())):
        pre = f"model.layers.{i}."
        if ff != "dense":
            raise _unknown_mixer(i, f"{mixer}/{ff}")
        for leaf, name in _PHI4FLASH_NORMS.items():
            put_norm(pre + name, lp[leaf])
        sd[pre + "mlp.gate_up_proj.weight"] = get(lp["mlp"]["win"]).T
        sd[pre + "mlp.down_proj.weight"] = get(lp["mlp"]["wout"]).T
        pre += "attn."
        if mixer in _PHI4FLASH_MIXER_NAMES:
            mp = lp[mixer]
            for leaf, name in _PHI4FLASH_MIXER_NAMES[mixer].items():
                w = get(mp[leaf])
                sd[pre + name] = (w.T if leaf.startswith("w")
                                  else w[:, None, :] if leaf == "taps" else w)
            continue
        if mixer not in ("full_attention", "sliding_attention",
                         "cross_attention"):
            raise _unknown_mixer(i, mixer)
        ap = lp["attn"]
        cols = _diff_query_columns(cfg, i, to_hf=True)
        if mixer == "cross_attention":
            w, b = get(ap["wq"])[:, cols], get(ap["bq"])[cols]
        else:
            w, b = get(ap["wqkv"]), get(ap["bqkv"])
            w = np.concatenate([w[:, :cols.size][:, cols], w[:, cols.size:]],
                               axis=1)
            b = np.concatenate([b[:cols.size][cols], b[cols.size:]])
        sd[pre + "Wqkv.weight"], sd[pre + "Wqkv.bias"] = w.T, b
        sd[pre + "out_proj.weight"] = get(ap["wo"]).T
        sd[pre + "out_proj.bias"] = get(ap["bo"])
        if cfg.differential_attention:
            for name, row in zip(_PHI4FLASH_LAMBDAS, get(ap["lambdas"])):
                sd[pre + name] = row
            sd[pre + "subln.weight"] = get(ap["subln"]["scale"])
    put_norm("model.final_layernorm", params["prenorm"])
    return sd


def _phi4flash_hf_to_params(sd: Dict[str, Any], cfg: ModelArgs) -> Params:
    def norm(name):
        return {"scale": sd[name + ".weight"], "bias": sd[name + ".bias"]}

    layers = []
    for i, (mixer, ff) in enumerate(cfg.block_kinds()):
        pre = f"model.layers.{i}."
        if ff != "dense":
            raise _unknown_mixer(i, f"{mixer}/{ff}")
        lp: Params = {leaf: norm(pre + name)
                      for leaf, name in _PHI4FLASH_NORMS.items()}
        lp["mlp"] = {"win": sd[pre + "mlp.gate_up_proj.weight"].T,
                     "wout": sd[pre + "mlp.down_proj.weight"].T}
        pre += "attn."
        if mixer in _PHI4FLASH_MIXER_NAMES:
            lp[mixer] = {
                leaf: (w.T if leaf.startswith("w")
                       else w[:, 0, :] if leaf == "taps" else w)
                for leaf, w in ((leaf, sd[pre + name]) for leaf, name in
                                _PHI4FLASH_MIXER_NAMES[mixer].items())}
        elif mixer in ("full_attention", "sliding_attention",
                       "cross_attention"):
            cols = _diff_query_columns(cfg, i, to_hf=False)
            w, b = sd[pre + "Wqkv.weight"].T, sd[pre + "Wqkv.bias"]
            ap: Params = {"wo": sd[pre + "out_proj.weight"].T,
                          "bo": sd[pre + "out_proj.bias"]}
            if mixer == "cross_attention":
                ap.update(wq=w[:, cols], bq=b[cols])
            else:
                ap.update(
                    wqkv=np.concatenate(
                        [w[:, :cols.size][:, cols], w[:, cols.size:]], axis=1),
                    bqkv=np.concatenate([b[:cols.size][cols],
                                         b[cols.size:]]))
            if cfg.differential_attention:
                ap["lambdas"] = np.stack(
                    [sd[pre + name] for name in _PHI4FLASH_LAMBDAS])
                ap["subln"] = {"scale": sd[pre + "subln.weight"]}
            lp["attn"] = ap
        else:
            raise _unknown_mixer(i, mixer)
        layers.append(lp)
    return {"embed": {"wte": _pad_vocab(sd["model.embed_tokens.weight"],
                                        cfg)},
            "layers": tuple(layers),
            "prenorm": norm("model.final_layernorm"), "head": {}}


# the ``nemotron_h`` layout (``cfg.hf_layout``; nvidia's NemotronH,
# ``model_type`` ``nemotron_h``): blocks of ONE branch under
# ``backbone.layers.{i}``, the block's norm ``norm`` and whatever the block
# is under ``mixer``: a Mamba-2 mixer (Granite's leaf names without their
# ``mamba.`` prefix), an attention (q, k, v, o), an ungated MLP (up, down)
# or the experts (``gate`` with its ``e_score_correction_bias``,
# ``experts.{e}``, ``shared_experts``)
_NEMOTRON_H_EMBED = "backbone.embeddings.weight"
_NEMOTRON_H_FINAL = "backbone.norm_f.weight"


def _refuse_one_branch(cfg: ModelArgs) -> None:
    if cfg.one_branch_blocks:
        raise NotImplementedError(
            f"the {cfg.hf_layout} HF layout names a mixer and a "
            "feed-forward in every block; a stack of one-branch blocks "
            "(model.layer_types with experts / dense entries) goes out "
            "under model.hf_layout=nemotron_h")


def _nemotron_h_check(cfg: ModelArgs) -> None:
    from hetu_galvatron_tpu.models.modules import _is_gated

    if not cfg.one_branch_blocks or _is_gated(cfg.hidden_act) \
            or cfg.hc_mult > 1:
        raise NotImplementedError(
            "the nemotron_h HF layout names blocks of one branch "
            "(model.layer_types with experts / dense entries), ungated "
            "MLPs (two matrices) and one residual stream")


def _nemotron_h_params_to_hf(params: Params, cfg: ModelArgs
                             ) -> Dict[str, np.ndarray]:
    _nemotron_h_check(cfg)
    get = lambda t: np.asarray(jax.device_get(t))
    V, hd, nkv = cfg.vocab_size, cfg.head_dim, cfg.kv_heads
    sd = {_NEMOTRON_H_EMBED: get(params["embed"]["wte"])[:V]}
    for i, (lp, (mixer, ff)) in enumerate(zip(params["layers"],
                                              cfg.block_kinds())):
        pre = f"backbone.layers.{i}."
        sd[pre + "norm.weight"] = get(lp["ln1"]["scale"])
        pre += "mixer."
        if mixer == "mamba":
            sd.update({pre + name.removeprefix("mamba."): w for name, w in
                       _mamba_to_hf(lp["mamba"], get).items()})
        elif mixer == "full_attention":
            nq = cfg.block_heads(i)
            q, k, v = np.split(get(lp["attn"]["wqkv"]),
                               [nq * hd, (nq + nkv) * hd], axis=1)
            for name, w in (("q", q), ("k", k), ("v", v),
                            ("o", get(lp["attn"]["wo"]))):
                sd[pre + f"{name}_proj.weight"] = w.T
        elif ff == "dense":
            sd[pre + "up_proj.weight"] = get(lp["mlp"]["win"]).T
            sd[pre + "down_proj.weight"] = get(lp["mlp"]["wout"]).T
        elif ff == "experts":
            mp = lp["moe"]
            sd[pre + "gate.weight"] = get(mp["router"]).T
            if "expert_bias" in mp:
                sd[pre + "gate.e_score_correction_bias"] = get(
                    mp["expert_bias"])
            win, wout = get(mp["win"]), get(mp["wout"])
            # held experts go out under their published indices
            for j in range(win.shape[0]):
                e = cfg.moe_first_held_expert + j
                sd[pre + f"experts.{e}.up_proj.weight"] = win[j].T
                sd[pre + f"experts.{e}.down_proj.weight"] = wout[j].T
            if "shared" in mp:
                sd[pre + "shared_experts.up_proj.weight"] = get(
                    mp["shared"]["win"]).T
                sd[pre + "shared_experts.down_proj.weight"] = get(
                    mp["shared"]["wout"]).T
        else:
            raise _unknown_mixer(i, f"{mixer}/{ff}")
    sd[_NEMOTRON_H_FINAL] = get(params["prenorm"]["scale"])
    if not cfg.tie_word_embeddings and params.get("head"):
        sd["lm_head.weight"] = get(params["head"]["whead"]).T[:V]
    return sd


def _nemotron_h_hf_to_params(sd: Dict[str, Any], cfg: ModelArgs) -> Params:
    _nemotron_h_check(cfg)
    layers = []
    for i, (mixer, ff) in enumerate(cfg.block_kinds()):
        pre = f"backbone.layers.{i}."
        lp: Params = {"ln1": {"scale": sd[pre + "norm.weight"]}}
        pre += "mixer."
        if mixer == "mamba":
            mp = {}
            for leaf, name in _MAMBA_HF_NAMES.items():
                name = pre + name.removeprefix("mamba.")
                if name not in sd:
                    continue
                w = sd[name]
                mp[leaf] = (w.T if leaf in ("win", "wout")
                            else w[:, 0, :] if leaf == "taps"
                            else {"scale": w} if leaf == "norm" else w)
            lp["mamba"] = mp
        elif mixer == "full_attention":
            lp["attn"] = {
                "wqkv": np.concatenate(
                    [sd[pre + f"{n}_proj.weight"].T for n in "qkv"], axis=1),
                "wo": sd[pre + "o_proj.weight"].T}
        elif ff == "dense":
            lp["mlp"] = {"win": sd[pre + "up_proj.weight"].T,
                         "wout": sd[pre + "down_proj.weight"].T}
        elif ff == "experts":
            held = range(cfg.moe_first_held_expert,
                         cfg.moe_first_held_expert + cfg.held_experts)
            mp = {"router": sd[pre + "gate.weight"].T,
                  "win": np.stack([sd[pre + f"experts.{e}.up_proj.weight"].T
                                   for e in held]),
                  "wout": np.stack(
                      [sd[pre + f"experts.{e}.down_proj.weight"].T
                       for e in held])}
            if cfg.moe_router_enable_expert_bias:
                mp["expert_bias"] = sd[pre + "gate.e_score_correction_bias"]
            if cfg.num_shared_experts:
                mp["shared"] = {
                    "win": sd[pre + "shared_experts.up_proj.weight"].T,
                    "wout": sd[pre + "shared_experts.down_proj.weight"].T}
            lp["moe"] = mp
        else:
            raise _unknown_mixer(i, f"{mixer}/{ff}")
        layers.append(lp)
    wte = _pad_vocab(sd[_NEMOTRON_H_EMBED], cfg)
    head: Params = {}
    if not cfg.tie_word_embeddings:
        head = {"whead": (_pad_vocab(sd["lm_head.weight"], cfg).T
                          if "lm_head.weight" in sd else wte.T)}
    return {"embed": {"wte": wte}, "layers": tuple(layers),
            "prenorm": {"scale": sd[_NEMOTRON_H_FINAL]}, "head": head}


def _unknown_mixer(i: int, mixer: str) -> ValueError:
    from hetu_galvatron_tpu.models.modules import MIXERS

    return ValueError(
        f"block {i}: no public names for a {mixer!r} mixer; the exporter "
        f"knows the mixer kinds {', '.join(MIXERS)} and the "
        "feed-forward kinds dense, experts")


def hf_to_params(state_dict: Dict[str, Any], cfg: ModelArgs) -> Params:
    """HF torch state dict -> our params pytree (reference h2g converters,
    tools/checkpoint_convert_h2g.py + llama_adapter.py:51-163). Supports the
    gpt2 (Conv1D fused qkv) and llama (separate q/k/v Linear) layouts."""
    import numpy as np

    def arr(t):
        return np.asarray(t.detach().numpy() if hasattr(t, "detach") else t)

    sd = {k: arr(v) for k, v in state_dict.items()}
    n = cfg.num_hidden_layers
    if cfg.tower_layers:
        # the decoder's names without their prefix; the tower's as they are
        sd = {k.removeprefix(_DECODER_PREFIX): v for k, v in sd.items()}
    if cfg.model_type == "gpt" or "transformer.wte.weight" in sd:
        layers = []
        for i in range(n):
            pre = f"transformer.h.{i}."
            lp = {
                "ln1": {"scale": sd[pre + "ln_1.weight"],
                        "bias": sd[pre + "ln_1.bias"]},
                "attn": {"wqkv": sd[pre + "attn.c_attn.weight"],
                         "bqkv": sd[pre + "attn.c_attn.bias"],
                         "wo": sd[pre + "attn.c_proj.weight"],
                         "bo": sd[pre + "attn.c_proj.bias"]},
                "ln2": {"scale": sd[pre + "ln_2.weight"],
                        "bias": sd[pre + "ln_2.bias"]},
                "mlp": {"win": sd[pre + "mlp.c_fc.weight"],
                        "bin": sd[pre + "mlp.c_fc.bias"],
                        "wout": sd[pre + "mlp.c_proj.weight"],
                        "bout": sd[pre + "mlp.c_proj.bias"]},
            }
            layers.append(lp)
        wte = sd["transformer.wte.weight"]
        pad = cfg.padded_vocab_size - wte.shape[0]
        if pad > 0:
            wte = np.concatenate([wte, np.zeros((pad, wte.shape[1]),
                                                wte.dtype)])
        # HF gpt2 always ties lm_head to wte; an untied target config needs
        # its own whead or apply_lm_head would KeyError much later (ADVICE r2)
        head: Params = {}
        if not cfg.tie_word_embeddings:
            head = {"whead": (_pad_vocab(sd["lm_head.weight"], cfg).T
                              if "lm_head.weight" in sd else wte.T)}
        return {
            "embed": {"wte": wte, "wpe": sd["transformer.wpe.weight"]},
            "layers": tuple(layers),
            "prenorm": {"scale": sd["transformer.ln_f.weight"],
                        "bias": sd["transformer.ln_f.bias"]},
            "head": head,
        }

    if cfg.hf_layout == "phi4flash":
        return _phi4flash_hf_to_params(sd, cfg)
    if cfg.hf_layout == "nemotron_h":
        return _nemotron_h_hf_to_params(sd, cfg)
    _refuse_one_branch(cfg)
    if cfg.model_type == "bert" or "bert.embeddings.word_embeddings.weight" in sd:
        return _bert_hf_to_params(sd, cfg)
    if cfg.model_type == "t5" or "encoder.final_layer_norm.weight" in sd:
        return _t5_hf_to_params(sd, cfg)

    # llama-family: torch Linear stores [out, in] -> transpose
    def lin(name):
        return sd[name].T

    router, gate, up, down = _MOE_HF_NAMES[cfg.moe_hf_layout]
    names = _BLOCK_HF_NAMES[cfg.hf_layout]

    def read_block(i, mixer, ff):
        pre = f"model.layers.{i}."
        norms = {**names,
                 **_BLOCK_NORM_HF_NAMES.get((cfg.hf_layout, mixer), {})}
        lp = {"ln1": {"scale": sd[pre + norms["ln1"]]},
              "ln2": {"scale": sd[pre + norms["ln2"]]}}
        if cfg.hc_mult > 1:
            for leaf, part in _HC_HF_NAMES.items():
                lp[leaf] = {"phi": lin(pre + part + "phi.weight"),
                            "alpha": sd[pre + part + "alpha"],
                            "bias": sd[pre + part + "bias"]}
        if mixer == "conv":
            w_in, w_taps, w_out = (sd[pre + nm] for nm in _CONV_HF_NAMES)
            # in_proj's rows are the thirds B | C | X; Conv1d's depthwise
            # kernel is [channels, 1, taps]
            lp["conv"] = {"win": np.stack([t.T for t in np.split(w_in, 3)]),
                          "taps": w_taps[:, 0, :], "wout": w_out.T}
        elif mixer == "mamba":
            nm = {k: pre + v for k, v in _MAMBA_HF_NAMES.items()}
            lp["mamba"] = {
                "win": lin(nm["win"]), "taps": sd[nm["taps"]][:, 0, :],
                "dt_bias": sd[nm["dt_bias"]], "A_log": sd[nm["A_log"]],
                "D": sd[nm["D"]], "norm": {"scale": sd[nm["norm"]]},
                "wout": lin(nm["wout"])}
            if cfg.mamba_conv_bias:
                lp["mamba"]["conv_bias"] = sd[nm["conv_bias"]]
        elif mixer in ("full_attention", "sliding_attention"):
            lp["attn"] = {
                "wqkv": np.concatenate(
                    [lin(pre + "self_attn.q_proj.weight"),
                     lin(pre + "self_attn.k_proj.weight"),
                     lin(pre + "self_attn.v_proj.weight")], axis=1),
                "wo": lin(pre + names["o"])}
            if cfg.gating:
                lp["attn"]["wg"] = lin(pre + _ATTN_GATE_HF_NAME)
            if cfg.qk_norm:
                lp["attn"]["q_norm"] = {"scale": sd[pre + names["q_norm"]]}
                lp["attn"]["k_norm"] = {"scale": sd[pre + names["k_norm"]]}
        elif mixer == "latent_attention":
            lp["attn"] = {
                leaf: ({"scale": sd[pre + name]} if leaf.endswith("norm")
                       else lin(pre + name))
                for leaf, name in _latent_hf_names(cfg).items()}
            for leaf, order in _latent_rope_orders(cfg, to_hf=False).items():
                lp["attn"][leaf] = lp["attn"][leaf][:, order]
        elif mixer == "kda":
            # Conv1d's depthwise kernel is [channels, 1, taps]; A_log is
            # stored [1, 1, heads, 1]
            lp["kda"] = {
                "wqkv": np.concatenate(
                    [lin(pre + nm) for nm in _KDA_HF_THIRDS["wqkv"]], axis=1),
                "taps": np.concatenate(
                    [sd[pre + nm][:, 0, :] for nm in _KDA_HF_THIRDS["taps"]]),
                "wlow": np.concatenate(
                    [lin(pre + nm) for nm in _KDA_HF_THIRDS["wlow"]], axis=1),
                "wf_b": lin(pre + _KDA_HF_NAMES["wf_b"]),
                "wg_b": lin(pre + _KDA_HF_NAMES["wg_b"]),
                "dt_bias": sd[pre + _KDA_HF_NAMES["dt_bias"]],
                "A_log": sd[pre + _KDA_HF_NAMES["A_log"]].reshape(-1),
                "norm": {"scale": sd[pre + _KDA_HF_NAMES["norm"]]},
                "wout": lin(pre + _KDA_HF_NAMES["wout"])}
        elif mixer == "linear_attention":
            lp["gdn"] = {
                leaf: np.concatenate(
                    [sd[pre + nm][:, 0, :] if leaf == "taps"
                     else lin(pre + nm) for nm in parts],
                    axis=0 if leaf == "taps" else 1)
                for leaf, parts in _GDN_HF_PARTS.items()}
            for leaf, name in _GDN_HF_NAMES.items():
                w = sd[pre + name]
                lp["gdn"][leaf] = ({"scale": w} if leaf == "norm"
                                   else w.T if leaf.startswith("w") else w)
        else:
            raise _unknown_mixer(i, mixer)
        if ff == "experts":
            # MoE FFN (reference moe_adapter.py:58-266): each expert's gate
            # and up fuse into win [E, H, 2F], down -> wout [E, F, H]; the
            # names are the layout's that cfg.moe_hf_layout says. A layer
            # that holds a share finds its experts under their published
            # indices
            shared = _MOE_HF_SHARED.get(cfg.moe_hf_layout)
            if cfg.num_shared_experts and not shared:
                raise NotImplementedError(
                    f"the {cfg.moe_hf_layout} HF layout "
                    f"({pre}{router}) has no shared-expert slot; "
                    "import with num_shared_experts=0")
            first, E = cfg.moe_first_held_expert, 0
            while pre + gate.format(e=first + E) in sd:
                E += 1
            if E != cfg.held_experts:
                raise ValueError(
                    f"layer {i}: checkpoint has {E} experts"
                    + (f" from expert {first} on" if first else "")
                    + f" but cfg.num_experts is {cfg.num_experts}"
                    + (f" and cfg.moe_held_experts {cfg.moe_held_experts}"
                       if cfg.moe_held_experts else ""))
            held = range(first, first + E)
            lp["moe"] = {
                "router": lin(pre + router),
                "win": np.stack([
                    np.concatenate([lin(pre + gate.format(e=e)),
                                    lin(pre + up.format(e=e))], axis=1)
                    for e in held]),
                "wout": np.stack([lin(pre + down.format(e=e))
                                  for e in held]),
            }
            bias = _MOE_HF_BIAS.get(cfg.moe_hf_layout)
            if cfg.moe_router_enable_expert_bias and bias:
                lp["moe"]["expert_bias"] = sd[pre + bias]
            if cfg.num_shared_experts:
                s_gate, s_up, s_down = (pre + nm for nm in shared)
                lp["moe"]["shared"] = {
                    "win": np.concatenate([lin(s_gate), lin(s_up)], axis=1),
                    "wout": lin(s_down)}
        else:
            win = (lin(pre + names["gate_up"]) if "gate_up" in names
                   else np.concatenate([lin(pre + names["gate"]),
                                        lin(pre + names["up"])], axis=1))
            lp["mlp"] = {"win": win, "wout": lin(pre + names["down"])}
        if cfg.add_qkv_bias and "attn" in lp:
            lp["attn"]["bqkv"] = np.concatenate(
                [sd[pre + "self_attn.q_proj.bias"],
                 sd[pre + "self_attn.k_proj.bias"],
                 sd[pre + "self_attn.v_proj.bias"]])
        return lp

    layers = [read_block(i, mixer, ff)
              for i, (mixer, ff) in enumerate(cfg.block_kinds())]
    wte = sd["model.embed_tokens.weight"]
    pad = cfg.padded_vocab_size - wte.shape[0]
    if pad > 0:
        wte = np.concatenate([wte, np.zeros((pad, wte.shape[1]), wte.dtype)])
    out: Params = {
        "embed": {"wte": wte},
        "layers": tuple(layers),
        "prenorm": {"scale": sd[names["final"]]},
    }
    if cfg.num_nextn_predict_layers:
        pre = f"model.layers.{n}."
        out["mtp"] = {
            leaf: (lin(pre + name) if leaf == "eh_proj"
                   else {"scale": sd[pre + name]})
            for leaf, name in _MTP_HF_NAMES.items()}
        out["mtp"]["layer"] = read_block(n, *cfg.block_kinds()[-1])
    if cfg.tie_word_embeddings:
        out["head"] = {}
    else:
        whead = lin("lm_head.weight")
        if pad > 0:
            whead = np.concatenate(
                [whead, np.zeros((whead.shape[0], pad), whead.dtype)], axis=1)
        out["head"] = {"whead": whead}
    if cfg.tower_layers:
        out["tower"] = _tower_from_hf(sd, cfg)
    return out


def _pad_vocab(w: "np.ndarray", cfg: ModelArgs) -> "np.ndarray":
    import numpy as np

    pad = cfg.padded_vocab_size - w.shape[0]
    if pad > 0:
        w = np.concatenate(
            [w, np.zeros((pad,) + w.shape[1:], w.dtype)])
    return w


def _bert_hf_to_params(sd: Dict[str, Any], cfg: ModelArgs) -> Params:
    """HF BertForMaskedLM -> our post-norm encoder layout (reference
    tools/checkpoint_convert_h2g.py bert path). Token-type embeddings are
    folded into wpe for single-segment (type-0) training — the parallelism
    framework trains MLM on single segments (runtime/dataloader.py
    mlm_batches)."""
    import numpy as np

    def lin(name):
        return sd[name].T

    n = cfg.num_hidden_layers
    layers = []
    for i in range(n):
        pre = f"bert.encoder.layer.{i}."
        wqkv = np.concatenate(
            [lin(pre + "attention.self.query.weight"),
             lin(pre + "attention.self.key.weight"),
             lin(pre + "attention.self.value.weight")], axis=1)
        bqkv = np.concatenate(
            [sd[pre + "attention.self.query.bias"],
             sd[pre + "attention.self.key.bias"],
             sd[pre + "attention.self.value.bias"]])
        layers.append({
            "attn": {"wqkv": wqkv, "bqkv": bqkv,
                     "wo": lin(pre + "attention.output.dense.weight"),
                     "bo": sd[pre + "attention.output.dense.bias"]},
            "ln1": {"scale": sd[pre + "attention.output.LayerNorm.weight"],
                    "bias": sd[pre + "attention.output.LayerNorm.bias"]},
            "mlp": {"win": lin(pre + "intermediate.dense.weight"),
                    "bin": sd[pre + "intermediate.dense.bias"],
                    "wout": lin(pre + "output.dense.weight"),
                    "bout": sd[pre + "output.dense.bias"]},
            "ln2": {"scale": sd[pre + "output.LayerNorm.weight"],
                    "bias": sd[pre + "output.LayerNorm.bias"]},
        })
    wte = _pad_vocab(sd["bert.embeddings.word_embeddings.weight"], cfg)
    wpe = (sd["bert.embeddings.position_embeddings.weight"]
           + sd["bert.embeddings.token_type_embeddings.weight"][0][None, :])
    head: Params = {
        "wt": lin("cls.predictions.transform.dense.weight"),
        "bt": sd["cls.predictions.transform.dense.bias"],
        "ln": {"scale": sd["cls.predictions.transform.LayerNorm.weight"],
               "bias": sd["cls.predictions.transform.LayerNorm.bias"]},
        "bias": _pad_vocab(sd["cls.predictions.bias"], cfg),
    }
    if not cfg.tie_word_embeddings:
        head["whead"] = _pad_vocab(
            sd.get("cls.predictions.decoder.weight",
                   sd["bert.embeddings.word_embeddings.weight"]), cfg).T
    return {
        "embed": {"wte": wte, "wpe": wpe,
                  "ln": {"scale": sd["bert.embeddings.LayerNorm.weight"],
                         "bias": sd["bert.embeddings.LayerNorm.bias"]}},
        "layers": tuple(layers),
        "prenorm": {},
        "head": head,
    }


def _t5_hf_to_params(sd: Dict[str, Any], cfg: ModelArgs) -> Params:
    """HF T5ForConditionalGeneration -> our encoder-decoder layout.

    All projection/norm/MLP weights map 1:1 (q/k/v fused per stack; the
    decoder's EncDecAttention becomes the fused-KV cross block). HF T5's
    relative_attention_bias has no slot here by design — this runtime is
    position-scheme agnostic (models/encdec.py docstring) and runs the
    configured scheme (RoPE/learned), so imported T5 weights fine-tune
    rather than bit-match HF generation."""
    import numpy as np

    def lin(name):
        return sd[name].T

    inner = sd["encoder.block.0.layer.0.SelfAttention.q.weight"].shape[0]
    if inner != cfg.num_attention_heads * cfg.head_dim:
        raise ValueError(
            f"t5 checkpoint attention inner dim {inner} != heads*head_dim "
            f"{cfg.num_attention_heads * cfg.head_dim}: this runtime derives "
            "head_dim = hidden//heads (t5-small/base/large match; t5-3b/11b "
            "use d_kv=128 and need a config with matching geometry)")

    gated = "encoder.block.0.layer.1.DenseReluDense.wi_0.weight" in sd

    def mlp(pre):
        if gated:  # t5 v1.1 gated-act: wi_0 (gate) | wi_1 (up)
            win = np.concatenate([lin(pre + "DenseReluDense.wi_0.weight"),
                                  lin(pre + "DenseReluDense.wi_1.weight")],
                                 axis=1)
        else:
            win = lin(pre + "DenseReluDense.wi.weight")
        return {"win": win, "wout": lin(pre + "DenseReluDense.wo.weight")}

    n_enc = (cfg.num_encoder_layers if cfg.num_encoder_layers is not None
             else cfg.num_hidden_layers)
    enc_layers = []
    for i in range(n_enc):
        pre = f"encoder.block.{i}."
        wqkv = np.concatenate(
            [lin(pre + "layer.0.SelfAttention.q.weight"),
             lin(pre + "layer.0.SelfAttention.k.weight"),
             lin(pre + "layer.0.SelfAttention.v.weight")], axis=1)
        enc_layers.append({
            "ln1": {"scale": sd[pre + "layer.0.layer_norm.weight"]},
            "attn": {"wqkv": wqkv,
                     "wo": lin(pre + "layer.0.SelfAttention.o.weight")},
            "ln2": {"scale": sd[pre + "layer.1.layer_norm.weight"]},
            "mlp": mlp(pre + "layer.1."),
        })
    dec_layers = []
    for i in range(cfg.num_hidden_layers):
        pre = f"decoder.block.{i}."
        wqkv = np.concatenate(
            [lin(pre + "layer.0.SelfAttention.q.weight"),
             lin(pre + "layer.0.SelfAttention.k.weight"),
             lin(pre + "layer.0.SelfAttention.v.weight")], axis=1)
        wkv = np.concatenate(
            [lin(pre + "layer.1.EncDecAttention.k.weight"),
             lin(pre + "layer.1.EncDecAttention.v.weight")], axis=1)
        dec_layers.append({
            "ln1": {"scale": sd[pre + "layer.0.layer_norm.weight"]},
            "attn": {"wqkv": wqkv,
                     "wo": lin(pre + "layer.0.SelfAttention.o.weight")},
            "lnx": {"scale": sd[pre + "layer.1.layer_norm.weight"]},
            "cross": {"wq": lin(pre + "layer.1.EncDecAttention.q.weight"),
                      "wkv": wkv,
                      "wo": lin(pre + "layer.1.EncDecAttention.o.weight")},
            "ln2": {"scale": sd[pre + "layer.2.layer_norm.weight"]},
            "mlp": mlp(pre + "layer.2."),
        })
    out: Params = {
        "embed": {"wte": _pad_vocab(sd["shared.weight"], cfg)},
        "enc_layers": tuple(enc_layers),
        "enc_norm": {"scale": sd["encoder.final_layer_norm.weight"]},
        "layers": tuple(dec_layers),
        "prenorm": {"scale": sd["decoder.final_layer_norm.weight"]},
    }
    if cfg.tie_word_embeddings:
        out["head"] = {}
    else:
        out["head"] = {"whead": _pad_vocab(sd["lm_head.weight"], cfg).T}
    return out


def _bert_params_to_hf(params: Params, cfg: ModelArgs) -> Dict[str, "np.ndarray"]:
    """Inverse of :func:`_bert_hf_to_params`. Token-type embeddings were
    folded into wpe on import, so type 0 exports as zeros (wpe carries the
    sum) — re-importing reproduces the same forward exactly."""
    import numpy as np

    get = lambda t: np.asarray(jax.device_get(t))
    V, H = cfg.vocab_size, cfg.hidden_size
    hd, nq, nkv = cfg.head_dim, cfg.num_attention_heads, cfg.kv_heads
    sd: Dict[str, np.ndarray] = {
        "bert.embeddings.word_embeddings.weight": get(params["embed"]["wte"])[:V],
        "bert.embeddings.position_embeddings.weight": get(params["embed"]["wpe"]),
        "bert.embeddings.token_type_embeddings.weight": np.zeros((2, H),
                                                                 np.float32),
        "bert.embeddings.LayerNorm.weight": get(params["embed"]["ln"]["scale"]),
        "bert.embeddings.LayerNorm.bias": get(params["embed"]["ln"]["bias"]),
    }
    for i, lp in enumerate(params["layers"]):
        pre = f"bert.encoder.layer.{i}."
        wqkv = get(lp["attn"]["wqkv"])
        q, k, v = np.split(wqkv, [nq * hd, (nq + nkv) * hd], axis=1)
        bq, bk, bv = np.split(get(lp["attn"]["bqkv"]),
                              [nq * hd, (nq + nkv) * hd])
        sd[pre + "attention.self.query.weight"] = q.T
        sd[pre + "attention.self.query.bias"] = bq
        sd[pre + "attention.self.key.weight"] = k.T
        sd[pre + "attention.self.key.bias"] = bk
        sd[pre + "attention.self.value.weight"] = v.T
        sd[pre + "attention.self.value.bias"] = bv
        sd[pre + "attention.output.dense.weight"] = get(lp["attn"]["wo"]).T
        sd[pre + "attention.output.dense.bias"] = get(lp["attn"]["bo"])
        sd[pre + "attention.output.LayerNorm.weight"] = get(lp["ln1"]["scale"])
        sd[pre + "attention.output.LayerNorm.bias"] = get(lp["ln1"]["bias"])
        sd[pre + "intermediate.dense.weight"] = get(lp["mlp"]["win"]).T
        sd[pre + "intermediate.dense.bias"] = get(lp["mlp"]["bin"])
        sd[pre + "output.dense.weight"] = get(lp["mlp"]["wout"]).T
        sd[pre + "output.dense.bias"] = get(lp["mlp"]["bout"])
        sd[pre + "output.LayerNorm.weight"] = get(lp["ln2"]["scale"])
        sd[pre + "output.LayerNorm.bias"] = get(lp["ln2"]["bias"])
    hp = params["head"]
    sd["cls.predictions.transform.dense.weight"] = get(hp["wt"]).T
    sd["cls.predictions.transform.dense.bias"] = get(hp["bt"])
    sd["cls.predictions.transform.LayerNorm.weight"] = get(hp["ln"]["scale"])
    sd["cls.predictions.transform.LayerNorm.bias"] = get(hp["ln"]["bias"])
    sd["cls.predictions.bias"] = get(hp["bias"])[:V]
    if not cfg.tie_word_embeddings and "whead" in hp:
        sd["cls.predictions.decoder.weight"] = get(hp["whead"]).T[:V]
    return sd


def _t5_params_to_hf(params: Params, cfg: ModelArgs) -> Dict[str, "np.ndarray"]:
    """Inverse of :func:`_t5_hf_to_params` (gated t5-v1.1 MLP layout when the
    model uses a gated activation)."""
    import numpy as np

    get = lambda t: np.asarray(jax.device_get(t))
    from hetu_galvatron_tpu.models.modules import _is_gated

    V = cfg.vocab_size
    hd, nq, nkv = cfg.head_dim, cfg.num_attention_heads, cfg.kv_heads
    sd: Dict[str, np.ndarray] = {
        "shared.weight": get(params["embed"]["wte"])[:V],
        "encoder.final_layer_norm.weight": get(params["enc_norm"]["scale"]),
        "decoder.final_layer_norm.weight": get(params["prenorm"]["scale"]),
    }

    def put_mlp(pre, mp):
        win = get(mp["win"])
        if _is_gated(cfg.hidden_act):
            gate, up = np.split(win, 2, axis=1)
            sd[pre + "DenseReluDense.wi_0.weight"] = gate.T
            sd[pre + "DenseReluDense.wi_1.weight"] = up.T
        else:
            sd[pre + "DenseReluDense.wi.weight"] = win.T
        sd[pre + "DenseReluDense.wo.weight"] = get(mp["wout"]).T

    def put_self_attn(pre, ap):
        wqkv = get(ap["wqkv"])
        q, k, v = np.split(wqkv, [nq * hd, (nq + nkv) * hd], axis=1)
        sd[pre + "SelfAttention.q.weight"] = q.T
        sd[pre + "SelfAttention.k.weight"] = k.T
        sd[pre + "SelfAttention.v.weight"] = v.T
        sd[pre + "SelfAttention.o.weight"] = get(ap["wo"]).T

    for i, lp in enumerate(params["enc_layers"]):
        pre = f"encoder.block.{i}."
        put_self_attn(pre + "layer.0.", lp["attn"])
        sd[pre + "layer.0.layer_norm.weight"] = get(lp["ln1"]["scale"])
        sd[pre + "layer.1.layer_norm.weight"] = get(lp["ln2"]["scale"])
        put_mlp(pre + "layer.1.", lp["mlp"])
    for i, lp in enumerate(params["layers"]):
        pre = f"decoder.block.{i}."
        put_self_attn(pre + "layer.0.", lp["attn"])
        sd[pre + "layer.0.layer_norm.weight"] = get(lp["ln1"]["scale"])
        sd[pre + "layer.1.layer_norm.weight"] = get(lp["lnx"]["scale"])
        sd[pre + "layer.1.EncDecAttention.q.weight"] = get(lp["cross"]["wq"]).T
        wkv = get(lp["cross"]["wkv"])
        k, v = np.split(wkv, 2, axis=1)
        sd[pre + "layer.1.EncDecAttention.k.weight"] = k.T
        sd[pre + "layer.1.EncDecAttention.v.weight"] = v.T
        sd[pre + "layer.1.EncDecAttention.o.weight"] = get(lp["cross"]["wo"]).T
        sd[pre + "layer.2.layer_norm.weight"] = get(lp["ln2"]["scale"])
        put_mlp(pre + "layer.2.", lp["mlp"])
    if not cfg.tie_word_embeddings and params.get("head"):
        sd["lm_head.weight"] = get(params["head"]["whead"]).T[:V]
    return sd


def params_to_hf(params: Params, cfg: ModelArgs) -> Dict[str, np.ndarray]:
    """Our params -> HF-layout numpy state dict (reference g2h converters).
    Inverse of :func:`hf_to_params`; vocab padding rows are dropped."""
    get = lambda t: np.asarray(jax.device_get(t))
    sd: Dict[str, np.ndarray] = {}
    V = cfg.vocab_size
    if cfg.model_type == "bert":
        return _bert_params_to_hf(params, cfg)
    if cfg.model_type == "t5":
        return _t5_params_to_hf(params, cfg)
    if cfg.hf_layout == "phi4flash":
        return _phi4flash_params_to_hf(params, cfg)
    if cfg.hf_layout == "nemotron_h":
        return _nemotron_h_params_to_hf(params, cfg)
    _refuse_one_branch(cfg)
    if cfg.model_type == "gpt":
        sd["transformer.wte.weight"] = get(params["embed"]["wte"])[:V]
        sd["transformer.wpe.weight"] = get(params["embed"]["wpe"])
        for i, lp in enumerate(params["layers"]):
            pre = f"transformer.h.{i}."
            sd[pre + "ln_1.weight"] = get(lp["ln1"]["scale"])
            sd[pre + "ln_1.bias"] = get(lp["ln1"]["bias"])
            sd[pre + "attn.c_attn.weight"] = get(lp["attn"]["wqkv"])
            sd[pre + "attn.c_attn.bias"] = get(lp["attn"]["bqkv"])
            sd[pre + "attn.c_proj.weight"] = get(lp["attn"]["wo"])
            sd[pre + "attn.c_proj.bias"] = get(lp["attn"]["bo"])
            sd[pre + "ln_2.weight"] = get(lp["ln2"]["scale"])
            sd[pre + "ln_2.bias"] = get(lp["ln2"]["bias"])
            sd[pre + "mlp.c_fc.weight"] = get(lp["mlp"]["win"])
            sd[pre + "mlp.c_fc.bias"] = get(lp["mlp"]["bin"])
            sd[pre + "mlp.c_proj.weight"] = get(lp["mlp"]["wout"])
            sd[pre + "mlp.c_proj.bias"] = get(lp["mlp"]["bout"])
        sd["transformer.ln_f.weight"] = get(params["prenorm"]["scale"])
        sd["transformer.ln_f.bias"] = get(params["prenorm"]["bias"])
        return sd

    sd["model.embed_tokens.weight"] = get(params["embed"]["wte"])[:V]
    hd, nkv = cfg.head_dim, cfg.kv_heads
    router, e_gate, e_up, e_down = _MOE_HF_NAMES[cfg.moe_hf_layout]
    names = _BLOCK_HF_NAMES[cfg.hf_layout]
    kinds = cfg.block_kinds()
    if len(kinds) != len(params["layers"]):
        raise ValueError(
            f"the parameters hold {len(params['layers'])} blocks and the "
            f"configuration describes {len(kinds)}")

    def put_block(i, lp, mixer, ff):
        pre = f"model.layers.{i}."
        # the block's own query heads (the further prediction depth's block
        # is the last block's kind)
        nq = cfg.block_heads(min(i, len(kinds) - 1))
        for leaf, part in _HC_HF_NAMES.items():
            if leaf in lp:
                sd[pre + part + "phi.weight"] = get(lp[leaf]["phi"]).T
                sd[pre + part + "alpha"] = get(lp[leaf]["alpha"])
                sd[pre + part + "bias"] = get(lp[leaf]["bias"])
        if mixer == "conv":
            n_in, n_taps, n_out = (pre + nm for nm in _CONV_HF_NAMES)
            sd[n_in] = np.concatenate(
                [t.T for t in get(lp["conv"]["win"])])
            sd[n_taps] = get(lp["conv"]["taps"])[:, None, :]
            sd[n_out] = get(lp["conv"]["wout"]).T
        elif mixer == "mamba":
            sd.update({pre + name: w for name, w in
                       _mamba_to_hf(lp["mamba"], get).items()})
        elif mixer in ("full_attention", "sliding_attention"):
            wqkv = get(lp["attn"]["wqkv"])
            q, k, v = np.split(wqkv, [nq * hd, (nq + nkv) * hd], axis=1)
            if "wg" in lp["attn"]:
                sd[pre + _ATTN_GATE_HF_NAME] = get(lp["attn"]["wg"]).T
            sd[pre + "self_attn.q_proj.weight"] = q.T
            sd[pre + "self_attn.k_proj.weight"] = k.T
            sd[pre + "self_attn.v_proj.weight"] = v.T
            sd[pre + names["o"]] = get(lp["attn"]["wo"]).T
            if "bqkv" in lp["attn"]:
                bqkv = get(lp["attn"]["bqkv"])
                bq, bk, bv = np.split(bqkv, [nq * hd, (nq + nkv) * hd])
                sd[pre + "self_attn.q_proj.bias"] = bq
                sd[pre + "self_attn.k_proj.bias"] = bk
                sd[pre + "self_attn.v_proj.bias"] = bv
            if "q_norm" in lp["attn"]:
                sd[pre + names["q_norm"]] = get(lp["attn"]["q_norm"]["scale"])
                sd[pre + names["k_norm"]] = get(lp["attn"]["k_norm"]["scale"])
        elif mixer == "latent_attention":
            orders = _latent_rope_orders(cfg, to_hf=True)
            for leaf, name in _latent_hf_names(cfg).items():
                if leaf.endswith("norm"):
                    sd[pre + name] = get(lp["attn"][leaf]["scale"])
                    continue
                w = get(lp["attn"][leaf])
                sd[pre + name] = (w[:, orders[leaf]] if leaf in orders
                                  else w).T
        elif mixer == "kda":
            kp = lp["kda"]
            d, inner = cfg.kda_head_dim, cfg.kda_inner
            for nm, w in zip(_KDA_HF_THIRDS["wqkv"],
                             np.split(get(kp["wqkv"]), 3, axis=1)):
                sd[pre + nm] = w.T
            for nm, w in zip(_KDA_HF_THIRDS["taps"],
                             np.split(get(kp["taps"]), 3)):
                sd[pre + nm] = w[:, None, :]
            for nm, w in zip(_KDA_HF_THIRDS["wlow"],
                             np.split(get(kp["wlow"]), [d, 2 * d], axis=1)):
                sd[pre + nm] = w.T
            for leaf, name in _KDA_HF_NAMES.items():
                w = get(kp[leaf]["scale"] if leaf == "norm" else kp[leaf])
                sd[pre + name] = (w.T if leaf.startswith("w")
                                  else w.reshape(1, 1, -1, 1)
                                  if leaf == "A_log" else w)
        elif mixer == "linear_attention":
            gp = lp["gdn"]
            qkv = [cfg.linear_key_dim, 2 * cfg.linear_key_dim]
            for leaf, cuts in (("wqkv", qkv), ("taps", qkv), ("wab", 2)):
                w = get(gp[leaf])
                for nm, part in zip(_GDN_HF_PARTS[leaf], np.split(
                        w, cuts, axis=0 if leaf == "taps" else 1)):
                    # Conv1d's depthwise kernel is [channels, 1, taps]
                    sd[pre + nm] = (part[:, None, :] if leaf == "taps"
                                    else part.T)
            for leaf, name in _GDN_HF_NAMES.items():
                w = get(gp[leaf]["scale"] if leaf == "norm" else gp[leaf])
                sd[pre + name] = w.T if leaf.startswith("w") else w
        else:
            raise _unknown_mixer(i, mixer)
        if ff == "experts":
            shared = _MOE_HF_SHARED.get(cfg.moe_hf_layout)
            if "shared" in lp["moe"] and not shared:
                raise NotImplementedError(
                    f"the {cfg.moe_hf_layout} HF layout "
                    f"({pre}{router}) has no shared-expert slot; "
                    "export models with num_shared_experts=0")
            if "shared" in lp["moe"]:
                s_gate, s_up = np.split(get(lp["moe"]["shared"]["win"]), 2,
                                        axis=1)
                sd[pre + shared[0]] = s_gate.T
                sd[pre + shared[1]] = s_up.T
                sd[pre + shared[2]] = get(lp["moe"]["shared"]["wout"]).T
            sd[pre + router] = get(lp["moe"]["router"]).T
            win = get(lp["moe"]["win"])
            wout = get(lp["moe"]["wout"])
            # held experts go out under their published indices
            for j in range(win.shape[0]):
                e = cfg.moe_first_held_expert + j
                w_gate, w_up = np.split(win[j], 2, axis=1)
                sd[pre + e_gate.format(e=e)] = w_gate.T
                sd[pre + e_up.format(e=e)] = w_up.T
                sd[pre + e_down.format(e=e)] = wout[j].T
            bias = _MOE_HF_BIAS.get(cfg.moe_hf_layout)
            if "expert_bias" in lp["moe"] and bias:
                sd[pre + bias] = get(lp["moe"]["expert_bias"])
        else:
            win = get(lp["mlp"]["win"])
            if "gate_up" in names:
                sd[pre + names["gate_up"]] = win.T
            else:
                gate, up = np.split(win, 2, axis=1)
                sd[pre + names["gate"]] = gate.T
                sd[pre + names["up"]] = up.T
            sd[pre + names["down"]] = get(lp["mlp"]["wout"]).T
        norms = {**names,
                 **_BLOCK_NORM_HF_NAMES.get((cfg.hf_layout, mixer), {})}
        sd[pre + norms["ln1"]] = get(lp["ln1"]["scale"])
        sd[pre + norms["ln2"]] = get(lp["ln2"]["scale"])

    for i, (lp, (mixer, ff)) in enumerate(zip(params["layers"], kinds)):
        put_block(i, lp, mixer, ff)
    if "mtp" in params:
        pre = f"model.layers.{len(kinds)}."
        for leaf, name in _MTP_HF_NAMES.items():
            sd[pre + name] = (get(params["mtp"][leaf]).T if leaf == "eh_proj"
                              else get(params["mtp"][leaf]["scale"]))
        put_block(len(kinds), params["mtp"]["layer"], *kinds[-1])
    sd[names["final"]] = get(params["prenorm"]["scale"])
    if not cfg.tie_word_embeddings and params.get("head"):
        sd["lm_head.weight"] = get(params["head"]["whead"]).T[:V]
    if "tower" in params:
        sd = {_DECODER_PREFIX + k: v for k, v in sd.items()}
        sd.update(_tower_to_hf(params["tower"], cfg))
    return sd
