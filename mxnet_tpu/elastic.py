"""Preemption-aware elastic training: failure detection, sharded async
checkpoints, deterministic resume, and a supervised restart loop.

TPU-native re-design of the reference's fault story (SURVEY §5.3), which
lives in ps-lite: scheduler heartbeats, ``KVStoreDist::GetDeadNodes(timeout)``
(kvstore_dist.h:121) and the ``is_recovery`` re-rendezvous flag
(kvstore_dist.h:52,138). A TPU job has no parameter server to survive a
worker — SPMD collectives fail as a unit — and on preemptible slices the
dominant failure is the *scheduler taking the machine back*, so the
equivalent capability is:

- **liveness**: every worker heartbeats through the jax coordination
  service's key-value store; :func:`get_dead_nodes` reports ranks whose
  heartbeat went stale (the ``GetDeadNodes`` API, same timeout contract);
- **durability**: atomic checkpoints (:class:`CheckpointManager`:
  fsync + rename commit, per-file content hashes, manifest committed
  LAST, bounded retention that can never retire the newest committed
  epoch). A ZeRO-partitioned updater (``fastpath.zero``) saves each dp
  shard *directly* — no materialize/all-gather, no HBM spike — into
  per-shard files under a topology manifest, and restore re-buckets onto
  ANY dp size; a corrupted or missing shard falls back to the previous
  committed epoch instead of raising. ``async_save`` snapshots state to
  host bytes at the step boundary and writes/fsyncs on the host engine,
  overlapping subsequent steps, with :meth:`CheckpointManager.wait`
  barriers so a new save or a preemption flush never races a pending one;
- **determinism**: checkpoints carry the data-iterator cursor
  (``state_dict``/``set_state`` on the io iterators), the RNG streams
  (``mx.random.get_state``) and the optimizer's step counters, so a
  killed-and-resumed run is bit-identical to an uninterrupted one
  (asserted in tests/test_elastic_resume.py);
- **preemption**: a SIGTERM / ``MXNET_PREEMPTION_FILE`` watcher turns the
  eviction notice into a best-effort checkpoint-now (:func:`step_boundary`)
  and a clean :class:`Preempted` exit;
- **supervision**: :func:`run_elastic` restarts the training function
  from the last COMMITTED epoch after a crash, backs off exponentially,
  treats *no step progress within* ``MXNET_ELASTIC_STALL_SECS`` as a hang
  (restart, not an eternal wedge), resets the restart budget whenever an
  attempt commits new progress (a long run with occasional preemptions is
  not killed by ``max_restarts`` accumulated over its lifetime), and
  publishes per-restart telemetry plus the
  ``mxnet_elastic_goodput_ratio`` gauge.

The whole save→kill→resume cycle is chaos-tested through the PR-4
harness: ``action=kill`` at the ``elastic.step`` site is kill-at-step,
``action=torn-write``/``drop-shard`` at ``ckpt.shard`` corrupt or lose a
committed shard — recovery must never crash (docs/elastic.md runbook).
"""
from __future__ import annotations

import hashlib
import json
import logging
import os
import pickle
import tempfile
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from . import resilience, telemetry
from .base import MXNetError, fetch_host, get_env
from .resilience import chaos
from .telemetry import flightrec as _flightrec

__all__ = ["CheckpointManager", "run_elastic", "start_heartbeat",
           "stop_heartbeat", "get_dead_nodes",
           "Preempted", "StallError", "step_boundary", "note_progress",
           "request_preemption", "clear_preemption", "preempt_requested",
           "start_preemption_watcher"]

_LOG = logging.getLogger("mxnet_tpu.elastic")

# ---------------------------------------------------------------------------
# heartbeats over the jax coordination service
# ---------------------------------------------------------------------------

_HB_PREFIX = "mxtpu_heartbeat/"
_hb_thread: Optional[threading.Thread] = None
_hb_stop = threading.Event()


def _coord_client():
    try:
        from jax._src import distributed

        return distributed.global_state.client
    except Exception:  # pragma: no cover - jax internals moved
        return None


def start_heartbeat(interval: float = 2.0) -> bool:
    """Begin publishing this process's liveness (reference: ps-lite node
    heartbeats to the scheduler). Returns False when no distributed runtime
    is active (single-process: nothing to detect)."""
    global _hb_thread
    client = _coord_client()
    if client is None:
        return False
    import jax

    if jax.process_count() <= 1:
        return False
    if _hb_thread is not None and _hb_thread.is_alive():
        return True
    _hb_stop.clear()
    rank = jax.process_index()

    def beat():
        key = "%s%d" % (_HB_PREFIX, rank)
        while not _hb_stop.wait(interval):
            try:
                client.key_value_set(key, repr(time.time()), allow_overwrite=True)
            except Exception:  # pragma: no cover - service shutting down
                return

    client.key_value_set("%s%d" % (_HB_PREFIX, rank), repr(time.time()),
                         allow_overwrite=True)
    _hb_thread = threading.Thread(target=beat, daemon=True,
                                  name="mxtpu-heartbeat")
    _hb_thread.start()
    return True


def stop_heartbeat() -> None:
    _hb_stop.set()


def get_dead_nodes(timeout: float = 10.0) -> List[int]:
    """Ranks whose heartbeat is older than ``timeout`` seconds (reference
    ``KVStoreDist::GetDeadNodes``, kvstore_dist.h:121). Ranks that never
    published a heartbeat are reported dead too."""
    client = _coord_client()
    if client is None:
        return []
    import jax

    if jax.process_count() <= 1:
        return []
    now = time.time()
    dead = []
    for rank in range(jax.process_count()):
        try:
            raw = client.key_value_try_get("%s%d" % (_HB_PREFIX, rank))
            if now - float(raw) > timeout:
                dead.append(rank)
        except Exception:  # no heartbeat published
            dead.append(rank)
    return dead


# ---------------------------------------------------------------------------
# preemption signal + supervision primitives
# ---------------------------------------------------------------------------


class Preempted(MXNetError):
    """The run is being evicted (SIGTERM / preemption file): state was
    flushed best-effort and the process should exit cleanly so the
    scheduler can reschedule it. :func:`run_elastic` re-raises this
    WITHOUT consuming a restart — rescheduling is the pod supervisor's
    job, not the in-process loop's."""


class StallError(MXNetError):
    """No step progress within ``MXNET_ELASTIC_STALL_SECS`` — the hang
    class of failure (wedged device wait, deadlocked input
    pipeline) surfaced as a restartable error instead of an eternal
    wedge."""


_PREEMPT = threading.Event()
_PROGRESS_LOCK = threading.Lock()
_PROGRESS = [time.monotonic()]
_SIGTERM_INSTALLED = False
_FILE_WATCHER: Optional[threading.Thread] = None

#: per-thread attempt bookkeeping: the stall watchdog abandons a wedged
#: attempt thread by flipping its ``cancelled`` event — the zombie then
#: STOPS at its next step boundary instead of training on, so it can
#: neither feed heartbeats that mask a stall in the replacement attempt
#: nor keep drawing from the process-global RNG streams underneath it.
_ATTEMPT_TL = threading.local()


def _attempt_cancelled() -> Optional[threading.Event]:
    return getattr(_ATTEMPT_TL, "cancelled", None)


def note_progress() -> None:
    """Heartbeat for the stall watchdog: called by :func:`step_boundary`
    and by every checkpoint commit. A cancelled (watchdog-abandoned)
    attempt thread's heartbeats are dropped — only the live attempt may
    feed the watchdog."""
    ev = _attempt_cancelled()
    if ev is not None and ev.is_set():
        return
    with _PROGRESS_LOCK:
        _PROGRESS[0] = time.monotonic()


def _last_progress() -> float:
    with _PROGRESS_LOCK:
        return _PROGRESS[0]


def request_preemption() -> None:
    """Raise the preemption flag in-process (tests; ops tooling uses the
    ``MXNET_PREEMPTION_FILE`` touch-file or SIGTERM)."""
    _PREEMPT.set()


def clear_preemption() -> None:
    _PREEMPT.clear()


def _preemption_file() -> str:
    return str(get_env("MXNET_PREEMPTION_FILE", "", str, cache=False))


def preempt_requested() -> bool:
    """Whether an eviction notice is pending: the in-process flag, a
    delivered SIGTERM, or the existence of ``MXNET_PREEMPTION_FILE``
    (the file is polled here too, so the notice is seen even when the
    watcher thread was never started)."""
    if _PREEMPT.is_set():
        return True
    path = _preemption_file()
    if path and os.path.exists(path):
        _PREEMPT.set()
        return True
    return False


def start_preemption_watcher(poll_interval: float = 1.0) -> bool:
    """Install the preemption listeners: a SIGTERM handler (main thread
    only — signal delivery is a main-thread affair in CPython) and, when
    ``MXNET_PREEMPTION_FILE`` names a path, a polling thread watching for
    its appearance (the GKE/maintenance-event pattern: the node agent
    touches a file ahead of eviction). Idempotent; returns whether any
    listener is active. :func:`run_elastic` calls this on entry."""
    global _SIGTERM_INSTALLED, _FILE_WATCHER
    if not _SIGTERM_INSTALLED and \
            threading.current_thread() is threading.main_thread():
        try:
            import signal

            prev = signal.getsignal(signal.SIGTERM)

            def handler(signum, frame):
                _PREEMPT.set()
                # black box first: if the grace period is short, the dump
                # must not depend on reaching the next step boundary
                _flightrec.record("preemption.sigterm")
                _flightrec.dump("SIGTERM (preemption notice)")
                _LOG.warning("SIGTERM received: preemption checkpoint will "
                             "run at the next step boundary")
                if callable(prev):
                    try:
                        prev(signum, frame)
                    except Exception:  # noqa: BLE001 - the chained
                        # handler's failure must not lose OUR notice
                        _LOG.exception("chained SIGTERM handler failed")

            signal.signal(signal.SIGTERM, handler)
            _SIGTERM_INSTALLED = True
        except (ValueError, OSError):  # pragma: no cover - restricted env
            pass
    if (_FILE_WATCHER is None or not _FILE_WATCHER.is_alive()) \
            and _preemption_file():
        def poll():
            while not _PREEMPT.wait(poll_interval):
                path = _preemption_file()
                if path and os.path.exists(path):
                    _PREEMPT.set()
                    return

        _FILE_WATCHER = threading.Thread(target=poll, daemon=True,
                                         name="mxtpu-preempt-watch")
        _FILE_WATCHER.start()
    return _SIGTERM_INSTALLED or _FILE_WATCHER is not None


def step_boundary(manager: Optional["CheckpointManager"] = None,
                  save_fn: Optional[Callable[[], Any]] = None) -> None:
    """Per-step hook for elastic training loops (``trainplane.fit`` calls
    it per batch; hand-rolled loops should too):

    1. heartbeats the stall watchdog (:func:`note_progress`);
    2. is the ``elastic.step`` chaos site — an ``action=kill`` schedule
       simulates preemption-without-warning exactly here (kill-at-step);
    3. honors a pending graceful preemption: runs the best-effort
       ``save_fn`` (checkpoint-now), joins pending async writes on
       ``manager``, counts ``mxnet_preemptions_total`` and raises
       :class:`Preempted` for a clean exit.

    An attempt the stall watchdog already abandoned stops HERE: its next
    boundary raises :class:`StallError` so the zombie thread cannot keep
    training (committing stale epochs, consuming RNG draws) underneath
    the replacement attempt.
    """
    ev = _attempt_cancelled()
    if ev is not None and ev.is_set():
        raise StallError("attempt was abandoned by the stall watchdog; "
                         "a replacement attempt owns the run now")
    note_progress()
    chaos.maybe_fail("elastic.step")
    if not preempt_requested():
        return
    telemetry.PREEMPTIONS.inc()
    _flightrec.record("preemption.honored")
    if save_fn is not None:
        try:
            save_fn()
        except Exception:  # noqa: BLE001 - best-effort by contract: the
            # LAST committed epoch is still durable; losing the final
            # window beats dying inside the eviction grace period
            _LOG.exception("preemption checkpoint-now failed; the run will "
                           "resume from the last committed epoch")
    if manager is not None:
        try:
            manager.wait()
        except Exception:  # noqa: BLE001 - same best-effort contract
            _LOG.exception("pending async checkpoint failed during "
                           "preemption flush")
    raise Preempted("preemption requested (SIGTERM or %s)"
                    % (_preemption_file() or "request_preemption()"))


# ---------------------------------------------------------------------------
# atomic checkpoints
# ---------------------------------------------------------------------------

def _fsync_file(path: str) -> None:
    """Flush a written file's data to stable storage before it is renamed
    into place (rename-then-crash must not expose torn contents)."""
    fd = os.open(path, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def _fsync_dir(path: str) -> None:
    """Flush a directory entry (the rename itself) to stable storage.
    Directory fds are a POSIX notion; where they can't be opened (or fsync
    on them is rejected, e.g. some network filesystems) durability falls
    back to the filesystem's own ordering."""
    flags = os.O_RDONLY | getattr(os, "O_DIRECTORY", 0)
    try:
        fd = os.open(path, flags)
    except OSError:
        return
    try:
        os.fsync(fd)
    except OSError:  # pragma: no cover - fs-dependent
        pass
    finally:
        os.close(fd)


def _bytes_of(writer: Callable[[str], None]) -> bytes:
    """Run a path-writing serializer into memory: the snapshot half of an
    async save (serialize NOW on the caller, write later on the engine)."""
    fd, tmp = tempfile.mkstemp(suffix=".snap")
    os.close(fd)
    try:
        writer(tmp)
        with open(tmp, "rb") as f:
            return f.read()
    finally:
        os.remove(tmp)


def _write_bytes(path: str, data: bytes) -> None:
    with open(path, "wb") as f:
        f.write(data)


def commit_bytes(path: str, data: bytes, kind: str) -> None:
    """One durable standalone commit for callers OUTSIDE a
    :class:`CheckpointManager` (symbol/module save paths): the same
    tmp+fsync+rename atomic write, ``ckpt.commit`` retry policy,
    ``mxnet_ckpt_bytes_total`` accounting and stall-watchdog progress
    the manager's ``_commit_bytes`` gives every managed file."""
    telemetry.CKPT_BYTES.inc(len(data), kind=kind)
    resilience.call(
        "ckpt.commit",
        lambda: CheckpointManager._atomic_write(
            path, lambda p: _write_bytes(p, data)))
    _flightrec.record("ckpt.commit", file=os.path.basename(path),
                      artifact=kind, bytes=len(data))
    note_progress()


def _host_snapshot(params: Dict) -> Dict:
    """Host copies of a name→array dict in ONE batched transfer
    (``base.fetch_host``) — the save IS the host snapshot, but it needn't
    drain the device stream once per parameter the way a per-item
    ``.asnumpy()`` loop does."""
    nd_keys = [k for k, v in params.items() if hasattr(v, "asnumpy")]
    fetched = dict(zip(nd_keys, fetch_host([params[k] for k in nd_keys])
                       if nd_keys else []))
    return {k: fetched[k] if k in fetched else np.asarray(v)
            for k, v in params.items()}


def _sha256(data: bytes) -> str:
    return hashlib.sha256(data).hexdigest()


def _updater_of(trainer):
    """The state-owning Updater behind either a gluon ``Trainer`` or a
    bare ``optimizer.Updater`` (both are accepted wherever checkpoints
    take a ``trainer``)."""
    if trainer is None:
        return None
    if hasattr(trainer, "_updaters"):
        ups = getattr(trainer, "_updaters") or []
        return ups[0] if ups else None
    if hasattr(trainer, "states") and hasattr(trainer, "optimizer"):
        return trainer
    return None


class _CorruptCheckpoint(MXNetError):
    """A committed-looking epoch that cannot actually be restored
    (missing referenced file, content-hash mismatch, unreadable
    manifest). Restore walks back to an older epoch instead of raising."""


class CheckpointManager(object):
    """Atomic, hashed, bounded-retention checkpoints for elastic resume.

    Artifacts per epoch extend the reference's two-file contract
    (``prefix-####.params`` + optimizer states, model.py:383):

    ========================  ============================================
    file                      contents
    ========================  ============================================
    ``*.params``              parameters (``Block.save_parameters`` / raw
                              dict via ``nd.save``)
    ``*.states``              materialized optimizer state
                              (``Trainer.save_states``) — replicated path
    ``*.shard{r}-of-{dp}``    dp rank ``r``'s piece of the ZeRO-partitioned
                              state flat buckets — sharded path (no
                              all-gather at save)
    ``*.repl``                replicated slots of a sharded save (the
                              level-1 fp32 masters)
    ``*.zmeta``               sharded-topology pickle: plan signature/
                              buckets/padding, state treedef templates,
                              the optimizer (with its step counters)
    ``*.train``               deterministic-resume pickle: data-iterator
                              cursor, RNG streams, caller extra state
    ``*.manifest.json``       the commit point: file list + sha256 per
                              file, written LAST
    ========================  ============================================

    Every file is written tmp + fsync + rename (+ directory fsync); the
    manifest commits last, so a crash mid-save can never leave a
    readable-but-torn checkpoint. Restore verifies the recorded content
    hashes and treats any mismatch or missing file as *uncommitted*,
    falling back to the previous committed epoch
    (``mxnet_ckpt_corruption_total`` counts each fallback).
    """

    def __init__(self, directory: str, prefix: str = "ckpt",
                 max_keep: int = 5):
        self.directory = directory
        self.prefix = prefix
        self.max_keep = max_keep
        self.last_restored_extra: Optional[Dict] = None
        os.makedirs(directory, exist_ok=True)
        # serializes checkpoint writes on the host dependency engine when
        # saving asynchronously (write-after-write on one var keeps commits
        # ordered; reference: checkpoint IO rides the engine like any op)
        from . import engine as _engine

        self._engine = _engine
        self._io_var = _engine.new_var()

    # -- paths -------------------------------------------------------------
    def _manifest_path(self, epoch: int) -> str:
        return os.path.join(self.directory,
                            "%s-%04d.manifest.json" % (self.prefix, epoch))

    def _params_path(self, epoch: int) -> str:
        return os.path.join(self.directory,
                            "%s-%04d.params" % (self.prefix, epoch))

    def _states_path(self, epoch: int) -> str:
        return os.path.join(self.directory,
                            "%s-%04d.states" % (self.prefix, epoch))

    def _train_path(self, epoch: int) -> str:
        return os.path.join(self.directory,
                            "%s-%04d.train" % (self.prefix, epoch))

    def _zmeta_path(self, epoch: int) -> str:
        return os.path.join(self.directory,
                            "%s-%04d.zmeta" % (self.prefix, epoch))

    def _repl_path(self, epoch: int) -> str:
        return os.path.join(self.directory,
                            "%s-%04d.repl" % (self.prefix, epoch))

    def _shard_path(self, epoch: int, rank: int, dp: int) -> str:
        return os.path.join(self.directory, "%s-%04d.shard%d-of-%d"
                            % (self.prefix, epoch, rank, dp))

    @staticmethod
    def _atomic_write(path: str, writer: Callable[[str], None]) -> None:
        """tmp + fsync + rename + directory-fsync commit. The rename alone
        (the previous implementation) is atomic against concurrent READERS
        but not crash-durable: after a power loss the file system may
        replay the rename before the tmp file's data blocks, leaving a
        committed name with torn contents — exactly the state the manifest
        protocol promises can't exist. fsync the data before the rename
        and the directory entry after it, and the commit point is real.
        A failed attempt always removes its tmp file (no stale partials
        for a retry or a later save to trip over)."""
        tmp = path + ".tmp.%d" % os.getpid()
        try:
            writer(tmp)
            _fsync_file(tmp)
            chaos.maybe_fail("ckpt.commit")
            os.replace(tmp, path)
        except BaseException:
            try:
                os.remove(tmp)
            except OSError:
                pass
            raise
        _fsync_dir(os.path.dirname(path) or ".")

    def _commit(self, path: str, writer: Callable[[str], None]) -> None:
        """One durable file commit under the resilience retry policy: a
        transient write failure (or injected ``ckpt.commit`` fault) retries
        with backoff instead of losing the checkpoint. Every successful
        commit is step progress for the stall watchdog."""
        resilience.call("ckpt.commit",
                        lambda: self._atomic_write(path, writer))
        _flightrec.record("ckpt.commit", file=os.path.basename(path))
        note_progress()

    def _commit_bytes(self, path: str, data: bytes, kind: str) -> None:
        # one commit idiom, shared with standalone callers (symbol/module)
        commit_bytes(path, data, kind)

    @staticmethod
    def _torn_write(path: str, data: bytes) -> None:
        """Chaos ``action=torn-write``: commit a DELIBERATELY truncated
        shard under the final name — the silently-torn-write failure a
        lying fsync or bitrot produces, which the manifest's content hash
        exists to catch (restore must fall back, never crash)."""
        with open(path, "wb") as f:  # tpulint: disable=non-atomic-write - simulating the torn commit IS the test
            f.write(data[:max(1, len(data) // 2)])
        _fsync_file(path)

    # -- save (legacy two-file contract) -----------------------------------
    def save(self, epoch: int, net=None, trainer=None,
             params: Optional[Dict] = None,
             metadata: Optional[Dict] = None, async_save: bool = False) -> str:
        """Commit a checkpoint for ``epoch``. ``net`` is a Gluon Block (or
        pass a raw name→NDArray ``params`` dict); ``trainer`` optionally
        adds optimizer state (materializing any ZeRO-sharded layout —
        use :meth:`save_training` for the shard-direct path).

        ``async_save=True`` snapshots the parameter values now (host copy)
        and performs the file writes on the host engine so training
        continues immediately; writes to this manager stay ordered, and
        :meth:`wait` / the next synchronous call joins them.
        """
        if async_save:
            # EVERYTHING is serialized to bytes NOW — params through the same
            # save_parameters/io_utils code path the sync branch uses (so
            # restore naming matches) and optimizer state through
            # trainer.save_states — because serializing later on the engine
            # thread would snapshot a LATER training step than the caller saw.
            params_bytes = None
            if net is not None:
                params_bytes = _bytes_of(lambda p: net.save_parameters(p))
            elif params is not None:
                from .ndarray import io_utils

                snap = _host_snapshot(params)
                params_bytes = _bytes_of(lambda p: io_utils.save(p, snap))
            states_bytes = None
            if trainer is not None:
                states_bytes = _bytes_of(lambda p: trainer.save_states(p))

            def commit():
                files = {}
                if params_bytes is not None:
                    self._commit_bytes(self._params_path(epoch),
                                       params_bytes, "params")
                    files["params"] = os.path.basename(self._params_path(epoch))
                if states_bytes is not None:
                    self._commit_bytes(self._states_path(epoch),
                                       states_bytes, "states")
                    files["states"] = os.path.basename(self._states_path(epoch))
                manifest = {"epoch": epoch, "time": time.time(),
                            "files": files, "metadata": metadata or {}}
                self._commit_manifest(epoch, manifest)
                self._retire_old()

            self._engine.push(commit, mutable_vars=[self._io_var])
            return self._manifest_path(epoch)
        files = {}
        if net is not None:
            self._commit(self._params_path(epoch),
                         lambda p: net.save_parameters(p))
            files["params"] = os.path.basename(self._params_path(epoch))
        elif params is not None:
            from .ndarray import io_utils

            self._commit(self._params_path(epoch),
                         lambda p: io_utils.save(p, params))
            files["params"] = os.path.basename(self._params_path(epoch))
        if trainer is not None:
            self._commit(self._states_path(epoch),
                         lambda p: trainer.save_states(p))
            files["states"] = os.path.basename(self._states_path(epoch))
        manifest = {"epoch": epoch, "time": time.time(), "files": files,
                    "metadata": metadata or {}}
        self._commit_manifest(epoch, manifest)
        self._retire_old()
        return self._manifest_path(epoch)

    def _commit_manifest(self, epoch: int, manifest: Dict) -> None:
        data = json.dumps(manifest).encode("utf-8")
        self._commit_bytes(self._manifest_path(epoch), data, "manifest")

    # -- save (the full training-state contract) ---------------------------
    def save_training(self, epoch: int, net=None, trainer=None,
                      params: Optional[Dict] = None, train_iter=None,
                      metadata: Optional[Dict] = None,
                      extra: Optional[Dict] = None, save_rng: bool = True,
                      async_save: bool = False, sharded="auto") -> str:
        """One complete training checkpoint: parameters, optimizer state,
        data-iterator cursor and RNG streams — everything deterministic
        resume needs, committed manifest-last with per-file sha256.

        Optimizer state routing (``sharded``):

        * ``"auto"`` (default) — when ``trainer``'s updater carries an
          active ZeRO plane (``MXNET_ZERO`` ≥ 1), each dp shard of the
          flat state buckets is saved DIRECTLY from its device shard:
          no materialize, no all-gather, no step-long full-state HBM
          spike (``mxnet_zero_materializations_total`` provably does not
          move). Otherwise the materialized ``Trainer.save_states`` path
          runs. ``MXNET_CKPT_SHARDED=0`` forces the materialized path
          (debugging escape hatch: single mesh-independent file).
        * ``False`` — always materialize (mesh-independent single file).

        ``async_save=True`` performs ONLY the device→host snapshot on the
        caller (one 1/dp copy per shard on the sharded path), then
        writes, fsyncs and commits on the host engine overlapping
        subsequent steps. A new save first :meth:`wait`\\ s for any
        pending one — two snapshots never interleave their writes.

        ``train_iter`` is any iterator implementing the
        ``state_dict``/``set_state`` cursor protocol (io.NDArrayIter and
        the prefetch pipelines do); ``save_rng`` captures
        ``mx.random.get_state()``. Returns the manifest path.
        """
        t0 = time.perf_counter()
        self.wait()  # barrier: never race a pending async save
        payloads: List[Tuple[str, bytes, str]] = []
        files: Dict[str, str] = {}
        hashes: Dict[str, str] = {}

        def add(name: str, path: str, data: bytes, kind: str) -> None:
            payloads.append((path, data, kind))
            files[name] = os.path.basename(path)
            hashes[name] = _sha256(data)

        if net is not None:
            add("params", self._params_path(epoch),
                _bytes_of(lambda p: net.save_parameters(p)), "params")
        elif params is not None:
            from .ndarray import io_utils

            snap = _host_snapshot(params)
            add("params", self._params_path(epoch),
                _bytes_of(lambda p: io_utils.save(p, snap)), "params")

        sharded_info = None
        shard_entries: List[Dict[str, Any]] = []
        updater = _updater_of(trainer)
        if trainer is not None:
            export = None
            if sharded is not False and \
                    get_env("MXNET_CKPT_SHARDED", 1, int, cache=False):
                export = self._sharded_export(updater)
                if export is None and sharded is True:
                    _LOG.warning("save_training(sharded=True) but no active "
                                 "ZeRO plane; saving materialized state")
            if export is not None:
                meta, shards, repl = export
                add("zmeta", self._zmeta_path(epoch), pickle.dumps(meta),
                    "meta")
                dp = int(meta["dp"])
                for r in range(dp):
                    data = pickle.dumps(shards[r])
                    path = self._shard_path(epoch, r, dp)
                    shard_entries.append({"file": os.path.basename(path),
                                          "sha256": _sha256(data),
                                          "rank": r})
                    payloads.append((path, data, "shard"))
                if repl:
                    add("repl", self._repl_path(epoch), pickle.dumps(repl),
                        "repl")
                sharded_info = {"dp": dp, "level": int(meta["level"]),
                                "mesh_shape": meta["mesh_shape"]}
            elif hasattr(trainer, "save_states"):
                add("states", self._states_path(epoch),
                    _bytes_of(lambda p: trainer.save_states(p)), "states")
            elif updater is not None:
                add("states", self._states_path(epoch),
                    updater.get_states(dump_optimizer=True), "states")

        train_state: Dict[str, Any] = {}
        if train_iter is not None and hasattr(train_iter, "state_dict"):
            train_state["iter"] = train_iter.state_dict()
        if save_rng:
            from . import random as _random

            train_state["rng"] = _random.get_state()
        if extra:
            train_state["extra"] = dict(extra)
        if train_state:
            add("train", self._train_path(epoch),
                pickle.dumps(train_state), "train")

        manifest = {"epoch": epoch, "time": time.time(), "format": 2,
                    "files": files, "hashes": hashes,
                    "shards": shard_entries, "sharded": sharded_info,
                    "metadata": metadata or {}}

        def commit():
            for path, data, kind in payloads:
                if kind == "shard":
                    try:
                        chaos.maybe_fail("ckpt.shard")
                    except chaos.TornWrite:
                        self._torn_write(path, data)
                        continue
                    except chaos.DropShard:
                        continue
                self._commit_bytes(path, data, kind)
            self._commit_manifest(epoch, manifest)
            self._retire_old()

        if async_save:
            self._engine.push(commit, mutable_vars=[self._io_var])
        else:
            commit()
        telemetry.CKPT_SAVE_MS.observe(
            (time.perf_counter() - t0) * 1e3,
            mode="async" if async_save else "sync")
        return self._manifest_path(epoch)

    def _sharded_export(self, updater):
        """The ZeRO plane's shard-direct snapshot, or ``None`` when the
        materialized path must run (no plane, plane without live buckets,
        buckets donated into a step that then failed)."""
        if updater is None:
            return None
        from .fastpath import zero

        plane = zero.plane_of(updater)
        if plane is None or plane.buckets is None:
            return None
        import jax

        for leaf in jax.tree_util.tree_leaves(plane.buckets):
            if getattr(leaf, "is_deleted", lambda: False)():
                return None
        try:
            meta, shards, repl = plane.shard_export()
        except Exception:  # noqa: BLE001 - never-a-crash: a failed shard
            # read degrades to the materialized save, not a lost epoch
            _LOG.exception("sharded state export failed; saving "
                           "materialized state instead")
            return None
        meta["optimizer"] = updater.optimizer
        return meta, shards, repl

    # -- manifest bookkeeping ----------------------------------------------
    def _epochs(self) -> List[int]:
        out = []
        for f in os.listdir(self.directory):
            if f.startswith(self.prefix + "-") and f.endswith(".manifest.json"):
                try:
                    out.append(int(f[len(self.prefix) + 1:-len(".manifest.json")]))
                except ValueError:
                    continue
        return sorted(out)

    def _read_manifest(self, epoch: int) -> Dict:
        try:
            with open(self._manifest_path(epoch)) as f:
                return json.load(f)
        except (OSError, ValueError) as exc:
            raise _CorruptCheckpoint("manifest for epoch %d unreadable: %s"
                                     % (epoch, exc))

    @staticmethod
    def _manifest_files(manifest: Dict) -> List[str]:
        """Every file basename a manifest commits to (legacy str values
        and format-2 alike, shard entries included)."""
        out = []
        for v in (manifest.get("files") or {}).values():
            out.append(v["file"] if isinstance(v, dict) else v)
        for s in manifest.get("shards") or []:
            out.append(s["file"])
        return out

    def _is_committed(self, epoch: int) -> bool:
        """A manifest whose referenced shard/param files are missing is
        NOT a committed checkpoint — resume must not anchor on it (the
        drop-one-shard failure mode, and half-retired epochs)."""
        try:
            manifest = self._read_manifest(epoch)
        except _CorruptCheckpoint:
            return False
        return all(os.path.isfile(os.path.join(self.directory, f))
                   for f in self._manifest_files(manifest))

    def _retire_old(self) -> None:
        """Bounded retention. ``max_keep <= 0``/None disables GC; any
        other value keeps AT LEAST one epoch, and the newest COMMITTED
        manifest is never retired regardless of how retention is
        (mis)configured — the last restorable state outranks the quota."""
        if not self.max_keep:
            return
        keep = max(1, int(self.max_keep))
        epochs = self._epochs()
        committed = [e for e in epochs if self._is_committed(e)]
        protect = {committed[-1]} if committed else set()
        for e in epochs[:-keep]:
            if e in protect:
                continue
            self._remove_epoch(e)

    def _remove_epoch(self, epoch: int) -> None:
        # the manifest goes FIRST so a crash mid-retire leaves the epoch
        # reading as uncommitted, never as committed-but-holey
        try:
            os.remove(self._manifest_path(epoch))
        except OSError:
            pass
        stem = "%s-%04d." % (self.prefix, epoch)
        for f in os.listdir(self.directory):
            if f.startswith(stem):
                try:
                    os.remove(os.path.join(self.directory, f))
                except OSError:
                    pass

    def wait(self) -> None:
        """Join pending async saves (re-raising any write failure) — the
        barrier new saves and preemption flushes take before touching the
        directory."""
        self._engine.wait_for_var(self._io_var)

    def flush(self) -> None:
        """Alias of :meth:`wait` — the preemption-path name."""
        self.wait()

    def latest_epoch(self) -> int:
        """Newest COMMITTED epoch (manifest readable and every referenced
        file present), or -1. Joins pending async saves first."""
        self.wait()
        for e in reversed(self._epochs()):
            if self._is_committed(e):
                return e
        return -1

    # -- restore ------------------------------------------------------------
    def restore(self, net=None, trainer=None, epoch: Optional[int] = None):
        """Load the latest (or given) committed checkpoint into net/trainer.
        Returns the epoch restored, or -1 when none exists. Corrupt epochs
        (hash mismatch, missing file) fall back to older ones."""
        return self.restore_training(net=net, trainer=trainer, epoch=epoch,
                                     restore_rng=False)

    def restore_training(self, net=None, trainer=None, train_iter=None,
                         epoch: Optional[int] = None,
                         restore_rng: bool = True) -> int:
        """Restore the full training state saved by :meth:`save_training`
        (or :meth:`save`): parameters into ``net``, optimizer state into
        ``trainer`` (sharded checkpoints are re-bucketed through the flat
        plan — the target mesh's dp size need not match the one saved),
        the data-iterator cursor into ``train_iter`` and the RNG streams.

        Walks committed epochs newest-first: an epoch whose content
        hashes mismatch or whose files vanished counts
        ``mxnet_ckpt_corruption_total`` and FALLS BACK to the previous
        committed epoch — corruption costs a window of training, never
        the run. Returns the epoch restored (-1 when none); the saved
        ``extra`` dict lands in :attr:`last_restored_extra`."""
        t0 = time.perf_counter()
        self.wait()
        self.last_restored_extra = None
        explicit = epoch is not None
        candidates = [epoch] if explicit else list(reversed(self._epochs()))
        for e in candidates:
            try:
                extra = self._restore_epoch(e, net, trainer, train_iter,
                                            restore_rng)
            except _CorruptCheckpoint as exc:
                telemetry.CKPT_CORRUPTION.inc()
                if explicit:
                    raise MXNetError("checkpoint epoch %d unusable: %s"
                                     % (e, exc))
                _LOG.warning("checkpoint epoch %d unusable (%s); falling "
                             "back to the previous committed epoch", e, exc)
                continue
            self.last_restored_extra = extra
            telemetry.CKPT_RESTORE_MS.observe(
                (time.perf_counter() - t0) * 1e3)
            return e
        return -1

    @staticmethod
    def _want_hash(manifest: Dict, name: str, fname: str) -> Optional[str]:
        want = (manifest.get("hashes") or {}).get(name)
        if want is None and name == "shard":
            want = next((s["sha256"] for s in manifest.get("shards") or []
                         if s["file"] == fname), None)
        return want

    def _verified_read(self, manifest: Dict, name: str,
                       fname: str) -> bytes:
        """Read an artifact that is CONSUMED from memory (shards, zmeta,
        repl, train), verifying its recorded hash on the way."""
        path = os.path.join(self.directory, fname)
        try:
            with open(path, "rb") as f:
                data = f.read()
        except OSError as exc:
            raise _CorruptCheckpoint("missing %s file %s: %s"
                                     % (name, fname, exc))
        want = self._want_hash(manifest, name, fname)
        if want is not None and _sha256(data) != want:
            raise _CorruptCheckpoint("content hash mismatch on %s (%s)"
                                     % (fname, name))
        return data

    def _verify_file(self, manifest: Dict, name: str, fname: str) -> None:
        """Stream-verify an artifact that is loaded from DISK by its
        consumer (params, states): a multi-GB params file must not be
        held in host memory just to hash it."""
        want = self._want_hash(manifest, name, fname)
        if want is None:
            return
        path = os.path.join(self.directory, fname)
        digest = hashlib.sha256()
        try:
            with open(path, "rb") as f:
                for chunk in iter(lambda: f.read(1 << 20), b""):
                    digest.update(chunk)
        except OSError as exc:
            raise _CorruptCheckpoint("missing %s file %s: %s"
                                     % (name, fname, exc))
        if digest.hexdigest() != want:
            raise _CorruptCheckpoint("content hash mismatch on %s (%s)"
                                     % (fname, name))

    def _restore_epoch(self, epoch: int, net, trainer, train_iter,
                       restore_rng) -> Optional[Dict]:
        manifest = self._read_manifest(epoch)
        missing = [f for f in self._manifest_files(manifest)
                   if not os.path.isfile(os.path.join(self.directory, f))]
        if missing:
            raise _CorruptCheckpoint("missing files: %s" % ", ".join(missing))
        raw_files = manifest.get("files") or {}
        fnames = {n: (v["file"] if isinstance(v, dict) else v)
                  for n, v in raw_files.items()}
        # verify hashes BEFORE mutating anything: a half-applied restore
        # would be worse than the corruption it detected. params/states
        # are stream-verified (their consumers load from disk); the
        # memory-consumed artifacts are read-and-verified in one pass
        blobs: Dict[str, bytes] = {}
        for name, fname in fnames.items():
            if name in ("params", "states"):
                self._verify_file(manifest, name, fname)
            else:
                blobs[name] = self._verified_read(manifest, name, fname)
        shard_blobs: List[Tuple[int, bytes]] = []
        for s in manifest.get("shards") or []:
            rank = int(s.get("rank", len(shard_blobs)))  # tpulint: disable=host-sync - manifest JSON int, no device value
            shard_blobs.append((rank,
                                self._verified_read(manifest, "shard",
                                                    s["file"])))

        if net is not None and "params" in fnames:
            net.load_parameters(os.path.join(self.directory,
                                             fnames["params"]))
        if trainer is not None:
            if manifest.get("sharded"):
                self._restore_sharded(trainer, blobs, shard_blobs)
            elif "states" in fnames:
                states_path = os.path.join(self.directory,
                                           fnames["states"])
                if hasattr(trainer, "load_states"):
                    trainer.load_states(states_path)
                else:
                    with open(states_path, "rb") as f:
                        _updater_of(trainer).set_states(f.read())

        train_state: Dict[str, Any] = {}
        if "train" in blobs:
            try:
                train_state = pickle.loads(blobs["train"])
            except Exception as exc:  # noqa: BLE001 - treat as corruption
                raise _CorruptCheckpoint("train-state pickle unreadable: %s"
                                         % exc)
        if train_iter is not None and hasattr(train_iter, "set_state") \
                and "iter" in train_state:
            train_iter.set_state(train_state["iter"])
        if restore_rng and "rng" in train_state:
            from . import random as _random

            _random.set_state(train_state["rng"])
        return train_state.get("extra")

    def _restore_sharded(self, trainer, blobs: Dict[str, bytes],
                         shard_blobs: List[Tuple[int, bytes]]) -> None:
        """Rebuild plain per-parameter states from the per-rank shard
        files (concatenate rank pieces → strip via the saved flat-plan
        layout) and adopt them into the updater. The NEXT sharded step
        re-packs onto whatever mesh is live (``bucketing.flat_plan``
        with the new dp), which is how restore onto a different dp size
        round-trips exactly."""
        from .fastpath import zero

        try:
            meta = pickle.loads(blobs["zmeta"])
        except Exception as exc:  # noqa: BLE001 - treat as corruption
            raise _CorruptCheckpoint("zmeta pickle unreadable: %s" % exc)
        pieces: Dict[str, List[Tuple[int, np.ndarray]]] = {}
        for rank, data in shard_blobs:
            try:
                shard = pickle.loads(data)
            except Exception as exc:  # noqa: BLE001 - treat as corruption
                raise _CorruptCheckpoint("shard %d unreadable: %s"
                                         % (rank, exc))
            for key, arr in shard.items():
                pieces.setdefault(key, []).append((rank, arr))
        slot_arrays: Dict[str, np.ndarray] = {}
        for key, parts in pieces.items():
            parts.sort(key=lambda p: p[0])
            slot_arrays[key] = np.concatenate([a for _, a in parts]) \
                if len(parts) > 1 else parts[0][1]
        if "repl" in blobs:
            try:
                slot_arrays.update(pickle.loads(blobs["repl"]))
            except Exception as exc:  # noqa: BLE001 - treat as corruption
                raise _CorruptCheckpoint("repl pickle unreadable: %s" % exc)
        try:
            trees = zero.states_from_export(meta, slot_arrays)
        except (KeyError, ValueError) as exc:
            raise _CorruptCheckpoint("sharded state incomplete: %s" % exc)
        states = {idx: tree
                  for idx, tree in zip(meta["indices"], trees)}
        optimizer = meta.get("optimizer")
        updater = _updater_of(trainer)
        updater.adopt_states(states, optimizer=optimizer)
        if hasattr(trainer, "_updaters") and optimizer is not None:
            trainer._optimizer = optimizer
            for u in trainer._updaters:
                u.optimizer = optimizer

    def load_params(self, epoch: Optional[int] = None) -> Dict:
        from .ndarray import io_utils

        if epoch is None:
            epoch = self.latest_epoch()
        if epoch < 0:
            raise MXNetError("no committed checkpoint to load")
        return io_utils.load(self._params_path(epoch))


# ---------------------------------------------------------------------------
# elastic run loop
# ---------------------------------------------------------------------------


def _invoke_attempt(train_fn, start_epoch: int, manager: CheckpointManager,
                    stall_timeout: float):
    """Run one attempt. With a stall timeout, the attempt runs on a
    worker thread and the supervisor watches the progress heartbeat
    (:func:`note_progress` — fed by :func:`step_boundary` and every
    checkpoint commit): silence longer than the timeout raises
    :class:`StallError` and the wedged thread is abandoned — its
    ``cancelled`` event flips, so if it ever wakes it dies at its next
    step boundary (and its heartbeats are dropped meanwhile). A thread
    hung in a device wait cannot be interrupted from Python, but a
    never-waking thread also never touches RNG or disk; late commits
    from the abandonment window stay harmless behind the atomic-commit
    protocol (worst case: a hash-mismatch fallback)."""
    if stall_timeout <= 0:
        return train_fn(start_epoch, manager)
    box: Dict[str, Any] = {}
    done = threading.Event()
    cancelled = threading.Event()

    def runner():
        _ATTEMPT_TL.cancelled = cancelled
        try:
            box["result"] = train_fn(start_epoch, manager)
        except BaseException as exc:  # noqa: BLE001 - re-raised below
            box["exc"] = exc
        finally:
            done.set()

    t = threading.Thread(target=runner, daemon=True,
                         name="mxtpu-elastic-train")
    note_progress()
    t.start()
    poll = max(0.01, min(0.25, stall_timeout / 4.0))
    while not done.wait(poll):
        if time.monotonic() - _last_progress() > stall_timeout:
            cancelled.set()
            # the hang class of death: dump the black box BEFORE the
            # restart machinery tears state down, so "what was the run
            # doing when it wedged" survives even if the restart also dies
            _flightrec.record("elastic.stall",
                              stall_timeout_s=stall_timeout)
            _flightrec.dump("elastic stall watchdog (no progress in "
                            "%.1fs)" % stall_timeout)
            raise StallError(
                "no step progress in %.1fs (MXNET_ELASTIC_STALL_SECS); "
                "treating the attempt as hung" % stall_timeout)
    if "exc" in box:
        raise box["exc"]
    return box["result"]


def run_elastic(train_fn: Callable[[int, CheckpointManager], object],
                manager: CheckpointManager, max_restarts: int = 3,
                restart_delay: float = 1.0, restart_backoff: float = 2.0,
                max_restart_delay: float = 60.0,
                stall_timeout: Optional[float] = None,
                watch_preemption: bool = True):
    """Run ``train_fn(start_epoch, manager)`` with automatic resume.

    On an exception the function is restarted from
    ``manager.latest_epoch() + 1`` — the epoch after the last COMMITTED
    checkpoint — and the final failure is re-raised. This is the
    reference's restarted-worker recovery (``is_recovery``,
    kvstore_dist.h:52) for a checkpoint-based world. Supervision rules:

    * the restart budget is ``max_restarts`` CONSECUTIVE unproductive
      attempts: any attempt that commits a newer epoch before failing
      resets the counter, so a week-long run with occasional preemptions
      is not killed by failures accumulated across its lifetime;
    * restart ``n`` waits ``restart_delay * restart_backoff**(n-1)``
      seconds (capped at ``max_restart_delay``); ``restart_delay=0``
      disables the wait (tests);
    * ``stall_timeout`` (default: ``MXNET_ELASTIC_STALL_SECS``, 0 = off)
      arms the hang watchdog: an attempt with no step progress for that
      long restarts instead of wedging forever;
    * :class:`Preempted` (the graceful-eviction exit from
      :func:`step_boundary`) flushes pending saves and re-raises WITHOUT
      consuming a restart — rescheduling belongs to the pod supervisor;
    * telemetry: ``mxnet_elastic_restarts_total{reason}`` per restart,
      ``mxnet_retries_total{site="elastic.restart"}`` (the PR-4 series),
      and ``mxnet_elastic_goodput_ratio`` — productive attempt time over
      wall time — updated at every transition.
    """
    if stall_timeout is None:
        stall_timeout = float(get_env("MXNET_ELASTIC_STALL_SECS", 0.0,
                                      float, cache=False))
    if watch_preemption:
        start_preemption_watcher()
    restarts = resilience.policies.retries_counter()
    attempt = 0
    wall0 = time.monotonic()
    productive = 0.0

    def goodput() -> None:
        wall = time.monotonic() - wall0
        if wall > 0:
            telemetry.ELASTIC_GOODPUT.set(min(1.0, productive / wall))

    while True:
        start_epoch = manager.latest_epoch() + 1
        committed_before = start_epoch - 1
        t_attempt = time.monotonic()
        try:
            result = _invoke_attempt(train_fn, start_epoch, manager,
                                     stall_timeout)
        except KeyboardInterrupt:
            raise
        except Preempted:
            try:
                manager.wait()
            except Exception:  # noqa: BLE001 - exiting anyway; the last
                # committed epoch is what the rescheduled pod resumes from
                _LOG.exception("pending async checkpoint failed during "
                               "preemption exit")
            # productive only if the attempt actually committed progress:
            # an attempt evicted before its first commit is pure replay
            # for the rescheduled pod, and the goodput gauge exists to
            # price exactly that
            try:
                if manager.latest_epoch() > committed_before:
                    productive += time.monotonic() - t_attempt
            except Exception:  # noqa: BLE001 - gauge accounting must not
                # mask the preemption exit
                _LOG.exception("goodput accounting failed during "
                               "preemption exit")
            goodput()
            raise
        except Exception as exc:  # noqa: BLE001 - the point of the harness
            duration = time.monotonic() - t_attempt
            try:
                committed_now = manager.latest_epoch()
            except Exception:  # noqa: BLE001 - a failed async save joined
                # here must not mask the restart decision
                _LOG.exception("joining pending saves after a crash failed")
                committed_now = committed_before
            made_progress = committed_now > committed_before
            if made_progress:
                productive += duration
                attempt = 1  # progress resets the consecutive-failure budget
            else:
                attempt += 1
            reason = "stall" if isinstance(exc, StallError) else "exception"
            telemetry.ELASTIC_RESTARTS.inc(reason=reason)
            _flightrec.record("elastic.restart", reason=reason,
                              attempt=attempt, error=repr(exc))
            goodput()
            if attempt > max_restarts:
                restarts.inc(site="elastic.restart", outcome="exhausted")
                raise
            restarts.inc(site="elastic.restart", outcome="retry")
            delay = min(restart_delay * (restart_backoff ** (attempt - 1)),
                        max_restart_delay) if restart_delay else 0.0
            _LOG.warning("train_fn failed (%s); restart %d/%d from epoch %d "
                         "in %.1fs", exc, attempt, max_restarts,
                         committed_now + 1, delay)
            if delay:
                time.sleep(delay)
        else:
            productive += time.monotonic() - t_attempt
            goodput()
            return result
