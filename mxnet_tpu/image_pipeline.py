"""Throughput-class image input pipeline: multiprocess RecordIO → JPEG
decode → augment → batch, the counterpart of the reference's C++
``ImageRecordIter2`` (``src/io/iter_image_recordio_2.cc:663,727`` —
multithreaded chunk reading, OpenCV decode, augment, batching, prefetch).

Python threads cannot scale JPEG decode (PIL holds the GIL for much of it),
so this pipeline uses **worker processes**: each worker opens the ``.rec``
independently, decodes + augments + batches with numpy/PIL only, and ships
finished float32 batches through POSIX shared memory. The master hands out
batch assignments over a task queue, restores order with a small reorder
buffer, and yields regular :class:`~mxnet_tpu.io.DataBatch` objects —
compose with :class:`~mxnet_tpu.io.DevicePrefetchIter` to overlap the
host→HBM transfer too.

Workers are ``spawn``ed, not forked: forking a process with a live XLA
runtime is the hazard the reference guards with fork handlers
(``src/initialize.cc``); a spawned child imports this package fresh with
``JAX_PLATFORMS=cpu``, so it never asks for the chip its parent holds.
"""
from __future__ import annotations

import os
import struct
import threading
from typing import List, Optional

import numpy as np

from .base import MXNetError
from .io import DataBatch, DataDesc, DataIter

__all__ = ["MPImageRecordIter"]


# ---------------------------------------------------------------------------
# worker side — numpy/PIL only (no jax compute)
# ---------------------------------------------------------------------------

def _decode_augment(raw: bytes, cfg: dict, rng: np.random.RandomState):
    """One record → (CHW float32 image, label vector)."""
    from PIL import Image
    import io as _io

    from . import recordio

    header, img_bytes = recordio.unpack(raw)
    label = np.atleast_1d(np.asarray(header.label, np.float32))

    img = Image.open(_io.BytesIO(img_bytes))
    if img.mode != "RGB":
        img = img.convert("RGB")
    c, th, tw = cfg["data_shape"]

    if not cfg.get("rand_crop") and not cfg.get("resize"):
        # plain configuration: stretch-resize straight to the target shape,
        # matching the single-process iterator's numerics exactly
        if img.size != (tw, th):
            img = img.resize((tw, th), Image.BILINEAR)
    else:
        # augmenting configuration: short-side resize then crop, the
        # reference default augmenter's geometry (image_aug_default.cc)
        short = cfg.get("resize") or max(th, tw)
        w, h = img.size
        scale = short / min(w, h)
        if scale != 1.0:
            img = img.resize((max(tw, int(w * scale + 0.5)),
                              max(th, int(h * scale + 0.5))), Image.BILINEAR)
        w, h = img.size
        if cfg.get("rand_crop"):
            x0 = rng.randint(0, w - tw + 1)
            y0 = rng.randint(0, h - th + 1)
        else:
            x0, y0 = (w - tw) // 2, (h - th) // 2
        img = img.crop((x0, y0, x0 + tw, y0 + th))

    arr = np.asarray(img, np.float32)
    if cfg.get("rand_mirror") and rng.randint(2):
        arr = arr[:, ::-1]

    mean = cfg.get("mean")
    if mean is not None:
        arr -= mean
    std = cfg.get("std")
    if std is not None:
        arr /= std
    chw = np.transpose(arr, (2, 0, 1))
    if c == 1:
        chw = chw.mean(axis=0, keepdims=True)
    return chw, label


_SPAWN_ENV_LOCK = threading.Lock()


def _worker_main(task_q, result_q, rec_path, idx_path, cfg, seed):
    """Worker loop: receive (seq, shm_name, keys, pad), write the batch into
    shared memory, report completion. Runs in a spawned process."""
    # keep the child off the chip: CPU-only jax if any transitive import
    # pulls it in (the parent already exported this around start(); kept
    # for a worker started some other way)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")  # tpulint: disable=env-knob -- worker env setup, not a knob read
    from multiprocessing import shared_memory

    from . import recordio

    rec = recordio.MXIndexedRecordIO(idx_path, rec_path, "r")
    rng = np.random.RandomState(seed)
    c, h, w = cfg["data_shape"]
    label_width = cfg["label_width"]
    batch_size = cfg["batch_size"]
    img_bytes = batch_size * c * h * w * 4
    opened = {}
    try:
        while True:
            task = task_q.get()
            if task is None:
                break
            seq, shm_name, keys, pad = task
            try:
                shm = opened.get(shm_name)
                if shm is None:
                    shm = shared_memory.SharedMemory(name=shm_name)
                    opened[shm_name] = shm
                data = np.ndarray((batch_size, c, h, w), np.float32,
                                  buffer=shm.buf[:img_bytes])
                labels = np.ndarray((batch_size, label_width), np.float32,
                                    buffer=shm.buf[img_bytes:])
                for slot, key in enumerate(keys):
                    img, lab = _decode_augment(rec.read_idx(key), cfg, rng)
                    data[slot] = img
                    labels[slot, :label_width] = lab[:label_width]
                result_q.put((seq, shm_name, pad, None))
            except Exception as exc:  # noqa: BLE001 - surfaced at next()
                result_q.put((seq, shm_name, pad,
                              "%s: %s" % (type(exc).__name__, exc)))
    finally:
        for shm in opened.values():
            shm.close()


# ---------------------------------------------------------------------------
# master side
# ---------------------------------------------------------------------------

class MPImageRecordIter(DataIter):
    """Multiprocess ImageRecordIter (reference iter_image_recordio_2.cc).

    Parameters mirror the reference's: ``path_imgrec`` (+``.idx`` required),
    ``data_shape`` (C,H,W), ``batch_size``, ``shuffle``, ``rand_crop``,
    ``rand_mirror``, ``resize`` (short side), ``mean_r/g/b``, ``std_r/g/b``,
    ``label_width``, ``preprocess_threads`` (worker processes),
    ``prefetch_buffer`` (in-flight batches).
    """

    def __init__(self, path_imgrec, data_shape, batch_size,
                 shuffle=False, rand_crop=False, rand_mirror=False,
                 resize=0, label_width=1, preprocess_threads=4,
                 prefetch_buffer=4, seed=None, round_batch=True,
                 mean_r=0.0, mean_g=0.0, mean_b=0.0,
                 std_r=1.0, std_g=1.0, std_b=1.0, **kwargs):
        super().__init__(batch_size)
        import multiprocessing as mp

        idx_path = os.path.splitext(path_imgrec)[0] + ".idx"
        if not os.path.exists(idx_path):
            raise MXNetError(
                "MPImageRecordIter requires %s (workers address records by "
                "key); build it with tools/im2rec.py" % idx_path)
        from . import recordio

        index = recordio.MXIndexedRecordIO(idx_path, path_imgrec, "r")
        self._keys: List[int] = list(index.keys)
        index.close()
        if not self._keys:
            raise MXNetError("empty record file %s" % path_imgrec)

        self.data_shape = tuple(data_shape)
        self._label_width = label_width
        self._shuffle = shuffle
        if seed is None:
            # derive from the framework RNG so mx.random.seed() governs
            # shuffle order and augmentation, like every other iterator
            from . import random as _random

            seed = int(_random.np_rng().randint(0, 2 ** 31 - 1))
        self._rng = np.random.RandomState(seed)
        self._round_batch = round_batch

        mean = None
        if mean_r or mean_g or mean_b:
            mean = np.asarray([mean_r, mean_g, mean_b], np.float32)
        std = None
        if (std_r, std_g, std_b) != (1.0, 1.0, 1.0):
            std = np.asarray([std_r, std_g, std_b], np.float32)
        cfg = {"data_shape": self.data_shape, "batch_size": batch_size,
               "label_width": label_width, "rand_crop": rand_crop,
               "rand_mirror": rand_mirror, "resize": resize,
               "mean": mean, "std": std}

        n_workers = max(1, int(preprocess_threads))
        depth = max(2, int(prefetch_buffer))
        c, h, w = self.data_shape
        self._img_bytes = batch_size * c * h * w * 4
        shm_bytes = self._img_bytes + batch_size * label_width * 4

        ctx = mp.get_context("spawn")
        self._task_q = ctx.Queue()
        self._result_q = ctx.Queue()
        from multiprocessing import shared_memory

        self._shms = [shared_memory.SharedMemory(create=True, size=shm_bytes)
                      for _ in range(depth + n_workers)]
        self._free = [s.name for s in self._shms]
        self._shm_by_name = {s.name: s for s in self._shms}
        self._workers = [
            ctx.Process(target=_worker_main,
                        args=(self._task_q, self._result_q, path_imgrec,
                              idx_path, cfg, seed + 101 * (i + 1)),
                        daemon=True)
            for i in range(n_workers)]
        # the spawned child imports this package BEFORE _worker_main runs,
        # so CPU-only jax must be in the environment it is exec'ed with —
        # multiprocessing offers no per-child env, hence the rewrite
        # around start(). It cannot change THIS process's backend: jax
        # binds jax_platforms from the environment when it is imported,
        # which importing this package already did. The lock keeps two
        # iterators starting on different threads from saving each
        # other's temporary value and "restoring" it for good.
        with _SPAWN_ENV_LOCK:
            saved = os.environ.get("JAX_PLATFORMS")  # tpulint: disable=env-knob -- save/restore around start(), not a knob read
            os.environ["JAX_PLATFORMS"] = "cpu"
            try:
                for p in self._workers:
                    p.start()
            finally:
                if saved is None:
                    os.environ.pop("JAX_PLATFORMS", None)
                else:
                    os.environ["JAX_PLATFORMS"] = saved

        self._seq_next = 0        # next sequence number to hand out
        self._seq_yield = 0       # next sequence number to yield
        self._pending = {}        # seq -> (shm_name, pad, err) done early
        self._epoch_batches: List = []
        self._dispatch_pos = 0
        self._closed = False
        self.reset()

    # -- epoch plan ---------------------------------------------------------
    def _plan_epoch(self):
        order = list(self._keys)
        if self._shuffle:
            self._rng.shuffle(order)
        batches = []
        bs = self.batch_size
        for start in range(0, len(order), bs):
            chunk = order[start:start + bs]
            pad = bs - len(chunk)
            if pad and not self._round_batch:
                break
            if pad:
                chunk = chunk + order[:pad]  # wrap-around fill, batch.pad set
            batches.append((chunk, pad))
        self._epoch_batches = batches
        self._dispatch_pos = 0

    def _dispatch(self):
        while self._free and self._dispatch_pos < len(self._epoch_batches):
            keys, pad = self._epoch_batches[self._dispatch_pos]
            shm_name = self._free.pop()
            self._task_q.put((self._seq_next, shm_name, keys, pad))
            self._seq_next += 1
            self._dispatch_pos += 1

    # -- DataIter interface -------------------------------------------------
    @property
    def provide_data(self):
        return [DataDesc("data", (self.batch_size,) + self.data_shape)]

    @property
    def provide_label(self):
        shape = (self.batch_size,) if self._label_width == 1 \
            else (self.batch_size, self._label_width)
        return [DataDesc("softmax_label", shape)]

    def _get_result(self):
        """result_q.get() that fails loudly if the workers died (a hung
        master is far worse than a failed epoch)."""
        import queue as _queue

        while True:
            try:
                return self._result_q.get(timeout=10)
            except _queue.Empty:
                if not any(p.is_alive() for p in self._workers):
                    raise MXNetError(
                        "image pipeline workers died (exitcodes %s); "
                        "note: multiprocessing 'spawn' requires a real "
                        "__main__ module (not stdin/interactive)"
                        % [p.exitcode for p in self._workers])

    def reset(self):
        # drain anything still in flight from the previous epoch
        while self._seq_yield < self._seq_next:
            seq, shm_name, pad, err = self._get_result()
            self._free.append(shm_name)
            self._seq_yield += 1
        self._plan_epoch()
        self._dispatch()

    def next(self):
        from .ndarray import ndarray as nd_mod

        if self._seq_yield >= self._seq_next \
                and self._dispatch_pos >= len(self._epoch_batches):
            raise StopIteration
        want = self._seq_yield
        while want not in self._pending:
            seq, shm_name, pad, err = self._get_result()
            self._pending[seq] = (shm_name, pad, err)
        shm_name, pad, err = self._pending.pop(want)
        self._seq_yield += 1
        if err is not None:
            self._free.append(shm_name)
            raise MXNetError("image pipeline worker failed: %s" % err)
        shm = self._shm_by_name[shm_name]
        c, h, w = self.data_shape
        data_np = np.ndarray((self.batch_size, c, h, w), np.float32,
                             buffer=shm.buf[:self._img_bytes]).copy()
        lab_np = np.ndarray((self.batch_size, self._label_width), np.float32,
                            buffer=shm.buf[self._img_bytes:]).copy()
        self._free.append(shm_name)
        self._dispatch()
        if self._label_width == 1:
            lab_np = lab_np[:, 0]
        return DataBatch(data=[nd_mod.array(data_np)],
                         label=[nd_mod.array(lab_np)],
                         pad=pad, provide_data=self.provide_data,
                         provide_label=self.provide_label)

    # -- teardown -----------------------------------------------------------
    def close(self):
        if self._closed:
            return
        self._closed = True
        for _ in self._workers:
            self._task_q.put(None)
        for p in self._workers:
            p.join(timeout=5)
            if p.is_alive():
                p.terminate()
        for s in self._shms:
            try:
                s.close()
                s.unlink()
            except FileNotFoundError:
                pass

    def __del__(self):  # best-effort
        try:
            self.close()
        except Exception:  # tpulint: disable=swallowed-error
            pass  # noqa: BLE001 - interpreter teardown
