"""Multiprocess image pipeline (mxnet_tpu/image_pipeline.py) — functional
coverage for the iter_image_recordio_2.cc counterpart: full-epoch label
accounting across worker processes, determinism plumbing, padding, augment
correctness, and the io.ImageRecordIter wiring."""
import collections
import os

import numpy as np
import pytest

from mxnet_tpu import io as mxio
from mxnet_tpu import recordio

N_REC = 48


@pytest.fixture(scope="module")
def rec_file(tmp_path_factory):
    d = tmp_path_factory.mktemp("recs")
    rec = str(d / "toy.rec")
    idx = str(d / "toy.idx")
    w = recordio.MXIndexedRecordIO(idx, rec, "w")
    rs = np.random.RandomState(0)
    for i in range(N_REC):
        img = (rs.rand(40, 56, 3) * 255).astype(np.uint8)
        hdr = recordio.IRHeader(0, float(i), i, 0)
        w.write_idx(i, recordio.pack_img(hdr, img, quality=92))
    w.close()
    return rec


def test_mp_pipeline_epochs_cover_dataset(rec_file):
    from mxnet_tpu.image_pipeline import MPImageRecordIter

    it = MPImageRecordIter(rec_file, data_shape=(3, 32, 32), batch_size=16,
                           shuffle=True, rand_crop=True, rand_mirror=True,
                           preprocess_threads=2, prefetch_buffer=3)
    try:
        seen = []
        for epoch in range(2):
            if epoch:
                it.reset()
            for batch in it:
                assert batch.data[0].shape == (16, 3, 32, 32)
                assert batch.label[0].shape == (16,)
                keep = 16 - batch.pad
                seen.extend(batch.label[0].asnumpy()[:keep].tolist())
        # every record exactly once per epoch, despite out-of-order workers
        assert collections.Counter(seen) == collections.Counter(
            [float(i) for i in range(N_REC)] * 2)
        # pixels are real decoded image content
        m = batch.data[0].asnumpy().mean()
        assert 100 < m < 155, m
    finally:
        it.close()


def test_mp_pipeline_padding(rec_file):
    from mxnet_tpu.image_pipeline import MPImageRecordIter

    it = MPImageRecordIter(rec_file, data_shape=(3, 32, 32), batch_size=20,
                           preprocess_threads=2)
    try:
        pads = [b.pad for b in it]
        # 48 records, bs=20 -> 20, 20, 8+12pad
        assert pads == [0, 0, 12]
    finally:
        it.close()


def test_io_wiring_selects_mp(rec_file, monkeypatch):
    from mxnet_tpu.image_pipeline import MPImageRecordIter

    # the factory picks the multi-process pipeline only under a
    # re-importable ``__main__`` (one with a ``__file__``). An xdist worker's
    # ``__main__`` is execnet's bootstrap, which has none — so this test
    # passed alone and failed under ``-n 6``. Give it one: a spawned worker
    # then runs that file as ``__mp_main__`` — so a trivially import-safe
    # one, the stdlib's ``keyword.py``.
    import keyword
    import sys

    monkeypatch.setattr(sys.modules["__main__"], "__file__",
                        keyword.__file__, raising=False)
    it = mxio.ImageRecordIter(path_imgrec=rec_file, data_shape=(3, 32, 32),
                              batch_size=8, preprocess_threads=2,
                              prefetch_buffer=2)
    try:
        assert isinstance(it, MPImageRecordIter)
        batch = it.next()
        assert batch.data[0].shape == (8, 3, 32, 32)
    finally:
        it.close()
    # single-process fallback preserved
    it2 = mxio.ImageRecordIter(path_imgrec=rec_file, data_shape=(3, 32, 32),
                               batch_size=8, preprocess_threads=0,
                               prefetch_buffer=0)
    assert not isinstance(it2, MPImageRecordIter)
    assert it2.next().data[0].shape == (8, 3, 32, 32)


def test_mp_matches_single_process_content(rec_file):
    """Center-crop, no augmentation: the MP pipeline and the single-process
    decoder must produce identical batches (same records, same math)."""
    from mxnet_tpu.image_pipeline import MPImageRecordIter

    mp_it = MPImageRecordIter(rec_file, data_shape=(3, 32, 32), batch_size=8,
                              preprocess_threads=2)
    sp_it = mxio.ImageRecordIter(path_imgrec=rec_file,
                                 data_shape=(3, 32, 32), batch_size=8,
                                 preprocess_threads=0, prefetch_buffer=0,
                                 force_single_process=True)
    try:
        b_mp = mp_it.next()
        b_sp = sp_it.next()
        np.testing.assert_array_equal(b_mp.label[0].asnumpy(),
                                      b_sp.label[0].asnumpy())
        # decoders differ in resize kernels; exact equality only on labels,
        # pixel content must agree closely (same crop of the same JPEG)
        d_mp = b_mp.data[0].asnumpy()
        d_sp = b_sp.data[0].asnumpy()
        assert d_mp.shape == d_sp.shape
        assert abs(d_mp.mean() - d_sp.mean()) < 10.0
    finally:
        mp_it.close()
