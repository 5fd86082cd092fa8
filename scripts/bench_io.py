#!/usr/bin/env python
"""Host-side data-pipeline benchmark: native C++ loader vs pure Python.

The reference's input path is TF's C++ FIFOQueue/queue-runner machinery
(SURVEY.md §2b N7); this framework's replacement is ``native/dtfio.cpp``
(mmap + splitmix64 shuffle + double-buffered prefetch thread) bound via
ctypes, with a numpy fallback. This bench puts numbers on that choice —
entirely chip-independent (no jax import): it measures images/sec for
the IDX epoch path and MB/s for TFRecord span indexing (native
CRC32C-verified single pass vs the pure-python framing walk).

The IDX rows compare each design AS SHIPPED, which is not identical
per-epoch work: ``MnistData`` converts u8→f32 ONCE at construction
(4× resident memory, conversion untimed here) so its timed epoch is a
f32 gather; ``NativeIdxData`` normalizes per batch inside the timed
loop at ¼ the memory. The ``python_per_batch_normalize`` row is the
equal-work control (u8 gather + astype(f32)*scale per batch).

Artifact: ``BENCH_IO.json``. Tiny mode (DTF_IO_TINY=1) is CI-pinned in
tests/test_scripts.py so the wiring cannot rot between benchmark runs.
"""

import json
import os
import struct
import sys
import tempfile
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
ARTIFACT = os.path.join(ROOT, "BENCH_IO.json")

TINY = os.environ.get("DTF_IO_TINY") == "1"
N_IMAGES = 2_000 if TINY else 60_000          # MNIST-train-sized
BATCH = 256
N_RECORDS = 200 if TINY else 2_000            # TFRecord corpus
RECORD_BYTES = 1_024 if TINY else 10_240      # ~20 MB full-size (writing
# is pure-python masked-CRC-bound, so a bigger corpus measures the writer
# not the indexers; 20 MB is plenty for a stable MB/s)
EPOCHS = 1 if TINY else 3


def _timed_epochs(next_batch, n_batches):
    t0 = time.perf_counter()
    for _ in range(EPOCHS * n_batches):
        b = next_batch()
        assert b["image"].dtype == np.float32
    return time.perf_counter() - t0


def bench_idx(d):
    from dtf_tpu.data.mnist import MnistData, write_idx
    from dtf_tpu.data.native import NativeIdxData, native_available

    r = np.random.RandomState(0)
    images = r.randint(0, 256, (N_IMAGES, 28, 28)).astype(np.uint8)
    labels = r.randint(0, 10, (N_IMAGES,)).astype(np.uint8)
    ip = os.path.join(d, "train-images-idx3-ubyte")
    lp = os.path.join(d, "train-labels-idx1-ubyte")
    write_idx(ip, images)
    write_idx(lp, labels)
    n_batches = N_IMAGES // BATCH
    out = {"n_images": N_IMAGES, "batch": BATCH, "epochs": EPOCHS}

    py = MnistData(d, BATCH, split="train", seed=1)
    it = iter(py)
    # warm one epoch (page cache + any lazy init), then measure
    for _ in range(n_batches):
        next(it)
    t = _timed_epochs(lambda: next(it), n_batches)
    out["python_images_per_sec"] = round(EPOCHS * n_batches * BATCH / t, 1)
    out["python_converts_once_at_init"] = True  # see module docstring

    # equal-work python control: u8 rows gathered and normalized PER
    # BATCH, like the native loader (and at the same 1x resident memory)
    flat = images.reshape(N_IMAGES, -1)
    rs = np.random.RandomState(1)
    scale = np.float32(1.0 / 255.0)

    def per_batch():
        idx = rs.randint(0, N_IMAGES, BATCH)
        return {"image": flat[idx].astype(np.float32) * scale,
                "label": labels[idx].astype(np.int32)}

    for _ in range(n_batches):
        per_batch()
    t = _timed_epochs(per_batch, n_batches)
    out["python_per_batch_normalize_images_per_sec"] = round(
        EPOCHS * n_batches * BATCH / t, 1)

    if native_available():
        nat = NativeIdxData(ip, lp, BATCH, seed=1)
        for _ in range(n_batches):
            nat.next_batch()
        t = _timed_epochs(nat.next_batch, n_batches)
        out["native_images_per_sec"] = round(
            EPOCHS * n_batches * BATCH / t, 1)
        out["native_speedup_vs_shipped"] = round(
            out["native_images_per_sec"] / out["python_images_per_sec"], 2)
        out["native_speedup_vs_equal_work"] = round(
            out["native_images_per_sec"]
            / out["python_per_batch_normalize_images_per_sec"], 2)
        nat.close()
    else:
        out["native_images_per_sec"] = None
        out["native_error"] = "no C++ toolchain"
    return out


def bench_tfrecord(d):
    from dtf_tpu.data import tfrecord as tfr
    from dtf_tpu.data.native import native_available

    payload = os.urandom(RECORD_BYTES)
    path = os.path.join(d, "bench.tfrecord")
    tfr.write_tfrecords(path, (payload for _ in range(N_RECORDS)))
    size_mb = os.path.getsize(path) / 1e6
    out = {"n_records": N_RECORDS, "file_mb": round(size_mb, 1)}

    t0 = time.perf_counter()
    off, lens = tfr._python_spans(path)
    t_py = time.perf_counter() - t0
    assert len(off) == N_RECORDS
    out["python_index_mb_per_sec"] = round(size_mb / t_py, 1)

    # apples-to-apples with the native pass (which CRC-verifies every
    # payload): the python walk above checks only the 12-byte length CRCs
    with open(path, "rb") as f:
        raw = f.read()
    t0 = time.perf_counter()
    for o, n in zip(off[:50], lens[:50]):   # 50 records ≈ 0.5 MB: plenty
        o, n = int(o), int(n)
        (pcrc,) = struct.unpack_from("<I", raw, o + n)
        assert pcrc == tfr.masked_crc32c(raw[o:o + n])
    t_crc = (time.perf_counter() - t0) * (N_RECORDS / 50)
    out["python_index_verified_mb_per_sec"] = round(
        size_mb / (t_py + t_crc), 2)

    if native_available():
        t0 = time.perf_counter()
        off, _len = tfr.tfrecord_spans(path)  # native, payload-CRC-verified
        t_nat = time.perf_counter() - t0
        assert len(off) == N_RECORDS
        out["native_index_mb_per_sec"] = round(size_mb / t_nat, 1)
        out["native_verifies_payload_crc"] = True
        # the fair comparison: both sides verifying every payload CRC
        out["native_speedup_verified"] = round(
            out["native_index_mb_per_sec"]
            / out["python_index_verified_mb_per_sec"], 1)
    else:
        out["native_index_mb_per_sec"] = None
        out["native_error"] = "no C++ toolchain"
    return out


STREAM_BATCH = 32
STREAM_SEQ = 128 if not TINY else 32
STREAM_STEPS = 40 if TINY else 400


def bench_stream(d):
    """Mixture-stream assembly throughput (ISSUE 15, docs/DATA.md): two
    token corpora mixed 70/30, inline vs the bounded background producer
    — the number that says whether the data tier can outrun the step."""
    from dtf_tpu.data.stream import MixtureStream, TokenBinSource

    r = np.random.RandomState(0)
    for name in ("a", "b"):
        r.randint(0, 50_000, 200_000).astype(np.uint16).tofile(
            os.path.join(d, f"{name}.bin"))
    out = {"batch": STREAM_BATCH, "seq_len": STREAM_SEQ,
           "steps": STREAM_STEPS, "weights": {"a": 0.7, "b": 0.3}}

    def sources():
        return [TokenBinSource(os.path.join(d, f"{n}.bin"), STREAM_SEQ,
                               vocab_size=50_000, seed=1, salt=i, name=n)
                for i, n in enumerate(("a", "b"))]

    for label, depth in (("inline", 0), ("producer_depth2", 2)):
        stream = MixtureStream(sources(), {"a": 0.7, "b": 0.3},
                               STREAM_BATCH, seed=1, producer_depth=depth)
        it = iter(stream)
        next(it)                                 # warm (thread spin-up)
        t0 = time.perf_counter()
        for _ in range(STREAM_STEPS):
            b = next(it)
            assert b["input_ids"].dtype == np.int32
        dt = time.perf_counter() - t0
        stream.close()
        out[f"{label}_batches_per_sec"] = round(STREAM_STEPS / dt, 1)
        out[f"{label}_tokens_per_sec"] = round(
            STREAM_STEPS * STREAM_BATCH * STREAM_SEQ / dt, 1)
    stats = stream.stats()
    out["realized_frac_a"] = stats["per_source"]["a"]["realized_frac"]
    return out


def main():
    row = {"tiny": TINY, "host_cpus": os.cpu_count()}
    with tempfile.TemporaryDirectory() as d:
        row["idx_epoch"] = bench_idx(d)
    with tempfile.TemporaryDirectory() as d:
        row["tfrecord_index"] = bench_tfrecord(d)
    with tempfile.TemporaryDirectory() as d:
        row["mixture_stream"] = bench_stream(d)
    if not TINY:
        with open(ARTIFACT, "w") as f:
            json.dump(row, f, indent=1)
    print(json.dumps(row))
    return 0


if __name__ == "__main__":
    sys.exit(main())
