"""Synthetic crowd data in the reference's on-disk layout (``images/*.jpg``
+ ``ground_truth/*.npy`` full-resolution float32 density maps), written by a
pool of processes before JAX is touched.  Imports no JAX and nothing of the
program.  Every seed gets the same multiset of sizes, in another order."""

from __future__ import annotations

import multiprocessing
import os
import shutil

import numpy as np


def sizes_for(traffic: dict, seed: int):
    """The mix's sizes: a fixed multiset (drawn once from ``size_seed``),
    shuffled by ``seed``."""
    n = int(traffic["n_images"])
    rng = np.random.default_rng(int(traffic["size_seed"]))
    snap = int(traffic.get("snap", 8))
    dom = tuple(traffic["dominant"])
    n_dom = round(n * float(traffic["dominant_share"]))
    lo, hi = int(traffic.get("lo", dom[0])), int(traffic.get("hi", dom[0]))
    sizes = [dom] * n_dom
    for _ in range(n - n_dom):
        h, w = int(rng.integers(lo, hi + 1)), int(rng.integers(lo, hi + 1))
        sizes.append(((h // snap) * snap, (w // snap) * snap))
    order = np.random.default_rng(seed).permutation(n)
    return [sizes[i] for i in order]


def _write_one(args):
    import cv2
    from PIL import Image

    root, i, h, w, seed = args
    rng = np.random.default_rng((seed, i))
    # photo-like content: low-frequency colour noise + fine grain, so that the
    # JPEG is the size (and decodes at the cost) of a photograph, not of noise
    cv2.setNumThreads(1)
    base = rng.integers(0, 256, (h // 16 + 1, w // 16 + 1, 3), dtype=np.uint8)
    img = cv2.resize(base, (w, h), interpolation=cv2.INTER_LINEAR).astype(np.int16)
    img += rng.integers(-10, 11, (h, w, 3), dtype=np.int16)
    n_people = int(rng.integers(50, 401))
    cols = rng.integers(0, w, n_people)
    rows = rng.integers(0, h, n_people)
    dots = np.zeros((h, w), np.float32)
    np.add.at(dots, (rows, cols), 1.0)
    for r, c in zip(rows, cols):
        img[max(0, r - 3):r + 4, max(0, c - 3):c + 4] = 255
    dmap = cv2.GaussianBlur(dots, (0, 0), 4.0, borderType=cv2.BORDER_CONSTANT)
    Image.fromarray(np.clip(img, 0, 255).astype(np.uint8)).save(
        os.path.join(root, "images", f"IMG_{i:04d}.jpg"), quality=95)
    np.save(os.path.join(root, "ground_truth", f"IMG_{i:04d}.npy"), dmap)
    return i


class DatasetWriter:
    """Replaces ``root`` with ``len(sizes)`` image / density pairs, in a pool
    of spawned processes that runs while the parent brings the chip up.
    ``wait()`` returns (image root, density root)."""

    def __init__(self, root: str, sizes, seed: int, workers: int | None = None):
        shutil.rmtree(root, ignore_errors=True)
        os.makedirs(os.path.join(root, "images"))
        os.makedirs(os.path.join(root, "ground_truth"))
        self.root = root
        jobs = [(root, i, h, w, seed) for i, (h, w) in enumerate(sizes)]
        workers = workers or min(len(jobs), max(1, (os.cpu_count() or 2) - 2))
        self._n = len(jobs)
        self._pool = multiprocessing.get_context("spawn").Pool(workers)
        self._result = self._pool.map_async(
            _write_one, jobs, chunksize=max(1, len(jobs) // (4 * workers)))

    def wait(self):
        try:
            done = self._result.get(timeout=600)
        finally:
            self._pool.terminate()
            self._pool.join()
        if len(done) != self._n:
            raise RuntimeError("dataset writer lost images")
        return os.path.join(self.root, "images"), os.path.join(self.root, "ground_truth")
