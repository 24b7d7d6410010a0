"""Geometry-adaptive Gaussian ground-truth density maps (offline generation).

Semantics follow the reference generator
(reference: data_preparation/k_nearest_gaussian_kernel.py:14-54):

* per head annotation ``(col, row)``, place a unit delta and blur with an
  isotropic Gaussian of ``sigma = 0.1 * (d1 + d2 + d3)`` where ``d*`` are
  distances to the 3 nearest other heads (KDTree, k=4 including self);
* points outside the image are skipped;
* ``scipy.ndimage.gaussian_filter(mode='constant')`` semantics — mass falling
  outside the image border is lost (no renormalisation).

Two deliberate departures from the reference:

1. **The 1-point case is fixed.** The reference references an undefined
   variable ``gt`` (k_nearest_gaussian_kernel.py:51) and crashes; we use
   ``sigma = mean(image_shape) / 4`` — the value that line was trying to
   compute (the classic MCNN/CSRNet fallback).
2. **Windowed stamping instead of per-point full-image filtering.** The
   reference runs a full-image ``gaussian_filter`` per person —
   O(people x H x W).  Convolving a delta is just the (separable, truncated)
   kernel itself, so we stamp the outer product of two 1-D Gaussian windows
   clipped to the image — identical output (scipy truncates at
   ``truncate * sigma`` anyway), ~1000x faster on dense images.
"""

from __future__ import annotations

import glob
import os
from typing import Sequence

import numpy as np


def _gaussian_kernel_1d(sigma: float, radius: int) -> np.ndarray:
    """Matches scipy.ndimage's Gaussian: sampled, normalised to sum 1."""
    x = np.arange(-radius, radius + 1, dtype=np.float64)
    phi = np.exp(-0.5 * (x / sigma) ** 2)
    return (phi / phi.sum()).astype(np.float64)


def _stamp_gaussian(density: np.ndarray, row: int, col: int, sigma: float,
                    truncate: float = 4.0) -> None:
    """Add a unit-mass truncated Gaussian at (row, col), clipped to bounds.

    Exactly equals ``scipy.ndimage.gaussian_filter(delta, sigma,
    mode='constant', truncate=truncate)`` because filtering a delta yields the
    separable truncated kernel centred on it; 'constant' mode means clipped
    mass is simply lost.
    """
    h, w = density.shape
    radius = int(truncate * float(sigma) + 0.5)
    if radius < 1:
        density[row, col] += 1.0
        return
    k = _gaussian_kernel_1d(sigma, radius)
    r0, r1 = max(0, row - radius), min(h, row + radius + 1)
    c0, c1 = max(0, col - radius), min(w, col + radius + 1)
    kr = k[r0 - (row - radius): r1 - (row - radius)]
    kc = k[c0 - (col - radius): c1 - (col - radius)]
    density[r0:r1, c0:c1] += np.outer(kr, kc)


_native_lib = None
_native_checked = False


def _load_native():
    """ctypes handle to the C++ stamping loop, or None — output is
    identical either way (tested), numpy is just slower on dense
    annotations.  The library is a BUILD PRODUCT (git-ignored; a checkout
    has none until ``python tools/build_native.py`` runs there), so which
    path a process took is printed once, at load: two copies of one
    commit must not differ silently."""
    global _native_lib, _native_checked
    if _native_checked:
        return _native_lib
    _native_checked = True
    import ctypes
    import os

    so = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                      "native", "libdensity_stamp.so")
    why = "not built — python tools/build_native.py"
    if os.path.exists(so):
        try:
            lib = ctypes.CDLL(so)
            d = ctypes.POINTER(ctypes.c_double)
            lib.stamp_gaussians.argtypes = [d, ctypes.c_int64, ctypes.c_int64,
                                            d, d, d, ctypes.c_int64,
                                            ctypes.c_double]
            lib.stamp_gaussians.restype = None
            _native_lib = lib
        except OSError as e:
            why = f"{so} failed to load: {e}"
    print(f"[density] stamping path: native ({so})" if _native_lib is not None
          else f"[density] stamping path: numpy ({why})", flush=True)
    return _native_lib


def gaussian_density_map(points: np.ndarray, shape: Sequence[int], *,
                         k: int = 3, sigma_scale: float = 0.1,
                         truncate: float = 4.0,
                         use_native: bool = True) -> np.ndarray:
    """Geometry-adaptive Gaussian density map.

    points: (P, 2) array of ``(col, row)`` head positions (the ShanghaiTech
      .mat convention, reference k_nearest_gaussian_kernel.py:17,79).
    shape: (H, W) of the image.
    Returns float32 (H, W) density map with sum ~= number of in-bounds heads
    (minus mass clipped at borders).

    The stamping loop runs in the C++ library (can_tpu/native/) when built;
    ``use_native=False`` or a missing .so falls back to numpy — identical
    output either way (tested).
    """
    h, w = int(shape[0]), int(shape[1])
    density = np.zeros((h, w), dtype=np.float64)
    points = np.asarray(points, dtype=np.float64).reshape(-1, 2)
    n = len(points)
    if n == 0:
        return density.astype(np.float32)

    if n > 1:
        # imported here, as ``scipy.io`` below: ``scipy.spatial`` takes a
        # second to load, and every process that serves (``serve/kinds.py``
        # imports this package) paid it at start-up for maps it never stamps
        from scipy.spatial import cKDTree

        tree = cKDTree(points, leafsize=2048)
        # k+1 neighbours: the nearest is the point itself at distance 0.
        distances, _ = tree.query(points, k=min(k + 1, n))
        distances = np.atleast_2d(distances)

    rows, cols, sigmas = [], [], []
    for i, (c, r) in enumerate(points):
        row, col = int(r), int(c)
        if not (0 <= row < h and 0 <= col < w):
            continue  # out-of-bounds annotations skipped (reference :44-46)
        if n > 1:
            # sum of available NN distances, scaled (reference :48-49).
            sigma = float(distances[i][1:].sum()) * sigma_scale
        else:
            sigma = (h + w) / 2.0 / 4.0  # fixed 1-point fallback (bug fix)
        if sigma <= 0:
            sigma = 1.0  # coincident points would give sigma 0
        rows.append(row)
        cols.append(col)
        sigmas.append(sigma)

    lib = _load_native() if use_native else None
    if lib is not None and rows:
        import ctypes

        ra = np.asarray(rows, np.float64)
        ca = np.asarray(cols, np.float64)
        sa = np.asarray(sigmas, np.float64)
        dptr = ctypes.POINTER(ctypes.c_double)
        lib.stamp_gaussians(
            density.ctypes.data_as(dptr), h, w,
            ra.ctypes.data_as(dptr), ca.ctypes.data_as(dptr),
            sa.ctypes.data_as(dptr), len(ra), float(truncate))
    else:
        for row, col, sigma in zip(rows, cols, sigmas):
            _stamp_gaussian(density, row, col, sigma, truncate)
    return density.astype(np.float32)


def _load_mat_points(mat_path: str) -> np.ndarray:
    """Extract (col,row) head annotations from a ShanghaiTech-style .mat
    (layout per reference k_nearest_gaussian_kernel.py:79), tolerating the
    nesting variants different MATLAB exporters produce."""
    import scipy.io as sio

    mat = sio.loadmat(mat_path)
    try:
        pts = np.asarray(mat["image_info"][0, 0][0, 0][0], dtype=np.float64)
        if pts.ndim == 2 and pts.shape[1] == 2:
            return pts
    except (KeyError, IndexError, TypeError, ValueError):
        pass
    # fallback: an (N, 2) numeric array under a recognised annotation key /
    # struct field only — an unconstrained search could silently pick up a
    # [W, H] size pair or bbox corners as "heads"
    for key in _ANNOTATION_KEYS:
        if key in mat:
            found = _find_points(mat[key])
            if found is not None:
                return found
    found = _find_points(mat.get("image_info"))
    if found is None:
        raise ValueError(
            f"no (N, 2) annotation array found in {mat_path} under keys "
            f"{sorted(k for k in mat if not k.startswith('__'))}")
    return found


_ANNOTATION_KEYS = ("annPoints", "points", "location", "locations")


def _find_points(obj):
    if isinstance(obj, np.ndarray):
        if obj.ndim >= 2 and obj.shape[-1] == 2 and obj.size > 0 and \
                np.issubdtype(obj.dtype, np.number):
            return np.asarray(obj, dtype=np.float64).reshape(-1, 2)
        if obj.dtype == object or obj.dtype.names:
            items = obj.flat
            for item in items:
                if obj.dtype.names:
                    for name in obj.dtype.names:
                        got = _find_points(item[name])
                        if got is not None:
                            return got
                else:
                    got = _find_points(item)
                    if got is not None:
                        return got
    elif isinstance(obj, (list, tuple)):
        for item in obj:
            got = _find_points(item)
            if got is not None:
                return got
    return None


def generate_density_maps(image_dirs: Sequence[str], *, k: int = 3,
                          sigma_scale: float = 0.1,
                          verbose: bool = True) -> int:
    """Offline driver: for every ``*.jpg`` under each dir, read its paired
    ``GT_IMG_*.mat`` annotation and write ``*.npy`` density map next to it
    (path scheme per reference k_nearest_gaussian_kernel.py:76-83).

    Returns the number of maps written.
    """
    from PIL import Image

    written = 0
    for path in image_dirs:
        for img_path in sorted(glob.glob(os.path.join(path, "*.jpg"))):
            # Component-wise path construction: blanket str.replace over
            # the ABSOLUTE path rewrote any parent directory containing
            # 'images'/'IMG_'/'.jpg' as a substring, silently reading or
            # writing in unrelated trees (code-review r5).  Only the
            # leaf directory named 'images' and the file's own basename
            # are transformed (reference k_nearest_gaussian_kernel.py:
            # 76-83 scheme).
            img_dir, fname = os.path.split(img_path)
            parent, leaf = os.path.split(img_dir)
            gt_dir = (os.path.join(parent, "ground_truth")
                      if leaf == "images" else img_dir)
            stem = os.path.splitext(fname)[0]
            mat_path = os.path.join(
                gt_dir, ("GT_" + stem if stem.startswith("IMG_") else stem)
                + ".mat")
            with Image.open(img_path) as im:
                w, h = im.size
            points = _load_mat_points(mat_path)
            dmap = gaussian_density_map(points, (h, w), k=k,
                                        sigma_scale=sigma_scale)
            out = os.path.join(gt_dir, stem + ".npy")
            np.save(out, dmap)
            written += 1
            if verbose:
                print(f"{img_path}: {len(points)} heads -> {out} "
                      f"(sum={dmap.sum():.2f})")
    return written
