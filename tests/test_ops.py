"""Parity of core ops against torch (the reference's numerical ground truth).

The reference model leans on torch.nn.functional.adaptive_avg_pool2d and
F.interpolate(align_corners=True) (model/CANNet.py:42-81); wrong bin/corner
math silently costs MAE, so these are bit-level checks (SURVEY.md §7 hard
part b).
"""

import numpy as np
import pytest
import torch
import torch.nn.functional as F

import jax.numpy as jnp

from can_tpu.ops import (
    adaptive_avg_pool2d,
    conv1x1,
    conv2d,
    max_pool2d,
    resize_bilinear_align_corners,
)

RNG = np.random.default_rng(0)


def _nhwc(n, h, w, c):
    return RNG.standard_normal((n, h, w, c)).astype(np.float32)


@pytest.mark.parametrize("hw", [(7, 9), (8, 8), (1, 5), (48, 64), (13, 3)])
@pytest.mark.parametrize("s", [1, 2, 3, 6])
def test_adaptive_avg_pool_matches_torch(hw, s):
    h, w = hw
    if s > h or s > w:
        pytest.skip("output larger than input not used by CANNet")
    x = _nhwc(2, h, w, 5)
    got = np.asarray(adaptive_avg_pool2d(jnp.asarray(x), s))
    want = (
        F.adaptive_avg_pool2d(torch.from_numpy(x).permute(0, 3, 1, 2), (s, s))
        .permute(0, 2, 3, 1)
        .numpy()
    )
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("s", [1, 2, 3, 6])
@pytest.mark.parametrize("out_hw", [(5, 7), (48, 64), (1, 1), (2, 2), (33, 17)])
def test_bilinear_align_corners_matches_torch(s, out_hw):
    x = _nhwc(2, s, s, 4)
    got = np.asarray(resize_bilinear_align_corners(jnp.asarray(x), out_hw))
    want = (
        F.interpolate(
            torch.from_numpy(x).permute(0, 3, 1, 2),
            size=out_hw,
            mode="bilinear",
            align_corners=True,
        )
        .permute(0, 2, 3, 1)
        .numpy()
    )
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("in_hw", [(3, 3), (6, 6), (4, 7)])
def test_bilinear_align_corners_downscale_and_general(in_hw):
    x = _nhwc(1, *in_hw, 3)
    out_hw = (2, 3)
    got = np.asarray(resize_bilinear_align_corners(jnp.asarray(x), out_hw))
    want = (
        F.interpolate(
            torch.from_numpy(x).permute(0, 3, 1, 2),
            size=out_hw,
            mode="bilinear",
            align_corners=True,
        )
        .permute(0, 2, 3, 1)
        .numpy()
    )
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dilation", [1, 2])
def test_conv2d_matches_torch(dilation):
    x = _nhwc(2, 10, 12, 6)
    w = RNG.standard_normal((3, 3, 6, 8)).astype(np.float32) * 0.1
    b = RNG.standard_normal((8,)).astype(np.float32)
    got = np.asarray(
        conv2d(
            jnp.asarray(x),
            jnp.asarray(w),
            jnp.asarray(b),
            dilation=dilation,
            precision="highest",
        )
    )
    want = (
        F.conv2d(
            torch.from_numpy(x).permute(0, 3, 1, 2),
            torch.from_numpy(w).permute(3, 2, 0, 1),
            torch.from_numpy(b),
            padding=dilation,
            dilation=dilation,
        )
        .permute(0, 2, 3, 1)
        .numpy()
    )
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_conv1x1_matches_torch():
    x = _nhwc(2, 5, 5, 6)
    w = RNG.standard_normal((6, 4)).astype(np.float32)
    got = np.asarray(conv1x1(jnp.asarray(x), jnp.asarray(w), precision="highest"))
    want = (
        F.conv2d(
            torch.from_numpy(x).permute(0, 3, 1, 2),
            torch.from_numpy(w).T.reshape(4, 6, 1, 1),
        )
        .permute(0, 2, 3, 1)
        .numpy()
    )
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("hw", [(8, 8), (9, 9), (10, 7)])
def test_max_pool_matches_torch(hw):
    x = _nhwc(2, *hw, 3)
    got = np.asarray(max_pool2d(jnp.asarray(x)))
    want = (
        F.max_pool2d(torch.from_numpy(x).permute(0, 3, 1, 2), 2, 2)
        .permute(0, 2, 3, 1)
        .numpy()
    )
    np.testing.assert_allclose(got, want)
