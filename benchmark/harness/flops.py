"""Operations and bytes CANNet needs, as a function of shapes.  BASELINE.md's
arithmetic (2 k^2 Cin Cout H W per convolution: 412.5 GFLOP forward at
576x768) kept per layer, forward and forward + backward.  Recomputed
operations (remat) do not count."""

from __future__ import annotations

FRONTEND = (64, 64, "M", 128, 128, "M", 256, 256, 256, "M", 512, 512, 512)
BACKEND = (512, 512, 512, 256, 128, 64)
SCALES = (1, 2, 3, 6)


def conv_layers(h: int, w: int):
    """[(name, flops, cin, cout, out_h, out_w, k)] of one image's forward."""
    out, cin, i = [], 3, 0
    for v in FRONTEND:
        if v == "M":
            h, w = h // 2, w // 2
            continue
        out.append((f"frontend{i}", 2 * 9 * cin * v * h * w, cin, v, h, w, 3))
        cin, i = v, i + 1
    for s in SCALES:
        # the pooled 1x1 is negligible; the contrast 1x1 runs at full feature size
        out.append((f"context_s{s}.ave", 2 * 512 * 512 * s * s, 512, 512, s, s, 1))
        out.append((f"context_s{s}.weight", 2 * 512 * 512 * h * w, 512, 512, h, w, 1))
    cin = 1024
    for i, v in enumerate(BACKEND):
        out.append((f"backend{i}", 2 * 9 * cin * v * h * w, cin, v, h, w, 3))
        cin = v
    out.append(("output", 2 * cin * h * w, cin, 1, h, w, 1))
    return out


def forward_flops(h: int, w: int) -> float:
    return float(sum(layer[1] for layer in conv_layers(h, w)))


def train_flops(h: int, w: int) -> float:
    """Forward + input-gradient + weight-gradient convolutions: 3x forward
    (the first layer needs no input gradient; counted all the same, 0.2%)."""
    return 3.0 * forward_flops(h, w)


def min_bytes(h: int, w: int, batch: int, *, train: bool, act_bytes: int = 2,
              image_bytes: int = 4) -> float:
    """The least HBM traffic: the input read once, every layer's output
    written once and read once (twice more in training), the weights read."""
    act = sum(cout * oh * ow for _, _, _, cout, oh, ow, _ in conv_layers(h, w))
    weights = sum(k * k * cin * cout for _, _, cin, cout, _, _, k in conv_layers(h, w))
    per_image = image_bytes * 3 * h * w + act_bytes * act * (4 if train else 2)
    return float(batch * per_image + weights * (12 if train else act_bytes))


def least_seconds(h: int, w: int, batch: int, peaks, *, train: bool,
                  image_bytes: int = 4):
    """(seconds, bound): the roofline's floor for one launch of ``batch``
    images padded to h x w, and which ceiling sets it."""
    f = (train_flops if train else forward_flops)(h, w) * batch
    b = min_bytes(h, w, batch, train=train, image_bytes=image_bytes)
    tf, tb = f / peaks.flops, b / peaks.hbm_bytes_s
    return (tf, "compute") if tf >= tb else (tb, "memory")
