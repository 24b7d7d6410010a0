"""The model's operations (forward + both gradients; harness/flops.py) at peak, over the device time of the ops that touch a convolution kernel.  Compute-bound at these shapes."""


def read(ctx):
    return ctx["trace"].get("contraction_roofline_pct")
