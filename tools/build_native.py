"""Build the native density-stamping library (can_tpu/native/).

Usage: python tools/build_native.py
Produces can_tpu/native/libdensity_stamp.so from density_stamp.cpp (g++).
The library is git-ignored — a fresh checkout has none — and
can_tpu/data/density.py uses it when present, numpy when not (identical
output); it prints ``[density] stamping path: native|numpy`` once at load,
so a run always says which one it took.
"""

from __future__ import annotations

import os
import subprocess
import sys


def build(verbose: bool = True) -> str:
    native = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                          "can_tpu", "native")
    src = os.path.join(native, "density_stamp.cpp")
    out = os.path.join(native, "libdensity_stamp.so")
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-o", out, src]
    if verbose:
        print(" ".join(cmd))
    subprocess.run(cmd, check=True)
    return out


if __name__ == "__main__":
    path = build()
    print(f"built {path}")
    sys.exit(0)
