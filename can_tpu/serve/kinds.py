"""Request kinds: what the batcher has to ask of a request's payload.

The queue, the batcher, the scheduler, the spans and the staging discipline
do not know what a request carries.  A kind tells the batcher two things:

* ``group_key(request)`` -> ``(H, W, dtype)``: requests with one key share
  a launch (and a compiled program);
* ``new_staging(key, slots)`` / ``assemble(key, requests, size, out)``: the
  launch's padded batch, built fresh (``out=None``) or into a staging
  buffer the batcher keeps per key and hands back (serve/batcher.py).

And it tells the scheduling core (``sched/core.py``) in what unit a launch
of it costs (``cost_unit``): the core prices ``slots x pixels + a launch
cost`` and refuses a kind whose launches do not cost that; a service of
such a kind runs one launch size under the plain timer.

``ImageKind`` is CANNet's: the bucket is ``data.batching.snap_to_bucket``
of the image's (H, W), the assembly ``data.batching.pad_batch`` with a zero
density target per item: the two calls the batcher made itself before the
seam, byte for byte.  ``TokenKind`` is the language model's: a prompt
buckets on a length ladder as ``(1, L_bucket, "i32")`` and pads in one
dimension, with its true length and a slot mask beside it.
"""

from __future__ import annotations

from typing import List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from can_tpu.data.batching import StagingBatch, pad_batch, snap_to_bucket

IMAGE, TOKENS = "image", "tokens"

GroupKey = Tuple[int, int, str]


class ImageKind:
    """HWC images on the (H, W) bucket ladder of ``snap_to_bucket``."""

    name = IMAGE
    cost_unit = "px"    # a launch costs its padded slots x the bucket's area

    def __init__(self, *, bucket_ladder=None, pad_multiple=None,
                 min_bucket_h: Optional[int] = None, ds: int = 8):
        if isinstance(pad_multiple, int):
            pad_multiple = (pad_multiple, pad_multiple)
        self.bucket_ladder = bucket_ladder
        self.pad_multiple = pad_multiple
        self.min_bucket_h = min_bucket_h
        self.ds = int(ds)

    def bucket_of(self, hw: Tuple[int, int]) -> Tuple[int, int]:
        return snap_to_bucket(hw, ladder=self.bucket_ladder,
                              pad_multiple=self.pad_multiple,
                              min_bucket_h=self.min_bucket_h)

    def group_key(self, request) -> GroupKey:
        # dtype is part of the jit signature, so u8 and f32 requests must
        # not share a batch buffer (pad_batch keeps the items' dtype)
        bh, bw = self.bucket_of(request.shape)
        return (bh, bw, str(request.image.dtype))

    def new_staging(self, key: GroupKey, slots: int) -> StagingBatch:
        return StagingBatch(key[:2], slots, self.ds, np.dtype(key[2]))

    def assemble(self, key: GroupKey, requests: Sequence, size: int, out):
        # zero per-item density targets: serve batches reuse the offline
        # Batch layout (image/dmap/pixel_mask/sample_mask) so the engine
        # can run the exact eval-step math; dmap is unused by prediction
        items = [(r.image,
                  np.zeros((r.shape[0] // self.ds, r.shape[1] // self.ds, 1),
                           np.float32))
                 for r in requests]
        return pad_batch(items, key[:2], size, [True] * len(items), self.ds,
                         out=out)

    @staticmethod
    def payload(batch) -> np.ndarray:
        return batch.image


class TokenBatch(NamedTuple):
    """One launch of prompts: ``tokens`` (slots, L) int32 right-padded with
    zeros, ``lengths`` (slots,) int32 (1 in a dead slot, so that "the last
    position" exists there too), ``sample_mask`` (slots,) float32: 1 where
    the slot holds a request (the name ``data.batching.Batch`` gives it)."""

    tokens: np.ndarray
    lengths: np.ndarray
    sample_mask: np.ndarray


class TokenStaging:
    """A ``TokenBatch``'s arrays at the top launch size, kept across
    launches (``data.batching.StagingBatch``'s discipline): ``assemble``
    writes the prompts in and zeroes only what a slot's previous prompt
    covered and the new one does not."""

    def __init__(self, bucket: int, slots: int):
        self.tokens = np.zeros((slots, bucket), np.int32)
        self.lengths = np.ones((slots,), np.int32)
        self.sample_mask = np.zeros((slots,), np.float32)
        self.extent: List[int] = [0] * slots   # tokens written per slot

    @property
    def nbytes(self) -> int:
        return (self.tokens.nbytes + self.lengths.nbytes
                + self.sample_mask.nbytes)


class TokenKind:
    """Prompts of token ids on a ladder of lengths."""

    name = TOKENS
    # no unit the core can price yet: a decode step reads every weight
    # whatever the slots, so a launch of 5 costs about what one of 64 does
    # (token-priced constants are ROADMAP's, Reach B2)
    cost_unit = None

    def __init__(self, length_ladder: Sequence[int]):
        self.ladder = tuple(sorted(int(n) for n in length_ladder))
        if not self.ladder or self.ladder[0] < 1:
            raise ValueError(f"length ladder {length_ladder!r} must hold "
                             f"positive lengths")

    def bucket_of(self, n: int) -> int:
        """The smallest rung that holds ``n`` tokens; ValueError past the
        ladder (the service refuses such a prompt at the door)."""
        for rung in self.ladder:
            if n <= rung:
                return rung
        raise ValueError(f"prompt of {n} tokens exceeds the largest bucket "
                         f"{self.ladder[-1]}")

    def group_key(self, request) -> GroupKey:
        return (1, self.bucket_of(request.shape[1]), "i32")

    def new_staging(self, key: GroupKey, slots: int) -> TokenStaging:
        return TokenStaging(key[1], slots)

    def assemble(self, key: GroupKey, requests: Sequence, size: int,
                 out: Optional[TokenStaging]) -> TokenBatch:
        if len(requests) > size:
            raise ValueError(f"{len(requests)} prompts for {size} slots")
        st = out if out is not None else TokenStaging(key[1], size)
        for slot, r in enumerate(requests):
            n = r.shape[1]
            st.tokens[slot, :n] = r.tokens
            if st.extent[slot] > n:
                st.tokens[slot, n:st.extent[slot]] = 0
            st.extent[slot] = n
            st.lengths[slot] = n
        for slot in range(len(requests), size):
            if st.extent[slot]:
                st.tokens[slot, :st.extent[slot]] = 0
                st.extent[slot] = 0
            st.lengths[slot] = 1
        st.sample_mask[:len(requests)] = 1.0
        st.sample_mask[len(requests):size] = 0.0
        return TokenBatch(st.tokens[:size], st.lengths[:size],
                          st.sample_mask[:size])

    @staticmethod
    def payload(batch: TokenBatch) -> np.ndarray:
        return batch.tokens
