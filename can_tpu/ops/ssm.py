"""The selective state-space recurrence of a Mamba-2 mixer (Dao & Gu,
"Transformers are SSMs", arXiv:2405.21060) and the causal depthwise
convolution in front of it: ONE layer in two forms, as latent attention has
two.  The convolution is also, between two gates, the whole mixer of an
LFM2 ``conv`` layer (``gated_conv_causal`` / ``gated_conv_step``).

Per head, with a state ``S`` of (P, N) numbers that starts from zero:

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T;    y_t = S_t C_t + D x_t

``x`` (B, L, H, P) in heads of P channels, ``dt`` (B, L, H) the step sizes
(positive, float32), ``A`` (H,) negative, ``B``, ``C`` (B, L, G, N) shared by
the H / G heads of a group, ``D`` (H,).

* ``ssd_chunked`` (prefill): the paper's chunked form.  Inside a chunk of
  ``chunk`` positions two matrix products against the chunk's decay matrix;
  between chunks a scan over the carried states, L / chunk steps and not L.
* ``ssd_step`` (decode): the state read, decayed, updated, written.

The state is float32 in both forms and is never rounded on the way: it is
an accumulator over every position seen.  The products inside a chunk take
their inputs in ``x``'s dtype and accumulate in float32, as attention's do.

Prompts are right-padded.  Attention masks the padding; a recurrence that
ran over it would hand decode a wrong state.  Here padding advances nothing:
at a position at or past a sequence's length ``dt`` is 0 (decay 1, input
0), so the state after the last chunk is the state at the sequence's own
length, and ``conv_tail`` gathers the convolution's inputs at ``lengths``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


# -- the convolution ----------------------------------------------------
def conv1d_causal(x, w, b):
    """Depthwise causal convolution over whole prompts: ``x`` (B, L, C),
    ``w`` (C, K), ``b`` (C,) or None -> ``y[t] = b + sum_j w[:, j] x[t - K +
    1 + j]`` (zeros before position 0), in ``x``'s dtype."""
    k = w.shape[-1]
    l = x.shape[1]
    xp = jnp.pad(x.astype(jnp.float32), ((0, 0), (k - 1, 0), (0, 0)))
    w32 = w.astype(jnp.float32)
    bias = None if b is None else b.astype(jnp.float32)
    y = sum(xp[:, j:j + l] * w32[:, j] for j in range(k))
    return (y if bias is None else bias + y).astype(x.dtype)


def conv_tail(x, lengths, width: int):
    """The convolution's last ``width - 1`` inputs of each sequence AT ITS
    OWN LENGTH: ``x`` (B, L, C), ``lengths`` (B,) -> (B, C, width - 1), column
    ``j`` the input at position ``length - (width - 1) + j`` (zero where that
    is before the prompt's start)."""
    at = lengths[:, None] - (width - 1) + jnp.arange(width - 1)[None, :]
    taken = jnp.take_along_axis(x, jnp.clip(at, 0, x.shape[1] - 1)[..., None],
                                axis=1)                       # (B, K-1, C)
    return jnp.where((at >= 0)[..., None], taken, 0).transpose(0, 2, 1)


def conv1d_step(tail, x, w, b):
    """One position: ``tail`` (B, C, K - 1) the inputs before it, ``x``
    (B, C), ``b`` (C,) or None -> (y (B, C) in ``x``'s dtype, the tail moved
    on by one)."""
    window = jnp.concatenate([tail.astype(x.dtype), x[..., None]], -1)
    bias = None if b is None else b.astype(jnp.float32)
    y = jnp.sum(window.astype(jnp.float32) * w.astype(jnp.float32), -1)
    return ((y if bias is None else bias + y).astype(x.dtype),
            window[..., 1:].astype(tail.dtype))


# -- the gated short convolution (LFM2) ---------------------------------
# The whole mixer of an LFM2 ``conv`` layer between its two projections:
# ``[B | C | X]`` (..., 3 C) the input projection's columns, ``u = B * X``,
# ``v`` the convolution of ``u`` (no bias, no activation), ``y = C * v``.
# What a decode step needs of a prompt is ``u``'s tail, not ``x``'s.
def gated_conv_causal(bcx, w, lengths):
    """Whole prompts: ``bcx`` (B, L, 3 C), ``w`` (C, K) -> (``C * conv(B *
    X)`` (B, L, C), ``conv_tail`` of ``B * X`` at each prompt's own length
    (B, C, K - 1))."""
    gate_in, gate_out, x = jnp.split(bcx, 3, axis=-1)
    u = gate_in * x
    return (gate_out * conv1d_causal(u, w, None),
            conv_tail(u, lengths, w.shape[-1]))


def gated_conv_step(tail, bcx, w):
    """One position: ``tail`` (B, C, K - 1), ``bcx`` (B, 3 C) -> (``C *
    conv(B * X)`` (B, C), the tail moved on by one)."""
    gate_in, gate_out, x = jnp.split(bcx, 3, axis=-1)
    v, tail = conv1d_step(tail, gate_in * x, w, None)
    return gate_out * v, tail


# -- the recurrence -----------------------------------------------------
def ssd_chunked(x, dt, A, B, C, D, lengths, *, chunk: int = 128):
    """Whole prompts -> (y (B, L, H, P) in ``x``'s dtype, the float32 state
    (B, H, P, N) at each sequence's own length)."""
    b, l, h, p = x.shape
    g, n = B.shape[-2:]
    chunk = min(chunk, l)
    if l % chunk:
        raise ValueError(f"prompt bucket {l} is not a multiple of the "
                         f"state-space chunk {chunk}")
    c = l // chunk
    live = jnp.arange(l)[None, :] < lengths[:, None]
    dt = jnp.where(live[..., None], dt.astype(jnp.float32), 0.0)
    # the log of each position's decay, and its running sum inside a chunk
    a = (dt * A.astype(jnp.float32)).reshape(b, c, chunk, g, h // g)
    a_cum = jnp.cumsum(a.transpose(0, 1, 3, 4, 2), axis=-1)    # (B,c,g,e,i)
    a_end = a_cum[..., -1]                                     # (B,c,g,e)
    xc = x.reshape(b, c, chunk, g, h // g, p)
    # dt_j x_j: what position j puts into the state
    dx = dt.reshape(b, c, chunk, g, h // g, 1) * xc.astype(jnp.float32)
    Bc, Cc = B.reshape(b, c, chunk, g, n), C.reshape(b, c, chunk, g, n)

    # inside a chunk: y_i += sum_{j <= i} (C_i . B_j) exp(a_i - a_j) dt_j x_j
    scores = jnp.einsum("bcign,bcjgn->bcgij", Cc, Bc,
                        preferred_element_type=jnp.float32)
    causal = jnp.arange(chunk)[:, None] >= jnp.arange(chunk)[None, :]
    decay = jnp.exp(jnp.where(causal, a_cum[..., :, None] - a_cum[..., None, :],
                              -jnp.inf))                       # (B,c,g,e,i,j)
    y = jnp.einsum("bcgeij,bcjgep->bcigep",
                   (scores[:, :, :, None] * decay).astype(x.dtype),
                   dx.astype(x.dtype), preferred_element_type=jnp.float32)

    # each chunk's own contribution to the state at its end
    to_end = jnp.exp(a_end[..., None] - a_cum)                 # (B,c,g,e,j)
    own = jnp.einsum("bcjgep,bcjgn->bcgepn",
                     (to_end.transpose(0, 1, 4, 2, 3)[..., None]
                      * dx).astype(x.dtype),
                     Bc, preferred_element_type=jnp.float32)

    # between chunks: the carried state, c steps
    def carry(s, step):
        fade, add = step
        return fade[..., None, None] * s + add, s

    last, before = jax.lax.scan(
        carry, jnp.zeros((b, g, h // g, p, n), jnp.float32),
        (jnp.exp(a_end).transpose(1, 0, 2, 3),
         own.transpose(1, 0, 2, 3, 4, 5)))
    before = before.transpose(1, 0, 2, 3, 4, 5)                # (B,c,g,e,P,N)

    # what the chunks before put into y_i: exp(a_i) C_i . S_before
    y = y + jnp.exp(a_cum).transpose(0, 1, 4, 2, 3)[..., None] * jnp.einsum(
        "bcign,bcgepn->bcigep", Cc, before.astype(x.dtype),
        preferred_element_type=jnp.float32)
    y = y + D.astype(jnp.float32).reshape(g, h // g, 1) * xc.astype(jnp.float32)
    return (y.reshape(b, l, h, p).astype(x.dtype),
            last.reshape(b, h, p, n))


def ssd_step(state, x, dt, A, B, C, D, active=None):
    """One position: ``state`` (B, H, P, N) float32, ``x`` (B, H, P), ``dt``
    (B, H), ``B``, ``C`` (B, G, N) -> (y (B, H, P) in ``x``'s dtype, the
    state moved on by one).  Slots ``active`` (B,) marks False keep their
    state."""
    b, h, p = x.shape
    g, n = B.shape[-2:]
    dt = dt.astype(jnp.float32)
    s = state.astype(jnp.float32).reshape(b, g, h // g, p, n)
    fade = jnp.exp(dt * A.astype(jnp.float32)).reshape(b, g, h // g, 1, 1)
    dx = (dt[..., None] * x.astype(jnp.float32)).reshape(b, g, h // g, p, 1)
    new = fade * s + dx * B.astype(jnp.float32)[:, :, None, None, :]
    y = jnp.sum(new * C.astype(jnp.float32)[:, :, None, None, :], -1)
    y = y.reshape(b, h, p) + D.astype(jnp.float32)[:, None] * x.astype(jnp.float32)
    new = new.reshape(b, h, p, n)
    if active is not None:
        new = jnp.where(active[:, None, None, None], new,
                        state.astype(jnp.float32))
    return y.astype(x.dtype), new.astype(state.dtype)

