"""Mamba-2's state-space recurrence in its chunked form (SSD), and the causal
depthwise convolution that feeds it (and that a ``conv`` layer's gated short
convolution takes its taps from). Pure ``jax.numpy``; training only. The
caller names the scopes (``models/mpt.py``: ``mamba/conv``, ``mamba/scan``;
``shortconv/mix``).

Per head ``h`` (width ``P``) and position ``t``, with one group of ``B_t``,
``C_t`` (width ``N``) shared by all heads, ``A_h = -exp(A_log_h)`` and
``dt`` already through its softplus:

    H_t = exp(dt_t A) H_(t-1) + dt_t x_t B_t^T        (P x N, H_0 = 0)
    y_t = H_t C_t + D x_t

The program never walks positions. A row is cut into chunks of ``Q``
positions and ``lax.scan`` walks the chunks, carrying the state ``H`` at each
chunk's start (float32). Inside a chunk, with ``cs_t`` the running sum of
``dt A`` from the chunk's first position:

    y_intra = ((C B^T) o L) (dt x)        L_ts = exp(cs_t - cs_s), s <= t
    y_inter = exp(cs_t) (C_t H_start)
    H_end   = exp(cs_Q) H_start + sum_s exp(cs_Q - cs_s) dt_s x_s B_s^T

``dt``, the running sums, every decay and the carried state are float32; the
products take ``compute_dtype`` operands and accumulate in float32.

The backward pass is the chunk's own: the scan's body is under
``jax.checkpoint``, so what the forward keeps is each chunk's inputs and its
start state (``[chunks, heads, P, N]``, 2 MB a chunk at 64 heads of 64 x 128),
and the transpose walks the chunks backwards, rebuilding one chunk's
``[heads, Q, Q]`` decays at a time. The ``[heads, chunks, Q, Q]`` tensor of a
whole row (0.5 GB in float32 at 8,192 positions) never exists.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def causal_conv1d(x: jax.Array, kernel: jax.Array,
                  bias: jax.Array | None = None) -> jax.Array:
    """Depthwise causal convolution over ``x [B, S, C]``: ``y_t = bias +
    sum_k kernel[k] * x_(t - W + 1 + k)`` with zeros before the row's start
    (``kernel [W, C]``; no ``bias``: the sum alone), as ``W`` shifted products
    in float32."""
    width, s = kernel.shape[0], x.shape[1]
    padded = jnp.pad(x.astype(jnp.float32), ((0, 0), (width - 1, 0), (0, 0)))
    kernel = kernel.astype(jnp.float32)
    y = None if bias is None else bias.astype(jnp.float32)
    for k in range(width):
        tap = kernel[k] * padded[:, k:k + s]
        y = tap if y is None else y + tap
    return y


def _chunk(a_neg: jax.Array, compute_dtype, state: jax.Array, inputs):
    """One chunk: ``(H_start [B, H, P, N], (x [B, Q, H, P], dt [B, Q, H],
    b [B, Q, N], c [B, Q, N]))`` -> ``(H_end, y [B, H, Q, P] float32)``. ``y``
    leaves head-major, as the products make it: the scan then stacks whole
    slabs, and the caller turns the row once (a position-major ``y`` made the
    compiler lay the stack out with the chunk index inside, and every chunk's
    write a strided one: 85 ms of a 589 ms step on a v5e; PERF.md, PR 33)."""
    x, dt, b, c = inputs
    q = x.shape[1]
    f32 = jnp.float32
    cs = jnp.cumsum(dt * a_neg, axis=1)  # [B, Q, H], <= 0 and falling
    cs_h = jnp.swapaxes(cs, 1, 2)  # [B, H, Q]
    causal = jnp.tril(jnp.ones((q, q), bool))
    # masked before the exponential: above the diagonal the difference is
    # positive and may overflow
    decay = jnp.exp(jnp.where(causal, cs_h[..., :, None] - cs_h[..., None, :], -jnp.inf))
    scores = jnp.einsum("btn,bsn->bts", c, b, preferred_element_type=f32)
    x32 = x.astype(f32)
    x_dt = (x32 * dt[..., None]).astype(compute_dtype)
    y = jnp.einsum("bhts,bshp->bhtp", (scores[:, None] * decay).astype(compute_dtype),
                   x_dt, preferred_element_type=f32)
    y = y + jnp.exp(cs_h)[..., None] * jnp.einsum(
        "btn,bhpn->bhtp", c, state.astype(compute_dtype), preferred_element_type=f32)
    to_end = jnp.exp(cs[:, -1:] - cs)  # [B, Q, H]
    grown = jnp.einsum("bshp,bsn->bhpn",
                       (x32 * (dt * to_end)[..., None]).astype(compute_dtype), b,
                       preferred_element_type=f32)
    return state * jnp.exp(cs_h[..., -1])[..., None, None] + grown, y


def ssd_scan(x: jax.Array, dt: jax.Array, a_log: jax.Array, b: jax.Array,
             c: jax.Array, d: jax.Array, *, chunk: int,
             compute_dtype=jnp.bfloat16) -> jax.Array:
    """``y [B, S, H, P]`` float32 of the recurrence above over ``x [B, S, H,
    P]``, ``dt [B, S, H]`` (positive, float32), ``a_log [H]``, ``b``, ``c``
    ``[B, S, N]`` and the skip weight ``d [H]``. ``S`` is a multiple of
    ``chunk``; every row starts from a zero state."""
    bsz, s, h, p = x.shape
    n = b.shape[-1]
    if s % chunk:
        raise ValueError(f"sequence {s} is not a multiple of the scan's chunk {chunk}")
    dt = dt.astype(jnp.float32)
    a_neg = -jnp.exp(a_log.astype(jnp.float32))

    def by_chunk(t):  # [B, S, ...] -> [chunks, B, Q, ...]
        return jnp.moveaxis(t.reshape(bsz, s // chunk, chunk, *t.shape[2:]), 1, 0)

    step = jax.checkpoint(functools.partial(_chunk, a_neg, compute_dtype))
    _, y = jax.lax.scan(
        step, jnp.zeros((bsz, h, p, n), jnp.float32),
        (by_chunk(x.astype(compute_dtype)), by_chunk(dt),
         by_chunk(b.astype(compute_dtype)), by_chunk(c.astype(compute_dtype))))
    y = jnp.transpose(y, (1, 0, 3, 2, 4)).reshape(bsz, s, h, p)  # [chunks, B, H, Q, P]
    return y + d.astype(jnp.float32)[:, None] * x.astype(jnp.float32)
