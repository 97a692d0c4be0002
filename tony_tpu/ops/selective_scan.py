"""Selective scan: the recurrence of a Mamba-1 state-space mixer
(models/ssm_hybrid.py steps 5-6) over one sequence,

    h_t = exp(delta_t * A) * h_{t-1} + (delta_t * c_t) * B_t      h: [N, E] float32
    y_t = (h_t . C_t + D * c_t) * silu(z_t)

with the state in float32 and the channels ``E`` on the minor dimension (the
chip's lanes). ``A`` differs per (state row, channel), so the update is
elementwise — vector-unit work, no matrix product — and sequential in ``t``.

Two forms, chosen by the platform alone (:func:`_run_kernel`; no knob):

- ``selective_scan`` on a TPU is ONE Pallas kernel named ``selective_scan``
  (a trace shows it by that name): grid (blocks of channels — parallel,
  chunks of the sequence — sequential), the state of a channel block resident
  in VMEM across the chunks (the result's block, revisited), the initial state
  in and the final state out, so a tail or a chunk of a chunked prefill starts
  from the state its predecessor left. Inside a chunk it walks ``GROUP``
  positions at a time, unrolled: ``B`` and ``C`` reach the kernel as ``[T /
  GROUP, N, GROUP]`` so that position ``j`` of a group is a static lane slice
  ``[N, 1]`` that broadcasts along the channels.
- elsewhere (and for a shape the kernel does not tile) the plain
  ``lax.scan`` of one position a step, which the tests compare the kernel
  with in interpret mode.

A position whose ``delta`` is 0 leaves the state as it is (``exp(0) = 1``, no
input): that is how a caller keeps a padded bucket's rows out of the state
it hands on. :func:`selective_step` is the one-token form for decode, plain
``jnp``: one read of ``h``, one update, one write.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tony_tpu.ops.compat import use_interpret as _use_interpret

GROUP = 16          # positions unrolled together: one bfloat16 tile of rows
CHANNEL_BLOCK = 512
CHUNKS = (256, 128, 64, 32, 16)


def _update(h, a, c_t, d_t, b_t, c2_t):
    """One position. The state rows ``N`` lead: ``h``, ``a`` are ``[N, ...,
    E]``, ``c_t`` / ``d_t`` float32 broadcast over them as ``[1, ..., E]``,
    ``b_t`` / ``c2_t`` over the channels as ``[N, ..., 1]``. Returns ``(h', h'
    . C [1, ..., E])``."""
    h = jnp.exp(d_t * a) * h + (d_t * c_t) * b_t
    return h, jnp.sum(h * c2_t, axis=0, keepdims=True)


def _scan_plain(c, delta, b, cc, z, a, d_skip, h0):
    f32 = jnp.float32

    def body(h, row):
        c_t, d_t, b_t, c2_t, z_t = row
        cf = c_t.astype(f32)[None]
        h, y = _update(h, a, cf, d_t[None], b_t.astype(f32)[:, None],
                       c2_t.astype(f32)[:, None])
        y = (y + d_skip * cf) * jax.nn.silu(z_t.astype(f32))
        return h, y[0].astype(c.dtype)

    h, y = lax.scan(body, h0.astype(f32), (c, delta.astype(f32), b, cc, z))
    return y, h


def _scan_kernel(c_ref, d_ref, z_ref, b_ref, c2_ref, a_ref, skip_ref, h0_ref,
                 y_ref, h_ref, y_sc, *, chunk: int):
    f32 = jnp.float32

    @pl.when(pl.program_id(1) == 0)
    def _start():
        h_ref[...] = h0_ref[...]

    a, skip = a_ref[...], skip_ref[...]

    def group(g, h):
        rows = pl.ds(pl.multiple_of(g * GROUP, GROUP), GROUP)
        cg, dg = c_ref[rows, :].astype(f32), d_ref[rows, :]
        bg, c2g = b_ref[g], c2_ref[g]                       # [N, GROUP]
        for j in range(GROUP):
            h, y = _update(h, a, cg[j:j + 1], dg[j:j + 1], bg[:, j:j + 1],
                           c2g[:, j:j + 1])
            y_sc[j:j + 1, :] = y
        y = (y_sc[...] + skip * cg) * jax.nn.silu(z_ref[rows, :].astype(f32))
        y_ref[rows, :] = y.astype(y_ref.dtype)
        return h

    h_ref[...] = lax.fori_loop(0, chunk // GROUP, group, h_ref[...])


def _tiles(T: int, E: int) -> tuple[int, int] | None:
    """(chunk of the sequence, block of channels) the kernel runs at, None
    where the shape does not tile."""
    chunk = next((n for n in CHUNKS if T % n == 0), 0)
    block = CHANNEL_BLOCK if E % CHANNEL_BLOCK == 0 else E
    return (chunk, block) if chunk and E % 128 == 0 else None


def _scan_pallas(c, delta, b, cc, z, a, d_skip, h0, *, chunk: int, block: int):
    T, E = c.shape
    N = a.shape[0]
    f32 = jnp.float32

    def grouped(x):          # [T, N] -> [T / GROUP, N, GROUP] float32
        return x.astype(f32).reshape(T // GROUP, GROUP, N).transpose(0, 2, 1)

    seq = pl.BlockSpec((chunk, block), lambda e, t: (t, e))
    per_group = pl.BlockSpec((chunk // GROUP, N, GROUP), lambda e, t: (t, 0, 0))
    channels = pl.BlockSpec((N, block), lambda e, t: (0, e))
    y, h = pl.pallas_call(
        functools.partial(_scan_kernel, chunk=chunk),
        grid=(E // block, T // chunk),
        in_specs=[seq, seq, seq, per_group, per_group, channels,
                  pl.BlockSpec((1, block), lambda e, t: (0, e)), channels],
        out_specs=[seq, channels],
        out_shape=[jax.ShapeDtypeStruct((T, E), c.dtype),
                   jax.ShapeDtypeStruct((N, E), f32)],
        scratch_shapes=[pltpu.VMEM((GROUP, block), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_use_interpret(),
        name="selective_scan",
    )(c, delta.astype(f32), z, grouped(b), grouped(cc), a.astype(f32),
      d_skip.astype(f32)[None], h0.astype(f32))
    return y, h


def _run_kernel() -> bool:
    """Whether :func:`selective_scan` may run its kernel: where the platform
    is a TPU. A test that wants the kernel interpreted on the CPU steers
    this name (ops/decode_attention.py's idiom)."""
    return not _use_interpret()


def selective_scan(c: jax.Array, delta: jax.Array, b: jax.Array, cc: jax.Array,
                   z: jax.Array, a: jax.Array, d_skip: jax.Array,
                   h0: jax.Array) -> tuple[jax.Array, jax.Array]:
    """One sequence through the recurrence: ``c``, ``z [T, E]`` (the
    activations' dtype), ``delta [T, E]`` float32, ``b``, ``cc [T, N]``, ``a
    [N, E]`` float32 (negative), ``d_skip [E]``, ``h0 [N, E]`` float32 — the
    state the predecessor left. Returns ``(y [T, E] in c's dtype, h [N, E]
    float32 after the last position)``."""
    tiles = _tiles(*c.shape) if _run_kernel() else None
    if tiles:
        return _scan_pallas(c, delta, b, cc, z, a, d_skip, h0, chunk=tiles[0],
                            block=tiles[1])
    return _scan_plain(c, delta, b, cc, z, a, d_skip, h0)


def selective_step(c: jax.Array, delta: jax.Array, b: jax.Array, cc: jax.Array,
                   z: jax.Array, a: jax.Array, d_skip: jax.Array,
                   h: jax.Array) -> tuple[jax.Array, jax.Array]:
    """One position of ``S`` independent sequences (a decode step): ``c``,
    ``z``, ``delta [S, E]``, ``b``, ``cc [S, N]``, ``h [N, S, E]`` float32 —
    the state rows lead, so a slot is a sublane and a channel a lane of every
    row, and the step size broadcasts over the rows for nothing. Returns ``(y
    [S, E] in c's dtype, h')``."""
    f32 = jnp.float32
    cf = c.astype(f32)[None]
    h, y = _update(h, a[:, None], cf, delta.astype(f32)[None], b.astype(f32).T[..., None],
                   cc.astype(f32).T[..., None])
    y = (y + d_skip * cf)[0] * jax.nn.silu(z.astype(f32))
    return y.astype(c.dtype), h


__all__ = ["selective_scan", "selective_step"]
