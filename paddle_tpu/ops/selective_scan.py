"""The selective scan of a state-space layer (Mamba-1's recurrence), and its
one-step form.

    h_t = exp(delta_t (x) A) . h_{t-1} + (delta_t . u_t) (x) B_t     [N, E]
    y_t = h_t C_t + D . u_t                                          [E]

E channels each keep N numbers of state; A [N, E] is negative, delta_t [E]
a positive step, B_t and C_t [N] the token's input and output maps. The
state is float32 and so is all arithmetic inside the recurrence; y comes
back in u's dtype. The state lies [N, E], E minor: 16 of state are two
sublanes of a register and 5,120 channels its lanes, where [E, N] would
pad 16 to 128 lanes, eight times the bytes, in HBM and in VMEM alike.

`selective_scan` runs a whole sequence in CHUNKS: inside a chunk the state
moves token by token in registers, from chunk to chunk it is the carry
[N, E], and an array [T, E, N] (671 MB a tensor at T = 2,048, E = 5,120)
never exists. Two forms of the one function, behind `ops/autobench`:

  * Pallas (TPU; `interpret` elsewhere): grid (batch, E / 1,024, T /
    chunk), the chunk axis in order. A program holds 1,024 channels' state
    as N registers [8, 128] and walks its chunk's tokens: per token and
    register one `exp`, six multiply-adds, nothing across lanes or
    sublanes. B_t[n] and C_t[n] are scalars, prefetched to SMEM. The
    streams u and delta go in, and y comes out, as float32 [T, E / 1,024,
    8, 128] blocks (eight sublanes are no bfloat16 tile). Bound by the
    vector and transcendental units (E N `exp` a token), not by HBM.
  * XLA: `lax.scan` over the chunks of a `lax.scan` over a chunk's
    tokens, the same arithmetic a token: a serial loop of small fusions.

`lengths` [batch]: positions from a sequence's length on do not advance
the recurrence (delta = 0 there: exp(0) = 1, nothing added), so `h_last`
is the state AT the length whatever the padding behind it.

`selective_step` is one token of every slot of a decode batch: the same
update on h [S, N, E] as XLA fuses it, and it has no second form. Alone it
is one fusion (y and the new state from one read); inside a decode program
whose state is a row of the stacked, donated cache it is two (y; the update
with its write in place), so the state is read twice and written once
(docs/KERNELS.md has the trace's reading and what a kernel would win).
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_attention import on_tpu

__all__ = ["selective_scan", "selective_scan_xla", "selective_scan_pallas",
           "selective_step", "SCAN_CHUNK"]

SCAN_CHUNK = 256        # positions a chunk: 1 MiB a stream's block
_LANES, _SUBLANES = 128, 8
_TILE = _LANES * _SUBLANES      # channels a program: one register a state
# B and C of a whole call are scalars in SMEM, 2 x batch x T x N x 4 B: a
# call past this goes to the XLA form (a prompt bucket of 2,048 is 256 KiB)
_SMEM_BYTES = 512 * 1024


def _one_token(h, u, dt, A, B, C):
    """The update: h [..., N, E] float32; u, dt [..., E]; B, C [..., N].
    Returns (h', y [..., E]), all float32."""
    h = jnp.exp(dt[..., None, :] * A) * h \
        + (dt * u)[..., None, :] * B[..., :, None]
    return h, jnp.sum(h * C[..., :, None], axis=-2)


def selective_step(u, delta, A, B, C, D, h):
    """One token of every slot. u, delta [S, E]; A [N, E]; B, C [S, N];
    D [E]; h [S, N, E] float32. Returns (y [S, E] in u's dtype, h')."""
    uf = u.astype(jnp.float32)
    h, y = _one_token(h, uf, delta.astype(jnp.float32), A,
                      B.astype(jnp.float32), C.astype(jnp.float32))
    return (y + D.astype(jnp.float32) * uf).astype(u.dtype), h


def selective_scan_xla(u, delta, A, B, C, h0, chunk):
    """u, delta [Bt, T, E] float32, T a multiple of `chunk`; B, C [Bt, T,
    N]; h0 [Bt, N, E]. Returns (y [Bt, T, E] float32, h_last)."""
    Bt, T, _ = u.shape

    def chunks(a):      # [Bt, T, w] -> [T / chunk, chunk, Bt, w]
        return jnp.moveaxis(a, 1, 0).reshape(T // chunk, chunk, Bt, -1)

    def token(h, xs):
        return _one_token(h, *xs[:2], A, *xs[2:])

    def one_chunk(h, xs):
        return jax.lax.scan(token, h, xs, unroll=8)

    h, y = jax.lax.scan(one_chunk, h0,
                        tuple(chunks(a) for a in (u, delta, B, C)))
    return jnp.moveaxis(y.reshape(T, Bt, -1), 0, 1), h


def _scan_kernel(b_ref, c_ref, u_ref, dt_ref, a_ref, h0_ref, y_ref, hl_ref,
                 h_scr, *, chunk, n_state, seq):
    """One chunk of one tile of channels. b_ref, c_ref: SMEM [Bt T N];
    u_ref, dt_ref, y_ref blocks [1, chunk, 1, 8, 128]; a_ref [N, 1, 8,
    128]; h0_ref, hl_ref [1, N, 1, 8, 128]; h_scr [N, 8, 128] carries the
    state from chunk to chunk."""
    N = n_state
    c, last = pl.program_id(2), pl.num_programs(2) - 1

    @pl.when(c == 0)
    def _start():
        h_scr[...] = h0_ref[0, :, 0]

    base = (pl.program_id(0) * seq + c * chunk) * N

    def token(t, hs):
        dt, u = dt_ref[0, t, 0], u_ref[0, t, 0]
        du = dt * u
        at = base + t * N
        y, out = jnp.zeros_like(u), []
        for n in range(N):
            h = jnp.exp(dt * a_ref[n, 0]) * hs[n] + du * b_ref[at + n]
            y = y + h * c_ref[at + n]
            out.append(h)
        y_ref[0, t, 0] = y
        return tuple(out)

    hs = jax.lax.fori_loop(0, chunk, token,
                           tuple(h_scr[n] for n in range(N)))
    for n in range(N):
        h_scr[n] = hs[n]

    @pl.when(c == last)
    def _end():
        for n in range(N):
            hl_ref[0, n, 0] = hs[n]


def selective_scan_pallas(u, delta, A, B, C, h0, chunk, interpret=None):
    """As `selective_scan_xla`; E a multiple of 1,024."""
    Bt, T, E = u.shape
    N = A.shape[0]
    tiles = E // _TILE

    def tiled(a):       # [..., E] -> [..., E / 1024, 8, 128]
        return a.reshape(a.shape[:-1] + (tiles, _SUBLANES, _LANES))

    stream = pl.BlockSpec((1, chunk, 1, _SUBLANES, _LANES),
                          lambda b, e, c, *_: (b, c, e, 0, 0))
    state = pl.BlockSpec((1, N, 1, _SUBLANES, _LANES),
                         lambda b, e, c, *_: (b, 0, e, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(Bt, tiles, T // chunk),
        in_specs=[stream, stream,
                  pl.BlockSpec((N, 1, _SUBLANES, _LANES),
                               lambda b, e, c, *_: (0, e, 0, 0)),
                  state],
        out_specs=[stream, state],
        scratch_shapes=[pltpu.VMEM((N, _SUBLANES, _LANES), jnp.float32)])
    y, h = pl.pallas_call(
        functools.partial(_scan_kernel, chunk=chunk, n_state=N, seq=T),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(tiled(u).shape, jnp.float32),
                   jax.ShapeDtypeStruct(tiled(h0).shape, jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=(not on_tpu()) if interpret is None else interpret,
        name="selective_scan",
    )(B.reshape(-1), C.reshape(-1), tiled(u), tiled(delta), tiled(A),
      tiled(h0))
    return y.reshape(Bt, T, E), h.reshape(Bt, N, E)


def _gate_scan(Bt, T, E, N, chunk):
    """(key, candidates, make_args) of the scan's gate."""
    key = ("selective_scan", Bt, T, E, N, chunk)

    def make_args():
        ks = jax.random.split(jax.random.PRNGKey(0), 5)
        return (jax.random.normal(ks[0], (Bt, T, E), jnp.float32),
                0.05 * jax.random.uniform(ks[1], (Bt, T, E), jnp.float32),
                -jnp.exp(jax.random.normal(ks[2], (N, E), jnp.float32)),
                jax.random.normal(ks[3], (Bt, T, N), jnp.float32),
                jax.random.normal(ks[4], (Bt, T, N), jnp.float32),
                jnp.zeros((Bt, N, E), jnp.float32))

    return key, {
        "xla": functools.partial(selective_scan_xla, chunk=chunk),
        "pallas": functools.partial(selective_scan_pallas, chunk=chunk,
                                    interpret=False)}, make_args


def _auto_impl(Bt, T, E, N, chunk) -> str:
    """The gate's draw on a TPU for a shape the kernel takes; elsewhere,
    and for another shape, the XLA form."""
    if os.environ.get("PADDLE_TPU_DISABLE_PALLAS") or not on_tpu() \
            or E % _TILE or 2 * Bt * T * N * 4 > _SMEM_BYTES:
        return "xla"
    from . import autobench
    return autobench.prefer(*_gate_scan(Bt, T, E, N, chunk), default="xla")


def selective_scan(u, delta, A, B, C, D, h0=None, lengths=None,
                   chunk=SCAN_CHUNK, impl=None):
    """u, delta [Bt, T, E]; A [N, E]; B, C [Bt, T, N]; D [E]; h0 [Bt, N,
    E] float32 (None: zeros); lengths [Bt] int32 (None: T). Returns (y
    [Bt, T, E] in u's dtype, h_last [Bt, N, E] float32: the state at
    `lengths`). impl: None = the gate, or "xla" / "pallas"."""
    Bt, T, E = u.shape
    N = A.shape[0]
    f32 = jnp.float32
    uf, dt = u.astype(f32), delta.astype(f32)
    if lengths is not None:
        live = jnp.arange(T, dtype=jnp.int32)[None, :] < lengths[:, None]
        dt = jnp.where(live[:, :, None], dt, 0.0)
    chunk = min(chunk, T)
    pad = -T % chunk    # a last chunk's tail: delta 0, as past a length
    streams = [jnp.pad(a.astype(f32), ((0, 0), (0, pad), (0, 0)))
               for a in (uf, dt, B, C)]
    if h0 is None:
        h0 = jnp.zeros((Bt, N, E), f32)
    if impl is None:
        impl = _auto_impl(Bt, T + pad, E, N, chunk)
    fn = selective_scan_pallas if impl == "pallas" else selective_scan_xla
    y, h = fn(streams[0], streams[1], A.astype(f32), streams[2], streams[3],
              h0.astype(f32), chunk)
    return (y[:, :T] + D.astype(f32) * uf).astype(u.dtype), h
