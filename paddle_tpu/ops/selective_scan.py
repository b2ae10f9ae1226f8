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
update on h [S, N, E]. Given the row alone it is what XLA fuses. Given a
`StackedRow` (the stacked, donated state [L, S, N, E] of a decode program
and the layer's index) it is one algorithm in two forms behind the gate,
key `("selective_step", S, E, N)`, default `xla`:

  * XLA: the row sliced out of the stack, the update, the row written
    back. Alone that is one fusion; inside a loop over the layers it is
    TWO (y; the new state with its write in place), each reading the row
    out of the stack: the state read twice and written once.
  * Pallas (`_step_kernel`): the whole stack is the operand, aliased to
    the result, the layer's index scalar-prefetched into the block's
    address; blocks of 8 slots x up to 5,120 channels of row m come in,
    their new state and y [8, E] go out: the state read ONCE and written
    once, the other rows neither read nor copied. In a block a slot's
    [N, E] passes through the registers as it lies, N on the sublanes: B
    and C arrive broadcast along 128 lanes, y is a sum over sublanes. Bound
    by HBM (the arithmetic hides behind the block's DMA). A stack that is
    not float32, or not whole tiles (S / 8, N / 8, E / 128), goes to the
    XLA form unasked.

The gate's trial (`_gate_step`) times both forms AS A DECODE PROGRAM RUNS
THEM, on a row at a traced index of a stack in a loop over its layers:
timed alone they tie (docs/KERNELS.md has the readings).
"""
from __future__ import annotations

import functools
import os
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_attention import on_tpu

__all__ = ["selective_scan", "selective_scan_xla", "selective_scan_pallas",
           "selective_step", "selective_step_xla", "selective_step_pallas",
           "StackedRow", "SCAN_CHUNK"]

SCAN_CHUNK = 256        # positions a chunk: 1 MiB a stream's block
_LANES, _SUBLANES = 128, 8
_TILE = _LANES * _SUBLANES      # channels a program: one register a state
# B and C of a whole call are scalars in SMEM, 2 x batch x T x N x 4 B: a
# call past this goes to the XLA form (a prompt bucket of 2,048 is 256 KiB)
_SMEM_BYTES = 512 * 1024
# the one-step kernel: slots a block (the streams' [8, E] are one tile of
# sublanes), the widest block of channels, and the lanes of a slot's state
# in registers at a time, widest first
_STEP_SLOTS, _STEP_CHANNELS = 8, 5120
_STEP_LANES = (512, 256, 128)


def _one_token(h, u, dt, A, B, C):
    """The update: h [..., N, E] float32; u, dt [..., E]; B, C [..., N].
    Returns (h', y [..., E]), all float32."""
    h = jnp.exp(dt[..., None, :] * A) * h \
        + (dt * u)[..., None, :] * B[..., :, None]
    return h, jnp.sum(h * C[..., :, None], axis=-2)


class StackedRow(NamedTuple):
    """Row `index` (may be traced) of a stacked state `stack` [L, S, N, E],
    where it lies: what a loop over stacked layers hands `selective_step`
    in place of a slice, so that the step reads the row out of the stack
    and writes it back there itself."""
    stack: jax.Array
    index: jax.Array

    def row(self):
        return jax.lax.dynamic_index_in_dim(self.stack, self.index, 0,
                                            keepdims=False)

    def astype(self, dtype):
        """The row as an array of its own (a caller that casts the state
        wants the row, never 26 layers of it)."""
        return self.row().astype(dtype)

    def put(self, h):
        return StackedRow(jax.lax.dynamic_update_index_in_dim(
            self.stack, h, self.index, 0), self.index)


def selective_step(u, delta, A, B, C, D, h, impl=None):
    """One token of every slot. u, delta [S, E]; A [N, E]; B, C [S, N];
    D [E]; h [S, N, E] float32, or a `StackedRow` of a stack [L, S, N, E].
    Returns (y [S, E] in u's dtype, h'): h' an array where h was one, a
    `StackedRow` of the updated stack where h was one. impl (a
    `StackedRow` only): None = the gate, or "xla" / "pallas"."""
    if not isinstance(h, StackedRow):
        return selective_step_xla(u, delta, A, B, C, D, h)
    if impl is None:
        impl = _auto_step_impl(h.stack)
    if impl == "pallas":
        y, stack = selective_step_pallas(u, delta, A, B, C, D, *h)
        return y, StackedRow(stack, h.index)
    y, row = selective_step_xla(u, delta, A, B, C, D, h.row())
    return y, h.put(row)


def selective_step_xla(u, delta, A, B, C, D, h):
    """The update as XLA fuses it; h [S, N, E] float32."""
    uf = u.astype(jnp.float32)
    h, y = _one_token(h, uf, delta.astype(jnp.float32), A,
                      B.astype(jnp.float32), C.astype(jnp.float32))
    return (y + D.astype(jnp.float32) * uf).astype(u.dtype), h


def _step_kernel(m_ref, u_ref, dt_ref, b_ref, c_ref, a_ref, d_ref, h_ref,
                 y_ref, ho_ref, *, lanes):
    """A block of slots of layer m's row. u_ref, dt_ref, y_ref [bs, be];
    b_ref, c_ref [bs, N, 128]: B and C along the lanes; a_ref [N, be];
    d_ref [1, be]; h_ref, ho_ref [1, bs, N, be], one buffer in HBM. A
    slot's state moves through the registers `lanes` channels at a time
    (N / 8 registers [8, 128] a 128 lanes): `_one_token`'s arithmetic, the
    sum over N a sum over sublanes. Slots and lanes are unrolled: every
    index is static."""
    del m_ref       # the index maps' alone
    bs, be = u_ref.shape
    for j in range(be // lanes):
        at = pl.ds(j * lanes, lanes)
        a, u, dt = a_ref[:, at], u_ref[:, at], dt_ref[:, at]
        du, y = dt * u, []
        for s in range(bs):
            b = jnp.tile(b_ref[s], (1, lanes // _LANES))
            c = jnp.tile(c_ref[s], (1, lanes // _LANES))
            h = jnp.exp(dt[s:s + 1] * a) * h_ref[0, s, :, at] \
                + du[s:s + 1] * b
            ho_ref[0, s, :, at] = h
            y.append(jnp.sum(h * c, axis=0, keepdims=True))
        y_ref[:, at] = jnp.concatenate(y, axis=0) + d_ref[:, at] * u


def selective_step_pallas(u, delta, A, B, C, D, stack, m, block=None,
                          interpret=None):
    """As `selective_step` on row m of `stack` [L, S, N, E] float32;
    returns (y, the stack with that row advanced, the buffer it came in
    where the caller donates it). E whole tiles of 128 lanes, N of 8
    sublanes, S whole blocks of slots; block = (slots, channels)."""
    L, S, N, E = stack.shape
    bs, be = block or _step_block(S, E)
    f32 = jnp.float32
    lanes = next(w for w in _STEP_LANES if be % w == 0)

    def along_lanes(a):     # [S, N] -> [S, N, 128]
        return jnp.broadcast_to(a.astype(f32)[:, :, None], (S, N, _LANES))

    stream = pl.BlockSpec((bs, be), lambda j, i, m: (i, j))
    maps = pl.BlockSpec((bs, N, _LANES), lambda j, i, m: (i, 0, 0))
    state = pl.BlockSpec((1, bs, N, be), lambda j, i, m: (m[0], i, 0, j))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(E // be, S // bs),    # slots minor: A's block stays
        in_specs=[stream, stream, maps, maps,
                  pl.BlockSpec((N, be), lambda j, i, m: (0, j)),
                  pl.BlockSpec((1, be), lambda j, i, m: (0, j)), state],
        out_specs=[stream, state])
    held = 4 * (4 * bs * N * be + 6 * bs * be + 4 * bs * N * _LANES
                + 2 * (N + 1) * be)     # every block twice
    y, stack = pl.pallas_call(
        functools.partial(_step_kernel, lanes=lanes),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((S, E), f32),
                   jax.ShapeDtypeStruct(stack.shape, f32)],
        input_output_aliases={7: 1},    # the stack, counted from m
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=held + (8 << 20)),
        interpret=(not on_tpu()) if interpret is None else interpret,
        name="selective_step",
    )(jnp.reshape(m, (1,)).astype(jnp.int32), u.astype(f32),
      delta.astype(f32), along_lanes(B), along_lanes(C), A.astype(f32),
      D.astype(f32)[None], stack)
    return y.astype(u.dtype), stack


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


def _step_block(S, E):
    """(slots, channels) of the step kernel's block, or None where the
    kernel does not take the shape: whole tiles only."""
    if S % _STEP_SLOTS or E % _LANES:
        return None
    return _STEP_SLOTS, max(w for w in range(_LANES, _STEP_CHANNELS + 1,
                                             _LANES) if E % w == 0)


# the gate's trial of the step: a stack this deep, swept this often by one
# loop. At 256 slots of Jamba2-3B a call is 256 steps of 0.25-0.4 ms: long
# enough that `autobench._measure` keeps ONE call in flight (a sample of
# fifteen short ones held fifteen results of 336 MB and read 13.7 GiB of
# peak beside a serving engine; chip run, PR 43), and the copy of a stack
# that is an argument, not donated, is a hundredth of it
_STEP_TRIAL = (2, 128)


def _gate_step(S, E, N):
    """(key, candidates, make_args) of the step's gate. A candidate is the
    step AS A DECODE PROGRAM RUNS IT: on the row at a traced index of a
    stacked state, in a loop over the layers whose streams hang on the
    layer before. Timed alone on [S, N, E] the XLA form is one fusion and
    ties with the kernel; in the loop it is two, each reading the row."""
    key = ("selective_step", S, E, N)
    layers, sweeps = _STEP_TRIAL

    def make_args():
        ks = jax.random.split(jax.random.PRNGKey(0), 7)
        return (jax.random.normal(ks[0], (layers, S, N, E), jnp.float32),
                jax.random.normal(ks[1], (S, E), jnp.float32),
                0.05 * jax.random.uniform(ks[2], (S, E), jnp.float32),
                -jnp.exp(jax.random.normal(ks[3], (N, E), jnp.float32)),
                jax.random.normal(ks[4], (S, N), jnp.float32),
                jax.random.normal(ks[5], (S, N), jnp.float32),
                0.1 * jax.random.normal(ks[6], (E,), jnp.float32))

    def in_a_loop(impl):
        def run(stack, u, dt, A, B, C, D):
            def layer(l, carry):
                stack, y = carry
                y, h = selective_step(u + 1e-3 * y, dt, A, B, C, D,
                                      StackedRow(stack, l % layers), impl)
                return h.stack, y
            return jax.lax.fori_loop(0, layers * sweeps, layer,
                                     (stack, jnp.zeros_like(u)))
        return run

    return key, {impl: in_a_loop(impl) for impl in ("xla", "pallas")}, \
        make_args


def _auto_step_impl(stack) -> str:
    """The gate's draw on a TPU for a stack the kernel takes (float32,
    whole tiles); elsewhere, and for another, the XLA form."""
    _, S, N, E = stack.shape
    if os.environ.get("PADDLE_TPU_DISABLE_PALLAS") or not on_tpu() \
            or stack.dtype != jnp.float32 or N % _SUBLANES \
            or _step_block(S, E) is None:
        return "xla"
    from . import autobench
    return autobench.prefer(*_gate_step(S, E, N), default="xla")


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
