"""`ops/selective_scan.py`: the chunked scan (the Pallas kernel in
interpret mode and the XLA form) against a plain `lax.scan` over the
positions, for chunks that do and do not divide T, lengths short of the
bucket and a non-zero h0; one-step updates from a scanned state against the
scan over the whole sequence; the one-step kernel in interpret mode against
the XLA step, on a row of a stack. float32 on the CPU: the tolerance is
1e-5 on numbers of size ~1 (read: 2e-6)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import selective_scan as ss

ATOL = 1e-5


def plain(u, dt, A, B, C, D, h0, lengths):
    """The recurrence a position at a time; positions from a sequence's
    length on leave the state where it is."""
    def position(h, xs):
        u_t, dt_t, B_t, C_t, t = xs
        d = jnp.where((t < lengths)[:, None], dt_t, 0.0)
        h = jnp.exp(d[:, None, :] * A) * h \
            + (d * u_t)[:, None, :] * B_t[:, :, None]
        return h, jnp.sum(h * C_t[:, :, None], 1) + D * u_t
    time = lambda a: jnp.swapaxes(a, 0, 1)      # noqa: E731
    h, y = jax.lax.scan(position, h0, (time(u), time(dt), time(B), time(C),
                                       jnp.arange(u.shape[1])))
    return time(y), h


def streams(Bt, T, E, N, seed=1):
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    return dict(
        u=jax.random.normal(k[0], (Bt, T, E)),
        dt=0.1 * jax.random.uniform(k[1], (Bt, T, E)),
        A=-jnp.exp(jax.random.normal(k[2], (N, E))),
        B=jax.random.normal(k[3], (Bt, T, N)),
        C=jax.random.normal(k[4], (Bt, T, N)),
        D=jax.random.normal(k[5], (E,)),
        h0=jax.random.normal(k[6], (Bt, N, E)))


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("chunk", [8, 12, 40, 256])
def test_the_chunked_scan_is_the_plain_scan(impl, chunk):
    """T = 40: chunks of 8 divide it, of 12 do not (a last chunk's tail is
    padding), 40 is one chunk, 256 more than the sequence. One sequence
    runs to its end, one stops at 23."""
    s = streams(2, 40, 1024, 4)
    lengths = jnp.array([40, 23], jnp.int32)
    want_y, want_h = plain(s["u"], s["dt"], s["A"], s["B"], s["C"], s["D"],
                           s["h0"], lengths)
    y, h = ss.selective_scan(s["u"], s["dt"], s["A"], s["B"], s["C"],
                             s["D"], s["h0"], lengths, chunk=chunk,
                             impl=impl)
    assert float(jnp.max(jnp.abs(y - want_y))) < ATOL
    assert float(jnp.max(jnp.abs(h - want_h))) < ATOL
    # the state at a length is not the state at the bucket's end
    full = plain(s["u"], s["dt"], s["A"], s["B"], s["C"], s["D"], s["h0"],
                 jnp.array([40, 40], jnp.int32))[1]
    assert float(jnp.max(jnp.abs(full[1] - want_h[1]))) > 1e-2


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_no_h0_is_a_zero_state_and_streams_keep_their_dtype(impl):
    s = streams(1, 32, 1024, 4, seed=2)
    zero = jnp.zeros_like(s["h0"])
    y, h = ss.selective_scan(s["u"].astype(jnp.bfloat16), s["dt"], s["A"],
                             s["B"], s["C"], s["D"], chunk=16, impl=impl)
    assert y.dtype == jnp.bfloat16 and h.dtype == jnp.float32
    u16 = s["u"].astype(jnp.bfloat16).astype(jnp.float32)
    want_y, want_h = plain(u16, s["dt"], s["A"], s["B"], s["C"], s["D"],
                           zero, jnp.array([32], jnp.int32))
    assert float(jnp.max(jnp.abs(h - want_h))) < ATOL
    assert float(jnp.max(jnp.abs(y.astype(jnp.float32) - want_y))) < 0.05


def step_args(S, E, N, seed=5):
    """One token of S slots: (u, delta, A, B, C, D)."""
    s = streams(S, 1, E, N, seed)
    return (s["u"][:, 0], s["dt"][:, 0], s["A"], s["B"][:, 0], s["C"][:, 0],
            s["D"])


@pytest.mark.parametrize("step", ["xla", "pallas"])
@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("cut", [1, 17, 31])
def test_one_step_updates_from_a_scanned_state_are_the_whole_scan(impl, cut,
                                                                  step):
    """Prefill to `cut`, then a token at a time: what serving does, the
    state a row of a stack of three as a decode program holds it."""
    s = streams(8, 32, 1024, 8, seed=3)
    whole_y, whole_h = ss.selective_scan(
        s["u"], s["dt"], s["A"], s["B"], s["C"], s["D"], s["h0"], chunk=8,
        impl="xla")
    lengths = jnp.full((8,), cut, jnp.int32)
    y, h = ss.selective_scan(s["u"], s["dt"], s["A"], s["B"], s["C"],
                             s["D"], s["h0"], lengths, chunk=8, impl=impl)
    assert float(jnp.max(jnp.abs(y[:, :cut] - whole_y[:, :cut]))) < ATOL
    others = jax.random.normal(jax.random.PRNGKey(9), (3,) + h.shape)
    row = ss.StackedRow(others.at[1].set(h), jnp.int32(1))
    for t in range(cut, 32):
        y_t, row = ss.selective_step(s["u"][:, t], s["dt"][:, t], s["A"],
                                     s["B"][:, t], s["C"][:, t], s["D"],
                                     row, impl=step)
        assert float(jnp.max(jnp.abs(y_t - whole_y[:, t]))) < ATOL
    assert float(jnp.max(jnp.abs(row.row() - whole_h))) < ATOL
    assert bool(jnp.all(row.stack[::2] == others[::2]))


@pytest.mark.parametrize("L,S,N,E,m,block", [
    (3, 8, 8, 128, 2, None),            # one tile of everything
    (2, 16, 16, 1024, 0, None),         # the cell's N, two blocks of slots
    (2, 16, 16, 1024, 1, (8, 256)),     # blocks of channels too
    (2, 16, 8, 640, 1, (16, 640)),      # 128 lanes at a time, 16 slots
], ids=["tiny", "aligned", "channel_blocks", "odd_tiles"])
def test_the_step_kernel_is_the_xla_step_on_a_row_of_the_stack(L, S, N, E,
                                                               m, block):
    """The kernel in interpret mode against `selective_step_xla` on random
    states: the same arithmetic in the same order a state number, the sum
    over N in another; every other layer's row comes back bit for bit."""
    args = step_args(S, E, N)
    stack = jax.random.normal(jax.random.PRNGKey(4), (L, S, N, E))
    want_y, want_h = ss.selective_step_xla(*args, stack[m])
    y, got = jax.jit(
        lambda stack, m: ss.selective_step_pallas(*args, stack, m,
                                                  block=block,
                                                  interpret=True),
        donate_argnums=0)(stack + 0.0, jnp.int32(m))
    assert y.dtype == want_y.dtype and got.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(got[m] - want_h))) < 1e-6
    assert float(jnp.max(jnp.abs(y - want_y))) < ATOL
    keep = [l for l in range(L) if l != m]
    assert bool(jnp.all(got[jnp.array(keep)] == stack[jnp.array(keep)]))


def test_a_step_keeps_its_streams_dtype_and_a_row_stays_a_row():
    """bfloat16 streams as the model hands them; an array in, an array
    out; a `StackedRow` in, one out, and its `astype` is the row's."""
    u, dt, A, B, C, D = step_args(8, 256, 8)
    stack = jax.random.normal(jax.random.PRNGKey(4), (2, 8, 8, 256))
    u16 = u.astype(jnp.bfloat16)
    y_row, h_row = ss.selective_step(u16, dt, A, B, C, D, stack[1])
    assert y_row.dtype == jnp.bfloat16 and h_row.shape == stack[1].shape
    for impl in ("xla", "pallas"):
        y, row = ss.selective_step(u16, dt, A, B, C, D,
                                   ss.StackedRow(stack, jnp.int32(1)), impl)
        assert isinstance(row, ss.StackedRow) and y.dtype == jnp.bfloat16
        assert float(jnp.max(jnp.abs(row.row() - h_row))) < 1e-6
        assert float(jnp.max(jnp.abs(
            y.astype(jnp.float32) - y_row.astype(jnp.float32)))) < 0.05
    half = ss.StackedRow(stack, jnp.int32(1)).astype(jnp.bfloat16)
    assert half.shape == stack[1].shape and half.dtype == jnp.bfloat16


def test_the_gate_is_asked_on_a_tpu_alone_and_for_shapes_the_kernel_takes(
        monkeypatch):
    assert ss._auto_impl(1, 64, 1024, 4, 64) == "xla"      # the CPU
    monkeypatch.setattr(ss, "on_tpu", lambda: True)
    asked = []
    from paddle_tpu.ops import autobench
    monkeypatch.setattr(autobench, "prefer",
                        lambda key, cands, make, default: asked.append(
                            (key, sorted(cands), default)) or "pallas")
    assert ss._auto_impl(1, 64, 1024, 4, 64) == "pallas"
    assert asked == [(("selective_scan", 1, 64, 1024, 4, 64),
                      ["pallas", "xla"], "xla")]
    # channels that are no whole tile, or B and C past SMEM: the XLA form
    assert ss._auto_impl(1, 64, 1000, 4, 64) == "xla"
    assert ss._auto_impl(8, 4096, 1024, 16, 256) == "xla"
    monkeypatch.setenv("PADDLE_TPU_DISABLE_PALLAS", "1")
    assert ss._auto_impl(1, 64, 1024, 4, 64) == "xla"
    assert len(asked) == 1


@pytest.mark.parametrize("shape,dtype,why", [
    ((2, 8, 16, 1000), jnp.float32, "channels that are no whole tile"),
    ((2, 6, 16, 1024), jnp.float32, "slots that are no whole block"),
    ((2, 8, 4, 1024), jnp.float32, "a state of half a tile of sublanes"),
    ((2, 8, 16, 1024), jnp.bfloat16, "a state that is not float32"),
], ids=lambda v: v.replace(" ", "_") if isinstance(v, str) else None)
def test_a_stack_the_step_kernel_does_not_take_goes_to_xla_unasked(
        monkeypatch, shape, dtype, why):
    monkeypatch.setattr(ss, "on_tpu", lambda: True)
    from paddle_tpu.ops import autobench
    monkeypatch.setattr(autobench, "prefer", lambda *a, **k: pytest.fail(why))
    stack = jax.ShapeDtypeStruct(shape, dtype)
    assert ss._auto_step_impl(stack) == "xla"


def test_the_steps_gate_times_both_forms_inside_a_loop_over_a_stack(
        monkeypatch):
    """The key, the default, and the trial itself: each candidate is the
    step on a row at a traced index of a stacked state in a `fori_loop`,
    not the step alone (alone the XLA form is one fusion and ties)."""
    stack = jax.ShapeDtypeStruct((26, 8, 8, 256), jnp.float32)
    assert ss._auto_step_impl(stack) == "xla"     # the CPU
    monkeypatch.setattr(ss, "on_tpu", lambda: True)
    asked = []
    from paddle_tpu.ops import autobench
    monkeypatch.setattr(autobench, "prefer",
                        lambda key, cands, make, default: asked.append(
                            (key, cands, make, default)) or "pallas")
    assert ss._auto_step_impl(stack) == "pallas"
    (key, cands, make, default), = asked
    assert key == ("selective_step", 8, 256, 8) and default == "xla"
    assert sorted(cands) == ["pallas", "xla"]
    monkeypatch.setenv("PADDLE_TPU_DISABLE_PALLAS", "1")
    assert ss._auto_step_impl(stack) == "xla" and len(asked) == 1
    # the trial as the gate runs it (the kernel interpreted here)
    monkeypatch.setattr(ss, "on_tpu", lambda: False)
    args = make()
    layers, sweeps = ss._STEP_TRIAL
    assert args[0].shape == (layers, 8, 8, 256)
    text = str(jax.make_jaxpr(cands["xla"])(*args))
    assert ("scan[" in text or "while[" in text) \
        and "dynamic_update_slice" in text
    assert "pallas_call" in str(jax.make_jaxpr(cands["pallas"])(*args))
    (hx, yx), (hp, yp) = (jax.jit(cands[n])(*args) for n in ("xla", "pallas"))
    assert float(jnp.max(jnp.abs(hx - hp))) < ATOL
    assert float(jnp.max(jnp.abs(yx - yp))) < ATOL
    assert float(jnp.max(jnp.abs(hx - args[0]))) > 1e-3    # every row moved
