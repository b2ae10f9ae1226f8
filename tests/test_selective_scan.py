"""`ops/selective_scan.py`: the chunked scan (the Pallas kernel in
interpret mode and the XLA form) against a plain `lax.scan` over the
positions, for chunks that do and do not divide T, lengths short of the
bucket and a non-zero h0; one-step updates from a scanned state against the
scan over the whole sequence. float32 on the CPU: the tolerance is 1e-5 on
numbers of size ~1 (read: 2e-6)."""
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


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("cut", [1, 17, 31])
def test_one_step_updates_from_a_scanned_state_are_the_whole_scan(impl, cut):
    """Prefill to `cut`, then a token at a time: what serving does."""
    s = streams(2, 32, 1024, 4, seed=3)
    whole_y, whole_h = ss.selective_scan(
        s["u"], s["dt"], s["A"], s["B"], s["C"], s["D"], s["h0"], chunk=8,
        impl="xla")
    lengths = jnp.array([cut, cut], jnp.int32)
    y, h = ss.selective_scan(s["u"], s["dt"], s["A"], s["B"], s["C"],
                             s["D"], s["h0"], lengths, chunk=8, impl=impl)
    assert float(jnp.max(jnp.abs(y[:, :cut] - whole_y[:, :cut]))) < ATOL
    for t in range(cut, 32):
        y_t, h = ss.selective_step(s["u"][:, t], s["dt"][:, t], s["A"],
                                   s["B"][:, t], s["C"][:, t], s["D"], h)
        assert float(jnp.max(jnp.abs(y_t - whole_y[:, t]))) < ATOL
    assert float(jnp.max(jnp.abs(h - whole_h))) < ATOL


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
