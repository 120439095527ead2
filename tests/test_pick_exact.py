"""The byte pick of the line form's tiles (ops/fusedgrid.py ``pick_exact``):
an f32 plane picked by int8 one-hot columns, byte by byte through ``int8 x
int8 -> int32`` products, against ``np.take`` to the BIT — on both backends
(the Pallas body interpreted, the XLA twin's plain jit), through the weights
a line plan really carries (``host_operands(..., line=True)``: two edge
slots a block up to 64 steps, one past them), whose columns of zeros — a
padded step, a cell below 0 or past the store, the unused half of a packed
block — must read +0.0.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

from filodb_tpu.ops import fusedgrid

BASE, IV, WINDOW = 1_700_000_000_000, 10_000, 300_000
ROWS, C = 8, 128

FLT_MAX = np.finfo(np.float32).max
CLASSES = {
    "normal": lambda rng: rng.normal(0, 1e3, (ROWS, C)),
    "integers_above_2_24": lambda rng: rng.integers(
        1 << 24, 1 << 31, (ROWS, C)) | 1,
    "denormals": lambda rng: rng.integers(1, 1 << 23, (ROWS, C)).astype(
        np.uint32).view(np.float32) * rng.choice([-1, 1], (ROWS, C)),
    "minus_zero": lambda rng: np.where(rng.random((ROWS, C)) < 0.5, -0.0, 0.0),
    "infinities": lambda rng: rng.choice([np.inf, -np.inf, 1.5], (ROWS, C)),
    "flt_max": lambda rng: rng.choice([FLT_MAX, -FLT_MAX], (ROWS, C)),
    "nan_payload": lambda rng: (
        np.uint32(0x7F800001) + rng.integers(0, 1 << 22, (ROWS, C)).astype(
            np.uint32) | (rng.integers(0, 2, (ROWS, C)).astype(np.uint32)
                          << np.uint32(31))).view(np.float32),
}


def plan(T):
    """``ohe`` of ``T`` steps that start before the store's first cell and
    end past its last, so that slots of both ends fall off it."""
    out_ts = BASE - 2 * IV + np.arange(T, dtype=np.int64) * (
        (C + 40) * IV // T) + 7
    _band, ohe, *_ = fusedgrid.host_operands(C, 128, out_ts, WINDOW, BASE, IV,
                                             "rate", line=True)
    assert ohe.shape[0] == C        # the whole width: no active-column cut
    return ohe


def picked(backend, x, w):
    if backend == "xla":
        return jax.jit(fusedgrid.pick_exact)(x, w)

    def body(x_ref, w_ref, o_ref):
        o_ref[:] = fusedgrid.pick_exact(x_ref[:], w_ref[:])
    return pl.pallas_call(
        body, out_shape=jax.ShapeDtypeStruct((x.shape[0], w.shape[1]),
                                             jnp.float32),
        interpret=True)(x, w)


@pytest.mark.parametrize("kind", sorted(CLASSES))
@pytest.mark.parametrize("T", [61, 100])          # packed = 2, packed = 1
@pytest.mark.parametrize("backend", ["pallas", "xla"])
def test_a_byte_pick_is_np_take_to_the_bit(backend, T, kind):
    w = plan(T)
    assert w.dtype == np.int8
    assert w.shape[1] == fusedgrid.EDGE_SLOTS // fusedgrid.slots_per_block(
        T) * 128
    ones = w.astype(np.int64).sum(0)
    # columns of zeros: the padded steps, and cells off the store besides
    padded = w.shape[1] - fusedgrid.EDGE_SLOTS * T
    assert (ones <= 1).all() and (ones == 0).sum() > padded + 8
    assert ones.sum() > 4 * T
    x = np.asarray(CLASSES[kind](np.random.default_rng(len(kind) + T)),
                   np.float32)
    want = np.take(x, w.argmax(0), axis=1).view(np.uint32)
    want[:, ones == 0] = 0                        # +0.0, every bit
    got = np.asarray(picked(backend, jnp.asarray(x), jnp.asarray(w)))
    assert got.dtype == np.float32
    np.testing.assert_array_equal(got.view(np.uint32), want)
    # every class reaches the planes it is there for
    bits = x.view(np.uint32)
    if kind == "denormals":
        assert ((bits >> 23) & 0xFF == 0).all() and (bits << 1).all()
    if kind == "nan_payload":
        assert np.isnan(x).all() and len(np.unique(bits)) > ROWS * C // 2
    if kind == "integers_above_2_24":
        assert (x >= 1 << 24).all()
