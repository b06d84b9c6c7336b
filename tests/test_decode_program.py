"""The decode program: the device half of ``pipeline.decode`` (binade
offsets, the family's inverse, significand to bit words, denormalization)
runs as one compiled program a chunk (``pipeline._inverse_program``),
bitwise equal to the transforms' own inverses run op by op, with one
dispatch a non-identity chunk and one program per length bucket
(``scoring.DECODE``)."""
import os
import sys
import threading

import jax.numpy as jnp
import numpy as np
import pytest

from repro.compression.bitplane import decompress_int_stream
from repro.core import pipeline as P, scoring as S, transforms as T
from repro.core.float_bits import denormalize_bits
from repro.core.lossless import significand_from_bits, significand_to_bits
from tests._helpers import words

# every family feasible at every width on the data below
FAMILIES = {
    "shift_save_even": {"D": 4},
    "compact_bins": {"n_bins": 4},
    "multiply_shift": {"D": 4},
    "shift_separate": {"D": 2},
}
DTYPES = {"float64": "f64", "float32": "f32", "bfloat16": "bf16",
          "float16": "f16"}
# the words of NaN, +inf, -inf, +0, -0: kept verbatim, outside the transform
N_PASSTHROUGH = 5


def _chunk(dtype: str, n_active: int, seed: int) -> np.ndarray:
    """``n_active`` finite nonzero values (signs and binades drawn, the
    significands in the bottom fifth of their binade, two of them
    subnormal) and the passthrough words among them."""
    spec = P.SPECS[DTYPES[dtype]]
    u = np.dtype(spec.uint_dtype).type
    rng = np.random.default_rng(seed)
    sign = rng.integers(0, 2, n_active).astype(u) << u(spec.sign_shift)
    exp = rng.integers(spec.bias - 3, spec.bias + 4, n_active).astype(u)
    man = rng.integers(0, (1 << spec.man_bits) // 5, n_active).astype(u)
    active = sign | (exp << u(spec.man_bits)) | man
    active[:2] = [1, 9]             # subnormals: significands 1 and 1.125
    inf = u(spec.exp_mask) << u(spec.man_bits)
    special = np.asarray([inf | u(1), inf, inf | (u(1) << u(spec.sign_shift)),
                          0, u(1) << u(spec.sign_shift)], u)
    assert special.shape == (N_PASSTHROUGH,)
    w = np.insert(active, rng.integers(0, n_active, N_PASSTHROUGH), special)
    return w.view(jnp.dtype(spec.float_dtype))


def _eager_decode(enc) -> np.ndarray:
    """The inverse as the transforms run it, op by op: the reference."""
    spec = P.SPECS[enc.spec_name]
    flat = np.asarray(enc.data).reshape(-1)
    out = flat.copy()
    pass_mask = P._unpack_z(enc.passthrough_z, enc.n).astype(bool)
    exps = decompress_int_stream(enc.exponents_z, enc.n_active)
    signs = P._unpack_z(enc.signs_z, enc.n_active)
    b = jnp.asarray(flat[~pass_mask].view(spec.uint_dtype))
    off = ((b >> spec.man_bits) & spec.uint_dtype(spec.exp_mask)).astype(
        jnp.int32) - spec.bias
    X = T.TRANSFORMS[enc.method][1](significand_from_bits(b, spec), off,
                                    enc.meta, spec=spec)
    y = significand_to_bits(X, jnp.zeros_like(off), spec)
    vals = denormalize_bits(y, exps.astype(np.int32), signs, spec)
    out[~pass_mask] = np.asarray(vals).view(spec.float_dtype)
    return out


@pytest.mark.parametrize("n_active", [512, 513])   # both sides of a bucket
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("method", FAMILIES)
def test_program_matches_eager_inverse(method, dtype, n_active):
    x = _chunk(dtype, n_active, seed=n_active)
    enc = P.apply_transform(x, method, FAMILIES[method])
    assert (enc.method, enc.n_active) == (method, n_active)
    S.DECODE.reset()
    got = P.decode(enc)
    assert S.DECODE.dispatches == 1
    assert np.array_equal(words(got), words(_eager_decode(enc)))
    assert np.array_equal(words(got), words(x))


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("passthrough_only", [False, True])
def test_identity_chunk_dispatches_nothing(passthrough_only, dtype):
    """An identity chunk, or one whose every value is passthrough (stored
    as identity whatever the method), decodes on the host alone."""
    x = _chunk(dtype, 600, seed=3)
    if passthrough_only:
        x = x[~np.isfinite(x.astype(np.float64)) | (x == 0)]
    method = "shift_save_even" if passthrough_only else "identity"
    enc = P.apply_transform(x, method, FAMILIES.get(method, {}))
    assert (enc.method, enc.n_active) == ("identity", 0)
    S.DECODE.reset()
    assert np.array_equal(words(P.decode(enc)), words(x))
    assert S.DECODE.dispatches == 0


def test_one_program_per_length_bucket():
    """N chunks of one length: N dispatches of one program; other lengths
    in the same bucket (600 and 640 active values share 640) build none."""
    P._inverse_program.cache_clear()
    S.DECODE.reset()
    encs = [P.apply_transform(_chunk("float64", 600, seed=s),
                              "shift_save_even", {"D": 4}) for s in range(4)]
    for enc in encs:
        P.decode(enc)
    assert (S.DECODE.dispatches, S.DECODE.programs) == (4, 1)
    P.decode(P.apply_transform(_chunk("float64", 640, seed=9),
                               "shift_save_even", {"D": 4}))
    assert (S.DECODE.dispatches, S.DECODE.programs) == (5, 1)
    P.decode(P.apply_transform(_chunk("float64", 641, seed=9),
                               "shift_save_even", {"D": 4}))
    assert (S.DECODE.dispatches, S.DECODE.programs) == (6, 2)


def test_shift_separate_schedules_share_a_program():
    """Chunks whose shift&separate iteration counts differ run one program:
    the schedule is padded with steps that select no lane."""
    P._inverse_program.cache_clear()
    S.DECODE.reset()
    x = _chunk("float64", 600, seed=5)
    encs = [P.apply_transform(x, "shift_separate", {"D": D})
            for D in (2, 3, 4)]
    assert len({e.meta.n_iter for e in encs}) > 1
    for enc in encs:
        assert np.array_equal(words(P.decode(enc)), words(x))
    assert (S.DECODE.dispatches, S.DECODE.programs) == (3, 1)


def test_concurrent_decodes_count_every_dispatch():
    """Decode-pool threads share the program cache and the counter: more
    threads than cores decode at once, and every run is counted and every
    chunk comes back bitwise."""
    chunks = [_chunk("float32", 700, seed=s) for s in range(4)]
    encs = [P.apply_transform(x, "shift_save_even", {"D": 4}) for x in chunks]
    P.decode(encs[0])
    n_threads, per_thread = 2 * (os.cpu_count() or 8), 4
    wrong = []

    def work(k):
        for i in range(per_thread):
            j = (k + i) % len(encs)
            if not np.array_equal(words(P.decode(encs[j])), words(chunks[j])):
                wrong.append((k, j))

    S.DECODE.reset()
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(k,))
                   for k in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(interval)
    assert not wrong
    assert S.DECODE.dispatches == n_threads * per_thread


@pytest.mark.parametrize("fault", ["short_stream", "unknown_method"])
def test_malformed_chunk_is_refused(fault):
    enc = P.apply_transform(_chunk("float64", 600, seed=1),
                            "shift_save_even", {"D": 4})
    if fault == "short_stream":
        enc.meta.chunk_ids = enc.meta.chunk_ids[:-1]
    else:
        enc.method = "shift_save_odd"
    with pytest.raises(ValueError, match="expected 600|no inverse"):
        P.decode(enc)
