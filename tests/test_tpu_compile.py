"""Compile rehearsals of the codec's device programs for a TPU v5e chip.

Nothing here runs on a chip: each test lowers one program of the main path
at the size the codec really dispatches and compiles it with the TPU
compiler for a *described* v5e topology.  A program the chip's compiler
would refuse (an op Mosaic cannot lower, an f64<->u64 bitcast, a kernel
that does not fit VMEM) fails here, at no chip time.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every xdist worker imports
this file.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import pipeline as P
from repro.core import scoring as S
from repro.core import transforms as T
from repro.core.transforms import TransformError
from repro.data import gas_turbine_emissions
from repro.kernels.rans import kernel as K
from repro.kernels.scoregrid.kernel import scoregrid_blocks

CHUNK = 65536           # DatasetWriter's default chunk, in elements
LANES = 64              # rANS interleave width at that chunk size


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def tpu_trace(monkeypatch):
    """Trace as the chip would: compiled Pallas kernels in the phase-1 grid,
    no persistent compilation cache (a described chip's entry can never be
    read back), and no jit trace shared with the CPU tests of this worker."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    monkeypatch.setattr(S, "_USE_PALLAS_GRID", True)
    monkeypatch.setattr(S, "INTERPRET_DEFAULT", False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    jax.clear_caches()
    yield
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _data(spec_name: str) -> np.ndarray:
    rng = np.random.default_rng(0)
    if spec_name == "f64":
        return gas_turbine_emissions(CHUNK)
    w = rng.standard_normal(CHUNK) * 0.02
    return w.astype({"f32": np.float32, "bf16": jnp.bfloat16}[spec_name])


def test_scoregrid_kernel_compiles(one_chip, tpu_trace):
    # 16 f64 candidates = 32 u32 rows of a 4096-word phase-1 sample
    x = jax.ShapeDtypeStruct((32, 32, 128), jnp.uint32, sharding=one_chip)
    c = _compile(lambda a, b: scoregrid_blocks(a, b, interpret=False), x, x)
    assert "tpu_custom_call" in c.as_text()


def test_rans_hist_kernel_compiles(one_chip, tpu_trace):
    # a 2 MiB byte stream packed into (ROWS, 128) uint32 tiles
    x = jax.ShapeDtypeStruct((4096, 128), jnp.uint32, sharding=one_chip)
    c = _compile(lambda a: K._hist_blocks(a, interpret=False), x)
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("spec_name", ["f64", "f32", "bf16"])
def test_fused_shift_save_even_compiles(one_chip, tpu_trace, spec_name):
    spec = P.SPECS[spec_name]
    n_bytes = CHUNK * spec.width // 8
    lanes, steps = P._fused_geometry(n_bytes)
    prog = P._fused_program("shift_save_even",
                            (("D", min(16, spec.man_bits - 1)),), spec_name,
                            CHUNK, n_bytes, steps, lanes)
    X = jax.ShapeDtypeStruct((CHUNK,), jnp.int64, sharding=one_chip)
    prog.lower(X).compile()


@pytest.mark.parametrize("n_bytes", [4096, CHUNK * 2])
def test_fused_identity_compiles(one_chip, tpu_trace, n_bytes):
    """The identity program takes its stream padded to its step bucket and
    the stream's length as an argument (a 2,048-value bf16 tensor and a
    whole bf16 chunk)."""
    lanes, steps = P._fused_geometry(n_bytes)
    prog = P._fused_program("identity", (), "bf16", 0, 0, steps, lanes)
    raw = jax.ShapeDtypeStruct((steps * lanes,), jnp.uint8, sharding=one_chip)
    n = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    prog.lower(raw, n).compile()


@pytest.mark.parametrize("method", ["shift_save_even", "compact_bins"])
@pytest.mark.parametrize("spec_name", ["f64", "f32", "bf16"])
def test_inverse_program_compiles(one_chip, tpu_trace, spec_name, method):
    """The decode program of a served chunk, with the arguments
    ``pipeline.decode`` passes it: integer words in and out, so no
    f64<->u64 bitcast reaches the chip's compiler."""
    spec = P.SPECS[spec_name]
    meta = {
        "shift_save_even": T.ShiftSaveEvenMeta(
            e_star=0, D=6, x_min=1 << spec.man_bits, n_chunks=1,
            chunk_ids=np.zeros(CHUNK, np.int64),
            evenness=np.zeros(CHUNK, np.uint8)),
        "compact_bins": T.CompactBinsMeta(
            e_star=0, shifts=np.zeros(16, np.int64),
            thresholds=np.zeros(15, np.int64)),
    }[method]
    args = P._inverse_args(method, meta, spec, CHUNK, CHUNK)
    prog = P._inverse_program(method, spec_name, CHUNK,
                              tuple((a.shape, a.dtype.str) for a in args))

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    prog.lower(sds((CHUNK,), spec.uint_dtype), sds((CHUNK,), jnp.int32),
               sds((CHUNK,), jnp.uint8),
               *(sds(a.shape, a.dtype) for a in args)).compile()


def test_rans_scans_compile(one_chip, tpu_trace):
    n = CHUNK * 8
    steps = K.bucket_steps(-(-n // LANES))
    maxw = K.bucket_steps(2 * steps, 64)

    def sd(shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    _compile(lambda s, n_, f, c: K.encode_scan(s, n_, f, c, steps=steps,
                                               lanes=LANES),
             sd((steps, LANES)), sd(()), sd((256,)), sd((256,)))
    _compile(lambda st, b, bl, n_, s2, f, c: K.decode_scan(
                 st, b, bl, n_, s2, f, c, steps=steps, lanes=LANES),
             sd((LANES,)), sd((LANES, maxw)), sd((LANES,)), sd(()),
             sd((4096,)), sd((256,)), sd((256,)))


@pytest.mark.parametrize("spec_name", ["f64", "f32"])
def test_phase1_grid_compiles_with_pallas(one_chip, tpu_trace, spec_name):
    spec = P.SPECS[spec_name]
    X = P._prepare(_data(spec_name)).X
    Xs = P._strided(X, P.DEFAULT_SAMPLE_ELEMS)
    extrema = (int(jnp.min(Xs)), int(jnp.max(Xs)))
    plan, dyn = [], []
    for name, p in P.DEFAULT_CANDIDATES:
        if name == "identity":
            continue
        try:
            cand = S._plan_candidate(name, p, spec, extrema, Xs.shape[0],
                                     X.shape[0])
        except TransformError:
            continue
        if cand[0] == "grid":
            plan.append(cand[1])
            dyn.append(cand[2])
    assert len(plan) >= 4

    def sds(a):
        a = np.asarray(a)
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    c = _compile(
        lambda x, m, d: S._grid_score(x, m, d, spec=spec, plan=tuple(plan)),
        sds(np.asarray(Xs)), sds(np.int64(extrema[0])),
        jax.tree.map(sds, tuple(dyn)),
    )
    assert "tpu_custom_call" in c.as_text()
