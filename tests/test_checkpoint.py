"""Checkpoint manager: bitwise round-trip, atomicity, retention, elasticity,
and the data pipeline's O(1) resume."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager, restore_tree, save_tree
from repro.data.shard_store import ShardStore
from repro.data.tokens import MultimodalStream, TokenStream


def mk_tree(seed=0):
    rng = np.random.default_rng(seed)
    return {
        "w": jnp.asarray(rng.normal(0, 0.02, (128, 256)), jnp.float32),
        "moments": {
            "m": jnp.asarray(rng.normal(0, 1e-4, (128, 256)), jnp.float32),
            "v": jnp.asarray(rng.random((128, 256)) * 1e-6, jnp.float32),
        },
        "emb_bf16": jnp.asarray(rng.normal(0, 1, (64, 32)), jnp.bfloat16),
        "step": jnp.asarray(1234, jnp.int32),
        "table_f64": jnp.asarray(rng.uniform(1, 2, 1000), jnp.float64),
    }


def bits(x):
    x = np.asarray(x)
    if x.dtype == jax.numpy.bfloat16.dtype:
        return x.view(np.uint16)
    return x.view({8: np.uint64, 4: np.uint32}[x.dtype.itemsize]) if \
        x.dtype.kind == "f" else x


def test_save_restore_bitwise(tmp_path):
    tree = mk_tree()
    stats = save_tree(tree, tmp_path / "ck", extra={"hello": 1})
    got, extra = restore_tree(tmp_path / "ck")
    assert extra["hello"] == 1
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(got)):
        assert np.array_equal(bits(a), bits(b))
    assert stats["ratio"] < 1.0  # compression actually happened


def test_compression_on_adam_moments(tmp_path):
    """Adam v-moments: max-entropy mantissas bound the lossless gain to the
    sign+exponent structure (~6-9 of 32 bits here); assert we capture most
    of that bound."""
    rng = np.random.default_rng(1)
    v = jnp.asarray((rng.random(200_000) * 1e-6 + 1e-7), jnp.float32)
    stats = save_tree({"v": v}, tmp_path / "ck")
    assert stats["ratio"] < 0.92, stats


def test_compression_on_structured_params(tmp_path):
    """Fresh layer params: norm scales (constant), zero biases, quantized
    embedding rows — the structured arrays real checkpoints are full of."""
    rng = np.random.default_rng(2)
    tree = {
        "ln": jnp.ones((4096,), jnp.float32),
        "bias": jnp.zeros((65536,), jnp.float32),
        "emb_q": jnp.asarray(
            np.round(rng.normal(0, 0.02, 100_000), 4), jnp.float32
        ),
    }
    stats = save_tree(tree, tmp_path / "ck")
    assert stats["ratio"] < 0.35, stats


def test_atomic_no_partial_state(tmp_path):
    tree = mk_tree()
    save_tree(tree, tmp_path / "ck")
    # a crashed second save leaves a .tmp dir; the committed dir still loads
    tmp = tmp_path / "ck.tmp"
    tmp.mkdir()
    (tmp / "garbage").write_text("crash")
    got, _ = restore_tree(tmp_path / "ck")
    assert len(jax.tree.leaves(got)) == len(jax.tree.leaves(tree))


def test_gc_ignores_and_sweeps_stale_tmp(tmp_path):
    """Regression: `_gc` used to crash with ValueError on a stale
    `step_*.tmp` staging dir left by a crashed save; now it filters them
    from step parsing AND sweeps the orphans."""
    mgr = CheckpointManager(tmp_path, keep=2)
    stale = Path(tmp_path) / "step_00000042.tmp"
    stale.mkdir()
    (stale / "garbage").write_text("crash")
    for s in [10, 20, 30]:
        mgr.save(s, mk_tree(s))
    assert mgr.latest_step() == 30
    assert not stale.exists(), "orphaned .tmp dir must be swept"
    kept = sorted(p.name for p in Path(tmp_path).glob("step_*"))
    assert kept == ["step_00000020", "step_00000030"]


def test_unsupported_tree_nodes_fail_at_save(tmp_path):
    """NamedTuples and custom pytree nodes must be rejected when SAVING —
    never written as a silently-unrestorable checkpoint."""
    import collections

    Pt = collections.namedtuple("Pt", ["m", "v"])
    with pytest.raises(Exception, match="NamedTuple"):
        save_tree({"opt": Pt(np.ones(4), np.ones(4))}, tmp_path / "nt")

    class Weird:
        pass

    with pytest.raises(Exception, match="not an array"):
        save_tree({"x": Weird()}, tmp_path / "obj")


def test_manifest_leaf_count_mismatch_is_loud(tmp_path):
    """A manifest whose tree spec disagrees with the stored array count
    must raise an explanatory error, not StopIteration / silence."""
    save_tree({"a": np.ones(4)}, tmp_path / "ck")
    mpath = tmp_path / "ck" / "manifest.json"
    m = json.loads(mpath.read_text())
    m["tree"] = {"t": "dict", "k": ["a", "b"],
                 "c": [{"t": "leaf"}, {"t": "leaf"}]}
    mpath.write_text(json.dumps(m))
    with pytest.raises(Exception, match="more leaves"):
        restore_tree(tmp_path / "ck")


def test_pre_container_checkpoint_rejected(tmp_path):
    """Old pickle-blob checkpoints are not readable (pre-1.0 format break):
    the failure must be a loud, explanatory error — never an unpickle."""
    d = tmp_path / "ck"
    d.mkdir()
    (d / "manifest.json").write_text(
        json.dumps({"treedef": "deadbeef", "arrays": [], "extra": {}})
    )
    with pytest.raises(Exception, match="pre-container"):
        restore_tree(d)


def test_manager_retention_and_resume(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=2)
    for s in [10, 20, 30]:
        mgr.save(s, mk_tree(s), extra={"data_step": s * 2})
    assert mgr.latest_step() == 30
    got, extra = mgr.restore_latest()
    assert extra["step"] == 30 and extra["data_step"] == 60
    # retention: only 2 kept
    kept = sorted(p.name for p in Path(tmp_path).glob("step_*"))
    assert len(kept) == 2


def test_elastic_restore_different_sharding(tmp_path):
    """Checkpoints are mesh-independent: save 'sharded' state (here: the
    logical arrays), restore, and re-shard onto a different layout."""
    tree = {"w": jnp.arange(64 * 8, dtype=jnp.float32).reshape(64, 8)}
    save_tree(tree, tmp_path / "ck")
    got, _ = restore_tree(tmp_path / "ck")
    # simulate resharding 1-device -> 4-way logical split
    w = np.asarray(got["w"])
    shards = np.split(w, 4, axis=0)
    re = np.concatenate(shards, axis=0)
    assert np.array_equal(re, w)


def test_data_pipeline_o1_resume():
    ts = TokenStream(vocab=1000, batch=4, seq=16, seed=3)
    b5 = ts.batch_at(5)
    it = ts.batches(start_step=5)
    s, b = next(it)
    assert s == 5
    assert np.array_equal(np.asarray(b5["tokens"]), np.asarray(b["tokens"]))


@pytest.mark.parametrize("kind", ["frames", "patches"])
def test_multimodal_stream_batches_resume(kind):
    ms = MultimodalStream(vocab=1000, batch=2, seq=16, d_model=8, kind=kind,
                          prefix=4, seed=3)
    it = ms.batches(start_step=7)
    for want in (7, 8):
        step, got = next(it)
        ref = ms.batch_at(want)
        assert step == want and got.keys() == ref.keys()
        for k in ref:
            assert np.array_equal(np.asarray(got[k]), np.asarray(ref[k])), k


def test_shard_store_roundtrip_and_random_access(tmp_path):
    from repro.data import gas_turbine_emissions

    store = ShardStore(tmp_path)
    x = gas_turbine_emissions(70000).reshape(7, 10000)
    store.write("turbine", x, chunk=16384)
    back = store.read("turbine")
    assert np.array_equal(back.view(np.uint64), x.view(np.uint64))
    c1 = store.read_chunk("turbine", 1)
    assert np.array_equal(
        c1, x.reshape(-1)[16384 : 2 * 16384]
    )
    assert store.ratio("turbine") < 1.0
    # the parallel read path and the prefetching iterator are byte-identical
    # to the serial read
    par = store.read("turbine", parallel=True)
    assert np.array_equal(par.view(np.uint64), x.view(np.uint64))
    it = np.concatenate(list(store.iter_chunks("turbine", prefetch=3)))
    assert np.array_equal(it.view(np.uint64), x.reshape(-1).view(np.uint64))


def test_parallel_restore_matches_serial(tmp_path):
    """restore_tree(parallel=True) — the default — must be bitwise-identical
    to the serial restore, leaf for leaf, including the single-leaf tree
    (which parallelizes across chunks instead of leaves)."""
    tree = mk_tree(7)
    save_tree(tree, tmp_path / "ck")
    serial, _ = restore_tree(tmp_path / "ck", parallel=False)
    par, _ = restore_tree(tmp_path / "ck", parallel=True)
    for a, b in zip(jax.tree.leaves(serial), jax.tree.leaves(par)):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert np.array_equal(bits(a), bits(b))
    single = {"w": jnp.asarray(np.linspace(1, 2, 600_000))}
    save_tree(single, tmp_path / "one")
    s1, _ = restore_tree(tmp_path / "one", parallel=False)
    p1, _ = restore_tree(tmp_path / "one", parallel=True)
    assert np.array_equal(bits(s1["w"]), bits(p1["w"]))


def test_parallel_restore_propagates_leaf_failure(tmp_path):
    """A corrupt leaf container fails the parallel restore loudly (the
    worker's exception reaches the caller), exactly like the serial path."""
    save_tree(mk_tree(9), tmp_path / "ck")
    victim = tmp_path / "ck" / "arr_1.fpc"
    blob = bytearray(victim.read_bytes())
    blob[len(blob) // 2] ^= 0xFF  # inside a record: checksum must catch it
    victim.write_bytes(bytes(blob))
    for parallel in (False, True):
        with pytest.raises(Exception, match="(?i)checksum|corrupt|truncated"):
            restore_tree(tmp_path / "ck", parallel=parallel)


def test_threaded_save_restore_latest_stress(tmp_path):
    """Concurrent saves and restore_latest calls: every restore must observe
    a complete, self-consistent checkpoint — some committed step's exact
    tree — never a torn directory or a mix of two steps.  ``keep`` is large
    so retention GC never races the readers (GC of a step a reader holds
    open is a separate, documented non-goal)."""
    import threading

    mgr = CheckpointManager(tmp_path, keep=50, method="identity")

    def tree_for(step):
        return {"w": np.arange(4096, dtype=np.float32) * step,
                "b": np.full(512, step, np.float64)}

    errors = []
    stop = threading.Event()

    def reader():
        try:
            while not stop.is_set():
                tree, extra = mgr.restore_latest()
                if tree is None:
                    continue
                want = tree_for(extra["step"])
                assert np.array_equal(tree["w"], want["w"])
                assert np.array_equal(tree["b"], want["b"])
        except Exception as e:  # pragma: no cover - failure reporting
            errors.append(e)

    threads = [threading.Thread(target=reader) for _ in range(2)]
    for t in threads:
        t.start()
    try:
        for step in range(1, 9):
            mgr.save(step, tree_for(step))
    finally:
        stop.set()
        for t in threads:
            t.join()
    assert not errors, errors
    # nothing was ever quarantined (a torn read would have been), and the
    # final state is the last step, bit-exact
    assert not list(tmp_path.glob("*.corrupt*"))
    tree, extra = mgr.restore_latest()
    assert extra["step"] == 8
    assert np.array_equal(tree["w"], tree_for(8)["w"])
