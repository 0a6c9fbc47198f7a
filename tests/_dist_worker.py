"""Subprocess worker: distributed HDB on N host devices must match the
single-device reference exactly. Invoked by test_distributed.py with
XLA_FLAGS=--xla_force_host_platform_device_count=8 set in the child env.
"""
import os
import sys

assert "--xla_force_host_platform_device_count" in os.environ.get("XLA_FLAGS", "")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import numpy as np

from repro.core import blocks, hdb, distributed, pairs
from repro.data import synthetic


def key_set(r):
    return set(zip(r.rids.tolist(), r.key_hi.tolist(), r.key_lo.tolist()))


def assert_pairsets_equal(got, want, label):
    assert got.exact == want.exact, label
    assert got.total_slots == want.total_slots, label
    np.testing.assert_array_equal(got.a, want.a, err_msg=label)
    np.testing.assert_array_equal(got.b, want.b, err_msg=label)
    np.testing.assert_array_equal(got.src_size, want.src_size, err_msg=label)


def check_routed_pair_dedupe(mesh_kind, mesh, axes, ref):
    """Routed distributed dedupe must be bit-identical to the numpy oracle
    on this mesh — exact path, budget-exceeded sampled path, and an
    empty-shard layout where whole shards receive no pairs."""
    blk = pairs.build_blocks(ref)
    want = pairs.dedupe_pairs(blk, backend="numpy")
    got = distributed.dedupe_pairs_distributed(blk, mesh, axes,
                                               chunk_per_shard=4096)
    assert_pairsets_equal(got, want, f"routed-exact {mesh_kind}")
    assert len(want.a) > 100, "blocking produced too few pairs to be a real test"

    # the radix shard-local dedupe sort must be bit-identical on the
    # emulated mesh (forces the device path — "auto" is the numpy u64
    # sort on this CPU backend)
    got_r = distributed.dedupe_pairs_distributed(
        blk, mesh, axes, chunk_per_shard=4096, sort_backend="radix")
    assert_pairsets_equal(got_r, want, f"routed-radix {mesh_kind}")

    budget = blk.num_pair_slots // 3
    want_s = pairs.dedupe_pairs(blk, budget=budget, backend="numpy",
                                sample_seed=13)
    got_s = distributed.dedupe_pairs_distributed(
        blk, mesh, axes, budget=budget, chunk_per_shard=1024, sample_seed=13)
    assert not got_s.exact
    assert_pairsets_equal(got_s, want_s, f"routed-sampled {mesh_kind}")

    # empty-shard edge: 1 tiny block => single pair, 7 of 8 shards idle
    one = pairs.Blocks(np.zeros(1, np.uint32), np.zeros(1, np.uint32),
                       np.zeros(1, np.int64), np.array([2], np.int64),
                       np.array([3, 9], np.int64))
    want_e = pairs.dedupe_pairs(one, backend="numpy")
    got_e = distributed.dedupe_pairs_distributed(one, mesh, axes,
                                                 chunk_per_shard=256)
    assert_pairsets_equal(got_e, want_e, f"routed-empty-shard {mesh_kind}")
    print("OK-PAIRS", mesh_kind)


def main(mesh_kind: str):
    corpus = synthetic.generate(synthetic.SyntheticSpec(num_entities=900, seed=11))
    keys, valid = blocks.build_keys(corpus.columns, corpus.blocking)
    # pad N to a multiple of 8 shards
    n = valid.shape[0]
    import jax.numpy as jnp
    pad = (-n) % 8
    if pad:
        keys = jnp.concatenate(
            [keys, jnp.full((pad,) + keys.shape[1:], 0xFFFFFFFF, jnp.uint32)])
        valid = jnp.concatenate([valid, jnp.zeros((pad, valid.shape[1]), bool)])
    cfg = hdb.HDBConfig(max_block_size=40, max_iterations=5)
    ref = hdb.hashed_dynamic_blocking(keys, valid, cfg)

    if mesh_kind == "flat":
        mesh = jax.make_mesh((8,), ("data",))
        axes = ("data",)
    elif mesh_kind == "pod":
        mesh = jax.make_mesh((2, 4), ("pod", "data"))
        axes = ("pod", "data")
    else:  # production-style 3-axis
        mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
        axes = ("pod", "data", "model")
    got = distributed.distributed_hashed_dynamic_blocking(
        keys, valid, cfg, mesh, axes)

    ks_ref, ks_got = key_set(ref), key_set(got)
    missing = ks_ref - ks_got
    extra = ks_got - ks_ref
    print(f"ref={len(ks_ref)} got={len(ks_got)} missing={len(missing)} extra={len(extra)}")
    assert len(ks_ref) > 1000, "reference produced too few assignments to be a real test"
    assert not extra, f"distributed produced spurious assignments: {list(extra)[:5]}"
    # the counts map holds every over-sized key exactly: nothing is lost
    assert not missing, f"missing assignments: {list(missing)[:5]}"
    for st_r, st_g in zip(ref.stats, got.stats):
        assert st_r.n_surviving_oversized == st_g.n_surviving_oversized, (st_r, st_g)
        assert st_r.n_right_cms == st_g.n_right_cms, (st_r, st_g)
    check_routed_pair_dedupe(mesh_kind, mesh, axes, ref)
    print("OK", mesh_kind)


if __name__ == "__main__":
    main(sys.argv[1] if len(sys.argv) > 1 else "pod")
